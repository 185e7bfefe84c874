"""The harness end to end on the CPU, at a tiny size, through the same
discovery by name as on the chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest
from jax.profiler import ProfileData

import chipbench_tiny as tiny
from chipbench import devtrace, peaks, run
from test_chipbench_trace import TEXT


@pytest.fixture
def cpu_run(monkeypatch):
    # The test process keeps no persistent compile cache, and the CPU
    # stands in for a chip with the v5e's peaks.
    monkeypatch.setattr(run, "_compile_cache", lambda: None)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


@pytest.mark.parametrize("mix", ["program-harp", "program-mra"])
def test_last_line_of_a_run(mix, tmp_path, cpu_run, capsys):
    root = tiny.make_root(tmp_path, mix=mix)
    rc = run.main(["--workload", f"tiny.{mix}", "--seed", str(2**33 + 5),
                   "--seconds", "0.5", "--trace", "0"], root=root, require_tpu=False)
    out = tiny.last_json(capsys.readouterr().out)
    assert rc == 0
    assert list(out)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True and out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"program_cols_per_s", "setup_s"}
    assert out["metrics"]["program_cols_per_s"]["unit"] == "cols/s"
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
    assert out["checks"]["differing_columns_share"]["value"] == 0.0
    assert out["window_compiles"] == 0


def test_dropped_in_metric_is_found(tmp_path, cpu_run, capsys, monkeypatch):
    root = tiny.make_root(tmp_path, extra_metric="deploys_in_window")
    # A CPU trace has no TPU plane: read the per-layer metrics off a
    # small TPU-shaped trace instead.
    monkeypatch.setattr(run, "_trace_reduction",
                        lambda d: devtrace.reduce(ProfileData.from_text_proto(TEXT)))
    rc = run.main(["--workload", tiny.CELL, "--seed", "3", "--seconds", "0.5",
                   "--trace", "1"], root=root, require_tpu=False)
    out = tiny.last_json(capsys.readouterr().out)
    assert rc == 0 and out["correct"] is True
    assert out["metrics"]["deploys_in_window"]["value"] == out["attempted"]
    assert {"wv_step_roofline", "fwht_roofline", "idle_share.program",
            "mfu.program"} <= set(out["metrics"])
    assert "program_cols_per_s" not in out["metrics"]
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_real_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tiny.REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path, capsys):
    """A directory with `BENCHMARK.json` and the files under `paths` but
    without the program: no result."""
    root = tmp_path / "bare"
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(tiny.REPO, path), root / path)
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), root / "BENCHMARK.json")
    rc = run.main(["--workload", bench["workloads"][0]["name"], "--seed", "1",
                   "--seconds", "1"], root=str(root), require_tpu=False)
    captured = capsys.readouterr()
    assert rc != 0 and captured.out == ""
    assert "program under test" in captured.err


def test_every_entry_finds_its_files():
    """Each cell's configuration, mix and driver, and each per-layer
    metric's reader, are where the harness looks them up by name."""
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench_dir = os.path.join(tiny.REPO, "chipbench")
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    used = set()
    for w in bench["workloads"]:
        used.add(w["config"])
        assert os.path.isfile(os.path.join(tiny.REPO, configs[w["config"]]["file"]))
        with open(os.path.join(bench_dir, "traffic", f"{w['traffic']}.json")) as f:
            mix = json.load(f)
        assert os.path.isfile(os.path.join(bench_dir, "drivers", f"{mix['driver']}.py"))
    assert used == set(configs)
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in end_to_end
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(bench_dir, "metrics", f"{m['name']}.py"))
        moved = end_to_end[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
