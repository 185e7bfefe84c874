"""The readers of the program's own deploy spans and loop counters, on the
CPU at a tiny size: through a whole traced run, and each on its own with
the program's telemetry on and off."""

import contextlib
import os

import pytest
from jax.profiler import ProfileData

import chipbench_tiny as tiny
from chipbench import devtrace, peaks, run
from test_chipbench_trace import TEXT

SPAN_READERS = ["plan_share.program", "fold_share.program", "loop_occupancy.program"]


def test_traced_run_reports_the_span_readers(tmp_path, capsys, monkeypatch):
    monkeypatch.setattr(run, "_compile_cache", lambda: None)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    root = tiny.make_root(tmp_path)
    # A CPU trace has no TPU plane: the device-trace readers read a small
    # TPU-shaped trace instead, and the span readers read the program.
    monkeypatch.setattr(run, "_trace_reduction",
                        lambda d: devtrace.reduce(ProfileData.from_text_proto(TEXT)))
    rc = run.main(["--workload", tiny.CELL, "--seed", str(2**33 + 11), "--seconds", "0.5",
                   "--trace", "1"], root=root, require_tpu=False)
    out = tiny.last_json(capsys.readouterr().out)
    assert rc == 0 and out["correct"] is True
    for name in SPAN_READERS:
        assert 0.0 < out["metrics"][name]["value"] <= 100.0


@pytest.fixture(scope="module")
def span_reads(tmp_path_factory):
    """Each span reader's value after a window of the tiny cell's driver
    with the program's telemetry on, and after one with it off; the
    device trace is a stand-in, since these readers take nothing from it."""
    from repro import obs

    root = tiny.make_root(tmp_path_factory.mktemp("spans"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "_compile_cache", lambda: None)
        _, driver_mod, ctx = run.load_cell(tiny.CELL, 2**33 + 7, root=root,
                                           require_tpu=False)
    readers = {
        name: run.load_module(os.path.join(root, "chipbench", "metrics", f"{name}.py"),
                              "span_reader_" + name.replace(".", "_"))
        for name in SPAN_READERS
    }
    trace = devtrace.reduce(ProfileData.from_text_proto(TEXT))
    driver = driver_mod.Driver(ctx)
    driver.setup()
    reads = {}
    for enabled in (True, False):
        obs.reset_all()
        with contextlib.ExitStack() as stack:
            if not enabled:
                stack.enter_context(obs.disabled())
            driver.window(0.5)
        rec = run.Run(cell=tiny.CELL, trace=trace, peaks=peaks.PEAKS["TPU v5 lite"],
                      records=driver.records)
        reads[enabled] = {name: r.read(rec) for name, r in readers.items()}
    obs.reset_all()
    return reads


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_reader_reads_the_window_and_nothing_when_disabled(name, span_reads):
    assert 0.0 < span_reads[True][name] <= 100.0
    assert span_reads[False][name] is None
