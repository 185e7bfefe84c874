"""A tiny benchmark root for CPU tests of the harness.

`make_root` copies the benchmark's files into a new directory, links the
program's `src`, and adds a test-only configuration (`tiny`, widths of a
few tens) and a cell `tiny.<mix>` on a copy of one of the real mixes
that checks every column, so that a run goes through the same discovery
by name as on the chip.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "tiny.program-harp"

TINY = {
    "name": "tiny", "model_type": "qwen3", "hidden_size": 32,
    "intermediate_size": 64, "num_attention_heads": 2, "num_key_value_heads": 1,
    "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
    "torch_dtype": "bfloat16", "initializer_range": 0.02,
    "analog": {"weight_bits": 6, "cell_bits": 3},
}


def make_root(tmp_path, *, mix: str = "program-harp",
              extra_metric: str | None = None) -> str:
    root = os.path.join(str(tmp_path), "bench")
    shutil.copytree(
        os.path.join(REPO, "chipbench"), os.path.join(root, "chipbench"),
        ignore=shutil.ignore_patterns("tests", "__pycache__"),
    )
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(root, "chipbench", "configs", "tiny.json"), "w") as f:
        json.dump(TINY, f)
    traffic = os.path.join(root, "chipbench", "traffic", f"tiny-{mix}.json")
    with open(os.path.join(root, "chipbench", "traffic", f"{mix}.json")) as f:
        params = json.load(f)
    params["check_columns_per_deploy"] = 1 << 20  # every column of every deploy
    with open(traffic, "w") as f:
        json.dump(params, f)

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny", "source": "test", "file": "chipbench/configs/tiny.json",
        "reduced": [], "why": "test",
    })
    cell = f"tiny.{mix}"
    bench["workloads"].append({
        "name": cell, "config": "tiny", "traffic": f"tiny-{mix}", "chips": 1,
        "why": "test",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "program_cols_per_s" in (m["name"], m.get("moves")):
            m["workloads"] = [cell]
    if extra_metric:
        bench["per_layer"].append({
            "name": extra_metric, "unit": "1", "better": "higher",
            "source": "program_counter", "layer": "test",
            "moves": "program_cols_per_s", "workloads": [cell],
        })
        with open(os.path.join(root, "chipbench", "metrics", f"{extra_metric}.py"), "w") as f:
            f.write("def read(run):\n    return run.records['deploys']\n")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
