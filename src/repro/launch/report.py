"""Render the roofline table (EXPERIMENTS.md Sec. Roofline) from the
dry-run JSONs.

    PYTHONPATH=src python -m repro.launch.report [--dir results/dryrun] \
        [--mesh pod16x16]
"""

from __future__ import annotations

import argparse
import glob
import json
import os


def load_rows(dir_: str, mesh: str) -> list[dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(dir_, mesh, "*.json"))):
        with open(path) as f:
            rows.append(json.load(f))
    return rows


def fmt_row(r: dict) -> str:
    """XLA:CPU cost_analysis counts while-loop bodies ONCE (scan trip
    counts omitted), so HLO-FLOPs is a lower bound on scanned models.
    We report the HLO-based compute term alongside the MODEL_FLOPS-based
    term (6ND / 2ND) and classify the bottleneck with the larger of the
    two; roofline-fraction = model-compute / (dominant-term)."""
    from repro.launch.roofline import chip_peaks

    ms = lambda s: f"{s * 1e3:9.3f}"
    peak = chip_peaks(r["device_kind"]).bf16_flops
    model_comp = r["model_flops"] / (r["chips"] * peak)
    comp = max(r["compute_s"], model_comp)
    terms = {
        "compute": comp,
        "memory": r["memory_s"],
        "collective": r["collective_s"],
    }
    dom = max(terms, key=terms.get)
    frac = model_comp / max(max(terms.values()), 1e-30)
    mem = r.get("memory_analysis", {})
    temp_gib = mem.get("temp_size_in_bytes", 0) / 2**30
    arg_gib = mem.get("argument_size_in_bytes", 0) / 2**30
    return (
        f"| {r['arch']} | {r['shape']} | {ms(r['compute_s'])} | {ms(model_comp)} | "
        f"{ms(r['memory_s'])} | {ms(r['collective_s'])} | {dom} | "
        f"{frac:.2f} | {arg_gib:.2f} | {temp_gib:.2f} |"
    )


HEADER = (
    "| arch | shape | HLO-comp [ms] | 6ND-comp [ms] | memory [ms] | "
    "collective [ms] | bottleneck | roofline-frac | args GiB/dev | temp GiB/dev |\n"
    "|---|---|---|---|---|---|---|---|---|---|"
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--mesh", default="pod16x16")
    args = ap.parse_args()
    rows = load_rows(args.dir, args.mesh)
    print(HEADER)
    for r in rows:
        print(fmt_row(r))
    print(f"\n{len(rows)} cells; mesh={args.mesh}; "
          "terms per formulae in launch/roofline.py (v5e constants)")


if __name__ == "__main__":
    main()
