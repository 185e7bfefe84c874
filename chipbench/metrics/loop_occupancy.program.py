"""Occupancy of the write-and-verify loops in the window's deploys: the
iterations columns ran while still being programmed, over the trips of
each bucket's loop times the columns it carries (filler included), both
summed from the args of the program's `deploy` spans
(`active_column_iterations`, `loop_column_iterations`).  A loop runs
until its slowest column is done, so the rest is work on finished
columns.  Nothing where the spans carry no such args."""

from chipbench import program_spans


def read(run):
    deploys = program_spans.window_deploys(run)
    if deploys is None:
        return None
    args = [d["args"] for d, _ in deploys]
    if any("loop_column_iterations" not in a for a in args):
        return None
    loop = sum(a["loop_column_iterations"] for a in args)
    if loop <= 0:
        return None
    return 100.0 * sum(a["active_column_iterations"] for a in args) / loop
