"""Share of the chip's HBM bandwidth that the window's programming
requires: every write-and-verify iteration of every column while the
column was still being programmed (the deploy reports' iteration
counts) moves its cells' state once (`work.wv_iteration`), over the
traced window.  The loop is bound by bytes, so the share is of the
bandwidth peak."""

from chipbench import work


def read(run):
    rec = run.records
    if not rec.get("column_iterations"):
        return None
    need = work.wv_iteration(rec["column_iterations"], rec["n_cells"])
    return 100.0 * need.bytes / run.peaks.hbm_bw / run.trace.window_s
