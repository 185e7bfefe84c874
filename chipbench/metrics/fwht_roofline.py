"""Share of its roofline that the `fwht` kernel reaches in the traced
window: the bytes and operations its calls require (`work.fwht`, from
each call's (columns, cells) shape) over the kernel's summed device
time.  Nothing when no `fwht` kernel ran."""

from chipbench import work


def read(run):
    events = run.trace.kernel_events("fwht")
    if not events:
        return None
    need = work.ZERO
    for op in events:
        c, n = op.shapes()[0][1]
        need = need + work.fwht(c, n)
    seconds = sum(op.seconds for op in events)
    return work.roofline_share(need, seconds, run.peaks.bf16_flops, run.peaks.hbm_bw)
