"""Share of the window's deploy time that the program spends planning:
its `deploy.plan` spans (quantize and pack every leaf, dispatched op by
op from the host) over its `deploy` spans.  Nothing where the program
records no `deploy.plan` span."""

from chipbench import program_spans


def read(run):
    return program_spans.child_share(run, "deploy.plan")
