"""Share of the window's deploy time that the program spends after its
one host sync: its `deploy.fold` spans (the health, digest and registry
folds and the ledger charges, host work while the device waits) over
its `deploy` spans.  Nothing where the program records no `deploy.fold`
span."""

from chipbench import program_spans


def read(run):
    return program_spans.child_share(run, "deploy.fold")
