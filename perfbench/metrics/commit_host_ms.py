"""Host time of the traced commit outside the device wait, in ms: its
``service.dispatch`` span (enqueueing the round segment) plus its
``service.record`` span (summary, ledger, checkpoint), children of the
``service_commit`` span; the ``service.fetch`` span between them is the
host waiting on the chip.  Nothing to read where the commit has no such
children."""
from perfbench.layers import last_commit


def read(ctx):
    sp = last_commit(ctx)
    if sp is None:
        return None
    parts = {c.name: c.duration_us for c in sp.children}
    if "service.dispatch" not in parts or "service.record" not in parts:
        return None
    return (parts["service.dispatch"] + parts["service.record"]) / 1e3
