"""Useful over attempted agents of the traced commit, in percent: the
agents whose gradients the uplink took (the participation probe's
realised count, ``agents_participating``) over the agents the round
program rolled out (``agents_rolled_out``, phantom block padding
included), both counters of the ``service_commit`` span.  Nothing to read
where the span lacks them."""
from perfbench.layers import last_commit


def read(ctx):
    sp = last_commit(ctx)
    if sp is None:
        return None
    took = sp.attrs.get("agents_participating")
    rolled = sp.attrs.get("agents_rolled_out")
    if took is None or not rolled:
        return None
    return 100.0 * took / rolled
