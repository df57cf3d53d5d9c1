"""Device time of the collective operations (all-reduce and the like) in
the traced tail of a call, in milliseconds, averaged over the chips used.
The tail holds the end of the call's last round, where the psum runs; the
cells that list this metric run one round a call.  Nothing to read where
the trace holds no collective."""


def read(ctx):
    dev = ctx.summary.devices
    total = sum(d.collective_ns for d in dev) / len(dev)
    return total / 1e6 if total > 0 else None
