"""Device ns per agent-step in the round's ``epilogue`` scope: work that only
produces the round's reported metrics: the exact mean on a noisy uplink,
``grad_sq``, reward and gain means.

Summed over chips: the scope's leaf-op device time over the traced window,
times 1e9 over the run's agent-steps per second (``perfbench.layers``).
Nothing to read where the program has no map of ops to scopes."""
from perfbench.layers import ns_per_agent_step


def read(ctx):
    return ns_per_agent_step(ctx, "epilogue")
