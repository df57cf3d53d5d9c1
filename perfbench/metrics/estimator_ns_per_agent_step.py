"""Device ns per agent-step in the round's ``estimator`` scope: the G(PO)MDP
gradient, forward and backward (``core/gpomdp``), where the serial
scatter-add loop of the ``logp[action]`` gather's gradient runs.

Summed over chips: the scope's leaf-op device time over the traced window,
times 1e9 over the run's agent-steps per second (``perfbench.layers``).
Nothing to read where the program has no map of ops to scopes."""
from perfbench.layers import ns_per_agent_step


def read(ctx):
    return ns_per_agent_step(ctx, "estimator")
