"""Device ns per agent-step in the round's ``rollout`` scope: trajectory
sampling (``rl/sampler.rollout_batch``), the agents' sampling keys and the
block loop's slicing.

Summed over chips: the scope's leaf-op device time over the traced window,
times 1e9 over the run's agent-steps per second (``perfbench.layers``).
Nothing to read where the program has no map of ops to scopes."""
from perfbench.layers import ns_per_agent_step


def read(ctx):
    return ns_per_agent_step(ctx, "rollout")
