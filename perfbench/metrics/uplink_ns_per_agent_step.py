"""Device ns per agent-step in the round's ``uplink`` scope: the channel
superposition and the server step: gains, participation mask, the gain-
weighted fold, the fused kernel, the psum.

Summed over chips: the scope's leaf-op device time over the traced window,
times 1e9 over the run's agent-steps per second (``perfbench.layers``).
Nothing to read where the program has no map of ops to scopes."""
from perfbench.layers import ns_per_agent_step


def read(ctx):
    return ns_per_agent_step(ctx, "uplink")
