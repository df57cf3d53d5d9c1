"""The round's share of the chips' bf16 peak, in percent: the policy's
forward and backward FLOPs per agent-step (the configuration's task
reference, ``flops_per_agent_step``, from its shapes) times the
agent-steps per second of this run's window, over chips times peak
(``perfbench.peaks``)."""


def read(ctx):
    flops = ctx.cell.ref.flops_per_agent_step(ctx.config) * ctx.rate
    return 100.0 * flops / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
