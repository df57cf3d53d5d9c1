"""``fedpg.run_jit``: one run of K rounds a call, from fresh parameters.

Traffic: ``{"entry": "run_jit", "rounds": K}``, with ``"agent_blocks": B``
to stream the agent axis in blocks of B, or ``"agent_mesh": C`` to shard
it over a mesh of C chips.  The parameters after the last round are the
call's output, so a one-round call is observable from start to end.
"""
from __future__ import annotations

from perfbench.cell import Answer, host, scan_rounds


def runs(cell) -> int:
    return 1


def rounds_per_call(cell) -> int:
    return cell.rounds


def build(cell, devices):
    if cell.chips > 1:
        import jax

        from repro.launch.mesh import make_agent_mesh

        devices = jax.devices() if devices is None else devices
        cell.program["mesh"] = make_agent_mesh(cell.chips,
                                               devices[:cell.chips])


def call(cell, i):
    from repro.core import fedpg

    p = cell.program
    return fedpg.run_jit(p["env"], p["pol"], p["fc"], cell.call_key(i),
                         ota=p["ota"], agent_blocks=p["blocks"],
                         agent_mesh=p["mesh"])


def plan(cell, i):
    return scan_rounds([cell.call_key(i)], cell.rounds)


def observed(cell, rnd) -> bool:
    return rnd.round_idx == cell.rounds - 1


def replays(cell) -> bool:
    return cell.rounds == 1


def sequential_sum(cell) -> bool:
    """The streamed round on one chip folds the agents in order."""
    return cell.program["blocks"] is not None and cell.chips == 1


def answers(cell, i, out):
    import numpy as np

    theta, hist = out
    return [Answer(call=i, init_key=rnd.init_key, round_key=rnd.key,
                   reward=float(np.asarray(hist.rewards)[rnd.round_idx]),
                   grad_sq=float(np.asarray(hist.grad_sq)[rnd.round_idx]),
                   gain_mean=float(np.asarray(hist.gain_mean)[rnd.round_idx]),
                   round_idx=rnd.round_idx,
                   theta_out=host(theta) if observed(cell, rnd) else None)
            for rnd in plan(cell, i)]


def uplink_launch(config, d: int):
    """Bytes and FLOPs of one launch of the fused uplink kernel (float32):
    the streamed round folds the agents before the kernel, which reads the
    d-sized superposition and the parameters and writes the parameters."""
    return {"bytes": 4 * 3 * d, "flops": 3 * d}
