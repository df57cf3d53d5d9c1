"""``fedpg.monte_carlo``: the configuration's ``mc_runs`` runs of K rounds,
vmapped, a call.

Traffic: ``{"entry": "monte_carlo", "rounds": K}``.  Only the rounds'
metrics leave the program, so no round's parameters are observable.
"""
from __future__ import annotations

from perfbench.cell import Answer, scan_rounds


def runs(cell) -> int:
    return int(cell.config["mc_runs"])


def rounds_per_call(cell) -> int:
    return cell.rounds


def build(cell, devices):
    pass


def call(cell, i):
    from repro.core import fedpg

    p = cell.program
    return fedpg.monte_carlo(p["env"], p["pol"], p["fc"], cell.call_key(i),
                             cell.runs, ota=p["ota"])


def plan(cell, i):
    import jax

    return scan_rounds(list(jax.random.split(cell.call_key(i), cell.runs)),
                       cell.rounds)


def observed(cell, rnd) -> bool:
    return False


def replays(cell) -> bool:
    return False


def sequential_sum(cell) -> bool:
    return False


def answers(cell, i, out):
    import numpy as np

    rewards, grad_sq = np.asarray(out.rewards), np.asarray(out.grad_sq)
    gain_mean = np.asarray(out.gain_mean)
    return [Answer(call=i, init_key=rnd.init_key, round_key=rnd.key,
                   reward=float(rewards[rnd.run, rnd.round_idx]),
                   grad_sq=float(grad_sq[rnd.run, rnd.round_idx]),
                   gain_mean=float(gain_mean[rnd.run, rnd.round_idx]),
                   run=rnd.run, round_idx=rnd.round_idx)
            for rnd in plan(cell, i)]


def uplink_launch(config, d: int):
    """Bytes and FLOPs of one launch of the stacked fused uplink kernel
    (float32), once a round for all vmapped runs: the gain matvec over N
    agents, the noise, the debias and the SGD step."""
    runs, n = int(config["mc_runs"]), int(config["n_agents"])
    return {"bytes": runs * 4 * (n * d + n + 2 * d),
            "flops": runs * (2 * n * d + 3 * d)}
