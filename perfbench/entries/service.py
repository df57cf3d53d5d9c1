"""``RoundService.commit`` of one round a call, in a loop that continues the
service's state from call to call.

Traffic: ``{"entry": "service", "rounds": 1, "agent_blocks": B,
"participation": {"rate": p, "straggler_mean": s, "deadline": t}}``:
Bernoulli participation at rate p, exponential upload delays of mean s,
and a deadline t past which an upload is dropped.  Every round's
parameters, before and after, are in the service's state, and its
participation mask is checked against the reference's
(:func:`reference_mask`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.cell import Answer, Round, host


def runs(cell) -> int:
    return 1


def rounds_per_call(cell) -> int:
    return 1


def _keys(cell):
    """(init, round, service) keys, as ``RoundService`` splits its key."""
    return jax.random.split(cell.base_key, 3)


def build(cell, devices):
    from repro.service.driver import RoundService, ServiceConfig
    from repro.service.faults import FaultConfig, StragglerModel
    from repro.service.participation import ParticipationConfig
    from repro.telemetry import TelemetryConfig

    p, pt = cell.program, cell.traffic["participation"]
    part = ParticipationConfig(rate=pt["rate"], faults=FaultConfig(
        stragglers=StragglerModel(dist="exp", mean=pt["straggler_mean"]),
        deadline=pt["deadline"]))
    # the probe a service reports its participation with
    tel = TelemetryConfig(snr=False, grad_norms=False, moment_drift=False,
                          dispersion=False, participation=True)
    p["part"] = part
    p["svc"] = RoundService(
        p["env"], p["pol"], p["fc"], cell.base_key,
        participation=part, ota=p["ota"], telemetry=tel,
        agent_blocks=p["blocks"],
        service=ServiceConfig(rounds_per_commit=1, max_rounds=1 << 30))


def call(cell, i):
    """The commit record with the state before and after it."""
    svc = cell.program["svc"]
    before = svc.state
    rec = svc.commit()
    return before, rec, svc.state


def plan(cell, i):
    """Call i commits round i of the service's one run."""
    key_init, key_round, _ = _keys(cell)
    return [Round(0, i, jax.random.fold_in(key_round, i), key_init)]


def observed(cell, rnd) -> bool:
    return True


def replays(cell) -> bool:
    return True


def sequential_sum(cell) -> bool:
    """The streamed round folds the agents in order."""
    return cell.program["blocks"] is not None


def answers(cell, i, out):
    before, rec, after = out
    (rnd,) = plan(cell, i)
    if int(before.round_idx) != rnd.round_idx:
        raise ValueError(f"call {i} committed round {int(before.round_idx)}")
    return [Answer(call=i, init_key=rnd.init_key, round_key=rnd.key,
                   reward=rec["reward"], grad_sq=rec["grad_sq"],
                   gain_mean=rec["gain_mean"], round_idx=rnd.round_idx,
                   theta_out=host(after.theta),
                   service_keys=(before.part_key, before.sched_key),
                   theta_in=host(before.theta))]


def program_mask(cell, ans):
    """The program's participation mask of a round: (N,) bool."""
    from repro.service.participation import round_mask

    part_key, sched_key = ans.service_keys
    return round_mask(cell.program["part"], part_key, sched_key,
                      jnp.int32(ans.round_idx),
                      jnp.arange(cell.n_agents, dtype=jnp.int32),
                      cell.n_agents)


def reference_mask(cell, round_idx: int):
    """Bernoulli participation AND an exponential upload delay within the
    deadline, each drawn from a ``fold_in(key, agent)`` counter stream of
    the round's key.  Returns (mask, near): ``near`` marks agents whose
    draw lies within rounding of a threshold."""
    pt = cell.traffic["participation"]
    part_key, _ = jax.random.split(_keys(cell)[2])
    k_bern, k_delay = jax.random.split(
        jax.random.fold_in(part_key, round_idx))
    ids = jnp.arange(cell.n_agents, dtype=jnp.int32)
    u_b = np.asarray(jax.vmap(lambda i: jax.random.uniform(
        jax.random.fold_in(k_bern, i)))(ids), np.float64)
    u_d = np.asarray(jax.vmap(lambda i: jax.random.uniform(
        jax.random.fold_in(k_delay, i)))(ids), np.float64)
    delay = -pt["straggler_mean"] * np.log1p(-u_d)
    mask = (u_b < pt["rate"]) & (delay <= pt["deadline"])
    near = (np.abs(u_b - pt["rate"]) < 1e-6) | \
        (np.abs(delay - pt["deadline"]) < 1e-5 * pt["deadline"])
    return mask, near


def uplink_launch(config, d: int):
    """Bytes and FLOPs of one launch of the fused uplink kernel (float32):
    the streamed round folds the participants before the kernel, which
    reads the d-sized superposition and writes the update."""
    return {"bytes": 4 * 2 * d, "flops": 2 * d}
