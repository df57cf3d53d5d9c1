#!/usr/bin/env python3
"""Smoke run of the federated round on a TPU, through the normal entry points.

    python chip_smoke.py                # one chip: phases paper, uplink, fleet
    python chip_smoke.py --four-chips   # four chips: the two sharded phases only

Phases (one process; weights and data are made from fixed seeds):

* ``paper``  -- the paper's Section IV setting (``configs/ota_pg_particle``
  ``RAYLEIGH``: LandmarkNav, the 4-16-5 MLP, N=10, M=10, T=20, alpha=1e-4,
  sigma^2=-60 dB, debiased) for 20 Monte Carlo runs of K=20 rounds through
  ``fedpg.monte_carlo`` with ``ota_backend="auto"``.  The backend must
  resolve to the fused Pallas kernel and the compiled program must hold a
  ``tpu_custom_call``.  Reference: the same runs at sigma=0 on the fused
  kernel against the XLA uplink; the gains are a shared operand.
* ``uplink`` -- ``ota.aggregate_apply(backend="auto")`` at N=8, d=2^21 with
  fp32 and bf16 wire, against the ``kernels/ref.py`` oracle fed the
  kernel's own noise.
* ``fleet``  -- N=10^4 agents streamed in blocks of 256 through
  ``fedpg.run_jit`` against the stacked form at the same N, and two
  ``RoundService`` commits at 10% Bernoulli participation with a
  straggler deadline.
* ``agent_mesh`` (only with ``--four-chips``) -- the ``agent_mesh`` round
  over 4 devices (N=1000) against the one-chip stacked form under the
  exact uplink and under a fixed gain.
* ``sharded_sweep`` (only with ``--four-chips``) -- ``sweep(mode=
  "sharded")`` over four noise lanes, one per chip, bitwise against
  ``mode="vmap"``'s program run on one chip over each chip's block of
  lanes (XLA compiles that program for each lane batch, so the four-lane
  batch may differ in the last bits; its difference is reported).  The
  engine compiles one program per partition and chip on every call;
  ``warm_s`` is the call less that compile.

Each phase prints one JSON line: its name, ``compile_s`` (the first call's
wall time less the warm call's: compilation plus one-time set-up),
``warm_s`` (host clock around a warm call ending in ``block_until_ready``)
and its checks, each with the error found and the tolerance used.  The
last line is ``{"ok": true, "device": {...}}`` only when every phase
passed; otherwise the script exits non-zero.  It fails when JAX finds no
TPU.  The persistent compile cache is on (``repro.utils.platform``).
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0
# Tolerances established on a TPU v5e (see CHANGES.md).  Each form sums
# the agents in its own order (the kernel's f32 matvec against XLA's
# reduce, a sequential block fold against one reduce, a psum of shard
# sums); the difference reaches rewards and grad_sq through the update.
# Measured largest relative differences: 1.4e-7 (paper, sigma=0, fused
# against XLA), 2.0e-6 (N=10^4, streamed against stacked).
RTOL_PAPER = 1e-6
RTOL_STREAMED = 1e-5
RTOL_MESH = 1e-5
# the fused-kernel parity tolerances of tests/test_kernels.py
RTOL_FP32, ATOL_FP32 = 1e-6, 1e-7
RTOL_BF16, ATOL_BF16 = 2e-2, 1e-4


# ---------------------------------------------------------------------------
# checks and timing
# ---------------------------------------------------------------------------

def _check(name, ok, **info):
    return {"check": name, "ok": bool(ok), **info}


def _close(name, got, want, rtol, atol=0.0):
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = np.abs(got - want)
    ok = got.shape == want.shape and np.all(np.isfinite(got)) \
        and np.all(err <= atol + rtol * np.abs(want))
    rel = err / np.maximum(np.abs(want), np.finfo(np.float32).tiny)
    return _check(name, ok, max_abs_err=float(err.max()),
                  max_rel_err=float(rel.max()), rtol=rtol, atol=atol)


def _equal(name, got, want):
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    ok = got.shape == want.shape and np.array_equal(got, want)
    diff = float(np.max(np.abs(got.astype(np.float64) - want)))
    return _check(name, ok, max_abs_err=diff, rtol=0.0, atol=0.0)


def _finite(name, *arrays):
    import numpy as np

    return _check(name, all(np.all(np.isfinite(np.asarray(a)))
                            for a in arrays))


def _first_and_warm(fn):
    """(result, compile_s, warm_s): two calls, each ending in
    ``block_until_ready``; compile_s is the first call's excess."""
    import jax

    from repro.telemetry import trace as rtrace

    with rtrace.span("first") as first:
        jax.block_until_ready(fn())
    with rtrace.span("warm") as warm:
        out = jax.block_until_ready(fn())
    return (out, (first.duration_us - warm.duration_us) / 1e6,
            warm.duration_us / 1e6)


def _descendants(span):
    for child in span.children:
        yield child
        yield from _descendants(child)


def _shard_devices(x) -> set:
    return {s.device.id for s in x.addressable_shards}


# ---------------------------------------------------------------------------
# shared settings
# ---------------------------------------------------------------------------

def _paper(n_agents=10, n_rounds=20):
    """(env, policy, FedPGConfig, OTAConfig) of the paper's Rayleigh run."""
    from repro.configs.ota_pg_particle import RAYLEIGH
    from repro.core.channel import make_channel
    from repro.core.ota import OTAConfig
    from repro.rl.env import LandmarkNav
    from repro.rl.policy import MLPPolicy

    cfg = RAYLEIGH.fedpg(n_agents=n_agents, batch_m=10, n_rounds=n_rounds)
    ota = OTAConfig(channel=make_channel(RAYLEIGH.channel,
                                         **dict(RAYLEIGH.channel_kwargs)),
                    noise_sigma=RAYLEIGH.noise_sigma, debias=True)
    return LandmarkNav(), MLPPolicy(4, 16, 5), cfg, ota


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_paper(runs: int = 20):
    import jax
    from dataclasses import replace

    from repro.core import fedpg, ota as ota_mod

    env, pol, cfg, ota = _paper()
    key = jax.random.key(SEED)
    backend = ota_mod.AggregateSpec(backend="auto").resolved_backend()
    hist, compile_s, warm_s = _first_and_warm(
        lambda: fedpg.monte_carlo(env, pol, cfg, key, runs, ota=ota))
    # the program monte_carlo runs (its jaxpr, so its compile comes from
    # the persistent cache): the vmap of fedpg.run over the runs' keys
    program = jax.jit(jax.vmap(
        lambda k: fedpg.run(env, pol, cfg, k, ota=ota)[1]))
    text = program.lower(jax.random.split(key, runs)).compile().as_text()

    quiet = replace(ota, noise_sigma=0.0)
    fused = fedpg.monte_carlo(env, pol, cfg, key, runs, ota=quiet)
    plain = fedpg.monte_carlo(env, pol, cfg, key, runs, ota=quiet,
                              ota_backend="xla")
    checks = [
        _check("backend_auto_is_pallas", backend == "pallas",
               backend=backend),
        _check("program_has_tpu_custom_call", "tpu_custom_call" in text),
        _check("history_shape",
               hist.rewards.shape == (runs, cfg.n_rounds),
               shape=list(hist.rewards.shape)),
        _finite("history_finite", hist.rewards, hist.grad_sq,
                hist.gain_mean),
        _equal("sigma0_gain_mean_fused_vs_xla", fused.gain_mean,
               plain.gain_mean),
        _close("sigma0_rewards_fused_vs_xla", fused.rewards, plain.rewards,
               RTOL_PAPER),
        _close("sigma0_grad_sq_fused_vs_xla", fused.grad_sq, plain.grad_sq,
               RTOL_PAPER),
    ]
    return dict(n_agents=cfg.n_agents, batch_m=cfg.batch_m,
                horizon=cfg.horizon, rounds=cfg.n_rounds, mc_runs=runs,
                compile_s=compile_s, warm_s=warm_s, checks=checks)


def phase_uplink(n_agents: int = 8, dim: int = 1 << 21):
    import jax
    import jax.numpy as jnp

    from repro.core import ota as ota_mod
    from repro.core.channel import RayleighChannel
    from repro.kernels import ota_fused, ref

    alpha, sigma = 0.05, 0.5
    kg, kp, kr = jax.random.split(jax.random.key(SEED), 3)
    grads = {"w": jax.random.normal(kg, (n_agents, dim), jnp.float32)}
    params = {"w": jax.random.normal(kp, (dim,), jnp.float32)}
    out = {}
    checks = []
    for wire, rtol, atol in (("float32", RTOL_FP32, ATOL_FP32),
                             ("bfloat16", RTOL_BF16, ATOL_BF16)):
        cfg = ota_mod.OTAConfig(
            channel=RayleighChannel(), noise_sigma=sigma, debias=True,
            wire_dtype=None if wire == "float32" else wire)
        step = jax.jit(lambda g, p, k, c=cfg: ota_mod.aggregate_apply(
            g, c, p, key=k, alpha=alpha, backend="auto"))
        (p_next, h), compile_s, warm_s = _first_and_warm(
            lambda: step(grads, params, kr))
        # the oracle, fed the kernel's own noise (zero gradients, one
        # unit-gain agent, sigma=1, scale=1 return the noise itself)
        _, key_n = jax.random.split(kr)
        seed = ota_mod._kernel_seed(key_n)
        noise = ota_fused.fused_aggregate(
            jnp.zeros((1, dim), jnp.float32), jnp.ones((1,), jnp.float32),
            sigma=1.0, scale=1.0, seed=seed, with_noise=True)
        scale = 1.0 / (n_agents * cfg.norm_const_for(n_agents))
        wire_grads = grads["w"].astype(wire)
        want = jax.jit(lambda g, hh, p, n: ref.ota_fused_sgd_ref(
            g, hh, p, n, alpha=alpha, sigma=sigma, scale=scale))(
                wire_grads, h, params["w"], noise)
        checks.append(_close(f"{wire}_wire_vs_ref", p_next["w"], want,
                             rtol, atol))
        out[f"{wire}_compile_s"] = compile_s
        out[f"{wire}_warm_s"] = warm_s
    return dict(n_agents=n_agents, dim=dim,
                compile_s=out["float32_compile_s"],
                warm_s=out["float32_warm_s"], **out, checks=checks)


def phase_fleet(n_agents: int = 10_000, blocks: int = 256, rounds: int = 3):
    import jax

    from repro.core import fedpg
    from repro.service.driver import RoundService, ServiceConfig
    from repro.service.faults import FaultConfig, StragglerModel
    from repro.service.participation import (ParticipationConfig,
                                             expected_count)
    from repro.telemetry import TelemetryConfig

    env, pol, cfg, ota = _paper(n_agents=n_agents, n_rounds=rounds)
    key = jax.random.key(SEED)
    (_, streamed), compile_s, warm_s = _first_and_warm(
        lambda: fedpg.run_jit(env, pol, cfg, key, ota=ota,
                              agent_blocks=blocks))
    _, stacked = fedpg.run_jit(env, pol, cfg, key, ota=ota)
    checks = [
        _finite("streamed_finite", streamed.rewards, streamed.grad_sq),
        _equal("gain_mean_streamed_vs_stacked", streamed.gain_mean,
               stacked.gain_mean),
        _close("rewards_streamed_vs_stacked", streamed.rewards,
               stacked.rewards, RTOL_STREAMED),
        _close("grad_sq_streamed_vs_stacked", streamed.grad_sq,
               stacked.grad_sq, RTOL_STREAMED),
    ]

    part = ParticipationConfig(rate=0.1, faults=FaultConfig(
        stragglers=StragglerModel(dist="exp", mean=1.0), deadline=2.0))
    per_commit = 2
    svc = RoundService(
        env, pol, cfg, key, participation=part, ota=ota,
        telemetry=TelemetryConfig(), agent_blocks=blocks,
        service=ServiceConfig(rounds_per_commit=per_commit, max_rounds=4))
    records = [svc.commit(), svc.commit()]
    p = expected_count(part, n_agents) / n_agents
    # five binomial standard deviations of a rate over one commit's draws
    tol = 5.0 * math.sqrt(p * (1.0 - p) / (n_agents * per_commit))
    for i, rec in enumerate(records):
        rate = rec.get("participation_rate", float("nan"))
        checks.append(_finite(f"commit{i}_finite", rec["reward"],
                              rec["grad_sq"], rec["gain_mean"]))
        checks.append(_check(f"commit{i}_participation",
                             abs(rate - p) <= tol, got=rate, expected=p,
                             atol=tol))
    checks.append(_finite("service_theta_finite",
                          *jax.tree.leaves(svc.state.theta)))
    return dict(n_agents=n_agents, agent_blocks=blocks, rounds=rounds,
                compile_s=compile_s, warm_s=warm_s,
                service_commit_s=[r["wall_us"] / 1e6 for r in records],
                checks=checks)


def phase_agent_mesh(n_agents: int = 1000):
    import jax

    from repro.core import fedpg
    from repro.core.channel import FixedGainChannel
    from repro.core.ota import OTAConfig
    from repro.launch.mesh import make_agent_mesh

    mesh = make_agent_mesh(4, jax.devices()[:4])
    ids = {d.id for d in mesh.devices.flat}
    checks = [_check("agent_mesh_spans_4_devices", len(ids) == 4,
                     devices=sorted(ids))]
    env, pol, cfg, _ = _paper(n_agents=n_agents, n_rounds=10)
    key = jax.random.key(SEED)
    uplinks = (("exact", None),
               ("fixed_gain", OTAConfig(channel=FixedGainChannel(gain=0.8),
                                        debias=True)))
    times = {}
    for name, ota in uplinks:
        (_, sharded), compile_s, warm_s = _first_and_warm(
            lambda o=ota: fedpg.run_jit(env, pol, cfg, key, ota=o,
                                        agent_mesh=mesh))
        _, stacked = fedpg.run_jit(env, pol, cfg, key, ota=ota)
        times[f"{name}_compile_s"] = compile_s
        times[f"{name}_warm_s"] = warm_s
        checks += [
            _check(f"{name}_output_on_4_devices",
                   _shard_devices(sharded.rewards) == ids,
                   devices=sorted(_shard_devices(sharded.rewards))),
            _equal(f"{name}_gain_mean", sharded.gain_mean,
                   stacked.gain_mean),
            _close(f"{name}_rewards", sharded.rewards, stacked.rewards,
                   RTOL_MESH),
            _close(f"{name}_grad_sq", sharded.grad_sq, stacked.grad_sq,
                   RTOL_MESH),
        ]
    return dict(n_agents=n_agents, rounds=cfg.n_rounds,
                compile_s=times["exact_compile_s"],
                warm_s=times["exact_warm_s"], **times, checks=checks)


def phase_sharded_sweep(mc_runs: int = 20):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.core import distribute
    from repro.core.channel import RayleighChannel
    from repro.core.sweep import grid, lane_program, partition_scenarios, sweep
    from repro.launch.mesh import make_sweep_mesh
    from repro.telemetry import trace as rtrace

    devices = jax.devices()[:4]
    ids = {d.id for d in devices}
    env, pol, paper, _ = _paper()
    key = jax.random.key(SEED)
    scens = grid(channel=RayleighChannel(),
                 noise_sigma=[1e-3, 3e-3, 1e-2, 3e-2],
                 n_agents=paper.n_agents, batch_m=paper.batch_m,
                 horizon=paper.horizon, n_rounds=paper.n_rounds,
                 alpha=paper.alpha, debias=True)
    mesh = make_sweep_mesh(devices=devices)
    with rtrace.span("sharded") as sp:
        sharded = sweep(env, pol, scens, key, mc_runs, mode="sharded",
                        mesh=mesh)
    # the sweep's partition program, placed by hand to see where it ran
    (part,) = partition_scenarios(scens)
    packed, fn, _ = lane_program(env, pol, part, mc_runs=mc_runs)
    keys = jax.random.split(key, mc_runs)
    placement = distribute.plan_placement(mesh, len(scens), mc_runs)
    placed = distribute.PartitionProgram(fn, placement)(
        jax.device_put(packed, NamedSharding(mesh, placement.lane_spec)),
        jax.device_put(keys, NamedSharding(mesh, placement.key_spec)))
    # the contract's reference: mode="vmap"'s program on one chip over
    # each device's block of lanes (one lane each here)
    per_lane = [jax.jit(fn)(jax.tree.map(lambda x: x[i:i + 1], packed), keys)
                for i in range(len(scens))]
    with rtrace.span("vmap") as sp_vmap:
        vmapped = sweep(env, pol, scens, key, mc_runs, mode="vmap")
    lane_devices = _shard_devices(placed.rewards)
    checks = [_check(
        "lanes_on_4_devices",
        lane_devices == ids and not placed.rewards.sharding
        .is_fully_replicated, devices=sorted(lane_devices))]
    whole_batch = {}
    for field in ("rewards", "grad_sq", "gain_mean"):
        got = getattr(sharded.history, field)
        checks.append(_equal(f"placed_vs_sweep_{field}",
                             getattr(placed, field), got))
        checks.append(_equal(
            f"sharded_vs_vmap_per_device_{field}", got,
            np.concatenate([np.asarray(getattr(h, field))
                            for h in per_lane])))
        # not a check: XLA compiles the vmap program for each lane batch
        # (see repro.core.distribute)
        whole_batch[field] = float(np.max(np.abs(
            np.asarray(got, np.float64) - getattr(vmapped.history, field))))
    # dispatch_partition records its compile as a span of its own
    compile_us = sum(c.duration_us for c in _descendants(sp)
                     if c.name == "compile")
    return dict(lanes=len(scens), mc_runs=mc_runs,
                compile_s=compile_us / 1e6,
                warm_s=(sp.duration_us - compile_us) / 1e6,
                vmap_s=sp_vmap.duration_us / 1e6,
                vmap_whole_batch_max_abs_diff=whole_batch, checks=checks)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase, across four chips")
    args = ap.parse_args(argv)

    try:
        from repro.utils import platform as rplat
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({ROOT / 'src'}): {e}", file=sys.stderr)
        return 2
    cache = rplat.enable_compile_cache()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found: JAX sees platform {dev.platform!r} "
              f"({dev.device_kind}); this smoke run needs a TPU",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices), "jax": jax.__version__,
                      "compile_cache": cache}), flush=True)

    phases = ([("agent_mesh", phase_agent_mesh),
               ("sharded_sweep", phase_sharded_sweep)] if args.four_chips else
              [("paper", phase_paper), ("uplink", phase_uplink),
               ("fleet", phase_fleet)])
    failed = []
    for name, fn in phases:
        try:
            rec = fn()
            ok = all(c["ok"] for c in rec["checks"])
        except Exception:  # reported, and the run still fails below
            rec = {"error": traceback.format_exc(limit=8)}
            ok = False
        print(json.dumps({"phase": name, "ok": ok, **rec}), flush=True)
        if not ok:
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
