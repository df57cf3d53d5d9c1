"""Algorithm 1 (federated PG) and Algorithm 2 (over-the-air federated PG).

Fully-jitted loops: each communication round samples N agents x M
trajectories (vmap x vmap over independent PRNG streams), forms per-agent
mini-batch G(PO)MDP estimates (Eq. 4), aggregates — exactly (Algorithm 1) or
through the simulated fading channel (Algorithm 2, Eq. 6-7) — and applies the
server update.  ``lax.scan`` carries theta across the K rounds so a whole
training run is a single XLA program.

Per-round metrics (the paper's Figs. 1-5):
    reward   — empirical cumulative (discounted) reward, averaged over all
               N*M freshly-sampled trajectories;
    grad_sq  — ||(1/N) sum_i grad_hat J_i||^2, the best available estimate of
               ||grad J(theta^k)||^2 (Fig. 2/5's y-axis before K-averaging).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import gpomdp
from repro.core import ota
from repro.core.ota import OTAConfig
from repro.rl.envs.heterogeneous import HeterogeneousEnv, check_agent_count
from repro.rl.sampler import empirical_reward, rollout_batch
from repro.service import participation as svc_participation
from repro.service import staleness as svc_staleness
from repro.service.participation import ParticipationConfig, ServiceState
from repro.service.staleness import StalenessConfig
from repro.telemetry.probes import RoundTelemetry, TelemetryConfig
from repro.telemetry import probes as _probes
from repro.utils.tree import tree_global_norm_sq

PyTree = Any


@dataclass(frozen=True)
class FedPGConfig:
    n_agents: int = 10           # N
    batch_m: int = 10            # M (trajectories per agent per round)
    horizon: int = 20            # T
    gamma: float = 0.99
    alpha: float = 1e-4          # step size
    n_rounds: int = 200          # K
    estimator: str = "gpomdp"    # or "reinforce"


class History(NamedTuple):
    """Per-round training metrics; prefix-compatible with its 3-field
    predecessor — ``telemetry`` defaults to None (an empty pytree subtree)
    and only holds a ``RoundTelemetry`` stack when a ``TelemetryConfig``
    with active probes was passed to the run."""

    rewards: jax.Array    # (K,)
    grad_sq: jax.Array    # (K,)
    gain_mean: jax.Array  # (K,) mean sampled h per round (1.0 for exact)
    telemetry: Optional[RoundTelemetry] = None  # (K,)-leaved probes, or None


def _active_telemetry(
    telemetry: Optional[TelemetryConfig],
    participation: Optional[ParticipationConfig] = None,
) -> Optional[TelemetryConfig]:
    """Normalise: a config with every probe off is telemetry-off (the
    emitted program must be byte-identical to ``telemetry=None``).  The
    ``participation`` probe flag only counts when an active (normalised)
    participation config makes a service round — on plain runs it has
    nothing to observe and must not activate telemetry."""
    if telemetry is None:
        return None
    if telemetry.active or (participation is not None
                            and telemetry.participation):
        return telemetry
    return None


def _estimator_grad(cfg: FedPGConfig):
    if cfg.estimator == "gpomdp":
        return gpomdp.gpomdp_gradient
    if cfg.estimator == "reinforce":
        return gpomdp.reinforce_gradient
    raise ValueError(f"unknown estimator {cfg.estimator!r}")


def make_round_fn(
    env,
    policy,
    cfg: FedPGConfig,
    ota_cfg: Optional[OTAConfig],
    *,
    agent_mesh=None,
    agent_axis: str = "agents",
    ota_backend: str = "auto",
    telemetry: Optional[TelemetryConfig] = None,
    agent_blocks: Optional[int] = None,
    participation: Optional[ParticipationConfig] = None,
    staleness: Optional[StalenessConfig] = None,
):
    """One communication round: (theta, key) -> (theta', metrics).

    With an *active* ``participation`` config (one that can actually drop
    agents — see :func:`repro.service.participation.normalize`) the round
    becomes a service round ``(ServiceState, key) -> (ServiceState',
    metrics)``: a per-round participation mask (counter-PRNG on ``(round,
    agent_id)``, block/shard invariant) selects the contributing agents,
    non-contributors are masked to exact zeros phantom-agent style, and
    the update is renormalised by the realised (or closed-form expected)
    contribution weight.  ``staleness`` additionally replays
    non-participants' last contributed gradients with age-decay weights
    (stacked and ``agent_blocks`` forms; not composed with
    ``agent_mesh``).  A config that normalises away — ``kind="full"``, a
    static Bernoulli ``rate >= 1`` with no active faults — emits the
    byte-identical plain round.

    A ``HeterogeneousEnv`` is unrolled per agent: the agent vmap additionally
    maps over the wrapper's per-agent field stacks, so agent i samples from
    its own dynamics inside the same jitted program.

    ``agent_mesh`` shards the agent axis across a device mesh instead: each
    shard rolls out its slice of the fleet (``n_agents / axis_size`` agents,
    per-agent env stacks sliced by ``shard_map``) and the uplink runs through
    :func:`repro.core.ota.aggregate` in its axis-stacked form — the
    production shard_map/psum form, with per-agent power control keyed on global agent
    indices.  Numerical relationship to the vmapped form: rollouts are
    identical (same per-agent keys); cross-agent reductions psum in mesh
    order, so exact-uplink runs and *deterministic* channels (FixedGain,
    per-agent budgets over it) match to reduction tolerance — but gains of
    a *stochastic* channel come from the indexed fold_in stream rather than
    the stacked batched draw, a different random realisation entirely:
    those histories agree in distribution, not numerically.

    ``ota_backend`` selects the aggregation implementation ("xla",
    "pallas", or "auto" — see :class:`repro.core.ota.AggregateSpec`); on
    the pallas backend the uplink *and* the server SGD step run as one
    fused kernel pass (:func:`repro.core.ota.aggregate_apply`).

    ``telemetry`` (a :class:`repro.telemetry.TelemetryConfig` with at least
    one probe on) appends a :class:`RoundTelemetry` pytree to the metrics
    tuple — in-jit per-round diagnostics, see ``repro.telemetry.probes``.
    With ``telemetry=None`` (or all probes off) the emitted program is
    bitwise identical to the pre-telemetry round.

    ``agent_blocks`` streams the agent axis: rollouts, gradient estimation
    and the channel superposition run in a ``lax.scan`` over blocks of that
    many agents, so peak memory is O(agent_blocks × d) in the fleet size
    (the scan carry holds one block of trajectories/gradients plus the
    d-sized running sums; only O(N) per-agent *scalars* — gains, returns,
    probe norms — are ever materialised).  Per-agent sampling keys and
    channel gains are indexed by ABSOLUTE agent index, identically to the
    unblocked round, and the cross-agent sums are strict sequential folds:
    histories are bitwise-invariant to the choice of block size (any
    partition of the agent axis, dividing or not — the tail block pads
    masked phantom agents).  Relative to ``agent_blocks=None`` the gain
    means are bitwise-identical and rewards/updates differ only at
    floating-point reassociation level (XLA fuses the blocked rollouts and
    the agent sum differently — last-mantissa-bit effects, ~1e-7
    relative).  Composes with ``agent_mesh``:
    each shard scans its local slice in blocks and the partial sums psum
    across the mesh; a non-dividing ``n_agents`` is then padded with
    masked phantom agents instead of raising.
    """
    part = svc_participation.normalize(participation, cfg.n_agents)
    stale_cfg = svc_staleness.normalize(staleness, part)
    telem = _active_telemetry(telemetry, part)

    if agent_mesh is not None:
        return _make_agent_sharded_round_fn(
            env, policy, cfg, ota_cfg, agent_mesh, agent_axis, ota_backend,
            telemetry=telem, agent_blocks=agent_blocks,
            participation=part, staleness=stale_cfg)
    if agent_blocks is not None:
        return _make_streamed_round_fn(
            env, policy, cfg, ota_cfg, agent_blocks, ota_backend,
            telemetry=telem, participation=part, staleness=stale_cfg)

    grad_fn = _estimator_grad(cfg)
    hetero = isinstance(env, HeterogeneousEnv)
    if hetero:
        check_agent_count(env, cfg.n_agents)

    def round_fn(theta: PyTree, key: jax.Array):
        key_samp, key_chan = jax.random.split(key)
        agent_keys = jax.random.split(key_samp, cfg.n_agents)

        # --- local sampling + estimation (parallel across agents) --------
        def agent_grad(k, lane_params):
            e = env.lane(lane_params) if hetero else env
            traj = rollout_batch(e, policy, theta, k, cfg.horizon, cfg.batch_m)
            return grad_fn(policy, theta, traj, cfg.gamma), traj

        lane_stacks = dict(env.params) if hetero else {}
        grads, trajs = jax.vmap(agent_grad)(agent_keys, lane_stacks)  # N axis

        # --- uplink + server update --------------------------------------
        mean_grad = ota.aggregate(grads, None)[0]  # also the grad_sq metric
        if ota_cfg is None:
            gain_mean = jnp.ones(())
            theta_next = jax.tree.map(
                lambda p, u: p - cfg.alpha * u, theta, mean_grad)
        else:
            theta_next, h = ota.aggregate_apply(
                grads, ota_cfg, theta, key=key_chan, alpha=cfg.alpha,
                backend=ota_backend)
            gain_mean = jnp.mean(h)

        # --- metrics ------------------------------------------------------
        reward = empirical_reward(trajs, cfg.gamma)
        grad_sq = tree_global_norm_sq(mean_grad)
        if telem is None:
            return theta_next, (reward, grad_sq, gain_mean)

        # --- telemetry probes (in-jit, only when requested) ---------------
        if ota_cfg is None:
            gains = jnp.ones((cfg.n_agents,))
            update_norm = jnp.sqrt(grad_sq)
        else:
            gains = h
            update_norm = jnp.sqrt(tree_global_norm_sq(jax.tree.map(
                jnp.subtract, theta, theta_next))) / cfg.alpha
        probes = _probes.stacked_round_probes(
            telem, grads_stacked=grads, gains=gains, ota_cfg=ota_cfg,
            n_agents=cfg.n_agents, gain_mean=gain_mean,
            update_norm=update_norm)
        return theta_next, (reward, grad_sq, gain_mean, probes)

    if part is None:
        return round_fn

    from repro.rl.sampler import discounted_return

    def service_round(state: ServiceState, key: jax.Array):
        theta = state.theta
        key_samp, key_chan = jax.random.split(key)
        agent_keys = jax.random.split(key_samp, cfg.n_agents)
        ids = jnp.arange(cfg.n_agents, dtype=jnp.int32)
        mask = svc_participation.round_mask(
            part, state.part_key, state.sched_key, state.round_idx, ids,
            cfg.n_agents)
        mf = mask.astype(jnp.float32)
        count_p = jnp.sum(mf)

        # rollouts run for every agent (same per-agent keys as the plain
        # round: the realised trajectories of a participant are identical
        # whether or not its peers made the round); non-participants are
        # masked to exact-zero rows before any cross-agent reduction.
        def agent_grad(k, lane_params):
            e = env.lane(lane_params) if hetero else env
            traj = rollout_batch(e, policy, theta, k, cfg.horizon, cfg.batch_m)
            return grad_fn(policy, theta, traj, cfg.gamma), traj

        lane_stacks = dict(env.params) if hetero else {}
        grads, trajs = jax.vmap(agent_grad)(agent_keys, lane_stacks)
        gm = svc_participation.mask_agent_axis(grads, mask)

        if stale_cfg is not None:
            rw = svc_staleness.replay_weights(stale_cfg, mask, state.stale.age)
            w_replay, _, stale_age = svc_staleness.stats(
                stale_cfg, mask, state.stale.age)
            ssum = svc_staleness.replay_sum_stacked(state.stale, rw)
            stale_next = svc_staleness.advance(
                stale_cfg, state.stale, mask, grads)
        else:
            w_replay = jnp.zeros((), jnp.float32)
            ssum = stale_next = stale_age = None

        w_real = count_p + w_replay
        w_norm = w_real if part.debias == "realized" else jnp.asarray(
            svc_participation.expected_count(part, cfg.n_agents), jnp.float32)
        inv_w = svc_participation.safe_inv(w_norm)
        pf = svc_participation.participation_factor(cfg.n_agents, w_norm)

        gsum = jax.tree.map(lambda g: jnp.sum(g, axis=0), gm)
        if ssum is not None:
            gsum = jax.tree.map(jnp.add, gsum, ssum)
        mean_grad = jax.tree.map(lambda s: s * inv_w, gsum)

        if ota_cfg is None:
            gain_mean = jnp.ones(())
            update = mean_grad
        else:
            key_h, _ = jax.random.split(key_chan)
            h = ota.sample_gains(ota_cfg, key_h, cfg.n_agents)
            hm = jnp.where(mask, h, jnp.zeros_like(h))
            # passing key_chan reproduces the plain round's AWGN stream:
            # aggregate re-splits it to the same key_n internally
            u_fresh = ota.aggregate(gm, ota_cfg, key=key_chan, gains=hm,
                                    backend=ota_backend)[0]
            update = jax.tree.map(lambda u: u * pf, u_fresh)
            if ssum is not None:
                update = jax.tree.map(
                    lambda u, s: u + s * inv_w, update, ssum)
            gain_mean = jnp.sum(hm) * svc_participation.safe_inv(count_p)
        theta_next = jax.tree.map(
            lambda p, u: p - cfg.alpha * u, theta, update)

        # metrics over the agents that actually made the round
        returns = discounted_return(trajs.losses, cfg.gamma)
        reward = -jnp.sum(jnp.where(mask[:, None], returns, 0.0)) \
            * svc_participation.safe_inv(count_p) / cfg.batch_m
        grad_sq = tree_global_norm_sq(mean_grad)

        state_next = state._replace(theta=theta_next,
                                    round_idx=state.round_idx + 1,
                                    stale=stale_next)
        if telem is None:
            return state_next, (reward, grad_sq, gain_mean)

        probes = _probes.stacked_round_probes(
            telem, grads_stacked=gm, gains=hm if ota_cfg is not None else mf,
            ota_cfg=ota_cfg, n_agents=cfg.n_agents, gain_mean=gain_mean,
            update_norm=jnp.sqrt(tree_global_norm_sq(update)))
        probes = _probes.participation_probes(
            telem, probes, rate_realized=count_p / cfg.n_agents,
            rate_expected=svc_participation.expected_count(
                part, cfg.n_agents) / cfg.n_agents,
            staleness_mean=stale_age)
        return state_next, (reward, grad_sq, gain_mean, probes)

    return service_round


def _make_streamed_round_fn(
    env, policy, cfg: FedPGConfig, ota_cfg: Optional[OTAConfig],
    agent_blocks: int, ota_backend: str = "auto",
    telemetry: Optional[TelemetryConfig] = None,
    participation: Optional[ParticipationConfig] = None,
    staleness: Optional[StalenessConfig] = None,
):
    """The vmap round evaluated as a blocked scan over the agent axis.

    Each scan step rolls out one block of ``agent_blocks`` agents (a vmap
    *within* the block), folds their gradients into the running exact-mean
    and channel-superposition accumulators (strict sequential folds — see
    :func:`repro.core.ota.stream_fold_block`) and emits only O(block)
    per-agent scalars (returns, probe norms) as scan outputs.  Peak memory
    is therefore O(agent_blocks × d) in the fleet size.  Sampling keys and
    channel gains are indexed by absolute agent index — the same
    ``split(key_samp, N)`` / ``sample_gains(key_h, N)`` streams as the
    unblocked round — so the emitted history is bitwise-invariant to the
    choice of block size.
    """
    from repro.rl.sampler import discounted_return

    grad_fn = _estimator_grad(cfg)
    hetero = isinstance(env, HeterogeneousEnv)
    if hetero:
        check_agent_count(env, cfg.n_agents)
    n_blocks, block, pad = ota.blocked_layout(cfg.n_agents, agent_blocks)
    noisy = ota_cfg is not None
    spec = ota._make_spec(ota_cfg, None, False, ota_backend)
    pallas = not spec.exact and spec.resolved_backend() == "pallas"
    wire_dt = ota._wire_dtype(ota_cfg) if pallas else None
    want_norms = telemetry is not None and (
        telemetry.grad_norms or telemetry.dispersion)

    def round_fn(theta: PyTree, key: jax.Array):
        key_samp, key_chan = jax.random.split(key)
        agent_keys = jax.random.split(key_samp, cfg.n_agents)
        lane_stacks = dict(env.params) if hetero else {}
        xs = {
            "keys": ota.block_view(
                ota.pad_agent_axis(agent_keys, pad), n_blocks, block),
            "stacks": ota.block_view(
                ota.pad_agent_axis(lane_stacks, pad), n_blocks, block),
            "valid": ota.block_valid_mask(cfg.n_agents, n_blocks, block),
        }
        if noisy:
            key_h, key_n = jax.random.split(key_chan)
            h = ota.sample_gains(ota_cfg, key_h, cfg.n_agents)
            hp = jnp.concatenate([h, jnp.zeros((pad,), h.dtype)]) \
                if pad else h
            xs["gains"] = hp.reshape(n_blocks, block)

        def agent_grad(k, lane_params):
            e = env.lane(lane_params) if hetero else env
            traj = rollout_batch(e, policy, theta, k, cfg.horizon, cfg.batch_m)
            return grad_fn(policy, theta, traj, cfg.gamma), traj

        def block_body(carry, x):
            grads_b, trajs_b = jax.vmap(agent_grad)(x["keys"], x["stacks"])
            gsum = ota.stream_fold_block(carry[0], grads_b, None, x["valid"])
            ys = {"returns": discounted_return(trajs_b.losses, cfg.gamma)}
            if want_norms:
                ys["norms_sq"] = sum(
                    _probes._leaf_norms(g, block)
                    for g in jax.tree.leaves(grads_b))
            if not noisy:
                return (gsum,), ys
            gb = jax.tree.map(lambda a: a.astype(jnp.float32), grads_b) \
                if pallas else grads_b
            v = ota.stream_fold_block(carry[1], gb, x["gains"], x["valid"],
                                      wire_dtype=wire_dt)
            return (gsum, v), ys

        carry0 = (jax.tree.map(jnp.zeros_like, theta),)
        if noisy:
            vdt = (lambda p: jnp.float32) if pallas else (lambda p: p.dtype)
            carry0 += (jax.tree.map(
                lambda p: jnp.zeros(p.shape, vdt(p)), theta),)
        carry, ys = jax.lax.scan(block_body, carry0, xs)

        # per-agent scalars come back (n_blocks, block, ...); restore the
        # absolute agent order and drop the phantom tail before reducing
        # with the exact ops the unblocked round uses.
        returns = ys["returns"].reshape(
            (n_blocks * block,) + ys["returns"].shape[2:])[:cfg.n_agents]
        reward = -jnp.mean(returns)
        mean_grad = jax.tree.map(lambda s: s / cfg.n_agents, carry[0])
        grad_sq = tree_global_norm_sq(mean_grad)

        if not noisy:
            gain_mean = jnp.ones(())
            theta_next = jax.tree.map(
                lambda p, u: p - cfg.alpha * u, theta, mean_grad)
        else:
            theta_next = ota.stream_finalize_apply(
                ota_cfg, key_n, carry[1], theta, cfg.alpha, cfg.n_agents,
                backend="pallas" if pallas else "xla")
            gain_mean = jnp.mean(h)

        if telemetry is None:
            return theta_next, (reward, grad_sq, gain_mean)

        if not noisy:
            update_norm = jnp.sqrt(grad_sq)
        else:
            update_norm = jnp.sqrt(tree_global_norm_sq(jax.tree.map(
                jnp.subtract, theta, theta_next))) / cfg.alpha
        norms_sq = ys["norms_sq"].reshape(-1)[:cfg.n_agents] \
            if want_norms else None
        probes = _probes.streamed_round_probes(
            telemetry, v=carry[1] if noisy else None, norms_sq=norms_sq,
            ota_cfg=ota_cfg, n_agents=cfg.n_agents,
            param_dim=sum(int(p.size) for p in jax.tree.leaves(theta)),
            gain_mean=gain_mean, update_norm=update_norm)
        return theta_next, (reward, grad_sq, gain_mean, probes)

    part, stale_cfg = participation, staleness
    if part is None:
        return round_fn

    def service_round(state: ServiceState, key: jax.Array):
        # the mask, replay weights and every normaliser scalar derive from
        # (N,) vectors computed BEFORE the block scan — identical across
        # block sizes, so the streamed service round inherits the blocked
        # round's bitwise block-invariance.
        theta = state.theta
        key_samp, key_chan = jax.random.split(key)
        agent_keys = jax.random.split(key_samp, cfg.n_agents)
        lane_stacks = dict(env.params) if hetero else {}
        ids = jnp.arange(cfg.n_agents, dtype=jnp.int32)
        mask = svc_participation.round_mask(
            part, state.part_key, state.sched_key, state.round_idx, ids,
            cfg.n_agents)
        mf = mask.astype(jnp.float32)
        count_p = jnp.sum(mf)

        if stale_cfg is not None:
            rw = svc_staleness.replay_weights(stale_cfg, mask, state.stale.age)
            w_replay, _, stale_age = svc_staleness.stats(
                stale_cfg, mask, state.stale.age)
        else:
            rw = None
            w_replay = jnp.zeros((), jnp.float32)
            stale_age = None
        w_real = count_p + w_replay
        w_norm = w_real if part.debias == "realized" else jnp.asarray(
            svc_participation.expected_count(part, cfg.n_agents), jnp.float32)
        inv_w = svc_participation.safe_inv(w_norm)

        def _pad_row(a):
            return jnp.concatenate([a, jnp.zeros((pad,), a.dtype)]) \
                if pad else a

        xs = {
            "keys": ota.block_view(
                ota.pad_agent_axis(agent_keys, pad), n_blocks, block),
            "stacks": ota.block_view(
                ota.pad_agent_axis(lane_stacks, pad), n_blocks, block),
            "valid": ota.block_valid_mask(cfg.n_agents, n_blocks, block),
            "pmask": _pad_row(mf).reshape(n_blocks, block),
        }
        if noisy:
            key_h, key_n = jax.random.split(key_chan)
            h = ota.sample_gains(ota_cfg, key_h, cfg.n_agents)
            hm = jnp.where(mask, h, jnp.zeros_like(h))
            xs["gains"] = _pad_row(hm).reshape(n_blocks, block)
        if stale_cfg is not None:
            xs["stale"] = ota.block_view(
                ota.pad_agent_axis(state.stale.grads, pad), n_blocks, block)
            xs["rw"] = _pad_row(rw).reshape(n_blocks, block)

        def agent_grad(k, lane_params):
            e = env.lane(lane_params) if hetero else env
            traj = rollout_batch(e, policy, theta, k, cfg.horizon, cfg.batch_m)
            return grad_fn(policy, theta, traj, cfg.gamma), traj

        def block_body(carry, x):
            grads_b, trajs_b = jax.vmap(agent_grad)(x["keys"], x["stacks"])
            out = {"gsum": ota.stream_fold_block(
                carry["gsum"], grads_b, x["pmask"], x["valid"])}
            ys = {"returns": discounted_return(trajs_b.losses, cfg.gamma)}
            if want_norms:
                ys["norms_sq"] = sum(
                    _probes._leaf_norms(g, block)
                    for g in jax.tree.leaves(grads_b))
            if stale_cfg is not None:
                out["ssum"] = ota.stream_fold_block(
                    carry["ssum"], x["stale"], x["rw"], x["valid"])
                pm = x["pmask"] > 0
                ys["stale_new"] = jax.tree.map(
                    lambda fresh, old: jnp.where(
                        pm.reshape((-1,) + (1,) * (fresh.ndim - 1)),
                        fresh, old),
                    grads_b, x["stale"])
            if noisy:
                gb = jax.tree.map(
                    lambda a: a.astype(jnp.float32), grads_b) \
                    if pallas else grads_b
                out["v"] = ota.stream_fold_block(
                    carry["v"], gb, x["gains"], x["valid"],
                    wire_dtype=wire_dt)
            return out, ys

        carry0 = {"gsum": jax.tree.map(jnp.zeros_like, theta)}
        if stale_cfg is not None:
            carry0["ssum"] = jax.tree.map(jnp.zeros_like, theta)
        if noisy:
            vdt = (lambda p: jnp.float32) if pallas else (lambda p: p.dtype)
            carry0["v"] = jax.tree.map(
                lambda p: jnp.zeros(p.shape, vdt(p)), theta)
        carry, ys = jax.lax.scan(block_body, carry0, xs)

        gsum = carry["gsum"]
        if stale_cfg is not None:
            gsum = jax.tree.map(jnp.add, gsum, carry["ssum"])
        mean_grad = jax.tree.map(lambda s: s * inv_w, gsum)
        grad_sq = tree_global_norm_sq(mean_grad)

        if not noisy:
            gain_mean = jnp.ones(())
            update = mean_grad
        else:
            update = ota.stream_finalize(
                ota_cfg, key_n, carry["v"], cfg.n_agents,
                backend="pallas" if pallas else "xla", n_eff=w_norm)
            if stale_cfg is not None:
                update = jax.tree.map(
                    lambda u, s: u + s * inv_w, update, carry["ssum"])
            gain_mean = jnp.sum(hm) * svc_participation.safe_inv(count_p)
        theta_next = jax.tree.map(
            lambda p, u: p - cfg.alpha * u, theta, update)

        returns = ys["returns"].reshape(
            (n_blocks * block,) + ys["returns"].shape[2:])[:cfg.n_agents]
        reward = -jnp.sum(jnp.where(mask[:, None], returns, 0.0)) \
            * svc_participation.safe_inv(count_p) / cfg.batch_m

        if stale_cfg is not None:
            buf = jax.tree.map(
                lambda s: s.reshape(
                    (n_blocks * block,) + s.shape[2:])[:cfg.n_agents],
                ys["stale_new"])
            age = jnp.where(mask, jnp.int32(1),
                            jnp.minimum(state.stale.age + 1,
                                        svc_staleness.AGE_NEVER))
            stale_next = svc_staleness.StaleState(grads=buf, age=age)
        else:
            stale_next = None
        state_next = state._replace(theta=theta_next,
                                    round_idx=state.round_idx + 1,
                                    stale=stale_next)
        if telemetry is None:
            return state_next, (reward, grad_sq, gain_mean)

        norms_sq = jnp.where(
            mask, ys["norms_sq"].reshape(-1)[:cfg.n_agents], 0.0) \
            if want_norms else None
        probes = _probes.streamed_round_probes(
            telemetry, v=carry["v"] if noisy else None, norms_sq=norms_sq,
            ota_cfg=ota_cfg, n_agents=cfg.n_agents,
            param_dim=sum(int(p.size) for p in jax.tree.leaves(theta)),
            gain_mean=gain_mean,
            update_norm=jnp.sqrt(tree_global_norm_sq(update)))
        probes = _probes.participation_probes(
            telemetry, probes, rate_realized=count_p / cfg.n_agents,
            rate_expected=svc_participation.expected_count(
                part, cfg.n_agents) / cfg.n_agents,
            staleness_mean=stale_age)
        return state_next, (reward, grad_sq, gain_mean, probes)

    return service_round


def _make_agent_sharded_round_fn(
    env, policy, cfg: FedPGConfig, ota_cfg: Optional[OTAConfig],
    mesh, axis_name: str, ota_backend: str = "auto",
    telemetry: Optional[TelemetryConfig] = None,
    agent_blocks: Optional[int] = None,
    participation: Optional[ParticipationConfig] = None,
    staleness: Optional[StalenessConfig] = None,
):
    """The agent axis laid across ``mesh[axis_name]`` via shard_map.

    Each shard vmaps over its ``n_local = n_agents / axis_size`` agents;
    per-agent env stacks and sampling keys enter with ``P(axis_name)`` specs
    so shard_map hands every shard exactly its fleet slice.  The uplink is
    the psum form (``ota.aggregate`` with ``local_stack=True``); metrics
    psum local partial sums, so every shard ends the round with identical
    (replicated) theta and metrics.

    With ``agent_blocks`` each shard consumes its local slice as a blocked
    scan (strict sequential folds, O(agent_blocks × d) peak memory per
    shard) and the partial sums psum across the mesh.  A non-dividing
    ``n_agents`` is then handled by padding the global stacks to
    ``ceil(N / n_shards) * n_shards`` with masked phantom agents — their
    gains and gradients fold exact zeros and every normaliser (reward,
    gain mean, debias) uses the true agent count.  Without ``agent_blocks``
    a non-dividing fleet still raises.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.rl.sampler import discounted_return

    grad_fn = _estimator_grad(cfg)
    hetero = isinstance(env, HeterogeneousEnv)
    if hetero:
        check_agent_count(env, cfg.n_agents)
    if axis_name not in mesh.shape:
        raise ValueError(
            f"agent mesh has no axis {axis_name!r}; axes are "
            f"{tuple(mesh.axis_names)}")
    part, stale_cfg = participation, staleness
    if stale_cfg is not None:
        raise ValueError(
            "staleness replay does not compose with agent_mesh: the stale "
            "buffer is absolute-agent-indexed carried state and the mesh "
            "round carries only replicated theta (use agent_blocks without "
            "a mesh, or staleness=None)")
    if part is not None and agent_blocks is None:
        raise ValueError(
            "participation under agent_mesh needs agent_blocks: the "
            "service round reuses the streamed shard path's phantom-agent "
            "masking (any block size works, e.g. agent_blocks=n_local)")
    n_shards = mesh.shape[axis_name]
    if cfg.n_agents % n_shards != 0 and agent_blocks is None:
        raise ValueError(
            f"n_agents={cfg.n_agents} does not divide across the "
            f"{axis_name!r} mesh axis of size {n_shards}; pass agent_blocks "
            "to run with a masked phantom-agent tail instead")
    n_local = -(-cfg.n_agents // n_shards)
    pad_total = n_local * n_shards - cfg.n_agents

    def local_round(theta, agent_keys, lane_stacks, key_chan):
        # agent_keys/lane_stacks are this shard's (n_local,)-leading slices
        def agent_grad(k, lane_params):
            e = env.lane(lane_params) if hetero else env
            traj = rollout_batch(e, policy, theta, k, cfg.horizon, cfg.batch_m)
            return grad_fn(policy, theta, traj, cfg.gamma), traj

        grads, trajs = jax.vmap(agent_grad)(agent_keys, lane_stacks)
        mean_grad = ota.aggregate(
            grads, None, axis=(axis_name,), n_agents=cfg.n_agents,
            local_stack=True)[0]

        if ota_cfg is None:
            update = mean_grad
            gain_mean = jnp.ones(())
        else:
            update, h = ota.aggregate(
                grads, ota_cfg, key=key_chan, axis=(axis_name,),
                n_agents=cfg.n_agents, local_stack=True,
                backend=ota_backend)
            gain_mean = jax.lax.psum(jnp.sum(h), axis_name) / cfg.n_agents
        theta_next = jax.tree.map(lambda p, u: p - cfg.alpha * u, theta, update)

        # metrics: psum of local partial sums == the global means
        r_local = -jnp.sum(discounted_return(trajs.losses, cfg.gamma))
        reward = jax.lax.psum(r_local, axis_name) / (cfg.n_agents * cfg.batch_m)
        grad_sq = tree_global_norm_sq(mean_grad)
        if telemetry is None:
            return theta_next, (reward, grad_sq, gain_mean)

        # telemetry probes: psum/pmax reductions, replicated outputs
        n_local = jax.tree.leaves(grads)[0].shape[0]
        local_gains = h if ota_cfg is not None else jnp.ones((n_local,))
        probes = _probes.sharded_round_probes(
            telemetry, local_grads=grads, local_gains=local_gains,
            ota_cfg=ota_cfg, n_agents=cfg.n_agents, axis_name=axis_name,
            gain_mean=gain_mean,
            update_norm=jnp.sqrt(tree_global_norm_sq(update)))
        return theta_next, (reward, grad_sq, gain_mean, probes)

    if agent_blocks is not None:
        nb, blk, bpad = ota.blocked_layout(n_local, agent_blocks)
    want_norms = telemetry is not None and (
        telemetry.grad_norms or telemetry.dispersion)

    def local_round_streamed(theta, agent_keys, lane_stacks, key_chan):
        # agent_keys/lane_stacks are this shard's (n_local,)-leading slices
        # of the globally padded stacks; rows whose global agent index is
        # >= n_agents are masked phantoms.
        def agent_grad(k, lane_params):
            e = env.lane(lane_params) if hetero else env
            traj = rollout_batch(e, policy, theta, k, cfg.horizon, cfg.batch_m)
            return grad_fn(policy, theta, traj, cfg.gamma), traj

        _, valid_local = ota._sharded_stream_meta(
            (axis_name,), n_local, cfg.n_agents)
        if ota_cfg is not None:
            key_h, key_n = jax.random.split(key_chan)
            h, valid_local = ota.sharded_stream_gains(
                ota_cfg, key_h, (axis_name,), n_local, cfg.n_agents)

        vp = jnp.concatenate([valid_local, jnp.zeros((bpad,), bool)]) \
            if bpad else valid_local
        xs = {
            "keys": ota.block_view(
                ota.pad_agent_axis(agent_keys, bpad), nb, blk),
            "stacks": ota.block_view(
                ota.pad_agent_axis(lane_stacks, bpad), nb, blk),
            "valid": vp.reshape(nb, blk),
        }
        if ota_cfg is not None:
            hp = jnp.concatenate([h, jnp.zeros((bpad,), h.dtype)]) \
                if bpad else h
            xs["gains"] = hp.reshape(nb, blk)

        def block_body(carry, x):
            grads_b, trajs_b = jax.vmap(agent_grad)(x["keys"], x["stacks"])
            gsum = ota.stream_fold_block(carry[0], grads_b, None, x["valid"])
            ys = {"returns": discounted_return(trajs_b.losses, cfg.gamma)}
            if want_norms:
                ys["norms_sq"] = sum(
                    _probes._leaf_norms(g, blk)
                    for g in jax.tree.leaves(grads_b))
            if ota_cfg is None:
                return (gsum,), ys
            v = ota.stream_fold_block(carry[1], grads_b, x["gains"],
                                      x["valid"])
            return (gsum, v), ys

        carry0 = (jax.tree.map(jnp.zeros_like, theta),)
        if ota_cfg is not None:
            carry0 += (jax.tree.map(jnp.zeros_like, theta),)
        carry, ys = jax.lax.scan(block_body, carry0, xs)

        mean_grad = jax.tree.map(
            lambda s: jax.lax.psum(s, axis_name) / cfg.n_agents, carry[0])
        v_global = None
        if ota_cfg is None:
            update = mean_grad
            gain_mean = jnp.ones(())
        else:
            v_global = jax.tree.map(
                lambda s: jax.lax.psum(s, axis_name), carry[1])
            update = ota.stream_finalize(ota_cfg, key_n, v_global,
                                         cfg.n_agents)
            gain_mean = jax.lax.psum(jnp.sum(h), axis_name) / cfg.n_agents
        theta_next = jax.tree.map(
            lambda p, u: p - cfg.alpha * u, theta, update)

        # metrics: restore absolute local order, mask phantoms, psum
        returns = ys["returns"].reshape(
            (nb * blk,) + ys["returns"].shape[2:])[:n_local]
        r_local = -jnp.sum(jnp.where(valid_local[:, None], returns, 0.0))
        reward = jax.lax.psum(r_local, axis_name) / (cfg.n_agents * cfg.batch_m)
        grad_sq = tree_global_norm_sq(mean_grad)
        if telemetry is None:
            return theta_next, (reward, grad_sq, gain_mean)

        norms_sq = ys["norms_sq"].reshape(-1)[:n_local] if want_norms \
            else None
        probes = _probes.sharded_streamed_round_probes(
            telemetry, v=v_global, local_norms_sq=norms_sq,
            valid_local=valid_local, ota_cfg=ota_cfg, n_agents=cfg.n_agents,
            axis_name=axis_name,
            param_dim=sum(int(p.size) for p in jax.tree.leaves(theta)),
            gain_mean=gain_mean,
            update_norm=jnp.sqrt(tree_global_norm_sq(update)))
        return theta_next, (reward, grad_sq, gain_mean, probes)

    def local_round_streamed_svc(theta, agent_keys, lane_stacks, key_chan,
                                 round_idx, part_key, sched_key):
        # the streamed shard body with a participation mask: each shard
        # derives its rows of the GLOBAL mask from absolute agent indices
        # (the same counter-PRNG scheme as ``sharded_stream_gains``), so
        # the realised mask is invariant to the mesh layout and blocking.
        def agent_grad(k, lane_params):
            e = env.lane(lane_params) if hetero else env
            traj = rollout_batch(e, policy, theta, k, cfg.horizon, cfg.batch_m)
            return grad_fn(policy, theta, traj, cfg.gamma), traj

        _, valid_local = ota._sharded_stream_meta(
            (axis_name,), n_local, cfg.n_agents)
        idx, _ = ota._flat_axis_index((axis_name,))
        gids = idx * n_local + jnp.arange(n_local, dtype=jnp.int32)
        mask_local = jnp.logical_and(
            svc_participation.round_mask(part, part_key, sched_key,
                                         round_idx, gids, cfg.n_agents),
            valid_local)
        mf_local = mask_local.astype(jnp.float32)
        count_p = jax.lax.psum(jnp.sum(mf_local), axis_name)
        w_norm = count_p if part.debias == "realized" else jnp.asarray(
            svc_participation.expected_count(part, cfg.n_agents), jnp.float32)
        inv_w = svc_participation.safe_inv(w_norm)

        if ota_cfg is not None:
            key_h, key_n = jax.random.split(key_chan)
            h, valid_local = ota.sharded_stream_gains(
                ota_cfg, key_h, (axis_name,), n_local, cfg.n_agents)
            hm = jnp.where(mask_local, h, jnp.zeros_like(h))

        def _pad_row(a):
            return jnp.concatenate(
                [a, jnp.zeros((bpad,), a.dtype)]) if bpad else a

        vp = _pad_row(valid_local)
        xs = {
            "keys": ota.block_view(
                ota.pad_agent_axis(agent_keys, bpad), nb, blk),
            "stacks": ota.block_view(
                ota.pad_agent_axis(lane_stacks, bpad), nb, blk),
            "valid": vp.reshape(nb, blk),
            "pmask": _pad_row(mf_local).reshape(nb, blk),
        }
        if ota_cfg is not None:
            xs["gains"] = _pad_row(hm).reshape(nb, blk)

        def block_body(carry, x):
            grads_b, trajs_b = jax.vmap(agent_grad)(x["keys"], x["stacks"])
            gsum = ota.stream_fold_block(carry[0], grads_b, x["pmask"],
                                         x["valid"])
            ys = {"returns": discounted_return(trajs_b.losses, cfg.gamma)}
            if want_norms:
                ys["norms_sq"] = sum(
                    _probes._leaf_norms(g, blk)
                    for g in jax.tree.leaves(grads_b))
            if ota_cfg is None:
                return (gsum,), ys
            v = ota.stream_fold_block(carry[1], grads_b, x["gains"],
                                      x["valid"])
            return (gsum, v), ys

        carry0 = (jax.tree.map(jnp.zeros_like, theta),)
        if ota_cfg is not None:
            carry0 += (jax.tree.map(jnp.zeros_like, theta),)
        carry, ys = jax.lax.scan(block_body, carry0, xs)

        mean_grad = jax.tree.map(
            lambda s: jax.lax.psum(s, axis_name) * inv_w, carry[0])
        v_global = None
        if ota_cfg is None:
            update = mean_grad
            gain_mean = jnp.ones(())
        else:
            v_global = jax.tree.map(
                lambda s: jax.lax.psum(s, axis_name), carry[1])
            update = ota.stream_finalize(ota_cfg, key_n, v_global,
                                         cfg.n_agents, n_eff=w_norm)
            gain_mean = jax.lax.psum(jnp.sum(hm), axis_name) \
                * svc_participation.safe_inv(count_p)
        theta_next = jax.tree.map(
            lambda p, u: p - cfg.alpha * u, theta, update)

        returns = ys["returns"].reshape(
            (nb * blk,) + ys["returns"].shape[2:])[:n_local]
        r_local = -jnp.sum(jnp.where(mask_local[:, None], returns, 0.0))
        reward = jax.lax.psum(r_local, axis_name) \
            * svc_participation.safe_inv(count_p) / cfg.batch_m
        grad_sq = tree_global_norm_sq(mean_grad)
        if telemetry is None:
            return theta_next, (reward, grad_sq, gain_mean)

        norms_sq = ys["norms_sq"].reshape(-1)[:n_local] if want_norms \
            else None
        probes = _probes.sharded_streamed_round_probes(
            telemetry, v=v_global, local_norms_sq=norms_sq,
            valid_local=mask_local, ota_cfg=ota_cfg, n_agents=cfg.n_agents,
            axis_name=axis_name,
            param_dim=sum(int(p.size) for p in jax.tree.leaves(theta)),
            gain_mean=gain_mean,
            update_norm=jnp.sqrt(tree_global_norm_sq(update)))
        probes = _probes.participation_probes(
            telemetry, probes, rate_realized=count_p / cfg.n_agents,
            rate_expected=svc_participation.expected_count(
                part, cfg.n_agents) / cfg.n_agents)
        return theta_next, (reward, grad_sq, gain_mean, probes)

    def round_fn(theta: PyTree, key: jax.Array):
        key_samp, key_chan = jax.random.split(key)
        agent_keys = jax.random.split(key_samp, cfg.n_agents)
        lane_stacks = dict(env.params) if hetero else {}
        if agent_blocks is not None and pad_total:
            agent_keys = ota.pad_agent_axis(agent_keys, pad_total)
            lane_stacks = ota.pad_agent_axis(lane_stacks, pad_total)
        stack_specs = jax.tree.map(lambda _: P(axis_name), lane_stacks)
        metric_specs = (P(), P(), P())
        if telemetry is not None:
            metric_specs += (RoundTelemetry(P(), P(), P(), P(), P()),)
        body = local_round_streamed if agent_blocks is not None \
            else local_round
        return shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axis_name), stack_specs, P()),
            out_specs=(P(), metric_specs),
            check_vma=False,
        )(theta, agent_keys, lane_stacks, key_chan)

    if part is None:
        return round_fn

    def service_round(state: ServiceState, key: jax.Array):
        theta = state.theta
        key_samp, key_chan = jax.random.split(key)
        agent_keys = jax.random.split(key_samp, cfg.n_agents)
        lane_stacks = dict(env.params) if hetero else {}
        if pad_total:
            agent_keys = ota.pad_agent_axis(agent_keys, pad_total)
            lane_stacks = ota.pad_agent_axis(lane_stacks, pad_total)
        stack_specs = jax.tree.map(lambda _: P(axis_name), lane_stacks)
        metric_specs = (P(), P(), P())
        if telemetry is not None:
            metric_specs += (RoundTelemetry(P(), P(), P(), P(), P())._replace(
                participation_rate=P(), participation_drift=P()),)
        theta_next, metrics = shard_map(
            local_round_streamed_svc, mesh=mesh,
            in_specs=(P(), P(axis_name), stack_specs, P(), P(), P(), P()),
            out_specs=(P(), metric_specs),
            check_vma=False,
        )(theta, agent_keys, lane_stacks, key_chan, state.round_idx,
          state.part_key, state.sched_key)
        state_next = state._replace(theta=theta_next,
                                    round_idx=state.round_idx + 1)
        return state_next, metrics

    return service_round


def run(
    env,
    policy,
    cfg: FedPGConfig,
    key: jax.Array,
    *,
    ota: Optional[OTAConfig] = None,
    theta0: Optional[PyTree] = None,
    agent_mesh=None,
    agent_axis: str = "agents",
    ota_backend: str = "auto",
    telemetry: Optional[TelemetryConfig] = None,
    agent_blocks: Optional[int] = None,
    participation: Optional[ParticipationConfig] = None,
    staleness: Optional[StalenessConfig] = None,
):
    """Run K rounds; returns (theta_K, History).

    ``ota=None`` is Algorithm 1 (exact aggregation); an ``OTAConfig`` is
    Algorithm 2 over the configured channel.  ``agent_mesh`` shards the
    agent axis across a device mesh (see :func:`make_round_fn`) — use
    ``repro.core.distribute.agent_mesh_for`` to build one.  ``ota_backend``
    routes the uplink ("xla" | "pallas" | "auto").  ``telemetry`` (active
    probes) fills ``History.telemetry`` with ``(K,)``-leaved round probes.
    ``agent_blocks`` streams the agent axis in blocked-scan chunks of that
    many agents — O(agent_blocks × d) peak memory, history bitwise-invariant
    to the block size (see :func:`make_round_fn`).  ``participation`` /
    ``staleness`` run the rounds as *service* rounds (partial agent
    participation, stale-gradient replay — see :mod:`repro.service`); a
    config that normalises away (full participation, ``max_age=0``) emits
    the byte-identical plain program.
    """
    part = svc_participation.normalize(participation, cfg.n_agents)
    stale_cfg = svc_staleness.normalize(staleness, part)
    round_fn = make_round_fn(env, policy, cfg, ota,
                             agent_mesh=agent_mesh, agent_axis=agent_axis,
                             ota_backend=ota_backend, telemetry=telemetry,
                             agent_blocks=agent_blocks,
                             participation=part, staleness=stale_cfg)
    if part is not None:
        key_init, key_scan, key_svc = jax.random.split(key, 3)
        theta = policy.init(key_init) if theta0 is None else theta0
        state0 = svc_participation.init_state(theta, key_svc, cfg.n_agents,
                                              stale_cfg)
        keys = jax.random.split(key_scan, cfg.n_rounds)
        state, metrics = jax.lax.scan(round_fn, state0, keys)
        theta = state.theta
    else:
        key_init, key_scan = jax.random.split(key)
        theta = policy.init(key_init) if theta0 is None else theta0

        def body(carry, key_k):
            theta = carry
            theta, metrics = round_fn(theta, key_k)
            return theta, metrics

        keys = jax.random.split(key_scan, cfg.n_rounds)
        theta, metrics = jax.lax.scan(body, theta, keys)
    if len(metrics) == 4:
        rewards, grad_sq, gain_mean, probes = metrics
        return theta, History(rewards=rewards, grad_sq=grad_sq,
                              gain_mean=gain_mean, telemetry=probes)
    rewards, grad_sq, gain_mean = metrics
    return theta, History(rewards=rewards, grad_sq=grad_sq, gain_mean=gain_mean)


# ---------------------------------------------------------------------------
# Compiled-callable cache.  ``jax.jit`` caches per function object, so
# wrapping a fresh lambda on every run_jit/monte_carlo call used to recompile
# the whole training program from scratch each time.  The jitted closures are
# instead cached on the (hashable) argument tuple; configs with traced or
# otherwise unhashable fields fall back to a fresh closure.
# ---------------------------------------------------------------------------

# Bounded: each entry pins its compiled executable (and the captured
# env/policy) alive, so an unbounded cache would leak across a long
# hand-rolled parameter grid that bypasses the sweep engine.
_CACHE_SIZE = 64


# NOTE: the cache keys must include EVERY program-shaping argument of
# `run` — a key that omits one silently returns a stale compiled program
# for the other value.  Keep these signatures in lockstep with `run`.

@functools.lru_cache(maxsize=_CACHE_SIZE)
def _compiled_run(env, policy, cfg: FedPGConfig, ota_cfg, backend: str,
                  telemetry=None, agent_mesh=None, agent_axis: str = "agents",
                  agent_blocks=None, participation=None, staleness=None):
    return jax.jit(
        lambda k: run(env, policy, cfg, k, ota=ota_cfg, ota_backend=backend,
                      telemetry=telemetry, agent_mesh=agent_mesh,
                      agent_axis=agent_axis, agent_blocks=agent_blocks,
                      participation=participation, staleness=staleness))


@functools.lru_cache(maxsize=_CACHE_SIZE)
def _compiled_monte_carlo(env, policy, cfg: FedPGConfig, ota_cfg,
                          n_runs: int, backend: str, telemetry=None,
                          agent_mesh=None, agent_axis: str = "agents",
                          agent_blocks=None, participation=None,
                          staleness=None):
    return jax.jit(jax.vmap(
        lambda k: run(env, policy, cfg, k, ota=ota_cfg,
                      ota_backend=backend, telemetry=telemetry,
                      agent_mesh=agent_mesh, agent_axis=agent_axis,
                      agent_blocks=agent_blocks,
                      participation=participation, staleness=staleness)[1]))


# every compiled-program cache in the package; other modules (e.g.
# event_triggered) register theirs so one reset call clears them all
_COMPILED_CACHES = [_compiled_run, _compiled_monte_carlo]


def register_compiled_cache(cache) -> None:
    _COMPILED_CACHES.append(cache)


def clear_compilation_cache() -> None:
    """Drop every cached compiled program (mainly for tests) — including
    caches other modules registered via ``register_compiled_cache``."""
    for cache in _COMPILED_CACHES:
        cache.cache_clear()


def _hashable(*objs) -> bool:
    try:
        hash(objs)
        return True
    except TypeError:
        return False


def run_jit(env, policy, cfg: FedPGConfig, key, *, ota=None, theta0=None,
            ota_backend: str = "auto",
            telemetry: Optional[TelemetryConfig] = None,
            agent_mesh=None, agent_axis: str = "agents",
            agent_blocks: Optional[int] = None,
            participation: Optional[ParticipationConfig] = None,
            staleness: Optional[StalenessConfig] = None):
    """jit-compiled entry point (env/policy/cfgs are closure constants).

    Repeated calls with the same ``(env, policy, cfg, ota, ota_backend,
    telemetry, agent_mesh, agent_axis, agent_blocks, participation,
    staleness)`` reuse the compiled program (``theta0`` is a pytree and
    cannot key a cache, so passing one compiles fresh).  Caching needs
    every argument hashable: envs holding jax arrays (e.g. ``TabularMDP``)
    take the uncached path.  Participation/staleness configs are
    *normalised* before keying, so a full-participation config hits the
    same cache entry as ``None``.
    """
    participation = svc_participation.normalize(participation, cfg.n_agents)
    staleness = svc_staleness.normalize(staleness, participation)
    telemetry = _active_telemetry(telemetry, participation)
    if theta0 is None and _hashable(env, policy, cfg, ota, telemetry,
                                    agent_mesh, agent_axis, agent_blocks,
                                    participation, staleness):
        return _compiled_run(env, policy, cfg, ota, ota_backend, telemetry,
                             agent_mesh, agent_axis, agent_blocks,
                             participation, staleness)(key)
    fn = jax.jit(lambda k: run(env, policy, cfg, k, ota=ota, theta0=theta0,
                               ota_backend=ota_backend, telemetry=telemetry,
                               agent_mesh=agent_mesh, agent_axis=agent_axis,
                               agent_blocks=agent_blocks,
                               participation=participation,
                               staleness=staleness))
    return fn(key)


def avg_grad_sq(history: History) -> jax.Array:
    """The paper's reported quantity: (1/K) sum_k ||grad J(theta^k)||^2."""
    return jnp.mean(history.grad_sq)


def monte_carlo(
    env, policy, cfg: FedPGConfig, key: jax.Array, n_runs: int, *, ota=None,
    ota_backend: str = "auto",
    telemetry: Optional[TelemetryConfig] = None,
    agent_mesh=None, agent_axis: str = "agents",
    agent_blocks: Optional[int] = None,
    participation: Optional[ParticipationConfig] = None,
    staleness: Optional[StalenessConfig] = None,
):
    """n_runs independent repetitions (the paper uses 20): vmapped.

    Repeated calls with the same ``(env, policy, cfg, ota, n_runs,
    ota_backend, telemetry, agent_mesh, agent_axis, agent_blocks,
    participation, staleness)`` reuse the compiled program; only the PRNG
    keys change between calls.  Caching needs every argument hashable:
    envs holding jax arrays (e.g. ``TabularMDP``) take the uncached path.
    """
    participation = svc_participation.normalize(participation, cfg.n_agents)
    staleness = svc_staleness.normalize(staleness, participation)
    telemetry = _active_telemetry(telemetry, participation)
    keys = jax.random.split(key, n_runs)
    if _hashable(env, policy, cfg, ota, telemetry, agent_mesh, agent_axis,
                 agent_blocks, participation, staleness):
        return _compiled_monte_carlo(env, policy, cfg, ota, n_runs,
                                     ota_backend, telemetry, agent_mesh,
                                     agent_axis, agent_blocks,
                                     participation, staleness)(keys)
    fn = jax.jit(jax.vmap(
        lambda k: run(env, policy, cfg, k, ota=ota,
                      ota_backend=ota_backend, telemetry=telemetry,
                      agent_mesh=agent_mesh, agent_axis=agent_axis,
                      agent_blocks=agent_blocks,
                      participation=participation,
                      staleness=staleness)[1]))
    return fn(keys)
