"""Over-the-air aggregation (Eq. 6-7) — the paper's core primitive.

The physical channel computes ``v_k = sum_i h_{i,k} * g_i + n_k`` "for free"
by analog superposition; the server applies ``theta <- theta - alpha * v_k/N``.
On a TPU mesh the sum is a ``psum`` and the distortion/noise are explicit
tensor ops.

**Entry point:** :func:`aggregate` — one dispatcher over every mathematically
equivalent implementation form, described by an :class:`AggregateSpec`:

* form ``"stacked"``      — literal Algorithm 2 over per-agent gradient
  pytrees stacked on a leading N axis (the RL loops' vmapped workers).
* form ``"axis"``         — ``shard_map`` form: each data-shard scales its
  local gradient by its own gain and ``psum``s across the agent axes; the
  AWGN is generated identically on every shard from a shared key.
* form ``"axis_stacked"`` — the axis form for shards that each carry a
  *stack* of agents (the agent-mesh production path).
* ``exact=True``          — the Algorithm-1 baseline (ideal uplink) in any
  form: the plain mean.

Backends: ``"xla"`` executes the historical op chain (bit-identical to the
pre-dispatcher entry points); ``"pallas"`` routes the stacked form through
the fused kernel ``repro.kernels.ota_fused`` (gain matvec + counter-PRNG
AWGN + debias in ONE pass over the flattened parameter vector, bf16 wire
format via ``OTAConfig.wire_dtype``); ``"auto"`` picks pallas on TPU and
xla elsewhere.  The pallas backend draws its AWGN from the kernel's
counter PRNG — same distribution, different stream than the xla
threefry draw, so histories agree in distribution, not bitwise.

:func:`aggregate_apply` additionally fuses the server SGD update
``theta' = theta - alpha * u`` into the same kernel pass (the fedpg round
loop's uplink tail).

The legacy entry points (``aggregate_stacked``, ``exact_aggregate``,
``psum_aggregate``, ``psum_aggregate_stacked``) remain as thin deprecated
wrappers; new in-repo code must call :func:`aggregate` (enforced by
``tools/lint_aggregation_api.py`` in CI).

A third equivalent form needs no aggregation call at all: channel-weighted
loss — ``sample_gains`` + ``example_weights`` fold the gain into the
per-example loss weight *before* autodiff, so a vanilla pjit gradient
already equals ``sum_i h_i grad_i / N``; ``add_awgn`` then applies the
server noise once.  Zero extra collectives vs. plain DP.

All forms return the *update direction* ``u_k = v_k / N`` so that
``theta^{k+1} = theta^k - alpha * u_k`` matches Eq. (7) exactly.  Setting
``debias=True`` additionally divides by ``m_h`` which makes the estimator
unbiased for ``grad J`` (the quantity the analysis controls, Lemma 3); the
paper's faithful update uses ``debias=False``.
"""
from __future__ import annotations

import math
import warnings
from dataclasses import dataclass, replace
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.channel import Channel, IdealChannel
from repro.core.power_control import PowerPolicy, effective_moments
from repro.utils.tree import tree_normal_like

PyTree = Any
Scalar = Union[float, jax.Array]  # python literal, or traced in a sweep lane


def _noise_enabled(sigma: Scalar) -> bool:
    """Whether to emit the AWGN ops.  Python literals keep the exact
    pre-existing behaviour (skip when 0); arrays/tracers always emit them
    (a runtime sigma of 0 then adds exact zeros)."""
    if isinstance(sigma, (int, float)):
        return sigma > 0.0
    return True


@dataclass(frozen=True)
class OTAConfig:
    """Static configuration of the over-the-air uplink.

    ``noise_sigma`` may be a traced scalar (the sweep engine batches noise
    levels); ``power_control`` optionally shapes the transmit power so the
    effective gain becomes ``h = c * p(c)`` — with ``debias=True`` the
    update is then divided by the *effective* mean ``E[c p(c)]`` (see
    ``norm_const_for``), keeping the estimator unbiased under power
    control; ``update_scale`` overrides the full server normalisation
    ``1 / (N * norm_const)`` — the sweep engine precomputes it in float64
    per scenario so that batched lanes multiply by exactly the constant the
    unbatched program would have folded in.  ``wire_dtype`` narrows the
    uplink payload on the pallas backend (``"bfloat16"`` casts the stacked
    gradients before the fused gain matvec; compute and the parameter
    master copy stay float32); the default ``""`` keeps the native dtype.
    """

    channel: Channel
    noise_sigma: Scalar = 0.0  # sigma of the AWGN on the *sum* (Eq. 6)
    debias: bool = False       # divide by m_h (unbiased grad estimate)
    power_control: Optional[PowerPolicy] = None
    update_scale: Optional[Scalar] = None
    wire_dtype: str = ""       # "" (native) | "bfloat16" — pallas wire format

    def __post_init__(self):
        # Fail at config-build time, not rounds later: a debiased update
        # divides by m_h, and a NaN mean (a ControlledChannel whose moments
        # were never estimated) would silently corrupt every update.
        if self.debias and self.update_scale is None:
            m = self.channel.mean
            if isinstance(m, (int, float)) and not math.isfinite(m):
                raise ValueError(
                    f"debias=True needs a finite channel mean, got m_h={m!r}; "
                    "build power-controlled channels with "
                    "make_controlled_channel so their effective moments are "
                    "estimated"
                )

    @property
    def norm_const(self) -> Scalar:
        """The raw-channel debias normaliser m_h (no power control folded
        in); the aggregation forms use :meth:`norm_const_for`, which
        accounts for ``power_control``."""
        if not self.debias:
            return 1.0
        m = self.channel.mean
        if isinstance(m, (int, float)) and not math.isfinite(m):
            raise ValueError(
                f"non-finite debias normaliser m_h={m!r}; build "
                "power-controlled channels with make_controlled_channel"
            )
        return m

    def norm_const_for(self, n_agents: Optional[int] = None) -> Scalar:
        """The debias normaliser the aggregation forms divide by: the
        *effective* gain mean E[c p(c)] when ``power_control`` is set
        (closed form or cached Monte Carlo — identical to what
        ``Scenario.ota_config`` folds into ``update_scale``), the channel
        mean otherwise.  ``n_agents`` is needed by per-agent policies."""
        if not self.debias or self.power_control is None:
            return self.norm_const
        try:
            return effective_moments(self.channel, self.power_control,
                                     n_agents=n_agents)[0]
        except TypeError as e:  # traced/unhashable channel or policy params
            raise ValueError(
                "debias needs hashable channel and power-control parameters "
                "to derive the effective mean; traced configs must carry an "
                "explicit update_scale (the sweep engine packs one per lane)"
            ) from e

    def ideal(self) -> "OTAConfig":
        """The matching noiseless/distortionless config (Algorithm 1)."""
        return replace(self, channel=IdealChannel(), noise_sigma=0.0,
                       power_control=None, update_scale=None)


# ---------------------------------------------------------------------------
# The unified dispatcher.
# ---------------------------------------------------------------------------

_BACKENDS = ("auto", "xla", "pallas")
_FORMS = ("stacked", "axis", "axis_stacked")


@dataclass(frozen=True)
class AggregateSpec:
    """Fully resolved description of one aggregation call.

    ``form``    — ``"stacked"`` (leading-N pytree), ``"axis"`` (one agent
                  per shard inside shard_map), ``"axis_stacked"`` (a local
                  agent stack per shard inside shard_map).
    ``exact``   — ideal Algorithm-1 uplink (plain mean; no channel/noise).
    ``backend`` — ``"xla"`` | ``"pallas"`` | ``"auto"``.  The pallas fused
                  kernel implements the stacked form; axis forms always
                  lower to the xla psum chain (``"auto"`` resolves there,
                  an explicit ``"pallas"`` raises).
    """

    form: str = "stacked"
    exact: bool = False
    backend: str = "auto"

    def __post_init__(self):
        if self.form not in _FORMS:
            raise ValueError(f"unknown form {self.form!r}; one of {_FORMS}")
        if self.backend not in _BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; one of {_BACKENDS}")

    def resolved_backend(self) -> str:
        """The concrete backend this spec executes on, on this process."""
        if self.exact:
            return "xla"
        if self.backend == "auto":
            if self.form == "stacked" and jax.default_backend() == "tpu":
                return "pallas"
            return "xla"
        if self.backend == "pallas" and self.form != "stacked":
            raise ValueError(
                "backend='pallas' implements the stacked form only; axis "
                "forms run the psum chain (use backend='auto' or 'xla')")
        return self.backend


def _make_spec(cfg: Optional[OTAConfig], axis, local_stack: bool,
               backend: str) -> AggregateSpec:
    form = "stacked" if axis is None else (
        "axis_stacked" if local_stack else "axis")
    return AggregateSpec(form=form, exact=cfg is None, backend=backend)


def aggregate(
    grads: PyTree,
    cfg: Optional[OTAConfig],
    *,
    key: Optional[jax.Array] = None,
    axis: Optional[Sequence[str]] = None,
    n_agents: Optional[int] = None,
    backend: str = "auto",
    local_stack: bool = False,
    gains: Optional[jax.Array] = None,
    spec: Optional[AggregateSpec] = None,
    agent_blocks: Optional[int] = None,
) -> Tuple[PyTree, jax.Array]:
    """OTA-aggregate ``grads`` under ``cfg``; returns ``(u_k, h)``.

    ``cfg=None`` is the exact Algorithm-1 uplink (ideal mean; ``h == 1``).
    ``axis=None`` selects the stacked form (leaves carry a leading N axis);
    an axis-name tuple selects the shard_map/psum forms, ``local_stack=True``
    when each shard carries a stack of agents.  ``key`` is required for
    noisy forms; ``n_agents`` is the static global agent count when the
    caller knows it (needed by per-agent power policies).
    ``backend``/``spec`` pick the implementation — see
    :class:`AggregateSpec`.  ``gains`` overrides the channel draw
    (stacked form only, for equivalence tests).

    ``agent_blocks`` selects the *streaming* blocked-scan evaluation of the
    agent sum: the agent axis is consumed in ``lax.scan`` chunks of that
    many agents, each chunk folded into the running channel superposition
    by a strict sequential per-agent fold, with one AWGN draw + debias at
    the end.  The PRNG streams (gain draw, noise key / counter seed) are
    identical to the unblocked form, and the result is bitwise-invariant to
    the choice of block size — any partition of the agent axis, including a
    non-dividing one (the tail block is masked phantom agents), produces
    the identical update.  Relative to ``agent_blocks=None`` the only
    difference is the floating-point association of the cross-agent sum
    (XLA's batched reduce vs. the sequential fold), a last-mantissa-bit
    reassociation.  Needs an agent stack: the ``stacked`` and
    ``axis_stacked`` forms (in the latter, rows whose global agent index is
    ``>= n_agents`` are treated as phantom padding).  See
    ``fedpg.make_round_fn(agent_blocks=...)`` for the form that actually
    *produces* the gradients blockwise, which is where the O(B×d) peak
    memory comes from.

    ``h`` is the sampled gain realisation: shape ``(N,)`` for the stacked
    form, the local shard's gains for the axis forms (phantom entries
    zeroed under ``agent_blocks`` padding), ``1.0`` when exact.
    """
    sp = spec if spec is not None else _make_spec(cfg, axis, local_stack,
                                                  backend)
    if sp.form != "stacked" and axis is None:
        raise ValueError(f"form {sp.form!r} needs an axis-name tuple")
    if agent_blocks is not None and sp.form == "axis":
        raise ValueError(
            "agent_blocks streams an agent *stack*; the one-agent-per-shard "
            "'axis' form has nothing to block (use local_stack=True)")

    if sp.exact:
        if sp.form == "stacked":
            if agent_blocks is not None:
                return _exact_mean_streamed(grads, agent_blocks), jnp.ones(())
            return _exact_mean(grads), jnp.ones(())
        if sp.form == "axis":
            return jax.lax.pmean(grads, tuple(axis)), jnp.ones(())
        if agent_blocks is not None:
            return _exact_mean_axis_stacked_streamed(
                grads, tuple(axis), n_agents, agent_blocks), jnp.ones(())
        return _exact_mean_axis_stacked(grads, tuple(axis), n_agents), \
            jnp.ones(())

    if cfg is None:
        raise ValueError("noisy spec needs an OTAConfig")
    if key is None:
        raise ValueError("noisy aggregation needs a PRNG key")

    be = sp.resolved_backend()
    if sp.form == "stacked":
        if agent_blocks is not None:
            return _aggregate_stacked_streamed(
                cfg, key, grads, agent_blocks, gains=gains, backend=be)
        if be == "pallas":
            return _aggregate_stacked_pallas(cfg, key, grads, gains=gains)
        return _aggregate_stacked_xla(cfg, key, grads, gains=gains)
    if sp.form == "axis":
        u, h = _psum_axis(cfg, key, grads, tuple(axis), n_agents=n_agents)
        return u, h
    if agent_blocks is not None:
        return _psum_axis_stacked_streamed(cfg, key, grads, tuple(axis),
                                           n_agents=n_agents,
                                           agent_blocks=agent_blocks)
    return _psum_axis_stacked(cfg, key, grads, tuple(axis),
                              n_agents=n_agents)


def aggregate_apply(
    grads: PyTree,
    cfg: Optional[OTAConfig],
    params: PyTree,
    *,
    key: Optional[jax.Array] = None,
    alpha: Scalar,
    backend: str = "auto",
    gains: Optional[jax.Array] = None,
    agent_blocks: Optional[int] = None,
) -> Tuple[PyTree, jax.Array]:
    """Aggregate + server SGD step: ``theta' = theta - alpha * u_k``.

    Stacked form only (the fedpg round loop's uplink tail).  On the pallas
    backend the whole chain — gain matvec, AWGN, debias, parameter update —
    is ONE fused kernel pass (``ota_fused.fused_aggregate_sgd``); on xla it
    is the bit-exact historical two-step (aggregate, then tree-mapped
    update).  ``agent_blocks`` streams the agent axis in blocked-scan
    chunks (see :func:`aggregate`); on pallas the final noise + debias +
    SGD tail then still runs as one fused kernel pass over the accumulated
    superposition.  Returns ``(theta', h)``.
    """
    sp = _make_spec(cfg, None, False, backend)
    if agent_blocks is not None and not sp.exact \
            and sp.resolved_backend() == "pallas":
        return _aggregate_apply_streamed_pallas(
            cfg, key, grads, params, alpha, agent_blocks, gains=gains)
    if sp.exact or sp.resolved_backend() == "xla":
        u, h = aggregate(grads, cfg, key=key, gains=gains,
                         agent_blocks=agent_blocks,
                         spec=replace(sp, backend="xla"))
        return jax.tree.map(lambda p, x: p - alpha * x, params, u), h
    return _aggregate_apply_pallas(cfg, key, grads, params, alpha,
                                   gains=gains)


def uplink_jaxpr(cfg: Optional[OTAConfig], *, n_agents: int = 4,
                 dim: int = 8, apply: bool = False, alpha: Scalar = 1e-3,
                 backend: str = "xla", agent_blocks: Optional[int] = None):
    """Trace the stacked uplink for structural inspection.

    Returns the ClosedJaxpr of ``aggregate`` (or ``aggregate_apply`` with
    ``apply=True``) on a ``(n_agents, dim)`` gradient stack — no execution,
    no compile.  This is the hook ``repro.analyze.contracts``'s wire-dtype
    checker walks: the uplink may narrow floats *only* through the
    sanctioned ``OTAConfig.wire_dtype`` bf16 hop, so any other
    ``convert_element_type`` to a smaller float in this jaxpr is a
    precision bug.  ``agent_blocks`` traces the streaming blocked-scan
    form instead (the hook the stream-contract checker walks: the scan
    carry must stay O(block × d), independent of ``n_agents``).
    """
    grads = jnp.zeros((n_agents, dim), jnp.float32)
    key = jax.random.key(0)
    if apply:
        params = jnp.zeros((dim,), jnp.float32)
        return jax.make_jaxpr(
            lambda g, p, k: aggregate_apply(g, cfg, p, key=k, alpha=alpha,
                                            backend=backend,
                                            agent_blocks=agent_blocks)
        )(grads, params, key)
    return jax.make_jaxpr(
        lambda g, k: aggregate(g, cfg, key=k, backend=backend,
                               agent_blocks=agent_blocks)
    )(grads, key)


# ---------------------------------------------------------------------------
# Form 1 impl: stacked per-agent gradients (literal Algorithm 2).
# ---------------------------------------------------------------------------

def sample_gains(cfg: OTAConfig, key: jax.Array, n_agents: int) -> jax.Array:
    """Draw h_{i,k} for every agent for one round: shape (n_agents,).

    With power control, the effective gain is ``h = c * p(c)`` (Eq. 6's
    gain-times-power factorisation).
    """
    c = cfg.channel.sample(key, (n_agents,))
    if cfg.power_control is not None:
        c = c * cfg.power_control.apply(c)
    return c


def signal_power_sq(grads_stacked: PyTree, gains: jax.Array) -> jax.Array:
    """``||sum_i h_i g_i||^2`` — the received signal power of one uplink.

    Recomputes the combine of :func:`_aggregate_stacked_xla` on the same
    operands (identical op sequence, so XLA CSEs it against the aggregate
    when both appear in one program); the telemetry SNR probe divides this
    by the per-dimension noise power ``d * sigma_z^2``.
    """
    leading = jax.tree.leaves(grads_stacked)[0].shape[0]

    def _combine(g):
        hb = gains.reshape((leading,) + (1,) * (g.ndim - 1)).astype(g.dtype)
        return jnp.sum(hb * g, axis=0)

    v = jax.tree.map(_combine, grads_stacked)
    return sum(jnp.sum(jnp.square(leaf)) for leaf in jax.tree.leaves(v))


def effective_gain_mean(cfg: Optional[OTAConfig],
                        n_agents: Optional[int] = None) -> Scalar:
    """The closed-form effective gain mean ``m_h`` a config realises — the
    reference the telemetry moment-drift probe compares ``mean(h)`` against.

    Resolution order: exact uplink -> 1; a sweep-packed ``update_scale``
    (``1 / (N * m_eff)`` in float64) inverts back to the per-lane effective
    mean; otherwise the channel mean when no power control is set (possibly
    a traced ``BatchedChannel`` moment), else the closed-form/Monte-Carlo
    ``effective_moments``.  Falls back to the raw channel mean when traced
    power-control parameters make the closed form unavailable (the drift
    then includes the power-policy effect — documented approximation).
    """
    if cfg is None:
        return 1.0
    if cfg.debias and cfg.update_scale is not None and n_agents is not None:
        return 1.0 / (n_agents * cfg.update_scale)
    if cfg.power_control is None:
        return cfg.channel.mean
    try:
        return effective_moments(cfg.channel, cfg.power_control,
                                 n_agents=n_agents)[0]
    except TypeError:  # traced/unhashable channel or policy params
        return cfg.channel.mean


def _participation_rescale(n_total: Scalar, n_eff: Scalar) -> Scalar:
    """``n_total / n_eff`` — the round-service correction that retargets
    the full-fleet normaliser ``1/(n_total * m_h)`` at the round's
    effective contribution weight ``n_eff`` (realised participating count
    or its closed-form expectation, possibly fractional under staleness
    decay).  Exact zero at ``n_eff == 0``: an empty round must commit a
    zero update, never the amplified bare noise draw."""
    w = jnp.asarray(n_eff, jnp.float32)
    return jnp.where(w > 0, n_total / jnp.where(w > 0, w, 1.0), 0.0)


def _server_epilogue(
    cfg: OTAConfig,
    key_n: jax.Array,
    v: PyTree,
    n_total: Scalar,
    n_agents: Optional[int],
    n_eff: Optional[Scalar] = None,
) -> PyTree:
    """The shared server-side tail of every xla aggregation form: AWGN on
    the summed signal, then the update normalisation ``update_scale`` or
    ``1 / (n_total * norm_const)``.  One copy keeps the equivalence-tested
    forms from drifting apart.  ``n_eff`` (round service) renormalises by
    the effective contribution weight instead of the full fleet — see
    :func:`_participation_rescale`; ``None`` leaves the historical scale
    byte-identical."""
    if _noise_enabled(cfg.noise_sigma):
        noise = tree_normal_like(key_n, v, cfg.noise_sigma)
        v = jax.tree.map(jnp.add, v, noise)
    scale = cfg.update_scale
    if scale is None:
        scale = 1.0 / (n_total * cfg.norm_const_for(n_agents))
    if n_eff is not None:
        scale = scale * _participation_rescale(n_total, n_eff)
    return jax.tree.map(lambda x: x * scale, v)


def _server_scale(cfg: OTAConfig, n_total: Scalar,
                  n_agents: Optional[int],
                  n_eff: Optional[Scalar] = None) -> Scalar:
    """The epilogue's multiplicative constant, for backends that fuse it."""
    if cfg.update_scale is not None:
        scale = cfg.update_scale
    else:
        scale = 1.0 / (n_total * cfg.norm_const_for(n_agents))
    if n_eff is not None:
        scale = scale * _participation_rescale(n_total, n_eff)
    return scale


def _aggregate_stacked_xla(
    cfg: OTAConfig,
    key: jax.Array,
    grads_stacked: PyTree,
    *,
    gains: Optional[jax.Array] = None,
) -> Tuple[PyTree, jax.Array]:
    """u_k = (sum_i h_i g_i + n_k) / (N * c) as the historical XLA chain."""
    leading = jax.tree.leaves(grads_stacked)[0].shape[0]
    key_h, key_n = jax.random.split(key)
    h = sample_gains(cfg, key_h, leading) if gains is None else gains

    def _combine(g):
        hb = h.reshape((leading,) + (1,) * (g.ndim - 1)).astype(g.dtype)
        return jnp.sum(hb * g, axis=0)

    v = jax.tree.map(_combine, grads_stacked)
    return _server_epilogue(cfg, key_n, v, leading, leading), h


def _exact_mean(grads_stacked: PyTree) -> PyTree:
    """Algorithm-1 baseline: exact mean of per-agent gradients."""
    return jax.tree.map(lambda g: jnp.mean(g, axis=0), grads_stacked)


def _exact_mean_axis_stacked(
    local_grads: PyTree, axis_names: Tuple[str, ...],
    n_agents: Optional[int],
) -> PyTree:
    """Exact global mean over shard-local agent stacks (psum of local
    sums / N) — the op sequence the sharded fedpg round always used."""
    n_local = jax.tree.leaves(local_grads)[0].shape[0]
    if n_agents is None:
        idx_stride = 1
        for name in axis_names:
            idx_stride = idx_stride * jax.lax.axis_size(name)
        n_total: Scalar = idx_stride * n_local
    else:
        n_total = n_agents
    local_sum = jax.tree.map(lambda g: jnp.sum(g, axis=0), local_grads)
    return jax.tree.map(
        lambda s: jax.lax.psum(s, axis_names) / n_total, local_sum)


# ---------------------------------------------------------------------------
# Pallas backend: the fused kernel over the flattened parameter axis.
# ---------------------------------------------------------------------------

def _wire_dtype(cfg: OTAConfig):
    if not cfg.wire_dtype:
        return None
    return jnp.dtype(cfg.wire_dtype)


def _flatten_agent_stack(grads_stacked: PyTree):
    """(pytree of (N, ...) leaves) -> ((N, P) f32, unflatten)."""
    leaves, treedef = jax.tree.flatten(grads_stacked)
    n = leaves[0].shape[0]
    sizes = [int(leaf.size) // n for leaf in leaves]
    flat = jnp.concatenate(
        [leaf.reshape(n, -1).astype(jnp.float32) for leaf in leaves], axis=1)

    def unflatten(vec: jax.Array) -> PyTree:
        parts = []
        off = 0
        for leaf, size in zip(leaves, sizes):
            parts.append(
                vec[off:off + size].reshape(leaf.shape[1:]).astype(leaf.dtype))
            off += size
        return jax.tree.unflatten(treedef, parts)

    return flat, n, unflatten


def _flatten_params(params: PyTree):
    leaves, treedef = jax.tree.flatten(params)
    sizes = [int(leaf.size) for leaf in leaves]
    flat = jnp.concatenate(
        [leaf.reshape(-1).astype(jnp.float32) for leaf in leaves])

    def unflatten(vec: jax.Array) -> PyTree:
        parts = []
        off = 0
        for leaf, size in zip(leaves, sizes):
            parts.append(
                vec[off:off + size].reshape(leaf.shape).astype(leaf.dtype))
            off += size
        return jax.tree.unflatten(treedef, parts)

    return flat, unflatten


def _kernel_seed(key_n: jax.Array) -> jax.Array:
    """A uint32 counter-PRNG seed derived from the server noise key."""
    return jax.random.bits(key_n, (), jnp.uint32)


def _aggregate_stacked_pallas(
    cfg: OTAConfig,
    key: jax.Array,
    grads_stacked: PyTree,
    *,
    gains: Optional[jax.Array] = None,
) -> Tuple[PyTree, jax.Array]:
    from repro.kernels import ota_fused

    flat, n, unflatten = _flatten_agent_stack(grads_stacked)
    key_h, key_n = jax.random.split(key)
    h = sample_gains(cfg, key_h, n) if gains is None else gains
    u = ota_fused.fused_aggregate(
        flat, h.astype(jnp.float32),
        sigma=cfg.noise_sigma,
        scale=_server_scale(cfg, n, n),
        seed=_kernel_seed(key_n),
        with_noise=_noise_enabled(cfg.noise_sigma),
        wire_dtype=_wire_dtype(cfg),
    )
    return unflatten(u), h


def _aggregate_apply_pallas(
    cfg: OTAConfig,
    key: jax.Array,
    grads_stacked: PyTree,
    params: PyTree,
    alpha: Scalar,
    *,
    gains: Optional[jax.Array] = None,
) -> Tuple[PyTree, jax.Array]:
    from repro.kernels import ota_fused

    flat, n, _ = _flatten_agent_stack(grads_stacked)
    pflat, punflatten = _flatten_params(params)
    key_h, key_n = jax.random.split(key)
    h = sample_gains(cfg, key_h, n) if gains is None else gains
    p_next = ota_fused.fused_aggregate_sgd(
        flat, h.astype(jnp.float32), pflat,
        alpha=alpha,
        sigma=cfg.noise_sigma,
        scale=_server_scale(cfg, n, n),
        seed=_kernel_seed(key_n),
        with_noise=_noise_enabled(cfg.noise_sigma),
        wire_dtype=_wire_dtype(cfg),
    )
    return punflatten(p_next), h


# ---------------------------------------------------------------------------
# Streaming (blocked-scan) evaluation of the agent sum: agent_blocks.
#
# The agent axis is consumed in scan chunks of `block` agents; each chunk is
# folded into the running channel superposition by a STRICT sequential
# per-agent fold.  The fold's association is therefore independent of where
# the block boundaries fall — any partition of the agent axis (including a
# masked phantom tail for non-dividing counts) yields a bitwise-identical
# sum, mirroring the `block_rows` invariance of the fused kernel.  Gains
# and AWGN come from the exact same PRNG streams as the unblocked forms;
# only the cross-agent summation association differs from XLA's batched
# reduce (a last-mantissa-bit reassociation, documented in README).
# ---------------------------------------------------------------------------

def blocked_layout(n_agents: int, agent_blocks: int) -> Tuple[int, int, int]:
    """Resolve a block partition: ``(n_blocks, block, pad)``.

    ``pad`` phantom agents fill the tail block when ``agent_blocks`` does
    not divide ``n_agents``; their contributions are masked to exact zeros,
    so the padded fold is bitwise-identical to the unpadded one.

    The block is capped at ``ceil(n_agents / 2)`` so the scan always runs
    at least two steps: XLA inlines a trip-count-1 loop, which changes how
    the block body fuses and would make ``agent_blocks >= n_agents`` a
    bitwise outlier among block sizes.  Capping only shrinks the block
    (peak memory stays within the requested O(agent_blocks × d)) and the
    strict sequential fold is invariant to where the boundaries fall, so
    every finite ``agent_blocks`` lands on the same history.
    """
    if agent_blocks < 1:
        raise ValueError(f"agent_blocks must be >= 1, got {agent_blocks}")
    block = min(int(agent_blocks), max(1, -(-n_agents // 2)))
    n_blocks = -(-n_agents // block)
    return n_blocks, block, n_blocks * block - n_agents


def pad_agent_axis(tree: PyTree, pad: int) -> PyTree:
    """Append ``pad`` phantom rows to every leading-axis leaf (row-0 copies;
    the values never contribute — every streamed consumer masks them).
    Works on PRNG key arrays too (gather + concatenate only)."""
    if pad == 0:
        return tree

    def _pad(a):
        filler = a[jnp.zeros((pad,), jnp.int32)]
        return jnp.concatenate([a, filler], axis=0)

    return jax.tree.map(_pad, tree)


def block_view(tree: PyTree, n_blocks: int, block: int) -> PyTree:
    """Reshape padded leading-axis leaves to ``(n_blocks, block, ...)`` —
    the xs layout the blocked scan consumes (absolute agent order is
    preserved: block b holds agents ``[b*block, (b+1)*block)``)."""
    return jax.tree.map(
        lambda a: a.reshape((n_blocks, block) + a.shape[1:]), tree)


def block_valid_mask(n_agents: int, n_blocks: int, block: int) -> jax.Array:
    """(n_blocks, block) bool — False on phantom (padding) rows."""
    return (jnp.arange(n_blocks * block) < n_agents).reshape(n_blocks, block)


def stream_fold_block(
    acc: PyTree,
    grads_block: PyTree,
    gains_block: Optional[jax.Array] = None,
    valid: Optional[jax.Array] = None,
    wire_dtype=None,
) -> PyTree:
    """Fold one agent block into the running sum, strictly sequentially.

    ``acc + h_0 g_0 + h_1 g_1 + ...`` as an explicit left fold (a
    ``fori_loop`` of per-agent adds), so the association never depends on
    the block size.  ``gains_block=None`` folds the unweighted gradients
    (the exact-uplink mean numerator).  ``valid`` masks phantom rows to
    exact zeros — IEEE-safe: the running value can never be ``-0.0`` (a sum
    starting from ``+0.0`` cannot produce it), so ``+ 0.0`` is a bitwise
    no-op and padding never perturbs the fold.  ``wire_dtype`` applies the
    pallas wire-format quantisation per agent row (cast down, compute in
    float32), matching the fused kernel's per-row math.
    """
    leaves = jax.tree.leaves(grads_block)
    block = leaves[0].shape[0]

    def step(i, acc):
        def add_row(a, g):
            row = g[i]
            if wire_dtype is not None:
                row = row.astype(wire_dtype).astype(jnp.float32)
            if gains_block is not None:
                row = gains_block[i].astype(row.dtype) * row
            if valid is not None:
                row = jnp.where(valid[i], row, jnp.zeros_like(row))
            return a + row.astype(a.dtype)
        return jax.tree.map(add_row, acc, grads_block)

    return jax.lax.fori_loop(0, block, step, acc)


def _stream_zero(grads_stacked: PyTree, as_f32: bool = False) -> PyTree:
    dt = (lambda a: jnp.float32) if as_f32 else (lambda a: a.dtype)
    return jax.tree.map(
        lambda a: jnp.zeros(a.shape[1:], dt(a)), grads_stacked)


def _stream_superpose(
    grads_stacked: PyTree,
    gains: Optional[jax.Array],
    agent_blocks: int,
    *,
    wire_dtype=None,
    as_f32: bool = False,
) -> PyTree:
    """scan-of-folds over an already-materialised agent stack; returns the
    running superposition ``sum_i h_i g_i`` (or ``sum_i g_i``)."""
    n = jax.tree.leaves(grads_stacked)[0].shape[0]
    n_blocks, block, pad = blocked_layout(n, agent_blocks)
    gp = block_view(pad_agent_axis(grads_stacked, pad), n_blocks, block)
    valid = block_valid_mask(n, n_blocks, block)
    xs = (gp, valid)
    if gains is not None:
        hp = jnp.concatenate([gains, jnp.zeros((pad,), gains.dtype)]) \
            if pad else gains
        xs = (gp, valid, hp.reshape(n_blocks, block))

    def body(acc, x):
        gb, vb = x[0], x[1]
        hb = x[2] if gains is not None else None
        if as_f32:
            gb = jax.tree.map(lambda a: a.astype(jnp.float32), gb)
        return stream_fold_block(acc, gb, hb, vb, wire_dtype=wire_dtype), None

    v, _ = jax.lax.scan(body, _stream_zero(grads_stacked, as_f32), xs)
    return v


def stream_finalize(
    cfg: OTAConfig,
    key_n: jax.Array,
    v: PyTree,
    n_agents: int,
    *,
    backend: str = "xla",
    n_eff: Optional[Scalar] = None,
) -> PyTree:
    """Server tail over a streamed superposition: ONE AWGN draw + the
    debias normalisation.  On xla this is the shared `_server_epilogue`
    (the noise tensor is bitwise-identical to the unblocked form's — same
    ``key_n``, same shapes); on pallas it is one fused kernel pass over the
    flattened ``v`` with the counter PRNG (noise indexed by absolute flat
    position, so it too is invariant to the agent blocking).  ``n_eff``
    retargets the normaliser at the round service's effective
    contribution weight (see :func:`_participation_rescale`)."""
    if backend == "pallas":
        from repro.kernels import ota_fused

        flat, unflatten = _flatten_params(v)
        u = ota_fused.fused_server_pass(
            flat,
            sigma=cfg.noise_sigma,
            scale=_server_scale(cfg, n_agents, n_agents, n_eff),
            seed=_kernel_seed(key_n),
            with_noise=_noise_enabled(cfg.noise_sigma),
        )
        return unflatten(u)
    return _server_epilogue(cfg, key_n, v, n_agents, n_agents, n_eff)


def stream_finalize_apply(
    cfg: OTAConfig,
    key_n: jax.Array,
    v: PyTree,
    params: PyTree,
    alpha: Scalar,
    n_agents: int,
    *,
    backend: str = "xla",
    n_eff: Optional[Scalar] = None,
) -> PyTree:
    """`stream_finalize` fused with the server SGD step
    ``theta' = theta - alpha * u`` (one kernel pass on pallas)."""
    if backend == "pallas":
        from repro.kernels import ota_fused

        flat, _ = _flatten_params(v)
        pflat, punflatten = _flatten_params(params)
        p_next = ota_fused.fused_server_pass(
            flat,
            sigma=cfg.noise_sigma,
            scale=_server_scale(cfg, n_agents, n_agents, n_eff),
            seed=_kernel_seed(key_n),
            with_noise=_noise_enabled(cfg.noise_sigma),
            alpha=alpha,
            params=pflat,
        )
        return punflatten(p_next)
    u = _server_epilogue(cfg, key_n, v, n_agents, n_agents, n_eff)
    return jax.tree.map(lambda p, x: p - alpha * x, params, u)


def _aggregate_stacked_streamed(
    cfg: OTAConfig,
    key: jax.Array,
    grads_stacked: PyTree,
    agent_blocks: int,
    *,
    gains: Optional[jax.Array] = None,
    backend: str = "xla",
) -> Tuple[PyTree, jax.Array]:
    """The stacked form evaluated as a blocked scan.  Same key split, same
    full-N gain draw, same noise stream as the unblocked stacked form of
    the matching backend — only the agent-sum association differs."""
    n = jax.tree.leaves(grads_stacked)[0].shape[0]
    key_h, key_n = jax.random.split(key)
    h = sample_gains(cfg, key_h, n) if gains is None else gains
    pallas = backend == "pallas"
    v = _stream_superpose(
        grads_stacked, h.astype(jnp.float32) if pallas else h, agent_blocks,
        wire_dtype=_wire_dtype(cfg) if pallas else None, as_f32=pallas)
    if pallas:
        # match the kernel's output contract: float32 update leaves cast
        # back to the native parameter dtypes by the unflatten
        u = stream_finalize(cfg, key_n, v, n, backend="pallas")
        u = jax.tree.map(lambda x, g: x.astype(g.dtype), u,
                         jax.tree.map(lambda a: a[0], grads_stacked))
        return u, h
    return stream_finalize(cfg, key_n, v, n), h


def _aggregate_apply_streamed_pallas(
    cfg: OTAConfig,
    key: jax.Array,
    grads_stacked: PyTree,
    params: PyTree,
    alpha: Scalar,
    agent_blocks: int,
    *,
    gains: Optional[jax.Array] = None,
) -> Tuple[PyTree, jax.Array]:
    n = jax.tree.leaves(grads_stacked)[0].shape[0]
    key_h, key_n = jax.random.split(key)
    h = sample_gains(cfg, key_h, n) if gains is None else gains
    v = _stream_superpose(grads_stacked, h.astype(jnp.float32), agent_blocks,
                          wire_dtype=_wire_dtype(cfg), as_f32=True)
    return stream_finalize_apply(cfg, key_n, v, params, alpha, n,
                                 backend="pallas"), h


def _exact_mean_streamed(grads_stacked: PyTree, agent_blocks: int) -> PyTree:
    """Algorithm-1 mean as a blocked fold: ``(fold_i g_i) / N``."""
    n = jax.tree.leaves(grads_stacked)[0].shape[0]
    v = _stream_superpose(grads_stacked, None, agent_blocks)
    return jax.tree.map(lambda s: s / n, v)


def _exact_mean_axis_stacked_streamed(
    local_grads: PyTree, axis_names: Tuple[str, ...],
    n_agents: Optional[int], agent_blocks: int,
) -> PyTree:
    """Exact global mean with shard-local blocked folds (psum of local
    folds / N).  Rows whose global agent index is >= ``n_agents`` are
    phantom padding and fold exact zeros."""
    n_local = jax.tree.leaves(local_grads)[0].shape[0]
    n_total, valid_local = _sharded_stream_meta(axis_names, n_local, n_agents)
    v_local = _stream_superpose_masked(local_grads, None, agent_blocks,
                                       valid_local)
    return jax.tree.map(
        lambda s: jax.lax.psum(s, axis_names) / n_total, v_local)


def _sharded_stream_meta(axis_names, n_local: int,
                         n_agents: Optional[int]):
    """(true agent count, per-local-row validity) for a possibly padded
    shard-local stack: row j is global agent ``shard_index * n_local + j``,
    valid while that index is < n_agents."""
    idx, stride = _flat_axis_index(axis_names)
    if n_agents is None:
        return stride * n_local, jnp.ones((n_local,), bool)
    global_idx = idx * n_local + jnp.arange(n_local, dtype=jnp.int32)
    return n_agents, global_idx < n_agents


def _stream_superpose_masked(
    local_grads: PyTree,
    gains: Optional[jax.Array],
    agent_blocks: int,
    valid_local: jax.Array,
) -> PyTree:
    """`_stream_superpose` over a shard-local stack whose rows carry their
    own validity (shard-level phantom padding composed with the tail-block
    padding of the scan itself)."""
    n_local = jax.tree.leaves(local_grads)[0].shape[0]
    n_blocks, block, pad = blocked_layout(n_local, agent_blocks)
    gp = block_view(pad_agent_axis(local_grads, pad), n_blocks, block)
    vp = jnp.concatenate([valid_local, jnp.zeros((pad,), bool)]) \
        if pad else valid_local
    valid = vp.reshape(n_blocks, block)
    xs = (gp, valid)
    if gains is not None:
        hp = jnp.concatenate([gains, jnp.zeros((pad,), gains.dtype)]) \
            if pad else gains
        xs = (gp, valid, hp.reshape(n_blocks, block))

    def body(acc, x):
        gb, vb = x[0], x[1]
        hb = x[2] if gains is not None else None
        return stream_fold_block(acc, gb, hb, vb), None

    v, _ = jax.lax.scan(body, _stream_zero(local_grads), xs)
    return v


def sharded_stream_gains(
    cfg: OTAConfig,
    key_h: jax.Array,
    axis_names: Tuple[str, ...],
    n_local: int,
    n_agents: Optional[int],
) -> Tuple[jax.Array, jax.Array]:
    """This shard's ``(h_local, valid_local)`` for a streamed axis-stacked
    uplink: the same global-agent-index ``fold_in`` gain stream as the
    unblocked `_psum_axis_stacked` (so gains are invariant to both the mesh
    layout and the blocking), with phantom rows — global index >=
    ``n_agents`` under padding — zeroed so a ``psum(sum(h)) / N`` gain mean
    stays correct."""
    n_total, valid_local = _sharded_stream_meta(axis_names, n_local, n_agents)
    idx, _ = _flat_axis_index(axis_names)
    global_idx = idx * n_local + jnp.arange(n_local, dtype=jnp.int32)

    def gain_for(j):
        c = cfg.channel.sample(jax.random.fold_in(key_h, j), ())
        if cfg.power_control is not None:
            c = c * cfg.power_control.apply_indexed(c, j, n_total)
        return c

    h = jax.vmap(gain_for)(global_idx)
    return jnp.where(valid_local, h, jnp.zeros_like(h)), valid_local


def _psum_axis_stacked_streamed(
    cfg: OTAConfig,
    key: jax.Array,
    local_grads: PyTree,
    axis_names: Tuple[str, ...],
    *,
    n_agents: Optional[int] = None,
    agent_blocks: int,
) -> Tuple[PyTree, jax.Array]:
    """The axis-stacked form with shard-local blocked folds.

    Gains come from :func:`sharded_stream_gains` (the unblocked form's
    stream); phantom rows fold exact zeros.  Local folds are psummed once,
    then the shared server epilogue runs with the TRUE agent count — the
    reward/update normalisers never see the padding.
    """
    n_local = jax.tree.leaves(local_grads)[0].shape[0]
    key_h, key_n = jax.random.split(key)
    n_total, _ = _sharded_stream_meta(axis_names, n_local, n_agents)
    h, valid_local = sharded_stream_gains(cfg, key_h, axis_names, n_local,
                                          n_agents)
    v_local = _stream_superpose_masked(local_grads, h, agent_blocks,
                                       valid_local)
    v = jax.tree.map(lambda s: jax.lax.psum(s, axis_names), v_local)
    return _server_epilogue(cfg, key_n, v, n_total, n_agents), h


# ---------------------------------------------------------------------------
# Form 2 impl: shard_map / psum (production data-parallel form).
# ---------------------------------------------------------------------------

def _flat_axis_index(axis_names: Sequence[str]) -> Tuple[jax.Array, Scalar]:
    """(flattened shard index, total shard count) over the given mesh axes
    (row-major, matching the historical ``local_gain`` indexing)."""
    idx = jnp.zeros((), jnp.int32)
    stride: Scalar = 1
    for name in reversed(tuple(axis_names)):
        idx = idx + jax.lax.axis_index(name) * stride
        stride = stride * jax.lax.axis_size(name)
    return idx, stride


def local_gain(
    cfg: OTAConfig,
    key: jax.Array,
    axis_names: Sequence[str],
    n_agents: Optional[int] = None,
) -> jax.Array:
    """Sample this shard's h_{i,k} inside shard_map.

    Every shard folds its own agent index into the shared round key, so the
    gains are independent across agents but reproducible.  ``n_agents`` is
    the static total agent count when the caller knows it (per-agent
    policies like ``HeterogeneousBudget`` prefer a static count).
    """
    idx, stride = _flat_axis_index(axis_names)
    c = cfg.channel.sample(jax.random.fold_in(key, idx), ())
    if cfg.power_control is not None:
        # per-agent policies key the budget on this shard's agent index
        n = stride if n_agents is None else n_agents
        c = c * cfg.power_control.apply_indexed(c, idx, n)
    return c


def _psum_axis(
    cfg: OTAConfig,
    key: jax.Array,
    local_grad: PyTree,
    axis_names: Tuple[str, ...],
    *,
    n_agents: Optional[int] = None,
) -> Tuple[PyTree, jax.Array]:
    """OTA aggregation across mesh axes, called inside shard_map.

    The per-agent gain scaling happens *before* the psum, so OTA adds zero
    communication volume over exact data-parallel aggregation — which is the
    paper's efficiency claim transplanted to the interconnect.  ``n_agents``
    is the static total agent count when known; without it the count is the
    mesh size, which keeps the maths right, but debiased per-agent-policy
    configs must then carry an explicit ``update_scale`` (their closed-form
    effective moments are keyed on ``n_agents``).
    """
    key_h, key_n = jax.random.split(key)
    h = local_gain(cfg, key_h, axis_names, n_agents)
    scaled = jax.tree.map(lambda g: g * h.astype(g.dtype), local_grad)
    v = jax.lax.psum(scaled, axis_names)
    # Same key_n on every shard => identical noise everywhere, i.e. the
    # server's single n_k draw without any broadcast collective.
    n = n_agents
    if n is None and cfg.update_scale is None:  # only then is the count used
        n = _flat_axis_index(axis_names)[1]
    return _server_epilogue(cfg, key_n, v, n, n_agents), h


def _psum_axis_stacked(
    cfg: OTAConfig,
    key: jax.Array,
    local_grads: PyTree,
    axis_names: Tuple[str, ...],
    *,
    n_agents: Optional[int] = None,
) -> Tuple[PyTree, jax.Array]:
    """The axis form for shards that each carry a *stack* of agents.

    ``local_grads`` leaves have a leading ``n_local`` axis (this shard's
    slice of the agent axis).  Gains are drawn exactly like ``local_gain``
    but keyed on the *global* agent index ``shard_index * n_local + j`` —
    with one agent per shard the stream is identical to the plain axis
    form.  Each shard reduces its gain-weighted stack locally, ``psum``s
    across the mesh axes, and applies the shared AWGN + normalisation once.
    This is the agent-axis sharding hook ``fedpg.make_round_fn`` uses, so
    ``HeterogeneousEnv`` fleets and per-agent power control
    (``HeterogeneousBudget``) run in their production shard_map form.

    Returns ``(update, h_local)``; ``h_local`` is this shard's (n_local,)
    gain slice (psum its sum for the global gain mean).
    """
    n_local = jax.tree.leaves(local_grads)[0].shape[0]
    key_h, key_n = jax.random.split(key)
    idx, stride = _flat_axis_index(axis_names)
    n_total: Scalar = n_agents if n_agents is not None else stride * n_local
    global_idx = idx * n_local + jnp.arange(n_local, dtype=jnp.int32)

    def gain_for(j):
        c = cfg.channel.sample(jax.random.fold_in(key_h, j), ())
        if cfg.power_control is not None:
            c = c * cfg.power_control.apply_indexed(c, j, n_total)
        return c

    h = jax.vmap(gain_for)(global_idx)

    def _combine(g):
        hb = h.reshape((n_local,) + (1,) * (g.ndim - 1)).astype(g.dtype)
        return jnp.sum(hb * g, axis=0)

    v = jax.lax.psum(jax.tree.map(_combine, local_grads), axis_names)
    return _server_epilogue(cfg, key_n, v, n_total, n_agents), h


# ---------------------------------------------------------------------------
# Deprecated entry points — thin wrappers over the dispatcher-era impls.
# New in-repo code must use :func:`aggregate`; CI lints for fresh callers
# (tools/lint_aggregation_api.py).
# ---------------------------------------------------------------------------

def _warn_deprecated(name: str, repl: str) -> None:
    warnings.warn(
        f"ota.{name} is deprecated; use ota.aggregate({repl})",
        DeprecationWarning, stacklevel=3,
    )


def aggregate_stacked(
    cfg: OTAConfig,
    key: jax.Array,
    grads_stacked: PyTree,
    *,
    gains: Optional[jax.Array] = None,
) -> Tuple[PyTree, jax.Array]:
    """Deprecated: ``aggregate(grads, cfg, key=key, backend="xla")``."""
    _warn_deprecated("aggregate_stacked", "grads, cfg, key=key")
    return _aggregate_stacked_xla(cfg, key, grads_stacked, gains=gains)


def exact_aggregate(grads_stacked: PyTree) -> PyTree:
    """Deprecated: ``aggregate(grads, None)[0]``."""
    _warn_deprecated("exact_aggregate", "grads, None")
    return _exact_mean(grads_stacked)


def psum_aggregate(
    cfg: OTAConfig,
    key: jax.Array,
    local_grad: PyTree,
    axis_names: Sequence[str],
    *,
    n_agents: Optional[int] = None,
) -> PyTree:
    """Deprecated: ``aggregate(grads, cfg, key=key, axis=axis_names)[0]``."""
    _warn_deprecated("psum_aggregate", "grads, cfg, key=key, axis=...")
    return _psum_axis(cfg, key, local_grad, tuple(axis_names),
                      n_agents=n_agents)[0]


def psum_aggregate_stacked(
    cfg: OTAConfig,
    key: jax.Array,
    local_grads: PyTree,
    axis_names: Sequence[str],
    *,
    n_agents: Optional[int] = None,
) -> Tuple[PyTree, jax.Array]:
    """Deprecated: ``aggregate(..., axis=..., local_stack=True)``."""
    _warn_deprecated("psum_aggregate_stacked",
                     "grads, cfg, key=key, axis=..., local_stack=True")
    return _psum_axis_stacked(cfg, key, local_grads, tuple(axis_names),
                              n_agents=n_agents)


# ---------------------------------------------------------------------------
# Form 3: channel-weighted loss (fold distortion into autodiff).
# ---------------------------------------------------------------------------

def example_weights(
    gains: jax.Array, global_batch: int, *, dtype=jnp.float32
) -> jax.Array:
    """Expand per-agent gains (N,) to per-example weights (global_batch,).

    Agent i owns the contiguous example slice [i*B/N, (i+1)*B/N).  With the
    per-example loss  L = (1/B) sum_e w_e l_e  and w_e = h_{agent(e)}, plain
    autodiff gives  grad L = (1/N) sum_i h_i grad J_i = v_k / N  (pre-noise).
    """
    n_agents = gains.shape[0]
    if global_batch % n_agents != 0:
        raise ValueError(
            f"global_batch={global_batch} not divisible by n_agents={n_agents}"
        )
    per = global_batch // n_agents
    return jnp.repeat(gains.astype(dtype), per)


def add_awgn(
    cfg: OTAConfig, key: jax.Array, grad: PyTree, n_agents: int,
    *, backend: str = "xla",
) -> PyTree:
    """Apply the server-side AWGN and normalisation to a weighted-loss grad.

    ``grad`` must already equal ``(1/N) sum_i h_i g_i`` (from the weighted
    loss); this adds ``n_k / N`` and optionally debiases by ``m_h``.  An
    ``update_scale`` override (``1 / (N * c)`` over the raw sum) is honoured
    here as the equivalent ``N * update_scale`` factor, keeping the
    aggregation forms interchangeable for sweep-built configs.

    ``backend="pallas"`` (or ``"auto"`` on TPU) runs the whole epilogue as
    one fused-kernel pass over the flattened gradient — the LLM trainer's
    server tail at transformer scale; the noise then comes from the kernel's
    counter PRNG (same distribution, different stream than xla threefry).
    """
    if backend not in _BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {_BACKENDS}")
    be = backend
    if be == "auto":
        be = "pallas" if jax.default_backend() == "tpu" else "xla"
    if be == "pallas":
        return _add_awgn_pallas(cfg, key, grad, n_agents)
    if _noise_enabled(cfg.noise_sigma):
        noise = tree_normal_like(key, grad, cfg.noise_sigma / n_agents)
        grad = jax.tree.map(jnp.add, grad, noise)
    if cfg.update_scale is not None:
        scale = n_agents * cfg.update_scale
        grad = jax.tree.map(lambda x: x * scale, grad)
    elif cfg.debias:
        inv = 1.0 / cfg.norm_const_for(n_agents)
        grad = jax.tree.map(lambda x: x * inv, grad)
    return grad


def _add_awgn_pallas(
    cfg: OTAConfig, key: jax.Array, grad: PyTree, n_agents: int
) -> PyTree:
    """The weighted-loss server epilogue as one fused kernel pass: the
    already-averaged gradient enters as a single-"agent" stack with unit
    gain, sigma/N noise, and the Form-3 normalisation."""
    from repro.kernels import ota_fused

    flat, unflatten = _flatten_params(grad)
    if cfg.update_scale is not None:
        scale: Scalar = n_agents * cfg.update_scale
    elif cfg.debias:
        scale = 1.0 / cfg.norm_const_for(n_agents)
    else:
        scale = 1.0
    u = ota_fused.fused_aggregate(
        flat.reshape(1, -1), jnp.ones((1,), jnp.float32),
        sigma=jnp.asarray(cfg.noise_sigma, jnp.float32) / n_agents,
        scale=scale,
        seed=_kernel_seed(key),
        with_noise=_noise_enabled(cfg.noise_sigma),
        wire_dtype=_wire_dtype(cfg),
    )
    return unflatten(u)
