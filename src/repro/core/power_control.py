"""Transmit-power policies: h_{i,k} = c_{i,k} * p_{i,k}.

The paper folds the power coefficient p into the effective gain h and only
needs the pair (m_h, sigma_h^2) that Theorems 1/2 are stated in.  These
policies shape p as a function of the actual channel gain c — the main lever
on that pair in the OTA-FL literature (Cao et al., "Optimized Power Control
for Over-the-Air Federated Edge Learning"; Fan et al., "Joint Optimization
of Communications and Federated Learning Over the Air").

Policies
--------
* ``UnitPower``          — p = 1, the paper's default (h = c).
* ``TruncatedInversion`` — p = min(target/c, p_max) with outage below c_min.
* ``FullInversion``      — p = min(target/c, p_max), no outage region.
* ``ConstantReceived``   — phase-aware exact inversion, h = target a.s.
* ``HeterogeneousBudget``— per-agent constant budgets linspaced over agents.

Moments contract
----------------
The effective-gain channel ``ControlledChannel`` is registered in
``channel._REGISTRY`` (kind ``'controlled'``) and must carry *finite*
``(m_h, sigma_h^2)``; build it with :func:`make_controlled_channel`, which
prefers the closed forms below and falls back to Monte Carlo:

* ``TruncatedInversion``/``FullInversion`` over Rayleigh — exact via lower
  incomplete gamma functions (``gamma(3/2, .)`` and ``gamma(2, .)``, both
  elementary: erf/exp);
* ``ConstantReceived`` — (target, 0) for any base with P(c = 0) = 0;
* ``HeterogeneousBudget`` — exact mixture moments from the base moments
  (needs ``n_agents``);
* anything else — :func:`estimate_moments` Monte Carlo.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import channel as _channel
from repro.core.channel import BatchedChannel, Channel, RayleighChannel

_POLICY_REGISTRY: Dict[str, type] = {}


def register_policy(cls: type) -> type:
    """Class decorator: make a policy reconstructable inside batched lanes."""
    _POLICY_REGISTRY[cls.__name__] = cls
    return cls


@dataclass(frozen=True)
class PowerPolicy:
    # True for policies whose p depends on the agent index (the gain vector's
    # last axis is then interpreted as the agent axis).
    per_agent = False

    def apply(self, c: jax.Array) -> jax.Array:
        """Map actual channel gains c to transmit power coefficients p."""
        raise NotImplementedError

    def apply_indexed(self, c: jax.Array, idx: jax.Array, n_agents) -> jax.Array:
        """Single-agent form for the shard_map/psum path: this shard's p
        given its scalar gain ``c``, agent index and total agent count."""
        del idx, n_agents
        return self.apply(c)

    def closed_form_moments(
        self, base: Channel, n_agents: Optional[int] = None
    ) -> Optional[Tuple[float, float]]:
        """Exact effective-gain (m_h, sigma_h^2) over ``base`` when known,
        else None (callers fall back to :func:`estimate_moments`)."""
        del base, n_agents
        return None


@register_policy
@dataclass(frozen=True)
class UnitPower(PowerPolicy):
    """p == 1: the paper's default (h = c)."""

    def apply(self, c: jax.Array) -> jax.Array:
        return jnp.ones_like(c)

    def closed_form_moments(self, base, n_agents=None):
        return float(base.mean), float(base.var)


# ---------------------------------------------------------------------------
# Channel-inversion policies.
# ---------------------------------------------------------------------------

def _rayleigh_partial_moments(scale: float, lo: float, hi: float) -> Tuple[float, float]:
    """(int_lo^hi c f(c) dc, int_lo^hi c^2 f(c) dc) for Rayleigh(scale).

    With u = c^2/(2 s^2) ~ Exp(1) these are lower-incomplete-gamma
    differences: gamma(3/2, u) = sqrt(pi)/2 erf(sqrt(u)) - sqrt(u) e^-u and
    gamma(2, u) = 1 - (1+u) e^-u.
    """
    s2 = scale * scale

    def u(c: float) -> float:
        return c * c / (2.0 * s2)

    def g32(x: float) -> float:
        return 0.5 * math.sqrt(math.pi) * math.erf(math.sqrt(x)) - math.sqrt(x) * math.exp(-x)

    def g2(x: float) -> float:
        return 1.0 - (1.0 + x) * math.exp(-x)

    i1 = scale * math.sqrt(2.0) * (g32(u(hi)) - g32(u(lo)))
    i2 = 2.0 * s2 * (g2(u(hi)) - g2(u(lo)))
    return i1, i2


def _rayleigh_inversion_moments(
    scale: float, target: float, p_max: float, c_min: float
) -> Tuple[float, float]:
    """Exact (m_h, sigma_h^2) of h = c * min(target/c, p_max) * 1{c >= c_min}
    over Rayleigh(scale): h = p_max c on [c_min, target/p_max), = target above.
    """
    t = target / p_max
    lo, hi = c_min, max(c_min, t)
    i1, i2 = _rayleigh_partial_moments(scale, lo, hi)
    surv = math.exp(-hi * hi / (2.0 * scale * scale))  # P(c >= hi)
    m = p_max * i1 + target * surv
    m2 = p_max * p_max * i2 + target * target * surv
    return m, max(m2 - m * m, 0.0)


@register_policy
@dataclass(frozen=True)
class TruncatedInversion(PowerPolicy):
    """p = min(target/c, p_max), with outage (p=0) below c_min.

    Classic OTA power control: agents invert their channel so the server
    sees ~equal gains, but deep fades are truncated to respect the power
    budget (otherwise E[p^2] diverges for Rayleigh).
    """

    target: float = 1.0
    p_max: float = 10.0
    c_min: float = 0.05

    def apply(self, c: jax.Array) -> jax.Array:
        p = jnp.minimum(self.target / jnp.maximum(c, 1e-12), self.p_max)
        return jnp.where(c >= self.c_min, p, 0.0)

    def closed_form_moments(self, base, n_agents=None):
        if type(base) is RayleighChannel:
            return _rayleigh_inversion_moments(
                float(base.scale), float(self.target), float(self.p_max),
                float(self.c_min))
        return None


@register_policy
@dataclass(frozen=True)
class FullInversion(PowerPolicy):
    """p = min(target/c, p_max): inversion with a power cap but no outage.

    Deep fades transmit at the cap instead of going silent, so weak agents
    still contribute (attenuated) signal rather than dropping out.
    """

    target: float = 1.0
    p_max: float = 10.0

    def apply(self, c: jax.Array) -> jax.Array:
        return jnp.minimum(self.target / jnp.maximum(c, 1e-12), self.p_max)

    def closed_form_moments(self, base, n_agents=None):
        if type(base) is RayleighChannel:
            return _rayleigh_inversion_moments(
                float(base.scale), float(self.target), float(self.p_max), 0.0)
        return None


@register_policy
@dataclass(frozen=True)
class ConstantReceived(PowerPolicy):
    """Phase-aware exact inversion: p = target/c, so h = target a.s.

    Models perfect channel-state pre-compensation (amplitude inversion with
    phase alignment, unbounded peak power): the server sees a deterministic
    gain, killing the channel-variance floor entirely — sigma_h^2 = 0, the
    best case of Theorems 1/2.
    """

    target: float = 1.0

    def apply(self, c: jax.Array) -> jax.Array:
        return self.target / jnp.maximum(c, 1e-12)

    def closed_form_moments(self, base, n_agents=None):
        # exact for any base with no atom at 0 (all continuous models here).
        return float(self.target), 0.0


@register_policy
@dataclass(frozen=True)
class HeterogeneousBudget(PowerPolicy):
    """Per-agent constant budgets: agent i transmits at b_i, with budgets
    linearly spaced from ``p_min`` (agent 0) to ``p_max`` (agent N-1).

    Models a fleet with heterogeneous power headroom; the effective gains
    stay independent but are no longer identically distributed, so the
    theory plugs in the *mixture* moments over a uniformly random agent.
    The gain vector's last axis is interpreted as the agent axis.
    """

    p_min: float = 0.5
    p_max: float = 1.5

    per_agent = True

    def _budgets(self, n: int, dtype) -> jax.Array:
        return jnp.linspace(self.p_min, self.p_max, n).astype(dtype)

    def apply(self, c: jax.Array) -> jax.Array:
        if jnp.ndim(c) == 0:
            raise ValueError(
                "HeterogeneousBudget.apply needs a trailing agent axis; "
                "single-agent (scalar) paths must use apply_indexed — the "
                "shard_map/psum form only supports per-agent policies via "
                "OTAConfig.power_control, not via ControlledChannel"
            )
        return jnp.broadcast_to(self._budgets(c.shape[-1], c.dtype), c.shape)

    def apply_indexed(self, c, idx, n_agents):
        # the agent count is static (mesh axis sizes are Python ints): fold
        # the step in as a literal, matching the stacked form's linspace
        step = (self.p_max - self.p_min) / max(int(n_agents) - 1, 1)
        return (self.p_min + idx.astype(c.dtype) * step) * jnp.ones_like(c)

    def closed_form_moments(self, base, n_agents=None):
        if n_agents is None:
            raise ValueError(
                "HeterogeneousBudget moments depend on the agent count; "
                "pass n_agents (e.g. make_controlled_channel(..., n_agents=N))"
            )
        n = int(n_agents)
        mean_b = (self.p_min + self.p_max) / 2.0
        step = (self.p_max - self.p_min) / max(n - 1, 1)
        var_b = 0.0 if n == 1 else step * step * (n * n - 1) / 12.0
        m_c, v_c = float(base.mean), float(base.var)
        m = mean_b * m_c
        m2 = (var_b + mean_b * mean_b) * (v_c + m_c * m_c)
        return m, max(m2 - m * m, 0.0)


# ---------------------------------------------------------------------------
# The effective-gain channel, registered as a first-class channel family.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ControlledChannel(Channel):
    """Effective-gain channel h = c * policy(c) over a base channel.

    Registered in the channel registry as kind ``'controlled'`` with the
    composite structural tag ``controlled:<base_kind>:<PolicyType>``, so
    same-shaped instances batch into one sweep partition.  Construct with
    :func:`make_controlled_channel`, which fills the (m_h, sigma_h^2)
    moments (closed form where available, Monte Carlo otherwise) — the
    debiased update and the theory tables are poisoned by NaN moments, and
    ``OTAConfig``/``batched_channel_arrays`` reject them loudly.
    """

    base: Channel = None  # type: ignore[assignment]
    policy: PowerPolicy = UnitPower()
    # Effective moments; NaN until filled in (dataclass is frozen, so they
    # are passed explicitly by make_controlled_channel).
    _mean: float = float("nan")
    _var: float = float("nan")
    # For per-agent policies: the agent count the moments were baked for
    # (mixture moments depend on it); checked by check_agent_count.
    _n_agents: Optional[int] = None

    def __post_init__(self):
        if self.base is None:
            raise ValueError(
                "ControlledChannel needs a base channel; construct it with "
                "make_controlled_channel(base, policy, ...)"
            )

    def kind_tag(self) -> str:
        base_kind = _channel.channel_kind(self.base)
        if ":" in base_kind:
            raise ValueError("nested ControlledChannel is not supported")
        return f"controlled:{base_kind}:{type(self.policy).__name__}"

    def sample(self, key: jax.Array, shape) -> jax.Array:
        if (self.policy.per_agent and self._n_agents is not None
                and (not shape or shape[-1] != self._n_agents)):
            raise ValueError(
                f"ControlledChannel moments were baked for n_agents="
                f"{self._n_agents} but sample() was asked for agent axis "
                f"{shape[-1] if shape else '(scalar)'}; rebuild with "
                "make_controlled_channel(..., n_agents=<runtime count>)"
            )
        c = self.base.sample(key, shape)
        return c * self.policy.apply(c)

    @property
    def mean(self) -> float:
        return self._mean

    @property
    def var(self) -> float:
        return self._var


def _pack_controlled(channels):
    """Batched-array packer: base params under ``base.``, policy params under
    ``pc.`` (the common ``_mean``/``_var`` columns are added by the caller)."""
    _, base_params = _channel.batched_channel_arrays(
        [ch.base for ch in channels])
    params = {f"base.{k}": v for k, v in base_params.items()}
    for f in dataclasses.fields(channels[0].policy):
        params[f"pc.{f.name}"] = np.array(
            [float(getattr(ch.policy, f.name)) for ch in channels], np.float64
        )
    return params


def _sample_controlled(kind, params, key, shape):
    """Batched sampler: reconstruct base draw + policy from the lane's traced
    scalars — same ops as ControlledChannel.sample, so draws are bitwise
    identical to the concrete dataclass at equal parameter values."""
    _, base_kind, policy_name = kind.split(":")
    base_params = {k[len("base."):]: v for k, v in params.items()
                   if k.startswith("base.")}
    pol = _POLICY_REGISTRY[policy_name](
        **{k[len("pc."):]: v for k, v in params.items() if k.startswith("pc.")}
    )
    c = BatchedChannel(kind=base_kind, params=base_params).sample(key, shape)
    return c * pol.apply(c)


_channel.register_channel(
    "controlled", ControlledChannel,
    packer=_pack_controlled, sampler=_sample_controlled,
)


# ---------------------------------------------------------------------------
# Moments: closed form where known, Monte Carlo fallback.
# ---------------------------------------------------------------------------

def estimate_moments(
    base: Channel,
    policy: PowerPolicy,
    key: jax.Array,
    n: int = 200_000,
    *,
    n_agents: Optional[int] = None,
) -> Tuple[float, float]:
    """Monte Carlo (m_h, sigma_h^2) of the effective gain h = c * p(c).

    Per-agent policies need ``n_agents``: gains are drawn with an explicit
    trailing agent axis and the *mixture* moments over agents are returned.
    """
    if policy.per_agent:
        if not n_agents:
            raise ValueError("per-agent policy moments need n_agents")
        c = base.sample(key, (max(1, n // n_agents), n_agents))
    else:
        c = base.sample(key, (n,))
    h = c * policy.apply(c)
    return float(jnp.mean(h)), float(jnp.var(h))


def closed_form_moments(
    base: Channel, policy: PowerPolicy, *, n_agents: Optional[int] = None
) -> Optional[Tuple[float, float]]:
    """Exact effective moments when the (base, policy) pair has a closed
    form, else None."""
    return policy.closed_form_moments(base, n_agents)


@functools.lru_cache(maxsize=None)
def effective_moments(
    base: Channel,
    policy: PowerPolicy,
    *,
    n_agents: Optional[int] = None,
    n: int = 200_000,
) -> Tuple[float, float]:
    """Effective-gain (m_h, sigma_h^2): closed form if available, otherwise
    Monte Carlo with a fixed documented seed (jax.random.key(0)) so sweep
    packing and per-scenario configs agree deterministically."""
    closed = closed_form_moments(base, policy, n_agents=n_agents)
    if closed is not None:
        return closed
    return estimate_moments(base, policy, jax.random.key(0), n,
                            n_agents=n_agents)


def make_controlled_channel(
    base: Channel,
    policy: PowerPolicy,
    key: Optional[jax.Array] = None,
    n: int = 200_000,
    *,
    n_agents: Optional[int] = None,
) -> ControlledChannel:
    """The documented ControlledChannel constructor: fills the effective
    (m_h, sigma_h^2) via closed form when available, else Monte Carlo.

    ``key`` only matters for the Monte Carlo fallback (default
    jax.random.key(0)); ``n_agents`` is required by per-agent policies.
    """
    closed = closed_form_moments(base, policy, n_agents=n_agents)
    if closed is not None:
        m, v = closed
    else:
        if key is None:
            key = jax.random.key(0)
        m, v = estimate_moments(base, policy, key, n, n_agents=n_agents)
    return ControlledChannel(
        base=base, policy=policy, _mean=m, _var=v,
        _n_agents=n_agents if policy.per_agent else None,
    )


def check_agent_count(channel: Channel, n_agents: int) -> None:
    """Guard against using a ControlledChannel whose per-agent mixture
    moments were baked for a different agent count than it now runs with —
    the sampling would silently follow the runtime count while the debias
    normaliser and theory tables followed the baked one."""
    if (isinstance(channel, ControlledChannel)
            and channel._n_agents is not None
            and channel._n_agents != n_agents):
        raise ValueError(
            f"ControlledChannel moments were baked for n_agents="
            f"{channel._n_agents} but the scenario runs {n_agents} agents; "
            "rebuild it with make_controlled_channel(..., n_agents="
            f"{n_agents})"
        )
