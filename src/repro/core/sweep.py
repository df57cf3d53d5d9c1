"""Batched scenario-sweep engine: the paper's figure grids as ONE program.

The paper's results (Figs. 1-5) — and the wider channel/power-control grids
of the related over-the-air FL literature — are grids of scenarios:
``(channel params, noise_sigma, alpha, n_agents, estimator, power control)``.
Running each grid point through its own ``fedpg.monte_carlo`` call re-traces
and re-compiles a fresh XLA program per point, so a benchmark suite spends
most of its wall time inside the compiler.

This module expresses the grid declaratively and compiles **one program per
structural partition**:

* **structural axes** change the trace shape or graph and force a partition
  split: ``n_agents``, ``batch_m``, ``horizon``, ``n_rounds``, ``gamma``,
  ``estimator``, ``debias``, the channel *family*, the power-control policy
  *type*, the environment *family* (registry kind tag, incl. structural
  sizes like grid dims), the policy, noise on/off, and exact-vs-OTA uplink;
* **continuous axes** (channel parameters, ``noise_sigma``, ``alpha``,
  power-control parameters, environment parameters — wind strengths, slip
  probabilities, Garnet P/l/rho tables) batch inside a single jitted
  program — mapped over scenarios, ``vmap``-ed over Monte-Carlo seeds —
  reusing the existing ``fedpg.run`` round body unchanged.

Exactness contract: a continuous axis that does **not** vary inside a
partition is closed over as the same Python-float literal the per-scenario
path uses, so those lanes are **bit-identical** to ``fedpg.monte_carlo``
under the same PRNG keys (XLA folds literals; re-materialising them as
runtime values can move a multiply and drift the last mantissa bit).  Axes
that do vary are fed as traced scalars via ``BatchedChannel`` /
``OTAConfig.update_scale``, whose float64-precomputed derived constants keep
the channel draws and updates bit-identical as well; likewise the env
registry packs only *varying* env parameters, so constant fields stay
folded literals.  Two exceptions: the debias normaliser when the axes it
depends on — channel parameters, or power-control parameters (effective
moments) — vary within a partition, where ``grad_sq`` may differ in the
final bit (documented in ``Scenario.debias``); and env families whose
dynamics run matvec/quadratic reductions over the traced parameters (LQR),
whose fusions may reassociate the final mantissa bit — elementwise-dynamics
families (particle, cliff-walk, tabular) stay bitwise.

Typical use::

    scenarios = grid(
        channel=[RayleighChannel(), NakagamiChannel(m=0.1, omega=1.0)],
        noise_sigma=[1e-3, 1e-2],
        alpha=[1e-3, 1e-4],
        n_agents=10, batch_m=10, n_rounds=200, debias=True,
    )
    result = sweep(env, policy, scenarios, jax.random.key(0), mc_runs=20)
    print(result.to_csv())
"""
from __future__ import annotations

import dataclasses
import io
import itertools
import math
import time
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fedpg
from repro.core.channel import (
    BatchedChannel, Channel, batched_channel_arrays, channel_kind,
)
from repro.core.fedpg import FedPGConfig, History
from repro.core.ota import OTAConfig
from repro.core.power_control import (
    PowerPolicy, check_agent_count, effective_moments,
)
from repro.rl.envs import (
    batched_env_arrays, build_lane_env, env_kind, robust_eq, values_vary,
)
from repro.rl.envs import check_agent_count as check_env_agent_count
from repro.rl.envs import default_policy as env_default_policy
from repro.service import participation as svc_participation
from repro.service import staleness as svc_staleness
from repro.service.participation import ParticipationConfig
from repro.service.staleness import StalenessConfig
from repro.telemetry import trace as rtrace
from repro.telemetry import probes as _probes
from repro.telemetry.probes import RoundTelemetry, TelemetryConfig

# Modes for laying scenarios into the partition program.  ``vmap`` (default)
# batches lanes into one vectorised computation — fastest on one device, and
# bit-identical to ``monte_carlo`` whenever the debias normaliser is
# partition-constant.  ``map`` runs the lanes through ``lax.map`` (sequential
# inside one program); every lane keeps the exact rank of the unbatched path,
# which is the conservative choice if a platform's batched reductions ever
# reassociate.  ``sharded`` is the vmap program with its lane/MC axes laid
# across a device mesh (``repro.core.distribute``): partitions dispatch
# asynchronously, uneven lane counts pad with masked replicate-lanes, and
# every device runs the ``vmap`` program on its block of lanes (results
# are bit-identical to ``vmap`` on that block, and so on one device to
# ``vmap``; see ``repro.core.distribute``).
MODES = ("map", "vmap", "sharded")


@dataclass(frozen=True)
class Scenario:
    """One grid point: everything a single ``monte_carlo`` call would need.

    ``channel=None`` selects the exact Algorithm-1 uplink (``ota=None``).
    ``debias`` divides the update by the *effective* gain mean ``m_h``: the
    channel mean when ``power_control`` is None (the plain ``OTAConfig``
    convention), and the effective-gain mean ``E[c p(c)]`` — closed form
    where known, deterministic Monte Carlo otherwise — when a policy is set
    (threaded through ``OTAConfig.update_scale`` in float64, so batched
    lanes and the per-scenario path fold in the identical constant).

    ``env=None`` runs the environment ``sweep()`` was called with (the
    pre-env-zoo convention); an env instance makes the workload itself a
    grid axis — the env *family* (registry kind tag) is structural, its
    continuous parameters batch as lanes through the registry packer hooks.
    ``policy=None`` resolves to ``sweep()``'s policy for default-env
    scenarios and to the env family's ``default_policy()`` otherwise.
    """

    channel: Optional[Channel] = None
    noise_sigma: float = 0.0
    alpha: float = 1e-3
    n_agents: int = 10
    batch_m: int = 10
    horizon: int = 20
    gamma: float = 0.99
    n_rounds: int = 200
    estimator: str = "gpomdp"
    power_control: Optional[PowerPolicy] = None
    debias: bool = False
    # streaming round form: lax.scan over agent blocks (structural — it
    # changes the jaxpr, so it splits partitions; see fedpg.make_round_fn)
    agent_blocks: Optional[int] = None
    # round-service axes (fedpg.run(participation=..., staleness=...)):
    # the participation *kind*, debias mode, fault structure, and replay
    # depth are structural; the Bernoulli rate, the fault deadline (under
    # realized debias), and the age-decay batch as lanes
    participation: Optional[ParticipationConfig] = None
    staleness: Optional[StalenessConfig] = None
    env: Any = None
    policy: Any = None
    tag: str = ""  # free-form label carried into tables/CSV

    def fedpg_config(self) -> FedPGConfig:
        return FedPGConfig(
            n_agents=self.n_agents, batch_m=self.batch_m, horizon=self.horizon,
            gamma=self.gamma, alpha=self.alpha, n_rounds=self.n_rounds,
            estimator=self.estimator,
        )

    def effective_moments(self) -> Tuple[float, float]:
        """The effective-gain (m_h, sigma_h^2) this scenario realises —
        including power control — in float64.  This is the pair the
        Theorem-1/2 bounds must be evaluated with."""
        if self.channel is None:
            return 1.0, 0.0
        check_agent_count(self.channel, self.n_agents)
        if self.power_control is None:
            return float(self.channel.mean), float(self.channel.var)
        return effective_moments(self.channel, self.power_control,
                                 n_agents=self.n_agents)

    def ota_config(self) -> Optional[OTAConfig]:
        """The equivalent per-scenario OTAConfig (None for exact uplink)."""
        if self.channel is None:
            return None
        check_agent_count(self.channel, self.n_agents)
        update_scale = None
        if self.debias and self.power_control is not None:
            m_eff, _ = self.effective_moments()
            update_scale = 1.0 / (self.n_agents * m_eff)
        return OTAConfig(
            channel=self.channel, noise_sigma=self.noise_sigma,
            debias=self.debias, power_control=self.power_control,
            update_scale=update_scale,
        )

    def describe(self) -> Dict[str, Any]:
        """Flat, CSV-friendly view of the scenario."""
        chan = "exact" if self.channel is None else _channel_tag(self.channel)
        chan_params = "" if self.channel is None else ";".join(
            f"{f.name}={_fmt_param(getattr(self.channel, f.name))}"
            for f in dataclasses.fields(self.channel)
        )
        pc = "" if self.power_control is None else type(self.power_control).__name__
        pc_params = "" if self.power_control is None else ";".join(
            f"{f.name}={_fmt_param(getattr(self.power_control, f.name))}"
            for f in dataclasses.fields(self.power_control)
        )
        env_tag = "default" if self.env is None else _env_tag(self.env)
        env_params = ""
        if self.env is not None and dataclasses.is_dataclass(self.env):
            env_params = ";".join(
                f"{f.name}={_fmt_param(getattr(self.env, f.name))}"
                for f in dataclasses.fields(self.env)
            )
        pol = "" if self.policy is None else type(self.policy).__name__
        pp = self.participation
        part_kind = "" if pp is None else pp.kind
        part_rate: Any = ""
        if pp is not None:
            part_rate = pp.rate if pp.kind == "bernoulli" else (
                pp.subset if pp.kind == "subset" else "")
        part_debias = "" if pp is None else pp.debias
        faults = ""
        if pp is not None and pp.faults is not None:
            faults = "active" if pp.faults.active else "inactive"
        st = self.staleness
        m_eff, v_eff = self.effective_moments()
        return {
            "tag": self.tag, "channel": chan, "channel_params": chan_params,
            "noise_sigma": self.noise_sigma, "alpha": self.alpha,
            "n_agents": self.n_agents, "batch_m": self.batch_m,
            "horizon": self.horizon, "gamma": self.gamma,
            "n_rounds": self.n_rounds, "estimator": self.estimator,
            "power_control": pc, "power_control_params": pc_params,
            "debias": self.debias,
            "agent_blocks": "" if self.agent_blocks is None
            else self.agent_blocks,
            "participation": part_kind, "participation_rate": part_rate,
            "participation_debias": part_debias, "faults": faults,
            "staleness_max_age": "" if st is None else st.max_age,
            "staleness_decay": "" if st is None else st.decay,
            "env": env_tag, "env_params": env_params,
            "policy": pol, "m_h_eff": m_eff, "sigma_h2_eff": v_eff,
        }


def _fmt_param(v: Any) -> str:
    """Compact field rendering for describe(): numbers as %g, nested
    channel/policy objects (e.g. ControlledChannel.base) as their type,
    array-valued env parameters (TabularMDP tables, per-agent stacks) as
    their shape."""
    if isinstance(v, (int, float)):
        return f"{v:g}"
    if dataclasses.is_dataclass(v):
        return type(v).__name__
    if isinstance(v, (np.ndarray, jax.Array)):
        return f"array{tuple(v.shape)}"
    if isinstance(v, dict):
        return "{" + " ".join(sorted(v)) + "}"
    return str(v)


def _env_tag(env: Any) -> str:
    """Registry kind when available, else the concrete type name (custom
    envs outside the registry still sweep fine as partition constants)."""
    try:
        return env_kind(env)
    except ValueError:
        return type(env).__name__


def resolve_env_policy(scenario: Scenario, env: Any = None, policy: Any = None):
    """The (env, policy) a scenario actually runs: scenario fields override
    the sweep-level defaults; a scenario-specific env with no explicit
    policy resolves through the registry's ``default_policy`` hook (the
    sweep-level policy is for the sweep-level env and would generally
    mismatch the scenario env's observation/action spaces)."""
    e = scenario.env if scenario.env is not None else env
    if e is None:
        raise ValueError(
            "scenario has no env: set Scenario.env or pass sweep(env=...)"
        )
    if scenario.policy is not None:
        p = scenario.policy
    elif scenario.env is None and policy is not None:
        p = policy
    else:
        p = env_default_policy(e)
    check_env_agent_count(e, scenario.n_agents)
    return e, p


def grid(**axes) -> List[Scenario]:
    """Cartesian product of scenario axes.

    Each keyword is a ``Scenario`` field; a list/tuple value is an axis, a
    scalar is a fixed setting.  Axis order follows keyword order, last axis
    fastest — matching nested for-loops over the same lists.
    """
    valid = {f.name for f in dataclasses.fields(Scenario)}
    unknown = set(axes) - valid
    if unknown:
        raise ValueError(f"unknown scenario axes {sorted(unknown)}; "
                         f"choose from {sorted(valid)}")
    names = list(axes)
    values = [v if isinstance(v, (list, tuple)) else [v] for v in axes.values()]
    return [Scenario(**dict(zip(names, combo)))
            for combo in itertools.product(*values)]


# ---------------------------------------------------------------------------
# Partitioning by structural shape.
# ---------------------------------------------------------------------------

def _channel_tag(ch: Channel) -> str:
    """Registry kind when available, else the concrete type name (custom
    channels outside the registry still sweep fine as long as they don't
    vary within a partition)."""
    try:
        return channel_kind(ch)
    except ValueError:
        return type(ch).__name__


def _workload_key(s: Scenario) -> Tuple:
    """The (env, policy) part of the structure key.  The env *family* (kind
    tag, which encodes structural ints like grid sizes) splits partitions;
    same-family instances batch their continuous params as lanes.  The
    policy is structural outright (its params pytree shapes the trace)."""
    env_tag = None if s.env is None else _env_tag(s.env)
    if s.policy is None:
        pol_tag = None
    else:
        try:
            hash(s.policy)
            pol_tag = s.policy
        except TypeError:
            # unhashable policies (params-carrying dataclasses) split by
            # identity: merging distinct instances by type would silently
            # run the prototype's policy for every lane
            pol_tag = (type(s.policy).__name__, id(s.policy))
    return env_tag, pol_tag


def _service_key(s: Scenario) -> Tuple:
    """The round-service part of the structure key.  Normalised first, so
    a config that can never drop an agent shares its partition with plain
    scenarios (byte-identical programs).  The Bernoulli ``rate``, the
    fault ``deadline`` (realized debias only — the expected normaliser is
    a host-side closed form over the deadline, so a traced deadline can't
    feed it), and the staleness ``decay`` are continuous lane axes and
    are sentinel-zeroed out of the key; everything else is structural."""
    p = svc_participation.normalize(s.participation, s.n_agents)
    if p is None:
        return (None, None)
    f = p.faults if (p.faults is not None and p.faults.active) else None
    if f is None:
        f_tag = None
    else:
        dl_tag = -1.0 if p.debias == "realized" else f.deadline
        f_tag = (f.stragglers, dl_tag, f.crashes)
    rate_tag = -1.0 if p.kind == "bernoulli" else 0.0
    p_tag = (p.kind, rate_tag, p.subset, p.debias, f_tag)
    st = svc_staleness.normalize(s.staleness, p)
    st_tag = None if st is None else (st.max_age, -1.0)
    return (p_tag, st_tag)


def _structure_key(s: Scenario) -> Tuple:
    """Everything that changes the trace shape or the computation graph."""
    if s.channel is None:
        # exact uplink: the OTA-only axes don't reach the program — zero
        # them so equivalent exact scenarios share one partition/compile.
        return (s.n_agents, s.batch_m, s.horizon, s.gamma, s.n_rounds,
                s.estimator, False, None, None, False,
                s.agent_blocks) + _service_key(s) + _workload_key(s)
    pc = None if s.power_control is None else type(s.power_control).__name__
    return (s.n_agents, s.batch_m, s.horizon, s.gamma, s.n_rounds,
            s.estimator, s.debias, _channel_tag(s.channel), pc,
            s.noise_sigma > 0.0, s.agent_blocks) + _service_key(s) \
        + _workload_key(s)


@dataclass
class Partition:
    """A structurally-uniform slice of the grid, compiled as one program."""

    indices: List[int]           # positions in the original scenario list
    scenarios: List[Scenario]
    key: Tuple = ()
    wall_time_us: float = 0.0    # compile + execute, filled in by sweep()

    @property
    def proto(self) -> Scenario:
        return self.scenarios[0]

    def varying(self, name: str) -> bool:
        # unhashable values (envs carrying arrays: TabularMDP,
        # HeterogeneousEnv) fall back to identity — distinct instances
        # count as varying, so reuse ONE instance for a partition constant
        return values_vary([getattr(s, name) for s in self.scenarios])


def partition_scenarios(scenarios: Sequence[Scenario]) -> List[Partition]:
    groups: Dict[Tuple, Partition] = {}
    for i, s in enumerate(scenarios):
        k = _structure_key(s)
        part = groups.setdefault(k, Partition(indices=[], scenarios=[], key=k))
        part.indices.append(i)
        part.scenarios.append(s)
    return list(groups.values())


def _norm_const64(s: Scenario) -> float:
    """The per-scenario debias normaliser, in float64: the *effective* gain
    mean under power control, the raw channel mean otherwise (matching
    ``Scenario.ota_config``)."""
    if not s.debias:
        return 1.0
    return s.effective_moments()[0]


def _pack_partition(part: Partition) -> Dict[str, Any]:
    """Stack the axes that actually vary inside this partition.

    Returns a dict of (S,)-shaped float32 arrays (dtypes match what the
    unbatched path would have produced after weak-type promotion); constant
    axes are deliberately left out so the lane builder closes over the same
    Python literals the per-scenario program uses.
    """
    packed: Dict[str, Any] = {}

    def f32(vals64):
        return jnp.asarray(np.asarray(vals64, np.float64), jnp.float32)

    if part.proto.env is not None and part.varying("env"):
        _, env_arrays = batched_env_arrays([s.env for s in part.scenarios])
        # identity-distinct but parameter-identical envs (e.g. two all-equal
        # fleets) pack to nothing: leave them out so the partition takes the
        # replicate-one-lane path instead of vmapping a zero-leaf pytree
        if env_arrays:
            packed["env"] = {k: f32(v) for k, v in env_arrays.items()}
    if part.varying("alpha"):
        packed["alpha"] = f32([s.alpha for s in part.scenarios])
    if part.proto.channel is not None:
        if part.varying("noise_sigma"):
            packed["noise_sigma"] = f32([s.noise_sigma for s in part.scenarios])
        if part.varying("channel"):
            kind, arrays = batched_channel_arrays(
                [s.channel for s in part.scenarios])
            packed["channel"] = {k: f32(v) for k, v in arrays.items()}
        if part.proto.power_control is not None and part.varying("power_control"):
            fields = dataclasses.fields(part.proto.power_control)
            packed["power_control"] = {
                f.name: f32([float(getattr(s.power_control, f.name))
                             for s in part.scenarios])
                for f in fields
            }
        # the debias normaliser follows whichever axis moves the effective
        # moments — channel params or power-control params
        if part.proto.debias and (part.varying("channel")
                                  or "power_control" in packed):
            packed["update_scale"] = f32([
                1.0 / (s.n_agents * _norm_const64(s))
                for s in part.scenarios
            ])
    # round-service lane axes: structure keying guarantees every scenario
    # here normalises to the same shape as the prototype, so only the
    # continuous knobs can differ
    p0 = svc_participation.normalize(part.proto.participation,
                                     part.proto.n_agents)
    if p0 is not None:
        parts_n = [svc_participation.normalize(s.participation, s.n_agents)
                   for s in part.scenarios]
        if p0.kind == "bernoulli":
            rates = [float(p.rate) for p in parts_n]
            if values_vary(rates):
                packed["participation_rate"] = f32(rates)
        if p0.debias == "realized" and p0.faults is not None \
                and p0.faults.active:
            deadlines = [float(p.faults.deadline) for p in parts_n]
            if values_vary(deadlines):
                packed["participation_deadline"] = f32(deadlines)
        st0 = svc_staleness.normalize(part.proto.staleness, p0)
        if st0 is not None:
            decays = [float(svc_staleness.normalize(s.staleness, pn).decay)
                      for s, pn in zip(part.scenarios, parts_n)]
            if values_vary(decays):
                packed["staleness_decay"] = f32(decays)
    return packed


def _make_lane(env, policy, part: Partition,
               telemetry: Optional[TelemetryConfig] = None):
    """Build lane(packed_slice, keys) -> History(stacked over mc_runs).

    ``packed_slice`` holds only the *varying* axes (scalar tracers inside
    the partition program); everything constant is closed over exactly as
    the per-scenario path would.  ``keys`` stays a runtime argument — just
    like ``monte_carlo`` passes it — so XLA cannot constant-fold the PRNG
    chain differently than the unbatched program.
    """
    proto = part.proto
    base_cfg = proto.fedpg_config()
    # The scenario-resolved workload: proto env/policy override the sweep
    # defaults, same resolution the per-scenario reference path uses.
    lane_env, lane_policy = resolve_env_policy(proto, env, policy)
    # The per-scenario OTAConfig of the prototype: every constant axis —
    # including a power-control-derived update_scale literal — is closed
    # over exactly as the unbatched path would fold it in.
    proto_ota = proto.ota_config()
    # Registry kind, only needed when channel params vary (BatchedChannel);
    # constant non-registry channels are closed over like any other.
    chan_kind = (channel_kind(proto.channel)
                 if proto.channel is not None and part.varying("channel")
                 else None)
    # Likewise for env params: the registry builder reconstructs a lane env
    # from traced scalars; constant envs are closed over as-is.
    env_tag = (env_kind(proto.env)
               if proto.env is not None and part.varying("env")
               else None)
    pc_type = None if proto.power_control is None else type(proto.power_control)
    # normalised prototype service configs: constant partitions close over
    # them whole (same literals as the per-scenario path); varying knobs
    # are re-injected as traced lane scalars below
    proto_part = svc_participation.normalize(proto.participation,
                                             proto.n_agents)
    proto_stale = svc_staleness.normalize(proto.staleness, proto_part)

    def lane(packed: Dict[str, Any], keys: jax.Array) -> History:
        env_l = lane_env
        if "env" in packed:
            env_l = build_lane_env(env_tag, lane_env, packed["env"])
        cfg = base_cfg
        if "alpha" in packed:
            cfg = replace(cfg, alpha=packed["alpha"])
        ota = proto_ota
        if ota is not None:
            if "channel" in packed:
                channel: Channel = BatchedChannel(
                    kind=chan_kind, params=packed["channel"])
                ota = replace(ota, channel=channel)
            if "noise_sigma" in packed:
                ota = replace(ota, noise_sigma=packed["noise_sigma"])
            if "power_control" in packed:
                ota = replace(ota, power_control=pc_type(**packed["power_control"]))
            if "update_scale" in packed:
                ota = replace(ota, update_scale=packed["update_scale"])
        part_l = proto_part
        if "participation_rate" in packed:
            part_l = replace(part_l, rate=packed["participation_rate"])
        if "participation_deadline" in packed:
            part_l = replace(part_l, faults=replace(
                part_l.faults, deadline=packed["participation_deadline"]))
        stale_l = proto_stale
        if "staleness_decay" in packed:
            stale_l = replace(stale_l, decay=packed["staleness_decay"])
        return jax.vmap(
            lambda k: fedpg.run(env_l, lane_policy, cfg, k, ota=ota,
                                telemetry=telemetry,
                                agent_blocks=proto.agent_blocks,
                                participation=part_l,
                                staleness=stale_l)[1]
        )(keys)

    return lane


def lane_program(env, policy, part: Partition, mc_runs: int = 2,
                 telemetry: Optional[TelemetryConfig] = None):
    """The partition's program, exposed for structural inspection.

    Returns ``(packed, fn, keys)`` where ``fn(packed, keys)`` is exactly the
    callable ``sweep()`` would jit for this partition in ``mode="vmap"``
    (vmapped over lanes when anything varies, the single replicate lane
    otherwise) and ``keys`` is a ``split``-shaped example argument.  This is
    the hook ``repro.analyze.contracts.check_lane_contract`` traces: the
    bitwise-lane exactness contract says ``packed`` holds *only* the axes
    that vary inside the partition — every packed leaf must differ across
    lanes and must survive as a consumed dynamic input of the traced
    program, while constant axes stay closed-over Python literals.
    """
    packed = _pack_partition(part)
    lane = _make_lane(env, policy, part,
                      telemetry=fedpg._active_telemetry(telemetry))
    keys = jax.random.split(jax.random.key(0), mc_runs)
    fn = jax.vmap(lane, in_axes=(0, None)) if packed else lane
    return packed, fn, keys


# ---------------------------------------------------------------------------
# Results.
# ---------------------------------------------------------------------------

@dataclass
class SweepResult:
    """Histories for every scenario, plus grid/partition bookkeeping.

    ``history`` leaves have shape ``(n_scenarios, mc_runs, n_rounds)`` in
    the original scenario order (a 1-D object array of ``(mc_runs, K_i)``
    arrays when the grid varies ``n_rounds``).  ``mode``/``n_devices``
    record how the partitions executed (``n_devices > 1`` only for
    ``mode="sharded"``).
    """

    scenarios: List[Scenario]
    history: History
    partitions: List[Partition] = field(default_factory=list)
    mc_runs: int = 0
    mode: str = "vmap"
    n_devices: int = 1

    @property
    def n_partitions(self) -> int:
        return len(self.partitions)

    def scenario_time_us(self, i: int) -> float:
        """Per-(scenario, MC run) share of the owning partition's wall time
        — structurally different scenarios keep distinguishable timings.
        Synchronous modes charge compile + execute; ``sharded`` partitions
        dispatch asynchronously, so their wall time spans dispatch to
        results-ready (which for later partitions includes waiting on
        earlier ones still occupying the mesh)."""
        for part in self.partitions:
            if i in part.indices:
                return part.wall_time_us / (len(part.indices)
                                            * max(self.mc_runs, 1))
        raise IndexError(f"scenario {i} not in any partition")

    @property
    def n_compiles(self) -> int:
        """Compiled partition programs: one jit per structural shape."""
        return len(self.partitions)

    def __len__(self) -> int:
        return len(self.scenarios)

    def scenario_history(self, i: int) -> History:
        # tree.map (not a positional splat) so the optional telemetry
        # subtree — None when probes were off — passes through untouched.
        return jax.tree.map(lambda x: np.asarray(x[i]), self.history)

    def telemetry_summary(self, i: int) -> Optional[Dict[str, Any]]:
        """NaN/inf-aware mean of each in-jit probe for scenario ``i`` (see
        ``repro.telemetry.probes.summarize``); None when the sweep ran
        without telemetry."""
        if self.history.telemetry is None:
            return None
        tel = jax.tree.map(lambda x: np.asarray(x[i]),
                           self.history.telemetry)
        return _probes.summarize(tel)

    def final_reward(self, i: int, tail: int = 20) -> float:
        # jnp reductions, matching benchmarks.common exactly.
        return float(jnp.mean(jnp.asarray(self.history.rewards[i])[:, -tail:]))

    def avg_grad_sq(self, i: int) -> float:
        """(1/K) sum_k ||grad J||^2, averaged over MC runs (Fig. 2/5)."""
        return float(jnp.mean(jnp.asarray(self.history.grad_sq[i])))

    def index(self, **fields) -> int:
        """Position of the first scenario matching all given field values.

        ``env=`` matches by identity first, then equality — envs carrying
        arrays (TabularMDP, HeterogeneousEnv) compare ambiguously under
        ``==``, so pass the same instance the scenario was built with.
        """
        for i, s in enumerate(self.scenarios):
            if all(robust_eq(getattr(s, k), v) for k, v in fields.items()):
                return i
        raise KeyError(f"no scenario matches {fields}")

    def to_dicts(self, tail: int = 20) -> List[Dict[str, Any]]:
        rows = []
        for i, s in enumerate(self.scenarios):
            row = {"index": i, **s.describe()}
            row["final_reward"] = self.final_reward(i, tail)
            row["avg_grad_sq"] = self.avg_grad_sq(i)
            row["mean_gain"] = float(np.mean(np.asarray(self.history.gain_mean[i])))
            tel = self.telemetry_summary(i)
            if tel is not None:
                for k, v in tel.items():
                    row[f"telemetry_{k}"] = v
            rows.append(row)
        return rows

    def to_csv(self, path: Optional[str] = None, tail: int = 20) -> str:
        rows = self.to_dicts(tail)
        buf = io.StringIO()
        cols = list(rows[0]) if rows else []
        buf.write(",".join(cols) + "\n")
        for row in rows:
            buf.write(",".join(_csv_cell(row[c]) for c in cols) + "\n")
        text = buf.getvalue()
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text


def _csv_cell(v: Any) -> str:
    if isinstance(v, float):
        return f"{v:.6g}"
    s = str(v)
    if any(c in s for c in ',"\n\r'):  # RFC-4180 quoting
        return '"' + s.replace('"', '""') + '"'
    return s


def _stack_histories(arrs: List[np.ndarray]) -> np.ndarray:
    """Stack per-scenario arrays; ragged round counts (``n_rounds`` is a
    structural axis) fall back to a 1-D object array so ``history.x[i]``
    indexing keeps working."""
    if len({a.shape for a in arrs}) == 1:
        return np.stack(arrs)
    out = np.empty(len(arrs), dtype=object)
    for i, a in enumerate(arrs):
        out[i] = a
    return out


# ---------------------------------------------------------------------------
# The engine.
# ---------------------------------------------------------------------------

def sweep(
    env,
    policy,
    scenarios: Sequence[Scenario],
    key: jax.Array,
    mc_runs: int,
    *,
    mode: str = "vmap",
    mesh: Any = None,
    telemetry: Optional[TelemetryConfig] = None,
) -> SweepResult:
    """Run every scenario x mc_runs, one compiled program per partition.

    All scenarios share the same Monte-Carlo key set ``split(key, mc_runs)``
    — exactly what per-scenario ``fedpg.monte_carlo(..., key, mc_runs)``
    calls would use, so results are directly comparable across scenarios
    and against the unbatched path.

    ``env``/``policy`` are the defaults for scenarios that don't carry their
    own (see ``Scenario.env``); a grid where every scenario names an env may
    pass ``env=None, policy=None``.

    ``mode="sharded"`` lays each partition's (lanes x mc_runs) batch across
    a device mesh (``mesh=`` from ``launch.mesh.make_sweep_mesh``, default
    all devices on the lane axis), dispatches partitions asynchronously and
    defers ``block_until_ready`` to result materialisation; each device
    runs the ``mode="vmap"`` program on its block of lanes, bit-identical
    to ``mode="vmap"`` on that block (on one device, the whole partition;
    see ``repro.core.distribute``).

    ``telemetry`` (a :class:`repro.telemetry.TelemetryConfig` with active
    probes) fills ``SweepResult.history.telemetry`` with ``(S, mc, K)``
    per-round probe stacks; telemetry off leaves every partition program
    bitwise identical to today's.  Partition execution is traced as
    ``repro.telemetry.trace`` spans either way.
    """
    telemetry = fedpg._active_telemetry(telemetry)
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    sharded = mode == "sharded"
    if mesh is not None and not sharded:
        raise ValueError("mesh= is only meaningful with mode='sharded'")
    scenarios = list(scenarios)
    if not scenarios:
        raise ValueError("empty scenario list")
    keys = jax.random.split(key, mc_runs)
    parts = partition_scenarios(scenarios)
    n_devices = 1
    if sharded:
        from repro.core import distribute
        if mesh is None:
            mesh = distribute.default_sweep_mesh()
        n_devices = mesh.size

    out_hist: List[Optional[History]] = [None] * len(scenarios)

    def collect(part: Partition, stacked: History, lanes: bool) -> None:
        """Materialise one partition: ONE device->host transfer per leaf,
        sliced on the host (no per-scenario eager gathers to dispatch or
        compile).  ``lanes=False`` is the replicate path — every scenario
        shares the single history; with lanes, trailing padded
        replicate-lanes (sharded mode) are masked off by the j < n slice."""
        s_np = jax.tree.map(np.asarray, stacked)
        for j, idx in enumerate(part.indices):
            out_hist[idx] = (jax.tree.map(lambda a: a[j], s_np)
                             if lanes else s_np)

    pending: List[Tuple[Partition, float, Any, Any]] = []
    for part in parts:
        packed = _pack_partition(part)
        lane = _make_lane(env, policy, part, telemetry=telemetry)
        if sharded:
            # async: launch and move on — drained after the loop.  A span
            # can't straddle the deferred materialisation, so the dispatch
            # -> ready wall time keeps a raw clock.
            t0 = time.perf_counter()  # repro: noqa[raw-timing]
            stacked, placement = distribute.dispatch_partition(
                lane, packed, keys, mesh)
            pending.append((part, t0, stacked, placement))
            continue
        # One jit per loop iteration is the design here, not the recompile
        # bug repro.analyze's jit-in-loop rule hunts: each partition is a
        # structurally distinct program and compiles exactly once.
        with rtrace.span("partition", mode=mode,
                         scenarios=len(part.indices)) as sp:
            if not packed:
                # Every scenario in the partition is identical: run one lane
                # and replicate its history.
                stacked, lanes = jax.jit(lane)({}, keys), False  # repro: noqa[jit-in-loop]
            elif mode == "vmap":
                stacked = jax.jit(jax.vmap(lane, in_axes=(0, None)))(  # repro: noqa[jit-in-loop]
                    packed, keys)
                lanes = True
            else:
                stacked = jax.jit(  # repro: noqa[jit-in-loop]
                    lambda pk, ks: jax.lax.map(lambda p: lane(p, ks), pk)
                )(packed, keys)
                lanes = True
            jax.block_until_ready(stacked)
        part.wall_time_us = sp.duration_us
        collect(part, stacked, lanes)

    # sharded drain: the deferred block_until_ready — results materialise
    # here, padded replicate-lanes are masked off, wall time spans
    # dispatch -> ready per partition
    for part, t0, stacked, placement in pending:
        with rtrace.span("materialize", scenarios=len(part.indices)):
            jax.block_until_ready(stacked)
        part.wall_time_us = (time.perf_counter() - t0) * 1e6  # repro: noqa[raw-timing]
        collect(part, stacked, placement.n_lanes > 0)

    history = History(
        rewards=_stack_histories([h.rewards for h in out_hist]),
        grad_sq=_stack_histories([h.grad_sq for h in out_hist]),
        gain_mean=_stack_histories([h.gain_mean for h in out_hist]),
        # per-field None guard: the service probe fields (participation
        # rate/drift, staleness age) exist only for service partitions —
        # a mixed sweep keeps the common probes stacked and drops a
        # service-only field unless every scenario carries it
        telemetry=None if out_hist[0].telemetry is None else RoundTelemetry(
            *((None if any(getattr(h.telemetry, f) is None
                           for h in out_hist)
               else _stack_histories([getattr(h.telemetry, f)
                                      for h in out_hist]))
              for f in RoundTelemetry._fields)),
    )
    return SweepResult(scenarios=scenarios, history=history, partitions=parts,
                       mc_runs=mc_runs, mode=mode, n_devices=n_devices)
