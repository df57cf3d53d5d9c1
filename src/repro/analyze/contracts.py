"""Layer 2: trace-level contract checkers.

Where the AST rules (:mod:`repro.analyze.engine`) read source, these
checkers import the real registries, trace representative
(env x channel x uplink) programs through the hooks the core modules
expose (``sweep.lane_program``, ``ota.uplink_jaxpr``,
``envs.registered_envs``), and assert structural properties of the
resulting jaxprs / compiled artifacts:

``lane-contract``
    The sweep engine's bitwise-exactness invariant, checked structurally
    rather than via golden traces: for every registered env family, a
    two-lane partition must pack *exactly* the varying axes (set equality
    against an independent re-derivation from the scenario list), every
    packed leaf must actually differ across lanes (a constant promoted to
    a dynamic argument un-folds an XLA literal and can drift the last
    mantissa bit), every packed leaf must survive as a *consumed* input
    variable of the traced lane program (a packed-but-unread leaf means a
    lane silently runs the prototype's value), and a fully-constant
    partition must pack to ``{}`` (the replicate path).

``wire-dtype``
    No ``convert_element_type`` float narrowing anywhere in the uplink
    jaxpr, except the sanctioned ``OTAConfig.wire_dtype`` bf16 hop — and
    when ``wire_dtype="bfloat16"`` is requested, the hop must actually
    appear.

``compile-budget``
    A sweep compiles at most one program per structural partition (plus
    bounded slack), and repeated ``fedpg.monte_carlo`` calls with equal
    configs reuse the cached executable (zero recompiles on the second
    call).  Counting uses :mod:`repro.analyze.budget`.

``collective-audit``
    The ``agent_mesh`` shard_map path's compiled HLO contains only the
    expected collective kinds (psum -> all-reduce); an unexpected
    all-gather / all-to-all / reduce-scatter means a resharding snuck into
    the uplink.  The streamed (``agent_blocks``) form — including a
    non-dividing, phantom-padded fleet — is held to the same psum-only
    contract.  Skipped (with a report note) on single-device hosts.

``stream-contract``
    The streaming (``agent_blocks``) forms' memory invariant, checked
    structurally: every ``scan``/``while`` carry aval in the blocked
    uplink jaxpr and in the blocked round program must be *identical*
    across two fleet sizes at a fixed block size.  A carry that grows
    with ``n_agents`` means the streamed form secretly materialises the
    agent axis and the O(block × d) claim is false.

``participation-contract``
    The round service's normaliser liveness (realized debias must
    consume the PRNG key, expected debias must not), the sweep packing
    of the continuous service knobs (rate / deadline / decay as live
    lanes, structural knobs as partition splits, never-dropping configs
    folded into the plain partition), and a ``key-reuse`` hygiene scan
    of ``src/repro/service``.

Checkers emit the same :class:`~repro.analyze.findings.Finding` records as
the AST layer; source anchors point at the module that owns the violated
invariant.  jax is imported lazily so ``--ast-only`` runs never pay for it.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

from repro.analyze.findings import Finding, Report

_CHECKS: Dict[str, Callable[[Report], None]] = {}


def register_check(name: str):
    def deco(fn):
        _CHECKS[name] = fn
        return fn
    return deco


def all_checks() -> Dict[str, Callable[[Report], None]]:
    return dict(_CHECKS)


def run_contracts(report: Report,
                  checks: Optional[Sequence[str]] = None) -> Report:
    """Run the named trace-level checks (default: all) into ``report``."""
    names = list(checks) if checks is not None else sorted(_CHECKS)
    for name in names:
        if name not in _CHECKS:
            raise KeyError(
                f"unknown contract check {name!r}; known: {sorted(_CHECKS)}")
        _CHECKS[name](report)
    return report


def _finding(rule: str, path: str, message: str,
             severity: str = "error") -> Finding:
    return Finding(rule=rule, severity=severity, path=path, line=0,
                   message=message)


# ---------------------------------------------------------------------------
# lane-contract
# ---------------------------------------------------------------------------

_SWEEP_PATH = "src/repro/core/sweep.py"
_OTA_PATH = "src/repro/core/ota.py"
_FEDPG_PATH = "src/repro/core/fedpg.py"

# Tiny-but-real run shape shared by every traced program below.
_TINY = dict(n_agents=2, batch_m=1, horizon=3, n_rounds=2)


def family_instances(name: str) -> Optional[list]:
    """Two same-kind instances of a registered family differing in a
    continuous parameter (``None`` when the family has no continuous axis).

    Default-packer families perturb their first declared-float field;
    array-parameter families (``tabular``, ``hetero``) get explicit
    constructions that exercise their custom packer hooks.
    """
    import jax

    from repro.rl.envs import is_float_field, make_env

    if name == "tabular":
        from repro.rl.envs.tabular import garnet
        return [garnet(jax.random.key(11)), garnet(jax.random.key(12))]
    if name == "hetero":
        from repro.rl.envs import WindyLandmarkNav, make_heterogeneous_env
        return [
            make_heterogeneous_env([WindyLandmarkNav(wind=0.0),
                                    WindyLandmarkNav(wind=0.1)]),
            make_heterogeneous_env([WindyLandmarkNav(wind=0.05),
                                    WindyLandmarkNav(wind=0.2)]),
        ]
    proto = make_env(name)
    ffields = [f for f in dataclasses.fields(proto) if is_float_field(f)]
    if not ffields:
        return None
    f = ffields[0]
    other = dataclasses.replace(
        proto, **{f.name: float(getattr(proto, f.name)) * 1.5 + 0.125})
    return [proto, other]


def _expected_packed_keys(part) -> set:
    """Independent re-derivation of which axes must be packed: exactly the
    axes whose values vary across the partition's scenarios (env only when
    the registry packer yields varying parameters)."""
    from repro.rl.envs import batched_env_arrays

    scens = part.scenarios
    proto = part.proto
    expected = set()
    if proto.env is not None and part.varying("env"):
        _, arrays = batched_env_arrays([s.env for s in scens])
        if arrays:
            expected.add("env")
    if part.varying("alpha"):
        expected.add("alpha")
    if proto.channel is not None:
        if part.varying("noise_sigma"):
            expected.add("noise_sigma")
        if part.varying("channel"):
            expected.add("channel")
        if proto.power_control is not None and part.varying("power_control"):
            expected.add("power_control")
        if proto.debias and ("channel" in expected
                             or "power_control" in expected):
            expected.add("update_scale")
    # round-service lane axes (see sweep._pack_partition): Bernoulli rate,
    # fault deadline (realized debias only) and staleness decay batch
    from repro.service import participation as svc_participation
    from repro.service import staleness as svc_staleness

    p0 = svc_participation.normalize(proto.participation, proto.n_agents)
    if p0 is not None:
        pn = [svc_participation.normalize(s.participation, s.n_agents)
              for s in scens]
        if p0.kind == "bernoulli" \
                and len({float(p.rate) for p in pn}) > 1:
            expected.add("participation_rate")
        if p0.debias == "realized" and p0.faults is not None \
                and p0.faults.active \
                and len({float(p.faults.deadline) for p in pn}) > 1:
            expected.add("participation_deadline")
        st0 = svc_staleness.normalize(proto.staleness, p0)
        if st0 is not None and len(
                {float(svc_staleness.normalize(s.staleness, q).decay)
                 for s, q in zip(scens, pn)}) > 1:
            expected.add("staleness_decay")
    return expected


def _check_one_partition(report: Report, scens, label: str) -> None:
    """The structural lane-contract assertions for one scenario list that
    must form a single partition."""
    import jax
    from jax.extend import core as jex_core
    import numpy as np

    from repro.core.sweep import lane_program, partition_scenarios

    parts = partition_scenarios(scens)
    if len(parts) != 1:
        report.findings.append(_finding(
            "lane-contract", _SWEEP_PATH,
            f"{label}: continuous-axis grid split into {len(parts)} "
            "partitions (a continuous axis leaked into _structure_key)"))
        return
    part = parts[0]
    packed, fn, keys = lane_program(None, None, part)

    expected = _expected_packed_keys(part)
    if set(packed) != expected:
        report.findings.append(_finding(
            "lane-contract", _SWEEP_PATH,
            f"{label}: packed axes {sorted(packed)} != varying axes "
            f"{sorted(expected)} — constant axes must stay closed-over "
            "literals, varying axes must be packed"))
        return

    # trace the lane program once; a packed leaf is "live" when its input
    # variable is consumed by some equation (or returned)
    closed = jax.make_jaxpr(fn)(packed, keys)
    jaxpr = closed.jaxpr
    leaves = jax.tree_util.tree_flatten_with_path(packed)[0]
    invars = jaxpr.invars[:len(leaves)]
    used = set()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not isinstance(v, jex_core.Literal):
                used.add(v)
    for v in jaxpr.outvars:
        if not isinstance(v, jex_core.Literal):
            used.add(v)

    # Channel / power-control objects pack WHOLESALE by design: all fields
    # of the varying dataclass (plus float64-precomputed derived constants
    # like BatchedChannel's _mean) travel as lane parameters, so individual
    # leaves may legitimately be constant or unused — but the object as a
    # whole must still vary and feed the trace.  Everything else packs
    # per-axis and is held to the strict leaf-level contract.
    wholesale = {"channel", "power_control"}
    n_lanes = len(part.scenarios)
    axis_varies: Dict[str, bool] = {}
    axis_live: Dict[str, bool] = {}
    for (path, leaf), var in zip(leaves, invars):
        axis = str(getattr(path[0], "key", path[0]))
        pstr = jax.tree_util.keystr(path)
        arr = np.asarray(leaf)
        if arr.shape[0] != n_lanes:
            report.findings.append(_finding(
                "lane-contract", _SWEEP_PATH,
                f"{label}: packed leaf {pstr} lane axis {arr.shape[0]} != "
                f"{n_lanes} lanes"))
            continue
        varies = not all(np.array_equal(arr[0], arr[j])
                         for j in range(1, n_lanes))
        live = var in used
        axis_varies[axis] = axis_varies.get(axis, False) or varies
        axis_live[axis] = axis_live.get(axis, False) or live
        if axis in wholesale:
            continue
        if not varies:
            report.findings.append(_finding(
                "lane-contract", _SWEEP_PATH,
                f"{label}: packed leaf {pstr} is identical across lanes — "
                "a partition constant was promoted to a dynamic argument "
                "(un-folds the XLA literal the per-scenario path uses)"))
        if not live:
            report.findings.append(_finding(
                "lane-contract", _SWEEP_PATH,
                f"{label}: packed leaf {pstr} is a dead input of the lane "
                "program — its lanes silently run the prototype's folded "
                "value"))
    for axis in sorted(set(axis_varies) & wholesale):
        if not axis_varies[axis]:
            report.findings.append(_finding(
                "lane-contract", _SWEEP_PATH,
                f"{label}: packed object {axis!r} is identical across all "
                "lanes — a partition-constant object was promoted to "
                "dynamic arguments"))
        if not axis_live[axis]:
            report.findings.append(_finding(
                "lane-contract", _SWEEP_PATH,
                f"{label}: no leaf of packed object {axis!r} reaches the "
                "lane program — its lanes silently run the prototype"))


@register_check("lane-contract")
def check_lane_contract(report: Report,
                        families: Optional[Sequence[str]] = None) -> None:
    from repro.core.channel import NakagamiChannel, RayleighChannel
    from repro.core.power_control import TruncatedInversion
    from repro.core.sweep import Scenario, partition_scenarios
    from repro.rl.envs import make_env, registered_envs

    names = list(families) if families is not None else sorted(registered_envs())
    chan = RayleighChannel()
    for name in names:
        envs = family_instances(name)
        if envs is None:
            # no continuous env axis: alpha still varies, env stays constant
            report.skipped.append(
                f"lane-contract: env family {name!r} has no continuous "
                "parameter; alpha-axis coverage only")
            proto = make_env(name)
            envs = [proto, proto]
        scens = [
            Scenario(channel=chan, noise_sigma=1e-3, alpha=a, env=e, **_TINY)
            for a, e in zip((1e-3, 2e-3), envs)
        ]
        _check_one_partition(report, scens, f"family {name!r}")

    # the uplink axes: channel params + power control + noise + debias vary
    # together inside one landmark partition, so BatchedChannel packing and
    # the update_scale normaliser are exercised too
    env = make_env("landmark")
    scens = [
        Scenario(channel=NakagamiChannel(m=m, omega=om), noise_sigma=ns,
                 alpha=1e-3, env=env, debias=True,
                 power_control=TruncatedInversion(c_min=c), **_TINY)
        for m, om, ns, c in ((0.5, 1.0, 1e-3, 0.05), (1.5, 2.0, 1e-2, 0.1))
    ]
    _check_one_partition(report, scens, "uplink axes (channel/pc/noise)")

    # a fully-constant partition must take the replicate path: packed == {}
    from repro.core.sweep import _pack_partition
    const = [Scenario(channel=chan, noise_sigma=1e-3, alpha=1e-3, env=env,
                      **_TINY)] * 2
    part = partition_scenarios(const)[0]
    packed = _pack_partition(part)
    if packed:
        report.findings.append(_finding(
            "lane-contract", _SWEEP_PATH,
            f"identical-scenario partition packed {sorted(packed)}; "
            "constants must stay closed-over literals (replicate path)"))


# ---------------------------------------------------------------------------
# wire-dtype
# ---------------------------------------------------------------------------

def _iter_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every sub-jaxpr reachable through eqn params
    (pjit, scan, cond, custom_jvp, ...)."""
    from jax.extend import core as jex_core

    yield jaxpr
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for v in vals:
                if isinstance(v, jex_core.ClosedJaxpr):
                    yield from _iter_jaxprs(v.jaxpr)
                elif isinstance(v, jex_core.Jaxpr):
                    yield from _iter_jaxprs(v)


def narrowing_converts(closed_jaxpr) -> List[tuple]:
    """Every float->smaller-float ``convert_element_type`` in the jaxpr
    tree, as ``(src_dtype, dst_dtype)`` pairs."""
    import numpy as np
    import jax.numpy as jnp

    hits = []
    for jx in _iter_jaxprs(closed_jaxpr.jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            src = eqn.invars[0].aval.dtype
            dst = np.dtype(eqn.params["new_dtype"])
            if (jnp.issubdtype(src, jnp.floating)
                    and jnp.issubdtype(dst, jnp.floating)
                    and dst.itemsize < np.dtype(src).itemsize):
                hits.append((str(src), str(dst)))
    return hits


@register_check("wire-dtype")
def check_wire_dtype(report: Report) -> None:
    from repro.core.channel import RayleighChannel
    from repro.core.ota import OTAConfig, uplink_jaxpr

    native = OTAConfig(channel=RayleighChannel(), noise_sigma=1e-3,
                       debias=True)
    bf16 = dataclasses.replace(native, wire_dtype="bfloat16")

    for apply_form in (False, True):
        form = "aggregate_apply" if apply_form else "aggregate"
        for backend in ("xla", "pallas"):
            # no config may narrow floats without asking for it — and the
            # knob is documented pallas-only, so xla/bf16 must stay native
            for cfg, tag in ((None, "exact"), (native, "native"),
                             *(((bf16, "bf16"),) if backend == "xla" else ())):
                hits = narrowing_converts(
                    uplink_jaxpr(cfg, apply=apply_form, backend=backend))
                if hits:
                    report.findings.append(_finding(
                        "wire-dtype", _OTA_PATH,
                        f"{form}/{backend}/{tag}: unsanctioned float "
                        f"narrowing on the uplink: {hits} (only "
                        "OTAConfig.wire_dtype on the pallas backend may "
                        "narrow)"))
        # the sanctioned hop: pallas + wire_dtype="bfloat16" must narrow to
        # bf16, and to nothing else
        hits = narrowing_converts(
            uplink_jaxpr(bf16, apply=apply_form, backend="pallas"))
        bad = [h for h in hits if h[1] != "bfloat16"]
        if bad:
            report.findings.append(_finding(
                "wire-dtype", _OTA_PATH,
                f"{form}/pallas/bf16: narrowing beyond the sanctioned bf16 "
                f"hop: {bad}"))
        if not hits:
            report.findings.append(_finding(
                "wire-dtype", _OTA_PATH,
                f"{form}/pallas/bf16: wire_dtype='bfloat16' produced no "
                "bf16 hop — the wire-dtype knob is being ignored"))


# ---------------------------------------------------------------------------
# compile-budget
# ---------------------------------------------------------------------------

# One partition program per structural shape, plus this much slack for
# residual tiny dispatches the warm pass could not anticipate.
_COMPILE_SLACK = 1


@register_check("compile-budget")
def check_compile_budget(report: Report) -> None:
    import jax

    from repro.analyze import budget
    from repro.core import fedpg
    from repro.core.channel import RayleighChannel
    from repro.core.sweep import (
        grid, partition_scenarios, resolve_env_policy, sweep,
    )
    from repro.rl.envs import WindyLandmarkNav

    budget.warm_eager_helpers()
    fedpg.clear_compilation_cache()

    scens = grid(channel=[None, RayleighChannel()], noise_sigma=1e-3,
                 alpha=[1e-3, 2e-3],
                 env=[WindyLandmarkNav(wind=w) for w in (0.0, 0.2)],
                 **_TINY)
    n_parts = len(partition_scenarios(scens))
    key = jax.random.key(5)
    with budget.CompileCounter() as c:
        sweep(None, None, scens, key, 2)
    if c.count > n_parts + _COMPILE_SLACK:
        report.findings.append(_finding(
            "compile-budget", _SWEEP_PATH,
            f"sweep over {len(scens)} scenarios / {n_parts} partitions "
            f"compiled {c.count} programs (budget {n_parts} + "
            f"{_COMPILE_SLACK} slack) — a lane axis is splitting the "
            "partition program"))

    # repeated monte_carlo with equal configs must reuse the cached
    # executable: the recompile-per-call bug the _compiled_* caches fixed
    s = scens[-1]
    env, policy = resolve_env_policy(s)
    cfg, ota = s.fedpg_config(), s.ota_config()
    fedpg.monte_carlo(env, policy, cfg, key, 2, ota=ota)
    with budget.CompileCounter() as c2:
        fedpg.monte_carlo(env, policy, cfg, jax.random.key(6), 2, ota=ota)
    if c2.count != 0:
        report.findings.append(_finding(
            "compile-budget", _FEDPG_PATH,
            f"repeated monte_carlo with equal configs recompiled "
            f"{c2.count} program(s); the compiled-callable cache is not "
            "keying correctly"))


# ---------------------------------------------------------------------------
# collective-audit
# ---------------------------------------------------------------------------

# psum lowers to all-reduce; anything else on the agent-sharded uplink is a
# resharding that should not be there.
_EXPECTED_COLLECTIVES = frozenset({"all-reduce"})

# SPMD-partitioning jax.random.split across the mesh shuffles a few u32 key
# words between devices as tiny collective-permutes (threefry plumbing), and
# the phantom-agent key padding (gather + concatenate before shard_map)
# likewise lowers to a tiny all-gather of key words.  Tolerate those kinds up
# to this many wire bytes; a gradient-sized transfer (>= 4 bytes x param
# count x agents) still trips the audit.
_PERMUTE_BYTE_TOLERANCE = 1024
_TOLERATED_SMALL_KINDS = frozenset({"collective-permute", "all-gather"})


@register_check("collective-audit")
def check_collectives(report: Report) -> None:
    import jax

    if jax.device_count() < 2:
        report.skipped.append(
            "collective-audit: single-device host (set "
            "REPRO_EMULATED_DEVICES=8 to emulate a mesh)")
        return

    from repro.core import distribute, fedpg
    from repro.core.channel import RayleighChannel
    from repro.core.ota import OTAConfig
    from repro.rl.envs import make_env
    from repro.utils.hlo import parse_collective_bytes

    n_agents = jax.device_count()
    mesh = distribute.agent_mesh_for(n_agents)
    env = make_env("landmark")
    policy = env.default_policy()
    cfg = fedpg.FedPGConfig(n_agents=n_agents, batch_m=1, horizon=3,
                            n_rounds=2)
    ota = OTAConfig(channel=RayleighChannel(), noise_sigma=1e-3, debias=True)

    def audit(fn, label):
        hlo = fn.lower(jax.random.key(0)).compile().as_text()
        stats = parse_collective_bytes(hlo)
        unexpected_set = set(stats.count_by_kind) - _EXPECTED_COLLECTIVES
        for kind in _TOLERATED_SMALL_KINDS:
            if stats.bytes_by_kind.get(kind, 0.0) <= _PERMUTE_BYTE_TOLERANCE:
                unexpected_set.discard(kind)
        unexpected = sorted(unexpected_set)
        if unexpected:
            report.findings.append(_finding(
                "collective-audit", _FEDPG_PATH,
                f"{label} round program contains unexpected collectives "
                f"{unexpected} (expected only "
                f"{sorted(_EXPECTED_COLLECTIVES)}; stats: {stats.summary()})"
                " — a resharding snuck into the shard_map uplink"))
        if not stats.count_by_kind:
            report.findings.append(_finding(
                "collective-audit", _FEDPG_PATH,
                f"{label} round program contains no collectives at all — "
                "the psum aggregation is not crossing the mesh",
                severity="warning"))

    audit(jax.jit(lambda k: fedpg.run(env, policy, cfg, k, ota=ota,
                                      agent_mesh=mesh)),
          "agent-mesh")
    # the streamed form, on a fleet the mesh does NOT divide: the phantom
    # padding + blocked scan must still lower to psum-only collectives
    cfg_pad = fedpg.FedPGConfig(n_agents=n_agents + 1, batch_m=1, horizon=3,
                                n_rounds=2)
    audit(jax.jit(lambda k: fedpg.run(env, policy, cfg_pad, k, ota=ota,
                                      agent_mesh=mesh, agent_blocks=1)),
          "streamed agent-mesh (padded)")


# ---------------------------------------------------------------------------
# stream-contract
# ---------------------------------------------------------------------------

def _loop_carry_avals(closed_jaxpr) -> List[tuple]:
    """Every ``scan`` / ``while`` carry aval in the jaxpr tree, as sorted
    ``(primitive, shape, dtype)`` triples.

    The streamed forms' memory claim lives here: a blocked program's loop
    carries are the only state that survives across agent blocks, so their
    avals must be a function of ``(agent_blocks, d)`` alone — comparing the
    multiset across two fleet sizes at a fixed block is an exact structural
    test for "peak state independent of N".
    """
    avals = []
    for jx in _iter_jaxprs(closed_jaxpr.jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                lo = eqn.params["num_consts"]
                hi = lo + eqn.params["num_carry"]
                carry = eqn.invars[lo:hi]
            elif eqn.primitive.name == "while":
                lo = (eqn.params["cond_nconsts"]
                      + eqn.params["body_nconsts"])
                carry = eqn.invars[lo:]
            else:
                continue
            for v in carry:
                avals.append((eqn.primitive.name, str(v.aval.shape),
                              str(v.aval.dtype)))
    return sorted(avals)


@register_check("stream-contract")
def check_stream_contract(report: Report) -> None:
    import jax

    from repro.core import fedpg, ota
    from repro.core.channel import RayleighChannel
    from repro.core.ota import OTAConfig, uplink_jaxpr
    from repro.rl.envs import make_env

    block = 2
    small, large = 6, 24

    # 1) the aggregate level: the blocked uplink jaxpr's loop carries must
    #    not change when the fleet grows 4x at a fixed block size
    noisy = OTAConfig(channel=RayleighChannel(), noise_sigma=1e-3,
                      debias=True)
    for cfg, tag in ((None, "exact"), (noisy, "noisy")):
        for apply_form in (False, True):
            form = "aggregate_apply" if apply_form else "aggregate"
            carries = [
                _loop_carry_avals(uplink_jaxpr(
                    cfg, apply=apply_form, n_agents=n, agent_blocks=block))
                for n in (small, large)
            ]
            if carries[0] != carries[1]:
                report.findings.append(_finding(
                    "stream-contract", _OTA_PATH,
                    f"{form}/{tag}: blocked uplink loop carries differ "
                    f"between n_agents={small} and n_agents={large} at "
                    f"agent_blocks={block} — the scan carry grows with the "
                    f"fleet (got {carries[0]} vs {carries[1]})"))
            if not carries[0]:
                report.findings.append(_finding(
                    "stream-contract", _OTA_PATH,
                    f"{form}/{tag}: blocked uplink jaxpr contains no "
                    f"scan/while loops — agent_blocks={block} is not "
                    "streaming at all"))

    # 2) the round level: the full streamed round program (rollouts +
    #    uplink + server pass) must likewise keep all loop state O(block x d)
    env = make_env("landmark")
    policy = env.default_policy()

    def round_carries(n):
        cfg = fedpg.FedPGConfig(n_agents=n, batch_m=1, horizon=3, n_rounds=2)
        closed = jax.make_jaxpr(
            lambda k: fedpg.run(env, policy, cfg, k, ota=noisy,
                                agent_blocks=block))(jax.random.key(0))
        return _loop_carry_avals(closed)

    got = [round_carries(n) for n in (small, large)]
    if got[0] != got[1]:
        only_small = [a for a in got[0] if a not in got[1]]
        only_large = [a for a in got[1] if a not in got[0]]
        report.findings.append(_finding(
            "stream-contract", _FEDPG_PATH,
            f"streamed round program loop carries differ between "
            f"n_agents={small} and n_agents={large} at "
            f"agent_blocks={block} — some loop state scales with the fleet "
            f"(only at N={small}: {only_small}; only at N={large}: "
            f"{only_large})"))


# ---------------------------------------------------------------------------
# participation-contract
# ---------------------------------------------------------------------------

_PARTICIPATION_PATH = "src/repro/service/participation.py"


def _key_invar_live(closed_jaxpr) -> bool:
    """Whether any top-level input variable of the jaxpr is consumed by an
    equation (or returned)."""
    from jax.extend import core as jex_core

    jaxpr = closed_jaxpr.jaxpr
    used = set()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not isinstance(v, jex_core.Literal):
                used.add(v)
    for v in jaxpr.outvars:
        if not isinstance(v, jex_core.Literal):
            used.add(v)
    return any(v in used for v in jaxpr.invars)


@register_check("participation-contract")
def check_participation_contract(report: Report) -> None:
    """The round service's debias-normaliser and lane-packing contracts.

    1. ``debias="realized"``: the traced ``key -> N/W`` normaliser
       (``participation.scale_jaxpr``) must CONSUME its key — the
       realised count is data-dependent on the drawn mask, and a dead key
       means the normaliser constant-folded back to the expected-count
       analysis.  ``debias="expected"``: the key must be DEAD — the
       closed-form normaliser must not touch the realisation.
    2. The sweep engine packs exactly the continuous service knobs
       (Bernoulli rate, fault deadline under realized debias, staleness
       decay) as live lane inputs, while the structural knobs (kind,
       debias mode) split partitions.
    3. The counter-PRNG hygiene of ``src/repro/service`` itself: the
       ``key-reuse`` AST rule over the whole package (mask and fault
       draws must stay pure fold_in counter-mode).
    """
    from repro.core.channel import RayleighChannel
    from repro.core.sweep import Scenario, partition_scenarios
    from repro.rl.envs import make_env
    from repro.service.faults import FaultConfig, StragglerModel
    from repro.service.participation import ParticipationConfig, scale_jaxpr
    from repro.service.staleness import StalenessConfig

    realized = [
        ParticipationConfig(rate=0.5),
        ParticipationConfig(kind="subset", subset=3),
        ParticipationConfig(kind="full", faults=FaultConfig(
            stragglers=StragglerModel(mean=1.0), deadline=1.0)),
    ]
    for p in realized:
        if not _key_invar_live(scale_jaxpr(p)):
            report.findings.append(_finding(
                "participation-contract", _PARTICIPATION_PATH,
                f"realized-debias normaliser for {p.kind!r} does not "
                "consume its PRNG key — N/W constant-folded back to the "
                "expected-count analysis"))
    expected = ParticipationConfig(rate=0.5, debias="expected")
    if _key_invar_live(scale_jaxpr(expected)):
        report.findings.append(_finding(
            "participation-contract", _PARTICIPATION_PATH,
            "expected-debias normaliser consumes the PRNG key — the "
            "closed-form E[W] must not depend on the realisation"))

    # 2) lane packing: each continuous service knob batches as a live lane
    #    input of a single partition program
    env = make_env("landmark")
    chan = RayleighChannel()

    def svc_scen(**kw):
        return Scenario(channel=chan, noise_sigma=1e-3, env=env,
                        debias=True, **_TINY, **kw)

    _check_one_partition(report, [
        svc_scen(participation=ParticipationConfig(rate=r))
        for r in (0.3, 0.7)
    ], "service rate axis")
    _check_one_partition(report, [
        svc_scen(participation=ParticipationConfig(kind="full", faults=FaultConfig(
            stragglers=StragglerModel(mean=1.0), deadline=d)))
        for d in (0.5, 2.0)
    ], "service deadline axis")
    _check_one_partition(report, [
        svc_scen(participation=ParticipationConfig(rate=0.5),
                 staleness=StalenessConfig(max_age=2, decay=dc))
        for dc in (0.5, 0.9)
    ], "service staleness-decay axis")

    # structural knobs must SPLIT: realized vs expected debias are
    # different programs (live vs dead key), never lanes of one
    split = partition_scenarios([
        svc_scen(participation=ParticipationConfig(rate=0.5, debias=d))
        for d in ("realized", "expected")
    ])
    if len(split) != 2:
        report.findings.append(_finding(
            "participation-contract", _SWEEP_PATH,
            "realized- and expected-debias scenarios merged into one "
            "partition — the debias mode is structural and must split"))
    # ...and a config that can never drop an agent must share the plain
    # partition (byte-identical programs)
    merged = partition_scenarios([
        svc_scen(participation=None),
        svc_scen(participation=ParticipationConfig(rate=1.0)),
    ])
    if len(merged) != 1:
        report.findings.append(_finding(
            "participation-contract", _SWEEP_PATH,
            "a full-participation config split from the plain partition — "
            "normalize() must fold it to participation=None"))

    # 3) PRNG hygiene of the service package itself
    from repro.analyze.engine import repo_root, scan
    from repro.analyze.rules import get_rules

    scan(repo_root(), ["src/repro/service"], rules=get_rules(["key-reuse"]),
         report=report)
