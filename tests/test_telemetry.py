"""Observability tests: in-jit probes, span tracer, run ledger.

The load-bearing invariant: telemetry OFF (None or an all-probes-off
config) emits a program bitwise identical to the pre-telemetry one — the
jaxpr equality here plus the golden-trace suite pin it.  Probe *math* is
checked against hand-computed references on tiny fixed inputs.
"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fedpg, theory
from repro.core.channel import FixedGainChannel, RayleighChannel
from repro.core.ota import OTAConfig
from repro.core.sweep import grid, sweep
from repro.rl.env import LandmarkNav
from repro.rl.policy import MLPPolicy
from repro.telemetry import (
    Ledger, RoundTelemetry, TelemetryConfig, read_ledger, set_ledger,
    using_ledger,
)
from repro.telemetry import trace as rtrace
from repro.telemetry import probes
from repro.telemetry.report import render

SMALL = dict(n_agents=3, batch_m=2, horizon=6, n_rounds=4)

ALL_OFF = TelemetryConfig(snr=False, grad_norms=False, moment_drift=False,
                          dispersion=False)


def _setting():
    return LandmarkNav(), MLPPolicy()


def _rayleigh_ota():
    return OTAConfig(channel=RayleighChannel(), noise_sigma=0.1, debias=True)


def _strip_addresses(jaxpr_text: str) -> str:
    # function-object reprs in jvp_jaxpr_thunk params carry addresses
    return re.sub(r"0x[0-9a-f]+", "0x", jaxpr_text)


# ---------------------------------------------------------------------------
# telemetry off == pre-telemetry program
# ---------------------------------------------------------------------------

def test_history_prefix_compatible():
    r, g, m = jnp.zeros(3), jnp.ones(3), jnp.ones(3)
    h = fedpg.History(r, g, m)  # 3-positional construction still works
    assert h.telemetry is None
    assert len(jax.tree.leaves(h)) == 3  # None is an empty subtree


@pytest.mark.parametrize("uplink", ["exact", "rayleigh"])
def test_all_off_config_is_bitwise_off(uplink):
    env, pol = _setting()
    cfg = fedpg.FedPGConfig(**SMALL)
    ota = None if uplink == "exact" else _rayleigh_ota()
    key = jax.random.key(0)
    j_none = jax.make_jaxpr(
        lambda k: fedpg.run(env, pol, cfg, k, ota=ota))(key)
    j_off = jax.make_jaxpr(
        lambda k: fedpg.run(env, pol, cfg, k, ota=ota, telemetry=ALL_OFF))(key)
    assert _strip_addresses(str(j_none)) == _strip_addresses(str(j_off))


def test_telemetry_on_leaves_metrics_bitwise_identical():
    env, pol = _setting()
    cfg = fedpg.FedPGConfig(**SMALL)
    ota = _rayleigh_ota()
    key = jax.random.key(0)
    h_off = fedpg.run_jit(env, pol, cfg, key, ota=ota)[1]
    h_on = fedpg.run_jit(env, pol, cfg, key, ota=ota,
                         telemetry=TelemetryConfig())[1]
    for name in ("rewards", "grad_sq", "gain_mean"):
        a = np.asarray(getattr(h_off, name))
        b = np.asarray(getattr(h_on, name))
        assert (a == b).all(), name
    assert h_off.telemetry is None
    assert isinstance(h_on.telemetry, RoundTelemetry)
    assert h_on.telemetry.snr.shape == (SMALL["n_rounds"],)


# ---------------------------------------------------------------------------
# probe math vs hand-computed references
# ---------------------------------------------------------------------------

def test_stacked_probes_hand_computed():
    # 2 agents, 1-leaf pytree of shape (2, 2); everything exactly known
    grads = {"w": jnp.array([[3.0, 4.0], [0.0, 12.0]])}  # norms 5, 12
    gains = jnp.array([2.0, 0.5])
    ota = OTAConfig(channel=FixedGainChannel(gain=1.0), noise_sigma=0.5)
    tel = probes.stacked_round_probes(
        TelemetryConfig(), grads_stacked=grads, gains=gains, ota_cfg=ota,
        n_agents=2, gain_mean=jnp.mean(gains), update_norm=jnp.asarray(7.0))
    # sum_i h_i g_i = 2*[3,4] + 0.5*[0,12] = [6, 14]; ||.||^2 = 232
    # snr = 232 / (d=2 * sigma^2=0.25) = 464
    assert np.isclose(float(tel.snr), 464.0, rtol=1e-6)
    assert np.isclose(float(tel.grad_norm_pre), (5.0 + 12.0) / 2, rtol=1e-6)
    assert float(tel.grad_norm_post) == 7.0
    # FixedGain(1.0), no power control: reference is channel.mean = 1.0
    assert np.isclose(float(tel.moment_drift), 1.25 - 1.0, rtol=1e-6)
    assert np.isclose(float(tel.dispersion), 12.0 / 8.5, rtol=1e-6)


def test_stacked_probes_disabled_fields_are_nan():
    grads = {"w": jnp.ones((2, 3))}
    tel = probes.stacked_round_probes(
        TelemetryConfig(snr=False, grad_norms=False, dispersion=False),
        grads_stacked=grads, gains=jnp.ones((2,)), ota_cfg=None, n_agents=2,
        gain_mean=jnp.ones(()), update_norm=jnp.ones(()))
    assert np.isnan(float(tel.snr))
    assert np.isnan(float(tel.grad_norm_pre))
    assert np.isnan(float(tel.dispersion))
    assert np.isfinite(float(tel.moment_drift))


def test_exact_uplink_probes():
    """Noiseless/exact: SNR is inf, moment drift exactly 0."""
    env, pol = _setting()
    cfg = fedpg.FedPGConfig(**SMALL)
    h = fedpg.run_jit(env, pol, cfg, jax.random.key(0),
                      telemetry=TelemetryConfig())[1]
    assert np.isinf(np.asarray(h.telemetry.snr)).all()
    assert (np.asarray(h.telemetry.moment_drift) == 0.0).all()
    assert (np.asarray(h.telemetry.dispersion) >= 1.0).all()


def test_fixed_gain_drift_is_zero():
    """Deterministic channel: realised mean(h) == closed-form m_h, so the
    drift probe must return exactly 0 every round."""
    env, pol = _setting()
    cfg = fedpg.FedPGConfig(**SMALL)
    ota = OTAConfig(channel=FixedGainChannel(gain=0.7), noise_sigma=0.05)
    h = fedpg.run_jit(env, pol, cfg, jax.random.key(0), ota=ota,
                      telemetry=TelemetryConfig())[1]
    np.testing.assert_allclose(np.asarray(h.telemetry.moment_drift), 0.0,
                               atol=1e-6)


def test_sharded_probes_match_vmap():
    """The shard_map probe reductions agree with the stacked form on a
    deterministic channel (the random realisation is shared)."""
    from repro.core import distribute

    env, pol = _setting()
    cfg = fedpg.FedPGConfig(n_agents=4, batch_m=2, horizon=6, n_rounds=3)
    mesh = distribute.agent_mesh_for(cfg.n_agents)
    ota = OTAConfig(channel=FixedGainChannel(gain=0.8), noise_sigma=0.05,
                    debias=True)
    key = jax.random.key(0)
    tc = TelemetryConfig()
    _, h_v = fedpg.run(env, pol, cfg, key, ota=ota, telemetry=tc)
    _, h_s = fedpg.run(env, pol, cfg, key, ota=ota, telemetry=tc,
                       agent_mesh=mesh)
    for f in RoundTelemetry._fields:
        a, b = getattr(h_v.telemetry, f), getattr(h_s.telemetry, f)
        if a is None or b is None:
            # service-only probes: absent on both forms without an
            # active participation config
            assert a is None and b is None, f
            continue
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, err_msg=f)


def test_summarize():
    tel = RoundTelemetry(
        snr=np.array([np.inf, np.inf]),
        grad_norm_pre=np.array([1.0, 3.0]),
        grad_norm_post=np.array([2.0, 2.0]),
        moment_drift=np.array([np.nan, np.nan]),
        dispersion=np.array([1.0, 2.0]))
    s = probes.summarize(tel)
    assert s["snr"] == float("inf")
    assert s["grad_norm_pre"] == 2.0
    assert s["moment_drift"] is None
    assert probes.summarize(None) is None


# ---------------------------------------------------------------------------
# sweep integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sweep_pair():
    env, pol = _setting()
    scens = grid(channel=[RayleighChannel()], noise_sigma=[1e-2, 1e-1],
                 debias=True, **SMALL)
    key = jax.random.key(0)
    off = sweep(env, pol, scens, key, 2)
    on = sweep(env, pol, scens, key, 2, telemetry=TelemetryConfig())
    return off, on


def test_sweep_telemetry_shapes_and_bitwise(sweep_pair):
    off, on = sweep_pair
    assert off.history.telemetry is None
    assert on.history.telemetry.snr.shape == (2, 2, SMALL["n_rounds"])
    assert (np.asarray(on.history.rewards)
            == np.asarray(off.history.rewards)).all()
    assert (np.asarray(on.history.grad_sq)
            == np.asarray(off.history.grad_sq)).all()


def test_sweep_scenario_accessors(sweep_pair):
    off, on = sweep_pair
    assert off.scenario_history(0).telemetry is None
    assert off.telemetry_summary(0) is None
    sh = on.scenario_history(1)
    assert sh.telemetry.snr.shape == (2, SMALL["n_rounds"])
    summ = on.telemetry_summary(1)
    # service-only probes (participation/staleness) are absent on a
    # sweep without an active participation config
    assert set(summ) == {f for f in RoundTelemetry._fields
                         if getattr(sh.telemetry, f) is not None}
    assert {"snr", "grad_norm_pre", "grad_norm_post", "moment_drift",
            "dispersion"} <= set(summ)
    assert summ["snr"] > 0
    row = on.to_dicts()[0]
    assert "telemetry_snr" in row and "telemetry_dispersion" in row
    assert "telemetry_snr" not in off.to_dicts()[0]


def test_sweep_records_partition_spans(sweep_pair):
    names = [s.name for s in rtrace.spans()]
    assert "partition" in names


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_export(tmp_path):
    tr = rtrace.Tracer()
    with tr.span("outer", label="a") as outer:
        with tr.span("inner"):
            pass
    assert outer.duration_us > 0
    assert [c.name for c in outer.children] == ["inner"]

    doc = tr.to_chrome_trace()
    text = json.dumps(doc)  # must be valid strict JSON
    back = json.loads(text)
    complete = [e for e in back["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"outer", "inner"}
    for e in complete:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)

    path = tmp_path / "trace.json"
    tr2 = rtrace.Tracer()
    with tr2.span("solo"):
        pass
    tr2.export(str(path))
    assert json.loads(path.read_text())["traceEvents"]


def test_timed_call_timing():
    t = rtrace.timed_call(lambda: sum(range(100)), warmup=1, iters=3,
                          name="toy")
    assert isinstance(t, rtrace.Timing)
    assert float(t) == t.run_us > 0
    assert t.compile_us is not None
    assert f"{t:.1f}"  # format like the plain float it replaced


def test_time_call_returns_timing():
    from benchmarks.common import time_call

    t = time_call(jax.jit(lambda x: x * 2), jnp.ones(4), iters=2)
    assert isinstance(t, rtrace.Timing)
    assert t.compile_us is not None and t.compile_us > 0


# ---------------------------------------------------------------------------
# ledger + report
# ---------------------------------------------------------------------------

def test_ledger_schema_and_report(tmp_path, sweep_pair):
    _, on = sweep_pair
    path = tmp_path / "LEDGER.jsonl"
    consts = theory.MDPConstants(G=1.0, F=0.5, l_bar=1.0, gamma=0.9)
    with Ledger(str(path)) as led:
        led.log_platform()
        with led.count_compiles(label="noop"):
            pass
        led.log_sweep(on, constants=consts, label="unit")
    events = read_ledger(str(path))
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "ledger_start"
    assert "platform" in kinds and "compiles" in kinds and "sweep" in kinds
    scen = [e for e in events if e["kind"] == "scenario"]
    assert len(scen) == len(on.scenarios)
    for ev in scen:
        assert {"avg_grad_sq", "final_reward", "floor", "floor_which",
                "distance_to_floor", "telemetry"} <= set(ev)
        assert ev["floor_which"] in ("theorem1", "theorem2")
    text = render(events, title="Unit")
    assert "avg_grad_sq vs theory floors" in text
    assert "## Platform" in text


def test_ledger_skips_malformed_lines(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"kind": "ok", "ts": 1}\nnot json\n{"no_kind": 1}\n')
    with pytest.warns(UserWarning):
        events = read_ledger(str(path))
    assert [e["kind"] for e in events] == ["ok"]


def test_ambient_ledger(tmp_path):
    path = tmp_path / "amb.jsonl"
    from repro.telemetry import get_ledger

    assert get_ledger() is None
    with Ledger(str(path)) as led, using_ledger(led):
        assert get_ledger() is led
    assert get_ledger() is None
    set_ledger(None)  # idempotent


def test_floor_report():
    fr = theory.floor_report(n_agents=10, batch_m=10, m_h=1.0, sigma_h2=0.2,
                             noise_sigma2=1e-4, V=2.0)
    assert fr["floor_which"] in ("theorem1", "theorem2")
    assert fr["floor"] in (fr["floor_theorem1"], fr["floor_theorem2"])
    assert fr["floor"] > 0


# ---------------------------------------------------------------------------
# named scopes and the map from device ops to them
# ---------------------------------------------------------------------------

from repro.analyze.budget import CompileCounter  # noqa: E402
from repro.service.driver import RoundService, ServiceConfig  # noqa: E402
from repro.service.participation import ParticipationConfig  # noqa: E402
from repro.telemetry import scopes  # noqa: E402

_INSTR_META = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*op_name="([^"]*)"')
SERVICE_TEL = TelemetryConfig(snr=False, grad_norms=False,
                              moment_drift=False, dispersion=False,
                              participation=True)


def _named_ops(hlo_text):
    """(instruction name, op_name) of every instruction whose op_name
    carries a name stack."""
    return [m.groups() for m in map(_INSTR_META.match, hlo_text.splitlines())
            if m is not None and "/" in m.group(2)]


def _round_program(kind):
    env, pol = _setting()
    cfg = fedpg.FedPGConfig(n_agents=7, batch_m=2, horizon=4, n_rounds=1)
    kw = {"stacked": {}, "streamed": dict(agent_blocks=3),
          "service": dict(agent_blocks=3,
                          participation=ParticipationConfig(rate=0.5))}[kind]
    round_fn = fedpg.make_round_fn(env, pol, cfg, _rayleigh_ota(),
                                   telemetry=TelemetryConfig(), **kw)
    theta = pol.init(jax.random.key(0))
    if kind == "service":
        from repro.service import participation as svc_participation
        theta = svc_participation.init_state(theta, jax.random.key(2),
                                             cfg.n_agents, None)
    return jax.jit(round_fn).lower(theta, jax.random.key(1)).compile()


# the block loop's and the shard_map's own ops, in no scope: the ``while``
# and the single ops of its body and condition (slicing the blocks,
# stacking the outputs, the counter), and the partitioner's own
_LOOP_OWN = re.compile(
    r"^jit\([^)]*\)(/(while|body|cond|closed_call|shard_map))*(/[\w.-]+)?$")


@pytest.mark.parametrize("kind", ["stacked", "streamed", "service"])
def test_round_scopes_cover_every_named_op(kind):
    """Inside a round, every instruction with a name stack falls under one
    of the four scopes (control flow aside) or is the block loop's own
    (unscoped), all four occur, and the scatter-add gradient of the
    ``logp[action]`` gather is the estimator's."""
    text = _round_program(kind).as_text()
    ops = scopes.hlo_scopes(text)
    named = _named_ops(text)
    assert named
    stray = [(n, o) for n, o in named
             if ops[n] not in scopes.SCOPES + (scopes.CONTROL,)]
    assert all(_LOOP_OWN.match(o) for _, o in stray), [
        (n, o) for n, o in stray if not _LOOP_OWN.match(o)][:5]
    assert bool(stray) == (kind != "stacked")
    assert set(scopes.SCOPES) <= {ops[n] for n, _ in named}
    scatter = [n for n, o in named if o.endswith("scatter-add")]
    assert scatter and {ops[n] for n in scatter} <= {
        scopes.ESTIMATOR, scopes.CONTROL}


def test_scope_of_reads_transformed_and_nested_stacks():
    assert scopes.scope_of("jit(f)/rollout/while/body/vmap(estimator)/"
                           "transpose(jvp(vmap()))/scatter-add") == "estimator"
    assert scopes.scope_of("jit(f)/vmap(uplink)/mul") == "uplink"
    assert scopes.scope_of("jit(f)/while/body/add") == scopes.UNSCOPED
    assert scopes.scope_of("jit(rollout_x)/add") == scopes.UNSCOPED


def test_loop_body_without_metadata_takes_its_loop_scope():
    """A loop XLA builds (a TPU's expanded scatter) carries no metadata in
    its body; its ops take the scope of the loop op, and the loop op itself
    is marked control flow."""
    text = """HloModule m

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %dynamic-slice.16 = f32[1]{0} dynamic-slice(%x, %i), dynamic_slice_sizes={1}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%i, %y)
}

%cond.1 (p: (s32[], f32[8])) -> pred[] {
  ROOT %lt.1 = pred[] compare(%a, %b), direction=LT
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %while.127 = (s32[], f32[8]{0}) while(%t), condition=%cond.1, body=%body.1, metadata={op_name="jit(f)/estimator/transpose(jvp(g))/scatter-add"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc.3, metadata={op_name="jit(f)/epilogue/mul"}
  ROOT %add.4 = f32[8]{0} add(%a, %a), metadata={op_name="jit(f)/add"}
}
"""
    ops = scopes.hlo_scopes(text)
    assert ops["while.127"] == scopes.CONTROL
    assert ops["dynamic-slice.16"] == ops["lt.1"] == scopes.ESTIMATOR
    assert ops["fusion.3"] == scopes.EPILOGUE
    assert ops["add.4"] == ops["a"] == scopes.UNSCOPED


def _service(agent_blocks=3):
    env, pol = _setting()
    cfg = fedpg.FedPGConfig(n_agents=7, batch_m=2, horizon=4, n_rounds=1)
    return RoundService(
        env, pol, cfg, jax.random.key(1),
        participation=ParticipationConfig(rate=0.5), ota=_rayleigh_ota(),
        telemetry=SERVICE_TEL, agent_blocks=agent_blocks,
        service=ServiceConfig(rounds_per_commit=2, max_rounds=100))


def _call_entry(entry):
    env, pol = _setting()
    cfg = fedpg.FedPGConfig(n_agents=7, batch_m=2, horizon=4, n_rounds=2)
    if entry == "fedpg.run_jit":
        fedpg.run_jit(env, pol, cfg, jax.random.key(0), ota=_rayleigh_ota(),
                      agent_blocks=3)
    elif entry == "fedpg.monte_carlo":
        fedpg.monte_carlo(env, pol, cfg, jax.random.key(0), 2,
                          ota=_rayleigh_ota())
    else:
        _service().commit()


@pytest.mark.parametrize("entry", ["fedpg.run_jit", "fedpg.monte_carlo",
                                   "RoundService.commit"])
def test_entry_point_map_needs_no_compile(entry):
    """The map of the executable an entry point last ran is built from that
    executable without a second backend compile, and names all four
    scopes."""
    _call_entry(entry)
    with CompileCounter() as c:
        ops = scopes.op_scopes()
    assert c.count == 0
    assert set(scopes.SCOPES) <= set(ops.values())
    assert scopes.CONTROL in ops.values()


def test_op_scopes_before_any_call_is_none(monkeypatch):
    monkeypatch.setattr(scopes, "_LAST", None)
    assert scopes.op_scopes() is None


def test_recorded_call_keeps_no_device_arrays():
    """The record of a commit keeps its arguments' specs, not the arrays:
    the state a commit replaced is freed, and the map still builds."""
    import gc
    import weakref

    svc = _service()
    svc.commit()
    old = weakref.ref(jax.tree.leaves(svc.state)[0])
    svc.commit()
    gc.collect()
    assert old() is None
    _, args = scopes._LAST
    assert not any(isinstance(a, jax.Array) for a in jax.tree.leaves(args))
    assert set(scopes.SCOPES) <= set(scopes.op_scopes().values())


def test_unscoped_op_in_the_block_loop_reads_unscoped(monkeypatch):
    """The block loop itself carries no scope, so an op planted in its
    body outside the four scopes maps to ``unscoped`` and is not charged
    to a scope by the loop around it."""
    rollout_batch = fedpg.rollout_batch

    def planted(*args, **kwargs):
        traj = rollout_batch(*args, **kwargs)
        return traj._replace(losses=jnp.cos(traj.losses))

    monkeypatch.setattr(fedpg, "rollout_batch", planted)
    text = _round_program("streamed").as_text()
    ops = scopes.hlo_scopes(text)
    cos = [n for n, o in _named_ops(text) if o.endswith("/cos")]
    assert cos and {ops[n] for n in cos} == {scopes.UNSCOPED}


_SHARDED_SCRIPT = """
import json, re, sys
from repro.utils import platform as rplat
rplat.apply_emulated_devices()
import jax
from repro.core import distribute, fedpg
from repro.core.channel import RayleighChannel
from repro.core.ota import OTAConfig
from repro.rl.env import LandmarkNav
from repro.rl.policy import MLPPolicy
from repro.analyze.budget import CompileCounter
from repro.telemetry import scopes

assert jax.device_count() == 4, jax.devices()
env, pol = LandmarkNav(), MLPPolicy()
cfg = fedpg.FedPGConfig(n_agents=8, batch_m=2, horizon=4, n_rounds=1)
ota = OTAConfig(channel=RayleighChannel(), noise_sigma=0.1, debias=True)
mesh = distribute.agent_mesh_for(cfg.n_agents)
out = {}
for blocks in (None, 1):
    fedpg.run_jit(env, pol, cfg, jax.random.key(0), ota=ota,
                  agent_mesh=mesh, agent_blocks=blocks)
    with CompileCounter() as c:
        ops = scopes.op_scopes()
    fn, args = scopes._LAST
    text = fn.lower(*args).compile().as_text()
    names = re.findall(r'%?([^\\s=]+) = [^\\n]*op_name="([^"]*/shard_map'
                       r'[^"]*)"', text)
    psums = re.findall(r'%?([^\\s=]+) = [^\\n]* all-reduce\\(', text)
    out[str(blocks)] = {
        "compiles": c.count,
        "body": sorted({ops[n] for n, _ in names}),
        "stray": sorted({o for n, o in names if ops[n] == scopes.UNSCOPED}),
        "psum": sorted({ops[n] for n in psums}),
        "scopes": sorted(set(ops.values()))}
print(json.dumps(out))
"""


def test_sharded_round_scopes_on_four_devices():
    """The agent_mesh round on 4 virtual CPU devices: every op of the
    sharded body falls under one of the four scopes or is the shard_map's
    or the block loop's own, the psums belong to the uplink or the
    epilogue, and the map costs no compile."""
    import os
    import pathlib
    import subprocess
    import sys

    repo = pathlib.Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", REPRO_EMULATED_DEVICES="4",
               PYTHONPATH=str(repo / "src"))
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    for blocks, r in res.items():
        assert r["compiles"] == 0, blocks
        assert r["body"] and set(r["body"]) <= set(scopes.SCOPES) | {
            scopes.CONTROL, scopes.UNSCOPED}, (blocks, r)
        assert all(_LOOP_OWN.match(o) for o in r["stray"]), (blocks, r)
        assert r["psum"] and set(r["psum"]) <= {scopes.UPLINK,
                                                scopes.EPILOGUE}, (blocks, r)
        assert set(scopes.SCOPES) <= set(r["scopes"]), (blocks, r)


# ---------------------------------------------------------------------------
# program spans on the profiler's clock; the service commit's spans
# ---------------------------------------------------------------------------

def test_span_lands_in_the_profile_host_plane(tmp_path):
    """A span run under the profiler appears by name in the xplane's host
    plane, inside the profiled window, at the time the tracer gave it."""
    import glob

    from jax.profiler import ProfileData

    tr = rtrace.Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("service.fetch", round=3) as sp:
            jnp.arange(4.0).block_until_ready()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    pd = ProfileData.from_file(path)
    env = {k: v for p in pd.planes if p.name == "Task Environment"
           for k, v in p.stats}
    t0, t1 = env["profile_start_time"], env["profile_stop_time"]
    events = [ev for p in pd.planes if p.name.startswith("/host:")
              for line in p.lines for ev in line.events
              if ev.name == "service.fetch"]
    assert len(events) == 1
    (ev,) = events
    assert dict(ev.stats)["round"] == 3
    assert 0 <= ev.start_ns and ev.start_ns + ev.duration_ns <= t1 - t0
    # the tracer's wall clock is the profiler's: the Chrome export lines
    # up with the xplane by its own profile_start_time
    assert abs(sp.start_us * 1e3 - (t0 + ev.start_ns)) < 5e6
    assert abs(sp.duration_us * 1e3 - ev.duration_ns) < 5e6


def test_service_commit_spans_and_counters():
    """A commit span holds dispatch, fetch and record children tagged with
    the round, and counts the agents rolled out (phantom block padding
    included), the realised participants and the backend compiles."""
    svc = _service(agent_blocks=3)
    recs = []
    for _ in range(2):
        recs.append(svc.commit())
    commits = [s for s in rtrace.spans() if s.name == "service_commit"][-2:]
    for sp, rec in zip(commits, recs):
        assert [c.name for c in sp.children] == [
            "service.dispatch", "service.fetch", "service.record"]
        assert {c.attrs["round"] for c in [sp] + sp.children} == {
            rec["round_start"]}
        # 7 agents in blocks of 3: 3 blocks of 3 rolled out, 2 rounds
        assert sp.attrs["agents_rolled_out"] == 2 * 9
        assert sp.attrs["agents_participating"] == round(
            rec["participation_rate"] * 7 * 2)
        assert rec["wall_us"] == pytest.approx(
            sp.children[0].duration_us + sp.children[1].duration_us)
    assert commits[0].attrs["compiles"] >= 1
    assert commits[1].attrs["compiles"] == 0


def test_agents_rolled_out_follows_the_layout():
    assert fedpg.agents_rolled_out(10) == 10
    assert fedpg.agents_rolled_out(10 ** 4, 256) == 40 * 256
    assert fedpg.agents_rolled_out(7, 100) == 8  # the block caps at ceil(N/2)


def test_wait_keeps_a_span_visible_to_a_profiler_started_mid_wait(
        tmp_path, monkeypatch):
    """A span entered before the profiler started is missing from the
    profile, unless it waits through ``trace.wait``: the wait re-enters
    it once the profiler runs, so the profile's tail still names it."""
    import glob
    import threading

    from jax.profiler import ProfileData

    class Pending:  # a device result, ready once the gate opens
        def __init__(self):
            self.gate = threading.Event()

        def block_until_ready(self):
            assert self.gate.wait(timeout=60)
            return self

    reentered = threading.Event()
    annotation = rtrace._annotation

    def spy(name, attrs):
        if rtrace._profiling():
            reentered.set()
        return annotation(name, attrs)

    monkeypatch.setattr(rtrace, "_annotation", spy)
    tr = rtrace.Tracer()
    names = []
    for name, waits in (("fetch.plain", False), ("fetch.waited", True)):
        pending, entered = Pending(), threading.Event()
        reentered.clear()

        def commit():
            with tr.span(name, round=7):
                entered.set()
                if waits:
                    tr.wait({"a": pending, "b": jnp.ones(2)})
                pending.block_until_ready()

        th = threading.Thread(target=commit)
        th.start()
        assert entered.wait(timeout=60)
        logdir = tmp_path / name
        jax.profiler.start_trace(str(logdir))
        if waits:
            assert reentered.wait(timeout=60)
        pending.gate.set()
        th.join(timeout=60)
        assert not th.is_alive()
        jax.profiler.stop_trace()
        (path,) = glob.glob(str(logdir / "plugins/profile/*/*.xplane.pb"))
        names += [(ev.name, dict(ev.stats)) for p in
                  ProfileData.from_file(path).planes
                  if p.name.startswith("/host:") for line in p.lines
                  for ev in line.events if ev.name.startswith("fetch.")]
    assert names == [("fetch.waited", {"round": 7})]
    assert [s.name for s in tr.spans()] == ["fetch.plain", "fetch.waited"]


def test_wait_raises_what_the_device_wait_raised():
    class Broken:
        def block_until_ready(self):
            raise RuntimeError("device lost")

    tr = rtrace.Tracer()
    with pytest.raises(RuntimeError, match="device lost"):
        with tr.span("service.fetch"):
            tr.wait([Broken()])
