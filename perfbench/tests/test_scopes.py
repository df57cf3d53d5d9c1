"""The per-scope device-time readers and the service commit readers, on
synthetic summaries, maps and spans with hand-computed values."""
import sys
from types import SimpleNamespace

import pytest

from conftest import REPO
from perfbench import trace
from perfbench.harness import Benchmark

SCOPES = ("rollout", "estimator", "uplink", "epilogue")
# op -> scope, as the program's map gives it: the while spans its body
OPS = {"while.127": "control", "dynamic-slice.16": "estimator",
       "dynamic-update-slice.2": "estimator", "fusion.310": "epilogue",
       "fusion.9": "rollout", "uplink.3": "uplink", "add.1": "unscoped"}


def _device(name, window_ns, busy_ns, op_ns):
    return trace.Device(name, busy_ns=busy_ns, window_ns=window_ns,
                        op_ns=dict(op_ns))


def _ctx(devices, ops=OPS, rate=2e5):
    ctx = SimpleNamespace(summary=trace.Summary(devices, []), rate=rate)
    ctx.op_scopes = ops  # the map the program would build, kept on ctx
    return ctx


def _readers():
    bench = Benchmark(REPO)
    return {s: bench.reader({"name": f"{s}_ns_per_agent_step"})
            for s in SCOPES}


# one chip: 1 ms window, 0.9 ms busy; the while spans 0.6 ms of leaf ops
ONE = {"while.127": 700e3, "dynamic-slice.16": 350e3,
       "dynamic-update-slice.2": 250e3, "fusion.310": 80e3,
       "fusion.9": 40e3, "uplink.3": 10e3, "add.1": 5e3}


def test_scope_readers_hand_computed():
    read = _readers()
    ctx = _ctx([_device("/device:TPU:0", 1e6, 0.9e6, ONE)])
    per_step = 1e9 / 2e5  # 5,000 wall-ns per agent-step
    want = {"estimator": 0.6, "epilogue": 0.08, "rollout": 0.04,
            "uplink": 0.01}
    for s, share in want.items():
        assert read[s](ctx) == pytest.approx(share * per_step), s


def test_scope_readers_sum_over_chips():
    """Each chip's share of its own window, summed over the chips."""
    read = _readers()
    two = [_device("/device:TPU:0", 1e6, 0.9e6, ONE),
           _device("/device:TPU:1", 2e6, 1.0e6,
                   {"dynamic-slice.16": 500e3, "while.127": 600e3})]
    ctx = _ctx(two, rate=4e5)
    assert read["estimator"](ctx) == pytest.approx((0.6 + 0.25) * 1e9 / 4e5)
    assert read["uplink"](ctx) == pytest.approx(0.01 * 1e9 / 4e5)


def test_scope_readers_skip_control_flow_and_stay_under_busy():
    """The while op spans the ops of its body: counted too, it would put
    the four scopes above the busy time."""
    read = _readers()
    dev = _device("/device:TPU:0", 1e6, 0.9e6, ONE)
    ctx = _ctx([dev])
    total = sum(read[s](ctx) for s in SCOPES)
    assert total <= dev.busy_ns / dev.window_ns * 1e9 / ctx.rate
    as_leaf = _ctx([dev], ops=dict(OPS, **{"while.127": "estimator"}))
    assert sum(read[s](as_leaf) for s in SCOPES) \
        > dev.busy_ns / dev.window_ns * 1e9 / ctx.rate


def test_scope_readers_without_a_map_read_nothing(monkeypatch):
    read = _readers()
    dev = _device("/device:TPU:0", 1e6, 0.9e6, ONE)
    for s in SCOPES:
        assert read[s](_ctx([dev], ops=None)) is None
    # a program with no scopes module, as before the scopes existed
    import repro.telemetry

    monkeypatch.delattr(repro.telemetry, "scopes", raising=False)
    monkeypatch.setitem(sys.modules, "repro.telemetry.scopes", None)
    ctx = SimpleNamespace(summary=trace.Summary([dev], []), rate=2e5)
    assert read["estimator"](ctx) is None


def test_scope_readers_build_the_map_of_the_recorded_call(monkeypatch):
    """Without a kept map the helper asks the program for the map of its
    last recorded call, the traced one."""
    from repro.telemetry import scopes

    asked = []
    monkeypatch.setattr(scopes, "op_scopes", lambda: asked.append(1) or OPS)
    read = _readers()
    ctx = SimpleNamespace(summary=trace.Summary(
        [_device("/device:TPU:0", 1e6, 0.9e6, ONE)], []), rate=2e5)
    assert read["estimator"](ctx) == pytest.approx(0.6 * 5000)
    assert read["rollout"](ctx) == pytest.approx(0.04 * 5000)
    assert asked == [1]  # built once, kept on ctx


def _commit_tracer(attrs, children=(("service.dispatch", 1500.0),
                                    ("service.fetch", 9.9e6),
                                    ("service.record", 700.0))):
    from repro.telemetry import trace as rtrace

    tr = rtrace.Tracer()
    with tr.span("service_commit", round=4, rounds=1) as sp:
        for name, _ in children:
            with tr.span(name, round=4):
                pass
        sp.attrs.update(attrs)
    for child, (_, us) in zip(sp.children, children):
        child.duration_us = us
    return tr


def _service_readers():
    bench = Benchmark(REPO)
    return (bench.reader({"name": "participant_share"}),
            bench.reader({"name": "commit_host_ms"}))


def test_service_readers_hand_computed(monkeypatch):
    from repro.telemetry import trace as rtrace

    share, host_ms = _service_readers()
    monkeypatch.setattr(rtrace, "_TRACER", _commit_tracer(
        {"agents_rolled_out": 10240, "agents_participating": 885,
         "compiles": 0}))
    assert share(None) == pytest.approx(100.0 * 885 / 10240)
    assert host_ms(None) == pytest.approx((1500.0 + 700.0) / 1e3)


def test_service_readers_read_the_last_commit(monkeypatch):
    from repro.telemetry import trace as rtrace

    share, _ = _service_readers()
    tr = _commit_tracer({"agents_rolled_out": 100,
                         "agents_participating": 1})
    last = _commit_tracer({"agents_rolled_out": 100,
                           "agents_participating": 9})
    tr.roots += last.roots
    monkeypatch.setattr(rtrace, "_TRACER", tr)
    assert share(None) == pytest.approx(9.0)


def test_service_readers_without_counters_or_children_read_nothing(
        monkeypatch):
    """A program whose commit span has no counters and no children (as
    before they existed), or no commit at all, gives nothing."""
    from repro.telemetry import trace as rtrace

    share, host_ms = _service_readers()
    monkeypatch.setattr(rtrace, "_TRACER", _commit_tracer({}, children=()))
    assert share(None) is None and host_ms(None) is None
    monkeypatch.setattr(rtrace, "_TRACER", rtrace.Tracer())
    assert share(None) is None and host_ms(None) is None
