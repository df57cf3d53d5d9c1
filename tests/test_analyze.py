"""Tests for ``repro.analyze`` — both layers.

Layer 1 (AST rules) is exercised on a fixture corpus through
``scan_source``: for every rule a tripping snippet, a should-not-trip
sibling, and the ``# repro: noqa[rule-id]`` suppression path.  A planted
multi-violation module proves ``--strict`` exits non-zero on every rule
class through the real CLI entry point.

Layer 2 (trace-level contracts) is exercised against the live registries:
the lane contract must pass for every registered env family on the real
tree, and must *fail* on mutants that promote a partition constant to a
dynamic argument or pack an extra axis; the wire-dtype check must pass the
real uplink and flag a planted narrowing; the compile-budget check must
pass the real caches and flag a planted cache-buster.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from repro.analyze import Report, get_rules, run, scan_source
from repro.analyze.findings import Finding, noqa_rules
from repro.analyze.__main__ import main as analyze_main


def _ids(findings):
    return sorted({f.rule for f in findings})


def _scan(source, rule_id, relpath="<string>"):
    return scan_source(source, relpath=relpath, rules=get_rules([rule_id]))


# ---------------------------------------------------------------------------
# key-reuse
# ---------------------------------------------------------------------------

def test_key_reuse_trips_on_double_consume():
    fs = _scan(
        "import jax\n"
        "def f(key):\n"
        "    a = jax.random.normal(key)\n"
        "    b = jax.random.uniform(key)\n"
        "    return a + b\n",
        "key-reuse")
    assert _ids(fs) == ["key-reuse"] and fs[0].line == 4


def test_key_reuse_trips_on_use_after_split():
    fs = _scan(
        "import jax\n"
        "def f(key):\n"
        "    k1, k2 = jax.random.split(key)\n"
        "    return jax.random.normal(key)\n",
        "key-reuse")
    assert _ids(fs) == ["key-reuse"]


def test_key_reuse_clean_after_split_refresh():
    fs = _scan(
        "import jax\n"
        "def f(key):\n"
        "    k1, k2 = jax.random.split(key)\n"
        "    return jax.random.normal(k1) + jax.random.uniform(k2)\n",
        "key-reuse")
    assert fs == []


def test_key_reuse_clean_on_exclusive_branches():
    fs = _scan(
        "import jax\n"
        "def f(key, flag):\n"
        "    if flag:\n"
        "        x = jax.random.normal(key)\n"
        "    else:\n"
        "        x = jax.random.uniform(key)\n"
        "    return x\n",
        "key-reuse")
    assert fs == []


def test_key_reuse_clean_on_guard_return_chain():
    # a branch that returns must not leak its consumption into the
    # fall-through path (the BatchedChannel.sample dispatch shape)
    fs = _scan(
        "import jax\n"
        "def f(kind, key):\n"
        "    if kind == 'a':\n"
        "        return jax.random.normal(key)\n"
        "    if kind == 'b':\n"
        "        return jax.random.gamma(key, 1.0)\n"
        "    return jax.random.uniform(key)\n",
        "key-reuse")
    assert fs == []


def test_key_reuse_clean_inside_with_block():
    # a with body is scanned once, like any other block
    fs = _scan(
        "import jax\n"
        "def f(key):\n"
        "    with jax.named_scope('rollout'):\n"
        "        k1, k2 = jax.random.split(key)\n"
        "    return jax.random.normal(k1) + jax.random.uniform(k2)\n",
        "key-reuse")
    assert fs == []


def test_key_reuse_trips_across_with_block():
    fs = _scan(
        "import jax\n"
        "def f(key):\n"
        "    with jax.named_scope('rollout'):\n"
        "        a = jax.random.normal(key)\n"
        "    return a + jax.random.uniform(key)\n",
        "key-reuse")
    assert _ids(fs) == ["key-reuse"] and fs[0].line == 5


def test_key_reuse_trips_on_cross_iteration_reuse():
    fs = _scan(
        "import jax\n"
        "def f(key, n):\n"
        "    total = 0.0\n"
        "    for _ in range(n):\n"
        "        total += jax.random.normal(key)\n"
        "    return total\n",
        "key-reuse")
    assert _ids(fs) == ["key-reuse"]


def test_key_reuse_clean_with_per_iteration_fold():
    fs = _scan(
        "import jax\n"
        "def f(key, n):\n"
        "    total = 0.0\n"
        "    for i in range(n):\n"
        "        key, sub = jax.random.split(key)\n"
        "        total += jax.random.normal(sub)\n"
        "    return total\n",
        "key-reuse")
    assert fs == []


def test_key_reuse_tracks_constant_subscripts():
    fs = _scan(
        "import jax\n"
        "def f(key):\n"
        "    ks = jax.random.split(key, 3)\n"
        "    a = jax.random.normal(ks[0])\n"
        "    b = jax.random.uniform(ks[0])\n"
        "    return a + b\n",
        "key-reuse")
    assert _ids(fs) == ["key-reuse"]


# ---------------------------------------------------------------------------
# deprecated-aggregation
# ---------------------------------------------------------------------------

def test_deprecated_aggregation_trips_on_call_and_import():
    fs = _scan(
        "from repro.core.ota import exact_aggregate\n"
        "def f(g, key):\n"
        "    return exact_aggregate(g)\n",
        "deprecated-aggregation")
    assert len(fs) == 2  # the import and the call


def test_deprecated_aggregation_clean_on_new_api():
    fs = _scan(
        "from repro.core import ota\n"
        "def f(cfg, g, key):\n"
        "    return ota.aggregate(cfg, g, key)\n",
        "deprecated-aggregation")
    assert fs == []


def test_deprecated_aggregation_excludes_owner_module():
    # the module that defines the deprecated wrappers may reference them
    fs = _scan(
        "def exact_aggregate(g):\n"
        "    return g\n"
        "x = exact_aggregate(None)\n",
        "deprecated-aggregation", relpath="src/repro/core/ota.py")
    assert fs == []


# ---------------------------------------------------------------------------
# xla-flags
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("line", [
    "os.environ['XLA_FLAGS'] = '--xla_foo'",
    "os.environ['XLA_FLAGS'] += ' --xla_foo'",
    "os.environ.setdefault('XLA_FLAGS', '--xla_foo')",
    "os.environ.update({'XLA_FLAGS': '--xla_foo'})",
    "os.putenv('XLA_FLAGS', '--xla_foo')",
])
def test_xla_flags_trips_on_mutation(line):
    fs = _scan(f"import os\n{line}\n", "xla-flags")
    assert _ids(fs) == ["xla-flags"]


def test_xla_flags_clean_on_reads():
    fs = _scan(
        "import os\n"
        "a = os.environ.get('XLA_FLAGS', '')\n"
        "b = os.environ['XLA_FLAGS']\n",
        "xla-flags")
    assert fs == []


def test_xla_flags_excludes_owner_module():
    fs = _scan("import os\nos.environ['XLA_FLAGS'] = 'x'\n",
               "xla-flags", relpath="src/repro/utils/platform.py")
    assert fs == []


# ---------------------------------------------------------------------------
# raw-timing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("line", [
    "t0 = time.perf_counter()",
    "t0 = time.perf_counter_ns()",
    "t0 = time.monotonic()",
])
def test_raw_timing_trips_on_clock_calls(line):
    fs = _scan(f"import time\n{line}\n", "raw-timing")
    assert _ids(fs) == ["raw-timing"]


def test_raw_timing_trips_through_aliases():
    fs = _scan("import time as t\nx = t.perf_counter()\n", "raw-timing")
    assert _ids(fs) == ["raw-timing"]
    fs = _scan("from time import perf_counter as pc\nx = pc()\n",
               "raw-timing")
    assert _ids(fs) == ["raw-timing"]


def test_raw_timing_clean_on_span_usage_and_time_time():
    src = ("import time\n"
           "from repro.telemetry import trace\n"
           "ts = time.time()\n"
           "with trace.span('work'):\n"
           "    pass\n")
    assert _scan(src, "raw-timing") == []


def test_raw_timing_noqa_suppresses():
    src = ("import time\n"
           "t0 = time.perf_counter()  # repro: noqa[raw-timing]\n")
    assert _scan(src, "raw-timing") == []


def test_raw_timing_excludes_owner_package():
    fs = _scan("import time\nt0 = time.perf_counter()\n", "raw-timing",
               relpath="src/repro/telemetry/trace.py")
    assert fs == []


# ---------------------------------------------------------------------------
# in-jit pitfalls
# ---------------------------------------------------------------------------

def test_np_under_trace_trips_on_traced_arg():
    fs = _scan(
        "import jax\nimport numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.mean(x)\n",
        "np-under-trace")
    assert _ids(fs) == ["np-under-trace"]


def test_np_under_trace_clean_on_static_math_and_untraced():
    fs = _scan(
        "import jax\nimport numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x * np.sqrt(2.0)\n"   # static scalar: fine
        "def g(x):\n"
        "    return np.mean(x)\n",        # not traced: fine
        "np-under-trace")
    assert fs == []


def test_tracer_leak_trips_on_float_of_param():
    fs = _scan(
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x)\n",
        "tracer-leak")
    assert _ids(fs) == ["tracer-leak"]


def test_tracer_leak_clean_outside_trace_and_on_constants():
    fs = _scan(
        "import jax\n"
        "def g(x):\n"
        "    return float(x)\n"           # eager: fine
        "@jax.jit\n"
        "def f(x):\n"
        "    return x * float('1e-3')\n"  # constant: fine
        ,
        "tracer-leak")
    assert fs == []


def test_traced_branch_trips_on_jnp_predicate():
    fs = _scan(
        "import jax\nimport jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if jnp.any(x):\n"
        "        return x\n"
        "    return -x\n",
        "traced-branch")
    assert _ids(fs) == ["traced-branch"]


def test_traced_branch_clean_on_static_predicate():
    fs = _scan(
        "import jax\n"
        "@jax.jit\n"
        "def f(x, n=3):\n"
        "    if n > 2:\n"        # static python arg: fine
        "        return x\n"
        "    return -x\n",
        "traced-branch")
    assert fs == []


def test_jit_in_loop_trips_in_for_and_comprehension():
    fs = _scan(
        "import jax\n"
        "def f(fns):\n"
        "    out = []\n"
        "    for g in fns:\n"
        "        out.append(jax.jit(g))\n"
        "    return out + [jax.jit(g) for g in fns]\n",
        "jit-in-loop")
    assert len(fs) == 2 and _ids(fs) == ["jit-in-loop"]


def test_jit_in_loop_clean_at_module_level_and_in_nested_def():
    fs = _scan(
        "import jax\n"
        "h = jax.jit(lambda x: x)\n"
        "def f(fns):\n"
        "    makers = []\n"
        "    for g in fns:\n"
        "        def mk(g=g):\n"
        "            return jax.jit(g)\n"  # fresh scope per call anyway
        "        makers.append(mk)\n"
        "    return makers\n",
        "jit-in-loop")
    assert fs == []


# ---------------------------------------------------------------------------
# suppression + report plumbing
# ---------------------------------------------------------------------------

def test_noqa_suppresses_named_rule():
    src = ("import os\n"
           "os.environ['XLA_FLAGS'] = 'x'  # repro: noqa[xla-flags]\n")
    assert _scan(src, "xla-flags") == []


def test_noqa_blanket_suppresses_everything():
    src = ("import os\n"
           "os.environ['XLA_FLAGS'] = 'x'  # repro: noqa\n")
    assert _scan(src, "xla-flags") == []


def test_noqa_wrong_id_does_not_suppress():
    src = ("import os\n"
           "os.environ['XLA_FLAGS'] = 'x'  # repro: noqa[key-reuse]\n")
    assert _ids(_scan(src, "xla-flags")) == ["xla-flags"]


def test_noqa_rules_parser():
    assert noqa_rules("x = 1") is None
    assert noqa_rules("x = 1  # repro: noqa") == frozenset()
    assert noqa_rules("x = 1  # repro: noqa[a-b, c]") == frozenset({"a-b", "c"})


def test_report_exit_codes():
    warn = Report(findings=[Finding("jit-in-loop", "warning", "x.py", 1, "m")])
    assert warn.exit_code() == 0 and warn.exit_code(strict=True) == 1
    err = Report(findings=[Finding("key-reuse", "error", "x.py", 1, "m")])
    assert err.exit_code() == 1 and err.exit_code(strict=True) == 1
    assert Report().exit_code(strict=True) == 0


def test_suppressed_findings_still_counted():
    src = ("import os\n"
           "os.environ['XLA_FLAGS'] = 'x'  # repro: noqa[xla-flags]\n")
    report = Report()
    from repro.analyze.engine import scan_module
    from repro.analyze.astutils import ModuleContext
    import ast as ast_mod
    import pathlib
    ctx = ModuleContext(path=pathlib.Path("<s>"), relpath="<s>",
                        tree=ast_mod.parse(src), source_lines=src.splitlines())
    scan_module(ctx, get_rules(["xla-flags"]), report)
    assert report.findings == [] and len(report.suppressed) == 1
    assert report.counts["suppressed"] == 1
    assert json.loads(report.to_json())["counts"]["suppressed"] == 1


# ---------------------------------------------------------------------------
# the planted-violation module: every rule class through the real CLI
# ---------------------------------------------------------------------------

_PLANTED = '''\
import os
import time
import jax
import jax.numpy as jnp
import numpy as np
from repro.core.ota import exact_aggregate

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
t0 = time.perf_counter()

def reuse(key):
    a = jax.random.normal(key)
    return a + jax.random.uniform(key)

@jax.jit
def traced(x):
    if jnp.any(x):
        return float(x)
    return np.mean(x)

def loop(fns):
    return [jax.jit(g) for g in fns]
'''

_ALL_RULE_CLASSES = [
    "deprecated-aggregation", "jit-in-loop", "key-reuse", "np-under-trace",
    "raw-timing", "traced-branch", "tracer-leak", "xla-flags",
]


def test_planted_module_trips_every_rule_class(tmp_path):
    p = tmp_path / "planted.py"
    p.write_text(_PLANTED)
    report = run([str(p)], ast_only=True)
    assert _ids(report.findings) == _ALL_RULE_CLASSES
    assert report.exit_code(strict=True) == 1


def test_cli_strict_nonzero_on_planted_zero_on_clean(tmp_path):
    bad, good = tmp_path / "bad.py", tmp_path / "good.py"
    bad.write_text(_PLANTED)
    good.write_text("import jax\n\ndef f(key):\n"
                    "    return jax.random.normal(key)\n")
    out = tmp_path / "r.json"
    rc = analyze_main([str(bad), "--ast-only", "--strict",
                       "--json", str(out)])
    assert rc == 1
    data = json.loads(out.read_text())
    assert sorted({f["rule"] for f in data["findings"]}) == _ALL_RULE_CLASSES
    assert analyze_main([str(good), "--ast-only", "--strict",
                         "--json", ""]) == 0


# ---------------------------------------------------------------------------
# layer 2: trace-level contracts
# ---------------------------------------------------------------------------

def test_lane_contract_passes_every_registered_family():
    from repro.analyze import contracts
    from repro.rl.envs import registered_envs

    report = Report()
    contracts.check_lane_contract(report)
    assert report.findings == [], "\n".join(f.render() for f in report.findings)
    # every family was actually visited (no silent coverage loss): families
    # without a continuous axis leave a skip note instead
    assert len(registered_envs()) >= 6  # the zoo as of this PR


def test_lane_contract_fails_constant_promoted_mutant(monkeypatch):
    # promote a partition constant to a dynamic argument: broadcast lane 0's
    # alpha over the packed axis, exactly the bug that un-folds an XLA
    # literal and lets lanes drift from the per-scenario reference
    from repro.analyze import contracts
    from repro.core import sweep as sweep_mod

    orig = sweep_mod._pack_partition

    def mutant(part):
        packed = orig(part)
        if "alpha" in packed:
            packed["alpha"] = jnp.broadcast_to(
                packed["alpha"][:1], packed["alpha"].shape)
        return packed

    monkeypatch.setattr(sweep_mod, "_pack_partition", mutant)
    report = Report()
    contracts.check_lane_contract(report, families=["landmark"])
    msgs = [f.message for f in report.findings if f.rule == "lane-contract"]
    assert any("identical across lanes" in m for m in msgs), msgs


def test_lane_contract_fails_extra_packed_axis_mutant(monkeypatch):
    # pack an axis that does not vary: the set-equality leg must flag it
    from repro.analyze import contracts
    from repro.core import sweep as sweep_mod

    orig = sweep_mod._pack_partition

    def mutant(part):
        packed = orig(part)
        if part.proto.channel is not None and "noise_sigma" not in packed:
            n = len(part.scenarios)
            packed["noise_sigma"] = jnp.full((n,), 1e-3, jnp.float32)
        return packed

    monkeypatch.setattr(sweep_mod, "_pack_partition", mutant)
    report = Report()
    contracts.check_lane_contract(report, families=["landmark"])
    msgs = [f.message for f in report.findings if f.rule == "lane-contract"]
    assert any("packed axes" in m for m in msgs), msgs


def test_wire_dtype_passes_real_uplink():
    from repro.analyze import contracts

    report = Report()
    contracts.check_wire_dtype(report)
    assert report.findings == [], "\n".join(f.render() for f in report.findings)


def test_wire_dtype_flags_planted_narrowing(monkeypatch):
    from repro.analyze import contracts
    from repro.core import ota as ota_mod

    def narrowed(cfg, **kw):
        return jax.make_jaxpr(lambda g: g.astype(jnp.float16))(
            jnp.zeros((4, 8), jnp.float32))

    monkeypatch.setattr(ota_mod, "uplink_jaxpr", narrowed)
    report = Report()
    contracts.check_wire_dtype(report)
    msgs = [f.message for f in report.findings if f.rule == "wire-dtype"]
    assert any("unsanctioned float narrowing" in m for m in msgs), msgs


def test_narrowing_converts_unit():
    from repro.analyze.contracts import narrowing_converts

    down = jax.make_jaxpr(lambda x: x.astype(jnp.bfloat16))(
        jnp.zeros((3,), jnp.float32))
    assert narrowing_converts(down) == [("float32", "bfloat16")]
    up = jax.make_jaxpr(lambda x: x.astype(jnp.float32))(
        jnp.zeros((3,), jnp.float16))
    assert narrowing_converts(up) == []
    to_int = jax.make_jaxpr(lambda x: x.astype(jnp.int8))(
        jnp.zeros((3,), jnp.float32))
    assert narrowing_converts(to_int) == []  # int casts are not wire dtypes


def test_compile_budget_passes_real_caches(compile_counter):
    from repro.analyze import contracts

    report = Report()
    contracts.check_compile_budget(report)
    assert report.findings == [], "\n".join(f.render() for f in report.findings)


def test_compile_budget_flags_planted_cache_buster(monkeypatch, compile_counter):
    from repro.analyze import contracts
    from repro.core import fedpg

    orig = fedpg.monte_carlo

    def cache_busting(*args, **kwargs):
        fedpg.clear_compilation_cache()   # the recompile-per-call bug
        return orig(*args, **kwargs)

    monkeypatch.setattr(fedpg, "monte_carlo", cache_busting)
    report = Report()
    contracts.check_compile_budget(report)
    msgs = [f.message for f in report.findings if f.rule == "compile-budget"]
    assert any("recompiled" in m for m in msgs), msgs


def test_collective_audit_single_device_skips():
    from repro.analyze import contracts

    if jax.device_count() >= 2:
        pytest.skip("multi-device host: the audit runs for real here")
    report = Report()
    contracts.check_collectives(report)
    assert report.findings == []
    assert any("collective-audit" in note for note in report.skipped)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 devices (REPRO_EMULATED_DEVICES=8)")
def test_collective_audit_passes_on_mesh():
    from repro.analyze import contracts

    report = Report()
    contracts.check_collectives(report)
    errors = [f for f in report.findings if f.severity == "error"]
    assert errors == [], "\n".join(f.render() for f in errors)

def test_stream_contract_passes_real_streaming():
    from repro.analyze import contracts

    report = Report()
    contracts.check_stream_contract(report)
    assert report.findings == [], "\n".join(f.render() for f in report.findings)


def test_stream_contract_flags_unblocked_layout_mutant(monkeypatch):
    # collapse the blocked layout to one fleet-sized block: the rollout scan
    # carry becomes (N, M, ...) and must differ between the two traced fleet
    # sizes — exactly the "carry grows with N" regression the check exists for
    from repro.analyze import contracts
    from repro.core import ota as ota_mod

    monkeypatch.setattr(ota_mod, "blocked_layout",
                        lambda n, b: (1, int(n), 0))
    report = Report()
    contracts.check_stream_contract(report)
    msgs = [f.message for f in report.findings if f.rule == "stream-contract"]
    assert any("grows with the fleet" in m or "scales with the fleet" in m
               for m in msgs), msgs
