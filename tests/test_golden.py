"""Golden-trace regression suite: checked-in History fingerprints.

Each ``tests/golden/<family>.json`` pins the fingerprint (first/last-round
``rewards``/``grad_sq`` per MC run, printed through float64 so the float32
values round-trip exactly) of one canonical scenario per env family under
each of three uplinks:

    exact       — Algorithm 1 (channel=None)
    rayleigh    — Algorithm 2 over RayleighChannel + AWGN, debiased
    controlled  — power-controlled uplink (TruncatedInversion over Rayleigh)

Tolerance policy (see tests/README.md): every family compares **exactly**
(the sweep engine's bitwise-lane contract) except ``lqr``, whose traced-
parameter matvec/quadratic fusions may reassociate the last mantissa bit —
it compares at ``rtol=1e-6``.

Regenerating after an INTENTIONAL numerical change:

    python -m pytest tests/test_golden.py --update-golden

then inspect the JSON diff — every changed number is a behaviour change the
PR must justify.  ``tests/test_distribute.py`` reuses these scenarios to
hold ``mode="sharded"`` bit-identical to ``mode="vmap"`` (on one device;
across devices, see ``repro.core.distribute``).
"""
import functools
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.core.channel import RayleighChannel
from repro.core.power_control import TruncatedInversion, make_controlled_channel
from repro.core.sweep import Scenario, sweep
from repro.rl.envs import (
    CliffWalk, LQRTask, MultiLandmarkNav, WindyLandmarkNav, garnet, make_env,
    make_heterogeneous_env,
)

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"

SMALL = dict(n_agents=3, batch_m=2, horizon=6, n_rounds=4)
MC_RUNS = 2
KEY_SEED = 0

# families compare exactly unless listed here (documented reassociation)
RTOL = {"lqr": 1e-6}


def _families():
    """One canonical env per family (deterministic construction)."""
    return {
        "landmark": make_env("landmark"),
        "windy": WindyLandmarkNav(wind=0.05),
        "multilandmark": MultiLandmarkNav(n_landmarks=3),
        "cliffwalk": CliffWalk(width=4, height=3, slip=0.1),
        "lqr": LQRTask(process_sigma=0.1),
        "tabular": garnet(jax.random.key(0), 4, 2, branching=2),
        # one lane per agent (SMALL n_agents=3): the heterogeneous-fleet
        # golden the streamed (agent_blocks) equivalence suite pins against
        "hetero": make_heterogeneous_env(
            [WindyLandmarkNav(wind=w) for w in (0.0, 0.1, 0.2)]),
    }


def _uplinks():
    return {
        "exact": dict(channel=None),
        "rayleigh": dict(channel=RayleighChannel(), noise_sigma=1e-3,
                         debias=True),
        "controlled": dict(
            channel=make_controlled_channel(RayleighChannel(),
                                            TruncatedInversion()),
            noise_sigma=1e-3, debias=True),
    }


def golden_cases():
    """[(family, uplink, Scenario)] — the canonical grid, in a stable order."""
    cases = []
    for fam, env in _families().items():
        for uplink, kw in _uplinks().items():
            cases.append((fam, uplink,
                          Scenario(env=env, tag=f"{fam}:{uplink}", **kw,
                                   **SMALL)))
    return cases


@functools.lru_cache(maxsize=None)
def run_golden_sweep(mode: str = "vmap"):
    """The whole golden grid through one sweep() call; cached so
    test_distribute.py's sharded comparison doesn't recompute the vmap
    reference inside the same process."""
    cases = golden_cases()
    res = sweep(None, None, [s for _, _, s in cases],
                jax.random.key(KEY_SEED), MC_RUNS, mode=mode)
    return {(fam, up): res.scenario_history(i)
            for i, (fam, up, _) in enumerate(cases)}


def fingerprint(hist) -> dict:
    """First/last-round rewards/grad_sq per MC run, as float64-printed
    lists (exact round-trip for the underlying float32 values)."""
    r = np.asarray(hist.rewards, np.float64)
    g = np.asarray(hist.grad_sq, np.float64)
    return {
        "rewards_first": [float(x) for x in r[:, 0]],
        "rewards_last": [float(x) for x in r[:, -1]],
        "grad_sq_first": [float(x) for x in g[:, 0]],
        "grad_sq_last": [float(x) for x in g[:, -1]],
    }


@pytest.mark.parametrize("family", sorted(_families()))
def test_golden_trace(family, request):
    update = request.config.getoption("--update-golden")
    # NB: pass "vmap" explicitly — lru_cache keys () and ("vmap",)
    # separately, and test_distribute.py reuses this exact entry
    hists = run_golden_sweep("vmap")
    got = {up: fingerprint(hists[(family, up)]) for up in _uplinks()}
    path = GOLDEN_DIR / f"{family}.json"

    if update:
        GOLDEN_DIR.mkdir(exist_ok=True)
        doc = {
            "_comment": (
                "Golden History fingerprint (float64-printed float32). "
                "Regenerate ONLY for an intentional numerical change: "
                "python -m pytest tests/test_golden.py --update-golden "
                "— then inspect this diff. Tolerance policy: tests/README.md."
            ),
            "config": {**SMALL, "mc_runs": MC_RUNS, "key_seed": KEY_SEED,
                       "jax": jax.__version__},
            "uplinks": got,
        }
        path.write_text(json.dumps(doc, indent=1) + "\n")
        pytest.skip(f"regenerated {path.name}")

    if not path.exists():
        pytest.fail(f"{path} missing — generate it with --update-golden")
    stored = json.loads(path.read_text())["uplinks"]
    rtol = RTOL.get(family)
    for uplink, fp in got.items():
        for field, vals in fp.items():
            want = stored[uplink][field]
            if rtol is None:
                assert vals == want, (
                    f"{family}/{uplink}/{field}: {vals} != golden {want} "
                    "(exact-compare family; see tests/README.md)")
            else:
                np.testing.assert_allclose(
                    vals, want, rtol=rtol, atol=0.0,
                    err_msg=f"{family}/{uplink}/{field} (rtol={rtol})")


def test_golden_unchanged_by_telemetry():
    """In-jit telemetry probes must not perturb the trained metrics: the
    landmark family re-run with every probe enabled fingerprints bitwise
    identical to the stored (telemetry-off) goldens."""
    from repro.telemetry import TelemetryConfig

    path = GOLDEN_DIR / "landmark.json"
    if not path.exists():
        pytest.skip("landmark golden missing — generate with --update-golden")
    stored = json.loads(path.read_text())["uplinks"]

    env = make_env("landmark")
    scens = [Scenario(env=env, tag=f"landmark:{up}", **kw, **SMALL)
             for up, kw in _uplinks().items()]
    res = sweep(None, None, scens, jax.random.key(KEY_SEED), MC_RUNS,
                telemetry=TelemetryConfig())
    assert res.history.telemetry is not None
    for i, uplink in enumerate(_uplinks()):
        fp = fingerprint(res.scenario_history(i))
        for field, vals in fp.items():
            assert vals == stored[uplink][field], (
                f"telemetry-on landmark/{uplink}/{field}: {vals} != golden "
                f"{stored[uplink][field]}")


def test_golden_covers_every_family_x_uplink():
    """The canonical grid really is families x uplinks, each exactly once."""
    cases = golden_cases()
    assert len(cases) == len(_families()) * len(_uplinks())
    assert len({(f, u) for f, u, _ in cases}) == len(cases)
    # every scenario resolves an env + a policy (no sweep-level defaults)
    from repro.core.sweep import resolve_env_policy
    for _, _, s in cases:
        env, pol = resolve_env_policy(s)
        assert env is not None and pol is not None
