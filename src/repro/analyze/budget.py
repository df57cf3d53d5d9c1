"""XLA compile counting as an importable checker.

This generalises what used to be a test-only fixture in
``tests/conftest.py``: a process-wide listener on jax's
``backend_compile`` telemetry, a :class:`CompileCounter` context manager,
and :func:`warm_eager_helpers`, which compiles JAX's eager scaffolding
(key splits, float32 packing converts, effective-moment math,
``l_bar_for``, env-registry packers, History unstacking) once per process
so counts taken afterwards are partition/lane programs only.

``tests/conftest.py`` re-exports these for the ``compile_counter``
fixture; ``repro.analyze.contracts.check_compile_budget`` uses them to
machine-enforce the no-recompile-per-call invariant in CI.

The listener must be registered once per process; ``jax.monitoring``
offers no unregister, so the counter toggles an "active" flag instead.
It also keeps a running total, :func:`backend_compiles`, that callers
difference around a region of their own (``RoundService.commit`` records
its commit's compiles that way).
"""
from __future__ import annotations

_COMPILE_COUNTER = {"active": False, "count": 0, "total": 0}
_LISTENER_REGISTERED = False
_EAGER_HELPERS_WARMED = False

_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_event_duration(event: str, *args, **kwargs) -> None:
    if event == _EVENT:
        _COMPILE_COUNTER["total"] += 1
        if _COMPILE_COUNTER["active"]:
            _COMPILE_COUNTER["count"] += 1


def _ensure_listener() -> None:
    global _LISTENER_REGISTERED
    if _LISTENER_REGISTERED:
        return
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    _LISTENER_REGISTERED = True


def backend_compiles() -> int:
    """Backend compiles in this process since the listener was first
    registered (this call registers it if no one has)."""
    _ensure_listener()
    return _COMPILE_COUNTER["total"]


class CompileCounter:
    """Context manager counting XLA backend compilations while active.

    ``with CompileCounter() as c: ...; c.count`` — nesting is not
    supported (one process-wide flag).
    """

    def __init__(self):
        self.count = 0

    def __enter__(self):
        _ensure_listener()
        _COMPILE_COUNTER["count"] = 0
        _COMPILE_COUNTER["active"] = True
        return self

    def __exit__(self, *exc):
        _COMPILE_COUNTER["active"] = False
        self.count = _COMPILE_COUNTER["count"]
        return False


def warm_eager_helpers() -> None:
    """Compile JAX's eager scaffolding ONCE per process so compile counters
    compare partition programs, not cold-start helpers.

    A sweep's first run also compiles tiny eager dispatches — key
    splitting, float32 packing converts, effective-moment math,
    ``l_bar_for``, the env registry packer, History unstacking slices.
    Shapes here are deliberately distinct from any real test's so no
    *partition* program is pre-compiled on the caller's behalf.
    """
    global _EAGER_HELPERS_WARMED
    if _EAGER_HELPERS_WARMED:
        return
    import jax

    from repro.core import fedpg
    from repro.core.channel import RayleighChannel
    from repro.core.power_control import (
        TruncatedInversion, make_controlled_channel,
    )
    from repro.core.sweep import grid, resolve_env_policy, sweep
    from repro.rl.envs import WindyLandmarkNav

    tiny = dict(n_agents=2, batch_m=1, horizon=3, n_rounds=2, debias=True)
    chan = make_controlled_channel(RayleighChannel(), TruncatedInversion())
    scens = grid(env=[WindyLandmarkNav(wind=w) for w in (0.0, 0.31, 0.62)],
                 channel=[chan], noise_sigma=1e-3, **tiny)
    key = jax.random.key(99)
    # mc_runs=2 matches the sweep tests' Monte-Carlo width, so the tiny
    # split/convert programs they dispatch are all compiled here
    sweep(None, None, scens, key, 2)
    for s in scens[:1]:
        fedpg.monte_carlo(*resolve_env_policy(s), s.fedpg_config(), key, 2,
                          ota=s.ota_config())
    fedpg.clear_compilation_cache()
    _EAGER_HELPERS_WARMED = True
