"""Shared helpers for the benchmark harness.

Importing this module applies the process platform config (see
``repro.utils.platform``): ``REPRO_EMULATED_DEVICES=8`` runs the same
benches on an emulated 8-device CPU mesh that a real accelerator job runs
on hardware — no per-job ``XLA_FLAGS`` surgery — and turns on the
persistent compile cache (``rplat.enable_compile_cache``).

Timing runs through ``repro.telemetry.trace``: ``time_call`` returns a
:class:`~repro.telemetry.trace.Timing` (a float carrying ``compile_us`` /
``run_us``) and every call lands as ``compile:<name>`` / ``run:<name>``
spans in the process trace, exportable with ``benchmarks.run --trace``.
``emit`` rows are dicts with those fields and mirror to the ambient run
ledger when ``--ledger`` installed one.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

from repro.utils import platform as rplat  # pre-jax: may set device flags

rplat.apply_emulated_devices()
rplat.enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.telemetry import get_ledger  # noqa: E402
from repro.telemetry import trace as rtrace  # noqa: E402

# structured row records; formatted only at print time so consumers (the
# --json export, the run ledger) never re-parse CSV strings
ROWS: List[Dict[str, Any]] = []


def emit(name: str, us_per_call: float, derived: str) -> None:
    row: Dict[str, Any] = {"name": name, "us_per_call": float(us_per_call),
                           "derived": derived}
    # Timing (from time_call) carries the compile/run split; a bare float
    # (derived rates, totals) leaves the fields absent.
    if isinstance(us_per_call, rtrace.Timing):
        row["run_us"] = us_per_call.run_us
        if us_per_call.compile_us is not None:
            row["compile_us"] = us_per_call.compile_us
    ROWS.append(row)
    led = get_ledger()
    if led is not None:
        led.event("bench_row", **row)
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def time_call(fn: Callable, *args, warmup: int = 1, iters: int = 5,
              name: Optional[str] = None) -> rtrace.Timing:
    """Median wall time per call in microseconds (blocks on jax arrays).

    Returns a :class:`~repro.telemetry.trace.Timing`: the median run time
    as a plain float, with the first-warmup (compile) time on
    ``.compile_us``.  Both phases land as spans named after ``fn`` (or
    ``name=``).
    """
    return rtrace.timed_call(
        fn, *args, warmup=warmup, iters=iters,
        block=jax.block_until_ready, name=name)


def run_setting(env, pol, cfg, ota, mc_runs: int, seed: int = 0):
    """Monte Carlo fedpg histories (vmapped); returns (rewards, grad_sq).

    The naive per-scenario path — one fresh XLA program per call.  Kept as
    the reference the sweep engine is tested bit-identical against; new
    benchmarks should declare a scenario grid and use ``run_sweep``.
    """
    from repro.core import fedpg

    hist = fedpg.monte_carlo(env, pol, cfg, jax.random.key(seed), mc_runs,
                             ota=ota)
    return hist.rewards, hist.grad_sq


def run_sweep(env, pol, scenarios, mc_runs: int, seed: int = 0):
    """Run a declarative scenario list through the batched sweep engine.

    One compiled program per structural partition; every scenario shares the
    Monte-Carlo key set of ``jax.random.key(seed)`` — the same keys the
    per-scenario ``run_setting(..., seed=seed)`` calls would use.  The
    result is mirrored to the ambient run ledger when one is installed.
    """
    from repro.core.sweep import sweep

    res = sweep(env, pol, scenarios, jax.random.key(seed), mc_runs)
    led = get_ledger()
    if led is not None:
        led.log_sweep(res)
    return res


def final_reward(rewards: jnp.ndarray, tail: int = 20) -> float:
    return float(jnp.mean(rewards[:, -tail:]))


def avg_grad_sq(grad_sq: jnp.ndarray) -> float:
    """(1/K) sum_k E||grad J||^2, averaged over MC runs (paper Fig. 2/5)."""
    return float(jnp.mean(grad_sq))
