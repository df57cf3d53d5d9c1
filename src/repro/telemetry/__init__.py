"""Observability for the repro: in-jit probes, span traces, run ledgers.

Three layers, designed to compose:

* :mod:`repro.telemetry.probes` — a static :class:`TelemetryConfig` that,
  threaded through ``fedpg.run``/``monte_carlo``/``sweep``, makes every
  communication round emit a :class:`RoundTelemetry` pytree (effective
  SNR, pre/post-aggregation gradient norms, channel-moment drift, per-agent
  grad-norm dispersion) as extra scan outputs — computed *inside* the
  jitted program.  Telemetry off (the default) is bitwise identical to the
  pre-telemetry programs: the golden-trace suite pins this.
* :mod:`repro.telemetry.trace` — the span tracer (``with trace.span(...)``)
  that owns all wall-clock timing; exports Chrome trace-event JSON
  (Perfetto-loadable) of sweep partition compile/dispatch/materialize and
  benchmark phases, and writes each span into a running profiler trace.
* :mod:`repro.telemetry.scopes` — the round's four named scopes (rollout,
  estimator, uplink, epilogue) and the map from the compiled device ops
  of the last recorded call to them.
* :mod:`repro.telemetry.ledger` — a JSONL event log per run (platform,
  compile counts, per-scenario results vs the Theorem-1/2 floors,
  telemetry summaries) rendered to markdown by
  ``python -m repro.telemetry.report``.

The ``trace``, ``scopes`` and ``ledger`` modules are themselves jax-free
(import them directly in jax-less tooling); only ``probes`` — and this
package init, which re-exports it — pulls in jax.
"""
from repro.telemetry.ledger import (  # noqa: F401
    Ledger, get_ledger, read_ledger, set_ledger, using_ledger,
)
from repro.telemetry.probes import (  # noqa: F401
    RoundTelemetry, TelemetryConfig,
)
from repro.telemetry import trace  # noqa: F401

__all__ = [
    "Ledger", "RoundTelemetry", "TelemetryConfig", "get_ledger",
    "read_ledger", "set_ledger", "trace", "using_ledger",
]
