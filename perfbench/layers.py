"""Device time of the round's four layers, from the program's own map of
device ops to named scopes (``repro.telemetry.scopes``).

The map is built once per run, for the executable the program's entry
point ran last (the traced call), and joined with the trace's time per op
name.  Control-flow ops (``while``, ``conditional``, ``call``) span the
ops of their bodies and are skipped: only leaf ops count, so the four
scopes together never exceed the device's busy time.  A program without the map
(no ``repro.telemetry.scopes``, or no recorded call) gives None.
"""
from __future__ import annotations

from typing import Dict, Optional

def op_scopes(ctx) -> Optional[Dict[str, str]]:
    """``{op name: scope}`` of the traced call's executable (the last call
    the program recorded), built on first use and kept on ``ctx``; None
    where the program has no such map."""
    if not hasattr(ctx, "op_scopes"):
        try:
            from repro.telemetry import scopes
        except ImportError:
            ctx.op_scopes = None
        else:
            ctx.op_scopes = scopes.op_scopes()
    return ctx.op_scopes


def window_share(summary, ops: Dict[str, str], scope: str) -> float:
    """Sum over chips of the scope's leaf-op device time over the chip's
    traced window."""
    total = 0.0
    for d in summary.devices:
        if d.window_ns > 0:
            total += sum(ns for op, ns in d.op_ns.items()
                         if ops.get(op) == scope) / d.window_ns
    return total


def ns_per_agent_step(ctx, scope: str) -> Optional[float]:
    """Device ns the scope takes per agent-step of the run's rate: its
    window share summed over chips, times 1e9 / agent-steps per second."""
    ops = op_scopes(ctx)
    if not ops or ctx.rate <= 0:
        return None
    return window_share(ctx.summary, ops, scope) * 1e9 / ctx.rate


def last_commit(ctx):
    """The traced call's commit span (the last ``service_commit`` the
    program's tracer holds), or None."""
    try:
        from repro.telemetry import trace
    except ImportError:
        return None
    commits = [s for s in trace.spans() if s.name == "service_commit"]
    return commits[-1] if commits else None
