"""The round's four layers as named scopes, and device ops mapped to them.

Every round body names its work with ``jax.named_scope`` under one of
:data:`SCOPES`:

* ``rollout``   — trajectory sampling (``rl/sampler.rollout_batch``) and
  the per-agent sampling keys;
* ``estimator`` — the G(PO)MDP / REINFORCE gradient, forward and backward
  (``core/gpomdp``);
* ``uplink``    — the channel superposition and the server step: gains,
  participation mask, the gain-weighted fold, the fused kernel, the psum;
* ``epilogue``  — work that only produces the round's reported metrics
  (the exact mean on a noisy uplink, ``grad_sq``, reward and gain means,
  telemetry probes).

The loops that carry the scoped work (the block loop's ``scan``, the
``shard_map``) have no scope of their own, so their own ops (slicing the
blocks, stacking the outputs, the counter, the hand-out to the shards)
read :data:`UNSCOPED`.

Scopes are metadata: XLA copies them into each compiled instruction's
``op_name``, and the profiler's ``XLA Ops`` line names every device op by
its instruction name (``while.127``).  :func:`op_scopes` joins the two for
the executable the last recorded call ran, from that executable's compiled
HLO text.  The entry points (``fedpg.run_jit``, ``fedpg.monte_carlo``,
``RoundService.commit``) call :func:`record` once per call, which keeps
the callable and its arguments' shapes, dtypes and shardings, never the
arrays; the map is built when asked for, by lowering the same callable
for the same arguments, which hits the in-memory executable cache and
compiles nothing.

This module imports jax only inside :func:`record`.
"""
from __future__ import annotations

import re
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = [
    "CONTROL", "EPILOGUE", "ESTIMATOR", "ROLLOUT", "SCOPES", "UNSCOPED",
    "UPLINK", "hlo_scopes", "op_scopes", "record", "scope_of",
]

ROLLOUT, ESTIMATOR, UPLINK, EPILOGUE = SCOPES = (
    "rollout", "estimator", "uplink", "epilogue")
UNSCOPED = "unscoped"
# while / conditional / call ops span the ops of their bodies in a device
# trace: readers skip them and count the leaf ops inside
CONTROL = "control"
CONTROL_OPCODES = ("while", "conditional", "call")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([^\s(=]+)\s*\(.*\{\s*$")
_CALLEE = re.compile(
    r"\b(?:body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# a transform wraps a scope in the name stack: ``transpose(jvp(estimator))``
_WRAPPERS = re.compile(r"^(?:[\w-]+\()+|\)+$")

# (the jitted callable the last recorded call ran, its arguments' specs)
_LAST: Optional[Tuple[Callable, Tuple[Any, ...]]] = None


def record(jitted: Callable, *args: Any) -> None:
    """Note that ``jitted(*args)`` is about to run.  Arrays are kept as
    their ``ShapeDtypeStruct``: enough to lower the call again, and
    nothing that holds device memory."""
    import jax

    def spec(x):
        if not isinstance(x, jax.Array):
            return x
        # an uncommitted array's placement is the call's to choose
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None,
            weak_type=jax.typeof(x).weak_type)

    global _LAST
    _LAST = (jitted, jax.tree.map(spec, args))


def op_scopes() -> Optional[Dict[str, str]]:
    """``{HLO op name: scope}`` of the executable the last recorded call
    ran; None before the first."""
    if _LAST is None:
        return None
    jitted, args = _LAST
    return hlo_scopes(jitted.lower(*args).compile().as_text())


def scope_of(op_name: str) -> str:
    """The innermost of :data:`SCOPES` on an ``op_name`` name stack, or
    :data:`UNSCOPED`."""
    found = UNSCOPED
    for part in op_name.split("/"):
        part = _WRAPPERS.sub("", part)
        if part in SCOPES:
            found = part
    return found


def _opcode(rhs: str) -> str:
    """``(s32[], f32[8]{0}) while(%t), ...`` -> ``while``: skip the result
    shape (a tuple shape is one balanced parenthesis group)."""
    if rhs.startswith("("):
        depth = 0
        for i, c in enumerate(rhs):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        rest = rhs[i + 1:].lstrip()
    else:
        rest = rhs.partition(" ")[2]
    return rest.partition("(")[0]


def _callees(rhs: str):
    """Computations a control-flow instruction runs."""
    out = _CALLEE.findall(rhs)
    for group in _BRANCHES.findall(rhs):
        out += [c.strip().lstrip("%") for c in group.split(",")]
    return out


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: scope}`` over every computation of a compiled
    HLO module's text.  Control-flow instructions map to :data:`CONTROL`.
    An instruction with no scope of its own inside a control-flow body
    takes the scope of the instruction that runs the body: XLA builds
    some loops itself (a TPU expands a scatter into a ``while`` whose body
    ops carry no metadata), and their ops belong to the op they came from.
    """
    comps: Dict[str, list] = {}
    entry, body = None, None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            body = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
            continue
        m = _INSTR.match(line)
        if m is None or body is None:
            continue
        name, rhs = m.groups()
        meta = _OP_NAME.search(rhs)
        callees = _callees(rhs) if _opcode(rhs) in CONTROL_OPCODES else None
        body.append((name, scope_of(meta.group(1)) if meta else UNSCOPED,
                     callees))
    out = {name: CONTROL if callees is not None else scope
           for instrs in comps.values() for name, scope, callees in instrs}
    todo, seen = [(entry, UNSCOPED)], set()
    while todo:
        comp, outer = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, scope, callees in comps[comp]:
            scope = outer if scope == UNSCOPED else scope
            if callees is None:
                out[name] = scope
            else:
                todo += [(c, scope) for c in callees]
    return out
