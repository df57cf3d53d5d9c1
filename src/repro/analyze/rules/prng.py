"""``key-reuse``: a PRNG key consumed by two ``jax.random.*`` calls.

JAX keys are single-use: feeding the same key to two ``jax.random``
consumers (or using a key again after splitting it) silently correlates
the two draws — in this codebase that means correlated channel gains and
AWGN, a *wrong-science* bug the histories never reveal.  The rule runs a
linear abstract interpretation over each function body:

* a ``jax.random.<fn>(key, ...)`` call *consumes* ``key`` (``split`` and
  ``fold_in`` included — using the parent key after splitting it is the
  classic form of this bug);
* any assignment to the name *refreshes* it (``key, sub = split(key)``);
* ``if``/``else`` branches are analysed independently on copies of the
  state and merged by union, so exclusive-branch consumption does not
  false-positive;
* loop bodies are analysed twice, so a key consumed every iteration
  without a per-iteration ``fold_in``/``split`` refresh is caught
  (cross-iteration reuse).

Keys are tracked as names (``key``) and constant-subscript names
(``ks[0]``); anything fancier (attributes, dynamic subscripts) is out of
scope.  Nested function bodies are analysed as their own scopes.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from repro.analyze.astutils import FuncNode, ModuleContext, dotted_name
from repro.analyze.findings import Finding
from repro.analyze.rules import Rule, register_rule

# jax.random callables whose FIRST positional argument is a consumed key.
# (jax.random.key / PRNGKey are constructors, not consumers; wrappers like
# ota.aggregate take key= but route it to exactly one consumer themselves.)
CONSUMERS = frozenset({
    "split", "fold_in", "bits", "normal", "uniform", "randint", "choice",
    "permutation", "shuffle", "bernoulli", "categorical", "gumbel",
    "laplace", "logistic", "exponential", "gamma", "beta", "dirichlet",
    "poisson", "rademacher", "truncated_normal", "t", "cauchy", "ball",
    "orthogonal", "multivariate_normal", "loggamma", "binomial",
})

# dotted prefixes that denote the jax.random module
_RANDOM_PREFIXES = ("jax.random.", "random.", "jrandom.", "jr.")


def _consumer_key_expr(call: ast.Call) -> Optional[ast.AST]:
    """The consumed key expression of a jax.random consumer call, else None.

    Bare ``random.*`` only counts when the module was imported from jax
    (callers pass an alias map); to stay import-robust we accept the
    ``random.`` prefix but require the attribute to be a known consumer —
    stdlib ``random`` has none of these taking a key first.
    """
    dotted = dotted_name(call.func)
    if not dotted:
        return None
    head, _, attr = dotted.rpartition(".")
    if attr not in CONSUMERS:
        return None
    if not any((head + ".").startswith(p) or (head + ".") == p
               for p in _RANDOM_PREFIXES):
        return None
    if call.args:
        return call.args[0]
    for kw in call.keywords:
        if kw.arg == "key":
            return kw.value
    return None


def _key_id(expr: ast.AST) -> Optional[str]:
    """Canonical tracked id: ``key`` for Name, ``ks[0]`` for a
    constant-subscripted Name, None otherwise."""
    if isinstance(expr, ast.Name):
        return expr.id
    if (isinstance(expr, ast.Subscript)
            and isinstance(expr.value, ast.Name)
            and isinstance(expr.slice, ast.Constant)):
        return f"{expr.value.id}[{expr.slice.value!r}]"
    return None


def _assigned_names(node: ast.AST) -> Set[str]:
    """Names (re)bound by an assignment-like statement."""
    names: Set[str] = set()

    def collect(t: ast.AST):
        if isinstance(t, ast.Name):
            names.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                collect(e)
        elif isinstance(t, ast.Starred):
            collect(t.value)
        elif isinstance(t, ast.Subscript) and isinstance(t.value, ast.Name):
            kid = _key_id(t)
            names.add(kid if kid is not None else t.value.id)

    if isinstance(node, ast.Assign):
        for t in node.targets:
            collect(t)
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign, ast.NamedExpr)):
        collect(node.target)
    elif isinstance(node, ast.For):
        collect(node.target)
    elif isinstance(node, (ast.With, ast.AsyncWith)):
        for item in node.items:
            if item.optional_vars is not None:
                collect(item.optional_vars)
    return names


def _terminates(body: List[ast.stmt]) -> bool:
    """Whether a block ends by leaving the enclosing flow (guard-style
    ``if kind == ...: return consume(key)`` chains must not leak their
    branch's consumption into the fall-through path)."""
    return bool(body) and isinstance(
        body[-1], (ast.Return, ast.Raise, ast.Break, ast.Continue))


class _State:
    """name -> line of the consuming call (None = fresh)."""

    def __init__(self):
        self.consumed: Dict[str, int] = {}

    def copy(self) -> "_State":
        s = _State()
        s.consumed = dict(self.consumed)
        return s

    def merge(self, *others: "_State") -> None:
        for o in others:
            for k, v in o.consumed.items():
                self.consumed.setdefault(k, v)

    def refresh(self, names: Set[str]) -> None:
        for n in names:
            self.consumed.pop(n, None)
            # rebinding `ks` also refreshes every tracked `ks[...]`
            prefix = n + "["
            for tracked in [t for t in self.consumed if t.startswith(prefix)]:
                self.consumed.pop(tracked, None)


@register_rule
class KeyReuseRule(Rule):
    id = "key-reuse"
    severity = "error"
    description = ("a PRNG key is consumed by two jax.random calls "
                   "(or used again after being split)")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        findings: List[Finding] = []
        # module top level + every function body, each as its own scope
        scopes: List[List[ast.stmt]] = [ctx.tree.body]
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.append(node.body)
        seen: Set[Tuple[int, str]] = set()
        for body in scopes:
            self._scan_block(body, _State(), findings, ctx)
        for f in findings:
            dedup = (f.line, f.message)
            if dedup not in seen:
                seen.add(dedup)
                yield f

    # -- the linear walk ---------------------------------------------------

    def _consume_in_stmt(self, stmt: ast.stmt, state: _State,
                         findings: List, ctx: ModuleContext) -> None:
        """Find consumer calls in ``stmt`` (excluding nested function
        bodies, which are separate scopes) and update/flag."""
        # nested defs/lambdas are their own scopes; ast.walk would still
        # yield their children, so collect and skip them explicitly
        nested: Set[ast.AST] = set()
        for node in ast.walk(stmt):
            if isinstance(node, FuncNode) and node is not stmt:
                nested.update(ast.walk(node))
        for node in ast.walk(stmt):
            if node in nested or not isinstance(node, ast.Call):
                continue
            key_expr = _consumer_key_expr(node)
            if key_expr is None:
                continue
            kid = _key_id(key_expr)
            if kid is None:
                continue
            prev = state.consumed.get(kid)
            if prev is not None:
                findings.append(ctx.finding(
                    self, node,
                    f"PRNG key {kid!r} already consumed on line {prev}; "
                    "split/fold_in a fresh subkey instead of reusing it",
                ))
            else:
                state.consumed[kid] = node.lineno

    def _scan_block(self, stmts: List[ast.stmt], state: _State,
                    findings: List, ctx: ModuleContext) -> None:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue  # separate scope (classes: methods scanned there)
            if isinstance(stmt, ast.If):
                self._consume_in_stmt(stmt.test, state, findings, ctx)
                s_then, s_else = state.copy(), state.copy()
                self._scan_block(stmt.body, s_then, findings, ctx)
                self._scan_block(stmt.orelse, s_else, findings, ctx)
                # post-if state is the union of the branch exits that FALL
                # THROUGH (each inherits the pre-state) — a branch ending in
                # return/raise/break/continue contributes nothing, so
                # guard-style dispatch chains don't cross-contaminate; and
                # because the pre-state is not unioned back in, a key
                # refreshed in both live branches reads as fresh afterwards
                exits = [s for s, body in ((s_then, stmt.body),
                                           (s_else, stmt.orelse))
                         if not _terminates(body)]
                if exits:
                    post = exits[0]
                    post.merge(*exits[1:])
                    state.consumed = post.consumed
                continue
            if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                if isinstance(stmt, (ast.For, ast.AsyncFor)):
                    self._consume_in_stmt(stmt.iter, state, findings, ctx)
                    state.refresh(_assigned_names(stmt))
                else:
                    self._consume_in_stmt(stmt.test, state, findings, ctx)
                # two passes: the second catches cross-iteration reuse
                body_state = state.copy()
                self._scan_block(stmt.body, body_state, findings, ctx)
                self._scan_block(stmt.body, body_state, findings, ctx)
                self._scan_block(stmt.orelse, body_state, findings, ctx)
                state.merge(body_state)
                continue
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                # the context expressions only: the body is scanned below
                for item in stmt.items:
                    self._consume_in_stmt(item.context_expr, state,
                                          findings, ctx)
                state.refresh(_assigned_names(stmt))
                self._scan_block(stmt.body, state, findings, ctx)
                continue
            if isinstance(stmt, ast.Try):
                s_try = state.copy()
                self._scan_block(stmt.body, s_try, findings, ctx)
                for handler in stmt.handlers:
                    s_h = state.copy()
                    self._scan_block(handler.body, s_h, findings, ctx)
                    s_try.merge(s_h)
                self._scan_block(stmt.orelse, s_try, findings, ctx)
                self._scan_block(stmt.finalbody, s_try, findings, ctx)
                state.merge(s_try)
                continue
            # plain statement: consumers fire, then assignments refresh
            self._consume_in_stmt(stmt, state, findings, ctx)
            state.refresh(_assigned_names(stmt))
