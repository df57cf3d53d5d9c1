"""The comparison that decides ``correct``.

Each round the timed path produced (an :class:`~perfbench.cell.Answer`) is
recomputed by the plain reference from the same seed: the configuration's
task reference (``perfbench/tasks/``) for the trajectories and estimates,
``perfbench/reference.py`` for the uplink and the server step, with the
channel's gains from ``perfbench/channels/<kind>.py``.  The numbers below
are the largest gaps over all rounds compared.

The reference draws everything from the seed by the documented PRNG
schedule: start states, Gumbel noise, channel gains, the uplink's noise
and the participation mask, which it checks against the program's.  It
takes the program's actions only, and then checks them:

A sampled action is ``argmax(logits + Gumbel)``, so a rounding difference
between the program's logits and the reference's can flip a draw at a
near-tie, and one flipped action changes an agent's whole trajectory and
gradient.  The comparison is built so that no such flip can reach a
compared number:

* Where the parameters a round starts from are observable (one round per
  run, or a service commit), the reference replays the program's own
  actions, drawn by the program's rollout code from the program's
  parameters and the round key.  It then checks each of those actions
  against its own logits: an action may lose to another by a near-tie
  only (``tie_miss`` counts those that lose by more than
  :data:`TIE_TOLERANCE`).
* Where they are not (``monte_carlo``: K rounds inside one program), the
  reference draws its own actions and leaves out every run in which some
  draw came within the tie tolerance of another action: in the runs it
  keeps, no rounding of the program's logits can have chosen otherwise.

Relative gaps: ``|program - reference| / |reference|``.  ``update_gap`` is
taken leaf by leaf: the largest difference of a leaf's change over the
round, over that leaf's update size ``alpha * max|u_leaf|`` or the median
leaf's, whichever is larger; the worst leaf counts.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import reference as R
from perfbench.cell import Answer, Cell, host

# An action may trail the reference's best by this much times
# max(1, |logit + Gumbel|), about 8 ulps of float32, and still count as
# a tie that rounding can decide.  One constant for every cell: the
# tolerance is already relative to the level of the logits.
TIE_TOLERANCE = 1e-6


def _rel(a, b) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


class Gaps:
    """Running maxima of the compared numbers, over all calls and per call,
    plus totals for the log."""

    def __init__(self):
        self.numbers: Dict[str, float] = {}
        self.calls: Dict[int, Dict[str, float]] = {}
        self.info: Dict[str, float] = {}

    def put(self, name: str, value: float, call: int):
        value = float(value)
        self.numbers[name] = max(self.numbers.get(name, 0.0), value)
        per = self.calls.setdefault(call, {})
        per[name] = max(per.get(name, 0.0), value)

    def add(self, name: str, value: float):
        self.info[name] = self.info.get(name, 0) + value


_TASKS: Dict[tuple, tuple] = {}


def _fns(cell: Cell, precision: str):
    """The task reference's jitted functions for a cell, compiled once per
    task, configuration and precision in a process."""
    key = (cell.ref.__name__, repr(sorted(cell.config.items())))
    _TASKS[key] = (cell.ref, cell.config)
    return _jitted(key, precision)


@functools.lru_cache(maxsize=None)
def _jitted(key: tuple, precision: str):
    ref, cfg = _TASKS[key]

    def given(theta, d, actions):
        ro = ref.rollout(cfg, theta, d, precision, actions=actions)
        return ro, ref.agent_grads(cfg, theta, ro, precision)

    def own(theta, d):
        ro = ref.rollout(cfg, theta, d, precision)
        return ro, ref.agent_grads(cfg, theta, ro, precision)

    def draws(key, n):
        return ref.round_draws(cfg, key, n)

    def draws_runs(keys, n):
        return jax.vmap(lambda k: draws(k, n))(keys)

    return {"draws": jax.jit(draws, static_argnums=1),
            "draws_runs": jax.jit(draws_runs, static_argnums=1),
            "given": jax.jit(given), "own": jax.jit(own),
            "own_runs": jax.jit(jax.vmap(own))}


def _gains(cell: Cell, key_h):
    return R.gains(cell.chan, cell.config["channel"], key_h, cell.n_agents,
                   cell.gains_indexed())


def _round(cell: Cell, theta, ro, grads, d, mask=None, gains=None):
    """The reference's round from its trajectories and estimates."""
    return R.round_outputs(cell.config, theta, ro.losses, grads,
                           _gains(cell, d.key_h) if gains is None else gains,
                           R.noise(cell.noise_stream(), d.key_n, theta),
                           cell.m_h, mask=mask,
                           sequential=cell.sequential_sum())


def check(cell: Cell, answers: List[Answer], limits: Dict) -> Gaps:
    """The gaps of ``answers`` against the float32 reference.

    ``answers`` come from the program, or from :func:`control_answers`
    (which carry their own actions).
    """
    gaps = Gaps()
    if cell.replays():
        _check_given(cell, answers, gaps)
    else:
        _check_own(cell, answers, gaps)
    return gaps


def update_gap(cell: Cell, theta_out, start, theta_ref, out) -> float:
    """The worst leaf's gap of the round's change of the parameters, each
    side from its own start, over max(that leaf's, the median leaf's)
    update size ``alpha * max|u_leaf|``."""
    upd = R.unflat(out["update"], theta_ref)
    nxt = R.unflat(out["theta_next"], theta_ref)
    size = {k: cell.config["alpha"] * float(np.max(np.abs(upd[k])))
            for k in upd}
    floor = float(np.median(list(size.values())))
    worst = 0.0
    for k in upd:
        change = np.asarray(theta_out[k], np.float64) - \
            np.asarray(start[k], np.float64)
        ref = nxt[k] - np.asarray(theta_ref[k], np.float64)
        diff = float(np.max(np.abs(change - ref)))
        scale = max(size[k], floor)
        # a round nobody made commits no update: then exact equality
        worst = max(worst, diff / scale if scale > 0 else
                    (0.0 if diff == 0 else np.inf))
    return worst


def _compare_round(cell, gaps, ans: Answer, out: Dict, theta_ref=None):
    c = ans.call
    gaps.put("reward_gap", _rel(ans.reward, out["reward"]), c)
    gaps.put("grad_sq_gap", _rel(ans.grad_sq, out["grad_sq"]), c)
    gaps.put("gain_gap", _rel(ans.gain_mean, out["gain_mean"]), c)
    if ans.theta_out is not None:
        start = ans.theta_in if ans.theta_in is not None else theta_ref
        gaps.put("update_gap",
                 update_gap(cell, ans.theta_out, start, theta_ref, out), c)


def _next(out, theta) -> Dict[str, jax.Array]:
    """The parameters after a round, as float32 as the program keeps them."""
    return {k: jnp.asarray(v, jnp.float32)
            for k, v in R.unflat(out["theta_next"], theta).items()}


def _check_given(cell: Cell, answers, gaps):
    cfg, ref, n = cell.config, cell.ref, cell.n_agents
    fns = _fns(cell, "highest")
    mask_of = getattr(cell.entry_mod, "reference_mask", None)
    theta_ref = None
    for ans in sorted(answers, key=lambda a: (a.call, a.run, a.round_idx)):
        if ans.round_idx == 0:
            theta_ref = ref.init_params(cfg, ans.init_key)
        elif theta_ref is None:
            raise ValueError("a run's rounds are checked from its first")
        d = fns["draws"](ans.round_key, n)
        actions = ans.actions
        if actions is None:
            theta_prog = ans.theta_in if ans.theta_in is not None else \
                cell.program["pol"].init(ans.init_key)
            actions = cell.program_actions(theta_prog, ans.round_key)
        ro, g = fns["given"](theta_ref, d, actions)
        # a given action may trail the reference's best by a near-tie only
        flips = int(jnp.sum(ro.margin < 0))
        miss = int(jnp.sum(ro.margin < -TIE_TOLERANCE * ro.level))
        gaps.add("flips", flips)
        gaps.add("tie_miss", miss)
        gaps.put("tie_miss", miss, ans.call)
        mask = None
        if mask_of is not None:
            mask_ref, near = mask_of(cell, ans.round_idx)
            mask = np.asarray(ans.mask if ans.mask is not None
                              else cell.entry_mod.program_mask(cell, ans))
            miss = int(np.sum((mask != mask_ref) & ~near))
            gaps.add("mask_miss", miss)
            gaps.put("mask_miss", miss, ans.call)
        out = _round(cell, theta_ref, ro, g, d, mask)
        _compare_round(cell, gaps, ans, out, theta_ref)
        theta_ref = _next(out, theta_ref)


def _check_own(cell: Cell, answers, gaps):
    cfg, ref, n = cell.config, cell.ref, cell.n_agents
    fns = _fns(cell, "highest")
    by_call = {}
    for a in answers:
        by_call.setdefault(a.call, []).append(a)
    for call, anss in sorted(by_call.items()):
        runs = sorted({a.run for a in anss})
        rounds = max(a.round_idx for a in anss) + 1
        grid = {(a.run, a.round_idx): a for a in anss}
        theta = jax.device_get(jax.tree.map(lambda *x: jnp.stack(x), *[
            ref.init_params(cfg, grid[(r, 0)].init_key) for r in runs]))
        outs = {}
        near = np.zeros(len(runs), bool)
        for k in range(rounds):
            d = fns["draws_runs"](
                jnp.stack([grid[(r, k)].round_key for r in runs]), n)
            ro, g = jax.device_get(fns["own_runs"](theta, d))
            gains = jax.device_get(jax.vmap(lambda kh: _gains(cell, kh))(
                d.key_h))
            near |= (ro.margin < TIE_TOLERANCE * ro.level).reshape(
                len(runs), -1).any(axis=1)
            nxt = []
            for j, r in enumerate(runs):
                sub = jax.tree.map(lambda x: x[j], (ro, g, theta, d))
                out = _round(cell, sub[2], sub[0], sub[1], sub[3],
                             gains=gains[j])
                outs[(r, k)] = out
                nxt.append(host(_next(out, sub[2])))
            theta = jax.tree.map(lambda *x: np.stack(x), *nxt)
        for j, r in enumerate(runs):
            if near[j]:
                continue
            for k in range(rounds):
                _compare_round(cell, gaps, grid[(r, k)], outs[(r, k)])
        gaps.add("runs_total", len(runs))
        gaps.add("runs_left_out", int(near.sum()))
        gaps.put("runs_left_out", near.mean(), call)


def control_answers(cell: Cell, call: int, prior: Optional[Dict] = None
                    ) -> List[Answer]:
    """The reference at ``bf16x3`` put in the program's place: the answers
    it gives for timed call ``call``, with its own actions and mask, and
    its parameters as float32, as the program keeps them.  ``prior``
    carries each run's parameters from call to call where the entry's
    rounds continue (the service)."""
    cfg, ref, n = cell.config, cell.ref, cell.n_agents
    fns = _fns(cell, "bf16x3")
    mask_of = getattr(cell.entry_mod, "reference_mask", None)
    prior = {} if prior is None else prior
    res = []
    for rnd in cell.plan(call):
        theta = ref.init_params(cfg, rnd.init_key) if rnd.round_idx == 0 \
            else prior[rnd.run]
        d = fns["draws"](rnd.key, n)
        ro, g = fns["own"](theta, d)
        mask = mask_of(cell, rnd.round_idx)[0] if mask_of else None
        out = _round(cell, theta, ro, g, d, mask)
        nxt = _next(out, theta)
        seen = cell.observed(rnd)
        res.append(Answer(
            rnd.key, out["reward"], out["grad_sq"], out["gain_mean"],
            run=rnd.run, round_idx=rnd.round_idx, call=call,
            init_key=rnd.init_key, actions=ro.actions, mask=mask,
            theta_in=host(theta) if seen else None,
            theta_out=host(nxt) if seen else None))
        prior[rnd.run] = nxt
    return res


def verdict(gaps: Gaps, limits: Dict) -> Dict[str, Dict[str, float]]:
    """Each compared number beside its limit; a number a cell does not
    produce is not listed."""
    return {name: {"number": gaps.numbers[name], "limit": lim}
            for name, lim in sorted(limits["limits"].items())
            if name in gaps.numbers}


def failed_calls(gaps: Gaps, limits: Dict) -> int:
    """Calls with some number over its limit."""
    lim = limits["limits"]
    return sum(any(v > lim[k] for k, v in per.items() if k in lim)
               for per in gaps.calls.values())


def is_correct(checks: Dict, limits: Dict) -> bool:
    return set(checks) == set(limits["limits"]) and all(
        np.isfinite(c["number"]) and c["number"] <= c["limit"]
        for c in checks.values())
