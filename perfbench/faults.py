"""Faults planted in the program under test, to show that the comparison
catches them (tests and ``perfbench/calibrate.py``; never in a benchmark
run).  Each is a context manager that patches one place of the program and
clears its compiled-program caches on entry and exit.
"""
from __future__ import annotations

import contextlib

import jax


def _clear():
    from repro.core import fedpg

    fedpg.clear_compilation_cache()
    jax.clear_caches()


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    _clear()
    try:
        yield
    finally:
        setattr(obj, name, old)
        _clear()


def frozen_state():
    """Every round returns the parameters it was given."""
    from repro.core import fedpg

    make = fedpg.make_round_fn

    def make_frozen(*a, **k):
        round_fn = make(*a, **k)

        def frozen(state, key):
            nxt, metrics = round_fn(state, key)
            if hasattr(state, "theta"):
                return nxt._replace(theta=state.theta), metrics
            return state, metrics
        return frozen
    return _patched(fedpg, "make_round_fn", make_frozen)


def half_batch():
    """Each agent rolls out half of its M trajectories; the estimator and
    the reward average over the rest."""
    from repro.core import fedpg

    roll = fedpg.rollout_batch
    return _patched(fedpg, "rollout_batch",
                    lambda env, pol, p, key, horizon, batch:
                    roll(env, pol, p, key, horizon, batch // 2))


def altered_answer():
    """Every trajectory's discounted return, the answer the reward is made
    of, is 0.1% off where it is produced."""
    from repro.rl import sampler

    ret = sampler.discounted_return
    return _patched(sampler, "discounted_return",
                    lambda losses, gamma: ret(losses, gamma) * 1.001)


def altered_gain():
    """The Rayleigh channel's gains come out 10% high."""
    from repro.core.channel import RayleighChannel

    sample = RayleighChannel.sample
    return _patched(RayleighChannel, "sample",
                    lambda self, key, shape: sample(self, key, shape) * 1.1)


def altered_mask():
    """The participation mask lets every 50th agent in besides those its
    draws let in, where the mask is produced."""
    from repro.service import participation

    mask = participation.round_mask

    def more(p, part_key, sched_key, round_idx, agent_ids, n_agents):
        return mask(p, part_key, sched_key, round_idx, agent_ids,
                    n_agents) | (agent_ids % 50 == 0)
    return _patched(participation, "round_mask", more)


def no_exchange():
    """The cross-chip sums are left out: each chip keeps its own part."""
    return _patched(jax.lax, "psum", lambda x, axis_name, **_: x)


@contextlib.contextmanager
def default_precision():
    """Not a fault: the program at the TPU's default matmul precision (one
    bfloat16 pass) instead of the float32 that the configuration states."""
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "default")
    _clear()
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", old)
        _clear()


FAULTS = {f.__name__: f for f in (frozen_state, half_batch, altered_answer,
                                  altered_gain, altered_mask, no_exchange,
                                  default_precision)}
