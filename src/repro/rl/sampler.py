"""Batched trajectory sampling with ``lax.scan``.

``rollout`` samples one trajectory of T+1 action steps (the paper's objective
sums t = 0..T); ``rollout_batch`` vmaps it over a trajectory batch, and the
federated loops vmap once more over agents, giving fully-jitted
(agents x batch x time) sampling with independent PRNG streams.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.telemetry.scopes import ROLLOUT

PyTree = Any


class Trajectory(NamedTuple):
    """One rollout: arrays are time-major (T+1, ...)."""

    obs: jax.Array      # (T+1, obs_dim) — state the action was taken in
    actions: jax.Array  # (T+1,) discrete; (T+1, act_dim) continuous policies
    losses: jax.Array   # (T+1,)  l(s_t, a_t)

    @property
    def horizon(self) -> int:
        return self.obs.shape[-2] - 1


def rollout(env, policy, params: PyTree, key: jax.Array, horizon: int) -> Trajectory:
    """Sample s_0 ~ rho, then T+1 policy steps (t = 0..T inclusive)."""
    key_reset, key_scan = jax.random.split(key)
    s0 = env.reset(key_reset)

    def body(carry, key_t):
        state = carry
        key_a, key_s = jax.random.split(key_t)
        action = policy.sample(params, key_a, state)
        nxt, loss = env.step(key_s, state, action)
        return nxt, (state, action, loss)

    keys = jax.random.split(key_scan, horizon + 1)
    _, (obs, actions, losses) = jax.lax.scan(body, s0, keys)
    return Trajectory(obs=obs, actions=actions, losses=losses)


def rollout_batch(
    env, policy, params: PyTree, key: jax.Array, horizon: int, batch: int
) -> Trajectory:
    """(batch,) independent trajectories; arrays gain a leading batch dim.
    Runs under the ``rollout`` named scope."""
    with jax.named_scope(ROLLOUT):
        keys = jax.random.split(key, batch)
        return jax.vmap(lambda k: rollout(env, policy, params, k, horizon))(
            keys)


def discounted_return(losses: jax.Array, gamma: float) -> jax.Array:
    """sum_t gamma^t l_t along the last axis."""
    t = jnp.arange(losses.shape[-1], dtype=jnp.float32)
    return jnp.sum(losses * gamma**t, axis=-1)


def empirical_reward(traj: Trajectory, gamma: float) -> jax.Array:
    """The paper's 'empirical cumulative reward' = -discounted loss, averaged
    over the batch dims."""
    return -jnp.mean(discounted_return(traj.losses, gamma))
