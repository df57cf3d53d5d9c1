"""Policy-gradient estimators: REINFORCE and mini-batch G(PO)MDP (Eq. 4).

G(PO)MDP [Baxter & Bartlett '01] weights each log-prob by the *discounted
loss-to-go* rather than the full return — the "causality trick":

    sum_t phi(t) gamma^t l_t  ==  sum_tau (grad log pi_tau) * sum_{t>=tau} gamma^t l_t

(phi(t) = sum_{tau<=t} grad log pi_tau), which is exactly Eq. (4) and has
strictly lower variance than REINFORCE.  Both estimators are implemented as
*surrogate losses* whose autodiff gradient equals the estimator, so they
compose with jax.grad / jax.vmap / shard_map and with the channel-weighted
OTA form.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.rl.sampler import Trajectory
from repro.telemetry.scopes import ESTIMATOR

PyTree = Any


def discounted_to_go(losses: jax.Array, gamma: float) -> jax.Array:
    """w_tau = sum_{t>=tau} gamma^t l_t (note: gamma^t, NOT gamma^{t-tau} —
    the paper's Eq. (4) keeps the absolute discounting).

    Works on the last axis; implemented as a reverse cumulative sum.
    """
    t = jnp.arange(losses.shape[-1], dtype=jnp.float32)
    disc = losses * gamma**t
    return jnp.flip(jnp.cumsum(jnp.flip(disc, -1), -1), -1)


def total_discounted(losses: jax.Array, gamma: float) -> jax.Array:
    t = jnp.arange(losses.shape[-1], dtype=jnp.float32)
    return jnp.sum(losses * gamma**t, axis=-1)


def _traj_logps(policy, params: PyTree, traj: Trajectory) -> jax.Array:
    """log pi(a_t | s_t; theta) along time (and any leading batch dims).

    Discrete actions are scalar per step; continuous policies (e.g.
    ``GaussianPolicy``) carry a trailing action-dim axis, which is flattened
    alongside the observation one.  ``traj.losses`` always has exactly the
    (batch..., time) shape, so it anchors both cases.
    """
    batch_time = traj.losses.shape
    flat_obs = traj.obs.reshape((-1, traj.obs.shape[-1]))
    if traj.actions.ndim > len(batch_time):  # vector (continuous) actions
        flat_act = traj.actions.reshape((-1, traj.actions.shape[-1]))
    else:
        flat_act = traj.actions.reshape((-1,))
    logps = jax.vmap(lambda o, a: policy.log_prob(params, o, a))(flat_obs, flat_act)
    return logps.reshape(batch_time)


def gpomdp_surrogate(
    policy, params: PyTree, traj: Trajectory, gamma: float,
    weights: jax.Array | None = None,
) -> jax.Array:
    """Scalar whose gradient is the mini-batch G(PO)MDP estimate (Eq. 4).

    ``traj`` may have arbitrary leading batch dims; the surrogate averages
    over them (the 1/M of Eq. 4).  ``weights`` (matching the leading batch
    dims) optionally reweights trajectories — this is the hook the
    channel-weighted OTA form uses (weight = h_{agent(m)}).
    """
    logps = _traj_logps(policy, params, traj)
    to_go = jax.lax.stop_gradient(discounted_to_go(traj.losses, gamma))
    per_traj = jnp.sum(logps * to_go, axis=-1)
    if weights is not None:
        per_traj = per_traj * weights
    return jnp.mean(per_traj)


def reinforce_surrogate(
    policy, params: PyTree, traj: Trajectory, gamma: float,
    weights: jax.Array | None = None,
) -> jax.Array:
    """REINFORCE surrogate: every log-prob weighted by the full return."""
    logps = _traj_logps(policy, params, traj)
    ret = jax.lax.stop_gradient(total_discounted(traj.losses, gamma))
    per_traj = jnp.sum(logps, axis=-1) * ret
    if weights is not None:
        per_traj = per_traj * weights
    return jnp.mean(per_traj)


def gpomdp_gradient(
    policy, params: PyTree, traj: Trajectory, gamma: float,
    weights: jax.Array | None = None,
) -> PyTree:
    """The estimator itself: grad_theta of the G(PO)MDP surrogate, forward
    and backward under the ``estimator`` named scope."""
    with jax.named_scope(ESTIMATOR):
        return jax.grad(
            lambda p: gpomdp_surrogate(policy, p, traj, gamma, weights)
        )(params)


def reinforce_gradient(
    policy, params: PyTree, traj: Trajectory, gamma: float,
    weights: jax.Array | None = None,
) -> PyTree:
    with jax.named_scope(ESTIMATOR):
        return jax.grad(
            lambda p: reinforce_surrogate(policy, p, traj, gamma, weights)
        )(params)
