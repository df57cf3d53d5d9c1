"""Plain reference of the task of a round (arXiv:2310.16592, Section IV):
LandmarkNav rolled out by an MLP policy, and its G(PO)MDP estimates.

Written from the paper's equations, in straightforward ``jax.numpy`` and
float32, and independent of the program under test: it imports nothing
from ``repro``.  What it shares with the program is the seed and the
documented PRNG schedule, so that both see the same start states and
Gumbel noise.  The uplink and server step are ``perfbench/reference.py``.

* LandmarkNav (``env.kind`` ``"landmark"``): state (x, y, x_l, y_l), five
  moves of ``step_size``, loss = distance to the landmark after the move.
* Policy (``policy.kind`` ``"MLPPolicy"``): obs-hidden-actions MLP, ReLU,
  softmax; actions are drawn as ``argmax(logits + Gumbel)``.
* G(PO)MDP (Eq. 4): ``g_i = 1/M sum_m sum_t grad log pi(a_t|s_t) w_t`` with
  ``w_t = sum_{t'>=t} gamma^t' l_t'``, here in closed form (no autodiff).

``precision`` is that of :func:`perfbench.reference.matmul`.
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from perfbench.reference import matmul

ENV, POLICY = "landmark", "MLPPolicy"


def check(cfg: Dict):
    """Raises where the configuration asks for another task."""
    env, pol = cfg["env"]["kind"], cfg["policy"]["kind"]
    if (env, pol) != (ENV, POLICY):
        raise ValueError(f"this reference is {ENV} with {POLICY}, not "
                         f"{env} with {pol}")


def n_params(cfg: Dict) -> int:
    """d: parameters of the MLP (w1, b1, w2, b2)."""
    p = cfg["policy"]
    o, h, a = p["obs_dim"], p["hidden"], p["n_actions"]
    return o * h + h + h * a + a


def flops_per_agent_step(cfg: Dict) -> int:
    """Matrix FLOPs of one agent-step, counted for the algorithm (a program
    that recomputes or pads does more, never less): the rollout's forward
    pass to draw the action, and the estimator's forward and backward
    passes (the backward computes both the input and the weight gradient,
    twice the forward)."""
    p = cfg["policy"]
    fwd = 2 * (p["obs_dim"] * p["hidden"] + p["hidden"] * p["n_actions"])
    return fwd + fwd + 2 * fwd


class Draws(NamedTuple):
    """The random inputs of one round, from its round key."""

    s0: jax.Array       # (N, M, 4) start states
    gumbel: jax.Array   # (N, M, T+1, A) Gumbel noise of each action draw
    key_h: jax.Array    # channel-gain key
    key_n: jax.Array    # noise key


class Rollout(NamedTuple):
    obs: jax.Array      # (..., T+1, 4)
    actions: jax.Array  # (..., T+1) int32
    losses: jax.Array   # (..., T+1)
    margin: jax.Array   # (..., T+1) the action's logit + Gumbel minus the
    #                     best other's: < 0 where another action was ahead
    level: jax.Array    # (..., T+1) max(1, |logit + Gumbel| of the action)


def init_params(cfg: Dict, key) -> Dict[str, jax.Array]:
    """Gaussian weights scaled by 1/sqrt(fan-in), zero biases."""
    p = cfg["policy"]
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (p["obs_dim"], p["hidden"]), jnp.float32)
        * (1.0 / math.sqrt(p["obs_dim"])),
        "b1": jnp.zeros((p["hidden"],), jnp.float32),
        "w2": jax.random.normal(k2, (p["hidden"], p["n_actions"]), jnp.float32)
        * (1.0 / math.sqrt(p["hidden"])),
        "b2": jnp.zeros((p["n_actions"],), jnp.float32),
    }


def round_draws(cfg: Dict, round_key, n_agents: int) -> Draws:
    """Start states and Gumbel noise for every step of every trajectory."""
    m, t1 = cfg["batch_m"], cfg["horizon"] + 1
    arena, n_act = cfg["env"]["arena"], cfg["policy"]["n_actions"]
    key_samp, key_chan = jax.random.split(round_key)

    def trajectory(k):
        k_reset, k_steps = jax.random.split(k)
        s0 = jax.random.uniform(k_reset, (4,), jnp.float32,
                                minval=-arena, maxval=arena)
        k_act = jax.vmap(lambda kt: jax.random.split(kt)[0])(
            jax.random.split(k_steps, t1))
        g = jax.vmap(lambda ka: jax.random.gumbel(ka, (n_act,), jnp.float32))(
            k_act)
        return s0, g

    def agent(k):
        return jax.vmap(trajectory)(jax.random.split(k, m))

    s0, g = jax.vmap(agent)(jax.random.split(key_samp, n_agents))
    key_h, key_n = jax.random.split(key_chan)
    return Draws(s0, g, key_h, key_n)


def _moves(cfg: Dict) -> jax.Array:
    step = cfg["env"]["step_size"]
    return jnp.array([[0, 0], [-1, 0], [1, 0], [0, 1], [0, -1]],
                     jnp.float32) * step


def _logits(theta, obs, precision):
    h = jax.nn.relu(matmul(obs, theta["w1"], precision) + theta["b1"])
    return matmul(h, theta["w2"], precision) + theta["b2"]


def _advance(cfg, state, action):
    pos = state[..., :2] + _moves(cfg)[action]
    nxt = jnp.concatenate([pos, state[..., 2:]], axis=-1)
    d = pos - state[..., 2:]
    return nxt, jnp.sqrt(jnp.sum(d * d, axis=-1) + 1e-12)


def rollout(cfg: Dict, theta, draws: Draws, precision: str,
            actions=None) -> Rollout:
    """Trajectories from ``draws``: the reference's own actions, or the
    given ``actions`` replayed (the margin is then of the given action)."""
    batch = draws.s0.shape[:-1]
    s0 = draws.s0.reshape(-1, 4)
    g = draws.gumbel.reshape((s0.shape[0],) + draws.gumbel.shape[-2:])
    g = jnp.moveaxis(g, 1, 0)
    given = None if actions is None else jnp.moveaxis(
        actions.reshape(s0.shape[0], -1), 1, 0)

    def step(state, x):
        y = _logits(theta, state, precision) + x[0]
        a = jnp.argmax(y, axis=-1) if x[1] is None else x[1]
        y_a = jnp.take_along_axis(y, a[:, None], axis=-1)[:, 0]
        others = jnp.where(jax.nn.one_hot(a, y.shape[-1], dtype=bool),
                           -jnp.inf, y)
        nxt, loss = _advance(cfg, state, a)
        return nxt, (state, a.astype(jnp.int32), loss,
                     y_a - jnp.max(others, axis=-1),
                     jnp.maximum(1.0, jnp.abs(y_a)))

    _, outs = jax.lax.scan(step, s0, (g, given))
    t1 = g.shape[0]
    fix = lambda x: jnp.moveaxis(x, 0, 1).reshape(  # noqa: E731
        batch + (t1,) + x.shape[2:])
    return Rollout(*(fix(x) for x in outs))


def discounted_to_go(cfg: Dict, losses):
    disc = losses * (cfg["gamma"] ** jnp.arange(losses.shape[-1],
                                                dtype=jnp.float32))
    return jnp.flip(jnp.cumsum(jnp.flip(disc, -1), -1), -1)


def agent_grads(cfg: Dict, theta, ro: Rollout, precision: str):
    """(N, d)-leaved G(PO)MDP estimates, one per agent, in closed form."""
    n = ro.obs.shape[0]
    m = ro.obs.shape[1]
    w = discounted_to_go(cfg, ro.losses) / m           # (N, M, T+1)
    x = ro.obs.reshape(n, -1, 4)                         # (N, B, 4)
    pre = matmul(x, theta["w1"], precision) + theta["b1"]
    h = jax.nn.relu(pre)
    z = matmul(h, theta["w2"], precision) + theta["b2"]
    onehot = jax.nn.one_hot(ro.actions.reshape(n, -1), z.shape[-1],
                            dtype=jnp.float32)
    dz = (onehot - jax.nn.softmax(z, axis=-1)) * w.reshape(n, -1, 1)
    dh = matmul(dz, theta["w2"].T, precision) * (pre > 0)
    return {
        "w1": matmul(jnp.swapaxes(x, 1, 2), dh, precision),
        "b1": jnp.sum(dh, axis=1),
        "w2": matmul(jnp.swapaxes(h, 1, 2), dz, precision),
        "b2": jnp.sum(dz, axis=1),
    }
