"""Plain reference of a round's uplink and server step (arXiv:2310.16592,
Eq. 6-7), shared by every configuration.

``u = (sum_i h_i g_i + sigma n) / (N m_h)`` and ``theta' = theta - alpha u``,
with float64 sums on the host.  Independent of the program under test: it
imports nothing from ``repro``.  What it shares with the program is the
seed and the documented PRNG schedule, so that both draw the same gains
and the same unit noise ``n``:

* gains from the channel's own file (``perfbench/channels/<kind>.py``),
  one draw of N gains, or (a sharded round) one ``fold_in(key_h, agent)``
  draw per agent;
* the noise of the fused uplink kernel on a TPU (``"counter"``): element j
  of the flat parameter vector (sorted-key leaf order) takes two murmur3
  finalizer rounds of the uint32 counter j, salted by the kernel's seed
  ``bits(key_n)`` times 0x9E3779B9, into Box-Muller;
* the noise of the XLA uplink elsewhere (``"leafwise"``): one
  ``normal(split(key_n, leaves)[l], shape)`` per leaf, sorted-key order.

The task's own part (environment, policy, estimator) is the file that the
configuration names under ``reference`` (``perfbench/tasks/``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def matmul(a, b, precision: str):
    """``a @ b`` at the named precision (batched like ``jnp.matmul``):
    ``"highest"`` (float32 products, what the configurations state) or
    ``"bf16x3"``, three bfloat16 passes as a TPU's ``HIGH`` precision
    computes them: the control that the comparison has to fail."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=HIGHEST)
    if precision != "bf16x3":
        raise ValueError(f"unknown precision {precision!r}")
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    mm = lambda x, y: jnp.matmul(x, y, precision=HIGHEST)  # noqa: E731
    return (mm(a_hi, b_lo) + mm(a_lo, b_hi)) + mm(a_hi, b_hi)


def gains(chan, channel: Dict, key_h, n_agents: int, indexed: bool):
    """(N,) gains of one round from the channel file ``chan``."""
    if indexed:
        return jax.vmap(lambda j: chan.draw(
            channel, jax.random.fold_in(key_h, j), ()))(
                jnp.arange(n_agents, dtype=jnp.int32))
    return chan.draw(channel, key_h, (n_agents,))


_M1, _M2 = np.uint32(0x85EBCA6B), np.uint32(0xC2B2AE35)


def _mix(x: np.ndarray, salt: np.uint32) -> np.ndarray:
    x = x ^ salt
    x = (x ^ (x >> np.uint32(16))) * _M1
    x = (x ^ (x >> np.uint32(13))) * _M2
    return x ^ (x >> np.uint32(16))


def counter_noise(seed: int, d: int) -> np.ndarray:
    """(d,) float64 standard normals of the counter stream at ``seed``."""
    with np.errstate(over="ignore"):
        salt = np.uint32((int(seed) * 0x9E3779B9) & 0xFFFFFFFF)
        base = _mix(np.arange(d, dtype=np.uint32), salt)
        u1 = _mix(base, np.uint32(0xA511E9B3)) >> np.uint32(8)
        u2 = _mix(base, np.uint32(0x63D83595)) >> np.uint32(8)
    f1 = u1.astype(np.float64) / 2.0 ** 24 + 2.0 ** -25   # in (0, 1]
    f2 = u2.astype(np.float64) / 2.0 ** 24
    return np.sqrt(-2.0 * np.log(f1)) * np.cos(2.0 * np.pi * f2)


def noise(stream: str, key_n, like: Dict) -> np.ndarray:
    """The round's unit noise, flat (d,) float64 in sorted-key order, for
    parameters shaped as ``like``."""
    if stream == "counter":
        d = sum(int(np.prod(like[k].shape)) for k in like)
        seed = int(jax.random.bits(key_n, (), jnp.uint32))
        return counter_noise(seed, d)
    if stream != "leafwise":
        raise ValueError(f"unknown noise stream {stream!r}")
    names = sorted(like)
    keys = jax.random.split(key_n, len(names))
    return np.concatenate([np.asarray(jax.random.normal(
        k, like[n].shape, jnp.float32), np.float64).reshape(-1)
        for k, n in zip(keys, names)])


def returns(cfg: Dict, losses):
    """Discounted return of each trajectory (float64, on the host)."""
    losses = np.asarray(losses, np.float64)
    return losses @ (cfg["gamma"] ** np.arange(losses.shape[-1]))


def flat(tree) -> np.ndarray:
    """Leaves in sorted-key order, flattened (float64, host)."""
    return np.concatenate([np.asarray(tree[k], np.float64).reshape(-1)
                           for k in sorted(tree)])


def stack_flat(tree) -> np.ndarray:
    """(N, d) float64 rows from an (N, ...)-leaved tree, sorted-key order."""
    return np.concatenate([np.asarray(tree[k], np.float64).reshape(
        tree[k].shape[0], -1) for k in sorted(tree)], axis=1)


def unflat(vec: np.ndarray, like) -> Dict[str, np.ndarray]:
    out, i = {}, 0
    for k in sorted(like):
        size = int(np.prod(like[k].shape))
        out[k] = vec[i:i + size].reshape(like[k].shape)
        i += size
    return out


def round_outputs(cfg: Dict, theta, losses, grads, gains, noise, m_h: float,
                  mask=None, sequential: bool = False):
    """One round from its trajectories' losses (N, M, T+1), the per-agent
    estimates (an (N, ...)-leaved tree), the gains, the unit noise (flat,
    d) and the channel mean ``m_h``: host float64 reductions.

    ``sequential`` forms the mean gradient as a float32 left fold over the
    agents in order, the sum a streamed round computes: over 10^4 agents
    its rounding (some 1e-6 of the result) is then shared with the program
    and not counted as a gap.

    ``mask`` (N,) bool selects the agents that made the round (service);
    the mean gradient and reward are then over them and the update is
    renormalised by their count.  Returns a dict of numpy values:
    reward, grad_sq, gain_mean, theta_next (flat), update (flat), count.
    """
    g = stack_flat(grads)                                    # (N, d)
    h = np.asarray(gains, np.float64)
    ret = returns(cfg, losses)                               # (N, M)
    n = g.shape[0]
    keep = np.ones(n, bool) if mask is None else np.asarray(mask, bool)
    count = int(keep.sum())
    w = keep.astype(np.float64)
    if sequential:
        fold = np.cumsum((g * w[:, None]).astype(np.float32), axis=0,
                         dtype=np.float32)[-1]
        inv = np.float32(1.0) / np.float32(max(count, 1))
        mean_grad = (fold / np.float32(n) if mask is None
                     else fold * inv).astype(np.float64)
    else:
        mean_grad = (w @ g) / max(count, 1)
    sigma = np.sqrt(10.0 ** (cfg["noise_db"] / 10.0))
    # a round nobody made commits no update, and no noise
    update = ((w * h) @ g + sigma * np.asarray(noise, np.float64)) \
        / (count * m_h) if count else np.zeros(g.shape[1])
    theta_flat = flat({k: np.asarray(v) for k, v in theta.items()})
    return {
        "reward": -float((w @ ret).sum() / (max(count, 1) * ret.shape[1])),
        "grad_sq": float(mean_grad @ mean_grad),
        "gain_mean": float((w @ h) / max(count, 1)),
        "update": update,
        "theta_next": theta_flat - cfg["alpha"] * update,
        "count": count,
    }
