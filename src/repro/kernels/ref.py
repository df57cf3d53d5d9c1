"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

These are *definitions*, optimised for clarity: full-score attention, the
chunked-but-vectorised SSD from models/ssm.py, and the direct OTA update.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.ssm import ssd_ref as _ssd_chunked


def flash_attention_ref(
    q: jax.Array,        # (B, H, Sq, Dh)
    k: jax.Array,        # (B, Hkv, Sk, Dh)
    v: jax.Array,        # (B, Hkv, Sk, Dh)
    *,
    causal: bool = True,
    window: Optional[int] = None,
) -> jax.Array:
    b, h, sq, dh = q.shape
    hkv = k.shape[1]
    g = h // hkv
    kf = jnp.repeat(k, g, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, g, axis=1).astype(jnp.float32)
    qf = q.astype(jnp.float32) / jnp.sqrt(jnp.float32(dh))
    scores = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    qp = jnp.arange(sq)
    kp = jnp.arange(k.shape[2])
    ok = jnp.ones((sq, k.shape[2]), bool)
    if causal:
        ok &= kp[None, :] <= qp[:, None]
    if window is not None:
        ok &= kp[None, :] > qp[:, None] - window
    scores = jnp.where(ok[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return out.astype(q.dtype)


def ssd_ref(
    x: jax.Array,       # (B, S, H, P)
    dt: jax.Array,      # (B, S, H)
    A: jax.Array,       # (H,)
    B: jax.Array,       # (B, S, G, N)
    C: jax.Array,       # (B, S, G, N)
    chunk: int,
) -> jax.Array:
    """Delegates to the model's chunked SSD (itself equality-tested against
    the O(1)-state recurrent step in tests/test_models.py)."""
    return _ssd_chunked(x, dt, A, B, C, chunk)


def ssd_sequential_ref(x, dt, A, B, C):
    """Fully sequential SSD recurrence — the *definition* (slow, exact)."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hg = h // g
    f32 = jnp.float32

    def step(state, inp):
        xt, dtt, Bt, Ct = inp                       # (b,h,p),(b,h),(b,g,n),(b,g,n)
        decay = jnp.exp(dtt * A[None, :])           # (b,h)
        dg = decay.reshape(b, g, hg)
        dax = (xt * dtt[..., None]).reshape(b, g, hg, p)
        state = state * dg[..., None, None] + jnp.einsum("bgn,bghp->bghpn", Bt, dax)
        y = jnp.einsum("bgn,bghpn->bghp", Ct, state)
        return state, y.reshape(b, h, p)

    s0 = jnp.zeros((b, g, hg, p, n), f32)
    xs = (
        jnp.moveaxis(x.astype(f32), 1, 0),
        jnp.moveaxis(dt.astype(f32), 1, 0),
        jnp.moveaxis(B.astype(f32), 1, 0),
        jnp.moveaxis(C.astype(f32), 1, 0),
    )
    _, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1)                   # (b,s,h,p)


def ota_fused_ref(
    grads: jax.Array,     # (n_agents, n_params) — stacked flat gradients
    gains: jax.Array,     # (n_agents,)
    noise: Optional[jax.Array] = None,   # (n_params,) std normal, or None
    *,
    sigma=0.0,
    scale=1.0,
) -> jax.Array:
    """u = (sum_i h_i g_i + sigma*n) * scale — the fused-kernel definition.

    Op order mirrors ``ota_fused._fused_kernel`` exactly (f32 matvec, then
    noise FMA, then scale) so fp32 parity is bitwise in interpret mode; the
    caller supplies the kernel's own counter-PRNG ``noise`` realisation when
    checking the noisy path (tests extract it with the zero-gradient trick).
    """
    v = jax.lax.dot_general(
        gains.astype(jnp.float32).reshape(1, -1), grads.astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).reshape(-1)
    if noise is not None:
        v = v + jnp.asarray(sigma, jnp.float32) * noise.astype(jnp.float32)
    return v * jnp.asarray(scale, jnp.float32)


def ota_fused_sgd_ref(grads, gains, params, noise=None, *, alpha,
                      sigma=0.0, scale=1.0) -> jax.Array:
    """p' = p - alpha*u over :func:`ota_fused_ref` (same op order as the
    kernel's sgd mode; compare under jit — XLA contracts the multiply-
    subtract into one FMA exactly as the kernel body does)."""
    u = ota_fused_ref(grads, gains, noise, sigma=sigma, scale=scale)
    return params.astype(jnp.float32) - jnp.asarray(alpha, jnp.float32) * u


def ota_fused_adam_ref(grads, gains, params, mu, nu, noise=None, *, alpha,
                       step, b1=0.9, b2=0.999, eps=1e-8, sigma=0.0,
                       scale=1.0):
    """Aggregation + bias-corrected Adam on the fused update — mirrors
    ``ota_fused.fused_aggregate_adam`` (and ``optim.optimizers._adam_core``
    with weight_decay=0) op for op.  Returns (p', mu', nu')."""
    f32 = jnp.float32
    u = ota_fused_ref(grads, gains, noise, sigma=sigma, scale=scale)
    a, b1, b2, eps = (jnp.asarray(x, f32) for x in (alpha, b1, b2, eps))
    t = jnp.asarray(step, f32)
    c1 = 1.0 - b1 ** t
    c2 = 1.0 - b2 ** t
    mu_n = b1 * mu.astype(f32) + (1.0 - b1) * u
    nu_n = b2 * nu.astype(f32) + (1.0 - b2) * jnp.square(u)
    delta = -(a * (mu_n / c1) / (jnp.sqrt(nu_n / c2) + eps))
    return params.astype(f32) + delta, mu_n, nu_n


def ota_channel_ref(
    v: jax.Array,         # aggregated sum_i h_i g_i (any shape)
    noise: jax.Array,     # standard normal, same shape
    *,
    sigma: float,
    n_agents: int,
    m_h: float,
    debias: bool = True,
) -> jax.Array:
    """(v + sigma * noise) / (N * m_h)  — Eq. (6)-(7) server-side update."""
    scale = 1.0 / (n_agents * (m_h if debias else 1.0))
    return ((v.astype(jnp.float32) + sigma * noise.astype(jnp.float32)) * scale).astype(
        v.dtype
    )
