"""Pallas TPU flash-attention forward (causal / sliding-window, GQA-aware).

Schedule (TPU-native, not a CUDA port): grid = (batch*heads, q_blocks,
k_blocks) with the k dimension sequential ('arbitrary'); each (bh, qi) owns a
``(block_q, head_dim)`` Q tile resident in VMEM, KV tiles stream through VMEM
``block_k`` rows at a time, and the online-softmax accumulators (m, l, acc)
live in VMEM scratch across the k steps.  GQA reads the *grouped* KV head via
the BlockSpec index_map (head -> head // group) — no materialised KV head
expansion, unlike the XLA fallback path.

MXU alignment: block_q/block_k default 128; head_dim must be a multiple of
8 (TPU lane packing) — all assigned configs use 64/112/128.

Causality is exploited at the *grid* level: k blocks strictly above the
diagonal are skipped by masking the whole tile cheaply (no MXU work saved in
interpret mode, but on TPU the mask short-circuits via @pl.when).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block_q: int, block_k: int, n_k_blocks: int, causal: bool,
            window: Optional[int], sm_scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    run = True
    if causal:
        # whole KV tile above the diagonal contributes nothing
        run = (ki * block_k) <= (qi * block_q + block_q - 1)
    if window is not None:
        run = jnp.logical_and(
            run, (ki * block_k + block_k) > (qi * block_q - window)
        ) if causal else run

    @pl.when(run)
    def _step():
        q = q_ref[...].astype(jnp.float32) * sm_scale      # (bq, d)
        k = k_ref[...].astype(jnp.float32)                  # (bk, d)
        v = v_ref[...].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                   # (bq, bk)
        ok = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        if window is not None:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]                                 # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                              # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                      # (bq, 1)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(ki == n_k_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,        # (B, H, Sq, Dh)
    k: jax.Array,        # (B, Hkv, Sk, Dh)
    v: jax.Array,        # (B, Hkv, Sk, Dh)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jax.Array:
    b, h, sq, dh = q.shape
    _, hkv, sk, _ = k.shape
    g = h // hkv
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    nq, nk = sq // block_q, sk // block_k

    qf = q.reshape(b * h, sq, dh)
    kf = k.reshape(b * hkv, sk, dh)
    vf = v.reshape(b * hkv, sk, dh)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        # GQA: flat q head -> flat kv head, via integer division by the group
        bi = bh // h
        hi = bh % h
        return (bi * hkv + hi // g, ki, 0)

    out = pl.pallas_call(
        functools.partial(
            _kernel, block_q=block_q, block_k=block_k, n_k_blocks=nk,
            causal=causal, window=window, sm_scale=1.0 / (dh ** 0.5),
        ),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, dh), q_map),
            pl.BlockSpec((None, block_k, dh), kv_map),
            pl.BlockSpec((None, block_k, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((None, block_q, dh), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, dh)
