"""Pallas TPU kernel for the Mamba2 SSD chunked scan [arXiv:2405.21060].

TPU adaptation of the SSD algorithm (DESIGN.md §6): the chunk dimension is
the *sequential* grid axis; the (state_dim x head_dim) running state lives in
VMEM scratch across chunk steps, and each chunk does three MXU matmuls —

    scores  = C_c B_c^T                    (Q x Q, the "duality" matmul)
    y_intra = (scores . decay_mask) X_c    (Q x P)
    y_inter = C_c S_prev . exp(cum)        (Q x P)
    S_new   = chunk_decay S_prev + (B_c . decay_to_end)^T X_c   (N x P)

Grid = (batch*heads, n_chunks); chunk length Q defaults to 128 (MXU-aligned).
Inputs are pre-scaled outside the kernel (dax = x*dt, da = dt*A): those are
cheap elementwise ops that XLA fuses into the producers, keeping the kernel's
working set to 4 tiles + scratch.

B/C are shared within a head group (G groups): the ops wrapper passes
per-head views via the BlockSpec index_map (head -> group), so no
materialised broadcast.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(dax_ref, da_ref, b_ref, c_ref, y_ref, state_scr, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    dax = dax_ref[...].astype(jnp.float32)          # (Q, P)
    da = da_ref[...].astype(jnp.float32)            # (Q, 1)
    B = b_ref[...].astype(jnp.float32)              # (Q, N)
    C = c_ref[...].astype(jnp.float32)              # (Q, N)

    cum = jnp.cumsum(da, axis=0)                    # (Q, 1)
    last = cum[chunk - 1, 0]

    # intra-chunk: (C B^T . decay_mask) @ dax
    scores = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                               # (Q, Q)
    seg = cum - cum.T                               # seg[q,k] = cum[q]-cum[k]
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(ki <= qi, jnp.exp(seg), 0.0)
    y = jax.lax.dot_general(
        scores * decay, dax, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                               # (Q, P)

    # inter-chunk: contribution of the carried state
    s_prev = state_scr[...]                         # (N, P)
    y += jax.lax.dot_general(
        C * jnp.exp(cum), s_prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # state update: S_new = e^{sum da} S_prev + (B . e^{last-cum})^T dax
    decay_to_end = jnp.exp(last - cum)              # (Q, 1)
    state_scr[...] = jnp.exp(last) * s_prev + jax.lax.dot_general(
        B * decay_to_end, dax, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    y_ref[...] = y.astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret")
)
def ssd_scan(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H) — post-softplus
    A: jax.Array,      # (H,) — negative
    B: jax.Array,      # (B, S, G, N)
    C: jax.Array,      # (B, S, G, N)
    *,
    chunk: int = 128,
    interpret: bool = True,
) -> jax.Array:
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    hg = h // g

    f32 = jnp.float32
    dax = (x.astype(f32) * dt.astype(f32)[..., None])
    da = dt.astype(f32) * A.astype(f32)[None, None, :]

    # layout: (B*H, S, *) with heads-major flattening
    dax_f = jnp.moveaxis(dax, 2, 1).reshape(b * h, s, p)
    da_f = jnp.moveaxis(da, 2, 1).reshape(b * h, s, 1)
    b_f = jnp.moveaxis(B.astype(f32), 2, 1).reshape(b * g, s, n)
    c_f = jnp.moveaxis(C.astype(f32), 2, 1).reshape(b * g, s, n)

    def x_map(bh, ci):
        return (bh, ci, 0)

    def bc_map(bh, ci):
        # head -> its B/C group
        bi = bh // h
        hi = bh % h
        return (bi * g + hi // hg, ci, 0)

    y = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, p), x_map),
            pl.BlockSpec((None, chunk, 1), x_map),
            pl.BlockSpec((None, chunk, n), bc_map),
            pl.BlockSpec((None, chunk, n), bc_map),
        ],
        out_specs=pl.BlockSpec((None, chunk, p), x_map),
        out_shape=jax.ShapeDtypeStruct((b * h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(dax_f, da_f, b_f, c_f)
    return jnp.moveaxis(y.reshape(b, h, s, p), 1, 2)
