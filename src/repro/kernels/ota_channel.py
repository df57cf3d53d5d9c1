"""Pallas TPU kernel for the paper's server-side OTA update (Eq. 6-7).

Every training step applies  u = (v + sigma*n) / (N * m_h)  over every
gradient element — a memory-bound elementwise pass over up to tens of GB.
Fusing the AWGN generation (threefry counter bits -> Box-Muller) with the
scale keeps it to ONE HBM read + ONE write per element; materialising the
noise tensor first (the naive jnp path) costs two extra HBM round-trips, so
the roofline win is ~3x on the aggregation step.

Layout: gradients are flattened and padded to (rows, 128) lanes; grid over
row blocks, each tile (block_rows, 128) resident in VMEM.  Noise bits come
from a counter-based integer-mix PRNG keyed on (seed, absolute element
index): bitwise deterministic for a given seed regardless of grid/block
size and portable between the TPU backend and interpret mode (the
``pltpu.prng_random_bits`` hardware path has no CPU interpret rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128


def _mix(x: jax.Array, salt: jax.Array) -> jax.Array:
    """One murmur3-finalizer round over uint32 counters (statistically ample
    for AWGN; two independent streams come from different salts)."""
    x = x ^ salt
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _kernel(v_ref, o_ref, *, sigma: float, scale: float, seed: int,
            block_rows: int):
    i = pl.program_id(0)
    v = v_ref[...].astype(jnp.float32)
    if sigma > 0.0:
        shape = v.shape
        # absolute element counter (row-major within the full padded buffer)
        row = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
        counter = (jnp.uint32(i * block_rows) + row) * jnp.uint32(LANES) + lane
        base = _mix(counter, jnp.uint32(seed) * jnp.uint32(0x9E3779B9))
        u1 = _mix(base, jnp.uint32(0xA511E9B3))
        u2 = _mix(base, jnp.uint32(0x63D83595))
        # uniform in (0, 1]: (bits >> 8) * 2^-24, offset by 2^-25 to avoid 0;
        # the 24-bit values go through int32 (Mosaic has no uint32 -> f32)
        b1 = (u1 >> 8).astype(jnp.int32).astype(jnp.float32)
        b2 = (u2 >> 8).astype(jnp.int32).astype(jnp.float32)
        f1 = b1 * (1.0 / (1 << 24)) + (1.0 / (1 << 25))
        f2 = b2 * (1.0 / (1 << 24))
        # Box-Muller
        r = jnp.sqrt(-2.0 * jnp.log(f1))
        n = r * jnp.cos(2.0 * jnp.pi * f2)
        v = v + sigma * n
    o_ref[...] = (v * scale).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("sigma", "n_agents", "m_h", "debias", "seed",
                     "block_rows", "interpret"),
)
def ota_channel_apply(
    v: jax.Array,
    *,
    sigma: float,
    n_agents: int,
    m_h: float = 1.0,
    debias: bool = True,
    seed: int = 0,
    block_rows: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """Fused (v + sigma*AWGN) / (N*m_h) over an arbitrary-shape tensor."""
    scale = 1.0 / (n_agents * (m_h if debias else 1.0))
    shape = v.shape
    flat = v.reshape(-1)
    n = flat.shape[0]
    per_block = block_rows * LANES
    n_pad = -n % per_block
    flat = jnp.pad(flat, (0, n_pad))
    rows = flat.shape[0] // LANES
    grid = rows // block_rows
    tiled = flat.reshape(rows, LANES)

    out = pl.pallas_call(
        functools.partial(_kernel, sigma=sigma, scale=scale, seed=seed,
                          block_rows=block_rows),
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(tiled)
    return out.reshape(-1)[:n].reshape(shape)
