"""Fused Pallas TPU kernel for the whole OTA aggregation step (Eq. 6-7).

One kernel launch performs, per grid step, everything the uplink + server do
to one block of the flattened parameter vector:

    1. per-agent gain application and superposition  v = sum_i h_i g_i
       (an (1, A) x (A, block) matvec on the MXU — the "air" sum),
    2. AWGN injection  v += sigma * n  from a counter-based PRNG keyed on
       the absolute element index (bitwise-deterministic for a given seed,
       independent of block size, portable to interpret mode),
    3. the debias/normalisation  u = v * scale  where ``scale`` is the
       server constant 1 / (N * E[c p(c)]) (``OTAConfig.norm_const_for``),
    4. optionally the parameter update: plain SGD  p' = p - alpha * u, or
       the full Adam/AdamW moment update (matching
       ``repro.optim.optimizers._adam_core`` bit for bit in fp32).

The naive XLA chain materialises the gain-scaled stack, the summed signal
and the noise tensor; the fused kernel reads each gradient element ONCE and
writes each parameter ONCE — at transformer scale the step is memory-bound,
so the roofline win is the ratio of HBM passes (see
``repro.utils.roofline.ota_fused_cost`` and ``benchmarks/ota_kernel.py``).

Wire format: gradients may enter as bfloat16 (the over-the-air "wire"
precision); the gain matvec accumulates in float32 and the master parameter
copy stays float32, so only the uplink payload is narrowed.

Every runtime quantity (sigma, scale, alpha, Adam constants, PRNG seed) is
passed as an *array* operand, not a static, so sweep lanes — which trace
per-lane sigma/scale — batch through ``jax.vmap``: the Pallas batching rule
folds the lane axis into the kernel grid, exactly one program for the whole
sweep partition.

CPU CI runs the same kernel body through the Pallas interpreter
(``interpret=None`` auto-selects it off-TPU); ``tests/test_kernels.py``
holds it to bitwise fp32 parity against ``kernels/ref.ota_fused_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.tree import ceil_div, next_pow2

LANES = 128

# consts vector layout (one f32 row, SMEM): indices into the (1, 8) operand
_SIGMA, _SCALE, _ALPHA, _B1, _B2, _C1, _C2, _EPS = range(8)
N_CONSTS = 8

# VMEM budget for the gradient-stack block when auto-sizing block_rows
_VMEM_BLOCK_BYTES = 4 * 1024 * 1024


def _mix(x: jax.Array, salt: jax.Array) -> jax.Array:
    """One murmur3-finalizer round over uint32 counters (same stream as
    ``kernels/ota_channel.py`` — statistically ample for AWGN)."""
    x = x ^ salt
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _u24_to_f32(bits: jax.Array) -> jax.Array:
    """The top 24 bits of ``bits`` as exact float32 integers.  Mosaic has no
    uint32 -> float32 cast; the shifted value is below 2^24, so the detour
    through int32 is lossless and gives the same floats in interpret mode."""
    return (bits >> 8).astype(jnp.int32).astype(jnp.float32)


def _counter_noise(seed: jax.Array, start: jax.Array, shape) -> jax.Array:
    """Standard-normal noise for ``shape`` elements at absolute flat offset
    ``start``: threefry-free counter PRNG -> Box-Muller, bitwise identical
    for any block partitioning of the same flat buffer."""
    pos = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 1)
    counter = start.astype(jnp.uint32) + pos
    base = _mix(counter, seed.astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
    u1 = _mix(base, jnp.uint32(0xA511E9B3))
    u2 = _mix(base, jnp.uint32(0x63D83595))
    # uniform in (0, 1]: (bits >> 8) * 2^-24, offset by 2^-25 to avoid 0
    f1 = _u24_to_f32(u1) * (1.0 / (1 << 24)) + (1.0 / (1 << 25))
    f2 = _u24_to_f32(u2) * (1.0 / (1 << 24))
    r = jnp.sqrt(-2.0 * jnp.log(f1))
    return r * jnp.cos(2.0 * jnp.pi * f2)


def _fused_kernel(consts_ref, seed_ref, h_ref, g_ref, *state_refs,
                  mode: str, with_noise: bool, per_block: int):
    """One (1, per_block) slice of the fused aggregation + update.

    ``state_refs`` by mode:
        "agg"  : (o_ref,)                      o = u
        "sgd"  : (p_ref, o_ref)                o = p - alpha * u
        "adam" : (p_ref, mu_ref, nu_ref, op_ref, omu_ref, onu_ref)
    """
    i = pl.program_id(0)
    h = h_ref[...].astype(jnp.float32)                     # (1, A)
    g = g_ref[...]                                         # (A, per_block)
    v = jax.lax.dot_general(
        h, g.astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,               # f32 on the MXU
        preferred_element_type=jnp.float32,
    )                                                      # (1, per_block)
    if with_noise:
        start = i.astype(jnp.uint32) * jnp.uint32(per_block)
        n = _counter_noise(seed_ref[0, 0], start, v.shape)
        v = v + consts_ref[0, _SIGMA] * n
    u = v * consts_ref[0, _SCALE]

    if mode == "agg":
        (o_ref,) = state_refs
        o_ref[...] = u
    elif mode == "sgd":
        p_ref, o_ref = state_refs
        a = consts_ref[0, _ALPHA]
        o_ref[...] = p_ref[...] - a * u
    else:  # adam
        p_ref, mu_ref, nu_ref, op_ref, omu_ref, onu_ref = state_refs
        a = consts_ref[0, _ALPHA]
        b1 = consts_ref[0, _B1]
        b2 = consts_ref[0, _B2]
        c1 = consts_ref[0, _C1]
        c2 = consts_ref[0, _C2]
        eps = consts_ref[0, _EPS]
        mu = b1 * mu_ref[...] + (1.0 - b1) * u
        nu = b2 * nu_ref[...] + (1.0 - b2) * jnp.square(u)
        step = -(a * (mu / c1) / (jnp.sqrt(nu / c2) + eps))
        op_ref[...] = p_ref[...] + step
        omu_ref[...] = mu
        onu_ref[...] = nu


def default_block_rows(n_agents: int, n_params: int,
                       wire_bytes: int = 4, cap: int = 256) -> int:
    """Largest power-of-two block_rows <= cap whose gradient-stack block fits
    the VMEM budget, shrunk further for short parameter vectors so padding
    stays bounded."""
    rows_needed = next_pow2(max(ceil_div(n_params, LANES), 1))
    br = min(cap, rows_needed)
    while br > 8 and n_agents * br * LANES * wire_bytes > _VMEM_BLOCK_BYTES:
        br //= 2
    return max(br, 1)


def _as_consts(sigma, scale, alpha=0.0, b1=0.0, b2=0.0, c1=1.0, c2=1.0,
               eps=0.0) -> jax.Array:
    vals = [sigma, scale, alpha, b1, b2, c1, c2, eps]
    return jnp.stack(
        [jnp.asarray(v, jnp.float32).reshape(()) for v in vals]
    ).reshape(1, N_CONSTS)


def _as_seed(seed) -> jax.Array:
    return jnp.asarray(seed, jnp.uint32).reshape(1, 1)


def _pad_flat(x: jax.Array, total: int) -> jax.Array:
    pad = total - x.shape[-1]
    if pad == 0:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths)


def _interpret_default(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _call(consts, seed, gains, grads, states, *, mode: str, with_noise: bool,
          block_rows: int, interpret: bool) -> Tuple[jax.Array, ...]:
    """Shared pallas_call builder over the padded flat layout.

    ``grads``: (A, total); ``states``: tuple of (1, total) f32 buffers
    (params / mu / nu as the mode requires).  Returns the mode's outputs,
    each (1, total) f32.
    """
    n_agents, total = grads.shape
    per_block = block_rows * LANES
    n_blocks = total // per_block
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)

    flat_spec = pl.BlockSpec((1, per_block), lambda i: (0, i))
    in_specs = [
        smem((1, N_CONSTS), lambda i: (0, 0)),
        smem((1, 1), lambda i: (0, 0)),
        pl.BlockSpec((1, n_agents), lambda i: (0, 0)),
        pl.BlockSpec((n_agents, per_block), lambda i: (0, i)),
    ] + [flat_spec] * len(states)

    n_out = {"agg": 1, "sgd": 1, "adam": 3}[mode]
    out_specs = [flat_spec] * n_out
    out_shape = [jax.ShapeDtypeStruct((1, total), jnp.float32)] * n_out
    if n_out == 1:
        out_specs, out_shape = out_specs[0], out_shape[0]

    out = pl.pallas_call(
        functools.partial(_fused_kernel, mode=mode, with_noise=with_noise,
                          per_block=per_block),
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(consts, seed, gains.reshape(1, n_agents), grads, *states)
    return out if isinstance(out, (tuple, list)) else (out,)


def _prep(grads: jax.Array, gains: jax.Array, block_rows: Optional[int],
          wire_dtype) -> Tuple[jax.Array, jax.Array, int, int, int]:
    """Pad the (A, P) gradient stack to the blocked flat layout."""
    if grads.ndim != 2:
        raise ValueError(f"grads must be (n_agents, n_params), got {grads.shape}")
    n_agents, n_params = grads.shape
    if wire_dtype is not None:
        grads = grads.astype(wire_dtype)
    wb = jnp.dtype(grads.dtype).itemsize
    br = block_rows or default_block_rows(n_agents, n_params, wb)
    per_block = br * LANES
    total = ceil_div(n_params, per_block) * per_block
    return _pad_flat(grads, total), gains, br, n_params, total


def fused_aggregate(
    grads: jax.Array,          # (n_agents, n_params) — stacked flat gradients
    gains: jax.Array,          # (n_agents,) f32 — this round's h_i
    *,
    sigma=0.0,                 # AWGN sigma on the summed signal (runtime ok)
    scale=1.0,                 # server normalisation 1/(N*m_eff) (runtime ok)
    seed=0,                    # uint32 counter-PRNG seed (runtime ok)
    with_noise: Optional[bool] = None,
    block_rows: Optional[int] = None,
    wire_dtype=None,           # e.g. jnp.bfloat16 — the uplink payload dtype
    interpret: Optional[bool] = None,
) -> jax.Array:
    """u = (sum_i h_i g_i + sigma*n) * scale, fused; returns (n_params,) f32."""
    grads, gains, br, n_params, _ = _prep(grads, gains, block_rows, wire_dtype)
    noise = with_noise if with_noise is not None else True
    (out,) = _call(
        _as_consts(sigma, scale), _as_seed(seed), gains, grads, (),
        mode="agg", with_noise=noise, block_rows=br,
        interpret=_interpret_default(interpret),
    )
    return out.reshape(-1)[:n_params]


def fused_aggregate_sgd(
    grads: jax.Array,          # (n_agents, n_params)
    gains: jax.Array,          # (n_agents,)
    params: jax.Array,         # (n_params,) f32 master copy
    *,
    alpha,                     # SGD step size (runtime ok)
    sigma=0.0,
    scale=1.0,
    seed=0,
    with_noise: Optional[bool] = None,
    block_rows: Optional[int] = None,
    wire_dtype=None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """p' = p - alpha * u with u the fused OTA update; (n_params,) f32."""
    grads, gains, br, n_params, total = _prep(grads, gains, block_rows,
                                              wire_dtype)
    p = _pad_flat(params.astype(jnp.float32).reshape(1, -1), total)
    noise = with_noise if with_noise is not None else True
    (out,) = _call(
        _as_consts(sigma, scale, alpha), _as_seed(seed), gains, grads, (p,),
        mode="sgd", with_noise=noise, block_rows=br,
        interpret=_interpret_default(interpret),
    )
    return out.reshape(-1)[:n_params]


def fused_server_pass(
    v: jax.Array,              # (n_params,) f32 — accumulated superposition
    *,
    sigma=0.0,
    scale=1.0,
    seed=0,
    with_noise: Optional[bool] = None,
    alpha=None,                # with params: fuse the SGD step too
    params: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """The server tail of a *streamed* (``agent_blocks``) uplink as one
    kernel pass: AWGN + debias/normalisation — and, when ``params`` (and
    ``alpha``) are given, the SGD update — over an already-accumulated
    superposition ``v = sum_i h_i g_i``.

    Reuses the aggregation kernel's block grid with ``v`` as a single
    unit-gain agent row and no wire-dtype hop (the blocked scan already
    applied the wire quantisation per agent row; re-narrowing the running
    sum would double-quantise).  The counter PRNG is keyed on the absolute
    flat element index, so the noise is bitwise-identical to the one-shot
    kernel's draw for the same seed — and invariant to the agent blocking.
    """
    flat = v.astype(jnp.float32).reshape(1, -1)
    ones = jnp.ones((1,), jnp.float32)
    if params is None:
        return fused_aggregate(
            flat, ones, sigma=sigma, scale=scale, seed=seed,
            with_noise=with_noise, interpret=interpret)
    if alpha is None:
        raise ValueError("fused_server_pass with params needs alpha")
    return fused_aggregate_sgd(
        flat, ones, params, alpha=alpha, sigma=sigma, scale=scale,
        seed=seed, with_noise=with_noise, interpret=interpret)


def fused_aggregate_adam(
    grads: jax.Array,          # (n_agents, n_params)
    gains: jax.Array,          # (n_agents,)
    params: jax.Array,         # (n_params,) f32 master copy
    mu: jax.Array,             # (n_params,) f32 first moment
    nu: jax.Array,             # (n_params,) f32 second moment
    *,
    alpha,                     # learning rate at this step (runtime ok)
    step,                      # 1-based step count t (runtime ok)
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    sigma=0.0,
    scale=1.0,
    seed=0,
    with_noise: Optional[bool] = None,
    block_rows: Optional[int] = None,
    wire_dtype=None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Aggregation + bias-corrected Adam, one pass: returns (p', mu', nu').

    Matches ``repro.optim.optimizers.adam`` (``_adam_core`` with
    weight_decay=0) applied to the fused update u, in fp32.
    """
    grads, gains, br, n_params, total = _prep(grads, gains, block_rows,
                                              wire_dtype)
    t = jnp.asarray(step, jnp.float32)
    c1 = 1.0 - jnp.asarray(b1, jnp.float32) ** t
    c2 = 1.0 - jnp.asarray(b2, jnp.float32) ** t
    states = tuple(
        _pad_flat(x.astype(jnp.float32).reshape(1, -1), total)
        for x in (params, mu, nu)
    )
    noise = with_noise if with_noise is not None else True
    outs = _call(
        _as_consts(sigma, scale, alpha, b1, b2, c1, c2, eps),
        _as_seed(seed), gains, grads, states,
        mode="adam", with_noise=noise, block_rows=br,
        interpret=_interpret_default(interpret),
    )
    return tuple(o.reshape(-1)[:n_params] for o in outs)
