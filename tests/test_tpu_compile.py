"""Compile the fused OTA uplink kernel for a described TPU v5e, alone and
inside the program each chip of a sharded sweep runs.

Nothing here runs on a chip: the TPU compiler, which is installed with
jax, compiles for a v5e topology that is described and not attached.  It
refuses what interpret mode accepts (unsupported casts, misaligned
blocks, too much VMEM), so these compiles guard the chip's default path
(``backend="auto"`` on a TPU selects this kernel) at real widths.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every worker of
a parallel test run imports this file.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ota
from repro.core.channel import RayleighChannel
from repro.core.sweep import grid, lane_program, partition_scenarios
from repro.kernels import ota_fused
from repro.rl.env import LandmarkNav
from repro.rl.policy import MLPPolicy

D_PAPER = 165          # the paper's MLP policy, 4 -> 16 -> 5
D_WIDE = 1 << 21       # the widest uplink the repo runs (benchmarks/ota_kernel.py)


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _sgd(wire_dtype):
    def f(grads, gains, params, seed):
        return ota_fused.fused_aggregate_sgd(
            grads, gains, params, alpha=1e-4, sigma=1e-3, scale=0.1,
            seed=seed, with_noise=True, wire_dtype=wire_dtype,
            interpret=False)
    return f


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_agents,n_params,wire_dtype", [
    (10, D_PAPER, None),
    (8, D_WIDE, None),
    (8, D_WIDE, jnp.bfloat16),
], ids=["paper", "wide-fp32", "wide-bf16"])
def test_noisy_sgd_compiles(one_chip, n_agents, n_params, wire_dtype):
    compiled = jax.jit(_sgd(wire_dtype)).lower(
        _spec(one_chip, (n_agents, n_params)),
        _spec(one_chip, (n_agents,)),
        _spec(one_chip, (n_params,)),
        _spec(one_chip, (), jnp.uint32),
    ).compile()
    _assert_kernel(compiled)


def test_noisy_sgd_vmapped_over_mc_runs_compiles(one_chip):
    """The Monte Carlo form: 20 runs folded into the kernel grid."""
    runs, n_agents = 20, 10
    compiled = jax.jit(jax.vmap(_sgd(None))).lower(
        _spec(one_chip, (runs, n_agents, D_PAPER)),
        _spec(one_chip, (runs, n_agents)),
        _spec(one_chip, (runs, D_PAPER)),
        _spec(one_chip, (runs,), jnp.uint32),
    ).compile()
    _assert_kernel(compiled)


def test_noisy_adam_compiles(one_chip):
    def f(grads, gains, params, mu, nu, seed):
        return ota_fused.fused_aggregate_adam(
            grads, gains, params, mu, nu, alpha=1e-3, step=3, sigma=1e-3,
            scale=0.1, seed=seed, with_noise=True, interpret=False)

    flat = _spec(one_chip, (D_WIDE,))
    compiled = jax.jit(f).lower(
        _spec(one_chip, (8, D_WIDE)), _spec(one_chip, (8,)), flat, flat,
        flat, _spec(one_chip, (), jnp.uint32),
    ).compile()
    _assert_kernel(compiled)


def test_sharded_sweep_partition_compiles(one_chip, monkeypatch):
    """``sweep(mode="sharded")`` runs a partition's ``vmap`` program on
    every device, here 1 of 4 noise lanes over 20 MC runs: on a TPU each
    lane runs the fused kernel."""
    # this process is on the CPU: steer the lanes onto the compiled kernel
    # that ``backend="auto"`` selects on a TPU
    monkeypatch.setattr(
        ota.AggregateSpec, "resolved_backend",
        lambda spec: "pallas" if spec.form == "stacked" and not spec.exact
        else "xla")
    monkeypatch.setattr(ota_fused, "_interpret_default", lambda _: False)
    scens = grid(channel=RayleighChannel(),
                 noise_sigma=[1e-3, 3e-3, 1e-2, 3e-2], n_agents=10,
                 batch_m=10, horizon=20, n_rounds=20, alpha=1e-4,
                 debias=True)
    (part,) = partition_scenarios(scens)
    packed, fn, keys = lane_program(LandmarkNav(), MLPPolicy(), part,
                                    mc_runs=20)
    compiled = jax.jit(fn).lower(
        jax.tree.map(lambda x: _spec(one_chip, (1,) + x.shape[1:], x.dtype),
                     packed),
        _spec(one_chip, keys.shape, keys.dtype),
    ).compile()
    _assert_kernel(compiled)
