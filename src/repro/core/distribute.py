"""Device-mesh execution for the sweep engine: lanes x MC seeds on a mesh.

``sweep(..., mode="sharded")`` routes every partition program through this
module (the ROADMAP's sharding/async north-star item wired into the hot
path):

* the packed lane axis lays across the mesh's ``"lane"`` axis via
  ``NamedSharding`` — uneven lane counts are padded with *replicate-lanes*
  (copies of the last real lane) that are masked off when results
  materialise;
* Monte-Carlo keys lay across the ``"mc"`` axis whenever ``mc_runs``
  divides it (otherwise they replicate across that axis);
* single-lane partitions (nothing packed — the replicate path) shard the
  MC axis across the *whole* mesh instead, so a lone scenario still uses
  every device;
* dispatch is asynchronous: partition programs launch back-to-back and
  ``block_until_ready`` is deferred until ``SweepResult`` materialisation,
  so device execution overlaps host-side packing/compilation of later
  partitions;
* packed lane arrays are donated to their partition program (they are
  rebuilt per partition, so the buffers are dead after dispatch) — on
  accelerator meshes; the CPU backend cannot reuse donated buffers, so
  donation is skipped there rather than tripping jax's warning.

Exactness: sharding only changes data *placement* — every device runs
the one-device program that ``mode="vmap"`` compiles, on its own block of
lanes and MC keys (:class:`PartitionProgram`), so each lane is
bit-identical to ``mode="vmap"`` run on that block; the padded lanes
recompute the last real lane and never reach the result.  On one device
the block is the whole partition, so lanes are bit-identical to the
default mode (and hence to per-scenario ``fedpg.monte_carlo``).  Across
devices the block is smaller, and XLA compiles the ``vmap`` program for
each batch size: a block can differ from the whole batch in the last
bits.  ``tests/test_distribute.py`` plus the golden-trace suite enforce
this (``REPRO_EMULATED_DEVICES=8`` runs them across emulated devices,
applied by ``repro.utils.platform`` before JAX initialises).

The *agent* axis inside a round is the other shardable dimension: build a
mesh with :func:`agent_mesh_for` and pass it to
``fedpg.run(..., agent_mesh=...)`` to run the per-round fleet in its
production ``shard_map`` form — ``ota.aggregate(..., axis=...,
local_stack=True)``.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_agent_mesh, make_sweep_mesh

LANE_AXIS = "lane"
MC_AXIS = "mc"

__all__ = [
    "LANE_AXIS", "MC_AXIS", "Placement", "agent_mesh_for",
    "default_sweep_mesh", "dispatch_partition", "pad_lanes",
    "PartitionProgram", "place_partition", "plan_placement",
]


def default_sweep_mesh() -> Mesh:
    """All available devices on the lane axis (``("lane", "mc")`` shaped)."""
    return make_sweep_mesh()


def agent_mesh_for(n_agents: int, devices=None) -> Mesh:
    """An ``("agents",)`` mesh over the largest device count dividing
    ``n_agents`` — the mesh ``fedpg.run(..., agent_mesh=...)`` wants."""
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    while n_agents % n:
        n -= 1
    return make_agent_mesh(n, devices)


@dataclass(frozen=True)
class Placement:
    """How one partition's (lanes x mc_runs) batch lands on the mesh.

    ``n_lanes == 0`` marks the replicate path (no packed arrays: the lane
    function runs once and the engine replicates its history); ``n_pad``
    is the number of masked replicate-lanes appended so the lane axis
    divides the mesh's lane dimension.
    """

    mesh: Mesh
    n_lanes: int
    n_pad: int
    lane_spec: P
    key_spec: P
    out_spec: P

    @property
    def n_devices(self) -> int:
        return self.mesh.size


def plan_placement(mesh: Mesh, n_lanes: int, mc_runs: int) -> Placement:
    """Choose shardings for one partition.

    Lanes shard over ``"lane"``; keys shard over ``"mc"`` when ``mc_runs``
    divides that axis.  With nothing packed the keys shard over the whole
    mesh when ``mc_runs`` divides ``mesh.size`` (else everything
    replicates — a 1-device degenerate placement that still runs).
    """
    if LANE_AXIS not in mesh.shape:
        raise ValueError(
            f"sweep mesh needs a {LANE_AXIS!r} axis; got {tuple(mesh.axis_names)} "
            "(build one with launch.mesh.make_sweep_mesh)")
    lane_d = mesh.shape[LANE_AXIS]
    mc_d = mesh.shape.get(MC_AXIS, 1)
    if n_lanes == 0:
        axes = tuple(mesh.axis_names)
        key_spec = P(axes) if mesh.size > 1 and mc_runs % mesh.size == 0 else P()
        return Placement(mesh=mesh, n_lanes=0, n_pad=0, lane_spec=P(),
                         key_spec=key_spec, out_spec=key_spec)
    n_pad = -n_lanes % lane_d
    mc_sharded = mc_d > 1 and mc_runs % mc_d == 0
    key_spec = P(MC_AXIS) if mc_sharded else P()
    out_spec = P(LANE_AXIS, MC_AXIS) if mc_sharded else P(LANE_AXIS)
    return Placement(mesh=mesh, n_lanes=n_lanes, n_pad=n_pad,
                     lane_spec=P(LANE_AXIS), key_spec=key_spec,
                     out_spec=out_spec)


def pad_lanes(packed: Dict[str, Any], n_pad: int) -> Dict[str, Any]:
    """Append ``n_pad`` copies of the last lane to every packed leaf.

    Replicate-lanes keep every value finite and every program branch
    identical to a real lane; the engine masks them off at collection, so
    they cost device FLOPs but never touch results.
    """
    if n_pad == 0:
        return packed
    return jax.tree.map(
        lambda x: jnp.concatenate([x] + [x[-1:]] * n_pad, axis=0), packed)


def place_partition(
    lane_fn,
    packed: Dict[str, Any],
    keys: jax.Array,
    mesh: Mesh,
    *,
    donate: bool = True,
) -> Tuple[Any, Dict[str, Any], jax.Array, Placement]:
    """Pad, place, and jit one partition program for the mesh.

    Returns ``(jitted, placed_packed, placed_keys, placement)`` without
    executing — benchmarks warm and time the call themselves (pass
    ``donate=False`` to re-invoke on the same buffers).
    """
    n_lanes = 0
    leaves = jax.tree.leaves(packed)
    if leaves:
        n_lanes = leaves[0].shape[0]
    # the CPU backend cannot reuse donated buffers (jax warns and ignores
    # them) — donation only pays on accelerator meshes
    donate = donate and mesh.devices.flat[0].platform != "cpu"
    placement = plan_placement(mesh, n_lanes, keys.shape[0])
    fn = jax.vmap(lane_fn, in_axes=(0, None)) if placement.n_lanes \
        else lane_fn
    jitted = PartitionProgram(fn, placement, donate=donate)
    keys_placed = jax.device_put(keys, NamedSharding(mesh, placement.key_spec))
    if placement.n_lanes:
        packed = jax.device_put(pad_lanes(packed, placement.n_pad),
                                NamedSharding(mesh, placement.lane_spec))
    return jitted, packed, keys_placed, placement


class PartitionProgram:
    """One partition program laid across a mesh, one executable per device.

    Every device runs ``jax.jit(fn)`` -- the one-device program that
    ``mode="vmap"`` compiles for a partition of that many lanes -- on its
    own block of lanes and MC keys; lanes and MC runs are independent, so
    no device needs another's data.  The blocks are assembled into arrays
    sharded as ``placement.out_spec``.

    Why not one program over the mesh: XLA's partitioner cannot split the
    fused OTA uplink kernel (a Mosaic custom call) that lanes run on a TPU,
    and a ``shard_map`` module, though it runs the same jaxpr, may be
    compiled differently from a one-device module (on the CPU a reduction
    in the rollout returns changed layout, and rewards their last bits).
    The price is one compile per device.  ``donate`` donates the packed lane
    blocks.  Call with ``packed`` and ``keys`` placed on the mesh as
    ``placement`` says (see :func:`place_partition`).
    """

    def __init__(self, fn, placement: Placement, *, donate: bool = False):
        self.placement = placement
        self._jitted = jax.jit(
            fn, donate_argnums=(0,) if donate and placement.n_lanes else ())
        self._compiled: Dict[Any, Any] = {}

    def _blocks(self, packed, keys):
        """(device, packed block, key block) for every device of the mesh."""
        def block(x, device):
            (data,) = [s.data for s in x.addressable_shards
                       if s.device == device]
            return data
        return [(d, jax.tree.map(lambda x: block(x, d), packed),
                 block(keys, d)) for d in self.placement.mesh.devices.flat]

    def compile(self, packed, keys) -> "PartitionProgram":
        """Compile every device's executable ahead of the call; the
        compiles are independent and run in parallel."""
        todo = {d: self._jitted.lower(pk, ks)
                for d, pk, ks in self._blocks(packed, keys)
                if d not in self._compiled}
        with ThreadPoolExecutor(max(len(todo), 1)) as pool:
            self._compiled.update(zip(
                todo, pool.map(lambda lowered: lowered.compile(),
                               todo.values())))
        return self

    def __call__(self, packed, keys):
        self.compile(packed, keys)
        outs = [self._compiled[d](pk, ks)
                for d, pk, ks in self._blocks(packed, keys)]
        mesh, spec = self.placement.mesh, self.placement.out_spec
        sharding = NamedSharding(mesh, spec)

        def assemble(*blocks):
            shape = list(blocks[0].shape)
            for dim, axes in enumerate(spec):
                for axis in (axes if isinstance(axes, tuple) else (axes,)):
                    if axis is not None:
                        shape[dim] *= mesh.shape[axis]
            return jax.make_array_from_single_device_arrays(
                tuple(shape), sharding, list(blocks))

        return jax.tree.map(assemble, *outs)


def dispatch_partition(
    lane_fn,
    packed: Dict[str, Any],
    keys: jax.Array,
    mesh: Mesh,
    *,
    donate: bool = True,
) -> Tuple[Any, Placement]:
    """Launch one partition on the mesh and return WITHOUT blocking.

    The result's leaves carry a (padded) leading lane axis when
    ``placement.n_lanes > 0``; the replicate path returns unstacked
    ``(mc_runs, ...)`` leaves.  Callers slice real lanes / replicate and
    defer ``block_until_ready`` until they materialise results.

    Compilation is split out ahead-of-time (one XLA compile per partition
    and device: on one device the compile-budget contract's one per
    partition) so the ``compile`` and ``dispatch`` phases land as separate
    ``repro.telemetry.trace`` spans in sweep trace exports.
    """
    from repro.telemetry import trace as rtrace

    jitted, placed, keys_placed, placement = place_partition(
        lane_fn, packed, keys, mesh, donate=donate)
    with rtrace.span("compile", lanes=placement.n_lanes,
                     pad=placement.n_pad, devices=mesh.size):
        jitted.compile(placed, keys_placed)
    with rtrace.span("dispatch", lanes=placement.n_lanes,
                     devices=mesh.size):
        out = jitted(placed, keys_placed)
    return out, placement
