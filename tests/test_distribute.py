"""Sharded sweep execution over a device mesh.

The contract under test: ``sweep(..., mode="sharded")`` is **bit-identical**
to ``mode="vmap"`` run on each device's block of lanes and MC keys — every
device runs the one-device program that mode compiles — for every golden
scenario (LQR at its documented rtol), with uneven lane counts padded by
masked replicate-lanes and partitions dispatched asynchronously.  On one
device the block is the whole batch.  Plus the agent-axis hook: ``fedpg.run(..., agent_mesh=...)``
runs each round's fleet in the production shard_map/psum form.

Everything here passes on a single device (degenerate 1-device mesh); CI
additionally runs this file under an emulated 8-device mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_distribute.py

which is also the recommended way to develop against it locally.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fedpg
from repro.core.channel import FixedGainChannel, RayleighChannel
from repro.core.distribute import (
    agent_mesh_for, default_sweep_mesh, dispatch_partition, pad_lanes,
    place_partition, plan_placement,
)
from repro.core.ota import (
    OTAConfig, aggregate_stacked, psum_aggregate, psum_aggregate_stacked,
)
from repro.core.power_control import HeterogeneousBudget
from repro.core.sweep import (
    Scenario, grid, lane_program, partition_scenarios, sweep,
)
from repro.launch.mesh import make_agent_mesh, make_sweep_mesh
from repro.rl.env import LandmarkNav
from repro.rl.policy import MLPPolicy
from test_golden import RTOL, golden_cases, run_golden_sweep

N_DEV = jax.device_count()
SMALL = dict(n_agents=4, batch_m=3, horizon=8, n_rounds=5, debias=True)


@pytest.fixture(scope="module")
def env_pol():
    return LandmarkNav(), MLPPolicy()


def _hist_equal(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b)
    )


def _vmap_per_block(env, pol, scens, key, mc, mesh):
    """The sharded contract's reference: the partition's ``mode="vmap"``
    program run on one device over each device's block of lanes and MC
    keys, stacked back as ``(lanes, mc, K)`` histories (``(mc, K)`` on the
    replicate path).  XLA compiles the program for each batch size, so
    blocks of another size may differ from the whole batch in the last
    bit; on one device the block is the whole batch."""
    (part,) = partition_scenarios(scens)
    packed, fn, _ = lane_program(env, pol, part, mc_runs=mc)
    keys = jax.random.split(key, mc)
    n = len(scens) if packed else 0
    placement = plan_placement(mesh, n, mc)
    packed = pad_lanes(packed, placement.n_pad)
    key_axes = jax.tree.leaves(tuple(placement.key_spec))
    key_blk = mc // math.prod(mesh.shape[a] for a in key_axes)
    lane_blk = (n + placement.n_pad) // mesh.shape["lane"] if n else 0
    run = jax.jit(fn)
    rows = []
    for i in range(0, max(n + placement.n_pad, 1), max(lane_blk, 1)):
        blocks = [run(jax.tree.map(lambda x: x[i:i + lane_blk], packed),
                      keys[j:j + key_blk]) for j in range(0, mc, key_blk)]
        rows.append(jax.tree.map(
            lambda *b: np.concatenate(b, axis=1 if n else 0), *blocks))
    if not n:
        return rows[0]
    return jax.tree.map(lambda *r: np.concatenate(r, axis=0), *rows)


# ---------------------------------------------------------------------------
# mesh constructors + placement planning
# ---------------------------------------------------------------------------

def test_make_sweep_mesh_shapes():
    mesh = make_sweep_mesh()
    assert tuple(mesh.axis_names) == ("lane", "mc")
    assert mesh.shape["lane"] == N_DEV and mesh.shape["mc"] == 1
    assert mesh.size == N_DEV
    sub = make_sweep_mesh(lane_shards=1)
    assert sub.size == 1
    with pytest.raises(ValueError, match="devices"):
        make_sweep_mesh(lane_shards=N_DEV + 1, mc_shards=2)
    with pytest.raises(ValueError, match="mc_shards"):
        make_sweep_mesh(mc_shards=0)
    with pytest.raises(ValueError, match="lane_shards"):
        make_sweep_mesh(lane_shards=0)


def test_make_agent_mesh_and_agent_mesh_for():
    mesh = make_agent_mesh()
    assert tuple(mesh.axis_names) == ("agents",)
    assert mesh.size == N_DEV
    with pytest.raises(ValueError, match="out of range"):
        make_agent_mesh(N_DEV + 1)
    # agent_mesh_for picks the largest device count dividing n_agents
    for n_agents in (1, 2, 3, 4, 6, 8, 12):
        m = agent_mesh_for(n_agents)
        assert n_agents % m.size == 0
        assert m.size <= N_DEV
    assert agent_mesh_for(1).size == 1


def test_plan_placement():
    mesh = make_sweep_mesh()
    d = mesh.shape["lane"]
    # uneven lanes pad up to the lane axis
    p = plan_placement(mesh, n_lanes=d + 1 if d > 1 else 3, mc_runs=2)
    assert (p.n_lanes + p.n_pad) % d == 0
    assert p.n_devices == mesh.size
    # the replicate path shards MC over the whole mesh only when divisible
    p0 = plan_placement(mesh, n_lanes=0, mc_runs=mesh.size)
    if mesh.size > 1:
        assert p0.key_spec != jax.sharding.PartitionSpec()
    p1 = plan_placement(mesh, n_lanes=0, mc_runs=mesh.size + 1)
    assert p1.key_spec == jax.sharding.PartitionSpec()
    # meshes without a lane axis are rejected with guidance
    bad = make_agent_mesh(1)
    with pytest.raises(ValueError, match="lane"):
        plan_placement(bad, 4, 2)


def test_pad_lanes_replicates_last_lane():
    packed = {"a": jnp.arange(3.0), "b": {"c": jnp.arange(6.0).reshape(3, 2)}}
    padded = pad_lanes(packed, 2)
    assert padded["a"].shape == (5,)
    np.testing.assert_array_equal(np.asarray(padded["a"]), [0, 1, 2, 2, 2])
    np.testing.assert_array_equal(np.asarray(padded["b"]["c"][3:]),
                                  np.asarray(packed["b"]["c"][2:]).repeat(2, 0))
    assert pad_lanes(packed, 0) is packed


# ---------------------------------------------------------------------------
# the bit-identity contract: sharded == vmap on each device's block
# ---------------------------------------------------------------------------

def test_sharded_matches_vmap_uneven_lanes(env_pol):
    """More lanes than divide the mesh (6 on most device counts): padding
    with masked replicate-lanes must not perturb a single real lane."""
    env, pol = env_pol
    scens = grid(channel=RayleighChannel(),
                 noise_sigma=[1e-3, 2e-3, 5e-3, 1e-2, 2e-2, 5e-2], **SMALL)
    key = jax.random.key(0)
    rv = sweep(env, pol, scens, key, 2, mode="vmap")
    rs = sweep(env, pol, scens, key, 2, mode="sharded")
    assert rs.mode == "sharded" and rs.n_devices == N_DEV
    assert rv.n_devices == 1
    ref = _vmap_per_block(env, pol, scens, key, 2, default_sweep_mesh())
    if N_DEV == 1:
        for i in range(len(scens)):
            assert _hist_equal(rv.scenario_history(i),
                               rs.scenario_history(i)), i
    for i in range(len(scens)):
        assert _hist_equal(jax.tree.map(lambda x: x[i], ref),
                           rs.scenario_history(i)), i


def test_sharded_matches_vmap_on_golden_scenarios():
    """The acceptance contract: every golden (env family x uplink) scenario
    is bit-identical between sharded and vmap execution — LQR within its
    documented rtol (see tests/test_golden.py)."""
    ref = run_golden_sweep("vmap")
    got = run_golden_sweep("sharded")
    assert set(ref) == set(got) and len(ref) == len(golden_cases())
    for (fam, uplink), h_ref in ref.items():
        h_got = got[(fam, uplink)]
        rtol = RTOL.get(fam)
        for name, a, b in zip(("rewards", "grad_sq", "gain_mean"),
                              h_ref, h_got):
            a, b = np.asarray(a), np.asarray(b)
            if rtol is None:
                assert np.array_equal(a, b), (fam, uplink, name)
            else:
                np.testing.assert_allclose(
                    a, b, rtol=rtol, atol=0.0,
                    err_msg=f"{fam}/{uplink}/{name}")


def test_sharded_replicate_path_and_mc_sharding(env_pol):
    """Identical scenarios pack to nothing: the replicate path shards the
    MC axis across the whole mesh and must still match vmap bitwise."""
    env, pol = env_pol
    s = Scenario(channel=RayleighChannel(), noise_sigma=1e-3, **SMALL)
    mc = max(N_DEV, 2)  # divisible by the mesh => keys shard
    key = jax.random.key(1)
    rv = sweep(env, pol, [s, s], key, mc, mode="vmap")
    rs = sweep(env, pol, [s, s], key, mc, mode="sharded")
    ref = _vmap_per_block(env, pol, [s, s], key, mc, default_sweep_mesh())
    for i in range(2):
        if N_DEV == 1:
            assert _hist_equal(rv.scenario_history(i),
                               rs.scenario_history(i))
        assert _hist_equal(ref, rs.scenario_history(i))
    assert _hist_equal(rs.scenario_history(0), rs.scenario_history(1))


@pytest.mark.skipif(N_DEV < 2, reason="needs >=2 devices for an mc axis")
def test_sharded_lane_x_mc_mesh(env_pol):
    env, pol = env_pol
    scens = grid(channel=RayleighChannel(), noise_sigma=[1e-3, 1e-2], **SMALL)
    mesh = make_sweep_mesh(lane_shards=N_DEV // 2, mc_shards=2)
    key = jax.random.key(2)
    ref = _vmap_per_block(env, pol, scens, key, 2, mesh)
    rs = sweep(env, pol, scens, key, 2, mode="sharded", mesh=mesh)
    assert rs.n_devices == mesh.size
    for i in range(len(scens)):
        assert _hist_equal(jax.tree.map(lambda x: x[i], ref),
                           rs.scenario_history(i))


def test_sharded_mixed_partitions_async_accounting(env_pol):
    """Several structurally distinct partitions dispatch asynchronously;
    timing lands on every partition and scenario_time_us stays positive."""
    env, pol = env_pol
    scens = [Scenario(channel=RayleighChannel(), noise_sigma=1e-3, **SMALL),
             Scenario(channel=None, **SMALL),
             Scenario(channel=RayleighChannel(), noise_sigma=2e-3, **SMALL)]
    res = sweep(env, pol, scens, jax.random.key(3), 2, mode="sharded")
    assert res.n_partitions == 2
    assert all(p.wall_time_us > 0 for p in res.partitions)
    assert all(res.scenario_time_us(i) > 0 for i in range(len(scens)))
    ref = fedpg.monte_carlo(env, pol, scens[1].fedpg_config(),
                            jax.random.key(3), 2, ota=None)
    assert _hist_equal(ref, res.scenario_history(1))


def test_sweep_rejects_mesh_without_sharded(env_pol):
    env, pol = env_pol
    s = Scenario(channel=None, **SMALL)
    with pytest.raises(ValueError, match="mode='sharded'"):
        sweep(env, pol, [s], jax.random.key(0), 2, mesh=default_sweep_mesh())


# ---------------------------------------------------------------------------
# dispatch internals
# ---------------------------------------------------------------------------

def test_place_partition_reusable_for_benchmarks(env_pol):
    """place_partition(donate=False) returns a program benchmarks can call
    repeatedly on the same placed buffers (fig_scaling.py's timing loop)."""
    from repro.core.sweep import _make_lane, _pack_partition, partition_scenarios

    env, pol = env_pol
    scens = grid(channel=RayleighChannel(), noise_sigma=[1e-3, 1e-2], **SMALL)
    part = partition_scenarios(scens)[0]
    packed = _pack_partition(part)
    lane = _make_lane(env, pol, part)
    keys = jax.random.split(jax.random.key(0), 2)
    mesh = default_sweep_mesh()
    jitted, placed, keys_p, placement = place_partition(
        lane, packed, keys, mesh, donate=False)
    a = jitted(placed, keys_p)
    b = jitted(placed, keys_p)  # donate=False: same buffers, same result
    assert _hist_equal(a, b)
    assert placement.n_lanes == 2
    # and the one-shot dispatcher agrees
    c, _ = dispatch_partition(lane, packed, keys, mesh)
    assert _hist_equal(a, jax.tree.map(lambda x: x, c))


# ---------------------------------------------------------------------------
# agent-axis sharding: the production shard_map/psum round form
# ---------------------------------------------------------------------------

def test_agent_sharded_round_matches_stacked_deterministic(env_pol):
    """With a deterministic channel (FixedGain, sigma=0) the sharded and
    stacked forms see identical gains, so histories must agree to psum
    reassociation tolerance."""
    env, pol = env_pol
    cfg = fedpg.FedPGConfig(n_agents=4, batch_m=2, horizon=6, n_rounds=4)
    ota = OTAConfig(channel=FixedGainChannel(gain=1.3), noise_sigma=0.0,
                    debias=True)
    mesh = agent_mesh_for(cfg.n_agents)
    _, h_ref = fedpg.run(env, pol, cfg, jax.random.key(1), ota=ota)
    _, h_sh = fedpg.run(env, pol, cfg, jax.random.key(1), ota=ota,
                        agent_mesh=mesh)
    for name, a, b in zip(("rewards", "grad_sq", "gain_mean"), h_ref, h_sh):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6, err_msg=name)
    # exact uplink too (psum mean vs stacked mean)
    _, e_ref = fedpg.run(env, pol, cfg, jax.random.key(2))
    _, e_sh = fedpg.run(env, pol, cfg, jax.random.key(2), agent_mesh=mesh)
    np.testing.assert_allclose(np.asarray(e_ref.rewards),
                               np.asarray(e_sh.rewards), rtol=1e-4, atol=1e-6)
    assert np.all(np.asarray(e_sh.gain_mean) == 1.0)


def test_agent_sharded_heterogeneous_fleet():
    """Per-agent env stacks slice across shards: a sharded hetero fleet must
    match the vmapped fleet, and differ from a homogeneous run."""
    from repro.rl.envs import WindyLandmarkNav, make_heterogeneous_env

    n = 4
    het = make_heterogeneous_env(
        [WindyLandmarkNav(wind=0.05 * i, gust_sigma=0.0) for i in range(n)])
    cfg = fedpg.FedPGConfig(n_agents=n, batch_m=2, horizon=6, n_rounds=3)
    pol = het.default_policy()
    mesh = agent_mesh_for(n)
    _, h_ref = fedpg.run(het, pol, cfg, jax.random.key(0))
    _, h_sh = fedpg.run(het, pol, cfg, jax.random.key(0), agent_mesh=mesh)
    np.testing.assert_allclose(np.asarray(h_ref.rewards),
                               np.asarray(h_sh.rewards), rtol=1e-4, atol=1e-6)
    _, h_plain = fedpg.run(WindyLandmarkNav(wind=0.0, gust_sigma=0.0), pol,
                           cfg, jax.random.key(0))
    assert not np.allclose(np.asarray(h_sh.rewards),
                           np.asarray(h_plain.rewards))


def test_agent_sharded_heterogeneous_budget():
    """HeterogeneousBudget keys budgets on *global* agent indices, so the
    sharded per-agent power control must reproduce the stacked linspace."""
    env, pol = LandmarkNav(), MLPPolicy()
    cfg = fedpg.FedPGConfig(n_agents=4, batch_m=2, horizon=5, n_rounds=3)
    ota = OTAConfig(channel=FixedGainChannel(gain=1.0), noise_sigma=0.0,
                    power_control=HeterogeneousBudget(p_min=0.5, p_max=1.5))
    mesh = agent_mesh_for(cfg.n_agents)
    _, h_ref = fedpg.run(env, pol, cfg, jax.random.key(4), ota=ota)
    _, h_sh = fedpg.run(env, pol, cfg, jax.random.key(4), ota=ota,
                        agent_mesh=mesh)
    # unit base gain: mean effective gain == mean budget == 1.0 exactly
    np.testing.assert_allclose(np.asarray(h_sh.gain_mean), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(h_ref.rewards),
                               np.asarray(h_sh.rewards), rtol=1e-4, atol=1e-6)


def test_agent_mesh_divisibility_guard(env_pol):
    env, pol = env_pol
    cfg = fedpg.FedPGConfig(n_agents=3, batch_m=2, horizon=4, n_rounds=2)
    mesh = make_agent_mesh(1)
    # axis name must exist
    with pytest.raises(ValueError, match="no axis"):
        fedpg.make_round_fn(env, pol, cfg, None, agent_mesh=mesh,
                            agent_axis="nope")
    if N_DEV >= 2:
        bad = make_agent_mesh(2)  # 3 agents across 2 shards
        with pytest.raises(ValueError, match="does not divide"):
            fedpg.make_round_fn(env, pol, cfg, None, agent_mesh=bad)


# ---------------------------------------------------------------------------
# psum aggregation regression (the shard_map forms must run on any mesh,
# the degenerate one-device mesh included)
# ---------------------------------------------------------------------------

def _shard_grads(key, n_agents):
    ks = jax.random.split(key, 2)
    return {
        "w": jax.random.normal(ks[0], (n_agents, 3, 4), jnp.float32),
        "b": jax.random.normal(ks[1], (n_agents, 5), jnp.float32),
    }


def test_psum_aggregate_runs_on_axis_size_free_jax(key):
    """Regression: local_gain/psum_aggregate size the mesh axis with
    jax.lax.axis_size and must run on any mesh (here the whole-device
    agents mesh, degenerate size 1 included)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_agent_mesh()
    n = mesh.size
    g = _shard_grads(key, n)
    cfg = OTAConfig(channel=RayleighChannel(), noise_sigma=0.1, debias=True)
    round_key = jax.random.key(5)

    # each shard's block arrives (1, ...); drop the block axis so the local
    # grad is the shard's own pytree, as production shard_map code holds it
    out = shard_map(
        lambda gl: psum_aggregate(
            cfg, round_key, {k: v[0] for k, v in gl.items()}, ("agents",),
            n_agents=n),
        mesh=mesh, in_specs=({k: P("agents") for k in g},),
        out_specs={k: P() for k in g}, check_vma=False,
    )(g)

    key_h, _ = jax.random.split(round_key)
    gains = jnp.stack([cfg.channel.sample(jax.random.fold_in(key_h, i), ())
                       for i in range(n)])
    ref, _ = aggregate_stacked(cfg, round_key, g, gains=gains)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-6)


def test_psum_aggregate_stacked_local_agent_stacks(key):
    """The multi-agent-per-shard form: global gain indices are
    shard*n_local+j, so a 1-shard mesh with the full stack must equal
    aggregate_stacked fed the fold_in gain stream explicitly."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n_agents = 6
    mesh = make_agent_mesh(1)
    g = _shard_grads(key, n_agents)
    cfg = OTAConfig(channel=RayleighChannel(), noise_sigma=0.05, debias=True,
                    power_control=HeterogeneousBudget(p_min=0.5, p_max=1.5))
    round_key = jax.random.key(6)

    def local(gl):
        upd, h = psum_aggregate_stacked(cfg, round_key, gl, ("agents",),
                                        n_agents=n_agents)
        return upd, h

    out, h = shard_map(
        local, mesh=mesh, in_specs=({k: P() for k in g},),
        out_specs=({k: P() for k in g}, P()), check_vma=False,
    )(g)
    assert h.shape == (n_agents,)

    key_h, _ = jax.random.split(round_key)

    def gain(i):
        c = cfg.channel.sample(jax.random.fold_in(key_h, i), ())
        return c * cfg.power_control.apply_indexed(
            c, jnp.asarray(i), n_agents)

    gains = jnp.stack([gain(i) for i in range(n_agents)])
    np.testing.assert_allclose(np.asarray(h), np.asarray(gains), rtol=1e-6)
    ref, _ = aggregate_stacked(cfg, round_key, g, gains=gains)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-6)
