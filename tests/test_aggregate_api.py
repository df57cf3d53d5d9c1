"""The unified ``ota.aggregate`` dispatcher: every spec must reproduce its
legacy entry point bit-for-bit on the xla backend (the golden-trace
contract), the deprecated wrappers must warn, and the pallas backend must
agree with xla wherever the streams coincide (noiseless paths)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ota
from repro.core.channel import FixedGainChannel, IdealChannel, RayleighChannel


def _grads(key, n_agents, shapes=((3, 4), (5,), (2, 2, 2))):
    ks = jax.random.split(key, len(shapes))
    return {
        f"w{i}": jax.random.normal(k, (n_agents,) + s, jnp.float32)
        for i, (k, s) in enumerate(zip(ks, shapes))
    }


def _legacy(name, *args, **kwargs):
    """Call a deprecated wrapper with its warning silenced."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return getattr(ota, name)(*args, **kwargs)


CFGS = [
    ota.OTAConfig(channel=IdealChannel(), noise_sigma=0.0),
    ota.OTAConfig(channel=RayleighChannel(), noise_sigma=0.1, debias=True),
    ota.OTAConfig(channel=FixedGainChannel(gain=2.5), noise_sigma=0.0,
                  debias=True),
    ota.OTAConfig(channel=RayleighChannel(), noise_sigma=0.3,
                  update_scale=0.0421),
]


@pytest.mark.parametrize("cfg", CFGS, ids=["ideal", "rayleigh", "fixed",
                                           "packed_scale"])
def test_dispatcher_stacked_equals_legacy_bitwise(key, cfg):
    g = _grads(key, 6)
    k = jax.random.key(3)
    u1, h1 = ota.aggregate(g, cfg, key=k, backend="xla")
    u2, h2 = _legacy("aggregate_stacked", cfg, k, g)
    for a, b in zip(jax.tree.leaves(u1), jax.tree.leaves(u2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))


def test_dispatcher_exact_equals_legacy_bitwise(key):
    g = _grads(key, 5)
    u1, h = ota.aggregate(g, None)
    u2 = _legacy("exact_aggregate", g)
    for a, b in zip(jax.tree.leaves(u1), jax.tree.leaves(u2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(h) == 1.0


def test_dispatcher_auto_is_xla_on_cpu():
    """Golden-trace safety: off-TPU, auto must resolve to the xla chain."""
    if jax.default_backend() == "tpu":
        pytest.skip("auto resolves to pallas on TPU by design")
    spec = ota.AggregateSpec(form="stacked", exact=False, backend="auto")
    assert spec.resolved_backend() == "xla"


def test_deprecated_wrappers_warn(key):
    g = _grads(key, 3)
    cfg = CFGS[0]
    with pytest.warns(DeprecationWarning):
        ota.aggregate_stacked(cfg, jax.random.key(0), g)
    with pytest.warns(DeprecationWarning):
        ota.exact_aggregate(g)


def test_spec_validation():
    with pytest.raises(ValueError):
        ota.AggregateSpec(form="nope")
    with pytest.raises(ValueError):
        ota.AggregateSpec(backend="cuda")
    with pytest.raises(ValueError):
        # pallas implements the stacked form only
        ota.AggregateSpec(form="axis", backend="pallas").resolved_backend()
    with pytest.raises(ValueError):
        # axis forms need axis names
        ota.aggregate({"w": jnp.ones((2, 3))}, CFGS[1], key=jax.random.key(0),
                      spec=ota.AggregateSpec(form="axis"))
    with pytest.raises(ValueError):
        # noisy aggregation needs a key
        ota.aggregate({"w": jnp.ones((2, 3))}, CFGS[1])


def test_aggregate_apply_xla_equals_two_step(key):
    """aggregate_apply on xla == aggregate + tree-mapped SGD, bitwise (the
    fedpg round loop's historical op order)."""
    g = _grads(key, 4)
    params = jax.tree.map(lambda x: jnp.zeros(x.shape[1:]), g)
    cfg = CFGS[1]
    k = jax.random.key(8)
    u, h1 = ota.aggregate(g, cfg, key=k, backend="xla")
    manual = jax.tree.map(lambda p, x: p - 0.05 * x, params, u)
    applied, h2 = ota.aggregate_apply(g, cfg, params, key=k, alpha=0.05,
                                      backend="xla")
    for a, b in zip(jax.tree.leaves(applied), jax.tree.leaves(manual)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))


def test_pallas_backend_noiseless_matches_xla(key):
    """With sigma=0 the pallas and xla paths compute the same estimator;
    summation order differs (flat matvec vs per-leaf broadcast sum), so
    parity is allclose-at-f32, not bitwise."""
    g = _grads(key, 6)
    cfg = ota.OTAConfig(channel=RayleighChannel(), noise_sigma=0.0,
                        debias=True)
    k = jax.random.key(5)
    up, hp = ota.aggregate(g, cfg, key=k, backend="pallas")
    ux, hx = ota.aggregate(g, cfg, key=k, backend="xla")
    np.testing.assert_array_equal(np.asarray(hp), np.asarray(hx))
    for a, b in zip(jax.tree.leaves(up), jax.tree.leaves(ux)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=1e-7)


def test_pallas_backend_noise_statistics(key):
    """The pallas noise stream differs from threefry by design; check the
    statistics instead: zero grads -> u = sigma*n*scale exactly."""
    n_agents, n_params = 4, 20000
    g = {"w": jnp.zeros((n_agents, n_params), jnp.float32)}
    cfg = ota.OTAConfig(channel=IdealChannel(), noise_sigma=0.8)
    u, _ = ota.aggregate(g, cfg, key=jax.random.key(2), backend="pallas")
    flat = np.asarray(u["w"]).ravel()
    assert abs(flat.mean()) < 0.02
    assert abs(flat.std() - 0.8 / n_agents) < 0.01


def test_aggregate_apply_pallas_smoke(key):
    """Fused sgd path end-to-end over a pytree: finite, close to xla."""
    g = _grads(key, 4)
    params = jax.tree.map(lambda x: jnp.ones(x.shape[1:]), g)
    cfg = ota.OTAConfig(channel=FixedGainChannel(gain=1.5), noise_sigma=0.0,
                        debias=True)
    k = jax.random.key(4)
    p_pl, _ = ota.aggregate_apply(g, cfg, params, key=k, alpha=0.1,
                                  backend="pallas")
    p_xla, _ = ota.aggregate_apply(g, cfg, params, key=k, alpha=0.1,
                                   backend="xla")
    for a, b in zip(jax.tree.leaves(p_pl), jax.tree.leaves(p_xla)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=1e-6)


def test_add_awgn_backend_equivalence_noiseless(key):
    grad = _grads(key, 1)
    grad = jax.tree.map(lambda x: x[0], grad)  # un-stack: plain grad pytree
    cfg = ota.OTAConfig(channel=FixedGainChannel(gain=2.0), noise_sigma=0.0,
                        debias=True)
    a = ota.add_awgn(cfg, jax.random.key(1), grad, n_agents=4, backend="xla")
    b = ota.add_awgn(cfg, jax.random.key(1), grad, n_agents=4,
                     backend="pallas")
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-6, atol=1e-7)


def test_dispatcher_axis_forms_match_legacy(key):
    """Axis and axis-stacked forms through the dispatcher == the legacy
    psum entry points, bitwise (same ops inside shard_map)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = jax.local_device_count()
    if n < 2:
        pytest.skip("needs >=2 devices (CI mesh tier runs with 8)")
    mesh = jax.make_mesh((n,), ("data",))
    g = _grads(key, n)
    cfg = ota.OTAConfig(channel=RayleighChannel(), noise_sigma=0.1,
                        debias=True)
    round_key = jax.random.key(5)

    def new(gl):
        return ota.aggregate(gl, cfg, key=round_key, axis=("data",),
                             n_agents=n)[0]

    def old(gl):
        return _legacy("psum_aggregate", cfg, round_key, gl, ("data",),
                       n_agents=n)

    specs = ({k: P("data") for k in g},)
    out_specs = {k: P() for k in g}
    a = shard_map(new, mesh=mesh, in_specs=specs, out_specs=out_specs,
                  check_vma=False)(g)
    b = shard_map(old, mesh=mesh, in_specs=specs, out_specs=out_specs,
                  check_vma=False)(g)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def new_stacked(gl):
        return ota.aggregate(gl, cfg, key=round_key, axis=("data",),
                             n_agents=n, local_stack=True)

    def old_stacked(gl):
        return _legacy("psum_aggregate_stacked", cfg, round_key, gl,
                       ("data",), n_agents=n)

    in_sp = ({k: P("data") for k in g},)
    out_sp = ({k: P() for k in g}, P("data"))
    a2 = shard_map(new_stacked, mesh=mesh, in_specs=in_sp, out_specs=out_sp,
                   check_vma=False)(g)
    b2 = shard_map(old_stacked, mesh=mesh, in_specs=in_sp, out_specs=out_sp,
                   check_vma=False)(g)
    for x, y in zip(jax.tree.leaves(a2), jax.tree.leaves(b2)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fedpg_round_backend_pallas_smoke():
    """The whole round loop with ota_backend='pallas' runs on CPU (interpret
    mode) and produces finite metrics close to the xla run."""
    from repro.core import fedpg
    from repro.rl.env import LandmarkNav
    from repro.rl.policy import MLPPolicy

    env, pol = LandmarkNav(), MLPPolicy()
    cfg = fedpg.FedPGConfig(n_agents=3, batch_m=2, horizon=5, n_rounds=2,
                            alpha=1e-3)
    ocfg = ota.OTAConfig(channel=FixedGainChannel(gain=1.2),
                         noise_sigma=0.0, debias=True)
    key = jax.random.key(0)
    _, hist_pl = fedpg.run(env, pol, cfg, key, ota=ocfg,
                           ota_backend="pallas")
    _, hist_xla = fedpg.run(env, pol, cfg, key, ota=ocfg, ota_backend="xla")
    assert np.isfinite(np.asarray(hist_pl.rewards)).all()
    np.testing.assert_allclose(np.asarray(hist_pl.rewards),
                               np.asarray(hist_xla.rewards),
                               rtol=1e-4, atol=1e-5)
