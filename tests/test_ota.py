"""The OTA aggregation primitive: the three implementations must agree, the
estimator must be (conditionally) unbiased after m_h debiasing, and the
noiseless/unit-gain configuration must reduce exactly to Algorithm 1."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare interpreter: only the property tests skip
    from _hypothesis_stub import given, settings, st

from repro.core import ota
from repro.core.channel import FixedGainChannel, IdealChannel, RayleighChannel
from repro.utils.tree import tree_global_norm, tree_sub


def _grads(key, n_agents, shapes=((3, 4), (5,), (2, 2, 2))):
    ks = jax.random.split(key, len(shapes))
    return {
        f"w{i}": jax.random.normal(k, (n_agents,) + s, jnp.float32)
        for i, (k, s) in enumerate(zip(ks, shapes))
    }


def test_ideal_channel_equals_exact_mean(key):
    g = _grads(key, 6)
    cfg = ota.OTAConfig(channel=IdealChannel(), noise_sigma=0.0)
    u, h = ota.aggregate_stacked(cfg, jax.random.key(1), g)
    exact = ota.exact_aggregate(g)
    for a, b in zip(jax.tree.leaves(u), jax.tree.leaves(exact)):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    assert jnp.all(h == 1.0)


def test_fixed_gain_debias_recovers_mean(key):
    g = _grads(key, 4)
    cfg = ota.OTAConfig(channel=FixedGainChannel(gain=2.5), noise_sigma=0.0,
                        debias=True)
    u, _ = ota.aggregate_stacked(cfg, jax.random.key(1), g)
    exact = ota.exact_aggregate(g)
    for a, b in zip(jax.tree.leaves(u), jax.tree.leaves(exact)):
        # identity holds to float32 round-off; atol covers near-zero elements
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_unbiasedness_under_rayleigh(key):
    """E[v_k/(m_h N)] == mean gradient (Lemma 3's premise)."""
    n_agents = 4
    g = _grads(key, n_agents)
    cfg = ota.OTAConfig(channel=RayleighChannel(), noise_sigma=0.01, debias=True)

    @jax.jit
    def one(k):
        u, _ = ota.aggregate_stacked(cfg, k, g)
        return u

    n_rounds = 3000
    us = jax.vmap(one)(jax.random.split(jax.random.key(7), n_rounds))
    mean_u = jax.tree.map(lambda x: jnp.mean(x, 0), us)
    exact = ota.exact_aggregate(g)
    err = tree_global_norm(tree_sub(mean_u, exact))
    scale = tree_global_norm(exact)
    assert float(err / scale) < 0.05


def test_weighted_loss_equals_explicit_per_agent(key):
    """The channel-weighted-loss trick == explicit sum_i h_i grad_i / N."""
    n_agents, per = 4, 3
    w = jax.random.normal(key, (8,), jnp.float32)
    x = jax.random.normal(jax.random.key(3), (n_agents * per, 8), jnp.float32)
    y = jax.random.normal(jax.random.key(4), (n_agents * per,), jnp.float32)
    gains = jnp.array([0.3, 1.7, 0.9, 2.2], jnp.float32)

    def per_example_loss(w, xi, yi):
        return (xi @ w - yi) ** 2

    # explicit per-agent gradients
    def agent_grad(i):
        sl = slice(i * per, (i + 1) * per)
        return jax.grad(
            lambda w: jnp.mean(jax.vmap(per_example_loss, (None, 0, 0))(w, x[sl], y[sl]))
        )(w)

    explicit = sum(gains[i] * agent_grad(i) for i in range(n_agents)) / n_agents

    # weighted-loss trick
    ew = ota.example_weights(gains, n_agents * per)
    weighted = jax.grad(
        lambda w: jnp.mean(ew * jax.vmap(per_example_loss, (None, 0, 0))(w, x, y))
    )(w)
    np.testing.assert_allclose(np.asarray(weighted), np.asarray(explicit), rtol=1e-5)


def test_example_weights_shape_and_errors():
    gains = jnp.array([1.0, 2.0])
    w = ota.example_weights(gains, 6)
    np.testing.assert_allclose(np.asarray(w), [1, 1, 1, 2, 2, 2])
    with pytest.raises(ValueError):
        ota.example_weights(gains, 5)


def test_add_awgn_statistics(key):
    grad = {"w": jnp.zeros((100, 100), jnp.float32)}
    cfg = ota.OTAConfig(channel=IdealChannel(), noise_sigma=0.8, debias=False)
    out = ota.add_awgn(cfg, key, grad, n_agents=4)
    # noise std should be sigma / N
    assert float(jnp.std(out["w"])) == pytest.approx(0.8 / 4, rel=0.05)


@settings(max_examples=20, deadline=None)
@given(
    n_agents=st.integers(1, 8),
    sigma=st.floats(0.0, 1.0),
    gain=st.floats(0.1, 3.0),
)
def test_property_zero_grads_yield_pure_noise(n_agents, sigma, gain):
    """With g_i = 0: u = n_k / N exactly — the channel cannot invent signal."""
    g = {"w": jnp.zeros((n_agents, 16), jnp.float32)}
    cfg = ota.OTAConfig(
        channel=FixedGainChannel(gain=gain), noise_sigma=sigma, debias=False
    )
    u, _ = ota.aggregate_stacked(cfg, jax.random.key(0), g)
    if sigma == 0.0:
        assert float(jnp.max(jnp.abs(u["w"]))) == 0.0
    else:
        # replicate aggregate_stacked's key path: split -> key_n, then
        # tree_normal_like splits key_n once per leaf
        key_n = jax.random.split(jax.random.key(0))[1]
        leaf_key = jax.random.split(key_n, 1)[0]
        expected = jax.random.normal(leaf_key, (16,), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(u["w"]), np.asarray(expected) * sigma / n_agents,
            rtol=1e-4, atol=1e-6,
        )


def test_psum_aggregate_matches_stacked(key):
    """Form 2 (shard_map psum) == Form 1 (stacked) given the same gains."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    n = jax.local_device_count()
    if n < 2:
        pytest.skip("needs >=2 devices (run via tests/test_dryrun_subprocess)")
    mesh = jax.make_mesh((n,), ("data",))
    g = _grads(key, n)
    cfg = ota.OTAConfig(channel=RayleighChannel(), noise_sigma=0.1, debias=True)
    round_key = jax.random.key(5)

    def local(gl):
        return ota.psum_aggregate(cfg, round_key, gl, ("data",))

    out = shard_map(
        local, mesh=mesh, in_specs=({k: P("data") for k in g},),
        out_specs={k: P() for k in g}, check_vma=False,
    )(g)

    key_h, _ = jax.random.split(round_key)
    gains = jnp.stack(
        [cfg.channel.sample(jax.random.fold_in(key_h, i), ()) for i in range(n)]
    )
    ref, _ = ota.aggregate_stacked(cfg, round_key, g, gains=gains)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6)
