"""Power control: effective-gain moments (closed form vs Monte Carlo), the
ControlledChannel registry contract, the NaN-moment guard rails, and
cross-form equivalence of the three OTA aggregation implementations under
``power_control`` + ``update_scale`` simultaneously."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ota
from repro.core.channel import (
    BatchedChannel, LogNormalChannel, NakagamiChannel, RayleighChannel,
    batched_channel_arrays, channel_kind, make_channel,
)
from repro.core.power_control import (
    ConstantReceived, ControlledChannel, FullInversion, HeterogeneousBudget,
    TruncatedInversion, UnitPower, closed_form_moments, estimate_moments,
    make_controlled_channel,
)

N_MC = 400_000


# ---------------------------------------------------------------------------
# Closed-form moments vs Monte Carlo.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy,n_agents", [
    (TruncatedInversion(), None),
    (TruncatedInversion(target=2.0, p_max=3.0, c_min=0.2), None),
    (TruncatedInversion(target=1.0, p_max=1.5, c_min=0.9), None),  # c_min > t
    (FullInversion(), None),
    (FullInversion(target=0.8, p_max=2.0), None),
    (ConstantReceived(target=1.3), None),
    (HeterogeneousBudget(p_min=0.5, p_max=1.5), 8),
    (UnitPower(), None),
])
def test_closed_form_matches_monte_carlo(policy, n_agents):
    base = RayleighChannel()
    closed = closed_form_moments(base, policy, n_agents=n_agents)
    assert closed is not None
    m, v = closed
    m_mc, v_mc = estimate_moments(base, policy, jax.random.key(1), N_MC,
                                  n_agents=n_agents)
    assert m == pytest.approx(m_mc, rel=0.01, abs=1e-3)
    assert v == pytest.approx(v_mc, rel=0.05, abs=1e-3)


def test_truncated_inversion_rayleigh_incomplete_gamma_terms():
    """Spot-check the incomplete-gamma expressions against a hand-computed
    pure-outage case: p_max -> huge makes h = target above c_min, so
    m = target * exp(-c_min^2/2) and E[h^2] = target^2 * exp(-c_min^2/2)."""
    target, c_min = 1.5, 0.3
    pol = TruncatedInversion(target=target, p_max=1e9, c_min=c_min)
    m, v = closed_form_moments(RayleighChannel(), pol)
    surv = math.exp(-c_min**2 / 2.0)
    assert m == pytest.approx(target * surv, rel=1e-9)
    assert v == pytest.approx(target**2 * surv - (target * surv) ** 2, rel=1e-9)


def test_closed_form_none_for_unknown_base():
    assert closed_form_moments(NakagamiChannel(), TruncatedInversion()) is None
    assert closed_form_moments(LogNormalChannel(), FullInversion()) is None
    # ConstantReceived / UnitPower / HeterogeneousBudget work over any base
    assert closed_form_moments(NakagamiChannel(), ConstantReceived()) == (1.0, 0.0)
    m, v = closed_form_moments(NakagamiChannel(m=0.5, omega=1.0),
                               HeterogeneousBudget(), n_agents=4)
    assert math.isfinite(m) and math.isfinite(v)


def test_constant_received_kills_variance():
    ch = make_controlled_channel(RayleighChannel(), ConstantReceived(target=1.0))
    assert ch.mean == pytest.approx(1.0) and ch.var == 0.0
    h = ch.sample(jax.random.key(0), (1000,))
    np.testing.assert_allclose(np.asarray(h), 1.0, rtol=1e-5)


def test_heterogeneous_budget_needs_n_agents():
    with pytest.raises(ValueError, match="n_agents"):
        closed_form_moments(RayleighChannel(), HeterogeneousBudget())
    with pytest.raises(ValueError, match="n_agents"):
        estimate_moments(RayleighChannel(), HeterogeneousBudget(),
                         jax.random.key(0), 100)


def test_heterogeneous_budget_indexed_matches_vector():
    pol = HeterogeneousBudget(p_min=0.5, p_max=1.5)
    c = jax.random.uniform(jax.random.key(0), (6,)) + 0.5
    vec = pol.apply(c)
    per = jnp.stack([
        pol.apply_indexed(c[i], jnp.asarray(i, jnp.int32), 6) for i in range(6)
    ])
    np.testing.assert_allclose(np.asarray(vec), np.asarray(per), rtol=1e-6)


# ---------------------------------------------------------------------------
# ControlledChannel: registry + constructor + moment guard rails.
# ---------------------------------------------------------------------------

def test_controlled_channel_is_registered():
    ch = make_controlled_channel(RayleighChannel(), TruncatedInversion())
    assert channel_kind(ch) == "controlled:rayleigh:TruncatedInversion"
    via_factory = make_channel("controlled", base=RayleighChannel(),
                               policy=UnitPower(), _mean=1.0, _var=0.5)
    assert channel_kind(via_factory) == "controlled:rayleigh:UnitPower"


def test_controlled_channel_requires_base():
    with pytest.raises(ValueError, match="make_controlled_channel"):
        ControlledChannel(policy=UnitPower())


def test_make_controlled_channel_fills_moments():
    # closed form: no key needed
    ch = make_controlled_channel(RayleighChannel(), FullInversion())
    assert math.isfinite(ch.mean) and math.isfinite(ch.var)
    # MC fallback for a base with no closed form
    ch2 = make_controlled_channel(NakagamiChannel(m=0.5, omega=1.0),
                                  TruncatedInversion(), jax.random.key(3),
                                  n=50_000)
    m_mc, v_mc = estimate_moments(NakagamiChannel(m=0.5, omega=1.0),
                                  TruncatedInversion(), jax.random.key(3),
                                  50_000)
    assert ch2.mean == m_mc and ch2.var == v_mc


def test_nan_moments_rejected_everywhere():
    bare = ControlledChannel(base=RayleighChannel(), policy=TruncatedInversion())
    # OTAConfig build time, with debias
    with pytest.raises(ValueError, match="make_controlled_channel"):
        ota.OTAConfig(channel=bare, debias=True)
    # batched packing
    with pytest.raises(ValueError, match="non-finite"):
        batched_channel_arrays([bare, bare])
    # debias=False never divides by m_h, so the un-estimated channel is fine
    cfg = ota.OTAConfig(channel=bare, debias=False)
    assert cfg.norm_const == 1.0
    # ... and an explicit update_scale bypasses norm_const entirely
    cfg2 = ota.OTAConfig(channel=bare, debias=True, update_scale=0.1)
    u, _ = ota.aggregate_stacked(
        cfg2, jax.random.key(0),
        {"w": jnp.ones((4, 3), jnp.float32)})
    assert bool(jnp.all(jnp.isfinite(u["w"])))


def test_batched_controlled_channel_bitwise():
    """Lane-sliced batched draws == concrete ControlledChannel draws."""
    chans = [
        make_controlled_channel(RayleighChannel(scale=sc),
                                TruncatedInversion(target=t))
        for sc, t in ((1.0, 1.0), (0.5, 2.0))
    ]
    kind, arrays = batched_channel_arrays(chans)
    assert kind == "controlled:rayleigh:TruncatedInversion"
    params = {k: jnp.asarray(v, jnp.float32) for k, v in arrays.items()}
    key = jax.random.key(7)

    def lane(p):
        return BatchedChannel(kind=kind, params=p).sample(key, (16,))

    batched = jax.jit(lambda pk: jax.lax.map(lane, pk))(params)
    for i, ch in enumerate(chans):
        ref = jax.jit(lambda c=ch: c.sample(key, (16,)))()
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(batched[i]))
        np.testing.assert_allclose(float(params["_mean"][i]), ch.mean, rtol=1e-7)
        np.testing.assert_allclose(float(params["_var"][i]), ch.var, rtol=1e-7)


def test_mixed_policy_types_do_not_batch():
    a = make_controlled_channel(RayleighChannel(), TruncatedInversion())
    b = make_controlled_channel(RayleighChannel(), FullInversion())
    with pytest.raises(ValueError, match="cannot batch"):
        batched_channel_arrays([a, b])


# ---------------------------------------------------------------------------
# Cross-form equivalence under power_control + update_scale simultaneously.
# ---------------------------------------------------------------------------

def _grads(key, n_agents, shapes=((3, 4), (5,))):
    ks = jax.random.split(key, len(shapes))
    return {
        f"w{i}": jax.random.normal(k, (n_agents,) + s, jnp.float32)
        for i, (k, s) in enumerate(zip(ks, shapes))
    }


@pytest.mark.parametrize("policy", [
    TruncatedInversion(target=1.0, p_max=5.0, c_min=0.1),
    FullInversion(target=1.2, p_max=4.0),
    HeterogeneousBudget(p_min=0.5, p_max=1.5),
])
def test_stacked_equals_weighted_loss_form(policy):
    """Form 1 (aggregate_stacked) == Form 3 (weighted grad + add_awgn) with
    power_control and update_scale set at the same time."""
    n_agents = 4
    cfg = ota.OTAConfig(channel=RayleighChannel(), noise_sigma=0.05,
                        power_control=policy, update_scale=0.21)
    g = _grads(jax.random.key(2), n_agents)
    round_key = jax.random.key(5)
    u1, h = ota.aggregate_stacked(cfg, round_key, g)

    # weighted-loss form: its input already carries (1/N) sum h_i g_i, and
    # add_awgn uses the same noise key aggregate_stacked derived internally
    key_h, key_n = jax.random.split(round_key)
    np.testing.assert_array_equal(
        np.asarray(h), np.asarray(ota.sample_gains(cfg, key_h, n_agents)))
    weighted = jax.tree.map(
        lambda x: jnp.tensordot(h, x, axes=1) / n_agents, g)
    u3 = ota.add_awgn(cfg, key_n, weighted, n_agents=n_agents)
    for a, b in zip(jax.tree.leaves(u1), jax.tree.leaves(u3)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-7)


@pytest.mark.parametrize("policy", [
    TruncatedInversion(target=1.0, p_max=5.0, c_min=0.1),
    HeterogeneousBudget(p_min=0.5, p_max=1.5),
])
def test_psum_equals_stacked_under_power_control(policy):
    """Form 2 (shard_map psum) == Form 1 given the same gains, with
    power_control and update_scale set at the same time."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P  # noqa: F401

    n = jax.local_device_count()
    if n < 2:
        pytest.skip("needs >=2 devices (run via tests/test_dryrun_subprocess)")
    mesh = jax.make_mesh((n,), ("data",))
    g = _grads(jax.random.key(8), n)
    cfg = ota.OTAConfig(channel=RayleighChannel(), noise_sigma=0.1,
                        power_control=policy, update_scale=0.17)
    round_key = jax.random.key(9)

    def local(gl):
        return ota.psum_aggregate(cfg, round_key, gl, ("data",))

    out = shard_map(
        local, mesh=mesh, in_specs=({k: P("data") for k in g},),
        out_specs={k: P() for k in g}, check_vma=False,
    )(g)

    key_h, _ = jax.random.split(round_key)
    cs = jnp.stack([
        cfg.channel.sample(jax.random.fold_in(key_h, i), ()) for i in range(n)
    ])
    gains = cs * jax.vmap(
        lambda c, i: policy.apply_indexed(c, i, n)
    )(cs, jnp.arange(n, dtype=jnp.int32))
    ref, _ = ota.aggregate_stacked(cfg, round_key, g, gains=gains)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)


def test_debias_uses_effective_mean_without_update_scale():
    """A directly-built OTAConfig(debias=True, power_control=...) divides by
    the *effective* mean E[c p(c)] — same normaliser Scenario folds into
    update_scale — not the raw channel mean."""
    from repro.core.power_control import effective_moments

    pol = TruncatedInversion()
    cfg = ota.OTAConfig(channel=RayleighChannel(), power_control=pol,
                        debias=True)
    n_agents = 4
    m_eff, _ = effective_moments(RayleighChannel(), pol)
    assert cfg.norm_const_for(n_agents) == pytest.approx(m_eff)
    assert cfg.norm_const_for(n_agents) != pytest.approx(RayleighChannel().mean)

    g = _grads(jax.random.key(0), n_agents)
    key = jax.random.key(1)
    u, _ = ota.aggregate_stacked(cfg, key, g)
    explicit = ota.OTAConfig(channel=RayleighChannel(), power_control=pol,
                             debias=True,
                             update_scale=1.0 / (n_agents * m_eff))
    u_ref, _ = ota.aggregate_stacked(explicit, key, g)
    for a, b in zip(jax.tree.leaves(u), jax.tree.leaves(u_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    # per-agent policies get their n_agents from the call site
    cfg_het = ota.OTAConfig(channel=RayleighChannel(), debias=True,
                            power_control=HeterogeneousBudget())
    assert cfg_het.norm_const_for(n_agents) == pytest.approx(
        effective_moments(RayleighChannel(), HeterogeneousBudget(),
                          n_agents=n_agents)[0])


def test_agent_count_mismatch_rejected():
    """Per-agent mixture moments baked for one N cannot silently run at
    another N."""
    from repro.core.power_control import check_agent_count
    from repro.core.sweep import Scenario

    ch = make_controlled_channel(RayleighChannel(), HeterogeneousBudget(),
                                 n_agents=8)
    check_agent_count(ch, 8)  # matching count passes
    with pytest.raises(ValueError, match="n_agents"):
        check_agent_count(ch, 4)
    with pytest.raises(ValueError, match="baked for n_agents=8"):
        Scenario(channel=ch, n_agents=4).ota_config()
    # the direct sampling path is guarded too, not just the Scenario layer
    with pytest.raises(ValueError, match="baked for n_agents=8"):
        ch.sample(jax.random.key(0), (4,))
    _ = ch.sample(jax.random.key(0), (8,))  # matching axis samples fine
    # non-per-agent channels are unconstrained
    check_agent_count(make_controlled_channel(RayleighChannel(),
                                              TruncatedInversion()), 3)


def test_per_agent_policy_rejects_scalar_sample():
    """ControlledChannel over a per-agent policy cannot be sampled without
    an agent axis (the shard_map path must use OTAConfig.power_control)."""
    ch = make_controlled_channel(RayleighChannel(), HeterogeneousBudget(),
                                 n_agents=4)
    with pytest.raises(ValueError, match="agent axis"):
        ch.sample(jax.random.key(0), ())


def test_sample_gains_per_agent_policy_uses_agent_axis():
    cfg = ota.OTAConfig(channel=RayleighChannel(),
                        power_control=HeterogeneousBudget(p_min=0.0, p_max=2.0))
    key = jax.random.key(0)
    h = ota.sample_gains(cfg, key, 5)
    c = RayleighChannel().sample(key, (5,))
    budgets = jnp.linspace(0.0, 2.0, 5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(c * budgets),
                               rtol=1e-6)
