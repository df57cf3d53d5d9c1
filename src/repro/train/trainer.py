"""The production train step: fwd+bwd, OTA/exact aggregation, optimizer.

The paper's technique enters here through exactly one seam — the gradient
aggregation mode:

* ``aggregator="exact"``  — Algorithm 1 semantics: ideal uplink, the batch
  gradient is the plain mean (vanilla data-parallel psum).
* ``aggregator="ota"``    — Algorithm 2: per-agent channel gains are folded
  into the per-sequence loss weights *before* autodiff (so autodiff emits
  ``(1/N) sum_i h_i g_i`` with zero extra collectives), then the server AWGN
  ``n_k / N`` is added to the aggregated gradient and the update optionally
  debiased by ``m_h``.  Each data-parallel shard group is one "agent".

Microbatching (gradient accumulation) uses an agent-major layout
(n_micro, n_agents, per, ...): the batch dim every shard owns stays the
second axis, so every mesh shard stays busy in every microbatch.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ota
from repro.core.channel import make_channel, noise_sigma_from_db
from repro.models.layers import lm_loss
from repro.models.model import Model
from repro.models import transformer
from repro.utils import unroll as uscan
from repro.optim.optimizers import (
    Optimizer, adamw, apply_updates, clip_by_global_norm, warmup_cosine,
)
from repro.utils.tree import tree_global_norm, tree_scale, tree_add, tree_zeros_like

PyTree = Any


@dataclass(frozen=True)
class TrainConfig:
    # paper technique ------------------------------------------------------
    aggregator: str = "ota"            # "exact" (Alg. 1) | "ota" (Alg. 2)
    channel: str = "rayleigh"
    channel_kwargs: Tuple = ()
    noise_db: float = -60.0            # sigma^2 of the uplink AWGN, in dB
    debias: bool = True                # divide aggregated grad by m_h
    n_agents: int = 16                 # data-parallel replica groups
    # optimisation ---------------------------------------------------------
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    microbatch: int = 1                # gradient-accumulation steps
    grad_accum_dtype: str = ""         # "" = param dtype; "float32" for exact
    seed: int = 0
    # uplink implementation ------------------------------------------------
    ota_backend: str = "auto"          # "xla" | "pallas" | "auto"
    wire_dtype: str = ""               # pallas uplink payload ("bfloat16")

    def ota_config(self) -> Optional[ota.OTAConfig]:
        if self.aggregator == "exact":
            return None
        if self.aggregator != "ota":
            raise ValueError(f"unknown aggregator {self.aggregator!r}")
        ch = make_channel(self.channel, **dict(self.channel_kwargs))
        return ota.OTAConfig(
            channel=ch,
            noise_sigma=noise_sigma_from_db(self.noise_db),
            debias=self.debias,
            wire_dtype=self.wire_dtype,
        )


class TrainState(NamedTuple):
    params: PyTree
    opt_state: Any
    step: jax.Array


def make_optimizer(tcfg: TrainConfig) -> Optimizer:
    sched = warmup_cosine(tcfg.lr, tcfg.warmup, tcfg.total_steps)
    return adamw(sched, weight_decay=tcfg.weight_decay)


def init_state(model: Model, tcfg: TrainConfig, key: jax.Array) -> TrainState:
    params = model.init(key)
    opt = make_optimizer(tcfg)
    return TrainState(
        params=params, opt_state=opt.init(params), step=jnp.zeros((), jnp.int32)
    )


def _agent_major(batch: Dict[str, jax.Array], n_agents: int, n_micro: int):
    """(B, ...) -> (n_micro, n_agents, B/(N*mu), ...) without reordering the
    agent ownership of examples (agent i owns the i-th contiguous slice)."""

    def _r(x):
        b = x.shape[0]
        per = b // n_agents
        assert per % n_micro == 0, (b, n_agents, n_micro)
        y = x.reshape((n_agents, n_micro, per // n_micro) + x.shape[1:])
        return jnp.moveaxis(y, 1, 0)

    return jax.tree.map(_r, batch)


def make_loss_fn(model: Model):
    """loss(params, microbatch, weights) over (n_agents, per, ...) batches."""

    def loss_fn(params, mb, weights):
        na, per = mb["tokens"].shape[:2]

        def flat(x):
            return x.reshape((na * per,) + x.shape[2:])

        fb = {k: flat(v) for k, v in mb.items()}
        logits, aux = transformer.forward(
            params, model.cfg, fb["tokens"], fb.get("memory")
        )
        w = None
        if weights is not None:
            w = jnp.repeat(weights, per)
        return lm_loss(logits, fb["labels"], w) + aux

    return loss_fn


def make_train_step(model: Model, tcfg: TrainConfig):
    """Returns train_step(state, batch, key) -> (state', metrics)."""
    opt = make_optimizer(tcfg)
    ota_cfg = tcfg.ota_config()
    loss_fn = make_loss_fn(model)
    n = tcfg.n_agents

    def train_step(state: TrainState, batch: Dict[str, jax.Array], key: jax.Array):
        key = jax.random.fold_in(key, state.step)
        key_h, key_n = jax.random.split(key)

        if ota_cfg is None:
            gains = None
        else:
            gains = ota.sample_gains(ota_cfg, key_h, n)   # (N,)

        mbs = _agent_major(batch, n, tcfg.microbatch)
        grad_fn = jax.value_and_grad(loss_fn)
        acc_dtype = jnp.dtype(tcfg.grad_accum_dtype) if tcfg.grad_accum_dtype \
            else None

        def micro(acc, mb):
            loss_acc, g_acc = acc
            loss, g = grad_fn(state.params, mb, gains)
            if acc_dtype is not None:
                g = jax.tree.map(lambda x: x.astype(acc_dtype), g)
            return (loss_acc + loss, tree_add(g_acc, g)), None

        acc0 = tree_zeros_like(state.params)
        if acc_dtype is not None:
            acc0 = jax.tree.map(lambda x: x.astype(acc_dtype), acc0)
        (loss_sum, grads), _ = uscan.scan(
            micro, (jnp.zeros(()), acc0), mbs
        )
        inv = 1.0 / tcfg.microbatch
        loss = loss_sum * inv
        grads = tree_scale(grads, inv)

        # --- the paper's uplink: server AWGN + optional m_h debias --------
        if ota_cfg is not None:
            grads = ota.add_awgn(ota_cfg, key_n, grads, n,
                                 backend=tcfg.ota_backend)

        grads, gnorm = clip_by_global_norm(grads, tcfg.clip_norm)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = apply_updates(state.params, updates)

        gain_mean = jnp.mean(gains) if gains is not None else jnp.ones(())
        metrics = {
            # the lowered loss is channel-weighted; de-scale by the mean gain
            # so the reported value estimates the plain CE.
            "loss": loss / jnp.maximum(gain_mean, 1e-6),
            "grad_norm": gnorm,
            "gain_mean": gain_mean,
            "update_norm": tree_global_norm(updates),
        }
        return TrainState(params=params, opt_state=opt_state, step=state.step + 1), metrics

    return train_step


# ---------------------------------------------------------------------------
# Explicit shard_map OTA aggregation (Form 2) — optional drop-in used by the
# paper-faithful trainer variant; semantics equal to the weighted-loss form.
# ---------------------------------------------------------------------------

def make_psum_train_step(model: Model, tcfg: TrainConfig, mesh, data_axes=("data",)):
    """Per-shard gradients aggregated with ``ota.aggregate`` (axis form)
    inside shard_map — the literal Eq. (6) dataflow.  Model axes must be unsharded
    (pure DP); used for equivalence tests and the paper-faithful RL-scale
    runs, not for the tensor-parallel production meshes."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    opt = make_optimizer(tcfg)
    ota_cfg = tcfg.ota_config()
    loss_fn = make_loss_fn(model)
    axes = tuple(a for a in data_axes if a in mesh.shape)

    bspec = P(axes)
    rep = P()

    def local_grads(params, batch, key):
        # batch here is this shard's (per, ...) slice; lift to (1, per, ...)
        def lf(p):
            mb = jax.tree.map(lambda x: x[None], batch)
            return loss_fn(p, mb, None)

        loss, g = jax.value_and_grad(lf)(params)
        g = ota.aggregate(g, ota_cfg, key=key, axis=axes)[0]
        return loss, g

    def train_step(state: TrainState, batch, key: jax.Array):
        key = jax.random.fold_in(key, state.step)
        sm = shard_map(
            local_grads,
            mesh=mesh,
            in_specs=(rep, bspec, rep),
            out_specs=(bspec, rep),
            check_vma=False,
        )
        losses, grads = sm(state.params, batch, key)
        grads, gnorm = clip_by_global_norm(grads, tcfg.clip_norm)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = apply_updates(state.params, updates)
        metrics = {"loss": jnp.mean(losses), "grad_norm": gnorm}
        return TrainState(params, opt_state, state.step + 1), metrics

    return train_step
