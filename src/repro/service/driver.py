"""Host-side continuous round service over the jitted service rounds.

:class:`RoundService` wraps the service round program built by
``fedpg.make_round_fn(participation=..., staleness=...)`` into a
long-running loop: rounds execute in jitted *commit segments* (a
``lax.scan`` of ``rounds_per_commit`` service rounds — one dispatch per
commit, any fleet size via ``agent_blocks`` streaming), the
:class:`~repro.service.participation.ServiceState` lives host-side
between commits, and each commit emits a ledger event with the round
service's telemetry (realised participation rate, realised-vs-expected
debias drift, staleness age histogram) plus a ``trace`` span.

The commit span, ``service_commit``, holds three children —
``service.dispatch`` (enqueue the segment), ``service.fetch`` (the
``device_get``, where the host waits on the chip) and ``service.record``
(summary, ledger, checkpoint).  All four carry ``round``, the commit's
first round index.  The commit span also counts ``agents_rolled_out``
(agents the segment passed through ``rollout_batch``, phantom block
padding included), ``agents_participating`` (the participation probe's
realised count, when that probe is on) and ``compiles`` (backend compiles
during the commit).

Determinism and resume: per-round scan keys are derived by
``fold_in(round_key, absolute_round_index)`` — NOT by splitting a
carried key — so round k consumes the identical key stream whether it
runs in the first segment of a fresh service or the first segment after
a checkpoint restore.  Together with the counter-PRNG participation
masks (keyed on the checkpointed ``round_idx``) this makes a resumed
service bitwise-identical to an uninterrupted one.

Checkpoints go through :mod:`repro.checkpoint` (atomic ``.npz`` +
manifest); typed PRNG keys are stored as their ``key_data`` bits and
re-wrapped on restore.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analyze.budget import backend_compiles
from repro.core import fedpg
from repro.service import participation as svc_participation
from repro.service import staleness as svc_staleness
from repro.service.participation import ParticipationConfig, ServiceState
from repro.service.staleness import StalenessConfig, StaleState
from repro.telemetry import get_ledger, scopes, trace
from repro.telemetry.probes import TelemetryConfig, summarize

PyTree = Any

__all__ = ["RoundService", "ServiceConfig"]


@dataclass(frozen=True)
class ServiceConfig:
    """Host-side service loop policy (all static)."""

    rounds_per_commit: int = 8     # rounds per jitted segment / ledger event
    max_rounds: int = 64           # total rounds before the service stops
    round_deadline_s: Optional[float] = None  # wall-clock budget per round
    checkpoint_dir: str = ""       # "" disables checkpointing
    checkpoint_every: int = 1      # checkpoint every this many commits

    def __post_init__(self):
        if self.rounds_per_commit < 1:
            raise ValueError("rounds_per_commit must be >= 1")
        if self.max_rounds < 1:
            raise ValueError("max_rounds must be >= 1")
        if self.checkpoint_every < 1:
            raise ValueError("checkpoint_every must be >= 1")


def _key_data(key: jax.Array) -> jax.Array:
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key


def _wrap_key(data: jax.Array, like: jax.Array) -> jax.Array:
    if jnp.issubdtype(like.dtype, jax.dtypes.prng_key):
        return jax.random.wrap_key_data(jnp.asarray(data, jnp.uint32))
    return data


class RoundService:
    """A continuous federated round service with partial participation.

    ``participation`` must be *active* (one that can actually drop agents
    — see :func:`repro.service.participation.normalize`): a service whose
    config normalises away is just ``fedpg.run``, which already covers
    that case with a single dispatch.  All round-program options
    (``ota``, ``telemetry``, ``agent_blocks``, ``ota_backend``) carry the
    same semantics as :func:`repro.core.fedpg.run`.
    """

    def __init__(self, env, policy, cfg: fedpg.FedPGConfig, key: jax.Array,
                 *, participation: ParticipationConfig,
                 staleness: Optional[StalenessConfig] = None,
                 ota=None, telemetry: Optional[TelemetryConfig] = None,
                 agent_blocks: Optional[int] = None,
                 ota_backend: str = "auto",
                 service: ServiceConfig = ServiceConfig(),
                 theta0: Optional[PyTree] = None):
        part = svc_participation.normalize(participation, cfg.n_agents)
        if part is None:
            raise ValueError(
                "RoundService needs an active participation config (one "
                "that can drop agents); full participation is plain "
                "fedpg.run")
        stale = svc_staleness.normalize(staleness, part)
        self.cfg = cfg
        self.service = service
        self._part = part
        self._stale = stale
        round_fn = fedpg.make_round_fn(
            env, policy, cfg, ota, ota_backend=ota_backend,
            telemetry=telemetry, agent_blocks=agent_blocks,
            participation=part, staleness=stale)

        key_init, self._round_key, key_svc = jax.random.split(key, 3)
        theta = policy.init(key_init) if theta0 is None else theta0
        self.state: ServiceState = svc_participation.init_state(
            theta, key_svc, cfg.n_agents, stale)

        seg = service.rounds_per_commit

        def _segment(state: ServiceState, round_key, r0):
            keys = jax.vmap(
                lambda r: jax.random.fold_in(round_key, r))(
                    r0 + jnp.arange(seg, dtype=jnp.int32))
            return jax.lax.scan(round_fn, state, keys)

        self._segment = jax.jit(_segment)
        self._commits = 0
        self._rolled_out = seg * fedpg.agents_rolled_out(cfg.n_agents,
                                                         agent_blocks)
        backend_compiles()  # the compile listener, registered once

    # -- checkpointing -----------------------------------------------------

    def _ckpt_tree(self, state: ServiceState) -> Dict[str, Any]:
        tree = {
            "theta": state.theta,
            "round_idx": state.round_idx,
            "part_key": _key_data(state.part_key),
            "sched_key": _key_data(state.sched_key),
        }
        if state.stale is not None:
            tree["stale_grads"] = state.stale.grads
            tree["stale_age"] = state.stale.age
        return tree

    def checkpoint(self) -> Optional[str]:
        """Write the current service state; returns the path (or None when
        checkpointing is disabled)."""
        if not self.service.checkpoint_dir:
            return None
        from repro import checkpoint as ckpt

        step = int(self.state.round_idx)
        return ckpt.save(self.service.checkpoint_dir, step,
                         self._ckpt_tree(self.state))

    def resume(self) -> bool:
        """Restore the latest checkpoint, if any.  Returns True when a
        checkpoint was loaded; the next commit continues from its round
        (identical key and mask streams to the uninterrupted run)."""
        if not self.service.checkpoint_dir:
            return False
        from repro import checkpoint as ckpt

        step = ckpt.latest_step(self.service.checkpoint_dir)
        if step is None:
            return False
        tree = ckpt.restore(self.service.checkpoint_dir, step,
                            self._ckpt_tree(self.state))
        stale = None
        if self._stale is not None:
            stale = StaleState(grads=tree["stale_grads"],
                               age=jnp.asarray(tree["stale_age"], jnp.int32))
        self.state = ServiceState(
            theta=tree["theta"],
            round_idx=jnp.asarray(tree["round_idx"], jnp.int32),
            part_key=_wrap_key(tree["part_key"], self.state.part_key),
            sched_key=_wrap_key(tree["sched_key"], self.state.sched_key),
            stale=stale)
        return True

    # -- the service loop --------------------------------------------------

    def commit(self) -> Dict[str, Any]:
        """Run one commit segment (``rounds_per_commit`` service rounds);
        advances the host-side state and returns the commit record that was
        also written to the ambient ledger (if one is installed)."""
        svc = self.service
        r0 = int(self.state.round_idx)
        compiles0 = backend_compiles()
        with trace.span("service_commit", round=r0,
                        rounds=svc.rounds_per_commit) as sp:
            with trace.span("service.dispatch", round=r0) as dispatch:
                args = (self.state, self._round_key, jnp.int32(r0))
                scopes.record(self._segment, *args)
                state, metrics = self._segment(*args)
            with trace.span("service.fetch", round=r0) as fetch:
                trace.wait(metrics)
                metrics = jax.tree.map(np.asarray, jax.device_get(metrics))
            sp.attrs.update(agents_rolled_out=self._rolled_out,
                            compiles=backend_compiles() - compiles0)
            with trace.span("service.record", round=r0):
                rec = self._record(r0, state, metrics, sp,
                                   dispatch.duration_us + fetch.duration_us)
        return rec

    def _record(self, r0: int, state: ServiceState, metrics,
                sp: trace.Span, wall_us: float) -> Dict[str, Any]:
        svc = self.service
        self.state = state
        self._commits += 1

        rewards, grad_sq, gain_mean = metrics[:3]
        rec: Dict[str, Any] = {
            "round_start": r0,
            "round_end": r0 + svc.rounds_per_commit,
            "reward": float(np.mean(rewards)),
            "grad_sq": float(np.mean(grad_sq)),
            "gain_mean": float(np.mean(gain_mean)),
            "wall_us": wall_us,
        }
        if len(metrics) == 4:
            rate = metrics[3].participation_rate
            if rate is not None and not np.isnan(rate).any():
                sp.attrs["agents_participating"] = int(round(
                    float(np.sum(rate, dtype=np.float64)) * self.cfg.n_agents))
            tel = summarize(metrics[3])
            if tel is not None:
                rec.update({k: v for k, v in tel.items() if k in (
                    "participation_rate", "participation_drift",
                    "staleness_mean")})
        if self._stale is not None:
            # host-side staleness histogram over the live buffer ages:
            # bucket k = agents whose copy is k rounds old, last bucket =
            # too old / never contributed (AGE_NEVER saturates the clip)
            age = np.asarray(self.state.stale.age)
            hist = np.bincount(
                np.clip(age, 0, self._stale.max_age + 1),
                minlength=self._stale.max_age + 2)
            rec["staleness_hist"] = [int(c) for c in hist]
        per_round_s = wall_us / 1e6 / svc.rounds_per_commit
        if svc.round_deadline_s is not None \
                and per_round_s > svc.round_deadline_s:
            rec["deadline_exceeded"] = True
            rec["per_round_s"] = per_round_s
        ledger = get_ledger()
        if ledger is not None:
            ledger.log_service(**rec)
        if svc.checkpoint_dir and self._commits % svc.checkpoint_every == 0:
            self.checkpoint()
        return rec

    def run(self) -> List[Dict[str, Any]]:
        """Run commits until ``max_rounds``; returns the commit records."""
        records = []
        while int(self.state.round_idx) < self.service.max_rounds:
            records.append(self.commit())
        return records
