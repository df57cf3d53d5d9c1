"""The one generator: builds a cell's timed calls from its configuration and
traffic files, and the draws and answers that the comparison needs.

A traffic file names the entry point, ``perfbench/entries/<entry>.py``, and
its parameters (rounds, agent blocks, a mesh, participation); the entry
file says how a call drives the program and which of its rounds can be
observed.  The configuration's environment and policy are built by their
``kind`` from the program's registries, its channel by its ``kind``; a
kind the program does not know is an error, never a default.

Call ``i`` of a cell draws its key from the seed and ``i``: the same seed
gives the same inputs.  Every call does the same work, counted from the
traffic (N*M*T agent-steps per round and run), never from what the program
reports.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (more than 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


class Round(NamedTuple):
    """One round of a timed call, as its entry plans it."""

    run: int          # Monte Carlo run within the call
    round_idx: int    # 0 starts a run from ``init_key``; others continue it
    key: Any          # the round key
    init_key: Any     # key of the run's initial parameters


@dataclass
class Answer:
    """What one round of a timed call produced, with what it needs to be
    checked: the round key, and where observable the parameters it started
    from and ended with."""

    round_key: Any
    reward: float
    grad_sq: float
    gain_mean: float
    run: int = 0                      # Monte Carlo run within the call
    round_idx: int = 0
    theta_out: Optional[Dict] = None  # parameters after the round
    service_keys: Optional[tuple] = None  # (part_key, sched_key)
    theta_in: Optional[Dict] = None   # parameters the round started from
    call: int = 0
    init_key: Any = None              # key of the run's initial parameters
    actions: Any = None               # own actions (the control's answers)
    mask: Any = None                  # own participation mask (the control)


def scan_rounds(run_keys, rounds: int) -> List[Round]:
    """The rounds of ``fedpg.run`` from each run key: ``(key_init,
    key_scan) = split(run_key)`` and one ``split(key_scan, K)`` key a
    round."""
    plan = []
    for r, rk in enumerate(run_keys):
        key_init, key_scan = jax.random.split(rk)
        plan += [Round(r, k, key, key_init) for k, key in
                 enumerate(jax.random.split(key_scan, rounds))]
    return plan


def host(tree) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v) for k, v in tree.items()}


@dataclass
class Cell:
    config: Dict
    traffic: Dict
    seed: int
    entry_mod: Any     # perfbench/entries/<entry>.py
    ref: Any           # the configuration's plain reference (task)
    chan: Any          # perfbench/channels/<kind>.py
    program: Dict = field(default_factory=dict)

    def __post_init__(self):
        self.ref.check(self.config)
        self.base_key = seed_key(self.seed)

    # -- sizes ---------------------------------------------------------------

    @property
    def n_agents(self) -> int:
        return int(self.config["n_agents"])

    @property
    def runs(self) -> int:
        return self.entry_mod.runs(self)

    @property
    def rounds(self) -> int:
        return int(self.traffic.get("rounds", 1))

    @property
    def chips(self) -> int:
        return int(self.traffic.get("agent_mesh", 1))

    @property
    def agent_steps_per_call(self) -> int:
        c = self.config
        return (self.runs * self.rounds * self.n_agents * c["batch_m"]
                * c["horizon"])

    @property
    def m_h(self) -> float:
        """What the debiased uplink divides by (1 where not debiased)."""
        return self.chan.mean(self.config["channel"]) \
            if self.config["debias"] else 1.0

    # -- the program -----------------------------------------------------------

    def build(self, devices=None) -> "Cell":
        """Construct the program's objects (no compile)."""
        from repro.core.channel import make_channel, noise_sigma_from_db
        from repro.core.fedpg import FedPGConfig
        from repro.core.ota import OTAConfig
        from repro.rl import policy as policies
        from repro.rl.envs import make_env

        c = self.config
        env_kw = dict(c["env"])
        env = make_env(env_kw.pop("kind"), **env_kw)
        pol_kw = dict(c["policy"])
        pol_cls = getattr(policies, pol_kw.pop("kind"), None)
        if not isinstance(pol_cls, type):
            raise ValueError(f"no policy {c['policy']['kind']!r} in the "
                             "program")
        ch = dict(c["channel"])
        ota = OTAConfig(channel=make_channel(ch.pop("kind"), **ch),
                        noise_sigma=noise_sigma_from_db(c["noise_db"]),
                        debias=c["debias"])
        fc = FedPGConfig(n_agents=self.n_agents, batch_m=c["batch_m"],
                         horizon=c["horizon"], gamma=c["gamma"],
                         alpha=c["alpha"],
                         n_rounds=self.entry_mod.rounds_per_call(self))
        self.program = dict(env=env, pol=pol_cls(**pol_kw), fc=fc, ota=ota,
                            blocks=self.traffic.get("agent_blocks"),
                            mesh=None)
        self.entry_mod.build(self, devices)
        return self

    def call_key(self, i: int):
        return jax.random.fold_in(self.base_key, i)

    def call(self, i: int):
        """Timed call ``i``; its raw outputs, ready on the device."""
        return jax.block_until_ready(self.entry_mod.call(self, i))

    def plan(self, i: int) -> List[Round]:
        """The rounds of call ``i``, in order."""
        return self.entry_mod.plan(self, i)

    def answers(self, i: int, out) -> List[Answer]:
        """One Answer per round (and run) of call ``i``'s outputs."""
        return self.entry_mod.answers(self, i, out)

    def observed(self, rnd: Round) -> bool:
        """Whether the parameters after ``rnd`` can be read from a call."""
        return self.entry_mod.observed(self, rnd)

    def replays(self) -> bool:
        """Whether the comparison replays the program's own actions (each
        round's starting parameters are observable), or the reference's
        own with runs that hold a near-tie left out."""
        return self.entry_mod.replays(self)

    def sequential_sum(self) -> bool:
        """The round sums the agents' gradients as a left fold."""
        return self.entry_mod.sequential_sum(self)

    def gains_indexed(self) -> bool:
        """The round draws one ``fold_in`` gain key per agent."""
        return self.chips > 1

    def noise_stream(self) -> str:
        """The uplink's noise stream: the fused kernel's counter stream
        where the program's uplink runs the kernel (one chip of a TPU),
        XLA's leaf-wise normal draw elsewhere (``perfbench/reference.py``)."""
        return "counter" if (self.chips == 1
                             and jax.default_backend() == "tpu") \
            else "leafwise"

    # -- the program's discrete draws --------------------------------------------

    def program_actions(self, theta, round_key):
        """The actions the program's own rollout draws for a round, from its
        parameters and the round key: (N, M, T+1) int32."""
        if not hasattr(self, "_actions_fn"):
            self._actions_fn = jax.jit(self._actions_program)
        return self._actions_fn(theta, round_key)

    def _actions_program(self, theta, round_key):
        from repro.rl.sampler import rollout_batch

        p, n = self.program, self.n_agents
        fc = p["fc"]
        key_samp, _ = jax.random.split(round_key)
        keys = jax.random.split(key_samp, n)
        block = p["blocks"] or n
        n_blocks = -(-n // block)
        pad = n_blocks * block - n
        if pad:
            keys = jnp.concatenate([keys, keys[:pad]])

        def one_block(_, ks):
            return None, jax.vmap(lambda k: rollout_batch(
                p["env"], p["pol"], theta, k, fc.horizon,
                fc.batch_m).actions)(ks)

        _, acts = jax.lax.scan(one_block, None,
                               keys.reshape((n_blocks, block)))
        return acts.reshape((n_blocks * block,) + acts.shape[2:])[:n]
