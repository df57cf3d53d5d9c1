"""Rayleigh fading, ``{"kind": "rayleigh", "scale": s}``: a gain is
``s * |(X, Y)|`` with X, Y ~ N(0, 1), drawn from its key as the program's
documented schedule does (two normals per gain, in the gain's last axis).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def draw(channel: Dict, key, shape: Tuple[int, ...]):
    z = jax.random.normal(key, tuple(shape) + (2,), jnp.float32)
    return channel["scale"] * jnp.sqrt(jnp.sum(z * z, axis=-1))


def mean(channel: Dict) -> float:
    """m_h = E[h], the mean the debiased uplink divides by."""
    return channel["scale"] * math.sqrt(math.pi / 2.0)
