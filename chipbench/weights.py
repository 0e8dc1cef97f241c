"""Seeded weights: the parts every model family shares.

A family module (``chipbench/families/``) draws its matrices with
``_draw`` from ``_key(seed)``, in the served dtype, on the device, in one
jitted call, and lays them out as the program's parameter tree; the
plain reference reads the published layout.  ``fingerprint`` sums each
published matrix so that a placement fault in the program's tree shows
as a mismatch (``same_fingerprint``).  Norm weights are drawn as their
deviation from one (``NORM_STD``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

NORM_STD = 0.1      # spread of the norm weights around one
EMBED_STD = 0.02


def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _key(seed: int):
    # seeds run past 32 bits: fold the high and low words in turn
    return jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(0), seed >> 32), seed & 0xFFFFFFFF)


def _pad(x, axis: int, size: int):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad)


def _sums_fn(x):
    x = x.astype(jnp.float32)
    return jnp.sum(x), jnp.sum(jnp.abs(x))


_sums = jax.jit(_sums_fn)


def fingerprint(c: Dict) -> Dict[str, tuple]:
    """Per-matrix float32 (sum, sum of magnitudes) of the
    published-layout weights, each reduced in one fused program (no
    float32 copy of a matrix)."""
    return {k: tuple(float(s) for s in _sums(v)) for k, v in c.items()}


def same_fingerprint(a: Dict, b: Dict) -> bool:
    """Equal up to float32 summation order: a matrix placed wrong (or
    another matrix in its place) moves its sum by far more."""
    return a.keys() == b.keys() and all(
        abs(a[k][0] - b[k][0]) <= 1e-5 * max(a[k][1], 1e-30)
        and abs(a[k][1] - b[k][1]) <= 1e-5 * max(a[k][1], 1e-30)
        for k in a)
