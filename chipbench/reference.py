"""The parts of the plain float32 reference that every model family
shares, written in ``jax.numpy`` from the published equations.  It
imports nothing of the program under test.  A family module
(``chipbench/families/``) writes its own layer equations with these and
runs its forward through ``run``.

    RMSNorm(x) * g = x / sqrt(mean(x^2) + eps) * (1 + deviation)
    rot(x)_i = x_i cos(p w_i) - x_{i+D/2} sin(p w_i)       (i < D/2)
    rot(x)_i = x_i cos(p w_j) + x_{i-D/2} sin(p w_j)       (j = i-D/2)
    w_i = theta^(-2i/D)
    attention = softmax(q k^T / sqrt(D) + causal mask) v

Every matmul runs at float32 with ``highest`` precision (a TPU otherwise
runs float32 matmuls as one bf16 pass).  A family runs its layers one at
a time over the whole sequence and attention in query blocks, so a long
prompt fits beside the weights.  A caller pads every request of a cell
to one shape, so one program serves them.

``quant=True`` gives the control of the comparison: the same forward
with every matmul in fp8 (e4m3), a step below the bfloat16 the
configurations serve in: weights scaled per output column and
activations per token (absmax to 448), products accumulated in float32;
the embedding rows are rounded alike.
"""
from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256
FP8_MAX = 448.0      # largest finite float8_e4m3fn


def rmsnorm(x, dev, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + dev.astype(jnp.float32))


def rope(x, pos, theta):
    D = x.shape[-1]
    w = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos[:, None].astype(jnp.float32) * w[None, :]       # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _fp8(w, axis=-2):
    """Round ``w`` to fp8 (e4m3), one absmax scale per slice along
    ``axis`` (weights (in, out): per output column; activations (T, d):
    per token, with ``axis=-1``)."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    """``x @ w``; the control rounds both operands to fp8 (weights per
    output column, activations per token), as an fp8 matmul would."""
    if quant:
        return _fp8(x, axis=-1) @ _fp8(w)
    return x @ w.astype(jnp.float32)


def _attention(q, k, v, H, KV):
    T, _, D = q.shape
    rep = H // KV
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    nb = -(-T // Q_BLOCK)
    pad = nb * Q_BLOCK - T
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(nb, Q_BLOCK, H, D)
    kpos = jnp.arange(T)

    def block(i, qi):
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qi, k) / jnp.sqrt(jnp.float32(D))
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(lambda a: block(*a), (jnp.arange(nb), qb))
    return out.reshape(nb * Q_BLOCK, H, D)[:T]


def embed(table, tokens, quant):
    """Rows ``tokens`` of the embedding in float32; the control rounds
    the table per row (for a tied model a row is one output column of
    the head)."""
    if quant:
        table = _fp8(table.T).T
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


def run(forward: Callable, tokens: Sequence[int], rows: Sequence[int],
        shape: Tuple[int, int] = (0, 0)) -> np.ndarray:
    """float32 next-token logits at positions ``rows`` of ``tokens``:
    ``forward(tokens, rows)`` on the sequence padded at its end to
    ``shape[0]`` tokens and the rows to ``shape[1]`` (one compiled
    program for every request of a cell; causal attention: padding
    after a position cannot change it), every matmul at ``highest``
    precision."""
    T, R = len(tokens), len(rows)
    toks = np.zeros(max(T, shape[0]), np.int32)
    toks[:T] = tokens
    rr = np.full(max(R, shape[1]), rows[-1], np.int32)
    rr[:R] = rows
    with jax.default_matmul_precision("highest"):
        out = forward(jnp.asarray(toks), jnp.asarray(rr))
    return np.asarray(out, np.float32)[:R]
