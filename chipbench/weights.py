"""Seeded weights in the published layout, and their placement into the
program's parameter tree.

``canonical(seed, model)`` draws every matrix of a llama-style stack
(RMSNorm, RoPE attention, SwiGLU MLP) in the served dtype, on the
device, in one jitted call; the plain reference (``reference.py``)
reads these.  ``for_program`` lays the same numbers out as the
program's ``models.model.init_params`` tree (fused gate|up, d_ff and
vocab padded with zeros per its padding plan), checked leaf for leaf
against that function's abstract output so a change of the program's
layout fails here loudly.  Norm weights are stored as their deviation
from one, which is what the program's RMSNorm multiplies by ``1 +``;
the reference uses ``1 + deviation`` in float32, the same numbers.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

NORM_STD = 0.1      # spread of the norm weights around one
EMBED_STD = 0.02


def shapes(model: Dict) -> Dict:
    d, L = model["hidden_size"], model["num_hidden_layers"]
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    dh, ff, V = model["head_dim"], model["intermediate_size"], \
        model["vocab_size"]
    out = {
        "embed": (V, d),
        "ln_attn": (L, d), "wq": (L, d, H * dh), "wk": (L, d, KV * dh),
        "wv": (L, d, KV * dh), "wo": (L, H * dh, d),
        "ln_mlp": (L, d), "w_gate": (L, d, ff), "w_up": (L, d, ff),
        "w_down": (L, ff, d), "ln_final": (d,),
    }
    if not model["tie_word_embeddings"]:
        out["lm_head"] = (d, V)
    return out


def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _canonical_fn(key, model_items):
    model = dict(model_items)
    dt = jnp.dtype(model["torch_dtype"])
    shp = shapes(model)
    L = model["num_hidden_layers"]
    per_layer = [k for k, s in shp.items() if len(s) >= 2 and s[0] == L
                 and k not in ("embed", "lm_head")]

    def layer(k):
        ks = jax.random.split(k, len(per_layer))
        out = {}
        for kk, name in zip(ks, per_layer):
            s = shp[name][1:]
            std = NORM_STD if name.startswith("ln_") else 1 / math.sqrt(s[0])
            out[name] = _draw(kk, s, std, dt)
        return out

    k_embed, k_head, k_final, k_layers = jax.random.split(key, 4)
    out = jax.lax.map(layer, jax.random.split(k_layers, L))
    out["embed"] = _draw(k_embed, shp["embed"], EMBED_STD, dt)
    out["ln_final"] = _draw(k_final, shp["ln_final"], NORM_STD, dt)
    if "lm_head" in shp:
        out["lm_head"] = _draw(k_head, shp["lm_head"],
                               1 / math.sqrt(shp["lm_head"][0]), dt)
    return out


_canonical = jax.jit(_canonical_fn, static_argnames=("model_items",))


def _key(seed: int):
    # seeds run past 32 bits: fold the high and low words in turn
    return jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(0), seed >> 32), seed & 0xFFFFFFFF)


def _items(model: Dict):
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "tie_word_embeddings", "torch_dtype")
    return tuple((k, model[k]) for k in keys)


def canonical(seed: int, model: Dict, device=None) -> Dict:
    """The seed's weights in the published layout."""
    key = _key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return _canonical(key, _items(model))


def _pad(x, axis: int, size: int):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad)


def _layout(c: Dict, ffp: int, vp: int, tied: bool) -> Dict:
    blocks = {
        "ln1": c["ln_attn"], "ln2": c["ln_mlp"],
        "attn": {"wq": c["wq"], "wk": c["wk"], "wv": c["wv"],
                 "wo": c["wo"]},
        "mlp": {"wi": jnp.concatenate([_pad(c["w_gate"], 2, ffp),
                                       _pad(c["w_up"], 2, ffp)], axis=2),
                "wo": _pad(c["w_down"], 1, ffp)},
    }
    out = {"embed": _pad(c["embed"], 0, vp), "blocks": [blocks], "rem": [],
           "final_ln": c["ln_final"]}
    if not tied:
        out["lm_head"] = _pad(c["lm_head"], 1, vp)
    return out


@partial(jax.jit, static_argnames=("model_items", "ffp", "vp"))
def _program(key, model_items, ffp, vp):
    c = _canonical_fn(key, model_items)
    return _layout(c, ffp, vp, dict(model_items)["tie_word_embeddings"])


def for_program(seed: int, model: Dict, cfg, plan, device=None) -> Dict:
    """The seed's weights as the program's parameter tree, drawn and
    laid out in one compiled call (no second copy on the device)."""
    from repro.models import model as M

    if (plan.q_heads_padded != model["num_attention_heads"]
            or plan.kv_slots != model["num_key_value_heads"]):
        raise ValueError(
            f"{model['name']}: the padding plan pads or replicates heads "
            f"({plan.q_heads_padded} q / {plan.kv_slots} kv slots); "
            "for_program places unpadded heads only")
    key = _key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    want = jax.eval_shape(lambda k: M.init_params(k, cfg, plan),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: _layout(
        _canonical_fn(k, _items(model)), plan.d_ff_padded,
        plan.vocab_padded, model["tie_word_embeddings"]), key)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter tree changed: "
                         f"{jax.tree.map(lambda a: a.shape, want)}")
    return _program(key, _items(model), plan.d_ff_padded, plan.vocab_padded)


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


def fingerprint_program(p: Dict, model: Dict) -> Dict[str, tuple]:
    """The same sums, read back out of the program's tree (padding
    sliced off), so a placement fault shows as a mismatch."""
    ff = model["intermediate_size"]
    b = p["blocks"][0]
    ffp = b["mlp"]["wi"].shape[-1] // 2
    # padding is zeros, so the padded matrices' sums are the published
    # ones; gate and up are told apart by slicing inside the reduction
    halves = jax.jit(lambda w: (_sums_fn(w[..., :ff]),
                                _sums_fn(w[..., ffp:ffp + ff])))
    gate, up = halves(b["mlp"]["wi"])
    out = fingerprint({"embed": p["embed"], "ln_attn": b["ln1"],
                       "ln_mlp": b["ln2"], "wq": b["attn"]["wq"],
                       "wk": b["attn"]["wk"], "wv": b["attn"]["wv"],
                       "wo": b["attn"]["wo"], "w_down": b["mlp"]["wo"],
                       "ln_final": p["final_ln"],
                       **({"lm_head": p["lm_head"]} if "lm_head" in p
                          else {})})
    out["w_gate"] = tuple(float(s) for s in gate)
    out["w_up"] = tuple(float(s) for s in up)
    return out
