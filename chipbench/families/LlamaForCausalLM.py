"""The llama-style decoder: RMSNorm, RoPE attention with grouped kv
heads, a SwiGLU MLP, the same in every layer; the head tied to the
embedding or not.

Reference equations (float32, from the published description):

    x_0 = E[t]
    h   = RMSNorm(x) * g              RMSNorm(x) = x / sqrt(mean(x^2) + eps)
    q, k, v = h Wq, h Wk, h Wv        per head, RoPE on q and k:
        rot(x)_i = x_i cos(p w_i) - x_{i+D/2} sin(p w_i)       (i < D/2)
        rot(x)_i = x_i cos(p w_j) + x_{i-D/2} sin(p w_j)       (j = i-D/2)
        w_i = theta^(-2i/D)
    a   = softmax(q k^T / sqrt(D) + causal mask) v,   kv heads shared by
          num_attention_heads / num_key_value_heads query heads
    x   = x + a Wo
    x   = x + (silu(h' Wgate) * (h' Wup)) Wdown,     h' = RMSNorm(x) * g'
    logits = RMSNorm(x) * g_final  E^T   (tied)  or  ... W_head (untied)

Weights: ``canonical`` draws every matrix in the served dtype, on the
device, in one jitted call; ``for_program`` lays the same numbers out
as the program's ``models.model.init_params`` tree (fused gate|up, d_ff
and vocab padded with zeros per its padding plan), checked leaf for
leaf against that function's abstract output so a change of the
program's layout fails here loudly.  Norm weights are stored as their
deviation from one, which is what the program's RMSNorm multiplies by
``1 +``; the reference uses ``1 + deviation`` in float32, the same
numbers.

Counts: at the published widths (padding the program adds is work it
chose, not work the model needs).  A multiply-add counts as two
operations; attention at query position ``p`` attends ``p + 1`` keys.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Iterable

import jax
import jax.numpy as jnp

from chipbench import reference as R
from chipbench.flops import DTYPE_BYTES
from chipbench.weights import (EMBED_STD, NORM_STD, _draw, _key, _pad,
                               _sums_fn, fingerprint)

__all__ = ["program_config", "tiny", "canonical", "for_program",
           "fingerprint_program", "logits", "params", "weight_bytes",
           "layer_params", "kv_bytes_per_token", "prefill_flops",
           "decode_flops", "decode_step_bytes", "chunk_kernel"]

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def program_config(model: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=model["name"], arch_type="dense",
        num_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        head_dim=model["head_dim"], activation="swiglu",
        tie_embeddings=model["tie_word_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype=model["torch_dtype"])


def tiny(model: Dict) -> Dict:
    """The configuration at a size the CPU serves in seconds (its
    norms, RoPE, tying and dtype kept)."""
    return dict(model, num_hidden_layers=2, hidden_size=128,
                num_attention_heads=4, num_key_value_heads=4, head_dim=32,
                intermediate_size=320, vocab_size=509)


# ---------------------------------------------------------------- weights

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


def fingerprint_program(p: Dict, model: Dict) -> Dict[str, tuple]:
    """``weights.fingerprint``'s sums, read back out of the program's
    tree (padding sliced off), so a placement fault shows as a
    mismatch."""
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


# -------------------------------------------------------------- reference

def _layer(x, w, pos, cfg, quant):
    H, KV, D, eps, theta = cfg
    T = x.shape[0]
    h = R.rmsnorm(x, w["ln_attn"], eps)
    q = R.rope(R._mm(h, w["wq"], quant).reshape(T, H, D), pos, theta)
    k = R.rope(R._mm(h, w["wk"], quant).reshape(T, KV, D), pos, theta)
    v = R._mm(h, w["wv"], quant).reshape(T, KV, D)
    a = R._attention(q, k, v, H, KV).reshape(T, H * D)
    x = x + R._mm(a, w["wo"], quant)
    h = R.rmsnorm(x, w["ln_mlp"], eps)
    g = jax.nn.silu(R._mm(h, w["w_gate"], quant)) * R._mm(h, w["w_up"],
                                                          quant)
    return x + R._mm(g, w["w_down"], quant)


@partial(jax.jit, static_argnames=("cfg", "quant", "tied"))
def _forward(weights, tokens, rows, cfg, quant, tied):
    x = R.embed(weights["embed"], tokens, quant)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    stacked = {k: weights[k] for k in MATRICES + ("ln_attn", "ln_mlp")}
    x, _ = jax.lax.scan(lambda x, w: (_layer(x, w, pos, cfg, quant), None),
                        x, stacked)
    head = weights["embed"].T if tied else weights["lm_head"]
    return R._mm(R.rmsnorm(x[rows], weights["ln_final"], cfg[3]), head,
                 quant)


def logits(weights: Dict, model: Dict, tokens, rows, quant: bool = False,
           shape=(0, 0)):
    """float32 next-token logits at positions ``rows`` of ``tokens``
    (``reference.run`` pads them to ``shape``); ``quant`` gives the fp8
    control."""
    cfg = (model["num_attention_heads"], model["num_key_value_heads"],
           model["head_dim"], float(model["rms_norm_eps"]),
           float(model["rope_theta"]))
    tied = bool(model["tie_word_embeddings"])
    return R.run(lambda t, r: _forward(weights, t, r, cfg, quant, tied),
                 tokens, rows, shape)


# ----------------------------------------------------------------- counts

def _dims(m: Dict):
    return (m["num_hidden_layers"], m["hidden_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["intermediate_size"], m["vocab_size"])


def layer_params(m: Dict) -> int:
    L, d, H, KV, D, ff, V = _dims(m)
    return d * (H + 2 * KV) * D + H * D * d + 3 * d * ff


def params(m: Dict) -> int:
    """All parameters, the embedding (and an untied head) included."""
    L, d, H, KV, D, ff, V = _dims(m)
    emb = V * d * (1 if m["tie_word_embeddings"] else 2)
    return L * (layer_params(m) + 2 * d) + emb + d


def weight_bytes(m: Dict) -> int:
    """Weights one forward step reads: every layer, and the head (the
    embedding lookup reads a handful of rows, not counted)."""
    L, d, H, KV, D, ff, V = _dims(m)
    b = DTYPE_BYTES[m["torch_dtype"]]
    return b * (L * (layer_params(m) + 2 * d) + V * d + d)


def kv_bytes_per_token(m: Dict) -> int:
    L, d, H, KV, D, ff, V = _dims(m)
    return 2 * L * KV * D * DTYPE_BYTES[m["torch_dtype"]]


def matmul_flops_per_token(m: Dict) -> int:
    """Layer matmuls of one token (no attention scores, no head)."""
    return 2 * m["num_hidden_layers"] * layer_params(m)


def head_flops(m: Dict) -> int:
    return 2 * m["hidden_size"] * m["vocab_size"]


def attention_flops(m: Dict, start: int, n: int) -> int:
    """Scores and weighted values of ``n`` queries at positions
    ``start .. start+n-1``, every layer: 4 * H * D per (query, key)."""
    L, d, H, KV, D, ff, V = _dims(m)
    keys = n * start + n * (n + 1) // 2
    return 4 * L * H * D * keys


def prefill_flops(m: Dict, start: int, n: int) -> int:
    """A prefill chunk of ``n`` tokens after ``start`` cached ones; the
    head runs for its last token only."""
    return (n * matmul_flops_per_token(m) + attention_flops(m, start, n)
            + head_flops(m))


def decode_flops(m: Dict, context: int) -> int:
    """One generated token whose query sits at position ``context - 1``
    (it attends ``context`` keys)."""
    return (matmul_flops_per_token(m) + attention_flops(m, context - 1, 1)
            + head_flops(m))


def decode_step_bytes(m: Dict, contexts: Iterable[int]) -> int:
    """Least bytes of one batched decode step: the weights once and the
    keys and values of each sequence's live context (not its
    reservation)."""
    return weight_bytes(m) + kv_bytes_per_token(m) * sum(contexts)


def chunk_kernel(m: Dict, start: int, n: int) -> Dict[str, int]:
    """The fused chunk-prefill attention kernel over all layers for one
    chunk: ``n`` queries after ``start`` cached positions.  Bytes: the
    queries and outputs once, the cached prefix's keys and values read,
    and the chunk's keys and values read and written to the pool."""
    L, d, H, KV, D, ff, V = _dims(m)
    b = DTYPE_BYTES[m["torch_dtype"]]
    q_out = 2 * n * H * D * b
    kv_tok = 2 * KV * D * b
    return {"flops": attention_flops(m, start, n),
            "bytes": L * (q_out + start * kv_tok + 2 * n * kv_tok)}

