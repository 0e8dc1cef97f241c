"""Operations and bytes that a step or a kernel call needs, computed from
shapes at the published widths (padding the program adds is work it
chose, not work the model needs, so it is not counted).

A multiply-add counts as two operations.  Attention at query position
``p`` attends ``p + 1`` keys (causal).  Bytes are bf16 (2 bytes) unless
the configuration serves another dtype.
"""
from __future__ import annotations

from typing import Dict, Iterable

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


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
    L = m["num_hidden_layers"]
    return 2 * L * layer_params(m)


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


def least_time(flops: float, nbytes: float, peak: Dict) -> float:
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


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
