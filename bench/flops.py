"""Operations and bytes of one prefill or decode call, from its shapes.

Counts are of what the algorithm needs: a multiply-add is 2 operations;
the bytes are the weights the call reads once, the KV cache it reads and
writes, and nothing for activations that a fused step keeps on chip.  The
program's prefill returns logits for the last position only.
"""
from __future__ import annotations

from spec import ModelSpec

BF16 = 2


def layer_matmul_params(m: ModelSpec) -> int:
    """Weights of one layer's projections (q, k, v, o and the SwiGLU
    gate, up and down), the ones every token multiplies."""
    attn = m.d_model * m.head_dim * (2 * m.heads + 2 * m.kv_heads)
    return attn + 3 * m.d_model * m.d_ff


def matmul_flops_per_token(m: ModelSpec) -> int:
    """Projection operations per token over all layers, without the
    attention scores and the vocabulary head."""
    return 2 * m.layers * layer_matmul_params(m)


def head_flops(m: ModelSpec) -> int:
    """Operations of the vocabulary head for one position."""
    return 2 * m.d_model * m.vocab


def attention_flops(m: ModelSpec, queries: int, keys: float) -> float:
    """Scores and weighted values over all layers, for ``queries``
    positions that see ``keys`` positions on average."""
    return 2 * 2 * m.layers * m.heads * m.head_dim * queries * keys


def weight_bytes(m: ModelSpec) -> int:
    """Weights one call reads: every layer's projections and the head
    (the embedding rows it gathers are counted apart)."""
    return BF16 * (m.layers * layer_matmul_params(m) + m.d_model * m.vocab)


def prefill(m: ModelSpec, batch: int, seq: int) -> tuple[float, float]:
    """``(operations, bytes)`` of a prefill of ``batch`` prompts of
    ``seq`` tokens that writes their keys and values."""
    tokens = batch * seq
    ops = (tokens * matmul_flops_per_token(m)
           + attention_flops(m, tokens, (seq + 1) / 2)
           + batch * head_flops(m))
    nbytes = (weight_bytes(m) + tokens * BF16 * m.d_model
              + tokens * m.kv_bytes_per_token)
    return ops, nbytes


def decode(m: ModelSpec, batch: int, pos: int, cache_len: int
           ) -> tuple[float, float]:
    """``(operations, bytes)`` of one decode step of ``batch`` sequences
    at position ``pos`` (``pos`` keys cached before it), over a cache
    ``cache_len`` positions long that the step reads whole."""
    ops = (batch * matmul_flops_per_token(m)
           + attention_flops(m, batch, pos + 1)
           + batch * head_flops(m))
    nbytes = (weight_bytes(m) + batch * BF16 * m.d_model
              + batch * cache_len * m.kv_bytes_per_token
              + batch * m.kv_bytes_per_token)
    return ops, nbytes
