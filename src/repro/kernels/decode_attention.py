"""Pallas TPU decode attention: one token's attention over one layer of a
stacked KV cache, read in place.

The cache is the stack a decoder keeps for all its layers, ``base`` =
(k, v), each [L, B, S, KV, D], with an optional ``tail`` = (k, v), each
[L, B, T, KV, D], that holds positions ``start .. start + T - 1`` (base
then holds the rows before ``start``; its rows at or above ``start`` are
not read). The layer index and the cache length reach the kernel by
scalar prefetch, so the BlockSpecs pick the layer's rows out of the
stack by DMA: nothing is sliced, transposed or copied outside the kernel.

Grid (B, n): a batch row, then its rows in blocks of at most ``block_s``
positions, sequential; at step j the base's block j and the tail's block
j arrive (a part with fewer blocks holds its last, masked, after it is
done), as [n, KV, D] in the stack's own layout (the base as [KV, n, D]
with ``kv_major``), which the kernel views as [n·KV, D]: each query head
meets every key row on the MXU, and a mask keeps the pairs whose KV head
is the query head's group (and whose position is live). The token's own
row joins at the last step. All parts share one online softmax, with
f32 scores, running max, sum and output in VMEM scratch; each step's
weights are divided by the running sum, then rounded to the cache's
dtype before they meet the values. Where every part fits one block (one
step a row), that is the arithmetic of the jnp reference
(``repro.models.attention.decode_attention_parts``): one softmax over
all parts, each weight divided by the whole sum before it is rounded.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# VMEM for one part's K and V blocks, each held twice while the next
# block streams in
_BLOCK_BYTES = 4 << 20


class _Part(NamedTuple):
    """A (k, v) pair's rows as the grid walks them: positions ``first ..
    end - 1`` in ``blocks`` blocks of ``rows``; ``first`` None for the
    token's own row, live at the last step."""
    first: Optional[int]
    end: int
    rows: int
    blocks: int
    major: bool


def _floor_div(x: jax.Array, n: int) -> jax.Array:
    """``x // n`` for small non-negative int32 ``x``: exact, since
    ``(x + 0.5) / n`` lies at least ``0.5 / n`` from an integer; the TPU's
    vector unit has no integer division."""
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1)
    return ((x.astype(jnp.float32) + 0.5) * (1.0 / n)).astype(jnp.int32)


def _kernel(meta_ref, q_ref, *refs, parts: tuple, steps: int, group: int,
            window: int, scale: float):
    """``refs``: a (k, v) pair of refs for each of ``parts``, the output,
    then the running max, sum and output."""
    *ins, o_ref, m_scr, l_scr, acc_scr = refs
    j = pl.program_id(1)
    cache_len = meta_ref[1]
    q = q_ref[...]                                        # [H, D]
    d = q.shape[1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    scores, values = [], []
    for part, k_ref, v_ref in zip(parts, ins[0::2], ins[1::2]):
        a, c, _ = k_ref.shape
        n, kv = (c, a) if part.major else (a, c)
        k = k_ref[...].reshape(n * kv, d)
        v = v_ref[...].reshape(n * kv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        def split(col):
            """(position in the block, KV head) of flattened row ``col``."""
            if part.major:
                head = _floor_div(col, n)
                return col - head * n, head
            at = _floor_div(col, kv)
            return at, col - at * kv

        at, head = split(jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        ok = head == _floor_div(
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0), group)
        if part.first is None:
            if steps > 1:
                ok &= j == steps - 1                      # the last step's
        else:
            pos = part.first + j * part.rows + at
            ok &= pos < cache_len
            if window:
                ok &= pos > cache_len - window
            if part.rows * part.blocks > part.end - part.first:
                # the last block runs past the part: its rows there may
                # not be numbers, so they meet the values as zeros
                ok &= pos < part.end
                vat, _ = split(jax.lax.broadcasted_iota(jnp.int32,
                                                        v.shape, 0))
                v = jnp.where(part.first + j * part.rows + vat < part.end,
                              v, jnp.zeros_like(v))
            if part.blocks < steps:
                # done before the last step: it holds its last block
                ok &= j < part.blocks
        scores.append(jnp.where(ok, s, NEG_INF))
        values.append(v)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = functools.reduce(jnp.maximum, [m_prev] + [
        s.max(axis=1, keepdims=True) for s in scores])
    alpha = jnp.exp(m_prev - m_new)
    e = [jnp.exp(s - m_new) for s in scores]
    l_new = alpha * l_prev + sum(x.sum(axis=1, keepdims=True) for x in e)
    acc_scr[...] = acc_scr[...] * (alpha * l_prev / l_new) + sum(
        jax.lax.dot((x / l_new).astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
        for x, v in zip(e, values))
    m_scr[...], l_scr[...] = m_new, l_new

    @pl.when(j == steps - 1)
    def _finish():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def decode_attention(q: jax.Array, base: tuple, tail: Optional[tuple],
                     new: Optional[tuple], layer: jax.Array | int,
                     cache_len: jax.Array | int, *, start: int,
                     window: int = 0, kv_major: bool = False,
                     block_s: Optional[int] = None,
                     interpret: bool = False) -> jax.Array:
    """q: [B, 1, H, D]; ``base``, ``tail`` as in the module docstring;
    ``new`` = (k, v), each [B, 1, KV, D], the token's own row, always
    live, or None. Base rows ``0 .. start - 1`` and the tail's rows are
    live below ``cache_len`` (and, with ``window``, above ``cache_len -
    window``). ``kv_major`` reads the base as [KV, S, D], a free view
    where the TPU stores it with S second-minor. ``block_s``: the most
    rows a block holds, a multiple of 8; by default as many as fit
    ``_BLOCK_BYTES``. Returns [B, 1, H, D] in q's dtype."""
    b, _, h, d = q.shape
    kb, vb = base
    kv = kb.shape[3]
    assert h % kv == 0 and 0 < start <= kb.shape[2]
    if block_s is None:
        block_s = max(8, _BLOCK_BYTES // (4 * kv * d * kb.dtype.itemsize)
                      // 8 * 8)
    assert block_s % 8 == 0

    def part(first, end, major=False):
        rows = min(end - first, block_s)
        return _Part(first, end, rows, pl.cdiv(end - first, rows), major)

    def spec(p):
        """Block j of the part's rows of layer ``meta[0]``, batch row i;
        held at its last block once the part is done."""
        def index(i, j, meta):
            at = jnp.minimum(j, p.blocks - 1)
            return (meta[0], i) + ((0, at) if p.major else (at, 0)) + (0,)
        return pl.BlockSpec((None, None) + ((kv, p.rows) if p.major
                                            else (p.rows, kv)) + (d,),
                            index)

    if kv_major:
        kb, vb = jnp.swapaxes(kb, 2, 3), jnp.swapaxes(vb, 2, 3)
    parts = [part(0, start, kv_major)]
    arrays = [kb, vb]
    if tail is not None:
        parts.append(part(start, start + tail[0].shape[2]))
        arrays += tail
    specs = [spec(p) for p in parts for _ in "kv"]
    if new is not None:
        parts.append(_Part(None, 0, 1, 1, False))
        arrays += new
        specs += [pl.BlockSpec((None, 1, kv, d),
                               lambda i, j, meta: (i, 0, 0, 0))] * 2
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(cache_len, jnp.int32)])
    heads = pl.BlockSpec((None, h, d), lambda i, j, meta: (i, 0, 0))
    steps = max(p.blocks for p in parts)
    out = pl.pallas_call(
        functools.partial(_kernel, parts=tuple(parts), steps=steps,
                          group=h // kv, window=window, scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, steps),
            in_specs=[heads] + specs, out_specs=heads,
            scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(meta, q.reshape(b, h, d), *arrays)
    return out.reshape(b, 1, h, d)
