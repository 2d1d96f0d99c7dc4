"""Attention in pure JAX: chunked online-softmax ("flash") prefill paths
and cache-based decode paths.

The chunked implementation keeps the materialized score block bounded at
``[B, H, q_chunk, kv_chunk]`` regardless of sequence length — this is the
XLA-path equivalent of the Pallas flash kernel in ``repro.kernels`` and
is what the models run.  One Pallas kernel is on the model path: on a
TPU, a stacked cache's decode attends in one fused pass per layer
(:func:`decode_attention_stacked`); the jnp parts are its reference and
serve everywhere else.  The kernels are tested against jnp references in
interpret mode on the CPU and AOT-compiled for a described v5e
(``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import \
    decode_attention as fused_decode_attention
from repro.models.layers import (DP, FSDP, TP, ParamDef, apply_rope,
                                 shard_activation)

NEG_INF = -1e30


def _chunk_sizes(s: int, target: int) -> int:
    c = min(s, target)
    while s % c:
        c -= 1
    return c


def seq_parallel_degree(num_heads: int) -> int:
    """Sequence-parallel degree for the XLA attention path: when the
    head count doesn't divide the model axis, attention cannot use the
    model axis via head sharding and GSPMD replicates the whole O(S²)
    computation across it (§Perf iteration 1).  Returns the model-axis
    size to shard the query-chunk dimension over instead, or 1."""
    from repro.models.layers import get_axis_env
    env = get_axis_env()
    if env is None:
        return 1
    mesh = env.get("mesh")
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    tp = mesh.shape["model"]
    return 1 if num_heads % tp == 0 else tp


def flash_attention_sp(q, k, v, *, causal=True, window=0, n_sp=1):
    """Sequence-parallel chunked attention: the outer query-chunk dim is
    a real tensor dim sharded on the model axis (a scan/map dim cannot
    be sharded), with per-lane position offsets for causal masking."""
    b, sq, h, d = q.shape
    if n_sp <= 1 or sq % n_sp or (sq // n_sp) < 1:
        return flash_attention(q, k, v, causal=causal, window=window)
    from repro.models.layers import shard_activation, TP
    qs = q.reshape(b, n_sp, sq // n_sp, h, d)
    qs = shard_activation(qs, DP, TP, None, None, None)
    offs = jnp.arange(n_sp) * (sq // n_sp)

    def lane(qq, off):
        return flash_attention(qq, k, v, causal=causal, window=window,
                               q_offset=off)

    out = jax.vmap(lane, in_axes=(1, 0), out_axes=1)(qs, offs)
    return out.reshape(b, sq, h, d)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    window: int = 0,
                    q_offset: jax.Array | int = 0,
                    q_chunk: int = 512,
                    kv_chunk: int = 512,
                    bias: Optional[jax.Array] = None) -> jax.Array:
    """Chunked online-softmax attention.

    q: [B, Sq, H, D]; k, v: [B, Sk, KV, D] with H % KV == 0 (GQA).
    ``window > 0`` restricts attention to the last ``window`` positions
    (sliding-window / local attention).  ``q_offset`` is the absolute
    position of q[0] relative to k[0] (for chunked prefill with history).
    Returns [B, Sq, H, D].
    """
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    dv = v.shape[-1]
    g = h // kv
    qc = _chunk_sizes(sq, q_chunk)
    kc = _chunk_sizes(sk, kv_chunk)
    nq, nk = sq // qc, sk // kc
    scale = d ** -0.5

    # [B, nq, qc, KV, G, D]
    qr = q.reshape(b, nq, qc, kv, g, d)
    kr = k.reshape(b, nk, kc, kv, d)
    vr = v.reshape(b, nk, kc, kv, dv)

    q_pos = q_offset + jnp.arange(sq).reshape(nq, qc)
    k_pos = jnp.arange(sk).reshape(nk, kc)

    def q_block(args):
        qb, qp = args                        # [B, qc, KV, G, D], [qc]

        def kv_step(carry, inp):
            acc, m, l = carry
            kb, vb, kp = inp                 # [B, kc, KV, D], ..., [kc]
            s = jnp.einsum("bqkgd,bckd->bkgqc", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((qc, kc), dtype=bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window:
                mask &= qp[:, None] - kp[None, :] < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc = acc * alpha[..., None] + pv
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, kv, g, qc, dv), jnp.float32)
        m0 = jnp.full((b, kv, g, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, g, qc), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (kr.swapaxes(0, 1), vr.swapaxes(0, 1), k_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4)         # [B, qc, KV, G, D]

    out = jax.lax.map(q_block, (qr.swapaxes(0, 1), q_pos))
    out = out.swapaxes(0, 1).reshape(b, sq, h, dv)
    return out.astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array | int, *,
                     window: int = 0) -> jax.Array:
    """Single-token attention against a KV cache.

    q: [B, 1, H, D]; k_cache/v_cache: [B, S, KV, D]. ``cache_len`` is the
    number of valid cache positions (query position == cache_len).
    The score tensor [B, H, S] is linear in S — decode never materializes
    an S×S object.  With the cache sharded on S, XLA inserts the max/sum
    all-reduces of a distributed (flash-decoding style) softmax.
    """
    b, _, h, d = q.shape
    _, s, kv, _ = k_cache.shape
    g = h // kv
    qr = q.reshape(b, kv, g, d)
    scores = jnp.einsum("bkgd,bskd->bkgs", qr, k_cache,
                        preferred_element_type=jnp.float32) * d ** -0.5
    pos = jnp.arange(s)
    valid = pos < cache_len
    if window:
        valid &= pos >= cache_len - window
    scores = jnp.where(valid[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def _softmax_parts(scores: list, weigh) -> jax.Array:
    """One softmax over the last axis of the score ``parts`` (f32, masked
    entries at NEG_INF), as if they were one array; returns the sum over
    parts of ``weigh(i, w)``, with ``w`` part i's normalized weights."""
    m = scores[0].max(axis=-1)
    for sc in scores[1:]:
        m = jnp.maximum(m, sc.max(axis=-1))
    e = [jnp.exp(sc - m[..., None]) for sc in scores]
    denom = sum(x.sum(axis=-1) for x in e)
    return sum(weigh(i, x / denom[..., None]) for i, x in enumerate(e))


def decode_attention_parts(q: jax.Array, parts: list) -> jax.Array:
    """Single-token attention over a cache held in ``parts``, each
    ``(k, v, valid)``: k/v [B, n, KV, D], valid [n] bool, as one softmax
    (f32 scores and accumulation, as :func:`decode_attention`). The
    token's own k/v is a part of one always-valid row, so no cache has
    to be written before it is read."""
    b, _, h, d = q.shape
    kv = parts[0][0].shape[2]
    qr = q.reshape(b, kv, h // kv, d)
    scores = [jnp.where(valid[None, None, None],
                        jnp.einsum("bkgd,bskd->bkgs", qr, k,
                                   preferred_element_type=jnp.float32)
                        * d ** -0.5, NEG_INF)
              for k, _, valid in parts]
    out = _softmax_parts(scores, lambda i, w: jnp.einsum(
        "bkgs,bskd->bkgd", w.astype(parts[i][1].dtype), parts[i][1],
        preferred_element_type=jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)


def fused_view(base_shape: tuple, tail_len: int) -> Optional[bool]:
    """Whether decode over a stack of ``base_shape`` [L, B, S, KV, D] with
    a tail of ``tail_len`` rows takes the fused pass
    (:mod:`repro.kernels.decode_attention`) on a TPU: None where it does
    not, else whether the pass reads the stack KV-major.

    The pass reads the stack in the layout the TPU stores it in, which
    tiles the two minor dims by 8 rows: [S, KV, D] as it stands where KV
    is a multiple of 8, else the base as [KV, S, D] where S and the tail
    are (the TPU then puts S second-minor, so that nothing is padded);
    the tail it reads as [T, KV, D] either way, so that decode's row
    write into it fills whole tiles. Elsewhere, with no tail, or with
    heads narrower than the 128 lanes, the jnp parts serve."""
    s, kv, d = base_shape[2:]
    if not tail_len or d % 128:
        return None
    if kv % 8 == 0:
        return False
    if s % 8 == 0 and tail_len % 8 == 0:
        return True
    return None


def decode_attention_stacked(q: jax.Array, base: tuple,
                             tail: Optional[tuple], new: tuple,
                             layer: jax.Array | int,
                             cache_len: jax.Array | int, *, start: int,
                             window: int = 0) -> jax.Array:
    """Single-token attention of layer ``layer`` of a stacked cache
    (``base``, ``tail`` as :func:`gqa_attend_stacked` holds them; base
    rows ``start ..`` are the tail's) plus the token's own ``new`` k/v.
    On a TPU, where :func:`fused_view` allows, one Pallas pass reads the
    layer in place; elsewhere :func:`decode_attention_parts` serves, the
    reference. Positions below ``cache_len`` are live (with ``window``,
    only the last ``window - 1`` of them)."""
    def parts(q, base, tail, new, layer, cache_len):
        def live(pos):
            ok = pos < cache_len
            return ok & (pos > cache_len - window) if window else ok

        s_cache = base[0].shape[2]
        at = jnp.arange(s_cache)
        ps = [(layer_of(base[0], layer), layer_of(base[1], layer),
               live(at) & (at < start))]
        if tail is not None:
            ps.append((layer_of(tail[0], layer), layer_of(tail[1], layer),
                       live(start + jnp.arange(s_cache - start))))
        ps.append((*new, jnp.ones(1, bool)))
        return decode_attention_parts(q, ps)

    view = fused_view(base[0].shape, 0 if tail is None else tail[0].shape[2])
    args = (q, base, tail, new, layer, cache_len)
    if view is None:
        return parts(*args)
    return jax.lax.platform_dependent(
        *args, default=parts, tpu=partial(
            fused_decode_attention, start=start, window=window,
            kv_major=view))


def put_rows(stack: jax.Array, rows: jax.Array, layer: jax.Array | int,
             pos: jax.Array | int) -> jax.Array:
    """``stack`` [L, B, S, ...] with ``rows`` [B, n, ...] written into
    layer ``layer`` at positions ``pos .. pos + n - 1``: an in-place
    update of those rows where the stack is a loop carry."""
    start = (layer, 0, pos) + (0,) * (stack.ndim - 3)
    return jax.lax.dynamic_update_slice(
        stack, rows[None].astype(stack.dtype), tuple(map(_as_idx, start)))


def layer_of(stack: jax.Array, layer: jax.Array | int) -> jax.Array:
    """Layer ``layer`` of ``stack`` [L, ...]."""
    return jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------


def gqa_defs(cfg) -> dict:
    d, h, kv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    dt = cfg.dtype
    defs = {
        "wq": ParamDef((d, h, hd), (FSDP, TP, None), dt),
        "wk": ParamDef((d, kv, hd), (FSDP, TP, None), dt),
        "wv": ParamDef((d, kv, hd), (FSDP, TP, None), dt),
        "wo": ParamDef((h, hd, d), (TP, None, FSDP), dt,
                       fan_in_axes=(0, 1)),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h, hd), (TP, None), dt, init="zeros")
        defs["bk"] = ParamDef((kv, hd), (TP, None), dt, init="zeros")
        defs["bv"] = ParamDef((kv, hd), (TP, None), dt, init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((hd,), (None,), "float32", init="zeros")
        defs["k_norm"] = ParamDef((hd,), (None,), "float32", init="zeros")
    return defs


def _qk_norm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def gqa_project_qkv(p: dict, cfg, x: jax.Array, positions: jax.Array):
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dke->bske", x, p["wk"])
    v = jnp.einsum("bsd,dke->bske", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = _qk_norm(q, p["q_norm"], cfg.norm_eps)
        k = _qk_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    # pin batch (and heads when divisible) sharding: GSPMD otherwise
    # replicates attention for head counts that don't divide the model
    # axis (§Perf iteration 1)
    q = shard_activation(q, DP, None, TP, None)
    k = shard_activation(k, DP, None, TP, None)
    v = shard_activation(v, DP, None, TP, None)
    return q, k, v


def gqa_attend(p: dict, cfg, x: jax.Array, positions: jax.Array, *,
               causal: bool = True, window: int = 0,
               cache: Optional[tuple] = None,
               cache_len: jax.Array | int = 0):
    """Full-sequence (train/prefill) or decode attention.

    Returns (out, new_cache).  cache = (k_cache, v_cache) of one layer,
    static shape [B, S_max, KV, D]; prefill writes positions [0, Sq);
    decode appends at ``cache_len``. (The stacked caches of ``DecoderLM``
    and their sliding windows go through :func:`gqa_attend_stacked`.)
    """
    b, sq, _ = x.shape
    q, k, v = gqa_project_qkv(p, cfg, x, positions)
    new_cache = None
    if cache is not None:
        k_cache, v_cache = cache
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype),
            (0, _as_idx(cache_len), 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype),
            (0, _as_idx(cache_len), 0, 0))
        new_cache = (k_cache, v_cache)
        if sq == 1:   # decode against a full-length cache
            out = decode_attention(q, k_cache, v_cache,
                                   cache_len + 1, window=window)
            return _proj_out(p, out), new_cache
        # prefill attends over freshly computed k/v (cache == prefix here)
    out = flash_attention_sp(q, k, v, causal=causal, window=window,
                             n_sp=seq_parallel_degree(cfg.num_heads))
    return _proj_out(p, out), new_cache


def gqa_attend_stacked(p: dict, cfg, x: jax.Array, positions: jax.Array, *,
                       cache: tuple, layer: jax.Array | int,
                       window: int = 0, cache_len: jax.Array | int = 0):
    """Prefill or decode attention of layer ``layer`` of a stack whose
    cache = (base, tail) is stacked over its layers: base = (k, v), each
    [L, B, S, KV, D]; tail = (k, v), each [L, B, T, KV, D], holding
    positions S - T .. S - 1 (base then holds only the positions before
    them), or None.

    Returns (out, (base, tail)) with only this call's rows written:
    prefill's positions [0, Sq) into base (the last S where Sq is
    longer); decode's one row at ``cache_len`` into the tail, or into
    base where there is none. Decode leaves base as it was given where
    it writes the tail. It attends over the cache as it stood plus its
    own k/v (:func:`decode_attention_stacked`). A rolling window base
    (S == ``window``, no tail) shifts and rewrites its whole layer.
    """
    sq = x.shape[1]
    q, k, v = gqa_project_qkv(p, cfg, x, positions)
    (k_base, v_base), tail = cache
    k_new, v_new = k.astype(k_base.dtype), v.astype(v_base.dtype)
    s_cache = k_base.shape[2]
    if sq == 1 and window and s_cache == window:
        # rolling window cache: shift left, append at the end; valid
        # entries are the last min(pos+1, W) slots.
        k_l = jnp.concatenate([layer_of(k_base, layer)[:, 1:], k_new],
                              axis=1)
        v_l = jnp.concatenate([layer_of(v_base, layer)[:, 1:], v_new],
                              axis=1)
        eff = jnp.minimum(_as_idx(cache_len) + 1, window)
        out = _windowed_decode(q, k_l, v_l, eff)
        return _proj_out(p, out), ((put_rows(k_base, k_l, layer, 0),
                                    put_rows(v_base, v_l, layer, 0)), None)
    if sq == 1:
        start = s_cache - (0 if tail is None else tail[0].shape[2])
        out = _proj_out(p, decode_attention_stacked(
            q, (k_base, v_base), tail, (k_new, v_new), layer, cache_len,
            start=start, window=window))
        if tail is None:
            return out, ((put_rows(k_base, k_new, layer, cache_len),
                          put_rows(v_base, v_new, layer, cache_len)), None)
        return out, ((k_base, v_base), (
            put_rows(tail[0], k_new, layer, cache_len - start),
            put_rows(tail[1], v_new, layer, cache_len - start)))
    at = cache_len
    if s_cache < sq:
        # prefill longer than the (windowed) cache: keep its last rows
        k_new, v_new, at = k_new[:, -s_cache:], v_new[:, -s_cache:], 0
    base = (put_rows(k_base, k_new, layer, at),
            put_rows(v_base, v_new, layer, at))
    # prefill attends over freshly computed k/v (cache == prefix here)
    out = flash_attention_sp(q, k, v, causal=True, window=window,
                             n_sp=seq_parallel_degree(cfg.num_heads))
    return _proj_out(p, out), (base, tail)


def _windowed_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     eff: jax.Array) -> jax.Array:
    """Decode over a rolling window cache whose last ``eff`` slots are
    valid (newest entry at the end)."""
    b, _, h, d = q.shape
    _, w, kv, _ = k_cache.shape
    g = h // kv
    qr = q.reshape(b, kv, g, d)
    scores = jnp.einsum("bkgd,bskd->bkgs", qr, k_cache,
                        preferred_element_type=jnp.float32) * d ** -0.5
    valid = jnp.arange(w) >= w - eff
    scores = jnp.where(valid[None, None, None], scores, NEG_INF)
    pr = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", pr.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def _proj_out(p: dict, out: jax.Array) -> jax.Array:
    return jnp.einsum("bshe,hed->bsd", out, p["wo"])


def _as_idx(x):
    return x if isinstance(x, jax.Array) else jnp.int32(x)


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------


def mla_defs(cfg) -> dict:
    m, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    dt = cfg.dtype
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    defs = {
        "w_dkv": ParamDef((d, m.kv_lora_rank + m.qk_rope_head_dim),
                          (FSDP, None), dt),
        "w_uk": ParamDef((m.kv_lora_rank, h, m.qk_nope_head_dim),
                         (None, TP, None), dt, fan_in_axes=(0,)),
        "w_uv": ParamDef((m.kv_lora_rank, h, m.v_head_dim),
                         (None, TP, None), dt, fan_in_axes=(0,)),
        "wo": ParamDef((h, m.v_head_dim, d), (TP, None, FSDP), dt,
                       fan_in_axes=(0, 1)),
        "kv_norm": ParamDef((m.kv_lora_rank,), (None,), "float32",
                            init="zeros"),
    }
    if m.q_lora_rank:
        defs["w_dq"] = ParamDef((d, m.q_lora_rank), (FSDP, None), dt)
        defs["w_uq"] = ParamDef((m.q_lora_rank, h, qd), (None, TP, None), dt,
                                fan_in_axes=(0,))
        defs["q_norm"] = ParamDef((m.q_lora_rank,), (None,), "float32",
                                  init="zeros")
    else:
        defs["wq"] = ParamDef((d, h, qd), (FSDP, TP, None), dt)
    return defs


def _mla_queries(p: dict, cfg, x: jax.Array, positions: jax.Array):
    from repro.models.layers import rms_norm
    m = cfg.mla
    if m.q_lora_rank:
        cq = jnp.einsum("bsd,dr->bsr", x, p["w_dq"])
        cq = rms_norm(cq, p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhe->bshe", cq, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions,
                        cfg.rope_theta)
    return q_nope, q_rope


def mla_attend(p: dict, cfg, x: jax.Array, positions: jax.Array, *,
               cache: Optional[tuple] = None,
               layer: jax.Array | int = 0,
               cache_len: jax.Array | int = 0):
    """MLA with a compressed-KV cache = (base, tail) stacked over the
    layers of a stack, this being layer ``layer``: base [L, B, S,
    kv_lora + rope_dim], tail [L, B, T, ...] holding positions S - T ..
    S - 1, or None; rows are written as :func:`gqa_attend_stacked`
    writes them.

    Decode uses the absorbed-matmul formulation: queries are projected
    into the latent space, so per-step work is O(S * kv_lora) and the
    cache stays compressed (the paper-exact memory saving of MLA). It
    reads the cache as it stood, with the token's own latent as one more
    score column of the softmax.
    """
    from repro.models.layers import rms_norm
    m = cfg.mla
    b, sq, _ = x.shape
    ckv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    c, k_rope = ckv[..., : m.kv_lora_rank], ckv[..., m.kv_lora_rank:]
    c = rms_norm(c, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]
    q_nope, q_rope = _mla_queries(p, cfg, x, positions)

    new_cache = None
    if cache is not None:
        base, tail = cache
        packed = jnp.concatenate([c, k_rope], axis=-1).astype(base.dtype)
        c, k_rope = (packed[..., : m.kv_lora_rank],
                     packed[..., m.kv_lora_rank:])
        if sq > 1:
            new_cache = (put_rows(base, packed, layer, cache_len), tail)
        else:   # absorbed decode
            s_cache = base.shape[2]
            start = s_cache - (0 if tail is None else tail.shape[2])
            at = jnp.arange(s_cache)
            parts = [(layer_of(base, layer),
                      (at < cache_len) & (at < start))]
            if tail is not None:
                parts.append((layer_of(tail, layer),
                              start + jnp.arange(s_cache - start)
                              < cache_len))
            parts.append((packed, jnp.ones(1, bool)))
            qa = jnp.einsum("bshe,rhe->bshr", q_nope, p["w_uk"])  # latent q
            scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
            r = m.kv_lora_rank
            scores = [jnp.where(
                valid[None, None, None],
                (jnp.einsum("bshr,btr->bhst", qa, part[..., :r])
                 + jnp.einsum("bshe,bte->bhst", q_rope, part[..., r:])
                 ).astype(jnp.float32) * scale, NEG_INF)
                for part, valid in parts]
            lat = _softmax_parts(scores, lambda i, w: jnp.einsum(
                "bhst,btr->bshr", w.astype(base.dtype), parts[i][0][..., :r]
            ).astype(jnp.float32)).astype(base.dtype)
            out = jnp.einsum("bshr,rhe->bshe", lat, p["w_uv"])
            if tail is None:
                new_cache = (put_rows(base, packed, layer, cache_len), None)
            else:
                new_cache = (base, put_rows(tail, packed, layer,
                                            cache_len - start))
            return jnp.einsum("bshe,hed->bsd", out, p["wo"]), new_cache

    # train / prefill: expand k, v per position (flash path)
    k_nope = jnp.einsum("bsr,rhe->bshe", c, p["w_uk"])
    v = jnp.einsum("bsr,rhe->bshe", c, p["w_uv"])
    h = cfg.num_heads
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :],
                                k_rope.shape[:2] + (h, m.qk_rope_head_dim))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    out = flash_attention(q_full, k_full, v, causal=True)
    return jnp.einsum("bshe,hed->bsd", out, p["wo"]), new_cache
