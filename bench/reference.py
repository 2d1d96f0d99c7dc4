"""Plain forward pass of the served decoders, as their published
``config.json`` describes them (Qwen3ForCausalLM, Qwen2ForCausalLM).

It imports nothing of the program.  The weights are the benchmark's own
(``weights.py``), bf16 as served, and everything is computed in float32 at
``Precision.HIGHEST``, one layer at a time inside a scan, with no cache:
RMSNorm, q/k/v projections (with bias for Qwen2, per-head RMSNorm of q and
k for Qwen3), rotary embedding on the two halves of each head, causal
grouped-query softmax attention, the output projection, a SwiGLU MLP, the
final RMSNorm and the vocabulary head.  Norm scales are ``1 + g``, the
layout the weights are stored in.

``fp8=True`` is the control: every projection's inputs are rounded to
float8 (e4m3, scaled by the absolute maximum per row of activations and per
output channel of weights) before the product.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from spec import ModelSpec

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
E4M3_MAX = 448.0


def _fp8(a, axes):
    """``a`` rounded to e4m3 with one scale per slice over ``axes``."""
    scale = jnp.max(jnp.abs(a), axis=axes, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _proj(spec: str, x, w, x_axes, w_axes, fp8: bool):
    if fp8:
        x, w = _fp8(x, x_axes), _fp8(w, w_axes)
    return jnp.einsum(spec, x, w, precision=HI, preferred_element_type=F32)


def _rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g)


def _rope(x, theta):
    """x: [B, S, H, D], positions 0..S-1."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs    # [S, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m: ModelSpec, fp8: bool, x, p):
    p = jax.tree.map(lambda a: a.astype(F32), p)
    a = p["attn"]
    h = _rms(x, p["ln_attn"], m.norm_eps)
    q = _proj("bsd,dhe->bshe", h, a["wq"], (-1,), (0,), fp8)
    k = _proj("bsd,dhe->bshe", h, a["wk"], (-1,), (0,), fp8)
    v = _proj("bsd,dhe->bshe", h, a["wv"], (-1,), (0,), fp8)
    if m.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    if m.qk_norm:
        q = _rms(q, a["q_norm"], m.norm_eps)
        k = _rms(k, a["k_norm"], m.norm_eps)
    q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
    group = m.heads // m.kv_heads
    k = jnp.repeat(k, group, axis=2)      # query head i reads kv head i // g
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * m.head_dim ** -0.5
    n = x.shape[1]
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    x = x + _proj("bshe,hed->bsd", o, a["wo"], (-2, -1), (0, 1), fp8)
    h = _rms(x, p["ln_ffn"], m.norm_eps)
    f = p["ffn"]
    g = _proj("bsd,df->bsf", h, f["gate"], (-1,), (0,), fp8)
    u = _proj("bsd,df->bsf", h, f["up"], (-1,), (0,), fp8)
    x = x + _proj("bsf,fd->bsd", jax.nn.silu(g) * u, f["down"], (-1,), (0,),
                  fp8)
    return x, None


@partial(jax.jit, static_argnums=(0, 1, 2))
def _logits(m: ModelSpec, fp8: bool, last: int, params, seq):
    x = params["embed"][seq].astype(F32)
    x, _ = jax.lax.scan(partial(_layer, m, fp8), x, params["blocks"])
    x = _rms(x[:, -last:], params["ln_f"].astype(F32), m.norm_eps)
    head = (params["embed"].T if m.tied else params["head"]).astype(F32)
    return _proj("bsd,dv->bsv", x, head, (-1,), (0,), fp8)


def logits(m: ModelSpec, params: dict, prompts, served, fp8: bool = False):
    """Logits that predict each served token, teacher-forced on the served
    tokens: ``[Q, G, V]`` float32 for prompts ``[Q, P]`` and served tokens
    ``[Q, G]``."""
    seq = jnp.concatenate([prompts, served[:, :-1]], axis=1)
    shape_only = dataclasses.replace(m, alias="", seed_offset=0)
    return _logits(shape_only, fp8, served.shape[1], params, seq)


def gaps(ref, tokens):
    """How far each token's reference logit lies below the reference's
    best at its position: ``[Q, G]``."""
    picked = jnp.take_along_axis(ref, tokens[..., None], axis=-1)[..., 0]
    return jnp.max(ref, axis=-1) - picked
