"""Model assembly: stacked-parameter blocks + lax.scan over layers.

Exposes a uniform ``Model`` facade per architecture family with:
  * ``param_defs()``      — ParamDef tree (shapes + PartitionSpecs)
  * ``init(key)``         — concrete params (smoke tests / examples)
  * ``forward(params, batch)``            — logits (train/prefill math)
  * ``train_loss(params, batch)``         — mean xent (+ MoE aux)
  * ``init_cache(batch, max_len)``        — abstract/concrete cache
  * ``prefill(params, tokens, cache)``    — fills cache, returns logits
  * ``decode_step(params, token, cache, pos)`` — one-token step

Layer stacking: per-layer params are stacked on a leading axis and the
layer loop is a ``jax.lax.scan`` (+ ``jax.checkpoint`` for remat), so
the lowered HLO stays compact even for 60-layer models — essential for
the 512-device AOT dry-run on a single CPU host.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (DP, FSDP, TP, ParamDef, abstract_params,
                                 apply_ffn, embed_defs, ffn_defs,
                                 init_params, norm_defs, param_specs,
                                 rms_norm, stack_defs, unembed_logits)

Cache = Any


def _shard(x, *spec):
    """Sharding constraint; resolves the DP placeholder via the active
    axis environment and is a no-op when no mesh env is set (CPU tests)."""
    from repro.models.layers import resolve_spec
    rs = resolve_spec(spec)
    if rs is None:
        return x
    return jax.lax.with_sharding_constraint(x, P(*rs))


def _remat(fn, enabled: bool):
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable) if enabled else fn


# ---------------------------------------------------------------------------
# Dense / MoE / MLA decoder-only LM
# ---------------------------------------------------------------------------


class DecoderLM:
    """GQA or MLA decoder-only LM; optional MoE FFN; optional
    local:global sliding-window interleave (gemma3); optional VLM patch
    embeddings (llava) via ``extra_embeds``."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.n_global, self.n_local = self._layer_split()

    # --- layer pattern -----------------------------------------------------
    def _layer_split(self):
        cfg = self.cfg
        if not cfg.local_global_pattern:
            return cfg.num_layers, 0
        pat = cfg.local_global_pattern
        n_global = cfg.num_layers // pat
        return n_global, cfg.num_layers - n_global

    def layer_kinds(self) -> list[str]:
        """Execution order of layer kinds ('L' local / 'G' global)."""
        cfg = self.cfg
        if not cfg.local_global_pattern:
            return ["G"] * cfg.num_layers
        pat = cfg.local_global_pattern
        out = []
        for i in range(cfg.num_layers):
            out.append("G" if (i + 1) % pat == 0 else "L")
        return out

    # --- params ------------------------------------------------------------
    def _block_defs(self, is_moe_layer: bool) -> dict:
        cfg = self.cfg
        d = {
            "ln_attn": norm_defs(cfg.d_model),
            "ln_ffn": norm_defs(cfg.d_model),
        }
        if cfg.attention == "mla":
            d["attn"] = attn.mla_defs(cfg)
        else:
            d["attn"] = attn.gqa_defs(cfg)
        if is_moe_layer:
            d["moe"] = moe_mod.moe_defs(cfg)
        else:
            d["ffn"] = ffn_defs(cfg.d_model, cfg.d_ff, cfg.dtype)
        return d

    def param_defs(self) -> dict:
        cfg = self.cfg
        defs: dict[str, Any] = {
            "embed": embed_defs(cfg.vocab_size, cfg.d_model, cfg.dtype),
            "ln_f": norm_defs(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            defs["head"] = ParamDef((cfg.d_model, cfg.vocab_size),
                                    (FSDP, TP), cfg.dtype)
        if cfg.moe is not None and cfg.moe_layer_start > 0:
            defs["dense_blocks"] = stack_defs(
                self._block_defs(False), cfg.moe_layer_start)
            defs["blocks"] = stack_defs(
                self._block_defs(True),
                cfg.num_layers - cfg.moe_layer_start)
        elif cfg.local_global_pattern:
            defs["local_blocks"] = stack_defs(
                self._block_defs(cfg.moe is not None), self.n_local)
            defs["global_blocks"] = stack_defs(
                self._block_defs(cfg.moe is not None), self.n_global)
        else:
            defs["blocks"] = stack_defs(
                self._block_defs(cfg.moe is not None), cfg.num_layers)
        return defs

    def init(self, key: jax.Array) -> dict:
        return init_params(self.param_defs(), key)

    def specs(self) -> dict:
        return param_specs(self.param_defs())

    # --- forward -----------------------------------------------------------
    def _block(self, p: dict, cfg, x, positions, *, window: int,
               cache=None, layer=0, cache_len=0):
        """One layer; ``cache`` is the whole stack's, this is its layer
        ``layer``."""
        h = rms_norm(x, p["ln_attn"], cfg.norm_eps)
        if cfg.attention == "mla":
            a, new_cache = attn.mla_attend(p["attn"], cfg, h, positions,
                                           cache=cache, layer=layer,
                                           cache_len=cache_len)
        elif cache is None:
            a, new_cache = attn.gqa_attend(p["attn"], cfg, h, positions,
                                           window=window)
        else:
            a, new_cache = attn.gqa_attend_stacked(
                p["attn"], cfg, h, positions, cache=cache, layer=layer,
                window=window, cache_len=cache_len)
        x = x + a
        h = rms_norm(x, p["ln_ffn"], cfg.norm_eps)
        if "moe" in p:
            f = moe_mod.apply_moe(p["moe"], cfg, h)
        else:
            f = apply_ffn(p["ffn"], h)
        return x + f, new_cache

    def _run_stack(self, stacked: dict, x, positions, *, window: int,
                   caches=None, first=0, cache_len=0, remat=True):
        """The layers of ``stacked`` in a scan. ``caches`` = (base,
        tail) is a stacked cache whose layers ``first, first + 1, ...``
        are these (see ``attention.gqa_attend_stacked``). The part the
        layers write (the tail where there is one, else base) rides in
        the scan's carry, so each layer writes only its new rows into it,
        in place; the other part is only read."""
        cfg = self.cfg

        from repro.models.attention import seq_parallel_degree
        from repro.models.layers import shard_activation
        n_sp = seq_parallel_degree(cfg.num_heads)

        def constrain(xc):
            # sequence-parallel archs keep tokens sharded on the model
            # axis between attention calls (Megatron-SP style): all
            # per-token work then divides by the model axis too.
            # MoE blocks are excluded: their per-sample sort/scatter
            # dispatch contracts along S, and S-sharding there forces
            # per-layer all-gathers (§Perf iteration 3) — attention
            # still sequence-parallelizes internally via the vmap lane.
            if (n_sp > 1 and cfg.moe is None
                    and xc.shape[1] % n_sp == 0 and xc.shape[1] > 1):
                return shard_activation(xc, DP, TP, None)
            return _shard(xc, DP, None, None)

        if caches is None:
            def body_nc(carry, p):
                out, _ = _remat(
                    lambda pp, xx: self._block(pp, cfg, xx, positions,
                                               window=window),
                    remat and cfg.remat)(p, constrain(carry))
                return out, None
            x, _ = jax.lax.scan(body_nc, x, stacked)
            return x, None
        base, tail = caches
        has_tail = tail is not None

        def body(carry, layer):
            xc, written = carry
            p, i = layer
            cache = (base, written) if has_tail else (written, None)
            out, (b, t) = self._block(p, cfg, constrain(xc), positions,
                                      window=window, cache=cache, layer=i,
                                      cache_len=cache_len)
            return (out, t if has_tail else b), None

        layers = first + jnp.arange(jax.tree.leaves(stacked)[0].shape[0],
                                    dtype=jnp.int32)
        (x, written), _ = jax.lax.scan(
            body, (x, tail if has_tail else base), (stacked, layers))
        return x, (base, written) if has_tail else (written, None)

    def _embed_tokens(self, params, tokens, extra_embeds=None):
        cfg = self.cfg
        x = params["embed"][tokens]          # [B, S, d]
        if cfg.tie_embeddings or cfg.name.startswith("gemma"):
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if extra_embeds is not None:
            # VLM: first P positions come from the (stub) vision frontend
            pnum = extra_embeds.shape[1]
            x = jnp.concatenate(
                [extra_embeds.astype(x.dtype), x[:, pnum:]], axis=1)
        return _shard(x, DP, None, None)

    def forward(self, params: dict, tokens: jax.Array,
                extra_embeds: Optional[jax.Array] = None,
                remat: bool = True) -> jax.Array:
        cfg = self.cfg
        x = self._embed_tokens(params, tokens, extra_embeds)
        positions = jnp.arange(tokens.shape[1])[None, :]
        x = self._apply_layers(params, x, positions, remat=remat)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = self._logits(params, x)
        return logits

    def _logits(self, params, x):
        cfg = self.cfg
        w = params["embed"] if cfg.tie_embeddings else params["head"]
        logits = unembed_logits(x, w, cfg.tie_embeddings)
        return _shard(logits, DP, None, TP)

    def _apply_layers(self, params, x, positions, *, caches=None,
                      cache_len=0, remat=True):
        cfg = self.cfg
        if cfg.local_global_pattern:
            return self._apply_interleaved(params, x, positions,
                                           caches=caches,
                                           cache_len=cache_len, remat=remat)
        if "dense_blocks" in params:
            c0 = caches["dense"] if caches else None
            x, nc0 = self._run_stack(params["dense_blocks"], x, positions,
                                     window=0, caches=c0,
                                     cache_len=cache_len, remat=remat)
            c1 = caches["moe"] if caches else None
            x, nc1 = self._run_stack(params["blocks"], x, positions,
                                     window=0, caches=c1,
                                     cache_len=cache_len, remat=remat)
            if caches is not None:
                return x, {"dense": nc0, "moe": nc1}
            return x
        c = caches["blocks"] if caches else None
        x, nc = self._run_stack(params["blocks"], x, positions, window=0,
                                caches=c, cache_len=cache_len, remat=remat)
        if caches is not None:
            return x, {"blocks": nc}
        return x

    def _apply_interleaved(self, params, x, positions, *, caches=None,
                           cache_len=0, remat=True):
        """gemma3 5:1 local:global — grouped execution: repeat
        (pattern-1 locals, 1 global) then trailing locals."""
        cfg = self.cfg
        pat = cfg.local_global_pattern
        n_groups = self.n_global
        loc_per_group = pat - 1
        tail = self.n_local - n_groups * loc_per_group

        def slice_stack(tree, lo, hi):
            return jax.tree.map(lambda a: a[lo:hi], tree)

        c_loc = caches["local"] if caches else None
        c_glob = caches["global"] if caches else None
        li = gi = 0
        for g in range(n_groups):
            lp = slice_stack(params["local_blocks"], li, li + loc_per_group)
            x, c_loc = self._run_stack(lp, x, positions,
                                       window=cfg.sliding_window,
                                       caches=c_loc, first=li,
                                       cache_len=cache_len, remat=remat)
            gp = slice_stack(params["global_blocks"], gi, gi + 1)
            x, c_glob = self._run_stack(gp, x, positions, window=0,
                                        caches=c_glob, first=gi,
                                        cache_len=cache_len, remat=remat)
            li += loc_per_group
            gi += 1
        if tail:
            lp = slice_stack(params["local_blocks"], li, li + tail)
            x, c_loc = self._run_stack(lp, x, positions,
                                       window=cfg.sliding_window,
                                       caches=c_loc, first=li,
                                       cache_len=cache_len, remat=remat)
        if caches is not None:
            return x, {"local": c_loc, "global": c_glob}
        return x

    # --- loss --------------------------------------------------------------
    def train_loss(self, params: dict, batch: dict) -> jax.Array:
        cfg = self.cfg
        logits = self.forward(params, batch["tokens"],
                              batch.get("extra_embeds"))
        loss = softmax_xent(logits, batch["labels"])
        if cfg.moe is not None:
            # aux loss on the mean over MoE layers is folded into the
            # router grads via one representative evaluation (cheap proxy
            # — full per-layer aux is available in training.trainer).
            pass
        return loss

    # --- caches ------------------------------------------------------------
    def _kv_cache_shape(self, batch: int, max_len: int):
        cfg = self.cfg
        if cfg.attention == "mla":
            m = cfg.mla
            return (batch, max_len, m.kv_lora_rank + m.qk_rope_head_dim)
        return (batch, max_len, cfg.num_kv_heads, cfg.resolved_head_dim)

    def cache_defs(self, batch: int, max_len: int) -> dict:
        """CacheLeaf tree: KV (or compressed-latent) cache per stack.

        The sequence axis is sharded on ``model`` — universal across all
        kv-head counts (several archs have kv_heads not divisible by the
        model axis); XLA turns the softmax over the sharded axis into a
        distributed flash-decoding reduction.
        """
        cfg = self.cfg
        shape = self._kv_cache_shape(batch, max_len)

        def kv_leaf(n, length):
            if cfg.attention == "mla":
                s = (n, batch, length, shape[-1])
                return {"c": CacheLeaf(s, cfg.dtype,
                                       (None, DP, "model", None))}
            s = (n, batch, length) + shape[2:]
            return {
                "k": CacheLeaf(s, cfg.dtype, (None, DP, "model", None, None)),
                "v": CacheLeaf(s, cfg.dtype, (None, DP, "model", None, None)),
            }

        if cfg.local_global_pattern:
            win = min(cfg.sliding_window, max_len)
            return {"local": kv_leaf(self.n_local, win),
                    "global": kv_leaf(self.n_global, max_len)}
        if cfg.moe is not None and cfg.moe_layer_start:
            return {"dense": kv_leaf(cfg.moe_layer_start, max_len),
                    "moe": kv_leaf(cfg.num_layers - cfg.moe_layer_start,
                                   max_len)}
        return {"blocks": kv_leaf(cfg.num_layers, max_len)}

    def init_cache(self, batch: int, max_len: int, abstract: bool = False):
        return materialize_cache(self.cache_defs(batch, max_len), abstract)

    # prefill / decode ------------------------------------------------------
    def prefill(self, params: dict, tokens: jax.Array, cache,
                extra_embeds: Optional[jax.Array] = None):
        """Writes the prompt's rows into ``cache`` and returns it with
        fresh tails (:meth:`_with_tails`), any it had dropped."""
        cfg = self.cfg
        x = self._embed_tokens(params, tokens, extra_embeds)
        positions = jnp.arange(tokens.shape[1])[None, :]
        bases = {k: (base, None) for k, (base, _) in
                 self._unwrap(cache).items()}
        x, new_caches = self._apply_layers(
            params, x, positions, caches=bases, cache_len=0, remat=False)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        return (self._logits(params, x[:, -1:]),
                self._wrap(self._with_tails(new_caches, tokens.shape[1])))

    def decode_step(self, params: dict, token: jax.Array, cache,
                    pos: jax.Array):
        """token: [B, 1]; pos: scalar int32 — current cache length.
        Writes the token's row into each stack's tail where it has one
        (after prefill), returning the rest of the cache as given."""
        cfg = self.cfg
        x = params["embed"][token]
        if cfg.tie_embeddings or cfg.name.startswith("gemma"):
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        x = _shard(x, DP, None, None)
        positions = jnp.full((1, 1), pos, jnp.int32)
        x, new_caches = self._apply_layers(
            params, x, positions, caches=self._unwrap(cache),
            cache_len=pos, remat=False)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        return self._logits(params, x), self._wrap(new_caches)

    def _with_tails(self, caches, prompt_len: int):
        """After prefill, each stack's cache gains a tail of zeros for
        the positions after the prompt, which decode writes while it
        only reads base: a step then hands base back as it was given
        (the engine's step passes it through, uncopied). A rolling
        window stack keeps none: decode rewrites it whole."""
        out = {}
        for key, (base, _) in caches.items():
            s = jax.tree.leaves(base)[0].shape[2]
            rolling = key == "local" and s == self.cfg.sliding_window
            tail = None
            if prompt_len < s and not rolling:
                tail = jax.tree.map(
                    lambda a: jnp.zeros(a.shape[:2] + (s - prompt_len,)
                                        + a.shape[3:], a.dtype), base)
            out[key] = (base, tail)
        return out

    # cache trees are stored as dicts {"k", "v"[, "k_tail", "v_tail"]} /
    # {"c"[, "c_tail"]}; the block functions take (base, tail) tuples —
    # translate at the boundary.
    def _unwrap(self, cache):
        names = ("c",) if self.cfg.attention == "mla" else ("k", "v")

        def part(c, suffix):
            if names[0] + suffix not in c:
                return None
            parts = tuple(c[n + suffix] for n in names)
            return parts[0] if len(parts) == 1 else parts

        return {k: (part(c, ""), part(c, "_tail")) for k, c in cache.items()}

    def _wrap(self, caches):
        names = ("c",) if self.cfg.attention == "mla" else ("k", "v")

        def conv(base, tail):
            out = {}
            for suffix, part in (("", base), ("_tail", tail)):
                if part is not None:
                    parts = part if isinstance(part, tuple) else (part,)
                    out.update({n + suffix: a for n, a in zip(names, parts)})
            return out

        return {k: conv(*c) for k, c in caches.items()}

    def decode_pass(self, cache, platform: str) -> str:
        """The attention pass this model's decode takes over ``cache`` (as
        prefill returns it) on a device of ``platform``: "fused" where a
        stack's decode runs the Pallas pass
        (:func:`attention.decode_attention_stacked`), else "parts"."""
        return "fused" if platform == "tpu" and any(
            isinstance(tail, tuple)
            and attn.fused_view(base[0].shape, tail[0].shape[2]) is not None
            for base, tail in self._unwrap(cache).values()) else "parts"


@dataclasses.dataclass(frozen=True)
class CacheLeaf:
    shape: tuple
    dtype: str
    spec: tuple


def materialize_cache(defs, abstract: bool):
    def mk(leaf: CacheLeaf):
        if abstract:
            return jax.ShapeDtypeStruct(leaf.shape, jnp.dtype(leaf.dtype))
        return jnp.zeros(leaf.shape, jnp.dtype(leaf.dtype))
    return jax.tree.map(mk, defs,
                        is_leaf=lambda x: isinstance(x, CacheLeaf))


def cache_specs(defs):
    return jax.tree.map(lambda l: P(*l.spec), defs,
                        is_leaf=lambda x: isinstance(x, CacheLeaf))


def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)
