"""Per-architecture smoke tests: reduced same-family configs run one
forward/train step on CPU, asserting output shapes and finiteness, plus
prefill+decode consistency against the full forward."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.archs import ARCHS, SMOKE
from repro.models.families import build_model
from repro.serving.engine import jit_steps

ARCH_IDS = list(SMOKE.keys())


def _batch(cfg, key, b=2, s=16):
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.family == "audio":
        batch["extra_embeds"] = jax.random.normal(
            key, (b, cfg.encoder_frames, cfg.d_model))
    elif cfg.family == "vlm":
        batch["extra_embeds"] = jax.random.normal(
            key, (b, cfg.num_patches, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_grad(arch):
    cfg = SMOKE[arch]
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    batch = _batch(cfg, key)
    logits = model.forward(params, batch["tokens"],
                           batch.get("extra_embeds"))
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    loss, grads = jax.value_and_grad(
        lambda p: model.train_loss(p, batch))(params)
    assert bool(jnp.isfinite(loss))
    gnorm = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads))
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_consistency(arch):
    cfg = SMOKE[arch]
    model = build_model(cfg)
    key = jax.random.PRNGKey(1)
    params = model.init(key)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 17), 0,
                              cfg.vocab_size)
    ee = None
    if cfg.family == "audio":
        ee = jax.random.normal(key, (2, cfg.encoder_frames, cfg.d_model))
    elif cfg.family == "vlm":
        ee = jax.random.normal(key, (2, cfg.num_patches, cfg.d_model))
    full = model.forward(params, toks, ee)
    cache = model.init_cache(2, 32)
    lg, cache = model.prefill(params, toks[:, :16], cache, ee)
    lg2, _ = model.decode_step(params, toks[:, 16:17], cache,
                               jnp.int32(16))
    a = jax.nn.softmax(full[:, 15].astype(jnp.float32))
    b = jax.nn.softmax(lg[:, 0].astype(jnp.float32))
    assert float(jnp.max(jnp.abs(a - b))) < 0.03
    a2 = jax.nn.softmax(full[:, 16].astype(jnp.float32))
    b2 = jax.nn.softmax(lg2[:, 0].astype(jnp.float32))
    assert float(jnp.max(jnp.abs(a2 - b2))) < 0.05


def test_full_configs_param_counts():
    """Full configs match published sizes (±10%)."""
    expected = {
        "glm4-9b": 9.4e9, "qwen1.5-4b": 3.95e9, "gemma3-4b": 3.9e9,
        "qwen3-1.7b": 1.7e9, "deepseek-v2-236b": 240e9,
        "zamba2-2.7b": 2.5e9, "rwkv6-3b": 3.2e9,
        "llava-next-mistral-7b": 7.2e9, "whisper-small": 0.32e9,
    }
    for name, exp in expected.items():
        got = ARCHS[name].param_count()
        assert abs(got - exp) / exp < 0.12, (name, got, exp)


def test_moe_active_params_below_total():
    for name in ("granite-moe-3b-a800m", "deepseek-v2-236b"):
        cfg = ARCHS[name]
        assert cfg.active_param_count() < 0.35 * cfg.param_count()


@pytest.mark.parametrize("arch", ["qwen1.5-4b", "granite-moe-3b-a800m",
                                  "deepseek-v2-236b", "gemma3-4b"])
def test_multistep_decode_through_engine_steps(arch):
    """Prefill 16 tokens, then 8 decode steps through the engine's jitted
    steps, rebinding the cache each step: each step's distribution
    matches the full forward at its position, and the cache ends as a
    prefill of all 24 tokens leaves it. Prefill consumes the cache it is
    given; decode consumes nothing and hands the prompt's cache back as
    the same arrays. Covers a dense GQA stack, an MoE stack, MLA behind a
    dense/MoE split, and the local/global interleave whose local cache
    rolls; a wrong layer index, position or lost row shows by the later
    steps. Experts get room for every token: the forward over 24 tokens
    would otherwise drop tokens past an expert's capacity, which
    one-token decode never does."""
    cfg = SMOKE[arch]
    if cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=cfg.moe.num_experts / cfg.moe.top_k))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0,
                              cfg.vocab_size)
    full = jax.nn.softmax(model.forward(params, toks).astype(jnp.float32))
    prefill, decode = jit_steps(model)
    given = model.init_cache(2, 24)
    lg, cache = prefill(params, toks[:, :16], given)
    assert all(a.is_deleted() for a in jax.tree.leaves(given))
    b = jax.nn.softmax(lg[:, 0].astype(jnp.float32))
    assert float(jnp.max(jnp.abs(full[:, 15] - b))) < 0.03
    for t in range(16, 24):
        given = cache
        lg, cache = decode(params, toks[:, t:t + 1], given, jnp.int32(t))
        assert not any(a.is_deleted() for a in jax.tree.leaves(given))
        for stack, leaves in cache.items():
            if any(name.endswith("_tail") for name in leaves):
                assert all(a is given[stack][name]
                           for name, a in leaves.items()
                           if not name.endswith("_tail")), stack
        b = jax.nn.softmax(lg[:, 0].astype(jnp.float32))
        assert float(jnp.max(jnp.abs(full[:, t] - b))) < 0.05, t
    # every row sits where a prefill of all 24 tokens puts it
    _, want = prefill(params, toks, model.init_cache(2, 24))
    for stack, leaves in want.items():
        for name, ref in leaves.items():
            rows = cache[stack][name].astype(jnp.float32)
            tail = cache[stack].get(name + "_tail")
            if tail is not None:
                n = tail.shape[2]
                rows = rows.at[:, :, -n:].set(tail.astype(jnp.float32))
            ref = ref.astype(jnp.float32)
            assert (float(jnp.max(jnp.abs(rows - ref)))
                    < 0.05 * float(jnp.max(jnp.abs(ref)))), (stack, name)
