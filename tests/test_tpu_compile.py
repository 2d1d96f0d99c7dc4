"""Ahead-of-time compiles for a described TPU v5e, without a chip.

The TPU compiler refuses what interpret mode and the CPU backend accept
(tiling rules, primitives with no Mosaic lowering, programs that do not
fit HBM), so the served step functions at full qwen3-1.7b width and the
Pallas kernels at real widths are compiled here for one v5e chip.
Nothing runs: these tests say nothing about results or times, but the
compiled program's text and memory analysis show how it uses HBM.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.archs import ARCHS
from repro.kernels import ops
from repro.models.families import build_model
from repro.serving.engine import jit_steps


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    """Shapes of ``tree`` placed on ``sharding`` (no arrays exist)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_qwen3_full_width_prefill_and_decode_compile(one_chip):
    """The engine's jitted steps for the full qwen3-1.7b config, at the
    shard shape the chip smoke serves (8 queries, 128-token prompts,
    8 generated tokens)."""
    cfg = ARCHS["qwen3-1.7b"]
    model = build_model(cfg)
    prefill, decode = jit_steps(model)
    batch, prompt_len, gen_len = 8, 128, 8
    params = _on(one_chip, jax.eval_shape(model.init,
                                          jax.random.PRNGKey(0)))
    cache = _on(one_chip, model.init_cache(batch, prompt_len + gen_len,
                                           abstract=True))
    tokens = _spec(one_chip, (batch, prompt_len), jnp.int32)
    compiled = prefill.lower(params, tokens, cache).compile()
    mem = compiled.memory_analysis()
    # 1.72e9 bf16 params fit one 16 GB chip with room for two bundles
    assert mem.argument_size_in_bytes < 4.0e9
    token = _spec(one_chip, (batch, 1), jnp.int32)
    pos = _spec(one_chip, (), jnp.int32)
    decode.lower(params, token, cache, pos).compile()


@pytest.mark.parametrize("sq", [2048, 40])          # full and ragged
def test_flash_attention_compiles(one_chip, sq):
    h, kv, d = 16, 8, 128                            # qwen3-1.7b heads
    q = _spec(one_chip, (1, sq, h, d))
    k = _spec(one_chip, (1, sq, kv, d))
    ops.flash_attention.lower(q, k, k, interpret=False).compile()


@pytest.mark.parametrize("layers,s,h,kv,kv_major", [
    (24, 136, 16, 16, False), (40, 136, 20, 20, True),
    (28, 2048, 16, 8, False)], ids=["24-16-False", "40-20-True", "qwen3-2048"])
def test_decode_attention_compiles(one_chip, layers, s, h, kv, kv_major):
    """The fused decode pass at the benchmark's two widths (Qwen1.5-1.8B,
    16 KV heads, read as stored; Qwen1.5-4B, 20, base read KV-major), over
    a stack of 136 rows with a tail of 8, in one block each; and at
    qwen3-1.7b's heads over 2048 rows, the base in four blocks of 512 (the
    last running past its 2040 rows). It reads the layer in place, so
    nothing copies the base. The 4B's tail is read as [T, KV, D], where
    decode's row write fills whole tiles; from its default layout (T
    second-minor) it is relaid out once, as at the decode step's entry."""
    b, t, d = 8, 8, 128
    q = _spec(one_chip, (b, 1, h, d))
    base = _spec(one_chip, (layers, b, s, kv, d))
    tail = _spec(one_chip, (layers, b, t, kv, d))
    new = _spec(one_chip, (b, 1, kv, d))
    n = _spec(one_chip, (), jnp.int32)
    text = ops.decode_attention.lower(
        q, (base, base), (tail, tail), (new, new), n, n, start=s - t,
        kv_major=kv_major, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text
    stacks = {(layers, b, s, kv, d)} | (set() if kv_major
                                        else {(layers, b, t, kv, d)})
    copies = [(n, shape) for instrs in _hlo_computations(text).values()
              for n, shape, op, _ in instrs if op == "copy"]
    assert not [c for c in copies if _dims(c[1]) in stacks], copies


def test_moe_gemm_compiles(one_chip):
    moe = ARCHS["granite-moe-3b-a800m"].moe
    d = ARCHS["granite-moe-3b-a800m"].d_model
    e, c = moe.num_experts, 256
    x = _spec(one_chip, (e, c, d))
    w = _spec(one_chip, (e, d, moe.d_expert))
    ops.moe_gemm.lower(x, w, interpret=False).compile()


def test_rwkv6_scan_compiles(one_chip):
    cfg = ARCHS["rwkv6-3b"]
    b, s, d = 1, 256, cfg.rwkv.head_dim
    h = cfg.d_model // d
    x = _spec(one_chip, (b, s, h, d), jnp.float32)
    bonus = _spec(one_chip, (h, d), jnp.float32)
    ops.rwkv6_scan.lower(x, x, x, x, bonus, chunk=cfg.rwkv.chunk,
                         interpret=False).compile()


def test_mamba2_scan_compiles(one_chip):
    cfg = ARCHS["zamba2-2.7b"]
    ssm = cfg.ssm
    b, s, p, n = 1, 512, ssm.head_dim, ssm.state_dim
    h = ssm.expand * cfg.d_model // p
    xh = _spec(one_chip, (b, s, h, p), jnp.float32)
    bc = _spec(one_chip, (b, s, n), jnp.float32)
    dt = _spec(one_chip, (b, s, h), jnp.float32)
    a_log = _spec(one_chip, (h,), jnp.float32)
    ops.mamba2_scan.lower(xh, bc, bc, dt, a_log, chunk=ssm.chunk,
                          interpret=False).compile()


# Qwen1.5-1.8B, as the benchmark serves it: two bundles of it share a chip
QWEN15_18B = dataclasses.replace(
    ARCHS["qwen1.5-4b"], name="qwen1.5-1.8b", num_layers=24, d_model=2048,
    num_heads=16, num_kv_heads=16, head_dim=128, d_ff=5504,
    rope_theta=1000000.0, norm_eps=1e-6)

_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\w+\[[\d,]*\])\S* ([\w-]+)\((.*)$")


def _hlo_computations(text: str) -> dict[str, list[tuple]]:
    """``{computation: [(name, shape, opcode, operands), ...]}`` of an HLO
    module's text; the entry computation's name starts with ``ENTRY``."""
    out: dict[str, list[tuple]] = {}
    cur = None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%(\S+) \(", line)
        if head:
            cur = (head.group(1) or "") + head.group(2)
            out[cur] = []
        elif cur is not None and (m := _INSTR.match(line)):
            name, shape, op, rest = m.groups()
            out[cur].append((name, shape, op,
                             re.findall(r"%([\w.\-]+)", rest.split(")")[0])))
    return out


def _dims(shape: str) -> tuple[int, ...]:
    return tuple(int(x) for x in shape[shape.index("[") + 1:-1].split(",")
                 if x)


@pytest.mark.parametrize("batch", [8, 4])
def test_decode_writes_its_row_in_place(one_chip, batch):
    """The engine's steps at the benchmark cell's shapes (prompt 128, 8
    generated tokens). Prefill writes the stacked KV cache in place (it
    is donated and aliased to the output). Decode, on the cache prefill
    returns, writes one row per layer into the tail of generated rows,
    in place, and neither copies nor returns the prompt's stacked cache,
    nor rewrites a layer's slice of it."""
    cfg = QWEN15_18B
    model = build_model(cfg)
    prefill, decode = jit_steps(model)
    prompt_len, max_len = 128, 136
    params = _on(one_chip, jax.eval_shape(model.init,
                                          jax.random.PRNGKey(0)))
    cache = _on(one_chip, model.init_cache(batch, max_len, abstract=True))
    tokens = _spec(one_chip, (batch, prompt_len), jnp.int32)
    stack = (cfg.num_layers, batch, max_len, cfg.num_kv_heads,
             cfg.resolved_head_dim)
    layer_bytes = 2 * 2 * math.prod(stack[1:])          # bf16 K and V
    tail_bytes = layer_bytes * cfg.num_layers * (max_len - prompt_len) \
        // max_len
    mem = prefill.lower(params, tokens, cache).compile().memory_analysis()
    assert mem.alias_size_in_bytes == cfg.num_layers * layer_bytes

    served = _on(one_chip, jax.eval_shape(prefill, params, tokens, cache)[1])
    compiled = decode.lower(params, _spec(one_chip, (batch, 1), jnp.int32),
                            served, _spec(one_chip, (), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes < 2 * tail_bytes    # logits and tail
    assert mem.temp_size_in_bytes < layer_bytes
    comps = _hlo_computations(compiled.as_text())
    updates = []
    for instrs in comps.values():
        shapes = {name: shape for name, shape, _, _ in instrs}
        updates += [(_dims(shape), _dims(shapes[operands[1]]))
                    for _, shape, op, operands in instrs
                    if op == "dynamic-update-slice"]
    assert updates
    for result, update in updates:
        assert update[2] == 1, (result, update)
        assert result not in (stack[1:], (1,) + stack[1:]), (result, update)
    entry, = (v for k, v in comps.items() if k.startswith("ENTRY"))
    assert not [n for n, shape, op, _ in entry
                if op == "copy" and _dims(shape) == stack]


@pytest.mark.parametrize("cfg,batch", [(QWEN15_18B, 8), (QWEN15_18B, 4),
                                       (ARCHS["qwen1.5-4b"], 8),
                                       (ARCHS["qwen1.5-4b"], 4)],
                         ids=["1.8b-8", "1.8b-4", "4b-8", "4b-4"])
def test_decode_layer_loop_holds_the_fused_pass(one_chip, cfg, batch):
    """Decode at both benchmark cells' shapes, on the cache prefill
    serves (prompt 128, 8 generated tokens): the layer loop attends in
    one fused pass, and copies no f32 array (the jnp parts relaid their
    f32 scores out 7 times a layer). The pass reads the prompt's stack
    in place (the 4B's KV-major, a free view of its layout): no op of the
    step makes an array of its shape or of one layer's, in either order
    of S and KV."""
    model = build_model(cfg)
    prefill, decode = jit_steps(model)
    params = _on(one_chip, jax.eval_shape(model.init,
                                          jax.random.PRNGKey(0)))
    cache = _on(one_chip, model.init_cache(batch, 136, abstract=True))
    tokens = _spec(one_chip, (batch, 128), jnp.int32)
    served = _on(one_chip, jax.eval_shape(prefill, params, tokens, cache)[1])
    text = decode.lower(params, _spec(one_chip, (batch, 1), jnp.int32),
                        served, _spec(one_chip, (), jnp.int32)
                        ).compile().as_text()
    loops = [instrs for instrs in _hlo_computations(text).values()
             if any(op == "custom-call" and n.startswith("decode_attention")
                    for n, _, op, _ in instrs)]
    assert len(loops) == 1
    (body,) = loops
    assert sum(n.startswith("decode_attention") for n, _, _, _ in body) == 1
    assert not [n for n, shape, op, _ in body
                if op == "copy" and shape.startswith("f32")]
    s, kv, d = 136, cfg.num_kv_heads, cfg.resolved_head_dim
    layer = {(batch, s, kv, d), (batch, kv, s, d)}
    base = layer | {(n,) + x for x in layer for n in (1, cfg.num_layers)}
    # an asynchronous copy moves an array between memory spaces (at batch
    # 4 the compiler prefetches the 1.8B's K or V stack into VMEM, the
    # bytes the pass reads anyway): it keeps the layout
    moves = re.findall(r"= \((\w+\[[\d,]*\]\{[^}]*\}), "
                       r"(\w+\[[\d,]*\]\{[^}]*\}), [^,]*\) copy-start", text)
    assert len(moves) == text.count(" copy-start(")
    assert all(re.sub(r"S\(\d+\)", "", a) == re.sub(r"S\(\d+\)", "", b)
               for a, b in moves), moves
    views = ("parameter", "get-tuple-element", "bitcast", "copy-done")
    made = [(n, shape) for instrs in _hlo_computations(text).values()
            for n, shape, op, _ in instrs
            if op not in views and _dims(shape) in base]
    assert not made, made
