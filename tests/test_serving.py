"""Serving engine end-to-end: real tiny models, FATE-driven placement,
residency switches and prefix-cache behaviour on virtual devices."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.archs import SMOKE
from repro.core.devices import homogeneous_cluster
from repro.core.executor import fresh_state
from repro.core.policies import make_policy
from repro.core.workflow import Stage, Workflow
from repro.serving.engine import ModelBundle, ServingEngine


@pytest.fixture(scope="module")
def bundles():
    cfg_a = SMOKE["qwen3-1.7b"]
    cfg_b = dataclasses.replace(SMOKE["glm4-9b"],
                                vocab_size=cfg_a.vocab_size)
    return {
        "qwen-7b": ModelBundle.create("qwen-7b", cfg_a, seed=0),
        "llama-8b": ModelBundle.create("llama-8b", cfg_b, seed=1),
    }


def _workflow(nq=4):
    stages = {
        "retrieve": Stage("retrieve", "qwen-7b", base_cost={-1: 0.01},
                          prefix_group="ctx", max_shards=2),
        "work_a": Stage("work_a", "llama-8b", base_cost={-1: 0.02},
                        parents=("retrieve",)),
        "work_b": Stage("work_b", "qwen-7b", base_cost={-1: 0.02},
                        prefix_group="ctx", parents=("retrieve",)),
        "merge": Stage("merge", "qwen-7b", base_cost={-1: 0.015},
                       prefix_group="ctx",
                       parents=("work_a", "work_b")),
    }
    return Workflow(wid="serve-test", stages=stages, num_queries=nq)


def test_serving_end_to_end(bundles):
    wf = _workflow()
    engine = ServingEngine(bundles, n_devices=2, gen_len=4,
                           prompt_len=8)
    state = fresh_state(homogeneous_cluster(2))
    prompts = jax.random.randint(jax.random.PRNGKey(0), (4, 8), 0, 256)
    results = engine.run_workflow(wf, make_policy("FATE"), state,
                                  prompts)
    assert set(results) == set(wf.stages)
    for sid, res in results.items():
        assert res.tokens_out.shape == (4, 4)
        assert bool(jnp.all(res.tokens_out >= 0))
    # residency: devices ended up hosting the models used
    hosted = {d.resident for d in engine.devices}
    assert hosted <= {"qwen-7b", "llama-8b", None}


def test_serving_residency_switch_counted(bundles):
    wf = _workflow()
    engine = ServingEngine(bundles, n_devices=1, gen_len=2,
                           prompt_len=8)
    state = fresh_state(homogeneous_cluster(1))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 256)
    engine.run_workflow(wf, make_policy("RoundRobin"), state, prompts)
    # single device + two models => at least 2 switches happened
    switched = sum(1 for r in engine.log if r.switched)
    assert switched >= 2


def test_serving_emits_calibration_observations(bundles):
    wf = _workflow()
    engine = ServingEngine(bundles, n_devices=2, gen_len=4,
                           prompt_len=8)
    state = fresh_state(homogeneous_cluster(2))
    prompts = jax.random.randint(jax.random.PRNGKey(3), (4, 8), 0, 256)
    engine.run_workflow(wf, make_policy("FATE"), state, prompts)
    obs = engine.observations()
    assert len(obs) == len(engine.log) == len(wf.stages)
    for o in obs:
        assert o.queries == 4
        assert o.prompt_tokens == 8 and o.output_tokens == 4
        assert o.wall_s > 0.0
        assert o.family in {"qwen", "llama"}
        assert o.transfer_ktokens == 0.0
    # the single-model prefix chain re-runs on a warm group at least
    # once, so some observation carries a nonzero hit fraction
    assert sum(o.switches for o in obs) >= 1


def test_serving_engine_asserts_profile_consistency(bundles):
    from repro.core.calibration import CalibrationProfile

    profile = CalibrationProfile.hand_set().perturbed(switch_mul=0.5)
    wf = _workflow()
    engine = ServingEngine(bundles, n_devices=2, gen_len=2,
                           prompt_len=8, calibration=profile)
    prompts = jax.random.randint(jax.random.PRNGKey(4), (4, 8), 0, 256)
    # state still carries the hand-set constants -> load-time error
    state = fresh_state(homogeneous_cluster(2))
    with pytest.raises(ValueError, match="calibration mismatch"):
        engine.run_workflow(wf, make_policy("FATE"), state, prompts)
    # loading the SAME profile into the state reconciles them
    state = fresh_state(homogeneous_cluster(2),
                        profiles=profile.model_profiles())
    results = engine.run_workflow(wf, make_policy("FATE"), state,
                                  prompts)
    assert set(results) == set(wf.stages)


def test_serving_deterministic_outputs(bundles):
    wf = _workflow()
    prompts = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0, 256)
    outs = []
    for _ in range(2):
        engine = ServingEngine(bundles, n_devices=2, gen_len=3,
                               prompt_len=8)
        state = fresh_state(homogeneous_cluster(2))
        res = engine.run_workflow(wf, make_policy("FATE"), state,
                                  prompts)
        outs.append({k: v.tokens_out for k, v in res.items()})
    for k in outs[0]:
        assert bool(jnp.all(outs[0][k] == outs[1][k]))


class _Recording:
    """Wraps a policy and keeps every placement it returns."""

    def __init__(self, inner):
        self.inner = inner
        self.placements = []

    def plan(self, wf, state, ready):
        out = self.inner.plan(wf, state, ready)
        self.placements.extend(out)
        return out


def _plain_greedy(bundle, prompts, gen_len, max_len):
    """Greedy decode on JAX's default device with nothing committed:
    the engine's computation before shards were bound to chips."""
    logits, kv = bundle.prefill(bundle.params, prompts,
                                bundle.model.init_cache(prompts.shape[0],
                                                        max_len))
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    gen = [tok]
    for step in range(gen_len - 1):
        logits, kv = bundle.decode(bundle.params, tok, kv,
                                   jnp.int32(prompts.shape[1] + step))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        gen.append(tok)
    return jnp.concatenate(gen, axis=1)


def test_shards_committed_to_their_virtual_devices_chip(bundles):
    wf = _workflow()
    engine = ServingEngine(bundles, n_devices=2, gen_len=3, prompt_len=8)
    state = fresh_state(homogeneous_cluster(2))
    prompts = jax.random.randint(jax.random.PRNGKey(5), (4, 8), 0, 256)
    policy = _Recording(make_policy("FATE"))
    results = engine.run_workflow(wf, policy, state, prompts)
    for p in policy.placements:
        r = results[p.sid]
        used = [d for d, n in zip(p.devices, p.shard_sizes) if n]
        assert len(r.shards) == len(used)
        for did, shard in zip(used, r.shards):
            chip = engine.devices[did].device
            assert shard.committed
            assert shard.sharding.device_set == {chip}
        for dev in engine.devices:
            if dev.params is not None:
                leaves = jax.tree.leaves(dev.params)
                assert all(x.sharding.device_set == {dev.device}
                           for x in leaves)
        assert bool(jnp.all(r.tokens_out
                            == jnp.concatenate(r.shards, axis=0)))


def test_bound_engine_tokens_equal_plain_default_device_run(bundles):
    wf = _workflow()
    gen_len, prompt_len = 3, 8
    engine = ServingEngine(bundles, n_devices=2, gen_len=gen_len,
                           prompt_len=prompt_len)
    assert {d.device for d in engine.devices} == {jax.devices()[0]}
    state = fresh_state(homogeneous_cluster(2))
    prompts = jax.random.randint(jax.random.PRNGKey(6), (4, prompt_len),
                                 0, 256)
    policy = _Recording(make_policy("FATE"))
    results = engine.run_workflow(wf, policy, state, prompts)
    for p in policy.placements:
        bundle = bundles[wf.stages[p.sid].model]
        parts, q0 = [], 0
        for n in p.shard_sizes:
            if n:
                parts.append(_plain_greedy(bundle, prompts[q0:q0 + n],
                                           gen_len, prompt_len + gen_len))
                q0 += n
        want = jnp.concatenate(parts, axis=0)
        assert bool(jnp.all(results[p.sid].tokens_out == want))


def test_run_stage_returns_ready_tokens(bundles):
    from repro.core.planner import Placement

    wf = _workflow()
    engine = ServingEngine(bundles, n_devices=2, gen_len=4, prompt_len=8)
    prompts = jax.random.randint(jax.random.PRNGKey(7), (4, 8), 0, 256)
    res = engine.run_stage(wf, wf.stages["retrieve"],
                           Placement(wf.wid, "retrieve", (0, 1), (2, 2)),
                           prompts)
    assert res.tokens_out.is_ready()
    assert all(s.is_ready() for s in res.shards)
    assert res.wall_s > 0.0


def test_kept_prefix_hits_on_rerun_and_holds_no_consumed_cache(bundles):
    """A stage that keeps its prefix, run twice on one virtual device:
    the rerun records a hit for all its queries. The device keeps a
    marker, not a cache that would pin its HBM (prefill starts fresh
    either way); no buffer a step consumed is read again, and both runs
    give the same tokens."""
    from repro.core.planner import Placement

    wf = _workflow()
    stage = wf.stages["retrieve"]
    engine = ServingEngine(bundles, n_devices=1, gen_len=3, prompt_len=8)
    prompts = jax.random.randint(jax.random.PRNGKey(6), (4, 8), 0, 256)
    where = Placement(wf.wid, stage.sid, (0,), (4,))
    first = engine.run_stage(wf, stage, where, prompts)
    again = engine.run_stage(wf, stage, where, prompts)
    assert not first.prefix_hit and first.prefix_fraction == 0.0
    assert again.prefix_hit and again.prefix_fraction == 1.0
    kept = engine.devices[0].prefix_caches
    assert list(kept) == [("ctx", "qwen-7b", 4)]
    assert not any(isinstance(x, jax.Array) for x in kept.values())
    assert bool(jnp.all(first.tokens_out == again.tokens_out))


def test_run_workflow_raises_on_empty_plan(bundles):
    class NoPlan:
        def plan(self, wf, state, ready):
            return []

    wf = _workflow()
    engine = ServingEngine(bundles, n_devices=2, gen_len=2, prompt_len=8)
    state = fresh_state(homogeneous_cluster(2))
    prompts = jax.random.randint(jax.random.PRNGKey(8), (4, 8), 0, 256)
    with pytest.raises(RuntimeError, match="no placement"):
        engine.run_workflow(wf, NoPlan(), state, prompts)
    assert engine.log == []


def test_chip_copy_dropped_after_last_holder_switches(bundles):
    chip = jax.devices()[0]
    engine = ServingEngine(bundles, n_devices=2, chips=[chip])
    a, b = bundles["qwen-7b"], bundles["llama-8b"]
    d0, d1 = engine.devices
    assert d0.ensure_resident(a) and d1.ensure_resident(a)
    assert engine.weights.placed() == {("qwen-7b", chip)}
    assert not d0.ensure_resident(a)           # already resident
    assert d0.ensure_resident(b)
    # d1 still holds qwen-7b on this chip: its copy stays
    assert engine.weights.placed() == {("qwen-7b", chip),
                                       ("llama-8b", chip)}
    assert d1.ensure_resident(b)
    assert engine.weights.placed() == {("llama-8b", chip)}
    assert d0.params is d1.params


def switch_bytes_main() -> None:
    """In a process with four CPU devices: two Qwen1.5-shaped models of
    different widths on 8 virtual devices (``d`` on chip ``d % 4``; home
    is chip 0), served through a fixed sequence of one-stage placements.
    Prints each stage's ``switch_bytes``, the engine's counters, each
    model's parameter bytes and the ids of the ``fate.stage.switch`` and
    ``fate.weights.copy`` spans of a profiler trace as one line of JSON."""
    import json
    import tempfile

    from repro.core.planner import Placement
    from test_spans import fate_spans

    small = SMOKE["qwen1.5-4b"]
    wide = dataclasses.replace(small, d_model=96, num_heads=6,
                               num_kv_heads=6, d_ff=192)
    models = {"qwen-7b": ModelBundle.create("qwen-7b", small, seed=0),
              "llama-8b": ModelBundle.create("llama-8b", wide, seed=1)}
    engine = ServingEngine(models, n_devices=8, gen_len=2, prompt_len=8,
                           chips=jax.devices()[:4])
    wf = _workflow()
    prompts = jax.random.randint(jax.random.PRNGKey(9), (4, 8), 0, 256)
    steps = [("qwen-7b", (0,)),        # home chip: shares the home copy
             ("qwen-7b", (1,)),        # chip 1 gains a copy
             ("qwen-7b", (5,)),        # chip 1 already holds it
             ("qwen-7b", (1, 2)),      # only chip 2 gains a copy
             ("llama-8b", (1,)),       # chip 1: vd 5 still holds qwen-7b
             ("llama-8b", (5,)),       # chip 1 holds llama-8b; qwen dropped
             ("qwen-7b", (1,))]        # released on chip 1, copied again
    out = []
    trace_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(trace_dir)
    for model, devices in steps:
        stage = dataclasses.replace(wf.stages["retrieve"], model=model)
        sizes = (4,) if len(devices) == 1 else (2, 2)
        res = engine.run_stage(wf, stage, Placement(wf.wid, stage.sid,
                                                    devices, sizes),
                               prompts)
        out.append(res.switch_bytes)
    jax.profiler.stop_trace()
    spans = fate_spans(trace_dir)
    print(json.dumps({
        "switch_spans": [[s.ids["model"], s.ids["did"], s.ids["chip"]]
                         for s in spans if s.name == "fate.stage.switch"],
        "copy_spans": [[s.ids["model"], s.ids["chip"], s.ids["bytes"]]
                       for s in spans if s.name == "fate.weights.copy"],
        "stages": out, "total": engine.weights.bytes_copied,
        "to": [[m, c, n] for (m, c), n in
               sorted(engine.weights.bytes_copied_to.items())],
        "model_bytes": {name: sum(x.nbytes for x in
                                  jax.tree.leaves(b.params))
                        for name, b in models.items()}}))


def test_switch_bytes_count_copies_between_chips():
    """A switch that copies a model onto a chip other than home counts
    its parameter bytes; the home chip and a chip that already holds the
    copy count 0; a copy dropped and made again counts again; the
    stages' ``switch_bytes`` add up to the engine's total; the spans
    name what each switch and copy did."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(here),
                                           str(here.parent / "src")]))
    run = subprocess.run(
        [sys.executable, "-c",
         "import test_serving as t; t.switch_bytes_main()"],
        cwd=here, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    got = json.loads(run.stdout.strip().splitlines()[-1])
    small, wide = got["model_bytes"]["qwen-7b"], got["model_bytes"]["llama-8b"]
    assert small != wide
    assert got["stages"] == [0, small, 0, small, wide, 0, small]
    assert sum(got["stages"]) == got["total"] == 3 * small + wide
    assert got["to"] == [["llama-8b", 1, wide], ["qwen-7b", 1, 2 * small],
                         ["qwen-7b", 2, small]]
    # each switch carries its ids; a copy span wraps each copy made
    assert got["switch_spans"] == [
        ["qwen-7b", 0, 0], ["qwen-7b", 1, 1], ["qwen-7b", 5, 1],
        ["qwen-7b", 1, 1], ["qwen-7b", 2, 2], ["llama-8b", 1, 1],
        ["llama-8b", 5, 1], ["qwen-7b", 1, 1]]
    assert got["copy_spans"] == [["qwen-7b", 1, small], ["qwen-7b", 2, small],
                                 ["llama-8b", 1, wide], ["qwen-7b", 1, small]]


def fresh_cache_main() -> None:
    """In a process with four CPU devices: one Qwen1.5-shaped model on
    4 virtual devices (``d`` on chip ``d``; home is chip 0), one stage
    run on chip 2, the same stage on chip 0, then one split over chips 2
    and 0. ``jax.device_put`` and the bundle's steps are wrapped to record
    where each array a shard reads comes from. Prints one line of JSON."""
    import json

    from repro.core.planner import Placement

    cfg = SMOKE["qwen1.5-4b"]
    base = ModelBundle.create("qwen-7b", cfg, seed=0)
    puts, prefills, decodes = [], [], []

    def ids(devices):
        return sorted(d.id for d in devices)

    def prefill(params, tokens, cache):
        prefills.append({
            "chip": ids(jax.tree.leaves(params)[0].devices()),
            "cache": [[x.committed, ids(x.devices())]
                      for x in jax.tree.leaves(cache)]})
        return base.prefill(params, tokens, cache)

    def decode(params, token, cache, pos):
        decodes.append({
            "chip": ids(jax.tree.leaves(params)[0].devices()),
            "pos": (ids(pos.devices()) if isinstance(pos, jax.Array)
                    else "host")})
        return base.decode(params, token, cache, pos)

    real_put = jax.device_put

    def device_put(x, device=None, **kw):
        to = ([device.id] if isinstance(device, jax.Device)
              else ids(device.device_set))
        puts.extend({"shape": list(a.shape), "to": to,
                     "from": (ids(a.devices()) if isinstance(a, jax.Array)
                              else "host")}
                    for a in jax.tree.leaves(x))
        return real_put(x, device, **kw)

    bundle = dataclasses.replace(base, prefill=prefill, decode=decode)
    gen_len, prompt_len = 3, 8
    engine = ServingEngine({"qwen-7b": bundle}, n_devices=4,
                           gen_len=gen_len, prompt_len=prompt_len,
                           chips=jax.devices()[:4])
    wf = _workflow()
    stage = wf.stages["retrieve"]
    prompts = jax.random.randint(jax.random.PRNGKey(11), (4, prompt_len),
                                 0, 256)
    out = {"stages": []}
    jax.device_put = device_put
    try:
        for devices, sizes in [((2,), (4,)), ((0,), (4,)),
                               ((2, 0), (1, 3))]:
            res = engine.run_stage(wf, stage, Placement(wf.wid, stage.sid,
                                                        devices, sizes),
                                   prompts)
            out["stages"].append({"sizes": list(sizes),
                                  "cache_bytes": res.cache_bytes,
                                  "tokens": res.tokens_out.tolist()})
    finally:
        jax.device_put = real_put
    item = jnp.dtype(cfg.dtype).itemsize
    out["kv_bytes_per_query"] = (2 * cfg.num_layers
                                 * (prompt_len + gen_len)
                                 * cfg.num_kv_heads * cfg.resolved_head_dim
                                 * item)
    out["cache_shapes"] = [list(x.shape) for x in jax.tree.leaves(
        bundle.model.init_cache(1, prompt_len + gen_len, abstract=True))]
    out.update(puts=puts, prefills=prefills, decodes=decodes)
    print(json.dumps(out))


def test_fresh_cache_and_positions_made_on_the_shards_chip():
    """A shard on chip 2 reads nothing made on chip 0: prefill is handed
    a fresh cache committed to the shard's chip, no ``device_put`` moves a
    cache-shaped array between chips, and the decode positions come from
    the host. Its tokens equal those of the same stage on chip 0, and
    ``cache_bytes`` is K plus V of the shard sizes."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(here),
                                           str(here.parent / "src")]))
    run = subprocess.run(
        [sys.executable, "-c",
         "import test_serving as t; t.fresh_cache_main()"],
        cwd=here, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    got = json.loads(run.stdout.strip().splitlines()[-1])
    on_2, on_0, split = got["stages"]
    assert on_2["tokens"] == on_0["tokens"]
    for st in got["stages"]:
        assert st["cache_bytes"] == (sum(st["sizes"])
                                     * got["kv_bytes_per_query"])
    # shards in order: chip 2, chip 0, then chip 2 and chip 0
    assert [p["chip"] for p in got["prefills"]] == [[2], [0], [2], [0]]
    for p in got["prefills"]:
        assert p["cache"] and all(c == [True, p["chip"]]
                                  for c in p["cache"])
    assert len(got["decodes"]) == 4 * 2
    assert {d["chip"][0] for d in got["decodes"]} == {0, 2}
    assert all(d["pos"] in ("host", d["chip"]) for d in got["decodes"])
    cache_dims = [s[:1] + s[2:] for s in got["cache_shapes"]]
    moved = [p for p in got["puts"]
             if p["from"] not in ("host", p["to"])
             and p["shape"][:1] + p["shape"][2:] in cache_dims]
    assert moved == []
    # the chip-2 stage's switch copied the weights there: the wrapper saw
    assert any(p["from"] == [0] and p["to"] == [2] for p in got["puts"])


def _fused_on_the_cpu(monkeypatch):
    """Decode attends in the fused pass wherever a TPU would take it, run
    here in Pallas interpret mode."""
    from repro.kernels.ops import decode_attention
    from repro.models import attention
    parts = attention.decode_attention_stacked

    def stacked(q, base, tail, new, layer, cache_len, *, start, window=0):
        view = attention.fused_view(base[0].shape,
                                    0 if tail is None else tail[0].shape[2])
        if view is None:
            return parts(q, base, tail, new, layer, cache_len, start=start,
                         window=window)
        return decode_attention(q, base, tail, new, layer, cache_len,
                                start=start, window=window, kv_major=view,
                                interpret=True)

    monkeypatch.setattr(attention, "decode_attention_stacked", stacked)


@pytest.mark.parametrize("heads,kv_heads", [(8, 8), (4, 2)])
def test_fused_decode_gives_the_parts_tokens(monkeypatch, heads, kv_heads):
    """Eight greedy decode steps of a tiny stack with 128-lane heads give
    the same tokens on the jnp parts and on the fused pass (in Pallas
    interpret mode): MHA read as [S, KV, D], and GQA read KV-major. On
    a TPU the model names its decode's pass "fused"."""
    import numpy as np

    from repro.models.families import build_model
    from repro.serving.engine import jit_steps
    cfg = dataclasses.replace(SMOKE["qwen1.5-4b"], head_dim=128,
                              num_heads=heads, num_kv_heads=kv_heads)
    prompt_len, gen_len, batch = 8, 8, 2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len),
                                0, cfg.vocab_size, jnp.int32)

    def greedy():
        model = build_model(cfg)
        prefill, decode = jit_steps(model)
        params = model.init(jax.random.PRNGKey(0))
        logits, kv = prefill(params, tokens,
                             model.init_cache(batch, prompt_len + gen_len))
        out = [jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)]
        for step in range(gen_len):
            logits, kv = decode(params, out[-1], kv,
                                np.int32(prompt_len + step))
            out.append(jnp.argmax(logits, -1).astype(jnp.int32))
        return model.decode_pass(kv, "tpu"), np.concatenate(out, 1)

    parts = greedy()
    _fused_on_the_cpu(monkeypatch)
    fused = greedy()
    assert parts[0] == fused[0] == "fused"
    np.testing.assert_array_equal(fused[1], parts[1])
