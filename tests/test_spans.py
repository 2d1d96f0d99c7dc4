"""The program's spans (``repro.spans``): a real ``jax.profiler`` trace of
a tiny serving engine holds every ``fate.*`` span, nested as the layers
are, and the planner's ``phase_ms`` is filled through the same helper."""
import dataclasses
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs.archs import SMOKE
from repro.core.devices import homogeneous_cluster
from repro.core.executor import fresh_state
from repro.core.planner import Placement
from repro.core.policies import make_policy
from repro.serving.engine import ModelBundle, ServingEngine
from repro.spans import span
from repro.workflowbench.suites import agentic_workflow

STAGE_PARTS = ("fate.stage.switch", "fate.stage.put", "fate.stage.prefill",
               "fate.stage.decode", "fate.stage.gather", "fate.stage.ready")


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    ids: dict

    def inside(self, other: "Span") -> bool:
        return other.start <= self.start and self.end <= other.end


class CountingPolicy:
    def __init__(self, inner):
        self.inner = inner
        self.calls = 0

    def plan(self, wf, state, ready):
        self.calls += 1
        return self.inner.plan(wf, state, ready)


def fate_spans(trace_dir: str) -> list[Span]:
    """The ``fate.*`` host events of the trace written under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    path = next(Path(trace_dir).rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            out.extend(Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats))
                       for e in line.events if e.name.startswith("fate."))
    return sorted(out, key=lambda s: s.start)


@pytest.fixture(scope="module")
def traced():
    """One agentic workflow under FATE on two virtual devices, then one
    two-shard stage run directly, all inside one profiler trace."""
    cfg_a = SMOKE["qwen3-1.7b"]
    cfg_b = dataclasses.replace(SMOKE["glm4-9b"],
                                vocab_size=cfg_a.vocab_size)
    bundles = {"qwen-7b": ModelBundle.create("qwen-7b", cfg_a, seed=0),
               "llama-8b": ModelBundle.create("llama-8b", cfg_b, seed=1)}
    engine = ServingEngine(bundles, n_devices=2, gen_len=3, prompt_len=8)
    state = fresh_state(homogeneous_cluster(2))
    policy = CountingPolicy(make_policy("FATE"))
    prompts = jax.random.randint(jax.random.PRNGKey(0), (4, 8), 0, 256)
    wf = agentic_workflow("wf-traced", num_queries=4)
    split = agentic_workflow("wf-split", num_queries=4)
    trace_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(trace_dir)
    try:
        engine.run_workflow(wf, policy, state, prompts)
        engine.run_stage(split, split.stages["retrieve"],
                         Placement(split.wid, "retrieve", (0, 1), (2, 2)),
                         prompts)
    finally:
        jax.profiler.stop_trace()
    return engine, policy, wf, fate_spans(trace_dir)


def named(spans, name):
    return [s for s in spans if s.name == name]


def test_stages_lie_inside_their_workflow(traced):
    engine, _, wf, spans = traced
    workflows = named(spans, "fate.workflow")
    assert [w.ids for w in workflows] == [{"wid": wf.wid}]
    stages = named(spans, "fate.stage")
    assert len(stages) == len(engine.log)
    in_wf = [s for s in stages if s.ids["wid"] == wf.wid]
    assert sorted(s.ids["sid"] for s in in_wf) == sorted(wf.stages)
    assert all(s.inside(workflows[0]) for s in in_wf)
    (split,) = [s for s in stages if s.ids["wid"] == "wf-split"]
    assert not split.inside(workflows[0])


def test_stage_parts_lie_inside_their_stage(traced):
    engine, _, _, spans = traced
    stages = named(spans, "fate.stage")
    for part in STAGE_PARTS:
        for s in named(spans, part):
            assert sum(s.inside(st) for st in stages) == 1, part
    # the direct call ran two shards: one switch, put, prefill and
    # decode span each, one gather and one wait for the tokens
    (split,) = [s for s in stages if s.ids["wid"] == "wf-split"]
    counts = {p: sum(s.inside(split) for s in named(spans, p))
              for p in STAGE_PARTS}
    assert counts == {"fate.stage.switch": 2, "fate.stage.put": 2,
                      "fate.stage.prefill": 2, "fate.stage.decode": 2,
                      "fate.stage.gather": 1, "fate.stage.ready": 1}
    shards = sum(len(r.shards) for r in engine.log)
    assert len(named(spans, "fate.stage.prefill")) == shards
    assert len(named(spans, "fate.stage.ready")) == len(engine.log)


def test_plans_and_state_updates_lie_inside_the_workflow(traced):
    _, policy, wf, spans = traced
    (workflow,) = named(spans, "fate.workflow")
    plans = named(spans, "fate.plan")
    assert len(plans) == policy.calls > 0
    assert all(p.inside(workflow) for p in plans)
    for part in ("fate.plan.score", "fate.plan.solve"):
        parts = named(spans, part)
        assert parts
        assert all(sum(s.inside(p) for p in plans) == 1 for s in parts)
    updates = named(spans, "fate.state")
    assert len(updates) == len(wf.stages)
    assert all(u.inside(workflow) for u in updates)
    stages = named(spans, "fate.stage")
    assert not any(u.inside(s) or s.inside(u)
                   for u in updates + plans for s in stages)


def test_phase_ms_accumulates_under_the_same_keys():
    policy = make_policy("FATE")
    keys = {"full_build", "delta_rescore", "solve"}
    assert set(policy.phase_ms) == keys
    assert all(v == 0.0 for v in policy.phase_ms.values())
    state = fresh_state(homogeneous_cluster(2))
    wf = agentic_workflow("wf-phase", num_queries=4)
    policy.plan(wf, state, ["retrieve"])
    first = dict(policy.phase_ms)
    assert set(first) == keys
    assert all(v >= 0.0 for v in first.values())
    assert first["full_build"] > 0.0 and first["solve"] > 0.0
    policy.plan(wf, state, ["retrieve"])
    assert set(policy.phase_ms) == keys
    assert all(policy.phase_ms[k] >= first[k] for k in keys)


def test_span_adds_its_time_under_the_key_set_inside():
    times = {"a": 0.0, "b": 0.0}
    with span("fate.test", times) as sp:
        sp.key = "b"
        jnp.ones(8).block_until_ready()
    assert times["a"] == 0.0 and times["b"] > 0.0
    with span("fate.test", times, "a", wid="w"):
        pass
    assert times["a"] > 0.0
    before = dict(times)
    with pytest.raises(ValueError):
        with span("fate.test", times):     # no key: nothing is added
            raise ValueError
    assert times == before


def test_put_spans_carry_the_chip_and_fresh_cache_bytes(traced):
    engine, _, _, spans = traced
    chip = engine.devices[0].device.id
    puts = named(spans, "fate.stage.put")
    assert puts and all(p.ids["chip"] == chip for p in puts)
    stages = named(spans, "fate.stage")
    assert len(stages) == len(engine.log)
    for st, res in zip(stages, engine.log):
        made = [p.ids["cache_bytes"] for p in puts if p.inside(st)]
        assert len(made) == len(res.shards)
        assert all(n > 0 for n in made)
        assert sum(made) == res.cache_bytes


def test_decode_spans_name_the_attention_pass(traced):
    """Heads of 16 lanes on the CPU: every decode takes the jnp parts."""
    decodes = named(traced[3], "fate.stage.decode")
    assert decodes and all(d.ids["attn"] == "parts" for d in decodes)


def test_decode_span_names_the_fused_pass_where_it_serves():
    """The decode span names the pass the model's decode takes over the
    cache prefill served, on the chip's platform: the jnp parts on the
    CPU; on a TPU the fused pass for a stack of 128-lane heads, and the
    parts for narrower heads."""
    prompt_len, gen_len, batch = 8, 8, 2
    for head_dim, on_tpu in ((128, "fused"), (16, "parts")):
        cfg = dataclasses.replace(SMOKE["qwen1.5-4b"], head_dim=head_dim)
        bundle = ModelBundle.create("m", cfg, seed=0)
        engine = ServingEngine({"m": bundle}, n_devices=1, gen_len=gen_len,
                               prompt_len=prompt_len)
        wf = agentic_workflow("wf-fused", num_queries=batch)
        prompts = jax.random.randint(jax.random.PRNGKey(0),
                                     (batch, prompt_len), 0, 256)
        trace_dir = tempfile.mkdtemp()
        jax.profiler.start_trace(trace_dir)
        try:
            engine.run_stage(wf, dataclasses.replace(
                wf.stages["retrieve"], model="m"),
                Placement(wf.wid, "retrieve", (0,), (batch,)), prompts)
        finally:
            jax.profiler.stop_trace()
        (decode,) = named(fate_spans(trace_dir), "fate.stage.decode")
        assert decode.ids["attn"] == "parts"
        served = jax.eval_shape(
            bundle.prefill, bundle.params, prompts,
            bundle.model.init_cache(batch, prompt_len + gen_len))[1]
        assert bundle.model.decode_pass(served, "cpu") == "parts"
        assert bundle.model.decode_pass(served, "tpu") == on_tpu
