"""CPU tests of the benchmark's yardstick: traffic, arithmetic, operation
counts, trace reduction and the cells' files."""
from __future__ import annotations

import functools
import json
import math
import re
from pathlib import Path

import jax
import numpy as np
import pytest

import calls
import flops
import spec
import stats
import trace_reduce
import weights
from workload import Traffic

BENCH = Path(__file__).resolve().parent
BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


CONFIGS = sorted(p.name for p in (BENCH / "configs").glob("*.json"))


def models(config: str) -> dict:
    """The models of ``configs/<config>``, by alias."""
    entry = json.loads((BENCH / "configs" / config).read_text())
    return {m["alias"]: spec.ModelSpec.from_json(m) for m in entry["models"]}


QWEN18 = models("qwen1.5-1.8b-pair.json")["qwen-7b"]
QWEN15 = models("qwen1.5-1.8b_qwen1.5-4b.json")["llama-8b"]


# -- traffic ------------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_prompts_are_a_function_of_the_seed(cell):
    t = Traffic(spec.load_cell(cell).traffic)
    a = t.prompts(2**31 + 7, 5, 151936)
    assert a.shape == (5, t.queries, t.prompt_len) and a.dtype == np.int32
    assert (a == t.prompts(2**31 + 7, 5, 151936)).all()
    assert not (a == t.prompts(2**31 + 8, 5, 151936)).all()
    assert a.min() >= 0 and a.max() < 151936


@pytest.mark.parametrize("cell", CELLS)
def test_arrivals_are_the_mix_s_own_schedule(cell):
    t = Traffic(spec.load_cell(cell).traffic)
    a = t.arrivals(30.0)
    assert a == t.arrivals(30.0)
    assert a == sorted(a) and 0 < a[0] and a[-1] < 30.0
    # a Poisson count: within 4 standard deviations of rate x seconds
    mean = t.mix["rate_per_s"] * 30.0
    assert abs(len(a) - mean) < 4 * math.sqrt(mean)
    # a shorter window is a prefix of a longer one
    assert t.arrivals(10.0) == [x for x in a if x < 10.0]


def test_workflow_is_the_agentic_dag():
    from repro.workflowbench.suites import agentic_workflow
    t = Traffic(spec.load_cell("pair.agentic.steady").traffic)
    ours, theirs = t.workflow("w"), agentic_workflow("w", t.queries)
    assert set(ours.stages) == set(theirs.stages)
    for sid, s in theirs.stages.items():
        o = ours.stages[sid]
        assert (o.model, o.base_cost, o.prefix_group, o.max_shards,
                o.output_tokens, o.parents) == (
            s.model, s.base_cost, s.prefix_group, s.max_shards,
            s.output_tokens, s.parents)
    assert t.tokens_per_stage() == 8 * (128 + 8)


# -- arithmetic ---------------------------------------------------------

@pytest.mark.parametrize("q,want", [(0.5, 5), (0.9, 9), (1.0, 10),
                                    (0.05, 1), (0.91, 10)])
def test_nearest_rank(q, want):
    assert stats.nearest_rank([float(x) for x in range(10, 0, -1)],
                              q) == want


def test_nearest_rank_small_and_empty():
    assert stats.nearest_rank([3.0], 0.9) == 3.0
    assert stats.nearest_rank([2.0, float("inf")], 0.9) == float("inf")
    with pytest.raises(ValueError):
        stats.nearest_rank([], 0.5)


def test_tokens_per_s_counts_stages_inside_the_window():
    ends = [100.5, 101.0, 110.0, 110.01, 99.0]
    # 3 stages end inside [100, 110]: 3 x 1088 tokens over 10 s
    assert stats.tokens_per_s(ends, 1088, 100.0, 10.0) == 3 * 1088 / 10.0


# -- operations and bytes -------------------------------------------------

def test_flops_hand_counts():
    # q, k, v, o + SwiGLU: 2048*(2*16+2*16)*128 + 3*2048*5504 per layer
    assert flops.layer_matmul_params(QWEN18) == 50_593_792
    assert flops.matmul_flops_per_token(QWEN18) == 2 * 1_214_251_008
    assert QWEN18.kv_bytes_per_token == 196_608
    # 4*2560^2 + 3*2560*6912 per layer, 40 layers
    assert flops.matmul_flops_per_token(QWEN15) == 2 * 3_171_942_400
    assert QWEN15.kv_bytes_per_token == 409_600


def test_prefill_and_decode_counts():
    ops, nbytes = flops.prefill(QWEN18, 8, 128)
    attn = 2 * 2 * 24 * 16 * 128 * 1024 * 64.5
    assert ops == 1024 * 2 * 1_214_251_008 + attn + 8 * 2 * 2048 * 151936
    w = 2 * (1_214_251_008 + 2048 * 151936)
    assert nbytes == w + 1024 * 2 * 2048 + 1024 * 196_608
    ops, nbytes = flops.decode(QWEN18, 4, 130, 136)
    assert ops == (4 * 2 * 1_214_251_008 + 2 * 2 * 24 * 16 * 128 * 4 * 131
                   + 4 * 2 * 2048 * 151936)
    assert nbytes == w + 4 * 2 * 2048 + 4 * 136 * 196_608 + 4 * 196_608


# -- weights and the program's layout -------------------------------------

@pytest.mark.parametrize("config", CONFIGS)
def test_weights_have_the_program_s_layout(config):
    import harness
    from repro.models.families import build_model
    for m in models(config).values():
        want = jax.eval_shape(build_model(harness.arch_config(m)).init,
                              jax.random.PRNGKey(0))
        got = jax.eval_shape(functools.partial(weights._init, m),
                             weights.model_key(0, m))
        assert (jax.tree.map(lambda a: (a.shape, a.dtype), want)
                == jax.tree.map(lambda a: (a.shape, a.dtype), got))
        assert weights.param_count(m) == sum(
            math.prod(a.shape) for a in jax.tree.leaves(want))


def test_weights_per_layer_keys_and_large_seeds():
    m = spec.ModelSpec(alias="a", seed_offset=3, arch="qwen3-1.7b",
                       layers=3, d_model=32, heads=2, kv_heads=1,
                       head_dim=16, d_ff=48, vocab=64, rope_theta=1e6,
                       norm_eps=1e-6, tied=False, qk_norm=True,
                       qkv_bias=True, dtype="bfloat16")
    p = weights.init(m, 2**33 + 5, jax.devices()[0])
    q = weights.init(m, 5, jax.devices()[0])
    assert not (p["embed"] == q["embed"]).all()
    wq = p["blocks"]["attn"]["wq"]
    assert not (wq[0] == wq[1]).all()
    idx = [leaf.path for leaf in weights.leaves(m)].index(
        ("blocks", "attn", "wq"))
    k = jax.random.fold_in(jax.random.fold_in(
        weights.model_key(2**33 + 5, m), idx), 2)
    one = (jax.random.normal(k, (32, 2, 16)) * 32 ** -0.5).astype(wq.dtype)
    assert (one == wq[2]).all()


# -- trace reduction ------------------------------------------------------

def test_merge_and_covered():
    merged = trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [(0, 3), (5, 8)]
    assert trace_reduce.covered(merged, 2, 6) == 2
    assert trace_reduce.covered(merged, 10, 20) == 0


# A trace in the plain form that ``trace_reduce.load`` makes: two chips,
# overlapping operations on chip 0, and the benchmark's host spans (ns).
SMALL_TRACE = {
    "ops": {0: [["fusion.1", 100, 50], ["fusion.2", 120, 60],
                ["fusion.3", 300, 20]],
            1: [["copy.1", 150, 100]]},
    "modules": {0: [["jit_prefill_fn", 100, 80], ["jit_decode_fn", 300, 20]],
                1: [["jit_decode_fn", 150, 100]]},
    "host": [["bench.window", 0, 1000], ["bench.plan#0", 10, 50],
             ["bench.stage#0", 90, 310], ["python_fn", 0, 5]],
}


def test_reduction_of_a_small_trace():
    t = SMALL_TRACE
    (_, lo, hi), = trace_reduce.host_spans(t, "bench.window")
    assert (lo, hi) == (0, 1000)
    # chip 0 runs [100, 180] and [300, 320]; chip 1 runs [150, 250]
    assert trace_reduce.busy(t, 0, lo, hi) == 100
    assert trace_reduce.busy(t, 1, lo, hi) == 100
    assert trace_reduce.busy(t, 0, 110, 310) == 80
    assert trace_reduce.executions(t, lo, hi, calls.PREFILL) == [
        (0, "jit_prefill_fn", 100, 180)]
    assert [e[0] for e in trace_reduce.executions(t, lo, hi, "fn")] == [
        0, 1, 0]
    assert trace_reduce.op_totals(t, [0, 1], lo, hi)[0] == (
        "copy.1", pytest.approx(100e-9))
    gaps = trace_reduce.idle_gaps(t, [0, 1], lo, hi)
    # longest first, each named by the innermost benchmark span over its
    # middle, or "wait"
    assert [n for n, _ in gaps] == ["chip1:wait", "chip0:wait", "chip1:wait",
                                    "chip0:bench.stage", "chip0:bench.plan"]
    assert [g for _, g in gaps] == pytest.approx(
        [750e-9, 680e-9, 150e-9, 120e-9, 100e-9])


# -- the benchmark's files --------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_names_its_files():
    assert BENCHMARK["paths"] == ["bench"]
    for c in BENCHMARK["configs"]:
        assert NAME.match(c["name"]) and (BENCH.parent / c["file"]).exists()
    for w in BENCHMARK["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
    for m in BENCHMARK["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in {e["name"] for e in BENCHMARK["end_to_end"]}


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_setup_and_a_layer(cell):
    c = spec.load_cell(cell)
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2
    assert c.per_layer and c.n_devices >= 2


# -- warm-up and the check's sample ------------------------------------------

class _Fake:
    """Stands in for the engine, policy and state that ``warm_up`` drives:
    it keeps the layouts it is asked to run."""

    def __init__(self, chips: int, per_chip: int):
        from types import SimpleNamespace
        self.devices = [SimpleNamespace(device=f"chip{i % chips}",
                                        resident=None)
                        for i in range(chips * per_chip)]
        self.layouts, self.log, self.records, self.spans = [], [], [], []

    def run_stage(self, wf, stage, placement, prompts):
        chips = tuple(self.devices[d].device for d in placement.devices)
        self.layouts.append((stage.model, chips, placement.shard_sizes))

    def run_workflow(self, wf, policy, state, prompts):
        pass

    def forget_workflow(self, wid):
        pass


@pytest.mark.parametrize("chips,per_chip", [(1, 2), (4, 1)])
def test_warm_up_runs_each_program_on_each_chip(chips, per_chip):
    """Each model runs on each chip with all queries and with half of
    them, and a split's first shard, where the halves are gathered, lies
    on each chip."""
    import harness
    t = Traffic(spec.load_cell("pair.agentic.steady").traffic)
    fake = _Fake(chips, per_chip)
    harness.warm_up(fake, fake, fake, t, ["a", "b"])
    q = t.queries
    for model in ("a", "b"):
        runs = [(c, s) for m, c, s in fake.layouts if m == model]
        for chip in {d.device for d in fake.devices}:
            assert ((chip,), (q,)) in runs
            assert any(c[0] == chip and s == (q // 2, q - q // 2)
                       for c, s in runs)
            assert chip in {c[1] for c, s in runs if len(c) == 2}
        assert len(runs) == 2 * chips


def test_sample_holds_one_stage_of_each_kind():
    import check
    from types import SimpleNamespace
    recs = [SimpleNamespace(model=m, chips=c)
            for m in ("a", "b") for c in ((0,), (0, 0), (1,))
            for _ in range(5)]
    picked = check.sample(recs, 2**31 + 3, 0, n=6)
    assert len(picked) == 6 and len({id(r) for r in picked}) == 6
    assert {check.kind(r, 0) for r in picked} == {
        check.kind(r, 0) for r in recs}
    assert [id(r) for r in picked] == [
        id(r) for r in check.sample(recs, 2**31 + 3, 0, n=6)]
