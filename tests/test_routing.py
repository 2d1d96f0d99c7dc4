"""Cost/quality model-family routing (core/routing.py).

Pins the routing contract: candidates below the quality floor are
filtered before the solve; variant stages are pure cost-scaled twins;
the (stage, family, device) solve respects family exclusivity; routing
disabled — or enabled over candidate-free workloads — is bit-identical
to the pre-routing planner; and the routed trace is served strictly
cheaper than the fixed-family run at chosen quality >= the floor.
"""
import dataclasses
import json

import numpy as np

from repro.core.devices import heterogeneous_cluster, \
    homogeneous_cluster
from repro.core.frontier_solver import FrontierProblem, \
    solve_frontier_exact
from repro.core.planner import FrontierPlanner
from repro.core.routing import (RoutingConfig, StageRouter,
                                admissible_candidates,
                                family_cost_ratio, variant_stage)
from repro.core.scheduler import Scheduler, SchedulerConfig
from repro.core.scoring import ScoreParams
from repro.core.workflow import DEFAULT_PROFILES, Stage, Workflow
from repro.workflowbench.suites import (poisson_serving_trace,
                                        routed_serving_trace,
                                        routed_workflow_instance)
from _sched_fixtures import bench_workflow, warmed_state


def _routed_stage():
    return Stage("w", "qwen-14b", base_cost={-1: 0.2},
                 candidates=(("qwen-7b", 0.92), ("llama-3b", 0.84)))


def _run(trace, config, n_devices=6):
    sched = Scheduler(homogeneous_cluster(n_devices), config)
    for t, wf in trace:
        sched.submit(wf, at=t)
    res = sched.drain()
    return res, sched


def _events(sched):
    return [(type(e).__name__, dataclasses.astuple(e))
            for e in sched.events]


def _placements(sched):
    return {k: (r.placement.devices, r.placement.shard_sizes,
                r.placement.model, r.start, r.finish)
            for k, r in sched.runs.items()}


# -- candidate admissibility / variant purity ---------------------------


def test_quality_floor_filters_candidates():
    st = _routed_stage()
    cfg = RoutingConfig(quality_floor=0.9)
    assert [m for m, _ in
            admissible_candidates(st, cfg, DEFAULT_PROFILES)] \
        == ["qwen-7b"]
    # a lower floor admits both; a floor above every candidate -> none
    low = RoutingConfig(quality_floor=0.8)
    assert [m for m, _ in
            admissible_candidates(st, low, DEFAULT_PROFILES)] \
        == ["qwen-7b", "llama-3b"]
    high = RoutingConfig(quality_floor=0.99)
    assert admissible_candidates(st, high, DEFAULT_PROFILES) == []


def test_max_candidates_caps_alternates():
    st = Stage("w", "qwen-14b", base_cost={-1: 0.2},
               candidates=(("qwen-7b", 0.95), ("llama-8b", 0.94),
                           ("deepseek-7b", 0.93)))
    cfg = RoutingConfig(quality_floor=0.9, max_candidates=2)
    assert len(admissible_candidates(st, cfg, DEFAULT_PROFILES)) == 2


def test_variant_stage_is_pure_cost_scaled_twin():
    st = _routed_stage()
    v = variant_stage(st, "qwen-7b", DEFAULT_PROFILES)
    assert v.sid == st.sid and v.parents == st.parents
    assert v.model == "qwen-7b"
    ratio = family_cost_ratio(DEFAULT_PROFILES, "qwen-14b", "qwen-7b",
                              st.prefill_fraction)
    assert v.base_cost[-1] == st.base_cost[-1] * ratio
    # the 7b family is genuinely cheaper than 14b
    assert 0.0 < ratio < 1.0
    # purity: same inputs, same output; the original is untouched
    v2 = variant_stage(st, "qwen-7b", DEFAULT_PROFILES)
    assert v2.base_cost == v.base_cost
    assert st.model == "qwen-14b" and st.base_cost[-1] == 0.2


def test_router_variant_cached_per_workflow():
    router = StageRouter(RoutingConfig())
    st = _routed_stage()
    a = router.variant("w1", st, "qwen-7b", DEFAULT_PROFILES)
    b = router.variant("w1", st, "qwen-7b", DEFAULT_PROFILES)
    assert a is b
    router.forget_workflow("w1")
    c = router.variant("w1", st, "qwen-7b", DEFAULT_PROFILES)
    assert c is not a and c.base_cost == a.base_cost


# -- solver exclusivity -------------------------------------------------


def test_solver_exclusive_groups_pick_one_family():
    """With default and variant rows for the same stage in one
    exclusive group, the exact solve assigns at most one of the two
    keys — and picks the higher-weight family."""
    rows = [("s", 0), ("s", 1),                     # default family
            (("s", "alt"), 0), (("s", "alt"), 1)]   # variant block
    weights = np.array([[1.0, 0.8], [0.0, 0.0],
                        [3.0, 2.5], [0.0, 0.0]])
    prob = FrontierProblem(rows, [0, 1], weights,
                           exclusive=[["s", ("s", "alt")]])
    sol = solve_frontier_exact(prob)
    placed = {key for (key, _slot) in sol.assignment}
    assert placed == {("s", "alt")}


def test_solver_exclusive_respects_better_default():
    rows = [("s", 0), (("s", "alt"), 0)]
    weights = np.array([[5.0], [1.0]])
    prob = FrontierProblem(rows, [0], weights,
                           exclusive=[["s", ("s", "alt")]])
    sol = solve_frontier_exact(prob)
    placed = {key for (key, _slot) in sol.assignment}
    assert placed == {"s"}


# -- disabled / candidate-free bit-identity -----------------------------


def test_routing_none_vs_enabled_on_candidate_free_serving():
    """Enabling routing over workflows with no candidates must be a
    provable no-op: identical events and placements."""
    trace = poisson_serving_trace(n_workflows=8, rate=6.0, seed=0,
                                  num_queries=4)
    off, s_off = _run(trace, SchedulerConfig(policy="FATE"))
    on, s_on = _run(trace, SchedulerConfig(policy="FATE",
                                           routing=RoutingConfig()))
    assert _events(s_off) == _events(s_on)
    assert _placements(s_off) == _placements(s_on)
    assert {w: s.makespan for w, s in off.stats.items()} \
        == {w: s.makespan for w, s in on.stats.items()}


def test_routing_enabled_batch_frontier_candidate_free_parity():
    """32x16 H=4 wide batch frontier: the routed planner over a
    candidate-free workflow produces the exact placements of the
    plain planner."""
    wf = bench_workflow(32)
    cluster = heterogeneous_cluster(16)
    ready = [f"w{i}" for i in range(32)]
    params = ScoreParams(horizon=4)
    plain = FrontierPlanner(params).plan(
        wf, warmed_state(wf, 32, cluster), list(ready))
    routed = FrontierPlanner(params, routing=RoutingConfig()).plan(
        wf, warmed_state(wf, 32, cluster), list(ready))
    assert [(p.sid, p.devices, p.shard_sizes, p.model)
            for p in plain] \
        == [(p.sid, p.devices, p.shard_sizes, p.model)
            for p in routed]
    assert all(p.model is None for p in plain)


# -- routed end-to-end --------------------------------------------------


def test_routed_trace_cheaper_at_quality_floor():
    trace = routed_serving_trace(n_workflows=6, rate=4.0, seed=0,
                                 num_queries=4)
    fixed, s_fixed = _run(trace, SchedulerConfig(policy="FATE"))
    routed, s_routed = _run(trace, SchedulerConfig(
        policy="FATE", routing=RoutingConfig()))
    assert len(routed.stats) == len(trace)          # all complete
    by_wid = {wf.wid: wf for _, wf in trace}
    floor = RoutingConfig().quality_floor
    n_routed = 0
    for (wid, sid), r in s_routed.runs.items():
        st = by_wid[wid].stages[sid]
        if r.placement.model and r.placement.model != st.model:
            n_routed += 1
            assert dict(st.candidates)[r.placement.model] >= floor
            # the below-floor llama-3b candidate is never chosen
            assert r.placement.model != "llama-3b"

    def cost(s):
        return sum((r.finish - r.start) * len(r.placement.devices)
                   for r in s.runs.values())

    assert n_routed > 0
    assert cost(s_routed) < cost(s_fixed)


def test_routed_placement_model_survives_snapshot():
    """A routed run's snapshot round-trips Placement.model; an
    unrouted run's placement docs carry no 'model' key at all."""
    trace = routed_serving_trace(n_workflows=3, rate=4.0, seed=0,
                                 num_queries=4)
    cfg = SchedulerConfig(policy="FATE", routing=RoutingConfig())
    sched = Scheduler(homogeneous_cluster(4), cfg)
    for t, wf in trace:
        sched.submit(wf, at=t)
    # advance until something routed is in flight
    while not any(r.placement.model for r in sched.runs.values()):
        assert sched.step(), "no routed run ever issued"
    snap = sched.snapshot()
    doc = json.loads(json.dumps(snap))       # wire round-trip
    restored = Scheduler.restore(doc)
    assert {k: r.placement.model for k, r in sched.runs.items()} \
        == {k: r.placement.model for k, r in restored.runs.items()}


def test_unrouted_snapshot_has_no_model_keys():
    trace = poisson_serving_trace(n_workflows=4, rate=6.0, seed=0,
                                  num_queries=4)
    cfg = SchedulerConfig(policy="FATE")
    sched = Scheduler(homogeneous_cluster(4), cfg)
    for t, wf in trace:
        sched.submit(wf, at=t)
    while not sched.runs:
        assert sched.step()
    snap = json.loads(json.dumps(sched.snapshot()))
    docs = [run["placement"] for _w, _s, run in snap["runs"]] \
        + list(snap["committed"])
    assert docs
    assert all("model" not in d for d in docs)


# -- config surface -----------------------------------------------------


def test_config_round_trips_routing_gateway_and_auto_pools():
    cfg = SchedulerConfig(
        policy="FATE",
        routing=RoutingConfig(quality_floor=0.85, max_candidates=2),
        gateway={"replicas": 3}, pools="auto")
    back = SchedulerConfig.from_json(cfg.to_json())
    assert back.routing is not None
    assert back.routing.quality_floor == 0.85
    assert back.routing.max_candidates == 2
    assert back.gateway == {"replicas": 3}
    assert back.pools == "auto"


def test_legacy_config_docs_load_with_routing_disabled():
    """Pre-gateway JSON documents (no routing/gateway keys) must load
    unchanged, with both features disabled."""
    doc = json.loads(SchedulerConfig(policy="FATE").to_json())
    doc.pop("routing", None)
    doc.pop("gateway", None)
    cfg = SchedulerConfig.from_json(json.dumps(doc))
    assert cfg.routing is None
    assert cfg.gateway is None
    assert cfg.pools == 1


def test_stage_candidates_round_trip_and_legacy_load():
    st = _routed_stage()
    back = Stage.from_dict(st.to_dict())
    assert back.candidates == st.candidates
    legacy = st.to_dict()
    legacy.pop("candidates")
    assert Stage.from_dict(legacy).candidates == ()
    wf = routed_workflow_instance(0, num_queries=4)
    wf2 = Workflow.from_dict(wf.to_dict())
    assert {s.sid: s.candidates for s in wf.stages.values()} \
        == {s.sid: s.candidates for s in wf2.stages.values()}
