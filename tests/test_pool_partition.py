"""Hierarchical sharded frontier solve: partition parity + invariants.

Gates the pooled-solve path of :class:`~repro.core.planner.
FrontierPlanner`: a forced single-pool hierarchical solve must be
bit-identical to the monolithic merged solve on the wide 32x16 H=4
frontier (the same configuration every other parity gate in the repo
is defined on), multi-pool solves must be deterministic, pool
assignment must be stable under delta rescoring that does not move
residency, the partitioner must fall back to the monolithic solve when
it cannot realize the pool count, and the ``pools`` config knob must
be inert for every non-FATE registered policy.
"""
import dataclasses

import pytest

from repro.core.costs import CostModel
from repro.core.devices import heterogeneous_cluster, homogeneous_cluster
from repro.core.executor import fresh_state
from repro.core.planner import FrontierPlanner
from repro.core.policies import ALL_POLICIES
from repro.core.scoring import ScoreParams
from repro.core.workflow import Stage, Workflow
from _sched_fixtures import MODELS, bench_workflow, warmed_state

WIDE = (32, 16, 4)              # width, devices, horizon: the repo's
                                # canonical parity configuration


def wide_workflow(width: int = 32) -> Workflow:
    """The wide frontier at depth 2, with its own wid."""
    return bench_workflow(width, depth=2, num_queries=8,
                          wid=f"pool-wide-{width}")


def plan_key(placements):
    return [(p.sid, p.devices, p.shard_sizes) for p in placements]


def _wide_plan(pools=1, forced=None, plans=2, max_waves=None):
    """``plan_shared`` the wide frontier ``plans`` times (the second
    plan exercises the cross-session delta-rescore path under the
    partitioned solve) and return placement keys.

    The partitioner only runs on the merged-frontier path
    (``plan_shared``); the single-workflow ``plan`` never partitions.
    """
    width, n_dev, horizon = WIDE
    wf = wide_workflow(width)
    cluster = heterogeneous_cluster(n_dev)
    state = warmed_state(wf, width, cluster)
    planner = FrontierPlanner(ScoreParams(horizon=horizon), pools=pools,
                              max_waves=max_waves)
    if forced is not None:
        planner._forced_partition = forced
    ready = [(wf.wid, f"w{i}") for i in range(width)]
    return [plan_key(planner.plan_shared({wf.wid: wf}, state,
                                         list(ready)))
            for _ in range(plans)], planner


def test_single_pool_bit_identical_to_monolithic():
    """Forced one-pool hierarchical solve == monolithic, bit for bit,
    on the 32x16 H=4 frontier — including the delta-rescored replan."""
    cluster = heterogeneous_cluster(WIDE[1])
    mono, _ = _wide_plan()
    hier, _ = _wide_plan(forced=[list(cluster.ids())])
    assert mono == hier
    assert all(mono[0])                 # non-vacuous: stages placed


def test_oversubscribed_pool_count_falls_back_to_monolithic():
    """pools >= n_devices cannot be realized: the partitioner returns
    None and the wave must solve monolithically — bit-identical."""
    mono, _ = _wide_plan()
    over, planner = _wide_plan(pools=WIDE[1] + 1)
    assert mono == over
    assert planner.pools == WIDE[1] + 1


def pooled_problem(n_wfs: int = 8, n_dev: int = 16):
    """Merged-frontier fixture the partitioner can actually split:
    many small workflows over a homogeneous cluster whose residency
    falls into four equal model blocks, so four pools pack one block
    each and every workflow has an affinity home."""
    cluster = homogeneous_cluster(n_dev)
    state = fresh_state(cluster)
    block = n_dev // 4
    for d in range(n_dev):
        state.residency[d] = MODELS[d // block]
    wfs: dict[str, Workflow] = {}
    ready = []
    for i in range(n_wfs):
        m = MODELS[i % 4]
        stages = {
            "a": Stage("a", m, base_cost={-1: 0.06},
                       output_tokens=192.0),
            "b": Stage("b", m, base_cost={-1: 0.08},
                       output_tokens=192.0, parents=("a",)),
        }
        wf = Workflow(wid=f"pp-{i:02d}", stages=stages, num_queries=4)
        wfs[wf.wid] = wf
        ready.append((wf.wid, "a"))
    return wfs, state, ready


def _pooled_plan(pools, max_waves=None, solve_shapes=None):
    wfs, state, ready = pooled_problem()
    planner = FrontierPlanner(ScoreParams(horizon=2), pools=pools,
                              max_waves=max_waves)
    key = plan_key(planner.plan_shared(wfs, state, list(ready)))
    if solve_shapes is not None:
        solve_shapes.extend(sorted((r.n_rows, r.n_devices)
                                   for r in planner.solve_log))
    return key


def test_multi_pool_deterministic():
    """Same state + same pool count -> identical placements, twice
    over fresh planners (no hidden RNG or dict-order dependence) —
    and the partition actually engaged (one solve per pool)."""
    shapes_a, shapes_b = [], []
    a = _pooled_plan(4, max_waves=1, solve_shapes=shapes_a)
    b = _pooled_plan(4, max_waves=1, solve_shapes=shapes_b)
    assert a == b and a
    assert shapes_a == shapes_b
    assert len(shapes_a) == 4           # partitioned, not fallback


def test_multi_pool_covers_frontier():
    """The 4-pool solve still places the merged ready frontier (pools
    partition devices, never drop work), and a single wave's disjoint
    per-pool solves never double-book a device."""
    full = _pooled_plan(4)
    assert sorted(s for s, _, _ in full) == ["a"] * 8
    wave1 = _pooled_plan(4, max_waves=1)
    used = [d for _, devs, _ in wave1 for d in devs]
    assert used and len(used) == len(set(used))


def test_forced_partition_must_cover_every_device():
    width, n_dev, horizon = WIDE
    wf = wide_workflow(width)
    cluster = heterogeneous_cluster(n_dev)
    state = warmed_state(wf, width, cluster)
    planner = FrontierPlanner(ScoreParams(horizon=horizon))
    planner._forced_partition = [list(cluster.ids())[:-1]]  # one short
    with pytest.raises(ValueError, match="cover every device"):
        planner.plan_shared({wf.wid: wf}, state,
                            [(wf.wid, f"w{i}") for i in range(width)])


def test_pool_assignment_stable_under_delta_updates():
    """Completion-like mutations that delta rescoring absorbs (free
    times, prefix warmth, the clock) must not move the partition:
    per-wave pool shapes (rows x devices, from the solve log) repeat
    exactly across replans as long as residency stays put."""
    wfs, state, ready = pooled_problem()
    n_dev = state.cluster.n
    planner = FrontierPlanner(ScoreParams(horizon=2), pools=4,
                              max_waves=1)

    def shapes():
        planner.solve_log.clear()
        planner.plan_shared(wfs, state, list(ready))
        return sorted((r.n_rows, r.n_devices)
                      for r in planner.solve_log)

    base = shapes()
    assert len(base) == 4               # one solve per pool
    for step in range(3):
        state.now += 0.05
        state.set_free_at(step % n_dev, state.now + 0.1)
        state.warm_prefix((step + 1) % n_dev, f"g{step % 4}",
                          MODELS[step % 5], 4, state.now)
        assert shapes() == base


def test_pools_knob_inert_for_non_fate_policies():
    """Every registered policy accepts a pooled SchedulerConfig; for
    the baselines (no FrontierPlanner) the knob must change nothing —
    event streams are bit-identical with pools=1 and pools=4."""
    from repro.core.scheduler import Scheduler, SchedulerConfig
    from repro.workflowbench.suites import poisson_serving_trace

    trace = poisson_serving_trace(n_workflows=6, rate=6.0, seed=3,
                                  num_queries=4)
    cluster = homogeneous_cluster(4)

    def events(policy, pools):
        sched = Scheduler(cluster, SchedulerConfig(policy=policy,
                                                   pools=pools))
        for t, wf in trace:
            sched.submit(wf, at=t)
        sched.drain()
        return [(type(e).__name__, dataclasses.astuple(e))
                for e in sched.events]

    for policy in ALL_POLICIES:
        if policy == "FATE":
            continue                    # pools is live for FATE
        assert events(policy, 1) == events(policy, 4), policy


def test_fate_pooled_serving_completes_under_audit():
    """End-to-end: FATE with pools=2 drains a concurrent trace with
    the per-step invariant audit armed (audit_every=1 raises on any
    violation) and completes every admitted workflow."""
    from repro.core.scheduler import Scheduler, SchedulerConfig
    from repro.workflowbench.suites import poisson_serving_trace

    trace = poisson_serving_trace(n_workflows=8, rate=8.0, seed=1,
                                  num_queries=4)
    sched = Scheduler(homogeneous_cluster(6),
                      SchedulerConfig(policy="FATE", pools=2),
                      audit_every=1)
    for t, wf in trace:
        sched.submit(wf, at=t)
    res = sched.drain()
    assert set(res.stats) == {wf.wid for _, wf in trace}
    assert not res.failed


def test_bursty_pooled_batched_trace_completes_under_audit():
    """The fleet-size configuration at a tier-1 size: bursty
    same-timestamp arrivals, a 4-pool solve, batched admission probes,
    a generous SLO and a bounded event ring that slides.  Audited every
    20 steps and after drain, every workflow completes and nothing is
    rejected or failed."""
    from repro.core.admission import SLOConfig
    from repro.core.scheduler import (Scheduler, SchedulerConfig,
                                      audit_invariants)
    from repro.workflowbench.suites import scale_serving_trace

    trace = scale_serving_trace(64, burst=8, gap=2.0, num_queries=1)
    sched = Scheduler(homogeneous_cluster(16),
                      SchedulerConfig(policy="FATE",
                                      slo=SLOConfig(latency_scale=100.0),
                                      pools=4, batch_probes=True,
                                      event_buffer=256),
                      audit_every=20)
    for t, wf in trace:
        sched.submit(wf, at=t)
    res = sched.drain()
    assert not audit_invariants(sched)
    assert len(res.stats) == len(trace)
    assert not res.rejected and not res.failed
    assert sched.events.n_dropped > 0   # the ring slid
