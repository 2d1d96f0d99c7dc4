"""Multi-class priority scheduling: weighted SLOs, anti-starvation
aging, and true preemption of running shards (ISSUE 9).

Covers the additive-machinery contract (a config whose only class is
``"default"`` reproduces the class-free control plane bit-identically,
in serving and batch mode), the class-config surface (submit-time
validation, per-class deadlines, aging promotion, class-major
re-admission), kill/replay semantics of running-shard preemption
(no lost work, per-stage kill caps, typed event round-trip, journal
replay), and a randomized property suite driving audited multi-class
runs with snapshot/restore bit-identity checks.
"""
import dataclasses
import functools
import random
import tempfile
import time

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                  # offline container
    from _fallback_hypothesis import given, settings, strategies as st

from repro.core.admission import (AdmissionController, ClassSpec,
                                  SLOConfig)
from repro.core.devices import heterogeneous_cluster, \
    homogeneous_cluster
from repro.core.journal import EventJournal
from repro.core.scheduler import (Scheduler, SchedulerConfig,
                                  SchedulerEvent, ShardPreemptionEvent,
                                  audit_invariants)
from repro.core.scoring import ScoreParams
from repro.core.workflow import Stage, Workflow
from repro.workflowbench.metrics import class_summary, slo_summary
from repro.workflowbench.suites import (chaos_fault_plan,
                                        multiclass_overloaded_trace,
                                        overloaded_serving_trace)
from test_scale_stress import random_trace

BUDGET_S = 120.0                # per-test wall-clock ceiling

#: The weighted two-tier config: platinum over batch, with aging and
#: running-shard preemption.
MC_SLO = dict(
    classes={"platinum": ClassSpec(weight=4.0, latency_scale=8.0),
             "batch": ClassSpec(weight=1.0, latency_scale=40.0,
                                backlog_limit=18)},
    aging_rate=0.5, preempt_running=True, preempt_running_max=6,
    preempt_kill_cap=3)


def _run_pairs(trace, cluster, slo, **cfg_kwargs):
    sched = Scheduler(cluster, SchedulerConfig(policy="FATE", slo=slo,
                                               **cfg_kwargs))
    for t, wf in trace:
        sched.submit(wf, at=t)
    return sched.drain(), sched


def _run_triples(trace, cluster, slo, journal=None, audit_every=None,
                 **cfg_kwargs):
    sched = Scheduler(cluster, SchedulerConfig(policy="FATE", slo=slo,
                                               **cfg_kwargs),
                      journal=journal, audit_every=audit_every)
    for t, wf, klass in trace:
        sched.submit(wf, at=t, klass=klass)
    return sched


def _events(sched):
    return [(type(e).__name__, dataclasses.astuple(e))
            for e in sched.events]


def _placements(sched):
    return {k: (r.placement.devices, r.placement.shard_sizes,
                r.start, r.finish)
            for k, r in sched.runs.items()}


def _result_key(res):
    return (sorted((w, dataclasses.astuple(s))
                   for w, s in res.stats.items()),
            sorted(res.rejected), sorted(res.failed), res.horizon,
            res.preemptions, res.deferrals, res.replans)


def _chain(wid: str, n: int = 3, cost: float = 0.05,
           model: str = "qwen-7b", num_queries: int = 4) -> Workflow:
    stages = {}
    prev = ()
    for i in range(n):
        stages[f"s{i}"] = Stage(f"s{i}", model, base_cost={-1: cost},
                                parents=prev)
        prev = (f"s{i}",)
    return Workflow(wid=wid, stages=stages, num_queries=num_queries)


# ---------------------------------------------------------------------------
# default-class parity: the multi-class machinery is strictly additive
# ---------------------------------------------------------------------------


def test_default_class_parity_serving():
    """ISSUE 9 satellite: ``classes={"default": ClassSpec()}`` must
    reproduce the class-free overloaded n=18 run bit-identically —
    same events field-for-field, same placements, same result."""
    trace = overloaded_serving_trace(n_workflows=18, rate=14.0, seed=0,
                                     num_queries=8)
    cl = homogeneous_cluster(6)
    plain, s_plain = _run_pairs(trace, cl, SLOConfig())
    defaulted, s_def = _run_pairs(
        trace, cl, SLOConfig(classes={"default": ClassSpec()}))
    assert _events(s_plain) == _events(s_def)
    assert _placements(s_plain) == _placements(s_def)
    assert _result_key(plain) == _result_key(defaulted)


def _wide_batch_workflow(width: int = 32) -> Workflow:
    """Map/reduce DAG with a ``width``-wide worker frontier (the
    32x16 H=4 bench shape, depth 1 to stay inside tier-1 time)."""
    models = ["qwen-7b", "deepseek-7b", "llama-8b", "llama-3b",
              "qwen-14b"]
    stages: dict[str, Stage] = {}
    for i in range(width):
        stages[f"in{i}"] = Stage(f"in{i}", models[i % 5],
                                 base_cost={-1: 0.05},
                                 output_tokens=256.0)
        stages[f"w{i}"] = Stage(
            f"w{i}", models[(i + 1) % 5], max_shards=2,
            base_cost={-1: 0.1 + 0.01 * (i % 7)},
            prefix_group=f"g{i % 4}", shared_fraction=0.5,
            output_tokens=384.0, parents=(f"in{i}",))
        stages[f"c{i}"] = Stage(
            f"c{i}", models[(i + 2) % 5], base_cost={-1: 0.08},
            prefix_group=f"g{i % 4}", output_tokens=256.0,
            parents=(f"w{i}",))
    return Workflow(wid="mc-batch-32", stages=stages, num_queries=4)


def test_default_class_parity_batch_suite():
    """Same parity on the 32-wide x 16-device H=4 batch suite: the
    priorities plumbing through the shared solve must be a no-op for
    a uniform-weight default class."""
    wf = _wide_batch_workflow(32)
    results = []
    for slo in (SLOConfig(), SLOConfig(classes={"default":
                                                ClassSpec()})):
        sched = Scheduler(heterogeneous_cluster(16),
                          SchedulerConfig(policy="FATE", slo=slo,
                                          score=ScoreParams(horizon=4)),
                          batch=True)
        sched.submit(wf)
        sched.drain()
        res = sched.batch_result(wf.wid)
        results.append((_placements(sched), _events(sched),
                        res.makespan, res.p95))
    assert results[0] == results[1]


# ---------------------------------------------------------------------------
# class-config surface
# ---------------------------------------------------------------------------


def test_submit_unknown_class_raises():
    """Satellite 1: with a class config active, submit validates the
    class name and names the registered classes in the error."""
    sched = Scheduler(homogeneous_cluster(2),
                      SchedulerConfig(policy="FATE",
                                      slo=SLOConfig(**MC_SLO)))
    with pytest.raises(ValueError, match="batch.*platinum"):
        sched.submit(_chain("wf0"), at=0.0, klass="gold")


def test_submit_free_form_class_without_config():
    """No class config: any label is accepted (back-compat — the
    label is carried through to per-workflow stats)."""
    cl = homogeneous_cluster(2)
    sched = Scheduler(cl, SchedulerConfig(policy="FATE",
                                          slo=SLOConfig()))
    sched.submit(_chain("wf0"), at=0.0, klass="anything")
    res = sched.drain()
    assert res.stats["wf0"].klass == "anything"


def test_per_class_deadline_scaling():
    slo = SLOConfig(latency_scale=2.0, **MC_SLO)
    # platinum overrides the global scale; an unconfigured class
    # falls back to it
    assert slo.deadline(3.0, 5.0, "platinum") == pytest.approx(43.0)
    assert slo.deadline(3.0, 5.0, "batch") == pytest.approx(203.0)
    assert slo.deadline(3.0, 5.0) == pytest.approx(13.0)


def test_aging_promotes_bottom_class():
    """The anti-starvation bound: after (w_top - w_bottom)/aging_rate
    seconds of waiting, a batch entry's effective weight reaches a
    fresh platinum arrival's."""
    slo = SLOConfig(**MC_SLO)
    ctl = AdmissionController(slo)
    bound = (slo.class_weight("platinum")
             - slo.class_weight("batch")) / slo.aging_rate
    assert bound == pytest.approx(6.0)
    assert ctl._eff_weight("batch", 0.0) < ctl._eff_weight("platinum",
                                                           0.0)
    assert ctl._eff_weight("batch", bound) \
        >= ctl._eff_weight("platinum", 0.0)
    # aging is monotone in wait and never demotes
    assert ctl._eff_weight("batch", 2.0) > ctl._eff_weight("batch", 1.0)
    assert ctl._eff_weight("platinum", 0.0) \
        == slo.class_weight("platinum")


def _drain_backlog_order(slo, backlog, classes, now=0.0):
    """Seed a controller's backlog directly and force-drain it one
    entry per sweep, returning the admission order."""
    from repro.core.executor import fresh_state
    from repro.core.policies import make_policy
    from repro.core.scheduler import SharedFrontier

    ctl = AdmissionController(slo)
    state = fresh_state(homogeneous_cluster(2))
    state.now = now
    for wid, klass in classes.items():
        ctl.note_class(wid, klass)
    ctl.backlog = list(backlog)
    frontier, policy = SharedFrontier(), make_policy("FATE")
    order = []
    while ctl.backlog:
        admitted = ctl.readmit(state, frontier, policy, set(),
                               force=True)
        assert len(admitted) == 1       # at most one per sweep
        order.append(admitted[0][1].wid)
    return order


def test_readmit_is_class_major():
    """Satellite 2: deferred platinum entries are re-probed before
    OLDER batch entries (weight-major), ties resolved by age."""
    slo = SLOConfig(
        latency_scale=60.0,
        classes={"platinum": ClassSpec(weight=4.0),
                 "batch": ClassSpec(weight=1.0)},
        aging_rate=0.0)
    order = _drain_backlog_order(
        slo,
        backlog=[(0.0, _chain("b-old")), (0.5, _chain("b-mid")),
                 (1.0, _chain("p-new"))],
        classes={"b-old": "batch", "b-mid": "batch",
                 "p-new": "platinum"},
        now=1.0)
    assert order == ["p-new", "b-old", "b-mid"], \
        "platinum first, then batch entries oldest-first"


def test_readmit_aging_overtakes_class_weight():
    """With aging on, a batch entry that has waited past the
    starvation bound outranks a fresh platinum arrival in the same
    sweep."""
    slo = SLOConfig(
        latency_scale=60.0,
        classes={"platinum": ClassSpec(weight=4.0),
                 "batch": ClassSpec(weight=1.0)},
        aging_rate=2.0)                  # bound = 3/2 = 1.5 s
    order = _drain_backlog_order(
        slo,
        backlog=[(0.0, _chain("b-starved")), (2.0, _chain("p-new"))],
        classes={"b-starved": "batch", "p-new": "platinum"},
        now=2.0)                         # b-starved waited 2.0 > 1.5
    assert order == ["b-starved", "p-new"]


# ---------------------------------------------------------------------------
# running-shard preemption: kill/replay semantics
# ---------------------------------------------------------------------------


def test_shard_preemption_event_roundtrip():
    ev = ShardPreemptionEvent(t=1.25, wid="wf1", sid="s0",
                              devices=(0, 3), trigger_wid="wf9",
                              klass="batch", trigger_klass="platinum")
    doc = ev.to_dict()
    assert doc["type"] == "ShardPreemptionEvent"
    assert doc["devices"] == [0, 3]              # JSON-safe
    back = SchedulerEvent.from_dict(doc)
    assert back == ev
    assert back.devices == (0, 3)                # tuple restored


def test_running_shard_preemption_fires_without_lost_work():
    """Kill/replay conserves work: every submitted workflow still ends
    in exactly one of completed / rejected / failed, preempted batch
    stages are replayed to completion, and audits stay clean."""
    trace = multiclass_overloaded_trace(n_workflows=18, rate=14.0,
                                        seed=0, num_queries=8)
    sched = _run_triples(trace, homogeneous_cluster(6),
                         SLOConfig(**MC_SLO))
    res = sched.drain()
    assert not audit_invariants(sched)
    assert res.shard_preemptions > 0
    preempted = {e.wid for e in sched.events
                 if isinstance(e, ShardPreemptionEvent)}
    assert preempted, "running shards must actually be killed"
    submitted = {wf.wid for _, wf, _ in trace}
    assert set(res.stats) | set(res.rejected) | set(res.failed) \
        == submitted
    assert not set(res.stats) & set(res.rejected)
    # every preempted workflow is still accounted for — kill/replay
    # loses no work
    for wid in preempted:
        assert wid in res.stats or wid in res.rejected \
            or wid in res.failed
    per_class = class_summary(res)
    assert per_class["batch"]["completion_rate"] == 1.0
    # kill victims are strictly lower-weight than their trigger
    for e in sched.events:
        if isinstance(e, ShardPreemptionEvent):
            slo = SLOConfig(**MC_SLO)
            assert slo.class_weight(e.trigger_klass) \
                > slo.class_weight(e.klass)


def test_preempt_kill_cap_bounds_kills_per_stage():
    """A stage killed ``preempt_kill_cap`` times becomes immune — the
    anti-livelock guarantee."""
    trace = multiclass_overloaded_trace(n_workflows=18, rate=14.0,
                                        seed=0, num_queries=8)
    slo = dataclasses.replace(SLOConfig(**MC_SLO), preempt_kill_cap=1)
    sched = _run_triples(trace, homogeneous_cluster(6), slo)
    sched.drain()
    kills: dict[tuple, int] = {}
    for e in sched.events:
        if isinstance(e, ShardPreemptionEvent):
            kills[(e.wid, e.sid)] = kills.get((e.wid, e.sid), 0) + 1
    assert kills, "cap=1 must still allow first kills"
    assert max(kills.values()) <= 1


def test_preempt_running_disabled_never_kills():
    trace = multiclass_overloaded_trace(n_workflows=18, rate=14.0,
                                        seed=0, num_queries=8)
    slo = dataclasses.replace(SLOConfig(**MC_SLO),
                              preempt_running=False)
    sched = _run_triples(trace, homogeneous_cluster(6), slo)
    res = sched.drain()
    assert res.shard_preemptions == 0
    assert not any(isinstance(e, ShardPreemptionEvent)
                   for e in sched.events)


def test_journal_replays_shard_preemption_bit_identically():
    """Crash just past the first ShardPreemptionEvent with only the
    t=0 snapshot on disk: the journal tail must replay the preemption
    (kill, τ/κ credit, re-enqueue) and drain to the bit-identical
    outcome."""
    trace = multiclass_overloaded_trace(n_workflows=18, rate=14.0,
                                        seed=0, num_queries=8)
    cl = homogeneous_cluster(6)
    base = _run_triples(trace, cl, SLOConfig(**MC_SLO))
    base_res = base.drain()
    pre = [i for i, e in enumerate(base.events)
           if isinstance(e, ShardPreemptionEvent)]
    assert pre, "baseline must preempt a running shard"

    with tempfile.TemporaryDirectory() as tmp:
        journal = EventJournal(tmp, rotate_bytes=64 * 1024)
        sched = _run_triples(trace, cl, SLOConfig(**MC_SLO),
                             journal=journal)
        journal.write_snapshot(sched.snapshot())
        while sched.events.n_total <= pre[0] and sched.step():
            pass                       # stop just past the first kill
        del sched, journal             # crash: abandon in place

        reopened = EventJournal(tmp)
        restored = Scheduler.restore(reopened.latest_snapshot(),
                                     reopened)
        assert not audit_invariants(restored)
        res = restored.drain()
        assert not audit_invariants(restored)
    assert _result_key(res) == _result_key(base_res)
    assert res.shard_preemptions == base_res.shard_preemptions
    assert res.classes == base_res.classes
    assert _events(restored) == _events(base)


@functools.cache
def _weighted_tier_runs():
    """The overloaded trace's first 12 arrivals on 6 devices, once
    class-free under the controlled SLO plane and once tagged
    platinum/batch/batch under ``MC_SLO``."""
    cl = homogeneous_cluster(6)
    single, _ = _run_pairs(
        overloaded_serving_trace(n_workflows=12, rate=14.0, seed=0,
                                 num_queries=8), cl, SLOConfig())
    sched = _run_triples(
        multiclass_overloaded_trace(n_workflows=12, rate=14.0, seed=0,
                                    num_queries=8),
        cl, SLOConfig(**MC_SLO))
    return single, sched.drain()


def test_platinum_attainment_at_least_single_class():
    """Weighting pays the top class: platinum's SLO attainment under
    the weighted config is at least the class-free run's overall
    attainment, with running shards actually killed for it."""
    single, weighted = _weighted_tier_runs()
    assert weighted.shard_preemptions > 0
    assert class_summary(weighted)["platinum"]["slo_attainment"] \
        >= slo_summary({"single": single})["single"]["slo_attainment"]


def test_batch_wait_within_starvation_bound():
    """Aging bounds the bottom class: batch completes everything, and
    its longest wait is at most the starvation bound
    ``(w_platinum - w_batch) / aging_rate`` plus twice the class-free
    horizon."""
    single, weighted = _weighted_tier_runs()
    slo = SLOConfig(**MC_SLO)
    bound = ((slo.class_weight("platinum") - slo.class_weight("batch"))
             / slo.aging_rate + 2.0 * single.horizon)
    batch = class_summary(weighted)["batch"]
    assert batch["completion_rate"] == 1.0
    assert batch["max_wait"] <= bound


def _mc_chaos_trace():
    """The overloaded trace's first 10 arrivals, class-tagged: the
    fewest on which the chaos script fires and a running shard is
    killed."""
    return multiclass_overloaded_trace(n_workflows=10, rate=14.0, seed=0,
                                       num_queries=8)


@functools.cache
def _mc_chaos_baseline():
    """The weighted config plus the chaos fault script, uninterrupted:
    its result, its event stream and the indexes of its
    ``ShardPreemptionEvent``s."""
    base = _run_triples(_mc_chaos_trace(), homogeneous_cluster(6),
                        SLOConfig(**MC_SLO), faults=chaos_fault_plan(0))
    res = base.drain()
    kills = [i for i, e in enumerate(base.events)
             if isinstance(e, ShardPreemptionEvent)]
    return res, _events(base), kills


@pytest.mark.parametrize("kill", [0.15, 0.5, 0.85,
                                  "past_first_preemption"])
def test_journaled_chaos_multiclass_recovers_at_kill_point(kill):
    """Weighted classes under the chaos script, journaled (snapshots
    every 20 steps), killed at a share of the event stream or just past
    the first running-shard kill, restored cold from snapshot plus
    journal tail: the drained run is bit-identical to the uninterrupted
    one and the audits are clean."""
    base_res, base_events, kills = _mc_chaos_baseline()
    assert kills, "baseline must preempt a running shard"
    at = (kills[0] + 1 if kill == "past_first_preemption"
          else max(1, int(len(base_events) * kill)))
    with tempfile.TemporaryDirectory() as tmp:
        journal = EventJournal(tmp, rotate_bytes=64 * 1024)
        sched = _run_triples(_mc_chaos_trace(), homogeneous_cluster(6),
                             SLOConfig(**MC_SLO), journal=journal,
                             faults=chaos_fault_plan(0))
        journal.write_snapshot(sched.snapshot())
        steps = 0
        while sched.events.n_total < at and sched.step():
            steps += 1
            if steps % 20 == 0:
                journal.write_snapshot(sched.snapshot())
        del sched, journal             # crash: abandon in place

        reopened = EventJournal(tmp)
        restored = Scheduler.restore(reopened.latest_snapshot(),
                                     reopened)
        assert not audit_invariants(restored)
        res = restored.drain()
        assert not audit_invariants(restored)
    assert _result_key(res) == _result_key(base_res)
    assert res.shard_preemptions == base_res.shard_preemptions
    assert res.classes == base_res.classes
    assert _events(restored) == base_events


# ---------------------------------------------------------------------------
# randomized property suite
# ---------------------------------------------------------------------------


def _random_class_slo(rng: random.Random) -> SLOConfig:
    classes = {"gold": ClassSpec(weight=rng.choice([2.0, 4.0]),
                                 latency_scale=rng.choice([None, 8.0])),
               "bulk": ClassSpec(weight=1.0,
                                 latency_scale=rng.choice([None, 30.0]),
                                 backlog_limit=rng.choice([None, 12]))}
    if rng.random() < 0.3:
        classes["default"] = ClassSpec()
    return SLOConfig(
        latency_scale=rng.choice([2.5, 6.0, 30.0]),
        classes=classes,
        aging_rate=rng.choice([0.0, 0.5, 2.0]),
        preempt_running=rng.random() < 0.8,
        preempt_running_max=rng.choice([1, 2, 4]),
        preempt_kill_cap=rng.choice([1, 2]),
        preempt_holdoff=rng.choice([0.0, 0.05]))


def _random_mc_trace(rng: random.Random, classes):
    names = sorted(classes)
    return [(t, wf, rng.choice(names))
            for t, wf in random_trace(rng, rng.randint(6, 12))]


@pytest.mark.slow
@given(st.integers(min_value=0, max_value=1_000_000))
@settings(max_examples=6, deadline=None)
def test_random_multiclass_traces_hold_invariants_every_step(seed):
    """Random bursty traces with random class tags under random
    weighted/aging/preempting configs, audited at EVERY step: zero
    violations, guaranteed drain, conservation of workflows."""
    t0 = time.perf_counter()
    rng = random.Random(seed)
    slo = _random_class_slo(rng)
    trace = _random_mc_trace(rng, slo.classes)
    sched = _run_triples(trace, homogeneous_cluster(rng.choice([3, 4])),
                         slo, audit_every=1,
                         pools=rng.choice([1, 2]),
                         batch_probes=rng.random() < 0.5)
    res = sched.drain()
    assert not audit_invariants(sched)
    submitted = {wf.wid for _, wf, _ in trace}
    assert set(res.stats) | set(res.rejected) | set(res.failed) \
        == submitted
    assert not set(res.stats) & set(res.rejected)
    assert not set(res.stats) & set(res.failed)
    # the class map covers every offered workflow
    assert set(res.classes) == submitted
    assert time.perf_counter() - t0 < BUDGET_S


@pytest.mark.slow
@given(st.integers(min_value=0, max_value=1_000_000),
       st.floats(min_value=0.1, max_value=0.9))
@settings(max_examples=4, deadline=None)
def test_random_multiclass_snapshot_restores_bit_identically(seed,
                                                             frac):
    """Snapshot a random multi-class run at a random point (including
    mid-preemption states), restore, audit, drain: bit-identical
    outcome, preemption counters included."""
    t0 = time.perf_counter()
    rng = random.Random(seed)
    slo = _random_class_slo(rng)
    trace = _random_mc_trace(rng, slo.classes)
    n_devices = rng.choice([3, 4])

    base = _run_triples(trace, homogeneous_cluster(n_devices), slo)
    steps = 0
    while base.step():
        steps += 1
    base_res = base.drain()

    sched = _run_triples(trace, homogeneous_cluster(n_devices), slo)
    for _ in range(max(1, int(steps * frac))):
        if not sched.step():
            break
    restored = Scheduler.restore(sched.snapshot())
    assert not audit_invariants(restored)
    res = restored.drain()
    assert not audit_invariants(restored)
    assert _result_key(res) == _result_key(base_res)
    assert res.shard_preemptions == base_res.shard_preemptions
    assert res.classes == base_res.classes
    assert time.perf_counter() - t0 < BUDGET_S
