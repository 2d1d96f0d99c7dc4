"""Fault-tolerant execution: deterministic fault injection,
failure-aware replanning, and graceful degradation.

The contract under test, in order of importance:

* the fault machinery is STRICTLY ADDITIVE — an armed-but-empty
  ``FaultPlan`` reproduces the fault-free run bit-for-bit (placements
  and event stream), and with ``faults=None`` nothing changes at all;
* seeded fault scripts are deterministic — two same-seed runs produce
  bit-identical event streams;
* the scheduler completes admitted work under device crashes
  (failure-aware replanning off the dead device), transient shard
  failures (retry with exponential backoff, quarantine on repeat
  offenders), and slowdown episodes (straggler detection +
  speculative re-issue);
* the bounded-buffer satellites: the scheduler event list and the
  admission probe log respect their configured caps.
"""
import dataclasses
import functools

import pytest

from repro.core.admission import (AdmissionController, SLOConfig,
                                  stage_floor_costs)
from repro.core.devices import heterogeneous_cluster, homogeneous_cluster
from repro.core.executor import fresh_state
from repro.core.faults import (DeviceCrash, DeviceHealth, FaultInjector,
                               FaultPlan, ShardFailure, Slowdown,
                               TransientStageFailure)
from repro.core.scheduler import (DegradedEvent, DeviceDownEvent,
                                  DeviceRecoveredEvent, EventLog,
                                  IssueEvent, RetryEvent, Scheduler,
                                  SchedulerConfig, ShardFailedEvent)
from repro.workflowbench.suites import (chaos_fault_plan,
                                        overloaded_serving_trace,
                                        poisson_serving_trace)


def _trace(n=6, seed=3):
    return poisson_serving_trace(n_workflows=n, rate=8.0, seed=seed,
                                 num_queries=8)


def _run(faults=None, trace=None, n_devices=4, **cfg_kwargs):
    trace = _trace() if trace is None else trace
    sched = Scheduler(homogeneous_cluster(n_devices),
                      SchedulerConfig(policy="FATE", faults=faults,
                                      **cfg_kwargs))
    for t, wf in trace:
        sched.submit(wf, at=t)
    res = sched.drain()
    return res, sched


def _events(sched):
    return [(type(e).__name__, dataclasses.astuple(e))
            for e in sched.events]


def _placements(sched):
    return {k: (r.placement.devices, r.placement.shard_sizes)
            for k, r in sched.runs.items()}


# ---------------------------------------------------------------------------
# FaultPlan / FaultInjector units
# ---------------------------------------------------------------------------


def test_fault_plan_roundtrip():
    plan = FaultPlan(
        seed=7,
        crashes=(DeviceCrash(device=2, at=1.0, recover_at=3.0),),
        slowdowns=(Slowdown(device=1, at=0.5, until=2.0, factor=4.0),),
        failures=(ShardFailure(wid="w", sid="s", at_fraction=0.25),),
        failure_rate=0.1, max_random_failures=2,
        max_retries=5, retry_backoff=0.1, retry_backoff_mult=3.0,
        straggler_threshold=2.0, speculate=False,
        quarantine_after=2, quarantine_s=0.5)
    assert FaultPlan.from_dict(plan.to_dict()) == plan
    assert not plan.empty
    assert FaultPlan().empty


def test_scheduler_config_roundtrip_with_faults():
    plan = FaultPlan(crashes=(DeviceCrash(device=0, at=2.0),),
                     straggler_threshold=1.5)
    cfg = SchedulerConfig(policy="FATE", faults=plan, event_buffer=128)
    back = SchedulerConfig.from_json(cfg.to_json())
    assert back.faults == plan
    assert back.event_buffer == 128
    none_back = SchedulerConfig.from_json(
        SchedulerConfig(policy="FATE").to_json())
    assert none_back.faults is None
    assert none_back.event_buffer is None


def test_backoff_schedule_is_exponential():
    plan = FaultPlan(retry_backoff=0.1, retry_backoff_mult=2.0)
    assert plan.backoff(1) == pytest.approx(0.1)
    assert plan.backoff(2) == pytest.approx(0.2)
    assert plan.backoff(3) == pytest.approx(0.4)


def test_injector_targeted_failure_fires_once_on_attempt_zero():
    plan = FaultPlan(failures=(ShardFailure(wid="w", sid="s",
                                            at_fraction=0.4),))
    inj = FaultInjector(plan)
    assert inj.failure_fraction("w", "s", (0,), attempt=0) == 0.4
    assert inj.failure_fraction("w", "s", (0,), attempt=0) is None
    inj2 = FaultInjector(plan)
    assert inj2.failure_fraction("w", "s", (0,), attempt=1) is None


def test_injector_random_failures_deterministic_and_bounded():
    plan = FaultPlan(seed=11, failure_rate=1.0, max_random_failures=2)
    draws = [FaultInjector(plan).failure_fraction(f"w{i}", "s", (0,), 0)
             for i in range(4)]
    inj = FaultInjector(plan)
    fired = [inj.failure_fraction(f"w{i}", "s", (0,), 0)
             for i in range(4)]
    assert sum(f is not None for f in fired) == 2
    assert fired[0] == draws[0]  # same seed, same first draw


def test_slowdown_episodes_window_and_compose():
    plan = FaultPlan(slowdowns=(
        Slowdown(device=1, at=1.0, until=2.0, factor=3.0),
        Slowdown(device=1, at=1.5, until=2.5, factor=5.0)))
    inj = FaultInjector(plan)
    assert inj.slow_factor(1, 0.5) == 1.0
    assert inj.slow_factor(1, 1.2) == 3.0
    assert inj.slow_factor(1, 1.8) == 5.0   # max over active episodes
    assert inj.slow_map((0, 1), 1.2) == {0: 1.0, 1: 3.0}
    assert inj.slow_map((0,), 1.2) is None  # all-1.0 -> no map


def test_device_health_quarantine_trips_after_n():
    health = DeviceHealth(FaultPlan(quarantine_after=2))
    assert not health.record_failure(3)
    assert health.record_failure(3)          # 2nd consecutive trips
    assert not health.record_failure(3)      # counter reset on trip
    health.record_success(3)
    assert not health.record_failure(3)      # success resets streak


# ---------------------------------------------------------------------------
# strict additivity: armed-but-empty plan is bit-identical
# ---------------------------------------------------------------------------


def test_empty_plan_bit_identical_to_fault_free():
    base, s_base = _run(faults=None)
    empty, s_empty = _run(faults=FaultPlan())
    assert _placements(s_base) == _placements(s_empty)
    assert _events(s_base) == _events(s_empty)
    assert base.horizon == empty.horizon
    assert {w: st.finish for w, st in base.stats.items()} \
        == {w: st.finish for w, st in empty.stats.items()}


def test_seeded_chaos_replay_bit_identical():
    plan = FaultPlan(
        seed=5,
        crashes=(DeviceCrash(device=1, at=3.0, recover_at=8.0),),
        slowdowns=(Slowdown(device=0, at=1.0, until=6.0, factor=3.0),),
        failures=(ShardFailure(wid="serve-prefix-000", sid="worker0"),),
        straggler_threshold=1.5)
    _, s1 = _run(faults=plan)
    _, s2 = _run(faults=plan)
    assert _events(s1) == _events(s2)


@functools.cache
def _chaos_script_runs():
    """The seeded chaos script (``chaos_fault_plan``: a crash with
    recovery, a 3x slowdown, two targeted shard failures) on the first
    8 arrivals of the overloaded trace, 6 devices: the smallest slice
    on which every fault class of the script fires.  Returns the
    trace, the fault-free result and the faulted result."""
    trace = overloaded_serving_trace(n_workflows=8, rate=14.0, seed=0,
                                     num_queries=8)
    base, _ = _run(faults=None, trace=trace, n_devices=6)
    chaos, _ = _run(faults=chaos_fault_plan(0), trace=trace,
                    n_devices=6)
    return trace, base, chaos


def test_chaos_script_completes_within_twice_fault_free_horizon():
    """Every admitted workflow completes under the chaos script, and
    its horizon stays within 2x the fault-free horizon."""
    trace, base, chaos = _chaos_script_runs()
    assert set(chaos.stats) == {wf.wid for _, wf in trace}
    assert not chaos.failed
    assert chaos.horizon <= 2.0 * base.horizon


def test_chaos_script_engages_every_fault_class():
    """The script is not vacuous on this trace: a device goes down,
    both targeted shards fail, and a straggler is detected."""
    _, _, chaos = _chaos_script_runs()
    assert chaos.device_downs >= 1
    assert chaos.shard_failures >= 2
    assert chaos.stragglers >= 1


# ---------------------------------------------------------------------------
# crash handling: failure-aware replanning off the dead device
# ---------------------------------------------------------------------------


def test_crash_completes_all_and_avoids_dead_device():
    base, _ = _run(faults=None)
    t_crash = 0.3 * base.horizon
    t_up = 0.7 * base.horizon
    plan = FaultPlan(crashes=(DeviceCrash(device=2, at=t_crash,
                                          recover_at=t_up),))
    res, sched = _run(faults=plan)
    assert set(res.stats) == set(base.stats)
    assert not res.failed
    assert res.device_downs == 1
    downs = [e for e in sched.events if isinstance(e, DeviceDownEvent)]
    ups = [e for e in sched.events
           if isinstance(e, DeviceRecoveredEvent)]
    assert [(e.device, e.reason) for e in downs] == [(2, "crash")]
    assert [e.device for e in ups] == [2]
    # nothing is issued onto the dead device during the outage
    for e in sched.events:
        if isinstance(e, IssueEvent) and t_crash <= e.t < t_up:
            assert 2 not in e.devices, e
    # in-flight stages on the device at crash time failed over
    assert res.shard_failures >= 0  # 0 is legal: device may be idle


def test_crash_without_recovery_still_completes():
    base, _ = _run(faults=None)
    plan = FaultPlan(crashes=(DeviceCrash(device=0,
                                          at=0.25 * base.horizon),))
    res, sched = _run(faults=plan)
    assert set(res.stats) == set(base.stats)
    assert not res.failed
    # the reduced cluster is slower, never faster
    assert res.horizon >= base.horizon - 1e-9
    for e in sched.events:
        if isinstance(e, IssueEvent) and e.t >= 0.25 * base.horizon:
            assert 0 not in e.devices, e


# ---------------------------------------------------------------------------
# transient shard failures: retry with backoff, give-up, quarantine
# ---------------------------------------------------------------------------


def test_transient_failure_retries_and_completes():
    plan = FaultPlan(failures=(
        ShardFailure(wid="serve-prefix-000", sid="worker0",
                     at_fraction=0.5),))
    base, _ = _run(faults=None)
    res, sched = _run(faults=plan)
    assert set(res.stats) == set(base.stats)
    assert not res.failed
    assert res.shard_failures == 1
    assert res.retries == 1
    fails = [e for e in sched.events if isinstance(e, ShardFailedEvent)]
    retries = [e for e in sched.events if isinstance(e, RetryEvent)]
    assert [(e.wid, e.sid, e.reason) for e in fails] \
        == [("serve-prefix-000", "worker0", "transient")]
    assert [(e.wid, e.sid, e.attempt) for e in retries] \
        == [("serve-prefix-000", "worker0", 1)]
    # the retry fires exactly one backoff after the failure
    assert retries[0].t == pytest.approx(fails[0].t + plan.backoff(1))


def test_give_up_after_retry_budget_exhausted():
    plan = FaultPlan(failures=(
        ShardFailure(wid="serve-prefix-000", sid="worker0"),),
        max_retries=0)
    res, sched = _run(faults=plan)
    assert res.failed == ["serve-prefix-000"]
    assert "serve-prefix-000" not in res.stats
    gave_up = [e for e in sched.events
               if isinstance(e, DegradedEvent) and e.kind == "gave_up"]
    assert [(e.wid, e.sid) for e in gave_up] \
        == [("serve-prefix-000", "worker0")]
    # everyone else still completes, and accounting stays closed
    assert len(res.stats) == len(_trace()) - 1
    assert res.n_offered == len(_trace())


def test_quarantine_lifecycle():
    plan = FaultPlan(failures=(
        ShardFailure(wid="serve-prefix-000", sid="worker0"),),
        quarantine_after=1, quarantine_s=0.5)
    res, sched = _run(faults=plan)
    assert not res.failed
    downs = [e for e in sched.events if isinstance(e, DeviceDownEvent)
             if e.reason == "quarantine"]
    ups = [e for e in sched.events
           if isinstance(e, DeviceRecoveredEvent)]
    assert len(downs) >= 1
    assert res.device_downs == len(downs)
    for d in downs:
        assert d.recover_at == pytest.approx(d.t + 0.5)
        assert any(u.device == d.device
                   and u.t == pytest.approx(d.recover_at) for u in ups)


# ---------------------------------------------------------------------------
# stragglers: detection + speculative re-issue
# ---------------------------------------------------------------------------


def test_straggler_detection_and_speculation():
    base, _ = _run(faults=None)
    plan = FaultPlan(slowdowns=(
        Slowdown(device=1, at=0.0, until=base.horizon * 2.0,
                 factor=6.0),),
        straggler_threshold=1.5)
    res, sched = _run(faults=plan)
    assert set(res.stats) == set(base.stats)
    assert not res.failed
    assert res.stragglers >= 1
    assert res.speculations >= 1
    straggler_evs = [e for e in sched.events
                     if isinstance(e, DegradedEvent)
                     and e.kind == "straggler"]
    assert len(straggler_evs) == res.stragglers
    # speculation never lands on the straggling device itself
    for ev in straggler_evs:
        assert ev.device is not None


def test_speculation_disabled_still_completes():
    base, _ = _run(faults=None)
    plan = FaultPlan(slowdowns=(
        Slowdown(device=1, at=0.0, until=base.horizon * 2.0,
                 factor=6.0),),
        straggler_threshold=1.5, speculate=False)
    res, _ = _run(faults=plan)
    assert set(res.stats) == set(base.stats)
    assert res.stragglers >= 1
    assert res.speculations == 0


# ---------------------------------------------------------------------------
# degraded admission: floors conditioned on the live device set
# ---------------------------------------------------------------------------


def test_stage_floor_costs_live_subset():
    trace = _trace(n=2)
    wf = trace[0][1]
    cluster = heterogeneous_cluster(4)
    top = max(d.speed for d in cluster.devices)
    live = [d.did for d in cluster.devices if d.speed < top]
    assert live, "heterogeneous cluster must have slow devices"
    full = stage_floor_costs(wf, cluster)
    reduced = stage_floor_costs(wf, cluster, live=live)
    assert all(reduced[s] >= full[s] for s in full)
    assert any(reduced[s] > full[s] for s in full)
    # every-eligible-device-down falls back to the full set (finite)
    assert stage_floor_costs(wf, cluster, live=[]) == full


def test_admission_caches_invalidate_on_fault_epoch():
    adm = AdmissionController(SLOConfig())
    state = fresh_state(homogeneous_cluster(4))
    adm._floor["x"] = {"s": 1.0}
    adm._tails["x"] = {"s": 1.0}
    state.mark_down(2)
    adm._sync_fault_epoch(state)
    assert adm._floor == {} and adm._tails == {}
    assert adm._fault_epoch == state.fault_epoch
    # no further change -> caches survive the next sync
    adm._floor["y"] = {"s": 2.0}
    adm._sync_fault_epoch(state)
    assert "y" in adm._floor


def test_state_mark_down_up_lifecycle():
    state = fresh_state(homogeneous_cluster(4))
    state.set_resident(2, "qwen-7b")
    state.warm_prefix(2, "g0", "qwen-7b", 4, 0.0)
    ep0 = state.fault_epoch
    state.mark_down(2, wipe=True)
    assert state.down == {2}
    assert state.live_ids() == [0, 1, 3]
    assert state.n_live == 3
    assert state.fault_epoch == ep0 + 1
    assert state.resident_model(2) is None
    assert state.prefix.get(2) in (None, {})
    ov = state.overlay()
    assert ov.down == {2} and ov.fault_epoch == state.fault_epoch
    state.mark_up(2)
    assert state.down == set()
    assert state.fault_epoch == ep0 + 2
    assert state.live_ids() == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# bounded buffers: scheduler event ring + admission probe log
# ---------------------------------------------------------------------------


def test_event_log_ring_buffer_unit():
    log = EventLog(maxlen=3)
    for i in range(5):
        log.append(("ev", i))
    assert len(log) == 3
    assert log.n_total == 5
    assert log.n_dropped == 2
    assert list(log) == [("ev", 2), ("ev", 3), ("ev", 4)]
    assert log.since(4) == [("ev", 4)]
    assert log.since(0) == list(log)     # dropped prefix is skipped
    assert log == [("ev", 2), ("ev", 3), ("ev", 4)]
    with pytest.raises(ValueError):
        EventLog(maxlen=0)


def test_scheduler_event_buffer_caps_memory_not_stream():
    cap = 40
    res_u, s_unbounded = _run()
    res_b, s_bounded = _run(event_buffer=cap)
    assert len(s_unbounded.events) > cap          # cap actually binds
    assert len(s_bounded.events) <= cap
    assert s_bounded.events.n_total == len(s_unbounded.events)
    # the retained suffix is exactly the unbounded tail
    assert list(s_bounded.events) \
        == list(s_unbounded.events)[-len(s_bounded.events):]
    # outcomes are untouched by the cap
    assert {w: st.finish for w, st in res_b.stats.items()} \
        == {w: st.finish for w, st in res_u.stats.items()}


def test_stream_and_handlers_see_every_event_despite_cap():
    # reference run: how many events does this trace emit per type?
    _, ref = _run()
    n_issues = sum(1 for e in ref.events if isinstance(e, IssueEvent))
    # tiny ring: on() handlers fire at emit time, BEFORE any eviction,
    # so they see every event even when stream() (which reads the
    # buffer between steps) can only surface the retained suffix
    sched = Scheduler(homogeneous_cluster(4),
                      SchedulerConfig(policy="FATE", event_buffer=16))
    seen_issues = []
    sched.on(IssueEvent, seen_issues.append)
    for t, wf in _trace():
        sched.submit(wf, at=t)
    streamed = list(sched.stream())
    assert len(seen_issues) == n_issues
    assert len(sched.events) <= 16
    assert 0 < len(streamed) <= sched.events.n_total
    assert sched.events.n_total == ref.events.n_total
    # ample ring: stream() surfaces every event, same as unbounded
    big = Scheduler(homogeneous_cluster(4),
                    SchedulerConfig(policy="FATE",
                                    event_buffer=ref.events.n_total))
    for t, wf in _trace():
        big.submit(wf, at=t)
    assert len(list(big.stream())) == ref.events.n_total


def test_admission_probe_log_cap():
    trace = _trace(n=8)
    sched = Scheduler(homogeneous_cluster(4),
                      SchedulerConfig(policy="FATE",
                                      slo=SLOConfig(probe_log_limit=3)))
    for t, wf in trace:
        sched.submit(wf, at=t)
    sched.drain()
    adm = sched.admission
    assert len(adm.probe_log) <= 3
    uncapped = Scheduler(homogeneous_cluster(4),
                         SchedulerConfig(policy="FATE",
                                         slo=SLOConfig()))
    for t, wf in trace:
        uncapped.submit(wf, at=t)
    uncapped.drain()
    assert len(uncapped.admission.probe_log) > 3
    # the retained records are the newest ones
    assert [r.wid for r in adm.probe_log] \
        == [r.wid for r in uncapped.admission.probe_log][-len(adm.probe_log):]


def test_slo_config_roundtrips_probe_log_limit():
    cfg = SchedulerConfig(policy="FATE",
                          slo=SLOConfig(probe_log_limit=7))
    back = SchedulerConfig.from_json(cfg.to_json())
    assert back.slo.probe_log_limit == 7


# ---------------------------------------------------------------------------
# engine-level fault injection (real-execution mirror)
# ---------------------------------------------------------------------------


def test_engine_retries_injected_transient_failure():
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.configs.archs import SMOKE
    from repro.core.policies import make_policy
    from repro.core.workflow import Stage, Workflow
    from repro.serving.engine import ModelBundle, ServingEngine

    wf = Workflow(wid="w", stages={
        "a": Stage(sid="a", model="m", base_cost={-1: 0.01}),
        "b": Stage(sid="b", model="m", base_cost={-1: 0.01},
                   parents=("a",)),
    }, num_queries=2)
    bundle = ModelBundle.create("m", SMOKE["qwen3-1.7b"])
    plan = FaultPlan(failures=(ShardFailure(wid="w", sid="a"),),
                     max_retries=2)
    eng = ServingEngine({"m": bundle}, n_devices=2,
                        faults=FaultInjector(plan))
    state = fresh_state(homogeneous_cluster(2))
    prompts = jnp.zeros((2, 8), jnp.int32)
    results = eng.run_workflow(wf, make_policy("RoundRobin"), state,
                               prompts)
    assert set(results) == {"a", "b"}
    assert eng.n_fault_retries == 1

    # with a zero retry budget the failure escapes
    eng2 = ServingEngine({"m": bundle}, n_devices=2,
                         faults=FaultInjector(FaultPlan(
                             failures=(ShardFailure(wid="w", sid="a"),),
                             max_retries=0)))
    state2 = fresh_state(homogeneous_cluster(2))
    with pytest.raises(TransientStageFailure):
        eng2.run_workflow(wf, make_policy("RoundRobin"), state2,
                          prompts)
