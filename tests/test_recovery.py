"""Durable control plane: event serialization, write-ahead journal,
snapshot/restore, and deterministic crash recovery.

The contract under test, in order of importance:

* every :class:`~repro.core.scheduler.SchedulerEvent` subclass
  round-trips through ``to_dict``/``from_dict`` (via JSON) exactly;
  unknown types and foreign schema versions are REJECTED, extra keys
  (the journal's ``"i"`` tag) are ignored;
* the :class:`~repro.core.journal.EventJournal` is a contiguous prefix
  of the event stream: gap appends raise, rotation preserves read
  order, a torn final line (mid-append crash) is detected and
  truncated while corruption anywhere else raises
  :class:`~repro.core.journal.JournalError`;
* ``Scheduler.snapshot()`` + ``Scheduler.restore()`` resume a mid-run
  scheduler whose drained result is BIT-IDENTICAL to the uninterrupted
  run — with or without a journal tail to replay — and
  ``audit_invariants`` stays clean throughout;
* lifecycle: submissions are refused after ``drain()`` and on restored
  schedulers; snapshots are refused for dependency-injected runs.
"""
import dataclasses
import functools
import json

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # offline container
    from _fallback_hypothesis import given, settings, strategies as st

from repro.core.admission import SLOConfig
from repro.core.devices import homogeneous_cluster
from repro.core.journal import EventJournal, JournalError
from repro.core.scheduler import (EVENT_REGISTRY, EVENT_SCHEMA_VERSION,
                                  EVENT_TYPES, CompletionEvent,
                                  EventLog, IssueEvent, Scheduler,
                                  SchedulerConfig, SchedulerEvent,
                                  audit_invariants)
from repro.core.workflow import DEFAULT_PROFILES
from repro.workflowbench.suites import (chaos_fault_plan,
                                        overloaded_serving_trace)


# ---------------------------------------------------------------------------
# event serialization
# ---------------------------------------------------------------------------

_FIELD_VALUES = {
    "float": st.floats(min_value=0.0, max_value=1e5),
    "int": st.integers(min_value=0, max_value=64),
    "str": st.sampled_from(["w0", "w1", "stage-2", "crash", ""]),
    "bool": st.booleans(),
    "tuple": st.lists(st.integers(min_value=0, max_value=15),
                      min_size=0, max_size=4),
}


def _field_strategy(annotation: str):
    if annotation.startswith("Optional["):
        return st.one_of(st.none(), _field_strategy(annotation[9:-1]))
    return _FIELD_VALUES[annotation]


def _draw_event(data, cls):
    kwargs = {}
    for f in dataclasses.fields(cls):
        v = data.draw(_field_strategy(f.type), label=f.name)
        kwargs[f.name] = tuple(v) if isinstance(v, list) else v
    return cls(**kwargs)


@settings(max_examples=30)
@given(st.data())
def test_every_event_type_round_trips_through_json(data):
    """Property: for EVERY registered event type, random field values
    survive to_dict -> json -> from_dict exactly (including tuple
    coercion and None optionals)."""
    for cls in EVENT_TYPES:
        ev = _draw_event(data, cls)
        doc = json.loads(json.dumps(ev.to_dict(), sort_keys=True))
        back = SchedulerEvent.from_dict(doc)
        assert type(back) is cls
        assert back == ev


def test_registry_covers_every_event_type():
    assert set(EVENT_REGISTRY.values()) == set(EVENT_TYPES)
    assert all(EVENT_REGISTRY[c.__name__] is c for c in EVENT_TYPES)


def test_from_dict_rejects_unknown_type():
    doc = {"event_version": EVENT_SCHEMA_VERSION,
           "type": "NotARealEvent", "t": 0.0}
    with pytest.raises(ValueError, match="unknown event type"):
        SchedulerEvent.from_dict(doc)


def test_from_dict_rejects_future_schema_version():
    doc = CompletionEvent(t=1.0, wid="w", sid="s").to_dict()
    doc["event_version"] = EVENT_SCHEMA_VERSION + 1
    with pytest.raises(ValueError, match="schema version"):
        SchedulerEvent.from_dict(doc)
    doc.pop("event_version")            # missing version is also foreign
    with pytest.raises(ValueError, match="schema version"):
        SchedulerEvent.from_dict(doc)


def test_from_dict_ignores_extra_keys():
    doc = IssueEvent(t=2.0, wid="w", sid="s", devices=(0, 1),
                     start=2.0, finish=3.5).to_dict()
    doc["i"] = 17                        # the journal's index tag
    doc["unknown_future_field"] = "x"
    ev = SchedulerEvent.from_dict(doc)
    assert ev == IssueEvent(t=2.0, wid="w", sid="s", devices=(0, 1),
                            start=2.0, finish=3.5)
    assert ev.devices == (0, 1)          # list -> tuple coercion


# ---------------------------------------------------------------------------
# event journal
# ---------------------------------------------------------------------------

def _events(n, start_t=0.0):
    return [CompletionEvent(t=start_t + i, wid=f"w{i}", sid="s")
            for i in range(n)]


def test_journal_append_read_round_trip(tmp_path):
    j = EventJournal(tmp_path)
    evs = _events(5)
    j.append_batch(evs[:3], 0)
    j.append_batch(evs[3:], 3)
    assert j.next_index == 5
    got = j.entries()
    assert [i for i, _ in got] == [0, 1, 2, 3, 4]
    assert [e for _, e in got] == evs
    assert [e for _, e in j.entries(3)] == evs[3:]


def test_journal_rejects_gap_appends(tmp_path):
    j = EventJournal(tmp_path)
    j.append_batch(_events(2), 0)
    with pytest.raises(JournalError, match="non-contiguous"):
        j.append_batch(_events(1), 5)
    with pytest.raises(JournalError, match="non-contiguous"):
        j.append_batch(_events(1), 1)    # replays are refused too


def test_journal_rotation_preserves_order(tmp_path):
    j = EventJournal(tmp_path, rotate_bytes=200)
    for k in range(10):
        j.append_batch(_events(1, start_t=float(k)), k)
    segs = sorted(tmp_path.glob("events-*.jsonl"))
    assert len(segs) > 1                 # rotation actually engaged
    j2 = EventJournal(tmp_path)          # cold reopen walks all segments
    assert j2.next_index == 10
    assert [i for i, _ in j2.entries()] == list(range(10))


def test_journal_torn_tail_is_truncated_on_reopen(tmp_path):
    j = EventJournal(tmp_path)
    j.append_batch(_events(4), 0)
    seg = sorted(tmp_path.glob("events-*.jsonl"))[-1]
    with seg.open("a") as fh:            # simulated mid-append crash
        fh.write('{"event_version": 1, "type": "Comple')
    j2 = EventJournal(tmp_path)
    assert j2.recovered_torn_tail
    assert j2.next_index == 4            # the 4 good events survive
    assert len(j2.entries()) == 4
    j2.append_batch(_events(1), 4)       # appends resume cleanly
    assert not EventJournal(tmp_path).recovered_torn_tail


def test_journal_mid_file_corruption_raises(tmp_path):
    j = EventJournal(tmp_path, rotate_bytes=200)
    for k in range(10):
        j.append_batch(_events(1, start_t=float(k)), k)
    first = sorted(tmp_path.glob("events-*.jsonl"))[0]
    lines = first.read_text().splitlines()
    lines[0] = '{"garbage": true}'       # NOT a torn tail: mid-journal
    first.write_text("\n".join(lines) + "\n")
    with pytest.raises(JournalError, match="corrupt journal entry"):
        EventJournal(tmp_path)


def test_snapshot_store_prunes_and_returns_latest(tmp_path):
    j = EventJournal(tmp_path)
    assert j.latest_snapshot() is None
    for n in (3, 7, 12):
        j.write_snapshot({"snapshot_version": 1, "mark": n,
                          "events": {"n_total": n}})
    snaps = sorted(tmp_path.glob("snapshot-*.json"))
    assert len(snaps) == 2               # keep=2 pruned the oldest
    assert j.latest_snapshot()["mark"] == 12


# ---------------------------------------------------------------------------
# EventLog.since hardening
# ---------------------------------------------------------------------------

def test_event_log_since_rejects_out_of_range_cursors():
    log = EventLog(maxlen=4)
    for ev in _events(6):
        log.append(ev)
    assert log.n_total == 6 and log.n_dropped == 2
    assert log.since(6) == []            # exactly-at-the-end is legal
    assert len(log.since(4)) == 2
    assert log.since(0) == list(log)     # evicted prefix: silent window
    with pytest.raises(ValueError, match="must be >= 0"):
        log.since(-1)
    with pytest.raises(ValueError, match="past the end"):
        log.since(7)


# ---------------------------------------------------------------------------
# snapshot / restore / lifecycle
# ---------------------------------------------------------------------------

def _trace():
    return overloaded_serving_trace(n_workflows=8, rate=14.0, seed=0,
                                    num_queries=4)


def _config():
    return SchedulerConfig(policy="FATE", slo=SLOConfig(),
                           faults=chaos_fault_plan(0))


def _fingerprint(res, sched):
    return {
        "stats": {w: (s.arrival, s.finish, tuple(s.query_completion),
                      s.deadline) for w, s in res.stats.items()},
        "rejected": tuple(res.rejected),
        "failed": tuple(res.failed),
        "horizon": res.horizon,
        "counters": (res.replans, res.preemptions, res.deferrals,
                     res.max_in_flight, res.device_downs,
                     res.shard_failures, res.retries, res.stragglers,
                     res.speculations),
        "n_events": sched.events.n_total,
    }


def _baseline():
    sched = Scheduler(homogeneous_cluster(4), _config())
    for t, wf in _trace():
        sched.submit(wf, at=t)
    res = sched.drain()
    return _fingerprint(res, sched), sched


def _run_until(sched, n_events):
    while sched.events.n_total < n_events and sched.step():
        pass


def test_submit_after_drain_raises():
    _, sched = _baseline()
    t, wf = _trace()[0]
    with pytest.raises(RuntimeError, match="lifecycle"):
        sched.submit(wf, at=t)


def test_snapshot_refused_for_injected_dependencies():
    sched = Scheduler(homogeneous_cluster(4), _config(),
                      world_profiles=dict(DEFAULT_PROFILES))
    with pytest.raises(ValueError, match="injected"):
        sched.snapshot()


def test_snapshot_restore_without_journal_is_bit_identical():
    base_fp, _ = _baseline()
    sched = Scheduler(homogeneous_cluster(4), _config())
    for t, wf in _trace():
        sched.submit(wf, at=t)
    _run_until(sched, base_fp["n_events"] // 2)
    snap = json.loads(json.dumps(sched.snapshot()))   # force plain JSON
    restored = Scheduler.restore(snap)
    assert audit_invariants(restored) == []
    res = restored.drain()
    assert audit_invariants(restored) == []
    assert _fingerprint(res, restored) == base_fp


def test_snapshot_document_round_trips_through_restore():
    sched = Scheduler(homogeneous_cluster(4), _config())
    for t, wf in _trace():
        sched.submit(wf, at=t)
    _run_until(sched, 120)
    snap = sched.snapshot()
    restored = Scheduler.restore(json.loads(json.dumps(snap)))
    again = restored.snapshot()
    snap.pop("lifecycle"), again.pop("lifecycle")
    assert json.loads(json.dumps(again)) == json.loads(json.dumps(snap))


def test_restore_rejects_foreign_snapshot_version():
    sched = Scheduler(homogeneous_cluster(4), _config())
    for t, wf in _trace():
        sched.submit(wf, at=t)
    snap = sched.snapshot()
    snap["snapshot_version"] = 99
    with pytest.raises(ValueError, match="snapshot version"):
        Scheduler.restore(snap)


def test_crash_restore_with_journal_replay_is_bit_identical(tmp_path):
    base_fp, _ = _baseline()
    journal = EventJournal(tmp_path, rotate_bytes=16 * 1024)
    sched = Scheduler(homogeneous_cluster(4), _config(),
                      journal=journal)
    for t, wf in _trace():
        sched.submit(wf, at=t)
    journal.write_snapshot(sched.snapshot())
    steps = 0
    while sched.events.n_total < int(base_fp["n_events"] * 0.4):
        if not sched.step():
            break
        steps += 1
        if steps % 15 == 0:
            journal.write_snapshot(sched.snapshot())
    killed_at = sched.events.n_total
    del sched, journal                   # crash: abandon in place

    reopened = EventJournal(tmp_path)
    snap = reopened.latest_snapshot()
    assert snap["events"]["n_total"] < killed_at   # a real tail to replay
    restored = Scheduler.restore(snap, reopened)
    assert restored.events.n_total == killed_at    # replay caught up
    assert audit_invariants(restored) == []
    t, wf = _trace()[0]
    with pytest.raises(RuntimeError, match="lifecycle"):
        restored.submit(wf, at=t)        # restored runs take no arrivals
    res = restored.drain()
    assert audit_invariants(restored) == []
    assert _fingerprint(res, restored) == base_fp
    # the journal kept recording through the post-restore drain
    assert reopened.next_index == base_fp["n_events"]


@functools.cache
def _baseline_fingerprint():
    return _baseline()[0]


@pytest.mark.parametrize("frac,torn", [(0.1, False), (0.3, False),
                                       (0.5, True), (0.7, False),
                                       (0.9, False)])
def test_crash_restore_at_swept_kill_points_is_bit_identical(
        tmp_path, frac, torn):
    """Kill the journaled chaos run at a swept share of its event
    stream (snapshots every 20 steps), reopen the journal cold and
    restore: the drained result is bit-identical to the uninterrupted
    run and the audits are clean.  One kill point also tears the
    journal's last line, which the reopen must detect."""
    base_fp = _baseline_fingerprint()
    journal = EventJournal(tmp_path, rotate_bytes=64 * 1024)
    sched = Scheduler(homogeneous_cluster(4), _config(),
                      journal=journal)
    for t, wf in _trace():
        sched.submit(wf, at=t)
    journal.write_snapshot(sched.snapshot())
    steps = 0
    while sched.events.n_total < max(1, int(base_fp["n_events"] * frac)):
        if not sched.step():
            break
        steps += 1
        if steps % 20 == 0:
            journal.write_snapshot(sched.snapshot())
    del sched, journal                   # crash: abandon in place
    if torn:
        seg = sorted(tmp_path.glob("events-*.jsonl"))[-1]
        with seg.open("a") as fh:        # simulated mid-append crash
            fh.write('{"event_version": 1, "type": "Sta')

    reopened = EventJournal(tmp_path)
    assert reopened.recovered_torn_tail == torn
    restored = Scheduler.restore(reopened.latest_snapshot(), reopened)
    assert audit_invariants(restored) == []
    res = restored.drain()
    assert audit_invariants(restored) == []
    assert _fingerprint(res, restored) == base_fp


def test_attach_journal_rejects_misaligned_cursor(tmp_path):
    journal = EventJournal(tmp_path)
    journal.append_batch(_events(3), 0)  # journal already holds 3 events
    sched = Scheduler(homogeneous_cluster(4), _config())
    with pytest.raises(JournalError):
        sched.attach_journal(journal)


# ---------------------------------------------------------------------------
# invariant auditor
# ---------------------------------------------------------------------------

def test_audit_clean_on_live_and_drained_schedulers():
    sched = Scheduler(homogeneous_cluster(4), _config())
    for t, wf in _trace():
        sched.submit(wf, at=t)
    _run_until(sched, 100)
    assert audit_invariants(sched) == []
    sched.drain()
    assert audit_invariants(sched) == []


def test_audit_detects_lost_inflight_work():
    sched = Scheduler(homogeneous_cluster(4), _config())
    for t, wf in _trace():
        sched.submit(wf, at=t)
    _run_until(sched, 100)
    sched.issued.add(("ghost", "s0"))    # issued with no run/heap event
    violations = audit_invariants(sched)
    assert any("ghost" in v for v in violations)


def test_audit_every_hook_runs_during_step():
    sched = Scheduler(homogeneous_cluster(4), _config(), audit_every=1)
    for t, wf in _trace():
        sched.submit(wf, at=t)
    res = sched.drain()                  # every step audited in-line
    assert res.stats or res.rejected
