"""HTTP serving gateway (serving/gateway.py).

Exercises the real asyncio server over loopback sockets: submission
status codes, NDJSON event streams that parse back through the
versioned ``SchedulerEvent.from_dict`` registry, the read-only metrics
endpoint, least-backlog replica spreading, and the headline contract —
a single-replica gateway fed a trace over HTTP is bit-identical
(events, placements, fingerprint) to driving the ``Scheduler``
directly.
"""
import dataclasses
import http.client
import json
import math

from repro.core.devices import homogeneous_cluster
from repro.core.scheduler import Scheduler, SchedulerConfig, \
    SchedulerEvent
from repro.serving.gateway import Gateway, GatewayServer, \
    scheduler_fingerprint
from repro.workflowbench.suites import poisson_serving_trace


def _config():
    return SchedulerConfig(policy="FATE")


def _gateway(replicas=1, n_devices=4):
    cluster = homogeneous_cluster(n_devices)
    cfg = _config()
    return Gateway(lambda: Scheduler(cluster, cfg), replicas=replicas)


def _request(port, method, target, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        payload = None if body is None else json.dumps(body)
        conn.request(method, target, body=payload)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _trace(n=6):
    return poisson_serving_trace(n_workflows=n, rate=6.0, seed=0,
                                 num_queries=4)


# -- endpoint status codes ----------------------------------------------


def test_submit_accepts_and_reports_placement_replica():
    with GatewayServer(_gateway()) as srv:
        t, wf = _trace(1)[0]
        status, body = _request(
            srv.port, "POST", "/v1/workflows",
            {"workflow": wf.to_dict(), "at": t})
        assert status == 202
        doc = json.loads(body)
        assert doc["wid"] == wf.wid
        assert doc["replica"] == 0
        assert doc["at"] == t


def test_malformed_submit_is_400_unknown_path_404():
    with GatewayServer(_gateway()) as srv:
        status, body = _request(srv.port, "POST", "/v1/workflows",
                                {"not_a_workflow": 1})
        assert status == 400
        assert "error" in json.loads(body)
        status, _ = _request(srv.port, "GET", "/v1/nope")
        assert status == 404


def test_events_for_unknown_wid_is_404():
    with GatewayServer(_gateway()) as srv:
        status, _ = _request(srv.port, "GET",
                             "/v1/workflows/ghost/events")
        assert status == 404


def test_submit_after_drain_is_409():
    with GatewayServer(_gateway()) as srv:
        t, wf = _trace(1)[0]
        status, _ = _request(srv.port, "POST", "/v1/workflows",
                             {"workflow": wf.to_dict(), "at": t})
        assert status == 202
        status, body = _request(srv.port, "POST", "/v1/drain")
        assert status == 200
        drained = json.loads(body)
        assert drained["replicas"][0]["completed"] == 1
        status, _ = _request(srv.port, "POST", "/v1/workflows",
                             {"workflow": wf.to_dict(), "at": t + 1})
        assert status == 409


# -- NDJSON event stream ------------------------------------------------


def test_event_stream_parses_and_terminates():
    """Every NDJSON line round-trips through the versioned event
    registry; the stream ends on (and includes) the workflow's
    terminal event; all lines concern the streamed wid."""
    with GatewayServer(_gateway()) as srv:
        t, wf = _trace(1)[0]
        _request(srv.port, "POST", "/v1/workflows",
                 {"workflow": wf.to_dict(), "at": t})
        status, body = _request(
            srv.port, "GET", f"/v1/workflows/{wf.wid}/events")
        assert status == 200
        lines = [json.loads(ln) for ln in body.splitlines() if ln]
        assert lines
        assert not any("error" in doc for doc in lines)
        events = [SchedulerEvent.from_dict(doc) for doc in lines]
        for ev in events:
            assert getattr(ev, "wid", None) == wf.wid \
                or getattr(ev, "trigger_wid", None) == wf.wid
        last = events[-1]
        assert type(last).__name__ == "CompletionEvent"
        assert last.workflow_done
        # the terminal event is the stream's end, not mid-stream
        assert sum(1 for e in events
                   if type(e).__name__ == "CompletionEvent"
                   and e.workflow_done) == 1


def test_metrics_endpoint_is_read_only():
    with GatewayServer(_gateway()) as srv:
        for t, wf in _trace(3):
            _request(srv.port, "POST", "/v1/workflows",
                     {"workflow": wf.to_dict(), "at": t})
        status, body = _request(srv.port, "GET", "/v1/metrics")
        assert status == 200
        doc = json.loads(body)
        # nothing stepped: the clock never moved, nothing completed
        assert doc["replicas"][0]["now"] == 0.0
        assert doc["replicas"][0]["submitted"] == 3
        assert doc["replicas"][0]["completed"] == 0
        assert doc["slo"]["n_offered"] == 0  # no completions yet
        status, body = _request(srv.port, "POST", "/v1/drain")
        doc = json.loads(body)
        assert doc["metrics"]["replicas"][0]["completed"] == 3
        assert doc["metrics"]["slo"]["n_completed"] == 3


def test_wall_clock_submissions_over_http_all_complete():
    """Submissions without ``at`` are stamped on the gateway's own
    clock, in order; the drained run completes every one of them with
    a finite P95 latency."""
    trace = _trace(6)
    with GatewayServer(_gateway()) as srv:
        stamps = []
        for _, wf in trace:
            status, body = _request(srv.port, "POST", "/v1/workflows",
                                    {"workflow": wf.to_dict()})
            assert status == 202
            stamps.append(json.loads(body)["at"])
        status, body = _request(srv.port, "POST", "/v1/drain")
        assert status == 200
    assert stamps == sorted(stamps) and stamps[0] >= 0.0
    slo = json.loads(body)["metrics"]["slo"]
    assert slo["n_completed"] == len(trace)
    assert math.isfinite(slo["p95_latency"])


# -- single-replica bit-parity ------------------------------------------


def _events(sched):
    return [(type(e).__name__, dataclasses.astuple(e))
            for e in sched.events]


def _placements(sched):
    return {k: (r.placement.devices, r.placement.shard_sizes,
                r.placement.model, r.start, r.finish)
            for k, r in sched.runs.items()}


def test_single_replica_http_parity_with_direct_scheduler():
    trace = _trace(6)
    cluster = homogeneous_cluster(4)
    direct = Scheduler(cluster, _config())
    for t, wf in trace:
        direct.submit(wf, at=t)
    direct.drain()

    gw = _gateway()
    with GatewayServer(gw) as srv:
        for t, wf in trace:
            status, _ = _request(
                srv.port, "POST", "/v1/workflows",
                {"workflow": wf.to_dict(), "at": t})
            assert status == 202
        status, body = _request(srv.port, "POST", "/v1/drain")
        assert status == 200
    via_http = gw.replicas[0].sched
    assert _events(direct) == _events(via_http)
    assert _placements(direct) == _placements(via_http)
    assert scheduler_fingerprint(direct) \
        == scheduler_fingerprint(via_http)
    assert json.loads(body)["replicas"][0]["fingerprint"] \
        == scheduler_fingerprint(direct)


# -- replica tier -------------------------------------------------------


def test_two_replicas_spread_by_least_backlog():
    gw = _gateway(replicas=2)
    for t, wf in _trace(6):
        gw.submit({"workflow": wf.to_dict(), "at": t})
    counts = [r.n_submitted for r in gw.replicas]
    assert sum(counts) == 6
    assert all(c > 0 for c in counts)
    res = gw.drain()
    # ownership maps every wid to the replica that completed it
    for wid, rep in gw._owner.items():
        assert wid in rep.sched.stats
    assert sum(r["completed"] for r in res["replicas"]) == 6
    assert res["metrics"]["slo"]["n_completed"] == 6


def test_gateway_from_config_reads_replica_count():
    cfg = SchedulerConfig(policy="FATE", gateway={"replicas": 3})
    gw = Gateway.from_config(homogeneous_cluster(4), cfg)
    assert len(gw.replicas) == 3
