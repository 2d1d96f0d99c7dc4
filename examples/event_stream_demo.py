"""Event-driven Scheduler API demo: typed config, submit/drain
lifecycle, and the replayable event stream.

Runs an overloaded Poisson trace under the SLO control plane, prints
control-plane decisions live from `on()` subscriptions, then replays
the event log to summarize the run — no accelerator required.

    PYTHONPATH=src python examples/event_stream_demo.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.core.admission import SLOConfig                    # noqa: E402
from repro.core.devices import homogeneous_cluster            # noqa: E402
from repro.core.scheduler import (AdmittedEvent,              # noqa: E402
                                  CompletionEvent, DeferredEvent,
                                  PreemptionEvent, RejectedEvent,
                                  Scheduler, SchedulerConfig)
from repro.workflowbench.suites import overloaded_serving_trace  # noqa: E402


def main() -> None:
    """Drive one overloaded trace through the Scheduler lifecycle."""
    config = SchedulerConfig(policy="FATE", slo=SLOConfig())
    print("config artifact (SchedulerConfig.from_json reproduces "
          "this run):")
    print("  " + " | ".join(config.to_json().split("\n")[1:4]))

    sched = Scheduler(homogeneous_cluster(6), config)
    sched.on(AdmittedEvent, lambda e: print(
        f"[{e.t:7.2f}s] admit  {e.wid} (deadline {e.deadline:.1f}s)"))
    sched.on(DeferredEvent, lambda e: print(
        f"[{e.t:7.2f}s] defer  {e.wid} "
        f"(predicted {e.predicted_latency:.1f}s)"))
    sched.on(RejectedEvent, lambda e: print(
        f"[{e.t:7.2f}s] reject {e.wid} ({e.reason})"))
    sched.on(PreemptionEvent, lambda e: print(
        f"[{e.t:7.2f}s] preempt: {e.n_revoked} commitments revoked "
        f"for {e.trigger_wid}"))

    for t, wf in overloaded_serving_trace(n_workflows=18, rate=14.0,
                                          seed=0, num_queries=8):
        sched.submit(wf, at=t)
    res = sched.drain()

    print(f"\ncompleted {len(res.stats)}/{res.n_offered} workflows, "
          f"attainment {res.slo_attainment:.2f}, "
          f"SLO goodput {res.goodput_slo_wps:.3f} wf/s, "
          f"{res.preemptions} preemptions")
    by_type: dict = {}
    for ev in sched.events:                    # replayable stream
        by_type[type(ev).__name__] = by_type.get(type(ev).__name__,
                                                 0) + 1
    done = [e for e in sched.events
            if isinstance(e, CompletionEvent) and e.workflow_done]
    print("event log: " + "  ".join(
        f"{k}={v}" for k, v in sorted(by_type.items())))
    print(f"workflow completions in stream: {len(done)}")


if __name__ == "__main__":
    main()
