"""The serving engine's own host time per stage: ``StageResult.wall_s``
less the time in which the stage's prefill and decode executions ran on
any chip (dispatch, ``device_put``, cache creation, the gather)."""
import calls
import trace_reduce


def read(view):
    gaps = []
    for rec, lo, hi in view.stages:
        cs = calls.of_stage(view, rec, lo, hi)
        if not cs:
            continue
        device = trace_reduce.covered(
            trace_reduce.merge((c.start, c.end) for c in cs), lo, hi)
        gaps.append(rec.wall_s - device * 1e-9)
    return sum(gaps) / len(gaps) * 1e3 if gaps else None
