"""One run of one cell: set-up, the measured window, the per-layer
reading and the check.

The window drives ``ServingEngine.run_workflow`` under
``make_policy("FATE")``: one engine, one policy and one ``ExecutionState``
for the whole run, as a deployment holds them.  Workflows arrive as the
traffic mix says; the engine serves them one at a time, in order of
arrival, so a workflow waits while the ones before it are served.  Its
latency runs from its due time to the moment its last stage's tokens are
ready.  Every workflow due in the window is served before the run ends.

The benchmark times the layers from its own side: a wrapper around the
policy times each ``plan`` call, a subclass of the engine each
``run_stage`` call, and each span is also a ``jax.profiler``
``TraceAnnotation``, so that it lies on the device trace's clock.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Any, Optional

import numpy as np

import calls
import check
import stats
import trace_reduce
import weights
from spec import BENCH, Cell, ModelSpec
from workload import Traffic

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the traced run traces this much of the window's start, in seconds: a
# whole window of operations is too large a trace to read within a run
TRACE_SECONDS = 8.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts the programs this process compiles or loads from the
    persistent cache."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


@dataclasses.dataclass
class StageRecord:
    index: int            # of the workflow in the run
    wid: str
    sid: str
    model: str
    device_ids: tuple
    chips: tuple          # chip id of each shard, in placement order
    shard_sizes: tuple
    switches: int
    start: float          # perf_counter
    end: float
    wall_s: float
    tokens: Any
    landed: bool          # tokens on the chip of the first shard


@dataclasses.dataclass
class WorkflowRecord:
    index: int
    wid: str
    due: float
    start: float
    finish: float
    failed: bool


def arch_config(m: ModelSpec):
    """The program's configuration of ``m``."""
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=m.arch, family="dense", num_layers=m.layers, d_model=m.d_model,
        num_heads=m.heads, num_kv_heads=m.kv_heads, d_ff=m.d_ff,
        vocab_size=m.vocab, head_dim=m.head_dim, attention="gqa",
        qk_norm=m.qk_norm, qkv_bias=m.qkv_bias, rope_theta=m.rope_theta,
        norm_eps=m.norm_eps, tie_embeddings=m.tied, dtype=m.dtype)


def build_bundles(cell: Cell, seed: int, home,
                  steps: Optional[dict] = None) -> dict:
    """One ``ModelBundle`` per model, with the benchmark's weights on the
    home chip; models of one shape share their jitted steps, kept in
    ``steps`` where it is given."""
    import jax

    from repro.models.families import build_model
    from repro.serving.engine import ModelBundle, jit_steps
    steps = {} if steps is None else steps
    bundles = {}
    for m in cell.models:
        shape = dataclasses.replace(m, alias="", seed_offset=0)
        if shape not in steps:
            model = build_model(arch_config(m))
            steps[shape] = (model, *jit_steps(model))
        model, prefill, decode = steps[shape]
        params = weights.init(m, seed, home)
        want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        if (jax.tree.map(lambda a: (a.shape, a.dtype), want)
                != jax.tree.map(lambda a: (a.shape, a.dtype), params)):
            raise RuntimeError(f"{m.alias}: the program's parameter layout "
                               f"is not the one weights.py makes")
        bundles[m.alias] = ModelBundle(m.alias, model.cfg, params, prefill,
                                       decode, model=model)
    jax.block_until_ready([b.params for b in bundles.values()])
    return bundles


def make_engine(bundles: dict, n_devices: int, traffic: Traffic, chips):
    import jax

    from repro.serving.engine import ServingEngine

    class TimedEngine(ServingEngine):
        """The program's engine, with each stage timed from outside."""

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.records: list[StageRecord] = []
            self.index = -1        # of the workflow being served

        def run_stage(self, wf, stage, placement, prompts, attempt=0):
            k = len(self.records)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"bench.stage#{k}"):
                res = super().run_stage(wf, stage, placement, prompts,
                                        attempt)
            t1 = time.perf_counter()
            chip_of = [self.devices[d].device for d in placement.devices]
            live = [(c, n) for c, n in zip(chip_of, placement.shard_sizes)
                    if n]
            self.records.append(StageRecord(
                self.index, wf.wid, stage.sid, stage.model,
                placement.devices, tuple(c.id for c, _ in live),
                tuple(n for _, n in live), res.switches, t0, t1,
                res.wall_s, res.tokens_out,
                res.tokens_out.devices() == {live[0][0]}))
            return res

    return TimedEngine(bundles, n_devices, gen_len=traffic.gen_len,
                       prompt_len=traffic.prompt_len, chips=chips)


class TimedPolicy:
    """The policy, with each ``plan`` call timed from outside."""

    def __init__(self, inner):
        self.inner = inner
        self.spans: list[tuple[float, float]] = []

    def plan(self, wf, state, ready):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.plan#{len(self.spans)}"):
            out = self.inner.plan(wf, state, ready)
        self.spans.append((t0, time.perf_counter()))
        return out

    def forget_workflow(self, wid: str) -> None:
        self.inner.forget_workflow(wid)


def warm_up(engine, policy, state, traffic: Traffic, models) -> None:
    """Run every program the window can use, each once: the compiled
    steps depend on the model, the shard's batch and its chip.  Each
    model runs with all queries on the first virtual device of each chip,
    and split in two over each virtual device and the next one in order,
    which puts a first shard, where the halves are gathered, on each chip.
    Then two whole workflows go through the planner."""
    from repro.core.planner import Placement
    n = len(engine.devices)
    q = traffic.queries
    firsts = {}
    for d, vd in enumerate(engine.devices):
        firsts.setdefault(vd.device, d)
    layouts = [((d,), (q,)) for d in firsts.values()]
    layouts += [((d, (d + 1) % n), (q // 2, q - q // 2))
                for d in firsts.values()]
    prompts = np.zeros((q, traffic.prompt_len), np.int32)
    wf = traffic.workflow("warm-up")
    stage = next(iter(wf.stages.values()))
    for alias in models:
        st = dataclasses.replace(stage, model=alias)
        for devs, sizes in layouts:
            engine.run_stage(wf, st, Placement(wf.wid, st.sid, devs, sizes),
                             prompts)
    for i in range(2):
        w = traffic.workflow(f"warm-up-{i}")
        engine.run_workflow(w, policy, state, prompts)
        policy.forget_workflow(w.wid)
    # the planner's state holds what the engine holds
    for d, vd in enumerate(engine.devices):
        if vd.resident is not None:
            state.set_resident(d, vd.resident)
    engine.log.clear()
    engine.records.clear()
    policy.spans.clear()


def peak_bytes(chips) -> int:
    return max(int((c.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for c in chips)


def load_metric(name: str):
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + "".join(ch if ch.isalnum() else "_"
                                  for ch in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Traced:
    """What a per-layer reader sees: the traced part of the window."""
    cell: Cell
    traffic: Traffic
    trace: dict                   # trace_reduce form
    lo: int                       # the traced window, trace clock (ns)
    hi: int
    stages: list                  # (StageRecord, start_ns, end_ns)
    plans: list                   # (start_ns, end_ns)
    workflows: list               # WorkflowRecord finished in the trace
    chips: list                   # chip ids of the cell
    peaks: dict
    models: dict                  # alias -> ModelSpec


def run_window(engine, policy, state, traffic: Traffic, prompts,
               seconds: float, trace_dir: Optional[str], wid: str = "wf"):
    """Serve the window; returns the window's start and the workflow
    records.  With ``trace_dir``, the profiler traces its first
    ``TRACE_SECONDS``."""
    import jax
    records: list[WorkflowRecord] = []
    tracing = trace_dir is not None
    if tracing:
        jax.profiler.start_trace(trace_dir)
        window_span = jax.profiler.TraceAnnotation("bench.window")
        window_span.__enter__()
    t0 = time.perf_counter()
    end = t0 + seconds
    due_at = ([t0 + a for a in traffic.arrivals(seconds)]
              if traffic.loop == "open" else None)
    i = 0
    while (i < len(due_at)) if due_at is not None else (
            time.perf_counter() < end):
        now = time.perf_counter()
        due = due_at[i] if due_at is not None else now
        if due > now:
            time.sleep(due - now)
        start = time.perf_counter()
        wf = traffic.workflow(f"{wid}-{i:05d}")
        engine.index = i
        failed = False
        try:
            engine.run_workflow(wf, policy, state, prompts[i])
        except Exception:               # a failure is counted, not fatal
            log(traceback.format_exc())
            failed = True
        finish = time.perf_counter()
        policy.forget_workflow(wf.wid)
        records.append(WorkflowRecord(i, wf.wid, due, start, finish, failed))
        if tracing and finish - t0 >= TRACE_SECONDS:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
        i += 1
    if time.perf_counter() < end:
        time.sleep(end - time.perf_counter())
    if tracing:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return t0, records


def end_to_end(cell: Cell, traffic: Traffic, t0: float, seconds: float,
               wfs, stages, setup_s: float) -> dict:
    lat = [float("inf") if w.failed else w.finish - w.due for w in wfs]
    values = {
        "wf_p90_s": stats.nearest_rank(lat, 0.9),
        "wf_p50_s": stats.nearest_rank(lat, 0.5),
        "tokens_per_s": stats.tokens_per_s([s.end for s in stages],
                                           traffic.tokens_per_stage(), t0,
                                           seconds),
        "setup_s": setup_s,
    }
    units = {"wf_p90_s": "s", "wf_p50_s": "s", "tokens_per_s": "tokens/s",
             "setup_s": "s"}
    return {k: {"value": values[k], "unit": units[k]}
            for k in cell.end_to_end}


def per_layer(cell: Cell, traffic: Traffic, trace: dict, engine_records,
              policy_spans, wfs, chips, device_kind: str):
    """The cell's per-layer metrics from the trace, plus ``busy_s``,
    ``window_s`` and the breakdown."""
    import peaks
    (_, lo, hi), = trace_reduce.host_spans(trace, "bench.window")
    stage_spans = {n: (s, e) for n, s, e in
                   trace_reduce.host_spans(trace, "bench.stage")}
    plan_spans = {n: (s, e) for n, s, e in
                  trace_reduce.host_spans(trace, "bench.plan")}
    stages = [(r, *stage_spans[f"bench.stage#{k}"])
              for k, r in enumerate(engine_records)
              if f"bench.stage#{k}" in stage_spans]
    plans = [plan_spans[f"bench.plan#{k}"] for k in range(len(policy_spans))
             if f"bench.plan#{k}" in plan_spans]
    traced_wf = {r.index for r, _, _ in stages}
    ids = [c.id for c in chips]
    view = Traced(cell, traffic, trace, lo, hi, stages, plans,
                  [w for w in wfs if w.index in traced_wf], ids,
                  peaks.peaks(device_kind),
                  {m.alias: m for m in cell.models})
    for kind in ("prefill", "decode"):
        if any(c.kind == kind for c in calls.traced(view)):
            log(f"{kind} steps are {calls.bound(view, kind)} bound")
    metrics = {}
    for entry in cell.per_layer:
        value = load_metric(entry["name"]).read(view)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    busy = [trace_reduce.busy(trace, c, lo, hi) * 1e-9 for c in ids]
    breakdown = {
        "device_ops": [list(x) for x in
                       trace_reduce.op_totals(trace, ids, lo, hi)],
        "idle_gaps": [list(x) for x in
                      trace_reduce.idle_gaps(trace, ids, lo, hi)],
    }
    return metrics, sum(busy) / len(busy), (hi - lo) * 1e-9, breakdown


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             process_start: float, out_dir: Optional[Path] = None) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object.
    ``out_dir``, where given, receives the reduced trace."""
    from repro.core.devices import homogeneous_cluster
    from repro.core.executor import fresh_state
    from repro.core.policies import make_policy
    counter = CompileCounter()
    chips = list(devices[:cell.chips])
    traffic = Traffic(cell.traffic)
    bundles = build_bundles(cell, seed, chips[0])
    engine = make_engine(bundles, cell.n_devices, traffic, chips)
    state = fresh_state(homogeneous_cluster(cell.n_devices))
    policy = TimedPolicy(make_policy("FATE"))
    vocab = min(m.vocab for m in cell.models)
    n_due = (len(traffic.arrivals(seconds)) if traffic.loop == "open"
             else int(seconds * 100) + 1)
    prompts = traffic.prompts(seed, n_due, vocab)
    warm_up(engine, policy, state, traffic, bundles)
    compiled_before = counter.count
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    setup_s = time.perf_counter() - process_start
    t0, wfs = run_window(engine, policy, state, traffic, prompts, seconds,
                         trace_dir)
    in_window = counter.count - compiled_before
    stages = list(engine.records)
    n_failed = sum(w.failed for w in wfs)
    log(f"window: {len(wfs)} workflows due, {n_failed} failed, "
        f"{len(stages)} stages, compiles_in_window={in_window}, "
        f"last finish {wfs[-1].finish - t0 if wfs else 0.0:.3f} s after "
        f"the window's start")
    print(f"compiles_in_window={in_window}", flush=True)
    result: dict = {"correct": False, "attempted": len(wfs),
                    "failed": n_failed}
    if trace:
        reduced = trace_reduce.load(
            next(Path(trace_dir).rglob("*.xplane.pb")))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if out_dir is not None:
            trace_reduce.save(reduced, out_dir / "trace.json")
        metrics, busy_s, window_s, breakdown = per_layer(
            cell, traffic, reduced, stages, policy.spans, wfs, chips,
            chips[0].device_kind)
        result["breakdown"] = breakdown
    else:
        metrics = end_to_end(cell, traffic, t0, seconds, wfs, stages,
                             setup_s)
    result["metrics"] = metrics
    dev = devices[0]
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices),
                        "memory_peak_bytes": peak_bytes(chips)}
    if trace:
        result["device"].update(busy_s=busy_s, window_s=window_s)

    # the check: the engine's copies of the weights go first, then the
    # program's steps are replayed from the home weights, which go in turn,
    # so that neither sets the memory peak and the reference has room
    t_ref = time.perf_counter()
    picked = check.sample(stages, seed, chips[0].id)
    numbers = check.exact_numbers(stages, n_failed, traffic.queries,
                                  traffic.gen_len, vocab)
    del engine, state, policy
    gc.collect()
    program = check.replay_all(bundles, picked, prompts, chips)
    del bundles
    gc.collect()
    numbers += check.model_numbers(*check.compare(
        cell, seed, picked, program, prompts, chips[0]))
    log(f"check: {len(picked)} stages, "
        f"{len(picked) * traffic.queries * traffic.gen_len} served tokens, "
        f"kinds {sorted({check.kind(r, chips[0].id) for r in picked})}, "
        f"check_s={time.perf_counter() - t_ref:.3f}")
    result["correct"] = all(n.ok for n in numbers)
    result["check"] = {n.name: {"value": n.value, "limit": n.limit}
                       for n in numbers}
    for n in numbers:
        log(f"check {n.name}: {n.value} (limit {n.limit})"
            f"{'' if n.ok else ' FAILED'}")
    return result
