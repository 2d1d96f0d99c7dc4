"""The wide map/reduce frontier the planner parity tests share.

``bench_workflow(32)`` on ``heterogeneous_cluster(16)`` at horizon 4 is
the repo's canonical parity configuration (32 ready workers × 16
devices, H = 4): each worker roots a fan-out subtree, so the horizon
tail has real downstream demand, and ``warmed_state`` makes every
scoring term (transfer, locality, prefix, residency) live.
"""
from repro.core.executor import fresh_state
from repro.core.workflow import Stage, Workflow

MODELS = ["qwen-7b", "deepseek-7b", "llama-8b", "llama-3b", "qwen-14b"]


def bench_workflow(width: int, depth: int = 3, fanout: int = 2,
                   num_queries: int = 16, wid: str | None = None
                   ) -> Workflow:
    """Map/reduce-style DAG: ``width`` parallel workers, each rooting a
    ``fanout**depth`` subtree (descendant demand for the horizon tail),
    fed by completed ingest stages (parent-location/transfer signals)."""
    stages: dict[str, Stage] = {}
    for i in range(width):
        stages[f"in{i}"] = Stage(f"in{i}", MODELS[i % 5],
                                 base_cost={-1: 0.05},
                                 output_tokens=256.0)
        stages[f"w{i}"] = Stage(
            f"w{i}", MODELS[(i + 1) % 5], max_shards=2,
            base_cost={-1: 0.1 + 0.01 * (i % 7)},
            prefix_group=f"g{i % 4}", shared_fraction=0.5,
            output_tokens=384.0,
            parents=(f"in{i}", f"in{(i + 1) % width}"))
        prev = [f"w{i}"]
        for lv in range(1, depth + 1):
            cur = []
            for pi, par in enumerate(prev):
                for b in range(fanout):
                    sid = f"c{i}_{lv}_{pi}_{b}"
                    stages[sid] = Stage(
                        sid, MODELS[(i + lv + b) % 5],
                        base_cost={-1: 0.08},
                        prefix_group=f"g{i % 4}",
                        output_tokens=256.0, parents=(par,))
                    cur.append(sid)
            prev = cur
    return Workflow(wid=wid or f"sched-bench-{width}", stages=stages,
                    num_queries=num_queries)


def warmed_state(wf: Workflow, width: int, cluster, profiles=None):
    """Ingest stages done, models resident, some prefixes warm — so every
    scoring term (transfer, locality, prefix, residency) is live."""
    state = fresh_state(cluster, profiles=profiles)
    for i in range(width):
        d = i % cluster.n
        state.output_loc[(wf.wid, f"in{i}")] = (d,)
        state.completed.add((wf.wid, f"in{i}"))
        state.residency[d] = MODELS[i % 5]
        state.warm_prefix(d, f"g{i % 4}", MODELS[(i + 1) % 5], 8, 0.0)
    return state
