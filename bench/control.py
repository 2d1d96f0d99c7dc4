"""Readings that the check's limits are set from, on the chip.

    python bench/control.py --workload pair.agentic.steady --seconds 6 \\
        --seeds 11 12 ... 22 --control-seeds 11 12 13 --fault-seeds 11 12 13

Everything runs in one process, one seed after another.  For each seed:
the cell's weights and prompts from the seed, a short window at the cell's
own load through the timed path, and the check's sample of the stages it
served, replayed and compared as a run compares them.  It prints one line
of JSON per seed with the check's numbers of the program (the readings of
a sound run); for the control seeds those of the control too (the
reference computed in float8 put in the program's place); and for the
fault seeds those of a window served with each of ``faults.FAULTS``
planted in the timed path.
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def readings(cell, seed: int, seconds: float, chips, steps: dict,
             fault=None, control: bool = False) -> dict:
    """The check's numbers of one short window of ``cell``."""
    import check
    import faults
    import harness
    from repro.core.devices import homogeneous_cluster
    from repro.core.executor import fresh_state
    from repro.core.policies import make_policy
    from workload import Traffic
    traffic = Traffic(cell.traffic)
    vocab = min(m.vocab for m in cell.models)
    bundles = harness.build_bundles(cell, seed, chips[0], steps)
    if fault is not None:
        faults.plant(bundles, fault)
    engine = harness.make_engine(bundles, cell.n_devices, traffic, chips)
    state = fresh_state(homogeneous_cluster(cell.n_devices))
    policy = harness.TimedPolicy(make_policy("FATE"))
    prompts = traffic.prompts(seed, len(traffic.arrivals(seconds)), vocab)
    harness.warm_up(engine, policy, state, traffic, bundles)
    _, wfs = harness.run_window(engine, policy, state, traffic, prompts,
                                seconds, None)
    records = list(engine.records)
    picked = check.sample(records, seed, chips[0].id)
    del engine, state, policy
    gc.collect()
    program = check.replay_all(bundles, picked, prompts, chips)
    del bundles
    gc.collect()
    out = {"workflows": len(wfs), "failed": sum(w.failed for w in wfs),
           "kinds": sorted(map(list, {check.kind(r, chips[0].id)
                                      for r in picked}))}
    out["gap"], out["logit_err"] = check.compare(cell, seed, picked, program,
                                                 prompts, chips[0])
    if control:
        out["control_gap"], out["control_logit_err"] = check.compare(
            cell, seed, picked, program, prompts, chips[0], fp8=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=())
    args = ap.parse_args(argv)
    import spec
    cell = spec.load_cell(args.workload)
    from repro.jax_cache import use_compile_cache
    use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    import faults
    chips = list(devices[:cell.chips])
    steps: dict = {}
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = {"seed": seed, **readings(cell, seed, args.seconds, chips,
                                        steps,
                                        control=seed in args.control_seeds)}
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    for seed in args.fault_seeds:
        for fault in faults.FAULTS:
            out = {"seed": seed, "fault": fault,
                   **readings(cell, seed, args.seconds, chips, steps,
                              fault=fault)}
            print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
