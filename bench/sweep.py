"""Offered-rate sweep of one cell, to find its knee once, on the chip.

    python bench/sweep.py --workload pair.agentic.steady --seed 3 \\
        --seconds 20 --rates 2.5 3.0 3.3 3.6 4.0

One process sets the cell up once, then serves a window at each rate in
turn (open Poisson arrivals, the mix's own schedule scaled to the rate) on
the same engine, policy and state.  For each rate it prints the workflows
due, how many were still waiting when the window closed, the mean service
time and the latency quartiles.  The knee is the highest rate at which the
backlog does not grow over the window; the cell's mix offers 0.8 of it.
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import spec
    cell = spec.load_cell(args.workload)
    from repro.jax_cache import use_compile_cache
    use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("sweep: needs the cell's TPU chips", file=sys.stderr)
        return 2
    import harness
    import stats
    from repro.core.devices import homogeneous_cluster
    from repro.core.executor import fresh_state
    from repro.core.policies import make_policy
    from workload import Traffic
    chips = list(devices[:cell.chips])
    base = Traffic(cell.traffic)
    bundles = harness.build_bundles(cell, args.seed, chips[0])
    engine = harness.make_engine(bundles, cell.n_devices, base, chips)
    state = fresh_state(homogeneous_cluster(cell.n_devices))
    policy = harness.TimedPolicy(make_policy("FATE"))
    vocab = min(m.vocab for m in cell.models)
    harness.warm_up(engine, policy, state, base, bundles)
    print(f"setup_s={time.perf_counter() - PROCESS_START}", flush=True)
    for rate in args.rates:
        traffic = Traffic(dict(cell.traffic, rate_per_s=rate))
        n = len(traffic.arrivals(args.seconds))
        prompts = traffic.prompts(args.seed, n, vocab)
        t0, wfs = harness.run_window(engine, policy, state, traffic,
                                     prompts, args.seconds, None,
                                     wid=f"r{rate}")
        end = t0 + args.seconds
        lat = [w.finish - w.due for w in wfs]
        service = [w.finish - w.start for w in wfs]
        print(json.dumps({
            "rate": rate, "due": len(wfs),
            "waiting_at_close": sum(w.start > end for w in wfs),
            "done_in_window": sum(w.finish <= end for w in wfs),
            "service_mean_s": sum(service) / len(service),
            "p50_s": stats.nearest_rank(lat, 0.5),
            "p90_s": stats.nearest_rank(lat, 0.9),
            "max_s": max(lat)}), flush=True)
        engine.records.clear()
        engine.log.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
