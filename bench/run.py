"""Run one cell of the benchmark once, on the chips of this machine.

    python bench/run.py --workload pair.agentic.steady --seed 7 \\
        --seconds 30 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window's start.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and with ``--trace 1`` ``breakdown``),
whose last key, ``check``, holds each number compared beside its limit;
the same numbers are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, it exits nonzero and prints no
result.  JAX's compilation cache is ``JAX_COMPILATION_CACHE_DIR`` where
that is set, else ``.jax_cache`` in the checkout.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", type=Path, default=None,
                    help="directory for the reduced trace (trace.json)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    import spec
    try:
        cell = spec.load_cell(args.workload)
    except (KeyError, OSError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        from repro.jax_cache import use_compile_cache
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    import jax
    # every program, however quick to compile, is kept, so that only the
    # first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r}); "
              f"the benchmark runs on the chip only", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    print(f"device: {devices[0].device_kind} x {len(devices)}, compile "
          f"cache {cache_dir}", flush=True)
    import harness
    if args.out is not None:
        args.out.mkdir(parents=True, exist_ok=True)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices, PROCESS_START,
                              args.out)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
