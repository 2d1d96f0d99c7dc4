"""Compile a configuration's prefill and decode steps for a described TPU
v5e, at a cell's shapes, without a chip.

    JAX_PLATFORMS=cpu python bench/aot_rehearse.py --workload pair.agentic.steady

For each model of the cell's configuration and each shard size the
window uses (all queries, and half of them), it lowers and compiles the
program's jitted prefill and decode steps against shapes committed to one
described chip, and prints each program's memory analysis.  The
compiler refuses here what it would refuse on the chip.
"""
import argparse
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    import spec
    from repro.models.families import build_model
    from repro.serving.engine import jit_steps
    from workload import Traffic
    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    traffic = Traffic(cell.traffic)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    q, p, g = traffic.queries, traffic.prompt_len, traffic.gen_len
    for m in cell.models:
        model = build_model(harness.arch_config(m))
        prefill, decode = jit_steps(model)
        params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        for batch in sorted({q, q // 2}):
            cache = on_chip(model.init_cache(batch, p + g, abstract=True))
            tokens = jax.ShapeDtypeStruct((batch, p), jnp.int32,
                                          sharding=chip)
            token = jax.ShapeDtypeStruct((batch, 1), jnp.int32,
                                         sharding=chip)
            pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
            for name, fn, xs in (("prefill", prefill, (params, tokens,
                                                       cache)),
                                 ("decode", decode, (params, token, cache,
                                                     pos))):
                mem = fn.lower(*xs).compile().memory_analysis()
                print(f"{m.alias} ({m.arch}) {name} batch={batch}: "
                      f"arguments={mem.argument_size_in_bytes} "
                      f"outputs={mem.output_size_in_bytes} "
                      f"temp={mem.temp_size_in_bytes}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
