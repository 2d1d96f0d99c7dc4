"""End-to-end driver: serve a heterogeneous workflow of REAL models with
batched requests, scheduled by FATE on virtual devices.

Two reduced-config models (qwen3-style, glm4-style) execute a
retrieval -> 2x worker -> merge DAG over a batch of 8 queries: real
prefill + autoregressive decode per stage, model residency switches,
and prefix-cache reuse on the serving engine.

    PYTHONPATH=src python examples/serve_workflow.py

It runs on JAX's default backend.  The benchmark serves the same DAG
at full Qwen1.5 width on a TPU: ``python3 bench/run.py --workload
pair.agentic.steady --seed 0 --seconds 40 --trace 0``.
"""
import dataclasses
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax                                                   # noqa: E402

from repro.configs.archs import SMOKE                        # noqa: E402
from repro.core.devices import homogeneous_cluster           # noqa: E402
from repro.core.executor import fresh_state                  # noqa: E402
from repro.core.policies import make_policy                  # noqa: E402
from repro.jax_cache import use_compile_cache                # noqa: E402
from repro.serving.engine import ModelBundle, ServingEngine  # noqa: E402
from repro.workflowbench.suites import agentic_workflow      # noqa: E402


def main() -> None:
    use_compile_cache()
    cfg_a = SMOKE["qwen3-1.7b"]
    cfg_b = dataclasses.replace(SMOKE["glm4-9b"],
                                vocab_size=cfg_a.vocab_size)
    print("loading model bundles (reduced configs)...")
    bundles = {
        "qwen-7b": ModelBundle.create("qwen-7b", cfg_a, seed=0),
        "llama-8b": ModelBundle.create("llama-8b", cfg_b, seed=1),
    }
    wf = agentic_workflow("agentic-demo", num_queries=8)

    engine = ServingEngine(bundles, n_devices=2, gen_len=6, prompt_len=16)
    state = fresh_state(homogeneous_cluster(2))
    prompts = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0,
                                 cfg_a.vocab_size)
    t0 = time.perf_counter()
    results = engine.run_workflow(wf, make_policy("FATE"), state, prompts)
    wall = time.perf_counter() - t0

    print(f"\nserved {len(results)} stages x {wf.num_queries} queries "
          f"in {wall:.2f}s")
    for sid in wf.topo_order:
        r = results[sid]
        flags = []
        if r.switched:
            flags.append("model-switch")
        if r.prefix_hit:
            flags.append("prefix-hit")
        print(f"  {sid:10s} devices={r.device_ids} "
              f"tokens={tuple(r.tokens_out.shape)} wall={r.wall_s:.2f}s "
              f"{' '.join(flags)}")
    print("\nresidency:", {d.did: d.resident for d in engine.devices})


if __name__ == "__main__":
    main()
