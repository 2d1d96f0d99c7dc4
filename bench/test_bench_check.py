"""CPU tests of the check that decides ``correct``, at a small size.

Each drives a whole run of the harness past its look for a chip, with the
cells' own traffic (8 queries, 128-token prompts, 8 generated tokens) over
two small Qwen1.5-shaped models: a sound run comes out correct; with the
timed path broken underneath, it comes out not correct; and the control,
the reference in float8 in the program's place, fails the check's limits.
The fault across chips runs in a child process that has four CPU devices.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

import check
import faults
import harness
import spec

BENCH = Path(__file__).resolve().parent
SEED = 2**31 + 12345


def tiny_model(alias: str, seed_offset: int, heads: int) -> spec.ModelSpec:
    """A 4-layer Qwen1.5 (Qwen2ForCausalLM) with 64-wide heads."""
    return spec.ModelSpec(alias=alias, seed_offset=seed_offset,
                          arch="qwen1.5-tiny", layers=4, d_model=64 * heads,
                          heads=heads, kv_heads=heads, head_dim=64,
                          d_ff=172 * heads, vocab=8192, rope_theta=1e6,
                          norm_eps=1e-6, tied=False, qk_norm=False,
                          qkv_bias=True, dtype="bfloat16")


def tiny_cell(chips: int = 1, per_chip: int = 2, rate: float = 5.0):
    """The pair cell's mix over two small models of different widths."""
    traffic = json.loads((BENCH / "traffic" / "agentic.pair.json").read_text())
    traffic.update(rate_per_s=rate)
    return spec.Cell("tiny", chips, {"chips": chips,
                                     "virtual_devices_per_chip": per_chip},
                     traffic, (tiny_model("qwen-7b", 0, 4),
                               tiny_model("llama-8b", 1, 5)),
                     ("wf_p50_s", "setup_s"), ())


def run(cell, seconds: float = 1.5) -> dict:
    return harness.run_cell(cell, SEED, seconds, False, jax.devices(),
                            time.perf_counter())


def test_sound_run_is_correct():
    r = run(tiny_cell())
    assert r["correct"], r["check"]
    assert r["attempted"] >= 3 and r["failed"] == 0
    assert list(r)[-1] == "check"
    assert set(r["metrics"]) == {"wf_p50_s", "setup_s"}


def test_traced_run_leaves_out_what_it_cannot_read(monkeypatch):
    """On the CPU the trace has no TPU planes: the host-side metrics are
    read, the device ones are left out rather than reported as 0."""
    import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12,
                                             "hbm_bytes_per_s": 1e11,
                                             "hbm_bytes": 1e9})
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    # and the reader of the four-chip cell's switches, not listed yet
    per_layer = bench["per_layer"] + [{"name": "switches_per_wf",
                                       "unit": "count"}]
    cell = dataclasses.replace(tiny_cell(), per_layer=tuple(per_layer))
    r = harness.run_cell(cell, SEED, 1.5, True, jax.devices(),
                         time.perf_counter())
    assert r["correct"], r["check"]
    assert set(r["metrics"]) == {"plan_ms", "switches_per_wf"}
    assert r["metrics"]["plan_ms"]["value"] > 0
    assert r["device"]["window_s"] > 0 and r["device"]["busy_s"] == 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_in_the_timed_path_is_not_correct(fault, monkeypatch):
    sound = harness.build_bundles

    def broken(*args, **kwargs):
        bundles = sound(*args, **kwargs)
        faults.plant(bundles, fault)
        return bundles

    monkeypatch.setattr(harness, "build_bundles", broken)
    r = run(tiny_cell())
    assert not r["correct"], r["check"]
    assert r["check"]["logit_err"]["value"] > r["check"]["logit_err"]["limit"]


def test_control_reads_wider_than_the_program():
    """The float8 control, compared as a run compares the program, comes
    out not correct at the check's limits, and reads at least three times
    the program's ``logit_err``."""
    cell = tiny_cell()
    kept = {}
    sound = check.compare

    def keep(*args, **kwargs):
        kept["args"] = args
        return sound(*args, **kwargs)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(check, "compare", keep)
        r = run(cell)
    assert r["correct"], r["check"]
    gap, err = check.compare(*kept["args"], fp8=True)
    control = check.model_numbers(gap, err)
    assert not all(n.ok for n in control), control
    assert err >= 3 * r["check"]["logit_err"]["value"], (
        err, r["check"]["logit_err"])


def exchange_fault_main() -> None:
    """In a process with four CPU devices: a sound run of a four-chip
    tiny cell, then one whose weight copies to other chips are left out
    (each chip is handed the home chip's weights).  Prints both results;
    a run that raises, as the benchmark would exit without a result,
    prints ``null``."""
    import repro.serving.engine as engine
    cell = tiny_cell(chips=4, per_chip=1)
    sound = run(cell)

    def no_copy(self, did, bundle, device):
        self._holders.setdefault((bundle.name, device), set()).add(did)
        self._copies[(bundle.name, device)] = bundle.params
        return bundle.params

    engine.ChipWeights.acquire = no_copy
    try:
        faulty = run(cell)
    except ValueError:        # arguments on different chips
        faulty = None
    print(json.dumps({"sound": sound, "faulty": faulty}))


def test_exchange_between_chips_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(BENCH),
                                           str(BENCH.parent / "src")]))
    out = subprocess.run(
        [sys.executable, "-c",
         "import test_bench_check as t; t.exchange_fault_main()"],
        cwd=BENCH, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    sound, faulty = res["sound"], res["faulty"]
    assert sound["correct"], sound["check"]
    assert sound["device"]["count"] == 4
    assert faulty is None or not faulty["correct"], faulty["check"]
