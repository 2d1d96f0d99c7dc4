"""CPU tests of what the four-chip cell adds: the reader of the weights
copied between chips (``metrics/copy_gb_per_wf.py``) on hand-written
stage records and against the engine's own counter in a sound harness
run on four CPU devices, and the tie between the program's Qwen1.5-4B
and the configuration the cell serves."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax

import harness
import spec
import weights

BENCH = Path(__file__).resolve().parent
SEED = 2**31 + 4242
COPY = harness.load_metric("copy_gb_per_wf")


def tiny(alias: str, seed_offset: int, heads: int) -> spec.ModelSpec:
    return spec.ModelSpec(alias=alias, seed_offset=seed_offset,
                          arch="qwen1.5-tiny", layers=2, d_model=32 * heads,
                          heads=heads, kv_heads=heads, head_dim=32,
                          d_ff=64 * heads, vocab=512, rope_theta=1e6,
                          norm_eps=1e-6, tied=False, qk_norm=False,
                          qkv_bias=True, dtype="bfloat16")


A, B = tiny("a", 0, 2), tiny("b", 1, 3)


def rec(index, model, devices, chips, switches):
    return SimpleNamespace(index=index, model=model, device_ids=devices,
                           chips=chips, switches=switches)


def view(records, n_chips=4, per_chip=1, done=None):
    chips = [10 + c for c in range(n_chips)]
    done = {r.index for r in records} if done is None else done
    return SimpleNamespace(
        chips=chips, cell=SimpleNamespace(n_devices=n_chips * per_chip),
        models={"a": A, "b": B}, stages=[(r, 0, 0) for r in records],
        workflows=[SimpleNamespace(index=i) for i in sorted(done)])


def test_model_bytes_are_the_served_parameters():
    params = weights.init(B, 7, jax.devices()[0])
    assert COPY.model_bytes(B) == sum(x.nbytes
                                      for x in jax.tree.leaves(params))
    # norms are kept in float32, the rest in bf16
    assert COPY.model_bytes(B) > 2 * weights.param_count(B)


def test_copies_on_hand_written_records():
    a, b = COPY.model_bytes(A), COPY.model_bytes(B)
    records = [
        rec(0, "a", (0,), (10,), 1),          # home chip: no copy
        rec(0, "b", (1,), (11,), 1),          # away chip, first seen
        rec(1, "b", (1, 2), (11, 12), 1),     # vd 1 holds b; vd 2 gains b
        rec(1, "a", (1, 3), (11, 13), 2),     # vd 1 back to a; vd 3 gains a
        rec(2, "a", (0, 1), (10, 11), 0),     # both already hold a
    ]
    got = [n for _, n in COPY.copied_bytes(view(records))]
    assert got == [0, b, b, 2 * a, 0]
    assert COPY.read(view(records)) == (2 * b + 2 * a) / 3 * 1e-9


def test_first_seen_devices_the_count_cannot_settle_are_left_out():
    a = COPY.model_bytes(A)
    records = [
        rec(0, "a", (1, 2), (11, 12), 1),     # one of two switched: which?
        rec(0, "a", (3,), (13,), 0),          # already held a
        rec(1, "b", (1, 2), (11, 12), 2),     # now both known
        rec(1, "a", (1,), (11,), 1),
    ]
    got = [n for _, n in COPY.copied_bytes(view(records))]
    assert got == [0, 0, 2 * COPY.model_bytes(B), a]


def test_a_chip_that_holds_the_model_gains_no_copy():
    """Two virtual devices a chip (vd ``d`` on chip ``d % 2``): the
    second to make a model resident on a chip shares the first's copy,
    and a copy dropped by its last holder is made again."""
    a, b = COPY.model_bytes(A), COPY.model_bytes(B)
    records = [
        rec(0, "a", (0, 2), (10, 10), 2),     # home
        rec(0, "b", (1,), (11,), 1),          # vd 3 on chip 11 unknown yet
        rec(0, "b", (3,), (11,), 1),          # chip 11 holds b (vd 1)
        rec(1, "a", (1,), (11,), 1),          # vd 3 holds b: a is copied
        rec(1, "a", (3,), (11,), 1),          # chip 11 holds a (vd 1)
        rec(1, "b", (1,), (11,), 1),          # b was dropped: copied again
    ]
    got = [n for _, n in COPY.copied_bytes(view(records, 2, 2))]
    assert got == [0, 0, 0, a, 0, b]


def test_only_stages_of_finished_workflows_count():
    records = [rec(0, "b", (1,), (11,), 1), rec(1, "a", (2,), (12,), 1)]
    v = view(records, done={0})
    assert COPY.read(v) == COPY.model_bytes(B) * 1e-9
    assert COPY.read(view([], done=set())) is None
    assert COPY.read(view(records, n_chips=1, per_chip=2)) is None


def test_zero_query_shards_are_not_live():
    r = rec(0, "a", (0, 1, 2), (10, 12), 2)
    assert COPY.live_devices(r, lambda d: 10 + d) == [0, 2]


def harness_main() -> None:
    """In a process with four CPU devices: a sound run of a four-chip
    tiny cell, one virtual device a chip, with the warm-up left out so
    that the window starts with nothing resident.  Prints the result, the
    engine's counter, the stages' ``switch_bytes`` and the reader's total
    over the window's stage records."""
    import test_bench_check as tb
    cell = tb.tiny_cell(chips=4, per_chip=1)
    engines = []
    make = harness.make_engine

    def keep(*args, **kwargs):
        engines.append(make(*args, **kwargs))
        return engines[-1]

    harness.make_engine = keep
    harness.warm_up = lambda *args: None
    r = harness.run_cell(cell, SEED, 1.5, False, jax.devices(),
                         time.perf_counter())
    engine, = engines
    v = SimpleNamespace(chips=[c.id for c in jax.devices()[:4]], cell=cell,
                        models={m.alias: m for m in cell.models},
                        stages=[(s, 0, 0) for s in engine.records])
    print(json.dumps({
        "result": r, "counter": engine.weights.bytes_copied,
        "stages": sum(s.switch_bytes for s in engine.log),
        "reader": sum(n for _, n in COPY.copied_bytes(v)),
        "away_switches": sum(s.switches for s in engine.records
                             if s.chips != (v.chips[0],))}))


def test_reader_total_equals_the_engine_counter_on_four_chips():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(BENCH),
                                           str(BENCH.parent / "src")]))
    out = subprocess.run(
        [sys.executable, "-c",
         "import test_bench_switch as t; t.harness_main()"],
        cwd=BENCH, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["result"]["correct"], got["result"]["check"]
    assert got["result"]["device"]["count"] == 4
    assert got["away_switches"] > 0 and got["counter"] > 0
    assert got["reader"] == got["stages"] == got["counter"]


def test_program_s_qwen15_4b_is_the_served_config():
    from repro.configs.archs import QWEN15_4B
    cfg = json.loads((BENCH / "configs" /
                      "qwen1.5-1.8b_qwen1.5-4b.json").read_text())
    served = {m["alias"]: spec.ModelSpec.from_json(m)
              for m in cfg["models"]}["llama-8b"]
    want = harness.arch_config(served)
    fields = ("num_layers", "d_model", "num_heads", "num_kv_heads", "d_ff",
              "vocab_size", "resolved_head_dim", "qkv_bias", "qk_norm",
              "rope_theta", "norm_eps", "tie_embeddings")
    assert ({f: getattr(QWEN15_4B, f) for f in fields}
            == {f: getattr(want, f) for f in fields})
    assert QWEN15_4B.source.startswith("hf:Qwen/Qwen1.5-4B")
