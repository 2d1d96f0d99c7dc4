"""Smoke run of the FATE-planned serving engine on TPU chips.

Two bundles of the full qwen3-1.7b config (random weights from
``--seed``), registered as the ``qwen-7b`` and ``llama-8b`` profiles so
that FATE prices residency switches between them, serve a few
retrieve -> two workers -> merge workflows through
``ServingEngine.run_workflow`` under ``make_policy("FATE")``.  The
generated tokens are checked against the uncached ``model.forward`` on
the same chip.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # 4 virtual devices on 4 chips,
                                     # then all 4 on chip 0

The last line of stdout is ``{"ok": true, "device": {...}}``; any
failure exits nonzero without printing it.  Without a TPU the script
fails: it never falls back to the CPU.  JAX's compilation cache lives
where ``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-1.7b"
MODELS = ("qwen-7b", "llama-8b")
QUERIES = 8
PROMPT_LEN = 128
GEN_LEN = 8
N_WORKFLOWS = 3
# four chips are charged four times per second: two workflows
# already place stages across chips and switch models on them
N_WORKFLOWS_4CHIP = 2

# Tolerances against the uncached reference, in logits.  With these
# random weights the logits have about unit scale (unit-RMS final norm
# against a tied N(0, 1/d_model) embedding).  The cached path (chunked
# prefill, then one-token decode over a bf16 KV cache) and the uncached
# forward over prompt + generated tokens sum in different orders, and
# every activation is rounded to bf16 (8 significant bits: a step of
# 0.03 at a logit of 4) through 28 layers.  A wrong position, cache slot
# or mask moves logits by O(1), far beyond either bound.
LOGIT_TOL = 0.25
# bf16 at full width can flip near-tied argmaxes, so a generated token
# need only be the reference argmax up to a near tie: its reference
# logit within TIE_TOL of the largest.
TIE_TOL = 0.25


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def build_bundles(cfg, seed: int) -> dict:
    """Two bundles of ``cfg`` with weights from ``seed`` and ``seed+1``,
    sharing one pair of jitted steps."""
    import jax

    from repro.serving.engine import ModelBundle
    t0 = time.perf_counter()
    first = ModelBundle.create(MODELS[0], cfg, seed=seed)
    bundles = {MODELS[0]: first,
               MODELS[1]: first.replica(MODELS[1], seed + 1)}
    jax.block_until_ready([b.params for b in bundles.values()])
    n = sum(x.size for x in jax.tree.leaves(first.params))
    log(f"setup: 2 x {cfg.name} bundles, {n} params each, "
        f"init_s={time.perf_counter() - t0}")
    return bundles


def workload(cfg, seed: int, n: int) -> list:
    """``n`` agentic workflows, each with its own random prompts."""
    import jax
    import jax.numpy as jnp

    from repro.workflowbench.suites import agentic_workflow
    out = []
    for i in range(n):
        prompts = jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(seed), i),
            (QUERIES, PROMPT_LEN), 0, cfg.vocab_size, jnp.int32)
        out.append((agentic_workflow(f"smoke-{i}", QUERIES), prompts))
    return out


def serve(bundles: dict, work: list, n_devices: int, chips=None) -> list:
    """Serve each workflow on a fresh engine and cluster state; returns
    ``[(chip id per virtual device, {sid: StageResult})]`` per workflow.
    Engines are not kept, so each one's chip copies of the params are
    freed before the next workflow."""
    from repro.core.devices import homogeneous_cluster
    from repro.core.executor import fresh_state
    from repro.core.policies import make_policy
    from repro.serving.engine import ServingEngine
    out = []
    for wf, prompts in work:
        engine = ServingEngine(bundles, n_devices, gen_len=GEN_LEN,
                               prompt_len=PROMPT_LEN, chips=chips)
        state = fresh_state(homogeneous_cluster(n_devices))
        results = engine.run_workflow(wf, make_policy("FATE"), state,
                                      prompts)
        out.append(([d.device.id for d in engine.devices], results))
    return out


def n_compiled(bundles: dict) -> int:
    """Executables held by the shared jitted steps."""
    b = next(iter(bundles.values()))
    return b.prefill._cache_size() + b.decode._cache_size()


def print_stages(served: list) -> None:
    for chip_of, results in served:
        for sid, r in results.items():
            chips = tuple(chip_of[d] for d in r.device_ids)
            log(f"stage {sid}: model={r.model} devices={r.device_ids} "
                f"chips={chips} wall_s={r.wall_s} switches={r.switches} "
                f"prefix_hit={r.prefix_hit}")


class Reference:
    """Uncached forward and cached replay of one model on one chip."""

    def __init__(self, bundles: dict, device):
        import jax
        import jax.numpy as jnp
        self.bundles = bundles
        self.device = device
        model = next(iter(bundles.values())).model
        self.vocab = model.cfg.vocab_size
        self._forward = jax.jit(
            lambda p, seq: model.forward(p, seq)[:, PROMPT_LEN - 1:]
            .astype(jnp.float32))

    def forward(self, model: str, prompts, tokens):
        """Uncached logits that predict each generated token, teacher-
        forced on ``tokens``: [Q, GEN_LEN, V]."""
        import jax
        import jax.numpy as jnp
        tokens = jax.device_put(tokens, self.device)
        seq = jnp.concatenate([prompts, tokens[:, :-1]], axis=1)
        return self._forward(self.bundles[model].params, seq)

    def cached(self, model: str, prompts, tokens):
        """The engine's prefill/decode path replayed on ``tokens``."""
        import jax
        import jax.numpy as jnp
        b = self.bundles[model]
        tokens = jax.device_put(tokens, self.device)
        cache = b.model.init_cache(tokens.shape[0], PROMPT_LEN + GEN_LEN)
        logits, kv = b.prefill(b.params, prompts, cache)
        steps = [logits[:, -1]]
        for t in range(GEN_LEN - 1):
            logits, kv = b.decode(b.params, tokens[:, t:t + 1], kv,
                                  jnp.int32(PROMPT_LEN + t))
            steps.append(logits[:, -1])
        return jnp.stack(steps, axis=1).astype(jnp.float32)


def check_against_reference(ref: Reference, work: list,
                            served: list) -> None:
    """Every stage's tokens are in the vocabulary and the reference's
    argmax up to a near tie; cached and uncached logits agree."""
    import jax
    import jax.numpy as jnp
    worst_logit = worst_gap = 0.0
    exact = total = 0
    for (wf, prompts), (_, results) in zip(work, served):
        for sid, r in results.items():
            toks = r.tokens_out
            if toks.shape != (QUERIES, GEN_LEN):
                raise SmokeFailure(f"{wf.wid}/{sid}: tokens {toks.shape}")
            if not bool(jnp.all((toks >= 0) & (toks < ref.vocab))):
                raise SmokeFailure(f"{wf.wid}/{sid}: token out of vocab")
            fwd = ref.forward(r.model, prompts, toks)
            cached = ref.cached(r.model, prompts, toks)
            if not (bool(jnp.all(jnp.isfinite(fwd)))
                    and bool(jnp.all(jnp.isfinite(cached)))):
                raise SmokeFailure(f"{wf.wid}/{sid}: non-finite logits")
            toks = jax.device_put(toks, ref.device)
            err = float(jnp.max(jnp.abs(fwd - cached)))
            picked = jnp.take_along_axis(fwd, toks[..., None], -1)[..., 0]
            gap = float(jnp.max(jnp.max(fwd, -1) - picked))
            exact += int(jnp.sum(jnp.argmax(fwd, -1) == toks))
            total += toks.size
            worst_logit = max(worst_logit, err)
            worst_gap = max(worst_gap, gap)
            if err > LOGIT_TOL or gap > TIE_TOL:
                raise SmokeFailure(
                    f"{wf.wid}/{sid}: cached vs uncached max |dlogit| "
                    f"{err} (limit {LOGIT_TOL}), token gap to the "
                    f"reference argmax {gap} (limit {TIE_TOL})")
    log(f"reference: max_abs_logit_diff={worst_logit} (limit "
        f"{LOGIT_TOL}) max_argmax_gap={worst_gap} (limit {TIE_TOL}) "
        f"tokens_equal_to_reference_argmax={exact}/{total}")


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def one_chip(cfg, seed: int) -> None:
    """Warm-up pass (compiles, reported as set-up), timed pass, and the
    reference check, all on chip 0."""
    import jax
    bundles = build_bundles(cfg, seed)
    work = workload(cfg, seed, N_WORKFLOWS)
    t0 = time.perf_counter()
    serve(bundles, work, n_devices=2)
    log(f"setup: compile + warm-up pass over {len(work)} workflows, "
        f"s={time.perf_counter() - t0} executables={n_compiled(bundles)}")
    before = n_compiled(bundles)
    t0 = time.perf_counter()
    served = serve(bundles, work, n_devices=2)
    wall = time.perf_counter() - t0
    print_stages(served)
    logs = [r for _, results in served for r in results.values()]
    log(f"served: {len(work)} workflows x {QUERIES} queries, "
        f"prompt_len={PROMPT_LEN} gen_len={GEN_LEN}, wall_s={wall}, "
        f"switches={sum(r.switches for r in logs)}, "
        f"prefix_hits={sum(r.prefix_hit for r in logs)}, "
        f"compiles_in_window={n_compiled(bundles) - before}")
    check_against_reference(Reference(bundles, jax.devices()[0]), work,
                            served)
    log(f"peak_bytes_in_use: chip0={peak_bytes(jax.devices()[0])}")


def first_divergence_near_tie(ref: Reference, model: str, prompts,
                              toks_a, toks_b) -> int:
    """Queries whose tokens differ between ``toks_a`` and ``toks_b``;
    raises unless each first difference is a near tie in the reference
    (both runs share the prefix up to it)."""
    import jax
    import jax.numpy as jnp
    a = jax.device_get(toks_a)
    b = jax.device_get(toks_b)
    if a.shape != b.shape:
        raise SmokeFailure(f"token shapes differ: {a.shape} vs {b.shape}")
    rows = [q for q in range(a.shape[0]) if (a[q] != b[q]).any()]
    if rows:
        fwd = ref.forward(model, prompts, toks_a)
        for q in rows:
            t = int((a[q] != b[q]).argmax())
            top = float(jnp.max(fwd[q, t]))
            if top - float(fwd[q, t, int(b[q, t])]) > TIE_TOL:
                raise SmokeFailure(
                    f"query {q} diverges at token {t} without a near tie")
    return len(rows)


def four_chips(cfg, seed: int, chips: list) -> None:
    """The same workflows with 4 virtual devices on 4 chips, then with
    all 4 on chip 0; FATE must use >= 2 chips and the tokens must agree
    up to near ties."""
    bundles = build_bundles(cfg, seed)
    work = workload(cfg, seed, N_WORKFLOWS_4CHIP)
    t0 = time.perf_counter()
    spread = serve(bundles, work, n_devices=4, chips=chips)
    log(f"4 chips: wall_s={time.perf_counter() - t0} (compiles included)")
    print_stages(spread)
    used = {chip_of[d] for chip_of, results in spread
            for r in results.values() for d in r.device_ids}
    log(f"4 chips: FATE placed stages on chips {sorted(used)}")
    if len(used) < 2:
        raise SmokeFailure(f"FATE used chips {sorted(used)}, expected >= 2")
    t0 = time.perf_counter()
    packed = serve(bundles, work, n_devices=4, chips=chips[:1])
    log(f"chip 0 only: wall_s={time.perf_counter() - t0}")
    print_stages(packed)
    ref = Reference(bundles, chips[0])
    differ = total = 0
    for (wf, prompts), (_, r4), (_, r1) in zip(work, spread, packed):
        for sid in wf.stages:
            differ += first_divergence_near_tie(
                ref, r1[sid].model, prompts, r1[sid].tokens_out,
                r4[sid].tokens_out)
            total += QUERIES
    log(f"4 chips vs chip 0: queries with differing tokens={differ}/"
        f"{total} (each first difference a near tie, limit {TIE_TOL})")
    log("peak_bytes_in_use: " + " ".join(
        f"chip{d.id}={peak_bytes(d)}" for d in chips))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: compare 4 virtual devices on 4 chips with "
                         "all 4 on chip 0 (only that phase)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.configs.archs import ARCHS
        from repro.jax_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this smoke runs on the chip only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    log(f"compile cache: {cache_dir}")
    try:
        if args.chips == 1:
            one_chip(ARCHS[ARCH], args.seed)
        else:
            four_chips(ARCHS[ARCH], args.seed, devices[:4])
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
