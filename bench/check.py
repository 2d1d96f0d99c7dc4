"""Whether what the timed window served is correct.

Once the window has closed and ``memory_peak_bytes`` has been read, a
sample of the stages it served, drawn from the seed, is compared with the
plain reference (``reference.py``), teacher-forced on the served tokens,
with the benchmark's own weights.  Compared, each against its limit:

- ``gap``: the widest gap by which a served token's reference logit lies
  below the reference's best at its position.  Greedy decoding in bf16
  picks the reference's best or a near tie; a token taken from the wrong
  query, position, cache slot or weights lies far below it.
- ``logit_err``: the largest absolute difference, over every position and
  every vocabulary entry, between the program's logits and the
  reference's.  The program's logits are those of the window's own
  compiled prefill and decode steps, replayed on each shard's chip at its
  shard size and teacher-forced on the served tokens (``replay``), before
  the program's state is freed.  It sees errors that flip no token, such
  as a decode step that keeps a stale cache.
- ``misplaced``: stages whose gathered tokens are not on the chip of the
  stage's first shard (every stage of the window, exact).
- ``malformed``: stages whose tokens are not ``[queries, gen_len]`` ids in
  the vocabulary (every stage of the window, exact).
- ``failed``: workflows of the window that raised (exact).

The sample holds at least one stage of each kind the window served: per
model, on its home chip or on a copy on another chip, in one shard or two.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# Limits, in logits, each between the largest reading of sound runs and
# the smallest of the float8 control (PERF.md section 6 gives the
# readings): ``gap`` 0.043 and 0.166, ``logit_err`` 0.036 and 0.347.
GAP_LIMIT = 0.1
LOGIT_LIMIT = 0.15
SAMPLE_STAGES = 6


@dataclasses.dataclass(frozen=True)
class Number:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def kind(rec, home) -> tuple:
    return (rec.model, all(c == home for c in rec.chips), len(rec.chips))


def sample(records, seed: int, home, n: int = SAMPLE_STAGES) -> list:
    """``n`` stage records drawn from ``seed``, one of each kind first."""
    rng = np.random.default_rng([seed, 0x636865636B])
    order = [records[i] for i in rng.permutation(len(records))]
    picked, kinds = [], set()
    for r in order:
        if kind(r, home) not in kinds:
            kinds.add(kind(r, home))
            picked.append(r)
    rest = [r for r in order if all(r is not p for p in picked)]
    return picked + rest[:max(0, n - len(picked))]


def exact_numbers(records, n_failed: int, queries: int, gen_len: int,
                  vocab: int) -> list[Number]:
    misplaced = sum(not r.landed for r in records)
    malformed = 0
    for r in records:
        t = np.asarray(r.tokens)
        if t.shape != (queries, gen_len) or t.min() < 0 or t.max() >= vocab:
            malformed += 1
    return [Number("misplaced", misplaced, 0), Number("malformed", malformed, 0),
            Number("failed", n_failed, 0)]


def replay(bundle, rec, prompts, chips: dict) -> np.ndarray:
    """The program's logits for each token that ``rec`` served,
    ``[Q, G, V]`` float32: the bundle's jitted prefill and decode steps,
    run on the chip and at the size of each of the stage's shards, fed
    the served tokens."""
    import jax
    import jax.numpy as jnp
    served = np.asarray(rec.tokens)
    p_len, g_len = prompts.shape[-1], served.shape[1]
    out, q0 = [], 0
    for chip, nq in zip(rec.chips, rec.shard_sizes):
        dev = chips[chip]
        params = jax.device_put(bundle.params, dev)
        cache = jax.device_put(bundle.model.init_cache(nq, p_len + g_len),
                               dev)
        logits, kv = bundle.prefill(
            params, jax.device_put(prompts[q0:q0 + nq], dev), cache)
        rows = [np.asarray(logits[:, -1], np.float32)]
        for t in range(g_len - 1):
            token = jax.device_put(served[q0:q0 + nq, t:t + 1], dev)
            logits, kv = bundle.decode(params, token, kv,
                                       jnp.int32(p_len + t))
            rows.append(np.asarray(logits[:, 0], np.float32))
        out.append(np.stack(rows, axis=1))
        del params
        q0 += nq
    return np.concatenate(out, axis=0)


def replay_all(bundles: dict, picked, prompts, chips) -> list[np.ndarray]:
    by_id = {c.id: c for c in chips}
    return [replay(bundles[r.model], r, prompts[r.index], by_id)
            for r in picked]


def compare(cell, seed: int, picked, program, prompts, device,
            fp8: bool = False) -> tuple[float, float]:
    """``(gap, logit_err)`` over the picked stages, whose replayed logits
    are ``program``.  With ``fp8`` they are the control's: the reference
    computed in float8 takes the program's place."""
    import jax
    import jax.numpy as jnp

    import reference
    import weights
    gap = err = 0.0
    for m in cell.models:
        mine = [(r, lg) for r, lg in zip(picked, program)
                if r.model == m.alias]
        if not mine:
            continue
        params = weights.init(m, seed, device)
        for r, lg in mine:
            p = jax.device_put(prompts[r.index], device)
            served = jax.device_put(np.asarray(r.tokens), device)
            ref = reference.logits(m, params, p, served)
            if fp8:
                lg = reference.logits(m, params, p, served, fp8=True)
                served = jnp.argmax(lg, axis=-1)
            gap = max(gap, float(jnp.max(reference.gaps(ref, served))))
            err = max(err, float(jnp.max(jnp.abs(jnp.asarray(lg) - ref))))
        del params
    return gap, err


def model_numbers(gap: float, err: float) -> list[Number]:
    return [Number("gap", gap, GAP_LIMIT),
            Number("logit_err", err, LOGIT_LIMIT)]
