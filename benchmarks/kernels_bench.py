"""Kernel micro-benchmarks: each Pallas kernel against its pure-jnp
oracle (the derived column reports max-abs error, plus analytic FLOPs
of the shape).

The kernels compile for the TPU.  ``interpret=True`` runs them in the
Pallas interpreter instead, which is how they run on the CPU; a time
from the interpreter is not a device time, and every row names the
device and mode it ran in."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels import ref as R


def _timed(fn, *args, reps=2, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / reps * 1e6


def run(interpret: bool = False) -> list[str]:
    dev = jax.devices()[0]
    where = f"{dev.platform}:{dev.device_kind}" + (
        ":interpret" if interpret else "")
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    rows = []

    b, s, h, kv, d = 1, 512, 8, 4, 64
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kv, d), jnp.bfloat16)
    out, us = _timed(ops.flash_attention, q, k, v, interpret=interpret)
    err = float(jnp.max(jnp.abs(
        out.astype(jnp.float32)
        - R.flash_attention_ref(q, k, v).astype(jnp.float32))))
    flops = 4 * b * h * s * s * d
    rows.append(f"kernel/flash_attention,{us:.1f},err={err:.1e};"
                f"flops={flops}")

    q1 = jax.random.normal(ks[0], (4, 1, h, d), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (4, 2048, kv, d), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (4, 2048, kv, d), jnp.bfloat16)
    out, us = _timed(ops.decode_attention, q1, kc, vc, jnp.int32(2048),
                     interpret=interpret)
    err = float(jnp.max(jnp.abs(
        out.astype(jnp.float32)
        - R.decode_attention_ref(q1, kc, vc, 2048).astype(jnp.float32))))
    rows.append(f"kernel/decode_attention,{us:.1f},err={err:.1e}")

    x = jax.random.normal(ks[3], (8, 128, 256), jnp.bfloat16)
    w = jax.random.normal(ks[4], (8, 256, 512), jnp.bfloat16)
    out, us = _timed(ops.moe_gemm, x, w, interpret=interpret)
    ref = R.moe_gemm_ref(x, w)
    rel = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32)))
                / jnp.max(jnp.abs(ref.astype(jnp.float32))))
    rows.append(f"kernel/moe_gemm,{us:.1f},relerr={rel:.1e}")

    bsz, s2, hh, p, n = 1, 256, 4, 32, 16
    xh = jax.random.normal(ks[0], (bsz, s2, hh, p))
    bb = jax.random.normal(ks[1], (bsz, s2, n))
    cc = jax.random.normal(ks[2], (bsz, s2, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (bsz, s2, hh)))
    (y, fin), us = _timed(ops.mamba2_scan, xh, bb, cc, dt,
                          jnp.zeros(hh), chunk=64, interpret=interpret)
    yr, _ = R.mamba2_scan_ref(xh, bb, cc, dt, jnp.zeros(hh))
    rows.append(f"kernel/mamba2_scan,{us:.1f},"
                f"err={float(jnp.max(jnp.abs(y - yr))):.1e}")

    r = jax.random.normal(ks[0], (1, 128, 2, 32)) * 0.5
    kk = jax.random.normal(ks[1], (1, 128, 2, 32)) * 0.5
    vv = jax.random.normal(ks[2], (1, 128, 2, 32))
    w6 = jax.nn.sigmoid(jax.random.normal(ks[3], (1, 128, 2, 32)))
    bonus = jax.random.normal(ks[4], (2, 32)) * 0.1
    (out, fin), us = _timed(ops.rwkv6_scan, r, kk, vv, w6, bonus,
                            chunk=32, interpret=interpret)
    outr, _ = R.rwkv6_scan_ref(r, kk, vv, w6, bonus)
    rows.append(f"kernel/rwkv6_scan,{us:.1f},"
                f"err={float(jnp.max(jnp.abs(out - outr))):.1e}")
    rows = [f"{row};device={where}" for row in rows]
    for row in rows:
        print(row)
    return rows
