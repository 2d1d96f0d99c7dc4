"""Pallas kernel validation: interpret-mode execution against pure-jnp
oracles across shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as R

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("b,sq,sk,h,kv,d", [
    (1, 128, 128, 4, 2, 64),
    (2, 256, 256, 4, 4, 32),
    (1, 64, 64, 8, 2, 128),
    (2, 100, 100, 4, 2, 64),      # ragged tail blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0),
                                           (True, 64)])
def test_flash_attention(b, sq, sk, h, kv, d, dtype, causal, window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, kv, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              interpret=True)
    ref = R.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("clen", [512, 300, 17, 1])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(clen, dtype):
    b, s, h, kv, d = 2, 512, 8, 4, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), dtype)
    kc = jax.random.normal(ks[1], (b, s, kv, d), dtype)
    vc = jax.random.normal(ks[2], (b, s, kv, d), dtype)
    # a one-layer stack with no tail and no new row: the plain cache
    out = ops.decode_attention(q, (kc[None], vc[None]), None, None,
                               jnp.int32(0), jnp.int32(clen), start=s,
                               interpret=True)
    ref = R.decode_attention_ref(q, kc, vc, clen)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < tol


# The fused pass and the jnp parts differ in the order of summation and,
# where the pass walks more than one block, in the sum each softmax weight
# is divided by before it is rounded to bf16 (the running one), then round
# the same f32 result to bf16: one bf16 rounding step (2**-7 of the value,
# or of 1 near zero) bounds the difference.
PARTS_TOL = 2.0 ** -7


def _against_parts(g, s, t, clen, window, kv_major, block_s=None):
    """One layer of a stacked cache of ``s`` rows (and a tail of ``t``,
    or none where ``t`` is 0), read in place by the fused pass, against
    :func:`decode_attention_parts` over the same rows."""
    from repro.models.attention import decode_attention_parts
    n_layers, b, kv, d = 3, 2, 2, 128
    h, layer = kv * g, 1
    start = s - t
    ks = jax.random.split(KEY, 7)
    dt = jnp.bfloat16
    base = tuple(jax.random.normal(k, (n_layers, b, s, kv, d), dt)
                 for k in ks[:2])
    tail = tuple(jax.random.normal(k, (n_layers, b, t, kv, d), dt)
                 for k in ks[2:4]) if t else None
    new = tuple(jax.random.normal(k, (b, 1, kv, d), dt) for k in ks[4:6])
    q = jax.random.normal(ks[6], (b, 1, h, d), dt)
    out = ops.decode_attention(q, base, tail, new, jnp.int32(layer),
                               jnp.int32(clen), start=start, window=window,
                               kv_major=kv_major, block_s=block_s,
                               interpret=True)

    def live(pos):
        ok = pos < clen
        return ok & (pos > clen - window) if window else ok

    pos = jnp.arange(s)
    parts = [(base[0][layer], base[1][layer], live(pos) & (pos < start))]
    if t:
        parts.append((tail[0][layer], tail[1][layer],
                      live(start + jnp.arange(t))))
    parts.append((*new, jnp.ones(1, bool)))
    ref = decode_attention_parts(q, parts)
    assert out.shape == ref.shape == (b, 1, h, d)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32),
                               rtol=PARTS_TOL, atol=PARTS_TOL)


@pytest.mark.parametrize("g", [1, 2])                  # MHA, GQA
@pytest.mark.parametrize("has_tail", [True, False])
@pytest.mark.parametrize("at", ["first", "middle", "last"])
@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("kv_major", [False, True])
def test_decode_attention_matches_parts(g, has_tail, at, window, kv_major):
    """Each part one block, with the cache length at the tail's first,
    middle and last row."""
    s, t = 24, 8
    clen = {"first": s - t, "middle": s - t // 2, "last": s - 1}[at]
    _against_parts(g, s, t if has_tail else 0, clen, window, kv_major)


@pytest.mark.parametrize("g", [1, 2])                  # MHA, GQA
@pytest.mark.parametrize("has_tail", [True, False])
@pytest.mark.parametrize("block_s", [8, 16])
@pytest.mark.parametrize("window", [0, 9])
@pytest.mark.parametrize("kv_major", [False, True])
def test_decode_attention_blocks(g, has_tail, block_s, window, kv_major):
    """Parts longer than a block: 28 base rows and a tail of 12, or 40
    base rows, walked in blocks of 8 or 16, each part's last block
    running past its rows (past the array's end, in interpret mode rows
    of NaN, but for 40 rows in blocks of 8); a window across a block's
    edge."""
    s, t = 40, 12 if has_tail else 0
    _against_parts(g, s, t, s - 5, window, kv_major, block_s)


@pytest.mark.parametrize("e,c,d,f", [(4, 96, 160, 192), (2, 128, 64, 64),
                                     (8, 40, 100, 70)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gemm(e, c, d, f, dtype):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (e, c, d), dtype)
    w = jax.random.normal(ks[1], (e, d, f), dtype)
    out = ops.moe_gemm(x, w, interpret=True)
    ref = R.moe_gemm_ref(x, w)
    rel = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32)))) / \
        max(1e-6, float(jnp.max(jnp.abs(ref.astype(jnp.float32)))))
    assert rel < (1e-5 if dtype == jnp.float32 else 3e-2)


@pytest.mark.parametrize("s,chunk", [(64, 16), (128, 32), (32, 32)])
def test_mamba2_scan(s, chunk):
    bsz, h, p, n = 2, 3, 16, 8
    ks = jax.random.split(KEY, 4)
    xh = jax.random.normal(ks[0], (bsz, s, h, p))
    b = jax.random.normal(ks[1], (bsz, s, n))
    c = jax.random.normal(ks[2], (bsz, s, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (bsz, s, h)))
    a_log = jnp.zeros(h)
    y, fin = ops.mamba2_scan(xh, b, c, dt, a_log, chunk=chunk,
                             interpret=True)
    yr, finr = R.mamba2_scan_ref(xh, b, c, dt, a_log)
    assert float(jnp.max(jnp.abs(y - yr))) < 5e-4
    assert float(jnp.max(jnp.abs(fin - finr))) < 5e-4


@pytest.mark.parametrize("s,chunk", [(64, 16), (96, 32)])
@pytest.mark.parametrize("strong_decay", [False, True])
def test_rwkv6_scan(s, chunk, strong_decay):
    b, h, d = 2, 2, 16
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (b, s, h, d)) * 0.5
    k = jax.random.normal(ks[1], (b, s, h, d)) * 0.5
    v = jax.random.normal(ks[2], (b, s, h, d))
    if strong_decay:
        # numerically adversarial: near-zero decays (kills factorized
        # implementations; the pairwise log-space kernel must survive)
        w = jnp.full((b, s, h, d), 1e-6)
    else:
        w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, d)))
    bonus = jax.random.normal(ks[4], (h, d)) * 0.1
    out, fin = ops.rwkv6_scan(r, k, v, w, bonus, chunk=chunk,
                              interpret=True)
    outr, finr = R.rwkv6_scan_ref(r, k, v, w, bonus)
    assert float(jnp.max(jnp.abs(out - outr))) < 5e-4
    assert bool(jnp.all(jnp.isfinite(out)))
    assert float(jnp.max(jnp.abs(fin - finr))) < 5e-4


def test_models_use_same_math_as_kernels():
    """The XLA-path model attention equals the Pallas kernel (the model
    is the lowering target; the kernel is the TPU implementation)."""
    from repro.models.attention import flash_attention as xla_flash
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 64))
    k = jax.random.normal(ks[1], (2, 128, 2, 64))
    v = jax.random.normal(ks[2], (2, 128, 2, 64))
    a = xla_flash(q, k, v, causal=True)
    b = ops.flash_attention(q, k, v, causal=True, interpret=True)
    assert float(jnp.max(jnp.abs(a - b))) < 2e-5
