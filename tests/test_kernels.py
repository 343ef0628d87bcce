"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Kernels run in interpret=True on CPU — the kernel body (BlockSpec
indexing included) executes for real.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.quant_matmul import quantize_weights


# ---------------------------------------------------------------------------
# quant_matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(32, 64, 128), (64, 256, 128),
                                   (128, 128, 512), (8, 512, 256)])
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_sweep(m, k, n, bits, xdtype):
    key = jax.random.PRNGKey(m * n + bits)
    x = jax.random.normal(key, (m, k), xdtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32) * 0.05
    wq, scale = quantize_weights(w, bits)
    out = ops.quant_matmul(x, wq, scale)
    expect = ref.quant_matmul_ref(x, wq, scale)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=0.05, atol=0.05)


def test_quantize_weights_bounds():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    for bits in (8, 4):
        q, s = quantize_weights(w, bits)
        lim = 2 ** (bits - 1)
        assert int(jnp.max(q)) <= lim - 1 and int(jnp.min(q)) >= -lim
        err = jnp.abs(q * s[None] - w).max()
        assert float(err) <= float(s.max())  # within one quantization step


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,h,kv,hd", [(64, 4, 2, 32), (128, 4, 4, 64),
                                       (32, 8, 1, 128), (256, 2, 2, 64)])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (16, 0.0),
                                            (0, 50.0), (32, 30.0)])
def test_flash_attention_sweep(s, h, kv, hd, window, softcap):
    key = jax.random.PRNGKey(s + h)
    q = jax.random.normal(key, (2, s, h, hd), jnp.float32) * 0.5
    k = jax.random.normal(jax.random.PRNGKey(1), (2, s, kv, hd)) * 0.5
    v = jax.random.normal(jax.random.PRNGKey(2), (2, s, kv, hd)) * 0.5
    out = ops.flash_attention(q, k, v, scale=hd ** -0.5, window=window,
                              softcap=softcap)
    expect = ref.flash_attention_ref(q, k, v, scale=hd ** -0.5,
                                     window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 4, 64), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 2, 64), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 2, 64), dtype)
    out = ops.flash_attention(q, k, v, scale=0.125)
    expect = ref.flash_attention_ref(q, k, v, scale=0.125)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("l,h,p,n,chunk", [(64, 4, 16, 8, 16),
                                           (128, 2, 32, 16, 32),
                                           (48, 4, 16, 8, 16),  # ragged tail
                                           (32, 8, 64, 32, 8)])
def test_ssd_scan_sweep(l, h, p, n, chunk):
    key = jax.random.PRNGKey(l + h)
    x = jax.random.normal(key, (2, l, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (2, l, h)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (h,)) * 0.3)
    B = jax.random.normal(jax.random.PRNGKey(3), (2, l, n))
    C = jax.random.normal(jax.random.PRNGKey(4), (2, l, n))
    y, hf = ops.ssd_scan(x, dt, A, B, C, chunk=chunk)
    yr, hr = ref.ssd_scan_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hr),
                               rtol=1e-3, atol=1e-3)


def test_ssd_scan_initial_state():
    """Continuation: scan(second half, h0=state(first half)) == full."""
    key = jax.random.PRNGKey(7)
    l, h, p, n = 64, 2, 16, 8
    x = jax.random.normal(key, (1, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (1, l, h)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (h,)) * 0.3)
    B = jax.random.normal(jax.random.PRNGKey(3), (1, l, n))
    C = jax.random.normal(jax.random.PRNGKey(4), (1, l, n))
    y_full, h_full = ops.ssd_scan(x, dt, A, B, C, chunk=16)
    _, h1 = ops.ssd_scan(x[:, :32], dt[:, :32], A, B[:, :32], C[:, :32],
                         chunk=16)
    y2, h2 = ops.ssd_scan(x[:, 32:], dt[:, 32:], A, B[:, 32:], C[:, 32:],
                          chunk=16, h0=h1)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, 32:]),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# paged attention (decode read)
# ---------------------------------------------------------------------------

def _paged_case(seed, B, H, kv, hd, nB, bs, n_blk):
    """Random pool + permuted (non-identity) block tables + ragged
    lengths, so physical page order really differs from logical order."""
    rng = np.random.default_rng(seed)
    q = jax.random.normal(jax.random.PRNGKey(seed), (B, H, hd),
                          jnp.float32) * 0.5
    kp = jax.random.normal(jax.random.PRNGKey(seed + 1),
                           (nB, bs, kv, hd)) * 0.5
    vp = jax.random.normal(jax.random.PRNGKey(seed + 2),
                           (nB, bs, kv, hd)) * 0.5
    bt = np.full((B, n_blk), -1, np.int32)
    lengths = np.zeros((B,), np.int32)
    perm = rng.permutation(nB)
    used = 0
    for b in range(B):
        n = int(rng.integers(1, n_blk * bs + 1))
        lengths[b] = n
        k = -(-n // bs)
        bt[b, :k] = perm[used:used + k]
        used += k
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths)


@pytest.mark.parametrize("B,H,kv,hd,nB,bs,n_blk",
                         [(3, 4, 2, 32, 12, 8, 4),
                          (2, 8, 8, 64, 10, 16, 2),
                          (4, 4, 1, 128, 20, 8, 4)])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_paged_attention_matches_ref(B, H, kv, hd, nB, bs, n_blk, softcap):
    q, kp, vp, bt, ln = _paged_case(B * 7 + H, B, H, kv, hd, nB, bs, n_blk)
    out = ops.paged_attention(q, kp, vp, bt, ln, scale=hd ** -0.5,
                              softcap=softcap)
    exp = ref.paged_attention_ref(q, kp, vp, bt, ln, scale=hd ** -0.5,
                                  softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# int8 KV quantization + fused dequant reads
# ---------------------------------------------------------------------------

def _quant_paged_case(seed, B, H, kv, hd, nB, bs, n_blk):
    """`_paged_case` plus the int8 twin of the pool: per-(token, kv-head)
    symmetric scales as written by ``layers.quantize_kv``."""
    from repro.models import layers as L
    q, kp, vp, bt, ln = _paged_case(seed, B, H, kv, hd, nB, bs, n_blk)
    kq, ks = L.quantize_kv(kp)
    vq, vs = L.quantize_kv(vp)
    return q, kq, ks, vq, vs, bt, ln


def test_quantize_kv_roundtrip_bounds():
    """int8 values stay in [-127, 127] and the dequant error of every
    head_dim vector is within one quantization step of its row scale."""
    from repro.models import layers as L
    x = jax.random.normal(jax.random.PRNGKey(3), (6, 8, 2, 64)) * 2.0
    q, s = L.quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
    assert int(jnp.max(q)) <= 127 and int(jnp.min(q)) >= -127
    err = jnp.abs(L.dequantize_kv(q, s) - x)
    assert float(jnp.max(err - 0.5 * s[..., None])) <= 1e-6


@pytest.mark.parametrize("B,H,kv,hd,nB,bs,n_blk",
                         [(3, 4, 2, 32, 12, 8, 4),
                          (2, 8, 8, 64, 10, 16, 2)])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_paged_attention_quant_matches_ref(B, H, kv, hd, nB, bs, n_blk,
                                           softcap):
    """Fused dequant decode kernel == gather+dequant reference."""
    q, kq, ks, vq, vs, bt, ln = _quant_paged_case(
        B * 11 + H, B, H, kv, hd, nB, bs, n_blk)
    out = ops.paged_attention(q, kq, vq, bt, ln, scale=hd ** -0.5,
                              softcap=softcap, k_scale=ks, v_scale=vs)
    exp = ref.paged_attention_ref(q, kq, vq, bt, ln, scale=hd ** -0.5,
                                  softcap=softcap, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-3, atol=2e-3)


def test_paged_attention_quant_close_to_f32():
    """Dequantized attention tracks the f32-pool result within int8
    tolerance — the dequant is semantically a KV read, not just
    self-consistent."""
    from repro.models import layers as L
    B, H, kv, hd, nB, bs, n_blk = 3, 4, 2, 64, 12, 8, 4
    q, kp, vp, bt, ln = _paged_case(5, B, H, kv, hd, nB, bs, n_blk)
    kq, ks = L.quantize_kv(kp)
    vq, vs = L.quantize_kv(vp)
    f32 = ref.paged_attention_ref(q, kp, vp, bt, ln, scale=hd ** -0.5)
    q8 = ops.paged_attention(q, kq, vq, bt, ln, scale=hd ** -0.5,
                             k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(q8), np.asarray(f32),
                               rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# paged extend (multi-token catch-up read)
# ---------------------------------------------------------------------------

def _extend_case(seed, B, H, kv, hd, nB, bs, n_blk, S):
    q1, kp, vp, bt, ln = _paged_case(seed, B, H, kv, hd, nB, bs, n_blk)
    qe = jax.random.normal(jax.random.PRNGKey(seed + 3),
                           (B, S, H, hd), jnp.float32) * 0.5
    kn = jax.random.normal(jax.random.PRNGKey(seed + 4),
                           (B, S, kv, hd)) * 0.5
    vn = jax.random.normal(jax.random.PRNGKey(seed + 5),
                           (B, S, kv, hd)) * 0.5
    return qe, kp, vp, kn, vn, bt, ln


@pytest.mark.parametrize("B,H,kv,hd,nB,bs,n_blk,S",
                         [(3, 4, 2, 32, 12, 8, 4, 4),
                          (2, 8, 8, 64, 10, 16, 2, 6),
                          (4, 4, 1, 128, 20, 8, 4, 3)])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_paged_extend_matches_ref(B, H, kv, hd, nB, bs, n_blk, S, softcap):
    """Fused extend kernel (paged context + dense causal suffix in one
    online-softmax pass) == the gather+concat reference."""
    qe, kp, vp, kn, vn, bt, ln = _extend_case(
        B * 13 + H + S, B, H, kv, hd, nB, bs, n_blk, S)
    out = ops.paged_extend_attention(qe, kp, vp, kn, vn, bt, ln,
                                     scale=hd ** -0.5, softcap=softcap)
    exp = ref.paged_extend_attention_ref(qe, kp, vp, kn, vn, bt, ln,
                                         scale=hd ** -0.5, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_paged_extend_quant_matches_ref(softcap):
    """Fused dequant extend kernel == gather+dequant+concat reference."""
    from repro.models import layers as L
    B, H, kv, hd, nB, bs, n_blk, S = 3, 4, 2, 64, 12, 8, 4, 5
    qe, kp, vp, kn, vn, bt, ln = _extend_case(
        9, B, H, kv, hd, nB, bs, n_blk, S)
    kq, ks = L.quantize_kv(kp)
    vq, vs = L.quantize_kv(vp)
    out = ops.paged_extend_attention(qe, kq, vq, kn, vn, bt, ln,
                                     scale=hd ** -0.5, softcap=softcap,
                                     k_scale=ks, v_scale=vs)
    exp = ref.paged_extend_attention_ref(qe, kq, vq, kn, vn, bt, ln,
                                         scale=hd ** -0.5, softcap=softcap,
                                         k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-3, atol=2e-3)


def test_paged_ref_matches_contiguous_attention():
    """The gather-based paged reference on an IDENTITY table equals
    masked dense attention over the same contiguous K/V — ties the
    paged oracle back to the existing flash oracle."""
    B, S, H, kv, hd, bs = 2, 32, 4, 2, 32, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, H, hd)) * 0.5
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, kv, hd)) * 0.5
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, kv, hd)) * 0.5
    # chop row 0's K/V into pages 0..3, row 1's into 4..7
    kp = k.reshape(B * S // bs, bs, kv, hd)
    vp = v.reshape(B * S // bs, bs, kv, hd)
    bt = jnp.asarray(np.arange(B * S // bs, dtype=np.int32).reshape(B, -1))
    ln = jnp.full((B,), S, jnp.int32)
    out = ref.paged_attention_ref(q[:, 0], kp, vp, bt, ln, scale=hd ** -0.5)
    # independent oracle: explicit full softmax over the contiguous K/V
    qg = q[:, 0].reshape(B, kv, H // kv, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k.astype(jnp.float32)) * hd ** -0.5
    p = jax.nn.softmax(s, axis=-1)
    expect = jnp.einsum("bkgt,btkd->bkgd", p,
                        v.astype(jnp.float32)).reshape(B, H, hd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_interpret_only_on_cpu(monkeypatch, backend, interpret):
    """Kernels compile on the TPU and interpret on the CPU; any other
    backend is an error, never a silent fall back to the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="neither"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret
