"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels import ops, ref
from repro.kernels import paged_decode_attention as pdec

# every test here exercises Pallas kernels in interpret mode — the
# `pallas-interpret` CI job runs this module under JAX_PLATFORMS=cpu so
# paged/flash kernel regressions fail without a TPU in the loop
pytestmark = pytest.mark.pallas_interpret

R = np.random.default_rng(42)


def rnd(*shape, dtype=np.float32, scale=1.0):
    return jnp.asarray(R.standard_normal(shape).astype(dtype) * scale)


FLASH_CASES = [
    # B, Sq, H, K, D, window, softcap, dtype
    (2, 128, 4, 2, 64, 0, 0.0, jnp.float32),
    (1, 100, 4, 1, 64, 0, 0.0, jnp.float32),     # padding path
    (2, 64, 8, 8, 32, 16, 0.0, jnp.float32),     # banded / MHA
    (1, 128, 4, 2, 64, 0, 30.0, jnp.float32),    # softcap
    (1, 96, 6, 3, 128, 0, 0.0, jnp.float32),     # non-pow2 heads
    (2, 64, 4, 2, 64, 0, 0.0, jnp.bfloat16),     # bf16 io
]


@pytest.mark.parametrize("B,Sq,H,K,D,window,cap,dt", FLASH_CASES)
def test_flash_attention(B, Sq, H, K, D, window, cap, dt):
    q, k, v = (rnd(B, Sq, H, D).astype(dt), rnd(B, Sq, K, D).astype(dt),
               rnd(B, Sq, K, D).astype(dt))
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              softcap=cap, block_q=32, block_k=32)
    want = ref.attention_ref(q, k, v, causal=True, window=window, softcap=cap)
    tol = 2e-5 if dt == jnp.float32 else 2e-2
    assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32),
                    atol=tol, rtol=tol)


DECODE_CASES = [
    (2, 8, 2, 64, 256, 100), (1, 4, 4, 32, 130, 130), (2, 8, 1, 128, 512, 1),
    (1, 16, 2, 64, 96, 33),
]


@pytest.mark.parametrize("B,H,K,D,S,nvalid", DECODE_CASES)
def test_decode_attention(B, H, K, D, S, nvalid):
    q, k, v = rnd(B, 1, H, D), rnd(B, S, K, D), rnd(B, S, K, D)
    valid = jnp.arange(S) < nvalid
    out = ops.decode_attention(q, k, v, valid, block_k=64)
    want = ref.decode_attention_ref(q, k, v, valid)
    assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


PAGED_CASES = [
    # B, H, K, D, page_tokens, max_len, softcap, page dtype, lengths
    # (None: random in [1, max_len])
    (3, 8, 2, 64, 16, 80, 0.0, np.float32, None),
    (2, 4, 4, 32, 8, 64, 0.0, np.float32, None),    # MHA, small pages
    (1, 16, 2, 64, 32, 96, 0.0, np.float32, None),  # wide GQA group
    (2, 6, 3, 16, 16, 48, 30.0, np.float32, None),  # non-pow2 heads + softcap
    # 8 pages (128 tokens) a block, 20 pages a row: the last block holds
    # 4 table columns. Rows of 0 and 1 tokens, one on a block boundary,
    # partial last blocks, and one at max_len
    (6, 8, 2, 64, 16, 320, 0.0, np.float32, (0, 1, 128, 200, 320, 129)),
    # bf16 pages of 4 heads: 4 pages a block, 10 a row
    (4, 8, 4, 128, 16, 160, 30.0, jnp.bfloat16, (1, 64, 160, 100)),
]


def _paged_case(rng, B, H, K, D, pt, S, lengths, dt):
    """A ragged batch whose rows' tokens sit in randomly scattered pages
    of a pool with spare garbage pages: (q, contiguous k, v [B, S, K, D],
    k/v pages, page table, lengths)."""
    P = -(-S // pt)                       # pages per row
    n_pages = B * P + 3                   # spare pages stay garbage
    if lengths is None:
        lengths = rng.integers(1, S + 1, size=B)
    lengths = np.asarray(lengths, np.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)).astype(np.float32))
    kd = rng.standard_normal((B, S, K, D)).astype(dt)
    vd = rng.standard_normal((B, S, K, D)).astype(dt)
    table = rng.permutation(n_pages)[: B * P].reshape(B, P).astype(np.int32)
    k_pages = rng.standard_normal((n_pages, K, pt, D)).astype(dt)
    v_pages = rng.standard_normal((n_pages, K, pt, D)).astype(dt)
    for b in range(B):
        for p in range(P):       # head-major pages: [K, pt, D] per page
            k_pages[table[b, p]] = kd[b, p * pt:(p + 1) * pt].swapaxes(0, 1)
            v_pages[table[b, p]] = vd[b, p * pt:(p + 1) * pt].swapaxes(0, 1)
    return q, kd, vd, k_pages, v_pages, table, lengths


def _block_tokens(K, D, pt, S, dt):
    """The paged kernel's tokens per block for this page shape."""
    return pdec.pages_per_block(K, pt, D, np.dtype(dt).itemsize,
                                -(-S // pt)) * pt


@pytest.mark.parametrize("B,H,K,D,pt,S,cap,dt,lengths", PAGED_CASES)
def test_paged_decode_matches_dense_bitwise(B, H, K, D, pt, S, cap, dt,
                                            lengths):
    """Paged kernel == dense decode kernel, BITWISE, on random GQA shapes.

    With the dense kernel's ``block_k`` equal to the paged kernel's
    block (``pages_per_block × page_tokens``) and pages holding the same
    tokens in order, both kernels run the identical f32 online-softmax op
    sequence — page indirection, the walk ending at each row's length and
    the last block's re-copied pages must not change a single ulp. Rows
    get random or given lengths (ragged batch) and pages are scattered
    randomly through the pool."""
    rng = np.random.default_rng(B * 1000 + S)
    q, kd, vd, k_pages, v_pages, table, lengths = _paged_case(
        rng, B, H, K, D, pt, S, lengths, dt)
    bk = _block_tokens(K, D, pt, S, dt)
    assert bk <= 128              # the dense kernel's largest block

    out = ops.paged_decode_attention(q, jnp.asarray(k_pages),
                                     jnp.asarray(v_pages),
                                     jnp.asarray(table),
                                     jnp.asarray(lengths), softcap=cap)
    for b in range(B):
        valid = jnp.arange(S) < lengths[b]
        want = ops.decode_attention(q[b:b + 1], jnp.asarray(kd[b:b + 1]),
                                    jnp.asarray(vd[b:b + 1]), valid,
                                    softcap=cap, block_k=bk)
        np.testing.assert_array_equal(np.asarray(out[b]), np.asarray(want[0]))


@pytest.mark.parametrize("B,H,K,D,pt,S,cap,dt,lengths", [
    c for c in PAGED_CASES if _block_tokens(*c[2:6], np.int8) <= 128])
def test_paged_decode_quantized_matches_dequant_bitwise(B, H, K, D, pt, S,
                                                        cap, dt, lengths):
    """Fused-dequant kernel == fp32 math on externally dequantized pages,
    BITWISE. The quantized kernel widens each int8 page to f32 and applies
    the per-(page, kv-head) scale BEFORE the shared online-softmax update,
    so it must reproduce the exact op sequence of a kernel fed
    ``page_dequant``-ed pages in blocks of the same size: the fp32 paged
    kernel where both walks have one block per row, else the dense decode
    kernel (pinned bitwise to the paged one above) with the int8 walk's
    block. Pages are built in f32 whatever ``dt``. This is the pin that
    lets the XLA gather fallback and the Pallas path share one numeric
    contract."""
    from repro.models.attention import page_dequant, page_quant
    rng = np.random.default_rng(B * 777 + S)
    q, _, _, k_pages, v_pages, table, lengths = _paged_case(
        rng, B, H, K, D, pt, S, lengths, np.float32)
    kq, ks = page_quant(jnp.asarray(k_pages), jnp.int8)
    vq, vs = page_quant(jnp.asarray(v_pages), jnp.int8)
    kf, vf = page_dequant(kq, ks), page_dequant(vq, vs)

    out = ops.paged_decode_attention(q, kq, vq, jnp.asarray(table),
                                     jnp.asarray(lengths), softcap=cap,
                                     k_scales=ks, v_scales=vs)
    bk = _block_tokens(K, D, pt, S, np.int8)
    if bk == _block_tokens(K, D, pt, S, np.float32):
        want = ops.paged_decode_attention(q, kf, vf, jnp.asarray(table),
                                          jnp.asarray(lengths), softcap=cap)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
        return
    for b in range(B):           # the row's tokens, contiguous [1, S, K, D]
        rows = lambda pages: jnp.swapaxes(pages[table[b]], 1, 2).reshape(
            1, -1, K, D)[:, :S]
        want = ops.decode_attention(q[b:b + 1], rows(kf), rows(vf),
                                    jnp.arange(S) < lengths[b],
                                    softcap=cap, block_k=bk)
        np.testing.assert_array_equal(np.asarray(out[b]), np.asarray(want[0]))


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_decode_never_reads_past_a_rows_length(quantized):
    """Table entries past each row's last page hold out-of-range ids or
    point at a page of NaNs: the output is bitwise what a clean table
    gives, so the walk never copies those pages (a copied NaN value row
    would poison the row even under a zero softmax weight)."""
    from repro.models.attention import page_quant
    B, H, K, D, pt, S, _, dt, lengths = PAGED_CASES[4]
    rng = np.random.default_rng(11)
    q, _, _, k_pages, v_pages, table, lengths = _paged_case(
        rng, B, H, K, D, pt, S, lengths, dt)
    nan_page = min(set(range(k_pages.shape[0])) - set(table.ravel()))
    k_pages[nan_page] = np.nan
    v_pages[nan_page] = np.nan
    dirty = table.copy()
    for b, n in enumerate(lengths):
        tail = dirty[b, -(-n // pt):]
        tail[:] = rng.choice([nan_page, -7, 10 ** 6], size=tail.shape)
    kw = {}
    kp, vp = jnp.asarray(k_pages), jnp.asarray(v_pages)
    if quantized:            # int8 codes cannot hold a NaN: its scales do
        kp, ks = page_quant(kp, jnp.int8)
        vp, vs = page_quant(vp, jnp.int8)
        kw = {"k_scales": ks.at[nan_page].set(np.nan),
              "v_scales": vs.at[nan_page].set(np.nan)}
    clean = ops.paged_decode_attention(q, kp, vp, jnp.asarray(table),
                                       jnp.asarray(lengths), **kw)
    out = ops.paged_decode_attention(q, kp, vp, jnp.asarray(dirty),
                                     jnp.asarray(lengths), **kw)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


def test_paged_walk_count_follows_the_block():
    """``pages_walked`` per row: its pages, at most the table's, rounded
    up to whole blocks; a row of length 0 copies nothing."""
    lengths = (0, 1, 128, 129, 200, 320, 400)   # 16-token pages, 8 a block
    assert pdec.pages_walked(lengths, 16, 20, 8) == (0 + 8 + 8 + 16 + 16
                                                     + 24 + 24)
    assert pdec.pages_per_block(2, 16, 128, 2, 288) == 8    # glm4-9b bf16
    assert pdec.pages_per_block(2, 16, 128, 1, 288) == 16   # glm4-9b int8
    assert pdec.pages_per_block(32, 16, 128, 2, 16) == 8    # llama2-7b bf16
    assert pdec.pages_per_block(32, 16, 128, 4, 16) == 4    # VMEM-bound
    assert pdec.pages_per_block(64, 16, 128, 4, 16) == 2
    assert pdec.pages_per_block(2, 16, 128, 2, 4) == 4      # capped at table


def test_paged_decode_row_isolation():
    """A row's output depends only on ITS pages: rewriting another row's
    pages (and the never-referenced spares) must not change it."""
    rng = np.random.default_rng(7)
    B, H, K, D, pt, S = 2, 4, 2, 32, 8, 32
    P = S // pt
    n_pages = B * P + 2
    lengths = np.asarray([S, S - 3], np.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)).astype(np.float32))
    table = np.arange(B * P).reshape(B, P).astype(np.int32)
    k_pages = rng.standard_normal((n_pages, K, pt, D)).astype(np.float32)
    v_pages = rng.standard_normal((n_pages, K, pt, D)).astype(np.float32)
    a = ops.paged_decode_attention(q, jnp.asarray(k_pages),
                                   jnp.asarray(v_pages), jnp.asarray(table),
                                   jnp.asarray(lengths))
    k2, v2 = k_pages.copy(), v_pages.copy()
    k2[P:] = rng.standard_normal(k2[P:].shape)  # row 1's + spare pages
    v2[P:] = rng.standard_normal(v2[P:].shape)
    b = ops.paged_decode_attention(q, jnp.asarray(k2), jnp.asarray(v2),
                                   jnp.asarray(table), jnp.asarray(lengths))
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert not np.array_equal(np.asarray(a[1]), np.asarray(b[1]))


@pytest.mark.parametrize("T,F,act,dt", [
    (64, 256, "swiglu", jnp.float32), (100, 128, "geglu", jnp.float32),
    (7, 96, "swiglu", jnp.float32), (64, 256, "swiglu", jnp.bfloat16)])
def test_fused_glu(T, F, act, dt):
    h = rnd(T, 2 * F).astype(dt)
    out = ops.fused_glu(h, act, block_t=32, block_f=64)
    want = ref.glu_ref(h, act)
    tol = 2e-5 if dt == jnp.float32 else 2e-2
    assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32),
                    atol=tol, rtol=tol)


@pytest.mark.parametrize("B,T,H,P,N,Q", [
    (1, 64, 2, 16, 16, 16), (2, 100, 4, 32, 64, 32), (1, 48, 3, 16, 32, 16)])
def test_ssd_kernel(B, T, H, P, N, Q):
    xh = rnd(B, T, H, P, scale=0.5)
    log_a = -jnp.abs(rnd(B, T, H, scale=0.1))
    Bm, Cm = rnd(B, T, N, scale=0.3), rnd(B, T, N, scale=0.3)
    y, fin = ops.ssd(xh, log_a, Bm, Cm, chunk=Q)
    yr, finr = ref.ssd_ref(xh, log_a, Bm, Cm)
    assert_allclose(np.asarray(y), np.asarray(yr), atol=3e-4, rtol=3e-4)
    assert_allclose(np.asarray(fin), np.asarray(finr), atol=3e-4, rtol=3e-4)


def test_ssd_kernel_matches_model_scan():
    from repro.models.ssm import _ssd_scan
    xh = rnd(2, 96, 4, 16, scale=0.5)
    log_a = -jnp.abs(rnd(2, 96, 4, scale=0.1))
    Bm, Cm = rnd(2, 96, 32, scale=0.3), rnd(2, 96, 32, scale=0.3)
    y_k, f_k = ops.ssd(xh, log_a, Bm, Cm, chunk=32)
    y_s, f_s = _ssd_scan(xh, log_a, Bm, Cm, 32)
    assert_allclose(np.asarray(y_k), np.asarray(y_s), atol=3e-4, rtol=3e-4)
    assert_allclose(np.asarray(f_k), np.asarray(f_s), atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize("B,T,W,bt", [(2, 64, 128, 16), (1, 100, 64, 32),
                                      (3, 33, 96, 8)])
def test_rglru_kernel(B, T, W, bt):
    a = jnp.exp(-jnp.abs(rnd(B, T, W, scale=0.5)))
    b = rnd(B, T, W, scale=0.5)
    h = ops.rglru(a, b, block_t=bt, block_w=64)
    assert_allclose(np.asarray(h), np.asarray(ref.rglru_ref(a, b)),
                    atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("arch", ["llama2-7b", "gemma-2b", "mamba2-370m",
                                  "recurrentgemma-9b"])
def test_model_pallas_path_matches_xla(arch):
    from repro.configs import get_smoke_config
    from repro.models import registry
    cfg = get_smoke_config(arch)
    model = registry.build(cfg)
    params = model.init(jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 32), 0,
                                          cfg.vocab_size)}
    lx = model.logits(params, batch, impl="xla")
    lp = model.logits(params, batch, impl="pallas")
    assert np.abs(np.asarray(lx) - np.asarray(lp)).max() < 5e-4


def test_chunked_attention_matches_plain():
    """The XLA memory-efficient chunked path == plain masked softmax."""
    from repro.configs import get_smoke_config
    from repro.models import attention
    cfg = get_smoke_config("llama2-7b")
    q, k, v = rnd(2, 8192, 4, 16), rnd(2, 8192, 2, 16), rnd(2, 8192, 2, 16)
    out_c = attention._sdpa_chunked(cfg, q, k, v)
    mask = attention._causal_mask(8192, 8192, 0)
    out_p = attention._sdpa(cfg, q, k, v, mask)
    assert_allclose(np.asarray(out_c), np.asarray(out_p), atol=2e-5,
                    rtol=2e-5)


def test_chunked_attention_banded():
    from repro.configs import get_smoke_config
    from repro.models import attention
    cfg = get_smoke_config("recurrentgemma-9b")
    S, w = 8192, 512
    q, k, v = rnd(1, S, 2, 16), rnd(1, S, 1, 16), rnd(1, S, 1, 16)
    out_c = attention._sdpa_chunked(cfg, q, k, v, window=w)
    mask = attention._causal_mask(S, S, w)
    out_p = attention._sdpa(cfg, q, k, v, mask)
    assert_allclose(np.asarray(out_c), np.asarray(out_p), atol=2e-5,
                    rtol=2e-5)
