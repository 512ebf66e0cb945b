"""Per-kernel correctness: Pallas (interpret) and XLA twins vs oracles,
swept over shapes, dtypes and feature flags."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention as fa_pallas
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.topk_retrieval import topk_retrieval as topk_pallas


def _qkv(key, b, hq, hkv, sq, sk, d, dv=None, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, sk, dv or d), dtype)
    return q, k, v


ATTN_CASES = [
    # b, hq, hkv, sq, sk, d, causal, window, softcap
    (1, 2, 2, 128, 128, 32, True, 0, 0.0),
    (2, 4, 2, 256, 256, 64, True, 0, 0.0),
    (2, 4, 1, 192, 192, 64, True, 64, 0.0),
    (1, 8, 4, 128, 128, 32, True, 0, 50.0),
    (2, 2, 2, 96, 160, 32, False, 0, 0.0),
    (1, 4, 4, 256, 256, 64, True, 100, 30.0),
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_pallas_vs_ref(case):
    b, hq, hkv, sq, sk, d, causal, window, cap = case
    q, k, v = _qkv(jax.random.PRNGKey(0), b, hq, hkv, sq, sk, d)
    want = ref.attention(q, k, v, causal=causal, window=window, softcap=cap)
    got = fa_pallas(q, k, v, causal=causal, window=window, softcap=cap,
                    block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 4, 2, 128, 128, 64, dtype=dtype)
    want = ref.attention(q, k, v, causal=True)
    got = fa_pallas(q, k, v, causal=True, block_q=64, block_k=64)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol,
                               rtol=atol)


@pytest.mark.parametrize("case", ATTN_CASES[:4])
def test_xla_blocked_attention_vs_ref(case):
    b, hq, hkv, sq, sk, d, causal, window, cap = case
    q, k, v = _qkv(jax.random.PRNGKey(2), b, hq, hkv, sq, sk, d)
    want = ref.attention(q, k, v, causal=causal, window=window, softcap=cap)
    if window > 0:
        got = ops._banded_window_attention(
            q, k, v, window=window, causal=causal, softcap=cap, scale=None,
            q_offset=0, block_q=64)
    else:
        got = ops._blocked_attention(q, k, v, causal=causal, softcap=cap,
                                     scale=None, q_offset=0, block_q=64,
                                     block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_attention_separate_v_dim():
    q, k, v = _qkv(jax.random.PRNGKey(3), 2, 4, 2, 128, 128, 48, dv=32)
    want = ref.attention(q, k, v, causal=True)
    got = fa_pallas(q, k, v, causal=True, block_q=64, block_k=64)
    assert got.shape == (2, 4, 128, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_decode_attention_matches_ref():
    b, hq, hkv, S, d = 2, 4, 2, 64, 32
    key = jax.random.PRNGKey(4)
    q, kc, vc = _qkv(key, b, hq, hkv, 1, S, d)
    cache_len = 40
    want = ref.attention(q, kc[:, :, :cache_len], vc[:, :, :cache_len],
                         causal=True, q_offset=cache_len - 1)
    got = ops.decode_attention(q, kc, vc, cache_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


SSD_CASES = [
    # b, l, h, p, n, chunk
    (1, 64, 2, 16, 8, 16),
    (2, 128, 3, 16, 8, 32),
    (2, 96, 1, 32, 16, 24),
    (1, 256, 4, 8, 4, 128),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_pallas_vs_ref(case):
    b, l, h, p, n, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (b, l, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, n)) * 0.5
    C = jax.random.normal(ks[4], (b, l, n)) * 0.5
    yr, sr = ref.ssd(x, dt, A, B, C, chunk=chunk)
    yp, sp = ssd_scan(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr), atol=2e-5)


def test_ssd_decode_consistency():
    b, l, h, p, n = 1, 32, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    x = jax.random.normal(ks[0], (b, l, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, n)) * 0.5
    C = jax.random.normal(ks[4], (b, l, n)) * 0.5
    y_chunk, s_chunk = ref.ssd(x, dt, A, B, C, chunk=8)
    st = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(l):
        yt, st = ref.ssd_decode_step(st, x[:, t], dt[:, t], A, B[:, t],
                                     C[:, t])
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_chunk), atol=2e-5)
    np.testing.assert_allclose(np.asarray(st), np.asarray(s_chunk),
                               atol=2e-5)


def test_ssd_init_state_handoff():
    """Chunked scan with init_state == one long chunked scan."""
    b, l, h, p, n = 1, 64, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (b, l, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, n)) * 0.5
    C = jax.random.normal(ks[4], (b, l, n)) * 0.5
    y_full, s_full = ref.ssd(x, dt, A, B, C, chunk=16)
    half = l // 2
    y1, s1 = ref.ssd(x[:, :half], dt[:, :half], A, B[:, :half], C[:, :half],
                     chunk=16)
    y2, s2 = ref.ssd(x[:, half:], dt[:, half:], A, B[:, half:], C[:, half:],
                     chunk=16, init_state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=2e-5)


TOPK_CASES = [(16, 64, 8, 3), (37, 301, 24, 5), (128, 250, 32, 5),
              (5, 1000, 16, 10)]


@pytest.mark.parametrize("case", TOPK_CASES)
def test_topk_pallas_vs_ref(case):
    nq, na, d, k = case
    kq, ka = jax.random.split(jax.random.PRNGKey(8))
    q = jax.random.normal(kq, (nq, d))
    a = jax.random.normal(ka, (na, d))
    sr, ir = ref.topk_retrieval(q, a, k)
    sp, ip = topk_pallas(q, a, k, block_q=16, block_n=64)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr), atol=1e-5)
    assert (np.asarray(ip) == np.asarray(ir)).mean() > 0.99


DECODE_CASES = [
    # b, hq, hkv, S, d, cache_len, window, softcap
    (2, 4, 2, 128, 32, 100, 0, 0.0),
    (1, 8, 4, 256, 64, 256, 0, 50.0),
    (2, 2, 1, 96, 32, 40, 16, 0.0),
    (1, 4, 4, 300, 32, 123, 0, 0.0),
]


@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_attention_pallas_vs_ref(case):
    from repro.kernels.decode_attention import decode_attention as da_pallas
    b, hq, hkv, S, d, clen, window, cap = case
    q, kc, vc = _qkv(jax.random.PRNGKey(9), b, hq, hkv, 1, S, d)
    want = ops.decode_attention(q, kc, vc, clen, window=window, softcap=cap)
    got = da_pallas(q, kc, vc, clen, window=window, softcap=cap, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_decode_attention_pallas_per_batch_lengths():
    from repro.kernels.decode_attention import decode_attention as da_pallas
    b, hq, hkv, S, d = 3, 4, 2, 128, 32
    q, kc, vc = _qkv(jax.random.PRNGKey(10), b, hq, hkv, 1, S, d)
    lens = jnp.array([10, 77, 128])
    want = ops.decode_attention(q, kc, vc, lens)
    got = da_pallas(q, kc, vc, lens, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
# Paged decode attention: XLA gather path vs dense oracle (bit-exact), the
# Pallas paged kernel vs both (interpret tolerance)
# ---------------------------------------------------------------------------
def _paged_layout(key, b, hkv, S, d, page_size, n_pages, perm_seed=0):
    """A dense (b, hkv, S, d) cache scattered into a token-major
    (n_pages+1, page_size, hkv, d) page store under a deliberately
    permuted page table — physical order must not matter."""
    kk, kv = jax.random.split(key)
    kc = jax.random.normal(kk, (b, hkv, S, d)) * 0.5
    vc = jax.random.normal(kv, (b, hkv, S, d)) * 0.5
    n_w = S // page_size
    assert b * n_w <= n_pages
    rng = np.random.default_rng(perm_seed)
    phys = rng.permutation(n_pages)[: b * n_w].reshape(b, n_w)
    k_pages = jnp.zeros((n_pages + 1, page_size, hkv, d))
    v_pages = jnp.zeros((n_pages + 1, page_size, hkv, d))
    for i in range(b):
        for w in range(n_w):
            sl = slice(w * page_size, (w + 1) * page_size)
            k_pages = k_pages.at[phys[i, w]].set(kc[i, :, sl].swapaxes(0, 1))
            v_pages = v_pages.at[phys[i, w]].set(vc[i, :, sl].swapaxes(0, 1))
    return kc, vc, k_pages, v_pages, jnp.asarray(phys, jnp.int32)


PAGED_CASES = [
    # b, hq, hkv, S, d, page_size, lens, softcap
    (2, 4, 2, 128, 32, 16, (100, 128), 0.0),
    (1, 8, 4, 64, 64, 8, (40,), 50.0),
    (3, 4, 2, 96, 32, 32, (10, 77, 96), 0.0),
    # qwen3-4b's geometry: a page holds 16 tokens of 8 heads x 128, g 4
    (2, 32, 8, 80, 128, 16, (61, 80), 0.0),
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_decode_xla_bit_identical_to_dense(case):
    """The gather-based paged path reconstructs the dense layout exactly
    and feeds the same kernel: bitwise-equal outputs, any page
    permutation, per-row lengths included."""
    b, hq, hkv, S, d, page, lens, cap = case
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (b, hq, 1, d)) * 0.5
    kc, vc, kp, vp, table = _paged_layout(
        jax.random.PRNGKey(12), b, hkv, S, d, page, n_pages=64)
    clen = jnp.asarray(lens, jnp.int32)
    want = ops.decode_attention(q, kc, vc, clen, softcap=cap, impl="xla")
    got = ops.paged_decode_attention(q, kp, vp, clen, table,
                                     page_size=page, kv_cap=S, softcap=cap)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_decode_pallas_vs_xla(case):
    from repro.kernels.decode_attention import KernelType
    b, hq, hkv, S, d, page, lens, cap = case
    key = jax.random.PRNGKey(13)
    q = jax.random.normal(key, (b, hq, 1, d)) * 0.5
    _, _, kp, vp, table = _paged_layout(
        jax.random.PRNGKey(14), b, hkv, S, d, page, n_pages=64)
    clen = jnp.asarray(lens, jnp.int32)
    want = ops.paged_decode_attention(q, kp, vp, clen, table,
                                      page_size=page, kv_cap=S, softcap=cap)
    got = ops.paged_decode_attention(q, kp, vp, clen, table,
                                     page_size=page, kv_cap=S, softcap=cap,
                                     kernel=KernelType.PALLAS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kernel", ["XLA", "PALLAS"])
def test_paged_decode_reads_one_layer_of_stacked_pages(kernel):
    """Given a layer index, each paged path reads that layer of the stacked
    storage (L, n_pages + 1, page, hkv, d) in place, exactly as it reads
    that layer's storage alone."""
    from repro.kernels.decode_attention import KernelType
    b, hq, hkv, S, d, page, lens, cap = PAGED_CASES[0]
    q = jax.random.normal(jax.random.PRNGKey(17), (b, hq, 1, d)) * 0.5
    stores = [_paged_layout(jax.random.PRNGKey(18 + i), b, hkv, S, d, page,
                            n_pages=64) for i in range(3)]
    k_all = jnp.stack([st[2] for st in stores])
    v_all = jnp.stack([st[3] for st in stores])
    table, clen = stores[0][4], jnp.asarray(lens, jnp.int32)
    kw = dict(page_size=page, kv_cap=S, softcap=cap,
              kernel=KernelType[kernel])
    for layer in range(3):
        want = ops.paged_decode_attention(q, k_all[layer], v_all[layer],
                                          clen, table, **kw)
        got = ops.paged_decode_attention(q, k_all, v_all, clen, table,
                                         layer=jnp.int32(layer), **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_decode_trash_page_is_inert():
    """Garbage in the trash page (or any unreferenced page) cannot leak
    into the output: only table-referenced, length-valid positions
    contribute."""
    b, hq, hkv, S, d, page = 1, 4, 2, 64, 32, 16
    q = jax.random.normal(jax.random.PRNGKey(15), (b, hq, 1, d)) * 0.5
    _, _, kp, vp, table = _paged_layout(
        jax.random.PRNGKey(16), b, hkv, S, d, page, n_pages=32)
    clen = jnp.asarray([40], jnp.int32)
    base = ops.paged_decode_attention(q, kp, vp, clen, table,
                                      page_size=page, kv_cap=S)
    # poison the trash page and every page the table does not reference
    used = set(np.asarray(table).ravel().tolist())
    poison_k, poison_v = kp, vp
    for pid in range(33):
        if pid not in used:
            poison_k = poison_k.at[pid].set(1e9)
            poison_v = poison_v.at[pid].set(1e9)
    # positions past clen inside a referenced page are masked to exact 0
    got = ops.paged_decode_attention(q, poison_k, poison_v, clen, table,
                                     page_size=page, kv_cap=S)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
