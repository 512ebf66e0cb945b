"""Kernel micro-benchmarks: XLA twins (jitted, wall time) and Pallas
interpret-mode parity cost.  On CPU the Pallas numbers measure the
interpreter, not the TPU — the roofline benchmark covers the TPU story."""
from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def _time(fn, *args, iters=5) -> float:
    fn(*args)                                   # compile / warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(bundle=None) -> List[Tuple[str, float, str]]:
    rows = []
    key = jax.random.PRNGKey(0)

    # flash attention (XLA blocked path)
    for (b, hq, hkv, s, d) in [(1, 8, 2, 2048, 128), (1, 8, 8, 4096, 64)]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (b, hq, s, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.bfloat16)
        f = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=True))
        us = _time(f, q, k, v)
        flops = 2 * 2 * b * hq * s * s * d / 2   # causal half
        rows.append((f"kernel/attn_xla_b{b}h{hq}s{s}d{d}", us,
                     f"gflops_s={flops/us/1e3:.1f}"))
        fw = jax.jit(lambda q, k, v: ops.flash_attention(
            q, k, v, causal=True, window=512))
        rows.append((f"kernel/attn_xla_window512_s{s}", _time(fw, q, k, v),
                     "banded"))

    # ssd scan (ref path)
    b, l, h, p, n = 2, 2048, 8, 64, 64
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, l, h, p)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, n)) * 0.3
    C = jax.random.normal(ks[4], (b, l, n)) * 0.3
    f = jax.jit(lambda *a: ops.ssd(*a, chunk=128)[0])
    rows.append((f"kernel/ssd_xla_l{l}h{h}p{p}n{n}",
                 _time(f, x, dt, A, B, C), "chunked_dual_form"))

    # paged decode attention (XLA gather path) vs the dense decode twin
    b, hq, hkv, S, d, page = 4, 8, 2, 2048, 64, 16
    n_pages = b * (S // page) + 1                # + trash page
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, 1, d))
    kc = jax.random.normal(ks[1], (b, hkv, S, d))
    vc = jax.random.normal(ks[2], (b, hkv, S, d))
    kp = jnp.zeros((n_pages, page, hkv, d))       # token-major pages
    vp = jnp.zeros((n_pages, page, hkv, d))
    table = np.full((b, S // page), n_pages - 1, np.int32)
    nxt = 0
    for i in range(b):                           # scatter rows into pages
        for j in range(S // page):
            sl = slice(j * page, (j + 1) * page)
            kp = kp.at[nxt].set(kc[i, :, sl].swapaxes(0, 1))
            vp = vp.at[nxt].set(vc[i, :, sl].swapaxes(0, 1))
            table[i, j] = nxt
            nxt += 1
    lens = jnp.full((b,), S, jnp.int32)
    table = jnp.asarray(table)
    fd = jax.jit(lambda q, k, v, n: ops.decode_attention(q, k, v, n))
    rows.append((f"kernel/decode_attn_dense_b{b}s{S}",
                 _time(fd, q, kc, vc, lens), "dense_cache"))
    fp = jax.jit(lambda q, k, v, n, t: ops.paged_decode_attention(
        q, k, v, n, t, page_size=page, kv_cap=S))
    rows.append((f"kernel/decode_attn_paged_b{b}s{S}p{page}",
                 _time(fp, q, kp, vp, lens, table), "paged_gather"))

    # topk retrieval
    q = jax.random.normal(ks[0], (256, 32))
    a = jax.random.normal(ks[1], (250, 32))
    f = jax.jit(lambda q, a: ops.topk_retrieval(q, a, 5)[0])
    rows.append(("kernel/topk_xla_q256_a250", _time(f, q, a),
                 "anchor_retrieval"))

    # pallas interpret parity spot (correctness tax on CPU, not perf)
    qs = jax.random.normal(ks[0], (1, 4, 256, 64))
    kk = jax.random.normal(ks[1], (1, 2, 256, 64))
    vv = jax.random.normal(ks[2], (1, 2, 256, 64))
    t0 = time.perf_counter()
    ops.flash_attention(qs, kk, vv, impl="pallas")
    rows.append(("kernel/attn_pallas_interpret_s256",
                 (time.perf_counter() - t0) * 1e6, "interpret_mode"))
    return rows
