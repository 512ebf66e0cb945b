"""Pallas TPU kernels for single-token decode attention (dense + paged).

The decode hot spot is a memory-bound sweep of the KV cache: one query
token attends to S cached keys.  The grid walks KV blocks sequentially per
(batch, kv-head); an online-softmax accumulator for all grouped query
heads lives in VMEM scratch, so the cache streams HBM->VMEM exactly once
— the roofline-optimal traffic for this op.

Masking supports a per-batch valid length (``cache_len``, scalar-prefetched
into SMEM like the paged kernel's table) and an optional sliding window
(both used by the ring-buffer serving caches).

``paged_decode_attention`` is the block-paged variant backing the KV pool
(`serving/kv_pool.py`): the cache lives as (n_pages, hkv, page_size, hd)
physical pages and each row's logical block ``iw`` is resolved through a
scalar-prefetched page table — ``PrefetchScalarGridSpec`` makes the table
available to the BlockSpec index map, so the grid DMAs exactly the pages a
row owns and never materializes a gathered dense cache.  Callers select
the implementation via the ``KernelType`` enum (``KernelTypeMapping`` in
``kernels/ops.py`` maps it to this kernel or the XLA gather path).

Validated against ``ref.attention`` / ``ops.decode_attention`` in
interpret mode; both kernels compile for TPU v5e
(``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import enum
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


class KernelType(enum.Enum):
    """Which paged decode-attention implementation to dispatch."""
    PALLAS = 0
    XLA = 1


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, window: int, softcap: float,
                   block_k: int, seq_k: int):
    ib = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the MXU multiplies the storage dtype exactly and accumulates in
    # f32; the scale applies to the f32 logits
    q = q_ref[0, 0]                                      # (g, d)
    k = k_ref[0, 0]                                      # (bk, d)
    v = v_ref[0, 0]                                      # (bk, dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    valid = len_ref[ib]
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (kpos < valid) & (kpos < seq_k)
    if window > 0:
        mask &= kpos >= (valid - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
    alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0, ...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len, *, window: int = 0, softcap: float = 0.0,
                     scale: Optional[float] = None, block_k: int = 512,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q: (b, hq, 1, d); caches: (b, hkv, S, d[v]); cache_len: (b,) or
    scalar valid lengths.  Returns (b, hq, 1, dv)."""
    b, hq, _, d = q.shape
    hkv, S = k_cache.shape[1], k_cache.shape[2]
    dv = v_cache.shape[-1]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))

    block_k = min(block_k, S)
    pad = (-S) % block_k
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = (S + pad) // block_k

    qg = q.reshape(b, hkv, g, d)[:, :, None]             # (b, hkv, 1, g, d)

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, softcap=softcap,
        block_k=block_k, seq_k=S)
    # the per-row lengths ride in SMEM through scalar prefetch: a rank-1
    # (1,) block of them is refused by the TPU lowering's tiling rule
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, ik, lens: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, ik, lens: (b_, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b_, h, ik, lens: (b_, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv),
                               lambda b_, h, ik, lens: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dv), q.dtype),
        interpret=resolve_interpret(interpret, "decode_attention"),
    )(cache_len, qg[:, :, 0], k_cache, v_cache)
    return out.reshape(b, hq, 1, dv)


def _paged_decode_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float,
                         softcap: float, page_size: int, kv_cap: int,
                         n_w: int):
    """One grid step = one logical page of one (batch row, kv head).

    ``table_ref``/``len_ref`` are scalar-prefetched: the flattened page
    table already steered the BlockSpec index map, so ``k_ref``/``v_ref``
    hold the *physical* page this row's logical block ``iw`` maps to
    (the trash page for unallocated entries — fully masked below).
    """
    ib = pl.program_id(0)
    iw = pl.program_id(2)

    @pl.when(iw == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the MXU multiplies the storage dtype exactly and accumulates in
    # f32; the scale applies to the f32 logits
    q = q_ref[0, 0]                                      # (g, d)
    k = k_ref[0, 0]                                      # (page, d)
    v = v_ref[0, 0]                                      # (page, dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    valid = len_ref[ib]
    kpos = iw * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (kpos < valid) & (kpos < kv_cap)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
    alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(iw == n_w - 1)
    def _finish():
        denom = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0, ...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, cache_len, page_table,
                           *, page_size: int, kv_cap: int,
                           softcap: float = 0.0,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Block-paged decode attention.

    q: (b, hq, 1, d); k_pages/v_pages: (n_pages, hkv, page_size, d[v])
    physical page storage; page_table: (b, W) int32 mapping each row's
    logical page to a physical one; cache_len: (b,) or scalar valid
    lengths; kv_cap: the per-row logical capacity (W * page_size rounded
    down to it).  Returns (b, hq, 1, dv).
    """
    b, hq, _, d = q.shape
    hkv = k_pages.shape[1]
    dv = v_pages.shape[-1]
    g = hq // hkv
    n_w = page_table.shape[1]
    scale = scale if scale is not None else d ** -0.5
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    table_flat = jnp.asarray(page_table, jnp.int32).reshape(-1)   # (b*W,)

    qg = q.reshape(b, hkv, g, d)

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, softcap=softcap,
        page_size=page_size, kv_cap=kv_cap, n_w=n_w)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_w),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda b_, h, iw, tbl, lens: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda b_, h, iw, tbl, lens:
                         (tbl[b_ * n_w + iw], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dv),
                         lambda b_, h, iw, tbl, lens:
                         (tbl[b_ * n_w + iw], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv),
                               lambda b_, h, iw, tbl, lens: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dv), q.dtype),
        interpret=resolve_interpret(interpret, "paged_decode_attention"),
    )(table_flat, cache_len, qg, k_pages, v_pages)
    return out.reshape(b, hq, 1, dv)
