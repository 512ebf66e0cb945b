"""Dispatching wrappers around the Pallas kernels and their XLA twins.

Model code calls these entry points.  ``impl`` selects:
  - "pallas": the Pallas TPU kernel — compiled on a TPU, interpreted
    elsewhere (``kernels/backend.resolve_interpret``, the one backend
    probe); exercised by kernel tests and benchmarks.
  - "xla": a blocked, memory-safe pure-XLA implementation with the same
    streaming structure (online softmax over KV blocks / chunked SSD).  This
    is the default inside model forward passes so the multi-pod dry-run's
    ``cost_analysis()`` reflects fused HLO rather than interpreter loops.
  - "ref": the naive oracle (small shapes / tests).

Note on causal FLOPs: the dense-blocked XLA path computes masked upper-
triangle blocks (~2x attention FLOPs at long seq); the Pallas kernel and the
banded sliding-window path skip them.  EXPERIMENTS.md §Roofline accounts for
this in the MODEL_FLOPS/HLO_FLOPs ratio.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd
from repro.kernels import topk_retrieval as _topk

# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: Optional[float] = None,
                    q_offset: int = 0, impl: str = "xla",
                    block_q: int = 512, block_k: int = 1024) -> jax.Array:
    """q: (b, hq, sq, d); k, v: (b, hkv, sk, d) -> (b, hq, sq, d)."""
    sq, sk = q.shape[2], k.shape[2]
    if impl == "pallas":
        return _fa.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, q_offset=q_offset)
    # Unblocked path up to 4k x 4k: one fused logits tensor (sharded over
    # heads) beats the blocked scan under XLA, whose loop-invariant code
    # motion materializes every block's mask/logits at once (HC1-iter3,
    # EXPERIMENTS.md §Perf).  Only profitable when the head count shards
    # over the model axis (16) — otherwise the logits replicate and temp
    # memory explodes (starcoder2 kv=2 / qwen2-vl 28H).  Longer sequences
    # use the blocked/banded paths.
    heads_shardable = q.shape[1] % 16 == 0
    if impl == "ref" or (sq <= 1024 and sk <= 1024) or (
            sq <= 4096 and sk <= 4096 and heads_shardable):
        return ref.attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, scale=scale, q_offset=q_offset)
    if window > 0:
        return _banded_window_attention(
            q, k, v, window=window, causal=causal, softcap=softcap,
            scale=scale, q_offset=q_offset, block_q=block_q)
    return _blocked_attention(q, k, v, causal=causal, softcap=softcap,
                              scale=scale, q_offset=q_offset,
                              block_q=block_q, block_k=block_k)


def _pad_axis(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _blocked_attention(q, k, v, *, causal, softcap, scale, q_offset,
                       block_q, block_k):
    """Online-softmax attention; outer scan over q blocks, inner over kv."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, max(sq, 1))
    block_k = min(block_k, max(sk, 1))

    qp = _pad_axis(q, 2, block_q)
    kp = _pad_axis(k, 2, block_k)
    vp = _pad_axis(v, 2, block_k)
    nq, nk = qp.shape[2] // block_q, kp.shape[2] // block_k

    qb = qp.reshape(b, hkv, g, nq, block_q, d).transpose(3, 0, 1, 2, 4, 5)
    kb = kp.reshape(b, hkv, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = vp.reshape(b, hkv, nk, block_k, dv).transpose(2, 0, 1, 3, 4)

    def q_block(carry, inp):
        iq, qblk = inp                                  # (b,hkv,g,bq,d)

        def kv_block(inner, kinp):
            m, l, acc = inner
            ik, kblk, vblk = kinp
            # keep q/k in their storage dtype; store logits in that dtype
            # too (bf16 halves the dominant logits HBM traffic), then do the
            # softmax math in f32
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk, kblk,
                           preferred_element_type=q.dtype)
            s = s.astype(jnp.float32) * scale
            if softcap > 0.0:
                s = ref.softcap_fn(s, softcap)
            qpos = iq * block_q + jnp.arange(block_q) + q_offset
            kpos = ik * block_k + jnp.arange(block_k)
            mask = (kpos[None, :] < sk) & (qpos[:, None] < sq + q_offset)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            m_safe = jnp.where(m_new <= -1e30, 0.0, m_new)
            p = jnp.where(mask[None, None, None], jnp.exp(s - m_safe), 0.0)
            alpha = jnp.where(m <= -1e30, 0.0, jnp.exp(m - m_safe))
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # probs in the storage dtype for the p@v matmul (f32 accum)
            acc = acc * alpha + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(v.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        init = (jnp.full((b, hkv, g, block_q, 1), -1e30, jnp.float32),
                jnp.zeros((b, hkv, g, block_q, 1), jnp.float32),
                jnp.zeros((b, hkv, g, block_q, dv), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            kv_block, init, (jnp.arange(nk), kb, vb))
        out = acc / jnp.where(l == 0.0, 1.0, l)
        return carry, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_block, None, (jnp.arange(nq), qb))
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(b, hq, nq * block_q, dv)
    return out[:, :, :sq]


def _banded_window_attention(q, k, v, *, window, causal, softcap, scale,
                             q_offset, block_q):
    """Sliding-window attention with a static banded KV slice per q block.

    Exact-FLOPs path for gemma2 local layers and the long-context variant:
    each q block attends only a (window + block_q)-wide KV band fetched with
    a dynamic slice, so compiled FLOPs/bytes scale with window, not seq^2.
    """
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, sq)

    span = min(sk, window + block_q)
    qp = _pad_axis(q, 2, block_q)
    nq = qp.shape[2] // block_q
    qb = qp.reshape(b, hkv, g, nq, block_q, d).transpose(3, 0, 1, 2, 4, 5)

    def q_block(carry, inp):
        iq, qblk = inp
        q_end = iq * block_q + block_q + q_offset       # absolute, exclusive
        start = jnp.clip(q_end - span, 0, max(sk - span, 0))
        kblk = jax.lax.dynamic_slice_in_dim(k, start, span, axis=2)
        vblk = jax.lax.dynamic_slice_in_dim(v, start, span, axis=2)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk, kblk,
                       preferred_element_type=q.dtype)
        s = s.astype(jnp.float32) * scale
        if softcap > 0.0:
            s = ref.softcap_fn(s, softcap)
        qpos = iq * block_q + jnp.arange(block_q) + q_offset
        kpos = start + jnp.arange(span)
        mask = (kpos[None, :] < sk) & (qpos[:, None] < sq + q_offset)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        mask &= (qpos[:, None] - kpos[None, :]) < window
        s = jnp.where(mask[None, None, None], s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)
        m_safe = jnp.where(m <= -1e30, 0.0, m)
        p = jnp.where(mask[None, None, None], jnp.exp(s - m_safe), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), vblk,
                         preferred_element_type=jnp.float32)
        out = out / jnp.where(l == 0.0, 1.0, l)
        return carry, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_block, None, (jnp.arange(nq), qb))
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(b, hq, nq * block_q, dv)
    return out[:, :, :sq]


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0,
                     softcap: float = 0.0,
                     scale: Optional[float] = None,
                     impl: str = "xla") -> jax.Array:
    """Single-token attention against a cache.

    q: (b, hq, 1, d); caches: (b, hkv, S, d); cache_len: scalar or (b,) —
    number of valid cache entries INCLUDING the current token.
    """
    if impl == "pallas":
        return _da.decode_attention(q, k_cache, v_cache, cache_len,
                                    window=window, softcap=softcap,
                                    scale=scale)
    b, hq, _, d = q.shape
    hkv, S = k_cache.shape[1], k_cache.shape[2]
    dv = v_cache.shape[-1]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (b,))

    qf = q.astype(jnp.float32).reshape(b, hkv, g, d) * scale
    s = jnp.einsum("bhgd,bhkd->bhgk", qf, k_cache.astype(jnp.float32))
    if softcap > 0.0:
        s = ref.softcap_fn(s, softcap)
    kpos = jnp.arange(S)[None]                          # (1, S)
    mask = kpos < cache_len[:, None]
    if window > 0:
        mask &= kpos >= (cache_len[:, None] - window)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, hq, 1, dv).astype(q.dtype)


def _paged_decode_attention_xla(q, k_pages, v_pages, cache_len, page_table,
                                *, page_size: int, kv_cap: int,
                                softcap: float = 0.0,
                                scale: Optional[float] = None,
                                layer=None) -> jax.Array:
    """XLA paged path: gather each row's pages into a dense per-row view,
    slice to ``kv_cap``, then run the exact dense masked-softmax above.
    With ``layer`` the gather indexes the stacked pages as
    ``pages[layer, table]``, so no per-layer slice of them is made.

    Every valid cache position holds the same value as the dense layout
    (the scatter wrote it there) and every position past ``cache_len``
    reaches the softmax as an exact-zero probability, so this path is
    **bit-identical** to the dense oracle whenever ``kv_cap`` equals the
    dense cache length — the parity the tests pin down.
    """
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    b, hq, _, d = q.shape
    hkv = k_pages.shape[2]
    n_w = page_table.shape[1]
    kd = k_pages[layer, page_table]             # (b, W, hkv, page, hd)
    vd = v_pages[layer, page_table]
    kd = kd.transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, n_w * page_size, d)[:, :, :kv_cap]
    vd = vd.transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, n_w * page_size, -1)[:, :, :kv_cap]
    return decode_attention(q, kd, vd, cache_len, softcap=softcap,
                            scale=scale, impl="xla")


def _paged_decode_attention_pallas(q, k_pages, v_pages, cache_len,
                                   page_table, *, page_size: int,
                                   kv_cap: int, softcap: float = 0.0,
                                   scale: Optional[float] = None,
                                   layer=None) -> jax.Array:
    return _da.paged_decode_attention(
        q, k_pages, v_pages, cache_len, page_table, page_size=page_size,
        kv_cap=kv_cap, softcap=softcap, scale=scale, layer=layer)


# KernelType -> implementation, the dispatch idiom shared with the other
# kernels: model code picks an enum member (a static jit argument), never
# a string, so the mapping is the single registry of paged backends.
KernelTypeMapping = {
    _da.KernelType.PALLAS: _paged_decode_attention_pallas,
    _da.KernelType.XLA: _paged_decode_attention_xla,
}


def paged_decode_attention(q, k_pages, v_pages, cache_len, page_table, *,
                           page_size: int, kv_cap: int, softcap: float = 0.0,
                           scale: Optional[float] = None,
                           kernel=_da.KernelType.XLA,
                           layer=None) -> jax.Array:
    """Single-token attention against a block-paged cache.

    q: (b, hq, 1, d); k_pages/v_pages: (n_pages, hkv, page_size, d)
    physical page storage (the last page is the trash page), or with
    ``layer`` (an int32 scalar) every layer's storage stacked (L, n_pages,
    hkv, page_size, d), read at that layer in place; page_table: (b, W)
    int32; cache_len: scalar or (b,) valid lengths INCLUDING the current
    token.
    """
    return KernelTypeMapping[kernel](
        q, k_pages, v_pages, cache_len, page_table, page_size=page_size,
        kv_cap=kv_cap, softcap=softcap, scale=scale, layer=layer)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------
def ssd(x, dt, A, B, C, *, chunk: int = 128,
        init_state: Optional[jax.Array] = None,
        impl: str = "xla") -> Tuple[jax.Array, jax.Array]:
    if impl == "pallas":
        if init_state is not None:
            raise NotImplementedError("pallas ssd starts from zero state")
        return _ssd.ssd_scan(x, dt, A, B, C, chunk=chunk)
    return ref.ssd(x, dt, A, B, C, chunk=chunk, init_state=init_state)


ssd_decode_step = ref.ssd_decode_step


# ---------------------------------------------------------------------------
# Retrieval
# ---------------------------------------------------------------------------
def topk_retrieval(queries, anchors, k: int, *, impl: str = "xla",
                   anchors_prenormalized: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    if impl == "pallas":
        return _topk.topk_retrieval(
            queries, anchors, k, anchors_prenormalized=anchors_prenormalized)
    return ref.topk_retrieval(queries, anchors, k,
                              anchors_prenormalized=anchors_prenormalized)
