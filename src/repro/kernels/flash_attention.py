"""Pallas TPU flash attention kernel.

TPU-native design: the grid's innermost dimension walks KV blocks while an
online-softmax accumulator (running max / denominator / weighted values)
persists in VMEM scratch.  Q/K/V tiles stream HBM->VMEM via BlockSpecs with
MXU-aligned (multiple-of-128) tiles.  Supports causal masking, sliding
windows (gemma2 local layers / long-context variant), gemma2 logit softcap
and GQA (KV-head index map folds the query-head group).

Validated on CPU with ``interpret=True`` against ``ref.attention``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, softcap: float,
                  block_q: int, block_k: int, seq_k: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the MXU multiplies the storage dtype exactly and accumulates in
    # f32; the scale applies to the f32 logits
    q = q_ref[0, 0]                                      # (bq, d)
    k = k_ref[0, 0]                                      # (bk, d)
    v = v_ref[0, 0]                                      # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_k                                   # padding guard
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                   # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows
    m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    p = jnp.where(mask, p, 0.0)
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


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: Optional[float] = None,
                    q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (b, hq, sq, d); k, v: (b, hkv, sk, d).  Returns (b, hq, sq, d)."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)

    # pad to block multiples: partially out-of-bounds blocks are poison in
    # interpret mode; masks below use the true seq_k so results are exact
    def _pad(x, mult):
        pad = (-x.shape[2]) % mult
        if pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return x
    q = _pad(q, block_q)
    k = _pad(k, block_k)
    v = _pad(v, block_k)
    sq_p = q.shape[2]

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, seq_k=sk,
        q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dv), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_p, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=resolve_interpret(interpret, "flash_attention"),
    )(q, k, v)
    return out[:, :, :sq]
