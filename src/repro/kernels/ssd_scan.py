"""Pallas TPU kernel for the Mamba2 SSD chunk scan.

TPU adaptation of the SSD (state-space duality) algorithm: GPU
implementations use warp-level scans; here each chunk's intra-chunk work is
expressed as MXU matmuls over a VMEM-resident (chunk x chunk) decay matrix,
and the inter-chunk recurrence is carried in VMEM scratch across the
innermost grid dimension (chunks are visited sequentially per (batch, head)).
The in-chunk cumulative decay is itself a matmul against a triangular
matrix of ones (Mosaic has no ``cumsum`` lowering).  Every matmul runs at
``HIGHEST`` precision: the scan's inputs are float32, and the MXU's
default one bf16 pass would round them to 8 bits.

Inputs are per-head: the grid is (batch, heads, num_chunks); BlockSpecs
stream one chunk of x/dt/B/C per step.  Chunk length should be a multiple of
128 for MXU alignment (the interpret-mode tests also sweep small chunks).

Validated against ``ref.ssd`` in interpret mode; compiles for TPU v5e
(``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

_HIGHEST = jax.lax.Precision.HIGHEST


def _ssd_kernel(x_ref, dt_ref, a_log_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_ref, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (t, p)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (t, 1)
    A = a_log_ref[0, 0]                          # (1, 1) negative rate
    B = b_ref[0].astype(jnp.float32)             # (t, n)
    C = c_ref[0].astype(jnp.float32)             # (t, n)

    a = dt * A                                   # (t, 1) log decay <= 0
    xdt = x * dt                                 # discretized input

    # cumulative decays as triangular matmuls: tri[s, t] = (t <= s), so
    # tri @ a is the column cumsum and a^T @ tri^T the same sums as a row
    srow = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    tcol = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = srow >= tcol
    tri = causal.astype(jnp.float32)
    a_cum = jax.lax.dot_general(tri, a, (((1,), (0,)), ((), ())),
                                precision=_HIGHEST,
                                preferred_element_type=jnp.float32)  # (t, 1)
    a_cum_row = jax.lax.dot_general(a, tri, (((0,), (1,)), ((), ())),
                                    precision=_HIGHEST,
                                    preferred_element_type=jnp.float32)  # (1, t)
    a_total = jnp.sum(a, axis=0, keepdims=True)  # (1, 1)

    # intra-chunk decay matrix L[s, t] = exp(sum_{t<k<=s} a_k), t <= s
    seg = a_cum - a_cum_row                      # (s, t) = a_cum[s] - a_cum[t]
    L = jnp.where(causal, jnp.exp(seg), 0.0)

    # y_diag = (C B^T * L) @ xdt
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             precision=_HIGHEST,
                             preferred_element_type=jnp.float32)  # (s, t)
    y = jax.lax.dot_general(cb * L, xdt, (((1,), (0,)), ((), ())),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)   # (s, p)

    # inter-chunk: y += (C decayed) @ h_entry^T   with h_entry (p, n)
    h_entry = state_ref[...]                                       # (p, n)
    c_dec = C * jnp.exp(a_cum)                                     # (s, n)
    y += jax.lax.dot_general(c_dec, h_entry, (((1,), (1,)), ((), ())),
                             precision=_HIGHEST,
                             preferred_element_type=jnp.float32)   # (s, p)

    y_ref[0, 0, ...] = y.astype(y_ref.dtype)

    # state update: h_exit = exp(a_total) h_entry + sum_t decay_t xdt_t B_t
    decay_states = jnp.exp(a_total - a_cum)                        # (t, 1)
    upd = jax.lax.dot_general(xdt * decay_states, B,
                              (((0,), (0,)), ((), ())),
                              precision=_HIGHEST,
                              preferred_element_type=jnp.float32)  # (p, n)
    state_ref[...] = state_ref[...] * jnp.exp(a_total) + upd

    @pl.when(ic == nc - 1)
    def _finish():
        state_out_ref[0, 0, ...] = state_ref[...]


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, *, chunk: int = 128,
             interpret: Optional[bool] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """Pallas SSD over full sequences.

    x: (b, l, h, p); dt: (b, l, h); A: (h,); B, C: (b, l, n).
    Returns (y (b, l, h, p), final_state (b, h, p, n)).
    """
    b, l, h, p = x.shape
    n = B.shape[-1]
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk

    # layout: per-(batch, head) chunked views
    xt = x.transpose(0, 2, 1, 3)                      # (b, h, l, p)
    dtt = dt.transpose(0, 2, 1)[..., None]            # (b, h, l, 1)
    a_log = A.reshape(1, h, 1, 1)                     # broadcastable block
    a_log = jnp.broadcast_to(a_log, (b, h, 1, 1))

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, final_state = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b_, h_, c_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, l, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=resolve_interpret(interpret, "ssd_scan"),
    )(xt, dtt, a_log, B, C)
    return y.transpose(0, 2, 1, 3), final_state
