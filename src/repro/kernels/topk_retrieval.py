"""Pallas TPU kernel for anchor-set top-k cosine retrieval (SCOPE Eq. 2).

The anchor matrix streams HBM->VMEM in tiles along the innermost grid
dimension; per query-tile a running (scores, indices) top-k buffer persists
in VMEM scratch and is merged with each anchor tile's scores.  Cosine
normalization is pre-applied outside the kernel (cheap, fused by XLA) so the
kernel body is a pure MXU matmul + merge.  The merge is k rounds of
max-and-mask over the running buffer and the tile (Mosaic lowers neither
``lax.top_k`` nor a lane concatenation of the two); ties go to the lower
anchor index, as ``lax.top_k`` breaks them.

Validated against ``ref.topk_retrieval`` in interpret mode; compiles for
TPU v5e (``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG = -2.0          # below min cosine similarity: empty buffer slots
TAKEN = -3.0        # below NEG: an entry already moved into the output
_NO_INDEX = jnp.iinfo(jnp.int32).max


def _topk_kernel(q_ref, a_ref, sc_out_ref, ix_out_ref, sc_ref, ix_ref, *,
                 k: int, block_n: int, num_anchors: int):
    ia = pl.program_id(1)
    na = pl.num_programs(1)

    @pl.when(ia == 0)
    def _init():
        sc_ref[...] = jnp.full_like(sc_ref, NEG)
        ix_ref[...] = jnp.full_like(ix_ref, _NO_INDEX)

    q = q_ref[...]                                   # (bq, d) normalized
    a = a_ref[...]                                   # (bn, d) normalized
    # HIGHEST: one bf16 MXU pass would reorder near-tied anchors
    sims = jax.lax.dot_general(q, a, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)  # (bq, bn)
    base = ia * block_n
    idx = base + jax.lax.broadcasted_iota(jnp.int32, sims.shape, 1)
    sims = jnp.where(idx < num_anchors, sims, NEG)

    # merge running top-k with this tile: each round takes the best score
    # left in either, lowest anchor index on ties, and masks it out
    run_sc, run_ix = sc_ref[...], ix_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, run_sc.shape, 1)
    out_sc = jnp.full_like(run_sc, NEG)
    out_ix = jnp.full_like(run_ix, _NO_INDEX)
    for j in range(k):
        best = jnp.maximum(jnp.max(run_sc, axis=1, keepdims=True),
                           jnp.max(sims, axis=1, keepdims=True))
        pick = jnp.minimum(
            jnp.min(jnp.where(run_sc == best, run_ix, _NO_INDEX), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(sims == best, idx, _NO_INDEX), axis=1,
                    keepdims=True))
        out_sc = jnp.where(col == j, best, out_sc)
        out_ix = jnp.where(col == j, pick, out_ix)
        run_sc = jnp.where((run_ix == pick) & (run_sc == best), TAKEN,
                           run_sc)
        sims = jnp.where((idx == pick) & (sims == best), TAKEN, sims)
    sc_ref[...] = out_sc
    ix_ref[...] = out_ix

    @pl.when(ia == na - 1)
    def _finish():
        sc_out_ref[...] = sc_ref[...]
        ix_out_ref[...] = ix_ref[...]


def topk_retrieval(queries: jax.Array, anchors: jax.Array, k: int, *,
                   block_q: int = 128, block_n: int = 256,
                   interpret: Optional[bool] = None,
                   anchors_prenormalized: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """queries (q, d), anchors (n, d) -> (scores (q, k), indices (q, k)).

    ``anchors_prenormalized`` skips the per-call anchor normalization for
    callers (``AnchorRetriever``) that cache the unit-norm anchor matrix.
    """
    nq, d = queries.shape
    na = anchors.shape[0]
    qn = (queries / (jnp.linalg.norm(queries, axis=-1, keepdims=True) + 1e-8)
          ).astype(jnp.float32)
    if anchors_prenormalized:
        an = anchors.astype(jnp.float32)
    else:
        an = (anchors / (jnp.linalg.norm(anchors, axis=-1, keepdims=True)
                         + 1e-8)).astype(jnp.float32)

    block_q = min(block_q, nq)
    block_n = min(block_n, na)
    gq = pl.cdiv(nq, block_q)
    gn = pl.cdiv(na, block_n)

    kernel = functools.partial(_topk_kernel, k=k, block_n=block_n,
                               num_anchors=na)
    scores, idx = pl.pallas_call(
        kernel,
        grid=(gq, gn),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda iq, ia: (iq, 0)),
            pl.BlockSpec((block_n, d), lambda iq, ia: (ia, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda iq, ia: (iq, 0)),
            pl.BlockSpec((block_q, k), lambda iq, ia: (iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, k), jnp.float32),
            jax.ShapeDtypeStruct((nq, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret, "topk_retrieval"),
    )(qn, an)
    return scores, idx
