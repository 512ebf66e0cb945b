"""Mixture-of-Experts FFN (Qwen3-MoE, DeepSeek-V2 style).

Two forms of one layer, sharing the router:

``moe_forward`` (training, dry-run lowering) is token-choice routing with
capacity buckets, in the **einsum-dispatch** form (Mesh-TF / Flaxformer
lineage):

  1. tokens are regrouped into routing groups of <= MOE_GROUP tokens —
     small groups keep the (T, E, C) dispatch tensor tiny (C scales with
     group size) while remaining MXU-friendly;
  2. per group, top-k choices get a position-in-expert via a cumsum rank;
     tokens beyond capacity drop (capacity_factor), so a token's output
     depends on which other tokens share its group;
  3. dispatch/combine are one-hot einsums — **no scatter/gather**: a
     data-dependent scatter defeats the SPMD partitioner, which replicates
     the (G, E, C, d) buffer and all-reduces it across the mesh, while the
     einsums shard cleanly (the expert axis resharding lowers to the
     expected expert-parallel all-to-all);
  4. per-expert SwiGLU runs as batched einsums on the MXU (experts sharded
     on the ``model`` axis);
  5. shared experts (DeepSeek) are a dense SwiGLU on every token.

``moe_dropless`` (the serve path: prefill and decode) drops nothing, so a
row's output never depends on the other rows of its batch.  The (token,
expert) pairs routed to a held expert are laid out expert by expert in
blocks of rows (each expert's pairs start a new block), and a loop over the
blocks in use multiplies each block by its own expert's weights: an
expert's weights are read once per block of its pairs, and the FLOPs are
those of the routed pairs plus the last block's padding.

Both forms compute only the experts this device holds
(``cfg.experts_held`` from ``cfg.expert_offset``): the router keeps all
``num_experts`` outputs and every token its ``num_experts_per_tok``
choices, and a choice of an expert held elsewhere adds nothing here — the
part of the result that expert parallelism's other chips would give.  The
gate is the softmax over all experts at the chosen ones, renormalised over
the top-k only when ``cfg.norm_topk_prob`` (Qwen3-MoE: yes; DeepSeek-V2:
no).

The load-balance auxiliary loss is the switch-style E * sum(f_e * P_e).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init, shard_activation
from repro.models.mlp import init_mlp, mlp_forward

MOE_GROUP = 256


def init_moe(key, cfg: ModelConfig) -> Dict:
    """The router over all ``num_experts``; expert stacks of the held ones."""
    d, f = cfg.d_model, cfg.resolved_moe_d_ff
    e, held = cfg.num_experts, cfg.resolved_experts_held
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), scale=1.0),
        "wi_gate": jax.vmap(lambda k_: dense_init(k_, (d, f)))(
            jax.random.split(ks[1], held)),
        "wi_up": jax.vmap(lambda k_: dense_init(k_, (d, f)))(
            jax.random.split(ks[2], held)),
        "wo": jax.vmap(lambda k_: dense_init(k_, (f, d)))(
            jax.random.split(ks[3], held)),
    }
    if cfg.num_shared_experts > 0:
        p["shared"] = init_mlp(ks[4], cfg,
                               d_ff=cfg.resolved_moe_d_ff * cfg.num_shared_experts)
    return p


def _group_size(total: int) -> int:
    g = min(MOE_GROUP, total)
    while total % g != 0:
        g -= 1
    return g


def route(p: Dict, cfg: ModelConfig, x: jax.Array, *, precision=None):
    """Softmax router over all experts: (probs, top-k gate weights, top-k
    expert ids), the logits in float32."""
    logits = jnp.matmul(x.astype(jnp.float32),
                        p["router"].astype(jnp.float32), precision=precision)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        top_p = top_p / (jnp.sum(top_p, -1, keepdims=True) + 1e-9)
    return probs, top_p, top_i


def moe_forward(p: Dict, cfg: ModelConfig, x: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """x: (b, s, d) -> (y, aux_load_balance_loss); capacity dispatch."""
    b, s, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    held = cfg.resolved_experts_held
    total = b * s
    T = _group_size(total)
    G = total // T
    C = max(int(math.ceil(T * k / E * cfg.capacity_factor)), 1)

    xg = x.reshape(G, T, d)
    xg = shard_activation(xg, "batch", None, None)
    with jax.named_scope("route"):
        probs, top_p, top_i = route(p, cfg, xg)             # (G, T, k)

    # --- position-in-expert via cumsum rank over the (T*k) flat order ----
    # (a choice of an expert held elsewhere one-hots to zeros: no slot)
    oe = jax.nn.one_hot(top_i - cfg.expert_offset, held,
                        dtype=jnp.float32)                  # (G, T, k, Eh)
    oe_flat = oe.reshape(G, T * k, held)
    pos = jnp.cumsum(oe_flat, axis=1) * oe_flat                # rank occurrences
    pos = jnp.sum(pos, axis=-1).reshape(G, T, k) - 1.0         # (G, T, k)
    keep = (pos < C).astype(jnp.float32)
    pos_c = jnp.clip(pos, 0, C - 1).astype(jnp.int32)

    # --- one-hot dispatch / combine tensors (no scatter) -----------------
    # build in the activation dtype: the (G,T,E,C) products are the largest
    # routing tensors and exact in bf16 (entries are 0/1 and top-k probs)
    oe_a = oe.astype(x.dtype)
    oc = (jax.nn.one_hot(pos_c, C, dtype=jnp.float32)
          * keep[..., None]).astype(x.dtype)
    dispatch = jnp.einsum("gtke,gtkc->gtec", oe_a, oc)         # (G, T, E, C)
    combine = jnp.einsum("gtke,gtkc->gtec", oe_a,
                         oc * top_p[..., None].astype(x.dtype))
    dispatch = shard_activation(dispatch, "batch", None, None, None)
    combine = shard_activation(combine, "batch", None, None, None)

    with jax.named_scope("experts"):
        # --- dispatch to experts -----------------------------------------
        expert_in = jnp.einsum("gtec,gtd->gecd", dispatch, xg)  # (G,E,C,d)
        expert_in = shard_activation(expert_in, "batch", None, None, None)

        # --- expert compute: weight-gathered expert parallelism ----------
        # Tokens stay sharded on (pod, data); the (much smaller) expert
        # weights are gathered per layer instead: resharding tokens
        # group->expert would make GSPMD all-gather the whole global
        # expert_in, an order of magnitude more traffic than the weights.
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in,
                                   p["wi_gate"])) \
            * jnp.einsum("gecd,edf->gecf", expert_in, p["wi_up"])
        expert_out = jnp.einsum("gecf,efd->gecd", h, p["wo"])  # (G,E,C,d)
        expert_out = shard_activation(expert_out, "batch", None, None, None)

        # --- combine ------------------------------------------------------
        y = jnp.einsum("gtec,gecd->gtd", combine, expert_out)
        y = y.reshape(b, s, d)

    if cfg.num_shared_experts > 0:
        with jax.named_scope("shared"):
            y = y + mlp_forward(p["shared"], cfg, x)

    # --- load-balance aux loss -------------------------------------------
    frac_tokens = jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32),
                          axis=(0, 1, 2)) / (G * T * k)         # f_e
    mean_prob = jnp.mean(probs, axis=(0, 1))                   # P_e
    aux = E * jnp.sum(frac_tokens * mean_prob)
    return y, aux


def block_rows(tokens: int, cfg: ModelConfig) -> int:
    """Rows per expert block of ``moe_dropless``: a power of two near
    twice the pairs an expert gets on average, within [8, 512], so a block
    usually holds all of an expert's pairs at decode and the weights are
    read once per block of a long prefill."""
    mean = tokens * cfg.num_experts_per_tok / cfg.num_experts
    return int(min(512, max(8, 2 ** math.ceil(math.log2(max(2 * mean, 1))))))


def moe_dropless(p: Dict, cfg: ModelConfig, x: jax.Array, routed=None
                 ) -> Tuple[jax.Array, jax.Array]:
    """x: (b, s, d) -> (y, tokens routed to each held expert (Eh,) int32).

    No pair is dropped: the blocks are sized for the worst case (every
    token choosing min(k, Eh) held experts) and the loop runs over the
    blocks in use only.  A token's routed part sums its held experts'
    outputs in expert order, in float32, whatever else is in the batch.

    ``routed`` (b, s) bool, when given, marks the tokens whose routed part
    is computed; the others (a prompt's right padding, a row bucket's
    filler rows: positions whose outputs nothing reads) get the shared
    experts only, and add no expert block and no count.
    """
    b, s, d = x.shape
    k, held = cfg.num_experts_per_tok, cfg.resolved_experts_held
    T = b * s
    B = block_rows(T, cfg)
    n_max = -(-T * min(k, held) // B) + held           # blocks, worst case
    x2 = x.reshape(T, d)
    with jax.named_scope("route"):
        _, top_p, top_i = route(p, cfg, x2,
                                precision=jax.lax.Precision.HIGHEST)
        e = (top_i - cfg.expert_offset).reshape(-1)          # (T*k,)
        mine = (e >= 0) & (e < held)
        if routed is not None:
            mine = mine & jnp.repeat(routed.reshape(-1), k)
        e = jnp.where(mine, e, held)                         # held: absent
        oh = jax.nn.one_hot(e, held + 1, dtype=jnp.int32)    # (T*k, Eh+1)
        rank = jnp.sum(jnp.cumsum(oh, axis=0) * oh, axis=-1) - 1
        counts = jnp.sum(oh, axis=0)[:held]                  # (Eh,)
        blocks = -(-counts // B)
        block_end = jnp.cumsum(blocks)
        start = jnp.concatenate([block_end - blocks,
                                 jnp.full((1,), n_max, blocks.dtype)])
        dest = start[e] * B + rank                           # absent: dropped
        tok = jnp.arange(T * k, dtype=jnp.int32) // k
        rows = jnp.full((n_max * B,), T, jnp.int32).at[dest].set(
            tok, mode="drop")                                # T: a zero row
        gate = jnp.zeros((n_max * B,), jnp.float32).at[dest].set(
            top_p.reshape(-1), mode="drop")
        expert_of = jnp.minimum(
            jnp.searchsorted(block_end, jnp.arange(n_max), side="right"),
            held - 1)

    with jax.named_scope("experts"):
        xz = jnp.concatenate([x2, jnp.zeros((1, d), x.dtype)])

        def body(i, acc):
            r = jax.lax.dynamic_slice_in_dim(rows, i * B, B)
            g = jax.lax.dynamic_slice_in_dim(gate, i * B, B)
            ei = expert_of[i]
            xb = xz[r]
            h = jax.nn.silu(xb @ p["wi_gate"][ei]) * (xb @ p["wi_up"][ei])
            out = (h @ p["wo"][ei]).astype(jnp.float32) * g[:, None]
            return acc.at[r].add(out)

        acc = jax.lax.fori_loop(0, block_end[-1], body,
                                jnp.zeros((T + 1, d), jnp.float32))
        y = acc[:T].astype(x.dtype).reshape(b, s, d)

    if cfg.num_shared_experts > 0:
        with jax.named_scope("shared"):
            y = y + mlp_forward(p["shared"], cfg, x)
    return y, counts.astype(jnp.int32)
