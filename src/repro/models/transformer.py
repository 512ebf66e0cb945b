"""Decoder/encoder stack assembly.

Layers are grouped into homogeneous **segments**; per-layer parameters are
stacked (leading layer axis) and the layer body is applied with
``jax.lax.scan`` so the traced HLO contains each distinct layer body once —
this keeps 94-layer MoE dry-run compiles tractable on 512 devices.

Unit patterns handle heterogeneous stacks:
  gemma2      -> unit ("attn_local", "attn") x 21
  zamba2      -> unit ("mamba",)*6 + ("shared_attn",) x 13  (+ remainder)
  deepseek-v2 -> segment ("mla",) x 1 (dense layer 0) + ("mla_moe",) x 26
``shared_attn`` blocks reuse one parameter set (closed over, Zamba2-style)
but keep per-occurrence KV caches.

An attention block's two halves run under ``jax.named_scope`` ``attn``
(norm through the residual add) and ``mlp`` — ``moe`` for an expert layer,
with ``route``, ``experts`` and ``shared`` inside — so a profile's ops name
the half they belong to.

The serve path (``prefill``, ``decode_step``: ``dropless=True``) runs expert
layers drop-free (``moe.moe_dropless``) and returns, per segment, the
tokens each layer routed to each held expert (``stats["expert_tokens"]``,
(count, experts_held)); training keeps the capacity dispatch.  A segment
with no expert layer returns an empty ``stats``, which adds nothing to the
traced program.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (init_rmsnorm, rmsnorm, shard_activation,
                                 stacked_init)


# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------
def build_plan(cfg: ModelConfig) -> List[Tuple[Tuple[str, ...], int]]:
    """Returns [(unit_kinds, count), ...] covering the decoder stack."""
    if cfg.has_ssm() and cfg.shared_attn_every > 0:
        every = cfg.shared_attn_every
        unit = ("mamba",) * every + ("shared_attn",)
        full = cfg.num_layers // every
        rem = cfg.num_layers % every
        plan = []
        if full:
            plan.append((unit, full))
        if rem:
            plan.append((("mamba",), rem))
        return plan

    kinds = list(cfg.layer_kinds())
    # first_dense_layers: MoE variants fall back to dense FFN
    for i in range(min(cfg.first_dense_layers, len(kinds))):
        kinds[i] = {"mla_moe": "mla", "moe": "attn"}.get(kinds[i], kinds[i])

    pat = cfg.block_pattern
    if (len(pat) > 1 and len(kinds) % len(pat) == 0
            and tuple(kinds[:len(pat)]) == pat
            and all(kinds[i] == pat[i % len(pat)] for i in range(len(kinds)))):
        return [(tuple(pat), len(kinds) // len(pat))]

    # group consecutive identical kinds
    plan = []
    i = 0
    while i < len(kinds):
        j = i
        while j < len(kinds) and kinds[j] == kinds[i]:
            j += 1
        plan.append(((kinds[i],), j - i))
        i = j
    return plan


def _is_attn(kind: str) -> bool:
    return kind in ("attn", "attn_local", "moe", "shared_attn")


def _is_mla(kind: str) -> bool:
    return kind in ("mla", "mla_moe")


def _is_moe(kind: str) -> bool:
    return kind in ("moe", "mla_moe")


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------
def init_block(key, cfg: ModelConfig, kind: str, *, cross: bool = False) -> Dict:
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    if kind == "mamba":
        return {"norm": init_rmsnorm(d), "mamba": ssm_mod.init_mamba(ks[0], cfg)}
    p: Dict = {"attn_norm": init_rmsnorm(d), "mlp_norm": init_rmsnorm(d)}
    if _is_mla(kind):
        p["attn"] = attn_mod.init_mla(ks[0], cfg)
    else:
        p["attn"] = attn_mod.init_gqa(ks[0], cfg)
    if _is_moe(kind):
        p["moe"] = moe_mod.init_moe(ks[1], cfg)
    else:
        p["mlp"] = mlp_mod.init_mlp(ks[1], cfg)
    if cfg.sandwich_norm:
        p["post_attn_norm"] = init_rmsnorm(d)
        p["post_mlp_norm"] = init_rmsnorm(d)
    if cross:
        p["cross_norm"] = init_rmsnorm(d)
        p["cross"] = attn_mod.init_cross(ks[2], cfg)
    return p


# ---------------------------------------------------------------------------
# Block apply — full sequence
# ---------------------------------------------------------------------------
def _ffn(p: Dict, cfg: ModelConfig, kind: str, h: jax.Array,
         dropless: bool, full: bool, routed=None
         ) -> Tuple[jax.Array, jax.Array, Dict]:
    """The block's second half, residual add included: (h, moe_aux or
    None, stats).  ``full``: a whole sequence, whose residual stream is
    sharding-constrained; ``routed``: ``moe_dropless``'s token mask."""
    aux = None
    stats: Dict = {}
    with jax.named_scope("moe" if _is_moe(kind) else "mlp"):
        x2 = rmsnorm(p["mlp_norm"], h, cfg.rmsnorm_eps)
        if _is_moe(kind) and dropless:
            y2, stats["expert_tokens"] = moe_mod.moe_dropless(
                p["moe"], cfg, x2, routed)
        elif _is_moe(kind):
            y2, aux = moe_mod.moe_forward(p["moe"], cfg, x2)
        else:
            y2 = mlp_mod.mlp_forward(p["mlp"], cfg, x2)
        if cfg.sandwich_norm:
            y2 = rmsnorm(p["post_mlp_norm"], y2, cfg.rmsnorm_eps)
        if full:
            return shard_activation(h + y2, "batch", None, "residual"), aux, \
                stats
        return h + y2, aux, stats


def block_full(p: Dict, cfg: ModelConfig, kind: str, h: jax.Array,
               cos, sin, *, enc_out=None, causal: bool = True,
               dropless: bool = False, routed=None
               ) -> Tuple[jax.Array, Dict, jax.Array, Dict]:
    """Returns (h, cache, moe_aux, stats)."""
    aux = jnp.zeros((), jnp.float32)
    cache: Dict = {}
    if kind == "mamba":
        y, cache = ssm_mod.mamba_full(p["mamba"], cfg,
                                      rmsnorm(p["norm"], h, cfg.rmsnorm_eps))
        return (shard_activation(h + y, "batch", None, "residual"), cache,
                aux, {})

    with jax.named_scope("attn"):
        x = rmsnorm(p["attn_norm"], h, cfg.rmsnorm_eps)
        if _is_mla(kind):
            y, kv = attn_mod.mla_full(p["attn"], cfg, x, cos, sin, kind=kind,
                                      causal=causal)
        else:
            y, kv = attn_mod.gqa_full(p["attn"], cfg, x, cos, sin, kind=kind,
                                      causal=causal)
        if cfg.sandwich_norm:
            y = rmsnorm(p["post_attn_norm"], y, cfg.rmsnorm_eps)
        h = h + y
    cache.update(kv)

    if "cross" in p and enc_out is not None:
        ckv = attn_mod.cross_kv(p["cross"], cfg, enc_out)
        xc = rmsnorm(p["cross_norm"], h, cfg.rmsnorm_eps)
        h = h + attn_mod.cross_attend(p["cross"], cfg, xc, ckv)
        cache.update(ckv)

    out, a, stats = _ffn(p, cfg, kind, h, dropless, True, routed)
    return out, cache, aux if a is None else a, stats


# ---------------------------------------------------------------------------
# Block apply — single-token decode
# ---------------------------------------------------------------------------
def block_decode(p: Dict, cfg: ModelConfig, kind: str, h: jax.Array,
                 cos, sin, cache: Dict, pos, *, paged=None
                 ) -> Tuple[jax.Array, Dict, Dict]:
    """Returns (h, cache, stats).  ``paged`` = (PagedSpec, page table
    (b, W)) routes attention layers through the block-paged cache layout
    (GQA K/V pages or MLA latent pages); ``kv_pool.check_paged_support``
    guarantees only full-window GQA and MLA kinds reach here when it is
    set."""
    if kind == "mamba":
        y, new = ssm_mod.mamba_decode(p["mamba"], cfg,
                                      rmsnorm(p["norm"], h, cfg.rmsnorm_eps),
                                      cache)
        return h + y, new, {}

    new_cache: Dict = {}
    with jax.named_scope("attn"):
        x = rmsnorm(p["attn_norm"], h, cfg.rmsnorm_eps)
        if paged is not None and _is_mla(kind):
            spec, table = paged
            y, kv = attn_mod.mla_decode_paged(p["attn"], cfg, x, cos, sin,
                                              cache, pos, table, spec)
        elif paged is not None:
            spec, table = paged
            y, kv = attn_mod.gqa_decode_paged(p["attn"], cfg, x, cos, sin,
                                              cache, pos, table, spec,
                                              kind=kind)
        elif _is_mla(kind):
            y, kv = attn_mod.mla_decode(p["attn"], cfg, x, cos, sin, cache,
                                        pos, kind=kind)
        else:
            y, kv = attn_mod.gqa_decode(p["attn"], cfg, x, cos, sin, cache,
                                        pos, kind=kind)
        if cfg.sandwich_norm:
            y = rmsnorm(p["post_attn_norm"], y, cfg.rmsnorm_eps)
        h = h + y
    new_cache.update(kv)

    if "cross" in p:
        ckv = {"ck": cache["ck"], "cv": cache["cv"]}
        xc = rmsnorm(p["cross_norm"], h, cfg.rmsnorm_eps)
        h = h + attn_mod.cross_attend(p["cross"], cfg, xc, ckv)
        new_cache.update(ckv)

    h, _, stats = _ffn(p, cfg, kind, h, True, False)
    return h, new_cache, stats


# ---------------------------------------------------------------------------
# Segment init / apply
# ---------------------------------------------------------------------------
def init_segment(key, cfg: ModelConfig, unit: Tuple[str, ...], count: int,
                 *, cross: bool = False) -> Dict:
    """Stacked per-unit params.  ``shared_attn`` kinds hold no per-layer
    params (tied set lives at model level)."""
    seg = {}
    ks = jax.random.split(key, len(unit))
    for j, kind in enumerate(unit):
        if kind == "shared_attn":
            continue
        seg[str(j)] = stacked_init(
            lambda k_, kind=kind: init_block(k_, cfg, kind, cross=cross),
            ks[j], count)
    return seg


def segment_full(seg_params: Dict, shared_params, cfg: ModelConfig,
                 unit: Tuple[str, ...], count: int, h: jax.Array, cos, sin,
                 *, enc_out=None, causal: bool = True, remat: bool = True,
                 want_cache: bool = True, dropless: bool = False,
                 routed=None):
    """Scan the unit body over ``count`` stacked layers; returns (h, aux,
    caches, stats).  ``routed`` (b, s) bool: the tokens a drop-free expert
    layer routes (``moe.moe_dropless``; None: all).

    The body is rematerialized (activation checkpointing, MaxText-style):
    backward recomputes layer internals instead of storing the blocked
    attention / SSD scan carries — without this, training memory explodes
    (the online-softmax accumulators of every KV block would be saved).
    """
    def body(carry, xs):
        hh, aux = carry
        caches, stats = {}, {}
        for j, kind in enumerate(unit):
            p = shared_params if kind == "shared_attn" else xs[str(j)]
            kk = "attn" if kind == "shared_attn" else kind
            hh, cache, a, st = block_full(p, cfg, kk, hh, cos, sin,
                                          enc_out=enc_out, causal=causal,
                                          dropless=dropless, routed=routed)
            if want_cache:
                caches[str(j)] = cache
            if st:
                stats[str(j)] = st
            aux = aux + a
        return (hh, aux), (caches, stats)

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    (h, aux), (caches, stats) = jax.lax.scan(
        body, (h, jnp.zeros((), jnp.float32)), seg_params, length=count)
    return h, aux, caches, stats


def segment_decode(seg_params: Dict, shared_params, cfg: ModelConfig,
                   unit: Tuple[str, ...], count: int, h: jax.Array, cos, sin,
                   caches: Dict, pos, *, paged=None):
    # the page table (in ``paged``) is closed over, not scanned: every
    # layer shares one table while each scanned layer consumes its own
    # (n_pages, hkv, page, hd) slice of the stacked page storage
    def body(hh, xs):
        layer_caches = xs["__cache__"]
        new_caches, stats = {}, {}
        for j, kind in enumerate(unit):
            p = shared_params if kind == "shared_attn" else xs[str(j)]
            kk = "attn" if kind == "shared_attn" else kind
            hh, nc, st = block_decode(p, cfg, kk, hh, cos, sin,
                                      layer_caches[str(j)], pos, paged=paged)
            new_caches[str(j)] = nc
            if st:
                stats[str(j)] = st
        return hh, (new_caches, stats)

    xs = dict(seg_params)
    xs["__cache__"] = caches
    h, (new_caches, stats) = jax.lax.scan(body, h, xs, length=count)
    return h, new_caches, stats
