"""Top-level model API.

  init_params(key, cfg)                      -> params pytree
  forward_train(params, cfg, batch)          -> (logits, aux)
  loss_fn(params, cfg, batch)                -> (loss, metrics)
  prefill(params, cfg, batch, lengths)       -> (last logits, caches)
  decode_step(params, cfg, token, caches, pos) -> (logits, caches)
  init_cache(cfg, batch_size, max_len)       -> zeroed cache pytree

``prefill`` and ``decode_step`` are the serve path: their expert layers
are drop-free, and ``with_stats=True`` adds a third result, ``stats``:
``{"expert_tokens": (expert layers, experts held) int32}``, the tokens
each expert layer routed to each held expert (empty without experts).

Batch dict keys: "tokens" (b, s) int32; optional "labels" (b, s) int32
(-100 = ignore), "enc_features" (b, enc_seq, d) for audio stubs,
"image_embeds" (b, P, d) for VLM stubs, "positions_3d" (3, b, s) for M-RoPE.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import rope as rope_mod
from repro.models import transformer as tf
from repro.models.common import (
    dense_init, dtype_of, embed_init, init_rmsnorm, rmsnorm,
    shard_activation, sinusoidal_positions, softcap)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(1,))
def init_params(key, cfg: ModelConfig) -> Dict:
    """Seeded random params in ``cfg.dtype``.

    Jitted so each leaf is drawn and cast inside one fusion: a bf16 model
    is built in bf16 and never holds its float32 image (8 GB against
    16 GB at scope-qwen3-4b, the whole of a v5e's HBM).
    """
    plan = tf.build_plan(cfg)
    ks = jax.random.split(key, len(plan) + 5)
    dt = dtype_of(cfg.dtype)
    cross = cfg.is_encoder_decoder

    params: Dict = {
        "embed": embed_init(ks[0], (cfg.vocab_size, cfg.d_model)),
        "final_norm": init_rmsnorm(cfg.d_model),
        "segments": tuple(
            tf.init_segment(ks[i + 1], cfg, unit, count, cross=cross)
            for i, (unit, count) in enumerate(plan)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[-1], (cfg.d_model, cfg.vocab_size))
    if any("shared_attn" in unit for unit, _ in plan):
        params["shared_attn"] = tf.init_block(ks[-2], cfg, "attn")
    if cfg.is_encoder_decoder:
        enc_plan = [(("attn",), cfg.num_encoder_layers)]
        params["encoder"] = {
            "frontend_proj": dense_init(ks[-3], (cfg.d_model, cfg.d_model)),
            "segments": tuple(
                tf.init_segment(ks[-4], cfg, unit, count)
                for unit, count in enc_plan),
            "final_norm": init_rmsnorm(cfg.d_model),
        }
    if cfg.num_stub_patches > 0:
        params["vision_proj"] = dense_init(ks[-5], (cfg.d_model, cfg.d_model))
    params = jax.tree.map(
        lambda a: a.astype(dt) if a.dtype == jnp.float32 and a.ndim >= 2 else a,
        params)
    return params


# ---------------------------------------------------------------------------
# Position embeddings
# ---------------------------------------------------------------------------
def _cos_sin_full(cfg: ModelConfig, batch: Dict, b: int, s: int):
    if cfg.rope_kind == "none" or cfg.is_attention_free() and cfg.shared_attn_every == 0:
        return None, None
    hd = cfg.resolved_head_dim
    rope_dim = cfg.qk_rope_head_dim if any(
        tf._is_mla(k) for k in cfg.layer_kinds()) else hd
    if cfg.rope_kind == "mrope":
        pos3 = batch.get("positions_3d")
        if pos3 is None:
            pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            pos3 = rope_mod.text_positions_3d(pos)
        return rope_mod.mrope_cos_sin(pos3, rope_dim, cfg.rope_theta,
                                      cfg.mrope_sections)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return _rope(cfg, pos, rope_dim)


def _cos_sin_decode(cfg: ModelConfig, b: int, pos):
    """``pos``: (b,) int32 — per-row absolute position of the new token."""
    if cfg.rope_kind == "none" or cfg.is_attention_free() and cfg.shared_attn_every == 0:
        return None, None
    hd = cfg.resolved_head_dim
    rope_dim = cfg.qk_rope_head_dim if any(
        tf._is_mla(k) for k in cfg.layer_kinds()) else hd
    positions = pos[:, None]                              # (b, 1)
    if cfg.rope_kind == "mrope":
        return rope_mod.mrope_cos_sin(rope_mod.text_positions_3d(positions),
                                      rope_dim, cfg.rope_theta,
                                      cfg.mrope_sections)
    return _rope(cfg, positions, rope_dim)


def _rope(cfg: ModelConfig, positions, rope_dim: int):
    """cos/sin of standard RoPE, or YaRN's when ``cfg.yarn_factor`` is set
    (its cos/sin factor is 1 when ``mscale`` equals ``mscale_all_dim``)."""
    if not cfg.yarn_factor:
        return rope_mod.rope_cos_sin(positions, rope_dim, cfg.rope_theta)
    freqs = rope_mod.yarn_frequencies(
        rope_dim, cfg.rope_theta, cfg.yarn_factor,
        cfg.yarn_original_max_position, cfg.yarn_beta_fast,
        cfg.yarn_beta_slow)
    cos, sin = rope_mod.rope_cos_sin(positions, rope_dim, cfg.rope_theta,
                                     freqs)
    m = (rope_mod.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
         / rope_mod.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    if m != 1.0:
        cos, sin = cos * m, sin * m
    return cos, sin


def _sinusoid_at(pos, d: int):
    """pos: (b,) -> (b, d) sinusoidal embedding at each row's position."""
    div = jnp.exp(jnp.arange(0, d, 2).astype(jnp.float32)
                  * (-jnp.log(10000.0) / d))
    ang = pos.astype(jnp.float32)[..., None] * div        # (b, d/2)
    out = jnp.zeros(ang.shape[:-1] + (d,), jnp.float32)
    out = out.at[..., 0::2].set(jnp.sin(ang))
    out = out.at[..., 1::2].set(jnp.cos(ang))
    return out


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------
def _embed(params, cfg: ModelConfig, tokens, batch: Dict):
    with jax.named_scope("embed"):
        h = params["embed"][tokens]
        if cfg.scale_embeddings:
            h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
        if cfg.rope_kind == "none" and not cfg.is_attention_free():
            s = tokens.shape[1]
            h = h + sinusoidal_positions(s, cfg.d_model, h.dtype)[None]
        if cfg.num_stub_patches > 0 and "image_embeds" in batch:
            img = batch["image_embeds"] @ params["vision_proj"]
            npatch = img.shape[1]
            h = jnp.concatenate([img.astype(h.dtype), h[:, npatch:]], axis=1)
        return h


def _logits(params, cfg: ModelConfig, h):
    with jax.named_scope("lm_head"):
        h = rmsnorm(params["final_norm"], h, cfg.rmsnorm_eps)
        if cfg.tie_embeddings:
            logits = h @ params["embed"].T
        else:
            logits = h @ params["lm_head"]
        logits = shard_activation(logits, "batch", None, "vocab")
        if cfg.final_logit_softcap > 0.0:
            logits = softcap(logits.astype(jnp.float32),
                             cfg.final_logit_softcap)
        return logits


def _encode(params, cfg: ModelConfig, enc_features):
    """Whisper-style encoder over stub frame embeddings."""
    enc = params["encoder"]
    h = enc_features @ enc["frontend_proj"]
    s = h.shape[1]
    h = h + sinusoidal_positions(s, cfg.d_model, h.dtype)[None]
    for seg, (unit, count) in zip(enc["segments"],
                                  [(("attn",), cfg.num_encoder_layers)],
                                  strict=False):
        h, _, _, _ = tf.segment_full(seg, None, cfg, unit, count, h, None,
                                     None, causal=False)
    return rmsnorm(enc["final_norm"], h, cfg.rmsnorm_eps)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------
def expert_layers(cfg: ModelConfig) -> int:
    """How many layers of the decoder stack are expert layers."""
    return sum(count for unit, count in tf.build_plan(cfg)
               for kind in unit if tf._is_moe(kind))


def _stats(per_segment) -> Dict:
    """Per-segment scan stats -> ``{"expert_tokens": (layers, held)}``."""
    got = [st[j]["expert_tokens"] for st in per_segment
           for j in sorted(st, key=int)]
    return {"expert_tokens": jnp.concatenate(got)} if got else {}


def _forward_full(params, cfg: ModelConfig, batch: Dict, *,
                  want_cache: bool = False, head_at=None,
                  dropless: bool = False, routed=None):
    """``head_at`` (b,) int32 applies the LM head at one position per row
    only — (b, 1, V) logits instead of (b, s, V).  ``dropless`` runs expert
    layers drop-free (the serve path), routing the tokens ``routed`` (b, s)
    marks (None: all).  Returns (logits, aux, caches, stats)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    h = _embed(params, cfg, tokens, batch)
    h = shard_activation(h, "batch", None, None)
    cos, sin = _cos_sin_full(cfg, batch, b, s)

    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = _encode(params, cfg, batch["enc_features"])

    plan = tf.build_plan(cfg)
    shared = params.get("shared_attn")
    aux_total = jnp.zeros((), jnp.float32)
    caches, stats = [], []
    for seg, (unit, count) in zip(params["segments"], plan, strict=True):
        h, aux, cache, st = tf.segment_full(seg, shared, cfg, unit, count, h,
                                            cos, sin, enc_out=enc_out,
                                            want_cache=want_cache,
                                            dropless=dropless, routed=routed)
        aux_total = aux_total + aux
        caches.append(cache)
        stats.append(st)
    if head_at is not None:
        idx = jnp.asarray(head_at, jnp.int32).reshape(b, 1, 1)
        h = jnp.take_along_axis(h, idx, axis=1)
    return _logits(params, cfg, h), aux_total, tuple(caches), _stats(stats)


def forward_train(params, cfg: ModelConfig, batch: Dict):
    logits, aux, _, _ = _forward_full(params, cfg, batch)
    return logits, aux


def loss_fn(params, cfg: ModelConfig, batch: Dict):
    logits, aux = forward_train(params, cfg, batch)
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.concatenate(
            [batch["tokens"][:, 1:], jnp.full_like(batch["tokens"][:, :1], -100)],
            axis=1)
    mask = labels >= 0
    safe = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(mask), 1)
    ce = jnp.sum(nll * mask) / denom
    loss = ce + cfg.router_aux_coef * aux
    acc = jnp.sum((jnp.argmax(logits, -1) == safe) * mask) / denom
    return loss, {"ce": ce, "aux": aux, "acc": acc}


def prefill(params, cfg: ModelConfig, batch: Dict, lengths=None, *,
            live=None, with_stats: bool = False):
    """Full forward returning the logits at each row's last valid prompt
    position, (b, V), and per-layer caches sized to the prompt.

    ``lengths`` (b,) gives the true length of each right-padded row (None
    = every row fills the width).  Only the sampled position goes through
    the LM head: the (b, L, V) logits of a whole prompt batch would cost
    more HBM than the KV cache at a 152k vocabulary.  ``live`` (b,) bool
    marks the rows that carry a prompt (None: all); expert layers route
    only live rows' tokens before their lengths, so padding and filler
    rows add no expert work.
    """
    b, s = batch["tokens"].shape
    last = (jnp.full((b,), s - 1, jnp.int32) if lengths is None
            else jnp.asarray(lengths, jnp.int32) - 1)
    routed = None
    if expert_layers(cfg) and (lengths is not None or live is not None):
        routed = jnp.arange(s)[None] <= last[:, None]
        if live is not None:
            routed = routed & jnp.asarray(live, bool)[:, None]
    logits, _, caches, stats = _forward_full(params, cfg, batch,
                                             want_cache=True, head_at=last,
                                             dropless=True, routed=routed)
    if with_stats:
        return logits[:, 0], caches, stats
    return logits[:, 0], caches


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def decode_step(params, cfg: ModelConfig, token, caches, pos, *, paged=None,
                with_stats: bool = False):
    """token: (b, 1) int32; pos: scalar OR (b,) int32 — per-row count of
    tokens already cached (row ``i``'s new token lands at absolute position
    ``pos[i]``).  A scalar broadcasts to every row, so rows at different
    sequence positions share one compiled decode executable.

    ``paged`` = (PagedSpec, page table (b, W)) switches the attention
    caches to the block-paged layout from ``serving/kv_pool.py``; the RoPE
    rotation, embedding and head math are untouched — positions stay
    absolute, only the KV storage addressing changes.

    Returns (logits (b, 1, V), new caches[, stats])."""
    b = token.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    with jax.named_scope("embed"):
        h = params["embed"][token]
        if cfg.scale_embeddings:
            h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
        if cfg.rope_kind == "none" and not cfg.is_attention_free():
            h = h + _sinusoid_at(pos, cfg.d_model).astype(h.dtype)[:, None]
    cos, sin = _cos_sin_decode(cfg, b, pos)

    plan = tf.build_plan(cfg)
    shared = params.get("shared_attn")
    new_caches, stats = [], []
    for seg, cache, (unit, count) in zip(params["segments"], caches, plan,
                                      strict=True):
        h, nc, st = tf.segment_decode(seg, shared, cfg, unit, count, h, cos,
                                      sin, cache, pos, paged=paged)
        new_caches.append(nc)
        stats.append(st)
    if with_stats:
        return _logits(params, cfg, h), tuple(new_caches), _stats(stats)
    return _logits(params, cfg, h), tuple(new_caches)


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------
def _block_cache_spec(cfg: ModelConfig, kind: str, b: int, S: int, dt):
    hd = cfg.resolved_head_dim if cfg.num_heads else 0
    if kind == "mamba":
        conv_dim = cfg.d_inner + 2 * cfg.ssm_state
        return {
            "conv": jnp.zeros((b, cfg.ssm_conv_width - 1, conv_dim), dt),
            "ssm": jnp.zeros((b, cfg.resolved_ssm_heads, cfg.ssm_head_dim,
                              cfg.ssm_state), jnp.float32),
        }
    if tf._is_mla(kind):
        c = {"c_kv": jnp.zeros((b, S, cfg.kv_lora_rank), dt),
             "k_rope": jnp.zeros((b, S, cfg.qk_rope_head_dim), dt)}
    else:
        c = {"k": jnp.zeros((b, cfg.num_kv_heads, S, hd), dt),
             "v": jnp.zeros((b, cfg.num_kv_heads, S, hd), dt)}
    if cfg.is_encoder_decoder:
        c["ck"] = jnp.zeros((b, cfg.num_heads, cfg.encoder_seq_len, hd), dt)
        c["cv"] = jnp.zeros((b, cfg.num_heads, cfg.encoder_seq_len, hd), dt)
    return c


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int):
    """Zeroed cache pytree shaped for ``decode_step``.

    Windowed layers (attn_local / force_window) allocate only
    window-sized KV rings... sized min(max_len, window + 1) here since the
    decode path indexes absolute positions we keep full length for
    correctness; the dry-run variant uses windowed sizes via
    ``cache_len_for``.
    """
    from repro.models.attention import resolve_window
    dt = dtype_of(cfg.dtype)
    plan = tf.build_plan(cfg)
    caches = []
    for unit, count in plan:
        unit_cache = {}
        for j, kind in enumerate(unit):
            kk = "attn" if kind == "shared_attn" else kind
            # windowed layers get ring buffers of exactly `window` slots
            w = resolve_window(cfg, kk) if not tf._is_mla(kk) else 0
            S = min(max_len, w) if w > 0 else max_len
            spec = _block_cache_spec(cfg, kk, batch_size, S, dt)
            unit_cache[str(j)] = jax.tree.map(
                lambda a, count=count: jnp.broadcast_to(a[None], (count,) + a.shape),
                spec)
        caches.append(unit_cache)
    return tuple(caches)
