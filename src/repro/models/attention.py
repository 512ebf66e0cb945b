"""Attention blocks: GQA (RoPE / M-RoPE / softcap / sliding window),
cross-attention (enc-dec), and Multi-head Latent Attention (DeepSeek-V2).

Shapes: activations are (batch, seq, d_model); heads are split internally.
Every block exposes a full-sequence path (train/prefill, returns the KV
cache slice) and a single-token decode path (reads/writes a cache).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import rope as rope_mod
from repro.models.common import dense_init, init_rmsnorm, rmsnorm, shard_activation


def resolve_window(cfg: ModelConfig, kind: str) -> int:
    if cfg.force_window > 0:
        return cfg.force_window
    if kind == "attn_local":
        return cfg.sliding_window
    return 0


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------
def init_gqa(key, cfg: ModelConfig) -> Dict:
    d, hq, hkv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, hq * hd)),
        "wk": dense_init(ks[1], (d, hkv * hd)),
        "wv": dense_init(ks[2], (d, hkv * hd)),
        "wo": dense_init(ks[3], (hq * hd, d)),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd)
        p["k_norm"] = init_rmsnorm(hd)
    return p


def _gqa_qkv(p, cfg: ModelConfig, x, cos, sin):
    b, s, _ = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(b, s, hq, hd)
    k = (x @ p["wk"]).reshape(b, s, hkv, hd)
    v = (x @ p["wv"]).reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.rmsnorm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.rmsnorm_eps)
    if cos is not None:
        q = rope_mod.apply_rope(q, cos, sin)
        k = rope_mod.apply_rope(k, cos, sin)
    return q, k, v


def gqa_full(p, cfg: ModelConfig, x, cos, sin, *, kind: str = "attn",
             causal: bool = True) -> Tuple[jax.Array, Dict]:
    """Full-sequence GQA.  Returns (y, {"k", "v"} cache slice)."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q, k, v = _gqa_qkv(p, cfg, x, cos, sin)
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    qh = shard_activation(qh, "batch", "heads", None, None)
    out = ops.flash_attention(
        qh, kh, vh, causal=causal,
        window=resolve_window(cfg, kind),
        softcap=cfg.logit_softcap,
        scale=cfg.attn_scale or None)
    y = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.num_heads * hd)
    y = y @ p["wo"]
    return y, {"k": kh, "v": vh}


def gqa_decode(p, cfg: ModelConfig, x, cos, sin, cache: Dict, pos,
               *, kind: str = "attn") -> Tuple[jax.Array, Dict]:
    """Single-token GQA decode.

    x: (b, 1, d); cache["k"/"v"]: (b, hkv, S, hd); pos: scalar or (b,)
    int — per-row number of tokens already cached (row ``i``'s new token
    has absolute position ``pos[i]``).  Per-row positions let rows at
    different sequence offsets (continuous batching, ragged prompt
    lengths) share one decode executable: the new KV lands at each row's
    own slot and the attention mask sees each row's own valid length.

    Windowed layers whose cache is allocated at exactly ``window`` entries
    run in **ring-buffer mode**: the new KV lands at ``pos % window`` and
    attention sees min(pos+1, window) valid slots — softmax is permutation
    invariant, so slot order is irrelevant.  This keeps long_500k decode
    memory/traffic at O(window), not O(context).
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    q, k, v = _gqa_qkv(p, cfg, x, cos, sin)
    window = resolve_window(cfg, kind)
    S_cache = cache["k"].shape[2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    ring = window > 0 and S_cache == window
    if ring:
        slot = pos % window
        valid = jnp.minimum(pos + 1, window)
        attn_window = 0                     # ring already enforces it
    else:
        slot = pos
        valid = pos + 1
        attn_window = window
    rows = jnp.arange(b)
    kc = cache["k"].at[rows, :, slot].set(
        k.transpose(0, 2, 1, 3)[:, :, 0].astype(cache["k"].dtype),
        unique_indices=True)
    vc = cache["v"].at[rows, :, slot].set(
        v.transpose(0, 2, 1, 3)[:, :, 0].astype(cache["v"].dtype),
        unique_indices=True)
    out = ops.decode_attention(
        q.transpose(0, 2, 1, 3), kc, vc, valid,
        window=attn_window,
        softcap=cfg.logit_softcap,
        scale=cfg.attn_scale or None)
    y = out.transpose(0, 2, 1, 3).reshape(b, 1, cfg.num_heads * hd)
    return y @ p["wo"], {"k": kc, "v": vc}


def gqa_decode_paged(p, cfg: ModelConfig, x, cos, sin, cache: Dict, pos,
                     table, spec, *, kind: str = "attn"
                     ) -> Tuple[jax.Array, Dict]:
    """Single-token GQA decode against a block-paged cache.

    cache["k"/"v"]: (n_pages, hkv, page_size, hd) physical pages shared by
    the whole batch; ``table``: (b, W) int32 page table; ``spec``: a
    ``PagedSpec`` (static page_size / kv_cap / kernel).  The new KV
    scatters into slot ``pos % page_size`` of physical page
    ``table[row, pos // page_size]``.  The logical page index is clamped
    to the table width: live rows never pass ``kv_cap`` (the sampler
    guards each segment), so the clamp only fires for retired/done rows
    whose table points at the trash page — their PAD writes collide there
    harmlessly, which is also why the scatter must NOT claim unique
    indices.  No ring-buffer mode: paged layers are full-window only
    (``kv_pool.check_paged_support``).
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    q, k, v = _gqa_qkv(p, cfg, x, cos, sin)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    lp = jnp.minimum(pos // spec.page_size, table.shape[1] - 1)
    rows = jnp.arange(b)
    pid = table[rows, lp]                                # (b,)
    slot = pos % spec.page_size
    kc = cache["k"].at[pid, :, slot].set(
        k.transpose(0, 2, 1, 3)[:, :, 0].astype(cache["k"].dtype))
    vc = cache["v"].at[pid, :, slot].set(
        v.transpose(0, 2, 1, 3)[:, :, 0].astype(cache["v"].dtype))
    out = ops.paged_decode_attention(
        q.transpose(0, 2, 1, 3), kc, vc, pos + 1, table,
        page_size=spec.page_size, kv_cap=spec.kv_cap,
        softcap=cfg.logit_softcap, scale=cfg.attn_scale or None,
        kernel=spec.kernel)
    y = out.transpose(0, 2, 1, 3).reshape(b, 1, cfg.num_heads * hd)
    return y @ p["wo"], {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------
def init_cross(key, cfg: ModelConfig) -> Dict:
    d, hq = cfg.d_model, cfg.num_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, hq * hd)),
        "wk": dense_init(ks[1], (d, hq * hd)),
        "wv": dense_init(ks[2], (d, hq * hd)),
        "wo": dense_init(ks[3], (hq * hd, d)),
    }


def cross_kv(p, cfg: ModelConfig, enc_out) -> Dict:
    """Project encoder output once; cached for the whole decode."""
    b, se, _ = enc_out.shape
    hq, hd = cfg.num_heads, cfg.resolved_head_dim
    k = (enc_out @ p["wk"]).reshape(b, se, hq, hd).transpose(0, 2, 1, 3)
    v = (enc_out @ p["wv"]).reshape(b, se, hq, hd).transpose(0, 2, 1, 3)
    return {"ck": k, "cv": v}


def cross_attend(p, cfg: ModelConfig, x, kv: Dict) -> jax.Array:
    b, s, _ = x.shape
    hq, hd = cfg.num_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
    out = ops.flash_attention(q, kv["ck"], kv["cv"], causal=False)
    y = out.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    return y @ p["wo"]


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 family)
# ---------------------------------------------------------------------------
def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(qk_nope + qk_rope)^-0.5``, times YaRN's ``mscale^2`` when the
    rope is YaRN-scaled with an ``mscale_all_dim`` (DeepSeek-V2)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        m = rope_mod.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        scale *= m * m
    return scale


def init_mla(key, cfg: ModelConfig) -> Dict:
    d, h = cfg.d_model, cfg.num_heads
    nope, rdim, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lora = cfg.kv_lora_rank
    ks = jax.random.split(key, 5)
    return {
        "wq": dense_init(ks[0], (d, h * (nope + rdim))),
        "w_dkv": dense_init(ks[1], (d, lora + rdim)),
        "kv_norm": init_rmsnorm(lora),
        "w_uk": dense_init(ks[2], (lora, h * nope)),
        "w_uv": dense_init(ks[3], (lora, h * vdim)),
        "wo": dense_init(ks[4], (h * vdim, d)),
    }


def _mla_q(p, cfg: ModelConfig, x, cos, sin):
    b, s, _ = x.shape
    h = cfg.num_heads
    nope, rdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (x @ p["wq"]).reshape(b, s, h, nope + rdim)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope_mod.apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def _mla_compress(p, cfg: ModelConfig, x, cos, sin):
    """Down-project to the latent cache: c_kv (b,s,lora) + k_rope (b,s,rdim)."""
    lora, rdim = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dkv = x @ p["w_dkv"]
    c_kv = rmsnorm(p["kv_norm"], dkv[..., :lora], cfg.rmsnorm_eps)
    k_rope = dkv[..., lora:][:, :, None, :]                 # 1 shared head
    k_rope = rope_mod.apply_rope(k_rope, cos, sin)[:, :, 0]
    return c_kv, k_rope


def mla_full(p, cfg: ModelConfig, x, cos, sin, *, kind: str = "mla",
             causal: bool = True) -> Tuple[jax.Array, Dict]:
    """Full-sequence MLA (naive/up-projected form for train & prefill)."""
    b, s, _ = x.shape
    h = cfg.num_heads
    nope, rdim, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(p, cfg, x, cos, sin)
    c_kv, k_rope = _mla_compress(p, cfg, x, cos, sin)

    k_nope = (c_kv @ p["w_uk"]).reshape(b, s, h, nope)
    v = (c_kv @ p["w_uv"]).reshape(b, s, h, vdim)
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, rdim))

    q = jnp.concatenate([q_nope, q_rope], -1).transpose(0, 2, 1, 3)
    k = jnp.concatenate([k_nope, k_rope_h], -1).transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    scale = mla_softmax_scale(cfg)
    out = ops.flash_attention(q, k, vh, causal=causal, scale=scale,
                              window=resolve_window(cfg, kind),
                              softcap=cfg.logit_softcap)
    y = out.transpose(0, 2, 1, 3).reshape(b, s, h * vdim)
    return y @ p["wo"], {"c_kv": c_kv, "k_rope": k_rope}


def _mla_absorbed(p, cfg: ModelConfig, x, q_nope, q_rope, ckv, krope, mask):
    """Absorbed-form attention of one new token per row over latent rows.

    ``ckv`` (b, S, lora) and ``krope`` (b, S, rdim) are each row's latent
    cache, ``mask`` (b, 1, S) the positions it may read.  The up
    projections w_uk / w_uv are folded into the query and the output, so
    the cache is read once and never re-expanded.  Products over the cache
    take its own dtype and accumulate in float32.
    """
    b = x.shape[0]
    h = cfg.num_heads
    nope, vdim = cfg.qk_nope_head_dim, cfg.v_head_dim
    lora = cfg.kv_lora_rank
    dt = ckv.dtype
    # absorb w_uk into q: q_lat[b,h,lora] = sum_n q_nope[b,h,n] w_uk[lora,h,n]
    w_uk = p["w_uk"].reshape(lora, h, nope)
    q_lat = jnp.einsum("bhn,lhn->bhl", q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = mla_softmax_scale(cfg)
    s_lat = jnp.einsum("bhl,bsl->bhs", q_lat.astype(dt), ckv,
                       preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bhr,bsr->bhs", q_rope[:, 0].astype(dt), krope,
                        preferred_element_type=jnp.float32)
    s = (s_lat + s_rope) * scale
    if cfg.logit_softcap > 0.0:
        s = cfg.logit_softcap * jnp.tanh(s / cfg.logit_softcap)
    s = jnp.where(mask, s, -1e30)
    probs = jax.nn.softmax(s, axis=-1)
    ctx_lat = jnp.einsum("bhs,bsl->bhl", probs.astype(dt), ckv,
                         preferred_element_type=jnp.float32)
    w_uv = p["w_uv"].reshape(lora, h, vdim)
    v_ctx = jnp.einsum("bhl,lhv->bhv", ctx_lat, w_uv.astype(jnp.float32))
    return v_ctx.reshape(b, 1, h * vdim).astype(x.dtype) @ p["wo"]


def mla_decode(p, cfg: ModelConfig, x, cos, sin, cache: Dict, pos,
               *, kind: str = "mla") -> Tuple[jax.Array, Dict]:
    """Absorbed-form MLA decode: attention runs in the latent space.

    cache: {"c_kv": (b, S, lora), "k_rope": (b, S, rdim)}.  ``pos`` is a
    scalar or per-row (b,) position, as in ``gqa_decode``.  The up
    projections w_uk/w_uv are folded into the query / output instead of
    re-expanding the cache each step (the TPU-friendly serving form — the
    naive form would up-project all S cached entries per token).
    """
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    q_nope, q_rope = _mla_q(p, cfg, x, cos, sin)            # (b,1,h,·)
    c_kv_new, k_rope_new = _mla_compress(p, cfg, x, cos, sin)

    rows = jnp.arange(b)
    ckv = cache["c_kv"].at[rows, pos].set(
        c_kv_new[:, 0].astype(cache["c_kv"].dtype), unique_indices=True)
    krope = cache["k_rope"].at[rows, pos].set(
        k_rope_new[:, 0].astype(cache["k_rope"].dtype), unique_indices=True)

    S = ckv.shape[1]
    kpos = jnp.arange(S)[None, None]                    # (1, 1, S)
    mask = kpos <= pos[:, None, None]                   # (b, 1, S)
    window = resolve_window(cfg, kind)
    if window > 0:
        mask = mask & (kpos > pos[:, None, None] - window)
    y = _mla_absorbed(p, cfg, x, q_nope, q_rope, ckv, krope, mask)
    return y, {"c_kv": ckv, "k_rope": krope}


def mla_decode_paged(p, cfg: ModelConfig, x, cos, sin, cache: Dict, pos,
                     table, spec) -> Tuple[jax.Array, Dict]:
    """Absorbed-form MLA decode against block-paged latent pages.

    cache["c_kv"]: (n_pages, page_size, lora) and cache["k_rope"]:
    (n_pages, page_size, rdim), physical pages shared by the batch, as
    ``gqa_decode_paged`` keeps K and V; ``table`` (b, W) and ``spec`` are
    the same.  The new token's latent lands in slot ``pos % page_size`` of
    page ``table[row, pos // page_size]`` (clamped, so retired rows write
    to the trash page and the scatter must not claim unique indices); each
    row then reads its pages up to ``kv_cap`` (the ``latent`` scope) and
    attends to positions <= ``pos``.
    """
    b = x.shape[0]
    ps = spec.page_size
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    q_nope, q_rope = _mla_q(p, cfg, x, cos, sin)            # (b,1,h,·)
    c_kv_new, k_rope_new = _mla_compress(p, cfg, x, cos, sin)
    lp = jnp.minimum(pos // ps, table.shape[1] - 1)
    pid = table[jnp.arange(b), lp]                       # (b,)
    slot = pos % ps
    ckv_pages = cache["c_kv"].at[pid, slot].set(
        c_kv_new[:, 0].astype(cache["c_kv"].dtype))
    krope_pages = cache["k_rope"].at[pid, slot].set(
        k_rope_new[:, 0].astype(cache["k_rope"].dtype))
    with jax.named_scope("latent"):
        width = table.shape[1] * ps
        ckv = ckv_pages[table].reshape(b, width, -1)[:, :spec.kv_cap]
        krope = krope_pages[table].reshape(b, width, -1)[:, :spec.kv_cap]
    kpos = jnp.arange(ckv.shape[1])[None, None]          # (1, 1, S)
    mask = kpos <= pos[:, None, None]                    # (b, 1, S)
    y = _mla_absorbed(p, cfg, x, q_nope, q_rope, ckv, krope, mask)
    return y, {"c_kv": ckv_pages, "k_rope": krope_pages}
