"""Plain float32 reference forward of the estimator backbones.

Two families are written out:

* the SCOPE estimator, a Qwen3-shaped dense decoder (arXiv:2505.09388):
  token embedding, then per layer RMSNorm -> grouped-query attention with
  per-head RMSNorm on q and k (qk-norm) and rotary positions (rotate-half
  form) -> residual -> RMSNorm -> SwiGLU MLP -> residual, a final RMSNorm
  and the LM head (the transposed embedding when tied);
* DeepSeek-V2 (arXiv:2405.04434): multi-head latent attention in its
  naive form (queries from ``wq``; keys and values up-projected from the
  RMS-normed latent ``c_kv``, plus one shared rotary key; YaRN rope and
  softmax scale), the first ``first_dense_layers`` with a SwiGLU MLP and
  the rest with a softmax router, greedy top-k and the experts held here
  (``experts_held`` from ``expert_offset``; a choice of another expert
  adds nothing) plus the shared experts.

This module writes those equations out in straightforward ``jax.numpy`` at
float32 and ``highest`` matmul precision: no kernels, no KV cache, no
batching tricks, no sharding, and every expert computed on every token and
weighted by its gate (zero where not chosen).  It reads the same params
pytree as ``models.model`` but shares none of its code, so it is the
oracle the serve path (prefill, then decode through the dense or paged
cache) is compared against.

Rope is the rotate-half form on the program's layout; DeepSeek-V2
publishes an interleaved rope, which equals this one under a fixed
permutation of the rope columns of ``wq`` and ``w_dkv`` — with random
weights a relabelling.  Any other block kind or option raises rather than
being silently approximated.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


def _kinds(cfg: ModelConfig):
    """Each layer's kind, the first dense layers of an expert stack made
    dense."""
    dense = {"mla_moe": "mla", "moe": "attn"}
    return [dense.get(k, k) if i < cfg.first_dense_layers else k
            for i, k in enumerate(cfg.layer_kinds())]


def check_supported(cfg: ModelConfig) -> None:
    kinds = set(_kinds(cfg))
    unsupported = {
        "block kinds other than 'attn', or 'mla' and 'mla_moe'":
            not (kinds == {"attn"} or kinds <= {"mla", "mla_moe"}),
        "rope_kind other than 'standard'": cfg.rope_kind != "standard",
        "attention or final logit softcaps":
            cfg.logit_softcap > 0.0 or cfg.final_logit_softcap > 0.0,
        "sliding windows": cfg.force_window > 0,
        "sandwich norms": cfg.sandwich_norm,
        "scaled embeddings": cfg.scale_embeddings,
        "encoder-decoder or stub frontends":
            cfg.is_encoder_decoder or cfg.num_stub_patches > 0,
        "non-SwiGLU MLPs": cfg.mlp_kind == "gelu",
        "YaRN outside MLA": cfg.yarn_factor > 0 and kinds == {"attn"},
    }
    bad = [name for name, hit in unsupported.items() if hit]
    if bad:
        raise NotImplementedError(
            f"reference forward covers the dense estimator and DeepSeek-V2 "
            f"families only; {cfg.name!r} has {', '.join(bad)}")


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _rope(x, positions, theta):
    """x: (b, s, h, d); rotate-half RoPE at absolute ``positions`` (s,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)[None]                              # (s, d/2)
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _yarn_inv_freq(cfg: ModelConfig, d: int) -> np.ndarray:
    """YaRN (DeepSeek-V2 ``rope_scaling``) inverse frequencies: the pair
    index ``i`` whose wavelength turns ``r`` times in the original length
    is d ln(L / (2 pi r)) / (2 ln theta); pairs below that of
    ``beta_fast`` keep theta^(-2i/d), pairs above that of ``beta_slow``
    take it over ``factor``, a linear ramp between."""
    theta, L = cfg.rope_theta, cfg.yarn_original_max_position
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def index(r):
        return d * math.log(L / (r * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(index(cfg.yarn_beta_fast)), 0)
    hi = min(math.ceil(index(cfg.yarn_beta_slow)), d - 1)
    hi = hi + 0.001 if hi == lo else hi
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0.0, 1.0)
    return inv * (1.0 - ramp) + inv / cfg.yarn_factor * ramp


def _yarn_m(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_mla(x, positions, cfg: ModelConfig):
    """Rotate-half rope of the MLA rope dims, YaRN-scaled when set (cos
    and sin times m(mscale) / m(mscale_all_dim))."""
    d = x.shape[-1]
    if not cfg.yarn_factor:
        return _rope(x, positions, cfg.rope_theta)
    inv = _yarn_inv_freq(cfg, d)
    f = (_yarn_m(cfg.yarn_factor, cfg.yarn_mscale)
         / _yarn_m(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)[None]
    cos = f * jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None]
    sin = f * jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _mla(a: Dict, cfg: ModelConfig, x):
    """Naive multi-head latent attention of normed ``x`` (b, s, d)."""
    b, s, _ = x.shape
    h, nope = cfg.num_heads, cfg.qk_nope_head_dim
    rdim, vdim, r = cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    pos = jnp.arange(s)
    q = (x @ a["wq"]).reshape(b, s, h, nope + rdim)
    q_nope, q_rope = q[..., :nope], _rope_mla(q[..., nope:], pos, cfg)
    kv = x @ a["w_dkv"]
    c = _rmsnorm(kv[..., :r], a["kv_norm"]["scale"], cfg.rmsnorm_eps)
    k_rope = _rope_mla(kv[..., r:][:, :, None], pos, cfg)    # one head
    k_nope = (c @ a["w_uk"]).reshape(b, s, h, nope)
    v = (c @ a["w_uv"]).reshape(b, s, h, vdim)
    scale = (nope + rdim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= _yarn_m(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0])) * scale
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(b, s, h * vdim)
    return o @ a["wo"]


def _swiglu(m: Dict, x):
    return (jax.nn.silu(x @ m["wi_gate"]) * (x @ m["wi_up"])) @ m["wo"]


def _moe(m: Dict, cfg: ModelConfig, x):
    """Softmax router over all experts, greedy top-k (renormalised only
    when ``norm_topk_prob``), the held experts' gated outputs and the
    shared experts."""
    probs = jax.nn.softmax(x @ m["router"], axis=-1)        # (b, s, E)
    top_p, top_i = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    held = cfg.experts_held or cfg.num_experts
    y = jnp.zeros_like(x)
    for j in range(held):
        e = cfg.expert_offset + j
        gate = jnp.sum(jnp.where(top_i == e, top_p, 0.0), -1)   # (b, s)
        w = {k: m[k][j] for k in ("wi_gate", "wi_up", "wo")}
        y = y + gate[..., None] * _swiglu(w, x)
    if cfg.num_shared_experts:
        y = y + _swiglu(m["shared"], x)
    return y


def _mla_layer(p: Dict, cfg: ModelConfig, kind: str, h):
    eps = cfg.rmsnorm_eps
    h = h + _mla(p["attn"], cfg, _rmsnorm(h, p["attn_norm"]["scale"], eps))
    x = _rmsnorm(h, p["mlp_norm"]["scale"], eps)
    return h + (_moe(p["moe"], cfg, x) if kind == "mla_moe"
                else _swiglu(p["mlp"], x))


def _layer(p: Dict, cfg: ModelConfig, h):
    b, s, _ = h.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    eps = cfg.rmsnorm_eps
    pos = jnp.arange(s)

    x = _rmsnorm(h, p["attn_norm"]["scale"], eps)
    a = p["attn"]
    q = (x @ a["wq"]).reshape(b, s, hq, hd)
    k = (x @ a["wk"]).reshape(b, s, hkv, hd)
    v = (x @ a["wv"]).reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = _rmsnorm(q, a["q_norm"]["scale"], eps)
        k = _rmsnorm(k, a["k_norm"]["scale"], eps)
    q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    # query head i reads kv head i // (hq / hkv)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    scale = cfg.attn_scale or hd ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(b, s, hq * hd)
    h = h + o @ a["wo"]

    x = _rmsnorm(h, p["mlp_norm"]["scale"], eps)
    m = p["mlp"]
    return h + (jax.nn.silu(x @ m["wi_gate"]) * (x @ m["wi_up"])) @ m["wo"]


def hidden(params: Dict, cfg: ModelConfig, tokens) -> jax.Array:
    """Final-normed hidden states (b, s, d) of ``tokens`` (b, s)."""
    check_supported(cfg)
    p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    kinds = _kinds(cfg)
    # the program stacks consecutive layers of one kind: segment i holds
    # the next run of them under "0"
    runs, i = [], 0
    while i < len(kinds):
        j = i
        while j < len(kinds) and kinds[j] == kinds[i]:
            j += 1
        runs.append((kinds[i], j - i))
        i = j
    with jax.default_matmul_precision("highest"):
        h = p32["embed"][tokens]
        for seg, (kind, count) in zip(p32["segments"], runs, strict=True):
            for i in range(count):
                p = jax.tree.map(lambda a, i=i: a[i], seg["0"])
                h = (_layer(p, cfg, h) if kind == "attn"
                     else _mla_layer(p, cfg, kind, h))
        return _rmsnorm(h, p32["final_norm"]["scale"], cfg.rmsnorm_eps)


def head(params: Dict, cfg: ModelConfig, h, columns=None) -> jax.Array:
    """LM-head logits of hidden ``h`` (..., d); ``columns`` restricts the
    vocabulary to those ids (e.g. the (YES, NO) decision pair)."""
    w = (jnp.asarray(params["embed"], jnp.float32).T if cfg.tie_embeddings
         else jnp.asarray(params["lm_head"], jnp.float32))
    if columns is not None:
        w = w[:, jnp.asarray(columns)]
    with jax.default_matmul_precision("highest"):
        return h @ w


# Serve path vs reference, per dtype the configuration computes in, as
# bounds on ``rel_errors``.  float32: the serve path and the reference do
# the same arithmetic in another order, so a few f32 ulp (measured ~1e-6
# on the CPU).  bfloat16: weights, activations and the KV cache round to 8
# significant bits (2^-8 ~ 0.004) at every layer; measured ~0.011 RMS and
# ~0.045 at worst over a 2-layer stack, so 0.03 / 0.25 leave a margin of
# about three while an 8-bit float (2^-3) or any dropped term fails them.
TOLERANCE = {"float32": {"rel_rms": 1e-4, "rel_max": 1e-3},
             "bfloat16": {"rel_rms": 0.03, "rel_max": 0.25}}


def parity_failures(result: Dict[str, Dict[str, float]], dtype: str):
    """What in a ``serve_parity`` result breaks ``TOLERANCE[dtype]``."""
    tol = TOLERANCE[dtype]
    bad = []
    for part, errs in result.items():
        if not errs["finite"]:
            bad.append(f"{part}: non-finite logits")
        bad += [f"{part}: {k} {errs[k]:.4g} > {v}" for k, v in tol.items()
                if errs[k] > v]
    return bad


def rel_errors(got, want) -> Dict[str, float]:
    """Error of ``got`` against ``want`` relative to ``want``'s RMS: the
    RMS of the difference and its largest element."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    rms = float(np.sqrt(np.mean(want ** 2))) or 1.0
    diff = got - want
    return {"rel_rms": float(np.sqrt(np.mean(diff ** 2))) / rms,
            "rel_max": float(np.abs(diff).max()) / rms,
            "finite": bool(np.isfinite(got).all())}


def serve_parity(params: Dict, cfg: ModelConfig, prompts, lens, last_logits,
                 gen, dec, decision_tokens, *, device=None
                 ) -> Dict[str, Dict[str, float]]:
    """Compare what the serve path produced with the reference, forced on
    the serve path's own tokens.

    ``prompts`` (b, L) right-padded with true ``lens`` (b,);
    ``last_logits`` (b, V) the prefill's logits at each row's last prompt
    position; ``gen`` (b, T) the tokens the decode sampled (and fed back);
    ``dec`` (b, T, 2) the logits of ``decision_tokens`` before each step.
    The reference runs one full forward over prompt + generated tokens:
    its logits at position ``lens - 1 + t`` are what decode step ``t``
    saw.  Everything is pulled to the host first and computed on
    ``device`` (default: JAX's default device).  Returns ``rel_errors``
    for the prefill logits and for the decision logits.
    """
    prompts, gen = np.asarray(prompts), np.asarray(gen)
    lens = np.asarray(lens, np.int64)
    b, T = gen.shape
    seq = np.zeros((b, prompts.shape[1] + T), np.int32)
    for i in range(b):
        seq[i, : lens[i]] = prompts[i, : lens[i]]
        seq[i, lens[i]: lens[i] + T] = gen[i]
    host = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    dev = device if device is not None else jax.devices()[0]
    p_dev = jax.device_put(host, dev)
    h = jax.jit(hidden, static_argnums=1)(p_dev, cfg,
                                          jax.device_put(seq, dev))
    rows = np.arange(b)[:, None]
    cols = (lens - 1)[:, None] + np.arange(T)[None]          # (b, T)
    h_dec = h[rows, cols]                                   # (b, T, d)
    head_fn = jax.jit(head, static_argnums=(1, 3))
    ref_last = head_fn(p_dev, cfg, h_dec[:, 0], None)
    ref_dec = head_fn(p_dev, cfg, h_dec, tuple(int(t) for t in
                                               decision_tokens))
    return {"prefill": rel_errors(last_logits, ref_last),
            "decision": rel_errors(dec, ref_dec)}


def compare_decodes(a, b) -> Dict[str, float]:
    """Two greedy decodes ``(gen (N, T), dec (N, T, 2))`` of the same
    prompts by two implementations of the same model.

    Near-tied logits can make them sample different tokens, after which
    the rows legitimately diverge.  So the decision logits are compared
    only at the steps whose earlier tokens agree in both — everything the
    two saw up to there was the same — and ``rows_equal`` reports the
    share of rows that generated identical tokens throughout.
    """
    ga, da = np.asarray(a[0]), np.asarray(a[1], np.float64)
    gb, db = np.asarray(b[0]), np.asarray(b[1], np.float64)
    same = np.cumprod(ga == gb, axis=1).astype(bool)     # equal through t
    fed_same = np.concatenate([np.ones((len(ga), 1), bool), same[:, :-1]],
                              axis=1)                   # equal before t
    out = rel_errors(da[fed_same], db[fed_same])
    out["rows_equal"] = float(same[:, -1].mean())
    out["steps_compared"] = float(fed_same.mean())
    return out
