"""Plain float32 reference forward of the SCOPE estimator backbone.

The estimator is a Qwen3-shaped dense decoder (arXiv:2505.09388): token
embedding, then per layer RMSNorm -> grouped-query attention with per-head
RMSNorm on q and k (qk-norm) and rotary positions (rotate-half form) ->
residual -> RMSNorm -> SwiGLU MLP -> residual, a final RMSNorm and the LM
head (the transposed embedding when tied).  This module writes those
equations out in straightforward ``jax.numpy`` at float32 and
``highest`` matmul precision: no kernels, no KV cache, no batching tricks,
no sharding.  It reads the same params pytree as ``models.model`` but
shares none of its code, so it is the oracle the serve path (prefill, then
decode through the dense or paged cache) is compared against.

Only the dense family the estimator uses is covered; any other block kind
or option raises rather than being silently approximated.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


def check_supported(cfg: ModelConfig) -> None:
    unsupported = {
        "block kinds other than 'attn'": set(cfg.layer_kinds()) != {"attn"},
        "rope_kind other than 'standard'": cfg.rope_kind != "standard",
        "attention or final logit softcaps":
            cfg.logit_softcap > 0.0 or cfg.final_logit_softcap > 0.0,
        "sliding windows": cfg.force_window > 0,
        "sandwich norms": cfg.sandwich_norm,
        "scaled embeddings": cfg.scale_embeddings,
        "encoder-decoder or stub frontends":
            cfg.is_encoder_decoder or cfg.num_stub_patches > 0,
        "non-SwiGLU MLPs": cfg.mlp_kind == "gelu",
    }
    bad = [name for name, hit in unsupported.items() if hit]
    if bad:
        raise NotImplementedError(
            f"reference forward covers the dense estimator family only; "
            f"{cfg.name!r} has {', '.join(bad)}")


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
    with jax.default_matmul_precision("highest"):
        h = p32["embed"][tokens]
        layers = p32["segments"][0]["0"]
        for i in range(cfg.num_layers):
            h = _layer(jax.tree.map(lambda a, i=i: a[i], layers), cfg, h)
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
