"""Plain float32 reference of the dense GQA decoder family (Qwen3, InternLM2).

Written from the published architecture, not from the program: token
embedding, then per layer RMSNorm -> grouped-query attention (per-head
RMSNorm on q and k when ``qk_norm``; rotate-half RoPE) -> residual ->
RMSNorm -> SwiGLU MLP -> residual, a final RMSNorm and the LM head (the
transposed embedding when tied).  Every matrix product runs in float32 at
``highest`` precision.  No kernel, cache, paging or batching trick.

The benchmark also makes the weights here, from the seed, in the pytree
layout the program under test reads, so the reference takes nothing the
program made.

``control="fp8"`` rounds every matrix-product input (weights and
activations) to float8 e4m3 first: the next precision below the bfloat16
the configurations state, used to show that the comparison fails it.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _dense(key, shape, dtype):
    """Truncated-normal fan-in init; the leading axis is the layer stack."""
    fan_in = shape[-2]
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (w * fan_in ** -0.5).astype(dtype)


def _scale(key, shape, dtype):
    """Norm scales near 1, drawn so that a path that ignores them differs."""
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_params(key, m: Dict) -> Dict:
    """Seeded weights for model sizes ``m`` (the config file's ``model``)."""
    dt = jnp.dtype(m["dtype"])
    L, d, f = m["num_layers"], m["d_model"], m["d_ff"]
    hq, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    ks = iter(jax.random.split(key, 16))
    attn = {"wq": _dense(next(ks), (L, d, hq * hd), dt),
            "wk": _dense(next(ks), (L, d, hkv * hd), dt),
            "wv": _dense(next(ks), (L, d, hkv * hd), dt),
            "wo": _dense(next(ks), (L, hq * hd, d), dt)}
    if m["qk_norm"]:
        attn["q_norm"] = {"scale": _scale(next(ks), (L, hd), dt)}
        attn["k_norm"] = {"scale": _scale(next(ks), (L, hd), dt)}
    layer = {"attn": attn,
             "attn_norm": {"scale": _scale(next(ks), (L, d), dt)},
             "mlp": {"wi_gate": _dense(next(ks), (L, d, f), dt),
                     "wi_up": _dense(next(ks), (L, d, f), dt),
                     "wo": _dense(next(ks), (L, f, d), dt)},
             "mlp_norm": {"scale": _scale(next(ks), (L, d), dt)}}
    p = {"embed": (0.02 * jax.random.normal(
            next(ks), (m["vocab_size"], d), jnp.float32)).astype(dt),
         "final_norm": {"scale": _scale(next(ks), (d,), jnp.float32)},
         "segments": ({"0": layer},)}
    if not m["tie_embeddings"]:
        p["lm_head"] = _dense(next(ks), (d, m["vocab_size"]), dt)
    return p


def make_params(seed32: int, m: Dict):
    """All weights in one jitted call on the default device."""
    return jax.jit(lambda k: init_params(k, m))(jax.random.PRNGKey(seed32))


def _round(x, control):
    if control == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(x, w, control):
    return jnp.matmul(_round(x, control),
                      _round(w.astype(jnp.float32), control),
                      precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (b, s, h, d), positions 0..s-1, rotate-half form."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    ang = np.concatenate([ang, ang], -1)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _layer(m: Dict, control, h, p):
    b, s, _ = h.shape
    hq, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps = m["rmsnorm_eps"]
    x = _rmsnorm(h, p["attn_norm"]["scale"], eps)
    a = p["attn"]
    q = _mm(x, a["wq"], control).reshape(b, s, hq, hd)
    k = _mm(x, a["wk"], control).reshape(b, s, hkv, hd)
    v = _mm(x, a["wv"], control).reshape(b, s, hkv, hd)
    if m["qk_norm"]:
        q = _rmsnorm(q, a["q_norm"]["scale"], eps)
        k = _rmsnorm(k, a["k_norm"]["scale"], eps)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    # query head i reads kv head i // (hq / hkv)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q, control),
                        _round(k, control), precision=HIGHEST) * hd ** -0.5
    causal = np.arange(s)[:, None] >= np.arange(s)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _round(w, control), _round(v, control),
                   precision=HIGHEST).reshape(b, s, hq * hd)
    h = h + _mm(o, a["wo"], control)
    x = _rmsnorm(h, p["mlp_norm"]["scale"], eps)
    f = p["mlp"]
    g = jax.nn.silu(_mm(x, f["wi_gate"], control)) * _mm(x, f["wi_up"], control)
    return h + _mm(g, f["wo"], control)


def _hidden(params, m: Dict, control, tokens):
    h = params["embed"][tokens].astype(jnp.float32)
    layers = params["segments"][0]["0"]

    def body(h, p):
        return _layer(m, control, h, p), None

    # one layer's weights in float32 at a time
    h, _ = jax.lax.scan(body, h, layers)
    return _rmsnorm(h, params["final_norm"]["scale"], m["rmsnorm_eps"])


def _head(params, m: Dict):
    if m["tie_embeddings"]:
        return params["embed"].T
    return params["lm_head"]


def readout(params, m: Dict, tokens, rows, cols, targets, extra=(), *,
            control: str = "none", block: int = 256) -> Dict[str, np.ndarray]:
    """Float32 logits at positions (rows[i], cols[i]) of ``tokens`` (b, s),
    reduced on the device to what the check reads: the best logit and its
    token, the logit of ``targets[i]``, and the logits of the ``extra``
    token ids (n, len(extra)).

    The forward runs once over the whole batch, one layer's weights in
    float32 at a time; the LM head is applied to ``block`` positions at a
    time, so no more than one block of (block, V) logits is ever held.
    """
    fn = _compiled(m, control, block, tuple(int(t) for t in extra))
    rows = np.asarray(rows, np.int32)
    n = len(rows)
    pad = (-n) % block

    def padded(a):
        return jnp.asarray(np.concatenate(
            [np.asarray(a, np.int32), np.zeros(pad, np.int32)]))

    out = fn(params, jnp.asarray(tokens, jnp.int32), padded(rows),
             padded(cols), padded(targets))
    return {k: np.asarray(v)[:n] for k, v in out.items()}


_FNS: Dict = {}


def _compiled(m: Dict, control: str, block: int, extra: tuple):
    key = (tuple(sorted(m.items())), control, block, extra)
    fn = _FNS.get(key)
    if fn is None:
        def run(params, tokens, rows, cols, targets):
            h = _hidden(params, m, control, tokens)
            sel = h[rows, cols].reshape(-1, block, h.shape[-1])
            tg = targets.reshape(-1, block)
            w = _head(params, m)
            ex = jnp.asarray(extra, jnp.int32)

            def one(_, xt):
                x, t = xt
                lg = _mm(x, w, control)                      # (block, V)
                out = {"max": lg.max(axis=-1),
                       "argmax": jnp.argmax(lg, axis=-1).astype(jnp.int32),
                       "at_target": jnp.take_along_axis(
                           lg, t[:, None], axis=-1)[:, 0]}
                if extra:
                    out["extra"] = lg[:, ex]
                return None, out

            _, out = jax.lax.scan(one, None, (sel, tg))
            return {k: v.reshape((-1,) + v.shape[2:]) for k, v in out.items()}

        fn = _FNS[key] = jax.jit(run)
    return fn
