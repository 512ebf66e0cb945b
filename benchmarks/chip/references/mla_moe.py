"""Plain float32 reference of the DeepSeek-V2 family (multi-head latent
attention, a first dense layer, then shared + routed experts), at the share
of the routed experts one chip holds.

Written from the published description (arXiv:2405.04434 and the
DeepSeek-V2-Lite ``config.json``), not from the program:

  h = embed[tokens]
  per layer:  h += MLA(RMSNorm(h)),  h += FFN(RMSNorm(h))
  logits = RMSNorm(h) @ lm_head                      (untied head)

MLA, naive (not absorbed) form, no query compression (``q_lora_rank``
null): q = x wq split into a no-rope part (qk_nope) and a rope part
(qk_rope); [c, k_r] = x w_dkv with c RMS-normed (kv_norm); keys are
[c w_uk per head, rope(k_r) shared by every head], values c w_uv; causal
softmax at scale (qk_nope + qk_rope)^-0.5 mscale^2; output o wo.
YaRN rope (``rope_scaling``): the pair index whose wavelength turns r
times in the original length L is d ln(L / (2 pi r)) / (2 ln theta);
pairs below that of ``beta_fast`` keep theta^(-2i/d), pairs above that of
``beta_slow`` take it over ``factor``, a linear ramp between; mscale =
0.1 mscale_all_dim ln(factor) + 1, and cos/sin are scaled by
m(mscale) / m(mscale_all_dim).

FFN: the first ``first_dense_layers`` a SwiGLU of width ``d_ff``; then a
router ``softmax(x router)`` in float32 over all ``num_experts``, greedy
top-k (``num_experts_per_tok``), the gate weights not renormalised
(``norm_topk_prob`` false, ``routed_scaling_factor`` 1), the gated
SwiGLUs of the chosen experts that this chip holds (``experts_held`` from
``expert_offset``; a choice of an expert held elsewhere adds nothing, the
part of the result another chip of the deployment gives), plus the
shared experts on every token.

Every matrix product runs in float32 at ``highest`` precision, layer by
layer (one layer's weights in float32 at a time).  Every held expert is
computed on every token and weighted by its gate, zero where not chosen:
no kernel, cache, paging, dispatch or batching trick.

Departures from the published model, each a relabelling or an input:

* rope is the rotate-half form; DeepSeek-V2 publishes an interleaved rope,
  which equals it under a fixed permutation of the rope columns of ``wq``
  and ``w_dkv`` — with random weights, a relabelling;
* weights are random from the seed, made here in the program's pytree
  layout, so the reference takes nothing the program made;
* at a chip's share, each expert layer's experts are relabelled (its
  router's columns permuted, ``place_experts``) so that the ones held
  carry the chip's fair share of the routed pairs of prompts made from the
  seed, as a deployment's balanced placement gives
  (``harness/placement.py``) — with iid expert weights, a relabelling.

``control="fp8"`` rounds every matrix-product input (weights and
activations, the router's included) to float8 e4m3 first: the next
precision below the bfloat16 the configuration states.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _dense(key, shape, dtype):
    """Truncated-normal fan-in init; leading axes are stacks."""
    fan_in = shape[-2]
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (w * fan_in ** -0.5).astype(dtype)


def _scale(key, shape, dtype):
    """Norm scales near 1, drawn so that a path that ignores them differs."""
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _held(m: Dict) -> int:
    return m["experts_held"] or m["num_experts"]


def _attn(ks, m: Dict, L: int, dt) -> Dict:
    d, h = m["d_model"], m["num_heads"]
    nope, rdim = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    r, vdim = m["kv_lora_rank"], m["v_head_dim"]
    return {"wq": _dense(next(ks), (L, d, h * (nope + rdim)), dt),
            "w_dkv": _dense(next(ks), (L, d, r + rdim), dt),
            "kv_norm": {"scale": _scale(next(ks), (L, r), dt)},
            "w_uk": _dense(next(ks), (L, r, h * nope), dt),
            "w_uv": _dense(next(ks), (L, r, h * vdim), dt),
            "wo": _dense(next(ks), (L, h * vdim, d), dt)}


def _swiglu_params(ks, lead, d: int, f: int, dt) -> Dict:
    return {"wi_gate": _dense(next(ks), lead + (d, f), dt),
            "wi_up": _dense(next(ks), lead + (d, f), dt),
            "wo": _dense(next(ks), lead + (f, d), dt)}


def init_params(key, m: Dict) -> Dict:
    """Seeded weights for model sizes ``m`` (the config file's ``model``):
    segment 0 the dense layers, segment 1 the expert layers."""
    dt = jnp.dtype(m["dtype"])
    d, V = m["d_model"], m["vocab_size"]
    n_dense = m["first_dense_layers"]
    n_moe = m["num_layers"] - n_dense
    mf, held = m["moe_d_ff"], _held(m)
    ks = iter(jax.random.split(key, 32))

    def norms(L):
        return {"attn_norm": {"scale": _scale(next(ks), (L, d), dt)},
                "mlp_norm": {"scale": _scale(next(ks), (L, d), dt)}}

    dense = dict(norms(n_dense), attn=_attn(ks, m, n_dense, dt),
                 mlp=_swiglu_params(ks, (n_dense,), d, m["d_ff"], dt))
    moe = _swiglu_params(ks, (n_moe, held), d, mf, dt)
    moe["router"] = _dense(next(ks), (n_moe, d, m["num_experts"]), dt)
    moe["shared"] = _swiglu_params(ks, (n_moe,), d,
                                   mf * m["num_shared_experts"], dt)
    expert = dict(norms(n_moe), attn=_attn(ks, m, n_moe, dt), moe=moe)
    return {"embed": (0.02 * jax.random.normal(
                next(ks), (V, d), jnp.float32)).astype(dt),
            "final_norm": {"scale": _scale(next(ks), (d,), jnp.float32)},
            "segments": ({"0": dense}, {"0": expert}),
            "lm_head": _dense(next(ks), (d, V), dt)}


def make_params(seed32: int, m: Dict):
    """All weights in one jitted call on the default device; at a chip's
    share of the experts, each expert layer's experts then placed on
    prompts made from the same seed (``place_experts``)."""
    params = jax.jit(lambda k: init_params(k, m))(jax.random.PRNGKey(seed32))
    if _held(m) < m["num_experts"]:
        from harness import placement
        params = place_experts(params, m,
                               *placement.sample_prompts(seed32))
    return params


def _at(tree, i: int):
    return jax.tree.map(lambda a: a[i], tree)


def place_experts(params, m: Dict, tokens, lens):
    """Relabel each expert layer's experts so that those this chip holds
    carry its fair share of the routed pairs of ``tokens`` (n, s), counted
    at the positions before ``lens`` (``harness.placement.held_first``): a
    permutation of the router's columns.  Layer by layer in float32, each
    layer placed before the next one's inputs are made."""
    from harness import placement
    fns = _placing(m)
    valid = np.arange(tokens.shape[1])[None] < np.asarray(lens)[:, None]
    h = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    dense, expert = (seg["0"] for seg in params["segments"])
    for i in range(m["first_dense_layers"]):
        h = fns["layer"](_at(dense, i), h)
    routers = []
    for i in range(m["num_layers"] - m["first_dense_layers"]):
        p = _at(expert, i)
        chosen = np.asarray(fns["top_k"](p, h))[valid]
        order = placement.held_first(
            np.bincount(chosen.reshape(-1), minlength=m["num_experts"]),
            _held(m), m["expert_offset"])
        p["moe"]["router"] = p["moe"]["router"][:, order]
        routers.append(p["moe"]["router"])
        h = fns["layer"](p, h)
    expert = dict(expert, moe=dict(expert["moe"], router=jnp.stack(routers)))
    return dict(params, segments=(params["segments"][0], {"0": expert}))


def _placing(m: Dict):
    key = ("place", tuple(sorted(m.items())))
    fns = _FNS.get(key)
    if fns is None:
        def top_k(p, h):
            x = _rmsnorm(_attn_half(m, "none", h, p),
                         p["mlp_norm"]["scale"], m["rmsnorm_eps"])
            probs = jax.nn.softmax(_mm(x, p["moe"]["router"], "none"), -1)
            return jax.lax.top_k(probs, m["num_experts_per_tok"])[1]

        fns = _FNS[key] = {
            "top_k": jax.jit(top_k),
            "layer": jax.jit(lambda p, h: _layer(m, "none", h, p))}
    return fns


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


def _yarn(m: Dict, d: int):
    """YaRN inverse frequencies (d/2,) and the cos/sin factor."""
    theta, factor = m["rope_theta"], m["yarn_factor"]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if not factor:
        return inv, 1.0
    L = m["yarn_original_max_position"]

    def index(r):
        return d * math.log(L / (r * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(index(m["yarn_beta_fast"])), 0)
    hi = min(math.ceil(index(m["yarn_beta_slow"])), d - 1)
    hi = hi + 0.001 if hi == lo else hi
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0.0, 1.0)
    inv = inv * (1.0 - ramp) + inv / factor * ramp
    return inv, _mscale(factor, m["yarn_mscale"]) / _mscale(
        factor, m["yarn_mscale_all_dim"])


def _mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope(x, m: Dict):
    """x: (b, s, h, d), positions 0..s-1, rotate-half form."""
    s, d = x.shape[1], x.shape[-1]
    inv, f = _yarn(m, d)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    ang = np.concatenate([ang, ang], -1)
    cos = jnp.asarray(f * np.cos(ang), jnp.float32)[None, :, None]
    sin = jnp.asarray(f * np.sin(ang), jnp.float32)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _softmax_scale(m: Dict) -> float:
    scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    if m["yarn_factor"] and m["yarn_mscale_all_dim"]:
        scale *= _mscale(m["yarn_factor"], m["yarn_mscale_all_dim"]) ** 2
    return scale


def _mla(a: Dict, m: Dict, control, x):
    b, s, _ = x.shape
    h, nope = m["num_heads"], m["qk_nope_head_dim"]
    rdim, r, vdim = m["qk_rope_head_dim"], m["kv_lora_rank"], m["v_head_dim"]
    q = _mm(x, a["wq"], control).reshape(b, s, h, nope + rdim)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], m)
    kv = _mm(x, a["w_dkv"], control)
    c = _rmsnorm(kv[..., :r], a["kv_norm"]["scale"], m["rmsnorm_eps"])
    k_rope = _rope(kv[..., r:][:, :, None], m)[:, :, 0]      # one head
    k_nope = _mm(c, a["w_uk"], control).reshape(b, s, h, nope)
    v = _mm(c, a["w_uv"], control).reshape(b, s, h, vdim)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", _round(q_nope, control),
                         _round(k_nope, control), precision=HIGHEST)
              + jnp.einsum("bqhd,bkd->bhqk", _round(q_rope, control),
                           _round(k_rope, control), precision=HIGHEST))
    scores = scores * _softmax_scale(m)
    causal = np.arange(s)[:, None] >= np.arange(s)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _round(w, control), _round(v, control),
                   precision=HIGHEST).reshape(b, s, h * vdim)
    return _mm(o, a["wo"], control)


def _swiglu(f: Dict, control, x):
    g = jax.nn.silu(_mm(x, f["wi_gate"], control)) * _mm(x, f["wi_up"],
                                                        control)
    return _mm(g, f["wo"], control)


def _moe(p: Dict, m: Dict, control, x):
    """Softmax router over all experts, greedy top-k, unnormalised gates;
    the held experts' gated outputs plus the shared experts."""
    probs = jax.nn.softmax(_mm(x, p["router"], control), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    y = _swiglu(p["shared"], control, x)
    for j in range(_held(m)):
        gate = jnp.sum(jnp.where(top_i == m["expert_offset"] + j, top_p, 0.0),
                       -1)
        w = {k: p[k][j] for k in ("wi_gate", "wi_up", "wo")}
        y = y + gate[..., None] * _swiglu(w, control, x)
    return y


def _attn_half(m: Dict, control, h, p):
    return h + _mla(p["attn"], m, control,
                    _rmsnorm(h, p["attn_norm"]["scale"], m["rmsnorm_eps"]))


def _layer(m: Dict, control, h, p):
    h = _attn_half(m, control, h, p)
    x = _rmsnorm(h, p["mlp_norm"]["scale"], m["rmsnorm_eps"])
    if "moe" in p:
        return h + _moe(p["moe"], m, control, x)
    return h + _swiglu(p["mlp"], control, x)


def _hidden(params, m: Dict, control, tokens):
    h = params["embed"][tokens].astype(jnp.float32)

    def body(h, p):
        return _layer(m, control, h, p), None

    for seg in params["segments"]:
        # one layer's weights in float32 at a time
        h, _ = jax.lax.scan(body, h, seg["0"])
    return _rmsnorm(h, params["final_norm"]["scale"], m["rmsnorm_eps"])


def readout(params, m: Dict, tokens, rows, cols, targets, extra=(), *,
            control: str = "none", block: int = 256) -> Dict[str, np.ndarray]:
    """Float32 logits at positions (rows[i], cols[i]) of ``tokens`` (b, s),
    reduced on the device to what the check reads: the best logit and its
    token, the logit of ``targets[i]``, and the logits of the ``extra``
    token ids (n, len(extra)).  The LM head is applied to ``block``
    positions at a time."""
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
            w = params["lm_head"]
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
