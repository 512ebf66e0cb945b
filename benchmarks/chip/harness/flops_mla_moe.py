"""Operations and bytes of the DeepSeek-V2 family's executables, from
shapes: multi-head latent attention (absorbed at decode, naive at
prefill), the expert layer at one chip's share of the routed experts, and
a whole decode step or prefill.

``m`` is a configuration file's ``model`` block.  Matrix-product weights
are counted as the executables multiply by them; norms are left out.  The
expert layer counts the FLOPs of routed tokens only: a token routes
``num_experts_per_tok`` choices over ``num_experts`` experts, so on
average ``k x held / E`` of them reach an expert held here, and each held
expert's weights are read once a step.  The embedding gather reads a row
per token and is not counted as a matrix product.
"""
from __future__ import annotations

from typing import Dict

from harness.flops import dtype_bytes


def held_experts(m: Dict) -> int:
    return m["experts_held"] or m["num_experts"]


def expert_layers(m: Dict) -> int:
    return m["num_layers"] - m["first_dense_layers"]


def attention_params(m: Dict) -> int:
    """One MLA layer's matrix-product weights: wq, w_dkv, w_uk, w_uv, wo."""
    d, h = m["d_model"], m["num_heads"]
    nope, rdim = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    r, vdim = m["kv_lora_rank"], m["v_head_dim"]
    return (d * h * (nope + rdim) + d * (r + rdim) + r * h * nope
            + r * h * vdim + h * vdim * d)


def expert_params(m: Dict) -> int:
    """One routed expert's SwiGLU."""
    return 3 * m["d_model"] * m["moe_d_ff"]


def shared_params(m: Dict) -> int:
    return 3 * m["d_model"] * m["moe_d_ff"] * m["num_shared_experts"]


def router_params(m: Dict) -> int:
    return m["d_model"] * m["num_experts"]


def dense_mlp_params(m: Dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def expert_layer_params(m: Dict) -> int:
    """What an expert layer holds here: its held experts, the shared
    experts and the router."""
    return (held_experts(m) * expert_params(m) + shared_params(m)
            + router_params(m))


def held_params(m: Dict) -> int:
    """Every matrix-product weight this chip holds, embedding and LM head
    included."""
    return (m["num_layers"] * attention_params(m)
            + m["first_dense_layers"] * dense_mlp_params(m)
            + expert_layers(m) * expert_layer_params(m)
            + 2 * m["vocab_size"] * m["d_model"])


def step_weight_bytes(m: Dict) -> int:
    """Weights one decode step reads: all but the embedding (a gather)."""
    return (held_params(m) - m["vocab_size"] * m["d_model"]) * dtype_bytes(m)


def routed_per_token(m: Dict) -> float:
    """Expert choices of one token that reach an expert held here, on
    average over a uniform router."""
    return m["num_experts_per_tok"] * held_experts(m) / m["num_experts"]


def active_params(m: Dict) -> float:
    """Weights one token multiplies by, LM head included; the routed
    experts at their average share of the token's choices held here."""
    moe = (router_params(m) + shared_params(m)
           + routed_per_token(m) * expert_params(m))
    return (m["num_layers"] * attention_params(m)
            + m["first_dense_layers"] * dense_mlp_params(m)
            + expert_layers(m) * moe
            + m["d_model"] * m["vocab_size"])


def latent_bytes_per_token(m: Dict) -> int:
    return (m["num_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            * dtype_bytes(m))


def mla_decode(m: Dict, rows: int, context: float) -> Dict[str, float]:
    """Absorbed MLA, every layer, one new token per row over ``context``
    cached latents: the projections, scores over [c, k_rope] and the
    latent context; the attention weights and the live latent pages."""
    h, r = m["num_heads"], m["kv_lora_rank"]
    per_row = (2.0 * attention_params(m)
               + 2.0 * h * context * (2 * r + m["qk_rope_head_dim"]))
    return {"flops": m["num_layers"] * rows * per_row,
            "bytes": (m["num_layers"] * attention_params(m) * dtype_bytes(m)
                      + rows * context * latent_bytes_per_token(m))}


def mla_prefill(m: Dict, tokens: int, context: float) -> Dict[str, float]:
    """Naive MLA, every layer: the projections (keys and values
    up-projected per token) and causal attention over ``context`` keys a
    query on average."""
    h = m["num_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    per_tok = (2.0 * attention_params(m)
               + 2.0 * h * context * (qk + m["v_head_dim"]))
    return {"flops": m["num_layers"] * tokens * per_tok,
            "bytes": (m["num_layers"] * attention_params(m) * dtype_bytes(m)
                      + tokens * latent_bytes_per_token(m))}


def expert_layer(m: Dict, tokens: float) -> Dict[str, float]:
    """Every expert layer over ``tokens``: the routed tokens' held-expert
    SwiGLUs, the shared experts and the router; each held weight read
    once."""
    per_tok = (routed_per_token(m) * expert_params(m) + shared_params(m)
               + router_params(m))
    return {"flops": 2.0 * expert_layers(m) * tokens * per_tok,
            "bytes": expert_layers(m) * expert_layer_params(m)
            * dtype_bytes(m)}


def decode_step(m: Dict, rows: int, context: float) -> Dict[str, float]:
    """One decode step of ``rows`` slots over ``context`` cached tokens:
    every weight but the embedding read once, plus the live latents."""
    flops = (2.0 * rows * active_params(m)
             + m["num_layers"] * rows * 2.0 * m["num_heads"] * context
             * (2 * m["kv_lora_rank"] + m["qk_rope_head_dim"]))
    nbytes = (step_weight_bytes(m)
              + rows * context * latent_bytes_per_token(m))
    return {"flops": flops, "bytes": nbytes}


def prefill(m: Dict, rows: int, length: int) -> Dict[str, float]:
    """Prefill of ``rows`` prompts of ``length`` tokens (causal)."""
    toks = rows * length
    att = mla_prefill(m, toks, (length + 1) / 2)
    flops = (2.0 * toks * (active_params(m)
                           - m["num_layers"] * attention_params(m))
             + att["flops"])
    nbytes = step_weight_bytes(m) + toks * latent_bytes_per_token(m)
    return {"flops": flops, "bytes": nbytes}
