"""Operations and bytes of the estimator's executables, from shapes.

``m`` is a configuration file's ``model`` block (dense GQA family).  The
embedding gather reads a row per token and is not counted as a matrix
product; a tied LM head is the embedding read in full.
"""
from __future__ import annotations

from typing import Dict


def dtype_bytes(m: Dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["dtype"]]


def matmul_params(m: Dict) -> int:
    """Weights that a token's forward multiplies by, LM head included."""
    d, f = m["d_model"], m["d_ff"]
    hq, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    return m["num_layers"] * per_layer + d * m["vocab_size"]


def params(m: Dict) -> int:
    """Every weight, norms included (the model's size)."""
    d, L, hd = m["d_model"], m["num_layers"], m["head_dim"]
    n = matmul_params(m) - d * m["vocab_size"]          # layers only
    n += L * 2 * d + d                                   # norms
    n += L * 2 * hd if m["qk_norm"] else 0
    n += m["vocab_size"] * d                             # embedding
    if not m["tie_embeddings"]:
        n += d * m["vocab_size"]                         # separate LM head
    return n


def kv_bytes_per_token(m: Dict) -> int:
    return (m["num_layers"] * 2 * m["num_kv_heads"] * m["head_dim"]
            * dtype_bytes(m))


def attention_flops(m: Dict, queries: float, context: float) -> float:
    """QK^T and PV of ``queries`` tokens over ``context`` keys each."""
    return 4.0 * m["num_layers"] * m["num_heads"] * m["head_dim"] \
        * queries * context


def decode_step(m: Dict, rows: int, context: float) -> Dict[str, float]:
    """One decode step of ``rows`` slots, each attending ``context`` cached
    tokens: every weight read once, plus the live KV."""
    flops = 2.0 * matmul_params(m) * rows + attention_flops(m, rows, context)
    nbytes = (matmul_params(m) * dtype_bytes(m)
              + rows * context * kv_bytes_per_token(m))
    return {"flops": flops, "bytes": nbytes}


def prefill(m: Dict, rows: int, length: int) -> Dict[str, float]:
    """Prefill of ``rows`` prompts of ``length`` tokens (causal)."""
    toks = rows * length
    flops = (2.0 * matmul_params(m) * toks
             + attention_flops(m, toks, (length + 1) / 2))
    nbytes = (matmul_params(m) * dtype_bytes(m)
              + toks * kv_bytes_per_token(m))
    return {"flops": flops, "bytes": nbytes}


def least_time(work: Dict[str, float], peak: Dict) -> Dict[str, float]:
    """The roofline: the larger of the compute and the memory bound, and
    which of the two it is."""
    t_flops = work["flops"] / float(peak["bf16_flops_per_s"])
    t_bytes = work["bytes"] / float(peak["hbm_bytes_per_s"])
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
