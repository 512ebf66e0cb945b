"""Plain reference of the router's host layers, written from the SCOPE paper
(Eq. 2-4 retrieval and serialization, Eq. 5 parse, Eq. 11-15 utility).

It takes its inputs as plain data (query and anchor embeddings, the pool's
prices, the anchors' recorded outcomes) and imports nothing of the program.
The token map is the structured vocabulary the estimator is served with;
it is written out here so that a change to the program's copy shows up as
a prompt that differs.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

# -- token map ---------------------------------------------------------------
PAD, BOS, EOS, SEP = 0, 1, 2, 3
ANCHOR, QUERY, PRED, THINK, THINK_END = 4, 5, 6, 7, 8
YES, NO = 9, 10
REASONING, STANDARD = 11, 12
UNK_MODEL = 13
MODEL_BASE, NUM_MODEL_TOKENS = 16, 20
DOMAIN_BASE = 36
SIM_BASE, NUM_SIM_BUCKETS = 44, 16
LEN_BASE, NUM_LEN_BUCKETS = 60, 32
PRICE_BASE, NUM_PRICE_BUCKETS = 92, 12
FEAT_BASE, NUM_FEAT_DIMS, NUM_FEAT_BUCKETS = 112, 16, 16

LEN_EDGES = np.geomspace(8, 16384, NUM_LEN_BUCKETS + 1)
LEN_CENTERS = np.sqrt(LEN_EDGES[:-1] * LEN_EDGES[1:])
PRICE_EDGES = np.geomspace(0.01, 20.0, NUM_PRICE_BUCKETS + 1)
FALLBACK_LEN = 512.0        # tokens charged for a malformed estimate
COST_EPS = 1e-6


# -- retrieval (Eq. 2-3) -----------------------------------------------------
def cosine(query_embs: np.ndarray, anchor_embs: np.ndarray) -> np.ndarray:
    """(Q, A) cosine similarities in float64."""
    q = np.asarray(query_embs, np.float64)
    a = np.asarray(anchor_embs, np.float64)
    q = q / np.linalg.norm(q, axis=1, keepdims=True)
    a = a / np.linalg.norm(a, axis=1, keepdims=True)
    return q @ a.T


def top_k(query_embs: np.ndarray, anchor_embs: np.ndarray, k: int):
    """Cosine top-k in float64: (sims (Q, k), idx (Q, k)), best first."""
    sims = cosine(query_embs, anchor_embs)
    idx = np.argsort(-sims, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(sims, idx, axis=1), idx


# -- serialization (Eq. 4) ---------------------------------------------------
def _len_bucket(tokens: float) -> int:
    return int(np.clip(np.searchsorted(LEN_EDGES, tokens) - 1, 0,
                       NUM_LEN_BUCKETS - 1))


def _sim_bucket(sim: float) -> int:
    return int(np.clip((sim + 1.0) / 2.0 * NUM_SIM_BUCKETS, 0,
                       NUM_SIM_BUCKETS - 1))


def _price_bucket(price_out: float) -> int:
    return int(np.clip(np.searchsorted(PRICE_EDGES, price_out) - 1, 0,
                       NUM_PRICE_BUCKETS - 1))


def prompt(model: Dict, anchors_domain: np.ndarray, anchor_y: np.ndarray,
           anchor_tokens: np.ndarray, sims: Sequence[float],
           idx: Sequence[int], query_domain: int,
           query_emb: np.ndarray) -> List[int]:
    """[BOS] model kind price [SEP] {ANCHOR dom sim y len}*K [QUERY] dom
    feats [PRED]; ``model`` has index, seen, reasoning and price_out."""
    toks = [BOS,
            (MODEL_BASE + model["index"] % NUM_MODEL_TOKENS
             if model["seen"] else UNK_MODEL),
            REASONING if model["reasoning"] else STANDARD,
            PRICE_BASE + _price_bucket(model["price_out"]), SEP]
    for s, i in zip(sims, idx, strict=True):
        i = int(i)
        toks += [ANCHOR, DOMAIN_BASE + int(anchors_domain[i]),
                 SIM_BASE + _sim_bucket(float(s)),
                 YES if int(round(float(anchor_y[i]))) else NO,
                 LEN_BASE + _len_bucket(float(anchor_tokens[i]))]
    vals = np.clip(np.asarray(query_emb)[:NUM_FEAT_DIMS], -2.0, 2.0)
    buckets = np.clip(((vals + 2.0) / 4.0 * NUM_FEAT_BUCKETS).astype(int), 0,
                      NUM_FEAT_BUCKETS - 1)
    toks += [QUERY, DOMAIN_BASE + int(query_domain)]
    toks += [FEAT_BASE + j * NUM_FEAT_BUCKETS + int(b)
             for j, b in enumerate(buckets)]
    return toks + [PRED]


# -- parse (Eq. 5) -----------------------------------------------------------
def parse(gen: Sequence[int]) -> Dict:
    """{y_hat, len_hat, well_formed, dec_pos}: the format gate of
    ``THINK ... THINK_END (YES|NO) LEN EOS`` (CoT optional), and the
    position of the first YES/NO after the rationale (None if none)."""
    toks = [int(t) for t in gen]
    start = 0
    bad = False
    if THINK in toks:
        if THINK_END in toks:
            start = toks.index(THINK_END) + 1
        else:
            bad = True
    dec_pos = next((j for j in range(start, len(toks))
                    if toks[j] in (YES, NO)), None)
    body = [t for t in toks[start:] if t != PAD]
    ok = (not bad and len(body) >= 3 and body[0] in (YES, NO)
          and LEN_BASE <= body[1] < LEN_BASE + NUM_LEN_BUCKETS
          and body[2] == EOS)
    if not ok:
        return {"y_hat": 0, "len_hat": 0.0, "well_formed": False,
                "dec_pos": dec_pos}
    return {"y_hat": int(body[0] == YES),
            "len_hat": float(LEN_CENTERS[body[1] - LEN_BASE]),
            "well_formed": True, "dec_pos": dec_pos}


def confidence(yes_logit: float, no_logit: float) -> float:
    m = max(yes_logit, no_logit)
    py, pn = np.exp(yes_logit - m), np.exp(no_logit - m)
    return float(py / (py + pn))


# -- utility and decision (Eq. 11-15) ----------------------------------------
def _norm_cost(c: np.ndarray) -> np.ndarray:
    """Log min-max normalization per row (Eq. 11)."""
    lo = np.log(c.min(axis=-1, keepdims=True) + COST_EPS)
    hi = np.log(c.max(axis=-1, keepdims=True) + COST_EPS)
    span = hi - lo
    flat = span < 1e-12
    out = (np.log(c + COST_EPS) - lo) / np.where(flat, 1.0, span)
    return np.clip(np.where(flat, 0.0, out), 0.0, 1.0)


def _utility(p, c_norm, alpha, gamma_base, beta):
    g = gamma_base * (1.0 + beta * (1.0 - alpha))
    s = np.power(np.clip(1.0 - c_norm, 0.0, 1.0), g)
    return alpha * p + (1.0 - alpha) * s


def utilities(p_hat: np.ndarray, len_hat: np.ndarray, wf: np.ndarray,
              prompt_tokens: np.ndarray, price_in: np.ndarray,
              price_out: np.ndarray, sims: np.ndarray, idx: np.ndarray,
              anchor_y: np.ndarray, anchor_cost: np.ndarray, alpha: float,
              *, gamma_base: float, beta: float, w_base: float) -> np.ndarray:
    """(Q, M) decision scores: (1 - w) U_pred + w U_cal (Eq. 15).

    ``anchor_y``/``anchor_cost`` are (M, A): each pool model's recorded
    outcomes on the anchors; ``sims``/``idx`` (Q, K) the retrieved ones.
    """
    lh = np.where(wf, len_hat, FALLBACK_LEN)
    cost = (prompt_tokens * price_in[None] + lh * price_out[None]) / 1e6
    u_pred = _utility(p_hat, _norm_cost(cost), alpha, gamma_base, beta)
    w = np.clip(np.asarray(sims, np.float64), 0.0, None) + 1e-6
    w = w / w.sum(axis=-1, keepdims=True)
    p_cal = np.einsum("qk,mqk->qm", w, anchor_y[:, idx])
    c_cal = np.einsum("qk,mqk->qm", w, anchor_cost[:, idx])
    u_cal = _utility(p_cal, _norm_cost(c_cal), alpha, gamma_base, beta)
    wc = w_base * (0.5 + 0.5 * alpha)
    return (1.0 - wc) * u_pred + wc * u_cal
