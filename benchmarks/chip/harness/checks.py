"""Whether what the window served is correct: the comparison with the plain
references, run once the window has closed and the program's state is
freed.

A sample of the counted queries, drawn from the seed, is checked at every
layer the window drives, each pair as the timed path served it:

  retrieval_gap    the program's top-k anchors against a float64 cosine
                   top-k: how far a picked anchor's similarity lies from
                   the rank it claims, and how far the reported similarity
                   lies from the exact one (cosine units)
  prompt_mismatch  pairs whose served prompt differs from the reference
                   serialization (Eq. 4) of the reference retrieval
  logit_gap        the widest gap by which a served token's logit lies
                   below the best logit of the float32 reference run over
                   the prompt and the served tokens (greedy decoding)
  parse_mismatch   pairs whose answered (y_hat, len_hat, well_formed)
                   differ from the reference parse of the served tokens
  decision_gap     how far the routed model's utility (Eq. 15, from the
                   reference parse and the reference's decision logits)
                   lies below the best model's
  failed_queries   counted queries not answered OK, and failures the
                   program counted without a planted fault

Hits of the prediction cache are checked against the tokens served when
the pair was first answered, so a hit must return the first answer.

``control="fp8"`` puts the reference, computed with every matrix-product
input rounded to float8, in the program's place for the logit gap: at each
position of the same prompts and served tokens it reads the gap of the
token the lower precision puts first.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from harness import routing_ref as rr
from harness import traffic


def _sample(sess, seed: int, n: int) -> List[int]:
    counted = [int(i) for i in sess.last_counted if int(i) in sess.last_pools]
    rng = traffic.rng_for(seed, 7)
    n = min(n, len(counted))
    return sorted(int(i) for i in rng.choice(counted, size=n, replace=False))


def check(sess, seed: int, *, control: str = "none") -> Dict[str, Any]:
    from repro.api.cache import query_key

    cfg = sess.cell.config
    lim = cfg["check"]
    router = cfg["deployment"]["router"]
    w = sess.w
    version = sess.engine.config.estimator_version
    k = int(cfg["deployment"]["top_k"])
    aset = w.anchors
    a_dom = np.asarray([q.domain for q in aset.queries])
    fps = [w.library.get(m) for m in w.models]
    a_y = np.stack([np.asarray(f.y, np.float64) for f in fps])
    a_tok = np.stack([np.asarray(f.tokens, np.float64) for f in fps])
    a_cost = np.stack([np.asarray(f.cost, np.float64) for f in fps])
    metas = [w.meta[m] for m in w.models]
    price_in = np.asarray([x.price_in for x in metas])
    price_out = np.asarray([x.price_out for x in metas])

    picks = _sample(sess, seed, int(lim["sample_queries"]))
    retrieval_gap = 0.0
    prompt_mismatch = 0
    rows: List[Dict[str, Any]] = []
    for i in picks:
        pool, r, choice = sess.last_pools[i]
        q = sess.last_qs[i]
        if list(pool.models) != list(w.models):
            raise RuntimeError("the pool's model order changed")
        cos = rr.cosine(q.embedding[None], aset.embeddings)[0]
        ref_sims, ref_idx = rr.top_k(q.embedding[None], aset.embeddings, k)
        got_idx = np.asarray(pool.idx[r], int)
        got_rank = np.sort(cos[got_idx])[::-1]
        retrieval_gap = max(retrieval_gap,
                            float(np.abs(got_rank - ref_sims[0]).max()),
                            float(np.abs(np.asarray(pool.sims[r], np.float64)
                                         - cos[got_idx]).max()))
        key = query_key(q)
        for mi, m in enumerate(w.models):
            prompt, gen = sess.est.served[(key, m, version)]
            want = rr.prompt(
                {"index": mi, "seen": metas[mi].seen,
                 "reasoning": metas[mi].reasoning,
                 "price_out": metas[mi].price_out},
                a_dom, a_y[mi], a_tok[mi], ref_sims[0], ref_idx[0],
                q.domain, q.embedding)
            prompt_mismatch += int(list(prompt) != want)
            gen = np.asarray(gen)
            # a row that emitted EOS decodes forced PAD after it: served
            # tokens end at the EOS
            eos = np.flatnonzero(gen == rr.EOS)
            if len(eos):
                gen = gen[: eos[0] + 1]
            # the estimator is held to what it was served: its prompt
            rows.append({"i": i, "r": r, "mi": mi, "choice": choice,
                         "pool": pool, "prompt": list(prompt), "gen": gen,
                         "sims": ref_sims[0], "idx": ref_idx[0]})

    # -- the estimator: one reference forward over prompt + served tokens
    width = max(len(x["prompt"]) + len(x["gen"]) for x in rows)
    tokens = np.zeros((len(rows), width), np.int32)
    pr, pc, pt = [], [], []
    for j, x in enumerate(rows):
        seq = list(x["prompt"]) + [int(t) for t in x["gen"]]
        tokens[j, : len(seq)] = seq
        start = len(x["prompt"]) - 1
        for t, tok in enumerate(x["gen"]):
            pr.append(j)
            pc.append(start + t)
            pt.append(int(tok))
    ref = sess.ref
    m = cfg["model"]
    extra = (rr.YES, rr.NO)
    if control == "none":
        out = ref.readout(sess.params, m, tokens, pr, pc, pt, extra)
        logit_gap = float((out["max"] - out["at_target"]).max())
    else:
        ctl = ref.readout(sess.params, m, tokens, pr, pc, pt, extra,
                          control=control)
        alt = ref.readout(sess.params, m, tokens, pr, pc, ctl["argmax"],
                          extra)
        out = ref.readout(sess.params, m, tokens, pr, pc, pt, extra)
        logit_gap = float((alt["max"] - alt["at_target"]).max())
    yes_no = out["extra"]

    # -- parse and decision, per pair then per query
    parse_mismatch = 0
    per_q: Dict[int, Dict[str, Any]] = {}
    pos = 0
    for x in rows:
        n = len(x["gen"])
        p = rr.parse(x["gen"])
        conf = 0.5
        if p["dec_pos"] is not None:
            yn = yes_no[pos + p["dec_pos"]]
            conf = rr.confidence(float(yn[0]), float(yn[1]))
        pos += n
        pool, r, mi = x["pool"], x["r"], x["mi"]
        lh = p["len_hat"] if p["well_formed"] else rr.FALLBACK_LEN
        same = (int(pool.y_hat[r, mi]) == p["y_hat"]
                and bool(pool.well_formed[r, mi]) == p["well_formed"]
                and abs(float(pool.len_hat[r, mi]) - lh) < 1e-9)
        parse_mismatch += int(not same)
        q = per_q.setdefault(x["i"], {
            "choice": x["choice"], "sims": x["sims"], "idx": x["idx"],
            "p": np.zeros(len(w.models)), "lh": np.zeros(len(w.models)),
            "wf": np.zeros(len(w.models), bool),
            "pt": np.zeros(len(w.models))})
        q["p"][mi], q["lh"][mi], q["wf"][mi] = conf, p["len_hat"], \
            p["well_formed"]
        q["pt"][mi] = len(x["prompt"])
    decision_gap = 0.0
    for q in per_q.values():
        u = rr.utilities(
            q["p"][None], q["lh"][None], q["wf"][None], q["pt"][None],
            price_in, price_out, q["sims"][None], q["idx"][None], a_y,
            a_cost, float(sess.cell.traffic["policy"]["alpha"]),
            gamma_base=float(router["gamma_base"]), beta=float(router["beta"]),
            w_base=float(router["w_base"]))[0]
        decision_gap = max(decision_gap, float(u.max() - u[q["choice"]]))

    numbers = {
        "retrieval_gap": retrieval_gap,
        "prompt_mismatch": prompt_mismatch,
        "logit_gap": logit_gap,
        "parse_mismatch": parse_mismatch,
        "decision_gap": decision_gap,
        "failed_queries": sess.last_failed,
    }
    checks = {name: {"value": v, "limit": lim[name]}
              for name, v in numbers.items()}
    return {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
            "checks": checks, "pairs": len(rows), "tokens": len(pt),
            "queries": len(picks)}
