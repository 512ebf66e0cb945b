"""Expert placement for a chip's share of an expert-parallel deployment.

A deployment that spreads a MoE layer's experts over chips keeps every
chip's load even: DeepSeek-V2 trains a device-level balance loss for it,
and the DeepSeek-V3/R1 inference system places experts on devices by their
measured load.  Random weights have neither, and the prompts of this
router are templated (a few hundred field tokens in a fixed layout), so a
random router sends most pairs to a few experts of each layer; which of
them fall among the experts one chip holds changes with the seed, and
with it the chip's work — by a tenth from seed to seed at DeepSeek-V2-Lite
widths.

This module stands in for the placement.  ``sample_prompts`` draws
prompts as the traffic makes them (a world of the same generative
process, its retrieval and serialization as ``routing_ref`` writes them),
and ``held_first`` picks, from one layer's routed-pair counts on those
prompts, the experts a chip holds: the chip's fair share of the pairs
(held / num_experts of them), taken from experts whose own load is near
the mean.  The reference's ``place_experts`` relabels each layer's
experts accordingly — a permutation of the router's columns, which with
random iid expert weights changes no law the weights are drawn from.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from harness import routing_ref as rr
from harness import traffic

# the prompts: the cells' deployment of the SCOPE router (its anchor set
# and retrieval depth), a few hundred of them
ANCHORS, TOP_K, QUERIES, WIDTH = 250, 5, 24, 64


def sample_prompts(seed32: int, queries: int = QUERIES
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """``queries`` x pool-size prompts of a world made from ``seed32``:
    (n, WIDTH) int32 tokens, right-padded with PAD, and their lengths."""
    from harness.runner import build_world
    w = build_world(seed32, {"anchors": ANCHORS})
    aset = w.anchors
    a_dom = np.asarray([q.domain for q in aset.queries])
    fps = [w.library.get(m) for m in w.models]
    rows = []
    for q in w.world.sample_queries(queries,
                                    seed=traffic.seed32(seed32, 5)):
        sims, idx = rr.top_k(q.embedding[None], aset.embeddings, TOP_K)
        for mi, m in enumerate(w.models):
            meta = w.meta[m]
            rows.append(rr.prompt(
                {"index": mi, "seen": meta.seen,
                 "reasoning": meta.reasoning, "price_out": meta.price_out},
                a_dom, np.asarray(fps[mi].y, np.float64),
                np.asarray(fps[mi].tokens, np.float64), sims[0], idx[0],
                q.domain, q.embedding))
    tokens = np.full((len(rows), WIDTH), rr.PAD, np.int32)
    for i, r in enumerate(rows):
        tokens[i, : len(r)] = r
    return tokens, np.asarray([len(r) for r in rows], np.int32)


def held_first(counts: np.ndarray, held: int, offset: int = 0) -> np.ndarray:
    """A permutation of the experts, new id -> old id, that puts at ids
    ``offset`` .. ``offset + held - 1`` the ``held`` experts a chip holds.

    ``counts`` (num_experts,) are the layer's routed pairs per expert.  The
    chosen experts start as the ``held`` whose counts lie nearest the mean;
    then, one exchange at a time, an expert in the set is swapped for one
    outside it while that brings the set's total nearer the fair share,
    ``counts.sum() * held / num_experts``."""
    counts = np.asarray(counts, np.int64)
    n = len(counts)
    target = counts.sum() * held / n
    order = np.argsort(np.abs(counts - counts.mean()), kind="stable")
    chosen, rest = list(order[:held]), list(order[held:])
    for _ in range(n * held):
        total = counts[chosen].sum()
        # the exchange that brings the total nearest the target
        gain = (np.abs(total - target)
                - np.abs(total - counts[chosen][:, None]
                         + counts[rest][None, :] - target))
        i, j = np.unravel_index(np.argmax(gain), gain.shape)
        if gain[i, j] <= 0:
            break
        chosen[i], rest[j] = rest[j], chosen[i]
    chosen, rest = sorted(chosen), sorted(rest)
    return np.asarray(rest[:offset] + chosen + rest[offset:], np.int64)
