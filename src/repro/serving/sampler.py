"""KV-cache generation: batch prefill + fused jitted decode scan segments.

Decode is organised around an explicit ``DecodeState`` (caches, per-row
positions, done-mask, carried sampling key) so the serve runtime can run
decode in **chunked scan segments**.  Two layouts back it:

* dense (``prefill_state``): one batch prefilled at once and decoded to
  the end — the one-shot path (``generate``, whole-retire microbatches);
* paged (``open_state`` over a ``kv_pool.KVPool``): continuous batching.
  A drained-at-EOS slot is refilled with a fresh prompt between segments
  instead of idling until the batch finishes, and the refill rides the
  next segment's launch:

  state = open_state(params, cfg, slots, max_new_tokens=12, kv_pool=pool)
  state, gen, dec = decode_segment(params, cfg, state, 4,
                                   refill=(mask, prompts, lens))
  state, gen2, dec2 = decode_segment(params, cfg, state, 4)

A fused refill prefills the admitted rows only, compacted into the
smallest row bucket (b/4, b/2 or b rows) that holds them; a state opens
with nothing prefilled, so its opening rows ride its first launch the same
way.

Positions are **per row**: rows at different sequence offsets (ragged
prompt lengths under a bucket grid, refilled slots mid-decode) share one
compiled decode executable, and sub-bucket rows reproduce an unpadded run
exactly — attention masks each row at its own valid length and RoPE rotates
at each row's own position.  (Exactness holds for attention backbones;
SSM/conv states consume right-pad tokens during prefill, so keep exact-fit
lengths for those.)

Each scan segment samples on device (greedy or temperature), carries an
EOS done-mask, and only what the estimator consumes crosses back to the
host — generated token ids plus the YES/NO logit pair at each step.  The
full ``(b, T, V)`` logits stack never leaves the device.

The executables name their halves with ``jax.named_scope`` — ``prefill``,
and per scan step ``decode`` with ``sample`` inside — so the ops of a
profile carry the layer they belong to in their ``op_name`` metadata.

For a backbone with expert layers the executables also return ``stats``:
``expert_tokens_decode`` (and, with a refill, ``expert_tokens_prefill``),
(expert layers, experts held) int32, the tokens each layer routed to each
held expert in the launch; ``DecodeState.stats`` carries them to the
host's next sync.  Without expert layers ``stats`` is empty and the
executables are what they were without it.

``COMPILE_COUNTS`` counts executable builds explicitly (incremented inside
the traced bodies, once per compilation) — the serve path's "0 recompiles
after warmup" gate reads it instead of sniffing jit internals.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import Counter
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.data.tokenizer import EOS, NO, PAD, YES
from repro.models import model as M
from repro.serving.kv_pool import (KVPool, PagedKV, _ceil_div,
                                   check_paged_support)
from repro.kernels.decode_attention import KernelType

# decision-logit channel order: [:, :, 0] = YES, [:, :, 1] = NO
DECISION_TOKENS = (YES, NO)

# Explicit compile-count instrumentation: the jitted bodies below increment
# these counters at trace time, which happens exactly once per compiled
# (shape, dtype, static-arg) combination.  Process-global and monotonic —
# diff two snapshots to count the compiles of a traffic window.
COMPILE_COUNTS: "Counter[str]" = Counter()


@functools.partial(jax.jit, static_argnums=(1,))
def _prefill(params, cfg: ModelConfig, tokens, lens):
    """Prefill returning each row's last *valid* prompt logits (b, V) f32
    (row i's at position ``lens[i] - 1``) and the prompt-sized caches."""
    COMPILE_COUNTS["prefill"] += 1          # traced once per compilation
    with jax.named_scope("prefill"):
        logits, caches = M.prefill(params, cfg, {"tokens": tokens}, lens)
        return logits.astype(jnp.float32), caches


# Explicit seq-axis contract for decode caches, keyed by leaf name.  The
# axis index includes the leading layer-stack dim the segment scan adds:
#   k / v  : (L, b, kv_heads, S, head_dim)  -> axis 3
#   c_kv   : (L, b, S, kv_lora_rank)        -> axis 2
#   k_rope : (L, b, S, qk_rope_head_dim)    -> axis 2
# Everything else (mamba conv/ssm states, ck/cv encoder cross caches) has no
# decode-time sequence axis and must never be grown, whatever its shape.
CACHE_SEQ_AXIS = {"k": 3, "v": 3, "c_kv": 2, "k_rope": 2}


def _leaf_name(path) -> str:
    entry = path[-1]
    if hasattr(entry, "key"):
        return str(entry.key)
    return str(entry)


def _pad_caches(caches, max_len: int, prompt_len: int):
    """Grow prefill caches (seq = prompt_len) to decode capacity.

    The sequence axis comes from the cache *structure* (leaf name ->
    ``CACHE_SEQ_AXIS``), never from sniffing shapes: a head count, conv
    width, or SSM state dim that happens to equal ``prompt_len`` must not
    be padded — growing the wrong axis silently corrupts decode.
    """
    def grow(path, leaf):
        ax = CACHE_SEQ_AXIS.get(_leaf_name(path))
        if ax is None:
            return leaf
        if leaf.shape[ax] != prompt_len:
            raise ValueError(
                f"cache leaf {_leaf_name(path)!r} has seq axis "
                f"{leaf.shape[ax]} != prompt_len {prompt_len} "
                f"(shape {leaf.shape})")
        widths = [(0, 0)] * leaf.ndim
        widths[ax] = (0, max_len - prompt_len)
        return jnp.pad(leaf, widths)

    return jax.tree_util.tree_map_with_path(grow, caches)


def _run_scan(params, cfg: ModelConfig, last_logits, caches, key,
              steps: int, temperature: float, stop_at_eos: bool,
              positions, done, paged=None):
    """Traced scan body shared by ``_scan_decode`` and the paged
    executables: sample -> emit (token, YES/NO) -> step, for
    ``steps`` steps.  ``paged`` = (PagedSpec, page table) reroutes the KV
    writes/reads through the block-paged layout; the sampling math is
    byte-for-byte the same code path.  Returns (gen, dec, last, caches,
    done, key, stats)."""
    dec_ix = jnp.asarray(DECISION_TOKENS, jnp.int32)

    def step(carry, t):
        logits, kv, dn, k, routed = carry
        with jax.named_scope("decode"):
            with jax.named_scope("sample"):
                if temperature > 0.0:
                    k, sub = jax.random.split(k)
                    nxt = jax.random.categorical(sub, logits / temperature,
                                                 axis=-1)
                else:
                    nxt = jnp.argmax(logits, axis=-1)
                nxt = jnp.where(dn, PAD, nxt).astype(jnp.int32)
                dec = logits[:, dec_ix]                  # (b, 2)
                if stop_at_eos:
                    dn = dn | (nxt == EOS)
            new_logits, kv, st = M.decode_step(params, cfg, nxt[:, None], kv,
                                               positions + t, paged=paged,
                                               with_stats=True)
            routed = jax.tree.map(jnp.add, routed, st)
            new_logits = new_logits[:, 0].astype(jnp.float32)
        return (new_logits, kv, dn, k, routed), (nxt, dec)

    # the step's stats summed over the segment (none without experts)
    routed = {}
    if cfg.has_moe():
        routed["expert_tokens"] = jnp.zeros(
            (M.expert_layers(cfg), cfg.resolved_experts_held), jnp.int32)
    init = (last_logits, caches, done, key, routed)
    (last, kv, done, key, routed), (gen, dec) = jax.lax.scan(
        step, init, jnp.arange(steps))
    stats = {f"{name}_decode": v for name, v in routed.items()}
    # (b, T), (b, T, 2), + carry for the next segment
    return gen.T, dec.transpose(1, 0, 2), last, kv, done, key, stats


# the dense executable keeps its caches undonated: a dense state may be
# decoded more than once (the tests branch segments from one state).  The
# paged executables below donate theirs.
@functools.partial(jax.jit, static_argnums=(1, 5, 6, 7))
def _scan_decode(params, cfg: ModelConfig, last_logits, caches, key,
                 steps: int, temperature: float, stop_at_eos: bool,
                 positions, done):
    """One fused decode segment.

    ``positions`` is the per-row (b,) count of tokens already cached at
    segment start, so row i's token at segment step t lands at absolute
    position ``positions[i] + t``.  Per-step outputs are the sampled token
    ids (b,) and the decision logit pair (b, 2).  Nothing of size V escapes
    the scan.  Returns the full carry so segments can be chained.
    """
    COMPILE_COUNTS["scan_decode"] += 1      # traced once per compilation
    return _run_scan(params, cfg, last_logits, caches, key, steps,
                     temperature, stop_at_eos, positions, done)


# ---------------------------------------------------------------------------
# Row buckets: a refill prefills the admitted rows only
# ---------------------------------------------------------------------------
def row_buckets(b: int, multiple: int = 1) -> Tuple[int, ...]:
    """Row counts the paged prefills of a ``b``-slot batch compute: b/4,
    b/2 and b, keeping those that are a whole multiple of ``multiple``
    (the batch-sharding degree, so a sharded batch still partitions)."""
    return tuple(sorted({r for r in (b // 4, b // 2, b)
                         if r == b or (r >= 1 and r % multiple == 0)}))


def bucket_rows(rows, b: int, multiple: int = 1) -> np.ndarray:
    """Slot rows ``rows`` padded to the smallest row bucket that holds
    them; filler entries equal ``b``, and the executables drop their
    updates and send their page blocks to trash."""
    rows = np.asarray(rows, np.int32).reshape(-1)
    size = next(r for r in row_buckets(b, multiple) if r >= len(rows))
    out = np.full((size,), b, np.int32)
    out[: len(rows)] = rows
    return out


def _row_shards(x) -> int:
    """How many ways ``x``'s leading (batch) axis is split over devices."""
    sharding = getattr(x, "sharding", None)
    if sharding is None:
        return 1
    return x.shape[0] // sharding.shard_shape(x.shape)[0]


def _admit_rows(params, cfg: ModelConfig, rows, prompts, lens,
                last_logits, positions, done):
    """Traced refill prefill of the fused refill executable: prefill the
    bucketed prompts (R, W) at their true lengths, and reset the slots
    ``rows`` (R,) to them — last logits, position, not done; filler
    entries (== b) are dropped.  Returns the prompts' prompt-sized caches
    (R rows) for the caller to merge into its own layout, and the
    prefill's ``stats`` (``expert_tokens_prefill``; empty without expert
    layers)."""
    # filler rows route no tokens to experts (checked only for an expert
    # backbone, so a dense one traces exactly what it did before)
    live = rows < last_logits.shape[0] if M.expert_layers(cfg) else None
    last_new, new, st = M.prefill(params, cfg, {"tokens": prompts}, lens,
                                  live=live, with_stats=True)
    stats = {f"{name}_prefill": v for name, v in st.items()}
    last_logits = last_logits.at[rows].set(last_new.astype(jnp.float32),
                                           mode="drop")
    positions = positions.at[rows].set(lens.astype(jnp.int32), mode="drop")
    done = done.at[rows].set(False, mode="drop")
    return new, last_logits, positions, done, stats


# ---------------------------------------------------------------------------
# Paged executables: prefill-scatter + decode over the block-paged KV layout
# ---------------------------------------------------------------------------
def _layers(count: int) -> jax.Array:
    """(count, 1) layer indices for a page scatter into stacked storage:
    indexing the layer axis with the page axis keeps the scatter's indexed
    axes leading, so XLA writes the pages in place rather than first
    transposing the storage to bring the page axis forward."""
    return jnp.arange(count)[:, None]


def _paged_latent_scatter(leaf, storage, page_ids, page_size: int):
    """``_paged_leaf_scatter`` for an MLA latent leaf: (count, b, L, r)
    into latent pages (count, n_pages + 1, page_size, r)."""
    count, b, L, r = leaf.shape
    npg = page_ids.shape[0] // b
    pad = npg * page_size - L
    if pad:
        leaf = jnp.pad(leaf, [(0, 0), (0, 0), (0, pad), (0, 0)])
    blocks = leaf.reshape(count, b * npg, page_size, r)
    return storage.at[_layers(count), page_ids].set(
        blocks.astype(storage.dtype))


def _paged_storage(leaf, n_pages_total: int, page_size: int):
    """Zeroed page storage for a prefill cache leaf: K/V (count, b, hkv,
    L, hd) -> token-major (count, n_pages_total, page_size, hkv, hd); an
    MLA latent (count, b, L, r) -> (count, n_pages_total, page_size, r)."""
    if leaf.ndim == 4:
        count, _, _, r = leaf.shape
        return jnp.zeros((count, n_pages_total, page_size, r), leaf.dtype)
    count, _, hkv, _, hd = leaf.shape
    return jnp.zeros((count, n_pages_total, page_size, hkv, hd), leaf.dtype)


def _paged_leaf_scatter(leaf, storage, page_ids, page_size: int):
    """Scatter a dense prefill leaf (count, b, hkv, L, hd) into token-major
    paged storage (count, n_pages + 1, page_size, hkv, hd) at the
    flattened (b * ceil(L / page_size),) physical destinations
    ``page_ids``: only the b prefilled rows are brought token-major.

    Pad/filler blocks all target the trash page; their writes collide
    there in nondeterministic order, which is unobservable — trash reads
    are always masked to exact-zero probability or belong to discarded
    rows — so the scatter must not claim unique indices.
    """
    count, b, hkv, L, hd = leaf.shape
    npg = page_ids.shape[0] // b
    pad = npg * page_size - L
    if pad:
        leaf = jnp.pad(leaf, [(0, 0), (0, 0), (0, 0), (0, pad), (0, 0)])
    blocks = leaf.reshape(count, b, hkv, npg, page_size, hd)
    blocks = blocks.transpose(0, 1, 3, 4, 2, 5).reshape(
        count, b * npg, page_size, hkv, hd)
    return storage.at[_layers(count), page_ids].set(
        blocks.astype(storage.dtype))


def _scatter_prefill_caches(caches, storage_of, page_ids, page_size: int):
    """Tree-map the page scatter over the cache leaves: GQA k/v pairs and
    MLA latents (c_kv, k_rope).

    ``check_paged_support`` guarantees every decode-cache leaf is one of
    those, so anything else here is a bug, not a user error.
    """
    def scatter(path, leaf):
        name = _leaf_name(path)
        if name in ("c_kv", "k_rope"):
            return _paged_latent_scatter(leaf, storage_of(path, leaf),
                                         page_ids, page_size)
        if name not in ("k", "v"):
            raise AssertionError(
                f"paged scatter hit an unpaged cache leaf {name!r}")
        return _paged_leaf_scatter(leaf, storage_of(path, leaf), page_ids,
                                   page_size)

    return jax.tree_util.tree_map_with_path(scatter, caches)


@functools.partial(jax.jit, static_argnums=(1, 3, 4))
def _paged_prefill(params, cfg: ModelConfig, tokens, n_pages_total: int,
                   page_size: int, page_ids, lens):
    """Prefill + scatter into **fresh** paged storage.

    ``page_ids`` (b * npg,) maps each row's prompt page blocks to the
    physical pages its table owns (trash for inactive rows / pad blocks).
    Storage is ``_paged_storage``'s per leaf, with the trash page at
    index n_pages_total - 1.  Returns the rows' last valid logits (b, V)
    f32, as ``_prefill``.
    """
    COMPILE_COUNTS["paged_prefill"] += 1    # traced once per compilation

    def storage_of(path, leaf):
        return _paged_storage(leaf, n_pages_total, page_size)

    with jax.named_scope("prefill"):
        logits, caches = M.prefill(params, cfg, {"tokens": tokens}, lens)
        return logits.astype(jnp.float32), _scatter_prefill_caches(
            caches, storage_of, page_ids, page_size)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _paged_open(params, cfg: ModelConfig, batch: int, n_pages_total: int,
                page_size: int):
    """Zeroed paged storage for ``cfg``'s decode caches, laid out as
    ``_paged_prefill`` leaves it, and zero (batch, V) last logits: a state
    opened with nothing prefilled."""
    COMPILE_COUNTS["paged_open"] += 1       # traced once per compilation
    logits, caches = jax.eval_shape(
        lambda p: M.prefill(p, cfg, {"tokens": jnp.zeros((1, page_size),
                                                         jnp.int32)}),
        params)

    return (jnp.zeros((batch, logits.shape[-1]), jnp.float32),
            jax.tree.map(lambda leaf: _paged_storage(leaf, n_pages_total,
                                                     page_size), caches))


# The paged executables donate the page pool (``caches``, argument 3): the
# layer scan carries it and writes each new token into it in place, so the
# pool returned aliases the pool passed and a launch copies none of it.
# The caller's pool is dead after the call; ``decode_segment`` hands the
# returned one on in the new state.
@functools.partial(jax.jit, static_argnums=(1, 5, 6, 7, 8),
                   donate_argnums=(3,))
def _paged_scan_decode(params, cfg: ModelConfig, last_logits, caches, key,
                       steps: int, temperature: float, stop_at_eos: bool,
                       spec, table, positions, done):
    """``_scan_decode`` over the paged layout.  ``spec`` (static) carries
    page_size / kv_cap / kernel; ``table`` is the traced (b, W) page
    table pushed fresh each segment — its shape is constant per batch, so
    table updates never recompile."""
    COMPILE_COUNTS["paged_scan_decode"] += 1
    return _run_scan(params, cfg, last_logits, caches, key, steps,
                     temperature, stop_at_eos, positions, done,
                     paged=(spec, table))


@functools.partial(jax.jit, static_argnums=(1, 5, 6, 7, 8),
                   donate_argnums=(3,))
def _paged_refill_scan_decode(params, cfg: ModelConfig, last_logits, caches,
                              key, steps: int, temperature: float,
                              stop_at_eos: bool, spec, table, positions,
                              done, refill_rows, refill_prompts,
                              refill_lens, refill_page_ids):
    """``_paged_scan_decode`` with slot refill fused into the same
    executable, prefilling only the admitted rows: ``refill_prompts``
    (R, W) are the admitted prompts compacted into a row bucket,
    ``refill_rows`` (R,) their slot rows (``bucket_rows``; filler entries
    equal b) and ``refill_lens`` (R,) their true lengths.  Prefill the R
    rows, scatter their page blocks into the pool storage at
    ``refill_page_ids`` (filler rows scatter to trash), reset the admitted
    slots' position / done / last logits, then run the segment over all
    b slots: one launch admits every slot drained at a boundary and
    decodes the next segment."""
    COMPILE_COUNTS["paged_refill_scan_decode"] += 1
    flat_cache = {}

    def name_leaf(path, leaf):
        flat_cache[jax.tree_util.keystr(path)] = leaf
        return leaf

    jax.tree_util.tree_map_with_path(name_leaf, caches)
    with jax.named_scope("prefill"):
        new, last_logits, positions, done, admitted = _admit_rows(
            params, cfg, refill_rows, refill_prompts, refill_lens,
            last_logits, positions, done)
        caches = _scatter_prefill_caches(
            new, lambda path, leaf: flat_cache[jax.tree_util.keystr(path)],
            refill_page_ids, spec.page_size)
    out = _run_scan(params, cfg, last_logits, caches, key, steps,
                    temperature, stop_at_eos, positions, done,
                    paged=(spec, table))
    return out[:-1] + (positions, {**out[-1], **admitted})


# ---------------------------------------------------------------------------
# DecodeState: explicit decode carry between scan segments
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DecodeState:
    """Decode carry between scan segments (slot-based continuous batching).

    ``positions[i]`` counts the tokens already in row i's cache; ``done``
    marks rows that emitted EOS (they keep decoding PAD at zero semantic
    cost until refilled or the batch retires).  ``used`` is a host-side
    upper bound on cache occupancy, checked against ``max_len`` before a
    segment runs off the end of the allocation.

    ``paged`` (a ``kv_pool.PagedKV``) switches the caches to the
    block-paged layout: ``max_len`` then equals the paged ``kv_cap`` and
    the batch-wide ``used`` guard is replaced by the attachment's per-row
    ``row_high`` bound — a drained row's pages return to the pool instead
    of idling until the whole batch retires.
    """
    caches: Any
    last_logits: jax.Array          # (b, V) float32
    positions: jax.Array            # (b,) int32
    done: jax.Array                 # (b,) bool
    key: Optional[jax.Array]        # carried sampling key (None = greedy)
    max_len: int                    # per-row cache capacity (slots)
    used: int                       # host upper bound of max(positions)
    paged: Optional[PagedKV] = None
    # rows the prefill of the launch that made this state computed (0 for
    # a plain segment), and the batch-sharding degree its row buckets keep
    prefill_rows: int = 0
    row_shards: int = 1
    # that launch's device-side counters (``expert_tokens_*``; empty
    # without expert layers), read by the host at its next sync
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def batch(self) -> int:
        return int(self.last_logits.shape[0])


def prefill_state(params, cfg: ModelConfig, prompts, *,
                  max_new_tokens: int, prompt_lens=None,
                  rng: Optional[jax.Array] = None,
                  kv_pool: Optional[KVPool] = None,
                  kv_kernel: KernelType = KernelType.XLA) -> DecodeState:
    """Batch prefill into a ``DecodeState`` sized for ``max_new_tokens``.

    ``prompts``: (b, L) int32, right-padded.  ``prompt_lens`` (b,) gives
    each row's true length; row i then decodes from position
    ``prompt_lens[i]`` with attention masked at its own valid length, so a
    sub-bucket row reproduces the unpadded run exactly (attention
    backbones).  ``None`` means every row is exactly L long.

    ``kv_pool`` backs the state with the block-paged KV layout instead of
    a dense O(b x max_len) allocation: each admitted row reserves its own
    worst case (``len + max_new_tokens`` tokens, page-rounded) and pages
    materialize only as positions advance (``open_state`` opens one with
    no row admitted, for the refill serve path).  ``kv_kernel`` selects the
    paged attention implementation (``KernelType.XLA`` is bit-identical
    to dense; PALLAS is the TPU kernel, interpreted on CPU).
    """
    prompts = jnp.asarray(prompts, jnp.int32)
    b, lp = prompts.shape
    max_len = lp + int(max_new_tokens)
    if prompt_lens is None:
        lens = None
    else:
        lens = np.asarray(prompt_lens, np.int64).reshape(-1)
        if lens.shape != (b,):
            raise ValueError(f"prompt_lens shape {lens.shape} != ({b},)")
        if lens.min() < 1 or lens.max() > lp:
            raise ValueError(
                f"prompt_lens must lie in [1, {lp}], got "
                f"[{lens.min()}, {lens.max()}]")
        if lens.min() < lp and cfg.has_ssm():
            # SSM/conv prefill has no per-row masking: the recurrent state
            # consumes right-pad tokens, silently corrupting sub-bucket
            # rows.  Loud failure beats wrong routing decisions.
            raise ValueError(
                "ragged prompt_lens require an attention-only backbone: "
                f"{cfg.name!r} has SSM/conv layers whose prefill state "
                "consumes right-pad tokens — use exact-fit lengths "
                "(BucketConfig(prompt_lens=()))")

    paged = None
    if kv_pool is not None:
        check_paged_support(cfg)
        paged = _attach(kv_pool, b, max_len, max_new_tokens, kv_kernel)
        row_lens = np.full((b,), lp, np.int64) if lens is None else lens
        for i in range(b):
            paged.admit_row(i, int(row_lens[i]))
    positions = (jnp.full((b,), lp, jnp.int32) if lens is None
                 else jnp.asarray(lens, jnp.int32))
    if paged is not None:
        npg = _ceil_div(lp, paged.page_size)
        ids = paged.prompt_page_ids(np.arange(b), npg).reshape(-1)
        last, caches = _paged_prefill(params, cfg, prompts,
                                      kv_pool.n_pages + 1,
                                      paged.page_size, jnp.asarray(ids),
                                      positions)
    else:
        last, caches = _prefill(params, cfg, prompts, positions)
        caches = _pad_caches(caches, max_len, lp)
    return DecodeState(caches, last, positions,
                       done=jnp.zeros((b,), bool), key=rng,
                       max_len=max_len, used=lp, paged=paged,
                       prefill_rows=b, row_shards=_row_shards(prompts))


def _attach(kv_pool: KVPool, b: int, max_len: int, max_new_tokens: int,
            kv_kernel: KernelType) -> PagedKV:
    """Page tables for a b-row paged state of ``max_len`` slots a row."""
    if kv_pool.page_size > max_len:
        raise ValueError(
            f"kv_page_size {kv_pool.page_size} exceeds the row "
            f"capacity {max_len} — a page would never fill")
    return kv_pool.attach(b, kv_cap=max_len,
                          budget_steps=int(max_new_tokens), kernel=kv_kernel)


def open_state(params, cfg: ModelConfig, prompts, *, max_new_tokens: int,
               kv_pool: KVPool, kv_kernel: KernelType = KernelType.XLA,
               rng: Optional[jax.Array] = None) -> DecodeState:
    """A paged ``DecodeState`` over a slot batch shaped (b, L) like
    ``prompts``, with no row admitted and nothing prefilled.

    Every slot starts free — zero logits, its table on the trash page —
    and is admitted by a refill: the opening prompts ride the first
    ``decode_segment(refill=...)`` launch, prefilled in the fused
    executable's row bucket with its first segment.  ``prompts`` gives
    the batch shape and its placement (a sharded batch keeps row buckets
    that partition); its tokens are not read.  Sizes and guards are
    ``prefill_state``'s.
    """
    check_paged_support(cfg)
    b, lp = np.shape(prompts)
    max_len = lp + int(max_new_tokens)
    paged = _attach(kv_pool, b, max_len, max_new_tokens, kv_kernel)
    last, caches = _paged_open(params, cfg, b, kv_pool.n_pages + 1,
                               kv_pool.page_size)
    return DecodeState(caches, last, jnp.full((b,), lp, jnp.int32),
                       done=jnp.zeros((b,), bool), key=rng, max_len=max_len,
                       used=lp, paged=paged, row_shards=_row_shards(prompts))


def _refill_operands(rows: np.ndarray, prompts: np.ndarray,
                     lens: np.ndarray) -> tuple:
    """The bucketed slot rows ``rows`` (``bucket_rows``) with their
    prompts and true lengths compacted from the slot-aligned (b, W)
    ``prompts`` and (b,) ``lens`` — filler entries take the last slot's —
    as the fused refill executable takes them."""
    take = functools.partial(np.take, indices=rows, axis=0, mode="clip")
    return (jnp.asarray(rows), jnp.asarray(take(prompts)),
            jnp.asarray(take(lens), jnp.int32))


# signatures (static arguments, input shapes, dtypes and placements) whose
# row buckets ``warm_row_buckets`` has already run: process-wide, as jit's
# own cache of executables is, which is what it stands for
_WARMED: set = set()


def _signature(*trees) -> tuple:
    return tuple((tuple(x.shape), str(x.dtype), getattr(x, "sharding", None))
                 for x in jax.tree.leaves(trees))


def warm_row_buckets(params, cfg: ModelConfig, state: DecodeState,
                     width: int, steps: int) -> None:
    """Run every row bucket of the paged fused refill once, so that no
    later refill of this state's shapes compiles.

    ``state`` is a paged state as opened (``open_state``), ``width`` its
    refill prompts' width and ``steps`` its segment length; the launches
    sample as ``decode_segment`` does by default (greedy, stopping at
    EOS).  Every row of them is filler — no update lands, every page
    block and decode write goes to trash — so the pool each launch
    returns (it donates the one it takes) differs from the one it took in
    the trash page alone, which no read sees; it becomes ``state``'s pool,
    and the launches' other outputs are dropped.  A signature already
    warmed in this process is skipped.
    """
    pg = state.paged
    b = state.batch
    # scopelint: allow[serve-time-nondeterminism] -- the key decode_segment passes a greedy state; these launches' outputs are dropped
    key = state.key if state.key is not None else jax.random.PRNGKey(0)
    sig = (cfg, int(width), int(steps), pg.spec, pg.pool.n_pages,
           _signature(params, state.caches, state.last_logits, key))
    if sig in _WARMED:
        return
    _WARMED.add(sig)
    pad = np.full((b, width), PAD, np.int32)
    for size in row_buckets(b, state.row_shards):
        rows = np.full((size,), b, np.int32)
        out = _paged_refill_scan_decode(
            params, cfg, state.last_logits, state.caches, key, int(steps),
            0.0, True, pg.spec, pg.device_table(), state.positions,
            state.done, *_refill_operands(rows, pad, np.ones((b,), np.int64)),
            jnp.asarray(pg.prompt_page_ids(
                rows, _ceil_div(width, pg.page_size)).reshape(-1)))
        state.caches = jax.block_until_ready(out[3])


def decode_segment(params, cfg: ModelConfig, state: DecodeState, steps: int,
                   *, temperature: float = 0.0, stop_at_eos: bool = True,
                   refill: Optional[Tuple] = None
                   ) -> Tuple[DecodeState, jax.Array, jax.Array]:
    """Run ``steps`` decode steps; returns (state, gen (b, T), dec (b, T, 2)).

    ``gen``/``dec`` are device arrays — the caller decides when to block
    (``np.asarray``), which is what lets the serve runtime overlap host
    assembly with device decode.  Chaining segments is bit-identical to one
    segment of the summed length (the scan body is unchanged and the
    sampling key is carried).  A paged ``state`` is consumed: its page
    pool is donated to the launch and updated in place, so decode on from
    the returned state only.

    ``refill`` = (mask (b,), prompts (b, W), prompt_lens (b,)) admits new
    requests into the masked slots of a paged state **in the same
    executable launch**: the masked rows' prompts are compacted into the
    smallest row bucket that holds them (``bucket_rows``) and prefilled
    (right-padded to width W, true lengths in ``prompt_lens``) into fresh
    pages, and the masked rows reset to decode from their own prompt
    before the segment runs; the other rows decode on untouched.  A dense
    state decodes its one batch to the end and takes no refill.
    """
    steps = int(steps)
    pg = state.paged
    if steps <= 0:
        raise ValueError(f"steps must be positive, got {steps}")
    if pg is None:
        if refill is not None:
            raise ValueError(
                "a refill needs a paged state (open_state, or "
                "prefill_state with a kv_pool): a dense state decodes its "
                "one batch to the end")
        if state.used + steps > state.max_len:
            raise ValueError(
                f"segment of {steps} steps overruns the cache: "
                f"{state.used} used of {state.max_len} slots")
    elif refill is None:
        # per-row bound: each live row needs `steps` more slots under its
        # own kv_cap.  With a refill the guard runs after the drained rows
        # are retired and re-admitted below.
        pg.check_steps(steps)
    if temperature > 0.0 and state.key is None:
        raise ValueError(
            "stochastic decoding (temperature > 0) requires an explicit "
            "rng key — the old PRNGKey(0) fallback made every call sample "
            "the identical key stream")
    # scopelint: allow[serve-time-nondeterminism] -- greedy placeholder: temperature > 0 without a carried key raises above, so this key is never sampled from
    key = state.key if state.key is not None else jax.random.PRNGKey(0)
    if refill is None:
        if pg is not None:
            pg.ensure(steps)
            gen, dec, last, caches, done, key, stats = _paged_scan_decode(
                params, cfg, state.last_logits, state.caches, key, steps,
                float(temperature), bool(stop_at_eos), pg.spec,
                pg.device_table(), state.positions, state.done)
        else:
            gen, dec, last, caches, done, key, stats = _scan_decode(
                params, cfg, state.last_logits, state.caches, key, steps,
                float(temperature), bool(stop_at_eos), state.positions,
                state.done)
        positions = state.positions
        used = state.used
    else:
        mask, prompts, lens = refill
        mask = np.asarray(mask, bool).reshape(-1)
        prompts = np.asarray(prompts, np.int32)
        b = state.batch
        if mask.shape != (b,) or prompts.ndim != 2 or prompts.shape[0] != b:
            raise ValueError(
                f"refill mask/prompts must be ({b},)/({b}, W), got "
                f"{mask.shape}/{prompts.shape}")
        width = prompts.shape[1]
        lens = (np.full((b,), width, np.int64) if lens is None
                else np.asarray(lens, np.int64).reshape(-1))
        if lens.shape != (b,):
            raise ValueError(f"prompt_lens shape {lens.shape} != ({b},)")
        if not mask.any():
            raise ValueError("refill mask selects no rows — pass "
                             "refill=None for a plain segment")
        mlens = lens[mask]
        if mlens.min() < 1 or mlens.max() > width:
            raise ValueError(
                f"prompt_lens must lie in [1, {width}], got "
                f"[{mlens.min()}, {mlens.max()}]")
        if mlens.max() >= state.max_len or width > state.max_len:
            raise ValueError(
                f"refill prompt of {mlens.max()} tokens (padded to {width}) "
                f"leaves no decode room in a {state.max_len}-slot cache")
        lens = np.where(mask, lens, 1)      # filler rows: any valid length
        # prefill the admitted rows only, in the smallest bucket that holds
        # them
        rows = bucket_rows(np.flatnonzero(mask), b, state.row_shards)
        operands = _refill_operands(rows, prompts, lens)
        # host-side admission before the launch: release whatever the
        # refilled slots still hold (no-op if the serve loop retired them
        # at sync), then allocate their prompt pages
        for i in np.flatnonzero(mask):
            if pg.row_preadmitted[i]:
                pg.row_preadmitted[i] = False   # reserved at admit time
            else:
                pg.retire_row(int(i))
                pg.admit_row(int(i), int(lens[i]))
        pg.check_steps(steps)
        npg = _ceil_div(width, pg.page_size)
        ids = jnp.asarray(pg.prompt_page_ids(rows, npg).reshape(-1))
        pg.ensure(steps)
        (gen, dec, last, caches, done, key, positions,
         stats) = _paged_refill_scan_decode(
            params, cfg, state.last_logits, state.caches, key, steps,
            float(temperature), bool(stop_at_eos), pg.spec,
            pg.device_table(), state.positions, state.done, *operands, ids)
        used = max(state.used, int(mlens.max()))
    new = DecodeState(caches, last, positions + steps, done,
                      key if state.key is not None else None,
                      state.max_len, used + steps, paged=pg,
                      prefill_rows=0 if refill is None else len(rows),
                      row_shards=state.row_shards, stats=stats)
    return new, gen, dec


# ---------------------------------------------------------------------------
# One-shot generation (prefill + a single decode segment)
# ---------------------------------------------------------------------------
def generate_async(params, cfg: ModelConfig, prompts, *,
                   max_new_tokens: int = 12, temperature: float = 0.0,
                   rng: Optional[jax.Array] = None, stop_at_eos: bool = True,
                   prompt_lens=None) -> Tuple[jax.Array, jax.Array]:
    """``generate`` without the host sync: returns device arrays so the
    caller can keep assembling the next microbatch while this one decodes
    (double-buffered dispatch blocks only at parse time)."""
    if temperature > 0.0 and rng is None:
        raise ValueError(
            "generate(temperature > 0) requires an explicit rng key — the "
            "old PRNGKey(0) fallback made every stochastic call sample the "
            "identical key stream; pass rng=jax.random.PRNGKey(...) "
            "(greedy decoding stays deterministic without one)")
    state = prefill_state(params, cfg, prompts,
                          max_new_tokens=max_new_tokens,
                          prompt_lens=prompt_lens, rng=rng)
    _, gen, dec = decode_segment(params, cfg, state, max_new_tokens,
                                 temperature=temperature,
                                 stop_at_eos=stop_at_eos)
    return gen, dec


def generate(params, cfg: ModelConfig, prompts: np.ndarray, *,
             max_new_tokens: int = 12, temperature: float = 0.0,
             rng: Optional[jax.Array] = None, stop_at_eos: bool = True,
             prompt_lens=None) -> Tuple[np.ndarray, np.ndarray]:
    """prompts: (b, Lp) int32, right-padded; ``prompt_lens`` (b,) marks
    each row's true length (None = all exactly Lp).  Returns
    (generated (b, T) int32, decision_logits (b, T, 2) float32) where the
    last axis is the (YES, NO) logit pair at each step — the only slice of
    the vocab distribution the estimator reads."""
    gen, dec = generate_async(params, cfg, prompts,
                              max_new_tokens=max_new_tokens,
                              temperature=temperature, rng=rng,
                              stop_at_eos=stop_at_eos,
                              prompt_lens=prompt_lens)
    return np.asarray(gen), np.asarray(dec)
