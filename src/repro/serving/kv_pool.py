"""Block-paged KV-cache pool: fixed-size pages, per-row page tables,
free-list allocation (no page sharing in v1).  It is the one layout a
continuous-batching decode state has.

KV storage is a flat array of ``n_pages`` fixed-size pages plus one
**trash page**, and each decode row owns a page table mapping its logical
page index to a physical page.  Pages are allocated on demand as
positions advance (prompt pages at admission, decode pages per segment)
and released when the row retires at EOS/parse, so a drained slot's
memory is immediately reusable by the next queued prompt — slot admission
checks free pages.  ``worst_case_pool`` sizes a pool for a slot batch.

Layout per attention layer-stack cache leaf:

  dense  k/v    : (count, b, hkv, S, hd)              S = max_len slots
  paged  k/v    : (count, n_pages + 1, hkv, page, hd) physical pages
  dense  latent : (count, b, S, r)                    MLA c_kv / k_rope
  paged  latent : (count, n_pages + 1, page, r)       r = kv_lora_rank or
                                                      qk_rope_head_dim

GQA K/V pages and MLA latent pages live side by side, each layer stack in
its own layout, under one page id space.

A *page id* spans **all** layers: allocating page p grants the row
``page_size`` token slots in every layer's storage at physical index p.
Physical index ``n_pages`` is the trash page: unallocated table entries
and retired rows point there, so done rows keep scatter-decoding PAD
harmlessly (their writes land in trash, their reads are masked or
discarded) — exactly mirroring the dense path's discarded free-slot rows.

Deadlock freedom: ``admit_row`` *reserves* the row's worst-case page count
up front (``ceil(min(len + budget, kv_cap) / page)``) and draws the
physical pages down from that reservation as decode advances, so a row
admitted is a row that can always finish — mid-decode allocation can
never fail.  ``available()`` is what is left for *new* admissions.

The pool itself is host-side accounting (free list, reservations, page
counters); the device storage lives in the ``DecodeState`` it backs, like
the dense caches.  ``PagedKV`` is the per-state attachment pairing the
pool with one decode batch's page tables.
"""
from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional

import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.decode_attention import KernelType


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def kv_bytes_per_token(cfg: ModelConfig) -> int:
    """Cache bytes one token costs across every attention layer: K and V
    of a GQA layer, the latent (``kv_lora_rank + qk_rope_head_dim``) of an
    MLA layer."""
    from repro.models import transformer as tf
    from repro.models.common import dtype_of

    itemsize = np.dtype(dtype_of(cfg.dtype)).itemsize
    gqa = 2 * cfg.num_kv_heads * cfg.resolved_head_dim * itemsize
    mla = (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * itemsize
    return sum(mla if tf._is_mla(k) else gqa
               for k in cfg.layer_kinds() if tf._is_attn(k) or tf._is_mla(k))


def check_paged_support(cfg: ModelConfig) -> None:
    """Paged KV covers full-window attention stacks: GQA (K/V pages) and
    MLA (latent pages).

    SSM/conv states and encoder cross caches have no paged layout, and
    windowed ring buffers already cap their own memory at O(window).
    Loud failure beats silently decoding from the wrong cache lines.
    """
    from repro.models import transformer as tf
    from repro.models.attention import resolve_window

    if cfg.is_encoder_decoder:
        raise ValueError(
            f"paged KV requires a decoder-only model: {cfg.name!r} carries "
            "encoder cross caches")
    for kind in cfg.layer_kinds():
        kk = "attn" if kind == "shared_attn" else kind
        if not (tf._is_attn(kk) or tf._is_mla(kk)):
            raise ValueError(
                "paged KV requires an attention-only backbone: "
                f"{cfg.name!r} has a {kind!r} layer (SSM states have no "
                "paged layout)")
        if resolve_window(cfg, kk) > 0:
            raise ValueError(
                "paged KV does not support sliding-window layers: "
                f"{cfg.name!r} layer kind {kind!r} resolves a window — "
                "ring buffers already bound their memory at O(window)")


class PagedSpec(NamedTuple):
    """Static (hashable) half of the paged layout, closed into the jitted
    decode executables; the page table itself is a traced argument."""
    page_size: int
    kv_cap: int                     # per-row logical capacity in tokens
    kernel: KernelType


class KVPool:
    """Free-list page allocator with reservation accounting.

    Host-side only.  ``reserved`` counts pages promised to admitted rows
    but not yet physically allocated; ``available()`` is what a *new*
    admission may claim.  Counters (``pages_in_use``/``pages_peak``/
    ``live_tokens``/``tokens_peak``) are updated at every alloc/free so
    benches read them instead of recomputing occupancy.
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 1 or page_size < 1:
            raise ValueError(
                f"need n_pages >= 1 and page_size >= 1, got "
                f"{n_pages}/{page_size}")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self._free: List[int] = list(range(self.n_pages))
        self.reserved = 0
        self.pages_peak = 0
        self.live_tokens = 0
        self.tokens_peak = 0

    # -- allocation -------------------------------------------------------
    @property
    def trash_page(self) -> int:
        return self.n_pages

    @property
    def pages_in_use(self) -> int:
        return self.n_pages - len(self._free)

    def available(self) -> int:
        """Pages a fresh admission may still reserve."""
        return len(self._free) - self.reserved

    def alloc(self, n: int, *, from_reserved: int = 0) -> List[int]:
        if from_reserved > self.reserved:
            raise RuntimeError(
                f"drawing {from_reserved} pages from a reservation of "
                f"{self.reserved}")
        if n > len(self._free) - (self.reserved - from_reserved):
            raise RuntimeError(
                f"page pool exhausted: want {n}, have {len(self._free)} "
                f"free of which {self.reserved - from_reserved} reserved")
        ids = [self._free.pop() for _ in range(n)]
        self.reserved -= from_reserved
        self.pages_peak = max(self.pages_peak, self.pages_in_use)
        return ids

    def free(self, ids: List[int]) -> None:
        for p in ids:
            if not (0 <= p < self.n_pages):
                raise RuntimeError(f"freeing invalid page id {p}")
            if p in self._free:
                raise RuntimeError(f"double free of page {p}")
        self._free.extend(ids)

    def reserve(self, n: int) -> None:
        if n > self.available():
            raise RuntimeError(
                f"cannot reserve {n} pages, only {self.available()} "
                "available")
        self.reserved += n

    def unreserve(self, n: int) -> None:
        if n > self.reserved:
            raise RuntimeError(
                f"releasing reservation of {n} > {self.reserved}")
        self.reserved -= n

    # -- token accounting -------------------------------------------------
    def add_live_tokens(self, n: int) -> None:
        self.live_tokens += int(n)
        self.tokens_peak = max(self.tokens_peak, self.live_tokens)

    def drop_live_tokens(self, n: int) -> None:
        self.live_tokens -= int(n)

    @property
    def fragmentation(self) -> float:
        """Fraction of in-use page slots not holding a live token
        (tail-of-page internal fragmentation; v1 never shares pages)."""
        cap = self.pages_in_use * self.page_size
        if cap == 0:
            return 0.0
        return max(0.0, cap - self.live_tokens) / cap

    def attach(self, batch: int, *, kv_cap: int, budget_steps: int,
               kernel: KernelType = KernelType.XLA) -> "PagedKV":
        return PagedKV(self, batch, kv_cap=kv_cap,
                       budget_steps=budget_steps, kernel=kernel)


def worst_case_pool(batch: int, width: int, budget_steps: int,
                    page_size: int = 16) -> KVPool:
    """A pool that holds every one of ``batch`` slots at its worst case at
    once — a ``width``-token prompt plus ``budget_steps`` decode steps,
    page-rounded — so admission never waits on pages while a slot is
    free."""
    return KVPool(n_pages=batch * _ceil_div(width + budget_steps, page_size),
                  page_size=page_size)


@dataclasses.dataclass
class PagedKV:
    """One decode batch's page tables over a shared ``KVPool``.

    ``table`` is the host mirror, shape (b, W) int32 with W =
    ceil(kv_cap / page_size); unallocated entries hold the trash page.
    ``row_high[i]`` upper-bounds row i's next write position, advanced per
    segment by ``ensure`` — the paged replacement for the dense
    ``used``/``max_len`` ceiling, per row instead of per batch.
    """
    pool: KVPool
    batch: int
    kv_cap: int
    budget_steps: int
    kernel: KernelType = KernelType.XLA

    def __post_init__(self):
        self.page_size = self.pool.page_size
        self.table_width = _ceil_div(self.kv_cap, self.page_size)
        self.table = np.full((self.batch, self.table_width),
                             self.pool.trash_page, np.int32)
        self.row_pages: List[List[int]] = [[] for _ in range(self.batch)]
        self.row_reserved = [0] * self.batch
        self.row_high = np.zeros((self.batch,), np.int64)
        self.row_live = np.zeros((self.batch,), bool)
        # rows admitted ahead of their refill launch (reservation already
        # taken); ``decode_segment`` consumes the flag instead of
        # re-admitting
        self.row_preadmitted = np.zeros((self.batch,), bool)
        self.spec = PagedSpec(self.page_size, int(self.kv_cap), self.kernel)

    # -- admission --------------------------------------------------------
    def row_need(self, true_len: int) -> int:
        """Worst-case pages a row admitted at ``true_len`` can touch."""
        return _ceil_div(min(true_len + self.budget_steps, self.kv_cap),
                         self.page_size)

    def can_admit(self, true_len: int) -> bool:
        return self.pool.available() >= self.row_need(true_len)

    def admit_row(self, row: int, true_len: int) -> None:
        """Reserve the row's worst case and allocate its prompt pages."""
        if self.row_live[row]:
            raise RuntimeError(f"row {row} already admitted")
        if not (1 <= true_len <= self.kv_cap):
            raise ValueError(
                f"prompt of {true_len} tokens outside [1, {self.kv_cap}]")
        need = self.row_need(true_len)
        if need > self.pool.n_pages:
            raise ValueError(
                f"kv pool of {self.pool.n_pages} pages "
                f"(page_size={self.page_size}) is too small to admit a "
                f"single full-budget row: a {true_len}-token prompt with "
                f"{self.budget_steps} decode steps needs {need} pages — "
                "raise kv_pool_pages or kv_page_size")
        if not self.can_admit(true_len):
            raise RuntimeError(
                f"admission of a {true_len}-token row needs {need} pages, "
                f"pool has {self.pool.available()} — check can_admit first")
        n_prompt = _ceil_div(true_len, self.page_size)
        self.pool.reserve(need)
        ids = self.pool.alloc(n_prompt, from_reserved=n_prompt)
        self.table[row, :n_prompt] = ids
        self.row_pages[row] = list(ids)
        self.row_reserved[row] = need - n_prompt
        self.row_high[row] = true_len
        self.row_live[row] = True
        self.pool.add_live_tokens(true_len)

    def retire_row(self, row: int) -> None:
        """Release a row's pages and reservation; its table entries fall
        back to the trash page so any still-running PAD decode of that slot
        scatters harmlessly.  Must run before the pages are re-admitted —
        the serve loop orders sync (retire) before admit before launch."""
        if not self.row_live[row]:
            return
        self.pool.free(self.row_pages[row])
        self.pool.unreserve(self.row_reserved[row])
        self.pool.drop_live_tokens(int(self.row_high[row]))
        self.table[row, :] = self.pool.trash_page
        self.row_pages[row] = []
        self.row_reserved[row] = 0
        self.row_high[row] = 0
        self.row_live[row] = False
        self.row_preadmitted[row] = False

    def pre_admit(self, row: int, true_len: int) -> None:
        """Retire + admit a row ahead of its refill launch.

        The serve loop admits several rows at one segment boundary before
        any of them launches; taking each row's reservation immediately
        keeps ``can_admit()`` truthful for the admissions that follow.
        ``decode_segment`` consumes ``row_preadmitted`` instead of
        re-admitting."""
        self.retire_row(row)
        self.admit_row(row, true_len)
        self.row_preadmitted[row] = True

    # -- per-segment growth ----------------------------------------------
    def check_steps(self, steps: int) -> None:
        """Per-row capacity guard (replaces the dense used/max_len check):
        every live row must fit ``steps`` more writes under ``kv_cap``."""
        if self.row_live.any():
            high = int(self.row_high[self.row_live].max())
            if high + steps > self.kv_cap:
                raise ValueError(
                    f"segment of {steps} steps overruns a paged row: "
                    f"{high} of {self.kv_cap} token capacity used")

    def starved_rows(self, steps: int) -> List[int]:
        """Live rows whose share of the next ``ensure(steps)`` would raise
        on true pool exhaustion — a dry run of ``ensure``'s allocation
        order with no side effects.

        Within its reserved budget a row can never starve (admission took
        its worst case up front), so this only names rows decoding *past*
        their budget under a drained pool.  The serve runtime fails those
        rows at the segment boundary (pages released, row requeued)
        instead of letting ``ensure`` kill the whole stream.
        """
        free = len(self.pool._free)
        reserved = self.pool.reserved
        out = []
        for row in range(self.batch):
            if not self.row_live[row]:
                continue
            target = min(int(self.row_high[row]) + steps, self.kv_cap)
            need = _ceil_div(target, self.page_size) - len(self.row_pages[row])
            if need <= 0:
                continue
            from_res = min(need, self.row_reserved[row])
            if need > free - (reserved - from_res):
                out.append(row)
                continue
            free -= need
            reserved -= from_res
        return out

    def ensure(self, steps: int) -> None:
        """Allocate the pages ``steps`` more decode writes need and
        advance ``row_high``.

        Pages come from the row's reservation first — a row is
        *guaranteed* its ``budget_steps`` of decode, so within budget this
        can never fail.  A row legally decoded past its own budget (a
        short row under a wide ``kv_cap``, plain ``decode_segment`` use)
        draws best-effort from the unreserved free pool and raises only
        on true exhaustion."""
        for row in range(self.batch):
            if not self.row_live[row]:
                continue
            target = min(int(self.row_high[row]) + steps, self.kv_cap)
            need = _ceil_div(target, self.page_size) - len(self.row_pages[row])
            if need > 0:
                from_res = min(need, self.row_reserved[row])
                ids = self.pool.alloc(need, from_reserved=from_res)
                start = len(self.row_pages[row])
                self.table[row, start:start + need] = ids
                self.row_pages[row].extend(ids)
                self.row_reserved[row] -= from_res
            self.pool.add_live_tokens(target - int(self.row_high[row]))
            self.row_high[row] = target

    # -- device views -----------------------------------------------------
    def device_table(self):
        import jax.numpy as jnp
        return jnp.asarray(self.table)

    def prompt_page_ids(self, rows: np.ndarray, n_pages_row: int
                        ) -> np.ndarray:
        """(len(rows), n_pages_row) scatter destinations for the prompt
        page blocks of slot rows ``rows``: each admitted row's freshly
        allocated prompt pages, the trash page for a filler entry (a row
        >= the batch), so no live row's pages are touched by the
        scatter."""
        rows = np.asarray(rows, np.int64).reshape(-1)
        real = rows < self.batch
        ids = np.full((len(rows), n_pages_row), self.pool.trash_page,
                      np.int32)
        ids[real] = self.table[rows[real], :n_pages_row]
        return ids
