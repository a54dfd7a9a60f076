"""Host-side paged KV cache bookkeeping for the JAX engine.

The device arrays (``k_pool``/``v_pool``: [L, H_kv, n_pages, page, D_h]) are a
head-major pool of fixed-size pages; a flat token slot
``page_id * page_size + offset`` addresses one token's KV. This module owns
the *maps*: per-sequence page tables, token-slot index computation for
scatter/gather, the sequence-hash chain, and — through
:class:`~dynamo_tpu.llm.kvbm.pool.DeviceBlockPool` — block states
(free/leased/reusable) enabling prefix reuse and tiered offload.

KV events: ``on_block_sealed`` fires when a page fills (router "stored"
event); ``on_blocks_removed`` fires when a sealed block is *evicted* from
the device pool (router "removed" event) — NOT on sequence release, because
released blocks stay matchable until evicted. ``on_block_evicted`` runs
first so the engine can offload the page to the host tier.

Reference capability: the engine-side half of the KV block manager
(lib/llm/src/kv/manager.rs:22-138 prepare_prefill_sequence, vllm patch block
manager hooks, event_manager.py stored/removed semantics).
"""

from __future__ import annotations

import math

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..llm.kvbm.pool import DeviceBlockPool, OutOfBlocks
from ..llm.tokens import (TokenSequence, chain_hash, hash_tokens,
                          lora_chain_root)


class OutOfPages(RuntimeError):
    pass


@dataclass(frozen=True)
class CacheKind:
    """What the layers of one kind keep for a token, and how it pages: the
    one description the engine sizes its pools by, the byte-honest planes
    price in and the gauges report (ROADMAP Design 2). A model of one law
    has one kind, ``global``; a per-kind model (``LlamaConfig.per_kind``)
    has ``global`` (its full layers: every token of the context) and
    ``window`` (its window layers: the last ``window`` tokens, in a page
    pool and page tables of their own, :class:`WindowPages`); a model with
    state-space layers has ``global`` (its attention layers) and ``state``:
    what those layers keep per LANE, whatever the lane's tokens — nothing
    pages it, nothing hashes it, and ``token_bytes`` of it is 0; a model
    with gated short-convolution layers has the same two, its ``state`` a
    convolution tail ALONE (no recurrent state: one pool, not two). A model
    with latent attention has ONE kind, ``global``, whose token is one row
    for all heads (``latent``): the rotated shared key (``k_dim``, stored
    zero-padded to a lane tile in the K pool) and the compressed vector
    every head's K and V are expanded from (``v_dim``, the V pool), under
    one "head". It is per token, hashed and sealed like any K/V page: prefix
    match and adoption stay on."""

    name: str                   # "global" | "window" | "state"
    layers: int
    kv_heads: int
    k_dim: int                  # a K row as the model defines it
    v_dim: int
    k_store: int                # ... and as the pool stores it
    window: Optional[int]       # keys a query sees, its own among them
    index_dim: int = 0          # an indexer's keys on the same pages
    fold: int = 1               # tokens stored to a pool row (kv_fold)
    latent: bool = False        # one row for all heads (latent attention)
    # per lane and layer (the ``state`` kind): the recurrent state's shape
    # (float32; None where the kind keeps a tail alone) and the convolution
    # tail's (the model's dtype)
    state: Optional[Tuple[Optional[Tuple[int, ...]], Tuple[int, ...]]] = None

    def token_bytes(self, itemsize: int, stored: bool = False) -> int:
        """Bytes a token holds in this kind, all of its layers: K and V as
        the model defines them, or (``stored``) as the pool stores them;
        an indexer's keys beside them."""
        k = self.k_store if stored else self.k_dim
        return self.layers * itemsize * (
            self.kv_heads * (k + self.v_dim) + self.index_dim)

    def pool_shapes(self, num_pages: int, page: int):
        """-> (K pool shape, V pool shape): [layers of the kind, heads,
        pages, page, row], head-major, as every program reads and writes
        them (models/llama.py "KV pool access")."""
        lead = (self.layers, self.kv_heads, num_pages, page // self.fold)
        return ((*lead, self.fold * self.k_store),
                (*lead, self.fold * self.v_dim))

    def label(self) -> str:
        """The kind as ``dyn_engine_info{cache_kinds}`` names it: layers x
        heads x (K + V) a token, or what a lane keeps in a state kind."""
        if self.latent:
            return (f"{self.name}:{self.layers}x(latent {self.v_dim}"
                    f"+rope {self.k_dim})")
        if self.state is None:
            return (f"{self.name}:{self.layers}x{self.kv_heads}x"
                    f"({self.k_dim}+{self.v_dim})")
        s, c = self.state
        rec = "" if s is None else "x".join(map(str, s)) + "f32+"
        return (f"{self.name}:{self.layers}x(" + rec
                + "x".join(map(str, c)) + ")")

    def lane_bytes(self, itemsize: int) -> int:
        """Bytes a LANE holds in this kind, all of its layers, whatever its
        tokens: the ``state`` kind's recurrent state (where it has one) and
        convolution tail; 0 for a kind that keeps K and V per token."""
        if self.state is None:
            return 0
        s, c = self.state
        return self.layers * ((4 * math.prod(s) if s is not None else 0)
                              + itemsize * math.prod(c))

    def state_shapes(self, lanes: int):
        """-> the shapes of the pools this kind HAS, [layers of the kind,
        lanes, ...] each: (state pool, convolution-tail pool), or (tail
        pool,) of a kind that keeps a tail alone."""
        return tuple((self.layers, lanes, *part) for part in self.state
                     if part is not None)


def cache_kinds(m) -> Tuple[CacheKind, ...]:
    """The cache kinds of model ``m`` (a ``LlamaConfig``), global first."""
    if m.has_latent:
        return (CacheKind("global", m.num_layers, 1, m.qk_rope_dim,
                          m.kv_lora_rank, m.latent_k_store, None,
                          latent=True),)
    if not m.per_kind:
        return (CacheKind("global", m.num_layers, m.num_kv_heads, m.head_dim,
                          m.v_dim, m.k_store_dim, None,
                          m.index_head_dim if m.has_indexer else 0,
                          fold=m.kv_fold),)
    if m.has_state:
        if m.has_window:
            raise ValueError("window layers beside state-space layers are "
                             "not implemented")
        if m.has_conv and 2 in m.layer_kinds:
            raise ValueError("state-space layers beside gated short-"
                             "convolution layers are not implemented")
        # (a tail's [taps - 1, channels] as ONE flat row)
        state = ((None, ((m.conv_cache - 1) * m.hidden_size,)) if m.has_conv
                 else ((m.ssm_heads, m.ssm_head_dim, m.ssm_state),
                       ((m.ssm_conv - 1) * m.ssm_conv_dim,)))
        return (CacheKind("global", len(m.kind_layers(False)),
                          m.num_kv_heads, m.head_dim, m.v_dim, m.k_store_dim,
                          None, fold=m.kv_fold),
                CacheKind("state", len(m.state_layers), 0, 0, 0, 0, None,
                          state=state))
    return tuple(
        CacheKind(name, len(m.kind_layers(win)), m.kv_heads_of(win),
                  m.head_dim, m.v_dim, m.k_store_dim,
                  m.sliding_window if win else None)
        for name, win in (("global", False), ("window", True)))


class WindowPages:
    """Host bookkeeping of the WINDOW cache's page pool: a lane holds pages
    only for the tokens some query of its can still see.

    A sequence's pages are a contiguous run of LOGICAL pages (``position //
    page_size``), ``first .. first + len(pages) - 1``: :meth:`ensure` leases
    at the end as the sequence grows, :meth:`release_behind` gives back at
    the front the pages that lie wholly behind ``position - (window - 1)``,
    ``position`` being the first query of a dispatch whose record has been
    fetched (every dispatch enqueued since queries at or past it, so none
    reads them; the engine calls it from its fetches). A lane's page table
    into the pool is as wide as its table into the global pool and indexed
    by the same logical pages; what the lane does not hold names scratch
    page 0, which every program may read (masked) and padded lanes write.
    No hashing, no sealing, no reuse: a window page is never matched,
    offloaded or moved (the engine refuses those features for such a model
    by name)."""

    def __init__(self, num_pages: int, page_size: int, window: int):
        self.num_pages, self.page_size, self.window = (num_pages, page_size,
                                                       window)
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self.seqs: Dict[str, Tuple[int, List[int]]] = {}   # first, pages
        self.released_total = 0

    @staticmethod
    def lane_pages(window: int, chunk: int, page_size: int) -> int:
        """Pages a lane can hold at most while one chunk a time prefills: a
        window behind the chunk and the chunk, and one for the page
        boundary."""
        return -(-(window - 1 + chunk) // page_size) + 1

    @staticmethod
    def chunk_read_pages(window: int, chunk: int, page_size: int) -> int:
        """Pages a prefill program of chunk bucket ``chunk`` reads of the
        window cache: :meth:`lane_pages` of it, rounded so that the short
        context tiles in 128 lanes."""
        n = WindowPages.lane_pages(window, chunk, page_size)
        if n * page_size <= 128:
            return n
        q = math.lcm(page_size, 128) // page_size
        return -(-n // q) * q

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - 1 - len(self._free)

    def tokens_held(self, seq_id: str, num_tokens: int) -> int:
        """Positions < ``num_tokens`` of the sequence that are on a page it
        holds."""
        first, pages = self.seqs.get(seq_id, (0, []))
        return max(0, min(num_tokens, (first + len(pages)) * self.page_size)
                   - first * self.page_size)

    def create(self, seq_id: str) -> None:
        if seq_id in self.seqs:
            raise ValueError(f"sequence {seq_id} already exists")
        self.seqs[seq_id] = (0, [])

    def ensure(self, seq_id: str, total_tokens: int) -> None:
        """Lease pages so that positions < ``total_tokens`` that are not
        behind the sequence's released front have one."""
        first, pages = self.seqs[seq_id]
        need = -(-total_tokens // self.page_size) - first - len(pages)
        if need > len(self._free):
            raise OutOfPages(f"window cache: need {need} pages, "
                             f"{len(self._free)} free")
        for _ in range(need):
            pages.append(self._free.pop())

    def release_behind(self, seq_id: str, position: int) -> int:
        """Give back the pages wholly behind ``position - (window - 1)``;
        returns how many."""
        if seq_id not in self.seqs:
            return 0
        first, pages = self.seqs[seq_id]
        keep_from = max(0, position - (self.window - 1)) // self.page_size
        n = min(max(0, keep_from - first), len(pages))
        if n:
            self._free.extend(pages[:n])
            self.seqs[seq_id] = (first + n, pages[n:])
            self.released_total += n
        return n

    def release(self, seq_id: str) -> None:
        _, pages = self.seqs.pop(seq_id, (0, []))
        self._free.extend(pages)

    # -- index computation for the programs ------------------------------
    def table_row(self, seq_id: str, padded_pages: int) -> np.ndarray:
        first, pages = self.seqs[seq_id]
        row = np.zeros(padded_pages, dtype=np.int32)
        n = max(0, min(len(pages), padded_pages - first))
        row[first:first + n] = pages[:n]
        return row

    def write_slots(self, seq_id: str, start: int, count: int) -> np.ndarray:
        first, pages = self.seqs[seq_id]
        t = np.arange(start, start + count)
        return (np.asarray(pages, np.int32)[t // self.page_size - first]
                * self.page_size + t % self.page_size)

    def read_window(self, seq_id: str, start: int, count: int,
                    n_pages: int
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """What a prefill chunk of ``count`` tokens from ``start`` reads:
        (pages [n_pages], positions and validity of their n_pages x
        page_size slots), from the page that holds position ``start -
        (window - 1)`` on; padding names scratch page 0, invalid."""
        first, pages = self.seqs[seq_id]
        pg = self.page_size
        lo = max(first, max(0, start - (self.window - 1)) // pg)
        ids = np.zeros(n_pages, np.int32)
        have = max(0, min(n_pages, first + len(pages) - lo))
        ids[:have] = pages[lo - first:lo - first + have]
        pos = lo * pg + np.arange(n_pages * pg, dtype=np.int32)
        valid = (pos < start + count) & (pos < (lo + have) * pg)
        return ids, pos, valid


@dataclass
class SeqCache:
    """Per-sequence cache state: owned pages + token count."""

    seq_id: str
    pages: List[int] = field(default_factory=list)
    num_tokens: int = 0
    # chained-hash view of the tokens in cache (block size == page size)
    hashes: Optional[TokenSequence] = None


class PagePool:
    """Sequence bookkeeping over a :class:`DeviceBlockPool`.

    Page 0 is reserved as the scratch page: masked/inactive lanes write there
    so every jit step has fully static shapes with no host branching.
    """

    def __init__(self, num_pages: int, page_size: int):
        self.num_pages = num_pages
        self.page_size = page_size
        self.blocks = DeviceBlockPool(num_pages)
        self.blocks.on_evict = self._evicted
        self.seqs: Dict[str, SeqCache] = {}
        # hook: (seq_id, sealed TokenBlock, page, lora_id) when a page
        # fills — feeds the KV event publisher ("stored") for the router
        # index; lora_id is the adapter the sequence was created under.
        # add_seal_hook registers ADDITIONAL listeners (the engine's
        # cluster write-through) without displacing this primary slot.
        self.on_block_sealed: Optional[Callable] = None
        self._seal_hooks: List[Callable] = []
        # hook: (seq_hashes: List[int]) when sealed blocks leave the device
        # pool — the router "removed" event
        self.on_blocks_removed: Optional[Callable] = None
        # hook: (seq_hash, page) BEFORE an evicted page is recycled — the
        # engine offloads the page to the host tier here
        self.on_block_evicted: Optional[Callable] = None
        self._removed_buf: List[int] = []

    def add_seal_hook(self, cb: Callable) -> None:
        """Subscribe an extra (seq_id, TokenBlock, page, lora_id) listener
        for newly-registered sealed blocks (fires after on_block_sealed)."""
        self._seal_hooks.append(cb)

    def _fire_sealed(self, seq_id: str, sealed, page: int,
                     lora_id: int) -> None:
        if self.on_block_sealed:
            self.on_block_sealed(seq_id, sealed, page, lora_id)
        for cb in self._seal_hooks:
            cb(seq_id, sealed, page, lora_id)

    def _evicted(self, seq_hash: int, page: int) -> None:
        if self.on_block_evicted:
            self.on_block_evicted(seq_hash, page)
        # buffer removals so a batched eviction (multi-page ensure_pages /
        # extend) publishes ONE removed event, as the reference's event
        # manager batches them, instead of N single-hash events
        self._removed_buf.append(seq_hash)

    def flush_reusable(self) -> int:
        """Evict every reusable (parked) block back to the free list and
        publish their removed events as one batch."""
        n = self.blocks.flush_reusable()
        self._flush_removed()
        return n

    def _flush_removed(self) -> None:
        if self._removed_buf and self.on_blocks_removed:
            buf, self._removed_buf = self._removed_buf, []
            self.on_blocks_removed(buf)
        else:
            self._removed_buf.clear()

    # ------------------------------------------------------------------
    @property
    def free_pages(self) -> int:
        """Pages a new allocation could obtain (free + evictable)."""
        return self.blocks.allocatable

    def pages_needed(self, num_tokens: int) -> int:
        return (num_tokens + self.page_size - 1) // self.page_size

    def can_admit(self, prompt_tokens: int, reserve_pages: int = 0) -> bool:
        return self.free_pages - reserve_pages >= self.pages_needed(prompt_tokens)

    # ------------------------------------------------------------------
    def create(self, seq_id: str, block_hashing: bool = True,
               lora_id: int = 0) -> SeqCache:
        """``lora_id`` salts the block-hash chain so blocks computed under
        different adapters never alias in reuse or in the router index."""
        if seq_id in self.seqs:
            raise ValueError(f"sequence {seq_id} already exists")
        sc = SeqCache(seq_id,
                      hashes=(TokenSequence(self.page_size, lora_id=lora_id)
                              if block_hashing else None))
        self.seqs[seq_id] = sc
        return sc

    def ensure_pages(self, seq_id: str, total_tokens: int) -> None:
        """Pre-allocate pages so the sequence can hold ``total_tokens`` (used
        before a multi-step decode dispatch writes tokens speculatively)."""
        sc = self.seqs[seq_id]
        need = self.pages_needed(total_tokens) - len(sc.pages)
        if need > self.blocks.allocatable:
            raise OutOfPages(
                f"need {need} pages, {self.blocks.allocatable} allocatable")
        for _ in range(need):
            sc.pages.append(self.blocks.lease_new())
        self._flush_removed()

    def account_tokens(self, seq_id: str, tokens: Sequence[int]) -> None:
        """Record tokens as present (pages must already exist); seals
        full-page blocks, registering them for reuse and firing the
        stored-event hook."""
        sc = self.seqs[seq_id]
        if sc.hashes is not None:
            for t in tokens:
                sealed = sc.hashes.append(int(t))
                if sealed is not None:
                    page = sc.pages[len(sc.hashes.blocks) - 1]
                    registered = self.blocks.seal(page, sealed.sequence_hash)
                    # stored events only for newly-registered blocks, so the
                    # router's per-worker refcount balances the single
                    # removed event fired at eviction
                    if registered:
                        self._fire_sealed(sc.seq_id, sealed, page,
                                          sc.hashes.lora_id)
        sc.num_tokens += len(tokens)

    def extend(self, seq_id: str, tokens: Sequence[int]) -> None:
        """Allocate-and-account in one call (prefill path)."""
        sc = self.seqs[seq_id]
        try:
            self.ensure_pages(seq_id, sc.num_tokens + len(tokens))
        except OutOfBlocks as e:
            raise OutOfPages(str(e)) from e
        self.account_tokens(seq_id, tokens)

    def release(self, seq_id: str) -> None:
        """Drop the sequence. Sealed pages park as reusable (still matchable
        by their sequence hash); partial pages return to the free list."""
        sc = self.seqs.pop(seq_id, None)
        if sc is None:
            return
        for page in sc.pages:
            self.blocks.release(page)

    # ------------------------------------------------------------------
    # prefix reuse
    # ------------------------------------------------------------------
    def match_prefix(self, seq_id: str,
                     prompt: Sequence[int], max_tokens: int,
                     host_lookup: Optional[Callable[[int], bool]] = None
                     ) -> Tuple[int, List[Tuple[int, int]]]:
        """Walk the prompt's chained block hashes, claiming matching device
        blocks for a freshly-created sequence. When a device miss occurs and
        ``host_lookup(seq_hash)`` returns True, a fresh page is leased for an
        upload instead (caller copies the data in).

        Returns (tokens_satisfied, uploads) where uploads is
        [(seq_hash, page)] the caller must fill from the host tier.
        """
        sc = self.seqs[seq_id]
        assert sc.num_tokens == 0, "match_prefix on a non-empty sequence"
        page_sz = self.page_size
        # the query chain MUST carry the sequence's lora salt: an unsalted
        # walk would adopt base-model blocks for adapter requests (and
        # never re-match the adapter's own salted blocks)
        parent: Optional[int] = lora_chain_root(
            sc.hashes.lora_id if sc.hashes is not None else 0)
        matched = 0
        uploads: List[Tuple[int, int]] = []
        limit = min(max_tokens, len(prompt))
        for start in range(0, limit - page_sz + 1, page_sz):
            blk = prompt[start:start + page_sz]
            sh = chain_hash(parent, hash_tokens(blk))
            page = self.blocks.match(sh)
            fire_stored = False
            if page is None and host_lookup is not None and host_lookup(sh):
                try:
                    page = self.blocks.lease_new()
                except OutOfBlocks:
                    break
                # host->device restore re-registers a block that fired a
                # removed event at eviction: publish stored again
                fire_stored = self.blocks.seal(page, sh)
                uploads.append((sh, page))
            if page is None:
                break
            self._adopt_block(sc, blk, page, fire_stored)
            parent = sh
            matched += page_sz
        self._flush_removed()
        return matched, uploads

    def probe_prefix(self, prompt: Sequence[int],
                     host_lookup: Optional[Callable[[int], bool]] = None,
                     lora_id: int = 0) -> int:
        """Non-claiming prefix probe: how many leading prompt tokens could be
        served from cache right now (device blocks + host tier). Feeds the
        disagg router's prefix_hit input without touching block states."""
        page_sz = self.page_size
        parent: Optional[int] = lora_chain_root(lora_id)
        n = 0
        for start in range(0, len(prompt) - page_sz + 1, page_sz):
            sh = chain_hash(parent,
                            hash_tokens(prompt[start:start + page_sz]))
            if not (self.blocks.contains(sh)
                    or (host_lookup is not None and host_lookup(sh))):
                break
            parent = sh
            n += page_sz
        return n

    def _adopt_block(self, sc: SeqCache, tokens: Sequence[int],
                     page: int, fire_stored: bool = False) -> None:
        """Attach an already-sealed device block to a fresh sequence.
        ``fire_stored`` is True only for host-tier restores (the block
        re-entered the device pool); plain device matches are already in
        the router index and must not re-fire."""
        sc.pages.append(page)
        sealed = None
        if sc.hashes is not None:
            for t in tokens:
                sealed = sc.hashes.append(int(t))
        sc.num_tokens += len(tokens)
        if fire_stored and sealed is not None:
            self._fire_sealed(sc.seq_id, sealed, page, sc.hashes.lora_id)

    # ------------------------------------------------------------------
    # index computation for the jitted forward
    # ------------------------------------------------------------------
    def write_slots(self, seq_id: str, start_token: int, count: int) -> np.ndarray:
        """Pool token-slot index for tokens [start, start+count) of a seq."""
        sc = self.seqs[seq_id]
        t = np.arange(start_token, start_token + count)
        pages = np.asarray(sc.pages, dtype=np.int32)
        return pages[t // self.page_size] * self.page_size + t % self.page_size

    def page_table_row(self, seq_id: str, padded_pages: int) -> np.ndarray:
        """This sequence's page ids padded (with scratch page 0) to a static
        width — the device-side index base for multi-step decode."""
        sc = self.seqs[seq_id]
        row = np.zeros(padded_pages, dtype=np.int32)
        n = min(len(sc.pages), padded_pages)
        row[:n] = sc.pages[:n]
        return row

    def read_slots(self, seq_id: str, length: int, padded: int
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(slots, positions, valid) arrays of static length ``padded``
        covering tokens [0, length); padding points at scratch page 0."""
        slots = np.zeros(padded, dtype=np.int32)
        pos = np.zeros(padded, dtype=np.int32)
        valid = np.zeros(padded, dtype=bool)
        n = min(length, padded)
        if n:
            slots[:n] = self.write_slots(seq_id, 0, n)
            pos[:n] = np.arange(n)
            valid[:n] = True
        return slots, pos, valid
