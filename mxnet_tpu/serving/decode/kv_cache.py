"""Device-resident paged KV cache with generation-stamped slots and
refcounted shared-prefix pages.

The decode batch's attention state lives on device as page-pool arrays
per cache, each ``(layers, num_pages, page_size, row width)``.  **The
block says which pools** (``block.cache_layout()``: how many, each row's
width, the dtype): :class:`CausalLM` keeps two, ``k_pages`` / ``v_pages``
with a row of ``heads * head_dim`` values; a latent-attention block keeps
ONE whose row is the token's ``(c_kv | k_r)`` shared by every head.  The
allocator, the page tables, the prefix index and copy-on-write know
nothing of what a row holds, nor of how it is stored: that is the page
format's (``kv_format.PageFormat``, built here from the layout and
``kv_dtype`` and handed to the block's programs as ``cache.pages``), which
alone writes rows into the pools and reads them back.  A sequence owns a
*slot* (its identity in the allocator) and a fixed-length page table
(``max_pages_per_seq`` entries, padded with the reserved trash page 0)
mapping logical token positions to physical pages.  Page 0 is never
allocated: padded batch rows and padded prompt positions scatter their K/V
there, so one compiled program per batch bucket serves every batch
composition.

**Slot-generation discipline** (the ShmRing pattern from the input
pipeline, generalized): every slot carries a recycle generation, bumped on
:meth:`free` — exactly the moment the pages may be handed to another
sequence.  A :class:`KVSlot` handle snapshots the generation at
allocation; under ``MXNET_SANITIZE=slots`` each decode-step read checks
the handle against the cache and a post-free read raises
:class:`~mxnet_tpu.analysis.sanitizer.StaleKVSlotError` naming the slot
and its allocation site — instead of silently attending over another
request's context.

**Prefix sharing** (``prefix_sharing=True``, the default): pages are
*refcounted*, and at prefill-commit time the scheduler publishes each
fully-written prompt page under a position-chained content hash
(:meth:`publish`).  A later :meth:`alloc` carrying the prompt tokens
matches the longest published page chain and *acquires* those pages
(refcount bump — a page-table update) instead of allocating + refilling
them; when the entire prompt matches a published entry the cached
last-position logits ride along and admission skips the prefill program
completely.  Shared pages are read-only by construction — generated
tokens land in pages past the shared prefix — and the one genuinely
written boundary page (a prompt's partial tail) is **copied on write**:
the index keeps a private immutable copy and every acquirer gets its own
(:meth:`ensure_writable` is the runtime guard).  Page generations are
stamped alongside slot generations so the slots sanitizer can tell
"my co-holder freed" (fine — refcount still > 0) from "the page really
recycled" (raises).  Published pages are pinned by the index and
reclaimed LRU-first under allocation pressure, so a hot prefix survives
across sessions without ever causing a spurious ``KVCacheExhausted``.

**State that is per sequence** (``cache_layout()``'s ``state`` section: a
state-space layer's recurrent state and convolution tail; a sliding-window
layer's ring of its last ``window`` tokens' K/V, so that one manager holds
two kinds of attention state: pages that grow with the context for the
global layers, a bounded ring a slot for the window layers) lives in *state
pools* behind the page pools, one row a slot (``kv_format.SlotState``,
``cache.pages.state``): the slot allocator owns the rows.  Slot ``i``'s
state row is ``i + 1`` (row 0 is the trash slot) and rides as the last
entry of the slot's table row, so the programs take it where they take the
page tables.  Nothing zeroes a row between owners: the prefill's commit
overwrites a slot's state whole before any step reads it.  The state at a
prefix boundary is not in any page, so such a cache makes no prefix lookup
(``decode.prefix.skipped`` counts the ones it was asked for) and publishes
nothing; its ``layers`` counts the layers that page, which need not be all
of the block's.

Sharding: pass ``mesh`` (+ ``kv_axis``) and the page pools are created
under a ``NamedSharding`` over the row axis (a contiguous split of
``heads * head_dim`` is a split by heads), so the cache scales with
the mesh without changing any scheduler/runtime code (the SNIPPETS.md [1]
GSPMD pattern).  Allocation state is host-side and tiny either way.

Fault site ``decode.kv_alloc`` fires inside :meth:`alloc` — KV exhaustion
under load is injectable like every other subsystem failure
(``MXNET_FAULTS=decode.kv_alloc:fail``).
"""
from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict

import numpy as np

from ...analysis import sanitizer as _san
from ...resilience import faults as _faults
from ...telemetry import bus as _tel
from .kv_format import PageFormat

__all__ = ["PagedKVCache", "KVSlot", "KVCacheExhausted", "pages_needed"]

TRASH_PAGE = 0


def pages_needed(prompt_len, max_new_tokens, page_tokens):
    """Pages a request reserves at admission, of ``page_tokens`` tokens each
    (``cache.page_tokens``: the page's rows, times the tokens a row stands
    for).  Written positions are the prompt (``0..n-1``) plus every
    generated token that is fed back (``n..n+max_new-2`` — the last sampled
    token is returned, never re-encoded), so the reservation covers ``n +
    max_new - 1`` positions."""
    written = int(prompt_len) + max(int(max_new_tokens) - 1, 0)
    return -(-max(written, 1) // int(page_tokens))


class KVCacheExhausted(RuntimeError):
    """Not enough free pages (or slots) to admit a sequence right now.

    The scheduler treats this as backpressure — the request waits for
    evictions — unless the request could never fit, in which case it is
    shed with ``reason="kv_exhausted"``.  ``reclaimable`` counts pages
    pinned only by the shared-prefix index at raise time (already-reclaimed
    pages are in ``free``): a persistently non-zero value under shedding
    means the pool is sized for the prefix cache, not the live load."""

    def __init__(self, need, free, what="pages", reclaimable=0):
        msg = f"KV cache exhausted: need {need} {what}, {free} free"
        if what == "pages":
            msg += (f", {reclaimable} reclaimable from the shared-prefix "
                    f"cache")
        super().__init__(msg)
        self.need = need
        self.free = free
        self.reclaimable = reclaimable


class KVSlot:
    """A sequence's handle on its cache residency: slot id, generation
    stamp, and the fixed-length page table (padded with the trash page).

    With prefix sharing the first ``shared_pages`` entries are refcounted
    pages acquired from the prefix index (read-only for this sequence);
    ``page_gens`` stamps each held page's recycle generation (checked by
    the slots sanitizer), and a full-prompt hit carries ``prefix_logits``
    — the cached last-position logits that let admission skip prefill."""

    __slots__ = ("slot_id", "generation", "pages", "page_table",
                 "shared_pages", "page_gens", "prefix_logits")

    def __init__(self, slot_id, generation, pages, max_pages,
                 shared_pages=0, page_gens=None, state_row=None):
        self.slot_id = slot_id
        self.generation = generation
        self.pages = list(pages)
        # where the cache keeps per-sequence state, the slot's state row
        # ends the table row (kv_format.PageFormat.addresses)
        self.page_table = list(self.pages) + \
            [TRASH_PAGE] * (max_pages - len(self.pages)) + \
            ([int(state_row)] if state_row is not None else [])
        self.shared_pages = int(shared_pages)
        self.page_gens = list(page_gens) if page_gens is not None \
            else [0] * len(self.pages)
        self.prefix_logits = None

    def write_table(self):
        """The commit-program scatter table: shared prefix pages are
        masked to the trash page (their content is already committed and
        read-only), so a partial-hit prefill stores only its own pages."""
        table = list(self.page_table)
        for i in range(self.shared_pages):
            table[i] = TRASH_PAGE
        return table

    def __repr__(self):
        return (f"KVSlot(id={self.slot_id}, gen={self.generation}, "
                f"pages={len(self.pages)}, shared={self.shared_pages})")


class _FullEntry:
    """One published full prompt: the canonical chain pages, an optional
    index-owned immutable copy of the partial tail page, the cached
    last-position logits, and the prompt length."""

    __slots__ = ("pages", "tail", "logits", "prompt_len")

    def __init__(self, pages, tail, logits, prompt_len):
        self.pages = tuple(pages)
        self.tail = tail
        self.logits = logits
        self.prompt_len = prompt_len


class PagedKVCache:
    """Fixed page pool + slot allocator for one decode runtime.

    Parameters
    ----------
    num_layers, num_heads, head_dim : int, optional
        The two-pool K/V geometry of full multi-head attention: pools
        ``k`` and ``v`` with a row of ``num_heads * head_dim`` values.
        Left out when ``layout`` is given.
    layout : dict, optional
        The block's ``cache_layout()``: ``layers``, ``pools`` (``(name,
        row_width, dtype)`` for each value pool — any number, each with
        its own row width and storage dtype), ``quantizable`` (may
        ``kv_dtype`` store them as int8 / fp8 with sidecars) and
        ``shard_heads`` (the head count a ``mesh`` splits the row axis by,
        or None where a row is not a concatenation of heads); optionally
        ``state`` (``layers``, ``arrays``: per-sequence state kept a slot,
        ``kv_format.SlotState``), with ``layers`` then counting only the
        layers that page.
    page_size : int
        Rows per page: tokens per page, but for a block whose layout states
        ``row_tokens`` (``page_tokens`` is then ``page_size * row_tokens``).
    num_pages : int
        Total pages *including* the reserved trash page 0; usable
        capacity is ``num_pages - 1``.
    max_pages_per_seq : int
        Page-table length — fixes the decode step's gathered context at
        ``max_pages_per_seq * page_tokens`` tokens (the model's effective
        context window; constant shape = one program per batch bucket).
    max_slots : int
        Concurrent-sequence bound (the scheduler's max batch bucket).
    dtype : str
        Dtype of the K/V values in the two-pool geometry (a ``layout``
        states its own).
    kv_dtype : str, optional
        The page format (``kv_format``): stored as the layout states by
        default, or quantized with per-row sidecars.
    prefix_sharing : bool
        Refcount + content-hash prompt pages across sequences (default
        on).  Off, :meth:`alloc` ignores ``prompt`` and behaves exactly
        like the unshared allocator.
    prefix_entries : int
        LRU cap on published full-prompt entries.
    mesh : jax Mesh, optional
        When given, page pools are sharded ``NamedSharding(mesh,
        P(None, None, None, kv_axis))`` — the row axis over the model
        axis, whose size must divide ``num_heads`` (a split by heads).
    """

    def __init__(self, num_layers=None, num_heads=None, head_dim=None,
                 page_size=16, num_pages=64, max_pages_per_seq=8,
                 max_slots=16, dtype="float32", kv_dtype=None,
                 prefix_sharing=True, prefix_entries=256, mesh=None,
                 kv_axis="model", layout=None):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is trash)")
        if layout is None:
            if None in (num_layers, num_heads, head_dim):
                raise ValueError("PagedKVCache needs num_layers, num_heads "
                                 "and head_dim, or the block's layout")
            row = int(num_heads) * int(head_dim)
            layout = {"layers": num_layers, "quantizable": True,
                      "shard_heads": int(num_heads),
                      "pools": (("k", row, str(dtype)),
                                ("v", row, str(dtype)))}
        #: the page format: what the pools store, and their only reader
        #: and writer
        self.pages = PageFormat(layout, kv_dtype, page_size)
        self.pool_layout = pools = self.pages.pool_layout
        self.num_layers = self.pages.num_layers
        self.num_heads = layout.get("shard_heads")
        self.head_dim = pools[0][1] // self.num_heads if self.num_heads \
            else None
        self.page_size = int(page_size)
        #: tokens a page stands for: its rows, times the tokens of a row
        #: (1 but for a block whose layout states ``row_tokens``)
        self.page_tokens = self.pages.page_tokens
        self.num_pages = int(num_pages)
        self.max_pages_per_seq = int(max_pages_per_seq)
        self.max_slots = int(max_slots)
        self.context_length = self.max_pages_per_seq * self.page_tokens
        self.dtype = str(dtype)
        #: the per-sequence state pools' format, or None
        self.state = self.pages.state
        #: entries of one row of a program's ``tables``
        self.table_width = self.max_pages_per_seq + (self.state is not None)
        if mesh is not None and self.state is not None:
            raise ValueError(
                "the block keeps per-sequence state in slot pools, which "
                "are not sharded: a mesh is not supported for it")
        if mesh is not None and not self.num_heads:
            raise ValueError(
                f"a mesh splits a pool's row by heads, and a row of the "
                f"block's pools {[n for n, _w, _d in pools]} is shared by "
                f"all heads: a sharded pool of these rows is not supported")
        # recurrent state at a prefix boundary is in no page: a cache with
        # state pools shares nothing, and counts what it was asked for
        self._skips_prefix = bool(prefix_sharing) and self.state is not None
        self.prefix_sharing = bool(prefix_sharing) and self.state is None
        self._prefix_entry_cap = int(prefix_entries)
        arrays = self.pages.new_pools(self.num_pages)
        if self.state is not None:
            arrays += self.state.new_pools(self.max_slots)
        if mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            if self.num_heads % mesh.shape[kv_axis]:
                raise ValueError(
                    f"num_heads={self.num_heads} is not divisible by the "
                    f"mesh's {kv_axis!r} axis ({mesh.shape[kv_axis]})")
            # the value pools split their row axis; the sidecars (one
            # number a token row) are replicated
            sharding = NamedSharding(
                mesh, PartitionSpec(None, None, None, kv_axis))
            rep = NamedSharding(mesh, PartitionSpec())
            arrays = tuple(
                jax.device_put(x, sharding if j < len(pools) else rep)
                for j, x in enumerate(arrays))
        self.mesh = mesh          # the runtime replicates params over it
        self._pools = arrays
        self._copy_fn = None
        self._lock = threading.Lock()
        self._free_pages = list(range(1, self.num_pages))  # 0 = trash
        self._free_slots = list(range(self.max_slots))
        self._gen = [0] * self.max_slots
        self._live = {}          # slot_id -> KVSlot
        # --- refcounted shared-prefix state -------------------------------
        self._slot_refs = [0] * self.num_pages   # live-slot holders
        self._pin_refs = [0] * self.num_pages    # prefix-index holders
        self._page_gen = [0] * self.num_pages    # bumped on recycle
        self._prefix_pages = OrderedDict()       # chain hash -> page (LRU)
        self._page_hash = {}                     # page -> chain hash
        self._chain_parent = {}                  # chain hash -> prev hash
        self._chain_children = {}                # chain hash -> {next hashes}
        self._full_index = OrderedDict()         # prompt hash -> _FullEntry
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_skipped = 0
        self.cow_copies = 0
        self.peak_pages = 0

    # ------------------------------------------------------------ geometry
    @property
    def usable_pages(self):
        return self.num_pages - 1

    # ----------------------------------------- what the format answers
    @property
    def kv_dtype(self):
        return self.pages.kv_dtype

    @property
    def quantized(self):
        return self.pages.quantized

    @property
    def num_sidecars(self):
        return self.pages.num_sidecars

    @property
    def kv_bytes_per_token(self):
        """Device bytes one token position costs across every pool (all
        layers), sidecars included."""
        return self.pages.kv_bytes_per_token

    @property
    def page_bytes(self):
        """Device bytes one page costs (every pool, all layers)."""
        return self.pages.row_bytes * self.page_size

    @property
    def pools(self):
        """Every device pool array the commit/step programs thread
        through (and donate), in the format's order (``kv_format``)."""
        return self._pools

    def set_pools(self, arrays):
        self._pools = tuple(arrays)

    @property
    def k_pages(self):
        """The two-pool geometry's key pool (the first value pool)."""
        return self._pools[0]

    @property
    def v_pages(self):
        return self._pools[1]

    # ------------------------------------------------------------ occupancy
    @property
    def pages_in_use(self):
        """Pages held by live slots (prefix-cache pins are reported
        separately — see :meth:`stats` ``prefix_cached_pages``)."""
        with self._lock:
            return sum(1 for r in self._slot_refs if r > 0)

    @property
    def slots_in_use(self):
        with self._lock:
            return self.max_slots - len(self._free_slots)

    def fits_ever(self, n_pages):
        """Could a reservation of ``n_pages`` EVER be satisfied (empty
        cache)?  False means the request must be shed, not queued.
        Index-pinned pages are reclaimable, so they never shrink this."""
        return n_pages <= self.usable_pages

    def reclaimable_pages(self):
        """Pages held only by the shared-prefix index (no live slot) —
        what allocation pressure can reclaim right now."""
        with self._lock:
            return self._reclaimable_locked()

    def _reclaimable_locked(self):
        return sum(1 for p in range(1, self.num_pages)
                   if self._pin_refs[p] > 0 and self._slot_refs[p] == 0)

    # ------------------------------------------------------------- hashing
    def _page_hashes(self, prompt):
        """Position-chained content hashes of the prompt's *full* pages:
        ``h_i = H(h_{i-1} || tokens_of_page_i)`` — equal hashes mean equal
        tokens at equal positions, which (row-stable math) means bitwise
        equal committed K/V."""
        ps = self.page_tokens
        out, h = [], b"kv-chain-0"
        for i in range(len(prompt) // ps):
            h = hashlib.sha1(
                h + prompt[i * ps:(i + 1) * ps].tobytes()).digest()
            out.append(h)
        return out

    @staticmethod
    def _full_hash(prompt):
        return hashlib.sha1(
            b"kv-full" + np.int64(prompt.size).tobytes()
            + prompt.tobytes()).digest()

    # ------------------------------------------------------------ allocator
    def alloc(self, n_pages, prompt=None, site="decode.kv_alloc"):
        """Reserve ``n_pages`` + a slot; returns a generation-stamped
        :class:`KVSlot`.

        With ``prompt`` (int32 token array) and prefix sharing on, the
        published page chains are consulted first: matched pages are
        acquired by refcount instead of allocated, and a full-prompt match
        additionally hands back cached last-position logits
        (``slot.prefix_logits``) plus a private copy of the prompt's
        partial tail page — admission without a prefill.  Raises
        :class:`KVCacheExhausted` when the pool can't satisfy the
        reservation *right now*, after reclaiming LRU index-pinned pages
        (injectable: ``MXNET_FAULTS=decode.kv_alloc:fail``)."""
        if _faults.active:
            _faults.check("decode.kv_alloc")
        n_pages = int(n_pages)
        if n_pages > self.max_pages_per_seq:
            raise ValueError(
                f"{n_pages} pages exceed max_pages_per_seq="
                f"{self.max_pages_per_seq} (context "
                f"{self.context_length} tokens)")
        use_prefix = (self.prefix_sharing and prompt is not None)
        if use_prefix:
            prompt = np.ascontiguousarray(np.asarray(prompt, "int32"))
        tail_copy = None           # (src_page, dst_page) pending device copy
        with self._lock:
            if not self._free_slots:
                raise KVCacheExhausted(1, 0, what="slots")
            shared, entry, fh = [], None, None
            if use_prefix:
                fh = self._full_hash(prompt)
                entry = self._full_index.get(fh)
                if entry is not None:
                    self._full_index.move_to_end(fh)
                    shared = list(entry.pages)
                    for p in shared:
                        h = self._page_hash.get(p)
                        if h is not None:
                            self._prefix_pages.move_to_end(h)
                else:
                    for h in self._page_hashes(prompt):
                        p = self._prefix_pages.get(h)
                        if p is None:
                            break
                        self._prefix_pages.move_to_end(h)
                        shared.append(p)
            # acquire the matched pages BEFORE any reclaim: with
            # slot_refs still 0 the reclaimer could evict exactly the
            # pages just matched and re-issue them as writable fresh
            # pages, aliasing the shared prefix
            for p in shared:
                self._slot_refs[p] += 1
            tail_src = None
            n_fresh = n_pages - len(shared)
            if entry is not None and entry.tail is not None:
                n_fresh = max(n_fresh, 1)   # room for the private tail copy
                # keep-alive ref on the index's tail page: holds it
                # through reclaim and the device copy below (dropped
                # once the copy lands)
                tail_src = entry.tail
                self._slot_refs[tail_src] += 1
            if n_fresh > len(self._free_pages):
                self._reclaim_locked(
                    n_fresh, keep=(fh,) if entry is not None else ())
            if n_fresh > len(self._free_pages):
                # roll back the acquisitions (releasing any page whose
                # index pin was reclaimed above) before reporting
                for p in shared:
                    self._drop_slot_ref_locked(p)
                if tail_src is not None:
                    self._drop_slot_ref_locked(tail_src)
                # not a hit/miss lookup: the scheduler retries this alloc
                # at every boundary until pages free up, and counting each
                # retry would skew prefix_hit_rate
                raise KVCacheExhausted(
                    n_pages, len(self._free_pages),
                    reclaimable=self._reclaimable_locked())
            slot_id = self._free_slots.pop()
            fresh = [self._free_pages.pop() for _ in range(n_fresh)]
            pages = list(shared) + fresh
            if tail_src is not None:
                # the entry's tail page is the index's immutable copy —
                # give this sequence its own (copy-on-write at admission:
                # its first generated token writes into this page)
                tail_copy = (tail_src, fresh[0])
            for p in fresh:
                self._slot_refs[p] += 1
            slot = KVSlot(slot_id, self._gen[slot_id], pages,
                          self.max_pages_per_seq,
                          shared_pages=len(shared),
                          page_gens=[self._page_gen[p] for p in pages],
                          state_row=slot_id + 1 if self.state is not None
                          else None)
            if entry is not None:
                slot.prefix_logits = entry.logits
            self._live[slot_id] = slot
            if use_prefix:
                self._count_lookup_locked(bool(shared))
            elif self._skips_prefix and prompt is not None:
                self.prefix_skipped += 1
                if _tel.enabled:
                    _tel.count("decode.prefix.skipped")
            in_use = self.num_pages - 1 - len(self._free_pages)
            self.peak_pages = max(self.peak_pages, in_use)
        if tail_copy is not None:
            self._copy_page(*tail_copy)
            with self._lock:
                # drop the temporary keep-alive ref on the source page
                self._drop_slot_ref_locked(tail_copy[0])
        if _san.slots:
            _san.register_kv_slot(self, slot_id, site)
        self._gauge(in_use)
        return slot

    def _count_lookup_locked(self, hit):
        if hit:
            self.prefix_hits += 1
        else:
            self.prefix_misses += 1
        if _tel.enabled:
            _tel.count("decode.prefix_hits" if hit
                       else "decode.prefix_misses")
            _tel.gauge("decode.prefix_hit_rate", round(
                self.prefix_hits
                / (self.prefix_hits + self.prefix_misses), 4))

    def free(self, slot):
        """Drop a slot's references.  Bumps the slot generation FIRST —
        any handle stamped with the old generation is stale from this
        point on (a later read raises under ``MXNET_SANITIZE=slots``).
        A page returns to the pool — and its page generation bumps — only
        when its LAST holder (slot or prefix-index pin) lets go, so
        freeing one session of a shared prefix never invalidates the
        survivors.  Double-frees raise instead of corrupting the
        refcounts."""
        with self._lock:
            live = self._live.get(slot.slot_id)
            if live is not slot or self._gen[slot.slot_id] != slot.generation:
                raise ValueError(
                    f"double/foreign free of {slot!r} (current generation "
                    f"{self._gen[slot.slot_id]})")
            self._gen[slot.slot_id] += 1
            del self._live[slot.slot_id]
            for p in slot.pages:
                self._drop_slot_ref_locked(p)
            self._free_slots.append(slot.slot_id)
            in_use = self.num_pages - 1 - len(self._free_pages)
        self._gauge(in_use)

    def _release_locked(self, page):
        """A page's last holder let go: recycle it (generation bump =
        the slots sanitizer's page-level poison)."""
        self._free_pages.append(page)
        self._page_gen[page] += 1

    def _drop_slot_ref_locked(self, page):
        self._slot_refs[page] -= 1
        if self._slot_refs[page] == 0 and self._pin_refs[page] == 0:
            self._release_locked(page)

    # ------------------------------------------------------- prefix index
    def publish(self, slot, prompt, logits_row=None):
        """Publish a freshly committed prompt's pages for sharing.

        Every fully-written prompt page not already in the index is
        pinned under its chain hash; with ``logits_row`` (the prompt's
        last-position logits) a full-prompt entry is added so an exact
        repeat skips prefill entirely.  A partial tail page is *copied*
        into an index-owned page first (the live sequence keeps writing
        its own tail — the index copy stays immutable), skipped silently
        when no free page is available."""
        if not self.prefix_sharing:
            return
        prompt = np.ascontiguousarray(np.asarray(prompt, "int32"))
        tail_copy = None
        with self._lock:
            hashes = self._page_hashes(prompt)
            chain, prev = [], None
            for i, h in enumerate(hashes):
                p = self._prefix_pages.get(h)
                if p is None:
                    p = slot.page_table[i]
                    if p == TRASH_PAGE:
                        return           # foreign slot shape; nothing to do
                    self._prefix_pages[h] = p
                    self._page_hash[p] = h
                    self._pin_refs[p] += 1
                    # chain links let eviction unpublish whole suffixes
                    # (h encodes its predecessor, so the parent of a
                    # published hash is the same across prompts)
                    self._chain_parent[h] = prev
                    if prev is not None:
                        self._chain_children.setdefault(prev, set()).add(h)
                chain.append(p)
                prev = h
            fh = self._full_hash(prompt)
            if logits_row is None or fh in self._full_index:
                self._gauge_prefix_locked()
                return
            tail = None
            if prompt.size % self.page_tokens:
                if not self._free_pages:
                    self._reclaim_locked(1)
                if not self._free_pages:
                    self._gauge_prefix_locked()
                    return               # no room for the tail copy: skip
                tail = self._free_pages.pop()
                tail_copy = (slot.page_table[len(hashes)], tail)
            entry = _FullEntry(chain, tail,
                               np.array(logits_row, "float32", copy=True),
                               prompt.size)
            self._full_index[fh] = entry
            for p in entry.pages:
                self._pin_refs[p] += 1
            if tail is not None:
                self._pin_refs[tail] += 1
            while len(self._full_index) > self._prefix_entry_cap:
                h, e = next(iter(self._full_index.items()))
                self._drop_full_locked(h)
            self._gauge_prefix_locked()
        if tail_copy is not None:
            self._copy_page(*tail_copy)

    def _drop_full_locked(self, fh):
        entry = self._full_index.pop(fh)
        for p in entry.pages:
            self._unpin_locked(p)
        if entry.tail is not None:
            self._unpin_locked(entry.tail)

    def _unpublish_page_locked(self, h):
        # unpublish the suffix first: links past ``h`` could never match
        # again once ``h`` is gone (alloc stops at the first missing
        # link), so leaving them pinned would just strand pages
        for child in list(self._chain_children.get(h, ())):
            if child in self._prefix_pages:
                self._unpublish_page_locked(child)
        self._chain_children.pop(h, None)
        parent = self._chain_parent.pop(h, None)
        if parent is not None:
            kids = self._chain_children.get(parent)
            if kids is not None:
                kids.discard(h)
                if not kids:
                    del self._chain_children[parent]
        page = self._prefix_pages.pop(h)
        del self._page_hash[page]
        # a broken chain invalidates every full entry that rides it
        for fh in [fh for fh, e in self._full_index.items()
                   if page in e.pages]:
            self._drop_full_locked(fh)
        self._unpin_locked(page)

    def _unpin_locked(self, page):
        self._pin_refs[page] -= 1
        if self._pin_refs[page] == 0 and self._slot_refs[page] == 0:
            self._release_locked(page)

    def _reclaim_locked(self, need_free, keep=()):
        """Evict LRU index state until ``need_free`` pages are free (or
        nothing reclaimable remains): full entries first (their private
        tail copies are pure cache), then whole published chains.
        ``keep`` full-entry hashes are exempt — the entry an in-flight
        alloc just matched must not be reclaimed out from under it.
        Unpublishing a chain link takes its whole suffix with it, so the
        surviving index state stays matchable."""
        for fh in list(self._full_index):
            if len(self._free_pages) >= need_free:
                break
            if fh in keep:
                continue
            self._drop_full_locked(fh)
        for h in list(self._prefix_pages):
            if len(self._free_pages) >= need_free:
                break
            if h not in self._prefix_pages:
                continue    # already gone as part of an earlier suffix
            if self._slot_refs[self._prefix_pages[h]] == 0:
                self._unpublish_page_locked(h)

    def drop_prefix_cache(self):
        """Unpublish everything: every index-only page returns to the
        pool (live slots keep theirs until freed).  The ops "drop
        caches" lever, and how tests separate a leak from a pin."""
        with self._lock:
            for fh in list(self._full_index):
                self._drop_full_locked(fh)
            for h in list(self._prefix_pages):
                if h in self._prefix_pages:
                    self._unpublish_page_locked(h)
            in_use = self.num_pages - 1 - len(self._free_pages)
            self._gauge_prefix_locked()
        self._gauge(in_use)

    def _gauge_prefix_locked(self):
        if _tel.enabled:
            _tel.gauge("decode.kv_cached_pages",
                       sum(1 for p in range(1, self.num_pages)
                           if self._pin_refs[p] > 0))

    # ------------------------------------------------------- copy-on-write
    def ensure_writable(self, slot, page_idx):
        """Guarantee the slot exclusively owns the page it is about to
        write (``page_idx`` in its table): a shared or index-pinned page
        is replaced by a private copy first — THE copy-on-write trigger.
        By construction admission already privatized every write-path
        page, so this is a cheap per-step guard (two refcount reads)."""
        if not self.prefix_sharing or page_idx >= len(slot.pages):
            return
        page = slot.pages[page_idx]
        with self._lock:
            if self._slot_refs[page] <= 1 and self._pin_refs[page] == 0:
                return
            if not self._free_pages:
                self._reclaim_locked(1)
            if not self._free_pages:
                raise KVCacheExhausted(
                    1, 0, reclaimable=self._reclaimable_locked())
            fresh = self._free_pages.pop()
            self._slot_refs[fresh] += 1
            slot.pages[page_idx] = fresh
            slot.page_table[page_idx] = fresh
            slot.page_gens[page_idx] = self._page_gen[fresh]
            if page_idx < slot.shared_pages:
                slot.shared_pages = page_idx
        self._copy_page(page, fresh)
        with self._lock:
            # the slot's ref on the old page is dropped only AFTER the
            # device copy: releasing it inside the lock above would let
            # a concurrent reclaim recycle the copy's source page
            self._drop_slot_ref_locked(page)

    def _copy_page(self, src, dst):
        """One jitted donated program copies page ``src`` onto ``dst``
        across every pool (values and sidecars) — physical page ids
        are traced scalars, so every CoW event replays one executable."""
        import jax
        if self._copy_fn is None:
            n = len(self.pools)

            def copy(src_, dst_, *pools):
                return tuple(p.at[:, dst_].set(p[:, src_]) for p in pools)

            self._copy_fn = jax.jit(
                copy, donate_argnums=tuple(range(2, 2 + n)))
        pools = self.pools
        new = self._copy_fn(np.int32(src), np.int32(dst), *pools)
        if _san.donation:
            _san.poison(list(pools), "decode.kv_cow")
        self.set_pools(new)
        self.cow_copies += 1
        if _tel.enabled:
            _tel.count("decode.kv_cow_copies")

    def warm_programs(self):
        """Compile the CoW copy program before traffic (trash -> trash:
        no allocated page is touched) — the same eager-warming discipline
        as the runtime's commit/step programs."""
        self._copy_page(TRASH_PAGE, TRASH_PAGE)
        self.cow_copies -= 1         # warming is not a CoW event

    # ------------------------------------------------------------ sanitizer
    def generation(self, slot_id):
        """Current recycle generation of a slot (the sanitizer's stale
        check compares a handle's stamp against this)."""
        with self._lock:
            return self._gen[slot_id]

    def page_generation(self, page):
        """Current recycle generation of a physical page — bumped only
        when the page's last holder (slot or index pin) releases it."""
        with self._lock:
            return self._page_gen[page]

    def check_slot(self, slot):
        """``MXNET_SANITIZE=slots`` read fence for the decode step: raises
        ``StaleKVSlotError`` when ``slot`` was freed, or when any page it
        references recycled out from under it (refcount discipline: a
        co-holder freeing is fine; the LAST free poisons).  Callers guard
        on ``sanitizer.slots`` — idle cost is one attribute read."""
        _san.check_kv_slot(self, slot.slot_id, slot.generation)
        _san.check_kv_pages(self, slot)

    def _gauge(self, in_use):
        if _tel.enabled:
            _tel.gauge("decode.kv_occupancy",
                       round(in_use / max(self.usable_pages, 1), 4))
            _tel.gauge("decode.kv_pages", in_use)
            _tel.gauge("decode.kv_bytes_per_token", self.kv_bytes_per_token)
            if self.state is not None:
                _tel.gauge("decode.state_slots_live", len(self._live))
                _tel.gauge("decode.state_bytes", self.state_bytes)

    @property
    def page_pool_bytes(self):
        """Device bytes of the page pools (every page, the trash page too):
        the kind of attention state that grows with the context."""
        return self.page_bytes * self.num_pages

    @property
    def state_bytes(self):
        """Device bytes of the state pools (every slot and the trash row);
        0 where the block keeps no per-sequence state."""
        return 0 if self.state is None else \
            self.state.bytes_per_slot * (self.max_slots + 1)

    def stats(self):
        with self._lock:
            slot_pages = sum(1 for r in self._slot_refs if r > 0)
            pinned = sum(1 for p in range(1, self.num_pages)
                         if self._pin_refs[p] > 0)
            lookups = self.prefix_hits + self.prefix_misses
            state = {} if self.state is None else {
                "state_slots_live": len(self._live),
                "state_bytes": self.state_bytes,
                "prefix_skipped": self.prefix_skipped}
            return {
                **state,
                "pages_in_use": slot_pages,
                "usable_pages": self.usable_pages,
                "page_pool_bytes": self.page_pool_bytes,
                "slots_in_use": self.max_slots - len(self._free_slots),
                "max_slots": self.max_slots,
                "peak_pages": self.peak_pages,
                "kv_dtype": self.kv_dtype,
                "kv_bytes_per_token": self.kv_bytes_per_token,
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "prefix_hit_rate": round(self.prefix_hits / lookups, 4)
                if lookups else 0.0,
                "prefix_cached_pages": pinned,
                "reclaimable_pages": self._reclaimable_locked(),
                "shared_pages": sum(
                    1 for p in range(1, self.num_pages)
                    if self._slot_refs[p] > 1
                    or (self._slot_refs[p] and self._pin_refs[p])),
                "cow_copies": self.cow_copies,
            }
