"""Runtime sanitizer — the dynamic half of the analysis suite.

``MXNET_SANITIZE=donation,slots,collectives`` (or :func:`enable` /
:class:`scope`) arms opt-in modes that turn silent corruption into loud,
attributed errors:

- **donation** — every donated jit call site (aggregated optimizer groups,
  engine segment flushes, ``SPMDTrainer`` steps) *poisons* the buffers it
  donated, recording the site.  Any later read of a poisoned buffer through
  the NDArray read funnel (``_materialize``/op dispatch) raises
  :class:`DonatedBufferError` naming the donation site — instead of the
  backend-dependent behavior (deleted-buffer error on TPU, silent aliasing
  on CPU zero-copy).
- **slots** — ``zero_copy_batches=True`` batches alias shared-memory ring
  slots whose contents are only stable until the slot recycles.  The
  iterator registers each staged buffer with its slot *generation*; the
  ring bumps the generation on ``release``.  A read through a stale-
  generation buffer raises :class:`StaleSlotError` naming the slot and
  registration site — instead of returning another batch's pixels.
  The same discipline covers the serving-decode **paged KV cache**: a
  sequence's :class:`~mxnet_tpu.serving.decode.KVSlot` is registered at
  allocation (:func:`register_kv_slot`) and every decode-step read checks
  the handle's generation stamp (:func:`check_kv_slot`) — a step driven
  through a freed slot raises :class:`StaleKVSlotError` naming the slot
  and its allocation site, instead of silently attending over another
  request's context.  With prefix sharing, pages are *refcounted*: a
  page's generation bumps only when its LAST holder (live slot or
  prefix-index pin) releases it, so freeing one session of a shared
  prefix never trips the survivors — :func:`check_kv_pages` compares the
  handle's per-page generation stamps and raises only on a genuinely
  recycled page (last-free poisons; an earlier co-holder free is clean).
- **collectives** — every collective call site (SPMD steps, pipeline/moe
  schedules, the kvstore dist hop, the checkpoint commit barrier) records
  a per-host fingerprint stream; streams are cross-checked at sync points
  (see :mod:`.divergence`) and a mismatch raises
  :class:`CollectiveDivergenceError` naming both hosts' next-op
  fingerprints — instead of the multi-controller pod hanging.  A watchdog
  (:func:`.divergence.sync`) bounds waits on stalled peers with a
  position dump (:class:`CollectiveStallTimeout`).

Cost discipline (same as ``telemetry.bus.enabled`` / ``faults.active``):
instrumented sites guard on the module attributes ``donation`` / ``slots``
/ ``active`` — one attribute read when idle.  When armed, a check is one
dict probe per buffer.  The registries hold strong references to the
poisoned *shells* (the buffer's device memory is already donated/recycled;
the Python object is tiny) so ``id()`` keys can never be reused while an
entry lives; both registries are bounded LRUs.

Telemetry (bus enabled): ``analysis.sanitizer_poisoned`` /
``analysis.sanitizer_slot_views`` counters and an
``analysis.sanitizer_violation`` instant+counter per raise.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict

from ..telemetry import bus as _tel

__all__ = ["SanitizerError", "DonatedBufferError", "StaleSlotError",
           "StaleKVSlotError", "CollectiveDivergenceError",
           "CollectiveStallTimeout",
           "enable", "disable", "configure", "scope", "modes", "active",
           "donation", "slots", "collectives", "poison",
           "register_slot_view", "register_kv_slot", "check_kv_slot",
           "check_kv_pages", "check_kv_write_span", "check_buffer",
           "stats", "reset"]

MODES = ("donation", "slots", "collectives")

# Fast-path flags: hooks do ``if sanitizer.active: sanitizer.check_buffer(b)``
# and sites do ``if sanitizer.donation: sanitizer.poison(...)``.  Mutated
# only under _lock, read without it (single attribute load).
active = False
donation = False
slots = False
collectives = False

_lock = threading.Lock()
_POISON_CAP = 8192
_SLOT_CAP = 1024
_KV_CAP = 4096
_poisoned = OrderedDict()     # id(buf) -> (site, shell)
_slot_views = OrderedDict()   # id(buf) -> (ring, slot_id, generation,
#                                           site, shell)
_kv_slots = OrderedDict()     # (id(cache), slot_id) -> site
_violations = 0


class SanitizerError(RuntimeError):
    """Base class for sanitizer-detected contract violations."""


class DonatedBufferError(SanitizerError):
    """A buffer was read after being donated to a jit call."""

    def __init__(self, site):
        super().__init__(
            f"use-after-donate: this buffer was donated at {site} — its "
            f"device memory has been reused in place.  Rebind the handle "
            f"before the donated call, or keep the value with an explicit "
            f"copy() (MXNET_SANITIZE=donation)")
        self.site = site


class StaleSlotError(SanitizerError):
    """A zero-copy shm-slot view was read after the slot recycled."""

    def __init__(self, site, slot_id):
        super().__init__(
            f"stale shm-slot read: slot {slot_id} (staged at {site}) was "
            f"released back to the ring and may hold another batch's "
            f"data.  Consume zero_copy_batches=True data before the next "
            f"next()/reset(), or drop zero_copy_batches "
            f"(MXNET_SANITIZE=slots)")
        self.site = site
        self.slot_id = slot_id


class StaleKVSlotError(StaleSlotError):
    """A decode step read a paged-KV slot after it was freed — or one of
    the slot's refcounted pages after its last holder released it."""

    def __init__(self, site, slot_id, page=None):
        # bypass StaleSlotError.__init__ (shm-ring wording); keep its type
        # so existing "slots-family violation" handlers catch both
        if page is None:
            msg = (f"stale KV-slot read: slot {slot_id} (allocated at "
                   f"{site}) was freed back to the paged KV cache and its "
                   f"pages may hold another sequence's context.  Stop "
                   f"stepping a sequence after freeing its slot — evict at "
                   f"the step boundary that frees it (MXNET_SANITIZE=slots)")
        else:
            msg = (f"stale KV-page read: page {page} held by slot "
                   f"{slot_id} (allocated at {site}) recycled — its LAST "
                   f"holder (slot or prefix-index pin) released it and it "
                   f"may hold another sequence's context.  A co-holder "
                   f"freeing a shared prefix is fine; this page's refcount "
                   f"reached zero (MXNET_SANITIZE=slots)")
        SanitizerError.__init__(self, msg)
        self.site = site
        self.slot_id = slot_id
        self.page = page


class CollectiveDivergenceError(SanitizerError):
    """Two hosts disagree on which collective comes next.

    On real hardware this is a silent pod-wide hang; under
    ``MXNET_SANITIZE=collectives`` the stream cross-check raises instead,
    naming BOTH hosts' next-op fingerprints at the first diverging
    sequence number."""

    def __init__(self, host_a, fp_a, site_a, host_b, fp_b, site_b, index,
                 point=""):
        at = f" at sync point {point!r}" if point else ""
        super().__init__(
            f"SPMD collective divergence{at}: hosts {host_a} and {host_b} "
            f"disagree on collective #{index} —\n"
            f"  host {host_a} issued: {fp_a} @ {site_a}\n"
            f"  host {host_b} issued: {fp_b} @ {site_b}\n"
            f"on real hardware this mispairing deadlocks the pod; find "
            f"the host-divergent branch/order upstream of the first "
            f"differing op (MXNET_SANITIZE=collectives)")
        self.host_a, self.fp_a, self.site_a = host_a, fp_a, site_a
        self.host_b, self.fp_b, self.site_b = host_b, fp_b, site_b
        self.index = index
        self.point = point
        self.site = point or site_a


class CollectiveStallTimeout(SanitizerError, TimeoutError):
    """The watchdog gave up waiting for peers to reach a sync point.

    The streams agree as far as they go — a peer simply stopped issuing
    collectives (crashed, or deadlocked elsewhere).  The message dumps
    every host's position so the stalled host is named instead of the
    whole pod hanging."""

    def __init__(self, point, waited_s, behind, dump):
        super().__init__(
            f"collective sync point {point!r}: host(s) {behind} did not "
            f"catch up within {waited_s:g}s — every host's position:\n"
            f"{dump}\n(MXNET_SANITIZE=collectives watchdog)")
        self.point = point
        self.behind = list(behind)
        self.site = point


def _refresh_locked(new_modes):
    global active, donation, slots, collectives
    donation = "donation" in new_modes
    slots = "slots" in new_modes
    collectives = "collectives" in new_modes
    active = bool(new_modes)


def _parse(spec):
    if not spec:
        return frozenset()
    norm = spec.strip().lower()
    if norm in ("1", "all", "true", "on", "yes"):
        return frozenset(MODES)
    if norm in ("0", "false", "off", "none", "no"):
        # conventional disable spellings must not crash `import mxnet_tpu`
        # (this parse runs at import when MXNET_SANITIZE is set)
        return frozenset()
    out = set()
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        if item not in MODES:
            raise ValueError(
                f"unknown MXNET_SANITIZE mode {item!r} (have {MODES})")
        out.add(item)
    return frozenset(out)


def modes():
    """Currently armed mode names (frozenset)."""
    return frozenset(m for m, on in (("donation", donation),
                                     ("slots", slots),
                                     ("collectives", collectives)) if on)


def enable(*names):
    """Arm the given modes (default: all).  Additive."""
    new = frozenset(names) if names else frozenset(MODES)
    bad = new - set(MODES)
    if bad:
        raise ValueError(f"unknown sanitizer modes {sorted(bad)}")
    with _lock:
        _refresh_locked(modes() | new)


def disable(*names):
    """Disarm the given modes (default: all).  Registries are kept —
    re-enabling resumes enforcement of already-poisoned buffers."""
    drop = frozenset(names) if names else frozenset(MODES)
    with _lock:
        _refresh_locked(modes() - drop)


def configure(spec):
    """Replace the armed modes from an ``MXNET_SANITIZE`` spec string."""
    new = _parse(spec)
    with _lock:
        _refresh_locked(new)


def reset():
    """Drop every registry entry (test isolation)."""
    global _violations
    with _lock:
        _poisoned.clear()
        _slot_views.clear()
        _kv_slots.clear()
        _violations = 0
    from . import divergence
    divergence.reset()


class scope:
    """Context manager for tests: arm a spec on enter, restore the previous
    modes on exit.  Registry entries persist deliberately — a buffer
    donated inside the scope is still donated after it; call
    :func:`reset` for full test isolation."""

    def __init__(self, spec):
        self._spec = spec
        self._saved = None

    def __enter__(self):
        self._saved = modes()
        configure(self._spec)
        return self

    def __exit__(self, *exc):
        with _lock:
            _refresh_locked(self._saved)
        return False


def stats():
    """Registry sizes + violation count (test/debug surface)."""
    from . import divergence
    n_coll = divergence.total_recorded()
    with _lock:
        return {"poisoned": len(_poisoned), "slot_views": len(_slot_views),
                "kv_slots": len(_kv_slots), "collectives": n_coll,
                "violations": _violations}


# ----------------------------------------------------------------- registry
def poison(buffers, site):
    """Record ``buffers`` (jax arrays) as donated at ``site``.  Call sites
    guard on ``sanitizer.donation`` so the idle cost is one attribute
    read."""
    if not donation:
        return
    n = 0
    with _lock:
        for b in buffers:
            if b is None:
                continue
            _poisoned[id(b)] = (site, b)
            n += 1
        while len(_poisoned) > _POISON_CAP:
            _poisoned.popitem(last=False)
    if n and _tel.enabled:
        _tel.count("analysis.sanitizer_poisoned", n)


def register_slot_view(buf, ring, slot_id, site):
    """Track a zero-copy staged buffer against its slot's current
    generation; reads after the ring bumps the generation raise."""
    if not slots or buf is None:
        return
    with _lock:
        _slot_views[id(buf)] = (ring, int(slot_id),
                                ring.generation(slot_id), site, buf)
        while len(_slot_views) > _SLOT_CAP:
            _slot_views.popitem(last=False)
    if _tel.enabled:
        _tel.count("analysis.sanitizer_slot_views")


def register_kv_slot(cache, slot_id, site):
    """Record a paged-KV slot allocation so a post-free read can name its
    site.  Unlike :func:`register_slot_view` (which tracks *buffers*), the
    stale check here compares a :class:`KVSlot` handle's generation stamp
    against the cache — see :func:`check_kv_slot`.  Only the site label is
    kept: holding the cache itself would pin its device-resident page
    pools long after the owning session closed.  (If the cache dies and a
    new one reuses its ``id()``, the worst case is a stale site label on
    a slot the new cache never re-registered — cosmetic, and registration
    at alloc overwrites.)"""
    if not slots:
        return
    with _lock:
        _kv_slots[(id(cache), int(slot_id))] = site
        while len(_kv_slots) > _KV_CAP:
            _kv_slots.popitem(last=False)
    if _tel.enabled:
        _tel.count("analysis.sanitizer_kv_slots")


def check_kv_slot(cache, slot_id, generation):
    """Read fence for the decode step: raise :class:`StaleKVSlotError`
    when ``cache``'s slot has recycled past ``generation`` (the handle's
    stamp).  Callers guard on ``sanitizer.slots``."""
    if not slots:
        return
    if cache.generation(slot_id) != generation:
        with _lock:
            site = _kv_slots.get((id(cache), int(slot_id)),
                                 "<unregistered>")
        _violation(StaleKVSlotError(site, slot_id))


def check_kv_pages(cache, slot):
    """Page-level read fence for refcounted (shared-prefix) caches: raise
    :class:`StaleKVSlotError` naming the page when any page a live
    :class:`KVSlot` handle references has recycled past the handle's
    stamp.  A shared page survives any number of co-holder frees — its
    generation bumps only on last-free — so this distinguishes "my
    neighbor left" (clean) from "my page was reassigned" (violation).
    Callers guard on ``sanitizer.slots``."""
    if not slots:
        return
    for page, gen in zip(slot.pages, slot.page_gens):
        if cache.page_generation(page) != gen:
            with _lock:
                site = _kv_slots.get((id(cache), int(slot.slot_id)),
                                     "<unregistered>")
            _violation(StaleKVSlotError(site, slot.slot_id, page=page))


def check_kv_write_span(cache, slot, position, n_tokens):
    """Write fence for the speculative *verify* step: the fused program
    is about to scatter candidate K/V at ``n_tokens`` consecutive
    positions starting at ``position``.  Every page covering that span
    must be generation-fresh AND exclusively owned by the slot
    (refcount 1, unpinned) — a shared or recycled page here means the
    verify scatter would scribble over a neighbour's (or the prefix
    index's) K/V, which the single-token write fence
    (:func:`check_kv_pages` + ``ensure_writable``) can't see because it
    only covers the *current* position's page.  Span positions past the
    slot's page table are legal: the program routes those writes to the
    trash page.  Callers guard on ``sanitizer.slots``."""
    if not slots:
        return
    ps = cache.page_tokens
    first = int(position) // ps
    last = (int(position) + max(int(n_tokens) - 1, 0)) // ps
    for idx in range(first, min(last, len(slot.pages) - 1) + 1):
        page = slot.pages[idx]
        fresh = cache.page_generation(page) == slot.page_gens[idx]
        shared = cache.prefix_sharing and (
            cache._slot_refs[page] > 1 or cache._pin_refs[page] > 0)
        if not fresh or shared:
            with _lock:
                site = _kv_slots.get((id(cache), int(slot.slot_id)),
                                     "<unregistered>")
            _violation(StaleKVSlotError(site, slot.slot_id, page=page))


def _violation(err):
    global _violations
    with _lock:
        _violations += 1
    if _tel.enabled:
        _tel.count("analysis.sanitizer_violations",
                   kind=type(err).__name__)
        _tel.instant("analysis.sanitizer_violation",
                     kind=type(err).__name__, site=err.site)
    # every sanitizer error funnels through here, which makes this the one
    # place the flight recorder's post-mortem fires: the dump names the
    # last N framework events before the violation, per host.  Lazy import
    # (cold path — we are about to raise) keeps telemetry/analysis
    # import-order free of cycles.
    from ..telemetry import flight as _flight
    _flight.record("sanitizer.violation",
                   detail=f"{type(err).__name__} @ {err.site}")
    _flight.postmortem(type(err).__name__, error=err)
    raise err


def check_buffer(buf):
    """The read-path hook (``NDArray._materialize`` / op dispatch).
    Callers guard on ``sanitizer.active``; a hit raises, a miss is one or
    two dict probes."""
    rec = _poisoned.get(id(buf))
    if rec is not None and rec[1] is buf:
        _violation(DonatedBufferError(rec[0]))
    rec = _slot_views.get(id(buf))
    if rec is not None and rec[4] is buf:
        ring, slot_id, gen, site, _shell = rec
        if ring.generation(slot_id) != gen:
            _violation(StaleSlotError(site, slot_id))


_env_spec = os.environ.get("MXNET_SANITIZE", "")
if _env_spec:
    configure(_env_spec)
