"""Host-side paged KV cache bookkeeping: allocator + prefix-cache index.

The device arrays live in ``models.init_kv_cache``; this module owns which
page holds what. Pages are the unit of both HBM allocation and prefix
caching: a *full* page of ``page_size`` tokens is content-addressed by the
chained MurmurHash3 digest of its tokens (``utils.hashing``), the same
digest scheme the service's cluster-wide ``GlobalKVCacheMgr`` keys on
(reference: common/hash_util.cpp:16-42, global_kvcache_mgr.cpp:71-129) — so
a worker's local prefix hits and the cluster's cache-aware routing agree
bit-for-bit on block identity.

Page id 0 is reserved as the NULL page (ops/attention.py) and never
allocated. Freed cache-registered pages are not zeroed: they stay in an LRU
pool and are only reclaimed when allocation pressure demands, giving
cross-request prefix reuse for free.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

from xllm_service_tpu.obs import steptrace
from xllm_service_tpu.utils.hashing import (
    chained_block_hash, prefix_block_hashes)
from xllm_service_tpu.utils.locks import make_lock

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class KvCacheEvent:
    """Delta of the worker's prefix-cache content, shipped in heartbeats to
    the service's global index (reference: xllm_rpc_service.proto KvCacheEvent
    — stored/removed block digests). ``offloaded`` = HBM → host-DRAM spill
    (the block is still servable from this worker, one tier down);
    ``offloaded_ssd`` = DRAM → disk demotion."""

    stored: List[bytes] = dataclasses.field(default_factory=list)
    removed: List[bytes] = dataclasses.field(default_factory=list)
    offloaded: List[bytes] = dataclasses.field(default_factory=list)
    offloaded_ssd: List[bytes] = dataclasses.field(default_factory=list)

    def merge(self, other: "KvCacheEvent") -> None:
        self.stored.extend(other.stored)
        self.removed.extend(other.removed)
        self.offloaded.extend(other.offloaded)
        self.offloaded_ssd.extend(other.offloaded_ssd)

    @property
    def empty(self) -> bool:
        return not (self.stored or self.removed or self.offloaded
                    or self.offloaded_ssd)


def encode_kv_block(k, v, extra: Optional[Dict] = None) -> bytes:
    """One K/V array pair as a meta-line + raw-bytes payload — the ONE
    codec for every KV byte stream (``/kv/blocks`` responses, the disk
    spill tier; ``/kv/import``/``/kv/chunk`` decode the same form via
    ``decode_kv_blob``): a JSON header ``{"shape", "dtype", **extra}``
    line, then K bytes, then V bytes."""
    import json
    head = json.dumps({"shape": list(k.shape), "dtype": str(k.dtype),
                       **(extra or {})})
    return head.encode("utf-8") + b"\n" + k.tobytes() + v.tobytes()


def decode_kv_blob(meta: Dict, blob: bytes):
    """Inverse of ``encode_kv_block`` given the parsed header ``meta``:
    (k, v) numpy views over ``blob``. Raises ValueError on a size
    mismatch (callers surface it as an HTTP 400 / corrupt-file skip)."""
    import numpy as np
    if meta["dtype"] == "bfloat16":
        import ml_dtypes
        dtype = np.dtype(ml_dtypes.bfloat16)
    else:
        dtype = np.dtype(meta["dtype"])
    shape = tuple(meta["shape"])
    nbytes = int(np.prod(shape)) * dtype.itemsize
    if len(blob) != 2 * nbytes:
        raise ValueError(
            f"payload size mismatch: {len(blob)} != {2 * nbytes}")
    k = np.frombuffer(blob[:nbytes], dtype=dtype).reshape(shape)
    v = np.frombuffer(blob[nbytes:], dtype=dtype).reshape(shape)
    return k, v


class HostKvTier:
    """Bounded host-DRAM (plus optional disk) parking lot for spilled KV
    pages, keyed by the same chained block digest the HBM index uses.

    A page evicted from the HBM pool under allocation pressure lands here
    instead of vanishing; a later prefix hit restores it through the
    donated pool scatter (write-then-attend zero-copy path preserved —
    the restore jit is the same ``_kv_scatter`` program PD import uses).
    LRU within the byte budget; overflow demotes to the disk tier when
    one is configured (``XLLM_KV_SPILL_DIR``), else drops the block.

    Thread-safe on its own lock (rank ``kv_cache.tier``): the engine owns
    the hot paths, but the worker's ``/kv/blocks`` holder endpoint reads
    blocks from an HTTP thread."""

    def __init__(self, capacity_bytes: int, disk_dir: str = "",
                 disk_capacity_bytes: int = 0) -> None:
        self.capacity_bytes = max(int(capacity_bytes), 0)
        self.disk_dir = disk_dir
        self.disk_capacity_bytes = max(int(disk_capacity_bytes), 0)
        self._lock = make_lock("kv_cache.tier", 22)
        # hash → (k_np, v_np); insertion order ~ LRU.
        self._blocks: "collections.OrderedDict[bytes, Tuple]" = \
            collections.OrderedDict()
        self._bytes = 0
        # hash → file path (disk tier); insertion order ~ LRU.
        self._disk: "collections.OrderedDict[bytes, str]" = \
            collections.OrderedDict()
        self._disk_bytes = 0
        self._pending = KvCacheEvent()
        self.spilled_blocks = 0       # lifetime DRAM admissions
        self.restored_blocks = 0      # lifetime promotions back to HBM
        if disk_dir:
            os.makedirs(disk_dir, exist_ok=True)

    @staticmethod
    def _nbytes(k, v) -> int:
        return int(k.nbytes) + int(v.nbytes)

    def put(self, h: bytes, k, v) -> bool:
        """Park one spilled page (host numpy arrays) under its digest.
        Returns False when the tier cannot hold it (the caller then
        reports the block removed, not offloaded)."""
        with self._lock:
            if h in self._blocks:
                self._blocks.move_to_end(h)
                return True
            n = self._nbytes(k, v)
            if n > self.capacity_bytes:
                return False                # block larger than the tier
            self._blocks[h] = (k, v)
            self._bytes += n
            self.spilled_blocks += 1
            while self._bytes > self.capacity_bytes and self._blocks:
                old_h, (ok, ov) = self._blocks.popitem(last=False)
                self._bytes -= self._nbytes(ok, ov)
                self._demote_locked(old_h, ok, ov)
            return True

    def _demote_locked(self, h: bytes, k, v) -> None:
        """DRAM overflow: write to the disk tier when configured (cold
        path — a header line + raw K/V bytes on the worker's local
        disk; .npz can't round-trip the ml_dtypes bfloat16 the pools
        use), else the block is gone everywhere and the cluster index
        must forget it. A disk dir WITHOUT a positive budget counts as
        no disk tier — otherwise every demotion would write a multi-MB
        file and immediately unlink it, on the admission hot path,
        retaining nothing."""
        if not self.disk_dir or self.disk_capacity_bytes <= 0:
            self._pending.removed.append(h)
            return
        n = self._nbytes(k, v)
        path = os.path.join(self.disk_dir, h.hex() + ".kv")
        try:
            with open(path, "wb") as f:
                f.write(encode_kv_block(k, v))
        except OSError as e:
            logger.warning("kv disk spill of %s failed: %s", h.hex(), e)
            self._pending.removed.append(h)
            return
        self._disk[h] = path
        self._disk_bytes += n
        self._pending.offloaded_ssd.append(h)
        while self._disk_bytes > self.disk_capacity_bytes and self._disk:
            old_h, old_path = self._disk.popitem(last=False)
            try:
                self._disk_bytes -= os.path.getsize(old_path)
                os.unlink(old_path)
            except OSError:
                pass
            self._pending.removed.append(old_h)

    def peek(self, h: bytes) -> Optional[Tuple]:
        """The block's (k, v) host arrays without consuming it — the
        restore path peeks first so a failed page allocation leaves the
        tier untouched. Disk blocks are loaded (and promoted to DRAM
        accounting stays put: the entry is consumed right after by
        ``pop`` on the success path)."""
        with self._lock:
            blk = self._blocks.get(h)
            if blk is not None:
                self._blocks.move_to_end(h)
                return blk
            path = self._disk.get(h)
        if path is None:
            return None
        import json
        try:
            with open(path, "rb") as f:
                raw = f.read()
            nl = raw.index(b"\n")
            meta = json.loads(raw[:nl].decode("utf-8"))
            return decode_kv_blob(meta, raw[nl + 1:])
        except (OSError, ValueError, KeyError) as e:
            logger.warning("kv disk read of %s failed: %s", h.hex(), e)
            return None

    def pop(self, h: bytes) -> None:
        """Consume one block (it was restored to HBM — the HBM `stored`
        delta supersedes this tier's claim at the cluster index)."""
        with self._lock:
            blk = self._blocks.pop(h, None)
            if blk is not None:
                self._bytes -= self._nbytes(*blk)
                self.restored_blocks += 1
                return
            path = self._disk.pop(h, None)
            if path is not None:
                try:
                    self._disk_bytes -= os.path.getsize(path)
                    os.unlink(path)
                except OSError:
                    pass
                self.restored_blocks += 1

    def __contains__(self, h: bytes) -> bool:
        with self._lock:
            return h in self._blocks or h in self._disk

    def drain_event(self) -> KvCacheEvent:
        with self._lock:
            ev = self._pending
            self._pending = KvCacheEvent()
            return ev

    @property
    def num_blocks(self) -> int:
        with self._lock:
            return len(self._blocks) + len(self._disk)

    @property
    def bytes_used(self) -> int:
        with self._lock:
            return self._bytes


class PageAllocator:
    """Free-list page allocator over ids [1, num_pages)."""

    def __init__(self, num_pages: int) -> None:
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is NULL)")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))

    @property
    def num_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        return pages

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            if not 0 < p < self.num_pages:
                raise ValueError(f"bad page id {p}")
            self._free.append(p)


class SlotAllocator:
    """Free-list allocator of the slots of a pool addressed by slot (the
    matrix states of a model with a mixer beside attention,
    ``models.init_kv_cache``'s fourth pool): ids ``first .. first +
    count - 1``. Slot 0 is the null slot, as page 0 is the null page,
    and belongs to no allocator."""

    def __init__(self, first: int, count: int) -> None:
        if first < 1 or count < 0:
            raise ValueError("slots start at 1 (slot 0 is NULL)")
        self.first, self.count = first, count
        self._free: List[int] = list(range(first + count - 1, first - 1, -1))

    @property
    def num_free(self) -> int:
        return len(self._free)

    def alloc(self) -> Optional[int]:
        return self._free.pop() if self._free else None

    def free(self, slot: int) -> None:
        if not self.first <= slot < self.first + self.count \
                or slot in self._free:
            raise ValueError(f"bad or free slot id {slot}")
        self._free.append(slot)


class WindowPool:
    """Host bookkeeping of the WINDOW layers' pool (a model whose
    sliding-window layers keep their keys and values apart from its full
    layers': ``models.init_kv_cache``'s last pair): pages by reference
    count, held by ROWS (the pages a row's window can reach; trimmed
    behind it as the row advances) and by TAILS.

    A tail is what a cached prefix keeps of the window layers: the
    ``tail_pages`` whole window pages that END at a full-page boundary
    of a finished prefill, keyed by the FULL pool's page at that
    boundary. A request that matches the boundary takes references to
    the tail's pages (it only ever reads them: its own positions land in
    pages of its own) and resumes there; a boundary without a live tail
    cannot be resumed at, because a window layer's rows could only be
    recomputed from every layer's rows before them. Tails are evicted
    when the pool runs short, the least recently HIT first (never-hit
    ones oldest first, then by last hit), and never while a row holds
    one of their pages; where ``max_tails`` are held a new tail takes a
    never-hit one's place or is not kept (a request's own prompt never
    pushes out a document's). Engine-internal state under
    the worker's engine lock, like the index it hangs off."""

    def __init__(self, num_pages: int, tail_pages: int,
                 max_tails: int) -> None:
        self.allocator = PageAllocator(num_pages)
        self.tail_pages = tail_pages
        self.max_tails = max_tails
        self._ref: Dict[int, int] = collections.defaultdict(int)
        self._tail_ref: Dict[int, int] = collections.defaultdict(int)
        # full-pool page id at the boundary -> (window pages, the digests
        # of the full pages up to the boundary)
        self._tails: Dict[int, Tuple[List[int], List[bytes]]] = {}
        self._unhit: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self._hit: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        # told the digests of a tail that goes (the index retracts what
        # it advertised for them)
        self.on_drop: Optional[Callable[[List[bytes]], None]] = None
        self.pages_peak = 0
        self.pages_trimmed = 0
        self.tails_taken = 0
        self.tail_hits = 0
        self.tail_misses = 0
        self.tail_evictions = 0

    # -- pages ------------------------------------------------------------
    @property
    def num_pages(self) -> int:
        return self.allocator.num_pages

    @property
    def pages_live(self) -> int:
        """Pages some row or tail holds (the null page is nobody's)."""
        return self.allocator.num_pages - 1 - self.allocator.num_free

    @property
    def num_tails(self) -> int:
        return len(self._tails)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` pages for a row, tails nobody holds making room where
        the pool is short; None where it stays short."""
        while n > self.allocator.num_free and self._evict_one():
            pass
        pages = self.allocator.alloc(n)
        if pages is not None:
            for pid in pages:
                self._ref[pid] += 1
            self.pages_peak = max(self.pages_peak, self.pages_live)
        return pages

    def acquire(self, pages: Sequence[int]) -> None:
        for pid in pages:
            self._ref[pid] += 1

    def release(self, pages: Sequence[int]) -> None:
        for pid in pages:
            self._ref[pid] -= 1
            if self._ref[pid] <= 0:
                del self._ref[pid]
                self.allocator.free([pid])

    # -- tails ------------------------------------------------------------
    def tail_of(self, pid: int) -> Optional[List[int]]:
        t = self._tails.get(pid)
        return t[0] if t is not None else None

    def note_hit(self, pid: int) -> None:
        self.tail_hits += 1
        self._unhit.pop(pid, None)
        self._hit[pid] = None
        self._hit.move_to_end(pid)

    def attach(self, pid: int, wpages: Sequence[int],
               digests: Sequence[bytes]) -> bool:
        """The full pool's page ``pid`` ends a finished prefill at a page
        boundary and ``wpages`` are the window pages that end there: the
        tail takes a reference to each. False where the page has one
        already, or ``max_tails`` are held and none of the never-hit
        ones can go (a row reads each): a new tail has never been hit
        itself, so it does not push out one that has."""
        if pid in self._tails or not wpages or not all(wpages):
            return False
        while len(self._tails) >= self.max_tails:
            if not self._evict_one(hit_too=False):
                return False
        for w in wpages:
            self._ref[w] += 1
            self._tail_ref[w] += 1
        self._tails[pid] = (list(wpages), list(digests))
        self._unhit[pid] = None
        self.tails_taken += 1
        return True

    def drop(self, pid: int) -> None:
        """The tail of ``pid`` goes (evicted here, or its page's content
        reclaimed in the full pool)."""
        t = self._tails.pop(pid, None)
        if t is None:
            return
        self._unhit.pop(pid, None)
        self._hit.pop(pid, None)
        for w in t[0]:
            self._tail_ref[w] -= 1
            if self._tail_ref[w] <= 0:
                del self._tail_ref[w]
        self.release(t[0])
        self.tail_evictions += 1
        if self.on_drop is not None:
            self.on_drop(t[1])

    def _evict_one(self, hit_too: bool = True) -> bool:
        """Drop the least recently hit tail none of whose pages a row
        holds (a page's holders are then all tails); of the never-hit
        ones alone where ``hit_too`` is false."""
        for order in (self._unhit, self._hit) if hit_too \
                else (self._unhit,):
            for pid in order:
                if all(self._ref[w] == self._tail_ref[w]
                       for w in self._tails[pid][0]):
                    with steptrace.span("xllm.kv.window_tail",
                                        event="evict"):
                        self.drop(pid)
                    return True
        return False


class PrefixCacheIndex:
    """Content-addressed index of *full* pages + LRU reclamation.

    Lifecycle of a page:
      allocated → (sequence fills it) → registered under its chained hash,
      refcount tracks sharing → when every owner releases it, it becomes
      *reclaimable* (still mapped, tokens still in HBM) → reused on a later
      prefix hit, or reclaimed LRU-first under allocation pressure.
    """

    def __init__(self, allocator: PageAllocator, page_size: int,
                 seed: int = 0, enable: bool = True) -> None:
        self.allocator = allocator
        self.page_size = page_size
        self.seed = seed
        self.enable = enable
        # The index is engine-internal state: every caller path runs
        # inside an Engine method serialized by the worker's engine
        # lock (there is deliberately no lock here — adding one would
        # double-lock the hot admit path).
        self._by_hash: Dict[bytes, int] = {}    # guarded-by: worker.engine
        self._hash_of: Dict[int, bytes] = {}    # guarded-by: worker.engine
        self._ref: Dict[int, int] = collections.defaultdict(int)  # guarded-by: worker.engine
        # page id → last-release time; insertion order ~ LRU.
        self._reclaimable: "collections.OrderedDict[int, float]" = \
            collections.OrderedDict()           # guarded-by: worker.engine
        self._pending_event = KvCacheEvent()
        # Tiered spill (engine-wired): called with (hash, page) when a
        # RECLAIMABLE registered page is about to be reused under
        # allocation pressure — the one eviction class whose content is
        # still intact in HBM. True = the block was parked in a lower
        # tier (event: offloaded); False/None-hook = it is gone
        # (event: removed).
        self.spill_hook: Optional[Callable[[bytes, int], bool]] = None
        # Tokens fed to the block hash over this index's life (lookup,
        # restore and registration alike): the engine's
        # ``hashed_tokens_total``.
        self.hashed_tokens = 0
        # Pages ``register_pages`` set out to look at over this index's
        # life (a row's full pages past its settled lead): the engine's
        # ``walked_pages_total``.
        self.walked_pages = 0
        # A model whose sequences carry a state that lives by SLOT and
        # not by page (``enable_snapshots``): page id -> the slot that
        # holds the state as of that page's last token. A prefix match
        # is then cut at the deepest page that has one: pages behind it
        # hold keys and values nobody can resume from. Eviction takes the
        # least recently HIT: first the snapshots no match has ever
        # ended at (``_snapshots_unhit``, oldest first), then the others
        # by their last hit (``_snapshots_hit``). A turn's own snapshot,
        # which nobody asks for again, so never pushes out a system
        # prompt's.
        self.snapshot_slots: Optional[SlotAllocator] = None
        self._snapshot_of: Dict[int, int] = {}  # guarded-by: worker.engine
        self._snapshots_unhit: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()           # guarded-by: worker.engine
        self._snapshots_hit: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()           # guarded-by: worker.engine
        self.snapshots_taken = 0
        self.snapshots_evicted = 0
        # A model whose window layers keep their keys and values in a
        # pool of their own (``enable_tails``): a prefix match ends at
        # the deepest boundary whose TAIL of window pages is live, and
        # the blocks told to the cluster's index are those under such a
        # boundary (digest -> how many live tails lie at or past it).
        self.tails: Optional[WindowPool] = None
        self._advertised: Dict[bytes, int] = {}  # guarded-by: worker.engine

    # -- hashing ----------------------------------------------------------
    def block_hashes(self, tokens: Sequence[int]) -> List[bytes]:
        """Digests of every full page of ``tokens``, hashed from block 0:
        for a caller that keeps no digests of the list."""
        hashes = prefix_block_hashes(tokens, self.page_size, self.seed)
        self.hashed_tokens += len(hashes) * self.page_size
        return hashes

    def extend_digests(self, digests: List[bytes], tokens: Sequence[int],
                       num_tokens: int) -> None:
        """Grow ``digests`` in place to the first ``num_tokens //
        page_size`` full pages of ``tokens``, hashing only the blocks it
        lacks. ``digests`` belongs to one token list that only grows
        (an engine ``Sequence``'s), so ``digest(block_i) =
        murmur3(digest(block_{i-1}) || le32(block_i))`` never changes once
        computed: byte-equal to ``block_hashes(tokens[:num_tokens])``.
        A chain that is still empty and wanted over the whole list (a
        fresh prompt at its admission) is made by the ONE call that
        hashes a list's blocks, not a slice, a pack and a call a block."""
        ps = self.page_size
        have, want = len(digests), num_tokens // ps
        if want <= have:
            return
        if not have and want == len(tokens) // ps:
            digests.extend(prefix_block_hashes(tokens, ps, self.seed))
        else:
            prev = digests[-1] if digests else None
            for b in range(have, want):
                prev = chained_block_hash(tokens[b * ps:(b + 1) * ps], prev,
                                          self.seed)
                digests.append(prev)
        self.hashed_tokens += (want - have) * ps

    # -- lookup -----------------------------------------------------------
    def match_prefix(self, tokens: Sequence[int],
                     digests: Optional[List[bytes]] = None
                     ) -> Tuple[List[int], int]:
        """Longest cached prefix of ``tokens`` in full-page units.
        ``digests`` is the caller's chain over a list that starts with
        ``tokens`` (``extend_digests``); it leaves here covering them.

        Returns (pages, num_cached_tokens); the pages are ref-counted for
        the caller and must be released via ``release_pages``."""
        if not self.enable:
            return [], 0
        if digests is None:
            digests = []
        pages: List[int] = []
        with steptrace.span("xllm.kv.match_prefix", tokens=len(tokens)):
            self.extend_digests(digests, tokens, len(tokens))
            for i in range(len(tokens) // self.page_size):
                pid = self._by_hash.get(digests[i])
                if pid is None:
                    break
                pages.append(pid)
        # Never hand out the *entire* prompt from cache: the last token must
        # be recomputed so prefill has at least one new token to produce
        # logits from.
        while pages and len(pages) * self.page_size >= len(tokens):
            pages = pages[:-1]
        if self.snapshot_slots is not None:
            while pages and pages[-1] not in self._snapshot_of:
                pages = pages[:-1]
            if pages:                                       # a hit
                self._snapshots_unhit.pop(pages[-1], None)
                self._snapshots_hit[pages[-1]] = None
                self._snapshots_hit.move_to_end(pages[-1])
        if self.tails is not None and pages:
            # The deepest matched boundary whose tail is live and nothing
            # deeper: no partial credit for pages past it.
            if self.tails.tail_of(pages[-1]) is None:
                self.tails.tail_misses += 1
                while pages and self.tails.tail_of(pages[-1]) is None:
                    pages = pages[:-1]
            if pages:
                self.tails.note_hit(pages[-1])
        for pid in pages:
            self._acquire(pid)
        return pages, len(pages) * self.page_size

    # -- snapshots of a state that lives by slot ---------------------------
    def enable_snapshots(self, slots: SlotAllocator) -> None:
        """From now on a match ends at a page that has a snapshot."""
        self.snapshot_slots = slots

    def snapshot_of(self, pid: int) -> int:
        """The slot holding the state as of page ``pid``'s last token
        (0: none)."""
        return self._snapshot_of.get(pid, 0)

    @property
    def num_snapshots(self) -> int:
        return len(self._snapshot_of)

    def reserve_snapshot(self) -> int:
        """A slot for a prefill to write a snapshot into, the least
        recently hit snapshot making room where none is free (its page
        stays registered and can no longer be resumed from). It belongs
        to no page until ``attach_snapshot``. 0: the model keeps none."""
        if self.snapshot_slots is None or not self.snapshot_slots.count:
            return 0
        slot = self.snapshot_slots.alloc()
        if slot is None:
            pid, _ = (self._snapshots_unhit
                      or self._snapshots_hit).popitem(last=False)
            slot = self._snapshot_of.pop(pid)
            self.snapshots_evicted += 1
        return slot

    def attach_snapshot(self, digest: bytes, slot: int) -> bool:
        """The prefill that was handed ``slot`` has run and the page of
        ``digest`` is registered (``register_pages``): the page that owns
        that content now has the snapshot, the newest of the never-hit.
        False (and the slot free again) where no page owns it or the
        owner has one already."""
        pid = self._by_hash.get(digest)
        if pid is None or pid in self._snapshot_of:
            self.snapshot_slots.free(slot)
            return False
        self._snapshot_of[pid] = slot
        self._snapshots_unhit[pid] = None
        self.snapshots_taken += 1
        return True

    # -- tails of a pool of window layers ---------------------------------
    def enable_tails(self, pool: WindowPool) -> None:
        """From now on a match ends at a boundary that has a tail, and
        the cluster is told of the blocks under one alone."""
        self.tails = pool
        pool.on_drop = self._retract

    def attach_tail(self, digests: Sequence[bytes], boundary: int,
                    wpages: Sequence[int]) -> bool:
        """A prefill has finished, the first ``boundary`` full pages of
        its tokens are registered (``register_pages``) and ``wpages`` are
        the window pool's pages that end there: the page that owns the
        boundary's content now has the tail, and the blocks up to it are
        told to the cluster (a request can resume there)."""
        pid = self._by_hash.get(digests[boundary - 1]) if boundary else None
        if pid is None:
            return False
        chain = list(digests[:boundary])
        with steptrace.span("xllm.kv.window_tail", event="attach",
                            pages=len(wpages)):
            if not self.tails.attach(pid, wpages, chain):
                return False
        for h in chain:
            n = self._advertised.get(h, 0)
            self._advertised[h] = n + 1
            if not n:
                self._pending_event.stored.append(h)
        return True

    def _retract(self, chain: Sequence[bytes]) -> None:
        for h in chain:
            n = self._advertised.get(h)
            if n is None:
                continue            # its page went first (_evict_mapping)
            if n > 1:
                self._advertised[h] = n - 1
            else:
                del self._advertised[h]
                self._pending_event.removed.append(h)

    def _drop_snapshot(self, pid: int) -> None:
        slot = self._snapshot_of.pop(pid, None)
        if slot is not None:
            self._snapshots_unhit.pop(pid, None)
            self._snapshots_hit.pop(pid, None)
            self.snapshot_slots.free(slot)
            self.snapshots_evicted += 1

    # -- registration -----------------------------------------------------
    def register_full_pages(self, tokens: Sequence[int],
                            pages: Sequence[int]) -> None:
        """``register_pages`` for a caller that owns no sequence, and so
        no digests: every full page of ``tokens`` is hashed here."""
        self.register_pages([], tokens, len(tokens), pages)

    def register_pages(self, digests: List[bytes], tokens: Sequence[int],
                       num_computed: int, pages: Sequence[int],
                       settled: int = 0) -> int:
        """Register every full page of ``tokens[:num_computed]`` under its
        chained hash. ``pages[i]`` holds tokens [i*ps, (i+1)*ps);
        ``digests`` is the sequence's chain (``extend_digests``), so a
        page is hashed once, when it fills. Safe to call repeatedly as a
        sequence grows, and again on new pages after a preemption.

        ``settled`` is what an earlier call on the SAME ``pages``
        returned (0: nothing known, every full page is walked): the
        count of leading pages that are registered under the row's own
        digest (``_hash_of[pages[i]] == digests[i]``). The walk starts
        there, and a call that finds no full page past it (every sampled
        token but the one that fills a page) returns before a span is
        opened or anything is looked up: the work follows the pages that
        FILLED since the last call, not the row's table. The count is
        sound because a settled page cannot lose its mapping while the
        row holds its reference: ``_evict_mapping`` is reached only from
        ``alloc``'s reclaim of pages with no owner, from this loop and
        ``register_blocks`` (both on a page being registered anew, never
        one that is settled). A page whose content ANOTHER page owns
        (``h in self._by_hash``: two rows prefilled one content at once)
        is not settled: the count stops in front of it and the next call
        tests it again, as a walk from page 0 would, so it takes the
        content over once the owner's mapping is evicted. Whoever empties
        or rebuilds ``pages`` starts again from 0.

        ``pages`` is the FULL pool's table: under a uniform window the
        engine trims it, and a sequence whose first page is trimmed
        registers nothing more (below); where the window layers have a
        pool of their own it is never trimmed, every full page is
        registered, and what makes a boundary resumable is its TAIL
        (``attach_tail``), with which the cluster is told of it."""
        if not self.enable:
            return settled
        if pages and not pages[0]:
            # Leading page already trimmed behind a UNIFORM window:
            # nothing below is registrable (see the break below), so
            # nothing is hashed or walked every decode step of a long
            # sequence. (Registration under a trimmed lead needs the
            # tails' rule for a model with no full layer: ROADMAP.md
            # Reach A2 (c).)
            return settled
        n_full = num_computed // self.page_size
        if n_full <= settled:
            return settled
        end = min(n_full, len(pages))
        walk = max(end - settled, 0)
        self.walked_pages += walk
        with steptrace.span(
                "xllm.kv.register_pages",
                tokens=max(n_full - len(digests), 0) * self.page_size,
                pages=walk):
            self.extend_digests(digests, tokens, num_computed)
            for i in range(settled, end):
                pid = pages[i]
                if not pid:
                    # NULL placeholder: a sliding-window-trimmed page
                    # (engine._swa_trim). Its content is gone — and
                    # blocks ABOVE the gap are unreachable too
                    # (match_prefix walks the chained hashes from block
                    # 0), so registering them would advertise digests the
                    # cluster's cache-aware routing could never actually
                    # hit.
                    break
                h = digests[i]
                if self._hash_of.get(pid) != h:
                    if h in self._by_hash:
                        continue  # another sequence already owns this content
                    self._evict_mapping(pid)
                    self._by_hash[h] = pid
                    self._hash_of[pid] = h
                    if self.tails is None:      # else: told with its tail
                        self._pending_event.stored.append(h)
                if settled == i:
                    settled = i + 1
        return settled

    # -- refcounting ------------------------------------------------------
    def _acquire(self, pid: int) -> None:
        self._ref[pid] += 1
        self._reclaimable.pop(pid, None)

    def acquire_pages(self, pages: Sequence[int]) -> None:
        for pid in pages:
            self._acquire(pid)

    def release_pages(self, pages: Sequence[int]) -> None:
        """Owner is done with these pages. Registered pages become
        reclaimable (content kept); unregistered ones go straight back to
        the allocator."""
        now = time.monotonic()
        for pid in pages:
            self._ref[pid] -= 1
            if self._ref[pid] > 0:
                continue
            del self._ref[pid]
            if pid in self._hash_of:
                self._reclaimable[pid] = now
                self._reclaimable.move_to_end(pid)
            else:
                self.allocator.free([pid])

    # -- allocation under pressure ---------------------------------------
    def alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages, reclaiming LRU cached pages if needed.
        A reclaimed page's content is still intact, so this is the one
        eviction site that can SPILL it to a lower tier first."""
        need = n - self.allocator.num_free
        while need > 0 and self._reclaimable:
            pid, _ = self._reclaimable.popitem(last=False)
            self._evict_mapping(pid, spillable=True)
            self.allocator.free([pid])
            need -= 1
        pages = self.allocator.alloc(n)
        if pages is not None:
            for pid in pages:
                self._acquire(pid)
        return pages

    def _evict_mapping(self, pid: int, spillable: bool = False) -> None:
        h = self._hash_of.pop(pid, None)
        if h is None:
            return
        self._drop_snapshot(pid)    # the content goes: so does its state
        self._by_hash.pop(h, None)
        if self.tails is not None:
            self.tails.drop(pid)        # ... and the tail that ends there
            if self._advertised.pop(h, None) is None:
                return                  # the cluster was never told of it
        if spillable and self.spill_hook is not None:
            try:
                if self.spill_hook(h, pid):
                    self._pending_event.offloaded.append(h)
                    return
            except Exception as e:  # noqa: BLE001 — spill is best-effort;
                # a failed copy degrades to a plain eviction, never an
                # allocation failure.
                logger.warning("kv spill of page %d failed: %s", pid, e)
        self._pending_event.removed.append(h)

    def register_blocks(self, hashes: Sequence[bytes],
                        pages: Sequence[int]) -> int:
        """Directly register hash→page mappings, positionally (the
        cross-worker adoption path, where the chain below may resolve
        through the spill tier rather than HBM — ``register_pages``
        would need every lead page id). Chain REACHABILITY is the
        caller's contract. Skips hashes already owned (exactly-once:
        the redundant page stays unregistered and frees on release).
        Returns the number registered."""
        n = 0
        for h, pid in zip(hashes, pages):
            if self._hash_of.get(pid) == h or h in self._by_hash:
                continue
            self._evict_mapping(pid)
            self._by_hash[h] = pid
            self._hash_of[pid] = h
            self._pending_event.stored.append(h)
            n += 1
        return n

    # -- cross-worker fetch (holder side) --------------------------------
    def pages_for_hashes(self, hashes: Sequence[bytes]) -> List[int]:
        """HBM pages for a digest run, stopping at the first miss (the
        fetch contract is a contiguous leading prefix). The returned
        pages are ACQUIRED for the caller (pinned against reclamation
        while the export gathers them) and must be released via
        ``release_pages``."""
        pages: List[int] = []
        for h in hashes:
            pid = self._by_hash.get(h)
            if pid is None:
                break
            pages.append(pid)
        for pid in pages:
            self._acquire(pid)
        return pages

    def page_of(self, h: bytes) -> Optional[int]:
        return self._by_hash.get(h)

    # -- heartbeat plumbing ----------------------------------------------
    def drain_event(self) -> KvCacheEvent:
        ev = self._pending_event
        self._pending_event = KvCacheEvent()
        return ev

    # -- introspection ----------------------------------------------------
    @property
    def num_cached_pages(self) -> int:
        return len(self._by_hash)

    @property
    def num_reclaimable(self) -> int:
        """Pages holding cached content but instantly reclaimable (no live
        owner) — effectively-free capacity for load reporting."""
        return len(self._reclaimable)

    def cached_hashes(self) -> Set[bytes]:
        return set(self._by_hash)
