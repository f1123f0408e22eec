"""Cluster-scale prefix reuse (docs/KV_CACHE.md): tiered KV spill on
the worker, cross-worker cached-block fetch, the fetch-vs-recompute
cost model, and the block-hash single source of truth.

Layers under test, cheapest first: pure index/tier units, the global
cluster index's replication, the scheduler's planner (no sockets), and
engine-level spill/restore + export/adopt round trips (tiny model,
CPU). The full two-worker e2e lives in tests/test_e2e.py
(TestPrefixReuse).
"""

import random
import threading
import time
from typing import List, Tuple

import numpy as np
import pytest

from xllm_service_tpu.config import (
    EngineConfig, InstanceType, ModelConfig, ServiceOptions)
from xllm_service_tpu.obs.events import EventLog
from xllm_service_tpu.runtime.kv_cache import (
    HostKvTier, KvCacheEvent, PageAllocator, PrefixCacheIndex,
    SlotAllocator, WindowPool)
from xllm_service_tpu.service.coordination import (
    InMemoryStore, instance_prefix)
from xllm_service_tpu.service.instance_types import (
    Heartbeat, InstanceMetaInfo, LatencyMetrics, LoadMetrics)
from xllm_service_tpu.service.kvcache_mgr import (
    GlobalKVCacheMgr, TIER_DRAM, TIER_HBM, TIER_SSD)
from xllm_service_tpu.service.scheduler import Scheduler
from xllm_service_tpu.utils.hashing import prefix_block_hashes
from xllm_service_tpu.utils.types import SamplingParams


@pytest.fixture()
def store():
    s = InMemoryStore(sweep_interval_s=0.02)
    yield s
    s.close()


# ---------------------------------------------------------------------------
# Block-hash single source of truth
# ---------------------------------------------------------------------------

class TestHashParity:
    def test_worker_hashes_byte_equal_to_service_digests(self):
        """The worker's PrefixCacheIndex and the service's
        GlobalKVCacheMgr must agree bit-for-bit on block identity when
        page_size == block_size and the seeds match — the invariant the
        registration advertisement fails loud about."""
        tokens = list(range(1000, 1137))           # 137 tokens
        for bs, seed in ((16, 0), (32, 7), (128, 12345)):
            idx = PrefixCacheIndex(PageAllocator(8), page_size=bs,
                                   seed=seed)
            assert idx.block_hashes(tokens) == \
                prefix_block_hashes(tokens, bs, seed)

    def test_mismatched_block_size_diverges(self):
        """Sanity for the quarantine rationale: different granularity
        means NO digest in common."""
        tokens = list(range(256))
        a = set(prefix_block_hashes(tokens, 16, 0))
        b = set(prefix_block_hashes(tokens, 32, 0))
        assert not (a & b)


# ---------------------------------------------------------------------------
# PrefixCacheIndex edges
# ---------------------------------------------------------------------------

def _register_seq(idx: PrefixCacheIndex, tokens: List[int]
                  ) -> List[int]:
    """Allocate + register the full pages of ``tokens``; release so the
    pages end reclaimable-but-cached (the steady state)."""
    n = len(tokens) // idx.page_size
    pages = idx.alloc(n)
    assert pages is not None
    idx.register_full_pages(tokens, pages)
    idx.release_pages(pages)
    return pages


class TestPrefixCacheIndexEdges:
    def test_evict_while_acquired_skips_live_pages(self):
        """A page acquired by a live match_prefix hit must never be
        reclaimed by allocation pressure — pressure takes free +
        reclaimable pages only, and fails (None) past them."""
        idx = PrefixCacheIndex(PageAllocator(4), page_size=4)  # 3 usable
        tokens = list(range(8))                     # 2 full pages
        _register_seq(idx, tokens)
        pages, cached = idx.match_prefix(tokens + [99, 98])
        assert cached == 8 and len(pages) == 2      # acquired
        # 1 free page left; asking for 3 must fail WITHOUT touching the
        # acquired pages.
        assert idx.alloc(3) is None
        assert idx.page_of(idx.block_hashes(tokens)[0]) == pages[0]
        again, cached2 = idx.match_prefix(tokens + [99, 98])
        assert again == pages and cached2 == 8
        idx.release_pages(pages)
        idx.release_pages(again)

    def test_reregister_of_evicted_hash(self):
        """Pressure evicts a reclaimable mapping (event: removed);
        re-registering the same content under fresh pages works and
        match hits again (event: stored twice total)."""
        idx = PrefixCacheIndex(PageAllocator(4), page_size=4)
        tokens = list(range(8))
        _register_seq(idx, tokens)
        assert idx.alloc(3) is not None             # evicts both mappings
        assert idx.num_cached_pages == 0
        ev = idx.drain_event()
        assert len(ev.stored) == 2 and len(ev.removed) == 2
        # Fresh pages, same content.
        idx2 = PrefixCacheIndex(PageAllocator(8), page_size=4)
        _register_seq(idx2, tokens)
        evicted_hash = idx2.block_hashes(tokens)[0]
        pid = idx2.page_of(evicted_hash)
        pressure = idx2.alloc(7)                    # evict everything
        assert pressure is not None
        assert idx2.page_of(evicted_hash) is None
        idx2.release_pages(pressure)
        _register_seq(idx2, tokens)                 # re-register
        assert idx2.page_of(evicted_hash) is not None
        assert idx2.page_of(evicted_hash) != pid or True  # id may differ
        pages, cached = idx2.match_prefix(tokens + [1, 2, 3])
        assert cached == 8
        idx2.release_pages(pages)

    def test_whole_prompt_hit_trims_last_page(self):
        """A prompt entirely covered by cached pages must forgo at
        least the last page: prefill needs one new token to produce
        logits from."""
        idx = PrefixCacheIndex(PageAllocator(8), page_size=4)
        tokens = list(range(12))                    # 3 full pages
        _register_seq(idx, tokens)
        pages, cached = idx.match_prefix(tokens)    # whole-prompt hit
        assert cached == 8 and len(pages) == 2      # last page trimmed
        idx.release_pages(pages)
        # One token past the boundary: all 3 pages usable.
        pages, cached = idx.match_prefix(tokens + [77])
        assert cached == 12 and len(pages) == 3
        idx.release_pages(pages)


# ---------------------------------------------------------------------------
# A page is hashed once: the per-sequence digests against the full rehash
# ---------------------------------------------------------------------------

def _register_by_whole_walk(idx: PrefixCacheIndex, digests: List[bytes],
                            tokens: List[int], num_computed: int,
                            pages: List[int]) -> int:
    """``register_pages`` as it was before a row carried its settled
    count (its span left out): EVERY call walks every full page of the
    row. The oracle for the watermark. Returns the pages it walked."""
    if pages and not pages[0]:
        return 0
    n_full = num_computed // idx.page_size
    idx.extend_digests(digests, tokens, num_computed)
    for i in range(min(n_full, len(pages))):
        pid = pages[i]
        if not pid:
            break
        h = digests[i]
        if idx._hash_of.get(pid) == h:
            continue
        if h in idx._by_hash:
            continue  # another sequence already owns this content
        idx._evict_mapping(pid)
        idx._by_hash[h] = pid
        idx._hash_of[pid] = h
        if idx.tails is None:
            idx._pending_event.stored.append(h)
    return min(n_full, len(pages))


def _register_by_full_rehash(idx: PrefixCacheIndex, tokens: List[int],
                             pages: List[int]) -> None:
    """Registration as it was before sequences kept their digests: every
    call hashes ``tokens`` from block 0, then walks. The reference for
    ``register_pages``'s digests."""
    if pages and not pages[0]:
        return
    _register_by_whole_walk(idx, idx.block_hashes(tokens), tokens,
                            len(tokens), pages)


def _toks(n: int, salt: int) -> List[int]:
    return [(i * 2654435761 + salt * 40503) % 32000 for i in range(n)]


class _Seq:
    """What the engine's Sequence gives the index: tokens that only
    grow, its pages, how many tokens have KV, and its digests."""

    def __init__(self, prompt: List[int]) -> None:
        self.prompt = list(prompt)
        self.tokens = list(prompt)
        self.pages: List[int] = []
        self.num_computed = 0
        self.digests: List[bytes] = []
        # what the watermark's parity test adds (_Watermark, below)
        self.settled = 0
        self.oracle_digests: List[bytes] = []
        self.wpages: List[int] = []
        self.trimmed = 0


class _Lockstep:
    """Every call made on two indices: ``new`` through the sequence's
    digests, ``old`` by the full rehash. After each, both hold the same
    mappings and have queued the same events."""

    def __init__(self, ps: int, num_pages: int = 24) -> None:
        self.ps = ps
        self.new = PrefixCacheIndex(PageAllocator(num_pages), ps, seed=7)
        self.old = PrefixCacheIndex(PageAllocator(num_pages), ps, seed=7)
        self.seqs: List[_Seq] = []

    def check(self) -> None:
        assert self.new._by_hash == self.old._by_hash
        assert self.new._hash_of == self.old._hash_of
        assert dict(self.new._ref) == dict(self.old._ref)
        assert list(self.new._reclaimable) == list(self.old._reclaimable)
        a, b = self.new.drain_event(), self.old.drain_event()
        assert (a.stored, a.removed) == (b.stored, b.removed)

    def alloc(self, n: int) -> List[int]:
        pages = self.new.alloc(n)
        assert pages is not None and pages == self.old.alloc(n)
        self.check()
        return pages

    def release(self, pages: List[int]) -> None:
        self.new.release_pages(pages)
        self.old.release_pages(pages)

    def pressure(self) -> None:
        """Take every free and every reclaimable page, and give them
        back: all unowned mappings are evicted."""
        self.release(self.alloc(self.new.allocator.num_free
                                + self.new.num_reclaimable))

    def admit(self, seq: _Seq, prefill: int = 0) -> int:
        """Engine._try_admit: look the prompt up, take pages for every
        token plus the one sampled next; ``prefill`` tokens past the
        hit are computed (0: all of them)."""
        if seq not in self.seqs:
            self.seqs.append(seq)
        hit, cached = self.new.match_prefix(seq.prompt, seq.digests)
        assert (hit, cached) == self.old.match_prefix(seq.prompt)
        need = -(-(len(seq.tokens) + 1) // self.ps) - len(hit)
        seq.pages = hit + self.alloc(need)
        seq.num_computed = cached + prefill if prefill \
            else len(seq.tokens)
        return cached

    def register(self, seq: _Seq) -> None:
        self.new.register_pages(seq.digests, seq.tokens, seq.num_computed,
                                seq.pages)
        _register_by_full_rehash(
            self.old, seq.tokens[:seq.num_computed], seq.pages)
        self.check()

    def decode(self, seq: _Seq, toks: List[int]) -> None:
        """Engine._append_token, once a token: the sampled one has no KV
        yet; register, then grow the table for its write."""
        for tok in toks:
            seq.tokens.append(tok)
            seq.num_computed = len(seq.tokens) - 1
            self.register(seq)
            need = -(-(len(seq.tokens) + 1) // self.ps) - len(seq.pages)
            if need > 0:
                seq.pages += self.alloc(need)

    def trim(self, seq: _Seq, i: int) -> None:
        """Engine._swa_trim of page ``i``: released, a NULL in its
        place."""
        self.release([seq.pages[i]])
        seq.pages[i] = 0

    def give_up(self, seq: _Seq) -> None:
        """Engine._preempt_seq and _finish_seq: register, drop the
        pages; the tokens and their digests stay."""
        self.register(seq)
        self.release([p for p in seq.pages if p])
        seq.pages, seq.num_computed = [], 0


def _plain(ls: _Lockstep, ps: int) -> None:
    a = _Seq(_toks(2 * ps + ps // 2, 1))
    assert ls.admit(a) == 0
    ls.decode(a, _toks(2 * ps, 2))              # two pages fill
    assert len(a.digests) == 4
    ls.give_up(a)
    b = _Seq(a.prompt)
    assert ls.admit(b) == 2 * ps                # a's pages, under a's digests
    ls.decode(b, _toks(3, 3))
    ls.give_up(b)


def _swa_null_lead(ls: _Lockstep, ps: int) -> None:
    a = _Seq(_toks(3 * ps + 3, 4))
    ls.admit(a)
    ls.decode(a, _toks(ps // 2, 5))
    ls.trim(a, 0)                               # the window moved on
    ls.decode(a, _toks(ps, 6))                  # a page fills: not registered
    assert len(ls.new._by_hash) == 3
    ls.give_up(a)
    b = _Seq(_toks(3 * ps + 3, 7))              # a NULL below full pages
    ls.admit(b)
    ls.trim(b, 1)
    ls.decode(b, _toks(2, 8))
    assert ls.new._hash_of.keys() >= {b.pages[0]}
    assert b.pages[2] not in ls.new._hash_of    # unreachable above the gap
    ls.give_up(b)


def _shared_content(ls: _Lockstep, ps: int) -> None:
    a, b = _Seq(_toks(2 * ps + 3, 9)), _Seq(_toks(2 * ps + 3, 9))
    ls.admit(a)
    ls.admit(b)                                 # both miss: pages of their own
    ls.decode(a, _toks(1, 10))                  # a owns the two digests
    ls.decode(b, _toks(1, 11))                  # b's copies stay unregistered
    assert not set(b.pages) & set(ls.new._hash_of)
    for k in range(ps):                         # a third page each, by turns
        ls.decode(a, _toks(1, 100 + k))
        ls.decode(b, _toks(1, 200 + k))
    assert b.pages[2] in ls.new._hash_of
    ls.give_up(a)
    ls.give_up(b)


def _preempt_reregister(ls: _Lockstep, ps: int) -> None:
    a = _Seq(_toks(ps + ps // 2, 12))
    ls.admit(a)
    ls.decode(a, _toks(ps, 13))                 # 2 full pages, registered
    before = list(a.pages)
    ls.give_up(a)                               # preempted
    held = ls.alloc(3)                          # its old pages are not next
    ls.pressure()                               # and their content is gone
    assert ls.new.num_cached_pages == 0
    hashed = ls.new.hashed_tokens
    assert ls.admit(a, prefill=ps // 2) == 0    # a first window only
    assert a.pages[:2] != before[:2]
    ls.register(a)                              # preempted again mid-prefill
    assert ls.new.num_cached_pages == 0         # (no page is full yet)
    a.num_computed = len(a.tokens)              # the remaining windows
    ls.decode(a, _toks(ps, 14))                 # and another page fills
    assert ls.new.hashed_tokens == hashed + ps  # only that one was hashed
    assert [ls.new._by_hash[h] for h in a.digests] == a.pages[:3]
    ls.release(held)
    ls.give_up(a)


def _evict_other_owner(ls: _Lockstep, ps: int) -> None:
    a, b = _Seq(_toks(2 * ps + 3, 15)), _Seq(_toks(2 * ps + 3, 15))
    ls.admit(a)
    ls.admit(b)
    ls.decode(b, _toks(1, 16))                  # b owns the shared content
    ls.decode(a, _toks(2, 17))
    ls.give_up(b)                               # b's pages: cached, unowned
    ls.decode(a, _toks(2, 18))
    assert not set(a.pages) & set(ls.new._hash_of)
    ls.pressure()                               # b's mappings evicted
    ls.decode(a, _toks(1, 19))                  # a's next call takes them over
    assert [ls.new._by_hash[h] for h in a.digests] == a.pages[:2]
    ls.give_up(a)


@pytest.mark.parametrize("ps", [16, 128])
@pytest.mark.parametrize("scenario", [
    _plain, _swa_null_lead, _shared_content, _preempt_reregister,
    _evict_other_owner], ids=lambda f: f.__name__.strip("_"))
def test_sequence_digests_leave_the_index_as_the_full_rehash_does(
        scenario, ps):
    ls = _Lockstep(ps)
    scenario(ls, ps)
    # each page of each sequence went through the hash once, however
    # many tokens were sampled; the rehash fed it many times over
    assert ls.new.hashed_tokens == ps * sum(
        len(s.digests) for s in ls.seqs)
    assert ls.old.hashed_tokens > 2 * ls.new.hashed_tokens
    for s in ls.seqs:
        assert s.digests == ls.old.block_hashes(s.tokens)[:len(s.digests)]


# ---------------------------------------------------------------------------
# Registration from a row's watermark: the whole walk as the oracle
# ---------------------------------------------------------------------------

class _Watermark(_Lockstep):
    """What the engine does to its prefix index, done to two: ``new``
    registers from each row's watermark (``Sequence.pages_settled``),
    ``old`` by the whole walk. ``family``: ``plain``; ``window`` (a
    uniform window of three pages: the lead is trimmed); ``tails`` (the
    window layers' pool of their own, a tail at a finished prefill's
    boundary); ``state`` (a snapshot at a prompt's last full page). After
    every call both hold the same mappings, the same reclaimable pages in
    the same order, the same snapshots and tails, and have queued the
    same events."""

    def __init__(self, ps: int, family: str = "plain",
                 num_pages: int = 24) -> None:
        super().__init__(ps, num_pages)
        self.family = family
        self.window = 3 * ps if family in ("window", "tails") else 0
        for idx in (self.new, self.old):
            if family == "state":
                idx.enable_snapshots(SlotAllocator(1, 3))
            if family == "tails":
                idx.enable_tails(WindowPool(num_pages, tail_pages=2,
                                            max_tails=3))
        self.running: List[_Seq] = []
        self.waiting: List[_Seq] = []
        self.calls = self.oracle_walked = self.twins = self.trims = 0

    def both(self, f, seq: _Seq = None):
        """``f(index, the row's chain on that side)`` on both sides: the
        same answer."""
        a = f(self.new, seq.digests if seq else None)
        b = f(self.old, seq.oracle_digests if seq else None)
        assert a == b
        return a

    def check(self) -> None:
        super().check()
        n, o = self.new, self.old
        assert n._advertised == o._advertised
        assert n._snapshot_of == o._snapshot_of
        assert list(n._snapshots_unhit) == list(o._snapshots_unhit)
        assert list(n._snapshots_hit) == list(o._snapshots_hit)
        if n.tails is not None:
            assert n.tails._tails == o.tails._tails
            assert list(n.tails._unhit) == list(o.tails._unhit)
            assert n.tails.pages_live == o.tails.pages_live

    def release(self, pages: List[int]) -> None:
        super().release(pages)
        self.check()

    def pressure(self, n: int = 0) -> None:
        """``n`` pages taken and given back (0: every free and every
        reclaimable one): the oldest unowned mappings are evicted."""
        n = n or self.new.allocator.num_free + self.new.num_reclaimable
        n = min(n, self.new.allocator.num_free + self.new.num_reclaimable)
        self.release(self.alloc(n))

    def _alloc_row(self, n: int):
        """Engine._alloc_pages: the window pool first, then the full
        one; None and nothing held where either is short."""
        w: List[int] = []
        if self.family == "tails":
            w = self.both(lambda idx, _: idx.tails.alloc(n))
            if w is None:
                return None
        pages = self.both(lambda idx, _: idx.alloc(n))
        if pages is None and self.family == "tails":
            self.both(lambda idx, _: idx.tails.release(w))
        self.check()
        return None if pages is None else (pages, w)

    def lookup(self, seq: _Seq) -> int:
        """Engine._try_admit: the cached prefix by reference (settled: it
        was found under the row's own digests), pages for the rest and
        the token sampled next. -1: no room, the row waits."""
        if seq not in self.seqs:
            self.seqs.append(seq)
        hit, cached = self.both(
            lambda idx, d: idx.match_prefix(seq.prompt, d), seq)
        tail: List[int] = []
        if self.family == "tails" and hit:
            tail = list(self.new.tails.tail_of(hit[-1]))
            self.both(lambda idx, _: idx.tails.acquire(tail))
        got = self._alloc_row(-(-(len(seq.tokens) + 1) // self.ps)
                              - len(hit))
        if got is None:
            self.release(hit)
            if tail:
                self.both(lambda idx, _: idx.tails.release(tail))
            if seq not in self.waiting:
                self.waiting.append(seq)
            return -1
        seq.pages = hit + got[0]
        seq.settled = len(hit)
        if self.family == "tails":
            seq.trimmed = len(hit) - len(tail)
            seq.wpages = [0] * seq.trimmed + tail + got[1]
        seq.num_computed = cached
        self.running.append(seq)
        return cached

    def prefill(self, batch: List[_Seq], salt: int = 0) -> None:
        """One prefill program over the rest of each row's tokens, and
        Engine._run_prefill's post: a window family's tail BEFORE the
        first token (``salt``; 0: none is appended), a state family's
        snapshots after every row's."""
        ps = self.ps
        snaps = []
        for seq in batch:                       # Engine._state_cols
            boundary = len(seq.tokens) // ps
            if self.family == "state" and seq.num_computed < boundary * ps:
                snaps.append((seq, boundary - 1, self.both(
                    lambda idx, _: idx.reserve_snapshot())))
        for seq in batch:
            seq.num_computed = len(seq.tokens)
            boundary = seq.num_computed // ps
            if self.family == "tails":          # Engine._attach_tail
                self.register(seq)
                if boundary:
                    first = max(boundary - self.new.tails.tail_pages, 0)
                    self.both(lambda idx, d: idx.attach_tail(
                        d, boundary, seq.wpages[first:boundary]), seq)
                    self.check()
            if salt:
                self.decode(seq, _toks(1, salt))
        for seq, page, slot in snaps:           # Engine._attach_snapshots
            self.register(seq)
            self.both(lambda idx, d: idx.attach_snapshot(d[page], slot),
                      seq)
            self.check()

    def admit(self, seq: _Seq, prefill: int = 0) -> int:
        """A row admitted alone: ``prefill`` tokens past the hit are
        computed (a first window that registers nothing; 0: all of
        them)."""
        cached = self.lookup(seq)
        if cached >= 0 and prefill:
            seq.num_computed = cached + prefill
        elif cached >= 0:
            self.prefill([seq])
        return cached

    def register(self, seq: _Seq) -> None:
        n_full = seq.num_computed // self.ps
        seq.settled = self.new.register_pages(
            seq.digests, seq.tokens, seq.num_computed, seq.pages,
            seq.settled)
        self.oracle_walked += _register_by_whole_walk(
            self.old, seq.oracle_digests, seq.tokens, seq.num_computed,
            seq.pages)
        self.calls += 1
        assert seq.digests == seq.oracle_digests
        self.check()
        if seq.pages and seq.pages[0]:
            # the watermark is what it says it is ...
            assert seq.settled <= min(n_full, len(seq.pages))
            assert all(self.new._hash_of.get(seq.pages[i]) == seq.digests[i]
                       for i in range(seq.settled))
            # ... and stops only in front of a page whose content
            # another page owns (or of a NULL the engine never makes
            # above a live lead)
            if seq.settled < min(n_full, len(seq.pages)) \
                    and seq.pages[seq.settled]:
                self.twins += 1
                assert self.new._by_hash[seq.digests[seq.settled]] \
                    != seq.pages[seq.settled]

    def decode(self, seq: _Seq, toks: List[int]) -> None:
        """Engine._append_token, once a token: register, trim behind
        the window, grow the table (a row that finds no page is
        preempted)."""
        for tok in toks:
            seq.num_computed = len(seq.tokens)
            seq.tokens.append(tok)
            self.register(seq)
            self._swa_trim(seq)
            need = -(-(len(seq.tokens) + 1) // self.ps) - len(seq.pages)
            if need > 0:
                got = self._alloc_row(need)
                if got is None:
                    self.give_up(seq)
                    self.waiting.append(seq)
                    return
                seq.pages += got[0]
                seq.wpages += got[1]

    def _swa_trim(self, seq: _Seq) -> None:
        if not self.window:
            return
        pages = seq.pages if self.family == "window" else seq.wpages
        bound = min((seq.num_computed - self.window) // self.ps,
                    len(pages))
        if bound <= seq.trimmed:
            return
        gone = [p for p in pages[seq.trimmed:bound] if p]
        if self.family == "window":
            self.release(gone)
        else:
            self.both(lambda idx, _: idx.tails.release(gone))
        pages[seq.trimmed:bound] = [0] * (bound - seq.trimmed)
        seq.trimmed = bound
        self.trims += 1

    def give_up(self, seq: _Seq) -> None:
        """Engine._finish_seq and _preempt_seq: register, drop the pages
        and the watermark with them; tokens and digests stay."""
        super().give_up(seq)
        if self.family == "tails":          # Engine._release_window
            self.both(lambda idx, _: idx.tails.release(
                [p for p in seq.wpages if p]))
        seq.settled = seq.trimmed = 0
        seq.wpages = []
        if seq in self.running:
            self.running.remove(seq)
        self.check()


def _twin_settles_late(ls: _Watermark, ps: int) -> None:
    """Two rows prefill one content at once: the second's pages are
    twins, its watermark stays in front of them through every token, and
    it takes the content over at its first call after the owner's
    mappings are evicted."""
    a, b = _Seq(_toks(2 * ps + 1, 30)), _Seq(_toks(2 * ps + 1, 30))
    ls.admit(a)
    ls.admit(b)
    ls.decode(a, _toks(1, 31))
    ls.decode(b, _toks(ps, 32))                 # a page of b's own fills
    assert (a.settled, b.settled) == (2, 0)
    assert b.pages[2] in ls.new._hash_of        # registered past the twins
    ls.give_up(a)
    ls.pressure()
    ls.decode(b, _toks(1, 33))
    assert b.settled == 3
    assert [ls.new._by_hash[h] for h in b.digests] == b.pages[:3]
    ls.give_up(b)


def _tails(ls: _Watermark, ps: int) -> None:
    """A window family: a finished prefill's boundary keeps a tail, a
    follow-up resumes there with the document's pages settled, the
    cluster hears of blocks with their tail alone."""
    doc = _toks(4 * ps, 34)
    a = _Seq(doc + _toks(3, 35))
    assert ls.admit(a) == 0
    assert ls.new.tails.num_tails == 1
    ls.decode(a, _toks(2 * ps, 36))             # the window moves on
    ls.give_up(a)
    b = _Seq(doc + _toks(ps + 2, 37))
    assert ls.admit(b) == 4 * ps and b.trimmed == 2
    ls.decode(b, _toks(ps, 38))
    assert b.settled == 6
    ls.pressure(2)
    ls.give_up(b)
    ls.pressure()                               # pages go, tails with them
    assert ls.new.tails.num_tails == 0 and not ls.new._advertised


def _snapshots(ls: _Watermark, ps: int) -> None:
    """A state family: the page a snapshot is attached to is looked up
    right after registration, and a match ends at a page that has one."""
    doc = _toks(3 * ps, 39)
    a = _Seq(doc + _toks(2, 40))
    assert ls.admit(a) == 0
    assert ls.new.snapshot_of(a.pages[2])
    ls.decode(a, _toks(ps, 41))                 # a page with no snapshot
    ls.give_up(a)
    b = _Seq(a.tokens[:4 * ps] + _toks(1, 42))
    assert ls.admit(b) == 3 * ps and b.settled == 3
    ls.decode(b, _toks(2, 43))
    # a's fourth page is cached behind no snapshot: b wrote a copy of its
    # own, a twin until a's unowned mapping goes
    assert b.settled == 3 and b.pages[3] not in ls.new._hash_of
    ls.pressure()
    ls.decode(b, _toks(1, 47))
    assert b.settled == 4 and ls.new._hash_of[b.pages[3]] == b.digests[3]
    ls.give_up(b)
    page = ls.new._by_hash[b.digests[2]]
    for salt in (44, 45, 46):                   # three slots: never-hit
        c = _Seq(_toks(ps + 1, salt))           # ones make room
        ls.admit(c)
        ls.give_up(c)
    assert ls.new.snapshot_of(page) and ls.new.snapshots_evicted >= 2


def _seeded(seed: int):
    def schedule(ls: _Watermark, ps: int) -> None:
        """Iterations of an engine under a seeded draw: admissions (one
        to three rows at once, on five shared documents or one of their
        own: rows that prefill one document at once are twins), a token
        for every running row, finishes, preemptions and readmissions,
        pages taken and given back."""
        rng = random.Random(seed)
        docs = [_toks((2 + d) * ps + rng.randrange(ps), 50 + d)
                for d in range(5)]
        for it in range(120):
            draw = rng.random()
            if draw < 0.22 and len(ls.running) < 5:
                batch = []
                for _ in range(rng.choice((1, 1, 2, 3))):
                    if ls.waiting and rng.random() < 0.6:
                        batch.append(ls.waiting.pop(0))
                    elif rng.random() < 0.8:
                        batch.append(_Seq(docs[rng.randrange(5)] + _toks(
                            rng.randrange(1, ps), 1000 + it)))
                    else:
                        batch.append(_Seq(_toks(
                            rng.randrange(ps, 4 * ps), 2000 + it)))
                # looked up one after another, prefilled in one program
                ls.prefill([s for s in batch if ls.lookup(s) >= 0],
                           salt=3000 + it)
            elif draw < 0.80:
                for s in list(ls.running):
                    ls.decode(s, _toks(1, 4000 + it))
            elif draw < 0.90 and ls.running:
                ls.give_up(rng.choice(ls.running))
            elif draw < 0.95 and ls.running:
                s = rng.choice(ls.running)
                ls.give_up(s)                   # preempted: admitted again
                ls.waiting.append(s)
            else:
                ls.pressure(rng.randrange(1, 5))
        for s in list(ls.running):
            ls.give_up(s)
        # the draw met what it is for
        assert ls.twins
        assert ls.trims or not ls.window
        assert ls.family != "tails" or (ls.new.tails.tail_hits
                                        and ls.new.tails.tail_evictions)
        assert ls.family != "state" or ls.new.snapshots_evicted
    schedule.__name__ = f"seeded{seed}"
    return schedule


@pytest.mark.parametrize("family, scenario", [
    ("plain", _plain), ("plain", _swa_null_lead),
    ("plain", _shared_content), ("plain", _preempt_reregister),
    ("plain", _evict_other_owner), ("plain", _twin_settles_late),
    ("tails", _tails), ("state", _snapshots)] + [
    (family, _seeded(seed))
    for family in ("plain", "window", "tails", "state")
    for seed in (1, 2, 3)],
    ids=lambda v: v if isinstance(v, str) else v.__name__.strip("_"))
def test_registration_from_the_watermark_leaves_the_index_as_the_whole_walk_does(
        family, scenario):
    ps = 8
    ls = _Watermark(ps, family, num_pages=40)
    scenario(ls, ps)
    assert ls.calls and ls.new.hashed_tokens == ls.old.hashed_tokens
    # the watermark looked at the pages that filled, the walk at every
    # page of every row at every call
    assert ls.new.walked_pages <= ls.oracle_walked


# ---------------------------------------------------------------------------
# HostKvTier
# ---------------------------------------------------------------------------

def _blk(fill: float, shape=(2, 4, 2, 2)) -> Tuple[np.ndarray,
                                                   np.ndarray]:
    k = np.full(shape, fill, np.float32)
    return k, k + 1.0


class TestHostKvTier:
    def test_put_peek_pop_round_trip(self):
        tier = HostKvTier(capacity_bytes=1 << 20)
        k, v = _blk(3.0)
        assert tier.put(b"h1", k, v)
        got = tier.peek(b"h1")
        assert got is not None
        np.testing.assert_array_equal(got[0], k)
        np.testing.assert_array_equal(got[1], v)
        tier.pop(b"h1")
        assert tier.peek(b"h1") is None
        assert tier.spilled_blocks == 1 and tier.restored_blocks == 1

    def test_budget_lru_eviction_reports_removed(self):
        k, v = _blk(0.0)
        one = k.nbytes + v.nbytes
        tier = HostKvTier(capacity_bytes=2 * one)
        for i in range(3):
            assert tier.put(bytes([i]) * 16, *_blk(float(i)))
        assert tier.num_blocks == 2
        assert tier.peek(b"\x00" * 16) is None      # LRU victim
        ev = tier.drain_event()
        assert ev.removed == [b"\x00" * 16]

    def test_disk_demotion_round_trip(self, tmp_path):
        k, v = _blk(7.0)
        one = k.nbytes + v.nbytes
        tier = HostKvTier(capacity_bytes=one, disk_dir=str(tmp_path),
                          disk_capacity_bytes=4 * one)
        tier.put(b"a" * 16, k, v)
        tier.put(b"b" * 16, *_blk(8.0))             # demotes "a" to disk
        ev = tier.drain_event()
        assert ev.offloaded_ssd == [b"a" * 16] and not ev.removed
        got = tier.peek(b"a" * 16)                  # reads the file back
        assert got is not None
        np.testing.assert_array_equal(got[0], k)
        tier.pop(b"a" * 16)
        assert tier.peek(b"a" * 16) is None

    def test_oversized_block_rejected(self):
        tier = HostKvTier(capacity_bytes=8)
        assert not tier.put(b"big" * 6, *_blk(1.0))


# ---------------------------------------------------------------------------
# GlobalKVCacheMgr: tiers, replication, removal
# ---------------------------------------------------------------------------

def _digests(n: int) -> List[bytes]:
    return prefix_block_hashes(list(range(4 * n)), 4, 0)


class TestGlobalKVCacheMgr:
    def test_offload_and_promote_tiers(self, store):
        mgr = GlobalKVCacheMgr(store, block_size=4)
        hs = _digests(3)
        mgr.record_updated_kvcaches("w1", stored=hs)
        mgr.record_updated_kvcaches("w1", offloaded=[hs[1]])
        mgr.record_updated_kvcaches("w1", offloaded_ssd=[hs[2]])
        matched, scores, holders = mgr.match_prefix_tiers(
            list(range(12)) + [99])
        assert matched == 3
        assert holders["w1"] == [TIER_HBM, TIER_DRAM, TIER_SSD]
        # Restore promotes: stored supersedes the DRAM claim.
        mgr.record_updated_kvcaches("w1", stored=[hs[1]])
        _, _, holders = mgr.match_prefix_tiers(list(range(12)) + [99])
        assert holders["w1"][1] == TIER_HBM
        # Spill + restore inside ONE delta lands HBM (demotions first).
        mgr.record_updated_kvcaches("w1", stored=[hs[0]],
                                    offloaded=[hs[0]])
        _, _, holders = mgr.match_prefix_tiers(list(range(12)) + [99])
        assert holders["w1"][0] == TIER_HBM
        mgr.close()

    def test_bootstrap_and_watch_replication(self, store):
        master = GlobalKVCacheMgr(store, block_size=4, is_master=True)
        hs = _digests(2)
        master.record_updated_kvcaches("w1", stored=hs)
        assert master.upload_kvcache() == 2
        # Bootstrap: a replica booted later loads the persisted index.
        replica = GlobalKVCacheMgr(store, block_size=4, is_master=False)
        assert replica.num_blocks() == 2
        m, scores, _ = replica.match_prefix_tiers(list(range(8)) + [5])
        assert m == 2 and scores["w1"] == 2.0
        # Watch: later master uploads replicate without a reboot.
        more = prefix_block_hashes(list(range(50, 62)), 4, 0)
        master.record_updated_kvcaches("w2", stored=more)
        master.upload_kvcache()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and replica.num_blocks() < 5:
            time.sleep(0.02)
        assert replica.num_blocks() == 5
        master.close()
        replica.close()

    def test_remove_instance_uploads_dirty_delta(self, store):
        master = GlobalKVCacheMgr(store, block_size=4, is_master=True)
        hs = _digests(2)
        master.record_updated_kvcaches("w1", stored=hs)
        master.record_updated_kvcaches("w2", stored=[hs[0]])
        master.upload_kvcache()
        master.remove_instance("w1")
        # hs[1] was w1-only → store key deleted; hs[0] keeps w2.
        assert master.upload_kvcache() == 2
        replica = GlobalKVCacheMgr(store, block_size=4, is_master=False)
        assert replica.num_blocks() == 1
        _, scores, _ = replica.match_prefix_tiers(list(range(8)) + [5])
        assert scores == {"w2": 1.0}
        master.close()
        replica.close()


# ---------------------------------------------------------------------------
# Scheduler: fetch-vs-recompute planner + digest quarantine
# ---------------------------------------------------------------------------

class FakeControl:
    def __call__(self, address, path, body):
        return 200, {"ok": True}


def _register_and_beat(store, sched, name, page_size=4, seed=0,
                       block_bytes=1024, itype=InstanceType.PREFILL):
    meta = InstanceMetaInfo(name=name, rpc_address=name,
                            instance_type=itype, models=["tiny"],
                            page_size=page_size, hash_seed=seed,
                            kv_block_bytes=block_bytes)
    lid = store.lease_grant(5.0)
    store.put_json(instance_prefix(itype.value) + name, meta.to_json(),
                   lid)
    assert sched.handle_instance_heartbeat(Heartbeat(
        name=name, instance_type=itype, load=LoadMetrics(),
        latency=LatencyMetrics()))
    return lid


class TestFetchPlanner:
    def _sched(self, store, **kw):
        kw.setdefault("num_output_pools", 2)
        kw.setdefault("block_size", 4)
        return Scheduler(ServiceOptions(**kw), store,
                         control=FakeControl(), events=EventLog())

    def test_fetch_verdict_and_terms(self, store):
        sched = self._sched(store)
        try:
            _register_and_beat(store, sched, "holder")
            _register_and_beat(store, sched, "target")
            tokens = list(range(16)) + [99]         # 4 full blocks
            hs = prefix_block_hashes(tokens, 4, 0)
            sched.kvcache_mgr.record_updated_kvcaches(
                "holder", stored=hs[:3])
            # 4-token blocks recompute in ~1 ms at the fallback tok/s;
            # the default 5 ms fixed overhead would drown that at this
            # toy size — price the overhead realistically for it.
            sched.kv_fetch_overhead_ms = 0.5
            audit = {}
            plan = sched._plan_kv_fetch(tokens, "target", audit)
            assert plan == {"holder": "holder", "holder_addr": "holder",
                            "blocks": 3, "block_size": 4}
            t = audit["kv_fetch"]
            assert t["verdict"] == "fetch"
            assert t["holder_blocks"] == 3 and t["local_blocks"] == 0
            # Both cost terms present and coherent: fetch must have won.
            assert t["fetch_ms"] < t["recompute_ms"] or \
                t["recompute_ms"] == 0.0
            assert t["bandwidth_gbps"] > 0 and t["prefill_tok_s"] > 0
        finally:
            sched.stop()

    def test_partial_fetch_cuts_at_losing_tier(self, store, monkeypatch):
        # Make an SSD block lose: bytes big enough that the 0.25-rate
        # SSD fetch exceeds the per-block recompute cost, while
        # HBM-held blocks still win.
        sched = self._sched(store)
        try:
            _register_and_beat(store, sched, "holder",
                               block_bytes=500_000)
            _register_and_beat(store, sched, "target")
            tokens = list(range(16)) + [99]
            hs = prefix_block_hashes(tokens, 4, 0)
            sched.kvcache_mgr.record_updated_kvcaches(
                "holder", stored=hs[:3])
            sched.kvcache_mgr.record_updated_kvcaches(
                "holder", offloaded=[hs[2]], offloaded_ssd=[hs[2]])
            sched.kv_fetch_overhead_ms = 0.0
            audit = {}
            plan = sched._plan_kv_fetch(tokens, "target", audit)
            # recompute/block = 4/4000*1e3 = 1 ms; HBM fetch = 0.5 ms
            # (wins); SSD fetch = 2 ms (loses) → partial at 2 blocks.
            assert audit["kv_fetch"]["verdict"] == "partial"
            assert plan["blocks"] == 2
        finally:
            sched.stop()

    def test_local_holder_and_cold_prompt(self, store):
        sched = self._sched(store)
        try:
            _register_and_beat(store, sched, "target")
            tokens = list(range(16)) + [99]
            hs = prefix_block_hashes(tokens, 4, 0)
            audit = {}
            # Cold prompt: no decision at all (nothing to attribute).
            assert sched._plan_kv_fetch(tokens, "target", audit) is None
            assert "kv_fetch" not in audit
            # Target itself is the only holder → verdict local, no plan.
            sched.kvcache_mgr.record_updated_kvcaches(
                "target", stored=hs[:2])
            audit = {}
            assert sched._plan_kv_fetch(tokens, "target", audit) is None
            assert audit["kv_fetch"]["verdict"] == "local"
        finally:
            sched.stop()

    def test_digest_mismatch_quarantines_worker(self, store):
        events = EventLog()
        sched = Scheduler(ServiceOptions(num_output_pools=2,
                                         block_size=4), store,
                          control=FakeControl(), events=events)
        try:
            # Advertises page_size 8 against service block_size 4.
            _register_and_beat(store, sched, "bad", page_size=8)
            _register_and_beat(store, sched, "target")
            assert not sched.instance_mgr.digest_ok("bad")
            assert any(e["type"] == "cache_digest_mismatch"
                       for e in events.since(0))
            # Its heartbeat cache deltas are never ingested...
            tokens = list(range(16)) + [99]
            hs = prefix_block_hashes(tokens, 4, 0)
            sched.handle_instance_heartbeat(Heartbeat(
                name="bad", instance_type=InstanceType.PREFILL,
                cache_stored=[h.hex() for h in hs[:3]]))
            assert sched.kvcache_mgr.num_blocks() == 0
            # ...and even index entries (e.g. pre-mismatch) never make
            # it a holder.
            sched.kvcache_mgr.record_updated_kvcaches("bad",
                                                      stored=hs[:3])
            audit = {}
            assert sched._plan_kv_fetch(tokens, "target", audit) is None
            assert audit["kv_fetch"]["verdict"] == "recompute"
        finally:
            sched.stop()


# ---------------------------------------------------------------------------
# Engine-level spill/restore + export/adopt (tiny model, CPU)
# ---------------------------------------------------------------------------

def _tiny_engine(num_pages=16, spill_mb=64.0, seed=0, **kw):
    from xllm_service_tpu.runtime.engine import Engine
    cfg = ModelConfig.tiny()
    ecfg = EngineConfig(page_size=16, num_pages=num_pages,
                        max_model_len=256, max_batch_size=2,
                        max_prefill_tokens=256,
                        prefill_buckets=(32, 64, 128),
                        kv_spill_mb=spill_mb, **kw)
    return Engine(cfg, ecfg, seed=seed)


def _run(eng, prompt, rid, max_tokens=8):
    from xllm_service_tpu.runtime.engine import EngineRequest
    eng.add_request(EngineRequest(
        request_id=rid, token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=max_tokens, temperature=0.0,
                                ignore_eos=True)))
    toks = []
    while eng.has_work():
        for o in eng.step():
            if o.request_id == rid:
                toks.extend(o.new_token_ids)
    return toks


class TestEngineSpillRestore:
    def test_spill_restore_round_trip_byte_identical(self):
        """The acceptance spill test: evict past HBM capacity,
        re-request, pages restore from the DRAM tier, output
        byte-identical, restored_pages nonzero, heartbeat delta says
        offloaded (not removed) for the spilled digests. Rides the same
        engine: a spilled holder still serves its blocks to a remote
        fetcher (the DRAM tier is an export source too)."""
        eng = _tiny_engine()
        p1 = [7] * 5 + list(range(40))
        out1 = _run(eng, p1, "a")
        eng.drain_kvcache_event()                   # clear boot deltas
        # Pressure: a long prompt reclaims p1's cached pages.
        _run(eng, list(range(100, 330, 1))[:230], "b")
        stats = eng.prefix_cache_stats()
        assert stats["spilled_pages"] > 0
        ev = eng.drain_kvcache_event()
        assert ev.offloaded and not ev.removed
        # Export while spilled: tier-parked blocks are servable.
        hashes = eng.prefix_cache.block_hashes(p1)
        exported = eng.export_blocks(hashes[:2])
        assert exported is not None and exported[0] == 2
        out1b = _run(eng, p1, "c")
        assert out1b == out1
        stats = eng.prefix_cache_stats()
        assert stats["restored_pages"] > 0
        assert stats["hit_tokens_total"] >= 32
        # The restore re-stored the digests (promote at the index).
        ev = eng.drain_kvcache_event()
        assert ev.stored

    @pytest.mark.slow  # two pressure runs (~35 s); the standing
    # tier-1 gate for this class is xlint rule 15 (resource-leak),
    # which pins the try/finally shape statically on every run
    def test_failed_restore_releases_pins_pages_and_reparks_tier(
            self, monkeypatch):
        """xlint rule-15 finding (PR 9): a restore scatter that raises
        must unpin the chain's HBM members, send the freshly-alloc'd
        pages back to the allocator, and re-park the popped tier blocks
        — then the SAME prefix must still restore cleanly once the
        fault clears (byte-identical)."""
        eng = _tiny_engine()
        p1 = [7] * 5 + list(range(40))
        out1 = _run(eng, p1, "a")
        _run(eng, list(range(100, 330, 1))[:230], "b")   # force spill
        assert eng.prefix_cache_stats()["spilled_pages"] > 0
        idx = eng.prefix_cache

        def accounted_pages():
            # every page is free, referenced, or reclaimable-cached;
            # a leak shows up as a page in NONE of the three
            return (idx.allocator.num_free + len(idx._ref)
                    + len(idx._reclaimable))

        free_before = idx.allocator.num_free
        refs_before = dict(idx._ref)
        total_before = accounted_pages()
        hashes = idx.block_hashes(p1)
        tier_before = [h for h in hashes if h in eng.host_tier]
        assert tier_before, "pressure run never spilled p1's lead"

        real_scatter = eng._jit_kv_scatter

        def exploding_scatter(*a, **kw):
            raise RuntimeError("injected scatter failure")

        monkeypatch.setattr(eng, "_jit_kv_scatter", exploding_scatter)
        with pytest.raises(RuntimeError, match="injected scatter"):
            eng._restore_spilled(p1, [], 0, [])
        # no page vanished (the alloc's pressure-reclaim may have
        # legitimately evicted a reclaimable mapping — more free pages
        # are fine, fewer accounted ones are the leak)
        assert accounted_pages() == total_before
        assert idx.allocator.num_free >= free_before
        # no pins left behind: the ref book is exactly as before
        assert dict(idx._ref) == refs_before
        # every tier block the restore popped is re-parked
        assert all(h in eng.host_tier for h in tier_before)
        # fault cleared: the prefix restores and decodes byte-identical
        monkeypatch.setattr(eng, "_jit_kv_scatter", real_scatter)
        assert _run(eng, p1, "c") == out1

    def test_spill_off_by_default(self):
        eng = _tiny_engine(num_pages=8, spill_mb=0.0)
        assert eng.host_tier is None
        _run(eng, list(range(24)), "a", max_tokens=4)
        _run(eng, list(range(100, 205)), "b", max_tokens=4)
        assert eng.prefix_cache_stats()["spilled_pages"] == 0
        ev = eng.drain_kvcache_event()
        assert ev.removed and not ev.offloaded     # pre-tier behavior

    def test_export_adopt_blocks_cross_engine(self):
        """Holder exports a digest run; a second engine adopts it
        content-addressed and serves a byte-identical continuation
        without recomputing those pages. Exactly-once: re-adopting the
        same run maps nothing twice."""
        a = _tiny_engine(num_pages=32)
        b = _tiny_engine(num_pages=32)
        prompt = list(range(60, 60 + 40))           # 2 full pages
        out_a = _run(a, prompt, "a")
        hashes = a.prefix_cache.block_hashes(prompt)
        exported = a.export_blocks(hashes[:2])
        assert exported is not None
        n, k, v = exported
        assert n == 2 and k.shape[1] == 2
        assert b.adopt_blocks(prompt, 0, k, v) == 2
        assert b.fetched_blocks == 2
        pages, cached = b.prefix_cache.match_prefix(prompt + [9])
        assert cached == 32
        b.prefix_cache.release_pages(pages)
        before = b.prefix_cache.num_cached_pages
        # Exactly-once: a duplicate adopt registers no second mapping.
        assert b.adopt_blocks(prompt, 0, k, v) == 2
        assert b.prefix_cache.num_cached_pages == before
        out_b = _run(b, prompt, "b")
        assert out_b == out_a                       # fetched KV == real KV
        # num_cached_tokens surfaced on the engine's sequence ledger.
        assert b.prefix_hit_tokens >= 32
        # Unreachable chain refused: a run starting past a block the
        # adopter does not hold must never register (digests past a gap
        # are unreachable by match_prefix). Use a DIFFERENT prompt so
        # its chain head is genuinely absent on b.
        other = list(range(150, 150 + 40))
        _run(a, other, "c")
        oh = a.prefix_cache.block_hashes(other)
        n2, k2, v2 = a.export_blocks(oh[:2])
        before = b.prefix_cache.num_cached_pages
        assert b.adopt_blocks(other, 1, k2[:, 1:], v2[:, 1:]) == 0
        assert b.prefix_cache.num_cached_pages == before

    def test_fetch_behind_spilled_lead_restores_whole_chain(self):
        """The memory-pressure compound: a requester whose LEADING
        blocks sit in its spill tier adopts the holder's tail blocks
        (tier-resident leads count as chain coverage), and the admit's
        restore walks the mixed tier→HBM chain — the whole prefix is
        served, byte-identical."""
        a = _tiny_engine(num_pages=32)
        b = _tiny_engine(num_pages=16)
        prompt = list(range(60, 60 + 70))           # 4 full blocks
        out_a = _run(a, prompt, "a")
        hashes = a.prefix_cache.block_hashes(prompt)
        # Seed b with blocks 0-1 locally, then spill them to its tier.
        _, k01, v01 = a.export_blocks(hashes[:2])
        assert b.adopt_blocks(prompt, 0, k01, v01) == 2
        _run(b, list(range(300, 530))[:230], "p", max_tokens=4)
        assert b.prefix_cache_stats()["spilled_pages"] >= 2
        assert hashes[0] in b.host_tier and hashes[1] in b.host_tier
        # Adopt the tail with its lead in the TIER, not HBM.
        _, k23, v23 = a.export_blocks(hashes[:4])
        assert b.adopt_blocks(prompt, 2, k23[:, 2:], v23[:, 2:]) == 2
        out_b = _run(b, prompt, "b")
        assert out_b == out_a
        # The admit restored the tier leads AND picked up the adopted
        # HBM tail behind them: 4 blocks = 64 cached tokens.
        assert b.prefix_hit_tokens >= 64
