"""Segment KV cache: unit tests + hypothesis property tests on the
allocator invariants (no overlap, coalesced free list, waiter progress),
a randomized admit/extend/release/preempt churn stress, and the
page-granular `PageAllocator` the online serving engine uses."""
import numpy as np
import pytest
from util import given, settings, st   # hypothesis, or a skip shim

from repro.serving.segment_cache import PageAllocator, SegmentCache


def test_admit_and_write():
    c = SegmentCache(max_tokens=1024, initial_segment=16, extend_chunk=16)
    assert c.admit(1, prompt_len=8, max_new=100)
    slots = [c.write_token(1) for _ in range(30)]
    assert all(s is not None for s in slots)
    assert len(set(slots)) == 30                  # distinct cache rows
    c.check_invariants()
    assert c.stats["extends"] >= 1                # grew past the first seg


def test_extend_prefers_adjacent():
    c = SegmentCache(max_tokens=1024, initial_segment=8, extend_chunk=8)
    c.admit(1, 4, 100)
    for _ in range(40):
        c.write_token(1)
    # single request: all growth should be in-place extension
    assert c.stats["appends"] == 0
    assert len(c.requests[1].segments) == 1
    c.check_invariants()


def test_append_when_blocked():
    c = SegmentCache(max_tokens=256, initial_segment=32, extend_chunk=32)
    c.admit(1, 8, 200)
    c.admit(2, 8, 200)     # sits right after request 1 -> blocks extension
    for _ in range(80):
        assert c.write_token(1) is not None
    assert c.stats["appends"] >= 1
    c.check_invariants()


def test_wait_and_revive():
    c = SegmentCache(max_tokens=80, initial_segment=32, extend_chunk=32)
    assert c.admit(1, 8, 100)           # 40 tokens
    assert c.admit(2, 8, 100)           # 40 tokens -> cache full
    # exhaust request 1's capacity; extension and append both impossible
    got_none = False
    for _ in range(200):
        if c.write_token(1) is None:
            got_none = True
            break
    assert got_none, "cache should eventually be exhausted"
    assert c.stats["waits"] >= 1
    revived = c.release(2)
    assert 1 in revived                 # waiter makes progress
    assert c.write_token(1) is not None
    c.check_invariants()


def test_prefix_caching_shares_segments():
    c = SegmentCache(max_tokens=4096, initial_segment=64, extend_chunk=64)
    c.admit(1, 32, 10)
    c.register_prefix(1, "sys-prompt")
    before_free = sum(l for _, l in c.free)
    c.admit(2, 32, 10, prefix_key="sys-prompt")
    c.admit(3, 32, 10, prefix_key="sys-prompt")
    assert c.stats["prefix_hits"] == 2
    # shared prefix: requests 2,3 allocated less fresh memory than req 1
    seg1 = c.requests[1].segments[0]
    assert seg1.refcount >= 3
    c.release(2)
    c.release(3)
    assert seg1.refcount >= 1           # still owned by request 1 + index
    c.check_invariants()


def _churn(seed: int, n_ops: int = 400, max_tokens: int = 256):
    """One deterministic admit/extend/release/preempt churn run.  Returns
    (cache, admission order, revived-waiter log) for cross-run
    comparison."""
    rs = np.random.RandomState(seed)
    c = SegmentCache(max_tokens=max_tokens, initial_segment=16,
                     extend_chunk=16)
    live, admitted, revived_log = [], [], []
    next_rid = 0
    for _ in range(n_ops):
        op = rs.randint(4)
        if op == 0:                                   # admit
            next_rid += 1
            if c.admit(next_rid, prompt_len=int(rs.randint(1, 16)),
                       max_new=int(rs.randint(1, 64))):
                live.append(next_rid)
                admitted.append(next_rid)
        elif op == 1 and live:                        # extend (write run)
            rid = live[rs.randint(len(live))]
            for _ in range(int(rs.randint(1, 24))):
                if c.write_token(rid) is None:
                    break
        elif op == 2 and live:                        # release
            rid = live.pop(rs.randint(len(live)))
            revived_log.append(tuple(c.release(rid)))
        elif op == 3 and live:                        # preempt
            rid = live.pop(rs.randint(len(live)))
            revived_log.append(tuple(c.preempt(rid)))
        c.check_invariants()
    for rid in list(live):
        c.release(rid)
    c.check_invariants()
    return c, admitted, revived_log


def test_churn_stress_admit_extend_release_preempt():
    """Randomized churn (incl. the new preempt path) never violates the
    allocator invariants, leaks no ranges, and replays identically —
    admissions AND the order waiters are revived in are deterministic."""
    for seed in (0, 1, 2):
        c, admitted, revived = _churn(seed)
        assert sum(l for _, l in c.free) == c.max_tokens   # nothing leaked
        assert not c.requests
        assert c.stats["preempts"] >= 1, "churn never preempted"
        c2, admitted2, revived2 = _churn(seed)
        assert admitted2 == admitted
        assert revived2 == revived


def test_preempt_frees_and_allows_readmission():
    c = SegmentCache(max_tokens=96, initial_segment=32, extend_chunk=32)
    assert c.admit(1, 8, 100)
    assert c.admit(2, 8, 100)
    for _ in range(20):
        assert c.write_token(1) is not None
    c.preempt(1)
    assert 1 not in c.requests
    assert c.stats["preempts"] == 1
    c.check_invariants()
    assert c.admit(1, 8, 100)          # deterministic re-admission works
    assert c.write_token(1) is not None
    c.check_invariants()


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 2), st.integers(1, 40)),
                min_size=1, max_size=60),
       st.integers(128, 512))
def test_allocator_invariants(ops, max_tokens):
    """Random admit/write/release sequences never violate the allocator
    invariants."""
    c = SegmentCache(max_tokens=max_tokens, initial_segment=16,
                     extend_chunk=16)
    rid = 0
    live = []
    for kind, arg in ops:
        if kind == 0:  # admit
            rid += 1
            if c.admit(rid, prompt_len=arg % 16 + 1, max_new=arg):
                live.append(rid)
        elif kind == 1 and live:  # write tokens
            r = live[arg % len(live)]
            for _ in range(arg):
                if c.write_token(r) is None:
                    break
        elif kind == 2 and live:  # release
            r = live.pop(arg % len(live))
            c.release(r)
        c.check_invariants()
    # drain
    for r in list(live):
        c.release(r)
    c.check_invariants()
    assert sum(l for _, l in c.free) == max_tokens   # all memory returned


# ---------------------------------------------------------------------------
# PageAllocator (online serving)
# ---------------------------------------------------------------------------


def test_page_allocator_admit_grow_release():
    a = PageAllocator(n_pages=9, page_size=8)
    assert a.admit(1) == 0
    assert a.ensure_capacity(1, 20)           # 3 pages
    assert a.capacity(1) == 24
    assert a.n_free == 5
    row = a.table_row(1, width=6)
    assert list(row[:3]) == a.pages[1] and not row[3:].any()
    assert 0 not in a.pages[1]                # scratch page never allocated
    a.check_invariants()
    a.release(1)
    assert a.n_free == 8
    a.check_invariants()


def test_page_allocator_all_or_nothing_and_preempt():
    a = PageAllocator(n_pages=5, page_size=8)   # 4 usable pages
    a.admit(1)
    a.admit(2)
    assert a.ensure_capacity(1, 24)             # 3 pages
    before = list(a.pages[2])
    assert not a.ensure_capacity(2, 24)         # needs 3, only 1 free
    assert a.pages[2] == before                 # failed grow allocated nothing
    assert a.stats["alloc_failures"] == 1
    a.preempt(1)
    assert a.stats["preempts"] == 1
    assert a.ensure_capacity(2, 24)             # victim's pages recycled
    a.check_invariants()


def test_page_allocator_prefix_sharing_refcounts():
    a = PageAllocator(n_pages=12, page_size=8)
    a.admit(1)
    a.ensure_capacity(1, 20)                    # 2 full pages + 1 partial
    a.register_prefix(1, "sys", 16)             # only FULL pages shared
    assert len(a.prefix_index["sys"]) == 2
    shared = a.admit(2, prefix_key="sys")
    assert shared == 16
    assert a.pages[2][:2] == a.pages[1][:2]
    assert a.stats["prefix_hits"] == 1
    a.ensure_capacity(2, 24)                    # private growth page
    assert a.pages[2][2] != a.pages[1][2]
    a.check_invariants()
    a.release(1)                                # shared pages stay (index+2)
    a.check_invariants()
    a.release(2)
    held = len(a.prefix_index["sys"])
    assert a.n_free == a.n_pages - a.reserved - held
    a.drop_prefix("sys")
    assert a.n_free == a.n_pages - a.reserved
    a.check_invariants()


def test_page_allocator_deterministic_recycling():
    """Identical op sequences hand out identical page ids (the engine's
    parity and compile-count tests rely on this)."""
    def run():
        a = PageAllocator(n_pages=8, page_size=4)
        ids = []
        a.admit(1); a.ensure_capacity(1, 10)
        a.admit(2); a.ensure_capacity(2, 6)
        ids.append(list(a.pages[1]) + list(a.pages[2]))
        a.preempt(1)
        a.admit(3); a.ensure_capacity(3, 12)
        ids.append(list(a.pages[3]))
        return ids
    assert run() == run()


def test_page_allocator_prefix_clamped_to_consumer_prompt():
    """A consumer whose prompt is shorter than the published prefix must
    not attach shared pages beyond its own prompt — its decode would
    write new-token KV straight into pages other requests attend."""
    a = PageAllocator(n_pages=12, page_size=8)
    a.admit(1)
    a.ensure_capacity(1, 24)
    a.register_prefix(1, "sys", 24)             # 3 full pages published
    shared = a.admit(2, prefix_key="sys", prompt_len=16)
    assert shared == 16                          # clamped, not 24
    assert len(a.pages[2]) == 2
    assert a.pages[2] == a.pages[1][:2]
    a.check_invariants()


# ---------------------------------------------------------------------------
# Radix prefix cache (content-addressed, LRU-evicted)
# ---------------------------------------------------------------------------


def test_radix_publish_match_attach():
    """Publish-on-release puts a request's full pages in the trie; a
    same-prefix admit attaches them refcounted; a diverging admit only
    matches the common page-aligned blocks."""
    a = PageAllocator(n_pages=12, page_size=4)
    toks = list(range(10))                      # 2 full pages + 2 tail
    a.admit(1, tokens=toks)
    assert a.shared_len[1] == 0                 # cold trie: no match
    a.ensure_capacity(1, len(toks))
    a.release(1, tokens=toks)
    assert a.n_cached_pages == 2                # only FULL pages published
    assert a.stats["published"] == 2

    shared = a.admit(2, tokens=toks)
    assert shared == 8                          # both cached pages attach
    assert a.stats["prefix_hits"] == 1
    assert a.stats["radix_hit_tokens"] == 8
    a.check_invariants()

    div = toks[:4] + [99, 98, 97, 96]           # diverges in block 2
    assert a.admit(3, tokens=div) == 4          # only block 1 matches
    a.ensure_capacity(3, len(div))              # private divergent page
    a.check_invariants()
    a.release(2, tokens=toks)                   # re-publish: pure dedup
    a.release(3, tokens=div)
    assert a.n_cached_pages == 3                # block1, block2, divergent
    a.flush_radix()
    assert a.n_free == a.n_pages - a.reserved
    a.check_invariants()


def test_radix_eviction_lru_leaf_first():
    """The sweep only takes childless unreferenced nodes, least recently
    used first — a chain dies tail-first, and touching a chain (via a
    fresh match) protects it over an untouched one."""
    a = PageAllocator(n_pages=12, page_size=2)
    chain_a = [1, 2, 3, 4, 5, 6]                # 3 pages
    chain_b = [7, 8, 9, 10]                     # 2 pages
    for rid, toks in ((1, chain_a), (2, chain_b)):
        a.admit(rid, tokens=toks)
        a.ensure_capacity(rid, len(toks))
        a.release(rid, tokens=toks)
    assert a.n_cached_pages == 5
    # touch chain_a -> chain_b is now the LRU chain
    a.admit(3, tokens=chain_a)
    a.release(3)
    assert a.evict_radix(1) == 1                # takes chain_b's LEAF
    assert [n.key for n in a.match_radix(chain_b)] == [(7, 8)]
    assert a.match_radix(chain_a) and len(a.match_radix(chain_a)) == 3
    a.check_invariants()
    # interior nodes become evictable as the subtree drains
    assert a.evict_radix(10) == 4               # everything else
    assert a.n_cached_pages == 0
    a.check_invariants()


def test_radix_eviction_mid_attach_adversarial():
    """Adversarial: a request is mid-flight holding attached cached pages
    (refcount 2) when pool pressure forces a full sweep.  The sweep may
    only take the unreferenced tail — the attached pages must survive,
    stay in the holder's table, AND stay in the trie."""
    a = PageAllocator(n_pages=8, page_size=2)   # 7 usable pages
    chain = list(range(10))                     # 5 pages
    a.admit(1, tokens=chain)
    a.ensure_capacity(1, len(chain))
    a.release(1, tokens=chain)
    assert a.n_cached_pages == 5

    b = a.admit(2, tokens=chain[:4])            # attach first 2 pages
    assert b == 4
    held = list(a.pages[2])
    # pool pressure: a new request wants 4 pages; only 2 are free, so
    # ensure_capacity sweeps the 3 unreferenced tail nodes
    a.admit(3, tokens=[50, 51])
    assert a.ensure_capacity(3, 8)
    assert a.stats["evictions"] == 2            # evicted only what it needed
    assert a.pages[2] == held                   # holder untouched
    assert [n.page for n in a.match_radix(chain[:4])] == held
    a.check_invariants()
    # the survivor keeps serving hits after the sweep
    assert a.admit(4, tokens=chain[:4]) == 4
    a.check_invariants()
    # drain everything; flush returns the pool to empty
    for rid in (2, 3, 4):
        a.release(rid)
    a.flush_radix()
    assert a.n_free == a.n_pages - a.reserved
    a.check_invariants()


def _radix_churn(seed: int, n_ops: int = 300, n_pages: int = 17,
                 page_size: int = 4, allocator=PageAllocator):
    """One deterministic radix-churn run mirroring engine usage: admit
    with content tokens (hot prefixes collide), grow, emit, publish on
    release/preempt, sweep under pressure, occasional flush.  Invariants
    after EVERY op plus explicit page accounting.  Returns a trace for
    replay comparison."""
    rs = np.random.RandomState(seed)
    a = allocator(n_pages=n_pages, page_size=page_size)
    hot = [list(rs.randint(0, 7, 8)), list(rs.randint(0, 7, 12)), []]
    live, trace, next_rid = {}, [], 0

    def account():
        a.check_invariants()
        attached = {p for pages in a.pages.values() for p in pages}
        cached = {n.page for n in a._iter_radix()}
        assert len(attached | cached) + a.n_free \
            == a.n_pages - a.reserved, "cached+live+free != pool"

    for _ in range(n_ops):
        op = rs.randint(6)
        if op <= 1:                                   # admit + grow
            next_rid += 1
            toks = (hot[rs.randint(3)]
                    + list(rs.randint(0, 7, rs.randint(1, 10))))
            shared = a.admit(next_rid, tokens=toks)
            trace.append(("admit", next_rid, shared))
            if a.ensure_capacity(next_rid, len(toks)):
                live[next_rid] = toks
            else:                                     # pool full: preempt
                victim = max(live) if live else None
                if victim is not None:
                    a.preempt(victim, tokens=live.pop(victim))
                    trace.append(("preempt", victim))
                a.release(next_rid)
                trace.append(("reject", next_rid))
        elif op == 2 and live:                        # decode-emit + grow
            rid = list(live)[rs.randint(len(live))]
            live[rid] = live[rid] + list(rs.randint(0, 7,
                                                    rs.randint(1, 6)))
            ok = a.ensure_capacity(rid, len(live[rid]))
            trace.append(("grow", rid, ok))
            if not ok:
                a.preempt(rid, tokens=live.pop(rid))
        elif op == 3 and live:                        # release-publish
            rid = list(live)[rs.randint(len(live))]
            a.release(rid, tokens=live.pop(rid))
            trace.append(("release", rid))
        elif op == 4 and live:                        # preempt-publish
            rid = list(live)[rs.randint(len(live))]
            a.preempt(rid, tokens=live.pop(rid))
            trace.append(("preempt", rid))
        elif op == 5:                                 # explicit sweep
            if rs.randint(4) == 0:
                trace.append(("flush", a.flush_radix()))
            else:
                trace.append(("evict", a.evict_radix(rs.randint(1, 4))))
        account()
    for rid in sorted(live):
        a.release(rid, tokens=live[rid])
        account()
    trace.append(("end", sorted(a.stats.items()), list(a.free_list)))
    return a, trace


def test_radix_churn_stress_and_replay():
    """Randomized radix churn: invariants + exact page accounting after
    every op, nothing leaks after a final flush, eviction/dedup paths
    actually exercised, and the whole run replays bit-identically from
    the seed (trace includes final stats AND free-list order)."""
    for seed in (0, 1, 2):
        a, trace = _radix_churn(seed)
        a.flush_radix()
        a.check_invariants()
        assert a.n_free == a.n_pages - a.reserved     # nothing leaked
        assert a.stats["prefix_hits"] > 0, "hot prefixes never hit"
        assert a.stats["evictions"] > 0, "churn never swept"
        _, trace2 = _radix_churn(seed)
        assert trace2 == trace, f"seed {seed} replay diverged"


class _SweepAllocator(PageAllocator):
    """The eviction rule written as a sweep: each page evicted is the
    least-recently-used (node_id breaking ties) childless cached page no
    one else references, found by walking the whole trie."""

    def evict_radix(self, n: int) -> int:
        freed = 0
        while freed < n:
            leaves = [node for node in self._iter_radix()
                      if not node.children and self.refcount[node.page] == 1]
            if not leaves:
                break
            best = min(leaves, key=lambda x: (x.last_used, x.node_id))
            self._drop_node(best)
            freed += 1
            self.stats["evictions"] += 1
        return freed


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_radix_eviction_order_is_the_sweeps(seed):
    """The queued eviction picks the same pages, in the same order, as a
    full sweep of the trie for every page: identical churn traces (page
    tables, stats and free-list order) on pools small enough to evict
    often."""
    for n_pages in (9, 17):
        _, fast = _radix_churn(seed, n_pages=n_pages)
        _, sweep = _radix_churn(seed, n_pages=n_pages,
                                allocator=_SweepAllocator)
        assert fast == sweep


def test_radix_ensure_capacity_evicts_before_failing():
    """Cached pages never cause an allocation failure an uncached run
    would not hit: ensure_capacity sweeps exactly the shortfall before
    reporting False."""
    a = PageAllocator(n_pages=6, page_size=2)   # 5 usable
    a.admit(1, tokens=list(range(8)))
    a.ensure_capacity(1, 8)                     # 4 pages
    a.release(1, tokens=list(range(8)))
    assert a.n_free == 1 and a.n_cached_pages == 4
    a.admit(2, tokens=[90, 91])
    assert a.ensure_capacity(2, 6)              # needs 3: sweeps 2 cached
    assert a.stats["evictions"] == 2
    assert a.stats["alloc_failures"] == 0
    a.check_invariants()
    # now ask for more than the whole pool: sweep everything, THEN fail
    assert not a.ensure_capacity(2, 99)
    assert a.n_cached_pages == 0
    assert a.stats["alloc_failures"] == 1
    a.check_invariants()


def test_page_allocator_reregister_prefix_releases_old():
    """Re-registering a key must drop the old entry's refcounts — the
    old pages return to the pool instead of leaking forever."""
    a = PageAllocator(n_pages=12, page_size=8)
    a.admit(1)
    a.ensure_capacity(1, 16)
    a.register_prefix(1, "sys", 16)
    a.admit(2)
    a.ensure_capacity(2, 16)
    a.register_prefix(2, "sys", 16)             # replaces the entry
    a.check_invariants()
    a.release(1)
    a.release(2)
    held = len(a.prefix_index["sys"])
    assert a.n_free == a.n_pages - a.reserved - held
    a.drop_prefix("sys")
    assert a.n_free == a.n_pages - a.reserved   # nothing leaked
    a.check_invariants()
