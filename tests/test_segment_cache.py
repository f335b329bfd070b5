"""The online engine's `PageAllocator` and its radix prefix cache:
admission, growth, preemption, deterministic recycling, refcounted
sharing, content-addressed attach, LRU eviction, and a randomized churn
stress with exact page accounting after every op."""
import numpy as np
import pytest

from repro.serving.segment_cache import PageAllocator


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


@pytest.mark.parametrize("n_tokens", [8, 10])
def test_page_allocator_without_tokens_shares_nothing(n_tokens):
    """A request admitted without tokens attaches no page, even when
    another request has published the same content, and gives back
    every page it took."""
    a = PageAllocator(n_pages=12, page_size=4)
    toks = list(range(n_tokens))
    a.admit(1, tokens=toks)
    a.ensure_capacity(1, n_tokens)
    a.release(1, tokens=toks)                   # full pages cached
    cached = a.n_cached_pages
    assert cached == n_tokens // 4
    assert a.admit(2) == 0
    assert a.pages[2] == [] and a.shared_len[2] == 0
    assert a.stats["prefix_hits"] == 0
    a.ensure_capacity(2, n_tokens)
    assert not set(a.pages[2]) & {n.page for n in a.match_radix(toks)}
    a.check_invariants()
    a.release(2)
    assert a.n_free == a.n_pages - a.reserved - cached
    a.flush_radix()
    assert a.n_free == a.n_pages - a.reserved
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
    # touch chain_a -> chain_b is now the LRU chain (the fed tokens run
    # one past the chain: the last fed token's page never attaches)
    a.admit(3, tokens=chain_a + [0])
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

    b = a.admit(2, tokens=chain[:5])            # attach first 2 pages
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
    assert a.admit(4, tokens=chain[:5]) == 4
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


@pytest.mark.parametrize("cached_len", [8, 12])
def test_radix_attach_leaves_last_token_private(cached_len):
    """A wholly cached, page-aligned prompt attaches every cached page
    but the one holding its last token: the prefill writes that token's
    KV, and it must land in a private page, not in one other requests
    read."""
    ps = 4
    a = PageAllocator(n_pages=12, page_size=ps)
    prompt = list(range(100, 100 + cached_len))
    a.admit(1, tokens=prompt)
    a.ensure_capacity(1, cached_len)
    a.release(1, tokens=prompt)
    cached = [n.page for n in a.match_radix(prompt)]
    assert len(cached) == cached_len // ps
    shared = a.admit(2, tokens=prompt)
    last_page = (cached_len - 1) // ps
    assert shared == last_page * ps             # ends before that page
    assert a.pages[2] == cached[:last_page]
    a.ensure_capacity(2, cached_len)
    assert a.pages[2][last_page] not in cached
    assert a.refcount[a.pages[2][last_page]] == 1
    a.check_invariants()


@pytest.mark.parametrize("order", [(1, 2), (2, 1)])
def test_radix_refcounts_survive_either_release_order(order):
    """Pages two live requests attached stay allocated until both
    release, then stay cached until the trie is flushed; the private
    pages each grew recycle on their own release."""
    a = PageAllocator(n_pages=12, page_size=8)
    prompt = list(range(17))                    # 2 full pages + 1
    a.admit(0, tokens=prompt)
    a.ensure_capacity(0, len(prompt))
    a.release(0, tokens=prompt)
    shared = [n.page for n in a.match_radix(prompt)]
    assert len(shared) == 2
    for rid in (1, 2):
        assert a.admit(rid, tokens=prompt) == 16
        a.ensure_capacity(rid, 24)              # private growth page
        assert a.pages[rid][:2] == shared
    assert a.pages[1][2] != a.pages[2][2]
    assert all(a.refcount[p] == 3 for p in shared)   # trie + 2 requests
    a.check_invariants()
    first, second = order
    a.release(first)
    assert all(a.refcount[p] == 2 for p in shared)
    assert not set(shared) & set(a.free_list)
    a.check_invariants()
    a.release(second)
    assert all(a.refcount[p] == 1 for p in shared)   # the trie's own
    assert a.n_free == a.n_pages - a.reserved - 2
    a.check_invariants()
    assert a.flush_radix() == 2
    assert a.n_free == a.n_pages - a.reserved
    a.check_invariants()
