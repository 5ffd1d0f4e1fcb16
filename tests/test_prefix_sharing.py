"""Prefix-sharing paged KV: differential + property harness.

Three layers of proof for ``SharedPagedAllocator`` and its engine wiring:

* **property tests** — random interleavings of allocate / match-prefix /
  COW / register / free against an independent pure-Python oracle, with
  the allocator's own invariant pack checked after every op;
* **model-level bit-exactness** — chunked prefill over a partially
  pre-populated block table (shared prefix pages) equals cold prefill;
* **differential end-to-end** — identical request streams through
  ``PagedRealEngine`` (and the simulator ``DPEngine``) with sharing on vs
  off produce token-identical outputs and finish order, while the shared
  run allocates strictly fewer physical pages.
"""
import dataclasses
from collections import OrderedDict

from hypothesis import given, settings, strategies as st

import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer as tfm
from repro.serving import (PagedBlockAllocator, PagedRealEngine,
                           RealClusterConfig, Request, RequestState,
                           SharedPagedAllocator, serve_real_cluster)


# ================================================================ oracle
class _ONode:
    """Oracle radix node: a token span within one page slot."""

    def __init__(self, tokens, page, depth, parent):
        self.tokens = list(tokens)
        self.page = page
        self.depth = depth
        self.parent = parent
        self.children = []

    @property
    def end(self):
        return self.depth + len(self.tokens)


class RadixOracle:
    """Independent model of the radix prefix-sharing allocator semantics.

    Pages are opaque objects — no free-list ids, no BlockPool books, no
    index dictionaries. The differential property test compares aggregate
    observables (free capacity, token-granular match lengths, COW counts,
    table sizes, cache size) after every operation, while
    ``check_invariants`` covers the impl's internal books.
    """

    def __init__(self, n_pages, page_size):
        self.n, self.ps = n_pages, page_size
        self.free = n_pages            # free + reclaimable cached
        self._nfree = n_pages          # never-cached free pages
        self.refs = {}                 # page-obj -> refcount (>= 1)
        self.node_of = {}              # page-obj -> node (indexed pages)
        self.cached = OrderedDict()    # refcount-0 indexed pages (LRU)
        self.tables = {}
        self.root = _ONode([], None, 0, None)

    @staticmethod
    def _cp(a, b):
        n = min(len(a), len(b))
        i = 0
        while i < n and a[i] == b[i]:
            i += 1
        return i

    def _best(self, node, tokens, d):
        best, best_cp = None, 0
        for c in node.children:
            cp = self._cp(c.tokens, tokens[d:d + len(c.tokens)])
            if cp > best_cp:
                best, best_cp = c, cp
        return best, best_cp

    def _evict(self, node):
        node.parent.children.remove(node)
        stack = [node]
        while stack:
            n = stack.pop()
            stack.extend(n.children)
            del self.node_of[n.page]
            if n.page in self.cached:
                del self.cached[n.page]
                if n is not node:
                    self._nfree += 1

    def _take(self):
        if self._nfree > 0:
            self._nfree -= 1
            return object()
        for p in self.cached:          # LRU leaf first
            if not self.node_of[p].children:
                self._evict(self.node_of[p])
                return p
        p = next(iter(self.cached))    # all interior: subtree goes with it
        self._evict(self.node_of[p])
        return p

    def _unref(self, p):
        self.refs[p] -= 1
        if self.refs[p] == 0:
            del self.refs[p]
            if p in self.node_of:
                self.cached[p] = None
            else:
                self._nfree += 1
            self.free += 1

    def allocate(self, rid, tokens):
        t = self.tables.get(rid, [])
        need = -(-max(tokens, 1) // self.ps) - len(t)
        if need <= 0:
            return True
        if need > self.free:
            return False
        for _ in range(need):
            p = self._take()
            self.refs[p] = 1
            self.tables.setdefault(rid, []).append(p)
        self.free -= need
        return True

    def match(self, rid, tokens):
        if self.tables.get(rid):
            return 0
        node, d = self.root, 0
        slot = {}
        while d < len(tokens):
            c, cp = self._best(node, tokens, d)
            if c is None or cp == 0:
                break
            slot[c.depth // self.ps] = c.page
            if c.page in self.cached:
                self.cached.move_to_end(c.page)
            d = c.depth + cp
            if cp < len(c.tokens):
                break
            node = c
        if d == 0:
            return 0
        table = [slot[k] for k in range((d - 1) // self.ps + 1)]
        for p in table:
            if p in self.cached:
                del self.cached[p]
                self.refs[p] = 1
                self.free -= 1
            else:
                self.refs[p] += 1
        self.tables[rid] = table
        return d

    def register(self, rid, tokens):
        table = self.tables.get(rid, [])
        limit = min(len(tokens), len(table) * self.ps)
        node, d = self.root, 0
        while d < limit:
            c, cp = self._best(node, tokens, d)
            if c is not None and cp == len(c.tokens):
                node = c
                d += cp
                continue
            end = min((d // self.ps + 1) * self.ps, limit)
            span = list(tokens[d:end])
            if c is not None and cp == len(span):
                break
            page = table[d // self.ps]
            if page in self.node_of:
                break
            new = _ONode(span, page, d, node)
            node.children.append(new)
            self.node_of[page] = new
            node = new
            d = end

    def prepare_write(self, rid, lo_tok, hi_tok):
        """Returns the COW copy count, or None on OOM (mirrors impl)."""
        if hi_tok <= lo_tok:
            return 0
        t = self.tables.get(rid, [])
        lo = lo_tok // self.ps
        hi = min(-(-hi_tok // self.ps), len(t))
        idxs = [i for i in range(lo, hi)
                if self.refs[t[i]] > 1 or t[i] in self.node_of]
        if not idxs:
            return 0
        if len(idxs) > self.free:
            return None
        for i in idxs:
            dst = self._take()
            self.refs[dst] = 1
            self.free -= 1
            self._unref(t[i])
            t[i] = dst
        return len(idxs)

    def free_req(self, rid):
        for p in self.tables.pop(rid, []):
            self._unref(p)


# ================================================================ properties
N_PAGES, PS = 12, 4

# prompts engineered for heavy prefix collision at TOKEN granularity:
# full duplicates, shared prefixes ending mid-page (13, 9), page-aligned
# prefixes, and one unshared prompt
_BASE = list(range(40))
_PROMPTS = [_BASE[:24], _BASE[:24], _BASE[:13] + [77] * 11,
            _BASE[:9] + [88] * 7, [5] * 20, _BASE[:18]]
# deterministic per-rid decode streams: finish-time registration indexes
# them, and re-admissions of the same rid query prompt+stream prefixes —
# the n-gram continuation-reuse path
_GENS = [[900 + 50 * i + j for j in range(16)] for i in range(6)]


def _impl_counts(a):
    return (a.free_blocks, a.n_cached,
            {r: len(t) for r, t in a.tables.items() if t})


def _oracle_counts(o):
    return (o.free, len(o.cached),
            {r: len(t) for r, t in o.tables.items() if t})


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 5),
                          st.integers(1, 12)),
                min_size=1, max_size=60))
def test_shared_allocator_matches_oracle(ops):
    """Random interleavings of admit / chunk / decode / finish+register /
    preempt / failing-allocate: token-granular match lengths and the books
    track the independent radix oracle, and the invariant pack (including
    tree reachability — eviction never strands a cached descendant) holds
    after every single operation."""
    a = SharedPagedAllocator(N_PAGES, page_size=PS)
    o = RadixOracle(N_PAGES, PS)
    state = {}   # rid -> {"q": query tokens, "done": int, "gen": int}

    def check():
        a.check_invariants()
        assert _impl_counts(a) == _oracle_counts(o)

    for op, rid, amt in ops:
        base = _PROMPTS[rid % len(_PROMPTS)]
        if op == 0 and rid not in state:          # admit: match + 1st chunk
            # some admissions extend the prompt with the rid's decode
            # stream — hits past the original prompt once it finished
            q = base + _GENS[rid % len(_GENS)][:amt % 4]
            m = a.match_prefix(rid, q)
            assert m == o.match(rid, q)
            assert 0 <= m <= len(q)               # token-granular: any value
            done = min(m, len(q) - 1)
            first = min(len(q) - done, 2 * PS)
            ok = a.allocate(rid, done + first)
            assert ok == o.allocate(rid, done + first)
            if ok:
                state[rid] = {"q": q, "done": done, "gen": 0}
            else:
                a.free(rid)
                o.free_req(rid)
        elif op == 1 and rid in state \
                and state[rid]["done"] < len(state[rid]["q"]):
            q, done = state[rid]["q"], state[rid]["done"]
            chunk = min(amt, len(q) - done)       # prefill one chunk
            ok = a.allocate(rid, done + chunk)
            assert ok == o.allocate(rid, done + chunk)
            if ok:
                cw = a.prepare_write(rid, done, done + chunk)
                cwo = o.prepare_write(rid, done, done + chunk)
                assert (cw is None) == (cwo is None)
                if cw is not None:
                    assert len(cw) == cwo
                    assert all(s != d for s, d in cw)
                    state[rid]["done"] = done + chunk
                    # unfloored: deliberately index the partial tail page
                    # (harsher than the engines, which floor mid-life) to
                    # stress token-granular registration + COW-on-reentry
                    a.register_prefix(rid, q[:done + chunk])
                    o.register(rid, q[:done + chunk])
        elif op == 2 and rid in state \
                and state[rid]["done"] >= len(state[rid]["q"]) - 1 \
                and state[rid]["gen"] < 10:       # decode one token
            pos = len(state[rid]["q"]) + state[rid]["gen"]
            ok = a.allocate(rid, pos + 1)
            assert ok == o.allocate(rid, pos + 1)
            if ok:
                cw = a.prepare_write(rid, pos, pos + 1)
                cwo = o.prepare_write(rid, pos, pos + 1)
                assert (cw is None) == (cwo is None)
                if cw is not None:
                    assert len(cw) == cwo
                    state[rid]["gen"] += 1
        elif op == 3 and rid in state:            # finish: register + free
            s = state.pop(rid)
            j0 = len(s["q"]) - len(base)          # stream continuation point
            seq = s["q"] + _GENS[rid % len(_GENS)][j0:j0 + s["gen"]]
            a.register_prefix(rid, seq)
            o.register(rid, seq)
            a.free(rid)
            o.free_req(rid)
        elif op == 4:                             # failing allocate: atomic
            snap = (a.free_blocks, list(a._free_ids),
                    {r: list(t) for r, t in a.tables.items()},
                    dict(a._held), dict(a.refcount),
                    list(a._cached), set(a._page_node))
            assert not a.allocate(rid, (N_PAGES + 1 + len(
                a.tables.get(rid, []))) * PS)
            assert snap == (a.free_blocks, list(a._free_ids),
                            {r: list(t) for r, t in a.tables.items()},
                            dict(a._held), dict(a.refcount),
                            list(a._cached), set(a._page_node))
        elif op == 5 and rid in state:            # preempt: free, no index
            a.free(rid)
            o.free_req(rid)
            state.pop(rid)
        check()

    for rid in list(state):
        a.free(rid)
        o.free_req(rid)
        check()
    assert a.free_blocks == N_PAGES               # all capacity reclaimable
    assert a.pages_in_use == 0


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 1), st.integers(0, 5),
                          st.integers(1, 24)),
                min_size=1, max_size=40))
def test_match_equals_bruteforce_longest_prefix(ops):
    """Tree-free cross-check: in the no-eviction regime the radix walk
    must return EXACTLY the longest common token prefix between the query
    and any registered sequence — computed here by brute force over a
    plain list, sharing no code or structure with the tree. (RadixOracle
    mirrors the algorithm to pin down the capacity books under eviction;
    this oracle is the independent check on the matching logic itself.)"""
    a = SharedPagedAllocator(512, page_size=4)    # roomy: never evicts
    registered = []
    rid = 0
    for op, which, amt in ops:
        rid += 1
        seq = (_PROMPTS[which % len(_PROMPTS)]
               + _GENS[which % len(_GENS)])[:amt]
        if op == 0:                               # register a fresh copy
            assert a.allocate(rid, len(seq))
            a.register_prefix(rid, seq)
            registered.append(list(seq))
        else:                                     # query
            m = a.match_prefix(rid, seq)
            want = 0
            for s in registered:
                cp = 0
                while cp < min(len(s), len(seq)) and s[cp] == seq[cp]:
                    cp += 1
                want = max(want, cp)
            assert m == want, (seq, registered)
            a.free(rid)
        a.check_invariants()
    assert a.stat_evictions == 0                  # premise of the oracle


def test_failed_admission_rolls_back_hit_stats():
    """A match whose follow-up allocate fails is released WITH its
    telemetry: a request retrying admission every step under KV pressure
    must not inflate stat_hit_tokens for prefill it never skipped."""
    a = SharedPagedAllocator(2, page_size=4)
    P = list(range(8))
    assert a.allocate(1, 8)                       # whole pool
    a.register_prefix(1, P)
    m = a.match_prefix(2, P + [9] * 8)
    assert m == 8                                 # shared pages attach fine
    assert not a.allocate(2, 12)                  # but the tail has no room
    a.release_match(2)
    a.check_invariants()
    assert a.stat_hit_tokens == 0
    assert a.stat_hit_pages == 0
    assert a.stat_hit_tokens_page == 0
    # the cache itself is intact — a later retry still matches
    assert a.match_prefix(2, P) == 8
    assert a.stat_hit_tokens == 8
    a.free(2)
    a.free(1)
    a.check_invariants()


@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 1), st.integers(0, 5),
                          st.integers(1, 70)),
                min_size=1, max_size=40))
def test_failed_allocate_is_atomic_both_allocators(ops):
    """Interleaved successful/failing allocates and frees: a failed
    allocate leaves ``_free_ids``, ``tables`` and the BlockPool books
    untouched, for the plain and the sharing allocator alike."""
    for cls in (PagedBlockAllocator, SharedPagedAllocator):
        a = cls(8, page_size=4)
        held = {}
        for op, rid, tok in ops:
            if op == 0:
                snap = (a.free_blocks, list(a._free_ids),
                        {r: list(t) for r, t in a.tables.items()},
                        dict(a._held))
                want = held.get(rid, 0) + tok
                if not a.allocate(rid, want):
                    assert snap == (a.free_blocks, list(a._free_ids),
                                    {r: list(t)
                                     for r, t in a.tables.items()},
                                    dict(a._held))
                else:
                    held[rid] = want
            elif rid in held:
                a.free(rid)
                held.pop(rid)
            a.check_invariants()


def test_free_does_not_reclaim_peer_pages():
    """Preempting/freeing one sharer must not free pages still referenced
    by peers, nor hand them to a third request."""
    a = SharedPagedAllocator(8, page_size=4)
    P = list(range(12))
    assert a.allocate(1, 12)
    a.register_prefix(1, P)
    assert a.match_prefix(2, P) == 12
    t2 = list(a.table_of(2))
    a.free(1)                      # preempt the original owner
    a.check_invariants()
    assert a.table_of(2) == t2
    assert all(a.refcount[p] == 1 for p in t2)
    assert a.free_blocks == 5      # 3 pages still held by request 2
    assert a.allocate(3, 20)       # exactly the 5 actually-free pages
    a.check_invariants()
    assert not set(a.table_of(3)) & set(t2), "peer page double-booked"


def test_cow_preserves_cached_content_page():
    """A write into an indexed page diverts to a private copy; the cached
    original stays matchable afterwards."""
    a = SharedPagedAllocator(8, page_size=4)
    P = list(range(8))
    assert a.allocate(1, 8)
    a.register_prefix(1, P)
    assert a.match_prefix(2, P) == 8           # full-prompt hit
    shared_last = a.table_of(2)[1]
    cw = a.prepare_write(2, 7, 8)              # recompute last prompt token
    assert len(cw) == 1 and cw[0][0] == shared_last
    assert a.table_of(2)[1] == cw[0][1] != shared_last
    assert a.table_of(1)[1] == shared_last     # owner untouched
    a.free(1)
    a.free(2)
    a.check_invariants()
    assert a.match_prefix(3, P) == 8           # chain survived the COW
    a.check_invariants()


def test_token_granular_matching():
    """Radix matching is token-granular: partial-page prompt tails match,
    mid-page divergence matches up to the first differing token, and a
    request with a non-empty table re-matches as a defined no-op (the
    resume-after-preemption path)."""
    a = SharedPagedAllocator(16, page_size=4)
    P = list(range(13))                        # 3 full pages + 1-token tail
    assert a.allocate(1, 13)
    a.register_prefix(1, P)
    a.check_invariants()

    assert a.match_prefix(2, P) == 13          # full incl. the partial tail
    assert len(a.table_of(2)) == 4
    a.check_invariants()
    assert a.match_prefix(2, P) == 0           # non-empty table: no-op, not
    assert len(a.table_of(2)) == 4             # an assertion failure
    a.free(2)

    assert a.match_prefix(3, P[:10] + [99, 99]) == 10   # mid-page diverge
    assert len(a.table_of(3)) == 3
    a.check_invariants()
    a.free(3)

    assert a.match_prefix(4, [7] * 8) == 0     # unshared prompt
    a.free(1)
    a.check_invariants()
    # strict domination over full-page matching is visible in the books
    assert a.stat_hit_tokens == 13 + 10
    assert a.stat_hit_tokens_page == 12 + 8
    assert a.stat_hit_tokens > a.stat_hit_tokens_page


def test_ngram_continuation_reuse():
    """Decode-generated pages registered at finish are matchable: a prompt
    that continues a finished request's token stream hits past the original
    prompt length."""
    a = SharedPagedAllocator(16, page_size=4)
    prompt, gen = list(range(10)), [500, 501, 502, 503, 504]
    assert a.allocate(1, 15)
    a.register_prefix(1, prompt + gen)         # finish-time registration
    a.free(1)
    a.check_invariants()

    m = a.match_prefix(2, prompt + gen[:3] + [9999])
    assert m == 13                             # past the 10-token prompt
    a.check_invariants()
    a.free(2)
    a.check_invariants()


def test_eviction_never_strands_cached_descendants():
    """LRU eviction prefers leaves; when only interior pages are cached,
    the subtree goes with them — afterwards every cached page must still
    be reachable from the root (the invariant pack checks reachability),
    and ancestors keep matching after a leaf eviction."""
    a = SharedPagedAllocator(6, page_size=4)
    P = list(range(16))                        # chain of 4 nodes
    assert a.allocate(1, 16)
    a.register_prefix(1, P)
    a.free(1)                                  # 4 cached pages, 2 free
    assert a.n_cached == 4

    # taking 3 pages: 2 free + 1 evicted — must be the deepest LRU leaf
    assert a.allocate(2, 12)
    a.check_invariants()
    assert a.stat_evictions == 1
    assert a.match_prefix(3, P) == 12          # ancestors survived
    a.check_invariants()
    a.free(3)

    # interior-page pressure: allocate everything reclaimable
    a.free(2)
    a.check_invariants()
    assert a.allocate(4, 6 * 4)                # whole pool: evicts the rest
    a.check_invariants()                       # reachability holds per-op
    assert a.n_cached == 0
    assert a.match_prefix(5, P) == 0           # tree fully evicted, cleanly
    a.free(4)
    a.check_invariants()


def test_interior_eviction_deindexes_live_descendants():
    """When every cached page is an interior node (live descendants pin
    the leaves), eviction takes the LRU subtree: the cached ancestor is
    reclaimed and live descendants merely lose their index entry — their
    owners keep them, and they return to the free list (not the cache)
    when finally released."""
    a = SharedPagedAllocator(4, page_size=4)
    P = list(range(8))
    assert a.allocate(1, 8)
    a.register_prefix(1, P)
    # COW the FIRST page: its node becomes a cached *interior* node whose
    # child (the second page) is live and still indexed
    cw = a.prepare_write(1, 0, 1)
    assert len(cw) == 1
    a.check_invariants()
    assert a.n_cached == 1
    assert a.free_blocks == 2                  # 1 free + 1 reclaimable
    # demand both reclaimable pages: no cached leaf exists, so the
    # interior page goes with its subtree
    assert a.allocate(2, 8)
    a.check_invariants()
    assert a.n_cached == 0
    assert len(a.table_of(1)) == 2             # live descendant untouched
    a.free(1)
    a.free(2)
    a.check_invariants()
    assert a.free_blocks == 4                  # de-indexed page -> free list
    assert a.n_cached == 0


# ================================================== decode-cache policy knobs
def test_register_ttl_expires_decode_entries():
    """Finish-time registrations stamped with a TTL are swept by
    ``expire_registrations``; entries registered without one (the prompt
    index) are permanent. Books stay balanced through the sweep."""
    a = SharedPagedAllocator(16, page_size=4)
    prompt, gen = list(range(8)), [500, 501, 502, 503]
    assert a.allocate(1, 12)
    a.register_prefix(1, prompt)                     # permanent
    a.register_prefix(1, prompt + gen, expires_at=1.0)   # decode tail
    a.free(1)
    a.check_invariants()
    m = a.match_prefix(2, prompt + gen)
    assert m == 12
    a.free(2)

    assert a.expire_registrations(0.5) == 0          # not due yet
    assert a.match_prefix(3, prompt + gen) == 12
    a.free(3)

    assert a.expire_registrations(1.5) == 1          # the gen node only
    a.check_invariants()
    assert a.stat_expirations == 1
    assert a.match_prefix(4, prompt + gen) == 8      # prompt still indexed
    a.free(4)
    a.check_invariants()


def test_expired_live_page_only_loses_its_index_entry():
    """Sweeping an expired entry whose page a live request still holds
    must de-index it without touching the owner's table."""
    a = SharedPagedAllocator(16, page_size=4)
    toks = list(range(8))
    assert a.allocate(1, 8)
    a.register_prefix(1, toks, expires_at=1.0)
    assert a.expire_registrations(2.0) == 2
    a.check_invariants()
    assert len(a.table_of(1)) == 2                   # owner unaffected
    assert a.match_prefix(2, toks) == 0              # but unmatchable now
    a.free(1)
    a.check_invariants()
    assert a.free_blocks == 16                       # nothing cached


def test_decode_register_policy_knobs(tiny_model, shared_runner):
    """PagedEngineConfig policy knobs for finish-time radix registration:
    default registers prompt+generated token-granular (n-gram reuse),
    ``register_decode_tokens=False`` registers the prompt only,
    ``min_register_len`` gates short sequences out entirely (leaving the
    page-floored mid-life prompt registration), and ``register_ttl_s``
    expires the finish-time entries on a later step."""
    cfg, params = tiny_model
    base = dataclasses.replace(shared_runner.ecfg, n_pages=32,
                               prefix_sharing=True)
    prompt = np.random.default_rng(33).integers(
        0, cfg.vocab_size, 10).tolist()

    def serve(**kw):
        e = PagedRealEngine(0, cfg, params, dataclasses.replace(base, **kw),
                            runner=shared_runner, n_sources=2)
        r = Request(req_id=0, prompt_len=10, max_new_tokens=4,
                    arrival_time=0.0, prompt_tokens=list(prompt))
        _drive_arrivals(e, [r])
        assert r.state is RequestState.FINISHED and not r.error
        return e, r

    def probe_match(e, toks):
        m = e.pool.match_prefix(999, toks)
        e.pool.release_match(999)
        e.pool.check_invariants()
        return m

    # default: prompt + generated, token-granular, capped at written KV
    # (10 prompt + 4 generated, newest sampled token never written -> 13)
    e, r = serve()
    probe = prompt + list(r.output_tokens)
    assert probe_match(e, probe) == 13

    # per-engine opt-out: the full prompt still registers (token-granular
    # at finish), generated tokens never do
    e, r = serve(register_decode_tokens=False)
    assert probe_match(e, prompt + list(r.output_tokens)) == 10

    # min length: finish-time registration skipped below the threshold —
    # only the page-floored mid-life prompt registration remains
    e, r = serve(min_register_len=64)
    assert probe_match(e, prompt + list(r.output_tokens)) == 8

    # the gate measures the sequence actually registered: with the
    # decode opt-out the prompt-only entry (10 tokens) is below a
    # threshold the prompt+generated length (13) would have passed
    e, r = serve(register_decode_tokens=False, min_register_len=12)
    assert probe_match(e, prompt + list(r.output_tokens)) == 8

    # TTL: finish-time entries expire on a later (even idle) step; the
    # mid-life page-aligned prompt entries are permanent
    e, r = serve(register_ttl_s=0.5)
    assert probe_match(e, prompt + list(r.output_tokens)) == 13
    e.step(r.finish_time + 1.0)       # idle step runs the expiry sweep
    e.pool.check_invariants()
    assert e.pool.stat_expirations > 0
    assert probe_match(e, prompt + list(r.output_tokens)) == 8


# ================================================================ model level
def test_partial_table_chunked_prefill_bit_exact(tiny_model):
    """Chunked prefill over a partially pre-populated block table (the
    matched-prefix path) is bit-exact vs the cold chunked prefill."""
    cfg, params = tiny_model
    ps, NB, P = 8, 6, 24
    pages = tfm.init_paged_cache(cfg, P + 1, ps)
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, 29)
    place = tfm.identity_placement(cfg)

    def chunk(pages, bt_row, start, toks, bucket):
        arr = np.zeros((1, bucket), np.int32)
        arr[0, :len(toks)] = toks
        batch = {"tokens": jnp.asarray(arr),
                 "chunk_starts": jnp.asarray([start], jnp.int32),
                 "chunk_lens": jnp.asarray([len(toks)], jnp.int32)}
        bt = np.zeros((1, NB), np.int32)
        bt[0, :len(bt_row)] = bt_row
        logits, pages, _ = tfm.prefill_chunk_paged(
            params, cfg, batch, pages, block_tables=jnp.asarray(bt),
            placement=place, n_sources=0, collect_stats=False,
            attn_backend="xla")
        return logits, pages

    # cold: request A prefills 16 + 13 tokens onto pages [1..4]
    _, pages = chunk(pages, [1, 2, 3, 4], 0, prompt[:16], 16)
    logits_cold, pages = chunk(pages, [1, 2, 3, 4], 16, prompt[16:], 16)
    # warm: request B shares A's two full prefix pages and prefills only
    # the unshared tail onto its own pages
    logits_warm, pages = chunk(pages, [1, 2, 10, 11], 16, prompt[16:], 16)
    np.testing.assert_array_equal(np.asarray(logits_cold),
                                  np.asarray(logits_warm))


# ================================================================ engines
# (shared_runner comes session-scoped from conftest.py)
def _stream(cfg, seed=3):
    """Request stream with full-duplicate, partial-prefix and unshared
    prompts (fresh Request objects per call)."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, cfg.vocab_size, 32).tolist()
    uniq = rng.integers(0, cfg.vocab_size, 64).tolist()

    def req(i, toks, arrival):
        return Request(req_id=i, prompt_len=len(toks), max_new_tokens=4,
                       arrival_time=arrival, prompt_tokens=list(toks))
    return [
        req(0, base, 0.0),
        req(1, base, 0.20),                     # identical: COW recompute
        req(2, base[:24] + uniq[:8], 0.25),     # 3-page prefix hit
        req(3, uniq[8:28], 0.25),               # unshared
        req(4, base[:16] + uniq[28:40], 0.30),  # 2-page prefix hit
    ]


def _drive_arrivals(engine, reqs, dt=0.01, max_steps=2000):
    pending = sorted(reqs, key=lambda r: (r.arrival_time, r.req_id))
    now = 0.0
    for _ in range(max_steps):
        while pending and pending[0].arrival_time <= now:
            engine.enqueue(pending.pop(0), now)
        engine.step(now)
        engine.pool.check_invariants()
        now += dt
        if not pending and not engine.has_work:
            break
    return now


def test_differential_sharing_on_off(tiny_model, shared_runner):
    """Identical streams with sharing on vs off: token-identical outputs,
    identical finish order, strictly fewer physical pages with sharing."""
    cfg, params = tiny_model
    base_cfg = shared_runner.ecfg
    off = PagedRealEngine(0, cfg, params, base_cfg, runner=shared_runner,
                          n_sources=2)
    on = PagedRealEngine(0, cfg, params,
                         dataclasses.replace(base_cfg, prefix_sharing=True),
                         runner=shared_runner, n_sources=2)
    reqs_off, reqs_on = _stream(cfg), _stream(cfg)
    _drive_arrivals(off, reqs_off)
    _drive_arrivals(on, reqs_on)

    assert all(r.state is RequestState.FINISHED and not r.error
               for r in reqs_off + reqs_on)
    for a, b in zip(reqs_off, reqs_on):
        assert a.output_tokens == b.output_tokens, \
            f"req {a.req_id} diverged under prefix sharing"
    assert [r.req_id for r in off.finished] == \
        [r.req_id for r in on.finished], "finish order changed"

    # sharing actually happened, and the books say so
    assert on.prefix_hit_tokens >= 31 + 24 + 16
    assert on.pool.stat_cow_copies >= 1          # full-duplicate recompute
    assert on.pool.stat_hit_pages >= 4 + 3 + 2
    assert on.pool.stat_blocks_allocated < off.pool.stat_blocks_allocated
    # skipped prefill is exactly the cache-hit tokens
    assert off.total_prefill_tokens - on.total_prefill_tokens \
        == on.prefix_hit_tokens
    # everything released; cached pages remain matchable yet reclaimable
    assert on.pool.usage == 0.0
    assert on.pool.n_cached > 0
    on.pool.check_invariants()


def test_preempt_resume_determinism_with_sharing(tiny_model, shared_runner):
    """KV-pressure eviction while peers share pages: outputs still match
    the unpressured shared run bit-for-bit (resume re-matches the cache),
    and no shared page is reclaimed behind a peer's back (invariants are
    checked every step by the driver)."""
    cfg, params = tiny_model
    roomy = dataclasses.replace(shared_runner.ecfg, prefix_sharing=True,
                                max_blocks_per_req=6)
    e1 = PagedRealEngine(0, cfg, params, roomy, runner=shared_runner,
                         n_sources=2)
    r1 = _stream(cfg)
    _drive_arrivals(e1, r1)
    assert sum(r.n_preemptions for r in r1) == 0

    tight = dataclasses.replace(roomy, n_pages=6)   # 48 tokens of pool
    e2 = PagedRealEngine(0, cfg, params, tight, runner=shared_runner,
                         n_sources=2)
    r2 = _stream(cfg)
    _drive_arrivals(e2, r2)
    assert all(r.state is RequestState.FINISHED and not r.error for r in r2)
    assert sum(r.n_preemptions for r in r2) > 0, "tight pool must evict"
    for a, b in zip(r1, r2):
        assert a.output_tokens == b.output_tokens, \
            f"req {a.req_id} diverged after eviction under sharing"
    e2.pool.check_invariants()
    assert e2.pool.usage == 0.0


# ================================================================ simulator
def test_dpengine_prefix_sharing_sim():
    """The simulator DPEngine runs the same SharedPagedAllocator: sharing
    skips prefill tokens, kv_usage stays truthful, and completion matches
    the non-sharing run."""
    from repro.serving import DPEngine, EngineConfig
    from repro.serving.costmodel import CostModelConfig, EngineCostModel
    base = list(range(100, 132))        # 32 tokens = 2 full blocks @ 16

    def mk():
        reqs = []
        for i in range(6):
            toks = base + [1000 + 10 * i + j for j in range(8)]
            reqs.append(Request(req_id=i, prompt_len=len(toks),
                                max_new_tokens=4, arrival_time=0.05 * i,
                                prompt_tokens=toks))
        return reqs

    def run(sharing):
        e = DPEngine(0, EngineConfig(kv_tokens=2048, kv_block=16,
                                     token_budget=64,
                                     prefix_sharing=sharing),
                     EngineCostModel(CostModelConfig()))
        reqs = mk()
        pending = sorted(reqs, key=lambda r: r.arrival_time)
        now = 0.0
        for _ in range(500):
            while pending and pending[0].arrival_time <= now:
                e.enqueue(pending.pop(0), now)
            dur, _, _ = e.step(now)
            if hasattr(e.pool, "check_invariants"):
                e.pool.check_invariants()
            now += max(dur, 0.01)
            if not pending and not e.has_work:
                break
        return e, reqs

    e_on, r_on = run(True)
    e_off, r_off = run(False)
    assert all(r.state is RequestState.FINISHED for r in r_on + r_off)
    assert e_on.prefix_hit_tokens > 0
    assert e_off.total_prefill_tokens - e_on.total_prefill_tokens \
        == e_on.prefix_hit_tokens
    # shared-aware kv_usage: all capacity back, Algorithm 1 sees the truth
    assert e_on.pool.usage == 0.0
    assert e_on.pool.stat_blocks_allocated < e_off.pool.stat_blocks_allocated
    # skipping prefill must not delay anyone
    on_ttft = np.mean([r.ttft for r in r_on])
    off_ttft = np.mean([r.ttft for r in r_off])
    assert on_ttft <= off_ttft + 1e-9


# ================================================================ cluster e2e
@pytest.mark.slow
def test_cluster_prefix_sharing_differential(tiny_model, shared_runner):
    """2-engine Gimbal cluster over the paged plane, sharing on vs off on
    the same shared-system-prompt stream: every request finishes with
    token-identical outputs, the shared run allocates fewer pages, and the
    scheduler keeps operating on truthful shared-aware kv_usage."""
    cfg, params = tiny_model
    rng = np.random.default_rng(9)
    system = rng.integers(0, cfg.vocab_size, 16).tolist()

    tails = [rng.integers(0, cfg.vocab_size,
                          int(rng.integers(4, 9))).tolist()
             for _ in range(8)]

    def mk():
        reqs = []
        for i in range(8):
            toks = system + tails[i]
            reqs.append(Request(req_id=i, prompt_len=len(toks),
                                max_new_tokens=3, arrival_time=0.05 * i,
                                prompt_tokens=toks))
        return reqs

    def serve(sharing):
        ecfg = dataclasses.replace(shared_runner.ecfg, n_pages=48,
                                   prefix_sharing=sharing)
        engines = [PagedRealEngine(i, cfg, params, ecfg,
                                   runner=shared_runner, n_sources=2)
                   for i in range(2)]
        reqs = mk()
        res = serve_real_cluster(reqs, engines,
                                 cluster_cfg=RealClusterConfig(
                                     window_tokens=200))
        for e in engines:
            e.pool.check_invariants()
            assert e.pool.usage == 0.0
        return res, reqs

    res_on, reqs_on = serve(True)
    res_off, reqs_off = serve(False)
    assert all(r.state is RequestState.FINISHED and not r.error
               for r in reqs_on + reqs_off)
    for a, b in zip(reqs_off, reqs_on):
        assert a.output_tokens == b.output_tokens
    assert sum(res_on.signals["decisions"].values()) == len(reqs_on)
    assert res_on.signals["prefix_hit_tokens"] > 0
    assert res_on.signals["pages_allocated"] \
        < res_off.signals["pages_allocated"]
    # sharing must not regress scheduling: no stalls introduced and TTFT
    # no worse than the truthful no-sharing baseline (loose bound: the
    # dispatch split may differ since kv pressure genuinely differs)
    assert res_on.mean_ttft <= res_off.mean_ttft * 1.25 + 0.05
