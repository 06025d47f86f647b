"""Serving-speed optimisations: prefix-cache CoW, speculative decoding,
quantized KV pool (ISSUE 14).

The load-bearing contract for all three: greedy outputs are IDENTICAL
with the feature on or off — prefix caching byte-identically (shared
blocks hold the exact K/V prefill wrote, divergence copies-on-write
first), speculation exactly (every committed token is the target's
argmax in its true greedy context), int8 exactly on short sequences
and within a measured logit-error bound on long ones. The features are
pure speed: correctness never depends on cache state, draft quality,
or storage dtype.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu import telemetry
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig, TransformerLM)
from distributed_tensorflow_tpu.serving import (
    BlockAllocator, CacheConfig, InferenceEngine, PrefixCache, Request,
    kv_quantization_probe, truncated_draft)
from distributed_tensorflow_tpu.serving.kv_cache import (
    BlockTable, HostTier, OutOfBlocksError, init_pool)

#: Documented int8 KV logit-error bound for the CI-sized config (the
#: probe measures ~0.004 on this box; README's KV-dtype table cites
#: this ceiling).
INT8_LOGIT_ERR_BOUND = 0.05


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig.tiny(max_seq_len=64)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def reference_greedy(cfg, params, prompt, n):
    model = TransformerLM(cfg)
    t = list(prompt)
    for _ in range(n):
        logits = model.apply({"params": params}, jnp.asarray([t]))
        t.append(int(jnp.argmax(logits[0, len(t) - 1])))
    return t[len(prompt):]


# a 16-token base prompt: two full blocks at block_size=8, so later
# requests can match one full block plus a partial tail (the CoW case)
X = [7, 3, 9, 1, 4, 4, 2, 8, 5, 5, 1, 9, 2, 6, 3, 7]


def _engine(cfg, params, **kw):
    kw.setdefault("num_blocks", 32)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_prompt_len", 16)
    return InferenceEngine(cfg, params, **kw)


def _assert_blocks_conserved(engine):
    """Every pool block is either free or held by the prefix cache
    once nothing is running — shared refs all unwound."""
    held = (len(engine.scheduler.prefix_cache)
            if engine.scheduler.prefix_cache is not None else 0)
    assert (engine.scheduler.allocator.num_free + held
            == engine.cache_cfg.usable_blocks)


# ---------------------------------------------------------------------------
# prefix cache: unit level
# ---------------------------------------------------------------------------

class TestPrefixCacheUnit:
    def _cache(self, num_blocks=16, bs=4):
        a = BlockAllocator(num_blocks)
        return a, PrefixCache(a, bs)

    def test_match_walks_registered_chain(self):
        a, pc = self._cache()
        toks = list(range(10))                   # 2 full blocks + 2
        blocks = a.alloc(3)
        pc.register(toks, blocks)                # indexes blocks 0..1
        n, got = pc.match(toks + [99])           # limit = 10
        assert n == 8 and got == blocks[:2]
        assert a.refcount(blocks[0]) == 3        # owner + cache + match
        a.free(got)                              # hand the match back
        # a diverging prompt matches only the agreeing prefix
        n, got = pc.match(list(range(4)) + [77] * 6)
        assert n == 4 and got == blocks[:1]
        a.free(got)

    def test_partial_tail_match(self):
        """A prompt ending mid-block can still match a cached block
        whose tokens extend it — the block the matching sequence will
        later copy-on-write."""
        a, pc = self._cache()
        blocks = a.alloc(2)
        pc.register(list(range(8)), blocks)
        n, got = pc.match(list(range(7)))         # limit 6: 1 full + 2
        assert n == 6 and got == blocks[:2]
        a.free(got)

    def test_match_never_covers_last_token(self):
        a, pc = self._cache()
        blocks = a.alloc(2)
        pc.register(list(range(8)), blocks)
        n, got = pc.match(list(range(8)))         # identical prompt
        assert n == 7                             # 8 would leave prefill
        a.free(got)                               # nothing to compute

    def test_eviction_lru_and_never_refcounted(self):
        """Eviction frees LRU unreferenced entries only: a block a
        sequence still shares (refcount > 1) survives any pressure."""
        a, pc = self._cache(num_blocks=8, bs=4)
        b1 = a.alloc(1)
        b2 = a.alloc(1)
        pc.register(list(range(4)), b1)
        pc.register(list(range(10, 14)), b2)
        a.free(b1)                                # cache is sole owner
        a.free(b2)
        n, shared = pc.match(list(range(5)))      # a "sequence" shares b1
        assert n == 4 and shared == b1
        freed = pc.evict(5)
        assert freed == 1                         # only b2 was evictable
        assert a.refcount(b1[0]) == 2             # untouched
        assert pc.match(list(range(10, 15)))[0] == 0   # b2's entry gone
        a.free(shared)                            # seq lets go
        assert pc.evict(5) == 1                   # NOW b1 is evictable
        assert a.num_free == 7

    def test_interior_of_chain_not_evicted_before_leaf(self):
        a, pc = self._cache()
        blocks = a.alloc(2)
        pc.register(list(range(8)), blocks)
        a.free(blocks)                            # cache sole owner
        assert pc.evict(1) == 1                   # evicts the LEAF
        n, got = pc.match(list(range(4)) + [9])   # parent still matches
        assert n == 4
        a.free(got)


# ---------------------------------------------------------------------------
# prefix cache: the eviction order, against a scan of every entry
# ---------------------------------------------------------------------------

def _scan_victims(cache, n_blocks):
    """The oracle: ``PrefixCache.evict`` as it was before the evictable
    leaves were kept in order — for each block it frees it walks every
    entry for the unreferenced leaf of least ``last_used``. Evicts from
    ``cache`` and returns the freed block ids in order."""
    freed = []
    while len(freed) < n_blocks:
        victim = None
        for e in cache._entries.values():
            if cache._children.get(e.key):
                continue                 # interior of a cached chain
            if cache._alloc.refcount(e.block) != 1:
                continue                 # a sequence still shares it
            if victim is None or e.last_used < victim.last_used:
                victim = e
        if victim is None:
            break
        if cache._spill is not None:
            cache._spill.put(victim.key, victim.parent, victim.tokens,
                             cache._spill_extract(victim.block),
                             cache._spill_epoch)
        del cache._entries[victim.key]
        cache._by_block.pop(victim.block, None)
        kids = cache._children.get(victim.parent)
        if kids is not None:
            kids.discard(victim.key)
            if not kids:
                del cache._children[victim.parent]
        cache._alloc.free([victim.block])
        cache.evictions += 1
        freed.append(victim.block)
    return freed


def _evict_logged(cache, n_blocks):
    """``cache.evict(n_blocks)``; the block ids it freed, in order."""
    freed = []
    free = cache._alloc.free
    cache._alloc.free = lambda blocks: (freed.extend(blocks),
                                        free(blocks))[1]
    try:
        assert cache.evict(n_blocks) == len(freed)
    finally:
        del cache._alloc.free            # the method shows again
    return freed


class _World:
    """An allocator, a prefix cache and the tables of a few live
    sequences, driven as the scheduler drives them. Two worlds take the
    same operations: one evicts with ``PrefixCache.evict``, the other
    with the scan."""

    def __init__(self, bs, spill, scan, num_blocks=48):
        self.cfg = CacheConfig(n_layers=1, n_heads=1, head_dim=1,
                               num_blocks=num_blocks, block_size=bs)
        self.alloc = BlockAllocator(num_blocks)
        self.cache = PrefixCache(self.alloc, bs)
        self.scan = scan
        self.tables = []
        self.log = []                     # what every evict left behind
        self.readopted = []               # (block, the block it was)
        self.tier = None
        if spill:
            self.tier = HostTier(capacity_blocks=6)
            self.cache.attach_spill(
                self.tier, epoch=0,
                extract=lambda b: {"was": np.asarray([b])},
                insert=lambda b, arrays: self.readopted.append(
                    (b, int(arrays["was"][0]))))

    def evict(self, n):
        freed = (_scan_victims(self.cache, n) if self.scan
                 else _evict_logged(self.cache, n))
        self.log.append((tuple(freed), tuple(self.cache._entries),
                         tuple(self.alloc._free),
                         tuple(self.tier._entries) if self.tier else ()))
        return len(freed)

    def _room(self, table, n_tokens):
        """``Scheduler._ensure_room``: evict for what is missing."""
        need = self.cfg.blocks_for(table.length + n_tokens)
        grow = need - len(table.blocks)
        if grow > self.alloc.num_free:
            self.evict(grow - self.alloc.num_free)
        try:
            table.ensure_room(n_tokens, self.alloc)
        except OutOfBlocksError:
            return False
        return True

    def admit(self, tokens, keep, cold):
        """Match (unless the ask runs ``cold``: the trap, a prompt
        registered again under other blocks), hand the match back or
        build the table, copy a shared tail on write, register."""
        n, blocks = (0, []) if cold else self.cache.match(tokens)
        out = (n, tuple(blocks))
        table = BlockTable(self.cfg, 64)
        table.blocks = list(blocks)
        if not keep or not self._room(table, len(tokens) + 1):
            table.release(self.alloc)
            return out + ("back",)
        if self.alloc.num_free < 1:
            self.evict(1)
        try:
            copies = table.ensure_writable(n, len(tokens), self.alloc)
        except OutOfBlocksError:
            table.release(self.alloc)
            return out + ("no room to copy",)
        table.length = len(tokens)
        self.cache.register(tokens, table.blocks)
        self.tables.append(table)
        return out + (tuple(copies), tuple(table.blocks))

    def grow(self, i, n):
        table = self.tables[i]
        if self._room(table, n):
            table.length += n
        return tuple(table.blocks)

    def release(self, i):
        self.tables.pop(i).release(self.alloc)

    def fence(self, epoch):
        return self.cache.fence(epoch)

    def state(self):
        c = self.cache
        return (tuple(c._entries), tuple(self.alloc._free),
                tuple(sorted(self.alloc._refs.items())),
                tuple(e.last_used for e in c._entries.values()),
                c.evictions, c.spill_hits, c.spill_rejects,
                tuple(self.readopted),
                self.tier.stats() if self.tier else None,
                tuple(self.tier._entries) if self.tier else ())


def _draw_prompt(rng, bs, docs):
    """A document's prefix and a short tail over three tokens: chains
    share blocks, fork inside one, and end on and off a block's edge."""
    doc = rng.choice(docs)
    cut = rng.choice([len(doc), len(doc), rng.randrange(1, len(doc) + 1),
                      (rng.randrange(len(doc)) // bs + 1) * bs])
    tail = [rng.randrange(3) for _ in range(rng.choice([0, 0, 1, bs,
                                                        bs + 1]))]
    return tuple(doc[:cut]) + tuple(tail)


@pytest.mark.parametrize("spill", [False, True], ids=["drop", "spill"])
@pytest.mark.parametrize("bs", [1, 4, 16])
def test_evict_frees_what_the_scan_frees(bs, spill):
    """Thousands of the scheduler's own operations on two caches in
    lock step: after every ``evict`` the same block ids were freed in
    the same order, and the same entries, free list and spill tier are
    left. ``last_used`` ties between eligible entries never arise, so
    the scan's pick is the heap's."""
    rng = random.Random(1000 * bs + spill)
    docs = [[rng.randrange(3) for _ in range(rng.randrange(bs, 5 * bs + 2))]
            for _ in range(6)]
    new, old = (_World(bs, spill, scan=False), _World(bs, spill, scan=True))
    evicts = 0
    for step in range(3000):
        r = rng.random()
        if r < 0.45 or not new.tables:
            op = ("admit", _draw_prompt(rng, bs, docs), rng.random() < 0.8,
                  rng.random() < 0.15)
        elif r < 0.60:
            op = ("grow", rng.randrange(len(new.tables)),
                  rng.randrange(1, 2 * bs + 1))
        elif r < 0.90:
            op = ("release", rng.randrange(len(new.tables)))
        elif r < 0.995:
            op = ("evict", rng.randrange(1, 6))
        else:
            op = ("fence", step)
        got = [getattr(w, op[0])(*op[1:]) for w in (new, old)]
        assert got[0] == got[1], (step, op)
        assert new.log == old.log, (step, op)
        evicts += len(new.log)
        new.log.clear()
        old.log.clear()
        assert new.state() == old.state(), (step, op)
        assert len(new.cache._lru) <= 4 * len(new.cache) + 65
    assert evicts > 300 and new.cache.evictions > 400
    if spill:
        assert new.cache.spill_hits > 0 and new.tier.dropped > 0
    # every reference is a table's or the cache's
    for w in (new, old):
        for t in list(w.tables):
            t.release(w.alloc)
        assert w.alloc.total_refs == len(w.cache)
        w.evict(10 ** 6)
        assert len(w.cache) == 0 and w.alloc.num_allocated == 0


def test_evict_cost_is_by_blocks_freed_not_by_entries():
    """By the counter, not the clock: 4,096 entries, 64 sequences'
    blocks still referenced, the evictable ones older and newer than
    those; freeing 50 looks at about 50 candidates (a scan would look
    at 200,000 and more)."""
    bs = 2
    alloc = BlockAllocator(4200)
    cache = PrefixCache(alloc, bs)
    cfg = CacheConfig(n_layers=1, n_heads=1, head_dim=1, num_blocks=4200,
                      block_size=bs)
    held = []
    for i in range(512):                     # 512 prompts of 8 blocks
        toks = [i // 256, i % 256] + [i % 7] * 14
        table = BlockTable(cfg, 16)
        table.ensure_room(len(toks), alloc)
        cache.register(toks, table.blocks)
        if i % 8 == 3:
            held.append(table)               # a running sequence
        else:
            table.release(alloc)
    assert len(cache) == 4096 and len(held) == 64
    for i in (5, 6, 7):                      # matched and handed back:
        n, got = cache.match([i // 256, i % 256] + [i % 7] * 14)
        alloc.free(got)                      # newer than the sequences
    before = cache.stats()["evict_examined"]
    assert cache.evict(50) == 50
    examined = cache.stats()["evict_examined"] - before
    assert 50 <= examined <= 150, examined
    assert all(alloc.refcount(b) == 2 for t in held for b in t.blocks)
    # the oldest went first: prompt 0's leaf, then up its chain
    assert cache.match([0, 0] + [0] * 14)[0] == 0


def test_stale_items_stay_bounded_without_eviction():
    """10,000 match / hand-back cycles and no eviction: every cycle
    files the chain's leaf again, and the structure stays within a
    fixed multiple of the cache."""
    alloc = BlockAllocator(64)
    cache = PrefixCache(alloc, 4)
    prompts = [[p] * 4 + [q] * 5 for p in range(3) for q in range(3)]
    for toks in prompts:
        blocks = alloc.alloc(2)
        cache.register(toks, blocks)
        alloc.free(blocks)
    for i in range(10000):
        n, got = cache.match(prompts[i % len(prompts)])
        assert n == 8
        alloc.free(got)
        assert len(cache._lru) <= 4 * len(cache) + 65
    assert cache.evictions == 0 and len(cache) == 12
    assert cache.evict(100) == 12            # and all of it is still found


def test_readopted_block_nobody_matched_is_evictable():
    """``_readopt`` leaves a leaf only the cache holds: it is in the
    eviction order from then on."""
    alloc = BlockAllocator(8)
    cache = PrefixCache(alloc, 2)
    tier = HostTier()
    cache.attach_spill(tier, epoch=0, extract=lambda b: {},
                       insert=lambda b, arrays: None)
    blocks = alloc.alloc(1)
    cache.register([5, 6], blocks)
    alloc.free(blocks)
    assert cache.evict(1) == 1 and len(tier) == 1
    (key,) = tier._entries
    assert cache._readopt(key, None) is not None and len(cache) == 1
    assert cache.evict(1) == 1 and alloc.num_allocated == 0


# ---------------------------------------------------------------------------
# prefix cache: engine level (the byte-parity contract)
# ---------------------------------------------------------------------------

class TestPrefixCacheEngine:
    def test_hit_skips_prefill_and_outputs_match_cold(self, tiny):
        """Second request with the same prompt: prefill computes only
        the suffix, outputs byte-identical to a cold engine."""
        cfg, params = tiny
        e = _engine(cfg, params, prefix_caching=True)
        e.submit(Request(id="a", tokens=tuple(X), max_new_tokens=6))
        done_a = e.run_until_idle()
        e.submit(Request(id="b", tokens=tuple(X), max_new_tokens=6))
        done_b = e.run_until_idle()
        st = e.stats()["prefix_cache"]
        assert st["hit_tokens"] == 15            # all but the last token
        assert done_b["b"]["tokens"] == done_a["a"]["tokens"] \
            == reference_greedy(cfg, params, X, 6)
        _assert_blocks_conserved(e)

    def test_shared_then_diverge_byte_parity(self, tiny):
        """The CoW case: request B matches one full block of A's prompt
        plus a PARTIAL tail block, then writes its own divergent tokens
        into that block — which must be copied first. B's outputs (and
        A's on a re-serve) are byte-identical to a cold cache."""
        cfg, params = tiny
        B_prompt = X[:12] + [9, 9]               # diverges mid-block 2
        e = _engine(cfg, params, prefix_caching=True)
        outs = {}
        for rid, p in (("a", X), ("b", B_prompt), ("a2", X)):
            e.submit(Request(id=rid, tokens=tuple(p), max_new_tokens=6))
            outs[rid] = e.run_until_idle()[rid]["tokens"]
        st = e.stats()["prefix_cache"]
        assert st["hit_tokens"] > 0 and st["hit_requests"] >= 2
        assert outs["a"] == outs["a2"] \
            == reference_greedy(cfg, params, X, 6)
        assert outs["b"] == reference_greedy(cfg, params, B_prompt, 6)
        _assert_blocks_conserved(e)

    def test_caching_on_off_parity_under_preemption(self, tiny):
        """A pool too small for the concurrency — preemption + replay
        + cache eviction all fire — and a shared-prefix workload still
        decodes byte-identically with caching on and off."""
        cfg, params = tiny
        prompts = [X, X[:12] + [9, 9], X[:5], list(X)]
        outs = {}
        for on in (False, True):
            e = _engine(cfg, params, num_blocks=8, block_size=4,
                        prefix_caching=on)
            outs[on] = e.generate(prompts, max_new_tokens=8)
            assert e.scheduler.preemptions > 0
            _assert_blocks_conserved(e)
        assert outs[True] == outs[False]
        for p, o in zip(prompts, outs[True]):
            assert o == reference_greedy(cfg, params, p, 8)

    def test_cache_parity_dp_tp_mesh(self, tiny, mesh2d):
        """Suffix prefill through the replicated extend program on a
        dp=4 × tp=2 mesh: hits adopt tp-sharded pool blocks and the
        outputs stay byte-identical to recompute."""
        cfg, params = tiny
        e = InferenceEngine(cfg, params, mesh=mesh2d, num_blocks=32,
                            block_size=8, max_slots=8, max_prompt_len=16,
                            prefix_caching=True)
        outs = {}
        for rid, p in (("a", X), ("b", X), ("c", X[:12] + [9, 9])):
            e.submit(Request(id=rid, tokens=tuple(p), max_new_tokens=6))
            outs[rid] = e.run_until_idle()[rid]["tokens"]
        assert e.stats()["prefix_cache"]["hit_tokens"] > 0
        assert outs["a"] == outs["b"] \
            == reference_greedy(cfg, params, X, 6)
        assert outs["c"] == reference_greedy(cfg, params,
                                             X[:12] + [9, 9], 6)

    def test_preempted_request_readmits_onto_warm_blocks(self, tiny):
        """A preempted sequence's registered prompt blocks survive its
        release (the cache holds them), so replay re-admits with a
        cache hit — replayed-token accounting unchanged."""
        cfg, params = tiny
        e = _engine(cfg, params, num_blocks=10, block_size=4,
                    prefix_caching=True)
        prompts = [X, X[:9], X[:6]]
        outs = e.generate(prompts, max_new_tokens=8)
        assert e.scheduler.preemptions > 0
        for p, o in zip(prompts, outs):
            assert o == reference_greedy(cfg, params, p, 8)
        assert e.stats()["prefix_cache"]["hit_tokens"] > 0
        _assert_blocks_conserved(e)

    def test_every_step_evicts_and_matches_the_scan(self, tiny, tmp_path,
                                                    monkeypatch):
        """A pool so small that nearly every step evicts, a closed loop
        of a few dozen requests: tokens, evictions, preemptions and the
        allocator's audit are those of the same run with the scan in
        ``evict``'s place, and the ``kv.evict`` spans count every
        evicted block."""
        cfg, params = tiny
        rng = random.Random(7)
        prompts = [[rng.randrange(1, 12)
                    for _ in range(rng.randrange(5, 17))]
                   for _ in range(30)]
        runs = {}
        for scan in (False, True):
            if scan:
                monkeypatch.setattr(
                    PrefixCache, "evict",
                    lambda self, n: len(_scan_victims(self, n)))
            e = _engine(cfg, params, num_blocks=14, block_size=4,
                        max_slots=3, prefix_caching=True)
            telemetry.configure(str(tmp_path / f"scan{scan}"),
                                process_id=0)
            try:
                outs = e.generate(prompts, max_new_tokens=6)
            finally:
                telemetry.shutdown()
            _assert_blocks_conserved(e)
            stats = e.stats()["prefix_cache"]
            runs[scan] = (outs, stats["evictions"],
                          e.scheduler.preemptions, e.block_accounting())
        assert runs[False] == runs[True]
        outs, evictions, _, audit = runs[False]
        assert evictions > len(prompts)
        assert audit["leaked_refs"] == 0 and audit["conserved"]
        assert outs[:3] == [reference_greedy(cfg, params, p, 6)
                            for p in prompts[:3]]
        spans = [ev for ev in telemetry.read_events(
            str(tmp_path / "scanFalse" / "events-0.jsonl"))
            if ev["ev"] == "kv.evict"]
        assert sum(ev["blocks"] for ev in spans) == evictions
        assert all(ev["examined"] >= ev["blocks"] for ev in spans)


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------

PROMPTS = [X, X[:12] + [9, 9], X[:5], [3, 1, 4, 1, 5]]


class TestSpeculative:
    @pytest.mark.parametrize("k", [1, 3])
    def test_greedy_parity_1device(self, tiny, k):
        """Whatever the (default truncated-target) draft proposes,
        committed tokens are exactly the non-speculative greedy ones."""
        cfg, params = tiny
        e = _engine(cfg, params, speculative_k=k)
        outs = e.generate(PROMPTS, max_new_tokens=6)
        for p, o in zip(PROMPTS, outs):
            assert o == reference_greedy(cfg, params, p, 6)
        st = e.stats()["speculative"]
        assert st["proposed"] > 0 and 0.0 <= st["accepted_rate"] <= 1.0

    def test_greedy_parity_with_adversarial_draft(self, tiny):
        """A draft from completely different weights (worst case: near-
        zero acceptance) still yields exact outputs — speculation only
        ever changes HOW MANY target forwards run, never what commits."""
        cfg, params = tiny
        other = TransformerLM(cfg).init(
            jax.random.PRNGKey(42), jnp.zeros((1, 8), jnp.int32))["params"]
        e = _engine(cfg, params, speculative_k=3,
                    draft_params=other, draft_cfg=cfg)
        outs = e.generate(PROMPTS, max_new_tokens=6)
        for p, o in zip(PROMPTS, outs):
            assert o == reference_greedy(cfg, params, p, 6)

    def test_self_draft_accepts_everything(self, tiny):
        """draft == target: every proposal is the target's own argmax,
        so acceptance is 1.0 — the accounting's upper anchor."""
        cfg, params = tiny
        e = _engine(cfg, params, speculative_k=3,
                    draft_params=params, draft_cfg=cfg)
        e.generate(PROMPTS, max_new_tokens=6)
        st = e.stats()["speculative"]
        assert st["proposed"] > 0
        assert st["accepted"] == st["proposed"]

    def test_greedy_parity_dp_tp_mesh(self, tiny, mesh2d):
        """Same contract on a dp=4 × tp=2 mesh: the verify forward's
        slots shard over dp, heads/vocab over tp."""
        cfg, params = tiny
        e = InferenceEngine(cfg, params, mesh=mesh2d, num_blocks=32,
                            block_size=8, max_slots=8, max_prompt_len=16,
                            speculative_k=3)
        outs = e.generate(PROMPTS, max_new_tokens=6)
        for p, o in zip(PROMPTS, outs):
            assert o == reference_greedy(cfg, params, p, 6)

    def test_parity_under_preemption_replay(self, tiny):
        """Speculation + a starved pool: preempted sequences replay
        their generated tokens as prompt and re-enter the speculative
        loop — outputs still exact, blocks conserved."""
        cfg, params = tiny
        pp = [[7, 7, 7], [8, 8, 8, 8], [9, 9]]
        e = _engine(cfg, params, num_blocks=6, block_size=4,
                    speculative_k=2)
        outs = e.generate(pp, max_new_tokens=8)
        assert e.scheduler.preemptions > 0
        for p, o in zip(pp, outs):
            assert o == reference_greedy(cfg, params, p, 8)
        _assert_blocks_conserved(e)

    def test_eos_respected_mid_speculation(self, tiny):
        """An EOS inside an accepted draft span truncates the commit
        exactly where sequential decode would stop."""
        cfg, params = tiny
        ref = reference_greedy(cfg, params, [5, 6, 7], 6)
        # the greedy output repeats itself: the end token is one that
        # first occurs after the first token, and the output ends at
        # that first occurrence
        eos = next(t for t in ref[1:] if t != ref[0])
        want = ref[:ref.index(eos) + 1]
        e = _engine(cfg, params, speculative_k=3)
        e.submit(Request(id="e", tokens=(5, 6, 7), max_new_tokens=6,
                         eos_id=eos))
        done = e.run_until_idle()
        assert done["e"]["tokens"] == want

    def test_truncated_draft_shapes(self, tiny):
        cfg, params = tiny
        dcfg, dparams = truncated_draft(cfg, params, 1)
        assert dcfg.n_layers == 1
        assert dparams["layers"]["attn"]["query"].shape[0] == 1
        with pytest.raises(ValueError):
            truncated_draft(cfg, params, cfg.n_layers + 1)


# ---------------------------------------------------------------------------
# quantized KV pool
# ---------------------------------------------------------------------------

class TestQuantizedKV:
    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    def test_greedy_parity_short_sequences(self, tiny, kv_dtype):
        """Short prompts + short generations: quantisation error is
        far below the argmax margins of this model, so tokens are
        exactly the f32 ones (fixed seeds -> deterministic)."""
        cfg, params = tiny
        e = _engine(cfg, params, kv_dtype=kv_dtype)
        outs = e.generate(PROMPTS, max_new_tokens=6)
        for p, o in zip(PROMPTS, outs):
            assert o == reference_greedy(cfg, params, p, 6)

    def test_int8_logit_error_within_documented_bound(self, tiny):
        """The probe drives the SAME tokens through an f32 and an int8
        pool over a long rollout; the worst logit divergence must stay
        under the bound the README documents."""
        cfg, params = tiny
        probe = kv_quantization_probe(cfg, params, X, "int8",
                                      n_steps=24)
        assert probe["max_abs_logit_err"] < INT8_LOGIT_ERR_BOUND
        assert probe["positions_checked"] == 25

    def test_bf16_logit_error_smaller_than_int8(self, tiny):
        cfg, params = tiny
        p8 = kv_quantization_probe(cfg, params, X, "int8", n_steps=8)
        p16 = kv_quantization_probe(cfg, params, X, "bf16", n_steps=8)
        assert p16["max_abs_logit_err"] <= p8["max_abs_logit_err"]

    def test_int8_doubles_slots_at_equal_budget(self):
        """Acceptance gate: at an equal pool byte budget the int8
        config fits >= 2x the f32 block count (and so >= 2x the
        servable slots), for both the CI head_dim and a production
        one."""
        for head_dim in (16, 64, 128):
            kw = dict(n_layers=2, n_heads=4, head_dim=head_dim,
                      num_blocks=8, block_size=16)
            f32 = CacheConfig(**kw, kv_dtype="f32")
            i8 = CacheConfig(**kw, kv_dtype="int8")
            budget = 1 << 20
            assert i8.blocks_for_budget(budget) \
                >= 2 * f32.blocks_for_budget(budget)
            assert f32.bytes_per_token >= 2 * i8.bytes_per_token
            # a token costs its K and V in EVERY cache layer (two here):
            # the pool's own bytes say so, and the budget follows
            for cc in (f32, i8):
                pool_bytes = sum(a.nbytes for a in init_pool(cc).values())
                assert pool_bytes == (cc.num_blocks * cc.block_size
                                      * cc.bytes_per_token)
                assert cc.blocks_for_budget(pool_bytes) == cc.num_blocks
            assert f32.bytes_per_token == 2 * (2 * 4 * head_dim * 4)

    def test_kv_dtype_spelling_validated(self):
        with pytest.raises(ValueError):
            CacheConfig(n_layers=1, n_heads=1, head_dim=8, num_blocks=4,
                        kv_dtype="fp4")

    def test_int8_with_prefix_cache_and_speculation(self, tiny):
        """All three optimisations stacked: shared-prefix workload,
        speculation, int8 pool — outputs equal the f32 baseline
        (fixed seeds; the stacked path reuses quantized cached blocks
        and verifies drafts against dequantized gathers)."""
        cfg, params = tiny
        prompts = [X, list(X), X[:12] + [9, 9]]
        base = _engine(cfg, params).generate(prompts, max_new_tokens=6)
        e = _engine(cfg, params, prefix_caching=True, speculative_k=2,
                    kv_dtype="int8")
        outs = e.generate(prompts, max_new_tokens=6)
        assert outs == base
        assert e.stats()["prefix_cache"]["hit_tokens"] > 0
        _assert_blocks_conserved(e)


# ---------------------------------------------------------------------------
# scheduler regression: zombie-table growth
# ---------------------------------------------------------------------------

def test_preempted_batch_member_not_grown(tiny):
    """Regression (found wiring speculation): grow_for_decode iterates
    a snapshot of the batch, so a sequence preempted by an EARLIER
    grower in the same step must be skipped — growing its released
    table would allocate blocks into a zombie table and leak them.
    The conservation assert catches any recurrence."""
    cfg, params = tiny
    pp = [[7, 7, 7], [8, 8, 8, 8], [9, 9]]
    e = _engine(cfg, params, num_blocks=6, block_size=4,
                prefix_caching=True, speculative_k=2)
    e.generate(pp, max_new_tokens=8)
    assert e.scheduler.preemptions > 0
    _assert_blocks_conserved(e)
