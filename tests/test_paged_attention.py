"""The paged paths: ``ops/paged_attention.py``, the decode program
``serving/decode.make_decode_fn`` builds from it, and the admission
programs (``make_prefill_fn``, ``make_extend_fn``) that write the pool
by blocks, and extend's read of the keys before its span in place.

On the CPU the kernels run in interpret mode. The contract is the window
path's: the same keys attended with the same float32 arithmetic
(``_pool_window`` + ``mha_reference`` on the pool with the new row
written), the pool changed in the written rows and nowhere else, and an
engine that gives the same greedy tokens whichever path its decode
program takes. What the TPU's compiler makes of the program (nothing of
the pool's size) is checked on a described v5e, no chip needed.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig, TransformerLM)
from distributed_tensorflow_tpu.ops import paged_attention as pa
from distributed_tensorflow_tpu.ops.attention import mha_reference
from distributed_tensorflow_tpu.serving import (
    InferenceEngine, decode as decode_lib, engine as engine_lib)
from distributed_tensorflow_tpu.serving.kv_cache import (
    TRASH_BLOCK, BlockAllocator, BlockTable, CacheConfig, init_pool)

L, NB, BS, H, HD = 2, 32, 16, 4, 16          # pool: 512 rows, 4 groups
MAX_BLOCKS = 8                                # window: 128 positions
TOL = {jnp.float32: dict(rtol=1e-5, atol=2e-6),
       jnp.bfloat16: dict(rtol=2 ** -7, atol=1e-6)}   # one bf16 ulp
#: this file builds engines and compiles whole serving programs: what
#: they leave on the heap slows a later file's garbage collection
pytestmark = pytest.mark.usefixtures("leave_no_programs_behind")


def _pool(rng, dtype):
    return {n: jnp.asarray(rng.standard_normal((L, NB * BS, H, HD)), dtype)
            for n in ("k", "v")}


def _tables(rng, lengths, order="scattered", share=(),
            max_blocks=MAX_BLOCKS):
    """Block tables (B, max_blocks) for ``lengths``: blocks handed out
    ``contiguous``, ``scattered`` or ``descending``; ``share`` lists
    (slot, from_slot, n_blocks) prefix sharing."""
    free = list(range(1, NB))
    if order == "scattered":
        free = list(rng.permutation(free))
    elif order == "descending":
        free.reverse()
    table = np.full((len(lengths), max_blocks), TRASH_BLOCK, np.int32)
    for b, n in enumerate(lengths):
        for j in range(-(-int(n) // BS)):
            table[b, j] = free.pop(0)
    for b, src, n in share:
        table[b, :n] = table[src, :n]
    return table


def _write_row(table, lengths):
    return np.array([table[b, (n - 1) // BS] * BS + (n - 1) % BS if n else 0
                     for b, n in enumerate(lengths)], np.int32)


def _reference(pool, l, q, k_new, v_new, lengths, table):
    """The window path: write, gather the whole window, mha_reference."""
    rows = jnp.asarray(_write_row(table, lengths))
    pool = decode_lib._pool_write(pool, l, rows, k_new, v_new, False)
    window = (table[:, :, None] * BS + np.arange(BS)).reshape(len(lengths), -1)
    kw, vw = decode_lib._pool_window(pool, l, jnp.asarray(window), q.dtype,
                                     False)
    lengths = jnp.asarray(lengths)
    return mha_reference(q[:, :, None], kw, vw, causal=True, lengths=lengths,
                         q_positions=jnp.maximum(lengths - 1, 0))[:, :, 0]


def _paged(pool, l, q, k_new, v_new, lengths, table):
    lengths = jnp.asarray(lengths)
    plan = pa.decode_plan(jnp.asarray(table), jnp.maximum(lengths - 1, 0),
                          block_size=BS)
    return pa.paged_attention_decode(
        q, k_new, v_new, pool["k"], pool["v"], l, plan, lengths,
        block_size=BS, interpret=True)


def _qkv(rng, n, dtype):
    return (jnp.asarray(rng.standard_normal((n, H, HD)), dtype)
            for _ in range(3))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("length", [0, 1, 15, 16, 17, 33, 127, 128])
def test_kernel_matches_window_reference(length, dtype):
    """One slot at every length that matters (empty, its own key only,
    a block's edges, a group's edge, the full window) among slots that
    are idle, share a prefix, and end mid-block."""
    rng = np.random.default_rng(length)
    lengths = [length, 0, 40, 45, 0, 128]
    table = _tables(rng, lengths, share=[(3, 2, 2)])
    pool = _pool(rng, dtype)
    q, k_new, v_new = _qkv(rng, len(lengths), dtype)
    for l in range(L):
        out = _paged(pool, l, q, k_new, v_new, lengths, table)
        ref = _reference(pool, l, q, k_new, v_new, lengths, table)
        assert out.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), **TOL[dtype])
    idle = [b for b, n in enumerate(lengths) if n == 0]
    assert not np.any(np.asarray(out, np.float32)[idle])


@pytest.mark.parametrize("order", ["contiguous", "scattered", "descending"])
def test_kernel_whatever_the_blocks_order(order):
    """Runs merge consecutive table entries of one 128-row group: the
    result may not depend on how the allocator laid the blocks out."""
    rng = np.random.default_rng(7)
    lengths = [100, 3, 64, 17]
    table = _tables(rng, lengths, order=order)
    pool = _pool(rng, jnp.float32)
    q, k_new, v_new = _qkv(rng, len(lengths), jnp.float32)
    out = _paged(pool, 1, q, k_new, v_new, lengths, table)
    ref = _reference(pool, 1, q, k_new, v_new, lengths, table)
    np.testing.assert_allclose(out, ref, **TOL[jnp.float32])


def _extend_case(rng, order, E, dtype):
    """Four slots extended by spans of up to ``E`` tokens (padded to
    ``E``) over 16-block windows: a prefix that ends mid-block, a slot
    with nothing in the pool yet (count 0: verify's idle or fresh slot),
    a one-token span, and a slot that shares the first's first two blocks
    and ends mid-block in its own copy of the third. Returns ``(pool,
    table, positions, lengths, write rows, q, k, v)``, the span's K and V
    as the pool holds them."""
    prefix = [40, 0, 17, 45]
    spans = [E - 3, E // 2, 1, E // 4]
    lengths = np.array([p + s for p, s in zip(prefix, spans)], np.int32)
    table = _tables(rng, lengths, order=order, share=[(3, 0, 2)],
                    max_blocks=16)
    W = 16 * BS
    positions = np.full((4, E), W, np.int32)             # pad -> masked
    rows = np.zeros((4, E), np.int32)                    # pad -> trash row
    for b, (p, n) in enumerate(zip(prefix, spans)):
        positions[b, :n] = np.arange(p, p + n)
        rows[b, :n] = table[b, positions[b, :n] // BS] * BS + \
            positions[b, :n] % BS
    q, k, v = (jnp.asarray(rng.standard_normal((4, H, E, HD)), dtype)
               for _ in range(3))
    return _pool(rng, dtype), table, positions, lengths, rows, q, k, v


@pytest.mark.parametrize("E", [8, 16, 64, 160])
@pytest.mark.parametrize("order", ["contiguous", "scattered", "descending"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_extend_kernel_matches_window_reference(dtype, order, E):
    """``paged_attention_extend`` (the kernel over the keys before each
    span, the span's own merged after it) against the window path of the
    extend program: the span written into the layer, the whole window
    gathered, ``mha_reference`` causal by position. Queries given in
    float32 so that both sides return what they computed before the
    output cast: the same float32 arithmetic but for the online softmax.
    In the pool's dtype, the output agrees to one rounding of it. Padded
    queries return zeros; 160 queries are two tiles of the kernel's grid.
    The kernel never reads a row the span writes: handed the pool before
    the write, it returns the same bits."""
    rng = np.random.default_rng(E)
    pool, table, positions, lengths, rows, q, k, v = _extend_case(
        rng, order, E, dtype)
    flat = jnp.asarray(rows.reshape(-1))
    written = decode_lib._pool_write(
        pool, 1, flat, k.transpose(0, 2, 1, 3).reshape(-1, H, HD),
        v.transpose(0, 2, 1, 3).reshape(-1, H, HD), False)
    window = (table[:, :, None] * BS + np.arange(BS)).reshape(4, -1)
    kw, vw = decode_lib._pool_window(written, 1, jnp.asarray(window),
                                     jnp.float32, False)
    pos, lens = jnp.asarray(positions), jnp.asarray(lengths)
    plan = pa.decode_plan(jnp.asarray(table), jnp.minimum(pos[:, 0], lens),
                          block_size=BS)
    assert list(np.asarray(plan["count"]) > 0) == [True, False, True, True]

    def paged(q, pool):
        return pa.paged_attention_extend(
            q, k, v, pool["k"], pool["v"], 1, plan, pos, lens,
            block_size=BS, interpret=True)

    for q_in, tol in ((q.astype(jnp.float32), TOL[jnp.float32]),
                      (q, TOL[dtype])):
        ref = mha_reference(q_in, kw.astype(q_in.dtype),
                            vw.astype(q_in.dtype), causal=True,
                            lengths=lens, q_positions=pos)
        out = paged(q_in, written)
        assert out.dtype == q_in.dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), **tol)
    padded = positions >= lengths[:, None]
    assert padded.any() and not np.any(
        np.asarray(out, np.float32).transpose(0, 2, 1, 3)[padded])
    np.testing.assert_array_equal(np.asarray(paged(q, pool), np.float32),
                                  np.asarray(out, np.float32))


@pytest.mark.parametrize("order", ["contiguous", "scattered", "descending"])
def test_plan_lists_each_slots_runs(order):
    """The run list against a plain loop over the table: a run opens
    where the 128-row group changes, holds one bit per block, and a slot
    whose pool keys are none (idle, or only its own key) has no run."""
    rng = np.random.default_rng(11)
    lengths = np.array([100, 1, 128, 17, 0, 36], np.int32)
    table = _tables(rng, lengths, order=order)
    visible = np.maximum(lengths - 1, 0)
    plan = pa.decode_plan(jnp.asarray(table), jnp.asarray(visible),
                          block_size=BS)
    per_group = pa.GROUP_ROWS // BS
    slot, group, bits, count, tail = [], [], [], [], []
    for b, n in enumerate(visible):
        n_blocks = -(-int(n) // BS)
        prev, count_b = None, 0
        for j in range(n_blocks):
            g, sub = divmod(int(table[b, j]), per_group)
            if g != prev:
                slot.append(b), group.append(g), bits.append(0)
                count_b += 1
            bits[-1] |= 1 << sub
            prev = g
        count.append(count_b)
        if n_blocks:
            tail.append(int(table[b, n_blocks - 1]) % per_group * 256
                        + int(n) - BS * (n_blocks - 1))
    n = int(plan["n_runs"][0])
    assert n == len(slot)
    assert list(np.asarray(plan["count"])) == count
    assert list(np.asarray(plan["first"])) == list(
        np.cumsum([0] + count[:-1]))
    assert list(np.asarray(plan["slot"])[:n]) == slot
    assert list(np.asarray(plan["group"])[:n]) == group
    assert list(np.asarray(plan["bits"])[:n]) == bits
    assert [int(t) for t, c in zip(np.asarray(plan["tail"]), count)
            if c] == tail
    if order == "contiguous":        # 19 blocks in 5 runs
        assert n < sum(-(-int(v) // BS) for v in visible)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_write_touches_its_rows_and_no_other(dtype):
    """Rows in one group, in neighbouring groups and at the pool's end;
    idle slots name the trash row and write nothing. Every other row of
    both pools stays bit for bit, in every layer."""
    rng = np.random.default_rng(3)
    pool = _pool(rng, dtype)
    rows = np.array([0, 130, 131, 0, 511, 257, 129, 0], np.int32)
    active = rows > 0
    k_new, v_new = (jnp.asarray(rng.standard_normal((L, len(rows), H, HD)),
                                dtype) for _ in range(2))
    k, v = pa.write_rows(pool["k"], pool["v"], k_new, v_new,
                         jnp.asarray(rows), jnp.asarray(active),
                         interpret=True)
    for got, old, new in ((k, pool["k"], k_new), (v, pool["v"], v_new)):
        want = np.array(old, np.float32)
        for b in np.flatnonzero(active):
            want[:, rows[b]] = np.asarray(new[:, b], np.float32)
        np.testing.assert_array_equal(np.asarray(got, np.float32), want)
        assert np.any(want != np.asarray(old, np.float32))


def test_write_applies_one_row_in_slot_order():
    """Two active slots naming one row (no engine does): the later wins,
    as with the window path's writes one slot after another."""
    rng = np.random.default_rng(4)
    pool = _pool(rng, jnp.float32)
    rows = np.array([200, 77, 200], np.int32)
    new = jnp.asarray(rng.standard_normal((L, 3, H, HD)), jnp.float32)
    k, _ = pa.write_rows(pool["k"], pool["v"], new, new, jnp.asarray(rows),
                         jnp.ones(3, bool), interpret=True)
    np.testing.assert_array_equal(k[:, 200], new[:, 2])
    np.testing.assert_array_equal(k[:, 77], new[:, 1])


def _position_rows(tables, n):
    """``BlockTable.rows(arange(n))`` for each table: positions past a
    table's blocks point into the trash block."""
    rows = np.zeros((len(tables), n), np.int32)
    for b, blocks in enumerate(tables):
        blocks = list(blocks) + [TRASH_BLOCK] * (-(-n // BS) - len(blocks))
        rows[b] = [blocks[p // BS] * BS + p % BS for p in range(n)]
    return rows


#: what an admission hands the block writer: (rows (B, n), first cache
#: layer, cache layers written). Four groups of eight blocks; block 0 is
#: the trash block.
BLOCK_WRITES = {
    "table_order": (_position_rows([[8, 9, 10, 11]], 64), 0, L),
    "reversed": (_position_rows([[11, 10, 9, 8]], 64), 0, L),
    "over_groups": (_position_rows([[3, 20, 12, 29]], 64), 0, L),
    # two sequences of a batch whose blocks interleave in two groups
    "two_share_groups": (_position_rows([[8, 17, 10], [9, 16, 11]], 48),
                         0, L),
    # one and two blocks of a 64-wide prefill: the padded positions name
    # the trash block, which lies in group 0 beside blocks 2, 5 and 6
    "padded_beside_trash": (_position_rows([[2], [5, 6]], 64), 0, L),
    # extend: spans that start inside a block and run on into the next
    # (neighbouring or not), padded with the trash ROW, one layer a call
    "mid_block_one_layer": (np.array(
        [[8 * BS + 5 + i for i in range(11)]
         + [20 * BS + i for i in range(9)] + [0] * 4,
         [9 * BS + 12 + i for i in range(22)] + [0] * 2], np.int32), 1, 1),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(BLOCK_WRITES))
def test_block_write_touches_its_rows_and_no_other(case, dtype):
    """``write_blocks`` sets every named row outside the trash block, in
    the layers it is given, to the new row cast to the pool's type;
    every other row of every layer (the trash block and blocks 1-7 of
    its group among them) stays bit for bit."""
    rows, layer, n_layers = BLOCK_WRITES[case]
    rng = np.random.default_rng(5)
    pool = _pool(rng, dtype)
    flat = rows.reshape(-1)
    active = flat >= BS
    k_new, v_new = (jnp.asarray(rng.standard_normal(
        (n_layers, flat.size, H, HD)), jnp.float32) for _ in range(2))
    plan = pa.write_plan(jnp.asarray(flat), jnp.asarray(active))
    # a piece never crosses a group of the pool or a tile of the new rows
    n = int(plan["n_pieces"][0])
    src, dst, count = (np.asarray(plan[x])[:n] for x in ("src", "dst", "n"))
    assert count.sum() == active.sum() and np.all(count > 0)
    assert np.all(dst // 128 == (dst + count - 1) // 128)
    assert np.all(src // 128 == (src + count - 1) // 128)
    assert np.all(np.diff(dst // 128) >= 0)
    k, v = pa.write_blocks(pool["k"], pool["v"], k_new, v_new, plan, layer,
                           interpret=True)
    for got, old, new in ((k, pool["k"], k_new), (v, pool["v"], v_new)):
        assert got.dtype == old.dtype
        want = np.array(old, np.float32)
        want[layer:layer + n_layers, flat[active]] = np.asarray(
            new.astype(dtype), np.float32)[:, active]
        np.testing.assert_array_equal(np.asarray(got, np.float32), want)
        assert np.any(want != np.asarray(old, np.float32))
        # a write of float32 rows is a cast and nothing else: the bits
        np.testing.assert_array_equal(
            np.asarray(got[layer:layer + n_layers, flat[active]]),
            np.asarray(new.astype(dtype)[:, active]))


def test_block_write_with_nothing_to_write():
    rng = np.random.default_rng(6)
    pool = _pool(rng, jnp.bfloat16)
    rows = jnp.zeros((32,), jnp.int32)
    new = jnp.ones((L, 32, H, HD), jnp.bfloat16)
    plan = pa.write_plan(rows, rows >= BS)
    assert int(plan["n_pieces"][0]) == 0
    k, v = pa.write_blocks(pool["k"], pool["v"], new, new, plan,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(k, np.float32),
                                  np.asarray(pool["k"], np.float32))
    np.testing.assert_array_equal(np.asarray(v, np.float32),
                                  np.asarray(pool["v"], np.float32))


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig.tiny(max_seq_len=64)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


@pytest.mark.parametrize("kv_dtype", ["f32", "bf16"])
def test_decode_program_matches_window_path(tiny, kv_dtype):
    """``make_decode_fn`` both ways from one prefilled pool: the same
    logits and the same new rows to float32 rounding (a later layer's K
    and V follow the earlier layers' attention), every row no slot wrote
    bit for bit as prefill left it."""
    cfg, params = tiny
    params = decode_lib.canonical_params(cfg, params)
    cc = CacheConfig.for_model(cfg, num_blocks=32, block_size=8,
                               kv_dtype=kv_dtype)
    prompts = [[5, 9, 2, 7, 1, 3, 8, 4, 6, 2, 9], [3, 1, 4], []]
    alloc = BlockAllocator(cc.num_blocks)
    prefill = jax.jit(decode_lib.make_prefill_fn(cfg, cc))
    pool = init_pool(cc)
    tables = []
    for p in prompts:
        t = BlockTable(cc, max_blocks=8)
        tables.append(t)
        if not p:
            continue
        t.ensure_room(len(p) + 4, alloc)
        toks = np.zeros((1, 16), np.int32)
        toks[0, :len(p)] = p
        _, pool = prefill(params, pool, jnp.asarray(toks),
                          jnp.asarray([len(p)], np.int32),
                          jnp.asarray(t.rows(np.arange(16))[None]))
        t.length = len(p)
    outs = {}
    for impl in ("window", "interpret"):
        fn = decode_lib.make_decode_fn(cfg, cc, implementation=impl)
        assert fn.kv_path == ("window" if impl == "window" else "paged")
        own = {n: jnp.array(a) for n, a in pool.items()}
        lengths = np.array([len(p) + 1 if p else 0 for p in prompts],
                           np.int32)
        for step in range(3):
            live = lengths > 0
            table = np.stack([
                t.window_rows() if impl == "window" else np.pad(
                    np.asarray(t.blocks, np.int32),
                    (0, 8 - len(t.blocks)), constant_values=TRASH_BLOCK)
                for t in tables])
            rows = np.array([t.row_of(n - 1) if n else 0
                             for t, n in zip(tables, lengths)], np.int32)
            logits, own = jax.jit(fn)(
                params, own, jnp.asarray([7 + step, 11, 0], np.int32),
                jnp.asarray(np.maximum(lengths - 1, 0)),
                jnp.asarray(lengths), jnp.asarray(rows), jnp.asarray(table))
            outs.setdefault(impl, []).append(np.asarray(logits)[live])
            lengths = lengths + live
        outs[impl].append({n: np.asarray(a, np.float32)
                           for n, a in own.items()})
    for a, b in zip(outs["window"][:-1], outs["interpret"][:-1]):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    start = {n: np.asarray(a, np.float32) for n, a in pool.items()}
    wrote = sorted({t.row_of(len(p) + i) for t, p in zip(tables, prompts)
                    if p for i in range(3)})
    kept = np.setdiff1d(np.arange(start["k"].shape[1]), wrote)
    for n in ("k", "v"):
        w, i = outs["window"][-1][n], outs["interpret"][-1][n]
        np.testing.assert_allclose(w[:, wrote], i[:, wrote],
                                   rtol=2e-5, atol=2e-5)
        assert np.any(w[:, wrote] != start[n][:, wrote])
        np.testing.assert_array_equal(i[:, kept], start[n][:, kept])
        np.testing.assert_array_equal(w[:, kept[8:]], start[n][:, kept[8:]])


def _admissions(cc):
    """Two prompts that share the pool's first group, as a (2, 32)
    prefill batch, and a span each to extend them by (one starts inside
    a block). Returns both programs' arguments after params and pool."""
    prompts = [[5, 9, 2, 7, 1, 3, 8, 4, 6, 2, 9], list(range(3, 23))]
    spans = [[7, 11, 13, 2, 5], [4] * 8]
    alloc = BlockAllocator(cc.num_blocks)
    tables = [BlockTable(cc, max_blocks=8) for _ in prompts]
    for t, p, s in zip(tables, prompts, spans):
        t.ensure_room(len(p) + len(s) + 1, alloc)
    S, E = 32, 8
    toks = np.zeros((2, S), np.int32)
    ext = np.zeros((2, E), np.int32)
    pos = np.full((2, E), 64, np.int32)               # pad -> masked query
    rows = np.zeros((2, E), np.int32)                 # pad -> trash row
    for b, (t, p, s) in enumerate(zip(tables, prompts, spans)):
        toks[b, :len(p)] = p
        ext[b, :len(s)] = s
        pos[b, :len(s)] = np.arange(len(p), len(p) + len(s))
        rows[b, :len(s)] = t.rows(pos[b, :len(s)])
    lengths = np.array([len(p) for p in prompts], np.int32)
    prefill_args = (jnp.asarray(toks), jnp.asarray(lengths), jnp.asarray(
        np.stack([t.rows(np.arange(S)) for t in tables])))
    extend_args = (jnp.asarray(ext), jnp.asarray(pos),
                   jnp.asarray(lengths + [len(s) for s in spans]),
                   jnp.asarray(rows),
                   jnp.asarray(np.stack([t.window_rows() for t in tables])))
    return prefill_args, extend_args


@pytest.mark.parametrize("kv_dtype", ["f32", "bf16"])
def test_admission_programs_match_the_scatter_path(tiny, kv_dtype):
    """``make_prefill_fn`` and ``make_extend_fn`` both ways over one
    pool. Prefill: the same logits, and the same pool on every row
    outside the trash block, bit for bit (the rows are a cast of the same
    K and V; only what lands in the trash block differs: the kernel skips
    it). Extend, whose layers read the keys before the span in place on
    the paged path: the same logits to float32 rounding (the kernel's
    softmax is online), the first layer's rows bit for bit, a later
    layer's (which follow the earlier layers' attention) to one rounding
    of the pool's dtype, and every row it does not write as it was."""
    cfg, params = tiny
    params = decode_lib.canonical_params(cfg, params)
    cc = CacheConfig.for_model(cfg, num_blocks=32, block_size=8,
                               kv_dtype=kv_dtype)
    prefill_args, extend_args = _admissions(cc)
    start = {n: a + 1 for n, a in init_pool(cc).items()}
    outs = {}
    for impl in ("scatter", "interpret"):
        prefill = decode_lib.make_prefill_fn(cfg, cc, implementation=impl)
        extend = decode_lib.make_extend_fn(cfg, cc, implementation=impl)
        want = "scatter" if impl == "scatter" else "paged"
        assert (prefill.kv_write, extend.kv_write) == (want, want)
        assert extend.kv_read == ("window" if impl == "scatter"
                                  else "paged")
        last, pool = jax.jit(prefill)(params, dict(start), *prefill_args)
        logits, after = jax.jit(extend)(params, pool, *extend_args)
        outs[impl] = (np.asarray(last), np.asarray(logits), pool, after)
    plain, paged = outs["scatter"], outs["interpret"]
    np.testing.assert_array_equal(paged[0], plain[0])
    np.testing.assert_allclose(paged[1][:, :5], plain[1][:, :5],
                               rtol=2e-5, atol=2e-5)
    live = cc.block_size                           # rows past the trash block
    written = np.asarray(extend_args[3]).reshape(-1)
    written = written[written >= live]
    kept = np.setdiff1d(np.arange(live, start["k"].shape[1]), written)
    tol = (TOL[jnp.bfloat16] if kv_dtype == "bf16"
           else dict(rtol=2e-5, atol=2e-5))
    for n in ("k", "v"):
        (pre, post), (pre_s, post_s) = (
            [np.asarray(x[n], np.float32) for x in out[2:]]
            for out in (paged, plain))
        np.testing.assert_array_equal(pre[:, live:], pre_s[:, live:])
        np.testing.assert_array_equal(post[:, kept], post_s[:, kept])
        np.testing.assert_array_equal(post[0, written], post_s[0, written])
        np.testing.assert_allclose(post[1:, written], post_s[1:, written],
                                   **tol)
        first = np.asarray(start[n], np.float32)
        assert np.any(pre[:, live:] != first[:, live:])
        assert np.any(post[:, written] != pre[:, written])
    # the kernel leaves the trash block as it was
    np.testing.assert_array_equal(
        np.asarray(paged[3]["k"], np.float32)[:, :live],
        np.asarray(start["k"], np.float32)[:, :live])


def _engine(cfg, params, impl, monkeypatch, admit=None, **kw):
    """An engine whose decode program is built with ``implementation``
    ``impl``, and its prefill and extend programs with ``admit`` if
    given: the engine itself passes none, so the test steers the
    builders."""
    steer = {"make_decode_fn": impl}
    if admit is not None:
        steer.update(make_prefill_fn=admit, make_extend_fn=admit)
    for name, how in steer.items():
        monkeypatch.setattr(
            engine_lib.decode_lib, name,
            lambda c, cc, implementation=None, how=how,
            real=getattr(decode_lib, name): real(c, cc, implementation=how))
    engine = InferenceEngine(cfg, params, **kw)
    monkeypatch.undo()
    return engine


SCENARIOS = {
    "mixed_batch": dict(
        engine=dict(num_blocks=32, block_size=8, max_slots=4),
        prompts=[[5, 6, 7], [9] * 11, [1, 2], [3] * 17, [4, 4, 4, 4]]),
    "prefix_hit": dict(
        engine=dict(num_blocks=32, block_size=8, max_slots=4,
                    prefix_caching=True),
        prompts=[list(range(1, 21)) + s
                 for s in ([30], [31, 32], [33] * 9, [34, 35, 36])]),
    "preemption": dict(
        engine=dict(num_blocks=16, block_size=8, max_slots=4,
                    max_prompt_len=16),
        prompts=[[7] * 9, [8] * 12, [9] * 5, [6] * 10, [5] * 14, [4] * 3]),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_engine_tokens_equal_on_both_paths(tiny, scenario, monkeypatch):
    cfg, params = tiny
    spec = SCENARIOS[scenario]
    outs, engines = {}, {}
    for impl in ("window", "interpret"):
        e = engines[impl] = _engine(cfg, params, impl, monkeypatch,
                                    **spec["engine"])
        outs[impl] = e.generate(spec["prompts"], max_new_tokens=32)
    assert engines["window"].kv_path == "window"
    assert engines["interpret"].kv_path == "paged"
    assert outs["interpret"] == outs["window"]
    for e in engines.values():
        acct = e.block_accounting()
        assert acct["conserved"] and acct["leaked_refs"] == 0
    if scenario == "preemption":
        assert engines["interpret"].scheduler.preemptions > 0
    if scenario == "prefix_hit":
        assert engines["interpret"].stats()["prefix_cache"]["hit_tokens"] > 0


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_engine_tokens_equal_on_both_write_paths(tiny, scenario,
                                                 monkeypatch):
    """A cold prompt, a prefix hit (extend) and a preempted replay
    through admission programs that scatter and read the window, and
    that write by blocks and read the blocks in place: the rows are the
    same bits (an extend's later layers the same to float32 rounding),
    so the tokens are the same."""
    cfg, params = tiny
    spec = SCENARIOS[scenario]
    outs, engines = {}, {}
    for admit in ("scatter", "interpret"):
        e = engines[admit] = _engine(cfg, params, "window", monkeypatch,
                                     admit=admit, **spec["engine"])
        outs[admit] = e.generate(spec["prompts"], max_new_tokens=32)
    assert engines["scatter"].kv_write == {"prefill": "scatter",
                                           "extend": "scatter"}
    assert engines["interpret"].kv_write == {"prefill": "paged",
                                             "extend": "paged"}
    assert (engines["scatter"].kv_read,
            engines["interpret"].kv_read) == ("window", "paged")
    assert outs["interpret"] == outs["scatter"]
    e = engines["interpret"]
    acct = e.block_accounting()
    assert acct["conserved"] and acct["leaked_refs"] == 0
    if scenario == "preemption":
        assert e.scheduler.preemptions > 0
    if scenario == "prefix_hit":
        assert e.stats()["prefix_cache"]["hit_tokens"] > 0


def test_engine_on_the_cpu_takes_the_window_path(tiny):
    cfg, params = tiny
    e = InferenceEngine(cfg, params, num_blocks=32, block_size=8,
                        max_slots=2)
    assert e.kv_path == "window"


@pytest.mark.parametrize("where,paths", [
    ("cpu", ("window", "scatter", "window")),
    ("tpu", ("paged", "paged", "paged")),
    ("tpu, int8 pool", ("window", "scatter", "window")),
    ("tpu, mesh", ("window", "scatter", "window")),
    ("tpu, rows pool", ("paged", "scatter", "window")),
    ("tpu, latent pool", ("paged", "scatter", "window")),
])
def test_engine_chooses_its_paths_by_what_it_sees(tiny, where, paths,
                                                  monkeypatch, request):
    """The kernels' paths only where the backend is a TPU (the builders
    ask ``jax.default_backend``; answered for them here), the pool is
    one a kernel reads, and no mesh would have to partition a
    ``pallas_call``: ``paths`` are decode's ``kv_path``, the admission
    programs' ``kv_write`` and extend's ``kv_read``. Extend reads in
    place, and both admissions write by blocks, only where the pool lies
    with its rows on the lanes; a row-major pool (heads of 128) and a
    latent one take XLA's scatter and the window gather, which read and
    write those layouts where they lie. Built, not run."""
    cfg, params = tiny
    kw = dict(num_blocks=32, block_size=8, max_slots=4)
    if "tpu" in where:
        monkeypatch.setattr(decode_lib.jax, "default_backend", lambda: "tpu")
    if "int8" in where:
        kw["kv_dtype"] = "int8"
    if "mesh" in where:
        kw["mesh"] = request.getfixturevalue("mesh2d")
    if "rows" in where:
        cfg = TransformerConfig.tiny(max_seq_len=64, d_model=1024,
                                     n_heads=8)
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    if "latent" in where:
        from distributed_tensorflow_tpu.models import scmoe
        cfg = TransformerConfig(
            vocab_size=128, d_model=64, n_layers=1, n_heads=4, d_ff=96,
            max_seq_len=64, dtype=jnp.float32, param_dtype=jnp.float32,
            tie_embeddings=False, sub_blocks=2,
            latent=dict(q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8,
                        v_dim=16),
            experts=dict(n_routed=8, n_identity=4, top_k=2, d_expert=48,
                         held=8))
        params = scmoe.init_params(cfg, jax.random.PRNGKey(0))
    e = InferenceEngine(cfg, params, **kw)
    assert (e.kv_path, e.kv_write["prefill"], e.kv_write["extend"],
            e.kv_read) == (paths[0], paths[1], paths[1], paths[2])
    if "rows" in where or "latent" in where:
        assert e._kv_layout == where.split()[1]


def test_speculative_engine_tokens_equal_on_both_read_paths(tiny,
                                                            monkeypatch):
    """Speculative verify is the extend program at (slots, k + 1): on a
    ``"lanes"`` pool it reads the keys before each slot's span in place,
    idle slots (nothing in the pool) among them. Drafts, verifies and the
    prefix hits' suffixes through admission programs that read the
    window, and that read the blocks: the same greedy tokens."""
    cfg, params = tiny
    spec = SCENARIOS["prefix_hit"]
    outs, engines = {}, {}
    for admit in ("scatter", "interpret"):
        e = engines[admit] = _engine(cfg, params, "window", monkeypatch,
                                     admit=admit, speculative_k=2,
                                     **spec["engine"])
        outs[admit] = e.generate(spec["prompts"], max_new_tokens=24)
    assert (engines["scatter"].kv_read,
            engines["interpret"].kv_read) == ("window", "paged")
    assert outs["interpret"] == outs["scatter"]
    for e in engines.values():
        assert e._spec_proposed_n > 0
        assert e.stats()["prefix_cache"]["hit_tokens"] > 0
        acct = e.block_accounting()
        assert acct["conserved"] and acct["leaked_refs"] == 0


@pytest.mark.parametrize("why,cache,impl", [
    ("unknown name", dict(num_blocks=32, block_size=8), "pallas"),
    ("int8 pool", dict(num_blocks=32, block_size=8, kv_dtype="int8"),
     "paged"),
    ("rows not a multiple of 128", dict(num_blocks=9, block_size=8),
     "interpret"),
    ("block size not a power of two", dict(num_blocks=32, block_size=12),
     "paged"),
])
def test_implementation_argument_is_checked(tiny, why, cache, impl):
    cfg, _ = tiny
    cc = CacheConfig.for_model(cfg, **cache)
    with pytest.raises(ValueError):
        decode_lib.make_decode_fn(cfg, cc, implementation=impl)
    for make in (decode_lib.make_prefill_fn, decode_lib.make_extend_fn):
        with pytest.raises(ValueError):
            make(cfg, cc, implementation=impl)
        assert make(cfg, cc).kv_write == "scatter"
    # and left alone, such a pool quietly takes the window path
    assert decode_lib.make_decode_fn(cfg, cc).kv_path == "window"


# -- what the TPU's compiler makes of it: a described v5e, no chip ----------

@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache and cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _tbig_serving_specs(one_chip):
    """transformer-big at the benchmark's serving shapes, described and
    not allocated: ``(cfg, cache config, spec(shape, dtype=int32),
    params, pool)`` with every array on ``one_chip``."""
    import dataclasses

    cfg = TransformerConfig.transformer_big(max_seq_len=1024,
                                            scan_layers=False)
    cc = CacheConfig.for_model(cfg, num_blocks=4096, block_size=16,
                               dtype=jnp.bfloat16)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    model = TransformerLM(dataclasses.replace(cfg, scan_layers=True))
    shapes = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    # the tree as the engine serves float32 weights: every matrix in
    # bfloat16, in the model's own shapes (heads of 64: the device keeps
    # D on the lanes by itself)
    assert not decode_lib.wants_resident(cfg)
    shapes = jax.eval_shape(
        lambda p: decode_lib.compute_params(cfg, p),
        decode_lib._plain(shapes["params"]))
    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype), shapes)
    pool = {n: spec((cc.n_layers, cc.num_blocks * cc.block_size, cc.n_heads,
                     cc.head_dim), cc.dtype) for n in ("k", "v")}
    return cfg, cc, spec, params, pool


def _weight_shaped_work(hlo: str, params) -> list:
    """The entry computation's converts, copies and fusions that produce
    an array of a weight matrix's shape: what a program run pays where
    its weights arrive in another type or layout than it computes with.
    (A prefetch into the chip's near memory is ``copy-start`` /
    ``copy-done`` / ``slice-start`` and is not matched: it moves a
    weight without rewriting it, beside the compute.)"""
    shapes = {",".join(map(str, leaf.shape))
              for leaf in jax.tree_util.tree_leaves(params) if leaf.ndim > 1}
    entry = hlo[hlo.index("\nENTRY "):]
    return [line.strip()[:160] for line in entry.splitlines()
            if (m := re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([0-9,]+)\]\S* "
                              r"(convert|copy|fusion)\(", line))
            and m.group(1) in shapes]


@pytest.mark.parametrize("impl,clean", [("paged", True), ("window", False)])
def test_compiled_decode_holds_nothing_of_the_pools_size(
        one_chip, no_compile_cache, impl, clean):
    """transformer-big at the benchmark's serving shapes, compiled for
    one v5e chip: the paged program produces no array of the pool's, a
    layer's or the 64 x 1024-row window's size (``chip_smoke``'s check,
    which the window program fails 200-fold), and the Mosaic kernels
    compile at these widths."""
    import chip_smoke

    cfg, cc, spec, params, pool = _tbig_serving_specs(one_chip)
    slots, window = 64, 1024
    rows = cc.num_blocks * cc.block_size
    row = cc.n_heads * cc.head_dim
    vec = spec((slots,))
    table = spec((slots, window // cc.block_size if impl == "paged"
                  else window))
    fn = decode_lib.make_decode_fn(cfg, cc, implementation=impl)
    hlo = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, vec, vec, vec, vec, table).compile().as_text()
    found = chip_smoke.pool_sized_ops(
        hlo, {cc.n_layers * rows * row, rows * row, slots * window * row})
    assert (not found) == clean, found[:5]
    assert ("paged_attn_decode" in hlo) == clean
    # the weights arrive as the program computes with them: no convert,
    # no relayout of a matrix at the head of a run
    assert not _weight_shaped_work(hlo, params)


@pytest.mark.parametrize("program,impl,clean", [
    ("prefill", "paged", True), ("prefill", "scatter", False),
    ("extend", "paged", True), ("extend", "scatter", False)])
def test_compiled_admission_holds_nothing_of_the_pools_size(
        one_chip, no_compile_cache, program, impl, clean):
    """transformer-big's admission programs at the benchmark's shapes
    (a 1024-wide cold prefill; a 64-wide extend over a 1024-row window),
    compiled for one v5e chip. Written by blocks (and extend's keys
    before the span read through the block table, ``paged_attn_extend``),
    neither produces an array of the pool's or a layer's size; both hold
    under a gigabyte of temporaries beside a pool that is updated where
    it lies. The scatter costs either two copies of each pool."""
    import chip_smoke

    cfg, cc, spec, params, pool = _tbig_serving_specs(one_chip)
    rows = cc.num_blocks * cc.block_size
    row = cc.n_heads * cc.head_dim
    sizes = {cc.n_layers * rows * row, rows * row}
    if program == "prefill":
        fn = decode_lib.make_prefill_fn(cfg, cc, implementation=impl)
        args = (spec((1, 1024)), spec((1,)), spec((1, 1024)))
    else:
        fn = decode_lib.make_extend_fn(cfg, cc, implementation=impl)
        args = (spec((1, 64)), spec((1, 64)), spec((1,)), spec((1, 64)),
                spec((1, 1024)))
        assert fn.kv_read == ("paged" if clean else "window")
    assert fn.kv_write == impl
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, *args).compile()
    hlo = compiled.as_text()
    found = chip_smoke.pool_sized_ops(hlo, sizes)
    assert (not found) == clean, found[:5]
    assert ("paged_kv_write" in hlo) == clean
    assert ("paged_attn_extend" in hlo) == (clean and program == "extend")
    assert not _weight_shaped_work(hlo, params)
    if program == "prefill":
        # the head runs on the one row a prompt's first token needs: a
        # (1024, vocab) array of logits is work nobody reads, and it took
        # the fast memory that the twelve layers' (heads, 1024, 1024)
        # score matrices then lost (PERF.md section 6, PR 35)
        entry = hlo[hlo.index("\nENTRY "):]
        assert f"[1024,{cfg.vocab_size}]" not in entry
        scores = re.findall(r"f32\[16,1024,1024\]\{[^}]*\}", entry)
        assert scores and all("S(1)" in s for s in scores)
    memory = compiled.memory_analysis()
    pool_bytes = cc.n_layers * rows * row * 2
    assert (memory.temp_size_in_bytes < 1 << 30) == clean
    assert memory.alias_size_in_bytes >= 2 * pool_bytes


@pytest.mark.parametrize("program", ["decode", "decode_steps", "prefill"])
def test_compiled_looped_programs_hold_nothing_of_the_pools_size(
        one_chip, no_compile_cache, program):
    """The looped model at its published widths (``chip_smoke
    .looped_config``: 3 layers x 4 passes, head_dim 128, bfloat16
    weights) over a pool of 192 blocks of 16 rows (at the benchmark's 256
    the pool would count the elements the 49152-row head counts), compiled
    for one v5e chip: the pool lies row-major there, the decode program
    reads it with the ``rows`` kernel and writes it with a scatter in
    place (alone, and eight steps of it in one program:
    ``make_multi_decode_fn``), prefill writes every cache layer in one
    scatter, and none produces or holds an array of the pool's or a cache
    layer's size."""
    import chip_smoke

    cfg = chip_smoke.looped_config()
    cc = CacheConfig.for_model(cfg, num_blocks=192, block_size=16)
    assert cc.n_layers == 12 and cc.bytes_per_token == 12 * 2 * 2048 * 2
    slots, window = 8, cfg.max_seq_len

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the tree as the engine keeps bfloat16 weights: projection kernels
    # as (H * hd, D) matrices, which the compiler does not relayout
    shapes = jax.eval_shape(
        lambda r: decode_lib.resident_params(cfg, TransformerLM(cfg).init(
            r, jnp.zeros((1, 8), jnp.int32))["params"]),
        jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype), decode_lib._plain(shapes))
    rows = cc.num_blocks * cc.block_size
    row = cc.n_heads * cc.head_dim
    pool = {n: spec((cc.n_layers, rows, cc.n_heads, cc.head_dim), cc.dtype)
            for n in ("k", "v")}
    vec = spec((slots,), jnp.int32)
    wide = spec((1, window), jnp.int32)
    decode = program != "prefill"
    if decode:
        fn = decode_lib.make_decode_fn(cfg, cc, implementation="paged")
        table = spec((slots, window // cc.block_size), jnp.int32)
        args = (vec, vec, vec, vec, table)
        if program == "decode_steps":
            fn = decode_lib.make_multi_decode_fn(fn, 8)
            args = (vec, vec, vec, spec((slots, 8), jnp.int32), table, vec)
        assert (fn.kv_path, fn.kv_layout, fn.passes) == ("paged", "rows", 4)
    else:
        fn = decode_lib.make_prefill_fn(cfg, cc, implementation="paged")
        assert fn.kv_write == "scatter"         # row-major: XLA's, in place
        args = (wide, spec((1,), jnp.int32), wide)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, *args).compile()
    hlo = compiled.as_text()
    sizes = {cc.n_layers * rows * row, rows * row}
    if decode:
        sizes.add(slots * window * row)
    found = chip_smoke.pool_sized_ops(hlo, sizes)
    assert not found, found[:5]
    memory = compiled.memory_analysis()
    pool_bytes = cc.n_layers * rows * row * 2
    assert memory.temp_size_in_bytes < pool_bytes
    if decode:
        # no copy of the stacked weights either (25 MB a projection
        # here): what is left is the step's own activations
        assert memory.temp_size_in_bytes < 8 << 20
        assert "copy(%params" not in hlo
    assert memory.alias_size_in_bytes >= 2 * pool_bytes
    assert ("paged_attn_decode_rows" in hlo) == decode
    # one compiled body for all four passes and all layers: the kernel
    # is called from a loop and stands once in the program's text
    if decode:
        assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 1


def test_compiled_checksum_holds_no_copy_of_its_leaf(one_chip,
                                                     no_compile_cache):
    """``params_digest`` computes on the device; at a hot swap it runs
    beside the old weights and the pool (11.78 of 16 GB for the looped
    model), so its checksum of the largest leaf (the stacked gate and up
    projections, 2.2 GB of bfloat16) must be one fused reduction:
    compiled for a v5e it holds no widened or flattened copy of the
    leaf."""
    from distributed_tensorflow_tpu.serving.engine import _leaf_checksum

    leaf = jax.ShapeDtypeStruct((48, 2048, 11264), jnp.bfloat16,
                                sharding=one_chip)
    memory = jax.jit(_leaf_checksum).lower(leaf).compile().memory_analysis()
    assert memory.argument_size_in_bytes == 48 * 2048 * 11264 * 2
    assert memory.temp_size_in_bytes < 1 << 20


def _latent_serving_specs(one_chip):
    """One shortcut-connected layer at the published widths of the
    benchmark's latent configuration over its pool, described and not
    allocated: ``(cfg, cache config, spec(shape, dtype=int32), params,
    pool)`` with every array on ``one_chip``."""
    from distributed_tensorflow_tpu.models import scmoe

    cfg = TransformerConfig(
        vocab_size=16384, d_model=6144, n_layers=1, n_heads=64, d_ff=12288,
        max_seq_len=1024, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        tie_embeddings=False, rope_base=1e7, norm_eps=1e-5, sub_blocks=2,
        latent=dict(q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
                    v_dim=128, scale_q=True, scale_kv=True),
        experts=dict(n_routed=512, n_identity=256, top_k=12, d_expert=2048,
                     scaling=6.0, held=16, offset=80))
    cc = CacheConfig.for_model(cfg, num_blocks=4608, block_size=16)
    assert (cc.n_layers, cc.row_shape, cc.bytes_per_token) == (2, (640,),
                                                               2 * 1280)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params: dict = {}
    for path, (shape, _) in scmoe.param_plan(cfg).items():
        node = params
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = spec(shape, jnp.bfloat16)
    rows = cc.num_blocks * cc.block_size
    pool = {"latent": spec((cc.n_layers, rows) + cc.row_shape, cc.dtype)}
    return cfg, cc, spec, params, pool


@pytest.mark.parametrize("program", ["decode", "prefill", "extend"])
def test_compiled_latent_programs_hold_nothing_of_the_pools_size(
        one_chip, no_compile_cache, program):
    """One shortcut-connected layer at the published widths of the
    benchmark's latent configuration (hidden 6144, 64 heads over a row of
    512 + 64, 16 held experts of width 2048; 1.24B parameters in
    bfloat16) over its pool of 4,608 blocks, compiled for one v5e chip:
    the latent decode kernel and the grouped expert product compile at
    these widths, no program produces an array of the pool's or a cache
    layer's size (the pool lies row-major, its rows in 640 values, and
    the (layer, row) scatter writes it in place), and none converts,
    copies or slices out a weight matrix (each is sliced where it is
    used; the experts' stacks go to their kernel whole)."""
    import chip_smoke

    cfg, cc, spec, params, pool = _latent_serving_specs(one_chip)
    rows = cc.num_blocks * cc.block_size
    slots, window = 64, cfg.max_seq_len
    vec, one = spec((slots,)), spec((1,))
    if program == "decode":
        fn = decode_lib.make_decode_fn(cfg, cc, implementation="paged")
        assert (fn.kv_path, fn.kv_layout) == ("paged", "latent")
        args = (vec, vec, vec, vec, spec((slots, window // cc.block_size)))
    elif program == "prefill":
        fn = decode_lib.make_prefill_fn(cfg, cc, implementation="paged")
        args = (spec((1, window)), one, spec((1, window)))
    else:
        fn = decode_lib.make_extend_fn(cfg, cc, implementation="paged")
        wide = spec((1, 128))
        args = (wide, wide, one, wide, spec((1, window)))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, *args).compile()
    hlo = compiled.as_text()
    def scatters(line):
        # a fusion whose root is the scatter itself writes the donated
        # pool where it lies (prefill's carries no op_name to say so)
        called = re.search(r"calls=(%[\w.]+)", line)
        body = hlo[hlo.index(f"\n{called.group(1)} ("):] if called else ""
        return " scatter(" in body[:body.find("\n}")].rsplit("ROOT", 1)[-1]

    found = [line for line in chip_smoke.pool_sized_ops(
        hlo, {cc.n_layers * rows * cc.row_shape[0], rows * cc.row_shape[0]})
        if not scatters(line)]
    assert not found, found[:5]
    memory = compiled.memory_analysis()
    pool_bytes = rows * cc.bytes_per_token
    assert memory.alias_size_in_bytes >= pool_bytes
    # what is left is the program's own activations: the decode step's
    # are small, an admission's hold its attention scores and the
    # worst-case layout of the expert product
    assert memory.temp_size_in_bytes < ((64 << 20) if program == "decode"
                                        else (1536 << 20))
    assert not _weight_shaped_work(hlo, params)
    # nor a matrix sliced out of its stack into an array of its own
    matrices = {",".join(map(str, leaf.shape[-2:]))
                for leaf in jax.tree_util.tree_leaves(params)
                if np.prod(leaf.shape[-2:]) >= 1 << 20}
    copies = [line.strip()[:160]
              for line in hlo[hlo.index("\nENTRY "):].splitlines()
              if (m := re.match(r"\s*(?:ROOT )?%\S+ = bf16\[(?:1,)*([0-9]+,"
                                r"[0-9]+)\]\S* (copy|fusion|transpose)\(",
                                line)) and m.group(1) in matrices]
    assert not copies, copies[:5]
    kernels = set(re.findall(r"%((?:paged_attn|expert)_\w+?)\.\d+ = ", hlo))
    assert kernels == ({"paged_attn_decode_latent", "expert_grouped_matmul"}
                       if program == "decode"
                       else {"expert_grouped_matmul"})


@pytest.mark.parametrize("model,program", [
    ("tbig", "decode"), ("tbig", "prefill"), ("tbig", "extend"),
    ("latent", "decode")])
def test_compiled_launches_choose_the_next_token_on_the_device(
        one_chip, no_compile_cache, model, program):
    """What the engine launches (``decode.launch_*``) at the benchmark's
    shapes, compiled for one v5e chip beside the program it wraps: it
    takes the chosen tokens and the one array the host sends, returns the
    chosen tokens ((slots,) int32: the argmax lies inside) beside the
    logits the plain program returns, updates the pool in place, keeps
    the kernels, and holds no more
    than the plain program with its outputs (so a second launch queued
    behind a first adds nothing to the peak the plain step had)."""
    cfg, cc, spec, params, pool = (_tbig_serving_specs if model == "tbig"
                                   else _latent_serving_specs)(one_chip)
    slots, window = 64, cfg.max_seq_len
    T, E = window // cc.block_size, 64
    if program == "decode":
        fn = decode_lib.make_decode_fn(cfg, cc, implementation="paged")
        launch, host = decode_lib.launch_decode(fn), spec((slots, 4 + T))
        plain = (spec((slots,)),) * 4 + (spec((slots, T)),)
    elif program == "prefill":
        fn = decode_lib.make_prefill_fn(cfg, cc, implementation="paged")
        launch, host = decode_lib.launch_prefill(fn), spec((2 + 2 * window,))
        plain = (spec((1, window)), spec((1,)), spec((1, window)))
    else:
        fn = decode_lib.make_extend_fn(cfg, cc, implementation="paged")
        launch = decode_lib.launch_extend(fn, window)
        host = spec((2 + 3 * E + window,))
        plain = (spec((1, E)),) * 2 + (spec((1,)), spec((1, E)),
                                       spec((1, window)))
    chosen = spec((slots,))
    out = jax.eval_shape(launch, params, pool, chosen, host)
    assert (out[0].shape, out[0].dtype) == ((slots,), jnp.int32)
    got = jax.jit(launch, donate_argnums=(1,)).lower(
        params, pool, chosen, host).compile()
    base = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, *plain).compile()
    assert got.as_text().count("tpu_custom_call") == \
        base.as_text().count("tpu_custom_call")
    m, b = got.memory_analysis(), base.memory_analysis()
    assert m.alias_size_in_bytes == b.alias_size_in_bytes > 0
    assert (m.temp_size_in_bytes + m.output_size_in_bytes
            <= b.temp_size_in_bytes + b.output_size_in_bytes + (1 << 20)), (
        m.temp_size_in_bytes, m.output_size_in_bytes,
        b.temp_size_in_bytes, b.output_size_in_bytes)
