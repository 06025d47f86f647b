"""Paged attention for the serving decode and extend steps (Pallas, TPU).

One query per running slot against the K and V of that slot's LIVE
blocks only, read out of the block-allocated pool (serving/kv_cache.py)
where it lies. The window path of ``serving/decode.py`` gathers every
slot's full ``max_seq_len`` window out of the pool on every step, so
its cost follows ``max_slots × max_seq_len``; this kernel's follows the
live tokens.

**The layout decides the shape of the kernel.** The pool is
``(L, rows, H, hd)`` and the TPU keeps an array whose minor dimension is
under 128 with its largest dimension minor-most: ``{1,3,2,0}``, rows on
the lanes, ``hd`` on the sublanes, tiled ``(8,128)(2,1)`` for bfloat16.
XLA's gather and scatter ask for ``{3,2,1,0}`` and pay a copy of the
whole pool on each side of every program that uses them. A custom call
takes its operands major-to-minor, so the kernel is handed the pool
through the transpose ``(L, H, hd, rows)``, which in that layout is a
bitcast: per head a ``K^T`` tile with the rows on the lanes. A DMA out of
such an array moves whole 128-lane tiles, so the unit the kernel reads is
a *group* of 128 consecutive rows (``128 / block_size`` blocks), and the
lanes that are not this slot's live rows are masked. Consecutive entries
of a slot's block table that fall into one group (the allocator hands
out lowest ids first, so a prompt's blocks mostly do) are merged into
one *run*: one DMA, one pass.

The grid is flat over the runs of all slots (its size is a traced
number), with the run list as scalar prefetch, so the pipeline of
``pallas_call`` itself double-buffers the groups across slot boundaries
and skips a group it already holds. Per run: float32 logits as an
elementwise product with the lane-broadcast query reduced over the
sublanes, online softmax, and the value product accumulated per lane and
reduced over the lanes once per slot. Arithmetic is ``mha_reference``'s:
K and V as stored, converted to float32 in VMEM; float32 logits, softmax
and accumulation; the same ``sm_scale`` and visibility rule.

**At ``head_dim`` 128 the layout is the other one**, and so is the
kernel. A minor dimension of 128 fills the lanes, so the device keeps the
pool as written, ``{3,2,1,0}``: a row is one ``(H, hd)`` tile, a block of
``block_size`` rows is contiguous, and the transpose above would be a copy
of the whole pool. :func:`supported` says which layout a pool lies in
(``"lanes"`` or ``"rows"``); the ``"rows"`` kernel (``paged_attn_decode_rows``)
takes the pool as it is and reads whole blocks and nothing else: a grid
step takes ``BLOCKS_PER_STEP`` consecutive entries of a slot's table (each
its own DMA; a slot's last step is padded with the trash block), the logits
of all heads are one matrix product of a block's ``(rows * H, hd)`` keys
with the queries, of which the entries whose key head is the query's head
are kept, and the value product is a second one. XLA's scatter writes that
layout in place, so :func:`write_rows` needs no kernel there.

**A latent pool** (``"latent"``: latent attention keeps ONE row of
``latent_dim`` values a token, shared by all query heads, and no per-head
K or V) is ``(L, rows, latent_dim)``, row-major as written, and has a
kernel of its own (``paged_attn_decode_latent``): a grid step reads
``LATENT_BLOCKS_PER_STEP`` blocks of a slot once for all heads, the
logits are one matrix product of the heads' absorbed queries with those
rows over the whole row, and the values are the rows' first ``v_dim``
values, so the output is a latent vector a head that the caller expands.
XLA's scatter writes that pool in place (:func:`write_latent_rows`).

The token being decoded never comes out of the pool: its K and V are in
registers when the step runs, so :func:`paged_attention_decode` merges
that one key into the softmax after the kernel (the query sees its own
position whatever the order of read and write), and the pool is written
once per step, after the last layer, by :func:`write_rows`.

**The extend program reads a ``"lanes"`` pool the same way**
(``paged_attn_extend``, :func:`paged_attention_extend`): E queries a slot
(a suffix after a prefix-cache hit, or a speculative verify), of which
every one sees every key before the span's first position, so the run
list is :func:`decode_plan`'s over the table with that position as the
length and the only mask is its lanes'. Per run the ``(H, E, hd)``
queries meet the ``(H, hd, 128)`` keys on the MXU, batched over the heads,
and the probabilities the ``(H, hd, 128)`` values in a product contracted
over the lanes; the grid is the tiles of at most ``EXTEND_QUERIES``
queries outside the flat runs.
The span's own keys are in registers: they are merged after the kernel,
an ``E x E`` block causal by position, as the decoded token's key is, so
the kernel never reads a row the span writes. XLA's gather of a slot's
window out of that layout relays a whole pool layer first, in every layer
of every extend (PERF.md section 6, PR 40).

**One kernel writes the ``"lanes"`` pool** (``paged_kv_write``,
:func:`write_blocks`), for the decode step's one row a slot and for the
admission programs' runs of whole blocks alike: it takes the pool in the
same view, aliased to its output, reads a group, sets the lanes of the
written rows that fall in it (a block is 16 neighbouring lanes) and
writes the group back, so that no other group is touched and nothing of
the pool's size is produced.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_tpu.ops.attention import DEFAULT_MASK_VALUE

#: Rows of the pool one DMA moves: the lane width of a TPU tile.
GROUP_ROWS = 128
#: Blocks of a row-major pool one grid step of its kernel reads. A step
#: costs about 0.35 us whatever it moves and a 16-row block of K and V is
#: 128 KB (0.16 us at the v5e's 819 GB/s): one block a step read 17,700
#: blocks in 9.1 ms (0.51 us each, 30% of the roofline; PERF.md, PR 28).
BLOCKS_PER_STEP = 4
#: Blocks of a latent pool one grid step reads: a 16-row block of 576
#: bfloat16 values is 18 KB, and 8 of them are the 128 rows that fill the
#: lanes of the logits and the contraction of the value product.
LATENT_BLOCKS_PER_STEP = 8


def supported(rows: int, block_size: int, head_dim: int, dtype,
              n_heads: int | None = None, latent_dim: int = 0) -> str | None:
    """The layout a floating-point pool of this shape lies in on the
    device, if a kernel here reads it, else ``None``.

    ``"lanes"``: ``head_dim`` under 128, which the device keeps with the
    rows on the lanes; read by 128-row groups, so the rows tile by 128,
    blocks tile a group (a power of two, so lane → block is a shift, and
    at most 31 of them, one bit each) and ``head_dim`` fills the dtype's
    sublane packing. ``"rows"``: ``head_dim`` a multiple of 128, kept
    row-major; read by blocks, whose ``(block_size * n_heads, head_dim)``
    keys are one matrix operand, so the heads fill the sublane packing
    (asked only where ``n_heads`` is given) and blocks are a power of two
    of at most 128 rows. Any other ``head_dim`` pads on the device in a
    way no kernel here reads. ``"latent"``: a pool of latent rows
    (``latent_dim`` > 0, no heads), row-major; read by blocks of whole
    rows, which fill the dtype's sublane packing."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        return None
    packing = 8 * (4 // dtype.itemsize)
    if block_size & (block_size - 1) or not 8 <= block_size <= GROUP_ROWS:
        return None
    if latent_dim:
        return "latent" if block_size % packing == 0 else None
    if head_dim < GROUP_ROWS:
        return ("lanes" if rows % GROUP_ROWS == 0
                and head_dim % packing == 0 else None)
    if head_dim % GROUP_ROWS or (n_heads or packing) % packing:
        return None
    return "rows"


def decode_plan(block_table, lengths, *, block_size: int) -> dict:
    """The run list of one decode step over a ``"lanes"`` pool, shared by
    every layer.

    ``block_table`` (B, max_blocks) int32 physical blocks in logical
    order (anything past a slot's live blocks is ignored), ``lengths``
    (B,) keys visible in the pool. A *run* is a maximal stretch of
    consecutive table entries inside one 128-row group. Returns int32
    arrays: per run (flat, slot-major, ``B * max_blocks`` long) its
    ``slot``, its ``group`` and the ``bits`` of the group's blocks it
    holds; per slot the ``first`` run, the ``count`` of runs, and
    ``tail`` = which block of its group the slot's last block is
    (high bits) and how many of its rows are live (low 8 bits); and
    ``n_runs`` ``(1,)``, the grid's size."""
    B, M = block_table.shape
    per_group = GROUP_ROWS // block_size
    lengths = lengths.astype(jnp.int32)
    n_blocks = (lengths + block_size - 1) // block_size           # (B,)
    j = jnp.arange(M, dtype=jnp.int32)[None]
    live = j < n_blocks[:, None]
    group = block_table // per_group
    sub = block_table % per_group
    prev = jnp.concatenate([jnp.full((B, 1), -1, jnp.int32),
                            group[:, :-1]], axis=1)
    starts = live & (group != prev)
    count = jnp.sum(starts, axis=1, dtype=jnp.int32)              # (B,)
    first = jnp.cumsum(count, dtype=jnp.int32) - count
    run = first[:, None] + jnp.cumsum(starts, axis=1, dtype=jnp.int32) - 1
    N = B * M
    run = jnp.where(live, run, N).reshape(-1)                     # N: dropped
    slot = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, M))
    zeros = jnp.zeros((N,), jnp.int32)
    last = jnp.maximum(n_blocks - 1, 0)
    tail_sub = jnp.take_along_axis(sub, last[:, None], axis=1)[:, 0]
    tail_rows = lengths - last * block_size
    return {
        "slot": zeros.at[run].set(slot.reshape(-1), mode="drop"),
        "group": zeros.at[run].set(group.reshape(-1), mode="drop"),
        "bits": zeros.at[run].add((1 << sub).reshape(-1), mode="drop"),
        "first": first, "count": count,
        "tail": tail_sub * 256 + tail_rows,
        "n_runs": jnp.sum(count, dtype=jnp.int32)[None],
    }


def block_plan(block_table, lengths, *, block_size: int,
               per_step: int = BLOCKS_PER_STEP) -> dict:
    """The run list of one decode step over a ``"rows"`` pool (or, with
    ``per_step`` = ``LATENT_BLOCKS_PER_STEP``, a ``"latent"`` one), shared
    by every cache layer: a *run* is ``per_step`` consecutive entries
    of a slot's table. Arguments as :func:`decode_plan`. Returns int32
    arrays: per run (flat, slot-major) its ``slot``, the ``rows`` of it
    that are live (counted from its first row) and its ``blocks``
    (``BLOCKS_PER_STEP`` a run, flat; past a slot's live blocks the trash
    block 0, whose rows are never live); per slot the ``first`` run and
    the ``count`` of runs; and ``n_runs`` ``(1,)``, the grid's size."""
    B, M = block_table.shape
    P = per_step
    C = -(-M // P)                                   # runs a slot at most
    lengths = lengths.astype(jnp.int32)
    n_blocks = (lengths + block_size - 1) // block_size           # (B,)
    count = (n_blocks + P - 1) // P
    first = jnp.cumsum(count, dtype=jnp.int32) - count
    c = jnp.arange(C, dtype=jnp.int32)[None]                      # (1, C)
    N = B * C
    run = jnp.where(c < count[:, None], first[:, None] + c, N).reshape(-1)
    j = jnp.arange(C * P, dtype=jnp.int32)[None]
    table = jnp.pad(block_table, ((0, 0), (0, C * P - M)))
    table = jnp.where(j < n_blocks[:, None], table, 0).reshape(N, P)
    rows = jnp.clip(lengths[:, None] - c * (P * block_size), 0,
                    P * block_size)
    slot = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, C))
    zeros = jnp.zeros((N,), jnp.int32)
    return {
        "slot": zeros.at[run].set(slot.reshape(-1), mode="drop"),
        "rows": zeros.at[run].set(rows.reshape(-1), mode="drop"),
        "blocks": jnp.zeros((N, P), jnp.int32).at[run].set(
            table, mode="drop").reshape(-1),
        "first": first, "count": count,
        "n_runs": jnp.sum(count, dtype=jnp.int32)[None],
    }


def plan_for(layout: str, block_table, lengths, *, block_size: int) -> dict:
    """The run list the ``layout``'s kernel takes."""
    if layout == "latent":
        return block_plan(block_table, lengths, block_size=block_size,
                          per_step=LATENT_BLOCKS_PER_STEP)
    plan = decode_plan if layout == "lanes" else block_plan
    return plan(block_table, lengths, block_size=block_size)


def count_runs(layout: str, block_table, n_blocks, block_size: int) -> int:
    """The runs (the kernel's grid steps per cache layer) of a step whose
    slots hold ``n_blocks`` (B,) live blocks each: what the plan's
    ``n_runs`` reads, computed on the host with numpy for the engine's
    ``runs_read`` counter."""
    import numpy as np
    if layout in ("rows", "latent"):
        per_step = (BLOCKS_PER_STEP if layout == "rows"
                    else LATENT_BLOCKS_PER_STEP)
        return int(np.sum(-(-n_blocks // per_step)))
    group = block_table // (GROUP_ROWS // block_size)
    opens = np.ones_like(group, bool)
    opens[:, 1:] = group[:, 1:] != group[:, :-1]
    live = np.arange(block_table.shape[1])[None] < n_blocks[:, None]
    return int(np.sum(opens & live))


def _live_lanes(shape, bits, tail, block_size: int):
    """Which lanes of a run's 128-row group are its slot's live rows, as a
    ``shape`` mask (the lanes minor-most): those of the blocks ``bits``
    holds, less the rows past the slot's last one where ``tail`` (a
    :func:`decode_plan` tail, or ``GROUP_ROWS * 256`` on a run that is not
    the slot's last) cuts its block."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    lane_sub = lane >> (block_size.bit_length() - 1)
    held = (jnp.full(shape, bits, jnp.int32) >> lane_sub) & 1
    cut = (lane_sub == (tail >> 8)) & ((lane & (block_size - 1))
                                       >= (tail & 255))
    return (held == 1) & jnp.logical_not(cut)


def _decode_kernel(layer_ref, slot_ref, group_ref, bits_ref, first_ref,
                   count_ref, tail_ref,                     # scalar prefetch
                   q_ref, k_ref, v_ref,                     # inputs
                   o_ref, m_ref, l_ref,                     # outputs
                   qb_scr, acc_scr, s_scr, p_scr, a_scr, m_scr, l_scr,
                   *, sm_scale: float, block_size: int, n_heads: int):
    """One run: 128 rows of one slot's K and V, ``(H, hd, 128)`` each."""
    del layer_ref, group_ref                     # the index maps read them
    i = pl.program_id(0)
    b = slot_ref[i]
    is_first = i == first_ref[b]
    is_last = i == first_ref[b] + count_ref[b] - 1
    hd = q_ref.shape[1]

    @pl.when(is_first)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, DEFAULT_MASK_VALUE, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
        for h in range(n_heads):
            qb_scr[h] = jnp.broadcast_to(q_ref[0, :, h:h + 1],
                                         (hd, GROUP_ROWS))

    tail = jnp.where(is_last, tail_ref[b], GROUP_ROWS * 256)
    valid = _live_lanes((n_heads, GROUP_ROWS), bits_ref[i], tail, block_size)

    for h in range(n_heads):
        kf = k_ref[h].astype(jnp.float32)                    # (hd, 128)
        s_scr[h:h + 1, :] = jnp.sum(kf * qb_scr[h], axis=0, keepdims=True)
    s = jnp.where(valid, s_scr[...] * sm_scale, DEFAULT_MASK_VALUE)
    m_prev = m_scr[...]                                      # (H, 128)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new
    p_scr[...] = p
    a_scr[...] = alpha
    for h in range(n_heads):
        vf = v_ref[h].astype(jnp.float32)                    # (hd, 128)
        acc_scr[h] = (acc_scr[h] * a_scr[h:h + 1, :]
                      + vf * p_scr[h:h + 1, :])

    @pl.when(is_last)
    def _():
        for h in range(n_heads):
            o_ref[0, :, h:h + 1] = jnp.sum(acc_scr[h], axis=1,
                                           keepdims=True)
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


def _dot(a, b, contract: int, batch: bool = False):
    """``a`` (M, K) times ``b`` contracted over its dimension
    ``contract``, accumulated in float32 and as exact as float32 products
    of the values the operands hold: operands of one type are one product
    (float32 ones at the highest precision); a float32 ``a`` against a
    narrower ``b`` goes in two parts of ``b``'s type (16 bits of its
    mantissa), so that ``b`` is never converted. With ``batch`` both have
    a leading dimension more, the same, over which the products are
    independent: ``a`` (N, M, K)."""
    dims = (((1 + batch,), (contract,)), ((0,), (0,)) if batch else ((), ()))

    def dot(x):
        return jax.lax.dot_general(
            x, b, dims, preferred_element_type=jnp.float32,
            precision=(jax.lax.Precision.HIGHEST
                       if b.dtype == jnp.float32 else None))

    if a.dtype.itemsize <= b.dtype.itemsize:
        return dot(a.astype(b.dtype))
    hi = a.astype(b.dtype)
    return dot(hi) + dot((a - hi.astype(a.dtype)).astype(b.dtype))


def _decode_rows_kernel(layer_ref, slot_ref, rows_ref, blocks_ref, first_ref,
                        count_ref,                          # scalar prefetch
                        q_ref, *refs, sm_scale: float):
    """One run of a row-major pool: ``BLOCKS_PER_STEP`` blocks of one
    slot's K and V, ``(G, H, hd)`` each. Column ``c`` of the ``(H, P * G
    * H)`` logits is row ``c // H`` of the run against key head ``c %
    H``; a query head keeps its own, of the run's live rows."""
    del layer_ref, blocks_ref                    # the index maps read them
    P = BLOCKS_PER_STEP
    k_refs, v_refs = refs[:P], refs[P:2 * P]
    o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr = refs[2 * P:]
    i = pl.program_id(0)
    b = slot_ref[i]
    G, H, hd = k_refs[0].shape

    @pl.when(i == first_ref[b])
    def _():
        m_scr[...] = jnp.full(m_scr.shape, DEFAULT_MASK_VALUE, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    shape = (H, P * G * H)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if H & (H - 1):
        row = jax.lax.div(col, jnp.int32(H))
    else:
        row = col >> (H.bit_length() - 1)
    valid = ((col - row * H) == jax.lax.broadcasted_iota(jnp.int32, shape, 0)
             ) & (row < rows_ref[i])

    q = q_ref[0]
    s = jnp.concatenate([_dot(q, k[...].reshape(G * H, hd), 1)
                         for k in k_refs], axis=1) * sm_scale
    s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
    m_prev = m_scr[...]                                      # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new
    acc = acc_scr[...] * alpha
    for n, v in enumerate(v_refs):
        acc += _dot(p[:, n * G * H:(n + 1) * G * H],
                    v[...].reshape(G * H, hd), 0)
    acc_scr[...] = acc

    @pl.when(i == first_ref[b] + count_ref[b] - 1)
    def _():
        o_ref[0] = acc_scr[...]
        m_ref[0] = jnp.broadcast_to(m_scr[...], (H, GROUP_ROWS))
        l_ref[0] = jnp.broadcast_to(l_scr[...], (H, GROUP_ROWS))


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_size",
                                             "interpret", "layout"))
def _pool_attention(q, k_pool, v_pool, layer, plan, *, sm_scale: float,
                    block_size: int, interpret: bool, layout: str):
    """The kernel call: attention of ``q`` (B, H, hd) over the pool keys
    the plan lists, unnormalised. Returns float32 ``o`` (B, H, hd) =
    Σ exp(s − m)·v, ``m`` (B, H) the running maximum and ``l`` (B, H) =
    Σ exp(s − m); a slot with no run reads ``o = l = 0`` and the mask
    value for ``m``. Jitted so that a program which calls it once per
    layer (``layer`` is an operand) traces and lowers the kernel once:
    lowering a Pallas kernel is Python time that no compile cache saves."""
    B, H, hd = q.shape
    L, rows = k_pool.shape[:2]

    def slot_map(i, layer_r, slot_r, *_):
        return (slot_r[i], 0, 0)

    stat = pl.BlockSpec((1, H, GROUP_ROWS), slot_map)
    stats = [jax.ShapeDtypeStruct((B, H, GROUP_ROWS), jnp.float32)] * 2
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    if layout == "rows":
        P = BLOCKS_PER_STEP

        def block_map(n):
            def index(i, layer_r, slot_r, rows_r, blocks_r, *_):
                return (layer_r[0], blocks_r[i * P + n], 0, 0)
            return pl.BlockSpec((None, block_size, H, hd), index)

        blocks = [block_map(n) for n in range(P)]
        q_spec = pl.BlockSpec((1, H, hd), slot_map)
        col = pltpu.VMEM((H, 1), jnp.float32)
        o, m, l = pl.pallas_call(
            functools.partial(_decode_rows_kernel, sm_scale=sm_scale),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=6,
                grid=(plan["n_runs"][0],),
                in_specs=[q_spec] + blocks + blocks,
                out_specs=[q_spec, stat, stat],
                scratch_shapes=[pltpu.VMEM((H, hd), jnp.float32), col, col],
            ),
            out_shape=[jax.ShapeDtypeStruct((B, H, hd), jnp.float32)] + stats,
            compiler_params=params,
            interpret=interpret,
            name="paged_attn_decode_rows",
        )(jnp.asarray(layer, jnp.int32).reshape(1), plan["slot"],
          plan["rows"], plan["blocks"], plan["first"], plan["count"], q,
          *([k_pool] * P), *([v_pool] * P))
        return _unseen_masked(plan, o, m, l)
    # (L, rows, H, hd) -> (L, H, hd, rows): the layout the pool lies in
    kt = jnp.transpose(k_pool, (0, 2, 3, 1))
    vt = jnp.transpose(v_pool, (0, 2, 3, 1))
    qt = jnp.transpose(q.astype(jnp.float32), (0, 2, 1))         # (B, hd, H)

    def pool_map(i, layer_r, slot_r, group_r, *_):
        return (layer_r[0], 0, 0, group_r[i])

    wide = pltpu.VMEM((H, hd, GROUP_ROWS), jnp.float32)
    row = pltpu.VMEM((H, GROUP_ROWS), jnp.float32)
    o, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale,
                          block_size=block_size, n_heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(plan["n_runs"][0],),
            in_specs=[
                pl.BlockSpec((1, hd, H), slot_map),
                pl.BlockSpec((None, H, hd, GROUP_ROWS), pool_map),
                pl.BlockSpec((None, H, hd, GROUP_ROWS), pool_map),
            ],
            out_specs=[pl.BlockSpec((1, hd, H), slot_map), stat, stat],
            scratch_shapes=[wide, wide, row, row, row, row, row],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, hd, H), jnp.float32)] + stats,
        compiler_params=params,
        interpret=interpret,
        name="paged_attn_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), plan["slot"], plan["group"],
      plan["bits"], plan["first"], plan["count"], plan["tail"], qt, kt, vt)
    return _unseen_masked(plan, o, m, l, heads_last=True)


def _unseen_masked(plan, o, m, l, heads_last: bool = False):
    """A slot with no run was never written: take nothing from its rows.
    ``o`` is ``(B, H, ..., hd)``, or ``(B, hd, H)`` with ``heads_last``;
    ``m`` and ``l`` ``(B, H, ..., 128)``, the statistic on every lane."""
    seen = (plan["count"] > 0).reshape((-1,) + (1,) * (m.ndim - 2))
    if heads_last:
        o = jnp.transpose(o, (0, 2, 1))
    return (jnp.where(seen[..., None], o, 0.0),
            jnp.where(seen, m[..., 0], DEFAULT_MASK_VALUE),
            jnp.where(seen, l[..., 0], 0.0))


def paged_attention_decode(q, k_new, v_new, k_pool, v_pool, layer, plan,
                           lengths, *, block_size: int,
                           sm_scale: float | None = None,
                           interpret: bool = False, layout: str = "lanes"):
    """Attention of one query per slot over positions ``0..length-1``.

    ``q`` (B, H, hd) sits at position ``lengths - 1``; ``k_new`` /
    ``v_new`` (B, H, hd) are that position's K and V in the pool's dtype
    (as the pool will hold them); positions below it are read out of
    layer ``layer`` of ``k_pool`` / ``v_pool`` (L, rows, H, hd) through
    ``plan`` = :func:`plan_for` the ``layout`` of the block table and
    ``lengths - 1``. A slot of
    length 0 attends nothing and returns zeros. Returns (B, H, hd) in
    ``q.dtype``."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    o, m, l = _pool_attention(q, k_pool, v_pool, layer, plan,
                              sm_scale=sm_scale, block_size=block_size,
                              interpret=interpret, layout=layout)
    s_new = jnp.sum(q.astype(jnp.float32) * k_new.astype(jnp.float32),
                    axis=-1) * sm_scale                            # (B, H)
    top = jnp.maximum(m, s_new)
    w_pool = jnp.exp(m - top)[..., None]
    w_new = jnp.exp(s_new - top)[..., None]
    out = ((w_pool * o + w_new * v_new.astype(jnp.float32))
           / (w_pool * l[..., None] + w_new))
    return jnp.where((lengths > 0)[:, None, None], out, 0.0).astype(q.dtype)


#: Queries of one slot a grid step of the extend kernel takes at most.
EXTEND_QUERIES = 128


def _extend_kernel(layer_ref, slot_ref, group_ref, bits_ref, first_ref,
                   count_ref, tail_ref,                     # scalar prefetch
                   q_ref, k_ref, v_ref,                     # inputs
                   o_ref, m_ref, l_ref,                     # outputs
                   acc_scr, m_scr, l_scr,
                   *, sm_scale: float, block_size: int):
    """One run against a tile of one slot's queries: 128 rows of K and V,
    ``(H, hd, 128)`` each, and ``(H, T, hd)`` queries. Every query sees
    every live row (the pool holds only keys before the span). The heads
    are the batch dimension of both products, so the body holds two
    products whatever the heads: a program lowers the kernel anew at each
    width of the span, and that is set-up time no compile cache saves."""
    del layer_ref, group_ref                     # the index maps read them
    i = pl.program_id(1)
    b = slot_ref[i]
    is_last = i == first_ref[b] + count_ref[b] - 1
    H, T, _ = acc_scr.shape

    @pl.when(i == first_ref[b])
    def _():
        m_scr[...] = jnp.full(m_scr.shape, DEFAULT_MASK_VALUE, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    tail = jnp.where(is_last, tail_ref[b], GROUP_ROWS * 256)
    valid = _live_lanes((H, T, GROUP_ROWS), bits_ref[i], tail, block_size)
    s = _dot(q_ref[0], k_ref[...], 1, batch=True) * sm_scale     # (H, T, 128)
    s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
    m_prev = m_scr[...]                                          # (H, T, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=2, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * alpha + _dot(p, v_ref[...], 2, batch=True)

    @pl.when(is_last)
    def _():
        o_ref[0] = acc_scr[...]
        m_ref[0] = jnp.broadcast_to(m_scr[...], (H, T, GROUP_ROWS))
        l_ref[0] = jnp.broadcast_to(l_scr[...], (H, T, GROUP_ROWS))


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_size",
                                             "interpret"))
def _extend_pool_attention(q, k_pool, v_pool, layer, plan, *,
                           sm_scale: float, block_size: int, interpret: bool):
    """The extend kernel call, as :func:`_pool_attention`'s ``"lanes"``
    one: ``q`` (B, H, E, hd) over the pool keys the plan lists, each
    query against all of them, unnormalised: float32 ``o`` (B, H, E, hd),
    ``m`` and ``l`` (B, H, E). The grid is the tiles of ``T`` queries
    (the span padded to the sublanes of a tile) outside the runs."""
    B, H, E, hd = q.shape
    T = min(-(-E // 16) * 16, EXTEND_QUERIES)
    pad = -E % T
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kt = jnp.transpose(k_pool, (0, 2, 3, 1))
    vt = jnp.transpose(v_pool, (0, 2, 3, 1))

    def slot_map(t, i, layer_r, slot_r, *_):
        return (slot_r[i], 0, t, 0)

    def pool_map(t, i, layer_r, slot_r, group_r, *_):
        return (layer_r[0], 0, 0, group_r[i])

    stat = pl.BlockSpec((1, H, T, GROUP_ROWS), slot_map)
    col = pltpu.VMEM((H, T, 1), jnp.float32)
    o, m, l = pl.pallas_call(
        functools.partial(_extend_kernel, sm_scale=sm_scale,
                          block_size=block_size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=((E + pad) // T, plan["n_runs"][0]),
            in_specs=[
                pl.BlockSpec((1, H, T, hd), slot_map),
                pl.BlockSpec((None, H, hd, GROUP_ROWS), pool_map),
                pl.BlockSpec((None, H, hd, GROUP_ROWS), pool_map),
            ],
            out_specs=[pl.BlockSpec((1, H, T, hd), slot_map), stat, stat],
            scratch_shapes=[pltpu.VMEM((H, T, hd), jnp.float32), col, col],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, E + pad, hd), jnp.float32)]
        + [jax.ShapeDtypeStruct((B, H, E + pad, GROUP_ROWS), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attn_extend",
    )(jnp.asarray(layer, jnp.int32).reshape(1), plan["slot"], plan["group"],
      plan["bits"], plan["first"], plan["count"], plan["tail"], q, kt, vt)
    return _unseen_masked(plan, o[:, :, :E], m[:, :, :E], l[:, :, :E])


def paged_attention_extend(q, k_new, v_new, k_pool, v_pool, layer, plan,
                           positions, lengths, *, block_size: int,
                           sm_scale: float | None = None,
                           interpret: bool = False):
    """Attention of a span of E queries per slot over positions
    ``0..length-1``: the extend program's (a suffix after a prefix-cache
    hit, or a speculative verify).

    ``q`` (B, H, E, hd) sits at ``positions`` (B, E): a slot's real
    queries are consecutive from ``positions[:, 0]`` up to ``lengths -
    1``, its padded ones at or past ``lengths``. ``k_new`` / ``v_new``
    (B, H, E, hd) are the span's K and V in the pool's dtype (as the pool
    holds them). The positions before the span are read out of layer
    ``layer`` of the ``"lanes"`` pools ``k_pool`` / ``v_pool`` (L, rows,
    H, hd) through ``plan`` = :func:`decode_plan` of the block table and
    the span's first position (0 for an idle slot): every query sees all
    of them. The span's own keys are merged into each query's softmax
    from registers, causally by position, as :func:`paged_attention_decode`
    merges the decoded token's. Returns (B, H, E, hd) in ``q.dtype``; a
    padded query returns zeros."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    o, m, l = _extend_pool_attention(q, k_pool, v_pool, layer, plan,
                                     sm_scale=sm_scale, block_size=block_size,
                                     interpret=interpret)
    positions = positions.astype(jnp.int32)
    live = positions < lengths[:, None]                           # (B, E)
    sees = (live[:, None, :] & (positions[:, None, :] <= positions[:, :, None])
            )[:, None]                                            # (B, 1, E, E)
    highest = jax.lax.Precision.HIGHEST
    s_new = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k_new.astype(jnp.float32), precision=highest) * sm_scale
    s_new = jnp.where(sees, s_new, DEFAULT_MASK_VALUE)
    top = jnp.maximum(m, jnp.max(s_new, axis=-1))                 # (B, H, E)
    w_pool = jnp.exp(m - top)[..., None]
    p_new = jnp.where(sees, jnp.exp(s_new - top[..., None]), 0.0)
    num = w_pool * o + jnp.einsum("bhqk,bhkd->bhqd", p_new,
                                  v_new.astype(jnp.float32), precision=highest)
    den = w_pool * l[..., None] + jnp.sum(p_new, axis=-1, keepdims=True)
    # a real query sees its own key: only a padded one can see nothing
    out = num / jnp.where(den > 0, den, 1.0)
    return jnp.where(live[:, None, :, None], out, 0.0).astype(q.dtype)


def _decode_latent_kernel(layer_ref, slot_ref, rows_ref, blocks_ref,
                          first_ref, count_ref,             # scalar prefetch
                          q_ref, *refs, sm_scale: float, v_dim: int):
    """One run of a latent pool: ``LATENT_BLOCKS_PER_STEP`` blocks of one
    slot's rows, ``(G, W)`` each, against the ``(H, W)`` absorbed queries
    of all heads: the rows are read once. A row's first ``v_dim`` values
    are its value."""
    del layer_ref, blocks_ref                    # the index maps read them
    P = LATENT_BLOCKS_PER_STEP
    kv_refs = refs[:P]
    o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr = refs[P:]
    i = pl.program_id(0)
    b = slot_ref[i]
    H = q_ref.shape[1]

    @pl.when(i == first_ref[b])
    def _():
        m_scr[...] = jnp.full(m_scr.shape, DEFAULT_MASK_VALUE, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    kv = jnp.concatenate([r[...] for r in kv_refs], axis=0)  # (P * G, W)
    col = jax.lax.broadcasted_iota(jnp.int32, (H, kv.shape[0]), 1)
    valid = col < rows_ref[i]
    s = _dot(q_ref[0], kv, 1) * sm_scale                     # (H, P * G)
    s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
    m_prev = m_scr[...]                                      # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * alpha + _dot(p, kv[:, :v_dim], 0)

    @pl.when(i == first_ref[b] + count_ref[b] - 1)
    def _():
        o_ref[0] = acc_scr[...]
        m_ref[0] = jnp.broadcast_to(m_scr[...], (H, GROUP_ROWS))
        l_ref[0] = jnp.broadcast_to(l_scr[...], (H, GROUP_ROWS))


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_size",
                                             "v_dim", "interpret"))
def _latent_pool_attention(q, pool, layer, plan, *, sm_scale: float,
                           block_size: int, v_dim: int, interpret: bool):
    """The latent kernel call, as :func:`_pool_attention`: ``q`` (B, H, W)
    over the rows of ``pool`` (L, rows, W) the plan lists, unnormalised:
    float32 ``o`` (B, H, v_dim), ``m`` and ``l`` (B, H)."""
    B, H, W = q.shape
    P = LATENT_BLOCKS_PER_STEP

    def slot_map(i, layer_r, slot_r, *_):
        return (slot_r[i], 0, 0)

    def block_map(n):
        def index(i, layer_r, slot_r, rows_r, blocks_r, *_):
            return (layer_r[0], blocks_r[i * P + n], 0)
        return pl.BlockSpec((None, block_size, W), index)

    stat = pl.BlockSpec((1, H, GROUP_ROWS), slot_map)
    col = pltpu.VMEM((H, 1), jnp.float32)
    o, m, l = pl.pallas_call(
        functools.partial(_decode_latent_kernel, sm_scale=sm_scale,
                          v_dim=v_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(plan["n_runs"][0],),
            in_specs=[pl.BlockSpec((1, H, W), slot_map)]
            + [block_map(n) for n in range(P)],
            out_specs=[pl.BlockSpec((1, H, v_dim), slot_map), stat, stat],
            scratch_shapes=[pltpu.VMEM((H, v_dim), jnp.float32), col, col],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, v_dim), jnp.float32)]
        + [jax.ShapeDtypeStruct((B, H, GROUP_ROWS), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attn_decode_latent",
    )(jnp.asarray(layer, jnp.int32).reshape(1), plan["slot"], plan["rows"],
      plan["blocks"], plan["first"], plan["count"], q, *([pool] * P))
    return _unseen_masked(plan, o, m, l)


def latent_attention_decode(q, row_new, pool, layer, plan, lengths, *,
                            block_size: int, v_dim: int, sm_scale: float,
                            interpret: bool = False):
    """Latent attention of one query per slot over positions
    ``0..length-1``, in the absorbed form: ``q`` (B, H, W) are the heads'
    queries in the space of the cached rows (``W`` = latent_dim), a row
    scores ``q . row`` over all ``W`` values and contributes its first
    ``v_dim`` values. ``row_new`` (B, W) is the row of position ``lengths
    - 1`` as the pool will hold it (merged into the softmax from
    registers, as :func:`paged_attention_decode` does K and V); the
    positions below it are read out of layer ``layer`` of ``pool`` (L,
    rows, W) through ``plan`` = :func:`plan_for` ``"latent"`` of the block
    table and ``lengths - 1``. Returns (B, H, v_dim) in ``q.dtype``; a
    slot of length 0 returns zeros."""
    o, m, l = _latent_pool_attention(q, pool, layer, plan, sm_scale=sm_scale,
                                     block_size=block_size, v_dim=v_dim,
                                     interpret=interpret)
    new = row_new.astype(jnp.float32)
    s_new = jnp.einsum("bhw,bw->bh", q.astype(jnp.float32), new,
                       precision=jax.lax.Precision.HIGHEST) * sm_scale
    top = jnp.maximum(m, s_new)
    w_pool = jnp.exp(m - top)[..., None]
    w_new = jnp.exp(s_new - top)[..., None]
    out = ((w_pool * o + w_new * new[:, None, :v_dim])
           / (w_pool * l[..., None] + w_new))
    return jnp.where((lengths > 0)[:, None, None], out, 0.0).astype(q.dtype)


def write_latent_rows(pool, new, rows, active=None, layers=None):
    """The latent pool (L, R, W) with row ``rows[n]`` of the cache layers
    ``layers`` (Ln,; all of them when None) set to ``new[:, n]`` (``(Ln,
    N, W)``) for every entry with ``active[n]`` (all when None), by XLA's
    scatter, in place when the pool is donated (an inactive entry's index
    is out of range and dropped). The scatter names (layer, row) pairs,
    so that what it writes is whole rows of ``W`` values wherever they
    lie: a scatter over the rows alone, with the layers as part of the
    written window, has the device turn the whole pool round to bring a
    row's layers together, and back (compiled for a described v5e)."""
    at = rows.astype(jnp.int32)
    if active is not None:
        at = jnp.where(active, at, pool.shape[1])
    if layers is None:
        layers = jnp.arange(pool.shape[0], dtype=jnp.int32)
    return pool.at[layers[:, None], at[None, :]].set(
        new.astype(pool.dtype), mode="drop")


def write_rows(k_pool, v_pool, k_new, v_new, rows, active, *,
               interpret: bool = False, layout: str = "lanes"):
    """Both pools (L, R, H, hd) with row ``rows[b]`` of every layer set
    to ``k_new[:, b]`` / ``v_new[:, b]`` (``(L, B, H, hd)``) for every
    slot with ``active[b]``, in place when the pools are donated; every
    other row stays bit for bit, and an inactive slot writes nothing.

    A row-major pool (``layout="rows"``) takes XLA's scatter, which
    writes that layout where it lies (an inactive slot's index is out of
    range and dropped; active slots name distinct rows). In the
    ``"lanes"`` layout a row is one lane of 64 tiles, and a DMA moves
    whole tiles: :func:`write_blocks` with every slot's row a piece of
    its own. There, slots that name one row are applied in slot order."""
    if layout == "rows":
        at = jnp.where(active, rows.astype(jnp.int32), k_pool.shape[1])
        return tuple(pool.at[:, at].set(new.astype(pool.dtype), mode="drop")
                     for pool, new in ((k_pool, k_new), (v_pool, v_new)))
    return write_blocks(k_pool, v_pool, k_new, v_new,
                        write_plan(rows, active), interpret=interpret)


def write_plan(rows, active) -> dict:
    """The piece list of :func:`write_blocks`, shared by every layer.

    ``rows`` (N,) int32 pool rows and ``active`` (N,) which of them are
    written (entries that name one row are applied in their order: the
    sort is stable). A *piece* is a maximal stretch
    of consecutive active entries whose rows are consecutive too, that
    stays inside one 128-entry tile of the new rows and one 128-row group
    of the pool: the kernel moves it with one lane rotation. A prompt's
    block is one piece, and blocks the allocator handed out in a row are
    one. Returns int32 arrays, per piece (``N`` long, sorted by group, so
    that the pieces of one group are neighbours): ``src`` its first
    entry, ``dst`` its first row, ``n`` its length; and ``n_pieces``
    ``(1,)``, the grid's size."""
    N = rows.shape[0]
    rows = rows.astype(jnp.int32)
    i = jnp.arange(N, dtype=jnp.int32)
    follows = jnp.concatenate([jnp.zeros((1,), bool),
                               active[:-1] & (rows[1:] == rows[:-1] + 1)])
    opens = active & ~(follows & (i % GROUP_ROWS != 0)
                       & (rows % GROUP_ROWS != 0))
    piece = jnp.cumsum(opens, dtype=jnp.int32) - 1                 # (N,)
    n_pieces = jnp.sum(opens, dtype=jnp.int32)
    zeros = jnp.zeros((N,), jnp.int32)
    at = jnp.where(opens, piece, N)                                # N: dropped
    src = zeros.at[at].set(i, mode="drop")
    dst = zeros.at[at].set(rows, mode="drop")
    n = zeros.at[jnp.where(active, piece, N)].add(1, mode="drop")
    # unused entries sort behind every group
    order = jnp.argsort(jnp.where(i < n_pieces, dst // GROUP_ROWS,
                                  jnp.iinfo(jnp.int32).max), stable=True)
    return {"src": src[order], "dst": dst[order], "n": n[order],
            "n_pieces": n_pieces[None]}


def _write_blocks_kernel(layer_ref, src_ref, dst_ref, n_ref,  # prefetch
                         kn_ref, vn_ref, k_ref, v_ref,        # inputs
                         ko_ref, vo_ref):                     # outputs
    """One piece: the 128-entry tile of new rows that holds it, rotated
    so that its lanes lie over its rows' lanes in their 128-row group.
    The group stays in VMEM while the pieces that follow name it."""
    del layer_ref                                # the index maps read it
    s = pl.program_id(1)
    dst = dst_ref[s]
    before = dst_ref[jnp.maximum(s - 1, 0)]

    @pl.when((s == 0) | (dst // GROUP_ROWS != before // GROUP_ROWS))
    def _():
        ko_ref[...] = k_ref[...]
        vo_ref[...] = v_ref[...]

    lo = dst % GROUP_ROWS
    shift = (lo - src_ref[s] % GROUP_ROWS + GROUP_ROWS) % GROUP_ROWS
    # Mosaic rotates 32-bit lanes only: narrower rows go as the words
    # their sublanes pack into (a lane's rotation moves whole sublanes)
    dtype = ko_ref.dtype
    words = jnp.uint32 if dtype.itemsize < 4 else dtype
    H, hd, _ = ko_ref.shape
    lane = jax.lax.broadcasted_iota(
        jnp.int32, (H, hd * dtype.itemsize // 4, GROUP_ROWS), 2)
    here = (lane >= lo) & (lane < lo + n_ref[s])
    for new_ref, out_ref in ((kn_ref, ko_ref), (vn_ref, vo_ref)):
        moved = pltpu.roll(pltpu.bitcast(new_ref[...], words), shift, 2)
        out_ref[...] = pltpu.bitcast(
            jnp.where(here, moved, pltpu.bitcast(out_ref[...], words)),
            dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _write_pieces(kt, vt, kn, vn, layer, plan, *, interpret: bool):
    """The kernel call of :func:`write_blocks` on the pools' views ``(L,
    H, hd, R)`` and the new rows' ``(Ln, H, hd, N)``, ``N`` a multiple of
    128. Jitted so that a program which calls it once per layer lowers
    the kernel once, as :func:`_pool_attention`."""
    _, H, hd, _ = kt.shape

    def new_map(l, s, layer_r, src_r, *_):
        return (l, 0, 0, src_r[s] // GROUP_ROWS)

    def pool_map(l, s, layer_r, src_r, dst_r, *_):
        return (layer_r[0] + l, 0, 0, dst_r[s] // GROUP_ROWS)

    new_spec = pl.BlockSpec((None, H, hd, GROUP_ROWS), new_map)
    pool_spec = pl.BlockSpec((None, H, hd, GROUP_ROWS), pool_map)
    return pl.pallas_call(
        _write_blocks_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(kn.shape[0], plan["n_pieces"][0]),
            in_specs=[new_spec, new_spec, pool_spec, pool_spec],
            out_specs=[pool_spec, pool_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_kv_write",
    )(jnp.asarray(layer, jnp.int32).reshape(1), plan["src"], plan["dst"],
      plan["n"], kn, vn, kt, vt)


def write_blocks(k_pool, v_pool, k_new, v_new, plan, layer=0, *,
                 interpret: bool = False):
    """Both ``"lanes"`` pools (L, R, H, hd) with the rows ``plan`` =
    :func:`write_plan` ``(rows, active)`` names set, in the cache layers
    ``layer .. layer + Ln - 1``, to ``k_new[:, i]`` / ``v_new[:, i]``
    (``(Ln, N, H, hd)``, cast to the pool's type and nothing else), in
    place when the pools are donated; every other row stays bit for bit.

    A row is one lane of 64 tiles and a DMA moves whole tiles, so the
    kernel reads a 128-row group, sets the lanes and writes the group
    back (``input_output_aliases``: no other group is touched). The
    admission programs write whole blocks, 16 neighbouring lanes of a
    group each; the decode step one row a slot. One grid step per layer
    and piece, the pieces sorted by group: the first of a group reads
    it, the last leaves it to be written back, so no two steps in flight
    hold the same rows and the pipeline may fetch ahead and write
    behind."""
    N = k_new.shape[1]
    pad = -N % GROUP_ROWS
    view = (0, 2, 3, 1)     # rows on the lanes; of the pools a bitcast
    kt, vt = (jnp.transpose(a, view) for a in (k_pool, v_pool))
    kn, vn = (jnp.pad(jnp.transpose(a.astype(k_pool.dtype), view),
                      ((0, 0), (0, 0), (0, 0), (0, pad)))
              for a in (k_new, v_new))
    kt, vt = _write_pieces(kt, vt, kn, vn, layer, plan, interpret=interpret)
    back = (0, 3, 1, 2)
    return jnp.transpose(kt, back), jnp.transpose(vt, back)
