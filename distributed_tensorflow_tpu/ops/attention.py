"""Fused multi-head attention for TPU (Pallas flash attention).

The reference framework has no fused attention of its own — its BERT /
Transformer workloads run unfused softmax(QK^T)V through stock TF ops
(SURVEY.md §5.7: no flash/blockwise attention anywhere in the reference
tree). On TPU the memory-bound softmax materialisation is the first thing
to kill HBM bandwidth at long sequence length, so the TPU-native framework
makes flash attention a core op: online-softmax tiling in VMEM, MXU-sized
blocks, O(S) memory, with a custom VJP whose backward recomputes
probabilities blockwise from the saved row logsumexp.

Layout convention: ``(batch, num_heads, seq, head_dim)`` throughout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Reference implementation (also the CPU fallback)
# ---------------------------------------------------------------------------

def length_valid_mask(lengths, q_len: int, kv_len: int, *,
                      causal: bool = False, causal_offset: int | None = None,
                      q_positions=None):
    """Validity mask for right-padded mixed-length batches — the ONE
    masking rule shared by full-sequence recompute (``mha_reference``)
    and the serving engine's incremental KV-cache decode
    (serving/decode.py). Keeping both sides on this function is the
    correctness contract that makes cached decode match full recompute.

    ``lengths``: (B,) true sequence lengths. Query ``i`` of sequence
    ``b`` may see key ``j`` iff both lie inside the sequence
    (``i < lengths[b]`` — via ``q_positions`` when the queries are a
    window into a longer cache — and ``j < lengths[b]``) and, under
    ``causal``, ``j <= i + causal_offset`` (offset defaults to
    ``kv_len - q_len``: bottom-right alignment, the incremental-decode
    case where the single query row sits at the END of the cache).

    Returns (B, 1, q_len, kv_len) bool.
    """
    if causal_offset is None:
        # explicit q_positions are ABSOLUTE cache positions: query p sees
        # key j iff j <= p, no alignment offset
        causal_offset = 0 if q_positions is not None else kv_len - q_len
    lengths = jnp.asarray(lengths, jnp.int32)
    if q_positions is None:
        q_ids = jnp.arange(q_len, dtype=jnp.int32)[None, :]     # (1, q)
    else:
        q_ids = jnp.asarray(q_positions, jnp.int32)
        if q_ids.ndim == 1:
            q_ids = q_ids[:, None]                              # (B, q=1)
    k_ids = jnp.arange(kv_len, dtype=jnp.int32)
    valid = ((q_ids[:, :, None] < lengths[:, None, None])
             & (k_ids[None, None, :] < lengths[:, None, None]))
    if causal:
        valid = valid & (k_ids[None, None, :]
                         <= q_ids[:, :, None] + causal_offset)
    return valid[:, None]                                       # (B,1,q,k)


def mha_reference(q, k, v, *, causal: bool = False, sm_scale: float | None = None,
                  segment_ids=None, lengths=None, q_positions=None):
    """Unfused attention — the semantics contract for the Pallas kernels.

    ``lengths`` (B,) masks a right-padded mixed-length batch via
    :func:`length_valid_mask`: padded keys are invisible to every query
    and fully-padded query rows output 0. ``q_positions`` places the
    queries at explicit cache positions (incremental decode: one query
    at position ``lengths-1`` against a longer key buffer)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    valid = None
    if causal and lengths is None:
        qs, ks = q.shape[2], k.shape[2]
        valid = jnp.tril(jnp.ones((qs, ks), dtype=bool), k=ks - qs)[None, None]
    if lengths is not None:
        valid = length_valid_mask(lengths, q.shape[2], k.shape[2],
                                  causal=causal, q_positions=q_positions)
    if segment_ids is not None:
        seg_mask = (segment_ids[:, None, :, None]
                    == segment_ids[:, None, None, :])
        valid = seg_mask if valid is None else valid & seg_mask
    if valid is not None:
        logits = jnp.where(valid, logits, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    if valid is not None:
        # Fully-masked query rows (causal with q_len > k_len, padded
        # rows) output 0, not the uniform average
        # softmax-of-equal-mask-values would give.
        probs = probs * jnp.any(valid, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32)
                      ).astype(q.dtype)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _block_mask(qb, kb, block_q, block_k, *, causal, causal_offset,
                q_limit=None, k_limit=None):
    """Validity mask for one (q-block, k-block) tile, or None if nothing
    needs masking. Shared by forward and both backward kernels so causal
    alignment and tail padding stay in lockstep across fwd/bwd.

    causal: bottom-right aligned — query i sees key j iff
    j <= i + causal_offset (offset = kv_len - q_len).
    q_limit/k_limit: true (unpadded) lengths; rows/cols past them are
    zero-padding and must not contribute.
    """
    if not causal and q_limit is None and k_limit is None:
        return None
    q_ids = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_ids = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = (q_ids + causal_offset >= k_ids) if causal else (q_ids >= 0)
    if q_limit is not None:
        valid = valid & (q_ids < q_limit)
    if k_limit is not None:
        valid = valid & (k_ids < k_limit)
    return valid


def _fwd_kernel(q_ref, k_ref, v_ref,          # inputs (blocked)
                o_ref, lse_ref,               # outputs
                m_scr, l_scr, acc_scr,        # VMEM scratch
                *, sm_scale: float, causal: bool,
                block_q: int, block_k: int, num_k_blocks: int,
                kv_len: int, causal_offset: int = 0):
    """One (batch·head, q-block, k-block) grid step of flash attention.

    TPU grids run sequentially over the last dimension, so the online
    softmax state (m, l, acc) lives in VMEM scratch carried across the
    k-block steps of one q-block.
    """
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, DEFAULT_MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qb = pl.program_id(1)
    # Under causal masking a k-block strictly above the (bottom-right
    # aligned) diagonal contributes nothing — predicate the step out.
    should_run = ((kb * block_k <= (qb + 1) * block_q - 1 + causal_offset)
                  if causal else kb >= 0)

    @pl.when(should_run)
    def _step():
        q = q_ref[0]                       # (block_q, d)
        k = k_ref[0]                       # (block_k, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

        valid = _block_mask(
            qb, kb, block_q, block_k, causal=causal,
            causal_offset=causal_offset,
            k_limit=kv_len if kv_len % block_k != 0 else None)
        if valid is not None:
            s = jnp.where(valid, s, DEFAULT_MASK_VALUE)

        m_prev = m_scr[:]                  # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)             # (block_q, block_k)
        alpha = jnp.exp(m_prev - m_new)    # rescale of previous state
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(kb == num_k_blocks - 1)
    def _finish():
        l = l_scr[:]
        empty = l == 0.0                   # fully-masked rows -> output 0
        l = jnp.where(empty, 1.0, l)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # Empty rows store lse = +inf so the backward kernels recompute
        # p = exp(masked_logit - inf) = 0 instead of exp(MASK - MASK) = 1.
        lse_ref[0] = jnp.where(empty, jnp.inf,
                               m_scr[:] + jnp.log(l))   # (block_q, 1)


def _pad_seq(x, multiple):
    """Zero-pad axis 1 (sequence) up to a multiple of ``multiple``."""
    s = x.shape[1]
    pad = (-s) % multiple
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0)))


def _flash_forward(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                   causal_offset=None):
    batch, heads, q_len, d = q.shape
    k_len = k.shape[2]
    if causal_offset is None:
        causal_offset = k_len - q_len
    block_q = min(block_q, q_len)
    block_k = min(block_k, k_len)
    bh = batch * heads

    qr = _pad_seq(q.reshape(bh, q_len, d), block_q)
    kr = _pad_seq(k.reshape(bh, k_len, d), block_k)
    vr = _pad_seq(v.reshape(bh, k_len, d), block_k)
    qp, kp = qr.shape[1], kr.shape[1]
    nq, nk = qp // block_q, kp // block_k

    grid = (bh, nq, nk)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk,
                          kv_len=k_len, causal_offset=causal_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, qp, d), q.dtype),
            jax.ShapeDtypeStruct((bh, qp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qr, kr, vr)
    return (out[:, :q_len].reshape(batch, heads, q_len, d),
            lse[:, :q_len].reshape(batch, heads, q_len))


# ---------------------------------------------------------------------------
# Backward kernels (recompute P from saved logsumexp, blockwise)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr,
                   *, sm_scale, causal, block_q, block_k, num_k_blocks,
                   kv_len: int, causal_offset: int = 0):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    qb = pl.program_id(1)
    should_run = ((kb * block_k <= (qb + 1) * block_q - 1 + causal_offset)
                  if causal else kb >= 0)

    @pl.when(should_run)
    def _step():
        q = q_ref[0]
        kk = k_ref[0]
        vv = v_ref[0]
        # Keep matmul OPERANDS in the input dtype (bf16 in production):
        # fp32 operands run the MXU at half rate, and with head_dim 64
        # already capping utilization at 50% the all-fp32 backward was
        # the single largest off-ideal factor in the step profile.
        # Accumulation stays fp32 via preferred_element_type; only the
        # elementwise softmax-gradient algebra runs in fp32.
        do = do_ref[0]
        lse = lse_ref[0]                   # (block_q, 1)
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, kk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        valid = _block_mask(
            qb, kb, block_q, block_k, causal=causal,
            causal_offset=causal_offset,
            k_limit=kv_len if kv_len % block_k != 0 else None)
        if valid is not None:
            s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse)               # (block_q, block_k)
        dp = jax.lax.dot_general(do, vv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(kk.dtype)
        dq_scr[:] += jax.lax.dot_general(
            ds, kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == num_k_blocks - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, sm_scale, causal, block_q, block_k, num_q_blocks,
                    q_len: int, causal_offset: int = 0):
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    kb = pl.program_id(1)
    # Causal: gradient only flows to k-block kb from q rows at or below
    # the diagonal, i.e. iff max(q_id) >= min(k_id).
    should_run = (((qb + 1) * block_q - 1 + causal_offset >= kb * block_k)
                  if causal else qb >= 0)

    @pl.when(should_run)
    def _step():
        q = q_ref[0]
        kk = k_ref[0]
        vv = v_ref[0]
        # bf16 matmul operands, fp32 accumulation — see _bwd_dq_kernel.
        do = do_ref[0]
        lse = lse_ref[0]                   # (block_q, 1)
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, kk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        valid = _block_mask(
            qb, kb, block_q, block_k, causal=causal,
            causal_offset=causal_offset,
            q_limit=q_len if q_len % block_q != 0 else None)
        if valid is not None:
            s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qb == num_q_blocks - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(res, g, *, sm_scale, causal, block_q, block_k,
                    interpret, causal_offset=None):
    q, k, v, out, lse = res
    batch, heads, q_len, d = q.shape
    k_len = k.shape[2]
    if causal_offset is None:
        causal_offset = k_len - q_len
    block_q = min(block_q, q_len)
    block_k = min(block_k, k_len)
    bh = batch * heads

    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1)                      # (b, h, q_len)

    # Zero-pad to block multiples; padded lse/delta rows are 0 so masked
    # logits give p = exp(MASK - 0) = 0 in the kernels.
    qr = _pad_seq(q.reshape(bh, q_len, d), block_q)
    kr = _pad_seq(k.reshape(bh, k_len, d), block_k)
    vr = _pad_seq(v.reshape(bh, k_len, d), block_k)
    dor = _pad_seq(g.reshape(bh, q_len, d), block_q)
    lser = _pad_seq(lse.reshape(bh, q_len, 1), block_q)
    deltar = _pad_seq(delta.reshape(bh, q_len, 1), block_q)
    qp, kp = qr.shape[1], kr.shape[1]
    nq, nk = qp // block_q, kp // block_k

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk,
                          kv_len=k_len, causal_offset=causal_offset),
        grid=(bh, nq, nk),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            q_spec, row_spec, row_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, qp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qr, kr, vr, dor, lser, deltar)

    # dk/dv: grid over k-blocks, inner loop over q-blocks.
    k_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    qj_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0))
    rowj_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q_blocks=nq,
                          q_len=q_len, causal_offset=causal_offset),
        grid=(bh, nk, nq),
        in_specs=[qj_spec, k_spec, k_spec, qj_spec, rowj_spec, rowj_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, kp, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, kp, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qr, kr, vr, dor, lser, deltar)

    shape = (batch, heads, q_len, d)
    kshape = (batch, heads, k_len, d)
    return (dq[:, :q_len].reshape(shape), dk[:, :k_len].reshape(kshape),
            dv[:, :k_len].reshape(kshape))


# ---------------------------------------------------------------------------
# Public op with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_mha(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, _ = _flash_forward(q, k, v, sm_scale, causal, block_q, block_k,
                            interpret)
    return out


def _flash_mha_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, sm_scale, causal, block_q, block_k,
                              interpret)
    return out, (q, k, v, out, lse)


def _flash_mha_bwd(sm_scale, causal, block_q, block_k, interpret, res, g):
    return _flash_backward(res, g, sm_scale=sm_scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: float | None = None,
                    block_q: int = 512, block_k: int = 1024,
                    implementation: str | None = None):
    """Fused attention. ``(b, h, s, d)`` in, ``(b, h, s, d)`` out.

    implementation: "pallas" | "reference" | "interpret" | None (auto:
    pallas on TPU, reference elsewhere).
    """
    if implementation is None:
        implementation = ("pallas" if jax.default_backend() == "tpu"
                          else "reference")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if implementation == "reference":
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    interpret = implementation == "interpret"
    return _flash_mha(q, k, v, sm_scale, causal, block_q, block_k, interpret)


def sharded_flash_attention(q, k, v, mesh, *, causal: bool = False,
                            sm_scale: float | None = None,
                            block_q: int = 512, block_k: int = 1024,
                            implementation: str | None = None):
    """``flash_attention`` shard_mapped over the mesh's batch/head axes.

    The Pallas kernel lowers to a Mosaic custom call, which the GSPMD
    partitioner cannot partition: invoked directly inside a partitioned
    jit it forces an all-gather of q/k/v and runs fully replicated on
    every device. Attention is embarrassingly parallel over (batch,
    heads), so run the kernel per-shard under ``shard_map`` over the
    (dcn, dp, fsdp) batch axes and the tp head axis — no collectives
    inside the region.

    Falls back to the plain call when the shard counts don't divide the
    operand dims (then GSPMD's replicated execution is still correct).
    """
    import math

    from jax import shard_map

    from distributed_tensorflow_tpu.cluster.topology import \
        attention_shard_spec

    spec = attention_shard_spec(mesh)
    batch_axes, head_axis = spec[0], spec[1]
    if isinstance(batch_axes, str):   # PartitionSpec flattens 1-tuples
        batch_axes = (batch_axes,)
    n_batch = (math.prod(mesh.shape[a] for a in batch_axes)
               if batch_axes else 1)
    n_head = mesh.shape[head_axis] if head_axis else 1
    if n_batch * n_head == 1 or q.shape[0] % n_batch or q.shape[1] % n_head:
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k,
                               implementation=implementation)
    fn = functools.partial(flash_attention, causal=causal, sm_scale=sm_scale,
                           block_q=block_q, block_k=block_k,
                           implementation=implementation)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
