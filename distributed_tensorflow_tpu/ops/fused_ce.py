"""Fused vocab-tiled cross-entropy for TPU (Pallas online-logsumexp).

The last-mile inefficiency of the transformer train step: next-token CE
against a tied (vocab, d_model) embedding. The classic path
materializes (tokens, vocab) fp32 logits — for transformer_big at
batch 4 / seq 1024 / vocab 32k that tensor alone is 512 MiB and every
softmax stage round-trips it through HBM, which is why the lax.scan
chunked form (models/transformer.py fused_next_token_loss) runs at
~45-60 % efficiency. These kernels stream the vocab axis through VMEM
flash-attention-style: a logits TILE exists only on-chip, reduced into
a running (max, sumexp) pair, and the backward recomputes each tile's
probabilities from the saved row logsumexp.

≙ the reference's fused softmax-CE lowering
(TF/python/ops/nn_ops.py softmax_cross_entropy_with_logits → fused XLA
reduction) extended to also fuse away the vocab projection itself.

Decomposition (N = flattened tokens, V = vocab, D = d_model):
- forward:  one kernel, grid (N/bn, V/bv): online
            lse_i = logsumexp_v(h_i·E_v) and the target logit
            tl_i = h_i·E_{t_i} picked up by one-hot masking as its tile
            streams by. loss_i = lse_i - tl_i.
- backward: p_adj_iv = (exp(h_i·E_v - lse_i) - 1[v = t_i]) · g_i
            (the softmax-CE gradient, one-hot folded INTO the tile so
            no XLA gather/scatter is needed):
            dh = p_adj @ E      [kernel, grid (N/bn, V/bv)]
            dE = p_adjᵀ @ h     [kernel, grid (V/bv, N/bn)]
  FLOP cost is 5·N·V·D MACs total (vs the scan path's 4) but every
  matmul is MXU-shaped and no (N, V) tensor ever touches HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -0.7 * float(jnp.finfo(jnp.float32).max)

# XLA's default scoped-VMEM allowance for custom calls is 16 MiB — a
# conservative slice of the chip's physical VMEM (v5e: 128 MiB). The
# merged backward legitimately wants ~24 MiB (fp32 accumulator scratch
# + double-buffered fp32 alias blocks), so raise the cap for these
# kernels only.
_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=64 * 1024 * 1024)


# ---------------------------------------------------------------------------
# Reference implementation (semantics contract + CPU fallback)
# ---------------------------------------------------------------------------

def ce_reference(hidden, embed, targets):
    """Per-token CE losses, unfused: logsumexp(h@Eᵀ) - (h·E_t)."""
    logits = jnp.einsum("nd,vd->nv", hidden, embed,
                        preferred_element_type=jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return lse - tl


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def _col_ids(vb, block_n, block_v):
    return vb * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)


def _fwd_kernel(h_ref, e_ref, t_ref, lse_ref, tl_ref, m_scr, s_scr, tl_scr,
                *, block_n, block_v, num_v_blocks, vocab_size):
    """Online logsumexp + target-logit pickup over vocab tiles; grid
    (N/bn, V/bv), vocab innermost so state carries in VMEM scratch."""
    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_BIG)
        s_scr[:] = jnp.zeros_like(s_scr)
        tl_scr[:] = jnp.zeros_like(tl_scr)

    logits = jax.lax.dot_general(
        h_ref[:], e_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (bn, bv)
    cols = _col_ids(vb, block_n, block_v)
    if vocab_size % block_v != 0:
        logits = jnp.where(cols < vocab_size, logits, _NEG_BIG)

    # Target logit: exactly one tile holds column t_i for row i.
    onehot = cols == t_ref[:]                        # (bn, bv), t: (bn,1)
    tl_scr[:] += jnp.sum(jnp.where(onehot, logits, 0.0), axis=1,
                         keepdims=True)

    m_prev = m_scr[:]                                # (bn, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    s_scr[:] = (s_scr[:] * jnp.exp(m_prev - m_new)
                + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True))
    m_scr[:] = m_new

    @pl.when(vb == num_v_blocks - 1)
    def _finish():
        lse_ref[:] = m_scr[:] + jnp.log(s_scr[:])
        tl_ref[:] = tl_scr[:]


def _masked_e(e_ref, vb, block_v, vocab_size):
    """E tile with rows past the vocab end zeroed: those rows are
    UNDEFINED on a padded tail read (NaN in interpret mode) and
    0 * NaN = NaN would poison any contraction over the vocab axis."""
    e = e_ref[:]
    if vocab_size % block_v != 0:
        row = vb * block_v + jax.lax.broadcasted_iota(
            jnp.int32, e.shape, 0)
        e = jnp.where(row < vocab_size, e, 0)
    return e


def _p_adj(h, e, t_ref, lse_ref, g_ref, vb, block_n, block_v, vocab_size):
    """(softmax - onehot(t)) · g for one tile — the CE gradient wrt
    logits, computed in-register from the saved row logsumexp."""
    logits = jax.lax.dot_general(
        h, e, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse_ref[:])
    cols = _col_ids(vb, block_n, block_v)
    p = p - (cols == t_ref[:]).astype(jnp.float32)
    if vocab_size % block_v != 0:
        p = jnp.where(cols < vocab_size, p, 0.0)
    return p * g_ref[:]


def _dh_kernel(h_ref, e_ref, t_ref, lse_ref, g_ref, dh_ref, acc_scr,
               *, block_n, block_v, num_v_blocks, vocab_size):
    """dh_i = Σ_v p_adj_iv E_v over vocab tiles; grid (N/bn, V/bv),
    vocab innermost."""
    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    e = _masked_e(e_ref, vb, block_v, vocab_size)
    p = _p_adj(h_ref[:], e, t_ref, lse_ref, g_ref, vb, block_n, block_v,
               vocab_size)
    acc_scr[:] += jax.lax.dot_general(
        p.astype(e.dtype), e, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(vb == num_v_blocks - 1)
    def _finish():
        dh_ref[:] = acc_scr[:].astype(dh_ref.dtype)


def _bwd_merged_kernel(h_ref, e_ref, t_ref, lse_ref, g_ref, dh_in_ref,
                       dh_out_ref, de_ref, de_scr,
                       *, block_n, block_v, num_v_blocks, vocab_size):
    """Merged backward: ONE logits recompute per tile feeds both
    dh += p_adj @ E and dE += p_adjᵀ @ h — 3 N·V·D matmuls total
    (the scan path's backward cost) instead of the split kernels' 4.

    Grid (V/bv, N/bn), tokens innermost: dE accumulates in VMEM
    scratch across the inner sweep and writes once per vocab tile;
    dh accumulates ACROSS vocab tiles through an fp32 HBM buffer
    aliased input→output (read-modify-write per visit)."""
    nb = pl.program_id(1)
    vb = pl.program_id(0)

    @pl.when(nb == 0)
    def _init():
        de_scr[:] = jnp.zeros_like(de_scr)

    e = _masked_e(e_ref, vb, block_v, vocab_size)
    p = _p_adj(h_ref[:], e, t_ref, lse_ref, g_ref, vb, block_n, block_v,
               vocab_size)
    pc = p.astype(e.dtype)
    de_scr[:] += jax.lax.dot_general(
        pc, h_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    contrib = jax.lax.dot_general(
        pc, e, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(vb == 0)
    def _first_visit():
        dh_out_ref[:] = contrib

    @pl.when(vb > 0)
    def _accumulate():
        dh_out_ref[:] = dh_in_ref[:] + contrib

    @pl.when(nb == pl.num_programs(1) - 1)
    def _finish():
        de_ref[:] = de_scr[:].astype(de_ref.dtype)


def _bwd_merged_b_kernel(h_ref, e_ref, t_ref, lse_ref, g_ref, de_in_ref,
                         dh_ref, de_out_ref, dh_scr,
                         *, block_n, block_v, num_v_blocks, vocab_size):
    """Merged backward, grid (N/bn, V/bv) with vocab innermost: dh
    accumulates in VMEM scratch (written once per token tile) and dE
    accumulates ACROSS token sweeps through the aliased HBM buffer.
    Per-sweep alias traffic is V·D (read+write) × N/bn sweeps — with
    bn ≥ 1024 that is less than variant A's N·D × V/bv, and the
    scratch-resident dh needs no roundtrips at all."""
    nb = pl.program_id(0)
    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)

    e = _masked_e(e_ref, vb, block_v, vocab_size)
    p = _p_adj(h_ref[:], e, t_ref, lse_ref, g_ref, vb, block_n, block_v,
               vocab_size)
    pc = p.astype(e.dtype)
    dh_scr[:] += jax.lax.dot_general(
        pc, e, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    contrib = jax.lax.dot_general(
        pc, h_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(nb == 0)
    def _first_sweep():
        de_out_ref[:] = contrib.astype(de_out_ref.dtype)

    @pl.when(nb > 0)
    def _accumulate():
        de_out_ref[:] = (de_in_ref[:].astype(jnp.float32)
                         + contrib).astype(de_out_ref.dtype)

    @pl.when(vb == num_v_blocks - 1)
    def _finish():
        dh_ref[:] = dh_scr[:].astype(dh_ref.dtype)


def _de_kernel(h_ref, e_ref, t_ref, lse_ref, g_ref, de_ref, acc_scr,
               *, block_n, block_v, num_v_blocks, vocab_size):
    """dE_v = Σ_i p_adj_iv h_i over token tiles; grid (V/bv, N/bn),
    tokens innermost."""
    nb = pl.program_id(1)
    vb = pl.program_id(0)

    @pl.when(nb == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    e = _masked_e(e_ref, vb, block_v, vocab_size)
    p = _p_adj(h_ref[:], e, t_ref, lse_ref, g_ref, vb, block_n, block_v,
               vocab_size)
    # (bv, bn) @ (bn, D)
    acc_scr[:] += jax.lax.dot_general(
        p.astype(h_ref.dtype), h_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(nb == pl.num_programs(1) - 1)
    def _finish():
        de_ref[:] = acc_scr[:].astype(de_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _pad_rows(x, multiple):
    n = x.shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return x
    width = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, width)


def _pad_rows_fill(x, multiple, fill):
    n = x.shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return x
    width = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, width, constant_values=fill)


def _fwd_call(h, emb, targets, block_n, block_v, interpret):
    n, d = h.shape
    v = emb.shape[0]
    nb, vb = pl.cdiv(n, block_n), pl.cdiv(v, block_v)
    lse, tl = pl.pallas_call(
        functools.partial(_fwd_kernel, block_n=block_n, block_v=block_v,
                          num_v_blocks=vb, vocab_size=v),
        grid=(nb, vb),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb * block_n, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb * block_n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="fused_ce_fwd",
    )(_pad_rows(h, block_n), emb,
      # pad target rows with -1: matches no vocab column
      _pad_rows_fill(targets[:, None].astype(jnp.int32), block_n, -1))
    return lse[:n, 0], tl[:n, 0]


def _dh_call(h, emb, targets, lse, g, block_n, block_v, interpret):
    n, d = h.shape
    v = emb.shape[0]
    nb, vb = pl.cdiv(n, block_n), pl.cdiv(v, block_v)
    dh = pl.pallas_call(
        functools.partial(_dh_kernel, block_n=block_n, block_v=block_v,
                          num_v_blocks=vb, vocab_size=v),
        grid=(nb, vb),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * block_n, d), h.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        interpret=interpret,
        name="fused_ce_bwd_dh",
    )(_pad_rows(h, block_n), emb,
      _pad_rows_fill(targets[:, None].astype(jnp.int32), block_n, -1),
      _pad_rows(lse[:, None], block_n), _pad_rows(g[:, None], block_n))
    return dh[:n]


def _bwd_merged_call(h, emb, targets, lse, g, block_n, block_v,
                     interpret):
    n, d = h.shape
    v = emb.shape[0]
    nb, vb = pl.cdiv(n, block_n), pl.cdiv(v, block_v)
    # Caller (_fused_ce_bwd) guarantees nb >= 4: the aliased dh buffer
    # is read back one vocab sweep after its write, and fewer inner
    # steps between them would race the write-back DMA.
    dh_init = jnp.zeros((nb * block_n, d), jnp.float32)
    dh, de = pl.pallas_call(
        functools.partial(_bwd_merged_kernel, block_n=block_n,
                          block_v=block_v, num_v_blocks=vb, vocab_size=v),
        grid=(vb, nb),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb * block_n, d), jnp.float32),
            jax.ShapeDtypeStruct((vb * block_v, d), emb.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_v, d), jnp.float32)],
        input_output_aliases={5: 0},
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="fused_ce_bwd_dhde_acc_dh",
    )(_pad_rows(h, block_n), emb,
      _pad_rows_fill(targets[:, None].astype(jnp.int32), block_n, -1),
      _pad_rows(lse[:, None], block_n),
      _pad_rows(g[:, None], block_n), dh_init)
    return dh[:n].astype(h.dtype), de[:v]


def _bwd_merged_b_call(h, emb, targets, lse, g, block_n, block_v,
                       interpret, de_acc_dtype=jnp.float32):
    n, d = h.shape
    v = emb.shape[0]
    nb, vb = pl.cdiv(n, block_n), pl.cdiv(v, block_v)
    # fp32 by default: the aliased dE accumulator round-trips HBM once
    # per token sweep, and bf16 would shed low-order gradient bits on
    # every sweep (then again at the cross-chunk sum).
    de_dtype = de_acc_dtype or emb.dtype
    de_init = jnp.zeros((vb * block_v, d), de_dtype)
    dh, de = pl.pallas_call(
        functools.partial(_bwd_merged_b_kernel, block_n=block_n,
                          block_v=block_v, num_v_blocks=vb, vocab_size=v),
        grid=(nb, vb),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb * block_n, d), h.dtype),
            jax.ShapeDtypeStruct((vb * block_v, d), de_dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="fused_ce_bwd_dhde_acc_de",
    )(_pad_rows(h, block_n), emb,
      _pad_rows_fill(targets[:, None].astype(jnp.int32), block_n, -1),
      _pad_rows(lse[:, None], block_n),
      _pad_rows(g[:, None], block_n), de_init)
    return dh[:n], de[:v].astype(emb.dtype)


def _de_call(h, emb, targets, lse, g, block_n, block_v, interpret):
    n, d = h.shape
    v = emb.shape[0]
    nb, vb = pl.cdiv(n, block_n), pl.cdiv(v, block_v)
    de = pl.pallas_call(
        functools.partial(_de_kernel, block_n=block_n, block_v=block_v,
                          num_v_blocks=vb, vocab_size=v),
        grid=(vb, nb),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((vb * block_v, d), emb.dtype),
        scratch_shapes=[pltpu.VMEM((block_v, d), jnp.float32)],
        interpret=interpret,
        name="fused_ce_bwd_de",
    )(_pad_rows(h, block_n), emb,
      _pad_rows_fill(targets[:, None].astype(jnp.int32), block_n, -1),
      _pad_rows(lse[:, None], block_n),
      # pad rows carry g=0 so they contribute nothing to dE
      _pad_rows(g[:, None], block_n))
    return de[:v]


# ---------------------------------------------------------------------------
# custom-VJP op
# ---------------------------------------------------------------------------

def _bwd_dispatch(hidden, embed, targets, lse, g, *, block_n, block_v,
                  interpret, variant, bwd_block_n, bwd_block_v):
    """Pick and run the backward kernels for one row chunk.

    Merged kernel: one logits recompute feeds both gradients (3
    N·V·D matmuls, the scan path's cost, vs the split kernels' 4).
    Variant "b" (dh in scratch, dE through the aliased buffer) has the
    lower accumulation traffic when N/bn sweeps are few; variant "a"
    (roles swapped) kept for sweeping; variant "split" forces the
    race-free unmerged kernels. Backward tiles derive from the
    caller's forward tiles (wider rows, narrower vocab — the fp32
    accumulators dominate VMEM) unless overridden explicitly.
    """
    if interpret:
        # The merged kernel accumulates dh through an input→output
        # ALIASED buffer — a compiled-mode memory property the
        # interpreter does not emulate (inputs there are functional
        # copies), so interpret mode runs the split kernels instead.
        variant = "split"
    n, v = hidden.shape[0], embed.shape[0]
    bn = min(bwd_block_n if bwd_block_n else min(2 * block_n, 1024), n)
    bv = min(bwd_block_v if bwd_block_v else max(128, block_v // 4), v)
    nb, vb = pl.cdiv(n, bn), pl.cdiv(v, bv)
    # The aliased accumulator block is re-read one sweep after its
    # write; with < 4 grid steps between them the write-back DMA may
    # not have landed before the prefetch (stale read). Variant A's
    # gap is nb steps, variant B's is vb — fall back to the split
    # kernels (no aliasing at all) when the margin is too thin.
    if variant == "a" and nb >= 4:
        return _bwd_merged_call(hidden, embed, targets, lse, g,
                                bn, bv, interpret)
    if variant == "b" and vb >= 4:
        return _bwd_merged_b_call(hidden, embed, targets, lse, g,
                                  bn, bv, interpret)
    dh = _dh_call(hidden, embed, targets, lse, g, block_n, block_v,
                  interpret)
    de = _de_call(hidden, embed, targets, lse, g, block_n,
                  min(block_v, 512), interpret)
    return dh, de


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fused_ce(hidden, embed, targets, block_n, block_v, interpret,
              variant, bwd_block_n, bwd_block_v):
    losses, _ = _fused_ce_fwd(hidden, embed, targets, block_n, block_v,
                              interpret, variant, bwd_block_n, bwd_block_v)
    return losses


def _fused_ce_fwd(hidden, embed, targets, block_n, block_v, interpret,
                  variant, bwd_block_n, bwd_block_v):
    lse, tl = _fwd_call(hidden, embed, targets, block_n, block_v,
                        interpret)
    return lse - tl, (hidden, embed, targets, lse)


def _fused_ce_bwd(block_n, block_v, interpret, variant, bwd_block_n,
                  bwd_block_v, res, g):
    hidden, embed, targets, lse = res
    g = g.astype(jnp.float32)
    dh, de = _bwd_dispatch(hidden, embed, targets, lse, g,
                           block_n=block_n, block_v=block_v,
                           interpret=interpret, variant=variant,
                           bwd_block_n=bwd_block_n,
                           bwd_block_v=bwd_block_v)
    return dh, de, None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_cross_entropy(hidden, embed, targets, *,
                        block_n: int = 512, block_v: int = 1024,
                        implementation: str | None = None,
                        bwd_variant: str = "b",
                        bwd_block_n: int | None = None,
                        bwd_block_v: int | None = None):
    """Per-token CE losses of ``hidden @ embed.T`` against ``targets``
    without materializing the (N, V) logits.

    hidden: (N, D) activations (bf16/fp32); embed: (V, D) tied embedding
    in the SAME dtype (cast outside, as the scan path does); targets:
    (N,) int. Returns fp32 (N,) losses; differentiable wrt hidden/embed.

    implementation: "pallas" | "reference" | "interpret" | None
    (auto: pallas on TPU, reference elsewhere).

    bwd_variant: "b" | "a" | "split" — merged-backward flavor (see
    ``_bwd_dispatch``); explicit kwargs, not env vars, so every process
    in a multi-host job traces the same program.
    """
    if implementation is None:
        implementation = ("pallas" if jax.default_backend() == "tpu"
                          else "reference")
    if implementation == "reference":
        return ce_reference(hidden, embed, targets)
    n, v = hidden.shape[0], embed.shape[0]
    interp = implementation == "interpret"
    # Row-chunking bounds the merged backward's aliased-dE traffic
    # (N/bn sweeps × V·D read+write per chunk) and keeps every chunk in
    # the VMEM-validated batch-4 tile geometry; autodiff sums the
    # per-chunk dE cotangents into the embedding gradient for free.
    row_chunk = 4096
    if n <= row_chunk or n % row_chunk:
        return _fused_ce(hidden, embed, targets, min(block_n, n),
                         min(block_v, v), interp, bwd_variant,
                         bwd_block_n, bwd_block_v)
    return jnp.concatenate([
        _fused_ce(hidden[i:i + row_chunk], embed,
                  targets[i:i + row_chunk], block_n,
                  min(block_v, v), interp, bwd_variant,
                  bwd_block_n, bwd_block_v)
        for i in range(0, n, row_chunk)])


# ---------------------------------------------------------------------------
# Sharded op: shard_map over token axes, two-pass merge over a tp vocab
# ---------------------------------------------------------------------------

def _local_targets(t, e_rows, vocab_axis):
    """Map global target ids to this vocab shard's local row space; ids
    owned by another shard become -1 (matches no column, so they add 0
    to the local target-logit partial and the one-hot correction)."""
    if vocab_axis is None:
        return t
    off = jax.lax.axis_index(vocab_axis) * e_rows
    return jnp.where((t >= off) & (t < off + e_rows), t - off, -1)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _sharded_ce(hidden, embed, targets, mesh, batch_axes, seq_axis,
                vocab_axis, block_n, block_v, interpret, variant,
                bwd_blocks):
    losses, _ = _sharded_ce_fwd(hidden, embed, targets, mesh, batch_axes,
                                seq_axis, vocab_axis, block_n, block_v,
                                interpret, variant, bwd_blocks)
    return losses


def _token_specs(batch_axes, seq_axis):
    from jax.sharding import PartitionSpec as P
    b = batch_axes if batch_axes else None
    return P(b, seq_axis), P(b, seq_axis, None)


def _sharded_ce_fwd(hidden, embed, targets, mesh, batch_axes, seq_axis,
                    vocab_axis, block_n, block_v, interpret, variant,
                    bwd_blocks):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    tspec, hspec = _token_specs(batch_axes, seq_axis)

    def body(h, e, t):
        bl, sl, d = h.shape
        n = bl * sl
        hf, tf = h.reshape(n, d), t.reshape(n)
        tf = _local_targets(tf, e.shape[0], vocab_axis)
        lse, tl = _fwd_call(hf, e, tf, min(block_n, n),
                            min(block_v, e.shape[0]), interpret)
        if vocab_axis is not None:
            # Cross-shard logsumexp merge: each shard holds the online
            # (running-max form) logsumexp of ITS vocab slice; combine
            # exactly, then sum the (one-owner) target-logit partials.
            m = jax.lax.pmax(lse, vocab_axis)
            lse = m + jnp.log(jax.lax.psum(jnp.exp(lse - m), vocab_axis))
            tl = jax.lax.psum(tl, vocab_axis)
        return ((lse - tl).reshape(bl, sl), lse.reshape(bl, sl))

    losses, lse = shard_map(
        body, mesh=mesh,
        in_specs=(hspec, P(vocab_axis, None), tspec),
        out_specs=(tspec, tspec), check_vma=False)(hidden, embed, targets)
    return losses, (hidden, embed, targets, lse)


def _sharded_ce_bwd(mesh, batch_axes, seq_axis, vocab_axis, block_n,
                    block_v, interpret, variant, bwd_blocks, res, g):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    hidden, embed, targets, lse = res
    tspec, hspec = _token_specs(batch_axes, seq_axis)
    token_axes = tuple(batch_axes) + ((seq_axis,) if seq_axis else ())
    bwd_block_n, bwd_block_v = bwd_blocks

    def body(h, e, t, lse_l, g_l):
        bl, sl, d = h.shape
        n = bl * sl
        hf, tf = h.reshape(n, d), t.reshape(n)
        tf = _local_targets(tf, e.shape[0], vocab_axis)
        row_chunk = 4096
        step = row_chunk if (n > row_chunk and n % row_chunk == 0) else n
        dhs, de = [], None
        for i in range(0, n, step):
            dh_c, de_c = _bwd_dispatch(
                hf[i:i + step], e, tf[i:i + step],
                lse_l.reshape(n)[i:i + step],
                g_l.reshape(n)[i:i + step].astype(jnp.float32),
                block_n=min(block_n, step), block_v=min(block_v, e.shape[0]),
                interpret=interpret, variant=variant,
                bwd_block_n=bwd_block_n, bwd_block_v=bwd_block_v)
            dhs.append(dh_c)
            de = de_c if de is None else de + de_c.astype(jnp.float32)
        dh = jnp.concatenate(dhs) if len(dhs) > 1 else dhs[0]
        if vocab_axis is not None:
            # Each vocab shard produced dh from ITS vocab slice only.
            dh = jax.lax.psum(dh.astype(jnp.float32), vocab_axis)
        if token_axes:
            # Each token shard produced dE from ITS tokens only.
            de = jax.lax.psum(de.astype(jnp.float32), token_axes)
        return (dh.astype(h.dtype).reshape(bl, sl, d),
                de.astype(e.dtype))

    dh, de = shard_map(
        body, mesh=mesh,
        in_specs=(hspec, P(vocab_axis, None), tspec, tspec, tspec),
        out_specs=(hspec, P(vocab_axis, None)), check_vma=False)(
            hidden, embed, targets, lse, g)
    return dh, de, None


_sharded_ce.defvjp(_sharded_ce_fwd, _sharded_ce_bwd)


def sharded_fused_cross_entropy(hidden, embed, targets, mesh, *,
                                block_n: int = 512, block_v: int = 1024,
                                implementation: str | None = None,
                                bwd_variant: str = "b",
                                bwd_block_n: int | None = None,
                                bwd_block_v: int | None = None):
    """``fused_cross_entropy`` for sharded meshes: the kernels run
    per-shard under ``shard_map`` (Pallas custom calls cannot be GSPMD-
    partitioned — same constraint as ops/attention.py
    ``sharded_flash_attention``), with tokens sharded over the mesh's
    data axes (dcn/dp/fsdp) and the sequence axis (sp), and the vocab
    either replicated or sharded over tp.

    Layouts and collectives (all forward-only, inside custom_vjp):
    - dp/fsdp/sp: embarrassingly parallel over tokens; the backward
      psums dE over the token axes (each shard saw only its tokens).
    - tp (vocab-sharded embedding): two-pass merge — each shard's
      forward kernel produces the logsumexp of its vocab slice and a
      target-logit partial; an exact ``pmax``/``psum`` combine yields
      the global row logsumexp, which the backward feeds to each
      shard's probability recompute, psumming dh over tp.

    hidden: (B, S, D) global array; targets: (B, S) int; returns (B, S)
    fp32 losses. ≙ the reference's fused softmax-CE partitioning under
    every strategy (TF/python/ops/nn_ops.py
    softmax_cross_entropy_with_logits — a fused XLA reduction GSPMD
    partitions like any HLO; here the partitioning is explicit because
    the op is a Mosaic custom call).
    """
    if implementation is None:
        implementation = ("pallas" if jax.default_backend() == "tpu"
                          else "reference")
    if implementation == "reference":
        B, S, D = hidden.shape
        return ce_reference(hidden.reshape(B * S, D), embed,
                            targets.reshape(B * S)).reshape(B, S)

    def axis_used(a):
        return a in mesh.shape and mesh.shape[a] > 1

    batch_axes = tuple(a for a in ("dcn", "dp", "fsdp") if axis_used(a))
    seq_axis = "sp" if axis_used("sp") else None
    vocab_axis = "tp" if axis_used("tp") else None
    B, S, _ = hidden.shape
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    if (B % n_batch or (seq_axis and S % mesh.shape[seq_axis])
            or (vocab_axis and embed.shape[0] % mesh.shape[vocab_axis])):
        raise ValueError(
            f"sharded_fused_cross_entropy: shapes B={B}, S={S}, "
            f"V={embed.shape[0]} not divisible by mesh shards "
            f"{dict(mesh.shape)}")
    return _sharded_ce(hidden, embed, targets, mesh, batch_axes, seq_axis,
                       vocab_axis, block_n, block_v,
                       implementation == "interpret", bwd_variant,
                       (bwd_block_n, bwd_block_v))
