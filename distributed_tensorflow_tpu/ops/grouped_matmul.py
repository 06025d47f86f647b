"""Grouped matrix product for a layer of sparse experts (Pallas, TPU).

``x`` is ``(M, K)`` rows laid out in *tiles* of ``tile_rows`` rows, each
tile belonging wholly to one group (an expert: its rows are the tokens
routed to it, padded to whole tiles), and ``w`` is ``(G, K, N)``, one
matrix a group. The product of tile ``i`` is ``x_tile @ w[tile_group[i]]``.

**Only what is named is read.** The grid runs over the first ``n_tiles``
tiles (a traced number, as the paged attention kernels' run lists) and a
tile's weight blocks are fetched through the scalar-prefetched
``tile_group``, so a group that no tile names is never touched: an expert
that received no token costs no byte. Rows of the tiles past ``n_tiles``
are not written; the caller masks them.

Weights move in ``(block_k, block_n)`` blocks of up to 2048 x 1024
elements (4 MB in bfloat16): a grid step costs about 0.35 us whatever it
moves and such a block takes 5 us at the v5e's 819 GB/s, so the product
of a tile with few rows (a decode step sends an expert one or two tokens)
runs at the rate its weights can be read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the largest weight block, in elements of ``K`` and of ``N``
MAX_BLOCK_K = 2048
MAX_BLOCK_N = 1024


def _block(size: int, most: int) -> int:
    """The largest divisor of ``size`` that is a multiple of 128 and at
    most ``most``; ``size`` itself where it has none (a small matrix)."""
    for b in range(min(most, size) // 128 * 128, 0, -128):
        if size % b == 0:
            return b
    return size


def _kernel(group_ref, x_ref, w_ref, o_ref, acc_scr):
    del group_ref                                # the index maps read it
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    x, w = x_ref[...], w_ref[...]
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if w.dtype == jnp.float32
                   else None))

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def grouped_matmul(x, w, tile_group, n_tiles, *, tile_rows: int,
                   interpret: bool = False):
    """``y`` (M, N) in ``x``'s type: rows of tile ``i < n_tiles[0]`` are
    ``x[i * tile_rows:(i + 1) * tile_rows] @ w[tile_group[i]]``
    accumulated in float32; the rows of later tiles are not written.

    ``x`` (M, K) with ``M`` a multiple of ``tile_rows``, ``w`` (G, K, N),
    ``tile_group`` (M // tile_rows,) int32, ``n_tiles`` (1,) int32.
    Jitted so that a program which calls it once per layer traces and
    lowers the kernel once."""
    M, K = x.shape
    G, _, N = w.shape
    if M % tile_rows or tile_group.shape != (M // tile_rows,):
        raise ValueError(f"{M} rows in tiles of {tile_rows} with "
                         f"{tile_group.shape} tile groups")
    bk, bn = _block(K, MAX_BLOCK_K), _block(N, MAX_BLOCK_N)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles[0], N // bn, K // bk),
            in_specs=[
                pl.BlockSpec((tile_rows, bk), lambda i, n, k, g: (i, k)),
                pl.BlockSpec((None, bk, bn),
                             lambda i, n, k, g: (g[i], k, n)),
            ],
            out_specs=pl.BlockSpec((tile_rows, bn),
                                   lambda i, n, k, g: (i, n)),
            scratch_shapes=[pltpu.VMEM((tile_rows, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="expert_grouped_matmul",
    )(tile_group.astype(jnp.int32), x, w)
