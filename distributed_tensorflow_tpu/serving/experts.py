"""A layer of sparse experts for the serving forward, told which experts
it holds.

The router scores ALL the layer's outputs (``n_routed`` routed experts,
then ``n_identity`` identity experts), a token takes the ``top_k`` largest
of ``score + bias`` (the bias chooses and does not weigh) and weighs them
by ``scaling x score``, not renormalised. This rank holds the routed
experts ``offset .. offset + held - 1`` (expert parallelism: the others
lie on other ranks) and computes

    MoE(h) = sum_{chosen e held here} w_e Expert_e(h)
           + sum_{chosen e identity}  w_e h

with ``Expert_e`` a SiLU-gated feed-forward. The identity experts have no
weights and belong to the token's own rank, so every rank adds their part
for its own tokens; what the absent experts would have added is left out,
and on one device nothing stands in for it or for the exchange.

**No token is dropped and no untouched expert is read.** The chosen
(token, expert) pairs that fall on a held expert are laid out by expert,
each expert's rows padded to whole tiles (:func:`tile_layout`: counting,
no sort), the tokens are gathered into that layout, and both products run
as ``ops.grouped_matmul`` over the tiles that hold a pair: an expert
without a token has no tile and its weights are never fetched. The layout
is sized for the worst routing (every token choosing every held expert);
a router that sends half its picks to one expert fills more tiles, not a
capacity. ``implementation="dense"`` is the plain form (every held expert
over every token, weighted by the choice), for the CPU and as the
kernels' check.

Parameters of one layer (``p``): ``router`` (D, n_outputs), ``bias``
(n_outputs,), ``wi`` (held, D, 2 x d_expert: gate then up), ``wo`` (held,
d_expert, D). The two stacks may hold other layers' experts too (``wi``
(groups, D, ...), the layer's own from ``first_group`` on): a kernel is
handed whole arrays, and a slice of a stack of all layers' experts would
be copied for it, so the stack goes in whole and the tiles name the
layer's groups in it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.ops.grouped_matmul import grouped_matmul

#: what :func:`expert_layer` counts, in the order of its ``counts``
COUNTS = ("picks", "picks_local", "picks_identity", "experts_touched")


def route(ecfg, p, h):
    """``(chosen, weights)``, both ``(T, top_k)``: the experts each token
    of ``h`` (T, D) takes, by their index among the router's outputs, and
    the weights of their parts. The scores are float32 whatever ``h`` and
    the router's matrix are kept in: operands of one narrow type are
    multiplied exactly and accumulated in float32."""
    with jax.named_scope("moe.route"):
        w = p["router"]
        exact = h.dtype == w.dtype and h.dtype.itemsize < 4
        lhs, rhs = (h, w) if exact else (h.astype(jnp.float32),
                                         w.astype(jnp.float32))
        logits = jax.lax.dot_general(
            lhs, rhs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=None if exact else jax.lax.Precision.HIGHEST)
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores + p["bias"].astype(jnp.float32),
                                  ecfg.top_k)
        weights = ecfg.scaling * jnp.take_along_axis(scores, chosen, axis=-1)
        return chosen.astype(jnp.int32), weights


def tile_layout(local, valid, held: int, tile_rows: int):
    """Where each chosen pair goes in the by-expert layout.

    ``local`` (P,) int32 the held expert of each pair (``0..held-1``;
    anything for a pair with ``valid`` false: one that fell on an absent
    or identity expert, or a padded token's). An expert's pairs take
    consecutive rows in the order they come, from the first row of a tile
    of ``tile_rows`` rows, so that no tile holds two experts. Returns
    ``row`` (P,) the pair's row (past every tile for an invalid pair),
    ``tile_group`` (tiles,) the expert of each tile, ``n_tiles`` (1,) the
    tiles in use (the rest are padding), ``counts`` (held,) the pairs an
    expert received, and ``max_rows``, the rows the layout is sized for:
    ``tiles`` = every pair on a tile of its own expert plus one partly
    filled tile an expert."""
    P = local.shape[0]
    tiles = -(-P // tile_rows) + held
    onehot = (local[:, None] == jnp.arange(held, dtype=jnp.int32)[None]
              ) & valid[:, None]                               # (P, held)
    seen = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    counts = seen[-1]
    rank = jnp.sum(jnp.where(onehot, seen - 1, 0), axis=1)     # (P,)
    tiles_of = (counts + tile_rows - 1) // tile_rows           # (held,)
    first = jnp.cumsum(tiles_of) - tiles_of
    start = jnp.sum(jnp.where(onehot, first[None], 0), axis=1) * tile_rows
    row = jnp.where(valid, start + rank, tiles * tile_rows)
    tile_group = jnp.repeat(jnp.arange(held, dtype=jnp.int32), tiles_of,
                            total_repeat_length=tiles)
    return (row, tile_group, jnp.sum(tiles_of, dtype=jnp.int32)[None],
            counts, tiles * tile_rows)


def _gated(hh):
    gate, up = jnp.split(hh, 2, axis=-1)
    return jax.nn.silu(gate) * up


def _held_dense(ecfg, p, h, local, weights, valid, dt, first_group):
    """Every held expert over every token, weighted by the choice."""
    T = h.shape[0]
    which = jnp.zeros((T, ecfg.held), jnp.float32).at[
        jnp.arange(T)[:, None], jnp.where(valid, local, ecfg.held)].add(
            weights, mode="drop")                              # (T, held)
    wi, wo = (p[n][first_group:first_group + ecfg.held].astype(dt)
              for n in ("wi", "wo"))
    hh = _gated(jnp.einsum("td,edf->etf", h, wi))
    y = jnp.einsum("etf,efd->etd", hh, wo)
    return jnp.einsum("etd,te->td", y.astype(jnp.float32), which)


def _held_grouped(ecfg, p, h, local, weights, valid, dt, first_group,
                  tile_rows, interpret):
    """The held experts' part by tiles: gather, two grouped products,
    weigh, add back to the tokens.

    What XLA does around the kernels (the gather, the gate, the weighted
    add) runs over every row the layout is sized for, used or not, and
    the worst routing is far from the usual one: a rank that holds 16 of
    768 outputs receives a fiftieth of the picks. So where the layout is
    large (an admission's tokens) it is built twice, for the worst case
    and for an eighth of the picks, and the step takes the small one
    whenever its tiles fit in it."""
    T, k = local.shape
    row, tile_group, n_tiles, counts, rows = tile_layout(
        local.reshape(-1), valid.reshape(-1), ecfg.held, tile_rows)
    tile_group = tile_group + first_group
    token = jnp.arange(T * k, dtype=jnp.int32) // k
    wi, wo = p["wi"].astype(dt), p["wo"].astype(dt)

    def product(rows: int):
        # rows nobody was laid on read token 0 and weigh nothing
        row_token = jnp.zeros((rows,), jnp.int32).at[row].set(
            token, mode="drop")
        row_weight = jnp.zeros((rows,), jnp.float32).at[row].set(
            weights.reshape(-1), mode="drop")
        used = jnp.zeros((rows,), bool).at[row].set(True, mode="drop")
        kw = dict(tile_rows=tile_rows, interpret=interpret)
        groups = tile_group[:rows // tile_rows]
        hh = _gated(grouped_matmul(h[row_token], wi, groups, n_tiles, **kw))
        y = grouped_matmul(hh, wo, groups, n_tiles, **kw)
        # the tiles past n_tiles were never written: take nothing there
        y = jnp.where(used[:, None], y.astype(jnp.float32)
                      * row_weight[:, None], 0.0)
        return jnp.zeros((T, h.shape[1]), jnp.float32).at[row_token].add(y)

    small = (-(-T * k // (8 * tile_rows)) + ecfg.held) * tile_rows
    if rows < 4096 or rows < 2 * small:
        return product(rows), counts
    return jax.lax.cond(n_tiles[0] * tile_rows <= small,
                        lambda: product(small), lambda: product(rows)
                        ), counts


def expert_layer(ecfg, p, h, valid, *, dtype, implementation: str = "dense",
                 tile_rows: int = 16, first_group: int = 0):
    """``(MoE(h), counts)`` for the tokens ``h`` (T, D): float32 (T, D),
    zero for a token with ``valid`` (T,) false (a padded position or an
    idle slot: it is not routed and touches no expert), and int32
    ``counts`` in the order of :data:`COUNTS`: the picks of the valid
    tokens (``top_k`` each), those that fell on an expert held here, on
    an identity expert, and the held experts that received any.

    implementation: ``"dense"`` | ``"grouped"`` | ``"interpret"`` (the
    grouped kernels interpreted, for the CPU)."""
    if implementation not in ("dense", "grouped", "interpret"):
        raise ValueError(f"implementation={implementation!r}; expected "
                         f"'dense', 'grouped' or 'interpret'")
    chosen, weights = route(ecfg, p, h)
    taken = valid[:, None]
    on_identity = taken & (chosen >= ecfg.n_routed)
    local = chosen - ecfg.offset
    on_held = taken & (local >= 0) & (local < ecfg.held)
    with jax.named_scope("moe.identity"):
        out = jnp.sum(jnp.where(on_identity, weights, 0.0), axis=-1,
                      keepdims=True) * h.astype(jnp.float32)
    with jax.named_scope("moe.experts"):
        if implementation == "dense":
            out = out + _held_dense(ecfg, p, h, local, weights, on_held,
                                    dtype, first_group)
            touched = jnp.zeros((ecfg.held + 1,), bool).at[
                jnp.where(on_held, local, ecfg.held)].set(True)[:-1]
            n_touched = jnp.sum(touched, dtype=jnp.int32)
        else:
            part, received = _held_grouped(
                ecfg, p, h, local, weights, on_held, dtype, first_group,
                tile_rows, implementation == "interpret")
            out = out + part
            n_touched = jnp.sum(received > 0, dtype=jnp.int32)
    counts = jnp.stack([
        jnp.sum(valid, dtype=jnp.int32) * ecfg.top_k,
        jnp.sum(on_held, dtype=jnp.int32),
        jnp.sum(on_identity, dtype=jnp.int32), n_touched])
    return out, counts
