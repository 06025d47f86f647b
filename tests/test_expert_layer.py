"""The serving layer of sparse experts (``serving/experts.py``) and its
grouped product (``ops/grouped_matmul.py``), at a small size on the CPU:
the kernels interpreted against the plain form, the by-expert layout, a
skewed router that drops no token, the counts, and the shares of an
expert-parallel deployment adding up to the whole layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import scmoe, scmoe_reference
from distributed_tensorflow_tpu.models.transformer import (
    ExpertLayer, TransformerConfig)
from distributed_tensorflow_tpu.ops.grouped_matmul import grouped_matmul
from distributed_tensorflow_tpu.serving import experts

pytestmark = pytest.mark.usefixtures("leave_no_programs_behind")

D, F = 32, 24
FULL = ExpertLayer(n_routed=32, n_identity=16, top_k=6, d_expert=F,
                   scaling=6.0)


def _params(rng, ecfg, spread=3.0, skew=None):
    k = jax.random.split(rng, 4)
    router = jax.random.normal(k[0], (D, ecfg.n_outputs)) * spread / D ** .5
    if skew is not None:
        # every token's largest score by far: expert ``skew`` takes a pick
        # of every token, half of all the picks that fall on held experts
        router = router.at[:, skew].set(0.0)
    bias = jnp.zeros((ecfg.n_outputs,)).at[skew].set(10.0) \
        if skew is not None else jax.random.normal(k[1], (ecfg.n_outputs,)
                                                   ) * 0.002
    return {"router": router, "bias": bias,
            "wi": jax.random.normal(k[2], (ecfg.n_routed, D, 2 * F)) / D ** .5,
            "wo": jax.random.normal(k[3], (ecfg.n_routed, F, D)) / F ** .5}


def _share(p, ecfg, rank, ranks):
    """Rank ``rank``'s configuration and parameters: its slice of the
    routed experts, the whole router."""
    held = ecfg.n_routed // ranks
    cfg = dataclasses.replace(ecfg, held=held, offset=rank * held)
    lo = rank * held
    return cfg, dict(p, wi=p["wi"][lo:lo + held], wo=p["wo"][lo:lo + held])


def _by_hand(ecfg, p, h, routed=True, identity=True):
    """The whole layer, one token and one pick at a time, in numpy."""
    h, out = np.asarray(h, np.float64), np.zeros(h.shape, np.float64)
    logits = h @ np.asarray(p["router"], np.float64)
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    for t in range(h.shape[0]):
        chosen = np.argsort(-(s[t] + np.asarray(p["bias"])))[:ecfg.top_k]
        for e in chosen:
            w = ecfg.scaling * s[t, e]
            if e >= ecfg.n_routed:
                out[t] += w * h[t] * identity
            elif routed:
                hh = h[t] @ np.asarray(p["wi"][e], np.float64)
                g, u = hh[:F], hh[F:]
                out[t] += w * ((g / (1 + np.exp(-g)) * u)
                               @ np.asarray(p["wo"][e], np.float64))
    return out


@pytest.mark.parametrize("impl", ["dense", "interpret"])
def test_whole_layer_matches_a_count_by_hand(impl):
    p = _params(jax.random.PRNGKey(0), FULL)
    h = jax.random.normal(jax.random.PRNGKey(1), (20, D))
    valid = jnp.arange(20) < 17
    out, counts = experts.expert_layer(FULL, p, h, valid, dtype=jnp.float32,
                                       implementation=impl, tile_rows=8)
    want = _by_hand(FULL, p, h)
    np.testing.assert_allclose(out[:17], want[:17], atol=2e-5)
    # a padded token is not routed: it gets nothing and counts nowhere
    assert not np.asarray(out[17:]).any()
    picks, local, identity, touched = map(int, counts)
    assert picks == 17 * FULL.top_k == local + identity    # all are held
    assert 0 < touched <= FULL.n_routed and identity > 0


@pytest.mark.parametrize("impl", ["dense", "interpret"])
def test_the_shares_add_up_to_the_whole_layer(impl):
    """32 experts over 4 ranks: the four routed parts, and the identity
    part counted once (every rank computes it alike for its own tokens),
    equal the uncut layer; and a rank's counts split its picks into
    local, identity and absent."""
    p = _params(jax.random.PRNGKey(2), FULL)
    h = jax.random.normal(jax.random.PRNGKey(3), (24, D))
    valid = jnp.ones((24,), bool)
    whole = _by_hand(FULL, p, h)
    identity_part = _by_hand(FULL, p, h, routed=False)
    total, picked_local = np.zeros_like(whole), 0
    for rank in range(4):
        cfg, mine = _share(p, FULL, rank, 4)
        out, counts = experts.expert_layer(
            cfg, mine, h, valid, dtype=jnp.float32, implementation=impl,
            tile_rows=8)
        picks, local, identity, touched = map(int, counts)
        assert picks == 24 * FULL.top_k and touched <= cfg.held == 8
        absent = picks - local - identity
        assert absent >= 0 and local + identity + absent == picks
        picked_local += local
        total += np.asarray(out, np.float64) - identity_part
    np.testing.assert_allclose(total + identity_part, whole, atol=5e-5)
    # every pick of a routed expert is some rank's local pick
    assert picked_local == 24 * FULL.top_k - identity


def test_no_token_is_dropped_under_a_skewed_router():
    """One held expert takes a pick of EVERY token (half the picks that
    fall on this rank): its rows fill several tiles, no capacity cuts
    them, and the result is the plain form's."""
    cfg, _ = _share(_params(jax.random.PRNGKey(4), FULL), FULL, 1, 4)
    p = _params(jax.random.PRNGKey(4), FULL, skew=cfg.offset + 3)
    _, mine = _share(p, FULL, 1, 4)
    h = jax.random.normal(jax.random.PRNGKey(5), (40, D))
    valid = jnp.ones((40,), bool)
    chosen, _ = experts.route(cfg, mine, h)
    on_skewed = int(jnp.sum(chosen == cfg.offset + 3))
    on_held = int(jnp.sum((chosen >= cfg.offset)
                          & (chosen < cfg.offset + cfg.held)))
    assert on_skewed == 40 and on_skewed >= on_held / 2
    dense, c0 = experts.expert_layer(cfg, mine, h, valid, dtype=jnp.float32,
                                     implementation="dense")
    tiled, c1 = experts.expert_layer(cfg, mine, h, valid, dtype=jnp.float32,
                                     implementation="interpret", tile_rows=8)
    np.testing.assert_allclose(tiled, dense, atol=2e-5)
    np.testing.assert_array_equal(c0, c1)
    assert int(c1[1]) == on_held
    # against the reference's layer given this rank's experts
    shape = TransformerConfig(d_model=D, n_heads=1, experts=cfg)
    ref = scmoe_reference._moe(shape, mine, h,
                               lambda a: jnp.asarray(a, jnp.float32), None)
    np.testing.assert_allclose(tiled, ref, atol=5e-5)


def test_tile_layout_gives_each_expert_its_own_tiles():
    local = jnp.asarray([2, 0, 2, 2, 1, 2, 2, 0, 3, 2], jnp.int32)
    valid = jnp.asarray([1, 1, 1, 1, 0, 1, 1, 1, 0, 1], bool)
    row, tile_group, n_tiles, counts, rows = experts.tile_layout(
        local, valid, held=4, tile_rows=4)
    assert rows == (3 + 4) * 4
    np.testing.assert_array_equal(counts, [2, 0, 6, 0])
    # expert 0: tile 0; expert 2: tiles 1 and 2; nothing for 1 and 3
    assert int(n_tiles[0]) == 3
    np.testing.assert_array_equal(tile_group[:3], [0, 2, 2])
    np.testing.assert_array_equal(
        row, [4, 0, 5, 6, rows, 7, 8, 1, rows, 9])


def test_grouped_matmul_reads_the_groups_its_tiles_name():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    # a group full of NaN that no tile names never reaches the result
    w = jnp.asarray(rng.normal(size=(5, 16, 256)), jnp.float32
                    ).at[3].set(jnp.nan)
    tile_group = jnp.asarray([4, 0, 0, 3], jnp.int32)
    y = grouped_matmul(x, w, tile_group, jnp.asarray([3], jnp.int32),
                       tile_rows=8, interpret=True)
    for i, g in enumerate([4, 0, 0]):
        np.testing.assert_allclose(y[i * 8:(i + 1) * 8],
                                   x[i * 8:(i + 1) * 8] @ w[g], atol=1e-4)
    with pytest.raises(ValueError, match="tiles"):
        grouped_matmul(x, w, tile_group[:3], jnp.asarray([3], jnp.int32),
                       tile_rows=8, interpret=True)


def test_expert_layer_shape_is_checked():
    with pytest.raises(ValueError, match="held"):
        ExpertLayer(n_routed=8, top_k=2, d_expert=4, held=6, offset=4)
    with pytest.raises(ValueError, match="top_k"):
        ExpertLayer(n_routed=8, top_k=9, d_expert=4)
    assert ExpertLayer(n_routed=8, top_k=2, d_expert=4).held == 8
    cfg = TransformerConfig(experts={"n_routed": 8, "top_k": 2,
                                     "d_expert": 4, "n_identity": 4})
    assert cfg.experts.n_outputs == 12 and hash(cfg) is not None
    with pytest.raises(ValueError, match="implementation"):
        experts.expert_layer(FULL, {}, jnp.zeros((1, D)),
                             jnp.ones((1,), bool), dtype=jnp.float32,
                             implementation="capacity")
    assert (scmoe.ROUTER_SPREAD, scmoe.EXPERT_GAIN) == (3.0, 4.0)


def test_a_large_layout_is_taken_small_when_the_picks_fit():
    """Many tokens (an admission): the layout for the worst routing and
    the one for an eighth of the picks give the same result, whichever
    the routing lets the step take."""
    cfg, _ = _share(_params(jax.random.PRNGKey(6), FULL), FULL, 2, 8)
    h = jax.random.normal(jax.random.PRNGKey(7), (720, D))
    valid = jnp.ones((720,), bool)
    for skew in (None, cfg.offset + 1):       # the small layout, the full
        p = _params(jax.random.PRNGKey(6), FULL, skew=skew)
        _, mine = _share(p, FULL, 2, 8)
        _, _, n_tiles, _, rows = experts.tile_layout(
            (experts.route(cfg, mine, h)[0] - cfg.offset).reshape(-1),
            jnp.ones((720 * 6,), bool), cfg.held, 8)
        small = (-(-720 * 6 // 64) + cfg.held) * 8
        assert rows >= 4096 and (int(n_tiles[0]) * 8 > small) == bool(skew)
        dense, c0 = experts.expert_layer(cfg, mine, h, valid,
                                         dtype=jnp.float32)
        tiled, c1 = experts.expert_layer(
            cfg, mine, h, valid, dtype=jnp.float32,
            implementation="interpret", tile_rows=8)
        np.testing.assert_allclose(tiled, dense, atol=5e-5)
        np.testing.assert_array_equal(c0, c1)
