"""A looped stack in ``TransformerLM`` (ISSUE 28): the layers run
``passes`` times with the same weights, the final norm after every pass,
four norms a layer, an untied head, a rotary base of its own, an exit
gate's parameters. Logits are compared with the plain reference
(``benchmark/reference_looped.py``) on seeded random weights at a small
size; with every new option at its default the model is the one the
repo had."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import reference, reference_looped  # noqa: E402
from distributed_tensorflow_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, TransformerLM)

LOOPED = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
              max_seq_len=64, passes=3, post_norms=True,
              tie_embeddings=False, exit_gate=True, rope_base=1e6)
#: float32 on both sides and six layer applications: only the order of
#: summation differs (measured 7e-7); a bfloat16 weight or activation
#: moves a logit by 1e-2
ATOL = 2e-5
pytestmark = pytest.mark.usefixtures("leave_no_programs_behind")


def seeded_params(cfg, seed=0, noise=0.1):
    """Random weights with every leaf away from its initial value: norm
    scales of 1 and a zero gate bias would hide a norm or a gate that is
    left out. (At a width of 1024 ``noise`` 0.1 makes the softmax so
    sharp that float32 rounding shows in the logits: use less there.)"""
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        (leaf + noise * jax.random.normal(k, leaf.shape, jnp.float32)
         ).astype(leaf.dtype) for leaf, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def looped():
    cfg = TransformerConfig.tiny(**LOOPED)
    return cfg, seeded_params(cfg)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 128, (2, 24))


@pytest.mark.parametrize("scan_layers", [True, False])
def test_forward_matches_the_reference(looped, tokens, scan_layers):
    cfg, params = looped
    if not scan_layers:
        cfg = dataclasses.replace(cfg, scan_layers=False)
        params = dict(params)
        stacked = params.pop("layers")
        for i in range(cfg.n_layers):
            params[f"layer_{i}"] = jax.tree_util.tree_map(
                lambda a: a[i], stacked)
    ours = TransformerLM(cfg).apply({"params": params}, jnp.asarray(tokens))
    logits, exits = reference_looped.forward(
        params, tokens, passes=cfg.passes, rope_base=cfg.rope_base)
    assert logits.shape == (3, 2, 24, 128) and exits.shape == (3, 2, 24)
    # the model's output is the LAST pass's logits (exit threshold 1)
    np.testing.assert_allclose(ours, logits[-1], atol=ATOL)
    # and no earlier pass's: the passes differ by far more than ATOL
    assert float(jnp.max(jnp.abs(logits[-1] - logits[-2]))) > 100 * ATOL


@pytest.mark.parametrize("option,off", [
    ("passes", dict(passes=2)),
    ("rope_base", dict(rope_base=10000.0)),
])
def test_the_comparison_sees_each_argument(looped, tokens, option, off):
    """The reference run with one argument changed misses the model by
    far more than the tolerance: the comparison would catch a pass left
    out or a rotary base ignored."""
    cfg, params = looped
    ours = TransformerLM(cfg).apply({"params": params}, jnp.asarray(tokens))
    kw = dict(passes=cfg.passes, rope_base=cfg.rope_base)
    kw.update(off)
    theirs = reference_looped.forward(params, tokens, **kw)[0][-1]
    assert float(jnp.max(jnp.abs(ours - theirs))) > 100 * ATOL


@pytest.mark.parametrize("drop", ["post_attn_norm", "post_mlp_norm",
                                  "lm_head"])
def test_the_comparison_sees_each_part_of_the_tree(looped, tokens, drop):
    """The reference without one of the optional parts (it reads them
    off the tree) misses the model: every norm and the untied head are
    really applied."""
    cfg, params = looped
    ours = TransformerLM(cfg).apply({"params": params}, jnp.asarray(tokens))
    cut = jax.tree_util.tree_map(lambda a: a, dict(params))
    if drop in cut:
        cut.pop(drop)
    else:
        cut["layers"] = {k: v for k, v in cut["layers"].items()
                         if k != drop}
    theirs = reference_looped.forward(cut, tokens, passes=cfg.passes,
                                      rope_base=cfg.rope_base)[0][-1]
    assert float(jnp.max(jnp.abs(ours - theirs))) > 100 * ATOL


def test_exit_distribution_leaves_at_the_last_pass_at_threshold_one(
        looped, tokens):
    cfg, params = looped
    _, exits = reference_looped.forward(params, tokens, passes=cfg.passes,
                                        rope_base=cfg.rope_base)
    exits = np.asarray(exits)
    np.testing.assert_allclose(exits.sum(0), 1.0, atol=1e-6)
    assert (exits > 0).all()
    # the cumulative probability reaches 1 at the last pass and not
    # before: at the published threshold every token runs every pass
    assert (np.cumsum(exits, 0)[:-1] < 1.0).all()
    gates = np.array([[0.25], [0.5], [0.9]], np.float32)
    np.testing.assert_allclose(
        reference_looped.exit_distribution(jnp.asarray(gates))[:, 0],
        [0.25, 0.75 * 0.5, 0.75 * 0.5], atol=1e-7)


def test_defaults_are_the_model_the_repo_had(tokens):
    """passes = 1 and the new options off: the parameter tree has the
    keys it had, the output is bit for bit that of the explicit
    defaults, and it is the old plain reference's model."""
    cfg = TransformerConfig.tiny(vocab_size=128)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    assert set(params) == {"embed", "final_norm", "layers"}
    assert set(params["layers"]) == {"RMSNorm_0", "RMSNorm_1", "attn",
                                     "mlp"}
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree_util.tree_leaves(params))
    spelled = dataclasses.replace(
        cfg, passes=1, post_norms=False, tie_embeddings=True,
        exit_gate=False, rope_base=10000.0, param_dtype=jnp.float32)
    assert spelled == cfg
    ours = TransformerLM(cfg).apply({"params": params}, jnp.asarray(tokens))
    np.testing.assert_allclose(
        ours, reference.forward(params, jnp.asarray(tokens)), atol=ATOL)
    # the looped reference at one pass is the old reference
    np.testing.assert_allclose(
        reference_looped.forward(params, tokens, passes=1)[0][0],
        reference.forward(params, jnp.asarray(tokens)), atol=ATOL)


def test_parameters_are_created_in_the_configured_type():
    cfg = TransformerConfig.tiny(**LOOPED, param_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda r: TransformerLM(cfg).init(r, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))["params"]
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(shapes)} == {
        jnp.dtype(jnp.bfloat16)}
    # the same weights at every pass: a looped model has no more
    # parameters than its stack, head and gate
    one = jax.eval_shape(
        lambda r: TransformerLM(dataclasses.replace(cfg, passes=1)).init(
            r, jnp.zeros((1, 8), jnp.int32)), jax.random.PRNGKey(0))
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == count(one["params"])
    assert shapes["lm_head"].shape == shapes["embed"].shape
    assert shapes["exit_gate_kernel"].shape == (cfg.d_model,)


def test_a_stack_runs_at_least_once():
    with pytest.raises(ValueError):
        TransformerConfig.tiny(passes=0)
