"""Flagship-transformer integration of pipeline (pp) and expert (ep)
parallelism: distributed == single/dp equivalence (≙ the reference's
distributed-correctness test discipline, SURVEY.md §4 applied to the two
parallelism axes the reference never had, §2.8 rows PP/EP)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.cluster.topology import make_mesh
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig,
    make_pipelined_train_step,
    make_sharded_train_step,
    synthetic_tokens,
)


def test_pipelined_step_matches_dp(devices):
    """GPipe over dp×pp == plain dp, step for step."""
    cfg = TransformerConfig.tiny()
    toks = synthetic_tokens(8, cfg.max_seq_len, cfg.vocab_size)

    mesh_pp = make_mesh({"dp": 2, "pp": 2}, devices=jax.devices()[:4])
    s_pp, step_pp = make_pipelined_train_step(cfg, mesh_pp, 8,
                                              num_microbatches=4, seed=0)
    mesh_dp = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    s_dp, step_dp = make_sharded_train_step(cfg, mesh_dp, 8, seed=0)

    for _ in range(3):
        s_pp, m_pp = step_pp(s_pp, {"tokens": toks})
        s_dp, m_dp = step_dp(s_dp, {"tokens": toks})
        np.testing.assert_allclose(float(m_pp["loss"]),
                                   float(m_dp["loss"]), rtol=5e-5)


def test_pipelined_step_single_stage_degenerates(devices):
    """pp=1 is numerically the plain model (wiring sanity)."""
    cfg = TransformerConfig.tiny()
    toks = synthetic_tokens(4, cfg.max_seq_len, cfg.vocab_size)
    mesh = make_mesh({"dp": 1, "pp": 1}, devices=jax.devices()[:1])
    s, step = make_pipelined_train_step(cfg, mesh, 4, num_microbatches=2,
                                        seed=0)
    mesh1 = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    s1, step1 = make_sharded_train_step(cfg, mesh1, 4, seed=0)
    s, m = step(s, {"tokens": toks})
    s1, m1 = step1(s1, {"tokens": toks})
    np.testing.assert_allclose(float(m["loss"]), float(m1["loss"]),
                               rtol=5e-5)


def test_moe_transformer_ep_matches_single_device(devices):
    """MoE-MLP flagship on dp×ep == the identical model on one device."""
    cfg = TransformerConfig.tiny(moe_experts=4, moe_top_k=2,
                                 moe_capacity_factor=2.0)
    toks = synthetic_tokens(8, cfg.max_seq_len, cfg.vocab_size)

    mesh_ep = make_mesh({"dp": 2, "ep": 4})
    s_ep, step_ep = make_sharded_train_step(cfg, mesh_ep, 8, seed=0)
    mesh_1 = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    s_1, step_1 = make_sharded_train_step(cfg, mesh_1, 8, seed=0)

    for _ in range(3):
        s_ep, m_ep = step_ep(s_ep, {"tokens": toks})
        s_1, m_1 = step_1(s_1, {"tokens": toks})
        np.testing.assert_allclose(float(m_ep["loss"]),
                                   float(m_1["loss"]), rtol=1e-4)


def test_moe_aux_loss_in_objective(devices):
    """The Switch aux loss actually reaches the objective: zeroing its
    weight changes the loss."""
    toks = synthetic_tokens(4, 128, 256)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    losses = {}
    for w in (0.0, 1.0):
        cfg = TransformerConfig.tiny(moe_experts=4, moe_aux_weight=w)
        s, step = make_sharded_train_step(cfg, mesh, 4, seed=0)
        _, m = step(s, {"tokens": toks})
        losses[w] = float(m["loss"])
    assert losses[1.0] > losses[0.0]     # aux adds a positive penalty
