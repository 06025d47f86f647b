"""Model families: ResNet-50, BERT MLM, Wide&Deep — distributed training
matches single-device and loss decreases (the reference's
keras_correctness_test_base.py pattern, SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.cluster.topology import make_mesh


# ---------------------------------------------------------------- ResNet
class TestResNet:
    @pytest.fixture(scope="class")
    def setup(self):
        from distributed_tensorflow_tpu.models import resnet
        cfg = resnet.ResNetConfig.tiny()
        batch = resnet.synthetic_images(8, image_size=32,
                                        num_classes=cfg.num_classes)
        return resnet, cfg, batch

    def test_loss_decreases_dp(self, setup, devices):
        resnet, cfg, batch = setup
        mesh = make_mesh({"dp": 8})
        state, step = resnet.make_sharded_train_step(
            cfg, mesh, global_batch=8, image_size=32)
        losses = []
        for _ in range(5):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

    def test_dp_matches_single_device(self, setup, devices):
        resnet, cfg, batch = setup
        mesh = make_mesh({"dp": 8})
        state, step = resnet.make_sharded_train_step(
            cfg, mesh, global_batch=8, image_size=32)
        dist = []
        for _ in range(3):
            state, m = step(state, batch)
            dist.append(float(m["loss"]))

        model = resnet.ResNet(cfg, train=True)
        tx = resnet.make_optimizer(cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((8, 32, 32, 3)))
        sstate = {"params": variables["params"],
                  "batch_stats": variables.get("batch_stats", {}),
                  "opt_state": tx.init(variables["params"]),
                  "step": jnp.zeros((), jnp.int32)}
        sstep = jax.jit(resnet.make_train_step(cfg, model, tx))
        single = []
        for _ in range(3):
            sstate, m = sstep(sstate, batch)
            single.append(float(m["loss"]))
        np.testing.assert_allclose(dist, single, rtol=2e-4)

    def test_bn_sync_changes_stats_not_structure(self, setup, devices):
        """sync BN must still train; its per-step losses legitimately
        differ from local BN (global vs local statistics)."""
        resnet, cfg, batch = setup
        import dataclasses
        sync_cfg = dataclasses.replace(cfg, sync_batch_norm=True)
        mesh = make_mesh({"dp": 8})
        state, step = resnet.make_sharded_train_step(
            sync_cfg, mesh, global_batch=8, image_size=32)
        losses = []
        for _ in range(3):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert np.isfinite(losses).all()


# ------------------------------------------------------------------ BERT
class TestBert:
    @pytest.fixture(scope="class")
    def setup(self):
        from distributed_tensorflow_tpu.models import bert
        cfg = bert.tiny_bert_config()
        batch = bert.synthetic_corpus(8, cfg.max_seq_len, cfg.vocab_size)
        return bert, cfg, batch

    def test_mlm_loss_ignores_unmasked(self, setup):
        bert, cfg, _ = setup
        logits = jnp.zeros((2, 4, cfg.vocab_size))
        labels = jnp.full((2, 4), bert.IGNORE_LABEL)
        labels = labels.at[0, 0].set(3)
        loss = bert.mlm_loss(logits, labels)
        np.testing.assert_allclose(float(loss), np.log(cfg.vocab_size),
                                   rtol=1e-5)

    def test_masking_rate(self, setup):
        bert, cfg, batch = setup
        inputs, labels = bert.apply_mlm_masking(
            jax.random.PRNGKey(0), batch["tokens"],
            vocab_size=cfg.vocab_size)
        rate = float((labels != bert.IGNORE_LABEL).mean())
        assert 0.10 < rate < 0.20, rate
        # 80% of masked positions replaced with MASK_TOKEN
        masked = labels != bert.IGNORE_LABEL
        frac_mask_tok = float((inputs[masked] == bert.MASK_TOKEN).mean())
        assert 0.6 < frac_mask_tok < 0.95, frac_mask_tok

    @pytest.mark.parametrize("axes", [{"dp": 8}, {"dp": 2, "tp": 4}])
    def test_training_decreases_loss(self, setup, axes, devices):
        bert, cfg, batch = setup
        mesh = make_mesh(axes)
        state, step = bert.make_sharded_train_step(cfg, mesh,
                                                   global_batch=8)
        losses = []
        for _ in range(5):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

    def test_mesh_equivalence(self, setup, devices):
        bert, cfg, batch = setup
        runs = {}
        for name, axes in [("dp", {"dp": 8}), ("tp", {"dp": 2, "tp": 4})]:
            state, step = bert.make_sharded_train_step(cfg, mesh := make_mesh(axes),
                                                       global_batch=8)
            ls = []
            for _ in range(3):
                state, m = step(state, batch)
                ls.append(float(m["loss"]))
            runs[name] = ls
        np.testing.assert_allclose(runs["dp"], runs["tp"], rtol=2e-4)


# ------------------------------------------------------------- Wide&Deep
class TestWideDeep:
    @pytest.fixture(scope="class")
    def setup(self):
        from distributed_tensorflow_tpu.models import wide_deep
        cfg = wide_deep.WideDeepConfig.tiny()
        batch = wide_deep.synthetic_clicks(cfg, 64)
        return wide_deep, cfg, batch

    @pytest.mark.parametrize("interaction", ["concat", "dot"])
    def test_loss_decreases(self, setup, interaction, devices):
        wide_deep, cfg, batch = setup
        import dataclasses
        cfg = dataclasses.replace(cfg, interaction=interaction)
        mesh = make_mesh({"dp": 4, "tp": 2})
        state, step = wide_deep.make_sharded_train_step(cfg, mesh,
                                                        global_batch=64)
        losses = []
        for _ in range(10):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

    def test_tables_sharded_over_tp(self, setup, devices):
        wide_deep, cfg, batch = setup
        mesh = make_mesh({"dp": 4, "tp": 2})
        state, _ = wide_deep.make_sharded_train_step(cfg, mesh,
                                                     global_batch=64)
        spec = tuple(state["params"]["table_0"].sharding.spec)
        assert spec and spec[0] == "tp", spec

    def test_tp_matches_dp(self, setup, devices):
        wide_deep, cfg, batch = setup
        runs = {}
        for name, axes in [("dp", {"dp": 8}), ("tp", {"dp": 4, "tp": 2})]:
            state, step = wide_deep.make_sharded_train_step(
                cfg, make_mesh(axes), global_batch=64)
            ls = []
            for _ in range(3):
                state, m = step(state, batch)
                ls.append(float(m["loss"]))
            runs[name] = ls
        np.testing.assert_allclose(runs["dp"], runs["tp"], rtol=2e-4)
