"""ResNet-50 — benchmark workload #2 (BASELINE.md: MWMS/NCCL reference).

TPU-native redesign: where the reference trains ResNet-50 with
`MultiWorkerMirroredStrategy` + NCCL allreduce (reference:
tensorflow/python/distribute/collective_all_reduce_strategy.py:57), here
the train step is one jit-compiled SPMD program over the mesh — batch
sharded over dp, gradient psum inserted by GSPMD over ICI.

TPU-first details:
- bfloat16 conv compute, float32 batch-norm statistics and parameters
  (bf16 variance is numerically unsafe).
- NHWC layout (TPU conv-friendly); convolutions hit the MXU via XLA's
  implicit im2col.
- BatchNorm under SPMD jit computes *global-batch* statistics by
  construction (the mean over a dp-sharded axis is the global mean;
  GSPMD inserts the reduce) — stronger than the reference, whose BN
  under MirroredStrategy normalizes per replica. ``sync_batch_norm``
  additionally psums stats when running inside shard_map (the
  TF-parity Strategy.run path, where batches really are per-replica).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp

import optax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    stage_sizes: tuple = (3, 4, 6, 3)       # ResNet-50
    num_classes: int = 1000
    width: int = 64
    dtype: Any = jnp.bfloat16
    sync_batch_norm: bool = False
    axis_names: tuple = ("dp",)             # BN sync axes (if enabled)
    learning_rate: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4
    label_smoothing: float = 0.1

    @classmethod
    def resnet50(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """CI-sized: resnet-8-ish on 32x32 inputs."""
        defaults = dict(stage_sizes=(1, 1), num_classes=10, width=8,
                        dtype=jnp.float32)
        defaults.update(kw)
        return cls(**defaults)


class BatchNorm(nn.Module):
    """BN with float32 statistics and optional shard_map-mode sync.

    Under SPMD jit, batch statistics are global across the sharded batch
    (≙ SyncBatchNormalization — beyond the reference's per-replica keras
    BN). ``sync_axes`` adds an explicit psum for shard_map contexts.
    """
    use_running_average: bool
    sync_axes: tuple = ()
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        features = x.shape[-1]
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros(features, jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones(features, jnp.float32))
        scale = self.param("scale", nn.initializers.ones, (features,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (features,),
                          jnp.float32)

        x32 = x.astype(jnp.float32)
        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            reduce_axes = tuple(range(x.ndim - 1))
            mean = jnp.mean(x32, axis=reduce_axes)
            mean2 = jnp.mean(jnp.square(x32), axis=reduce_axes)
            if self.sync_axes:
                # Only meaningful inside shard_map (the TF-parity
                # Strategy.run path). Under SPMD jit the mean over a
                # dp-sharded batch axis is already the GLOBAL mean —
                # GSPMD inserts the cross-replica reduce itself.
                try:
                    mean = jax.lax.pmean(mean, self.sync_axes)
                    mean2 = jax.lax.pmean(mean2, self.sync_axes)
                except NameError:   # axis not bound: jit/GSPMD context
                    pass
            var = mean2 - jnp.square(mean)
            if not self.is_initializing():
                ra_mean.value = (self.momentum * ra_mean.value
                                 + (1 - self.momentum) * mean)
                ra_var.value = (self.momentum * ra_var.value
                                + (1 - self.momentum) * var)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.epsilon)
        return (y * scale + bias).astype(self.dtype)


class BottleneckBlock(nn.Module):
    filters: int
    strides: int
    cfg: ResNetConfig
    train: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = functools.partial(
            BatchNorm, use_running_average=not self.train,
            sync_axes=cfg.axis_names if cfg.sync_batch_norm else (),
            dtype=cfg.dtype)
        conv = functools.partial(nn.Conv, use_bias=False, dtype=cfg.dtype)

        residual = x
        y = conv(self.filters, (1, 1))(x)
        y = norm()(y)
        y = nn.relu(y)
        y = conv(self.filters, (3, 3), strides=(self.strides,) * 2)(y)
        y = norm()(y)
        y = nn.relu(y)
        y = conv(4 * self.filters, (1, 1))(y)
        y = norm()(y)

        if residual.shape != y.shape:
            residual = conv(4 * self.filters, (1, 1),
                            strides=(self.strides,) * 2,
                            name="proj")(residual)
            residual = norm(name="proj_bn")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    cfg: ResNetConfig
    train: bool = True

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = functools.partial(
            BatchNorm, use_running_average=not self.train,
            sync_axes=cfg.axis_names if cfg.sync_batch_norm else (),
            dtype=cfg.dtype)
        x = x.astype(cfg.dtype)
        x = nn.Conv(cfg.width, (7, 7), strides=(2, 2), use_bias=False,
                    dtype=cfg.dtype, name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(cfg.stage_sizes):
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                x = BottleneckBlock(cfg.width * 2 ** i, strides, cfg,
                                    self.train,
                                    name=f"stage{i}_block{j}")(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(cfg.num_classes, dtype=jnp.float32,
                     name="classifier")(x.astype(jnp.float32))
        return x


def make_train_step(cfg: ResNetConfig, model: ResNet, tx):
    """(state, batch) -> (state, metrics). state: params/batch_stats/
    opt_state/step; batch: {"image": NHWC, "label": int}."""

    def loss_fn(params, batch_stats, images, labels):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            mutable=["batch_stats"])
        one_hot = optax.smooth_labels(
            jax.nn.one_hot(labels, cfg.num_classes), cfg.label_smoothing)
        loss = optax.softmax_cross_entropy(logits, one_hot).mean()
        return loss, (logits, mutated["batch_stats"])

    def train_step(state, batch):
        (loss, (logits, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"], state["batch_stats"],
                                   batch["image"], batch["label"])
        updates, opt_state = tx.update(grads, state["opt_state"],
                                       state["params"])
        params = optax.apply_updates(state["params"], updates)
        acc = jnp.mean(jnp.argmax(logits, -1) == batch["label"])
        return ({"params": params, "batch_stats": new_stats,
                 "opt_state": opt_state, "step": state["step"] + 1},
                {"loss": loss, "accuracy": acc})

    return train_step


def make_optimizer(cfg: ResNetConfig, total_steps: int = 10000):
    schedule = optax.cosine_decay_schedule(cfg.learning_rate, total_steps)
    # Standard ResNet recipe: no L2 on BN scale/bias or biases (any 1-D
    # parameter) — decaying BN scales toward 0 degrades final accuracy.
    decay_mask = lambda params: jax.tree_util.tree_map(
        lambda p: p.ndim > 1, params)
    return optax.chain(
        optax.add_decayed_weights(cfg.weight_decay, mask=decay_mask),
        optax.sgd(schedule, momentum=cfg.momentum, nesterov=True))


def make_sharded_train_step(cfg: ResNetConfig, mesh: Mesh,
                            global_batch: int, image_size: int = 224,
                            seed: int = 0):
    """Data-parallel SPMD training over the mesh's data axes: params and
    BN stats replicated, batch sharded, gradient allreduce by GSPMD (the
    TPU-native MultiWorkerMirroredStrategy — SURVEY.md §2.8 row 2)."""
    model = ResNet(cfg, train=True)
    tx = make_optimizer(cfg)
    rng = jax.random.PRNGKey(seed)
    image_shape = (global_batch, image_size, image_size, 3)

    def init_fn(rng):
        variables = model.init(rng, jnp.zeros(image_shape, jnp.float32))
        params = variables["params"]
        return {"params": params,
                "batch_stats": variables.get("batch_stats", {}),
                "opt_state": tx.init(params),
                "step": jnp.zeros((), jnp.int32)}

    from distributed_tensorflow_tpu.cluster.topology import \
        data_axes as mesh_data_axes
    replicated = NamedSharding(mesh, P())
    data_axes = mesh_data_axes(mesh) or None
    batch_shardings = {
        "image": NamedSharding(mesh, P(data_axes)),
        "label": NamedSharding(mesh, P(data_axes)),
    }
    state_shardings = jax.tree_util.tree_map(lambda _: replicated,
                                             jax.eval_shape(init_fn, rng))

    with mesh:
        state = jax.jit(init_fn, out_shardings=state_shardings)(rng)
        step_jit = jax.jit(
            make_train_step(cfg, model, tx),
            in_shardings=(state_shardings, batch_shardings),
            out_shardings=(state_shardings, replicated),
            donate_argnums=(0,))

    def wrapped(state, batch):
        with mesh:
            return step_jit(state, batch)

    return state, wrapped


def synthetic_images(n: int, image_size: int = 224, num_classes: int = 1000,
                     seed: int = 0):
    """Deterministic synthetic imagenet-shaped data with learnable signal."""
    import numpy as np
    rng = np.random.default_rng(seed)
    images = rng.normal(size=(n, image_size, image_size, 3)).astype("float32")
    labels = (np.abs(images.mean(axis=(1, 2, 3))) * 40).astype(
        "int32") % num_classes
    return {"image": images, "label": labels}
