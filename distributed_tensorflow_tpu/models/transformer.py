"""Flagship Transformer LM — TPU-native, fully sharded (dp × fsdp × tp).

Covers the reference's Transformer-big / BERT workload configs
(BASELINE.md configs #3 and #5). Where the reference runs these through
`MultiWorkerMirroredStrategy` + NCCL allreduce (reference:
tensorflow/python/distribute/collective_all_reduce_strategy.py:57), the
TPU-native design expresses every parallelism axis as a sharding over one
`jax.sharding.Mesh` and lets GSPMD insert the ICI collectives:

- dp:   batch sharding, gradient psum (≙ NcclAllReduce)
- fsdp: parameter + optimizer-state sharding along `embed`
        (≙ ShardedVariable, reference sharded_variable.py:843 — but over
        the *embed* axis with all-gather on use, not axis-0 PS placement)
- tp:   head/mlp/vocab sharding (≙ experimental_split_to_logical_devices,
        reference tpu_strategy.py:516)
- sp:   ring attention over the sequence axis (parallel/sequence_parallel)

Design notes (TPU-first):
- bfloat16 activations/params compute, float32 master params + adamw state.
- Flash attention (ops/attention.py) for the O(S) memory hot path.
- `nn.scan` over layers: one compiled block body regardless of depth.
- `nn.remat` on each block: recompute activations in backward, trading
  MXU FLOPs for HBM (the profitable direction on TPU).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp

import optax
from flax import linen as nn
from flax.linen import partitioning as nn_partitioning
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.ops.attention import flash_attention

param_with_axes = nn_partitioning.param_with_axes
def with_sharding_constraint(x, logical_axes, mesh=None):
    """flax's logical-axis sharding constraint with the mesh passed
    EXPLICITLY. In this jax/flax pairing ``with mesh:`` does not set the
    abstract-mesh context flax checks (``jax.sharding.get_abstract_mesh``
    — only ``jax.sharding.set_mesh`` does), so without the ``mesh``
    kwarg every logical constraint silently no-ops and GSPMD sharding
    propagation is free to pick mixed activation layouts (dp on batch +
    fsdp on d_model) whose transitions force involuntary full
    rematerialization. Duplicate mesh axes within one spec (batch over
    (dp, fsdp) plus embed over fsdp) resolve to unsharded for the later
    logical axis, matching the old intended semantics."""
    return nn_partitioning.with_sharding_constraint(x, logical_axes,
                                                    mesh=mesh)

# Logical axis name -> mesh axes. "sp" shards the sequence axis of
# activations when the mesh has it (ring attention path); "expert" axes
# shard MoE expert weights/activations over "ep" (parallel/moe.py).
LOGICAL_AXIS_RULES = (
    ("batch", ("dcn", "dp", "fsdp")),
    ("seq", "sp"),
    ("embed", "fsdp"),
    ("heads", "tp"),
    ("kv", None),
    ("mlp", "tp"),
    ("vocab", "tp"),
    ("layers", None),
    ("norm", None),
    ("expert", "ep"),
    ("expert_mlp", "tp"),
    ("expert_embed", None),
)


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """The shape of latent (MLA) attention: queries through a rank
    ``q_rank`` bottleneck into heads of ``[nope_dim | rope_dim]``; keys
    and values through one shared row of ``kv_rank`` values, normed, and
    ``rope_dim`` rotary values shared by all heads, which is all a token
    leaves in the cache (``row_dim``); heads of ``v_dim`` values.
    ``scale_q`` / ``scale_kv`` multiply the normed bottlenecks by
    ``sqrt(d_model / rank)``."""
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    scale_q: bool = False
    scale_kv: bool = False

    @property
    def row_dim(self) -> int:
        return self.kv_rank + self.rope_dim

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim


@dataclasses.dataclass(frozen=True)
class ExpertLayer:
    """The shape of a layer of sparse experts and the share of it held
    here: a router over ``n_routed`` gated feed-forwards of width
    ``d_expert`` and then ``n_identity`` experts that return their input
    ("zero-computation" experts), ``top_k`` of them a token, their
    softmax scores times ``scaling`` as weights. Of the routed experts
    this rank holds ``held`` (all of them when None), the first being
    ``offset``; the identity experts have no weights and belong to the
    token's own rank."""
    n_routed: int
    top_k: int
    d_expert: int
    n_identity: int = 0
    scaling: float = 1.0
    held: int | None = None
    offset: int = 0

    def __post_init__(self):
        if self.held is None:
            object.__setattr__(self, "held", self.n_routed)
        if not 0 <= self.offset <= self.offset + self.held <= self.n_routed:
            raise ValueError(
                f"experts {self.offset}..{self.offset + self.held} held "
                f"of {self.n_routed} routed")
        if not 1 <= self.top_k <= self.n_routed + self.n_identity:
            raise ValueError(f"top_k={self.top_k} of "
                             f"{self.n_routed + self.n_identity} experts")

    @property
    def n_outputs(self) -> int:
        return self.n_routed + self.n_identity


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 1024
    n_layers: int = 12
    n_heads: int = 16
    d_ff: int = 4096
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    causal: bool = True            # False -> bidirectional encoder (BERT)
    remat: bool = True
    remat_policy: str = "nothing"  # "nothing" | "dots" (save matmul outputs)
    scan_layers: bool = True
    attention_impl: str | None = None   # None = auto (pallas on TPU)
    # Pallas kernel tile sizes; the 512/1024 defaults are from a v5e
    # block sweep — grid overhead dominates below 512 and VMEM pressure
    # wins above 1024 at head_dim 64.
    attn_block_q: int = 512
    attn_block_k: int = 1024
    learning_rate: float = 3e-4
    weight_decay: float = 0.01
    # Sequence/context parallelism: when mesh (threaded in by
    # make_sharded_train_step) has an "sp" axis > 1, attention runs as
    # ring attention over it (parallel/sequence_parallel.py).
    mesh: Any = None
    sp_impl: str = "ring"        # "ring" | "ulysses" | "striped" (causal)
    # per-step attention inside SP: "flash" | "unfused" | "interpret";
    # None = auto (flash on TPU — sequence_parallel._resolve_attn_impl)
    sp_attn_impl: str | None = None
    # Mixture-of-Experts: moe_experts > 0 replaces every block's MLP with
    # a Switch-style MoE layer (parallel/moe.py), expert-sharded over the
    # mesh's "ep" axis; the load-balancing aux loss flows to the train
    # step through the flax "losses" collection.
    moe_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Fused (chunked) cross-entropy: > 0 splits the sequence into this many
    # chunks and computes logits + CE per chunk inside a rematerialized
    # lax.scan, so the (B, S, vocab) fp32 logits tensor never materializes
    # in HBM (the memory wall that capped global batch at 8 on v5e).
    # 0 = classic full-logits path.
    loss_chunks: int = 0
    # Fused-CE implementation: "scan" = the lax.scan chunk path above;
    # "kernel" = the Pallas vocab-tiled online-logsumexp kernels
    # (ops/fused_ce.py) — logits tiles never leave VMEM. On sharded
    # meshes the kernels run per-shard under shard_map with a cross-
    # shard logsumexp merge for tp-sharded vocabs
    # (ops/fused_ce.py sharded_fused_cross_entropy); a mesh whose
    # shapes don't divide raises (use "scan" there, whose einsums
    # GSPMD partitions natively). "kernel" implies the fused loss even
    # when loss_chunks == 0.
    loss_impl: str = "scan"
    loss_block_n: int = 512
    loss_block_v: int = 1024
    # Kernel-CE lowering: "pallas" | "interpret" | "reference" | None
    # (auto: pallas on TPU, reference elsewhere). "interpret" lets CPU
    # meshes (tests, dryrun) exercise the real kernel code paths.
    loss_kernel_impl: str | None = None
    # adamw first-moment dtype: bfloat16 halves the mu read+write HBM
    # traffic of the (bandwidth-bound) optimizer update; None = fp32.
    adam_mu_dtype: Any = None
    # The shape of a looped ("universal") stack: the ``n_layers`` blocks
    # run ``passes`` times with the same weights, the final norm after
    # every pass (its output feeds the next pass), and every pass of
    # every layer attends to the keys and values that pass produced
    # (serving keeps ``n_layers * passes`` cache layers). ``post_norms``
    # adds an RMSNorm on each sub-layer's output before the residual
    # (sandwich normalisation); ``tie_embeddings=False`` gives the output
    # head a matrix of its own (``lm_head``); ``exit_gate`` gives the
    # model the parameters of a per-pass exit gate sigma(w.h + b). At an
    # exit threshold of 1 the cumulative exit probability reaches the
    # threshold only at the last pass, so every token runs every pass
    # and the last pass's logits are the output: that is what the model
    # and the serving programs compute, and the gate is not evaluated
    # (``benchmark/reference_looped.py`` returns its distribution).
    passes: int = 1
    post_norms: bool = False
    tie_embeddings: bool = True
    exit_gate: bool = False
    rope_base: float = 10000.0
    # the type the parameters are created and kept in (the compute type
    # stays ``dtype``); bfloat16 is a serving configuration's choice
    param_dtype: Any = jnp.float32
    norm_eps: float = 1e-6
    # The shape of a shortcut-connected layer of experts over latent
    # attention (serving only: ``serving/decode.py`` and
    # ``serving/experts.py`` run it, ``models/scmoe.py`` draws its
    # weights, ``TransformerLM`` refuses it). ``latent`` replaces per-head
    # K and V by one latent row a token (a :class:`LatentAttention`, or
    # its fields as a dict); ``sub_blocks`` is the number of attention +
    # feed-forward pairs a layer holds (each its own cache layer);
    # ``experts`` gives a layer one sparse layer beside them (an
    # :class:`ExpertLayer` or its fields), fed what the first pair's
    # feed-forward is fed and added at the layer's end.
    latent: Any = None
    sub_blocks: int = 1
    experts: Any = None

    def __post_init__(self):
        if self.passes < 1:
            raise ValueError(f"passes={self.passes}; a stack runs at "
                             f"least once")
        for name, kind in (("latent", LatentAttention),
                           ("experts", ExpertLayer)):
            group = getattr(self, name)
            if isinstance(group, dict):
                object.__setattr__(self, name, kind(**group))
        if self.sub_blocks < 1:
            raise ValueError(f"sub_blocks={self.sub_blocks}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def tiny(cls, **kw) -> "TransformerConfig":
        """CI-sized config: compiles in seconds on a CPU mesh."""
        defaults = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                        d_ff=128, max_seq_len=128, dtype=jnp.float32,
                        attention_impl="reference")
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def bert_base(cls, **kw) -> "TransformerConfig":
        defaults = dict(vocab_size=30522, d_model=768, n_layers=12,
                        n_heads=12, d_ff=3072, max_seq_len=512, causal=False)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def transformer_big(cls, **kw) -> "TransformerConfig":
        """≙ Transformer-big WMT (BASELINE.md config #5)."""
        defaults = dict(vocab_size=32768, d_model=1024, n_layers=12,
                        n_heads=16, d_ff=4096, max_seq_len=1024)
        defaults.update(kw)
        return cls(**defaults)


class RMSNorm(nn.Module):
    dtype: Any = jnp.bfloat16
    eps: float = 1e-6
    mesh: Any = None
    param_dtype: Any = jnp.float32
    scale_init: float = 1.0

    @nn.compact
    def __call__(self, x):
        scale = param_with_axes(
            "scale", nn.initializers.constant(self.scale_init),
            (x.shape[-1],), self.param_dtype, axes=("norm",))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps) * scale
        y = y.astype(self.dtype)
        if y.ndim == 3:
            # Anchor the activation layout: without this, GSPMD sharding
            # propagation flows the fsdp-sharded weights' D-axis sharding
            # backward onto the norm output (a mixed dp-batch/fsdp-D
            # layout), and resharding the norm INPUT into it is a
            # transition XLA can only do by replicating ("involuntary
            # full rematerialization" — one full-activation broadcast
            # per layer on a dp×fsdp mesh).
            y = with_sharding_constraint(y, ("batch", "seq", "embed"),
                                         mesh=self.mesh)
        return y


def rotary_embedding(x, *, base: float = 10000.0, seq_axis: int = -3):
    """RoPE with the sequence axis at ``seq_axis`` and head_dim last.

    ``seq_axis=-3``: the (..., seq, heads, head_dim) projection layout;
    ``seq_axis=-2``: the (batch, heads, seq, head_dim) attention-kernel
    layout — projecting straight into kernel layout lets q/k/v skip the
    (B,S,H,d)->(B,H,S,d) transposes."""
    seq, d = x.shape[seq_axis], x.shape[-1]
    with jax.named_scope("rotary"):
        pos = jnp.arange(seq, dtype=jnp.float32)
        inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32)
                                   / d))
        angles = pos[:, None] * inv_freq[None, :]          # (seq, d/2)
        bshape = [1] * x.ndim
        bshape[seq_axis], bshape[-1] = seq, d // 2
        sin = jnp.sin(angles).reshape(bshape)
        cos = jnp.cos(angles).reshape(bshape)
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              axis=-1)
        return out.astype(x.dtype)


class MultiHeadAttention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, lengths=None):
        cfg = self.cfg
        B, S, D = x.shape
        H, hd = cfg.n_heads, cfg.head_dim

        def proj(name):
            # Project DIRECTLY into the (B, H, S, hd) kernel layout —
            # the former bshk projection + transpose(0,2,1,3) pair cost
            # ~5 ms/step in pure copies (profile: 96 copy ops/step).
            kernel = param_with_axes(
                name, nn.initializers.normal(D ** -0.5), (D, H, hd),
                cfg.param_dtype, axes=("embed", "heads", "kv"))
            return jnp.einsum("bsd,dhk->bhsk", x,
                              kernel.astype(cfg.dtype))

        q = rotary_embedding(proj("query"), base=cfg.rope_base, seq_axis=-2)
        k = rotary_embedding(proj("key"), base=cfg.rope_base, seq_axis=-2)
        v = proj("value")
        mesh = cfg.mesh
        if lengths is not None:
            # Right-padded mixed-length batch (serving prefill, BERT
            # over variable-length inputs): the ONE factored mask rule
            # (ops/attention.length_valid_mask) that the KV-cache
            # incremental decode also applies — full recompute and
            # cached decode mask identically by construction. The flash
            # kernels take no per-row length, so this path runs the
            # unfused reference; serving prefill shapes are
            # latency-bound, not HBM-bound.
            from distributed_tensorflow_tpu.ops.attention import (
                mha_reference)
            o = mha_reference(q, k, v, causal=cfg.causal, lengths=lengths)
        elif (mesh is not None and "sp" in mesh.shape
                and mesh.shape["sp"] > 1):
            # Sequence-parallel path: ring attention over the sp axis
            # (reference has no SP at all — SURVEY.md §5.7).
            from distributed_tensorflow_tpu.parallel.sequence_parallel \
                import make_ring_attention
            from distributed_tensorflow_tpu.cluster.topology import \
                attention_shard_spec
            base = attention_shard_spec(mesh)
            spec = P(base[0], base[1], "sp", None)
            o = make_ring_attention(mesh, causal=cfg.causal,
                                    impl=cfg.sp_impl, spec=spec,
                                    attn_impl=cfg.sp_attn_impl,
                                    block_q=cfg.attn_block_q,
                                    block_k=cfg.attn_block_k)(q, k, v)
        elif mesh is not None and mesh.size > 1:
            # Pallas custom calls can't be partitioned by GSPMD: run the
            # kernel per-shard via shard_map over batch/head axes.
            from distributed_tensorflow_tpu.ops.attention import \
                sharded_flash_attention
            o = sharded_flash_attention(q, k, v, mesh, causal=cfg.causal,
                                        block_q=cfg.attn_block_q,
                                        block_k=cfg.attn_block_k,
                                        implementation=cfg.attention_impl)
        else:
            o = flash_attention(q, k, v, causal=cfg.causal,
                                block_q=cfg.attn_block_q,
                                block_k=cfg.attn_block_k,
                                implementation=cfg.attention_impl)
        # Named save point: the "attn" remat policy keeps this tensor so
        # the backward pass never re-runs the flash kernel forward.
        from jax.ad_checkpoint import checkpoint_name
        o = checkpoint_name(o, "attn_out")            # (B, H, S, hd)

        out_kernel = param_with_axes(
            "out", nn.initializers.normal(D ** -0.5), (H, hd, D),
            cfg.param_dtype, axes=("heads", "kv", "embed"))
        # Contract straight from kernel layout — no transpose back.
        o = jnp.einsum("bhsk,hkd->bsd", o, out_kernel.astype(cfg.dtype))
        return with_sharding_constraint(o, ("batch", "seq", "embed"),
                                        mesh=cfg.mesh)


class MLP(nn.Module):
    """SwiGLU feed-forward, tp-sharded on the hidden axis."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        D, F = cfg.d_model, cfg.d_ff
        wi = param_with_axes("wi", nn.initializers.normal(D ** -0.5),
                             (D, 2 * F), cfg.param_dtype,
                             axes=("embed", "mlp"))
        wo = param_with_axes("wo", nn.initializers.normal(F ** -0.5),
                             (F, D), cfg.param_dtype, axes=("mlp", "embed"))
        h = jnp.einsum("bsd,df->bsf", x, wi.astype(cfg.dtype))
        gate, up = jnp.split(h, 2, axis=-1)
        h = nn.silu(gate) * up
        out = jnp.einsum("bsf,fd->bsd", h, wo.astype(cfg.dtype))
        return with_sharding_constraint(out, ("batch", "seq", "embed"),
                                        mesh=cfg.mesh)


def remat_policy_for(cfg: TransformerConfig):
    """The jax.checkpoint policy named by ``cfg.remat_policy`` (shared by
    the scan-layers path and the pipeline stage body)."""
    policies = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        # Save only attention outputs: O(B·S·D) per layer, and the
        # backward never recomputes the flash kernel forward.
        "attn": jax.checkpoint_policies.save_only_these_names("attn_out"),
        # Save matmul outputs AND attention outputs: backward recomputes
        # neither. Measured SLOWER than "dots" on v5e at this model size
        # (saving attention outputs costs more bandwidth than the
        # full-sequence-block kernel recompute); kept for configs where
        # the kernel recompute dominates (longer sequences, small tiles).
        "dots_attn": jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names("attn_out")),
    }
    if cfg.remat_policy not in policies:
        raise ValueError(f"remat_policy={cfg.remat_policy!r}; "
                         f"expected one of {sorted(policies)}")
    return policies[cfg.remat_policy]


class Block(nn.Module):
    """One transformer block with a scan-compatible (carry, _) signature.

    With ``cfg.moe_experts > 0`` the dense MLP is replaced by a
    Switch-style MoE layer (parallel/moe.py) whose aux loss is sown into
    the "losses" collection — summed over layers by the train step."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, lengths=None):
        cfg = self.cfg

        def norm(name=None, scale_init=1.0):
            return RMSNorm(cfg.dtype, eps=cfg.norm_eps, mesh=cfg.mesh,
                           param_dtype=cfg.param_dtype,
                           scale_init=scale_init, name=name)

        def post(name, y):
            # sandwich normalisation: the sub-layer's output is normed
            # before it joins the residual stream. Its scale starts at
            # 1/sqrt(branches the stream takes in): the residual scaling
            # deep stacks are initialised with, carried here by the norm
            # (a normed branch has an RMS of its scale whatever the
            # projection before it). At 1 a stream of passes x layers x 2
            # unit branches is a chaotic map of its weights: two
            # roundings of one model part by whole logits on some seeds
            # (PERF.md section 6, PR 28).
            if not cfg.post_norms:
                return y
            with jax.named_scope("norm.post"):
                return norm(name, (2 * cfg.n_layers * cfg.passes) ** -0.5)(y)

        x = x + post("post_attn_norm", MultiHeadAttention(
            cfg, name="attn")(norm()(x), lengths))
        h = norm()(x)
        if cfg.moe_experts > 0:
            from distributed_tensorflow_tpu.parallel.moe import (
                MoEConfig, MoELayer)
            moe_cfg = MoEConfig(
                num_experts=cfg.moe_experts, d_model=cfg.d_model,
                d_ff=cfg.d_ff, capacity_factor=cfg.moe_capacity_factor,
                top_k=cfg.moe_top_k, aux_loss_weight=cfg.moe_aux_weight,
                dtype=cfg.dtype, mesh=cfg.mesh)
            out, aux = MoELayer(moe_cfg, name="moe")(h)
            self.sow("losses", "moe_aux", aux,
                     reduce_fn=lambda a, b: a + b, init_fn=lambda: 0.0)
            x = x + post("post_mlp_norm", out)
        else:
            x = x + post("post_mlp_norm", MLP(cfg, name="mlp")(h))
        return x, None


class TransformerLM(nn.Module):
    """Decoder-only LM (cfg.causal=True) or bidirectional encoder."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, return_hidden=False, lengths=None):
        """``lengths`` (B,) marks a right-padded mixed-length batch:
        every layer's attention masks padded keys via the factored
        ``ops.attention.length_valid_mask`` rule (the full-sequence
        recompute side of the serving KV-cache correctness contract).
        None (the default) is the historical full-sequence behavior."""
        cfg = self.cfg
        for name, lacks in (
                ("latent", "latent (MLA) attention"),
                ("experts", "a layer of sparse experts held by share")):
            if getattr(cfg, name) is not None:
                raise NotImplementedError(
                    f"TransformerLM has no {lacks} (cfg.{name}): the "
                    f"training path does not run this shape; it is "
                    f"served by serving.InferenceEngine over weights "
                    f"from models.scmoe.init_params")
        if cfg.sub_blocks != 1:
            raise NotImplementedError(
                f"TransformerLM has no layer of {cfg.sub_blocks} "
                f"attention + feed-forward pairs (cfg.sub_blocks)")
        embed = param_with_axes(
            "embed", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.d_model), cfg.param_dtype,
            axes=("vocab", "embed"))
        # Unshard the table's d_model axis (fsdp) BEFORE the lookup: a
        # gather from the fsdp-sharded table inherits D-over-fsdp output
        # sharding, and the transition from that to the batch-sharded
        # activation layout is one GSPMD cannot do efficiently (it
        # replicates — "involuntary full rematerialization"). Gathering
        # from a D-unsharded table makes the output inherit the token
        # batch sharding directly; the explicit all-gather this forces
        # is the same V×D traffic, minus the bad transition.
        emb_c = with_sharding_constraint(embed.astype(cfg.dtype),
                                         ("vocab", None), mesh=cfg.mesh)
        x = emb_c[tokens]
        x = with_sharding_constraint(x, ("batch", "seq", "embed"),
                                     mesh=cfg.mesh)

        block = Block
        if cfg.remat:
            policy = remat_policy_for(cfg)
            block = nn_partitioning.remat(
                block, policy=policy,
                prevent_cse=not cfg.scan_layers)
        if cfg.scan_layers:
            variable_axes = {"params": 0}
            if cfg.moe_experts > 0:
                variable_axes["losses"] = 0     # per-layer aux stack
            stack = nn_partitioning.scan_with_axes(
                block,
                variable_axes=variable_axes,
                split_rngs={"params": True},
                in_axes=nn.broadcast,
                length=cfg.n_layers,
                axis_name="layers",
            )(cfg, name="layers")
            layers = [lambda x: stack(x, lengths)[0]]
        else:
            layers = [
                (lambda x, b=block(cfg, name=f"layer_{i}"): b(x, lengths)[0])
                for i in range(cfg.n_layers)]
        final_norm = RMSNorm(cfg.dtype, eps=cfg.norm_eps, mesh=cfg.mesh,
                             param_dtype=cfg.param_dtype, name="final_norm")
        # a looped stack: the same modules (so the same weights) at every
        # pass, the final norm after each, its output the next pass's input
        for _ in range(cfg.passes):
            for layer in layers:
                x = layer(x)
            x = final_norm(x)

        head = embed
        if not cfg.tie_embeddings:
            head = param_with_axes(
                "lm_head", nn.initializers.normal(0.02),
                (cfg.vocab_size, cfg.d_model), cfg.param_dtype,
                axes=("vocab", "embed"))
        if cfg.exit_gate:
            # sigma(w.h + b) after each pass; not evaluated at the exit
            # threshold of 1 this model computes (see TransformerConfig)
            param_with_axes("exit_gate_kernel", nn.initializers.normal(0.02),
                            (cfg.d_model,), cfg.param_dtype,
                            axes=("embed",))
            param_with_axes("exit_gate_bias", nn.initializers.zeros, (),
                            cfg.param_dtype, axes=())
        if return_hidden:
            # Fused-loss path: the caller computes chunked logits + CE
            # against the tied embedding itself (fused_next_token_loss).
            return x
        logits = jnp.einsum("bsd,vd->bsv", x, head.astype(cfg.dtype))
        return logits.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Training step
# ---------------------------------------------------------------------------

def next_token_loss(logits, tokens):
    """Shifted next-token cross-entropy (ignores the final position)."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    return losses.mean()


def fused_next_token_loss(hidden, embed, tokens, *, num_chunks,
                          compute_dtype=jnp.bfloat16):
    """Chunked next-token CE over the tied embedding — the fused loss.

    Equivalent to ``next_token_loss(einsum(hidden, embed), tokens)`` but
    the (B, S, vocab) fp32 logits tensor never exists: each of
    ``num_chunks`` sequence chunks computes its (B, S/num_chunks, vocab)
    logits inside a rematerialized ``lax.scan`` body, reduces them to a
    partial CE sum, and the backward recomputes one chunk's logits at a
    time. This removes the dominant HBM peak of the training step (for
    transformer_big at batch 16 / seq 1024 / vocab 32k the logits +
    their cotangent alone are 4 GiB fp32).

    ≙ the reference's fused softmax-CE op
    (TF/python/ops/nn_ops.py softmax_cross_entropy_with_logits lowering
    to a fused XLA reduction) — extended to also fuse away the vocab
    projection, which the reference never needed because GPU HBM held
    its logits.
    """
    B, S, D = hidden.shape
    if S % num_chunks:
        raise ValueError(f"seq len {S} not divisible by "
                         f"loss num_chunks={num_chunks}")
    C = S // num_chunks
    targets, mask = _shifted_targets_and_mask(tokens)
    emb = embed.astype(compute_dtype)
    xs = (hidden.reshape(B, num_chunks, C, D).swapaxes(0, 1),
          targets.reshape(B, num_chunks, C).swapaxes(0, 1),
          mask.reshape(B, num_chunks, C).swapaxes(0, 1))

    def chunk_body(carry, xtm):
        xc, tc, mc = xtm
        logits = jnp.einsum("bcd,vd->bcv", xc.astype(compute_dtype), emb)
        logits = logits.astype(jnp.float32)
        ls = optax.softmax_cross_entropy_with_integer_labels(logits, tc)
        return carry + jnp.sum(ls * mc), None

    total, _ = jax.lax.scan(
        jax.checkpoint(chunk_body,
                       policy=jax.checkpoint_policies.nothing_saveable),
        jnp.zeros((), jnp.float32), xs)
    return total / (B * (S - 1))


def _shifted_targets_and_mask(tokens):
    """Next-token shift shared by every fused-loss path: position t
    predicts token t+1; the final position has no target (pad target 0,
    mask 0) — identical semantics to ``next_token_loss``."""
    B, S = tokens.shape
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)
    mask = jnp.concatenate(
        [jnp.ones((B, S - 1), jnp.float32), jnp.zeros((B, 1), jnp.float32)],
        axis=1)
    return targets, mask


def kernel_next_token_loss(hidden, embed, tokens, *,
                           compute_dtype=jnp.bfloat16,
                           block_n: int = 512, block_v: int = 1024,
                           implementation: str | None = None,
                           mesh=None):
    """Shifted next-token CE via the Pallas fused-CE kernels
    (ops/fused_ce.py) — same semantics as ``fused_next_token_loss`` /
    ``next_token_loss`` but the (B, S, vocab) logits tensor never exists
    even per-chunk: vocab tiles stream through VMEM.

    With a sharded ``mesh`` the kernels run per-shard under shard_map
    (tokens over dcn/dp/fsdp/sp, vocab over tp with a cross-shard
    logsumexp merge — ops/fused_ce.py sharded_fused_cross_entropy).
    The next-token SHIFT happens here, outside the shard_map, so GSPMD
    handles the sp-boundary halo exchange of the shifted targets."""
    B, S, D = hidden.shape
    targets, mask = _shifted_targets_and_mask(tokens)
    if mesh is not None and mesh.size > 1:
        from distributed_tensorflow_tpu.ops.fused_ce import (
            sharded_fused_cross_entropy)
        losses = sharded_fused_cross_entropy(
            hidden.astype(compute_dtype), embed.astype(compute_dtype),
            targets, mesh, block_n=block_n, block_v=block_v,
            implementation=implementation)
        return jnp.sum(losses * mask) / (B * (S - 1))
    from distributed_tensorflow_tpu.ops.fused_ce import fused_cross_entropy
    losses = fused_cross_entropy(
        hidden.reshape(B * S, D).astype(compute_dtype),
        embed.astype(compute_dtype), targets.reshape(B * S),
        block_n=block_n, block_v=block_v, implementation=implementation)
    return jnp.sum(losses * mask.reshape(B * S)) / (B * (S - 1))


def make_optimizer(cfg: TransformerConfig):
    return optax.adamw(cfg.learning_rate, weight_decay=cfg.weight_decay,
                       mu_dtype=cfg.adam_mu_dtype)


def make_loss_fn(cfg: TransformerConfig, model: TransformerLM):
    """loss_fn(params, tokens) -> scalar for ``cfg``/``model`` — the
    objective shared by the GSPMD step, the bucketed data-parallel step,
    and the pipeline schedules. With MoE the per-layer load-balancing aux
    losses (flax "losses" collection) are summed in (≙ Switch
    Transformer training)."""

    if cfg.loss_impl not in ("scan", "kernel"):
        raise ValueError(f"loss_impl={cfg.loss_impl!r}; expected "
                         f"'scan' or 'kernel'")
    # The kernel CE path runs everywhere: plain on a single chip,
    # per-shard under shard_map on sharded meshes (tokens over
    # dcn/dp/fsdp/sp, vocab over tp with a cross-shard logsumexp merge
    # — ops/fused_ce.py sharded_fused_cross_entropy). A mesh whose
    # shard counts don't divide the batch/seq/vocab shapes is an error
    # when the kernel was asked for by name: the step builds the loss
    # it names or raises, it never quietly becomes the scan path.
    # loss_impl="kernel" implies a FUSED loss even when loss_chunks == 0.
    use_kernel = cfg.loss_impl == "kernel"
    fused = cfg.loss_chunks > 0 or use_kernel

    def _check_kernel_mesh(B, S):
        mesh = cfg.mesh
        if mesh is None or mesh.size == 1:
            return
        n_batch = 1
        for a in ("dcn", "dp", "fsdp"):
            if a in mesh.shape:
                n_batch *= mesh.shape[a]
        sp = mesh.shape.get("sp", 1)
        tp = mesh.shape.get("tp", 1)
        if B % n_batch or S % sp or cfg.vocab_size % tp:
            raise ValueError(
                f"loss_impl='kernel' on mesh {dict(mesh.shape)}: batch "
                f"{B} / seq {S} / vocab {cfg.vocab_size} must divide by "
                f"{n_batch} data shards / sp={sp} / tp={tp}; use "
                f"loss_impl='scan' for shapes the kernel cannot shard")

    @jax.named_scope("loss")
    def objective(out, params, tokens):
        if use_kernel:
            _check_kernel_mesh(*out.shape[:2])
            return kernel_next_token_loss(
                out, params["embed"], tokens, compute_dtype=cfg.dtype,
                block_n=cfg.loss_block_n, block_v=cfg.loss_block_v,
                implementation=cfg.loss_kernel_impl, mesh=cfg.mesh)
        if fused:
            return fused_next_token_loss(
                out, params["embed"], tokens,
                num_chunks=cfg.loss_chunks, compute_dtype=cfg.dtype)
        return next_token_loss(out, tokens)

    def loss_fn(params, tokens):
        if cfg.moe_experts > 0:
            out, out_vars = model.apply({"params": params}, tokens, fused,
                                        mutable=["losses"])
            aux = sum(jnp.sum(leaf) for leaf in
                      jax.tree_util.tree_leaves(out_vars.get("losses", {})))
            return objective(out, params, tokens) + aux
        out = model.apply({"params": params}, tokens, fused)
        return objective(out, params, tokens)

    return loss_fn


def make_train_step(cfg: TransformerConfig, model: TransformerLM, tx):
    """Functional (state, batch) -> (state, metrics) SPMD step built on
    :func:`make_loss_fn`."""
    loss_fn = make_loss_fn(cfg, model)

    def train_step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"],
                                                  batch["tokens"])
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state["opt_state"],
                                           state["params"])
            params = optax.apply_updates(state["params"], updates)
        return ({"params": params, "opt_state": opt_state,
                 "step": state["step"] + 1},
                {"loss": loss})

    return train_step


def mesh_axis_rules(mesh: Mesh, rules: Sequence = LOGICAL_AXIS_RULES):
    """Restrict logical-axis rules to the axes this mesh actually has, so
    the same model code runs on any mesh (dp-only, dp×tp, dp×fsdp×tp, …)."""
    out = []
    for logical, target in rules:
        if target is None:
            out.append((logical, None))
        elif isinstance(target, tuple):
            kept = tuple(a for a in target if a in mesh.shape)
            out.append((logical, kept if kept else None))
        else:
            out.append((logical, target if target in mesh.shape else None))
    return out


def _shard_like(tree, params_treedef, param_shardings, replicated):
    """Give every sub-tree that structurally matches ``params`` (mu, nu in
    adamw) the param shardings; replicate everything else."""
    def per_node(node):
        if jax.tree_util.tree_structure(node) == params_treedef:
            return param_shardings
        if hasattr(node, "_fields"):          # optax NamedTuple state
            return type(node)(*[per_node(getattr(node, f))
                                for f in node._fields])
        if isinstance(node, tuple):
            return tuple(per_node(x) for x in node)
        return jax.tree_util.tree_map(lambda _: replicated, node)
    return per_node(tree)


def state_shardings_for(model, tx, mesh: Mesh, example_tokens,
                        rules: Sequence | None = None):
    """Derive NamedShardings for the full train state from the model's
    logical axis metadata (the flax ``params_axes`` collection)."""
    rules = mesh_axis_rules(mesh) if rules is None else rules
    rng = jax.random.PRNGKey(0)
    with nn_partitioning.axis_rules(list(rules)):
        var_shapes = jax.eval_shape(
            lambda r: model.init(r, example_tokens), rng)
        logical_specs = nn_partitioning.get_axis_names(
            var_shapes["params_axes"])
        mesh_specs = nn_partitioning.logical_to_mesh(logical_specs)
    param_shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), mesh_specs,
        is_leaf=lambda x: isinstance(x, P))
    if hasattr(param_shardings, "unfreeze"):
        param_shardings = param_shardings.unfreeze()

    params_treedef = jax.tree_util.tree_structure(var_shapes["params"])
    replicated = NamedSharding(mesh, P())
    opt_shapes = jax.eval_shape(tx.init, var_shapes["params"])
    opt_shardings = _shard_like(opt_shapes, params_treedef,
                                param_shardings, replicated)
    return {"params": param_shardings, "opt_state": opt_shardings,
            "step": replicated}


def make_sharded_train_step(cfg: TransformerConfig, mesh: Mesh,
                            global_batch: int, seed: int = 0,
                            step_factory=None, grad_sync: str = "auto",
                            zero: int = 0):
    """Initialize sharded state and return (state, jitted step_fn).

    The returned step consumes batches of shape (global_batch, seq);
    inputs are sharded ("batch" over dcn×dp×fsdp, "seq" over sp if
    present) and all gradient/weight collectives are inserted by GSPMD
    over the mesh — the TPU-native replacement for the reference's
    CrossDeviceOps.batch_reduce (cross_device_ops.py:871).

    ``grad_sync`` selects the gradient-reduction schedule:

    - ``"bucketed"`` — explicit shard_map step with reverse-layer-order
      bucketed gradient allreduce (collectives.GradientBucketer): each
      bucket's psum launches as soon as backprop has produced its
      gradients, overlapping ICI/DCN reduction with the remaining
      backward pass. Pure data-parallel meshes only (axes ⊆ {dcn, dp}).
      On a hybrid dcn×dp mesh each bucket takes the hierarchical path,
      the DCN hop overlapping the next bucket's ICI phases.
    - ``"gspmd"`` — one compiler-scheduled sync (the pre-ISSUE-6 path).
    - ``"auto"`` (default) — "bucketed" on >1-device pure-dp meshes
      (no MoE, default step), "gspmd" otherwise.

    ``step_factory(cfg, model, tx)`` lets variants (BERT MLM) swap the
    per-step loss while reusing all sharding/jit wiring.

    ``zero`` selects ZeRO optimizer-state sharding over the dp axis
    (parallel/zero.py; params stay replicated over dp, Adam slots exist
    only for each rank's 1/N bucket slice — bit-identical to replicated
    Adam). Level 1 all-reduces gradients as usual; level 2
    reduce-scatters them so the full gradient buffer never materializes
    either. On meshes that are not exactly ("dp",), gradient sync stays
    with GSPMD and levels 1/2 behave identically (slots sharded, grads
    compiler-managed).
    """
    from distributed_tensorflow_tpu.cluster.topology import \
        data_axes as mesh_data_axes
    pure_dp = (set(mesh.shape) <= {"dcn", "dp"} and mesh.size > 1
               and cfg.moe_experts == 0 and step_factory is None)
    if grad_sync not in ("auto", "bucketed", "gspmd"):
        raise ValueError(f"grad_sync={grad_sync!r}; expected 'auto', "
                         f"'bucketed' or 'gspmd'")
    if zero not in (0, 1, 2):
        raise ValueError(f"zero={zero!r}; expected 0, 1, or 2")
    if zero:
        if step_factory is not None:
            raise ValueError("zero= is not supported with step_factory")
        if cfg.moe_experts > 0:
            raise NotImplementedError("zero= with MoE is not supported")
        if grad_sync != "auto":
            raise ValueError("zero= owns the gradient sync schedule; "
                             "leave grad_sync='auto'")
        if tuple(mesh.axis_names) == ("dp",):
            return _make_zero_dp_train_step(cfg, mesh, global_batch,
                                            seed, level=zero)
        return _make_zero_gspmd_train_step(cfg, mesh, global_batch,
                                           seed, level=zero)
    if grad_sync == "bucketed" and not pure_dp:
        raise ValueError(
            f"grad_sync='bucketed' needs a pure data-parallel mesh "
            f"(axes ⊆ {{dcn, dp}}, >1 device, no MoE); got "
            f"{dict(mesh.shape)}")
    if pure_dp and grad_sync in ("auto", "bucketed"):
        return _make_bucketed_dp_train_step(cfg, mesh, global_batch, seed)
    if cfg.mesh is None:
        cfg = dataclasses.replace(cfg, mesh=mesh)
    model = TransformerLM(cfg)
    tx = make_optimizer(cfg)
    rng = jax.random.PRNGKey(seed)
    tokens_shape = jnp.zeros((global_batch, cfg.max_seq_len), jnp.int32)

    state_shardings = state_shardings_for(model, tx, mesh, tokens_shape)

    def init_fn(rng):
        params = model.init(rng, tokens_shape)["params"]
        return {"params": params, "opt_state": tx.init(params),
                "step": jnp.zeros((), jnp.int32)}

    replicated = NamedSharding(mesh, P())
    data_axes = mesh_data_axes(mesh)
    seq_axis = "sp" if "sp" in mesh.shape else None
    batch_shardings = {"tokens": NamedSharding(
        mesh, P(data_axes if data_axes else None, seq_axis))}

    rules = mesh_axis_rules(mesh)
    step = (step_factory or make_train_step)(cfg, model, tx)
    with mesh, nn_partitioning.axis_rules(rules):
        state = jax.jit(init_fn, out_shardings=state_shardings)(rng)
        step_jit = jax.jit(
            step,
            in_shardings=(state_shardings, batch_shardings),
            out_shardings=(state_shardings, replicated),
            donate_argnums=(0,))

    def wrapped_step(state, batch):
        with mesh, nn_partitioning.axis_rules(rules):
            return step_jit(state, batch)

    return state, wrapped_step


def _make_bucketed_dp_train_step(cfg: TransformerConfig, mesh: Mesh,
                                 global_batch: int, seed: int = 0):
    """Pure data-parallel train step with explicit comm/compute overlap:
    the whole step runs under shard_map, per-device grads are reduced by
    collectives.GradientBucketer in reverse layer order (last-layer
    buckets launch while earlier layers still differentiate), and the
    replicated optimizer applies locally. Parameters are replicated on a
    pure-dp mesh, so state/step signatures match the GSPMD path
    (state replicated, batch sharded over dcn×dp)."""
    from distributed_tensorflow_tpu.cluster.topology import \
        data_axes as mesh_data_axes
    from distributed_tensorflow_tpu.parallel.collectives import (
        GradientBucketer, ReduceOp)
    from distributed_tensorflow_tpu.parallel.collectives import (
        all_reduce as collectives_all_reduce)

    data_axes = mesh_data_axes(mesh)
    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]
    if global_batch % n_shards:
        raise ValueError(f"global_batch={global_batch} not divisible by "
                         f"{n_shards} data shards of {dict(mesh.shape)}")
    # inside shard_map everything is per-shard: plain local kernels, no
    # nested sharding machinery (same convention as the pipeline path)
    cfg_local = dataclasses.replace(cfg, mesh=None)
    model = TransformerLM(cfg_local)
    tx = make_optimizer(cfg)
    loss_fn = make_loss_fn(cfg_local, model)

    outer = inner = None
    if len(data_axes) == 2 and all(mesh.shape[a] > 1 for a in data_axes):
        outer, inner = data_axes           # ("dcn", "dp") hybrid
    bucketer = GradientBucketer(data_axes, outer_axis=outer,
                                inner_axis=inner)

    rng = jax.random.PRNGKey(seed)
    tokens_shape = jnp.zeros((global_batch, cfg.max_seq_len), jnp.int32)
    replicated = NamedSharding(mesh, P())

    def init_fn(rng):
        params = model.init(rng, tokens_shape)["params"]
        return {"params": params, "opt_state": tx.init(params),
                "step": jnp.zeros((), jnp.int32)}

    state_shardings = jax.tree_util.tree_map(
        lambda _: replicated, jax.eval_shape(init_fn, rng))
    state = jax.jit(init_fn, out_shardings=state_shardings)(rng)

    def spmd_step(state, batch):
        # local mean loss; the global objective is the mean over shards,
        # so grads sync as a bucketed MEAN allreduce
        loss, grads = jax.value_and_grad(loss_fn)(state["params"],
                                                  batch["tokens"])
        grads = bucketer.all_reduce(grads, op=ReduceOp.MEAN)
        loss = collectives_all_reduce(loss, data_axes, ReduceOp.MEAN)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state["opt_state"],
                                           state["params"])
            params = optax.apply_updates(state["params"], updates)
        return ({"params": params, "opt_state": opt_state,
                 "step": state["step"] + 1},
                {"loss": loss})

    batch_spec = {"tokens": P(data_axes)}
    state_spec = jax.tree_util.tree_map(lambda _: P(), state)
    shard_step = jax.shard_map(
        spmd_step, mesh=mesh,
        in_specs=(state_spec, batch_spec),
        out_specs=(state_spec, P()),
        check_vma=False)
    batch_shardings = {"tokens": NamedSharding(mesh, P(data_axes))}
    step_jit = jax.jit(
        shard_step,
        in_shardings=(state_shardings, batch_shardings),
        out_shardings=(state_shardings, replicated),
        donate_argnums=(0,))

    def wrapped_step(state, batch):
        with mesh:
            return step_jit(state, batch)

    return state, wrapped_step


def _make_zero_dp_train_step(cfg: TransformerConfig, mesh: Mesh,
                             global_batch: int, seed: int = 0,
                             *, level: int = 1):
    """Pure data-parallel train step with ZeRO-sharded optimizer state
    (parallel/zero.py). Like :func:`_make_bucketed_dp_train_step` the
    whole step runs under shard_map with replicated params, but Adam's
    mu/nu exist only as each rank's 1/N slice of the packed parameter
    buckets. Level 1 syncs gradients with the same bucketed MEAN
    allreduce as the replicated path (bit-identical grads); level 2
    reduce-scatters the same packed buckets instead, so each rank only
    materializes its gradient shard. After the sliced update an
    all-gather over dp rebuilds the parameters — bit-identical to
    replicated Adam (tests/test_zero.py)."""
    from distributed_tensorflow_tpu import telemetry as _telemetry
    from distributed_tensorflow_tpu.parallel.collectives import (
        GradientBucketer, ReduceOp)
    from distributed_tensorflow_tpu.parallel.collectives import (
        all_reduce as collectives_all_reduce)
    from distributed_tensorflow_tpu.parallel.zero import (
        ZeroPartition, zero_opt_state)

    if tuple(mesh.axis_names) != ("dp",):
        raise ValueError(f"ZeRO explicit dp path needs a ('dp',) mesh, "
                         f"got {tuple(mesh.axis_names)}")
    n_shards = mesh.size
    if global_batch % n_shards:
        raise ValueError(f"global_batch={global_batch} not divisible by "
                         f"dp={n_shards}")
    cfg_local = dataclasses.replace(cfg, mesh=None)
    model = TransformerLM(cfg_local)
    tx = make_optimizer(cfg)
    loss_fn = make_loss_fn(cfg_local, model)
    bucketer = GradientBucketer(("dp",))

    rng = jax.random.PRNGKey(seed)
    tokens_shape = jnp.zeros((global_batch, cfg.max_seq_len), jnp.int32)
    replicated = NamedSharding(mesh, P())

    def init_params(rng):
        return model.init(rng, tokens_shape)["params"]

    params_abstract = jax.eval_shape(init_params, rng)
    param_shardings = jax.tree_util.tree_map(
        lambda _: replicated, params_abstract)
    params = jax.jit(init_params, out_shardings=param_shardings)(rng)

    leaves_abs, _ = jax.tree_util.tree_flatten(params_abstract)
    # same bucket plan as the bucketer's gradient sync, so the level-2
    # reduce-scatter runs over the very buffers level 1 would pmean
    partition = ZeroPartition(leaves_abs, n_shards)
    opt_state, opt_shardings, opt_specs = zero_opt_state(
        tx, partition, mesh, axes=("dp",))
    _telemetry.event("zero.partition", axis="dp", level=int(level),
                     **partition.summary())

    state = {"params": params, "opt_state": opt_state,
             "step": jnp.zeros((), jnp.int32)}
    state_shardings = {"params": param_shardings,
                       "opt_state": opt_shardings, "step": replicated}
    state_spec = {"params": jax.tree_util.tree_map(
                      lambda _: P(), params_abstract),
                  "opt_state": opt_specs, "step": P()}

    def spmd_step(state, batch):
        params = state["params"]
        loss, grads = jax.value_and_grad(loss_fn)(params, batch["tokens"])
        loss = collectives_all_reduce(loss, ("dp",), ReduceOp.MEAN)
        rank = jax.lax.axis_index("dp")
        if level == 1:
            grads = bucketer.all_reduce(grads, op=ReduceOp.MEAN)
            g_shards = partition.shard(
                partition.pack(jax.tree_util.tree_leaves(grads)), rank)
        else:
            g_shards = partition.reduce_scatter_mean(
                jax.tree_util.tree_leaves(grads), "dp")
        pl, td = jax.tree_util.tree_flatten(params)
        p_shards = partition.shard(partition.pack(pl), rank)
        updates, new_opt = tx.update(g_shards, state["opt_state"],
                                     p_shards)
        new_shards = optax.apply_updates(p_shards, updates)
        flats = partition.all_gather_flats(new_shards, "dp")
        new_params = jax.tree_util.tree_unflatten(
            td, partition.unpack(flats))
        return ({"params": new_params, "opt_state": new_opt,
                 "step": state["step"] + 1},
                {"loss": loss})

    batch_spec = {"tokens": P("dp")}
    shard_step = jax.shard_map(
        spmd_step, mesh=mesh,
        in_specs=(state_spec, batch_spec),
        out_specs=(state_spec, P()),
        check_vma=False)
    batch_shardings = {"tokens": NamedSharding(mesh, P("dp"))}
    step_jit = jax.jit(
        shard_step,
        in_shardings=(state_shardings, batch_shardings),
        out_shardings=(state_shardings, replicated),
        donate_argnums=(0,))

    def wrapped_step(state, batch):
        with mesh:
            return step_jit(state, batch)

    return state, wrapped_step


def _make_zero_gspmd_train_step(cfg: TransformerConfig, mesh: Mesh,
                                global_batch: int, seed: int = 0,
                                *, level: int = 1):
    """ZeRO optimizer-state sharding on a general mesh (dp×tp, single
    device, dcn hybrids) as a split program: the gradient computation
    stays a GSPMD jit exactly like the replicated path (so grads are
    bit-identical to it), and the optimizer update runs as a nested
    shard_map (parallel/zero.make_zero_update) that slices each dp
    rank's bucket shard of the mesh-local parameter blocks, updates it,
    and all-gathers over dp alone. Gradient sync is compiler-managed
    here, so levels 1 and 2 both shard only the slots."""
    from distributed_tensorflow_tpu.cluster.topology import \
        data_axes as mesh_data_axes
    from distributed_tensorflow_tpu.parallel.zero import make_zero_update

    del level  # grads are GSPMD-synced: levels differ only on pure dp
    if cfg.mesh is None:
        cfg = dataclasses.replace(cfg, mesh=mesh)
    model = TransformerLM(cfg)
    tx = make_optimizer(cfg)
    rng = jax.random.PRNGKey(seed)
    tokens_shape = jnp.zeros((global_batch, cfg.max_seq_len), jnp.int32)

    shardings = state_shardings_for(model, tx, mesh, tokens_shape)
    param_shardings = shardings["params"]
    param_specs = jax.tree_util.tree_map(
        lambda ns: ns.spec, param_shardings,
        is_leaf=lambda x: isinstance(x, NamedSharding))
    replicated = NamedSharding(mesh, P())
    rules = mesh_axis_rules(mesh)

    def init_params(rng):
        return model.init(rng, tokens_shape)["params"]

    with mesh, nn_partitioning.axis_rules(rules):
        params_abstract = jax.eval_shape(init_params, rng)
        params = jax.jit(init_params,
                         out_shardings=param_shardings)(rng)

    opt_state, opt_shardings, zero_update = make_zero_update(
        tx, mesh, param_specs, params_abstract, axis_name="dp")
    state = {"params": params, "opt_state": opt_state,
             "step": jnp.zeros((), jnp.int32)}
    state_shardings = {"params": param_shardings,
                       "opt_state": opt_shardings, "step": replicated}

    loss_fn = make_loss_fn(cfg, model)
    data_axes = mesh_data_axes(mesh)
    seq_axis = "sp" if "sp" in mesh.shape else None
    batch_shardings = {"tokens": NamedSharding(
        mesh, P(data_axes if data_axes else None, seq_axis))}

    def train_step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"],
                                                  batch["tokens"])
        new_params, new_opt = zero_update(state["params"], grads,
                                          state["opt_state"])
        return ({"params": new_params, "opt_state": new_opt,
                 "step": state["step"] + 1},
                {"loss": loss})

    with mesh, nn_partitioning.axis_rules(rules):
        step_jit = jax.jit(
            train_step,
            in_shardings=(state_shardings, batch_shardings),
            out_shardings=(state_shardings, replicated),
            donate_argnums=(0,))

    def wrapped_step(state, batch):
        with mesh, nn_partitioning.axis_rules(rules):
            return step_jit(state, batch)

    return state, wrapped_step


def make_pipelined_train_step(cfg: TransformerConfig, mesh: Mesh,
                              global_batch: int, num_microbatches: int,
                              seed: int = 0, schedule: str = "gpipe",
                              interleave: int = 2, zero: int = 0,
                              offload_activations=False):
    """Pipeline parallelism for the flagship transformer over a dp×pp
    mesh (parallel/pipeline.py; the reference has NO pipeline
    parallelism — SURVEY.md §2.8 row PP). ``schedule`` picks "gpipe"
    (forward pipeline + autodiff reverse; bubble (S-1)/(M+S-1),
    activation memory O(M)), "1f1b" (interleaved
    one-forward-one-backward with per-stage rematerialization; bubble
    2(S-1)/(M+2(S-1)) in the lockstep realization, activation memory
    O(S) — see parallel/pipeline.py), or "interleaved" (Megatron-style
    virtual stages: each pp rank holds ``interleave`` non-adjacent
    layer chunks, bubble (vW+W-2)/(Mv+vW+W-2) — below plain 1F1B for
    v>=2). All schedules compute the same objective; 1F1B and
    interleaved are loss-parity-tested against GPipe.

    - The scan-over-layers parameter stack (L, ...) regroups to
      (pp, L/pp, ...) with the stage axis sharded over "pp": each device
      holds exactly its stage's layers.
    - Microbatches flow through stages via ppermute inside a lax.scan
      (pipeline_apply); autodiff through it yields the reverse-schedule
      backward pipeline, with gradient accumulation over microbatches
      falling out of the loss mean.
    - Embedding + final norm + logits run as plain GSPMD ops outside the
      shard_map (batch sharded over dp, replicated over pp).

    ``offload_activations`` (1F1B only) re-realizes the schedule as a
    host-driven cycle loop whose per-stage activation stash spills to
    HOST memory between a microbatch's forward and backward
    (parallel/offload.py): device activation residency drops from
    O(min(M, 2S-1)) microbatches per rank to O(1). ``True`` spills
    (async device->host copies through the ``offload.spill`` chaos
    fault site); ``"device"`` runs the same host-driven loop with the
    stash kept as device arrays — the two are bit-identical end to end
    (the spill itself changes nothing), and vs the fused single-jit
    schedule losses are bit-identical with params agreeing to float
    tolerance (cross-program fusion artifact, see parallel/offload.py).

    Returns (state, step_fn) like make_sharded_train_step.
    """
    from distributed_tensorflow_tpu.parallel.pipeline import (
        make_1f1b_fn, make_interleaved_1f1b_fn, make_pipelined_fn)

    if schedule not in ("gpipe", "1f1b", "interleaved"):
        raise ValueError(f"schedule={schedule!r}; expected 'gpipe', "
                         f"'1f1b', or 'interleaved'")
    if offload_activations not in (False, True, "device"):
        raise ValueError(f"offload_activations={offload_activations!r}; "
                         f"expected False, True, or 'device'")
    if offload_activations and schedule != "1f1b":
        raise ValueError(
            "offload_activations requires schedule='1f1b': GPipe keeps "
            "O(M) activations alive inside autodiff (nothing discrete "
            "to spill) and the interleaved stash ring is not yet "
            "host-realized")
    if not cfg.scan_layers:
        raise ValueError("pipeline path requires scan_layers=True")
    if cfg.moe_experts > 0:
        raise NotImplementedError(
            "MoE under pipeline parallelism is not supported yet: the "
            "aux-loss 'losses' collection cannot escape the shard_map "
            "stage body — use make_sharded_train_step on a dp×ep mesh")
    n_stages = mesh.shape.get("pp", 1)
    n_chunks = int(interleave) if schedule == "interleaved" else 1
    if n_chunks < 1:
        raise ValueError(f"interleave must be >= 1, got {interleave}")
    if cfg.n_layers % (n_stages * n_chunks):
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by "
                         f"pp*interleave={n_stages * n_chunks}")
    if global_batch % num_microbatches:
        raise ValueError(f"global_batch={global_batch} not divisible by "
                         f"num_microbatches={num_microbatches}")
    mb = global_batch // num_microbatches
    n_dp = mesh.shape.get("dp", 1)
    if schedule in ("1f1b", "interleaved") and mb % n_dp:
        # these schedules run the microbatch dim through shard_map,
        # which needs exact divisibility (GPipe's GSPMD constraint pads)
        raise ValueError(
            f"schedule={schedule!r} needs the microbatch size "
            f"(global_batch/num_microbatches = {mb}) divisible by "
            f"dp={n_dp}; raise global_batch or lower num_microbatches")
    if schedule == "interleaved" and num_microbatches % n_stages:
        raise ValueError(
            f"schedule='interleaved' needs num_microbatches "
            f"({num_microbatches}) divisible by pp={n_stages} "
            f"(microbatches flow in groups of pp per chunk)")
    per_stage = cfg.n_layers // (n_stages * n_chunks)
    # One pipeline.schedule event per built step: the compiled schedule
    # is a single fused program, so the trace assembler renders its
    # analytic per-stage timeline (pipeline.schedule_spans) from this
    # record next to the measured step spans.
    from distributed_tensorflow_tpu import telemetry as _telemetry
    from distributed_tensorflow_tpu.parallel.pipeline import (
        bubble_fraction as _bubble)
    _telemetry.event("pipeline.schedule", schedule=schedule,
                     n_stages=int(n_stages),
                     n_micro=int(num_microbatches),
                     interleave=int(n_chunks),
                     offload=bool(offload_activations),
                     bubble_fraction=round(_bubble(n_stages,
                                                   num_microbatches,
                                                   schedule,
                                                   interleave=n_chunks),
                                           6))
    # inside the shard_map region blocks run per-shard: no nested
    # sharding machinery, direct attention kernel
    cfg_local = dataclasses.replace(cfg, mesh=None)
    block = Block(cfg_local)

    model = TransformerLM(dataclasses.replace(cfg, mesh=None))
    rng = jax.random.PRNGKey(seed)
    tokens_shape = jnp.zeros((global_batch, cfg.max_seq_len), jnp.int32)
    params = model.init(rng, tokens_shape)["params"]
    params = params.unfreeze() if hasattr(params, "unfreeze") else dict(params)

    # regroup the layer stack: (L, ...) -> (pp, L/pp, ...); interleaved
    # adds a chunk axis — (L, ...) -> (v, pp, L/(v*pp), ...) -> swap to
    # (pp, v, ...) so model stage j*pp + k lands on worker k, chunk j
    # (the NON-adjacent assignment the schedule requires).
    if schedule == "interleaved":
        params["layers"] = jax.tree_util.tree_map(
            lambda p: jnp.swapaxes(
                p.reshape(n_chunks, n_stages, per_stage, *p.shape[1:]),
                0, 1),
            params["layers"])
    else:
        params["layers"] = jax.tree_util.tree_map(
            lambda p: p.reshape(n_stages, per_stage, *p.shape[1:]),
            params["layers"])

    replicated = NamedSharding(mesh, P())
    stage_sharded = NamedSharding(mesh, P("pp"))
    param_shardings = {
        k: (jax.tree_util.tree_map(lambda _: stage_sharded, v)
            if k == "layers"
            else jax.tree_util.tree_map(lambda _: replicated, v))
        for k, v in params.items()}
    params = jax.tree_util.tree_map(jax.device_put, params,
                                    param_shardings)

    tx = make_optimizer(cfg)
    if zero:
        if zero not in (1, 2):
            raise ValueError(f"zero={zero!r}; expected 0, 1, or 2")
        # ZeRO over dp composes with the pipeline: layer grads come out
        # of the schedule already pmean'd over dp, so the sharded update
        # slices — never re-reduces — them. The full replicated slot
        # tree is never materialized.
        from distributed_tensorflow_tpu.parallel.zero import (
            make_zero_update)
        param_specs = jax.tree_util.tree_map(
            lambda ns: ns.spec, param_shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding))
        params_abstract = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params)
        opt_state, opt_shardings, zero_update = make_zero_update(
            tx, mesh, param_specs, params_abstract, axis_name="dp")
    else:
        opt_state = tx.init(params)
        opt_shardings = _shard_like(
            jax.eval_shape(lambda: opt_state),
            jax.tree_util.tree_structure(params), param_shardings,
            replicated)
    state = {"params": params, "opt_state": opt_state,
             "step": jnp.zeros((), jnp.int32)}
    state_shardings = {"params": param_shardings,
                       "opt_state": opt_shardings, "step": replicated}

    def stage_fn(stage_params, x):
        """Apply this stage's layer group: local scan over L/pp blocks."""
        def body(carry, layer_params):
            y, _ = block.apply({"params": layer_params}, carry)
            return y, None

        if cfg.remat:
            body = jax.checkpoint(body, policy=remat_policy_for(cfg))
        out, _ = jax.lax.scan(body, x, stage_params)
        return out

    mb_spec = P(None, "dp" if "dp" in mesh.shape else None)
    norm = RMSNorm(cfg.dtype, eps=cfg.norm_eps)

    if schedule in ("1f1b", "interleaved"):
        def head_fn(head_params, y_mb, tokens_mb):
            """Per-microbatch loss head on the last stage's output:
            final norm + tied-embedding logits + shifted CE."""
            x = norm.apply({"params": head_params["final_norm"]}, y_mb)
            embed = head_params["embed"].astype(cfg.dtype)
            logits = jnp.einsum("bsd,vd->bsv", x,
                                embed).astype(jnp.float32)
            return next_token_loss(logits, tokens_mb)

        if offload_activations:
            # host-driven realization: one jitted cycle program called
            # C times with the stash routed through the host store, a
            # jitted finalize, and a jitted optimizer apply. The step is
            # NOT one fused jit — that is the point: the host sits on
            # the spill path between forward and backward.
            from distributed_tensorflow_tpu.parallel.offload import (
                Offloaded1F1B)
            runner = Offloaded1F1B(
                mesh, stage_fn, head_fn, param_spec=P("pp"),
                data_spec=mb_spec,
                spill=offload_activations != "device")

            def embed_lookup(embed, tokens):
                x = embed.astype(cfg.dtype)[tokens]     # (B, S, D)
                x = x.reshape(num_microbatches, mb, *x.shape[1:])
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, mb_spec))

            embed_jit = jax.jit(embed_lookup)

            if zero:
                def apply_fn(params, grads, opt_state):
                    return zero_update(params, grads, opt_state)
            else:
                def apply_fn(params, grads, opt_state):
                    updates, opt_state = tx.update(grads, opt_state,
                                                   params)
                    return (optax.apply_updates(params, updates),
                            opt_state)

            apply_jit = jax.jit(
                apply_fn, out_shardings=(param_shardings, opt_shardings))

            def offload_step(state, batch):
                with mesh:
                    tokens = batch["tokens"]
                    params = state["params"]
                    x_mb, embed_vjp = jax.vjp(
                        lambda e: embed_jit(e, tokens), params["embed"])
                    t_mb = jax.device_put(
                        tokens.reshape(num_microbatches, mb,
                                       tokens.shape[1]),
                        NamedSharding(mesh, mb_spec))
                    head_params = {"final_norm": params["final_norm"],
                                   "embed": params["embed"]}
                    loss, g_layers, g_head, g_x = runner.value_and_grads(
                        params["layers"], head_params, x_mb, t_mb)
                    (g_embed_in,) = embed_vjp(g_x.astype(x_mb.dtype))
                    grads = {"layers": g_layers,
                             "final_norm": g_head["final_norm"],
                             "embed": g_embed_in + g_head["embed"]}
                    new_params, new_opt = apply_jit(
                        params, grads, state["opt_state"])
                    return ({"params": new_params, "opt_state": new_opt,
                             "step": state["step"] + 1},
                            {"loss": loss})

            return state, offload_step

        if schedule == "interleaved":
            pipelined_1f1b = make_interleaved_1f1b_fn(
                mesh, stage_fn, head_fn, n_chunks=n_chunks,
                param_spec=P("pp"), data_spec=mb_spec)
        else:
            pipelined_1f1b = make_1f1b_fn(mesh, stage_fn, head_fn,
                                          param_spec=P("pp"),
                                          data_spec=mb_spec)

        def value_and_grads(params, tokens):
            def embed_lookup(embed):
                x = embed.astype(cfg.dtype)[tokens]     # (B, S, D)
                x = x.reshape(num_microbatches, mb, *x.shape[1:])
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, mb_spec))
            x_mb, embed_vjp = jax.vjp(embed_lookup, params["embed"])
            t_mb = jax.lax.with_sharding_constraint(
                tokens.reshape(num_microbatches, mb, tokens.shape[1]),
                NamedSharding(mesh, mb_spec))
            head_params = {"final_norm": params["final_norm"],
                           "embed": params["embed"]}
            loss, g_layers, g_head, g_x = pipelined_1f1b(
                params["layers"], head_params, x_mb, t_mb)
            (g_embed_in,) = embed_vjp(g_x.astype(x_mb.dtype))
            grads = {"layers": g_layers,
                     "final_norm": g_head["final_norm"],
                     # embedding is tied: input-lookup + logits grads
                     "embed": g_embed_in + g_head["embed"]}
            return loss, grads
    else:
        pipelined = make_pipelined_fn(
            mesh, stage_fn, param_spec=P("pp"), data_spec=mb_spec)

        def loss_fn(params, tokens):
            embed = params["embed"].astype(cfg.dtype)
            x = embed[tokens]                           # (B, S, D)
            x = x.reshape(num_microbatches, mb, *x.shape[1:])
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, mb_spec))
            out = pipelined(params["layers"], x)
            x = out.reshape(global_batch, *out.shape[2:])
            x = norm.apply({"params": params["final_norm"]}, x)
            logits = jnp.einsum("bsd,vd->bsv", x,
                                embed).astype(jnp.float32)
            return next_token_loss(logits, tokens)

        def value_and_grads(params, tokens):
            return jax.value_and_grad(loss_fn)(params, tokens)

    def train_step(state, batch):
        loss, grads = value_and_grads(state["params"], batch["tokens"])
        if zero:
            new_params, opt_state = zero_update(state["params"], grads,
                                                state["opt_state"])
        else:
            updates, opt_state = tx.update(grads, state["opt_state"],
                                           state["params"])
            new_params = optax.apply_updates(state["params"], updates)
        return ({"params": new_params, "opt_state": opt_state,
                 "step": state["step"] + 1},
                {"loss": loss})

    data_axes = "dp" if "dp" in mesh.shape else None
    batch_shardings = {"tokens": NamedSharding(mesh, P(data_axes))}
    with mesh:
        step_jit = jax.jit(train_step,
                           in_shardings=(state_shardings, batch_shardings),
                           out_shardings=(state_shardings, replicated),
                           donate_argnums=(0,))

    def wrapped(state, batch):
        with mesh:
            return step_jit(state, batch)

    return state, wrapped


def synthetic_tokens(global_batch: int, seq_len: int, vocab_size: int,
                     seed: int = 0):
    rng = jax.random.PRNGKey(seed)
    return jax.random.randint(rng, (global_batch, seq_len), 0, vocab_size,
                              dtype=jnp.int32)
