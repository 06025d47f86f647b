"""The parameter tree of a shortcut-connected stack of sparse-expert
layers over latent attention (``TransformerConfig.latent`` /
``.sub_blocks`` / ``.experts``), and its seeded initialisation.

Serving only: ``serving/decode.py`` runs the tree, ``TransformerLM`` refuses
the shape, and the plain reference (``models/scmoe_reference.py``) reads the
same tree. Layout (the canonical stacked one, every leaf of ``layers`` with
a leading axis over the layers; ``attn``, ``mlp``, ``norm_in`` and
``norm_post`` with a second one over the layer's sub-blocks):

    embed (V, D), lm_head (V, D), final_norm.scale (D,)
    layers.norm_in.scale, layers.norm_post.scale          (L, sub, D)
    layers.attn: q_a (D, q_rank), q_norm (q_rank,), q_b_nope (H x nope,
        q_rank) and q_b_rope (H x rope, q_rank): the query's up projection,
        output features major and its two parts apart (one matrix whose
        product is cut into the heads' parts has the compiler cut the
        matrix instead, a copy of it in every decode step; input features
        major it is turned round in every step),
        kv_a (D, kv_rank + rope), kv_norm (kv_rank,),
        kv_b_k (H, kv_rank, nope), kv_b_v (H, kv_rank, v), out (H, v, D)
    layers.mlp: wi (D, 2 x d_ff: gate then up), wo (d_ff, D)
    layers.moe: router (D, n_routed + n_identity), bias (n_outputs,),
        wi (held, D, 2 x d_expert), wo (held, d_expert, D)

Only the experts held here have weights (``experts.held`` of
``n_routed``); the router keeps its full width.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: standard deviation the router's logits are drawn to have for a normed
#: input: wide enough that a token's ``top_k`` picks carry real weight
ROUTER_SPREAD = 3.0
#: standard deviation of the router's choosing bias: of the size of the
#: scores at the edge of the choice, so that it moves picks there
BIAS_STD = 0.002
#: the held experts' down projections are drawn this many times fan-in
#: scaling: a rank holds few of the routed experts (16 of 768 outputs in
#: the benchmark's configuration: 2% of the picks), and at fan-in scale
#: their whole part moves the logits by about as much as bfloat16
#: rounding does, so that a comparison on logits could not tell an engine
#: that dropped them
EXPERT_GAIN = 4.0


def param_plan(cfg) -> dict:
    """``{path: (shape, std)}`` of every leaf, paths as tuples of keys;
    ``std`` None for a norm's scale (ones). Matrices are normal with a
    standard deviation of ``fan_in ** -0.5`` (the embedding 1: its rows
    are what the first norm sees), the router ``ROUTER_SPREAD`` times
    that, its bias ``BIAS_STD``, the experts' down projections
    ``EXPERT_GAIN`` times. The matrices that follow a scaled norm
    (``q_b_*`` after ``cq x sqrt(D / q_rank)``, ``kv_b_*`` after ``c x
    sqrt(D / kv_rank)``) are drawn that factor smaller, so that queries,
    keys and values have unit scale and the attention scores a standard
    deviation near 1, as a trained model's do: at fan-in alone the scores
    spread by 5.8, the softmax picks one key, and a bfloat16 program and
    the float32 reference part by a quarter of the logits' spread
    (PERF.md section 6, PR 37)."""
    la, ex = cfg.latent, cfg.experts
    if la is None or cfg.passes != 1:
        raise ValueError("scmoe.param_plan describes a one-pass stack of "
                         "latent attention layers (cfg.latent)")
    D, H, F = cfg.d_model, cfg.n_heads, cfg.d_ff
    L, S = cfg.n_layers, cfg.sub_blocks
    fan = lambda n: n ** -0.5
    q_mult = (D / la.q_rank) ** 0.5 if la.scale_q else 1.0
    kv_mult = (D / la.kv_rank) ** 0.5 if la.scale_kv else 1.0
    plan = {
        ("embed",): ((cfg.vocab_size, D), 1.0),
        ("final_norm", "scale"): ((D,), None),
    }
    if not cfg.tie_embeddings:
        plan[("lm_head",)] = ((cfg.vocab_size, D), fan(D))
    sub = {
        ("norm_in", "scale"): ((D,), None),
        ("norm_post", "scale"): ((D,), None),
        ("attn", "q_a"): ((D, la.q_rank), fan(D)),
        ("attn", "q_norm"): ((la.q_rank,), None),
        ("attn", "q_b_nope"): ((H * la.nope_dim, la.q_rank),
                               fan(la.q_rank) / q_mult),
        ("attn", "q_b_rope"): ((H * la.rope_dim, la.q_rank),
                               fan(la.q_rank) / q_mult),
        ("attn", "kv_a"): ((D, la.row_dim), fan(D)),
        ("attn", "kv_norm"): ((la.kv_rank,), None),
        ("attn", "kv_b_k"): ((H, la.kv_rank, la.nope_dim),
                             fan(la.kv_rank) / kv_mult),
        ("attn", "kv_b_v"): ((H, la.kv_rank, la.v_dim),
                             fan(la.kv_rank) / kv_mult),
        ("attn", "out"): ((H, la.v_dim, D), fan(H * la.v_dim)),
        ("mlp", "wi"): ((D, 2 * F), fan(D)),
        ("mlp", "wo"): ((F, D), fan(F)),
    }
    for path, (shape, std) in sub.items():
        plan[("layers",) + path] = ((L, S) + shape, std)
    if ex is not None:
        moe = {
            "router": ((D, ex.n_outputs), ROUTER_SPREAD * fan(D)),
            "bias": ((ex.n_outputs,), BIAS_STD),
            "wi": ((ex.held, D, 2 * ex.d_expert), fan(D)),
            "wo": ((ex.held, ex.d_expert, D),
                   EXPERT_GAIN * fan(ex.d_expert)),
        }
        for name, (shape, std) in moe.items():
            plan[("layers", "moe", name)] = ((L,) + shape, std)
    return plan


def n_params(cfg) -> int:
    return sum(math.prod(shape) for shape, _ in param_plan(cfg).values())


def _leaf(key, shape, std, dtype):
    """One leaf; a stack of matrices is drawn a matrix at a time, so
    that a leaf of gigabytes is never held in float32."""
    if std is None:
        return jnp.ones(shape, dtype)
    draw = lambda k, shape: (jax.random.normal(k, shape, jnp.float32) * std
                             ).astype(dtype)
    if len(shape) < 3:
        return draw(key, shape)
    keys = jax.random.split(key, math.prod(shape[:-2]))
    return jax.lax.map(lambda k: draw(k, shape[-2:]), keys).reshape(shape)


def init_params(cfg, rng):
    """The tree drawn from ``rng`` in ``cfg.param_dtype``: the same key
    gives the same weights. One jitted program a leaf."""
    tree: dict = {}
    plan = sorted(param_plan(cfg).items())
    leaf = jax.jit(_leaf, static_argnums=(1, 2, 3))
    for key, (path, (shape, std)) in zip(
            jax.random.split(rng, len(plan)), plan):
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf(key, shape, std, cfg.param_dtype)
    return tree
