"""The plain reference: the decoder-only transformer this repo builds
(RMSNorm, rotary positions on half-split pairs, causal softmax
attention, SwiGLU feed-forward, tied output embedding) in
straightforward ``jax.numpy``, float32, matmul precision "highest", no
kernels, no cache, no batching tricks. Written from the parameter tree;
it calls nothing of ``TransformerLM.__call__`` or ``serving/decode.py``.

Departures from Vaswani et al. 2017 are the repo's own and are listed
under ``assumed`` in the configuration files: decoder-only, RMSNorm
before each sub-layer, rotary positions, a gated feed-forward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6
ROPE_BASE = 10000.0


def _layers(params) -> list[dict]:
    """Per-layer parameter dicts from either layout the repo uses:
    ``layer_<i>`` subtrees, or one ``layers`` subtree stacked on axis 0."""
    if "layers" in params:
        stacked = params["layers"]
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        return [jax.tree_util.tree_map(lambda a: a[i], stacked)
                for i in range(n)]
    n = sum(1 for k in params if k.startswith("layer_"))
    return [params[f"layer_{i}"] for i in range(n)]


def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x):
    """x: (batch, seq, heads, head_dim); pairs are (i, i + head_dim/2)."""
    seq, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (ROPE_BASE ** (jnp.arange(0, d, 2, dtype=jnp.float32)
                                    / d))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    sin = jnp.sin(angles)[None, :, None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward(params, tokens):
    """Logits ``(batch, seq, vocab)`` in float32 for int ``tokens``."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        embed = f32(params["embed"])
        x = embed[tokens]
        seq = tokens.shape[1]
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        for layer in _layers(params):
            attn, mlp = layer["attn"], layer["mlp"]
            h = _rms_norm(x, f32(layer["RMSNorm_0"]["scale"]))
            q = _rope(jnp.einsum("bsd,dhk->bshk", h, f32(attn["query"])))
            k = _rope(jnp.einsum("bsd,dhk->bshk", h, f32(attn["key"])))
            v = jnp.einsum("bsd,dhk->bshk", h, f32(attn["value"]))
            scores = jnp.einsum("bqhk,bshk->bhqs", q, k) * q.shape[-1] ** -0.5
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum("bhqs,bshk->bqhk", probs, v)
            x = x + jnp.einsum("bqhk,hkd->bqd", o, f32(attn["out"]))
            h = _rms_norm(x, f32(layer["RMSNorm_1"]["scale"]))
            gate, up = jnp.split(h @ f32(mlp["wi"]), 2, axis=-1)
            x = x + (jax.nn.silu(gate) * up) @ f32(mlp["wo"])
        x = _rms_norm(x, f32(params["final_norm"]["scale"]))
        return jnp.einsum("bsd,vd->bsv", x, embed)


def loss(params, tokens):
    """Mean next-token cross-entropy over positions 0..seq-2."""
    logp = jax.nn.log_softmax(forward(params, tokens)[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def chunked_loss(params, tokens, chunk: int = 2) -> float:
    """``loss`` over a whole batch, ``chunk`` sequences at a time, so
    that the float32 logits of the full-size model fit beside it."""
    fn = jax.jit(loss)
    parts = [float(fn(params, tokens[i:i + chunk]))
             for i in range(0, tokens.shape[0], chunk)]
    return sum(parts) / len(parts)


@jax.jit
def _gaps(params, padded):
    logits = forward(params, padded)[0, :-1]
    chosen = jnp.take_along_axis(logits, padded[0, 1:, None], axis=-1)
    return logits.max(-1) - chosen[:, 0]


def greedy_gap(params, tokens, n_prompt: int, width: int):
    """For one served request (``tokens`` = prompt + generated, as a
    list), the reference's margin at every generated position: its
    largest logit minus its logit of the token the engine chose; 0
    where the engine agrees with the reference's argmax. The sequence
    is right-padded to ``width`` so that one program serves every
    request; under the causal mask the padding changes nothing."""
    import numpy as np
    padded = np.zeros((1, width), np.int32)
    padded[0, :len(tokens)] = tokens
    return np.asarray(_gaps(params, padded))[n_prompt - 1:len(tokens) - 1]
