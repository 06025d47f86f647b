"""The plain reference of a looped ("universal") decoder: a stack of
layers run several times with the same weights, in straightforward
``jax.numpy``, float32, matmul precision "highest", no kernels, no cache,
no batching. Written from the parameter tree; it calls nothing of
``TransformerLM.__call__`` or ``serving/``.

    h0 = E[tokens]
    for t = 1..passes:                      # the same weights at every t
        x = h(t-1)
        for l = 1..layers:
            a = Attn_l(N1_l(x))             # q,k,v = W x; rotary on q,k;
            x = x + N2_l(a)                 #   causal softmax(q.k/sqrt(hd)).v;
            m = W_down(silu(W_gate N3_l(x)) * (W_up N3_l(x)))   # out proj.
            x = x + N4_l(m)
        h(t) = N_f(x)                       # final norm after EVERY pass
        z(t) = W_head h(t)                  # logits of pass t
        lam(t) = sigmoid(w_g . h(t) + b_g)  # exit gate
    p(1) = lam(1), p(t) = lam(t) prod_{j<t} (1 - lam(j)), p(last) = the rest

N is RMSNorm with a learned scale. Attention in pass ``t`` of layer ``l``
sees the keys and values pass ``t`` of layer ``l`` produced for the
earlier positions (a full forward computes exactly that: each pass is a
causal forward over the previous pass's outputs). The output is ``z(t)``
at the first ``t`` whose cumulative exit probability reaches the exit
threshold; at the published threshold of 1 that is the last pass, which
is what :func:`greedy_gap` checks a served request against.

The projection kernels are ``(layers, D, H, hd)`` as the model keeps
them, or plain matrices ``(layers, H * hd, D)`` as the engine keeps
bfloat16 weights (``serving/decode.resident_params``); ``out`` is
``(layers, H, hd, D)`` in both and says what ``H`` and ``hd`` are. The
tree's keys say which optional parts a model has: ``post_attn_norm``
/ ``post_mlp_norm`` in a layer (N2 and N4; without them the branch joins
the residual as it is), ``lm_head`` (an untied head; else the
embedding), ``exit_gate_kernel`` / ``exit_gate_bias`` (else no exit
distribution). ``passes`` and the rotary base are not in the tree and are
arguments.

Everything is computed a layer at a time from the tree as it lies
(:func:`_layer_step` converts ONE layer's weights to float32 inside the
program), so beside a served model of several gigabytes the reference
adds one layer's float32 copy, the head's, and the activations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


def _f32(a, weight_dtype=None):
    """A weight in float32; rounded through ``weight_dtype`` first where
    one is given (the low-precision reading of ``greedy_gap``)."""
    if weight_dtype is not None:
        a = jnp.asarray(a, jnp.float32).astype(weight_dtype)
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x, base: float):
    """x: (batch, seq, heads, head_dim); pairs are (i, i + head_dim/2)."""
    seq, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    sin = jnp.sin(angles)[None, :, None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _stacked(params) -> tuple[dict, int]:
    """The layers as one tree stacked on axis 0, from either layout the
    repo uses (``layers``, or ``layer_<i>`` subtrees), and their number."""
    if "layers" in params:
        stacked = params["layers"]
    else:
        n = sum(1 for k in params if k.startswith("layer_"))
        stacked = jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *[params[f"layer_{i}"]
                                       for i in range(n)])
    return stacked, jax.tree_util.tree_leaves(stacked)[0].shape[0]


@functools.partial(jax.jit, static_argnames=("rope_base", "weight_dtype"))
def _layer_step(stacked, l, x, *, rope_base: float, weight_dtype=None):
    """``x`` (batch, seq, d) through layer ``l`` of the stacked tree."""
    w = lambda a: _f32(a[l], weight_dtype)
    with jax.default_matmul_precision("highest"):
        attn, mlp = stacked["attn"], stacked["mlp"]
        seq = x.shape[1]
        heads = attn["out"].shape[1:3]          # (H, hd)

        def project(name):
            # (D, H, hd); a served tree may hold it as (H * hd, D)
            kernel = w(attn[name])
            if kernel.ndim == 2:
                kernel = kernel.reshape(heads + kernel.shape[1:]
                                        ).transpose(2, 0, 1)
            return jnp.einsum("bsd,dhk->bshk", h, kernel)

        h = _rms_norm(x, w(stacked["RMSNorm_0"]["scale"]))
        q = _rope(project("query"), rope_base)
        k = _rope(project("key"), rope_base)
        v = project("value")
        scores = jnp.einsum("bqhk,bshk->bhqs", q, k) * q.shape[-1] ** -0.5
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(scores, axis=-1), v)
        a = jnp.einsum("bqhk,hkd->bqd", o, w(attn["out"]))
        if "post_attn_norm" in stacked:
            a = _rms_norm(a, w(stacked["post_attn_norm"]["scale"]))
        x = x + a
        h = _rms_norm(x, w(stacked["RMSNorm_1"]["scale"]))
        gate, up = jnp.split(h @ w(mlp["wi"]), 2, axis=-1)
        m = (jax.nn.silu(gate) * up) @ w(mlp["wo"])
        if "post_mlp_norm" in stacked:
            m = _rms_norm(m, w(stacked["post_mlp_norm"]["scale"]))
        return x + m


@functools.partial(jax.jit, static_argnames=("weight_dtype",))
def _pass_end(params, x, *, weight_dtype=None):
    """After a pass: ``(h, logits, gate)``; the gate is ``None`` for a
    model without one."""
    w = lambda a: _f32(a, weight_dtype)
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, w(params["final_norm"]["scale"]))
        head = params["lm_head"] if "lm_head" in params else params["embed"]
        logits = jnp.einsum("bsd,vd->bsv", h, w(head))
        gate = None
        if "exit_gate_kernel" in params:
            gate = jax.nn.sigmoid(h @ w(params["exit_gate_kernel"])
                                  + w(params["exit_gate_bias"]))
        return h, logits, gate


def exit_distribution(gates):
    """``p(t)`` (passes, ...) from the gates ``lam(t)`` (passes, ...):
    the probability of leaving after pass ``t``, the last pass taking
    what is left."""
    stay = jnp.cumprod(1.0 - gates[:-1], axis=0)
    stay = jnp.concatenate([jnp.ones_like(gates[:1]), stay], axis=0)
    p = gates * stay
    return p.at[-1].set(stay[-1])


def forward(params, tokens, *, passes: int, rope_base: float = 10000.0,
            weight_dtype=None, every_pass: bool = True):
    """``(logits, exits)``: float32 logits of every pass ``(passes,
    batch, seq, vocab)`` (of the last pass alone, ``(1, ...)``, with
    ``every_pass=False``) and the exit distribution ``(passes, batch,
    seq)``, ``None`` for a model without a gate."""
    stacked, n_layers = _stacked(params)
    tokens = jnp.asarray(tokens)
    h = _f32(params["embed"], weight_dtype)[tokens]
    logits, gates = [], []
    for t in range(passes):
        x = h
        for l in range(n_layers):
            x = _layer_step(stacked, l, x, rope_base=float(rope_base),
                            weight_dtype=weight_dtype)
        h, z, gate = _pass_end(params, x, weight_dtype=weight_dtype)
        if every_pass or t == passes - 1:
            logits.append(z)
        if gate is not None:
            gates.append(gate)
    exits = exit_distribution(jnp.stack(gates)) if gates else None
    return jnp.stack(logits), exits


def greedy_gap(params, tokens, n_prompt: int, width: int, *, passes: int,
               rope_base: float = 10000.0, chooser_dtype=None):
    """For one served request (``tokens`` = prompt + generated, as a
    list), the reference's margin at every generated position: the
    last pass's largest logit minus its logit of the token the engine
    chose; 0 where the engine agrees with the reference's argmax. The
    sequence is right-padded to ``width`` so that one program serves
    every request; under the causal mask the padding changes nothing.

    With ``chooser_dtype`` the tokens judged are not the engine's but
    the ones a reference whose weights are rounded through that type
    would choose at the same positions (teacher-forced on ``tokens``):
    the reading that a margin has to refuse."""
    padded = np.zeros((1, width), np.int32)
    padded[0, :len(tokens)] = tokens
    kw = dict(passes=passes, rope_base=rope_base, every_pass=False)
    logits = forward(params, padded, **kw)[0][-1, 0, :-1]
    if chooser_dtype is None:
        chosen = jnp.asarray(padded[0, 1:])
    else:
        chosen = jnp.argmax(forward(params, padded, weight_dtype=chooser_dtype,
                                    **kw)[0][-1, 0, :-1], axis=-1)
    picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return np.asarray(logits.max(-1) - picked)[n_prompt - 1:len(tokens) - 1]
