"""The plain reference of a shortcut-connected stack of sparse-expert
layers over latent (MLA) attention: one sequence, straightforward
``jax.numpy``, float32, matmul precision "highest", no kernel, no cache,
no batching, attention in its expanded form only. Written from the
parameter tree (``models/scmoe.py`` gives its layout); it calls nothing
of ``serving/``.

A layer, on a stream ``x``, holds ``sub_blocks`` pairs of attention and
feed-forward and one layer of experts:

    for i in range(sub_blocks):
        x = x + MLA_i(N(x; norm_in_i))
        h = N(x; norm_post_i)
        if i == 0: m = MoE(h)            # the shortcut: taken after the first
        x = x + FFN_i(h)                 # attention, added at the layer's end
    x = x + m

``N`` is RMSNorm (``eps``), ``FFN(h) = (silu(h Wg) * (h Wu)) Wd`` (``wi``
holds ``[Wg | Wu]``).

``MLA(h)`` for the token at position p: ``cq = N(h Wqa)`` times
``sqrt(D / q_rank)`` (``scale_q``), ``q = cq Wqb`` in heads of ``[q_nope |
q_rope]`` (the tree keeps the two parts' rows of ``Wqb`` apart); ``[c | kr] = h Wkva``, ``c = N(c)`` times ``sqrt(D / kv_rank)``
(``scale_kv``); a head's ``k_nope = c Wkvb_K[head]``, ``v = c
Wkvb_V[head]``; ``k = [k_nope | rope(kr, p)]`` with ``kr`` shared by the
heads, ``q = [q_nope | rope(q_rope, p)]``; causal softmax of ``q.k /
sqrt(nope + rope)``; the heads' ``sum p v`` through ``Wo``. Rotary pairs
are ``(i, i + rope/2)``, base ``rope_base``.

``MoE(h)``: ``s = softmax(h Wr)`` over all ``n_routed + n_identity``
outputs; the ``top_k`` chosen are the largest of ``s + b`` (``b`` chooses,
it does not weigh); ``w_e = scaling x s_e``, not renormalised;

    MoE(h) = sum_{chosen e held} w_e Expert_e(h) + sum_{chosen e identity} w_e h

with ``Expert_e`` the gated feed-forward of the tree's expert ``e -
offset``. The tree holds the experts ``offset .. offset + held - 1`` of the
``n_routed`` (its own count says how many); a chosen routed expert that
is not among them adds nothing, here as in the program, and with all of
them held this is the whole layer. After the last layer ``N`` and the
head.

``shape`` is anything with ``n_heads``, ``rope_base``, ``norm_eps``,
``latent`` (``q_rank``, ``kv_rank``, ``nope_dim``, ``rope_dim``,
``v_dim``, ``scale_q``, ``scale_kv``) and ``experts`` (``n_routed``,
``n_identity``, ``top_k``, ``scaling``, ``offset``) or None: a
``TransformerConfig`` is one. It is hashable and static.

One layer's weights are upcast one matrix at a time inside the program
and attention runs a head at a time, so beside a served model of ten
gigabytes the reference adds one matrix in float32 and one head's scores.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: parts of the expert layer :func:`forward` can leave out, for the
#: readings a tolerance has to refuse
ABLATIONS = (None, "without_routed", "without_identity")


def _f32(a, weight_dtype=None):
    """A weight in float32; rounded through ``weight_dtype`` first where
    one is given (the low-precision reading)."""
    if weight_dtype is not None:
        a = jnp.asarray(a, jnp.float32).astype(weight_dtype)
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, base: float):
    """``x`` (seq, dim) at positions ``0..seq-1``; pairs ``(i, i + dim/2)``."""
    seq, d = x.shape
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[:, :d // 2], x[:, d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _ffn(h, wi, wo):
    gate, up = jnp.split(h @ wi, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ wo


def _mla(shape, att, h, w):
    """``att``: one sub-block's attention leaves, ``w`` upcasts one."""
    la = shape.latent
    D = h.shape[-1]
    cq = _rms_norm(h @ w(att["q_a"]), w(att["q_norm"]), shape.norm_eps)
    if la.scale_q:
        cq = cq * (D / la.q_rank) ** 0.5
    q = jnp.concatenate([
        (cq @ w(att[n]).T).reshape(h.shape[0], shape.n_heads, -1)
        for n in ("q_b_nope", "q_b_rope")], axis=-1)
    ckr = h @ w(att["kv_a"])
    c = _rms_norm(ckr[:, :la.kv_rank], w(att["kv_norm"]), shape.norm_eps)
    if la.scale_kv:
        c = c * (D / la.kv_rank) ** 0.5
    kr = _rope(ckr[:, la.kv_rank:], shape.rope_base)
    causal = jnp.tril(jnp.ones((h.shape[0],) * 2, bool))

    def head(args):
        q_h, wk, wv = args                       # (S, qk), (kvr, nope), (kvr, v)
        q_h = jnp.concatenate([q_h[:, :la.nope_dim],
                               _rope(q_h[:, la.nope_dim:], shape.rope_base)],
                              axis=-1)
        k = jnp.concatenate([c @ wk, kr], axis=-1)
        scores = (q_h @ k.T) * (la.nope_dim + la.rope_dim) ** -0.5
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return p @ (c @ wv)                      # (S, v)

    o = jax.lax.map(head, (q.transpose(1, 0, 2), w(att["kv_b_k"]),
                           w(att["kv_b_v"])))                 # (H, S, v)
    return jnp.einsum("hsv,hvd->sd", o, w(att["out"]))


def _moe(shape, moe, h, w, ablate):
    ex = shape.experts
    held = moe["wi"].shape[0]
    s = jax.nn.softmax(h @ w(moe["router"]), axis=-1)
    _, chosen = jax.lax.top_k(s + w(moe["bias"]), ex.top_k)   # (S, k)
    weight = jnp.zeros_like(s).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(
            ex.scaling * jnp.take_along_axis(s, chosen, axis=-1))
    out = jnp.zeros_like(h)
    if ablate != "without_identity":
        out = out + jnp.sum(weight[:, ex.n_routed:], axis=-1,
                            keepdims=True) * h
    if ablate != "without_routed":
        local = weight[:, ex.offset:ex.offset + held]         # (S, held)

        def expert(out, args):
            wi, wo, w_e = args
            return out + w_e[:, None] * _ffn(h, w(wi), w(wo)), None

        out, _ = jax.lax.scan(expert, out, (moe["wi"], moe["wo"], local.T))
    return out


@functools.partial(jax.jit, static_argnames=("shape", "weight_dtype",
                                             "ablate"))
def _layer_step(layers, l, x, *, shape, weight_dtype=None, ablate=None):
    """``x`` (seq, D) through layer ``l`` of the stacked tree."""
    w = lambda a: _f32(a, weight_dtype)
    with jax.default_matmul_precision("highest"):
        m = None
        n_sub = layers["norm_in"]["scale"].shape[1]
        for i in range(n_sub):
            at = lambda a: jax.lax.dynamic_index_in_dim(
                a, l, keepdims=False)[i]
            att = {n: at(a) for n, a in layers["attn"].items()}
            h = _rms_norm(x, w(at(layers["norm_in"]["scale"])),
                          shape.norm_eps)
            x = x + _mla(shape, att, h, w)
            h = _rms_norm(x, w(at(layers["norm_post"]["scale"])),
                          shape.norm_eps)
            if i == 0 and shape.experts is not None:
                moe = {n: jax.lax.dynamic_index_in_dim(a, l, keepdims=False)
                       for n, a in layers["moe"].items()}
                m = _moe(shape, moe, h, w, ablate)
            x = x + _ffn(h, w(at(layers["mlp"]["wi"])),
                         w(at(layers["mlp"]["wo"])))
        return x if m is None else x + m


@functools.partial(jax.jit, static_argnames=("shape", "weight_dtype"))
def _head(params, x, *, shape, weight_dtype=None):
    w = lambda a: _f32(a, weight_dtype)
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, w(params["final_norm"]["scale"]), shape.norm_eps)
        head = params["lm_head"] if "lm_head" in params else params["embed"]
        return h @ w(head).T


def forward(params, tokens, *, shape, weight_dtype=None, ablate=None):
    """Float32 logits ``(seq, vocab)`` of one sequence ``tokens`` (seq,)."""
    if ablate not in ABLATIONS:
        raise ValueError(f"ablate={ablate!r}; expected one of {ABLATIONS}")
    layers = params["layers"]
    x = _f32(jnp.asarray(params["embed"])[jnp.asarray(tokens)], weight_dtype)
    for l in range(layers["norm_in"]["scale"].shape[0]):
        x = _layer_step(layers, l, x, shape=shape, weight_dtype=weight_dtype,
                        ablate=ablate)
    return _head({k: v for k, v in params.items() if k != "layers"}, x,
                 shape=shape, weight_dtype=weight_dtype)


def last_logits(params, tokens, width: int, **kw):
    """The logits after the last of ``tokens``, the sequence right-padded
    to ``width`` so that one program serves every length (under the
    causal mask the padding changes nothing before it)."""
    padded = np.zeros((width,), np.int32)
    padded[:len(tokens)] = tokens
    return forward(params, padded, **kw)[len(tokens) - 1]


def greedy_gap(params, tokens, n_prompt: int, width: int, *,
               chooser_dtype=None, **kw):
    """For one served request (``tokens`` = prompt + generated), the
    reference's margin at every generated position: its largest logit
    minus its logit of the token the engine chose; 0 where they agree.
    With ``chooser_dtype`` the tokens judged are not the engine's but
    those a reference whose weights are rounded through that type would
    choose at the same positions (teacher-forced on ``tokens``): the
    reading that a margin has to refuse."""
    padded = np.zeros((width,), np.int32)
    padded[:len(tokens)] = tokens
    logits = forward(params, padded, **kw)[:-1]
    chosen = jnp.asarray(padded[1:]) if chooser_dtype is None else \
        jnp.argmax(forward(params, padded, weight_dtype=chooser_dtype,
                           **kw)[:-1], axis=-1)
    picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return np.asarray(logits.max(-1) - picked)[n_prompt - 1:len(tokens) - 1]
