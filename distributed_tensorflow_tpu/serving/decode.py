"""Compiled incremental decode for the transformer/BERT family.

Pure, jittable programs over a trained ``TransformerLM`` parameter
tree (stacked-layers layout) and the block-allocated KV pool
(serving/kv_cache.py):

- :func:`make_prefill_fn` — one right-padded mixed-length batch of
  prompts through the FULL forward (the exact math of
  ``models/transformer.TransformerLM``, masked by the factored
  ``ops.attention.length_valid_mask`` rule), writing every position's
  rotary-embedded K and V into the sequences' cache blocks and
  returning each prompt's last-position logits. The write is a scatter
  (plain jnp) or, where the device keeps the pool with its rows on the
  lanes, one in-place kernel call over the written blocks
  (``paged_attention.write_blocks``): a scatter into that layout copies
  the whole pool twice.
- :func:`make_decode_fn` — ONE token per running slot: project q/k/v
  for the new token, put k/v into the slot's current block, and attend
  the single query against the slot's keys. Two paths, one contract:
  the *window* path scatters, gathers the slot's whole block window and
  runs ``mha_reference`` (plain jnp: meshes, int8 pools, the CPU); the
  *paged* path reads only the live blocks through the block table
  (``ops/paged_attention.py``, a Pallas kernel) and writes the pool in
  place, so the program holds nothing of the pool's size. Because
  prefill wrote the same K/V the full forward computes and the mask is
  the same factored rule, greedy decode through the cache matches
  argmax over full-sequence recompute — the correctness contract
  tests/test_serving.py pins on 1 device and on dp×tp meshes.
- :func:`make_multi_decode_fn` — k of those steps in one program, each
  fed the token the last one chose: the host is needed once per k
  tokens a sequence.
- :func:`make_extend_fn` — the MULTI-token cache-aware forward: E new
  tokens per slot at explicit absolute positions, written then attended
  against each slot's keys (the window gather, or where prefill writes
  by blocks a kernel that reads the keys before the span through the
  block table and merges the span's own from registers). This is both
  the prefix-cache *start-offset prefill* (a prompt whose first C tokens
  hash-matched cached blocks runs only the suffix through it) and the
  speculative-decoding *verify* step (the target model scores the
  draft's k tokens plus the bonus position in one forward). At E=1 it is
  exactly :func:`make_decode_fn`.

A model with latent attention (``cfg.latent``: one row a token and
cache layer shared by all heads, in the pool array ``latent``) and
shortcut-connected layers of sparse experts (``cfg.experts``,
``cfg.sub_blocks``) is served by the same four builders: they hand such a
configuration to the section "latent attention and the shortcut-connected
layer" below, whose programs take the same arguments and return, after
the logits and the pool, the layers' pick counts.

Every program takes and returns the pool as ONE dict (``{"k", "v"}``
plus ``{"k_scale", "v_scale"}`` when the cache config is int8): writes
quantize on the way in, gathers dequantize on the way out, so the whole
quantisation story lives in :func:`_pool_write` / :func:`_pool_window`
and the attention math never sees anything but the compute dtype.

Everything but the paged decode path, the paged read of extend and the
block write of prefill and extend is plain jnp (no Pallas custom calls),
so on a serving mesh GSPMD partitions the programs directly: slots over
``dp``, heads/mlp/vocab over ``tp`` (:func:`param_shardings`), the pool
laid out by ``kv_cache.pool_shardings``. GSPMD cannot partition a
``pallas_call``, so an engine on a mesh asks for the plain paths
(``"window"``, ``"scatter"``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig, TransformerLM, mesh_axis_rules, rotary_embedding)
from distributed_tensorflow_tpu.ops import paged_attention
from distributed_tensorflow_tpu.ops.attention import (
    DEFAULT_MASK_VALUE, length_valid_mask, mha_reference)
from distributed_tensorflow_tpu.serving.experts import COUNTS, expert_layer


def _plain(tree):
    """Deep-convert FrozenDict/Mapping nodes to plain dicts so the
    parameter tree's pytree STRUCTURE matches the shardings tree the
    engine passes as jit in_shardings."""
    if hasattr(tree, "items"):
        return {k: _plain(v) for k, v in tree.items()}
    return tree


def canonical_params(cfg: TransformerConfig, params):
    """Parameter tree in the stacked-layers layout the decode programs
    index (``params["layers"]`` leaves shaped ``(L, ...)``, plain-dict
    nodes): unstacked ``layer_<i>`` trees (scan_layers=False training)
    are stacked."""
    params = _plain(params)
    if "layers" in params:
        return params
    names = [f"layer_{i}" for i in range(cfg.n_layers)]
    missing = [n for n in names if n not in params]
    if missing:
        raise ValueError(f"params have neither 'layers' nor {missing}")
    layers = [params.pop(n) for n in names]
    params["layers"] = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *layers)
    return params


def resident_params(cfg: TransformerConfig, params):
    """The canonical tree with the query, key and value kernels as plain
    matrices, output features major: ``(L, D, H, hd)`` → ``(L, H * hd,
    D)``: the form in which weights that stay on the device in the
    compute type cost no relayout. The device tiles an array's last two
    dimensions, so it keeps ``(D, H, hd)`` as one ``(H, hd)`` tile per
    input feature, which no matrix unit can contract over ``D``; the
    compiler then copies all three stacks into another layout at the
    head of every program run (for the looped model of PR 28 three
    copies of 403 MB: 3.7 ms of a 42 ms decode step and 1.2 GB of
    temporaries in each program). With ``D`` minor the compiled decode
    and extend programs hold no temporaries to speak of (compiled for a
    described v5e; ``(L, D, H * hd)`` still cost two of the copies). The
    programs read either form (:func:`_heads`); the engine serves this
    one where :func:`wants_resident` says the device would keep the
    model's own form the costly way."""
    params = dict(canonical_params(cfg, params))
    layers = dict(params["layers"])
    attn = dict(layers["attn"])
    for name in ("query", "key", "value"):
        w = jnp.asarray(attn[name])
        if w.ndim == 4:
            attn[name] = w.reshape(w.shape[:2] + (-1,)).transpose(0, 2, 1)
    layers["attn"] = attn
    params["layers"] = layers
    return params


def wants_resident(cfg: TransformerConfig) -> bool:
    """Whether 16-bit projection kernels of this model are worth keeping
    as :func:`resident_params` lays them out, on one device. It is the
    head's width that decides, as it decides how the KV pool lies
    (``paged_attention.supported``): a minor dimension under the 128
    lanes would pad, so the device itself keeps ``(L, D, H, hd)`` with
    the largest dimension, ``D``, on the lanes, which is the resident
    form already, and the programs compile to better fusions from the
    model's own shape (transformer-big, ``hd`` 64, on a v5e: a full
    decode step ran 7.66 ms handed the matrix form and 7.45 handed the
    model's, a nearly empty one 1.64 and 1.45: PERF.md section 6,
    PR 35). From 128 up it keeps ``(H, hd)`` tiles, the case
    ``resident_params`` describes."""
    return (cfg.latent is None and jnp.dtype(cfg.dtype).itemsize == 2
            and cfg.head_dim >= paged_attention.GROUP_ROWS)


def compute_params(cfg: TransformerConfig, params, *,
                   resident: bool = False):
    """The canonical tree as a program run needs it: every matrix the
    programs contract with (the embedding, an untied head, the attention
    and feed-forward kernels: the leaves they ``.astype(cfg.dtype)``) in
    the compute type, with ``resident`` in :func:`resident_params`'
    layout. The norm scales (and an exit gate) stay as they arrive:
    :func:`_rms_norm` multiplies them into a float32 stream. Rounding a
    matrix here and rounding it at the head of a run give the matmuls
    the same bits; done once, when the engine takes the weights, the
    programs' own casts are no-ops (for transformer-big 1.41 GB of
    traffic, 1.7 ms of every run on a v5e: PERF.md section 6, PR 35).
    Pure, so that the engine runs it as one program."""
    dt = cfg.dtype
    params = dict(params)
    for name in ("embed", "lm_head"):
        if name in params:
            params[name] = params[name].astype(dt)
    layers = dict(params["layers"])
    for group in ("attn", "mlp", "moe"):
        if group in layers:
            layers[group] = {n: w.astype(dt)
                             for n, w in layers[group].items()}
    params["layers"] = layers
    return resident_params(cfg, params) if resident else params


def _heads(h, w, n_heads: int):
    """``h`` through a projection kernel into heads, in the attention
    layout: ``(B, D)`` → ``(B, H, hd)``, ``(B, S, D)`` → ``(B, H, S,
    hd)``. ``w`` is one layer's ``(D, H, hd)`` kernel as the model keeps
    it, or ``(H * hd, D)`` as :func:`resident_params` does."""
    if w.ndim == 3:
        return jnp.einsum("bd,dhk->bhk" if h.ndim == 2 else "bsd,dhk->bhsk",
                          h, w)
    y = jnp.einsum("...d,nd->...n", h, w)
    y = y.reshape(y.shape[:-1] + (n_heads, -1))
    return y if h.ndim == 2 else y.transpose(0, 2, 1, 3)


def truncated_draft(cfg: TransformerConfig, params, n_layers=None):
    """Self-speculation draft: the target's FIRST ``n_layers`` layers
    plus the shared embeddings and final norm — a draft model that
    costs nothing to obtain (LayerSkip / Draft&Verify style) and is the
    engine's default when ``speculative_k > 0`` with no explicit draft.
    Returns ``(draft_cfg, draft_params)`` in the canonical layout."""
    n = n_layers if n_layers is not None else max(1, cfg.n_layers // 2)
    if not 1 <= n <= cfg.n_layers:
        raise ValueError(f"truncated_draft: n_layers={n} outside "
                         f"[1, {cfg.n_layers}]")
    p = canonical_params(cfg, params)
    dp = dict(p)
    dp["layers"] = jax.tree_util.tree_map(lambda a: a[:n],
                                          dict(p["layers"]))
    dcfg = dataclasses.replace(cfg, n_layers=n, mesh=None)
    return dcfg, dp


def _layer(params, l: int):
    return jax.tree_util.tree_map(lambda a: a[l], dict(params["layers"]))


def _rms_norm(x, scale, dtype, eps: float = 1e-6):
    """models/transformer.RMSNorm math, parameter passed explicitly."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(dtype)


def rotary_at(x, positions, *, base: float = 10000.0):
    """RoPE at explicit absolute positions: ``x`` is ``(B, H, Q, hd)``,
    ``positions`` ``(B, Q)``. Same angle formula as
    ``models/transformer.rotary_embedding`` so a token's K is bitwise
    the same whether computed in prefill (positions ``0..S-1``) or one
    at a time during decode."""
    d = x.shape[-1]
    with jax.named_scope("rotary"):
        inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32)
                                   / d))
        ang = positions.astype(jnp.float32)[..., None] * inv_freq
        sin = jnp.sin(ang)[:, None]                        # (B,1,Q,d/2)
        cos = jnp.cos(ang)[:, None]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              axis=-1)
        return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# pool write / gather (the quantisation seam)
# ---------------------------------------------------------------------------

def _quantize_rows(x):
    """``(..., H, hd)`` float → int8 codes + per-(row, head) f32 scale.
    The quantisation block is one head's ``hd``-vector of one pool row:
    symmetric absmax scaling, so dequantisation is one multiply."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x32 / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _pool_write(pool: dict, l, rows, k, v, quantized: bool) -> dict:
    """Scatter new K/V rows (``(N, H, hd)`` compute-dtype) into layer
    ``l`` of the pool at flat ``rows``; int8 pools quantize on write
    and store the scales alongside."""
    pool = dict(pool)
    with jax.named_scope("kv.write"):
        if quantized:
            qk, sk = _quantize_rows(k)
            qv, sv = _quantize_rows(v)
            pool["k"] = pool["k"].at[l, rows].set(qk)
            pool["v"] = pool["v"].at[l, rows].set(qv)
            pool["k_scale"] = pool["k_scale"].at[l, rows].set(sk)
            pool["v_scale"] = pool["v_scale"].at[l, rows].set(sv)
        else:
            pool["k"] = pool["k"].at[l, rows].set(
                k.astype(pool["k"].dtype))
            pool["v"] = pool["v"].at[l, rows].set(
                v.astype(pool["v"].dtype))
    return pool


def _pool_window(pool: dict, l, window_rows, dt, quantized: bool):
    """Gather each slot's block window from layer ``l``:
    ``(B, W, H, hd)`` → ``(B, H, W, hd)`` compute-dtype, dequantized
    for int8 pools."""
    with jax.named_scope("kv.gather"):
        kw = pool["k"][l][window_rows]
        vw = pool["v"][l][window_rows]
        if quantized:
            kw = (kw.astype(jnp.float32)
                  * pool["k_scale"][l][window_rows][..., None])
            vw = (vw.astype(jnp.float32)
                  * pool["v_scale"][l][window_rows][..., None])
        return (kw.transpose(0, 2, 1, 3).astype(dt),
                vw.transpose(0, 2, 1, 3).astype(dt))


def make_copy_fn():
    """``copy(pool, src_rows, dst_rows)`` → pool with rows ``src_rows``
    duplicated into ``dst_rows`` across every layer and every pool
    array (values AND scales) — the device side of copy-on-write: the
    engine applies it before the first divergent write into a shared
    block."""

    def copy(pool, src_rows, dst_rows):
        return {n: a.at[:, dst_rows].set(a[:, src_rows])
                for n, a in pool.items()}

    return copy


# ---------------------------------------------------------------------------
# the stack: one layer body per program, run once or looped
# ---------------------------------------------------------------------------

def _run_stack(cfg: TransformerConfig, params, x, carry, layer):
    """``x`` through the whole stack. ``layer(x, carry, p, cl)`` →
    ``(x, carry, out)`` is one layer: ``p`` its parameters, ``cl`` its
    CACHE layer (``pass * n_layers + layer``), ``carry`` whatever the
    program threads through the layers (the pool, or None), ``out`` what
    it hands back per layer (or None). The final norm follows every pass
    and its output feeds the next. Returns the normed ``x``, the carry,
    and the layers' outs: a list of them for one pass (the caller stacks
    them with :func:`_stacked` where it always did), stacked over the
    cache layers for a looped stack.

    One pass unrolls in Python with ``cl`` a Python int. A looped stack
    (``cfg.passes > 1``) is ONE compiled body: a scan over the passes of
    a scan over the layers, ``cl`` traced, so ``passes x n_layers``
    bodies and kernel calls are never unrolled; the body of a pass lies
    under the scope ``loop.pass``."""
    def final_norm(x):
        return _rms_norm(x, params["final_norm"]["scale"], cfg.dtype,
                         cfg.norm_eps)

    if cfg.passes == 1:
        outs = []
        for l in range(cfg.n_layers):
            x, carry, out = layer(x, carry, _layer(params, l), l)
            outs.append(out)
        return final_norm(x), carry, outs

    layers = dict(params["layers"])
    index = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    # the stream that passes x layers x 2 branches are added into lives
    # in float32 between the layers (every branch is still computed in
    # cfg.dtype from its norm): with post norms each branch has an RMS
    # near 1 and the stream grows to 10, where a bfloat16 stream drops
    # 2% of every branch it takes in, and the next pass multiplies what
    # the last one lost (PERF.md section 6, PR 28)
    stream = jnp.promote_types(cfg.dtype, jnp.float32)

    def one_pass(state, t):
        def one_layer(state, inp):
            p, l = inp
            x, carry, out = layer(*state, p, t * cfg.n_layers + l)
            return (x, carry), out

        with jax.named_scope("loop.pass"):
            (x, carry), outs = jax.lax.scan(one_layer, state,
                                            (layers, index))
            return (final_norm(x).astype(stream), carry), outs

    (x, carry), outs = jax.lax.scan(
        one_pass, (x.astype(stream), carry),
        jnp.arange(cfg.passes, dtype=jnp.int32))
    # (passes, n_layers, ...) -> (cache layers, ...)
    outs = jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), outs)
    return x.astype(cfg.dtype), carry, outs


def _stacked(outs):
    """:func:`_run_stack`'s outs stacked over the cache layers: a list of
    per-layer trees (one pass, unrolled) is stacked here; a looped
    stack's scans have stacked theirs."""
    if isinstance(outs, list):
        return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *outs)
    return outs


def _post_norm(cfg: TransformerConfig, p, name: str, y):
    """Sandwich normalisation: a sub-layer's output normed before it
    joins the residual stream, where the model has such norms."""
    if not cfg.post_norms:
        return y
    with jax.named_scope("norm.post"):
        return _rms_norm(y, p[name]["scale"], cfg.dtype, cfg.norm_eps)


def _gated_mlp(dt, mlp, h):
    """The SiLU-gated feed-forward of ``h``: ``wi`` holds gate then up."""
    hh = jnp.einsum("...d,df->...f", h, mlp["wi"].astype(dt))
    gate, up = jnp.split(hh, 2, axis=-1)
    hh = jax.nn.silu(gate) * up
    return jnp.einsum("...f,fd->...d", hh, mlp["wo"].astype(dt))


def _mlp_residual(cfg: TransformerConfig, p, x):
    """``x`` plus the gated feed-forward of its norm."""
    dt = cfg.dtype
    h = _rms_norm(x, p["RMSNorm_1"]["scale"], dt, cfg.norm_eps)
    with jax.named_scope("mlp"):
        return x + _post_norm(cfg, p, "post_mlp_norm",
                              _gated_mlp(dt, p["mlp"], h))


def _logits(cfg: TransformerConfig, params, x):
    """float32 logits of the normed ``x`` against the output head: the
    embedding, or the head's own matrix where it is not tied."""
    head = params["embed" if cfg.tie_embeddings else "lm_head"]
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("...d,vd->...v", x, head.astype(cfg.dtype))
        return logits.astype(jnp.float32)


def model_forward(cfg: TransformerConfig, params, tokens, lengths=None,
                  *, return_kv: bool = False, logits_at=None):
    """Full-sequence forward over the canonical parameter tree — the
    serving-side twin of ``TransformerLM.__call__`` (same einsums, same
    order, no sharding-constraint machinery; GSPMD lays it out from the
    caller's in_shardings). ``lengths`` masks a right-padded batch via
    the factored rule. ``return_kv`` additionally returns the per-cache-
    layer post-RoPE K and V stacks ``(L, B, H, S, hd)`` — exactly what
    prefill writes into the cache blocks. ``logits_at`` ``(B,)``: the
    one position of each sequence whose logits the caller wants,
    ``(B, vocab)``: the head then runs on those rows alone. A prompt's
    first token needs one row; ``(S, vocab)`` logits of a 1024-wide
    prefill are 69 GFLOP and 67 MB that nobody reads, and on a v5e that
    array took the fast memory the attention's score matrix lives in
    (PERF.md section 6, PR 35)."""
    dt = cfg.dtype
    x = params["embed"].astype(dt)[tokens]             # (B, S, D)

    def layer(x, carry, p, cl):
        h = _rms_norm(x, p["RMSNorm_0"]["scale"], dt, cfg.norm_eps)
        att = p["attn"]
        q = _heads(h, att["query"].astype(dt), cfg.n_heads)
        k = _heads(h, att["key"].astype(dt), cfg.n_heads)
        v = _heads(h, att["value"].astype(dt), cfg.n_heads)
        q = rotary_embedding(q, base=cfg.rope_base, seq_axis=-2)
        k = rotary_embedding(k, base=cfg.rope_base, seq_axis=-2)
        with jax.named_scope("attn"):
            o = mha_reference(q, k, v, causal=cfg.causal, lengths=lengths)
        o = jnp.einsum("bhsk,hkd->bsd", o, att["out"].astype(dt))
        x = x + _post_norm(cfg, p, "post_attn_norm", o)
        x = _mlp_residual(cfg, p, x)
        return x, carry, ((k, v) if return_kv else None)

    x, _, kv = _run_stack(cfg, params, x, None, layer)
    if logits_at is not None:
        x = x[jnp.arange(x.shape[0]), logits_at]
    logits = _logits(cfg, params, x)
    if return_kv:
        return logits, _stacked(kv)
    return logits


def _kernel_path(implementation, plain: str, cache_cfg):
    """``(implementation, layout)``: ``implementation`` checked against
    the pool (``plain``, the jnp path's name, or "paged" / "interpret":
    the kernels, compiled or interpreted; ``None`` is "paged" where a
    kernel reads the pool and the backend is a TPU, else ``plain``) and
    the layout the kernels of ``ops/paged_attention.py`` read this pool
    in (``"lanes"`` / ``"rows"`` / ``"latent"``), or a false value."""
    layout = cache_cfg is not None and paged_attention.supported(
        cache_cfg.num_blocks * cache_cfg.block_size, cache_cfg.block_size,
        cache_cfg.head_dim, cache_cfg.dtype, cache_cfg.n_heads,
        cache_cfg.latent_dim)
    if implementation is None:
        implementation = ("paged" if layout
                          and jax.default_backend() == "tpu" else plain)
    if implementation not in (plain, "paged", "interpret"):
        raise ValueError(f"implementation={implementation!r}; expected "
                         f"{plain!r}, 'paged', 'interpret' or None")
    if implementation != plain and not layout:
        raise ValueError(f"no kernel reads this pool ({cache_cfg}): see "
                         f"ops.paged_attention.supported")
    return implementation, layout


def _block_writer(implementation, cache_cfg):
    """``write(pool, k, v, plan, layer=0)`` for the admission programs,
    or None where they scatter: the pool with the rows ``plan`` names
    (``paged_attention.write_plan``) of cache layers ``layer ..`` set to
    ``k`` / ``v`` (``(Ln, N, H, hd)``), in place and with no array of
    the pool's size beside it. Only the ``"lanes"`` layout needs it (a
    scatter relayouts that whole pool on the way in and out, PERF.md
    section 6, PR 27); XLA's scatter writes a row-major pool where it
    lies."""
    implementation, layout = _kernel_path(implementation, "scatter",
                                          cache_cfg)
    if implementation == "scatter" or layout != "lanes":
        return None

    def write(pool, k, v, plan, layer=0):
        pool = dict(pool)
        with jax.named_scope("kv.write"):
            pool["k"], pool["v"] = paged_attention.write_blocks(
                pool["k"], pool["v"], k, v, plan, layer,
                interpret=implementation == "interpret")
        return pool

    return write


def _write_plan(rows, cache_cfg):
    """The block writer's plan for an admission's flat write ``rows``:
    every row but the trash block's, where padded positions point."""
    with jax.named_scope("kv.write"):
        return paged_attention.write_plan(rows, rows >= cache_cfg.block_size)


def make_prefill_fn(cfg: TransformerConfig, cache_cfg=None, *,
                    implementation: str | None = None):
    """``prefill(params, pool, tokens, lengths, write_rows)``
    → ``(last_logits, pool)``; ``prefill.kv_write`` says how the rows
    reach the pool.

    ``tokens`` (B, S) right-padded prompts, ``lengths`` (B,) true
    lengths, ``write_rows`` (B, S) flat pool rows per position (padded
    positions point at the trash block). ``last_logits`` (B, vocab) are
    the logits at each prompt's final REAL position — the first
    generated token's distribution.

    implementation: "scatter" | "paged" | "interpret" | None (auto, as
    :func:`make_decode_fn` decides; an engine on a mesh asks for
    "scatter"). ``kv_write == "paged"``: one in-place kernel call after
    the forward writes every cache layer's rows by blocks
    (``paged_attention.write_blocks``); the trash block is not written.
    ``"scatter"``: ``_pool_write``, also where the pool is row-major."""
    if cfg.latent is not None or cfg.experts is not None:
        return _make_latent_prefill_fn(cfg, cache_cfg, implementation)
    quantized = cache_cfg.quantized if cache_cfg is not None else False
    write = _block_writer(implementation, cache_cfg)

    def prefill(params, pool, tokens, lengths, write_rows):
        B, S = tokens.shape
        last, (ks, vs) = model_forward(
            cfg, params, tokens, lengths=lengths, return_kv=True,
            logits_at=jnp.maximum(lengths, 1) - 1)
        L, _, H, _, hd = ks.shape
        rows = write_rows.reshape(-1)                       # (B*S,)
        flat_k = ks.transpose(0, 1, 3, 2, 4).reshape(L, B * S, H, hd)
        flat_v = vs.transpose(0, 1, 3, 2, 4).reshape(L, B * S, H, hd)
        if write is not None:
            pool = write(pool, flat_k, flat_v, _write_plan(rows, cache_cfg))
        elif cfg.passes == 1:
            for l in range(L):
                pool = _pool_write(pool, l, rows, flat_k[l], flat_v[l],
                                   quantized)
        else:
            # every cache layer in one write: a looped stack has too
            # many to unroll one scatter each
            pool = _pool_write(pool, slice(None), rows, flat_k, flat_v,
                               quantized)
        return last, pool

    prefill.kv_write = "scatter" if write is None else "paged"
    prefill.passes = cfg.passes
    return prefill


def make_decode_fn(cfg: TransformerConfig, cache_cfg=None, *,
                   implementation: str | None = None):
    """``decode(params, pool, tokens, positions, lengths, write_rows,
    table)`` → ``(logits, pool)``; ``decode.kv_path`` says which path
    was built and so what ``table`` is.

    One incremental step for a batch of running slots: ``tokens`` (B,)
    the token being fed, ``positions`` (B,) its absolute position,
    ``lengths`` (B,) the post-append visible length (``positions + 1``
    for active slots, 0 for idle ones — an idle slot attends nothing
    and its logits row is garbage the scheduler never reads),
    ``write_rows`` (B,) the flat pool row this token's K/V lands in.

    implementation: "window" | "paged" | "interpret" | None (auto:
    paged when the pool is floating point, of a shape the kernel reads
    (``paged_attention.supported``) and the backend is a TPU; an engine
    on a mesh asks for "window").

    - ``kv_path == "window"``: ``table`` is ``window_rows`` (B, W), each
      slot's full block-window gather index.
    - ``kv_path == "paged"``: ``table`` is the block table (B,
      max_blocks), each slot's physical blocks in logical order, padded
      with the trash block: per cache layer one paged-attention kernel
      over the slot's live blocks (``decode.kv_layout`` names the layout
      the pool lies in and so the kernel), and after the last layer one
      in-place write of every cache layer's new row
      (``paged_attention.write_rows``). "interpret" is the same path
      with the kernels interpreted, for the CPU.
    """
    if not cfg.causal:
        raise ValueError("incremental decode requires a causal model; "
                         "serve bidirectional (BERT) configs through the "
                         "prefill/scoring path")
    if cfg.latent is not None or cfg.experts is not None:
        return _make_latent_decode_fn(cfg, cache_cfg, implementation)
    quantized = cache_cfg.quantized if cache_cfg is not None else False
    implementation, layout = _kernel_path(implementation, "window",
                                          cache_cfg)
    paged = implementation != "window"
    interpret = implementation == "interpret"

    def decode(params, pool, tokens, positions, lengths, write_rows, table):
        dt = cfg.dtype
        x = params["embed"].astype(dt)[tokens]          # (B, D)
        pos_q = positions[:, None]                      # (B, 1)
        if paged:
            bs = cache_cfg.block_size
            with jax.named_scope("kv.gather"):
                # the keys already in the pool: all but the new token's
                plan = paged_attention.plan_for(
                    layout, table, jnp.maximum(lengths - 1, 0),
                    block_size=bs)

        def layer(x, pool, p, cl):
            h = _rms_norm(x, p["RMSNorm_0"]["scale"], dt, cfg.norm_eps)
            att = p["attn"]
            q = _heads(h, att["query"].astype(dt), cfg.n_heads)
            k = _heads(h, att["key"].astype(dt), cfg.n_heads)
            v = _heads(h, att["value"].astype(dt), cfg.n_heads)
            q = rotary_at(q[:, :, None], pos_q,
                          base=cfg.rope_base)            # (B, H, 1, hd)
            k = rotary_at(k[:, :, None], pos_q,
                          base=cfg.rope_base)[:, :, 0]   # (B, H, hd)
            if paged:
                # the new token's K and V as the pool will hold them:
                # merged into the softmax from registers, written once
                # after the last layer
                new = (k.astype(pool["k"].dtype), v.astype(pool["v"].dtype))
                with jax.named_scope("kv.gather"):
                    o = paged_attention.paged_attention_decode(
                        q[:, :, 0], *new, pool["k"], pool["v"],
                        cl, plan, lengths, block_size=bs,
                        interpret=interpret, layout=layout)
            else:
                # write THEN gather: the query must see its own position
                new = None
                pool = _pool_write(pool, cl, write_rows, k, v, quantized)
                kw, vw = _pool_window(pool, cl, table, dt, quantized)
                with jax.named_scope("attn"):
                    o = mha_reference(q, kw, vw, causal=True,
                                      lengths=lengths,
                                      q_positions=positions)[:, :, 0]
            o = jnp.einsum("bhk,hkd->bd", o, att["out"].astype(dt))
            x = x + _post_norm(cfg, p, "post_attn_norm", o)
            return _mlp_residual(cfg, p, x), pool, new

        # the paged path only reads the pool inside the stack, so the
        # layers close over it; the window path threads it through them
        if paged:
            def reading(x, _, p, cl):
                x, _, new = layer(x, pool, p, cl)
                return x, None, new

            x, _, new_rows = _run_stack(cfg, params, x, None, reading)
        else:
            x, pool, _ = _run_stack(cfg, params, x, pool, layer)
        logits = _logits(cfg, params, x)
        if paged:
            with jax.named_scope("kv.write"):
                # every kernel has read the pool before a row changes
                pool_k, pool_v, logits = jax.lax.optimization_barrier(
                    (pool["k"], pool["v"], logits))
                pool = dict(pool)
                pool["k"], pool["v"] = paged_attention.write_rows(
                    pool_k, pool_v, *_stacked(new_rows), write_rows,
                    lengths > 0, interpret=interpret, layout=layout)
        return logits, pool

    decode.kv_path = "paged" if paged else "window"
    decode.kv_layout = layout if paged else None
    decode.passes = cfg.passes
    return decode


def make_multi_decode_fn(step, steps: int):
    """``steps`` greedy runs of ``step`` (a :func:`make_decode_fn`
    program) in ONE program: ``decode(params, pool, tokens, positions,
    lengths, write_rows, table, budget)`` → ``(tokens, pool)`` with
    ``tokens`` (B, steps) the greedy choice of every inner step.

    Inner step ``i`` is ``step`` as the engine would call it ``i`` steps
    later: slot ``b`` is fed the token inner step ``i - 1`` chose (the
    given ``tokens`` (B,) at ``i = 0``) at ``positions + i``, sees
    ``lengths + i`` keys and writes its K and V to ``write_rows[b, i]``
    (``write_rows`` is (B, steps)), while ``i < budget[b]``. Past its
    ``budget`` (B,) a slot idles as an empty one does (length 0, the
    trash row 0) and what it yields is garbage nobody reads; ``table``
    covers every position the budgets reach. The same body, the same
    inputs: the tokens are those of ``steps`` single launches.

    What the host costs between two launches (uploads, dispatch, the
    result's way back), and what a stall of the host costs the device,
    is then paid once per ``steps`` tokens a sequence (PERF.md section
    6, PR 28)."""
    if steps < 2:
        raise ValueError(f"a multi-step decode runs 2 steps or more, "
                         f"not {steps}")
    if getattr(step, "counts", False):
        raise NotImplementedError(
            "several decode steps a launch are not written for a program "
            "that returns expert pick counts (a layer of sparse experts): "
            "use decode_steps=1")

    def decode(params, pool, tokens, positions, lengths, write_rows, table,
               budget):
        def one(carry, i):
            pool, tokens = carry
            live = i < budget
            logits, pool = step(
                params, pool, tokens, positions + i,
                jnp.where(live, lengths + i, 0),
                jnp.where(live, write_rows[:, i], 0), table)
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (pool, tokens), tokens

        (pool, _), chosen = jax.lax.scan(
            one, (pool, tokens), jnp.arange(steps, dtype=jnp.int32))
        return chosen.T, pool

    decode.kv_path, decode.kv_layout = step.kv_path, step.kv_layout
    decode.passes = step.passes
    return decode


# ---------------------------------------------------------------------------
# the engine's launches: the next token chosen on the device
# ---------------------------------------------------------------------------
#
# An engine launches the next decode before it reads the last one's
# tokens, so each slot's last chosen token lives on the device: a
# (slots,) int32 vector ``chosen`` that every launch takes (not donated:
# the host reads the copy a launch was fed) and returns updated. What the
# host sends a launch is ONE int32 array (an upload costs about 0.25 ms
# of host time whatever its size: PERF.md section 6, PR 38). A launch
# returns ``(chosen, pool, scores, *counts)``: ``scores`` are the logits
# it chose from (the multi-step decode: its tokens), which the engine
# drops and a reference check reads (``InferenceEngine._prefill``).


def _greedy(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _named(run, program):
    """``run`` under ``program``'s name: the compiled program keeps its
    name (``jit_decode``, ``jit_prefill``, ``jit_extend``), which the
    device trace and the benchmark's readers know it by."""
    run.__name__ = run.__qualname__ = program.__name__
    return run


def launch_prefill(prefill):
    """``prefill`` (:func:`make_prefill_fn`) as the engine launches it:
    ``(params, pool, chosen, host)`` → ``(chosen, pool, last, *counts)``,
    ``last`` the (1, V) logits of the prompt's last row. ``host``
    (2 + 2S,) is ``[slot, prompt length, tokens (S,), pool rows (S,)]``,
    the prompt right-padded to the program's width S; the greedy first
    token lands in ``chosen[slot]``."""
    def run(params, pool, chosen, host):
        S = (host.shape[0] - 2) // 2
        last, pool, *counts = prefill(params, pool, host[None, 2:2 + S],
                                      host[1:2], host[None, 2 + S:])
        return chosen.at[host[0]].set(_greedy(last[0])), pool, last, *counts
    return _named(run, prefill)


def launch_extend(extend, window: int):
    """``extend`` (:func:`make_extend_fn`) for one admission's suffix, as
    the engine launches it: ``(params, pool, chosen, host)`` →
    ``(chosen, pool, last, *counts)``, ``last`` as
    :func:`launch_prefill`'s. ``host`` (2 + 3E + window,) is ``[slot,
    prompt length, tokens (E,), positions (E,), pool rows (E,), window
    rows]``; the suffix's last real position is ``length - 1`` and its
    greedy token lands in ``chosen[slot]``."""
    def run(params, pool, chosen, host):
        E = (host.shape[0] - 2 - window) // 3
        positions = host[None, 2 + E:2 + 2 * E]
        logits, pool, *counts = extend(
            params, pool, host[None, 2:2 + E], positions, host[1:2],
            host[None, 2 + 2 * E:2 + 3 * E], host[None, 2 + 3 * E:])
        last = logits[0, host[1] - 1 - positions[0, 0]][None]
        return chosen.at[host[0]].set(_greedy(last[0])), pool, last, *counts
    return _named(run, extend)


def launch_decode(decode, steps: int = 1):
    """``decode`` (:func:`make_decode_fn`, or with ``steps`` > 1
    :func:`make_multi_decode_fn`'s) as the engine launches it:
    ``(params, pool, chosen, host)`` → ``(chosen, pool, scores, *counts)``.

    ``host`` (slots, 3 + steps + T) holds a row a slot: ``[fed, length,
    budget, pool rows (steps,), table (T,)]``. A slot is fed ``fed``, or
    where that is -1 the token ``chosen`` holds (one the host has not
    read), at position ``length - 1``; an idle slot has length 0 and
    keeps its ``chosen``. One step: ``scores`` are the (slots, V) logits
    and ``chosen`` each live slot's greedy next token. Several:
    ``budget`` is each slot's inner steps, ``scores`` the ``(slots,
    steps)`` tokens and ``chosen`` each slot's last of them."""
    def run(params, pool, chosen, host):
        fed, lengths, budget = host[:, 0], host[:, 1], host[:, 2]
        rows, table = host[:, 3:3 + steps], host[:, 3 + steps:]
        tokens = jnp.where(fed >= 0, fed, chosen)
        positions = jnp.maximum(lengths - 1, 0)
        if steps == 1:
            logits, pool, *counts = decode(params, pool, tokens, positions,
                                           lengths, rows[:, 0], table)
            return (jnp.where(lengths > 0, _greedy(logits), tokens), pool,
                    logits, *counts)
        out, pool = decode(params, pool, tokens, positions, lengths, rows,
                           table, budget)
        last = jnp.take_along_axis(out, jnp.maximum(budget - 1, 0)[:, None],
                                   axis=1)[:, 0]
        return jnp.where(budget > 0, last, tokens), pool, out
    return _named(run, decode)


def make_extend_fn(cfg: TransformerConfig, cache_cfg=None, *,
                   implementation: str | None = None):
    """``extend(params, pool, tokens, positions, lengths, write_rows,
    window_rows)`` → ``(logits, pool)`` — E tokens per slot in one
    cache-aware forward; ``extend.kv_write`` and ``implementation`` as
    :func:`make_prefill_fn`'s, and ``extend.kv_read`` says how the
    layers read the keys before the span: ``"paged"`` where they write by
    blocks (the ``"lanes"`` layout), one kernel call a layer that reads
    the slot's blocks through its table where they lie
    (``paged_attention.paged_attention_extend``); ``"window"`` otherwise,
    a gather of each slot's whole window out of the layer
    (``_pool_window``), which on that layout relays the layer.

    ``tokens`` (B, E) the new tokens (right-padded), ``positions``
    (B, E) their ABSOLUTE cache positions, a slot's real ones consecutive
    up to ``lengths - 1`` (padded entries must point at or past
    ``lengths`` so the factored mask zeroes them), ``lengths`` (B,) the
    post-write visible length, ``write_rows`` (B, E) flat pool rows
    (padded entries at the trash block), ``window_rows`` (B, W) the
    block-window gather index, whose every ``block_size``-th row names a
    block of the slot's table. Returns logits for ALL E positions
    — row ``i`` is the next-token distribution after the token fed at
    ``positions[:, i]``.

    Two callers, one program: prefix-cache suffix prefill (positions
    ``C..L-1`` of a prompt whose first C tokens hash-matched) and
    speculative-decode verification (positions ``L-1..L+k-1``: the
    banked token plus k draft proposals, scored in one step). Per-query
    math is position-independent, so row 0 of a (B, E) extend is
    bitwise the row a (B,) decode on the window path produces at the
    same position (on the paged paths the two agree to float32 rounding
    before the output cast: the kernels' softmax is online) — the
    greedy-parity contract extends to both callers."""
    if not cfg.causal:
        raise ValueError("extend requires a causal model; serve "
                         "bidirectional (BERT) configs through the "
                         "prefill/scoring path")
    if cfg.latent is not None or cfg.experts is not None:
        return _make_latent_extend_fn(cfg, cache_cfg, implementation)
    quantized = cache_cfg.quantized if cache_cfg is not None else False
    write = _block_writer(implementation, cache_cfg)
    # the kernel reads the pool the block writer writes: rows on the lanes
    paged = write is not None
    interpret = implementation == "interpret"

    def extend(params, pool, tokens, positions, lengths, write_rows,
               window_rows):
        dt = cfg.dtype
        B, E = tokens.shape
        x = params["embed"].astype(dt)[tokens]          # (B, E, D)
        rows = write_rows.reshape(-1)                   # (B*E,)
        if paged:
            bs = cache_cfg.block_size
            plan = _write_plan(rows, cache_cfg)         # every layer's
            with jax.named_scope("kv.gather"):
                # the keys already in the pool: those before the span
                read = paged_attention.decode_plan(
                    window_rows[:, ::bs] // bs,
                    jnp.minimum(positions[:, 0], lengths), block_size=bs)

        def layer(x, pool, p, cl):
            h = _rms_norm(x, p["RMSNorm_0"]["scale"], dt, cfg.norm_eps)
            att = p["attn"]
            q = _heads(h, att["query"].astype(dt), cfg.n_heads)
            k = _heads(h, att["key"].astype(dt), cfg.n_heads)
            v = _heads(h, att["value"].astype(dt), cfg.n_heads)
            q = rotary_at(q, positions, base=cfg.rope_base)  # (B, H, E, hd)
            k = rotary_at(k, positions, base=cfg.rope_base)
            flat_k = k.transpose(0, 2, 1, 3).reshape(B * E, k.shape[1],
                                                     k.shape[3])
            flat_v = v.transpose(0, 2, 1, 3).reshape(B * E, v.shape[1],
                                                     v.shape[3])
            if paged:
                pool = write(pool, flat_k[None], flat_v[None], plan, cl)
                # the span's own K and V as the pool holds them: merged
                # into the softmax from registers; the kernel reads the
                # rows before the span, which the write leaves as they are
                with jax.named_scope("kv.gather"):
                    o = paged_attention.paged_attention_extend(
                        q, k.astype(pool["k"].dtype),
                        v.astype(pool["v"].dtype), pool["k"], pool["v"],
                        cl, read, positions, lengths, block_size=bs,
                        interpret=interpret)
            else:
                # write THEN gather: query i must see keys 0..i of the span
                pool = _pool_write(pool, cl, rows, flat_k, flat_v,
                                   quantized)
                kw, vw = _pool_window(pool, cl, window_rows, dt, quantized)
                with jax.named_scope("attn"):
                    o = mha_reference(q, kw, vw, causal=True,
                                      lengths=lengths,
                                      q_positions=positions)
            o = jnp.einsum("bhsk,hkd->bsd", o, att["out"].astype(dt))
            x = x + _post_norm(cfg, p, "post_attn_norm", o)
            return _mlp_residual(cfg, p, x), pool, None

        x, pool, _ = _run_stack(cfg, params, x, pool, layer)
        return _logits(cfg, params, x), pool

    extend.kv_write = "paged" if paged else "scatter"
    extend.kv_read = "paged" if paged else "window"
    return extend


# ---------------------------------------------------------------------------
# latent attention and the shortcut-connected layer
# ---------------------------------------------------------------------------
#
# A layer holds ``cfg.sub_blocks`` pairs of latent attention and gated
# feed-forward and, with ``cfg.experts``, one layer of sparse experts fed
# what the FIRST pair's feed-forward is fed and added at the layer's end
# (the shortcut: on several ranks its exchange overlaps the second pair):
#
#     for i in range(sub_blocks):
#         x = x + MLA_i(norm_in_i(x))
#         h = norm_post_i(x)
#         if i == 0: m = MoE(h)
#         x = x + FFN_i(h)
#     x = x + m
#
# Latent attention (MLA) of a token at position p: ``cq = norm(h Wqa)``,
# ``q = cq Wqb`` in heads of ``[q_nope | q_rope]``; ``[c | kr] = h Wkva``,
# ``c = norm(c)``; the token's CACHE ROW is ``[c | rope(kr, p)]``, shared
# by all heads. Expanded form: ``[k_nope | v] = c Wkvb`` a head, ``k =
# [k_nope | rope(kr)]``, softmax of ``q.k / sqrt(nope + rope)`` over the
# visible rows, the heads' values through ``Wo``. Absorbed form: ``q_lat =
# q_nope Wkvb_K`` so that a row scores ``q_lat.c + q_rope.rope(kr)`` as it
# lies in the cache, the values are the rows' ``c``, and ``Wkvb_V`` then
# ``Wo`` follow the softmax: nothing a head wide is made per cached row.
# Prefill and extend run the expanded form, decode the absorbed one; the
# two are the same function of the rows (tests/test_latent_serving.py).
#
# One layer's parameters, each leaf of ``attn``, ``mlp``, ``norm_in`` and
# ``norm_post`` with a leading axis over the sub-blocks: ``attn``: ``q_a``
# (D, q_rank), ``q_norm`` (q_rank,), ``q_b_nope`` (H x nope, q_rank) and
# ``q_b_rope`` (H x rope, q_rank),
# ``kv_a`` (D, kv_rank + rope), ``kv_norm`` (kv_rank,), ``kv_b_k`` (H,
# kv_rank, nope), ``kv_b_v`` (H, kv_rank, v), ``out`` (H, v, D); ``mlp``
# as every model's; ``moe`` as ``serving/experts.py`` says.

def _mla_project(cfg: TransformerConfig, att, h, positions):
    """``h`` (B, S, D) at ``positions`` (B, S) → the queries ``q_nope``
    (B, H, S, nope) and ``q_rope`` (B, H, S, rope; rotated), and the
    tokens' cache rows (B, S, kv_rank + rope)."""
    la, dt, eps = cfg.latent, cfg.dtype, cfg.norm_eps
    B, S, D = h.shape

    def normed(x, scale, rank, scaled):
        mult = (D / rank) ** 0.5 if scaled else 1.0
        return _rms_norm(x, scale.astype(jnp.float32) * mult, dt, eps)

    with jax.named_scope("mla.q"):
        cq = normed(jnp.einsum("bsd,dr->bsr", h, att["q_a"].astype(dt)),
                    att["q_norm"], la.q_rank, la.scale_q)
        q_nope, q_rope = (
            jnp.einsum("bsr,nr->bsn", cq, att[name].astype(dt)).reshape(
                B, S, cfg.n_heads, -1).transpose(0, 2, 1, 3)
            for name in ("q_b_nope", "q_b_rope"))
        q_rope = rotary_at(q_rope, positions, base=cfg.rope_base)
    with jax.named_scope("mla.kv"):
        ckr = jnp.einsum("bsd,dr->bsr", h, att["kv_a"].astype(dt))
        c = normed(ckr[..., :la.kv_rank], att["kv_norm"], la.kv_rank,
                   la.scale_kv)
        kr = rotary_at(ckr[:, None, :, la.kv_rank:], positions,
                       base=cfg.rope_base)[:, 0]
        return q_nope, q_rope, jnp.concatenate([c, kr], axis=-1)


def _mla_out(cfg: TransformerConfig, att, o):
    """The heads' values ``o`` (B, H, S, v) through the output matrix."""
    return jnp.einsum("bhsv,hvd->bsd", o, att["out"].astype(cfg.dtype))


def _mla_expanded(cfg: TransformerConfig, att, q_nope, q_rope, rows,
                  lengths, q_positions=None):
    """Attention of the queries over the cache rows ``rows`` (B, K, W) in
    the expanded form: keys and values a head are made from every row.
    Row ``j`` sits at position ``j``; the queries at ``q_positions``, or
    ``0..S-1``."""
    la, dt = cfg.latent, cfg.dtype
    c, kr = rows[..., :la.kv_rank], rows[..., la.kv_rank:]
    with jax.named_scope("mla.kv"):
        k_nope = jnp.einsum("bkc,hcn->bhkn", c, att["kv_b_k"].astype(dt))
        v = jnp.einsum("bkc,hcv->bhkv", c, att["kv_b_v"].astype(dt))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr[:, None], k_nope.shape[:3]
                                      + kr.shape[-1:])], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    with jax.named_scope("attn"):
        o = mha_reference(q, k, v, causal=True, lengths=lengths,
                          q_positions=q_positions,
                          sm_scale=la.qk_dim ** -0.5)
    return _mla_out(cfg, att, o)


def _mla_absorbed(cfg: TransformerConfig, att, q_nope, q_rope, attend):
    """One query a slot in the absorbed form: ``attend(q)`` takes the
    heads' queries in the rows' own space (B, H, W) and returns the
    softmax-weighted sum of the visible rows' first ``kv_rank`` values
    (B, H, kv_rank): the cache access, whatever reads the pool."""
    dt = cfg.dtype
    with jax.named_scope("mla.q"):
        q_lat = jnp.einsum("bhn,hcn->bhc", q_nope[:, :, 0],
                           att["kv_b_k"].astype(dt))
        q = jnp.concatenate([q_lat, q_rope[:, :, 0]], axis=-1)
    o_lat = attend(q)
    with jax.named_scope("mla.kv"):
        o = jnp.einsum("bhc,hcv->bhv", o_lat, att["kv_b_v"].astype(dt))
    return _mla_out(cfg, att, o[:, :, None])[:, 0]


def _window_attend(cfg: TransformerConfig, q, rows, lengths, positions):
    """The absorbed form's cache access in plain jnp: ``q`` (B, H, W) at
    ``positions`` (B,) over a slot's window of rows (B, K, W)."""
    la = cfg.latent
    with jax.named_scope("attn"):
        logits = jnp.einsum("bhw,bkw->bhk", q.astype(jnp.float32),
                            rows.astype(jnp.float32)) * la.qk_dim ** -0.5
        valid = length_valid_mask(lengths, 1, rows.shape[1], causal=True,
                                  q_positions=positions)[:, :, 0]
        probs = jax.nn.softmax(jnp.where(valid, logits, DEFAULT_MASK_VALUE),
                               axis=-1)
        probs = probs * jnp.any(valid, axis=-1, keepdims=True)
        return jnp.einsum("bhk,bkc->bhc", probs,
                          rows[..., :la.kv_rank].astype(jnp.float32)
                          ).astype(q.dtype)


def _shortcut_layer(cfg: TransformerConfig, params, l: int, x, attention,
                    valid, expert_impl: str):
    """Layer ``l``, as the section's head writes it. ``attention(i, att,
    h)`` is sub-block ``i``'s latent attention of the normed stream
    through whatever cache access the program has; ``valid`` marks the
    real tokens of ``x``. Returns the stream and the expert layer's
    counts (``experts.COUNTS``; None without one).

    Every matrix is sliced out of the stacked tree where it is used, once
    (a slice with one consumer fuses into it; a layer's slice shared by
    its sub-blocks is copied, 600 MB a feed-forward here), and the
    experts' stacks go to their kernel whole."""
    dt, eps = cfg.dtype, cfg.norm_eps
    layers = params["layers"]
    moe = counts = None
    for i in range(cfg.sub_blocks):
        sub = jax.tree_util.tree_map(
            lambda a: a[l, i], {n: layers[n] for n in (
                "attn", "mlp", "norm_in", "norm_post")})
        h = _rms_norm(x, sub["norm_in"]["scale"], dt, eps)
        x = x + attention(i, sub["attn"], h)
        h = _rms_norm(x, sub["norm_post"]["scale"], dt, eps)
        if i == 0 and cfg.experts is not None:
            tokens = valid.size
            stacks = {n: layers["moe"][n].reshape(
                (-1,) + layers["moe"][n].shape[2:]) for n in ("wi", "wo")}
            moe, counts = expert_layer(
                cfg.experts,
                dict(stacks, router=layers["moe"]["router"][l],
                     bias=layers["moe"]["bias"][l]),
                h.reshape(tokens, -1), valid.reshape(tokens), dtype=dt,
                implementation=expert_impl,
                tile_rows=64 if tokens >= 256 else 16,
                first_group=l * cfg.experts.held)
        with jax.named_scope("mlp"):
            x = x + _gated_mlp(dt, sub["mlp"], h)
    if moe is not None:
        x = x + moe.reshape(x.shape).astype(dt)
    return x, counts


def _latent_paths(cfg: TransformerConfig, cache_cfg, implementation,
                  plain: str):
    """``(paged, interpret, expert_impl)`` of a latent program: whether
    its decode step reads the pool through the kernel, whether kernels
    are interpreted, and how the experts' product runs: the kernels
    together, compiled on a TPU (``None``, ``"paged"``) or interpreted,
    the plain forms together (``plain``; ``None`` off the TPU)."""
    if cfg.latent is None:
        raise NotImplementedError(
            "a layer of sparse experts (cfg.experts) is served only with "
            "latent attention (cfg.latent): no program here runs experts "
            "beside per-head K and V")
    if cfg.passes != 1:
        raise NotImplementedError("a looped stack (passes > 1) of latent "
                                  "attention layers is not written")
    if cache_cfg is None or not cache_cfg.latent_dim:
        raise ValueError(f"latent attention needs a pool of latent rows "
                         f"(CacheConfig.for_model), not {cache_cfg}")
    implementation, _ = _kernel_path(implementation, plain, cache_cfg)
    kernels = implementation != plain
    interpret = implementation == "interpret"
    return kernels, interpret, ("interpret" if interpret else
                                "grouped" if kernels else "dense")


def _pool_wide(x, pool):
    """``x`` (..., W) padded with zeros to the width of the pool's rows
    (whole 128-value tiles: ``CacheConfig.row_shape``)."""
    pad = pool.shape[-1] - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _write_latent(pool: dict, layers, rows, new) -> dict:
    """The latent pool with ``new`` (Ln, N, W) written to the flat
    ``rows`` (N,) of the cache layers ``layers`` (Ln,)."""
    with jax.named_scope("kv.write"):
        pool = dict(pool)
        pool["latent"] = paged_attention.write_latent_rows(
            pool["latent"], _pool_wide(new, pool["latent"]), rows,
            layers=layers)
        return pool


def _latent_window(cfg: TransformerConfig, pool: dict, cl: int, rows):
    """Cache layer ``cl``'s rows ``rows`` (B, K) of the latent pool, (B,
    K, W): one gather by (layer, row) out of the pool as it lies (a slice
    of the layer first is an operation of a cache layer's size)."""
    return pool["latent"][jnp.full_like(rows, cl), rows][
        ..., :cfg.latent.row_dim]


def _counts(outs):
    """The layers' expert counts stacked ``(n_layers, len(COUNTS))``;
    empty for a model without an expert layer."""
    if outs[0] is None:
        return jnp.zeros((0, len(COUNTS)), jnp.int32)
    return jnp.stack(outs)


def _make_latent_prefill_fn(cfg: TransformerConfig, cache_cfg,
                            implementation):
    """:func:`make_prefill_fn` for latent attention: ``prefill(params,
    pool, tokens, lengths, write_rows)`` → ``(last_logits, pool,
    counts)``. The whole prompt in the expanded form over the rows it
    has just made (no cache access); every cache layer's rows are then
    written by one scatter."""
    _, _, expert_impl = _latent_paths(cfg, cache_cfg, implementation,
                                      "scatter")

    def prefill(params, pool, tokens, lengths, write_rows):
        B, S = tokens.shape
        x = params["embed"].astype(cfg.dtype)[tokens]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        valid = positions < lengths[:, None]

        def layer(x, carry, p, l):
            made = []

            def attention(i, att, h):
                q_nope, q_rope, rows = _mla_project(cfg, att, h, positions)
                made.append(rows)
                return _mla_expanded(cfg, att, q_nope, q_rope, rows,
                                     lengths)

            x, counts = _shortcut_layer(cfg, params, l, x, attention,
                                        valid, expert_impl)
            return x, carry, (jnp.stack(made), counts)

        x, _, outs = _run_stack(cfg, params, x, None, layer)
        x = x[jnp.arange(B), jnp.maximum(lengths, 1) - 1]
        logits = _logits(cfg, params, x)
        rows = jnp.stack([o[0] for o in outs])       # (L, sub, B, S, W)
        pool = _write_latent(
            pool, jnp.arange(cache_cfg.n_layers), write_rows.reshape(-1),
            rows.reshape(-1, B * S, rows.shape[-1]))
        return logits, pool, _counts([o[1] for o in outs])

    prefill.kv_write = "scatter"
    prefill.passes = cfg.passes
    prefill.counts = True
    return prefill


def _make_latent_extend_fn(cfg: TransformerConfig, cache_cfg,
                           implementation):
    """:func:`make_extend_fn` for latent attention: ``extend(params,
    pool, tokens, positions, lengths, write_rows, window_rows)`` →
    ``(logits, pool, counts)``. Each cache layer's new rows are written,
    then the slot's window of rows is gathered and attended in the
    expanded form."""
    _, _, expert_impl = _latent_paths(cfg, cache_cfg, implementation,
                                      "scatter")
    sub = cfg.sub_blocks

    def extend(params, pool, tokens, positions, lengths, write_rows,
               window_rows):
        x = params["embed"].astype(cfg.dtype)[tokens]
        valid = positions < lengths[:, None]
        flat = write_rows.reshape(-1)

        def layer(x, pool, p, l):
            def attention(i, att, h):
                nonlocal pool
                q_nope, q_rope, rows = _mla_project(cfg, att, h, positions)
                # write THEN gather: query i must see keys 0..i of the span
                pool = _write_latent(pool, jnp.asarray([l * sub + i]), flat,
                                     rows.reshape(1, flat.shape[0], -1))
                with jax.named_scope("kv.gather"):
                    window = _latent_window(cfg, pool, l * sub + i,
                                            window_rows)
                return _mla_expanded(cfg, att, q_nope, q_rope, window,
                                     lengths, positions)

            x, counts = _shortcut_layer(cfg, params, l, x, attention,
                                        valid, expert_impl)
            return x, pool, counts

        x, pool, outs = _run_stack(cfg, params, x, pool, layer)
        return _logits(cfg, params, x), pool, _counts(outs)

    extend.kv_write = "scatter"
    extend.kv_read = "window"
    extend.counts = True
    return extend


def _make_latent_decode_fn(cfg: TransformerConfig, cache_cfg,
                           implementation):
    """:func:`make_decode_fn` for latent attention: ``decode(params,
    pool, tokens, positions, lengths, write_rows, table)`` → ``(logits,
    pool, counts)``, in the absorbed form. ``kv_path == "paged"``: per
    cache layer one kernel over the slot's live blocks of latent rows
    (``paged_attention.latent_attention_decode``), the new rows written
    once after the last layer; ``"window"``: write, gather the slot's
    window, attend in plain jnp."""
    paged, interpret, expert_impl = _latent_paths(cfg, cache_cfg,
                                                  implementation, "window")
    la, sub = cfg.latent, cfg.sub_blocks

    def decode(params, pool, tokens, positions, lengths, write_rows, table):
        x = params["embed"].astype(cfg.dtype)[tokens][:, None]  # (B, 1, D)
        pos_q = positions[:, None]
        valid = lengths > 0
        if paged:
            with jax.named_scope("kv.gather"):
                plan = paged_attention.plan_for(
                    "latent", table, jnp.maximum(lengths - 1, 0),
                    block_size=cache_cfg.block_size)
        latent = pool["latent"]
        made = []

        def layer(x, pool, p, l):
            def attention(i, att, h):
                nonlocal pool
                q_nope, q_rope, row = _mla_project(cfg, att, h, pos_q)
                row = row[:, 0].astype(latent.dtype)
                if paged:
                    row = _pool_wide(row, latent)
                    made.append(row)

                    def attend(q):
                        with jax.named_scope("kv.gather"):
                            return paged_attention.latent_attention_decode(
                                _pool_wide(q, latent), row, latent,
                                l * sub + i, plan, lengths,
                                block_size=cache_cfg.block_size,
                                v_dim=la.kv_rank,
                                sm_scale=la.qk_dim ** -0.5,
                                interpret=interpret)
                else:
                    pool = _write_latent(pool, jnp.asarray([l * sub + i]),
                                         write_rows, row[None])

                    def attend(q):
                        with jax.named_scope("kv.gather"):
                            window = _latent_window(cfg, pool, l * sub + i,
                                                    table)
                        return _window_attend(cfg, q, window, lengths,
                                              positions)
                return _mla_absorbed(cfg, att, q_nope, q_rope,
                                     attend)[:, None]

            x, counts = _shortcut_layer(cfg, params, l, x, attention,
                                        valid, expert_impl)
            return x, pool, counts

        # the paged path only reads the pool inside the stack
        x, carried, outs = _run_stack(cfg, params, x,
                                      None if paged else pool, layer)
        logits = _logits(cfg, params, x[:, 0])
        if paged:
            with jax.named_scope("kv.write"):
                # every kernel has read the pool before a row changes
                latent, logits = jax.lax.optimization_barrier(
                    (latent, logits))
                pool = dict(pool)
                pool["latent"] = paged_attention.write_latent_rows(
                    latent, jnp.stack(made), write_rows, valid)
        else:
            pool = carried
        return logits, pool, _counts(outs)

    decode.kv_path = "paged" if paged else "window"
    decode.kv_layout = "latent" if paged else None
    decode.passes = cfg.passes
    decode.counts = True
    return decode


def make_draft_fn(cfg: TransformerConfig):
    """``draft(params, tokens, lengths)`` → (B,) greedy next token at
    each sequence's end — the speculative-decoding proposal step,
    batched over the decode slots. Full recompute (the draft model is
    small by construction; it keeps no cache state to invalidate on
    preemption or restart)."""

    def draft(params, tokens, lengths):
        last = model_forward(cfg, params, tokens, lengths=lengths,
                             logits_at=jnp.maximum(lengths, 1) - 1)
        return jnp.argmax(last, axis=-1).astype(jnp.int32)

    return jax.jit(draft)


def kv_quantization_probe(cfg: TransformerConfig, params, prompt,
                          kv_dtype: str = "int8", *,
                          n_steps: int = 8, num_blocks: int = 16,
                          block_size: int = 8) -> dict:
    """Measured logit-error bound of a quantized KV pool vs the f32
    reference: run the SAME prompt + greedy continuation through two
    pools (f32 and ``kv_dtype``), feeding the f32 path's tokens to both
    so the trajectories stay aligned, and track the worst absolute
    logit difference and whether any argmax flipped. This is the
    number the README's KV-dtype table documents."""
    from distributed_tensorflow_tpu.serving.kv_cache import (
        BlockAllocator, BlockTable, CacheConfig, init_pool)

    prompt = [int(t) for t in prompt]
    params = canonical_params(cfg, params)
    params = jax.tree_util.tree_map(jnp.asarray, dict(params))
    max_err = 0.0
    argmax_flips = 0
    cfgs = {
        "ref": CacheConfig.for_model(cfg, num_blocks=num_blocks,
                                     block_size=block_size,
                                     kv_dtype="f32"),
        "q": CacheConfig.for_model(cfg, num_blocks=num_blocks,
                                   block_size=block_size,
                                   kv_dtype=kv_dtype),
    }
    state = {}
    for name, cc in cfgs.items():
        alloc = BlockAllocator(cc.num_blocks)
        table = BlockTable(cc, max_blocks=cc.usable_blocks)
        table.ensure_room(len(prompt) + n_steps + 1, alloc)
        pool = init_pool(cc)
        prefill = jax.jit(make_prefill_fn(cfg, cc))
        # both pools through the window path: the probe compares
        # storage types, not the way the step reads them
        decode = jax.jit(make_decode_fn(cfg, cc, implementation="window"))
        toks = np.asarray([prompt], np.int32)
        rows = table.rows(np.arange(len(prompt)))[None]
        last, pool = prefill(params, pool, jnp.asarray(toks),
                             jnp.asarray([len(prompt)], np.int32),
                             jnp.asarray(rows))
        table.length = len(prompt)
        state[name] = (table, pool, decode, np.asarray(last[0]))
    ref_logits = state["ref"][3]
    q_logits = state["q"][3]
    max_err = float(np.max(np.abs(ref_logits - q_logits)))
    argmax_flips += int(np.argmax(ref_logits) != np.argmax(q_logits))
    token = int(np.argmax(ref_logits))       # f32 path drives both
    for _ in range(n_steps):
        outs = {}
        for name in ("ref", "q"):
            table, pool, decode, _ = state[name]
            pos = table.length
            table.length += 1
            logits, pool = decode(
                params, pool, jnp.asarray([token], np.int32),
                jnp.asarray([pos], np.int32),
                jnp.asarray([pos + 1], np.int32),
                jnp.asarray([table.row_of(pos)], np.int32),
                jnp.asarray(table.window_rows()[None]))
            outs[name] = np.asarray(logits[0])
            state[name] = (table, pool, decode, outs[name])
        max_err = max(max_err,
                      float(np.max(np.abs(outs["ref"] - outs["q"]))))
        argmax_flips += int(np.argmax(outs["ref"])
                            != np.argmax(outs["q"]))
        token = int(np.argmax(outs["ref"]))
    return {"kv_dtype": kv_dtype, "max_abs_logit_err": max_err,
            "argmax_flips": argmax_flips,
            "positions_checked": n_steps + 1}


def param_shardings(cfg: TransformerConfig, mesh):
    """NamedShardings for the canonical (stacked-layers) serving
    parameter tree from the SAME logical-axis metadata training uses
    (``LOGICAL_AXIS_RULES`` restricted to the serving mesh):
    heads/mlp/vocab over ``tp``, everything else replicated (a dp×tp
    serving mesh has no fsdp axis, so ``embed``'s fsdp rule maps to
    None)."""
    import dataclasses

    from flax.linen import partitioning as nn_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    # scan_layers=True yields the stacked "layers" tree directly — the
    # canonical layout — with the leading layer axis already unsharded
    # (the "layers" logical axis maps to None).
    shape_cfg = dataclasses.replace(cfg, scan_layers=True, mesh=None)
    model = TransformerLM(shape_cfg)
    rules = mesh_axis_rules(mesh)
    tokens = jnp.zeros((1, min(8, cfg.max_seq_len)), jnp.int32)
    with nn_partitioning.axis_rules(list(rules)):
        var_shapes = jax.eval_shape(
            lambda r: model.init(r, tokens), jax.random.PRNGKey(0))
        logical = nn_partitioning.get_axis_names(var_shapes["params_axes"])
        mesh_specs = nn_partitioning.logical_to_mesh(logical)
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), mesh_specs,
        is_leaf=lambda x: isinstance(x, P))
    return _plain(shardings)
