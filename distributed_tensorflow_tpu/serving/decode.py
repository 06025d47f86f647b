"""Compiled incremental decode for the transformer/BERT family.

Pure, jittable programs over a trained ``TransformerLM`` parameter
tree (stacked-layers layout) and the block-allocated KV pool
(serving/kv_cache.py):

- :func:`make_prefill_fn` — one right-padded mixed-length batch of
  prompts through the FULL forward (the exact math of
  ``models/transformer.TransformerLM``, masked by the factored
  ``ops.attention.length_valid_mask`` rule), writing every position's
  rotary-embedded K and V into the sequences' cache blocks and
  returning each prompt's last-position logits.
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
- :func:`make_extend_fn` — the MULTI-token cache-aware forward: E new
  tokens per slot at explicit absolute positions, written then attended
  against each slot's block window. This is both the prefix-cache
  *start-offset prefill* (a prompt whose first C tokens hash-matched
  cached blocks runs only the suffix through it) and the speculative-
  decoding *verify* step (the target model scores the draft's k tokens
  plus the bonus position in one forward). At E=1 it is exactly
  :func:`make_decode_fn`.

Every program takes and returns the pool as ONE dict (``{"k", "v"}``
plus ``{"k_scale", "v_scale"}`` when the cache config is int8): writes
quantize on the way in, gathers dequantize on the way out, so the whole
quantisation story lives in :func:`_pool_write` / :func:`_pool_window`
and the attention math never sees anything but the compute dtype.

Everything but the paged decode path is plain jnp (no Pallas custom
calls), so on a serving mesh GSPMD partitions the programs directly:
slots over ``dp``, heads/mlp/vocab over ``tp`` (:func:`param_shardings`),
the pool laid out by ``kv_cache.pool_shardings``. GSPMD cannot partition
a ``pallas_call``, so an engine on a mesh asks for the window path.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig, TransformerLM, mesh_axis_rules, rotary_embedding)
from distributed_tensorflow_tpu.ops import paged_attention
from distributed_tensorflow_tpu.ops.attention import mha_reference


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


def model_forward(cfg: TransformerConfig, params, tokens, lengths=None,
                  *, return_kv: bool = False):
    """Full-sequence forward over the canonical parameter tree — the
    serving-side twin of ``TransformerLM.__call__`` (same einsums, same
    order, no sharding-constraint machinery; GSPMD lays it out from the
    caller's in_shardings). ``lengths`` masks a right-padded batch via
    the factored rule. ``return_kv`` additionally returns the per-layer
    post-RoPE K and V stacks ``(L, B, H, S, hd)`` — exactly what prefill
    writes into the cache blocks."""
    dt = cfg.dtype
    embed = params["embed"]
    x = embed.astype(dt)[tokens]                       # (B, S, D)
    ks, vs = [], []
    for l in range(cfg.n_layers):
        p = _layer(params, l)
        h = _rms_norm(x, p["RMSNorm_0"]["scale"], dt)
        att = p["attn"]
        q = jnp.einsum("bsd,dhk->bhsk", h, att["query"].astype(dt))
        k = jnp.einsum("bsd,dhk->bhsk", h, att["key"].astype(dt))
        v = jnp.einsum("bsd,dhk->bhsk", h, att["value"].astype(dt))
        q = rotary_embedding(q, seq_axis=-2)
        k = rotary_embedding(k, seq_axis=-2)
        with jax.named_scope("attn"):
            o = mha_reference(q, k, v, causal=cfg.causal, lengths=lengths)
        o = jnp.einsum("bhsk,hkd->bsd", o, att["out"].astype(dt))
        x = x + o
        h = _rms_norm(x, p["RMSNorm_1"]["scale"], dt)
        mlp = p["mlp"]
        with jax.named_scope("mlp"):
            hh = jnp.einsum("bsd,df->bsf", h, mlp["wi"].astype(dt))
            gate, up = jnp.split(hh, 2, axis=-1)
            hh = jax.nn.silu(gate) * up
            x = x + jnp.einsum("bsf,fd->bsd", hh, mlp["wo"].astype(dt))
        if return_kv:
            ks.append(k)
            vs.append(v)
    x = _rms_norm(x, params["final_norm"]["scale"], dt)
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("bsd,vd->bsv", x, embed.astype(dt))
        logits = logits.astype(jnp.float32)
    if return_kv:
        return logits, (jnp.stack(ks), jnp.stack(vs))
    return logits


def make_prefill_fn(cfg: TransformerConfig, cache_cfg=None):
    """``prefill(params, pool, tokens, lengths, write_rows)``
    → ``(last_logits, pool)``.

    ``tokens`` (B, S) right-padded prompts, ``lengths`` (B,) true
    lengths, ``write_rows`` (B, S) flat pool rows per position (padded
    positions point at the trash block). ``last_logits`` (B, vocab) are
    the logits at each prompt's final REAL position — the first
    generated token's distribution."""
    quantized = cache_cfg.quantized if cache_cfg is not None else False

    def prefill(params, pool, tokens, lengths, write_rows):
        B, S = tokens.shape
        logits, (ks, vs) = model_forward(cfg, params, tokens,
                                         lengths=lengths, return_kv=True)
        L, _, H, _, hd = ks.shape
        rows = write_rows.reshape(-1)                       # (B*S,)
        flat_k = ks.transpose(0, 1, 3, 2, 4).reshape(L, B * S, H, hd)
        flat_v = vs.transpose(0, 1, 3, 2, 4).reshape(L, B * S, H, hd)
        for l in range(L):
            pool = _pool_write(pool, l, rows, flat_k[l], flat_v[l],
                               quantized)
        last = logits[jnp.arange(B), jnp.maximum(lengths, 1) - 1]
        return last, pool

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
      with the trash block: per layer one ``paged_attn_decode`` kernel
      over the slot's live blocks, and after the last layer one in-place
      write of every layer's new row (``paged_kv_write``). "interpret"
      is the same path with the kernels interpreted, for the CPU.
    """
    if not cfg.causal:
        raise ValueError("incremental decode requires a causal model; "
                         "serve bidirectional (BERT) configs through the "
                         "prefill/scoring path")
    quantized = cache_cfg.quantized if cache_cfg is not None else False
    can_page = cache_cfg is not None and paged_attention.supported(
        cache_cfg.num_blocks * cache_cfg.block_size, cache_cfg.block_size,
        cache_cfg.head_dim, cache_cfg.dtype)
    if implementation is None:
        implementation = ("paged" if can_page
                          and jax.default_backend() == "tpu" else "window")
    if implementation not in ("window", "paged", "interpret"):
        raise ValueError(f"implementation={implementation!r}; expected "
                         f"'window', 'paged', 'interpret' or None")
    paged = implementation != "window"
    if paged and not can_page:
        raise ValueError(f"the paged decode path cannot read this pool "
                         f"({cache_cfg}): see ops.paged_attention.supported")
    interpret = implementation == "interpret"

    def decode(params, pool, tokens, positions, lengths, write_rows, table):
        dt = cfg.dtype
        embed = params["embed"]
        x = embed.astype(dt)[tokens]                    # (B, D)
        pos_q = positions[:, None]                      # (B, 1)
        if paged:
            bs = cache_cfg.block_size
            with jax.named_scope("kv.gather"):
                # the keys already in the pool: all but the new token's
                plan = paged_attention.decode_plan(
                    table, jnp.maximum(lengths - 1, 0), block_size=bs)
            ks, vs = [], []
        for l in range(cfg.n_layers):
            p = _layer(params, l)
            h = _rms_norm(x, p["RMSNorm_0"]["scale"], dt)
            att = p["attn"]
            q = jnp.einsum("bd,dhk->bhk", h, att["query"].astype(dt))
            k = jnp.einsum("bd,dhk->bhk", h, att["key"].astype(dt))
            v = jnp.einsum("bd,dhk->bhk", h, att["value"].astype(dt))
            q = rotary_at(q[:, :, None], pos_q)          # (B, H, 1, hd)
            k = rotary_at(k[:, :, None], pos_q)[:, :, 0]  # (B, H, hd)
            if paged:
                # the new token's K and V as the pool will hold them:
                # merged into the softmax from registers, written once
                # after the last layer
                ks.append(k.astype(pool["k"].dtype))
                vs.append(v.astype(pool["v"].dtype))
                with jax.named_scope("kv.gather"):
                    o = paged_attention.paged_attention_decode(
                        q[:, :, 0], ks[-1], vs[-1], pool["k"], pool["v"],
                        l, plan, lengths, block_size=bs,
                        interpret=interpret)
            else:
                # write THEN gather: the query must see its own position
                pool = _pool_write(pool, l, write_rows, k, v, quantized)
                kw, vw = _pool_window(pool, l, table, dt, quantized)
                with jax.named_scope("attn"):
                    o = mha_reference(q, kw, vw, causal=True,
                                      lengths=lengths,
                                      q_positions=positions)[:, :, 0]
            o = jnp.einsum("bhk,hkd->bd", o, att["out"].astype(dt))
            x = x + o
            h = _rms_norm(x, p["RMSNorm_1"]["scale"], dt)
            mlp = p["mlp"]
            with jax.named_scope("mlp"):
                hh = jnp.einsum("bd,df->bf", h, mlp["wi"].astype(dt))
                gate, up = jnp.split(hh, 2, axis=-1)
                hh = jax.nn.silu(gate) * up
                x = x + jnp.einsum("bf,fd->bd", hh, mlp["wo"].astype(dt))
        x = _rms_norm(x, params["final_norm"]["scale"], dt)
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("bd,vd->bv", x, embed.astype(dt))
            logits = logits.astype(jnp.float32)
        if paged:
            with jax.named_scope("kv.write"):
                # every kernel has read the pool before a row changes
                pool_k, pool_v, logits = jax.lax.optimization_barrier(
                    (pool["k"], pool["v"], logits))
                pool = dict(pool)
                pool["k"], pool["v"] = paged_attention.write_rows(
                    pool_k, pool_v, jnp.stack(ks), jnp.stack(vs),
                    write_rows, lengths > 0, interpret=interpret)
        return logits, pool

    decode.kv_path = "paged" if paged else "window"
    return decode


def make_extend_fn(cfg: TransformerConfig, cache_cfg=None):
    """``extend(params, pool, tokens, positions, lengths, write_rows,
    window_rows)`` → ``(logits, pool)`` — E tokens per slot in one
    cache-aware forward.

    ``tokens`` (B, E) the new tokens (right-padded), ``positions``
    (B, E) their ABSOLUTE cache positions (padded entries must point at
    or past ``lengths`` so the factored mask zeroes them), ``lengths``
    (B,) the post-write visible length, ``write_rows`` (B, E) flat pool
    rows (padded entries at the trash block), ``window_rows`` (B, W)
    the block-window gather index. Returns logits for ALL E positions
    — row ``i`` is the next-token distribution after the token fed at
    ``positions[:, i]``.

    Two callers, one program: prefix-cache suffix prefill (positions
    ``C..L-1`` of a prompt whose first C tokens hash-matched) and
    speculative-decode verification (positions ``L-1..L+k-1``: the
    banked token plus k draft proposals, scored in one step). Per-query
    math is position-independent, so row 0 of a (B, E) extend is
    bitwise the row a (B,) decode on the window path produces at the
    same position (on the paged path the two agree to float32 rounding
    before the output cast: the kernel's softmax is online) — the
    greedy-parity contract extends to both callers."""
    if not cfg.causal:
        raise ValueError("extend requires a causal model; serve "
                         "bidirectional (BERT) configs through the "
                         "prefill/scoring path")
    quantized = cache_cfg.quantized if cache_cfg is not None else False

    def extend(params, pool, tokens, positions, lengths, write_rows,
               window_rows):
        dt = cfg.dtype
        B, E = tokens.shape
        embed = params["embed"]
        x = embed.astype(dt)[tokens]                    # (B, E, D)
        rows = write_rows.reshape(-1)                   # (B*E,)
        for l in range(cfg.n_layers):
            p = _layer(params, l)
            h = _rms_norm(x, p["RMSNorm_0"]["scale"], dt)
            att = p["attn"]
            q = jnp.einsum("bsd,dhk->bhsk", h, att["query"].astype(dt))
            k = jnp.einsum("bsd,dhk->bhsk", h, att["key"].astype(dt))
            v = jnp.einsum("bsd,dhk->bhsk", h, att["value"].astype(dt))
            q = rotary_at(q, positions)                  # (B, H, E, hd)
            k = rotary_at(k, positions)
            # write THEN gather: query i must see keys 0..i of the span
            flat_k = k.transpose(0, 2, 1, 3).reshape(B * E, k.shape[1],
                                                     k.shape[3])
            flat_v = v.transpose(0, 2, 1, 3).reshape(B * E, v.shape[1],
                                                     v.shape[3])
            pool = _pool_write(pool, l, rows, flat_k, flat_v, quantized)
            kw, vw = _pool_window(pool, l, window_rows, dt, quantized)
            with jax.named_scope("attn"):
                o = mha_reference(q, kw, vw, causal=True, lengths=lengths,
                                  q_positions=positions)  # (B, H, E, hd)
            o = jnp.einsum("bhsk,hkd->bsd", o, att["out"].astype(dt))
            x = x + o
            h = _rms_norm(x, p["RMSNorm_1"]["scale"], dt)
            mlp = p["mlp"]
            with jax.named_scope("mlp"):
                hh = jnp.einsum("bsd,df->bsf", h, mlp["wi"].astype(dt))
                gate, up = jnp.split(hh, 2, axis=-1)
                hh = jax.nn.silu(gate) * up
                x = x + jnp.einsum("bsf,fd->bsd", hh, mlp["wo"].astype(dt))
        x = _rms_norm(x, params["final_norm"]["scale"], dt)
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("bsd,vd->bsv", x, embed.astype(dt))
            return logits.astype(jnp.float32), pool

    return extend


def make_draft_fn(cfg: TransformerConfig):
    """``draft(params, tokens, lengths)`` → (B,) greedy next token at
    each sequence's end — the speculative-decoding proposal step,
    batched over the decode slots. Full recompute (the draft model is
    small by construction; it keeps no cache state to invalidate on
    preemption or restart)."""

    def draft(params, tokens, lengths):
        logits = model_forward(cfg, params, tokens, lengths=lengths)
        last = logits[jnp.arange(tokens.shape[0]),
                      jnp.maximum(lengths, 1) - 1]
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
    number the README's KV-dtype table documents and ``bench.py
    --serving --kv-dtype int8`` stamps into its row."""
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
