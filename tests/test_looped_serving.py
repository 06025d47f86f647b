"""A looped stack behind the paged-KV engine (ISSUE 28): prefill, extend
and decode run the pass loop as a loop of the program, every pass of
every layer has its own cache layer, and the logits that come through
the cache agree with the plain reference's full forward
(``benchmark/reference_looped.py``) on seeded random weights: on the
window path, on the interpreted paged path of a pool that lies
rows-on-lanes (``head_dim`` 16) and of one that lies row-major
(``head_dim`` 128)."""

import functools
import glob
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import reference_looped  # noqa: E402
from distributed_tensorflow_tpu.models.transformer import (  # noqa: E402
    TransformerConfig)
from distributed_tensorflow_tpu.ops import paged_attention  # noqa: E402
from distributed_tensorflow_tpu.serving import decode as decode_lib  # noqa: E402
from distributed_tensorflow_tpu.serving.engine import (  # noqa: E402
    InferenceEngine, params_digest)
from distributed_tensorflow_tpu.serving.kv_cache import (  # noqa: E402
    TRASH_BLOCK, BlockAllocator, BlockTable, CacheConfig, init_pool)
from distributed_tensorflow_tpu.serving.scheduler import Request  # noqa: E402
from test_looped_model import LOOPED, seeded_params  # noqa: E402

#: float32 model, float32 pool, float32 reference: what is left is the
#: order of summation over six layer applications and the online softmax
#: (measured under 3e-6). A bfloat16 pool moves a logit by 3e-3 and an
#: int8 one by 1e-2 (``test_a_coarser_cache_fails_the_tolerance``).
ATOL = 5e-5
pytestmark = pytest.mark.usefixtures("leave_no_programs_behind")
BLOCK, BLOCKS, SLOTS = 8, 32, 2
SHAPES = {
    "window": (dict(), "window", None),
    "paged-lanes": (dict(), "interpret", "lanes"),
    "paged-rows": (dict(d_model=1024, n_heads=8), "interpret", "rows"),
}


def _noise(cfg):
    """``seeded_params``' noise: less at a width of 1024 (see there)."""
    return 0.1 if cfg.d_model == 64 else 0.01


@pytest.fixture(scope="module", params=list(SHAPES))
def served(request):
    """``(cfg, params, implementation, layout)`` per path."""
    shape, impl, layout = SHAPES[request.param]
    cfg = TransformerConfig.tiny(**{**LOOPED, **shape})
    return cfg, seeded_params(cfg, seed=3, noise=_noise(cfg)), impl, layout


def reference_logits(cfg, params, tokens):
    """The last pass's float32 logits at every position."""
    return np.asarray(reference_looped.forward(
        params, np.asarray([tokens]), passes=cfg.passes,
        rope_base=cfg.rope_base, every_pass=False)[0][-1, 0])


class Cache:
    """One sequence in slot 0 of a two-slot batch (slot 1 stays idle),
    driven through the programs themselves."""

    def __init__(self, cfg, params, impl="window", **cache):
        self.cfg = cfg
        self.cc = CacheConfig.for_model(cfg, num_blocks=BLOCKS,
                                        block_size=BLOCK, **cache)
        self.params = jax.tree_util.tree_map(
            jnp.asarray, decode_lib.canonical_params(cfg, params))
        self.alloc = BlockAllocator(BLOCKS)
        self.alloc.alloc(3)                  # not from the pool's start
        self.table = BlockTable(self.cc, max_blocks=cfg.max_seq_len // BLOCK)
        self.pool = init_pool(self.cc)
        self.decode_fn = decode_lib.make_decode_fn(cfg, self.cc,
                                                   implementation=impl)
        self._prefill = jax.jit(decode_lib.make_prefill_fn(cfg, self.cc))
        self._decode = jax.jit(self.decode_fn)
        self._extend = jax.jit(decode_lib.make_extend_fn(cfg, self.cc))

    def prefill(self, tokens):
        n, width = len(tokens), 32
        self.table.ensure_room(n, self.alloc)
        toks = np.zeros((1, width), np.int32)
        toks[0, :n] = tokens
        rows = np.zeros((1, width), np.int32)
        rows[0, :n] = self.table.rows(np.arange(n))
        last, self.pool = self._prefill(
            self.params, self.pool, jnp.asarray(toks),
            jnp.asarray([n], np.int32), jnp.asarray(rows))
        self.table.length = n
        return np.asarray(last[0])

    def extend(self, tokens, width=8):
        c, n = self.table.length, len(tokens)
        self.table.ensure_room(n, self.alloc)
        toks = np.zeros((1, width), np.int32)
        toks[0, :n] = tokens
        pos = np.full((1, width), self.cfg.max_seq_len, np.int32)
        pos[0, :n] = np.arange(c, c + n)
        rows = np.zeros((1, width), np.int32)
        rows[0, :n] = self.table.rows(np.arange(c, c + n))
        logits, self.pool = self._extend(
            self.params, self.pool, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray([c + n], np.int32), jnp.asarray(rows),
            jnp.asarray(self.table.window_rows()[None]))
        self.table.length = c + n
        return np.asarray(logits[0, :n])

    def decode(self, token, pool=None):
        pos = self.table.length
        self.table.ensure_room(1, self.alloc)
        self.table.length += 1
        if self.decode_fn.kv_path == "paged":
            table = np.full((SLOTS, self.table.max_blocks), TRASH_BLOCK,
                            np.int32)
            table[0, :len(self.table.blocks)] = self.table.blocks
        else:
            table = np.zeros((SLOTS, self.cfg.max_seq_len), np.int32)
            table[0] = self.table.window_rows()
        slot = lambda a: jnp.asarray([a, 0], np.int32)
        logits, new_pool = self._decode(
            self.params, self.pool if pool is None else pool, slot(token),
            slot(pos), slot(pos + 1), slot(self.table.row_of(pos)),
            jnp.asarray(table))
        if pool is None:
            self.pool = new_pool
        return np.asarray(logits[0]), new_pool


def through_the_cache(cfg, params, tokens, n_prompt, impl, **cache):
    """Logits at positions ``n_prompt - 1 ..`` of ``tokens``: prefill of
    the prompt, then one decode step per following token."""
    c = Cache(cfg, params, impl, **cache)
    out = [c.prefill(tokens[:n_prompt])]
    for token in tokens[n_prompt:]:
        out.append(c.decode(token)[0])
    return np.stack(out), c


TOKENS = [int(t) for t in np.random.default_rng(5).integers(0, 128, 30)]


def test_prefill_then_decode_match_the_references_full_forward(served):
    cfg, params, impl, layout = served
    got, cache = through_the_cache(cfg, params, TOKENS, 19, impl)
    assert cache.decode_fn.kv_path == ("window" if impl == "window"
                                       else "paged")
    assert cache.decode_fn.kv_layout == layout
    assert cache.decode_fn.passes == cfg.passes
    want = reference_logits(cfg, params, TOKENS)[18:]
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("kv_dtype,least", [("bf16", 2e-3), ("int8", 3e-3)])
def test_a_coarser_cache_fails_the_tolerance(kv_dtype, least):
    """The same comparison with the pool in a narrower type misses by
    forty times the tolerance and more: it is tight enough to tell."""
    cfg = TransformerConfig.tiny(**LOOPED)
    params = seeded_params(cfg, seed=3)
    got, _ = through_the_cache(cfg, params, TOKENS, 19, "window",
                               kv_dtype=kv_dtype)
    err = np.max(np.abs(got - reference_logits(cfg, params, TOKENS)[18:]))
    assert err > least >= 40 * ATOL


def test_a_prefix_hit_and_an_extend_match_a_cold_prefill(served):
    """Sixteen tokens in the cache (two whole blocks, as a prefix hit
    leaves them), the next five through ``extend``: every position's
    logits are the reference's, and the last is a cold prefill's."""
    cfg, params, impl, _ = served
    warm = Cache(cfg, params, impl)
    warm.prefill(TOKENS[:16])
    got = warm.extend(TOKENS[16:21])
    want = reference_logits(cfg, params, TOKENS[:21])
    np.testing.assert_allclose(got, want[16:21], atol=ATOL)
    cold = Cache(cfg, params, impl).prefill(TOKENS[:21])
    np.testing.assert_allclose(got[-1], cold, atol=ATOL)
    # and decode goes on from the extended cache as from a cold one
    np.testing.assert_allclose(
        warm.decode(TOKENS[21])[0],
        reference_logits(cfg, params, TOKENS[:22])[21], atol=ATOL)


def test_the_pool_holds_a_slice_for_every_layer_and_pass(served):
    """``layers x passes`` cache layers, all different, pass-major; a
    pass's slices feed that pass's attention alone: with the LAST
    pass's slices zeroed the rows a decode step writes for the earlier
    passes are bit for bit what they were and the logits are not."""
    cfg, params, impl, _ = served
    cache = Cache(cfg, params, impl)
    assert cache.cc.n_layers == cfg.n_layers * cfg.passes == 6
    assert cache.pool["k"].shape == (6, BLOCKS * BLOCK, cfg.n_heads,
                                     cfg.head_dim)
    assert cache.cc.bytes_per_token == 6 * 2 * cfg.d_model * 4
    cache.prefill(TOKENS[:19])
    rows = cache.table.rows(np.arange(19))
    k = np.asarray(cache.pool["k"])[:, rows]
    for a in range(6):
        for b in range(a):
            assert np.abs(k[a] - k[b]).max() > 1e-3, (a, b)
    first_of_last = (cfg.passes - 1) * cfg.n_layers
    zeroed = {n: a.at[first_of_last:].set(0)
              for n, a in cache.pool.items()}
    logits, pool = cache.decode(TOKENS[19], pool=cache.pool)
    cache.table.length -= 1                  # the same step once more
    logits0, pool0 = cache.decode(TOKENS[19], pool=zeroed)
    row = cache.table.row_of(19)
    for name in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(pool[name])[:first_of_last + 1, row],
            np.asarray(pool0[name])[:first_of_last + 1, row])
    assert np.abs(logits - logits0).max() > 1e-3
    # the last pass's second layer attends over zeroed keys: its own
    # new row differs
    assert np.abs(np.asarray(pool["k"])[-1, row]
                  - np.asarray(pool0["k"])[-1, row]).max() > 1e-6


def test_the_layout_decides_the_kernel():
    """``supported`` names the layout the device keeps a pool in."""
    ok = lambda *a, **k: paged_attention.supported(*a, **k)
    assert ok(4096, 16, 64, jnp.bfloat16) == "lanes"
    assert ok(4096, 16, 128, jnp.bfloat16, 16) == "rows"
    assert ok(4096, 16, 256, jnp.bfloat16, 16) == "rows"
    assert ok(4096, 16, 128, jnp.bfloat16, 8) is None     # half a tile
    assert ok(4096, 16, 128, jnp.float32, 8) == "rows"
    assert ok(4096, 16, 192, jnp.bfloat16, 16) is None    # pads
    assert ok(4104, 8, 128, jnp.bfloat16, 16) == "rows"   # any row count
    assert ok(4104, 8, 64, jnp.bfloat16) is None
    assert ok(4096, 16, 128, jnp.int8, 16) is None


def _paged(engine):
    """The engine's decode program on the interpreted paged path (the
    CPU takes the window path when left alone)."""
    decode = decode_lib.make_decode_fn(engine.cfg, engine.cache_cfg,
                                       implementation="interpret")
    if engine.decode_steps > 1:
        decode = decode_lib.make_multi_decode_fn(decode, engine.decode_steps)
    engine._decode_next = jax.jit(
        decode_lib.launch_decode(decode, engine.decode_steps),
        donate_argnums=(1,))
    engine.kv_path = decode.kv_path
    engine._kv_layout = decode.kv_layout
    return engine


def test_engine_serves_the_looped_model_with_prefix_caching(served,
                                                            tmp_path):
    """``submit`` / ``step`` with the prefix cache on: a cold request,
    then one that shares its first two blocks (admitted through
    ``extend``), both greedy under the reference; the spans say how
    many passes each program ran and what the decode step read."""
    cfg, params, impl, layout = served
    paged = impl != "window"
    engine = InferenceEngine(cfg, params, num_blocks=BLOCKS,
                             block_size=BLOCK, max_slots=SLOTS,
                             max_prompt_len=32, prefix_caching=True)
    assert engine.cache_cfg.n_layers == 6
    if paged:
        _paged(engine)
    prompts = {"a": TOKENS[:19], "b": TOKENS[:16] + TOKENS[20:25]}
    jax.profiler.start_trace(str(tmp_path))
    try:
        done = {}
        for rid, prompt in prompts.items():
            engine.submit(Request(id=rid, tokens=tuple(prompt),
                                  max_new_tokens=4))
            done.update(engine.run_until_idle())
    finally:
        jax.profiler.stop_trace()
    for rid, prompt in prompts.items():
        seq = list(prompt) + list(done[rid]["tokens"])
        logits = reference_logits(cfg, params, seq)[len(prompt) - 1:-1]
        picked = logits[np.arange(4), done[rid]["tokens"]]
        assert (logits.max(-1) - picked).max() <= ATOL, rid
    assert engine.block_accounting()["conserved"]
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = [(e.name, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name in ("serve.prefill", "serve.decode")]
    prefills = [s for n, s in spans if n == "serve.prefill"]
    assert [(p["program"], p["passes"]) for p in prefills] == [
        ("prefill", 3), ("extend", 3)]
    # the spans that read a launch carry what it read
    decodes = [s for n, s in spans if n == "serve.decode"
               and "token_steps" in s]
    assert decodes and all(
        d["passes"] == 3 and d["cache_layers"] == 6 for d in decodes)
    # one sequence at a time: the first decode step after a prompt of n
    # tokens reads n + 1 rows
    assert decodes[0]["rows_read"] == 20
    if paged:
        # a run: in a row-major pool BLOCKS_PER_STEP entries of the
        # table; in the other, the blocks of one 128-row group that
        # follow one another
        assert decodes[0]["blocks_read"] == 3
        assert decodes[0]["runs_read"] == 1
    else:
        assert "runs_read" not in decodes[0]


def test_resident_kernels_give_the_programs_the_same_logits(served):
    """``resident_params`` (projection kernels as ``(H * hd, D)``
    matrices, what the engine keeps of bfloat16 weights) through
    prefill, extend and decode: the reference's logits, as with the
    model's own ``(D, H, hd)``; and the reference reads that tree too."""
    cfg, params, impl, _ = served
    resident = decode_lib.resident_params(cfg, params)
    shape = resident["layers"]["attn"]["query"].shape
    assert shape == (cfg.n_layers, cfg.d_model, cfg.d_model)
    assert decode_lib.resident_params(cfg, resident)[
        "layers"]["attn"]["key"].shape == shape          # idempotent
    want = reference_logits(cfg, params, TOKENS[:22])
    np.testing.assert_allclose(reference_logits(cfg, resident, TOKENS[:22]),
                               want, atol=1e-6)
    cache = Cache(cfg, resident, impl)
    cache.prefill(TOKENS[:16])
    np.testing.assert_allclose(cache.extend(TOKENS[16:21]), want[16:21],
                               atol=ATOL)
    np.testing.assert_allclose(cache.decode(TOKENS[21])[0], want[21],
                               atol=ATOL)


def test_engine_keeps_bfloat16_weights_resident():
    """Weights that arrive in the compute type stay as they are on the
    device and the engine holds ONE tree of them: what the programs take
    is ``engine.params``. Where that type is 16 bits wide and a head 128
    or more, the projection kernels lie as matrices; under that the
    device keeps the model's own form with ``D`` on the lanes, and so
    does the engine."""
    import dataclasses
    cfg = TransformerConfig.tiny(**LOOPED)
    half = dataclasses.replace(cfg, dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16)
    wide = dataclasses.replace(half, d_model=1024, n_heads=8)
    for c, want in ((cfg, (2, 64, 4, 16)), (half, (2, 64, 4, 16)),
                    (wide, (2, 1024, 1024))):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(c.param_dtype),
            seeded_params(dataclasses.replace(c, param_dtype=jnp.float32),
                          seed=3, noise=0.01))
        engine = InferenceEngine(c, params, num_blocks=BLOCKS,
                                 block_size=BLOCK, max_slots=SLOTS)
        assert engine.served_params is engine.params
        assert engine.params["layers"]["attn"]["value"].shape == want
        assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(
            engine.params)} == {jnp.dtype(c.param_dtype)}
        engine.submit(Request(id="r", tokens=tuple(TOKENS[:9]),
                              max_new_tokens=3))
        assert len(engine.run_until_idle()["r"]["tokens"]) == 3


# -- float32 weights, a 16-bit compute type: rounded once, when taken -------

#: the block designs the engine serves, computing in bfloat16 from
#: float32 weights as ``tbig_serve`` does: one pass over unstacked
#: layers with a tied head; the looped stack with its own head, post
#: norms and an exit gate, with heads of 16 (served in the model's
#: shapes) and of 128 (projection kernels served as matrices)
ROUNDED = {
    "one-pass": dict(vocab_size=128, max_seq_len=64, scan_layers=False),
    "looped": LOOPED,
    "looped-head-128": {**LOOPED, "d_model": 1024, "n_heads": 8},
}
#: the leaves the programs multiply by in the compute type; every other
#: leaf (norm scales, the exit gate) they use as it arrives
MATRICES = ("embed", "lm_head", "attn", "mlp")


@pytest.fixture(scope="module", params=list(ROUNDED))
def rounded(request):
    """``(cfg, float32 params)``, ``cfg.dtype`` bfloat16."""
    cfg = TransformerConfig.tiny(**{**ROUNDED[request.param],
                                    "dtype": jnp.bfloat16})
    return cfg, seeded_params(cfg, seed=3, noise=_noise(cfg))


def _engine(cfg, params):
    return InferenceEngine(cfg, params, num_blocks=BLOCKS, block_size=BLOCK,
                           max_slots=SLOTS, max_prompt_len=32,
                           prefix_caching=True)


def _is_matrix(path):
    return any(getattr(k, "key", None) in MATRICES for k in path)


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _assert_rounded_once(cfg, served, masters):
    """Every matrix of ``served`` is ``astype(cfg.dtype)`` of its master
    bit for bit, once the resident relayout of the three projection
    stacks is undone (heads of 128: ``decode.wants_resident``); every
    other leaf is the master's, in float32."""
    masters = decode_lib.canonical_params(cfg, masters)
    flat, treedef = jax.tree_util.tree_flatten_with_path(served)
    want, want_def = jax.tree_util.tree_flatten_with_path(masters)
    assert treedef == want_def
    seen = set()
    for (path, got), (_, master) in zip(flat, want):
        assert master.dtype == jnp.float32, path
        if not _is_matrix(path):
            assert got.dtype == jnp.float32, path
            np.testing.assert_array_equal(_bits(got), _bits(master))
            continue
        seen.add(path[-1].key)
        assert got.dtype == jnp.dtype(cfg.dtype), path
        if (path[-1].key in ("query", "key", "value")
                and decode_lib.wants_resident(cfg)):
            assert got.shape == (cfg.n_layers, cfg.d_model, cfg.d_model)
            got = got.transpose(0, 2, 1).reshape(master.shape)
        np.testing.assert_array_equal(
            _bits(got), _bits(master.astype(cfg.dtype)), err_msg=str(path))
    assert seen == {"embed", "query", "key", "value", "out", "wi", "wo"} | (
        set() if cfg.tie_embeddings else {"lm_head"})


def _serve_a_mixed_batch(engine, tag=""):
    """A cold prompt (prefill), then one that shares its first two
    blocks (extend, a prefix hit) beside an unrelated one, the two
    decoding together: ``{id: tokens}``."""
    hits = engine.stats()["prefix_cache"]["hit_requests"]
    engine.submit(Request(id=tag + "a", tokens=tuple(TOKENS[:19]),
                          max_new_tokens=5))
    done = dict(engine.run_until_idle())
    engine.submit(Request(id=tag + "b",
                          tokens=tuple(TOKENS[:16] + TOKENS[20:25]),
                          max_new_tokens=6))
    engine.submit(Request(id=tag + "c", tokens=tuple(TOKENS[7:18]),
                          max_new_tokens=4))
    done.update(engine.run_until_idle())
    assert engine.stats()["prefix_cache"]["hit_requests"] == hits + 1
    assert engine.block_accounting()["conserved"]
    return {rid[len(tag):]: list(r["tokens"]) for rid, r in done.items()}


def test_float32_weights_are_rounded_once_and_the_masters_kept(rounded):
    """An engine that computes in bfloat16 from float32 weights hands its
    programs a second tree, each matrix rounded once; ``engine.params``
    stays the float32 canonical tree (what the benchmark's reference
    reads, what ``weights_digest`` is of). It serves the tokens of an
    engine given the same weights already rounded, which holds one tree."""
    cfg, params = rounded
    engine = _engine(cfg, params)
    canonical = jax.tree_util.tree_map(
        jnp.asarray, decode_lib.canonical_params(cfg, params))
    assert engine.served_params is not engine.params
    got, want = (jax.tree_util.tree_flatten_with_path(t)
                 for t in (engine.params, canonical))
    assert got[1] == want[1]
    for (path, a), (_, b) in zip(got[0], want[0]):
        assert (a.shape, a.dtype) == (b.shape, jnp.float32), path
        np.testing.assert_array_equal(_bits(a), _bits(b))
    assert engine.weights_digest == params_digest(canonical)
    _assert_rounded_once(cfg, engine.served_params, params)

    already = _engine(cfg, decode_lib.compute_params(cfg, canonical))
    assert already.served_params is already.params
    tokens = _serve_a_mixed_batch(engine)
    assert tokens == _serve_a_mixed_batch(already)
    assert sorted(map(len, tokens.values())) == [4, 5, 6]


def test_a_hot_swap_of_float32_weights_compiles_nothing(rounded):
    """``install_version`` rounds the new float32 tree the same way, so
    the programs see the types and shapes they were compiled for: no
    jitted function gains an entry, and what is served is the new
    weights' (the tokens of an engine built on them)."""
    cfg, params = rounded
    engine = _engine(cfg, params)
    before = _serve_a_mixed_batch(engine)
    programs = (engine._prefill_next, engine._decode_next,
                engine._extend_next)
    sizes = [p._cache_size() for p in programs]
    assert all(sizes)
    digest = engine.weights_digest
    new = seeded_params(cfg, seed=11, noise=_noise(cfg))
    engine.install_version(new)
    assert engine.weights_digest != digest
    _assert_rounded_once(cfg, engine.served_params, new)
    swapped = _serve_a_mixed_batch(engine, tag="s-")
    assert [p._cache_size() for p in programs] == sizes
    assert swapped == _serve_a_mixed_batch(_engine(cfg, new))
    assert swapped != before


def _weight_converts(engine, params):
    """The float-to-compute-type ``convert``s of whole weights or of one
    layer's slice of them in the decode, prefill and extend programs as
    lowered for ``params``, by program."""
    cfg, B, W = engine.cfg, engine.max_slots, engine.window
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    # what each launch takes after params and pool: the chosen tokens
    # and the one array the host sends (the CPU's window path)
    args = {"decode": (engine._decode_next, (i32(B), i32((B, 4 + W)))),
            "prefill": (engine._prefill_next,
                        (i32(B), i32(2 + 2 * engine.max_seq_len))),
            "extend": (engine._extend_next, (i32(B), i32(2 + 3 * 8 + W)))}
    shapes = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if _is_matrix(path):
            shapes |= {leaf.shape, leaf.shape[1:]}
    sizes = {"x".join(map(str, s)) for s in shapes}
    found = {}
    for name, (program, rest) in args.items():
        text = program.lower(params, engine.pool, *rest).as_text()
        assert "stablehlo.convert" in text        # the dialect read below
        found[name] = [
            m.group(0) for m in re.finditer(
                r"stablehlo\.convert [^\n]*tensor<([0-9x]+)xf32>\) -> "
                r"tensor<[0-9x]+xbf16>", text) if m.group(1) in sizes]
    return found


def test_the_lowered_programs_convert_no_weight(rounded):
    """The guard that the casts do not come back: lowered for the served
    tree, no program of the bfloat16 engine converts an array of a
    weight's (or one layer of a stacked weight's) shape from float32;
    lowered for the float32 masters, which is what every run did before,
    each of the three converts every matrix."""
    cfg, params = rounded
    engine = _engine(cfg, params)
    assert _weight_converts(engine, engine.served_params) == {
        "decode": [], "prefill": [], "extend": []}
    before = _weight_converts(engine, engine.params)
    matrices = 7 if cfg.tie_embeddings else 8
    assert all(len(found) >= matrices for found in before.values()), before


# -- several decode steps in one launch (``decode_steps``) -------------------

def test_several_steps_in_one_program_choose_what_single_steps_choose(served):
    """``make_multi_decode_fn``: five greedy steps in one program pick the
    tokens five launches pick and leave the pool they leave; a slot whose
    budget ends stops writing, an idle slot never writes."""
    cfg, params, impl, _ = served
    single = Cache(cfg, params, impl)
    logits = single.prefill(TOKENS[:11])
    first, picked = int(np.argmax(logits)), []
    token = first
    for _ in range(5):
        token = int(np.argmax(single.decode(token)[0]))
        picked.append(token)

    for budget in (5, 3):
        many = Cache(cfg, params, impl)
        many.prefill(TOKENS[:11])
        fn = jax.jit(decode_lib.make_multi_decode_fn(many.decode_fn, 5))
        assert (fn.__wrapped__.kv_path, fn.__wrapped__.passes) == (
            many.decode_fn.kv_path, cfg.passes)
        many.table.ensure_room(5, many.alloc)
        if many.decode_fn.kv_path == "paged":
            table = np.full((SLOTS, many.table.max_blocks), TRASH_BLOCK,
                            np.int32)
            table[0, :len(many.table.blocks)] = many.table.blocks
        else:
            table = np.zeros((SLOTS, cfg.max_seq_len), np.int32)
            table[0] = many.table.window_rows()
        rows = np.zeros((SLOTS, 5), np.int32)
        rows[0] = many.table.rows(np.arange(11, 16))
        slot = lambda a: jnp.asarray([a, 0], np.int32)
        chosen, pool = fn(many.params, many.pool, slot(first), slot(11),
                          slot(12), jnp.asarray(rows), jnp.asarray(table),
                          slot(budget))
        assert list(np.asarray(chosen)[0, :budget]) == picked[:budget]
        # the rows the budget reached hold what the single steps wrote,
        # the rows past it were never touched (trash row 0 aside)
        mine, theirs = (np.asarray(p["k"]) for p in (pool, single.pool))
        written = rows[0, :budget]
        np.testing.assert_array_equal(mine[:, written], theirs[:, written])
        assert not mine[:, rows[0, budget:]].any()


def _answers(cfg, params, impl, requests, steps, **engine):
    eng = InferenceEngine(cfg, params, block_size=BLOCK, max_slots=SLOTS,
                          max_prompt_len=32, prefix_caching=True,
                          decode_steps=steps,
                          **{"num_blocks": BLOCKS, **engine})
    if impl != "window":
        _paged(eng)
    for request in requests:
        eng.submit(request)
    done = eng.run_until_idle()
    assert eng.block_accounting()["conserved"]
    return {rid: rec["tokens"] for rid, rec in done.items()}, eng


def test_engine_with_decode_steps_gives_the_answers_of_single_steps(served):
    """Three sequences on two slots with a shared prefix, a budget that
    ends inside a launch, one token only, and an end-of-sequence token
    inside a launch: ``decode_steps=3`` serves the tokens
    ``decode_steps=1`` serves, in a third of the launches."""
    cfg, params, impl, _ = served
    prompts = [("a", TOKENS[:19], 7), ("b", TOKENS[:16] + TOKENS[20:25], 4),
               ("c", TOKENS[5:14], 1), ("d", TOKENS[3:12], 8)]
    plain = [Request(id=i, tokens=tuple(p), max_new_tokens=n)
             for i, p, n in prompts]
    one, eng1 = _answers(cfg, params, impl, plain, 1)
    many, eng3 = _answers(cfg, params, impl, plain, 3)
    assert many == one and [len(one[i]) for i, _, _ in prompts] == [7, 4, 1, 8]
    assert eng3._step_idx < eng1._step_idx
    # an end-of-sequence token in the middle of a launch: the tokens the
    # launch computed past it are dropped
    eos = one["d"][4]
    stop = one["d"].index(eos)
    ending = [Request(id=r.id, tokens=r.tokens, eos_id=eos,
                      max_new_tokens=r.max_new_tokens) for r in plain]
    one_e, _ = _answers(cfg, params, impl, ending, 1)
    many_e, _ = _answers(cfg, params, impl, ending, 3)
    assert many_e == one_e and one_e["d"] == one["d"][:stop + 1]


def test_decode_steps_under_preemption_and_its_limits():
    """A pool too small for both sequences at their full length: the
    multi-step engine preempts and replays as the single-step one does,
    and the answers are those of a pool with room. ``decode_steps`` is at
    least 1 and excludes speculation."""
    shape, impl, _ = SHAPES["window"]
    cfg = TransformerConfig.tiny(**{**LOOPED, **shape})
    params = seeded_params(cfg, seed=3, noise=0.1)
    requests = [Request(id=i, tokens=tuple(TOKENS[a:a + 12]),
                        max_new_tokens=14) for i, a in (("a", 0), ("b", 9))]
    roomy, _ = _answers(cfg, params, impl, requests, 4)
    tight, eng = _answers(cfg, params, impl, requests, 4, num_blocks=7)
    assert eng.scheduler.preemptions > 0 and tight == roomy
    assert roomy == _answers(cfg, params, impl, requests, 1)[0]
    for bad in (dict(decode_steps=0),
                dict(decode_steps=2, speculative_k=2)):
        with pytest.raises(ValueError, match="decode_steps"):
            InferenceEngine(cfg, params, num_blocks=BLOCKS,
                            block_size=BLOCK, max_slots=SLOTS, **bad)


def test_the_spans_of_a_multi_step_launch_count_its_inner_steps(tmp_path):
    """``serve.decode`` of a ``decode_steps=3`` engine: ``token_steps``
    the tokens the launch computed, ``rows_read`` the live rows of every
    inner step, ``passes`` what one token runs (so passes x token_steps
    over the tokens committed reads the model's passes exactly)."""
    shape, impl, _ = SHAPES["window"]
    cfg = TransformerConfig.tiny(**{**LOOPED, **shape})
    params = seeded_params(cfg, seed=3, noise=0.1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _answers(cfg, params, impl, [Request(
            id="a", tokens=tuple(TOKENS[:19]), max_new_tokens=6)], 3)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = [(e.name, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name in ("serve.decode", "serve.decode.commit")]
    decodes = [s for n, s in spans if n == "serve.decode"
               and "token_steps" in s]
    launches = [s for n, s in spans if n == "serve.decode"
                and s.get("launched")]
    commits = [s for n, s in spans if n == "serve.decode.commit"]
    # the prefill's token, then launches of 3 and 2 tokens, each read in
    # the step after its own (the first read takes the prefill's alone)
    assert [d["token_steps"] for d in decodes] == [3, 2]
    assert [c["tokens"] for c in commits] == [0, 3, 2]
    assert [d["rows_read"] for d in decodes] == [20 + 21 + 22, 23 + 24]
    assert all(d["passes"] == 3 for d in decodes)
    # each launch fed a token the host had not read yet
    assert [(d["live"], d["ahead"]) for d in launches] == [(1, 1), (1, 1)]
