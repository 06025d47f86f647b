"""Latent (MLA) attention behind the paged engine, with shortcut-connected
layers of sparse experts, at a small size on the CPU: the three serving
programs against the plain reference (``models/scmoe_reference.py``) on
logits, the absorbed form against the expanded one, the latent kernel
interpreted against the gather, the engine end to end, the counters, what
a tolerance has to refuse, and the refusals of what is not written."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu import telemetry
from distributed_tensorflow_tpu.models import scmoe, scmoe_reference as ref
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig, TransformerLM)
from distributed_tensorflow_tpu.ops import paged_attention
from distributed_tensorflow_tpu.serving import InferenceEngine, Request
from distributed_tensorflow_tpu.serving import decode as decode_lib
from distributed_tensorflow_tpu.serving import migrate
from distributed_tensorflow_tpu.serving.kv_cache import (
    BlockAllocator, BlockTable, CacheConfig, init_pool)

pytestmark = pytest.mark.usefixtures("leave_no_programs_behind")

CFG = TransformerConfig(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=96,
    max_seq_len=64, dtype=jnp.float32, param_dtype=jnp.float32,
    tie_embeddings=False, rope_base=1e7, norm_eps=1e-5, sub_blocks=2,
    latent=dict(q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8, v_dim=16,
                scale_q=True, scale_kv=True),
    experts=dict(n_routed=32, n_identity=16, top_k=6, d_expert=48,
                 scaling=6.0, held=8, offset=8))
#: float32 programs against the float32 reference: summation order only
TOL = 2e-5
PICKS = CFG.experts.top_k * CFG.n_layers          # a token


@pytest.fixture(scope="module")
def params():
    return scmoe.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (40,), 0,
                                         CFG.vocab_size))


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))


def _cache(num_blocks=16, block_size=8):
    cc = CacheConfig.for_model(CFG, num_blocks=num_blocks,
                               block_size=block_size)
    alloc = BlockAllocator(cc.num_blocks)
    return cc, alloc, init_pool(cc)


def _prefill(params, cc, alloc, pool, prompt, impl, width=32):
    table = BlockTable(cc, max_blocks=cc.blocks_for(CFG.max_seq_len))
    table.ensure_room(width, alloc)
    fn = jax.jit(decode_lib.make_prefill_fn(CFG, cc, implementation=impl))
    toks = np.zeros((1, width), np.int32)
    toks[0, :len(prompt)] = prompt
    last, pool, counts = fn(params, pool, jnp.asarray(toks),
                            jnp.asarray([len(prompt)], np.int32),
                            jnp.asarray(table.rows(np.arange(width))[None]))
    table.length = len(prompt)
    return last[0], pool, counts, table


def test_the_cache_describes_a_latent_row():
    cc = CacheConfig.for_model(CFG, num_blocks=16, block_size=8)
    # a cache layer per layer and sub-block, one row of 16 + 8 values,
    # padded to whole 128-value tiles in the pool
    assert (cc.n_layers, cc.latent_dim, cc.n_heads, cc.head_dim) == (4, 24,
                                                                    0, 0)
    assert cc.pool_names == ("latent",) and cc.row_shape == (128,)
    assert cc.bytes_per_token == 4 * 128 * 4
    assert cc.blocks_for_budget(10 * 8 * cc.bytes_per_token) == 10
    pool = init_pool(cc)
    assert set(pool) == {"latent"} and pool["latent"].shape == (4, 128, 128)
    # per-head K and V as before
    plain = CacheConfig.for_model(TransformerConfig.tiny(), num_blocks=4)
    assert plain.pool_names == ("k", "v") and plain.row_shape == (4, 16)
    assert plain.bytes_per_token == 2 * 2 * 64 * 4
    assert paged_attention.supported(128, 8, 0, jnp.float32,
                                     latent_dim=24) == "latent"
    assert paged_attention.supported(128, 8, 0, jnp.int8,
                                     latent_dim=24) is None


@pytest.mark.parametrize("impl", ["plain", "interpret"])
def test_prefill_then_decode_equal_the_reference(params, tokens, impl):
    """Prefill (expanded form) writes the rows; decode (absorbed form)
    reads them through the pool, by the gather or by the kernel: every
    step's logits are the reference's full forward's."""
    want = ref.forward(params, tokens[:24], shape=CFG)
    cc, alloc, pool = _cache()
    n0 = 17
    last, pool, counts, table = _prefill(
        params, cc, alloc, pool, tokens[:n0],
        "scatter" if impl == "plain" else "interpret")
    np.testing.assert_allclose(last, want[n0 - 1], atol=TOL)
    counts = np.asarray(counts)
    assert counts.shape == (CFG.n_layers, 4)
    assert (counts[:, 0] == n0 * CFG.experts.top_k).all()
    decode = decode_lib.make_decode_fn(
        CFG, cc, implementation="window" if impl == "plain" else impl)
    assert decode.kv_path == ("window" if impl == "plain" else "paged")
    assert decode.counts and decode.kv_layout == (
        None if impl == "plain" else "latent")
    step = jax.jit(decode)
    for pos in range(n0, 24):
        table.ensure_room(1, alloc)
        table.length = pos + 1
        held = (np.asarray([table.blocks + [0] * (8 - len(table.blocks))],
                           np.int32) if decode.kv_path == "paged"
                else table.window_rows()[None])
        logits, pool, counts = step(
            params, pool, jnp.asarray([tokens[pos]], np.int32),
            jnp.asarray([pos], np.int32), jnp.asarray([pos + 1], np.int32),
            jnp.asarray([table.row_of(pos)], np.int32), jnp.asarray(held))
        np.testing.assert_allclose(logits[0], want[pos], atol=TOL)
        picks, local, identity, touched = np.asarray(counts).sum(0)
        assert picks == PICKS and local + identity <= picks
        assert touched <= min(local, CFG.experts.held * CFG.n_layers)


@pytest.mark.parametrize("impl", ["scatter", "interpret"])
def test_extend_over_a_shared_prefix_equals_the_reference(params, tokens,
                                                          impl):
    """A prompt whose first 16 tokens are in the pool runs its suffix
    through ``extend``: written, gathered through the window, attended
    in the expanded form."""
    want = ref.forward(params, tokens[:29], shape=CFG)
    cc, alloc, pool = _cache()
    _, pool, _, table = _prefill(params, cc, alloc, pool, tokens[:16], impl)
    E, C, n = 16, 16, 29
    table.ensure_room(n - C, alloc)
    toks = np.zeros((1, E), np.int32)
    toks[0, :n - C] = tokens[C:n]
    pos = np.full((1, E), CFG.max_seq_len, np.int32)
    pos[0, :n - C] = np.arange(C, n)
    rows = np.zeros((1, E), np.int32)
    rows[0, :n - C] = table.rows(np.arange(C, n))
    fn = jax.jit(decode_lib.make_extend_fn(CFG, cc, implementation=impl))
    logits, pool, counts = fn(
        params, pool, jnp.asarray(toks), jnp.asarray(pos),
        jnp.asarray([n], np.int32), jnp.asarray(rows),
        jnp.asarray(table.window_rows()[None]))
    np.testing.assert_allclose(logits[0, :n - C], want[C:n], atol=TOL)
    # the padded entries are not routed
    assert (np.asarray(counts)[:, 0] == (n - C) * CFG.experts.top_k).all()


def test_absorbed_form_equals_expanded_form(params):
    """One query over the same cached rows, both ways."""
    att = jax.tree_util.tree_map(lambda a: a[1, 0],
                                 params["layers"]["attn"])
    rng = jax.random.PRNGKey(7)
    h = jax.random.normal(rng, (3, 12, CFG.d_model))
    positions = jnp.broadcast_to(jnp.arange(12), (3, 12))
    lengths = jnp.asarray([12, 7, 1], jnp.int32)
    q_nope, q_rope, rows = decode_lib._mla_project(CFG, att, h, positions)
    expanded = decode_lib._mla_expanded(CFG, att, q_nope, q_rope, rows,
                                        lengths)
    for b, n in enumerate([12, 7, 1]):
        at = slice(n - 1, n)
        absorbed = decode_lib._mla_absorbed(
            CFG, att, q_nope[b:b + 1, :, at], q_rope[b:b + 1, :, at],
            lambda q: decode_lib._window_attend(
                CFG, q, rows[b:b + 1], lengths[b:b + 1],
                lengths[b:b + 1] - 1))
        np.testing.assert_allclose(absorbed[0], expanded[b, n - 1],
                                   atol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_latent_kernel_matches_the_gather(dtype, atol):
    """``paged_attn_decode_latent`` interpreted, over scattered block
    tables and lengths from an empty slot to several runs, against plain
    attention over the gathered rows."""
    rng = np.random.default_rng(0)
    B, H, W, v_dim, bs, blocks = 5, 4, 128, 64, 16, 40
    pool = jnp.asarray(rng.normal(size=(2, blocks * bs, W)), dtype)
    lengths = np.asarray([0, 1, 16, 150, 300], np.int32)
    max_blocks = 20
    table = np.zeros((B, max_blocks), np.int32)
    free = list(rng.permutation(np.arange(1, blocks)))
    for b, n in enumerate(lengths):
        for j in range(-(-int(n) // bs)):
            table[b, j] = free.pop()
    q = jnp.asarray(rng.normal(size=(B, H, W)), dtype)
    new = jnp.asarray(rng.normal(size=(B, W)), dtype)
    plan = paged_attention.plan_for("latent", jnp.asarray(table),
                                    jnp.asarray(lengths), block_size=bs)
    assert int(plan["n_runs"][0]) == paged_attention.count_runs(
        "latent", table, -(-lengths // bs), bs) == 0 + 1 + 1 + 2 + 3
    got = paged_attention.latent_attention_decode(
        q, new, pool, 1, plan, jnp.asarray(lengths + 1), block_size=bs,
        v_dim=v_dim, sm_scale=0.1, interpret=True)
    rows = np.asarray(pool[1], np.float64)
    for b, n in enumerate(lengths):
        at = [table[b, p // bs] * bs + p % bs for p in range(n)]
        keys = np.concatenate([rows[at].reshape(-1, W),
                               np.asarray(new[b:b + 1], np.float64)])
        s = np.asarray(q[b], np.float64) @ keys.T * 0.1
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ keys[:, :v_dim]
        np.testing.assert_allclose(np.asarray(got[b], np.float64), want,
                                   atol=atol)
    # an idle slot attends nothing
    idle = paged_attention.latent_attention_decode(
        q, new, pool, 1, plan, jnp.zeros((B,), jnp.int32), block_size=bs,
        v_dim=v_dim, sm_scale=0.1, interpret=True)
    assert not np.asarray(idle, np.float32).any()


def test_write_latent_rows_touches_its_rows_and_no_other():
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.normal(size=(3, 32, 128)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(3, 4, 128)), jnp.float32)
    rows = jnp.asarray([5, 17, 2, 30], jnp.int32)
    active = jnp.asarray([True, False, True, True])
    out = np.asarray(paged_attention.write_latent_rows(pool, new, rows,
                                                       active))
    want = np.asarray(pool).copy()
    for n in (0, 2, 3):
        want[:, int(rows[n])] = np.asarray(new)[:, n]
    np.testing.assert_array_equal(out, want)
    one = np.asarray(paged_attention.write_latent_rows(
        pool, new[1:2], rows, layers=jnp.asarray([1])))
    want = np.asarray(pool).copy()
    want[1, np.asarray(rows)] = np.asarray(new)[1]
    np.testing.assert_array_equal(one, want)


@pytest.fixture(scope="module")
def served(params, tmp_path_factory):
    """An engine run end to end with its spans written to an event log:
    five requests sharing a 16-token prefix on 4 slots."""
    directory = str(tmp_path_factory.mktemp("events"))
    log = telemetry.configure(directory, process_id=0)
    try:
        eng = InferenceEngine(CFG, params, num_blocks=48, block_size=8,
                              max_slots=4, max_prompt_len=32,
                              prefix_caching=True)
        rng = np.random.default_rng(0)
        shared = tuple(int(t) for t in rng.integers(0, CFG.vocab_size, 16))
        reqs = [Request(id=f"r{i}", tokens=shared + tuple(
            int(t) for t in rng.integers(0, CFG.vocab_size, 5 + i)),
            max_new_tokens=6) for i in range(5)]
        for r in reqs:
            eng.submit(r)
        done = eng.run_until_idle()
    finally:
        telemetry.shutdown()
    return eng, reqs, done, telemetry.read_events(log.path)


def test_engine_serves_it_end_to_end(params, served):
    eng, reqs, done, _ = served
    assert (eng.kv_path, eng.kv_write) == (
        "window", {"prefill": "scatter", "extend": "scatter"})
    assert eng.served_params is eng.params             # nothing to round
    acct = eng.block_accounting()
    assert acct["conserved"] and not acct["leaked_refs"]
    assert eng.stats()["prefix_cache"]["hit_tokens"] >= 16  # extend ran
    for r in reqs:
        out = done[r.id]["tokens"]
        assert len(out) == 6
        gap = ref.greedy_gap(params, list(r.tokens) + list(out),
                             len(r.tokens), CFG.max_seq_len, shape=CFG)
        assert gap.max() <= 1e-4


def test_spans_carry_the_expert_counters(served):
    _, _, _, events = served
    decodes = [e for e in events if e.get("ev") == "serve.decode"
               and e.get("token_steps")]
    prefills = [e for e in events if e.get("ev") == "serve.prefill"]
    assert decodes and len(prefills) == 5
    for e in decodes:
        assert e["picks"] == PICKS * e["token_steps"]
        assert e["picks_local"] + e["picks_identity"] <= e["picks"]
        assert e["experts_touched"] <= min(e["picks_local"],
                                           e["experts_held"])
        assert (e["experts_held"], e["expert_layers"]) == (
            CFG.experts.held * CFG.n_layers, CFG.n_layers)
        assert e["kv_path"] == "window" and e["rows_read"] > 0
    # a prefill's counts come with its first token, at the read
    read = {e["id"]: e for e in events
            if e.get("ev") == "serve.prefill.commit"}
    cold = [e for e in prefills if e["program"] == "prefill"]
    warm = [e for e in prefills if e["program"] == "extend"]
    assert cold and warm
    for e in cold:
        assert read[e["id"]]["picks"] == PICKS * e["prompt_tokens"]
    for e in warm:
        assert read[e["id"]]["picks"] == PICKS * (e["prompt_tokens"]
                                                  - e["cached_tokens"])


@pytest.mark.parametrize("fault,kw", [
    ("without_routed", {"ablate": "without_routed"}),
    ("without_identity", {"ablate": "without_identity"}),
    ("float8_weights", {"weight_dtype": jnp.float8_e4m3fn})])
def test_each_fault_fails_the_tolerance_the_tests_use(params, tokens, fault,
                                                      kw):
    want = ref.forward(params, tokens[:24], shape=CFG)
    got = ref.forward(params, tokens[:24], shape=CFG, **kw)
    worst = max(float(np.max(np.abs(np.asarray(got[i] - want[i]))))
                for i in range(24))
    assert worst > 100 * TOL, fault
    assert max(rel_rms(got[i], want[i]) for i in range(24)) > 0.01
    with pytest.raises(ValueError, match="ablate"):
        ref.forward(params, tokens[:4], shape=CFG, ablate="without_attention")


def test_whole_layer_when_given_all_the_experts(tokens):
    """The reference (and the program) with every routed expert held is
    the uncut layer; a share's logits differ from it."""
    whole = dataclasses.replace(CFG, experts=dataclasses.replace(
        CFG.experts, held=32, offset=0))
    params = scmoe.init_params(whole, jax.random.PRNGKey(3))
    want = ref.forward(params, tokens[:12], shape=whole)
    got = decode_lib.make_prefill_fn(
        whole, CacheConfig.for_model(whole, num_blocks=8, block_size=8),
        implementation="scatter")
    cc, alloc, pool = _cache(8)
    table = BlockTable(cc, max_blocks=8)
    table.ensure_room(16, alloc)
    toks = np.zeros((1, 16), np.int32)
    toks[0, :12] = tokens[:12]
    last, _, counts = jax.jit(got)(
        params, pool, jnp.asarray(toks), jnp.asarray([12], np.int32),
        jnp.asarray(table.rows(np.arange(16))[None]))
    np.testing.assert_allclose(last[0], want[11], atol=TOL)
    picks, local, identity, _ = np.asarray(counts).sum(0)
    assert local + identity == picks                   # none is absent


# -- what is not written refuses, by name ------------------------------------

def test_transformer_lm_refuses_the_shape():
    model = TransformerLM(CFG)
    with pytest.raises(NotImplementedError, match="latent .MLA. attention"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    experts_only = dataclasses.replace(CFG, latent=None, sub_blocks=1)
    with pytest.raises(NotImplementedError, match="sparse experts"):
        TransformerLM(experts_only).init(jax.random.PRNGKey(0),
                                         jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="sub_blocks"):
        TransformerLM(dataclasses.replace(
            CFG, latent=None, experts=None)).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    # and the serving programs an expert layer beside per-head K and V
    with pytest.raises(NotImplementedError, match="latent attention"):
        decode_lib.make_decode_fn(
            experts_only, CacheConfig.for_model(experts_only, num_blocks=4))


@pytest.mark.parametrize("kw,error", [
    ({"kv_dtype": "int8"}, "int8 pool quantizes per .row, head."),
    ({"speculative_k": 2}, "speculative decoding"),
    ({"decode_steps": 2}, "several decode steps a launch"),
    ({"mesh": "mesh2d"}, "a mesh")])
def test_engine_refuses_what_a_latent_pool_has_not(params, kw, error,
                                                   request):
    if "mesh" in kw:
        kw = {"mesh": request.getfixturevalue(kw["mesh"])}
    with pytest.raises(NotImplementedError, match=error):
        InferenceEngine(CFG, params, num_blocks=16, block_size=8,
                        max_slots=2, **kw)


def test_migration_refuses_a_latent_pool(params):
    eng = InferenceEngine(CFG, params, num_blocks=16, block_size=8,
                          max_slots=2)
    eng.submit(Request(id="m", tokens=(1, 2, 3, 4), max_new_tokens=4))
    eng.step()
    seq = next(iter(eng.scheduler.running.values()))
    before = eng.block_accounting()
    with pytest.raises(NotImplementedError, match="does not migrate"):
        eng.export_sequence(seq)
    assert eng.block_accounting() == before            # nothing released
    rows = np.zeros((4, 8, 128), np.float32)
    with pytest.raises(NotImplementedError, match="per-head K and V"):
        migrate.MigrationPayload(
            request_id="m", tokens=(1,), max_new_tokens=1, eos_id=None,
            generated_prefix=(), generated=(), length=1, fingerprint={},
            pool_epoch="e", arrival_wall=None, ttft_s=None, preemptions=0,
            arrays={"latent": rows})

    class Payload:
        request_id = "x"

    with pytest.raises(NotImplementedError, match="does not migrate"):
        eng.adopt_sequence(Payload())
