"""The looped configuration's benchmark files (ISSUE 28): runner
``serve_looped`` rehearsed through ``harness.run`` on the CPU with a tiny
looped configuration made of files, ``work_looped``'s operations and
bytes against a count done by hand, ``reference_looped`` against
``TransformerLM`` in float32, the reader ``span_work`` on hand-written
events, and the published configuration's file against the arithmetic
PERF.md gives."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_benchmark_rehearsal as rehearsal  # noqa: E402
from benchmark import reference_looped, work_looped  # noqa: E402
from benchmark.readers import span_work  # noqa: E402

PKG = rehearsal.PKG
TINY = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
            max_seq_len=64, dtype="float32", attention_impl="reference",
            passes=3, post_norms=True, tie_embeddings=False, exit_gate=True,
            rope_base=1e6, scan_layers=True)
MS = 1e-3
pytestmark = pytest.mark.usefixtures("leave_no_programs_behind")


# -- work_looped against a count by hand -------------------------------------

def test_work_matches_a_count_by_hand_at_the_tiny_size():
    # a layer: q, k, v, o of 64 x 64 and gate, up, down of 64 x 128
    layer = 4 * 64 * 64 + 3 * 64 * 128
    assert layer == 40960 == work_looped.layer_params(TINY)
    assert work_looped.stack_params(TINY) == 2 * layer
    assert work_looped.head_params(TINY) == 128 * 64
    assert work_looped.cache_layers(TINY) == 6
    # a cached token: K and V of 64 elements in each of 6 cache layers
    assert work_looped.kv_row_bytes(TINY, 4) == 6 * 2 * 64 * 4 == 3072
    # a token over 10 visible keys: every layer three times, the head
    # once, and per cache layer q.k and p.v over 10 keys of 64 each
    by_hand = (2 * 2 * layer * 3 + 2 * 128 * 64
               + 6 * (2 * 10 * 64 + 2 * 10 * 64))
    assert work_looped.token_flops(TINY, 10) == by_hand == 523264
    # a prompt of 4: its tokens see 1, 2, 3, 4 keys; the head runs once
    assert work_looped.prompt_flops(TINY, 4) == (
        4 * 2 * 2 * layer * 3 + 2 * 128 * 64 + 6 * 4 * 64 * (1 + 2 + 3 + 4))
    # a decode step reads the layers once a pass and the head once,
    # whatever the batch, and every live row
    weights = 4 * (2 * layer * 3 + 128 * 64)
    assert work_looped.decode_weight_bytes(TINY, 4) == weights
    assert work_looped.decode_step_bytes(TINY, 25, 4) == weights + 25 * 3072
    # one pass and no ``passes`` key: a plain model
    plain = {k: v for k, v in TINY.items() if k != "passes"}
    assert work_looped.cache_layers(plain) == 2
    assert work_looped.token_flops(plain, 0) == 2 * 2 * layer + 2 * 128 * 64


def test_published_configuration_is_whole_and_counts_what_perf_md_says():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == "ouro_serve")
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert entry["reduced"] == config["reduced"] == []
    assert entry["source"] == config["source_url"]
    model = config["model"]
    # the program's keys are the published ones, none cut
    assert (model["d_model"], model["n_layers"], model["n_heads"],
            model["d_ff"], model["vocab_size"], model["passes"]) == (
        config["hidden_size"], config["num_hidden_layers"],
        config["num_attention_heads"], config["intermediate_size"],
        config["vocab_size"], config["total_ut_steps"]) == (
        2048, 48, 16, 5632, 49152, 4)
    assert model["d_model"] // model["n_heads"] == config["head_dim"] == 128
    assert config["num_key_value_heads"] == model["n_heads"]
    assert model["rope_base"] == config["rope_theta"] == 1e6
    assert model["tie_embeddings"] is config["tie_word_embeddings"] is False
    assert config["early_exit_threshold"] == 1 and config["assumed"]
    assert len(config["layer_types"]) == 48
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert row["source_url"] == entry["source"]
        assert {k: config[k] for k in row["config"]} == row["config"]
    # PERF.md section 4's arithmetic
    assert work_looped.layer_params(model) == 51_380_224
    assert work_looped.stack_params(model) == 2_466_250_752
    assert work_looped.head_params(model) == 100_663_296
    assert work_looped.cache_layers(model) == 192
    assert work_looped.kv_row_bytes(model) == 1_572_864
    engine = config["engine"]
    rows = engine["num_blocks"] * engine["block_size"]
    assert rows * work_looped.kv_row_bytes(model) == 6_442_450_944
    assert round(work_looped.decode_weight_bytes(model) / 1e9, 2) == 19.93
    # the mix the cell names fits the pool: the longest request's blocks
    # on every slot, beside the trash block
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "reasoning.json")) as f:
        mix = json.load(f)
    longest = max(mix["prompt_lens"]) + max(mix["output_lens"])
    assert longest == 480 <= model["max_seq_len"]
    assert engine["max_slots"] * -(-longest // engine["block_size"]) \
        <= engine["num_blocks"] - 1
    assert max(mix["prompt_lens"]) <= engine["max_prompt_len"]
    assert sum(mix["prompt_lens"]) / 16 == 100
    assert sum(mix["output_lens"]) / 16 == 213


# -- the reference against the model -----------------------------------------

def test_looped_reference_matches_transformer_lm_in_float32():
    from distributed_tensorflow_tpu.models.transformer import (
        TransformerLM)

    from benchmark.runners.common import model_config
    cfg = model_config({"model": TINY,
                        "model_config": f"{PKG}.models.transformer"
                        ".TransformerConfig"})
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    theirs = model.apply({"params": params}, tokens)
    logits, exits = reference_looped.forward(
        params, np.asarray(tokens), passes=cfg.passes,
        rope_base=cfg.rope_base)
    # float32 on both sides: only the order of summation differs
    np.testing.assert_allclose(logits[-1], theirs, atol=2e-5)
    np.testing.assert_allclose(np.asarray(exits).sum(0), 1.0, atol=1e-6)
    seq = [int(t) for t in tokens[0]]
    gap = reference_looped.greedy_gap(params, seq, 10, 32, passes=cfg.passes,
                                      rope_base=cfg.rope_base)
    row = np.asarray(logits[-1, 0])
    np.testing.assert_allclose(
        gap, row[9:23].max(-1) - row[np.arange(9, 23), seq[10:]], atol=2e-5)
    # tokens chosen by weights rounded to float8 are not all the
    # reference's own
    low = reference_looped.greedy_gap(
        params, seq, 10, 32, passes=cfg.passes, rope_base=cfg.rope_base,
        chooser_dtype=jnp.float8_e4m3fn)
    assert low.shape == gap.shape and low.min() >= 0.0


# -- the runner, rehearsed on the CPU ----------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A looped cell made of data files alone."""
    root = str(tmp_path_factory.mktemp("looped_root"))
    write = rehearsal._write
    write(f"{root}/b/configs/tiny_looped.json", {
        "runner": "serve_looped", "builder": f"{PKG}.serving.InferenceEngine",
        "model_config": f"{PKG}.models.transformer.TransformerConfig",
        "model": TINY,
        "engine": {"num_blocks": 96, "block_size": 8, "max_slots": 4,
                   "max_prompt_len": 48, "prefix_caching": True,
                   "decode_steps": 2},
        "check": {"requests": 2, "logit_margin": 1e-3,
                  "low_precision": "float8_e4m3fn"}})
    write(f"{root}/b/traffic/few_clients.json", {
        "generator": "closed_clients", "clients": 6, "ramp_s": 0.3,
        "prompt_lens": [5, 9, 12], "output_lens": [3, 4], "stride": 1})
    metrics = {
        "end_to_end": {
            "tokens_per_s": {"stat": "ratio", "num": "tokens",
                             "den": "elapsed_s"},
            "setup_s": {"stat": "value", "series": "setup_s"}},
        "layer_metrics": {
            "passes": {"stat": "value", "series": "passes"},
            "flops_per_token": {"stat": "ratio", "num": "model_flops",
                                "den": "tokens"},
            "kv_row_bytes": {"stat": "value", "series": "kv_row_bytes"},
            # no peak on the CPU: the ratio has nothing to read
            "mfu": {"stat": "ratio", "num": "model_flops",
                    "den": ["elapsed_s", "peak_flops"], "scale": 100}}}
    for group, files in metrics.items():
        for name, args in files.items():
            write(f"{root}/b/{group}/{name}.json",
                  {"reader": "recorded", "args": args})
    write(f"{root}/b/layer_metrics/passes_per_token.json", {
        "reader": "span_work", "args": {
            "what": "counts_ratio",
            "num": {"span": "serve.decode",
                    "stats": ["passes", "token_steps"]},
            "den": {"span": "serve.decode.commit", "stat": "tokens"}}})
    write(f"{root}/BENCHMARK.json", {
        "paths": ["b"],
        "configs": [{"name": "tiny_looped",
                     "file": "b/configs/tiny_looped.json"}],
        "workloads": [{"name": "tiny_looped.few_clients",
                       "config": "tiny_looped", "traffic": "few_clients",
                       "chips": 1, "why": "rehearsal"}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "x"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": n, "unit": "x"} for n in (
            "passes", "flops_per_token", "kv_row_bytes", "mfu",
            "passes_per_token")]})
    return root


def _run(root, workload, trace):
    """``rehearsal._run`` with a window long enough for a busy box: six
    layer applications a token on the CPU, beside five other workers."""
    import io
    import time

    from benchmark import harness
    out = io.StringIO()
    rc = harness.run(root, workload, seed=2**31 + 12345, seconds=4.0,
                     trace=trace, process_start=time.monotonic(),
                     require_chip=False, out=out)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), json.loads(lines[-2])


@pytest.mark.parametrize("trace", [False, True])
def test_harness_runs_the_looped_runner(root, trace):
    rc, line, split = _run(root, "tiny_looped.few_clients", trace)
    assert rc == 0
    assert set(line) == rehearsal.CONTRACT_KEYS
    assert line["correct"] is True, split["failures"]
    assert line["attempted"] > 0 and line["failed"] == 0
    notes = split["notes"]
    # float32 engine against the float32 reference; and the reading the
    # margin has to refuse is reported beside it
    assert 0.0 <= notes["reference_worst_gap"] <= 1e-3
    assert notes["low_precision_worst_gap"] >= notes["reference_worst_gap"]
    counts = split["counts"]
    assert counts["passes"] == 3 and counts["kv_row_bytes"] == 6 * 2 * 64 * 2
    assert counts["model_flops"] > 0 and counts["hbm_bytes_needed"] > 0
    # a launch runs the engine's two decode steps: the weights twice
    assert counts["decode_weight_bytes"] == 2 * work_looped.decode_weight_bytes(
        TINY)
    assert counts["peak_hbm_bytes_per_s"] is None        # no chip here
    if not trace:
        assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
        return
    # mfu has no peak to read on the CPU and is left out; the CPU trace
    # has no device plane, so the span reader reads nothing either
    assert set(line["metrics"]) == {"passes", "flops_per_token",
                                    "kv_row_bytes"}
    per_token = line["metrics"]["flops_per_token"]["value"]
    # between a token over no key and one over the longest sequence
    assert (work_looped.token_flops(TINY, 0) * 0.5 < per_token
            < work_looped.prompt_flops(TINY, 16))


def test_a_program_that_skips_a_pass_is_not_correct(root):
    """``verify`` holds the engine to the passes the model has: a decode
    program built to run fewer (an exit before the last pass) fails the
    run, whatever its tokens read against the reference."""
    from benchmark import harness
    _, _, _, config, traffic = harness.load_cell(root,
                                                 "tiny_looped.few_clients")
    runner = harness.make_runner(config, traffic, 7, jax.devices()[:1])
    runner.build()
    assert (runner.engine.prefill_passes, runner.engine.decode_passes) \
        == (3, 3)
    runner.verify({"served": []})
    assert not any("passes" in f for f in runner.failures)
    runner.engine.decode_passes = 2
    runner.verify({"served": []})
    assert any("[2, 3] passes" in f for f in runner.failures)


# -- the reader on hand-written events ---------------------------------------

def span(name, start_ms, dur_ms, **stats):
    return (name, start_ms * MS, dur_ms * MS, stats, "main")


def op(start_ms, dur_ms, scope="", name="%copy.1 = bf16[4]{0} copy(%x)"):
    return (name, start_ms * MS, dur_ms * MS, scope)


KERNEL = ('%paged_attn_decode_rows.3 = f32[8,16,128]{2,1,0} custom-call(%a), '
          'custom_call_target="tpu_custom_call"')
# two whole steps in the slice (0.5-10 and 10-20.5) and one that outlives
# it; in each a decode program whose pass loop (a ``while`` under the
# scope, with the operations of its body inside it) runs 6 and 5 ms
SPANS = [
    span("serve.step", 0.5, 9.5),
    span("serve.decode", 1.0, 8.0, passes=4, live=8, rows_read=1000,
         cache_layers=192, kv_path="paged"),
    span("serve.decode.commit", 8.5, 0.2, tokens=8),
    span("serve.step", 10.0, 10.5),
    span("serve.decode", 11.0, 9.0, passes=4, live=7, rows_read=1400),
    span("serve.decode.commit", 19.5, 0.2, tokens=7),
    span("serve.step", 20.6, 3.0),
    span("serve.decode", 20.7, 2.0, passes=4, live=7, rows_read=9999),
    span("serve.decode.commit", 20.8, 0.1, tokens=7),   # inside the slice,
]                                        # its serve.decode is not
OPS = [
    op(0.3, 0.5, "jit(decode)/embed/gather"),
    op(2.0, 6.0, "jit(decode)/while/body/loop.pass/while"),
    op(2.0, 1.0, "jit(decode)/while/body/loop.pass/while/body/mlp/dot"),
    op(3.0, 2.0, "jit(decode)/while/body/loop.pass/while/body/kv.gather/"
       "paged_attn_decode_rows", KERNEL),
    op(12.0, 5.0, "jit(decode)/while/body/loop.pass/while"),
    op(13.0, 1.0, "jit(decode)/while/body/loop.pass/while/body/kv.gather/"
       "paged_attn_decode_rows", KERNEL),
    op(20.0, 1.0, "jit(decode)/lm_head/dot"),
]
RECORD = {"decode_weight_bytes": 4.0e6, "kv_row_bytes": 1.0e3,
          "peak_hbm_bytes_per_s": 1.0e9}
TRACE = {"programs": {"jit_decode": [2, 14 * MS], "jit_prefill": [1, MS]}}


def test_scope_union_counts_a_loop_and_its_body_once():
    assert span_work.scope_union_ms_per_span(
        SPANS, OPS, ["loop.pass"], "serve.step") == pytest.approx(
            (6.0 + 5.0) / 2)
    # the sum would have counted the body's operations again
    from benchmark.readers import program_trace
    assert program_trace.scope_ms_per_span(
        SPANS, OPS, ["loop.pass"], "serve.step", 2) == pytest.approx(
            (6.0 + 1.0 + 2.0 + 5.0 + 1.0) / 2)
    assert span_work.scope_union_ms_per_span(
        SPANS, OPS, ["rotary"], "serve.step") is None
    assert span_work.scope_union_ms_per_span(
        SPANS, OPS, ["loop.pass"], "train.step") is None


def test_counts_ratio_is_passes_per_released_token():
    args = {"num": {"span": "serve.decode", "stats": ["passes", "live"]},
            "den": {"span": "serve.decode.commit", "stat": "tokens"}}
    # the third step outlives the slice and is left out
    assert span_work.counts_ratio(SPANS, OPS, **args) == pytest.approx(
        (4 * 8 + 4 * 7) / (8 + 7)) == 4.0
    # spans from before they carried ``passes``
    old = [(n, s, d, {k: v for k, v in st.items() if k != "passes"}, t)
           for n, s, d, st, t in SPANS]
    assert span_work.counts_ratio(old, OPS, **args) is None


def test_bytes_roofline_of_the_step_and_of_the_kernel():
    step = {"span": "serve.decode", "rows": "rows_read",
            "fixed": "decode_weight_bytes", "per_row": "kv_row_bytes",
            "program": "jit_decode"}
    # (4e6 + 1e3 x 1200 rows) bytes over 1e9 B/s x 7 ms a run
    assert span_work.bytes_roofline(SPANS, OPS, RECORD, TRACE, step) == \
        pytest.approx(100 * 5.2e6 / (1e9 * 7e-3))
    kernel = dict(step, match=["paged_attn_decode"])
    del kernel["fixed"]
    # 1.2e6 bytes over 1e9 B/s x (2 + 1) ms of the kernel / 2 runs
    assert span_work.bytes_roofline(SPANS, OPS, RECORD, TRACE, kernel) == \
        pytest.approx(100 * 1.2e6 / (1e9 * 1.5e-3))
    for lacking in ({"programs": {}}, {}):
        assert span_work.bytes_roofline(SPANS, OPS, RECORD, lacking,
                                        step) is None
    assert span_work.bytes_roofline(
        SPANS, OPS, {"kv_row_bytes": 1e3}, TRACE, step) is None
    assert span_work.bytes_roofline(
        SPANS, OPS, RECORD, TRACE, dict(kernel, match=["flash_"])) is None
    bare = [(n, s, d, {}, t) for n, s, d, _, t in SPANS]
    assert span_work.bytes_roofline(bare, OPS, RECORD, TRACE, step) is None


def test_reader_reads_nothing_without_a_trace():
    for what in ("scope_union_ms_per_span", "counts_ratio",
                 "bytes_roofline"):
        assert span_work.read({"what": what}, RECORD, None) is None
