"""The shortcut-connected expert configuration's benchmark files (ISSUE
37): runner ``serve_scmoe`` rehearsed through ``harness.run`` on the CPU
with a tiny configuration made of files, ``work_scmoe``'s operations and
bytes against a count done by hand, ``benchmark/reference_scmoe.py`` a
copy of the repo's, the reader ``expert_work`` on hand-written events,
and the published configuration's file against the catalog row and the
arithmetic PERF.md gives."""

import json
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_benchmark_rehearsal as rehearsal  # noqa: E402
from benchmark import work_scmoe  # noqa: E402
from benchmark.readers import expert_work  # noqa: E402

PKG = rehearsal.PKG
TINY = dict(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=96,
    max_seq_len=64, dtype="float32", param_dtype="float32",
    tie_embeddings=False, rope_base=1e7, norm_eps=1e-5, sub_blocks=2,
    latent=dict(q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8, v_dim=16,
                scale_q=True, scale_kv=True),
    experts=dict(n_routed=32, n_identity=16, top_k=6, d_expert=48,
                 scaling=6.0, held=8, offset=8))
MS = 1e-3
CELL = "longcat_flash_serve.think_decode"
pytestmark = pytest.mark.usefixtures("leave_no_programs_behind")


# -- work_scmoe against a count by hand --------------------------------------

def test_work_matches_a_count_by_hand_at_the_tiny_size():
    # attention: 64x32 down, 32x(4x24) up, 64x24 kv down, 16x(4x32) kv
    # up, (4x16)x64 out, two norm scales
    attn = 64 * 32 + 32 * 96 + 64 * 24 + 16 * 128 + 64 * 64 + 32 + 16
    assert attn == 12848 == work_scmoe.attention_params(TINY)
    assert work_scmoe.ffn_params(TINY) == 3 * 64 * 96
    assert work_scmoe.router_params(TINY) == 64 * 48 + 48
    assert work_scmoe.expert_params(TINY) == 3 * 64 * 48 == 9216
    layer = 2 * (attn + 3 * 64 * 96 + 2 * 64) + 64 * 48 + 48
    assert work_scmoe.layer_params(TINY) == layer
    assert work_scmoe.held_params(TINY) == (
        2 * (layer + 8 * 9216) + 2 * 128 * 64 + 64)
    # a cached token: one row of 16 + 8 values in each of 4 cache layers
    assert work_scmoe.cache_layers(TINY) == 4
    assert work_scmoe.kv_row_bytes(TINY, 4) == 4 * 24 * 4
    # a decode step: the layers outside their experts and the head once,
    # each touched expert once, every live row
    fixed = 4 * (2 * layer + 128 * 64)
    assert work_scmoe.decode_fixed_bytes(TINY, 4) == fixed
    assert work_scmoe.decode_step_bytes(TINY, 25, 3, 4) == (
        fixed + 3 * 9216 * 4 + 25 * 4 * 24 * 4)
    # attention: per cache layer and head a score over 24 values and a
    # value sum over 16
    assert work_scmoe.attention_flops_per_row(TINY) == 2 * 4 * (24 + 16) * 4
    # 6 picks a layer, 8 of 48 outputs held: one pick a layer, two in all
    assert work_scmoe.expected_local_picks(TINY) == 2.0
    assert work_scmoe.token_flops(TINY, 10, local_picks=3) == (
        2 * (2 * layer + 128 * 64 + 3 * 9216) + 10 * 1280)
    assert work_scmoe.prompt_flops(TINY, 4, local_picks=0) == (
        2 * 4 * 2 * layer + 2 * 128 * 64 + 1280 * (1 + 2 + 3 + 4))


def test_published_configuration_keeps_the_catalog_row_and_its_cut():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "longcat_flash_serve")
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    reduced = ["num_layers", "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == config["reduced"] == reduced
    assert entry["source"] == config["source_url"]
    assert config["published"] == {"num_layers": 28,
                                   "n_routed_experts": 512,
                                   "vocab_size": 131072}
    assert {k: config[k] for k in reduced} == {
        "num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
    # the floors: four layers, 8 experts or more, an eighth of the rows
    assert config["num_layers"] >= 4 and config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert "32 chips" in config["deployment"] and config["assumed"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LongCat-Flash-Chat")
        assert row["source_url"] == entry["source"]
        assert {k: config[k] for k in row["config"]
                if k not in reduced} == {
            k: v for k, v in row["config"].items() if k not in reduced}
        assert {k: row["config"][k] for k in reduced} == config["published"]
    # the program's keys are the published widths, none cut
    model, la, ex = (config["model"], config["model"]["latent"],
                     config["model"]["experts"])
    assert (model["d_model"], model["n_heads"], model["d_ff"],
            ex["d_expert"], ex["top_k"], ex["n_identity"], ex["n_routed"],
            ex["scaling"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["ffn_hidden_size"], config["expert_ffn_hidden_size"],
        config["moe_topk"], config["zero_expert_num"],
        config["published"]["n_routed_experts"],
        config["routed_scaling_factor"]) == (6144, 64, 12288, 2048, 12, 256,
                                            512, 6)
    assert (la["q_rank"], la["kv_rank"], la["nope_dim"], la["rope_dim"],
            la["v_dim"], la["scale_q"], la["scale_kv"]) == (
        config["q_lora_rank"], config["kv_lora_rank"],
        config["qk_nope_head_dim"], config["qk_rope_head_dim"],
        config["v_head_dim"], config["mla_scale_q_lora"],
        config["mla_scale_kv_lora"]) == (1536, 512, 128, 64, 128, True, True)
    assert (model["n_layers"], ex["held"], model["vocab_size"]) == (
        config["num_layers"], config["n_routed_experts"],
        config["vocab_size"])
    assert ex["offset"] % ex["held"] == 0 and ex["offset"] < ex["n_routed"]
    assert (model["rope_base"], model["norm_eps"]) == (
        config["rope_theta"], config["rms_norm_eps"]) == (1e7, 1e-5)
    assert model["dtype"] == model["param_dtype"] == "bfloat16"
    # PERF.md section 4's arithmetic
    assert work_scmoe.attention_params(model) == 90_572_800
    assert work_scmoe.ffn_params(model) == 226_492_416
    assert work_scmoe.layer_params(model) == 638_874_368
    assert work_scmoe.expert_params(model) == 37_748_736
    assert work_scmoe.held_params(model) == 5_172_749_312
    assert work_scmoe.kv_row_bytes(model) == 9_216
    assert round(work_scmoe.decode_fixed_bytes(model) / 1e9, 2) == 5.31
    assert work_scmoe.expected_local_picks(model) == 1.0
    # the served tree holds exactly these parameters
    from benchmark.runners.common import model_config
    from distributed_tensorflow_tpu.models import scmoe
    from distributed_tensorflow_tpu.serving.kv_cache import CacheConfig
    cfg = model_config(config)
    assert scmoe.n_params(cfg) == work_scmoe.held_params(model)
    engine = config["engine"]
    cc = CacheConfig.for_model(cfg, num_blocks=engine["num_blocks"],
                               block_size=engine["block_size"])
    # 576 values a row, kept in 640: 10,240 B a token, a 0.75 GB pool
    assert (cc.n_layers, cc.latent_dim, cc.row_shape) == (8, 576, (640,))
    assert cc.bytes_per_token == 10_240
    assert engine["num_blocks"] * 16 * cc.bytes_per_token == 754_974_720
    # the mix the cell names fits the pool: the longest request's blocks
    # on every slot, beside the trash block
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "think_decode.json")) as f:
        mix = json.load(f)
    longest = max(mix["prompt_lens"]) + max(mix["output_lens"])
    assert longest == 1024 <= model["max_seq_len"]
    assert engine["max_slots"] * -(-longest // engine["block_size"]) \
        <= engine["num_blocks"] - 1
    assert max(mix["prompt_lens"]) <= engine["max_prompt_len"]
    assert (mix["clients"], engine["max_slots"]) == (96, 64)
    assert sum(mix["prompt_lens"]) / 16 == 218
    assert sum(mix["output_lens"]) / 16 == 464
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and len(manifest["workloads"]) == 5


def test_benchmarks_reference_is_a_copy_of_the_repos():
    with open(os.path.join(REPO, "benchmark", "reference_scmoe.py")) as f:
        copy = f.read()
    with open(os.path.join(REPO, PKG, "models",
                           "scmoe_reference.py")) as f:
        assert f.read() == copy


# -- the runner, rehearsed on the CPU ----------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A cell of this kind made of data files alone."""
    root = str(tmp_path_factory.mktemp("scmoe_root"))
    write = rehearsal._write
    write(f"{root}/b/configs/tiny_scmoe.json", {
        "runner": "serve_scmoe",
        "builder": f"{PKG}.serving.InferenceEngine",
        "model_config": f"{PKG}.models.transformer.TransformerConfig",
        "model": TINY,
        "engine": {"num_blocks": 96, "block_size": 8, "max_slots": 4,
                   "max_prompt_len": 16, "prefix_caching": True},
        "check": {"requests": 2, "logit_margin": 1e-3, "prompts": 2,
                  "reference_width": 32, "logit_rel_rms": 1e-4,
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
    write(f"{root}/b/layer_metrics/picks_per_token.json", {
        "reader": "expert_work", "args": {
            "what": "stat_ratio", "span": "serve.decode", "num": "picks",
            "den": ["token_steps", "expert_layers"]}})
    write(f"{root}/BENCHMARK.json", {
        "paths": ["b"],
        "configs": [{"name": "tiny_scmoe",
                     "file": "b/configs/tiny_scmoe.json"}],
        "workloads": [{"name": "tiny_scmoe.few_clients",
                       "config": "tiny_scmoe", "traffic": "few_clients",
                       "chips": 1, "why": "rehearsal"}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "x"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": n, "unit": "x"} for n in (
            "flops_per_token", "kv_row_bytes", "mfu", "picks_per_token")]})
    return root


def _run(root, workload, trace):
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
def test_harness_runs_the_scmoe_runner(root, trace):
    rc, line, split = _run(root, "tiny_scmoe.few_clients", trace)
    assert rc == 0
    assert set(line) == rehearsal.CONTRACT_KEYS
    assert line["correct"] is True, split["failures"]
    assert line["attempted"] > 0 and line["failed"] == 0
    notes = split["notes"]
    # a float32 engine against the float32 reference, on logits; and the
    # three readings the tolerance has to refuse beside it, each outside
    assert 0.0 <= notes["logits_rel_rms_prefill"] <= 1e-4
    assert 0.0 <= notes["logits_rel_rms_decode"] <= 1e-4
    for fault in ("low_precision", "without_routed", "without_identity"):
        assert notes[f"{fault}_rel_rms"] > 1e-3
    assert notes["picks_per_token_layer_ok"] is True
    picks, local, identity, touched = notes["decode_step_counts"]
    assert picks == 4 * 6 * 2 and local + identity <= picks
    assert 0.0 <= notes["reference_worst_gap"] <= 1e-3
    assert notes["low_precision_worst_gap"] >= notes["reference_worst_gap"]
    counts = split["counts"]
    assert counts["kv_row_bytes"] == work_scmoe.kv_row_bytes(TINY)
    assert counts["expert_bytes"] == work_scmoe.expert_bytes(TINY)
    assert counts["decode_fixed_bytes"] == work_scmoe.decode_fixed_bytes(TINY)
    assert counts["model_flops"] > 0
    assert counts["peak_hbm_bytes_per_s"] is None        # no chip here
    if not trace:
        assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
        return
    # mfu has no peak to read on the CPU and is left out; the CPU trace
    # has no device plane, so the span reader reads nothing either
    assert set(line["metrics"]) == {"flops_per_token", "kv_row_bytes"}
    per_token = line["metrics"]["flops_per_token"]["value"]
    assert (work_scmoe.token_flops(TINY, 0) * 0.5 < per_token
            < work_scmoe.prompt_flops(TINY, 16))


def test_a_wrong_share_or_parted_logits_are_not_correct(root):
    """``verify`` holds the engine to the experts the file says it holds,
    and ``reference_check`` fails a run whose logits part from the
    reference's by more than the tolerance (here: an engine whose
    identity experts' part is thrown away)."""
    import dataclasses

    from benchmark import harness
    _, _, _, config, traffic = harness.load_cell(root,
                                                 "tiny_scmoe.few_clients")
    runner = harness.make_runner(config, traffic, 7, jax.devices()[:1])
    runner.build()
    runner.verify({"served": []})
    assert not any("holds experts" in f for f in runner.failures)
    runner.engine.cfg = dataclasses.replace(
        runner.engine.cfg, experts=dataclasses.replace(
            runner.engine.cfg.experts, offset=0))
    runner.verify({"served": []})
    assert any("holds experts 0..8" in f for f in runner.failures)
    runner = harness.make_runner(config, traffic, 7, jax.devices()[:1])
    runner.build()
    # the served weights' router sends nothing to the identity experts
    # (in the tree the programs are handed alone, not the reference's)
    eng = runner.engine
    layers = dict(eng.params["layers"])
    layers["moe"] = dict(layers["moe"], bias=layers["moe"]["bias"].at[
        :, 32:].set(-10.0))
    eng.served_params = dict(eng.params, layers=layers)
    notes = runner.reference_check()
    assert notes["logits_rel_rms_prefill"] > 1e-2
    assert sum("logits part from the reference's" in f
               for f in runner.failures) == 2


# -- the reader on hand-written events ---------------------------------------

def span(name, start_ms, dur_ms, **stats):
    return (name, start_ms * MS, dur_ms * MS, stats, "main")


def op(start_ms, dur_ms, scope="", name="%copy.1 = bf16[4]{0} copy(%x)"):
    return (name, start_ms * MS, dur_ms * MS, scope)


ATTN = ('%paged_attn_decode_latent.3 = f32[8,16,128]{2,1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call"')
GMM = ('%expert_grouped_matmul.5 = bf16[1024,4096]{1,0} custom-call(%a), '
       'custom_call_target="tpu_custom_call"')
COUNTS = dict(token_steps=64, expert_layers=4, picks=3072, picks_local=60,
              picks_identity=1000, experts_touched=40, experts_held=64)
SPANS = [
    span("serve.step", 0.5, 9.5),
    span("serve.decode", 1.0, 8.0, rows_read=1000, **COUNTS),
    span("serve.step", 10.0, 10.5),
    span("serve.decode", 11.0, 9.0, rows_read=3000,
         **dict(COUNTS, picks_local=68, experts_touched=44)),
    span("serve.prefill", 20.1, 0.3, picks=9999),
    span("serve.step", 20.6, 5.0),
    span("serve.decode", 20.7, 4.5, rows_read=9999, **COUNTS),  # cut off
]
OPS = [
    op(0.3, 0.5, "jit(decode)/embed/gather"),
    op(2.0, 1.0, "jit(decode)/kv.gather/paged_attn_decode_latent", ATTN),
    op(4.0, 2.0, "jit(decode)/moe.experts/expert_grouped_matmul", GMM),
    op(12.0, 3.0, "jit(decode)/kv.gather/paged_attn_decode_latent", ATTN),
    op(15.0, 2.0, "jit(decode)/moe.experts/expert_grouped_matmul", GMM),
    # the same kernel inside a prefill: not a decode step's time
    op(20.2, 4.0, "jit(prefill)/moe.experts/expert_grouped_matmul", GMM),
    op(20.0, 1.0, "jit(decode)/lm_head/dot"),
]
RECORD = {"decode_fixed_bytes": 4.0e6, "expert_bytes": 1.0e5,
          "expert_flops": 2.0e5, "kv_row_bytes": 1.0e3,
          "attention_flops_per_row": 1.0e5,
          "peak_hbm_bytes_per_s": 1.0e9, "peak_flops": 1.0e12}
TRACE = {"programs": {"jit_decode": [2, 14 * MS], "jit_prefill": [1, MS]},
         "chips": 1}


def test_stat_ratio_reads_the_routing_off_the_spans():
    args = {"span": "serve.decode"}
    # the third step outlives the slice and is left out
    assert expert_work.stat_ratio(
        SPANS, OPS, num="picks", den=["token_steps", "expert_layers"],
        **args) == 12.0
    assert expert_work.stat_ratio(
        SPANS, OPS, num="picks_local", den="picks", scale=100, **args) \
        == pytest.approx(100 * 128 / 6144)
    assert expert_work.stat_ratio(
        SPANS, OPS, num="experts_touched", den="experts_held", scale=100,
        **args) == pytest.approx(100 * 84 / 128)
    # spans from before they carried the counters
    bare = [(n, s, d, {}, t) for n, s, d, _, t in SPANS]
    assert expert_work.stat_ratio(bare, OPS, num="picks", den="picks",
                                  **args) is None


def test_roofline_of_the_step_and_of_each_kernel():
    step = {"span": "serve.decode", "program": "jit_decode", "bytes": [
        {"per": "decode_fixed_bytes"},
        {"per": "expert_bytes", "stat": "experts_touched"},
        {"per": "kv_row_bytes", "stat": "rows_read"}]}
    # (4e6 + 1e5 x 42 experts + 1e3 x 2000 rows) bytes over 1e9 B/s x 7 ms
    assert expert_work.roofline(SPANS, OPS, RECORD, TRACE, step) == \
        pytest.approx(100 * 10.2e6 / (1e9 * 7e-3))
    attn = {"span": "serve.decode", "match": ["paged_attn_decode_latent"],
            "bytes": [{"per": "kv_row_bytes", "stat": "rows_read"}],
            "flops": [{"per": "attention_flops_per_row",
                       "stat": "rows_read"}]}
    # bytes: 2e6 / 1e9 = 2 ms; FLOPs: 2e8 / 1e12 = 0.2 ms; the kernel
    # took (1 + 3) / 2 ms a step: the bytes bound it
    assert expert_work.roofline(SPANS, OPS, RECORD, TRACE, attn) == \
        pytest.approx(100 * 2e-3 / 2e-3)
    gmm = {"span": "serve.decode", "match": ["expert_grouped_matmul"],
           "bytes": [{"per": "expert_bytes", "stat": "experts_touched"}],
           "flops": [{"per": "expert_flops", "stat": "picks_local"}]}
    # 4.2e6 B -> 4.2 ms over (2 + 2) / 2 ms: the prefill's is left out
    assert expert_work.roofline(SPANS, OPS, RECORD, TRACE, gmm) == \
        pytest.approx(100 * 4.2e-3 / 2e-3)
    # a peak of FLOPs low enough and the arithmetic bounds it
    slow = dict(RECORD, peak_flops=1.0e9)
    assert expert_work.roofline(SPANS, OPS, slow, TRACE, gmm) == \
        pytest.approx(100 * (64 * 2e5 / 1e9) / 2e-3)
    for lacking in ({"programs": {}, "chips": 1}, {}):
        assert expert_work.roofline(SPANS, OPS, RECORD, lacking,
                                    step) is None
    assert expert_work.roofline(
        SPANS, OPS, {"kv_row_bytes": 1e3}, TRACE, step) is None
    assert expert_work.roofline(
        SPANS, OPS, RECORD, TRACE, dict(gmm, match=["flash_"])) is None
    bare = [(n, s, d, {}, t) for n, s, d, _, t in SPANS]
    assert expert_work.roofline(bare, OPS, RECORD, TRACE, step) is None


def test_reader_reads_nothing_without_a_trace():
    for what in ("stat_ratio", "roofline"):
        assert expert_work.read({"what": what}, RECORD, None) is None
