"""The data-driven claim, proved: a configuration, a traffic mix and a
per-layer metric are added as files alone, in a temporary directory,
and the harness runs them in-process on the CPU at TransformerConfig.tiny
widths: once per runner, and once on four virtual devices for dp=4.
And the plain reference against TransformerLM in float32."""

import io
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, reference  # noqa: E402

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
PKG = "distributed_tensorflow_tpu"
TINY = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=128,
            dtype="float32", scan_layers=False)
TINY_TRAIN = dict(
    TINY, max_seq_len=128, remat=False, attention_impl="interpret",
    attn_block_q=64, attn_block_k=64, loss_impl="kernel",
    loss_kernel_impl="interpret", loss_block_n=32, loss_block_v=64,
    adam_mu_dtype="bfloat16")


def _train_config(mesh: dict, batch: int) -> dict:
    return {"runner": "train",
            "builder": f"{PKG}.models.transformer.make_sharded_train_step",
            "model_config": f"{PKG}.models.transformer.TransformerConfig",
            "model": TINY_TRAIN, "mesh": mesh, "global_batch": batch,
            "check": {"reference_chunk": 2, "loss_abs_tol": 1e-3}}


CONFIGS = {
    "tiny_train": _train_config({"dp": 1}, 2),
    "tiny_train_dp4": _train_config({"dp": 4}, 4),
    "tiny_serve": {
        "runner": "serve", "builder": f"{PKG}.serving.InferenceEngine",
        "model_config": f"{PKG}.models.transformer.TransformerConfig",
        "model": dict(TINY, max_seq_len=64,
                      attention_impl="reference"),
        "engine": {"num_blocks": 96, "block_size": 8, "max_slots": 4,
                   "max_prompt_len": 48, "prefix_caching": True},
        "check": {"requests": 2, "logit_margin": 1e-3}},
}
TRAFFIC = {
    "few_steps": {"generator": "train_batches", "zipf_exponent": 1.0},
    "two_clients": {"generator": "closed_clients", "clients": 6,
                    "ramp_s": 0.3, "prompt_lens": [5, 9, 12],
                    "output_lens": [3, 4], "stride": 1},
    "tiny_sessions": {"generator": "open_sessions", "rate_rps": 20,
                      "ramp_s": 0.3, "doc_lens": [16, 20],
                      "asks_per_doc": [2, 3], "reuse_gaps": [2, 4],
                      "question_lens": [4, 9], "answer_lens": [3, 5],
                      "burst_every": 4, "burst_size": 2},
}
CELLS = [("tiny_train", "few_steps", 1, "steps_per_s"),
         ("tiny_train_dp4", "few_steps", 4, "steps_per_s"),
         ("tiny_serve", "two_clients", 1, "tokens_per_s"),
         ("tiny_serve", "tiny_sessions", 1, "ttft_p50_ms")]
END_TO_END = {
    "steps_per_s": {"stat": "ratio", "num": "steps", "den": "elapsed_s"},
    "tokens_per_s": {"stat": "ratio", "num": "tokens", "den": "elapsed_s"},
    "ttft_p50_ms": {"stat": "percentile", "series": "ttft_ms", "q": 50},
    "setup_s": {"stat": "value", "series": "setup_s"},
}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark made of data files alone."""
    root = str(tmp_path_factory.mktemp("bench_root"))
    for name, cfg in CONFIGS.items():
        _write(f"{root}/b/configs/{name}.json", cfg)
    for name, mix in TRAFFIC.items():
        _write(f"{root}/b/traffic/{name}.json", mix)
    for name, args in END_TO_END.items():
        _write(f"{root}/b/end_to_end/{name}.json",
               {"reader": "recorded", "args": args})
    _write(f"{root}/b/layer_metrics/late_p50_ms.json",
           {"reader": "recorded", "args": {
               "stat": "percentile", "series": "submit_late_ms", "q": 50}})
    _write(f"{root}/b/layer_metrics/compiles_in_window.json",
           {"reader": "recorded", "args": {
               "stat": "value", "series": "compiles_in_window"}})
    cells = [{"name": f"{c}.{t}", "config": c, "traffic": t, "chips": n,
              "why": "rehearsal"} for c, t, n, _ in CELLS]
    serve = [c["name"] for c in cells if c["config"] == "tiny_serve"]
    _write(f"{root}/BENCHMARK.json", {
        "paths": ["b"],
        "configs": [{"name": n, "file": f"b/configs/{n}.json"}
                    for n in CONFIGS],
        "workloads": cells,
        "end_to_end": [
            {"name": m, "unit": "x", "workloads": [
                f"{c}.{t}" for c, t, _, own in CELLS if own == m]}
            for m in END_TO_END if m != "setup_s"]
        + [{"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "compiles_in_window", "unit": "count"},
            {"name": "late_p50_ms", "unit": "ms", "workloads": serve}],
    })
    return root


def _run(root, workload, trace):
    out = io.StringIO()
    rc = harness.run(root, workload, seed=2**31 + 12345, seconds=0.6,
                     trace=trace, process_start=time.monotonic(),
                     require_chip=False, out=out)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), json.loads(lines[-2])


@pytest.mark.parametrize("config,traffic,chips,own", CELLS)
def test_harness_runs_a_cell_made_of_files(root, config, traffic, chips,
                                           own):
    rc, line, split = _run(root, f"{config}.{traffic}", trace=False)
    assert rc == 0
    assert set(line) == CONTRACT_KEYS, line
    assert line["correct"] is True, split["failures"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {own, "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == chips
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(split["setup_split_s"]) == {"import", "build", "reference",
                                           "warm", "ramp"}


def test_a_slow_collection_costs_the_window_nothing(root, monkeypatch):
    """The runner collects garbage once before the window. In a test
    worker that has run a few hundred tests a full collection takes a
    second (it walks every object alive), longer than this ramp and
    window together: the closed loop then served nothing in its window.
    With a ramp too short to hold it, it comes before the clock starts."""
    from benchmark.runners import serve
    calls = []
    monkeypatch.setattr(serve.gc, "collect",
                        lambda: calls.append(time.sleep(1.0)))
    rc, line, split = _run(root, "tiny_serve.two_clients", trace=False)
    assert rc == 0 and len(calls) == 1
    assert line["correct"] is True, split["failures"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # the second that passed is set-up's, not the window's
    assert split["setup_split_s"]["ramp"] > 1.0


def test_traced_run_reports_the_per_layer_metrics(root):
    rc, line, split = _run(root, "tiny_serve.tiny_sessions", trace=True)
    assert rc == 0 and line["correct"] is True, split["failures"]
    # no device plane on the CPU: the trace reader finds nothing, and
    # the line carries no device time
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert set(line["metrics"]) == {"compiles_in_window", "late_p50_ms"}
    assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_too_few_chips_is_refused(root, monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:2])
    out = io.StringIO()
    rc = harness.run(root, "tiny_train_dp4.few_steps", seed=0, seconds=0.1,
                     trace=False, process_start=time.monotonic(),
                     require_chip=False, out=out)
    assert rc != 0 and out.getvalue() == ""


def test_reference_matches_transformer_lm_in_float32():
    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig, TransformerLM, next_token_loss)
    cfg = TransformerConfig.tiny(scan_layers=False, remat=False)
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    theirs = model.apply({"params": params}, tokens)
    ours = reference.forward(params, tokens)
    # float32 on both sides: only the order of summation differs
    np.testing.assert_allclose(ours[:, -1], theirs[:, -1], atol=1e-5)
    assert abs(float(reference.loss(params, tokens))
               - float(next_token_loss(theirs, tokens))) < 1e-5
    assert abs(reference.chunked_loss(params, tokens, chunk=1)
               - float(reference.loss(params, tokens))) < 1e-5
