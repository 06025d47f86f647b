"""Steady by construction: for every seed the generators offer the same
requests at the same due times. And the arithmetic from a timeline to
metrics, and from trace events to busy time, on hand-written inputs."""

import collections
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import metric_math, trace_reduce  # noqa: E402
from benchmark.generators import (  # noqa: E402
    closed_clients, open_sessions, train_batches)
from benchmark.readers import device_trace  # noqa: E402

SEEDS = (0, 7, 2**31 + 5)
VOCAB = 32768
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MANIFEST_RUN_SECONDS = json.load(_f)["run_seconds"]


def _mix(name):
    with open(os.path.join(REPO, "benchmark", "traffic",
                           name + ".json")) as f:
        return json.load(f)


def _shapes(source, n):
    reqs = [source.request(i) for i in range(n)]
    return [(len(r["tokens"]), r["max_new_tokens"], r["due_s"])
            for r in reqs], reqs


# -- rag_prefix: open loop of sessions ------------------------------------

def test_open_sessions_offer_the_same_requests_at_the_same_times():
    mix = _mix("rag_prefix")
    streams = [_shapes(open_sessions.make(mix, s, VOCAB), 320)
               for s in SEEDS]
    assert streams[0][0] == streams[1][0] == streams[2][0]
    # ... and the seed draws the token ids
    assert streams[0][1][0]["tokens"] != streams[1][1][0]["tokens"]


def test_open_sessions_keep_the_declared_rate_and_bursts():
    mix = _mix("rag_prefix")
    rate = mix["rate_rps"]
    assert rate == pytest.approx(mix["share_of_knee"] * mix["knee_rps"],
                                 rel=0.02)
    # below the knee, where latency is the metric, and some hundreds of
    # requests in the benchmark's window
    assert 0.4 <= mix["share_of_knee"] <= 0.8
    assert rate * MANIFEST_RUN_SECONDS >= 400
    due = [open_sessions.due_s(mix, i, rate) for i in range(161)]
    assert due[160] == pytest.approx(160 / rate)
    for i, t in enumerate(due[:160]):
        if i % 16 >= 12:        # the last four of every 16, together
            assert t == pytest.approx((i - i % 16 + 12) / rate)
        else:
            assert t == pytest.approx(i / rate)


def test_open_sessions_share_documents_as_the_mix_says():
    mix = _mix("rag_prefix")
    source = open_sessions.make(mix, 3, VOCAB)
    docs = collections.defaultdict(list)
    for i, (session, ask) in enumerate(open_sessions.layout(mix, 600)):
        docs[session].append((i, ask))
    whole = [v for v in docs.values()
             if len(v) == mix["asks_per_doc"][0] or v[-1][0] < 400]
    assert {len(v) for v in whole} == set(mix["asks_per_doc"])
    gaps = collections.Counter(b[0] - a[0] for v in whole
                               for a, b in zip(v, v[1:]))
    assert {g for g, _ in gaps.most_common(3)} == set(mix["reuse_gaps"])
    # two asks of one session share the document and nothing after it
    (i, _), (j, _) = docs[0][:2]
    a, b = source.request(i)["tokens"], source.request(j)["tokens"]
    n_doc = mix["doc_lens"][0]
    assert a[:n_doc] == b[:n_doc] and a[n_doc:n_doc + 8] != b[n_doc:n_doc + 8]
    lens = {len(source.request(i)["tokens"]) - n for n in mix["doc_lens"]
            for i in range(64)}
    assert set(mix["question_lens"]) <= lens
    # every prompt and answer fits the engine's window
    assert max(mix["doc_lens"]) + max(mix["question_lens"]) + max(
        mix["answer_lens"]) <= 1024


def test_open_sessions_warm_every_extend_width_without_touching_sessions():
    mix = _mix("rag_prefix")
    source = open_sessions.make(mix, 3, VOCAB)
    cold, followers = source.warmup()
    assert len(cold) == len(mix["doc_lens"])
    suffixes = {len(f["tokens"]) - len(c["tokens"])
                + len(c["tokens"]) - n
                for c, n in zip(cold, mix["doc_lens"])
                for f in followers if f["tokens"][:n] == c["tokens"][:n]}
    assert suffixes == set(mix["question_lens"])
    measured = source.request(0)["tokens"]
    assert all(r["tokens"][:16] != measured[:16] for r in cold)


# -- batch_chat: closed loop ----------------------------------------------

def test_closed_clients_same_multiset_same_cyclic_order():
    mix = _mix("batch_chat")
    cycle = len(mix["prompt_lens"]) * len(mix["output_lens"])
    per_seed = []
    for seed in SEEDS:
        shapes, _ = _shapes(closed_clients.make(mix, seed, VOCAB), cycle)
        per_seed.append([s[:2] for s in shapes])
    base = per_seed[0]
    for seed, shapes in zip(SEEDS, per_seed):
        k = seed % cycle
        assert shapes == base[k:] + base[:k]       # a rotation
        assert collections.Counter(shapes) == collections.Counter(base)
    # the stride brings every pair of lengths round
    assert set(base) == {(p, o) for p in mix["prompt_lens"]
                         for o in mix["output_lens"]}


def test_closed_clients_keep_128_outstanding():
    mix = _mix("batch_chat")
    source = closed_clients.make(mix, 1, VOCAB)
    first = source.poll(0.0)
    assert len(first) == mix["clients"] == 128
    assert source.poll(0.1) == []
    for r in first[:3]:
        source.finished(r["id"])
    again = source.poll(0.2)
    assert len(again) == 3
    assert len({r["id"] for r in first + again}) == 131
    warm = [r for wave in source.warmup() for r in wave]
    assert all(w["tokens"] != r["tokens"] for w in warm for r in first[:2])


def test_train_batches_same_shape_and_distribution_for_every_seed():
    mix = _mix("steady")
    a, b = (train_batches.make(mix, s, global_batch=8, seq_len=256,
                               vocab_size=1024) for s in SEEDS[:2])
    x, y = next(a), next(b)
    assert x.shape == y.shape == (8, 256) and x.dtype == np.int32
    assert (x != y).any() and (x != next(a)).any()
    assert 0 <= x.min() and x.max() < 1024
    # Zipf: the most frequent id takes far more than a uniform share
    top = np.bincount(x.ravel(), minlength=1024).max()
    assert top > 20 * x.size / 1024


# -- from a timeline to metrics -------------------------------------------

def test_latency_arithmetic_on_a_hand_written_timeline():
    # due at 10.0; the steps that released its tokens returned at
    # 10.4, 10.6, 10.8 (two tokens) and 11.2
    times = [10.4, 10.6, 10.8, 10.8, 11.2]
    assert metric_math.ttft_ms(10.0, times[0]) == pytest.approx(400.0)
    assert metric_math.tpot_ms(times) == pytest.approx(200.0)
    assert metric_math.tpot_ms([10.4]) is None


def test_percentiles():
    xs = list(range(1, 101))
    assert metric_math.percentile(xs, 50) == pytest.approx(50.5)
    assert metric_math.percentile(xs, 90) == pytest.approx(90.1)
    assert metric_math.percentile([3.0], 90) == 3.0
    assert metric_math.percentile([], 90) is None


def test_tokens_per_second_over_whole_steps():
    ends = [0.9, 1.1, 1.3, 1.5, 1.7, 2.05, 2.3]
    toks = [50, 64, 64, 60, 64, 64, 64]
    # window 1.0 .. 2.0: from the boundary at 1.1 to the one at 2.05
    count, seconds = metric_math.whole_step_rate(ends, toks, 1.0, 2.0)
    assert count == 64 + 60 + 64 + 64
    assert seconds == pytest.approx(0.95)


@pytest.mark.parametrize("waiting,began,ended", [
    # eight steps: the first two against the last two
    ([0, 2, 4, 0, 1, 3, 5, 7], 1.0, 6.0),
    # a queue that bursts of four fill and the engine drains: level
    ([4, 3, 2, 1, 0, 4, 3, 2, 1, 0, 4, 3, 2, 1, 0, 4, 3, 2, 1, 0],
     2.0, 2.0),
    ([3], 3.0, 3.0), ([1, 5], 1.0, 5.0)])
def test_the_queue_is_read_by_its_first_and_last_quarter(waiting, began,
                                                         ended):
    assert metric_math.quarter_means(waiting) == (
        pytest.approx(began), pytest.approx(ended))


@pytest.mark.parametrize("args,expected", [
    ({"stat": "ratio", "num": "tokens", "den": "elapsed_s"}, 500.0),
    ({"stat": "ratio", "num": "flops", "den": ["elapsed_s", "peak"],
      "scale": 100}, 25.0),
    ({"stat": "percentile", "series": "ttft_ms", "q": 50}, 20.0),
    ({"stat": "mean", "series": "ttft_ms"}, 20.0),
    ({"stat": "value", "series": "tokens"}, 1000.0),
    ({"stat": "value", "series": "absent"}, None),
    ({"stat": "percentile", "series": "empty", "q": 90}, None),
    ({"stat": "ratio", "num": "tokens", "den": "absent"}, None),
])
def test_reduce_reads_a_record_by_a_metric_files_arguments(args, expected):
    record = {"tokens": 1000, "elapsed_s": 2.0, "flops": 100.0,
              "peak": 200.0, "ttft_ms": [10.0, 20.0, 30.0], "empty": [],
              "peak_none": None}
    got = metric_math.reduce(record, args)
    assert got == (pytest.approx(expected) if expected is not None
                   else None)


# -- from trace events to busy time ---------------------------------------

OPS = [("fusion.1", 0.0, 1.0), ("fusion.2", 0.5, 1.0),   # overlap: 0..1.5
       ("copy.3", 2.0, 0.5),                             # gap 1.5..2.0
       ("all-reduce.4", 2.5, 0.5),                       # adjacent
       ("fusion.1", 4.0, 1.0)]                           # gap 3.0..4.0
SPANS = [("bench.engine_step", 1.4, 0.7),                # covers 1.75
         ("bench.wait_request", 3.2, 0.6),               # covers 3.5
         ("bench.outer", 3.0, 1.0)]                      # so does this


def test_busy_union_idle_share_and_op_totals():
    assert trace_reduce.busy_union(OPS) == [(0.0, 1.5), (2.0, 3.0),
                                            (4.0, 5.0)]
    busy, window = trace_reduce.busy_and_window(OPS)
    assert (busy, window) == (3.5, 5.0)
    assert trace_reduce.op_totals(OPS) == {
        "fusion": 3.0, "copy": 0.5, "all-reduce": 0.5}


@pytest.mark.parametrize("event,label", [
    ("fusion.12", "fusion"),
    ("%fusion.329 = (f32[1024,8192]{1,0:T(8,128)}, bf16[1024,8192]{1,0}) "
     "fusion(f32[1024,8192]{1,0} %p.1), kind=kOutput, calls=%fc.3",
     "fusion:kOutput_f32_1024_8192"),
    ("%copy.5 = bf16[12,65536,16,64]{3,2,1,0:T(8,128)(2,1)} "
     "copy(bf16[12,65536,16,64]{3,2,1,0} %p)", "copy_bf16_12_65536_16_64"),
    ("%all-reduce-start.3 = (f32[100]{0}, f32[100]{0}) "
     "all-reduce-start(f32[100]{0} %x), replica_groups={}",
     "all-reduce-start_f32_100"),
    ("%jvp__.2 = (f32[4096,1]{1,0:T(8,128)}, f32[4096,1]{1,0}) "
     "custom-call(bf16[4096,1024]{1,0} %b.2), "
     'custom_call_target="tpu_custom_call", operand_layout_constraints={}',
     "mosaic:jvp__"),
])
def test_an_operation_is_labelled_by_what_it_does(event, label):
    # on the TPU an event's name is its whole HLO instruction
    assert trace_reduce.op_name(event) == label


def test_gaps_go_to_the_innermost_host_span_that_covers_them():
    gaps = trace_reduce.gap_attribution(OPS, SPANS)
    assert gaps == {"bench.engine_step": pytest.approx(0.5),
                    "bench.wait_request": pytest.approx(1.0)}
    assert trace_reduce.gap_attribution(OPS, []) == {
        trace_reduce.NO_SPAN: pytest.approx(1.5)}


def test_a_gap_is_split_over_the_host_spans_it_crosses():
    # 1.5..2.0 lies inside the engine step; 3.0..4.0 is the outer
    # span's to 3.2 and from 3.8, the wait's between; the same seconds
    # as whole gaps given to the span at their middle
    shares = trace_reduce.gap_shares(OPS, SPANS)
    assert shares == {"bench.engine_step": pytest.approx(0.5),
                      "bench.wait_request": pytest.approx(0.6),
                      "bench.outer": pytest.approx(0.4)}
    assert sum(shares.values()) == pytest.approx(
        sum(trace_reduce.gap_attribution(OPS, SPANS).values()))
    # the program's spans beside the benchmark's: the innermost wins,
    # and what no span covers is between steps
    program = [("serve.step", 1.45, 0.5), ("serve.decode.wait", 1.6, 0.3)]
    assert trace_reduce.gap_shares(OPS, SPANS[:1] + program) == {
        "bench.engine_step": pytest.approx(0.05),       # 1.95..2.0
        "serve.step": pytest.approx(0.1 + 0.05),        # to 1.6, from 1.9
        "serve.decode.wait": pytest.approx(0.3),
        trace_reduce.NO_SPAN: pytest.approx(1.0)}


@pytest.mark.parametrize("name,named", [
    ("bench.wait_request", True), ("serve.decode.wait", True),
    ("kv.copy_on_write", True), ("serve.step", True),
    ("PjitFunction(decode)", False), ("copy.1", False),
    ("$profiler.py:91 trace", False), ("ThunkExecutor::Execute", False)])
def test_host_spans_are_the_named_ones(name, named):
    assert bool(trace_reduce.HOST_SPAN.match(name)) is named


def test_a_cut_list_still_sums_to_the_whole():
    totals = {f"span.n{chr(97 + i)}": float(20 - i) for i in range(14)}
    assert trace_reduce.top(totals) == [
        [f"span.n{chr(97 + i)}", float(20 - i)] for i in range(10)]
    cut = trace_reduce.top(totals, rest=trace_reduce.OTHER)
    assert len(cut) == 10 and cut[-1] == [
        trace_reduce.OTHER, float(sum(20 - i for i in range(9, 14)))]
    assert sum(v for _, v in cut) == pytest.approx(sum(totals.values()))
    few = {"serve.step": 2.0, "_none_": 1.0}
    assert trace_reduce.top(few, rest=trace_reduce.OTHER) == [
        ["serve.step", 2.0], ["_none_", 1.0]]


def test_summary_feeds_the_trace_reader():
    programs = [("jit_step.9", 0.0, 1.5), ("jit_step.9", 2.0, 1.0),
                ("jit_other", 4.0, 0.1)]
    summary = trace_reduce.summarize({0: OPS, 1: OPS[:3]}, programs, SPANS)
    assert summary["chips"] == 2
    assert summary["busy_s"] == pytest.approx((3.5 + 2.0) / 2)
    assert summary["window_s"] == pytest.approx((5.0 + 2.5) / 2)
    assert summary["device_ops"][0] == ["fusion", 3.0]
    assert summary["main_program_s"] == [1.5, 1.0]
    assert summary["idle_gaps"] == [
        ["bench.wait_request", pytest.approx(0.6)],
        ["bench.engine_step", pytest.approx(0.5)],
        ["bench.outer", pytest.approx(0.4)]]
    read = lambda **a: device_trace.read(a, {}, summary)
    assert read(what="idle_share") == pytest.approx(
        100 * (1 - 2.75 / 3.75))
    assert read(what="step_ms_p50") == pytest.approx(1250.0)
    assert read(what="ops_ms_per_step",
                match=["all-reduce", "copy"]) == pytest.approx(500.0)
    assert device_trace.read({"what": "idle_share"}, {}, None) is None
    assert trace_reduce.summarize({}, [], []) is None
