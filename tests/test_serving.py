"""Serving engine: block allocator, continuous batching, decode parity.

The load-bearing contract (ISSUE 9): greedy decode through the
block-allocated KV cache equals argmax over full-sequence recompute —
on one device and on dp×tp meshes — because prefill writes the exact
K/V the full forward computes and both sides mask with the ONE factored
rule (ops/attention.length_valid_mask).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig, TransformerLM)
from distributed_tensorflow_tpu.serving import (
    AdmissionQueue, BlockAllocator, BlockTable, CacheConfig,
    InferenceEngine, OutOfBlocksError, QueueOverflowError, Request)
from distributed_tensorflow_tpu.serving.kv_cache import TRASH_BLOCK


# ---------------------------------------------------------------------------
# shared tiny model
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig.tiny(max_seq_len=64)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def reference_greedy(cfg, params, prompt, n):
    """Argmax rollout via FULL-sequence recompute each step."""
    model = TransformerLM(cfg)
    t = list(prompt)
    for _ in range(n):
        logits = model.apply({"params": params}, jnp.asarray([t]))
        t.append(int(jnp.argmax(logits[0, len(t) - 1])))
    return t[len(prompt):]


# ---------------------------------------------------------------------------
# block allocator / table
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_alloc_free_roundtrip(self):
        a = BlockAllocator(8)                 # 7 usable (block 0 trash)
        got = a.alloc(3)
        assert len(got) == 3 and TRASH_BLOCK not in got
        assert a.num_free == 4 and a.num_allocated == 3
        a.free(got)
        assert a.num_free == 7 and a.num_allocated == 0

    def test_exhaustion_raises_without_partial_alloc(self):
        a = BlockAllocator(5)
        a.alloc(3)
        free_before = a.num_free
        with pytest.raises(OutOfBlocksError):
            a.alloc(2)
        assert a.num_free == free_before      # nothing leaked

    def test_no_fragmentation_interleaved(self):
        """Fixed-size blocks: after ANY interleaving of alloc/free the
        full free count is allocatable in one request."""
        a = BlockAllocator(9)
        x = a.alloc(3)
        y = a.alloc(2)
        a.free([x[0], x[2]])
        z = a.alloc(2)
        # freed blocks are reused (lowest-first determinism)
        assert set(z) == {x[0], x[2]}
        a.free(y)
        a.free(z)
        a.free([x[1]])
        assert len(a.alloc(a.num_free)) == 8

    def test_double_free_and_trash_free_raise(self):
        a = BlockAllocator(4)
        b = a.alloc(1)
        a.free(b)
        with pytest.raises(ValueError):
            a.free(b)
        with pytest.raises(ValueError):
            a.free([TRASH_BLOCK])

    def test_free_of_shared_block_decrefs_not_releases(self):
        """ISSUE 14 satellite: freeing a SHARED (refcount > 1) block
        must drop one reference, not return the block to the free list
        — and double-free detection stays refcount-aware: only freeing
        past the last reference raises."""
        a = BlockAllocator(4)
        [b] = a.alloc(1)
        a.incref(b)                           # a second owner
        assert a.refcount(b) == 2
        free_before = a.num_free
        a.free([b])                           # first owner lets go
        assert a.num_free == free_before      # NOT back in the pool
        assert a.refcount(b) == 1
        a.free([b])                           # last owner lets go
        assert a.num_free == free_before + 1
        assert a.refcount(b) == 0
        with pytest.raises(ValueError):       # now it IS a double free
            a.free([b])
        with pytest.raises(ValueError):       # incref of a free block
            a.incref(b)

    def test_block_table_rows(self):
        cc = CacheConfig(n_layers=1, n_heads=2, head_dim=4,
                         num_blocks=8, block_size=4)
        a = BlockAllocator(cc.num_blocks)
        t = BlockTable(cc, max_blocks=3)
        t.ensure_room(6, a)                   # 2 blocks
        assert len(t.blocks) == 2
        assert t.row_of(0) == t.blocks[0] * 4
        assert t.row_of(5) == t.blocks[1] * 4 + 1
        rows = t.rows(np.arange(12))
        # positions past the allocated blocks land in the trash block
        assert (rows[8:] < 4).all()
        with pytest.raises(OutOfBlocksError):
            t.ensure_room(20, a)              # > max_blocks capacity


# ---------------------------------------------------------------------------
# admission queue / scheduler
# ---------------------------------------------------------------------------

class TestAdmission:
    def test_queue_reject_on_overflow(self):
        q = AdmissionQueue(capacity=2, policy="reject")
        q.submit(Request(id="a", tokens=(1,)))
        q.submit(Request(id="b", tokens=(1,)))
        with pytest.raises(QueueOverflowError):
            q.submit(Request(id="c", tokens=(1,)))
        assert q.rejected == 1 and len(q) == 2

    def test_queue_evict_oldest_on_overflow(self):
        q = AdmissionQueue(capacity=2, policy="evict_oldest")
        q.submit(Request(id="a", tokens=(1,)))
        q.submit(Request(id="b", tokens=(1,)))
        evicted = q.submit(Request(id="c", tokens=(1,)))
        assert evicted.id == "a" and q.evicted == 1
        assert [q.pop().id, q.pop().id] == ["b", "c"]

    def test_queue_reject_counter_and_event(self, tmp_path):
        """Overload is observable, not just an exception (ISSUE 13
        satellite): a rejection ticks serving/rejected_total and emits
        a serve.reject event so the autoscaler and health_report can
        tell overload from failure."""
        from distributed_tensorflow_tpu import telemetry

        telemetry.configure(str(tmp_path), process_id=0)
        try:
            reg = telemetry.get_registry()
            rejected = reg.counter("serving/rejected_total")
            before = rejected.value
            q = AdmissionQueue(capacity=1, policy="reject")
            q.submit(Request(id="a", tokens=(1,)))
            with pytest.raises(QueueOverflowError):
                q.submit(Request(id="b", tokens=(1,)))
            assert rejected.value == before + 1
            # evictions tick their own counter and a serve.reject
            # event naming the shed (evicted) request
            evictions = reg.counter("serving/evicted_total")
            ev_before = evictions.value
            q2 = AdmissionQueue(capacity=1, policy="evict_oldest")
            q2.submit(Request(id="c", tokens=(1,)))
            q2.submit(Request(id="d", tokens=(1,)))
            assert evictions.value == ev_before + 1
        finally:
            telemetry.shutdown()
        events = telemetry.read_events(
            telemetry.event_log_path(str(tmp_path), 0))
        rejects = [e for e in events if e.get("ev") == "serve.reject"]
        assert len(rejects) == 2
        assert rejects[0]["id"] == "b" and rejects[0]["policy"] == "reject"
        assert rejects[1]["id"] == "c" \
            and rejects[1]["evicted_for"] == "d"

    def test_token_budget_defers_big_prompt(self, tiny):
        cfg, params = tiny
        engine = InferenceEngine(cfg, params, num_blocks=32, block_size=8,
                                 max_slots=4, max_prompt_len=16,
                                 token_budget=10)
        engine.submit(Request(id="small", tokens=(1, 2), max_new_tokens=2))
        engine.submit(Request(id="big", tokens=tuple(range(12)),
                              max_new_tokens=2))
        engine.step()
        sched = engine.scheduler
        running = {s.request.id for s in sched.running.values()}
        # 2 + 12 > budget 10: the big prompt waits a step
        assert running == {"small"}
        done = engine.run_until_idle()
        assert set(done) == {"small", "big"}   # but never starves


# ---------------------------------------------------------------------------
# decode parity (the correctness contract)
# ---------------------------------------------------------------------------

PROMPTS = [[5, 6, 7], [1, 2, 3, 4, 5, 6, 7, 8], [9] * 12, [3, 1, 4, 1, 5]]


class TestDecodeParity:
    def test_prefill_logits_match_full_forward(self, tiny):
        """Prefill IS a full forward over the factored mask: its
        last-position logits must match the module's bit-for-bit-close
        and argmax-exactly."""
        cfg, params = tiny
        from distributed_tensorflow_tpu.serving import (
            canonical_params, model_forward)
        model = TransformerLM(cfg)
        toks = jnp.asarray([[4, 8, 15, 16, 23, 42]])
        ref = model.apply({"params": params}, toks)
        got = model_forward(cfg, canonical_params(cfg, params), toks,
                            lengths=jnp.asarray([6]))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        assert (np.argmax(np.asarray(got), -1)
                == np.argmax(np.asarray(ref), -1)).all()

    def test_padded_mixed_length_batch_matches_solo(self, tiny):
        """Satellite contract: right-padded mixed-length batches through
        TransformerLM(lengths=...) produce logits identical to running
        each sequence alone (the factored length mask)."""
        cfg, params = tiny
        model = TransformerLM(cfg)
        toks = np.zeros((2, 10), np.int32)
        toks[0, :7] = [9, 8, 7, 6, 5, 4, 3]
        toks[1, :10] = np.arange(1, 11)
        padded = model.apply({"params": params}, jnp.asarray(toks),
                             False, jnp.asarray([7, 10]))
        solo = model.apply({"params": params}, jnp.asarray(toks[:1, :7]))
        np.testing.assert_array_equal(np.asarray(padded[0, :7]),
                                      np.asarray(solo[0]))

    def test_greedy_decode_matches_recompute_1device(self, tiny):
        cfg, params = tiny
        engine = InferenceEngine(cfg, params, num_blocks=32, block_size=8,
                                 max_slots=4, max_prompt_len=16)
        outs = engine.generate(PROMPTS, max_new_tokens=6)
        for p, o in zip(PROMPTS, outs):
            assert o == reference_greedy(cfg, params, p, 6)
        # every block returned to the pool
        assert (engine.scheduler.allocator.num_free
                == engine.cache_cfg.usable_blocks)

    @pytest.mark.parametrize("decode_steps", [1, 3])
    def test_greedy_decode_matches_recompute_dp_tp_mesh(self, tiny, mesh2d,
                                                        decode_steps):
        """Same contract on a dp=4 × tp=2 mesh: slots sharded over dp,
        heads/vocab over tp, KV pool heads over tp; one decode step a
        launch, and three (the multi-step program's write rows and
        budgets shard over dp with the slots)."""
        cfg, params = tiny
        engine = InferenceEngine(cfg, params, mesh=mesh2d, num_blocks=32,
                                 block_size=8, max_slots=8,
                                 max_prompt_len=16,
                                 decode_steps=decode_steps)
        outs = engine.generate(PROMPTS, max_new_tokens=6)
        for p, o in zip(PROMPTS, outs):
            assert o == reference_greedy(cfg, params, p, 6)

    def test_preemption_preserves_outputs(self, tiny):
        """A pool too small for the concurrency forces newest-first
        preemption; every request still completes with exactly the
        no-pressure outputs (re-admission replays generated tokens)."""
        cfg, params = tiny
        engine = InferenceEngine(cfg, params, num_blocks=6, block_size=4,
                                 max_slots=4, max_prompt_len=16)
        outs = engine.generate([[7, 7, 7], [8, 8, 8, 8], [9, 9]],
                               max_new_tokens=8)
        for p, o in zip([[7, 7, 7], [8, 8, 8, 8], [9, 9]], outs):
            assert o == reference_greedy(cfg, params, p, 8)
        assert (engine.scheduler.allocator.num_free
                == engine.cache_cfg.usable_blocks)

    def test_eos_stops_generation(self, tiny):
        cfg, params = tiny
        ref = reference_greedy(cfg, params, [5, 6, 7], 6)
        # the greedy output repeats itself: the end token is one that
        # first occurs after the first token, and the output ends at
        # that first occurrence
        eos = next(t for t in ref[1:] if t != ref[0])
        want = ref[:ref.index(eos) + 1]
        engine = InferenceEngine(cfg, params, num_blocks=32, block_size=8,
                                 max_slots=2, max_prompt_len=16)
        engine.submit(Request(id="e", tokens=(5, 6, 7),
                              max_new_tokens=6, eos_id=eos))
        done = engine.run_until_idle()
        assert done["e"]["tokens"] == want

    def test_bert_scoring_path(self):
        """Non-causal (BERT-family) configs serve scoring requests:
        prefill-only, last-position argmax, mixed lengths in one batch
        masked by the factored rule."""
        cfg = TransformerConfig.tiny(max_seq_len=32, causal=False)
        params = TransformerLM(cfg).init(
            jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
        engine = InferenceEngine(cfg, params, num_blocks=16, block_size=8,
                                 max_slots=2, max_prompt_len=16)
        with pytest.raises(ValueError):
            engine.submit(Request(id="gen", tokens=(1, 2),
                                  max_new_tokens=4))
        model = TransformerLM(cfg)
        for rid, prompt in (("s0", [3, 1, 4]), ("s1", [1, 5, 9, 2, 6])):
            engine.submit(Request(id=rid, tokens=tuple(prompt),
                                  max_new_tokens=0))
        done = engine.run_until_idle()
        for rid, prompt in (("s0", [3, 1, 4]), ("s1", [1, 5, 9, 2, 6])):
            ref = model.apply({"params": params}, jnp.asarray([prompt]))
            assert done[rid]["tokens"] == [int(jnp.argmax(
                ref[0, len(prompt) - 1]))]


# ---------------------------------------------------------------------------
# checkpoint restore
# ---------------------------------------------------------------------------

def test_from_checkpoint_restores_serving_weights(tiny, tmp_path):
    """Serving weights come back through CheckpointManager's ladder
    (local warm tier + durable) and decode exactly as the in-memory
    engine does."""
    cfg, params = tiny
    from distributed_tensorflow_tpu.checkpoint.checkpoint import (
        Checkpoint, CheckpointManager)
    plain = params.unfreeze() if hasattr(params, "unfreeze") else \
        dict(params)
    mgr = CheckpointManager(Checkpoint(params=plain),
                            str(tmp_path / "ckpt"),
                            local_dir=str(tmp_path / "local"))
    mgr.save(checkpoint_number=3)
    mgr.checkpoint.sync()
    engine = InferenceEngine.from_checkpoint(
        cfg, str(tmp_path / "ckpt"), local_dir=str(tmp_path / "local"),
        num_blocks=32, block_size=8, max_slots=2, max_prompt_len=16)
    out = engine.generate([[5, 6, 7]], max_new_tokens=4)
    assert out[0] == reference_greedy(cfg, params, [5, 6, 7], 4)


# ---------------------------------------------------------------------------
# chaos + telemetry
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_serve_step_fault_is_retryable(tiny):
    """An injected serve.step failure fires BEFORE any state mutation:
    retrying the step serves every request with unchanged outputs."""
    from distributed_tensorflow_tpu.resilience import faults

    cfg, params = tiny
    schedule = faults.FaultSchedule(
        rules=(faults.FaultRule(site="serve.step", hits=(2, 5)),),
        seed=int(os.environ.get("DTX_CHAOS_SEED", "0")))
    engine = InferenceEngine(cfg, params, num_blocks=32, block_size=8,
                             max_slots=4, max_prompt_len=16)
    with faults.inject(schedule) as registry:
        for i, p in enumerate(PROMPTS):
            engine.submit(Request(id=f"c{i}", tokens=tuple(p),
                                  max_new_tokens=5))
        done = engine.run_until_idle(retry_faults=True)
    assert len(registry.events()) == 2
    assert {e[0] for e in registry.events()} == {"serve.step"}
    for i, p in enumerate(PROMPTS):
        assert done[f"c{i}"]["tokens"] == reference_greedy(
            cfg, params, p, 5)


def test_serving_telemetry_events(tiny, tmp_path):
    """serve.step spans + serve.request completions land in the event
    log (the records obs_report's serving section and trace_report's
    serve track render)."""
    from distributed_tensorflow_tpu import telemetry

    cfg, params = tiny
    telemetry.configure(str(tmp_path), process_id=0)
    try:
        engine = InferenceEngine(cfg, params, num_blocks=32, block_size=8,
                                 max_slots=2, max_prompt_len=16)
        engine.generate([[5, 6, 7], [1, 2]], max_new_tokens=3)
    finally:
        telemetry.shutdown()
    events = telemetry.read_events(
        telemetry.event_log_path(str(tmp_path), 0))
    steps = [e for e in events if e.get("ev") == "serve.step"]
    reqs = [e for e in events if e.get("ev") == "serve.request"]
    assert steps and all("dur_s" in e for e in steps)
    assert len(reqs) == 2
    for e in reqs:
        assert e["dur_s"] >= 0 and e["new_tokens"] == 3

    # obs_report renders the serving section from the same run
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "obs_report.py"),
         str(tmp_path)], stdout=subprocess.PIPE, cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    text = out.stdout.decode()
    assert "serving: 2 request(s)" in text
    assert "request latency" in text


def test_request_span_id_threads_lifecycle_and_replay(tiny, tmp_path):
    """Per-request tracing (ISSUE 10): admission -> prefill -> per-token
    decode -> completion all share a deterministic request_span_id; a
    PREEMPTED request's second prefill reuses it (same id -> same span
    across replays/restarts), its completion prices replayed tokens,
    and the live goodput ledger moves that work into preempt_replay —
    with the identity intact."""
    from distributed_tensorflow_tpu import telemetry
    from distributed_tensorflow_tpu.telemetry import goodput

    cfg, params = tiny
    telemetry.configure(str(tmp_path), process_id=0)
    prev = goodput.activate(goodput.GoodputLedger(register=False))
    try:
        # pool too small for the concurrency: forces preemption
        engine = InferenceEngine(cfg, params, num_blocks=6, block_size=4,
                                 max_slots=4, max_prompt_len=16)
        engine.generate([[7, 7, 7], [8, 8, 8, 8], [9, 9]],
                        max_new_tokens=8)
        assert engine.scheduler.preemptions > 0
        led = goodput.active_ledger().snapshot()
    finally:
        goodput.activate(prev)
        telemetry.shutdown()
    events = telemetry.read_events(
        telemetry.event_log_path(str(tmp_path), 0))

    by_id: dict = {}
    for e in events:
        if e.get("ev", "").startswith("serve.") and "id" in e:
            by_id.setdefault(e["id"], []).append(e)
    assert set(by_id) == {"g0", "g1", "g2"}
    for rid, evs in by_id.items():
        names = [e["ev"] for e in evs]
        assert names[0] == "serve.admit"
        assert names[-1] == "serve.request"
        assert "serve.prefill" in names
        assert "serve.token" in names
        sids = {e.get("span_id") for e in evs}
        assert sids == {f"req/{rid}"}, sids
    # the preempted request replayed tokens through a SECOND prefill on
    # the same span, and its completion prices them
    replayed = [rid for rid, evs in by_id.items()
                if any(e["ev"] == "serve.request"
                       and e.get("replayed_tokens", 0) > 0
                       for e in evs)]
    assert replayed, "no request recorded replayed tokens"
    assert any(sum(1 for e in by_id[rid] if e["ev"] == "serve.prefill")
               >= 2 for rid in replayed)
    # ledger: replay priced as badput, identity exact
    assert led["badput_s"]["preempt_replay"] > 0
    total = led["goodput_s"] + sum(led["badput_s"].values())
    assert abs(led["wall_s"] - total) < 1e-6

    # trace assembly links the lifecycle with flow arrows per request
    trace = telemetry.assemble_run(str(tmp_path))
    assert trace["otherData"]["flow_links"] >= sum(
        len(v) - 1 for v in by_id.values())


def test_predict_emits_inference_telemetry(tmp_path):
    """Model.predict batches report predict.step events + the
    inference/ batch-latency histogram (satellite: batch and online
    inference share one namespace)."""
    from distributed_tensorflow_tpu import telemetry
    from distributed_tensorflow_tpu.models.mnist_cnn import MNISTCNN
    from distributed_tensorflow_tpu.training.model import Model

    model = Model(MNISTCNN())
    model.compile(optimizer="sgd", loss="sparse_categorical_crossentropy")
    x = np.random.default_rng(0).normal(
        size=(20, 28, 28, 1)).astype(np.float32)
    model.build(x[:8])
    telemetry.configure(str(tmp_path), process_id=0)
    try:
        preds = model.predict(x, batch_size=8)
    finally:
        telemetry.shutdown()
    assert preds.shape[0] == 20
    events = telemetry.read_events(
        telemetry.event_log_path(str(tmp_path), 0))
    psteps = [e for e in events if e.get("ev") == "predict.step"]
    assert len(psteps) == 3                    # 8 + 8 + 4
    assert [e["batch_size"] for e in psteps] == [8, 8, 4]
    hist = telemetry.get_registry().get("inference/step_time")
    assert hist is not None and hist.count >= 3


# ---------------------------------------------------------------------------
# supervised replica end-to-end (the chaos_sweep --serve shape)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.multiprocess
def test_supervised_replica_survives_sigkill(tmp_path):
    """A serving replica SIGKILLed mid-load is restarted by the
    supervisor and re-serves its in-flight requests: the completion log
    covers the whole workload, duplicates byte-identical."""
    from distributed_tensorflow_tpu.resilience import (
        KillSpec, RecoverySupervisor)
    from distributed_tensorflow_tpu.serving.replica import (
        completed_ids, seeded_requests, serving_replica)

    run_dir = str(tmp_path)
    n_requests = 10
    sup = RecoverySupervisor(
        serving_replica, num_workers=1,
        args=(run_dir, n_requests, 0),
        kwargs={"step_delay_s": 0.05},
        max_restarts=2,
        kill_plan=[KillSpec(worker=0, after_step=4)],
        generation_timeout_s=300.0,
        telemetry_dir=run_dir)
    sup.run()
    assert sup.restarts_used == 1
    done = completed_ids(os.path.join(run_dir, "served-0.jsonl"))
    expected = {r.id for r in seeded_requests(0, n_requests, 256)}
    assert set(done) == expected               # zero dropped
