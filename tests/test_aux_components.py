"""Tests for the auxiliary-component batch: platform resolvers,
CentralStorage/AggregatingVariable, V1 PS strategy, bf16 policy scope,
on-device loops + infeed, tensor tracer, summary writer, gauges,
check_health fail-fast."""

import json
import os
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_tensorflow_tpu as dtx


# -- platform resolvers (≙ slurm/sagemaker/gce/kubernetes resolvers) -------

def test_slurm_resolver_hostlist_and_tasks():
    from distributed_tensorflow_tpu.cluster.platform_resolvers import (
        SlurmClusterResolver, expand_hostlist, expand_tasks_per_node)
    assert expand_hostlist("n[1-3,7],m0") == ["n1", "n2", "n3", "n7", "m0"]
    assert expand_hostlist("c[01-03]") == ["c01", "c02", "c03"]
    assert expand_tasks_per_node("2(x3),1") == [2, 2, 2, 1]

    env = {
        "SLURM_PROCID": "3",
        "SLURM_STEP_NUM_TASKS": "4",
        "SLURM_STEP_NODELIST": "node[1-2]",
        "SLURM_STEP_TASKS_PER_NODE": "2(x2)",
    }
    r = SlurmClusterResolver(env=env, port_base=9000)
    spec = r.cluster_spec()
    assert spec.task_addresses("worker") == [
        "node1:9000", "node1:9001", "node2:9000", "node2:9001"]
    assert (r.task_type, r.task_id) == ("worker", 3)
    # ps + worker split
    r2 = SlurmClusterResolver(jobs={"ps": 1, "worker": 3}, env=env)
    spec2 = r2.cluster_spec()
    assert spec2.num_tasks("ps") == 1 and spec2.num_tasks("worker") == 3
    assert (r2.task_type, r2.task_id) == ("worker", 2)


def test_sagemaker_resolver():
    from distributed_tensorflow_tpu.cluster.platform_resolvers import (
        SageMakerClusterResolver)
    env = {"SM_HOSTS": json.dumps(["algo-2", "algo-1"]),
           "SM_CURRENT_HOST": "algo-2"}
    r = SageMakerClusterResolver(env=env)
    assert r.cluster_spec().task_addresses("worker") == [
        "algo-1:2223", "algo-2:2223"]
    assert (r.task_type, r.task_id) == ("worker", 1)


def test_gce_resolver_with_injected_lister():
    from distributed_tensorflow_tpu.cluster.platform_resolvers import (
        GCEClusterResolver)
    r = dtx.GCEClusterResolver(
        "proj", "us-central1-a", "group",
        list_instances_fn=lambda p, z, g: ["b-host", "a-host"])
    assert r.cluster_spec().task_addresses("worker") == [
        "a-host:8470", "b-host:8470"]


def test_kubernetes_resolver_with_injected_pods():
    def list_pods(selector):
        assert selector == "job-name=worker"
        return [("pod-1", "10.0.0.2", "Running"),
                ("pod-0", "10.0.0.1", "Running")]

    r = dtx.KubernetesClusterResolver(
        {"worker": ["job-name=worker"]}, list_pods_fn=list_pods)
    assert r.cluster_spec().task_addresses("worker") == [
        "10.0.0.1:8470", "10.0.0.2:8470"]

    def one_pending(selector):
        return [("pod-0", "10.0.0.1", "Pending")]

    r2 = dtx.KubernetesClusterResolver({"worker": ["job-name=worker"]},
                                       list_pods_fn=one_pending)
    with pytest.raises(RuntimeError, match="Pending"):
        r2.cluster_spec()


# -- central storage + aggregating variables (≙ ps_values.py) --------------

def test_central_storage_variable_lives_on_parameter_device(devices):
    s = dtx.CentralStorageStrategy()
    with s.scope():
        v = s.create_variable(np.ones((2, 2)), name="w")
    assert isinstance(v, dtx.AggregatingVariable)
    assert v.device == s.parameter_device
    # single copy, not mesh-placed
    assert v.value.device == s.parameter_device


def test_central_storage_run_aggregates_and_comes_home(devices):
    s = dtx.CentralStorageStrategy()
    n = s.num_replicas_in_sync
    with s.scope():
        v = s.create_variable(np.zeros(()), name="acc")

    def fn():
        ctx = dtx.get_replica_context()
        rid = ctx.replica_id_in_sync_group
        v.assign_add(rid.astype(jnp.float32) if hasattr(rid, "astype")
                     else float(rid))

    s.run(fn)
    # MEAN-aggregated write, applied to the one copy, back home
    np.testing.assert_allclose(float(np.asarray(v.read_value())),
                               (n - 1) / 2, rtol=1e-6)
    assert v.value.device == s.parameter_device


def test_caching_variable():
    from distributed_tensorflow_tpu.parallel.values import (
        DistributedVariable)
    src = DistributedVariable(jnp.ones((2,)), name="src")
    cache = dtx.CachingVariable(src)
    np.testing.assert_allclose(np.asarray(cache.read_value()), [1, 1])
    src.assign(jnp.zeros((2,)))
    np.testing.assert_allclose(np.asarray(cache.read_value()), [1, 1])
    cache.update_cache()
    np.testing.assert_allclose(np.asarray(cache.read_value()), [0, 0])
    cache.assign_add(jnp.ones((2,)))          # write-through + refresh
    np.testing.assert_allclose(np.asarray(src.read_value()), [1, 1])
    np.testing.assert_allclose(np.asarray(cache.read_value()), [1, 1])


def test_ps_v1_round_robin_placement(devices):
    s = dtx.ParameterServerStrategyV1(
        parameter_devices=jax.devices()[:2])
    with s.scope():
        vs = [s.create_variable(np.zeros(2), name=f"v{i}")
              for i in range(4)]
    homes = [v.device for v in vs]
    assert homes == [jax.devices()[0], jax.devices()[1]] * 2


# -- bf16 policy scope (≙ tpu/bfloat16.py) ---------------------------------

def test_bfloat16_scope():
    bf = dtx.bfloat16
    assert bf.get_policy().name == "float32"
    x = jnp.ones((2,), jnp.float32)
    ids = jnp.ones((2,), jnp.int32)
    with bf.bfloat16_scope() as p:
        assert p.compute_dtype == jnp.bfloat16
        assert p.variable_dtype == jnp.float32
        cx, cids = bf.cast_to_compute((x, ids))
        assert cx.dtype == jnp.bfloat16
        assert cids.dtype == jnp.int32        # ints untouched
        assert bf.cast_to_variable(cx).dtype == jnp.float32
    assert bf.get_policy().name == "float32"  # restored


# -- on-device loops + infeed (≙ training_loop.py / tpu_feed.py) -----------

def test_repeat_and_while_loop(devices):
    from distributed_tensorflow_tpu.training import loops
    out = loops.repeat(5, lambda s: s + 1.0, jnp.zeros(()))
    assert float(out) == 5.0
    out = loops.while_loop(lambda s: s < 7, lambda s: s + 2, jnp.zeros((),
                                                                       jnp.int32))
    assert int(out) == 8


def test_run_steps_scan_matches_python_loop(devices):
    from distributed_tensorflow_tpu.training import loops

    def step(s, batch):
        s = s + batch.sum()
        return s, {"loss": batch.mean()}

    batches = [np.full((4,), i, np.float32) for i in range(6)]
    stacked = loops.stack_batches(batches)
    final, metrics = jax.jit(
        lambda s, b: loops.run_steps(step, s, b))(jnp.zeros(()), stacked)
    assert float(final) == sum(4.0 * i for i in range(6))
    np.testing.assert_allclose(np.asarray(metrics["loss"]),
                               np.arange(6, dtype=np.float32))


def test_infeed_loop_streams_all_batches(devices):
    from distributed_tensorflow_tpu.training.loops import InfeedLoop
    batches = [np.full((2,), i, np.float32) for i in range(10)]
    loop = InfeedLoop(iter(batches), buffer_size=3)
    got = [float(b[0]) for b in loop]
    assert got == list(range(10))


# -- tensor tracer (≙ tpu/tensor_tracer.py) --------------------------------

def test_trace_point_collects_stats(devices):
    from distributed_tensorflow_tpu.utils.tensor_tracer import (
        TensorTracer, trace_point)

    @jax.jit
    def f(x):
        h = trace_point("hidden", x * 2.0)
        return trace_point("out", h.sum())

    tt = TensorTracer()
    with tt:
        f(jnp.ones((4,)))
    report = tt.report()
    names = [n for n, _ in report.entries]
    assert "hidden" in names and "out" in names
    stats = dict(report.entries)["hidden"]
    np.testing.assert_allclose(stats["norm"], 4.0)
    assert stats["nan_count"] == 0
    # outside the context: no recording
    f(jnp.ones((4,)))
    assert len(tt.report().entries) == len(report.entries)


def test_trace_flax_finds_first_nan(devices):
    from flax import linen as nn
    from distributed_tensorflow_tpu.utils.tensor_tracer import (
        find_first_nan, trace_flax)

    class Bad(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(4, name="ok")(x)
            x = jnp.log(-jnp.abs(x) - 1.0)    # always NaN
            return nn.Dense(2, name="after")(x)

    m = Bad()
    variables = m.init(jax.random.PRNGKey(0), jnp.ones((2, 3)))
    out, report = trace_flax(m, variables, jnp.ones((2, 3)))
    assert report.first_nan() is not None
    assert find_first_nan(m, variables, jnp.ones((2, 3))) is not None

    class Good(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(2)(x)

    g = Good()
    gv = g.init(jax.random.PRNGKey(0), jnp.ones((2, 3)))
    assert find_first_nan(g, gv, jnp.ones((2, 3))) is None


# -- summary writer + gauges (≙ §5.5 observability) ------------------------

def _read_tfrecords(path):
    """Decode the TFRecord framing back (validates lengths + crcs)."""
    from distributed_tensorflow_tpu.utils.summary import _masked_crc
    out = []
    with open(path, "rb") as f:
        while True:
            header = f.read(8)
            if not header:
                return out
            (length,) = struct.unpack("<Q", header)
            (hcrc,) = struct.unpack("<I", f.read(4))
            assert hcrc == _masked_crc(header)
            payload = f.read(length)
            (pcrc,) = struct.unpack("<I", f.read(4))
            assert pcrc == _masked_crc(payload)
            out.append(payload)


def test_summary_writer_event_file(tmp_path):
    from distributed_tensorflow_tpu.utils.summary import SummaryWriter
    with SummaryWriter(str(tmp_path)) as w:
        w.scalar("loss", 0.5, step=1)
        w.scalars({"acc": 0.9, "lr": 1e-3}, step=2)
    files = [f for f in os.listdir(tmp_path)
             if f.startswith("events.out.tfevents")]
    assert len(files) == 1
    records = _read_tfrecords(tmp_path / files[0])
    assert len(records) == 4                  # file_version + 3 scalars
    assert b"brain.Event:2" in records[0]
    assert b"loss" in records[1]
    # simple_value 0.5 encoded little-endian float after tag 2, wire 5
    assert struct.pack("<f", 0.5) in records[1]


def test_summary_histogram_wire_format(tmp_path):
    """HistogramProto encoding: parse back field-by-field (numbers from
    TF summary.proto: min=1,max=2,num=3,sum=4,sum_squares=5,
    bucket_limit=6,bucket=7) without importing TF."""
    import numpy as np
    from distributed_tensorflow_tpu.utils.summary import SummaryWriter
    vals = np.arange(100, dtype=np.float64)
    with SummaryWriter(str(tmp_path)) as w:
        w.histogram("wts", vals, step=3, bins=10)
    files = [f for f in os.listdir(tmp_path)
             if f.startswith("events.out.tfevents")]
    rec = _read_tfrecords(tmp_path / files[0])[1]
    assert b"wts" in rec
    # num = 100 encoded as double field 3 inside the histo submessage
    assert struct.pack("<d", 100.0) in rec
    assert struct.pack("<d", 0.0) in rec          # min
    assert struct.pack("<d", 99.0) in rec         # max
    assert struct.pack("<d", float(vals.sum())) in rec


def test_histogram_parses_with_tf_proto(tmp_path):
    """Interop crosscheck: TF's OWN Event proto parser reads our
    histogram events (field numbers + framing). Skipped when the
    installed protobuf runtime can't load TF's generated protos."""
    try:
        from tensorflow.core.util import event_pb2
    except Exception as e:                        # descriptor mismatch etc.
        pytest.skip(f"tensorflow protos unavailable: {e}")
    import numpy as np
    from distributed_tensorflow_tpu.utils.summary import SummaryWriter
    vals = np.concatenate([np.random.default_rng(0).normal(size=500),
                           [np.nan, np.inf]])     # non-finite must not crash
    with SummaryWriter(str(tmp_path)) as w:
        w.scalar("loss", 1.5, step=0)
        w.histogram("wts", vals, step=0)
    fn = [f for f in os.listdir(tmp_path) if "tfevents" in f][0]
    data = (tmp_path / fn).read_bytes()
    off, seen = 0, {}
    while off < len(data):
        (ln,) = struct.unpack("<Q", data[off:off + 8]); off += 12
        ev = event_pb2.Event(); ev.ParseFromString(data[off:off + ln])
        off += ln + 4
        for v in ev.summary.value:
            if v.HasField("histo"):
                seen["histo"] = v.histo
            elif v.HasField("simple_value"):
                seen[v.tag] = v.simple_value
    assert seen["loss"] == 1.5
    h = seen["histo"]
    assert h.num == 500                       # finite values only
    assert len(h.bucket_limit) == len(h.bucket)
    assert abs(sum(h.bucket) - h.num) < 1e-6


def test_tensorboard_callback_writes_train_and_val(tmp_path, devices):
    """≙ tf_keras.callbacks.TensorBoard: epoch scalars land in
    logdir/train and logdir/validation event files."""
    from distributed_tensorflow_tpu.training.callbacks import TensorBoard
    cb = TensorBoard(log_dir=str(tmp_path))
    cb.on_epoch_end(0, {"loss": 1.25, "val_loss": 2.5, "acc": 0.5})
    cb.on_train_end()
    train_files = os.listdir(tmp_path / "train")
    val_files = os.listdir(tmp_path / "validation")
    assert train_files and val_files
    # no validation data -> NO spurious empty validation run (lazy writers)
    cb2 = TensorBoard(log_dir=str(tmp_path / "noval"))
    cb2.on_epoch_end(0, {"loss": 1.0})
    cb2.on_train_end()
    assert not (tmp_path / "noval" / "validation").exists()
    train_rec = b"".join(_read_tfrecords(
        tmp_path / "train" / train_files[0]))
    val_rec = b"".join(_read_tfrecords(
        tmp_path / "validation" / val_files[0]))
    assert b"epoch_loss" in train_rec and b"epoch_acc" in train_rec
    assert b"epoch_loss" in val_rec and b"epoch_acc" not in val_rec
    assert struct.pack("<f", 2.5) in val_rec


def test_crc32c_known_vectors():
    from distributed_tensorflow_tpu.utils.summary import _crc32c
    # RFC 3720 test vector: 32 zero bytes
    assert _crc32c(b"\x00" * 32) == 0x8A9136AA
    assert _crc32c(b"123456789") == 0xE3069283


def test_strategy_gauge_set_by_scope(devices):
    from distributed_tensorflow_tpu.utils.summary import strategy_gauge
    s = dtx.MirroredStrategy()
    with s.scope():
        pass
    assert strategy_gauge.value() == "MirroredStrategy"


# -- legacy distribute coordinator (≙ distribute_coordinator.py:627) -------

def test_run_distribute_coordinator_standalone(devices):
    from distributed_tensorflow_tpu.coordinator.distribute_coordinator \
        import CoordinatorMode, run_distribute_coordinator

    def worker_fn(ctx):
        assert ctx.is_chief
        assert not ctx.distributed_mode
        assert dtx.get_strategy() is ctx.strategy
        v = ctx.strategy.create_variable(np.zeros(()), name="c")
        ctx.strategy.run(lambda: v.assign_add(1.0))
        return float(np.asarray(v.read_value()))

    out = run_distribute_coordinator(
        worker_fn, dtx.MirroredStrategy(),
        mode=CoordinatorMode.STANDALONE_CLIENT)
    assert out == 1.0


def test_instrument_traces_every_equation(devices):
    """Whole-program jaxpr instrumentation: every numeric intermediate
    gets a stats entry, no annotations (≙ tensor_tracer.py per-op
    rewrite), and the wrapper stays jit-compatible."""
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.utils.tensor_tracer import trace_fn

    def f(x):
        y = jnp.sin(x) * 2.0
        z = jax.jit(lambda a: a + 1.0)(y)   # entered recursively
        return z.sum()

    out, report = trace_fn(f, jnp.ones((4, 4)))
    np.testing.assert_allclose(float(out),
                               float((jnp.sin(jnp.ones((4, 4))) * 2
                                      + 1).sum()), rtol=1e-6)
    names = [n for n, _ in report.entries]
    assert any("sin" in n for n in names), names
    assert any("mul" in n for n in names), names
    assert any("add" in n for n in names), names        # inside the jit
    assert any("reduce_sum" in n for n in names), names
    # source-location suffix present (file:line localization)
    assert any(".py" in n for n in names), names


def test_instrument_filters_and_report_file(tmp_path, devices):
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.utils.tensor_tracer import trace_fn

    def f(x):
        return (jnp.sin(x) * jnp.cos(x)).sum()

    _, report = trace_fn(f, jnp.ones((8,)), op_regex="sin|cos",
                         report_path=str(tmp_path / "tt" / "report.txt"))
    names = [n for n, _ in report.entries]
    assert names and all(("sin" in n or "cos" in n) for n in names), names
    text = (tmp_path / "tt" / "report.txt").read_text()
    assert "first_nan: none" in text


def test_instrument_locates_injected_nan_in_flagship(devices):
    """The round-3 'done' criterion: locate an injected NaN inside the
    flagship transformer WITHOUT any model annotation, from the jaxpr
    alone, with a source-line report entry."""
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig, TransformerLM, synthetic_tokens)
    from distributed_tensorflow_tpu.utils.tensor_tracer import trace_fn
    from flax.linen import partitioning as nn_partitioning
    from distributed_tensorflow_tpu.models.transformer import (
        LOGICAL_AXIS_RULES)

    cfg = TransformerConfig.tiny(n_layers=1)
    model = TransformerLM(cfg)
    tokens = synthetic_tokens(2, cfg.max_seq_len, cfg.vocab_size)
    with nn_partitioning.axis_rules(list(LOGICAL_AXIS_RULES)):
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]

    # poison ONE weight deep inside the stacked layers
    bad = jax.tree_util.tree_map(lambda x: x, params)
    wi = np.array(bad["layers"]["mlp"]["wi"])   # writable copy
    wi[..., 0, 0] = np.nan
    bad["layers"]["mlp"]["wi"] = jnp.asarray(wi)

    def fwd(params, tokens):
        with nn_partitioning.axis_rules(list(LOGICAL_AXIS_RULES)):
            return model.apply({"params": params}, tokens).sum()

    _, report = trace_fn(fwd, bad, tokens)
    first = report.first_nan()
    assert first is not None
    # healthy params: no NaN anywhere
    _, clean = trace_fn(fwd, params, tokens)
    assert clean.first_nan() is None

def test_instrument_scan_body_per_iteration(devices):
    """Scan bodies are rewritten once and every trip reports stats
    tagged with the carried iteration counter (VERDICT r4 item 5)."""
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.utils.tensor_tracer import trace_fn

    def f(x):
        def body(c, t):
            return c * t + 1.0, c.sum()
        out, ys = jax.lax.scan(body, x, jnp.arange(4.0))
        return out.sum() + ys.sum()

    out, report = trace_fn(f, jnp.ones((3,)))
    scan_entries = [(n, s) for n, s in report.entries if "scan/" in n]
    assert scan_entries, [n for n, _ in report.entries]
    iters = sorted({int(s["iteration"]) for _, s in scan_entries})
    assert iters == [0, 1, 2, 3], iters
    # numerics unchanged by instrumentation
    def ref(x):
        def body(c, t):
            return c * t + 1.0, c.sum()
        out, ys = jax.lax.scan(body, x, jnp.arange(4.0))
        return out.sum() + ys.sum()
    np.testing.assert_allclose(float(out), float(ref(jnp.ones((3,)))),
                               rtol=1e-6)


def test_instrument_while_and_cond_bodies(devices):
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.utils.tensor_tracer import trace_fn

    def f(x):
        def cond(state):
            c, _ = state
            return c.sum() < 100.0

        def body(state):
            c, n = state
            return c * 2.0, n + 1

        c, n = jax.lax.while_loop(cond, body, (x, 0))
        return jax.lax.cond(n > 3, lambda v: v + 1.0,
                            lambda v: v - 1.0, c).sum()

    out, report = trace_fn(f, jnp.ones((2,)))
    names = [n for n, _ in report.entries]
    assert any("while/" in n for n in names), names
    assert any("branch" in n for n in names), names
    wh = [(n, s) for n, s in report.entries if "while/" in n]
    assert max(int(s["iteration"]) for _, s in wh) >= 1
    # numerics: 1 -> 2 -> ... while sum<100: 2 elems so stops at 64
    # (sum 128); n=6 -> branch v+1 -> sum = 130
    np.testing.assert_allclose(float(out), 130.0, rtol=1e-6)


def test_instrument_scan_layers_train_step_localizes_layer(devices):
    """THE VERDICT r4 item-5 'done' criterion: first-NaN localization
    inside a scan_layers=True flagship TRAIN step (value_and_grad +
    remat + scan) with no model reconfiguration — the iteration tag IS
    the layer index."""
    import jax.numpy as jnp
    import optax
    from distributed_tensorflow_tpu.models import transformer
    from distributed_tensorflow_tpu.utils.tensor_tracer import trace_fn

    cfg = transformer.TransformerConfig.tiny(scan_layers=True,
                                             remat=True, loss_chunks=4)
    model = transformer.TransformerLM(cfg)
    toks = transformer.synthetic_tokens(2, 64, cfg.vocab_size)[:, :64]
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    tx = optax.sgd(1e-2)
    step = transformer.make_train_step(cfg, model, tx)

    wi = np.array(params["layers"]["mlp"]["wi"])  # (n_layers, D, 2F)
    wi[1, 0, 0] = np.nan                          # poison layer 1 only
    params["layers"]["mlp"]["wi"] = jnp.asarray(wi)
    state = {"params": params, "opt_state": tx.init(params), "step": 0}

    _, report = trace_fn(step, state, {"tokens": toks})
    loc = report.first_nan()
    assert loc is not None and "scan/" in loc, loc
    assert "iteration 1" in loc, loc
    assert "transformer.py" in loc, loc
