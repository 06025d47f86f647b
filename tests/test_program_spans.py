"""The program's own spans and names (ISSUE 26): ``telemetry.span`` is a
trace annotation that lands in the profiler's trace with its counts, the
engine step is split where the work happens, every Pallas kernel carries
a stable name, and the two numbers the engine reports to its operators
(prefix hits, time to first token) mean what they say."""

import contextlib
import glob
import json
import time

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from distributed_tensorflow_tpu import telemetry
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig, TransformerLM, make_optimizer, make_train_step)
from distributed_tensorflow_tpu.ops import fused_ce
from distributed_tensorflow_tpu.serving.engine import InferenceEngine
from distributed_tensorflow_tpu.serving.scheduler import Request
from distributed_tensorflow_tpu.telemetry import events

X = list(range(2, 18))                      # two blocks of 8


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig.tiny(max_seq_len=64)
    params = TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def _engine(tiny, **kw):
    cfg, params = tiny
    kw = {"num_blocks": 32, "block_size": 8, "max_slots": 4,
          "max_prompt_len": 32, "prefix_caching": True, **kw}
    return InferenceEngine(cfg, params, **kw)


def _program_spans(logdir):
    """``[(name, start_ns, end_ns, stats)]`` of the dotted-name host
    events of the one xplane under ``logdir``, in start order."""
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "kv.")):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


# ---------------------------------------------------------------------------
# the span primitive
# ---------------------------------------------------------------------------

def test_span_off_formats_nothing_and_still_takes_fields(monkeypatch):
    """No profiler session, no log: the field-building branch is never
    entered, and the ``sp[key] = value`` idiom still works."""
    telemetry.shutdown()

    def boom(fields):
        raise AssertionError("fields were formatted with tracing off")

    monkeypatch.setattr(events, "_stats", boom)
    assert not telemetry.recording()
    with telemetry.span("serve.step", step=1, odd=object()) as sp:
        sp["admitted"] = 2
        sp.update(decoded=3)
    assert sp == {"admitted": 2, "decoded": 3}


class _Clock:
    """``time`` as ``events`` uses it, ticking 0.25 s a reading."""

    def __init__(self):
        self.now = 100.0

    def _tick(self):
        self.now += 0.25
        return self.now

    monotonic = perf_counter = time = _tick


def test_span_jsonl_record_is_byte_for_byte_the_old_one(tmp_path,
                                                        monkeypatch):
    """With a log configured ``telemetry.span`` writes exactly the line
    ``EventLog.span`` (unchanged) writes, fields, additions, error and
    all; a ``None`` field stays ``null``."""

    def body(span):
        with pytest.raises(KeyError):
            with span("serve.prefill", id="r1", cached_tokens=None,
                      queue_wait_s=0.000123) as sp:
                sp["bytes"] = 7
                raise KeyError("x")
        with span("serve.step", step=3) as sp:
            sp["admitted"] = 1

    monkeypatch.setattr(events, "time", _Clock())
    old = telemetry.EventLog(str(tmp_path / "old.jsonl"), process_id=5)
    body(old.span)
    old.close()
    monkeypatch.setattr(events, "time", _Clock())
    try:
        telemetry.configure(str(tmp_path), process_id=5)
        assert telemetry.recording()
        body(telemetry.span)
    finally:
        telemetry.shutdown()
    new = (tmp_path / "events-5.jsonl").read_bytes()
    assert new == (tmp_path / "old.jsonl").read_bytes()
    first = json.loads(new.splitlines()[0])
    assert first["cached_tokens"] is None and "KeyError" in first["error"]


def test_span_carries_fields_and_additions_into_the_trace(tmp_path):
    telemetry.shutdown()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert telemetry.recording()
        with telemetry.span("serve.step", step=4, none=None) as sp:
            with telemetry.span("kv.copy_on_write", blocks=2):
                pass
            sp["admitted"] = 1
    finally:
        jax.profiler.stop_trace()
    (outer, inner) = _program_spans(str(tmp_path))
    assert outer[0] == "serve.step" and inner[0] == "kv.copy_on_write"
    assert outer[3] == {"step": 4, "admitted": 1}      # None left out
    assert inner[3] == {"blocks": 2}
    assert outer[1] <= inner[1] and inner[2] <= outer[2]


# ---------------------------------------------------------------------------
# the engine step, split
# ---------------------------------------------------------------------------

def test_engine_step_spans_nest_in_order_with_their_counts(tiny, tmp_path):
    e = _engine(tiny)
    # warm every program and fill the prefix cache outside the trace
    e.submit(Request(id="w", tokens=tuple(X), max_new_tokens=3))
    e.submit(Request(id="w2", tokens=tuple(X[:5]), max_new_tokens=2))
    e.run_until_idle()
    admitted = []                       # per admit(): Σ cached_tokens
    admit = e.scheduler.admit

    def recording_admit():
        seqs = admit()
        admitted.append(sum(s.cached_tokens for s in seqs))
        return seqs

    e.scheduler.admit = recording_admit
    jax.profiler.start_trace(str(tmp_path))
    try:
        e.submit(Request(id="a", tokens=tuple(X + [40, 41]),
                         max_new_tokens=3))
        e.submit(Request(id="b", tokens=tuple(range(50, 59)),
                         max_new_tokens=3))
        e.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    spans = _program_spans(str(tmp_path))
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == len(admitted) >= 3
    last_launch = None                  # the decode launch read next
    for i, step in enumerate(steps):
        inside = [s for s in spans if s is not step
                  and step[1] <= s[1] and s[2] <= step[2]]
        names = [s[0] for s in inside]
        # everything the step did lies under it, in the order it ran
        assert names[:2] == ["serve.retire", "serve.schedule"]
        schedule = inside[1]
        assert schedule[3].get("cached_tokens", 0) == admitted[i]
        assert schedule[3]["admitted"] == step[3]["admitted"]
        # the step's decode: its launch (build, launch), then the read
        # (wait, commit) of the LAST step's launch and of this step's
        # admissions' first tokens, which the vector it was fed holds
        decode = next(s for s in inside if s[0] == "serve.decode")
        sub = [s for s in inside if s[0].startswith("serve.decode.")
               and decode[1] <= s[1] and s[2] <= decode[2]]
        launched = step[3]["decoded"]
        read = steps[i - 1][3]["decoded"] if i else 0
        assert [s[0] for s in sub] == (
            ["serve.decode.build", "serve.decode.launch"] * bool(launched)
            + ["serve.decode.wait", "serve.decode.commit"]
            * bool(read or step[3]["admitted"]))
        fields = dict(decode[3])
        assert (fields.pop("live"), fields.pop("kv_path")) == (launched,
                                                               "window")
        if launched:
            # every launch here is fed a token the host has not read:
            # the first the admissions', the others the last launch's
            assert (fields.pop("launched"), fields.pop("ahead")) == (1, 1)
        if read:
            # the CPU takes the window path: every slot's whole window;
            # the commit names the launch it banks, the last step's
            assert fields.pop("rows_read") >= read
            assert sub[-1][3] == {"tokens": read, "read": last_launch}
        if launched:
            launch = sub[1][3]
            assert launch["program"] == e._decode_next.__name__ == "decode"
            last_launch = launch["launch"]
        assert fields == ({
            "token_steps": read,
            "blocks_read": e.max_slots * e.window
            // e.cache_cfg.block_size,
            "passes": 1, "cache_layers": e.cfg.n_layers} if read else {})
    first = [s for s in spans
             if steps[0][1] <= s[1] and s[2] <= steps[0][2]]
    prefills = [s for s in first if s[0] == "serve.prefill"]
    assert [p[3]["id"] for p in prefills] == ["a", "b"]
    a, b = (p[3] for p in prefills)
    assert (a["program"], a["cached_tokens"], a["prompt_tokens"],
            a["span_id"]) == ("extend", 16, 18, "req/a")
    assert b["program"] == "prefill" and "cached_tokens" not in b
    # how the rows reached the pool (the CPU scatters) and how many
    # blocks took them: positions 16-17 lie in one, nine tokens and the
    # next one's room in two
    assert (a["kv_write"], a["blocks_written"]) == ("scatter", 1)
    assert (b["kv_write"], b["blocks_written"]) == ("scatter", 2)
    # and how extend's layers read the keys before its span (the CPU
    # gathers the window); a cold prefill reads none
    assert a["kv_read"] == e.kv_read == "window"
    assert "kv_read" not in b
    assert steps[0][3]["cached_tokens"] == admitted[0] == 16
    for p in prefills:                  # build and launch under each
        sub = [s[0] for s in first if s is not p
               and p[1] <= s[1] and s[2] <= p[2]]
        assert sub == ["serve.prefill.build", "serve.prefill.launch"]
    # each admission's first token is read in the step that launched it
    assert [s[3]["id"] for s in first
            if s[0] == "serve.prefill.commit"] == ["a", "b"]
    assert all(s[1] >= steps[0][1] for s in spans), "a span outside a step"


# ---------------------------------------------------------------------------
# launch and read numbers: which device run a launch starts, which launch a
# read banks
# ---------------------------------------------------------------------------

def _shared_prefixes(e):
    """A document, then two asks that share it (an extend each, the
    first a copy-on-write of the partly matched tail block) beside a cold
    prompt; returns the completion records."""
    e.submit(Request(id="doc", tokens=tuple(X), max_new_tokens=3))
    e.step()                            # doc prefilled: its blocks cached
    e.submit(Request(id="same", tokens=tuple(X), max_new_tokens=4))
    e.submit(Request(id="more", tokens=tuple(X + [40, 41]),
                     max_new_tokens=5))
    e.submit(Request(id="cold", tokens=tuple(range(50, 59)),
                     max_new_tokens=6))
    return e.run_until_idle()


def _preempting(e):
    """Three sequences on a pool too small for them: a preemption drains
    what is in flight before it picks a victim."""
    for i, p in enumerate([[7, 7, 7], [8, 8, 8, 8], [9, 9]]):
        e.submit(Request(id=f"p{i}", tokens=tuple(p), max_new_tokens=8))
    return e.run_until_idle()


LAUNCH_CASES = {
    "shared": ({}, _shared_prefixes),
    "steps4": ({"decode_steps": 4}, _shared_prefixes),
    "speculative": ({"speculative_k": 2}, _shared_prefixes),
    "preempt": ({"num_blocks": 6, "block_size": 4, "max_slots": 4,
                 "max_prompt_len": 16, "prefix_caching": False},
                _preempting),
}


@pytest.mark.parametrize("case", sorted(LAUNCH_CASES))
def test_every_launch_names_its_program_and_one_read_names_it(tiny, tmp_path,
                                                              case):
    """With an event log: each span that dispatches a program names it
    (``program``, the jitted function's ``__name__``) and numbers the
    dispatch (``launch``, consecutive over all programs); once
    ``run_until_idle`` returns, each admission's and decode's launch is
    named by exactly one ``read`` (a commit's or a drain's) and a copy's
    by none; each ``serve.prefill.commit`` carries the TTFT that the
    request's completion record reports, and each admission's launch the
    seconds since the scheduler made its sequence."""
    kw, run = LAUNCH_CASES[case]
    e = _engine(tiny, **kw)
    telemetry.configure(str(tmp_path), process_id=0)
    try:
        done = run(e)
    finally:
        telemetry.shutdown()
    evs = telemetry.read_events(telemetry.event_log_path(str(tmp_path), 0))
    launches = [x for x in evs if "launch" in x]
    numbers = sorted(x["launch"] for x in launches)
    assert numbers == list(range(1, e._launches + 1))
    decode = e._extend_spec if case == "speculative" else e._decode_next
    programs = {
        "serve.prefill.launch": {e._prefill_next.__name__,
                                 e._extend_next.__name__},
        "serve.decode.launch": {decode.__name__},
        "kv.copy_on_write": {e._copy.__name__}}
    for x in launches:
        assert x["program"] in programs[x["ev"]], x
        # an admission's launch times the host's share of it
        assert (x.get("since_admit_s", -1) >= 0) == (
            x["ev"] == "serve.prefill.launch"), x
    # an admission's launch is the last span to end inside its
    # serve.prefill, and names the program that span says it ran
    for before, x in zip(evs, evs[1:]):
        if x["ev"] == "serve.prefill":
            assert before["ev"] == "serve.prefill.launch"
            assert before["program"] == x["program"]
    kind = {x["launch"]: x["ev"] for x in launches}
    reads = [x for x in evs if "read" in x]
    for x in reads:
        assert kind[x["read"]] == ("serve.prefill.launch"
                                   if x["ev"] == "serve.prefill.commit"
                                   else "serve.decode.launch"), x
    times = {n: 0 for n in kind}
    for x in reads:
        times[x["read"]] += 1
    assert times == {n: int(ev != "kv.copy_on_write")
                     for n, ev in kind.items()}
    assert {x["ev"] for x in launches} >= {"serve.prefill.launch",
                                           "serve.decode.launch"}
    if case == "shared":
        assert "kv.copy_on_write" in kind.values()
        assert {x["program"] for x in launches
                if x["ev"] == "serve.prefill.launch"} == {"prefill", "extend"}
    if case == "preempt":
        assert e.scheduler.preemptions > 0
        assert any(x["ev"] == "serve.drain" and x["reason"] == "preempt"
                   for x in reads)
    # the last first token banked for a request (a preempted one's is
    # its replay's) is the TTFT its completion record reports
    ttft = {x["id"]: x["ttft_s"] for x in evs
            if x["ev"] == "serve.prefill.commit" and "ttft_s" in x}
    assert ttft == {rid: r["ttft_s"] for rid, r in done.items()}


def test_with_nothing_recording_the_spans_get_none_of_it(tiny, tmp_path,
                                                         monkeypatch):
    """No session, no log: no span is given a program, a launch number, a
    read, a TTFT or the seconds since an admission, and a read's counts of the pool are never built; the
    engine numbers its dispatches all the same, as a recorded run of the
    same requests does."""
    recorded = _engine(tiny)
    telemetry.configure(str(tmp_path), process_id=0)
    try:
        _shared_prefixes(recorded)
    finally:
        telemetry.shutdown()
    added = []
    span = telemetry.span

    @contextlib.contextmanager
    def watched(name, **fields):
        with span(name, **fields) as sp:
            yield sp
        added.append((name, dict(sp)))

    def never(host):
        raise AssertionError("a read's counts were built with nothing "
                             "recording")

    monkeypatch.setattr(telemetry, "span", watched)
    e = _engine(tiny)
    monkeypatch.setattr(e, "_decode_counts", never)
    assert not telemetry.recording()
    done = _shared_prefixes(e)
    assert set(done) == {"doc", "same", "more", "cold"}
    new = {"program", "launch", "read", "ttft_s", "since_admit_s",
           "token_steps",
           "blocks_read", "rows_read", "passes", "cache_layers"}
    assert added and all(not new & set(sp) for _, sp in added), added
    assert {"serve.prefill.commit", "serve.decode.commit",
            "kv.copy_on_write"} <= {name for name, _ in added}
    assert e._launches == recorded._launches > 0


# ---------------------------------------------------------------------------
# the two repaired numbers
# ---------------------------------------------------------------------------

def test_prefix_hits_count_an_admission_once_however_often_deferred(tiny):
    """A head request that waits two steps for the token budget is
    matched three times and admitted once: it counts once."""
    e = _engine(tiny, token_budget=12)
    e.submit(Request(id="doc", tokens=tuple(X), max_new_tokens=12))
    e.step()                            # doc prefilled: its blocks cached
    cache = e.scheduler.prefix_cache
    assert cache.stats()["hit_tokens"] == 0
    matches = []
    match = cache.match

    def counting_match(tokens):
        matches.append(len(tokens))
        return match(tokens)

    cache.match = counting_match
    # 16 cached + 14 to compute > the 11 tokens the budget leaves
    # beside the running sequence: deferred until that one finishes
    ask = tuple(X + list(range(60, 74)))
    e.submit(Request(id="ask", tokens=ask, max_new_tokens=2))
    done = e.run_until_idle()
    assert set(done) == {"doc", "ask"}
    assert len(matches) >= 3 and e.scheduler.deferred_prefill >= 2
    st = e.stats()["prefix_cache"]
    assert st["hit_requests"] == 1 and st["hit_tokens"] == 16
    assert st["lookups"] == 2
    assert st["lookup_tokens"] == len(X) - 1 + len(ask) - 1


@pytest.mark.parametrize("admit,read", [("scatter", "window"),
                                        ("interpret", "paged")])
def test_extend_span_says_how_its_layers_read_the_pool(tiny, tmp_path,
                                                       monkeypatch, admit,
                                                       read):
    """``serve.prefill`` says, for an extend and only for one, how its
    layers read the keys before the span: what the engine's extend
    program was built to do (a TPU reads a pool that lies with its rows
    on the lanes in place; here the builders are steered to the window
    or to the kernels, interpreted)."""
    from distributed_tensorflow_tpu.serving import decode as decode_lib
    from distributed_tensorflow_tpu.serving import engine as engine_lib
    for name in ("make_prefill_fn", "make_extend_fn"):
        monkeypatch.setattr(
            engine_lib.decode_lib, name,
            lambda c, cc, implementation=None, real=getattr(decode_lib, name):
            real(c, cc, implementation=admit))
    e = _engine(tiny)
    monkeypatch.undo()
    assert e.kv_read == read
    log = telemetry.configure(str(tmp_path), process_id=0)
    try:
        e.submit(Request(id="doc", tokens=tuple(X), max_new_tokens=2))
        e.run_until_idle()
        e.submit(Request(id="ask", tokens=tuple(X + [40, 41]),
                         max_new_tokens=2))
        e.run_until_idle()
    finally:
        telemetry.shutdown()
    prefills = {r["id"]: r for r in telemetry.read_events(log.path)
                if r.get("ev") == "serve.prefill"}
    assert (prefills["doc"]["program"], prefills["doc"]["kv_read"]) == (
        "prefill", None)
    assert (prefills["ask"]["program"], prefills["ask"]["kv_read"]) == (
        "extend", read)


def test_ttft_runs_from_submit_and_queue_wait_says_how_much(tiny,
                                                            tmp_path):
    e = _engine(tiny)
    try:
        telemetry.configure(str(tmp_path), process_id=0)
        e.submit(Request(id="r", tokens=tuple(X), max_new_tokens=2))
        time.sleep(0.25)                # queueing the client sees
        done = e.run_until_idle()
    finally:
        telemetry.shutdown()
    evs = telemetry.read_events(str(tmp_path / "events-0.jsonl"))
    (prefill,) = [x for x in evs if x["ev"] == "serve.prefill"]
    (request,) = [x for x in evs if x["ev"] == "serve.request"]
    ttft = done["r"]["ttft_s"]
    assert ttft >= 0.25 and prefill["queue_wait_s"] >= 0.25
    assert ttft >= prefill["queue_wait_s"]
    assert request["ttft_s"] == round(ttft, 6)
    hist = telemetry.get_registry().snapshot()[
        "inference/time_to_first_token"]
    assert hist["max"] >= 0.25


# ---------------------------------------------------------------------------
# names on the device
# ---------------------------------------------------------------------------

KERNEL_NAMES = {
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "fused_ce_fwd",
    "fused_ce_bwd_dh", "fused_ce_bwd_de", "fused_ce_bwd_dhde_acc_dh",
    "fused_ce_bwd_dhde_acc_de"}


def _pallas_names(jaxpr) -> list[str]:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    out.extend(_pallas_names(inner))
    return out


def test_every_kernel_of_a_train_step_carries_a_stable_name():
    """The jaxpr of a tiny train step on the kernel paths (traced, never
    lowered, so the CPU will do): flash attention forward and both
    backward kernels, fused CE forward and backward."""
    cfg = TransformerConfig.tiny(
        max_seq_len=128, scan_layers=False, remat=False,
        attention_impl="pallas", attn_block_q=64, attn_block_k=64,
        loss_impl="kernel", loss_kernel_impl="pallas", loss_block_n=32,
        loss_block_v=64)
    model = TransformerLM(cfg)
    tx = make_optimizer(cfg)
    tokens = jnp.zeros((2, 128), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])
    state = {"params": params,
             "opt_state": jax.eval_shape(tx.init, params),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    step = make_train_step(cfg, model, tx)
    names = _pallas_names(
        jax.make_jaxpr(step)(state, {"tokens": tokens}).jaxpr)
    assert set(names) <= KERNEL_NAMES, set(names) - KERNEL_NAMES
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
            "fused_ce_fwd"} <= set(names)
    assert any(n.startswith("fused_ce_bwd_") for n in names)


@pytest.mark.parametrize("variant,backward", [
    ("a", {"fused_ce_bwd_dhde_acc_dh"}),
    ("b", {"fused_ce_bwd_dhde_acc_de"}),
    ("split", {"fused_ce_bwd_dh", "fused_ce_bwd_de"})])
def test_every_fused_ce_backward_kernel_is_named(variant, backward):
    h = jnp.ones((256, 64), jnp.float32)
    e = jnp.ones((1024, 64), jnp.float32)
    t = jnp.zeros((256,), jnp.int32)

    def loss(h, e):
        return fused_ce.fused_cross_entropy(
            h, e, t, block_n=64, block_v=128, implementation="pallas",
            bwd_variant=variant, bwd_block_n=64, bwd_block_v=128).sum()

    names = _pallas_names(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(h, e).jaxpr)
    assert set(names) == {"fused_ce_fwd"} | backward
