"""Launch ahead (ISSUE 38): the engine launches the next decode before
it reads the last one's tokens, each slot's last chosen token kept on
the device. Every case holds the tokens to full-sequence recompute (or
to a solo run) and the pool's blocks to a whole account: the mechanism
must change when the host reads, never what it reads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu import telemetry
from distributed_tensorflow_tpu.models import scmoe
from distributed_tensorflow_tpu.models.transformer import (
    TransformerConfig, TransformerLM)
from distributed_tensorflow_tpu.serving import InferenceEngine, Request
from distributed_tensorflow_tpu.serving.kv_cache import BlockTable

pytestmark = pytest.mark.usefixtures("leave_no_programs_behind")

PROMPTS = [[5, 6, 7], [1, 2, 3, 4, 5, 6, 7, 8], [9] * 12, [3, 1, 4, 1, 5]]


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


def _engine(cfg, params, **kw):
    return InferenceEngine(cfg, params, **{
        "num_blocks": 32, "block_size": 8, "max_slots": 2,
        "max_prompt_len": 16, **kw})


def _clean(engine):
    acct = engine.block_accounting()
    assert acct["conserved"] and acct["leaked_refs"] == 0, acct
    assert not engine.scheduler.running and engine._launched is None


def _logged(tmp_path, run):
    """``run()`` with an event log; returns its result and the events."""
    telemetry.configure(str(tmp_path), process_id=0)
    try:
        out = run()
    finally:
        telemetry.shutdown()
    return out, telemetry.read_events(
        telemetry.event_log_path(str(tmp_path), 0))


def _named(events, name):
    return [e for e in events if e.get("ev") == name]


def _launches(events):
    return [e for e in _named(events, "serve.decode") if e.get("launched")]


@pytest.mark.parametrize("steps", [1, 4])
def test_a_busy_run_launches_ahead_and_serves_greedy_tokens(tiny, tmp_path,
                                                            steps):
    """Four requests on two slots, so admissions and decodes share
    steps: the tokens are recompute's, every launch is fed a token the
    host had not read, each launch's work is read once (its token-steps
    are the tokens committed from it), and where a budget ends no
    token-step is launched past it."""
    cfg, params = tiny
    budgets = [6, 9, 3, 7]
    e = _engine(cfg, params, decode_steps=steps)
    for i, (p, n) in enumerate(zip(PROMPTS, budgets)):
        e.submit(Request(id=f"r{i}", tokens=tuple(p), max_new_tokens=n))
    outs, events = _logged(tmp_path, e.run_until_idle)
    for i, (p, n) in enumerate(zip(PROMPTS, budgets)):
        assert outs[f"r{i}"]["tokens"] == reference_greedy(cfg, params, p, n)
    _clean(e)
    launches = _launches(events)
    assert launches and all(x["ahead"] == 1 for x in launches)
    reads = [x for x in _named(events, "serve.decode") if "token_steps" in x]
    commits = _named(events, "serve.decode.commit")
    # the first token of each request is its prefill's; the budget's
    # end is known before a launch, so nothing is computed past it
    assert sum(x["token_steps"] for x in reads) == sum(budgets) - len(
        budgets)
    assert sum(c["tokens"] for c in commits) == sum(budgets) - len(budgets)
    assert not _named(events, "serve.drain")
    # each admission's first token is read in the step that launched it
    # (a span's record is written when it ends: a prefill's lands before
    # the record of the step it ran in)
    step_of = {"serve.prefill": {}, "serve.prefill.commit": {}}
    pending = []
    for x in events:
        if x.get("ev") in step_of:
            pending.append((x["ev"], x["id"]))
        elif x.get("ev") == "serve.step":
            for name, rid in pending:
                step_of[name][rid] = x["step"]
            pending = []
    assert len(step_of["serve.prefill"]) == 4
    assert step_of["serve.prefill"] == step_of["serve.prefill.commit"]


@pytest.mark.parametrize("steps", [1, 4])
def test_an_end_token_read_with_the_next_launch_in_flight(tiny, tmp_path,
                                                          steps):
    """The end token is read while a launch that continued the sequence
    is already queued: the tokens end exactly at the first end token,
    and what was computed past it is dropped with its position."""
    cfg, params = tiny
    ref = reference_greedy(cfg, params, PROMPTS[0], 10)
    eos = next(t for t in ref[1:] if t != ref[0])
    want = ref[:ref.index(eos) + 1]
    e = _engine(cfg, params, decode_steps=steps)
    e.submit(Request(id="e", tokens=tuple(PROMPTS[0]), max_new_tokens=10,
                     eos_id=eos))
    done, events = _logged(tmp_path, e.run_until_idle)
    assert done["e"]["tokens"] == want
    _clean(e)
    reads = [x for x in _named(events, "serve.decode") if "token_steps" in x]
    wasted = (sum(x["token_steps"] for x in reads)
              - sum(c["tokens"] for c in _named(events,
                                                "serve.decode.commit")))
    # one step: the launch queued behind the end token's; several: the
    # rest of the launch the end token fell in, and maybe one more
    assert wasted >= 1 if steps == 1 else wasted >= 0


def test_an_end_token_first_leaves_a_launch_to_drain(tiny, tmp_path):
    """An end token that is the prefill's own: the sequence's first decode
    is already launched when the token is read; the request completes in
    that step, and the engine, left with nothing running, drains the
    launch (``serve.drain`` with its reason) and holds nothing in
    flight."""
    cfg, params = tiny
    ref = reference_greedy(cfg, params, PROMPTS[1], 1)
    e = _engine(cfg, params)
    e.submit(Request(id="f", tokens=tuple(PROMPTS[1]), max_new_tokens=8,
                     eos_id=ref[0]))
    done, events = _logged(tmp_path, lambda: e.step())
    assert [r["tokens"] for r in done] == [ref]
    _clean(e)
    assert [d["reason"] for d in _named(events, "serve.drain")] == ["idle"]


def test_a_preemption_drains_first_and_replays_what_was_generated(
        tiny, tmp_path):
    """A pool too small for three sequences at once: the preemption reads
    every token in flight first (its replay carries all it generated),
    and the outputs are recompute's."""
    cfg, params = tiny
    e = InferenceEngine(cfg, params, num_blocks=6, block_size=4,
                        max_slots=4, max_prompt_len=16)
    prompts = [[7, 7, 7], [8, 8, 8, 8], [9, 9]]
    outs, events = _logged(tmp_path, lambda: e.generate(prompts,
                                                       max_new_tokens=8))
    assert e.scheduler.preemptions > 0
    for p, o in zip(prompts, outs):
        assert o == reference_greedy(cfg, params, p, 8)
    _clean(e)
    assert "preempt" in {d["reason"] for d in _named(events, "serve.drain")}


def test_a_version_install_drains_then_serves_the_new_weights(tiny, tmp_path):
    """``install_version`` with a decode in flight: the launch is read
    (and its tokens dropped with the requeue), and every request is then
    served whole by the new weights."""
    cfg, params = tiny
    new = TransformerLM(cfg).init(jax.random.PRNGKey(7),
                                  jnp.zeros((1, 8), jnp.int32))["params"]
    e = _engine(cfg, params)
    for i, p in enumerate(PROMPTS[:2]):
        e.submit(Request(id=f"v{i}", tokens=tuple(p), max_new_tokens=6))

    def run():
        e.step()
        e.step()
        assert e._launched is not None              # a token in flight
        assert e.install_version(new)["requeued"] == 2
        assert e._launched is None
        return e.run_until_idle()

    done, events = _logged(tmp_path, run)
    for i, p in enumerate(PROMPTS[:2]):
        assert done[f"v{i}"]["tokens"] == reference_greedy(cfg, new, p, 6)
    _clean(e)
    assert "swap" in {d["reason"] for d in _named(events, "serve.drain")}


def test_a_migration_drains_both_ends(tiny, tmp_path):
    """Export with the sequence's next token in flight, adopt into an
    engine with a launch of its own in flight: both drain first, and both
    requests end as recompute ends them."""
    cfg, params = tiny
    a, b = _engine(cfg, params), _engine(cfg, params)
    a.submit(Request(id="m", tokens=tuple(PROMPTS[1]), max_new_tokens=8))
    b.submit(Request(id="s", tokens=tuple(PROMPTS[2]), max_new_tokens=8))

    def run():
        for eng in (a, b):
            eng.step()
            eng.step()
            assert eng._launched is not None
        seq = next(iter(a.scheduler.running.values()))
        b.adopt_sequence(a.export_sequence(seq))
        assert a._launched is None and b._launched is None
        out = {**a.run_until_idle(), **b.run_until_idle()}
        return out

    done, events = _logged(tmp_path, run)
    assert done["m"]["tokens"] == reference_greedy(cfg, params, PROMPTS[1], 8)
    assert done["s"]["tokens"] == reference_greedy(cfg, params, PROMPTS[2], 8)
    _clean(a)
    _clean(b)
    assert {"export", "adopt"} <= {d["reason"]
                                   for d in _named(events, "serve.drain")}


def test_speculation_stays_synchronous(tiny, tmp_path):
    """Drafts are built from the tokens the host has read: speculation
    reads each step's first tokens before it drafts, launches nothing
    ahead, and its outputs are still recompute's."""
    cfg, params = tiny
    e = _engine(cfg, params, max_slots=4, speculative_k=2)
    outs, events = _logged(tmp_path, lambda: e.generate(PROMPTS,
                                                       max_new_tokens=7))
    for p, o in zip(PROMPTS, outs):
        assert o == reference_greedy(cfg, params, p, 7)
    _clean(e)
    assert not _launches(events) and not _named(events, "serve.drain")
    assert e.stats()["speculative"]["proposed"] > 0


def test_a_launch_and_its_expert_counts_travel_together(tmp_path):
    """A LongCat-shaped tiny model (latent attention, shortcut-connected
    layers of 12-of-48 experts): the counts a decode launch returns are
    read with its tokens and land on the span of that read beside its
    ``token_steps``, so ``picks`` over ``token_steps x expert_layers``
    reads ``top_k`` exactly, and every launch but a first is fed ahead."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=96,
        max_seq_len=64, dtype=jnp.float32, param_dtype=jnp.float32,
        tie_embeddings=False, rope_base=1e7, norm_eps=1e-5, sub_blocks=2,
        latent=dict(q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8,
                    v_dim=16, scale_q=True, scale_kv=True),
        experts=dict(n_routed=32, n_identity=16, top_k=12, d_expert=48,
                     scaling=6.0, held=8, offset=8))
    params = scmoe.init_params(cfg, jax.random.PRNGKey(0))
    e = InferenceEngine(cfg, params, num_blocks=48, block_size=8,
                        max_slots=2, max_prompt_len=32)
    rng = np.random.default_rng(0)
    for i in range(3):
        e.submit(Request(id=f"x{i}", tokens=tuple(
            int(t) for t in rng.integers(0, cfg.vocab_size, 9 + i)),
            max_new_tokens=5))
    done, events = _logged(tmp_path, e.run_until_idle)
    assert sorted(len(r["tokens"]) for r in done.values()) == [5, 5, 5]
    _clean(e)
    reads = [x for x in _named(events, "serve.decode") if "token_steps" in x]
    assert reads
    for x in reads:
        assert x["picks"] == 12 * x["token_steps"] * x["expert_layers"]
    picks = sum(x["picks"] for x in reads)
    steps = sum(x["token_steps"] * x["expert_layers"] for x in reads)
    assert picks / steps == 12.0
    assert all(x["ahead"] == 1 for x in _launches(events)[1:])


def test_the_plain_call_form_runs_the_served_programs(tiny):
    """``_prefill`` / ``_decode`` (what the latent runner's reference
    check calls) run the compiled launches the engine serves with, no
    program of their own: after a served request they add no compiled
    entry, and their logits choose recompute's tokens."""
    cfg, params = tiny
    e = _engine(cfg, params)
    prompt = PROMPTS[1]
    want = reference_greedy(cfg, params, prompt, 2)
    assert e.generate([prompt], max_new_tokens=2)[0] == want
    programs = (e._prefill_next, e._decode_next)
    sizes = [p._cache_size() for p in programs]
    S, sched = e.max_seq_len, e.scheduler
    table = BlockTable(e.cache_cfg, max_blocks=e.cache_cfg.blocks_for(S))
    table.ensure_room(len(prompt) + 1, sched.allocator)
    toks = np.zeros((1, S), np.int32)
    toks[0, :len(prompt)] = prompt
    last, e.pool = e._prefill(e.served_params, e.pool, jnp.asarray(toks),
                              jnp.asarray([len(prompt)], np.int32),
                              jnp.asarray(table.rows(np.arange(S))[None]))
    assert last.shape == (1, cfg.vocab_size)
    assert int(jnp.argmax(last[0])) == want[0]
    B, n = e.max_slots, len(prompt)
    rows = np.zeros((B, e.window), np.int32)      # the CPU's window path
    rows[0] = table.window_rows()
    live = np.arange(B) == 0
    logits, e.pool = e._decode(
        e.served_params, e.pool, np.where(live, want[0], 0),
        np.where(live, n, -1), np.where(live, n + 1, 0),
        np.where(live, table.row_of(n), 0), rows)
    assert int(jnp.argmax(logits[0])) == want[1]
    assert [p._cache_size() for p in programs] == sizes
    with pytest.raises(ValueError, match="lengths - 1"):
        e._decode(e.served_params, e.pool, *([np.zeros(B, np.int32)] * 4),
                  rows)
    table.release(sched.allocator)
    _clean(e)
