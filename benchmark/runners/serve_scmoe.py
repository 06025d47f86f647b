"""Runner "serve_scmoe": ``runners.serve.Runner`` for a shortcut-connected
stack of sparse-expert layers over latent attention, served as one chip's
share of its experts. It differs in

- the weights: drawn from the seed by ``models.scmoe.init_params`` in the
  tree the serving programs index (``TransformerLM`` has no such shape);
- ``reference_check``: LOGITS of the engine's own compiled programs
  against ``benchmark/reference_scmoe.py`` (float32, "highest", blocked):
  the prefill program's last-row logits of ``check.prompts`` seeded
  prompts of the mix's lengths, then ONE step of the decode program over
  a full batch of slots through the latent pool (every slot prefilled
  first, fed its greedy token). A row's reading is the root mean square
  of its logits' difference over that of the reference's logits about
  their mean; every row has to stay under ``check.logit_rel_rms``. The
  notes carry what the same rows would read with the reference's weights
  rounded through ``check.low_precision``, with the held routed experts
  left out and with the identity experts left out (``<fault>_rel_rms``,
  the largest over the rows):
  the readings the tolerance was set to refuse, beside it in every run.
  The programs' pick counts are held to ``top_k`` a token and layer
  there;
- ``verify``: the margin check of ``serve.Runner.verify`` against the same
  reference (with the gap of the tokens a reference of rounded weights
  would choose beside it, as ``serve_looped`` reports it), the expert
  share the engine runs against the file's, block accounting;
- the record: ``model_flops`` and the bytes a decode step must move
  (``benchmark/work_scmoe.py``).
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_scmoe, work_scmoe
from benchmark.runners import serve
from benchmark.runners.common import resolve
from distributed_tensorflow_tpu.models import scmoe
from distributed_tensorflow_tpu.serving.kv_cache import BlockTable


def rel_rms(got, want) -> float:
    """Root mean square of ``got - want`` over that of ``want`` about
    its mean: one row of logits against the reference's."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.std(want))


class Runner(serve.Runner):
    def build(self) -> None:
        with jax.default_device(self.device):
            params = scmoe.init_params(self.model,
                                       jax.random.PRNGKey(self.seed))
            self.engine = resolve(self.config["builder"])(
                self.model, params, **self.config["engine"])

    # -- logits against the reference, before the window -------------------
    def _reference(self, tokens, width, **kw):
        return np.asarray(reference_scmoe.last_logits(
            self.engine.params, tokens, width, shape=self.model, **kw))

    def reference_check(self) -> dict:
        check, eng = self.config["check"], self.engine
        cc, sched = eng.cache_cfg, eng.scheduler
        ex = self.model.experts
        slots = eng.max_slots
        lens = self.traffic["prompt_lens"]
        rng = np.random.default_rng([self.seed, 0xc3])
        prompts = [tuple(int(t) for t in rng.integers(
            0, self.model.vocab_size, lens[(i * 5) % len(lens)]))
            for i in range(slots)]
        width = check["reference_width"]
        per_token = ex.top_k * self.model.n_layers
        tables, firsts, counts_ok = [], [], True
        S = eng.max_seq_len
        prefill_rows = []
        for i, prompt in enumerate(prompts):
            table = BlockTable(cc, max_blocks=cc.blocks_for(S))
            table.ensure_room(len(prompt) + 1, sched.allocator)
            toks = np.zeros((1, S), np.int32)
            toks[0, :len(prompt)] = prompt
            last, eng.pool, counts = eng._prefill(
                eng.served_params, eng.pool, jnp.asarray(toks),
                jnp.asarray([len(prompt)], np.int32),
                jnp.asarray(table.rows(np.arange(S))[None]))
            table.length = len(prompt)
            tables.append(table)
            firsts.append(int(jnp.argmax(last[0])))
            counts = np.asarray(counts).sum(axis=0)
            counts_ok &= int(counts[0]) == per_token * len(prompt)
            if i < check["prompts"]:
                prefill_rows.append((prompt, np.asarray(last[0])))
        # one decode step over every slot, through the pool
        tokens = np.asarray(firsts, np.int32)
        positions = np.asarray([len(p) for p in prompts], np.int32)
        table = np.zeros((slots, cc.blocks_for(S)), np.int32)
        for s, t in enumerate(tables):
            table[s, :len(t.blocks)] = t.blocks
        if eng.kv_path != "paged":
            table = np.stack([t.window_rows() for t in tables])
        logits, eng.pool, counts = eng._decode(
            eng.served_params, eng.pool, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(positions + 1),
            jnp.asarray([t.row_of(len(p)) for t, p in
                         zip(tables, prompts)], np.int32),
            jnp.asarray(table))
        logits = np.asarray(logits)
        counts = np.asarray(counts).sum(axis=0)
        counts_ok &= int(counts[0]) == per_token * slots
        for t in tables:
            t.release(sched.allocator)

        # (tokens, the engine's logits after them, the reference's)
        rows = {"prefill": prefill_rows, "decode": [
            (prompts[s] + (firsts[s],), logits[s])
            for s in range(check.get("decode_rows", slots))]}
        rows = {kind: [(t, got, self._reference(t, width))
                       for t, got in some] for kind, some in rows.items()}
        readings = {kind: max(rel_rms(got, want) for _, got, want in some)
                    for kind, some in rows.items()}
        # what the tolerance has to refuse, on the same rows: a fault
        # shows in the rows it touches (a row whose token took no held
        # expert says little of the routed experts), so the largest
        faults = {
            "low_precision": {"weight_dtype": getattr(
                jnp, check["low_precision"])},
            "without_routed": {"ablate": "without_routed"},
            "without_identity": {"ablate": "without_identity"}}
        notes = {f"logits_rel_rms_{k}": v for k, v in readings.items()}
        tol = check["logit_rel_rms"]
        for name, kw in faults.items():
            notes[f"{name}_rel_rms"] = max(
                rel_rms(self._reference(t, width, **kw), want)
                for some in rows.values() for t, _, want in some)
        for what, value in readings.items():
            if not value <= tol:
                self.failures.append(
                    f"{what} logits part from the reference's by "
                    f"{value:.4f} of their spread; the tolerance is {tol}")
        notes["picks_per_token_layer_ok"] = bool(counts_ok)
        notes["decode_step_counts"] = [int(c) for c in counts]
        if not counts_ok:
            self.failures.append(
                f"a program's picks are not {ex.top_k} a token and "
                f"expert layer (the decode step's counts: {counts})")
        return notes

    # -- the record's work ---------------------------------------------------
    def _record(self, source, reqs, steps, open_s, close_s,
                used_share) -> dict:
        record = super()._record(source, reqs, steps, open_s, close_s,
                                 used_share)
        model = self.config["model"]
        ends = [s[0] for s in steps]
        start = next((t for t in ends if t >= open_s), None)
        end = next((t for t in ends if t >= close_s),
                   ends[-1] if ends else None)
        flops = 0.0
        if start is not None:
            for r in reqs.values():
                for i, t in enumerate(r["times"]):
                    if not start < t <= end:
                        continue
                    flops += (work_scmoe.prompt_flops(model, r["n_prompt"])
                              if i == 0 else work_scmoe.token_flops(
                                  model, r["n_prompt"] + i))
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "peaks.json")) as f:
            peak = json.load(f).get(self.device.device_kind, {})
        record.update(
            model_flops=flops,
            decode_fixed_bytes=work_scmoe.decode_fixed_bytes(model),
            expert_bytes=float(work_scmoe.expert_bytes(model)),
            expert_flops=2.0 * work_scmoe.expert_params(model),
            kv_row_bytes=float(work_scmoe.kv_row_bytes(model)),
            attention_flops_per_row=work_scmoe.attention_flops_per_row(
                model),
            peak_hbm_bytes_per_s=peak.get("hbm_bytes_per_s"))
        return record

    def verify(self, record: dict) -> dict:
        check = self.config["check"]
        served = record.pop("served")
        kw = {"shape": self.model}
        first = served[:check["requests"]]
        gaps = [float(reference_scmoe.greedy_gap(
            self.engine.params, list(r["prompt"]) + list(r["tokens"]),
            r["n_prompt"], self.model.max_seq_len, **kw).max())
            for r in first]
        worst = max(gaps, default=0.0)
        notes = {"reference_worst_gap": worst, "reference_gaps": gaps}
        if first and check.get("low_precision"):
            # the reading the margin has to refuse: the tokens a
            # reference with rounded weights would choose there
            notes["low_precision_worst_gap"] = max(float(
                reference_scmoe.greedy_gap(
                    self.engine.params,
                    list(r["prompt"]) + list(r["tokens"]), r["n_prompt"],
                    self.model.max_seq_len, chooser_dtype=getattr(
                        jnp, check["low_precision"]), **kw).max())
                for r in first)
        if not worst <= check["logit_margin"]:
            self.failures.append(
                f"an engine token's reference logit is {worst:.4f} below "
                f"the reference's largest; the margin is "
                f"{check['logit_margin']}")
        if not served:
            self.failures.append("no measured request was served")
        want = self.config["model"]["experts"]
        runs = self.engine.cfg.experts
        if (runs.held, runs.offset) != (want["held"], want["offset"]):
            self.failures.append(
                f"the engine holds experts {runs.offset}.."
                f"{runs.offset + runs.held}; the configuration says "
                f"{want['offset']}..{want['offset'] + want['held']}")
        acct = self.engine.block_accounting()
        if not acct["conserved"] or acct["leaked_refs"]:
            self.failures.append(f"KV block accounting broken: {acct}")
        return notes
