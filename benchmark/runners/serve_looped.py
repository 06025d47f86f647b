"""Runner "serve_looped": ``runners.serve.Runner`` for a looped decoder
(a stack run several times with the same weights, one KV cache per
pass). It differs in the reference it checks against
(``benchmark/reference_looped.py``) and in the work it adds to the
record from ``benchmark/work_looped.py``: ``model_flops`` (the
algorithm's FLOPs of the tokens the window released and the prompts it
prefilled), ``decode_weight_bytes`` and ``kv_row_bytes`` (what a launch
of the decode program must read whatever its batch, ``decode_steps``
steps of it, and per live row), ``hbm_bytes_needed`` (the decode
launches' bytes over the window),
``peak_hbm_bytes_per_s`` and ``passes``.
"""

from __future__ import annotations

import json
import os

import jax.numpy as jnp

from benchmark import metric_math, reference_looped, work_looped
from benchmark.runners import serve


class Runner(serve.Runner):
    def _record(self, source, reqs, steps, open_s, close_s,
                used_share) -> dict:
        record = super()._record(source, reqs, steps, open_s, close_s,
                                 used_share)
        model = self.config["model"]
        # the whole steps the rate is taken over: ``whole_step_rate``'s
        ends = [s[0] for s in steps]
        start = next((t for t in ends if t >= open_s), None)
        end = next((t for t in ends if t >= close_s),
                   ends[-1] if ends else None)
        flops = rows = decode_steps = 0.0
        if start is not None:
            for r in reqs.values():
                for i, t in enumerate(r["times"]):
                    if not start < t <= end:
                        continue
                    if i == 0:          # the prefill's token
                        flops += work_looped.prompt_flops(model,
                                                          r["n_prompt"])
                    else:               # a decode step over n + i keys
                        flops += work_looped.token_flops(
                            model, r["n_prompt"] + i)
                        rows += r["n_prompt"] + i
            decode_steps = sum(1 for s in steps
                               if start < s[0] <= end and s[3] > 0)
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "peaks.json")) as f:
            peak = json.load(f).get(self.device.device_kind, {})
        # a launch of the decode program runs the engine's decode_steps
        # steps, and each reads the weights whatever its batch
        weights = (work_looped.decode_weight_bytes(model)
                   * self.engine.decode_steps)
        row = work_looped.kv_row_bytes(model)
        record.update(
            model_flops=flops, passes=float(self.engine.decode_passes),
            decode_weight_bytes=weights, kv_row_bytes=float(row),
            hbm_bytes_needed=decode_steps * weights + rows * row,
            peak_hbm_bytes_per_s=peak.get("hbm_bytes_per_s"))
        return record

    def verify(self, record: dict) -> dict:
        """``serve.Runner.verify`` against the looped reference: at every
        generated position of the first requests the reference's
        last-pass logit of the engine's token lies within the margin of
        its largest, the prefill and decode programs were built to run
        every pass, and the pool's blocks are all accounted for. The
        notes also carry the reading the margin has to refuse: the gap
        of the tokens a reference whose weights are rounded through
        ``check.low_precision`` (float8) would choose at the same
        positions of the same requests."""
        check = self.config["check"]
        served = record.pop("served")
        kw = {"passes": self.model.passes,
              "rope_base": self.model.rope_base}
        gaps = [float(reference_looped.greedy_gap(
            self.engine.params, list(r["prompt"]) + list(r["tokens"]),
            r["n_prompt"], self.model.max_seq_len, **kw).max())
            for r in served[:check["requests"]]]
        worst = max(gaps, default=0.0)
        notes = {"reference_worst_gap": worst, "reference_gaps": gaps}
        if served and check.get("low_precision"):
            low = [reference_looped.greedy_gap(
                self.engine.params, list(r["prompt"]) + list(r["tokens"]),
                r["n_prompt"], self.model.max_seq_len,
                chooser_dtype=getattr(jnp, check["low_precision"]), **kw)
                for r in served[:check["requests"]]]
            notes["low_precision_worst_gap"] = max(float(g.max())
                                                   for g in low)
            notes["low_precision_p50_gap"] = metric_math.percentile(
                [float(g) for gaps in low for g in gaps], 50)
        if not worst <= check["logit_margin"]:
            self.failures.append(
                f"an engine token's reference logit is {worst:.4f} below "
                f"the reference's largest; the margin is "
                f"{check['logit_margin']}")
        if not served:
            self.failures.append("no measured request was served")
        # every token runs every pass: the programs the engine BUILT run
        # the passes the model has (a skipped pass or an early exit is a
        # different result, not a faster one)
        built = {self.engine.prefill_passes, self.engine.decode_passes}
        if built != {self.model.passes}:
            self.failures.append(
                f"the engine's programs run {sorted(built)} passes over "
                f"the stack; the model has {self.model.passes}")
        acct = self.engine.block_accounting()
        if not acct["conserved"] or acct["leaked_refs"]:
            self.failures.append(f"KV block accounting broken: {acct}")
        return notes
