"""Closed loop: ``clients`` callers, each with one request outstanding
and no think time; a caller's next request is sent when its last one
completed.

The population is a table, not a draw: request ``j`` takes its prompt
length from ``prompt_lens[j % n]`` and its answer length from
``output_lens`` at a position that moves by ``stride`` (co-prime with
the grid) and once more every full turn, so that every pair of lengths
comes round. Every seed serves the same multiset in the same cyclic
order; the seed draws the token ids and rotates where the cycle starts.
"""

from __future__ import annotations

import numpy as np


def shape_of(params: dict, j: int) -> tuple[int, int]:
    """``(prompt_len, max_new_tokens)`` of the ``j``-th request."""
    prompts, outputs = params["prompt_lens"], params["output_lens"]
    return (prompts[j % len(prompts)],
            outputs[(j * params["stride"] + j // len(prompts))
                    % len(outputs)])


class Source:
    sample = "finished_in_window"

    def __init__(self, params: dict, seed: int, vocab_size: int,
                 tag: str = "c"):
        self.params = params
        self.seed = seed
        self.vocab = vocab_size
        self.tag = tag
        self.ramp_s = float(params["ramp_s"])
        cycle = len(params["prompt_lens"]) * len(params["output_lens"])
        self.offset = seed % cycle
        self.sent = 0
        self.ready = params["clients"]      # callers free to send

    def request(self, j: int, stream: int = 0xc1) -> dict:
        """The ``j``-th request of this run (``stream`` keeps the
        warm-up's token ids apart from the measured ones, which must
        find nothing of theirs in the prefix cache)."""
        n_prompt, n_new = shape_of(self.params, self.offset + j)
        rng = np.random.default_rng([self.seed, stream, j])
        return {"id": f"{self.tag}{j}", "due_s": None,
                "tokens": tuple(rng.integers(0, self.vocab, n_prompt)),
                "max_new_tokens": n_new}

    def warmup(self) -> list[list[dict]]:
        """The cold prefill and the decode program are the only shapes
        this traffic reaches: one wave of two short requests compiles
        both."""
        return [[dict(self.request(j, stream=0xc2), max_new_tokens=2)
                 for j in range(2)]]

    def poll(self, t: float) -> list[dict]:
        out = [self.request(self.sent + k) for k in range(self.ready)]
        self.sent += self.ready
        self.ready = 0
        return out

    def next_due_s(self) -> float | None:
        return None

    def finished(self, request_id: str) -> None:
        self.ready += 1


def make(params: dict, seed: int, vocab_size: int, **kw) -> Source:
    return Source(params, seed, vocab_size, **kw)
