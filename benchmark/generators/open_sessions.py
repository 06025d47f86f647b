"""Open loop of sessions that share long prefixes: a document is asked
about several times, some requests apart, each time with a new
question.

The population is a table, not a draw. Session ``s`` takes its document
length, its number of asks and the distance between its asks from the
mix file's grids, cyclically; its asks are laid into the request stream
at the first free index and then every ``gap`` indices (moved on to the
next free index where that one is taken); ask ``a`` of session ``s``
takes question and answer lengths from their grids. Request ``i`` is
due at ``i / rate``, except that in every ``burst_every`` consecutive
requests the last ``burst_size`` are due together, at the due time of
the first of them. So for every seed the stream offers the same
``(document, question, answer, reuse distance)`` at the same due times;
the seed draws the token ids and nothing else.
"""

from __future__ import annotations

import numpy as np


def _at(grid, i: int):
    return grid[i % len(grid)]


def layout(params: dict, n_requests: int) -> list[tuple[int, int]]:
    """``(session, ask)`` of each of the first ``n_requests`` indices."""
    slots: dict[int, tuple[int, int]] = {}
    first_free = 0
    session = 0
    while first_free < n_requests:
        gap = _at(params["reuse_gaps"], session)
        at = first_free
        for ask in range(_at(params["asks_per_doc"], session)):
            while at in slots:
                at += 1
            slots[at] = (session, ask)
            at += gap
        while first_free in slots:
            first_free += 1
        session += 1
    return [slots[i] for i in range(n_requests)]


def due_s(params: dict, i: int, rate: float) -> float:
    every, size = params["burst_every"], params["burst_size"]
    pos = i % every
    if pos >= every - size:
        i = i - pos + every - size
    return i / rate


def shape_of(params: dict, session: int, ask: int) -> tuple[int, int, int]:
    """``(document, question, answer)`` lengths of one ask."""
    k = session * 7 + ask * 3
    return (_at(params["doc_lens"], session),
            _at(params["question_lens"], k),
            _at(params["answer_lens"], k // len(params["question_lens"])
                + ask))


class Source:
    sample = "due_in_window"

    def __init__(self, params: dict, seed: int, vocab_size: int,
                 tag: str = "o", rate_rps: float | None = None):
        self.params = params
        self.seed = seed
        self.vocab = vocab_size
        self.tag = tag
        self.rate = float(rate_rps or params["rate_rps"])
        self.ramp_s = float(params["ramp_s"])
        self.sent = 0
        self._layout: list = []

    def _slot(self, i: int) -> tuple[int, int]:
        if i >= len(self._layout):
            self._layout = layout(self.params, 2 * i + 256)
        return self._layout[i]

    def request(self, i: int) -> dict:
        session, ask = self._slot(i)
        n_doc, n_q, n_a = shape_of(self.params, session, ask)
        doc = np.random.default_rng([self.seed, 0xd0c, session]).integers(
            0, self.vocab, n_doc)
        question = np.random.default_rng(
            [self.seed, 0xa5c, session, ask]).integers(0, self.vocab, n_q)
        return {"id": f"{self.tag}{i}",
                "due_s": due_s(self.params, i, self.rate),
                "tokens": tuple(doc) + tuple(question),
                "max_new_tokens": n_a}

    def warmup(self) -> list[list[dict]]:
        """Two waves, each run until the engine is idle: one cold
        prefill per document length, then a follower per (document,
        question length) pair, so that every extend width the traffic
        reaches is compiled. (Followers sent with their document would
        be matched before it is prefilled, and run cold.) The documents
        are the warm-up's own, so the measured sessions start cold."""
        cold, followers = [], []
        for d, n_doc in enumerate(self.params["doc_lens"]):
            rng = np.random.default_rng([self.seed, 0x3a7, d])
            doc = tuple(rng.integers(0, self.vocab, n_doc))
            for q, n_q in enumerate(sorted(set(
                    self.params["question_lens"]))):
                (followers if q else cold).append({
                    "id": f"warm-{self.tag}{d}-{q}", "due_s": None,
                    "tokens": doc + tuple(rng.integers(0, self.vocab, n_q)),
                    "max_new_tokens": 2})
                if not q:           # the smallest width needs a follower too
                    followers.append(dict(
                        cold[-1], id=f"warm-{self.tag}{d}-{q}b",
                        tokens=doc + tuple(rng.integers(0, self.vocab,
                                                        n_q))))
        return [cold, followers]

    def poll(self, t: float) -> list[dict]:
        out = []
        while due_s(self.params, self.sent, self.rate) <= t:
            out.append(self.request(self.sent))
            self.sent += 1
        return out

    def next_due_s(self) -> float | None:
        return due_s(self.params, self.sent, self.rate)

    def finished(self, request_id: str) -> None:
        pass


def make(params: dict, seed: int, vocab_size: int, **kw) -> Source:
    return Source(params, seed, vocab_size, **kw)
