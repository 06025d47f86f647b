"""KV-block migration: the primitive behind disaggregated serving.

The reference's WorkerService separates roles so each process does one
thing well; this module does the same for serving (DistServe, Zhong et
al. OSDI'24; Splitwise, Patel et al. ISCA'24): **prefill replicas** run
admission + prompt prefill only, **decode replicas** run the
memory-bound token loop, and a prompt's computed KV blocks move between
them as a :class:`MigrationPayload` — raw pool block rows (quantisation
scales included), the request, and every token generated so far carried
as LIVE state so the adopter replays nothing.

One primitive, three uses:

- **Disaggregation** (:class:`DisaggregatedEngine`) — a compute-bound
  prefill burst runs on the prefill replica while decode replicas keep
  emitting tokens: decode p99 stops paying for other requests'
  prompts. Greedy outputs are byte-identical to the monolithic engine
  (placement never changes argmax), which the tests pin per
  ``kv_dtype``.
- **Drain-by-migration** — a scale-down/preempted replica exports its
  live sequences to blobs a survivor adopts, instead of requeueing and
  REPLAYING generated tokens: the ``preempt_replay`` badput bucket
  goes to ~0 and handoff cost is priced honestly in the new
  ``kv_migrate`` bucket (telemetry/goodput.py).
- **Rescue** — when a decode replica's pool is exhausted, the
  scheduler's preemption hook first tries to migrate the victim to a
  sibling replica with free capacity; only when nobody can take it
  does the classic replay-requeue run.

**Wire format.** :func:`pack_payload` serializes a payload to one
blob: an 8-byte big-endian length, a JSON header (request fields +
per-array ``(name, shape, dtype)``), then each array's raw bytes in
header order. Arrays round-trip bit-exactly for every pool dtype —
bfloat16 included — because bytes are never reinterpreted through a
lossy dtype. The blob travels over the chunked (≤2 MiB) write-once
transport factored out of ``checkpoint/peer_snapshot.py``
(:func:`~distributed_tensorflow_tpu.checkpoint.peer_snapshot.
kv_put_blob`): chunks first, the chunk COUNT last, so a publisher
SIGKILLed mid-migration never leaves an adoptable half-blob — the
request is simply re-served from its prompt, and duplicates stay
byte-identical.

:class:`FileKV` is a filesystem agent for that transport (atomic
``os.replace`` per key), so migration works replica→replica through a
shared run directory without a coordination service; in-process
disaggregation skips the wire entirely unless asked to prove it
(``wire=True`` packs/unpacks every payload through the real format).
"""

from __future__ import annotations

import dataclasses
import json
import os
import struct
import time

import numpy as np

from distributed_tensorflow_tpu.checkpoint.peer_snapshot import (
    kv_blob_committed, kv_get_blob, kv_put_blob)


class FileKV:
    """Filesystem key-value agent for the chunked blob transport.

    Quacks like the coordination service's KV surface
    (``key_value_set`` / ``key_value_get`` / ``key_value_try_get``):
    every key is one file, committed atomically via ``os.replace`` —
    a reader never observes a torn value, and a writer SIGKILLed
    mid-``set`` leaves only an ignored ``.tmp`` file. Keys may contain
    ``/`` (flattened to ``__`` on disk)."""

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.root, key.replace("/", "__"))

    def key_value_set(self, key: str, value):
        if isinstance(value, str):
            value = value.encode("utf-8")
        path = self._path(key)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(value)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def key_value_try_get(self, key: str) -> "bytes | None":
        try:
            with open(self._path(key), "rb") as f:
                return f.read()
        except FileNotFoundError:
            return None

    def key_value_get(self, key: str, timeout_s: float = 10.0) -> bytes:
        deadline = time.monotonic() + timeout_s
        while True:
            val = self.key_value_try_get(key)
            if val is not None:
                return val
            if time.monotonic() >= deadline:
                raise TimeoutError(f"FileKV: key {key!r} not published "
                                   f"within {timeout_s}s")
            time.sleep(0.005)

    def list(self, prefix: str = "") -> list[str]:
        """Committed keys under ``prefix`` (tmp files excluded)."""
        flat = prefix.replace("/", "__")
        out = []
        try:
            names = os.listdir(self.root)
        except FileNotFoundError:
            return []
        for name in names:
            if ".tmp." in name:
                continue
            if name.startswith(flat):
                out.append(name.replace("__", "/"))
        return sorted(out)


@dataclasses.dataclass
class MigrationPayload:
    """Everything a replica needs to CONTINUE someone else's sequence.

    ``arrays`` are the sequence's pool block rows gathered source-side:
    ``k``/``v`` shaped ``(n_layers, n_blocks * block_size, n_heads,
    head_dim)`` in the pool's storage dtype, plus ``k_scale`` /
    ``v_scale`` ``(n_layers, rows, n_heads)`` f32 when quantized — the
    scales travel WITH their blocks, so int8 pools migrate ~4× cheaper
    than f32 on the wire and still dequantize identically.
    ``generated`` is live state (the adopter appends to it; nothing is
    replayed); ``generated_prefix`` preserves replay provenance from
    preemptions that happened BEFORE this migration. ``fingerprint``
    must equal the adopter's pool fingerprint; ``pool_epoch`` names the
    source incarnation (drain handoffs are fenced against staleness by
    the ADOPTER's policy, not here)."""

    request_id: str
    tokens: tuple
    max_new_tokens: int
    eos_id: "int | None"
    generated_prefix: tuple
    generated: tuple
    length: int
    fingerprint: dict
    pool_epoch: str
    arrival_wall: "float | None"
    ttft_s: "float | None"
    preemptions: int
    arrays: dict

    def __post_init__(self):
        if "k" not in self.arrays or "v" not in self.arrays:
            raise NotImplementedError(
                f"a migration payload holds per-head K and V block rows, "
                f"not {sorted(self.arrays)}: a pool of latent rows has no "
                f"payload or wire format here")

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self.arrays.values())

    @property
    def n_blocks(self) -> int:
        bs = self.fingerprint["block_size"]
        return self.arrays["k"].shape[1] // bs


def _np_dtype(name: str) -> np.dtype:
    """Dtype from its string name, extended dtypes included —
    ``np.dtype("bfloat16")`` fails in plain numpy, but jax's ml_dtypes
    registration makes ``np.dtype(jnp.bfloat16)`` real."""
    try:
        return np.dtype(name)
    except TypeError:
        import jax.numpy as jnp
        return np.dtype(getattr(jnp, name))


def pack_payload(payload: MigrationPayload) -> bytes:
    """One self-describing blob: ``[8B header length][JSON header]
    [array bytes...]``. Raw ``tobytes`` per array — bit-exact for
    every ``kv_dtype``."""
    names = sorted(payload.arrays)
    header = {
        "request_id": payload.request_id,
        "tokens": list(payload.tokens),
        "max_new_tokens": payload.max_new_tokens,
        "eos_id": payload.eos_id,
        "generated_prefix": list(payload.generated_prefix),
        "generated": list(payload.generated),
        "length": payload.length,
        "fingerprint": payload.fingerprint,
        "pool_epoch": payload.pool_epoch,
        "arrival_wall": payload.arrival_wall,
        "ttft_s": payload.ttft_s,
        "preemptions": payload.preemptions,
        "arrays": [{"name": n,
                    "shape": list(payload.arrays[n].shape),
                    "dtype": str(payload.arrays[n].dtype)}
                   for n in names],
    }
    head = json.dumps(header).encode("utf-8")
    parts = [struct.pack(">Q", len(head)), head]
    parts.extend(np.ascontiguousarray(payload.arrays[n]).tobytes()
                 for n in names)
    return b"".join(parts)


def unpack_payload(blob: bytes) -> MigrationPayload:
    (head_len,) = struct.unpack(">Q", blob[:8])
    header = json.loads(blob[8:8 + head_len].decode("utf-8"))
    arrays = {}
    off = 8 + head_len
    for spec in header["arrays"]:
        dt = _np_dtype(spec["dtype"])
        shape = tuple(spec["shape"])
        n = dt.itemsize * int(np.prod(shape)) if shape else dt.itemsize
        arrays[spec["name"]] = np.frombuffer(
            blob[off:off + n], dtype=dt).reshape(shape)
        off += n
    if off != len(blob):
        raise ValueError(f"migration blob: {len(blob) - off} trailing "
                         f"bytes (corrupt or mismatched header)")
    return MigrationPayload(
        request_id=header["request_id"],
        tokens=tuple(header["tokens"]),
        max_new_tokens=header["max_new_tokens"],
        eos_id=header["eos_id"],
        generated_prefix=tuple(header["generated_prefix"]),
        generated=tuple(header["generated"]),
        length=header["length"],
        fingerprint=header["fingerprint"],
        pool_epoch=header["pool_epoch"],
        arrival_wall=header["arrival_wall"],
        ttft_s=header["ttft_s"],
        preemptions=header["preemptions"],
        arrays=arrays)


def publish_payload(agent, prefix: str, payload: MigrationPayload):
    """Ship a payload over the write-once chunked transport. The chunk
    COUNT commits last — :func:`payload_committed` (and any adopter)
    sees either nothing or the complete blob, never a torn one."""
    kv_put_blob(agent, prefix, pack_payload(payload))


def fetch_payload(agent, prefix: str,
                  timeout_s: float = 10.0) -> MigrationPayload:
    return unpack_payload(kv_get_blob(agent, prefix,
                                      timeout_s=timeout_s))


def payload_committed(agent, prefix: str) -> bool:
    return kv_blob_committed(agent, prefix)


class DisaggregatedEngine:
    """Prefill/decode disaggregation over in-process engine replicas.

    One ``role="prefill"`` :class:`~distributed_tensorflow_tpu.serving.
    engine.InferenceEngine` owns admission, the prefix cache and prompt
    prefill; ``num_decode`` full engines own the token loop. Each
    :meth:`step`:

    1. steps the prefill engine (admit + prefill; scoring and
       1-token requests complete right there);
    2. EXPORTS every prefilled, unfinished sequence to the decode
       replica with capacity (deterministic round-robin — placement
       never affects greedy outputs), ``wire=True`` proving the real
       pack/unpack wire format on every hop;
    3. steps every decode engine.

    A decode replica that must preempt (pool exhausted) first offers
    the victim to its siblings via the scheduler's preemption hook
    (**rescue** migration — no replay); only when every sibling is
    full does the classic replay-requeue run, on the victim's own
    replica, preserving monolithic semantics exactly.

    The public surface mirrors the monolithic engine where the bench,
    replica runtime and tests touch it: ``submit`` / ``step`` /
    ``run_until_idle`` / ``generate`` / ``stats`` / ``idle``.
    """

    def __init__(self, cfg, params, *, num_decode: int = 1,
                 wire: bool = False, rescue: bool = True,
                 **engine_kwargs):
        from distributed_tensorflow_tpu.serving.engine import (
            InferenceEngine)
        if num_decode < 1:
            raise ValueError("num_decode must be >= 1")
        pf_kwargs = dict(engine_kwargs)
        # the prefill replica never decodes: no draft model, and the
        # spill tier follows the prefix cache (which lives with
        # admission, i.e. here)
        for k in ("speculative_k", "draft_params", "draft_cfg"):
            pf_kwargs.pop(k, None)
        self.prefill = InferenceEngine(cfg, params, role="prefill",
                                       **pf_kwargs)
        dec_kwargs = dict(engine_kwargs)
        dec_kwargs.pop("spill_tier", None)
        # decode replicas run no admission-side prefix matching —
        # adopted blocks arrive private, and caching there would only
        # duplicate the prefill replica's cache
        dec_kwargs["prefix_caching"] = False
        self.decoders = [InferenceEngine(cfg, params, **dec_kwargs)
                         for _ in range(num_decode)]
        self.wire = bool(wire)
        self.rescue = bool(rescue)
        self._rr = 0                      # round-robin placement cursor
        self.migrations: list[dict] = []
        if rescue and num_decode > 1:
            for i, eng in enumerate(self.decoders):
                eng.scheduler.preempt_hook = (
                    lambda victim, _i=i: self._rescue(_i, victim))

    # -- placement ---------------------------------------------------------
    def _decoder_for(self, n_blocks: int,
                     exclude: "int | None" = None) -> "int | None":
        """First decode replica (round-robin from the cursor) with a
        free slot and ``n_blocks`` free blocks; None when all full."""
        n = len(self.decoders)
        for k in range(n):
            i = (self._rr + k) % n
            if i == exclude:
                continue
            eng = self.decoders[i]
            if (eng.scheduler._free_slots
                    and eng.scheduler.allocator.num_free >= n_blocks):
                self._rr = (i + 1) % n
                return i
        return None

    def _ship(self, src_engine, seq, dst: int, *, kind: str,
              src: str) -> None:
        t0 = time.monotonic()
        payload = src_engine.export_sequence(seq, reason=kind)
        if self.wire:
            payload = unpack_payload(pack_payload(payload))
        self.decoders[dst].adopt_sequence(payload)
        self.migrations.append({
            "id": payload.request_id, "kind": kind, "src": src,
            "dst": f"decode{dst}", "blocks": payload.n_blocks,
            "bytes": payload.nbytes,
            "ms": (time.monotonic() - t0) * 1e3})

    def _rescue(self, src: int, victim) -> bool:
        """Preemption hook on decode replica ``src``: migrate the
        victim to a sibling instead of replaying. True = taken."""
        dst = self._decoder_for(len(victim.table.blocks), exclude=src)
        if dst is None:
            return False
        self._ship(self.decoders[src], victim, dst, kind="rescue",
                   src=f"decode{src}")
        return True

    # -- engine surface ----------------------------------------------------
    def submit(self, request, *, arrival_wall: "float | None" = None):
        return self.prefill.submit(request, arrival_wall=arrival_wall)

    def step(self) -> list[dict]:
        """One disaggregated iteration; returns completion records from
        every replica (order: prefill-side completions first, then
        decode replicas in index order)."""
        finished = list(self.prefill.step())
        sched = self.prefill.scheduler
        ready = sorted((s for s in sched.running.values()
                        if s.prefilled and not s.done),
                       key=lambda s: s.slot)
        for seq in ready:
            dst = self._decoder_for(len(seq.table.blocks))
            if dst is None:
                break       # every decoder full: park in prefill slot
            self._ship(self.prefill, seq, dst, kind="prefill",
                       src="prefill")
        for eng in self.decoders:
            finished.extend(eng.step())
        return finished

    @property
    def idle(self) -> bool:
        return (self.prefill.scheduler.idle
                and all(e.scheduler.idle for e in self.decoders))

    def run_until_idle(self, *, max_steps: int = 100000,
                       retry_faults: bool = False) -> dict:
        from distributed_tensorflow_tpu.resilience.faults import (
            FaultInjected)
        out: dict[str, dict] = {}
        for _ in range(max_steps):
            if self.idle:
                break
            try:
                for rec in self.step():
                    out[rec["id"]] = rec
            except FaultInjected:
                # every chaos site fires BEFORE its engine mutates
                # state, so re-running the whole composite step is safe
                if not retry_faults:
                    raise
        return out

    def generate(self, prompts, *, max_new_tokens: int = 16,
                 eos_id: int | None = None) -> list[list[int]]:
        from distributed_tensorflow_tpu.serving.scheduler import (
            Request)
        for i, p in enumerate(prompts):
            self.submit(Request(id=f"g{i}", tokens=tuple(p),
                                max_new_tokens=max_new_tokens,
                                eos_id=eos_id))
        done = self.run_until_idle()
        return [done[f"g{i}"]["tokens"] for i in range(len(prompts))]

    def block_accounting(self) -> dict:
        """Per-replica conservation audit + fleet totals (the chaos
        gate's zero-leak check)."""
        per = {"prefill": self.prefill.block_accounting()}
        for i, eng in enumerate(self.decoders):
            per[f"decode{i}"] = eng.block_accounting()
        per["leaked_refs"] = sum(v["leaked_refs"] for v in per.values()
                                 if isinstance(v, dict))
        per["conserved"] = all(v["conserved"] for v in per.values()
                               if isinstance(v, dict))
        return per

    def stats(self) -> dict:
        lat = sorted(m["ms"] for m in self.migrations)

        def pct(p):
            return (lat[min(len(lat) - 1,
                            int(round(p / 100 * (len(lat) - 1))))]
                    if lat else 0.0)

        return {
            "prefill": self.prefill.stats(),
            "decode": [e.stats() for e in self.decoders],
            "migrations": len(self.migrations),
            "migrations_rescue": sum(1 for m in self.migrations
                                     if m["kind"] == "rescue"),
            "migrated_bytes": sum(m["bytes"] for m in self.migrations),
            "migrate_p50_ms": pct(50),
            "migrate_p99_ms": pct(99),
        }
