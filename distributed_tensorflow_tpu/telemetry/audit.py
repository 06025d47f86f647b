"""Cause-itemized production-day audit: score a day from its logs alone.

The observability capstone over everything the repo already emits
(ROADMAP item 4): given ONE telemetry run directory — per-worker step
events, the recovery supervisor's transition log, the serving fleet's
completion records, the day driver's phase markers — answer, with no
access to any in-process state, the two questions a production
retrospective starts with:

1. **Where did the hardware-seconds go?** The fleet goodput identity
   (``wall == goodput + Σ badput`` across every worker and generation,
   :mod:`~distributed_tensorflow_tpu.telemetry.goodput`) is recomputed
   and its residual gated to ±1%; per-phase goodput breaks the same
   seconds down along the day's diurnal curve.
2. **Where did the SLO budget go?** Each SLO's budget spend
   (:mod:`~distributed_tensorflow_tpu.telemetry.slo`) is itemized by
   *attributed cause*: every violating completion record is matched
   against cause windows derived purely from logged control-plane
   transitions — recovery reforms, deliberate scale transitions,
   rollout swaps, KV migrations, preemption replay, flash-spike
   overload — with an explicit ``unattributed`` remainder the CI gate
   caps (an unexplained burn is an observability bug: some subsystem
   degraded service without logging why).

Cause attribution is deliberately coarse-but-honest: a record is
attributed when its service interval ``[wall - latency, wall]``
overlaps a cause window, record-level evidence (``replayed_tokens``)
wins over windows, and window causes apply in severity order
(``recovery`` > ``scale_transition`` > ``rollout`` > ``kv_migrate`` >
``spike_overload``), so a request that is late because a rack died
*during* a spike is priced to the rack, not the spike. Per-cause
spends partition the total: they sum exactly to each SLO's
``budget_consumed``.

Consumed by ``tools/day_report.py`` (render + ``--check`` gates),
``tools/obs_report.py`` / ``tools/health_report.py`` (per-cause budget
table, day-phase breakdown) and ``chaos_sweep.py --day``.
"""

from __future__ import annotations

#: Attribution causes, in priority order (highest first). ``recovery``
#: outranks everything: a failure reform degrades service no matter
#: what else is happening; ``spike_overload`` is last — pure load with
#: no control-plane event to blame.
CAUSES = ("recovery", "scale_transition", "rollout", "kv_migrate",
          "preempt_replay", "spike_overload")

#: Restore-tier rank, warmest first (the recovery ladder). The day
#: gate requires a rack loss to recover at ``peer`` or warmer.
TIER_RANK = {"host": 0, "memory": 0, "peer": 1, "local": 2,
             "durable": 3, "none": 4}

_WARM_TIERS = frozenset(t for t, r in TIER_RANK.items() if r <= 1)


def _walls(events_by_pid, name: str):
    """(wall, event) pairs of every ``name`` event, wall-sorted."""
    out = []
    for events in events_by_pid.values():
        for ev in events:
            if ev.get("ev") == name and \
                    isinstance(ev.get("wall"), (int, float)):
                out.append((ev["wall"], ev))
    out.sort(key=lambda p: p[0])
    return out


def day_records(events_by_pid) -> "list[dict]":
    """Completion records from ``serve.request`` events — the
    :func:`telemetry.slo.records_from_events` mapping plus the
    attribution evidence those drop (``replayed_tokens``, the emitting
    pid, the driver-stamped request class)."""
    records = []
    for pid, events in events_by_pid.items():
        for ev in events:
            if ev.get("ev") != "serve.request":
                continue
            records.append({
                "wall": ev.get("wall"),
                "latency_s": ev.get("dur_s"),
                "ttft_s": ev.get("ttft_s"),
                "model_version": ev.get("model_version"),
                "ok": not ev.get("error"),
                "pid": pid,
                "kind": ev.get("kind"),
                "replayed_tokens": ev.get("replayed_tokens"),
            })
    records.sort(key=lambda r: r.get("wall") or 0.0)
    return records


def phase_spans(events_by_pid) -> "list[dict]":
    """The day's phase timeline from the driver's ``day.phase``
    markers: each marker opens a phase, the next one (or ``day.end``)
    closes it."""
    marks = _walls(events_by_pid, "day.phase")
    ends = _walls(events_by_pid, "day.end")
    out = []
    for i, (wall, ev) in enumerate(marks):
        if i + 1 < len(marks):
            end = marks[i + 1][0]
        elif ends:
            end = ends[-1][0]
        else:
            end = wall
        out.append({"phase": ev.get("phase", f"phase{i}"),
                    "start": wall, "end": end,
                    "dur_s": round(max(0.0, end - wall), 6),
                    "rate_rps": ev.get("rate_rps")})
    return out


def cause_windows(events_by_pid, *,
                  recovery_backdate_s: float = 0.25,
                  recovery_drain_s: float = 1.0,
                  scale_lead_s: float = 0.5,
                  scale_drain_s: float = 1.0,
                  span_margin_s: float = 0.25,
                  spike_drain_s: float = 2.0) -> "dict[str, list]":
    """{cause: [(lo, hi), ...]} attribution windows, derived purely
    from logged control-plane transitions.

    - ``recovery``: each failure onset (a ``recovery.worker_death``,
      or the day driver's ``day.rack_kill`` which precedes detection)
      until the NEXT ``recovery.generation_start`` plus a drain margin
      (the respawned fleet still owes the backlog that queued while it
      was down).
    - ``scale_transition``: around each ``scale.applied`` (the event is
      emitted at reform end, so the lead covers the drain/terminate
      that preceded it).
    - ``rollout`` / ``kv_migrate``: the logged span of each
      ``serve.swap`` / ``kv.migrate`` event plus a margin.
    - ``spike_overload``: every ``day.phase`` marker whose phase name
      contains ``spike`` (or carries ``overload`` truthy), extended by
      a drain margin — queueing theory's revenge outlives the spike.
    """
    out: "dict[str, list]" = {c: [] for c in CAUSES}
    gen_starts = [w for w, _ in
                  _walls(events_by_pid, "recovery.generation_start")]

    def _until_gen_start(wall: float) -> float:
        later = [g for g in gen_starts if g > wall]
        return (later[0] if later else wall) + recovery_drain_s

    onsets = ([w for w, _ in _walls(events_by_pid, "day.rack_kill")]
              + [w for w, _ in
                 _walls(events_by_pid, "recovery.worker_death")])
    for wall in onsets:
        out["recovery"].append((wall - recovery_backdate_s,
                                _until_gen_start(wall)))
    for wall, _ in _walls(events_by_pid, "scale.applied"):
        out["scale_transition"].append((wall - scale_lead_s,
                                        wall + scale_drain_s))
    for name, cause in (("serve.swap", "rollout"),
                        ("kv.migrate", "kv_migrate")):
        for wall, ev in _walls(events_by_pid, name):
            dur = ev.get("dur_s")
            dur = float(dur) if isinstance(dur, (int, float)) else 0.0
            out[cause].append((wall - dur - span_margin_s,
                               wall + span_margin_s))
    for ph in phase_spans(events_by_pid):
        name = str(ph.get("phase", ""))
        if "spike" in name or ph.get("overload"):
            out["spike_overload"].append(
                (ph["start"], ph["end"] + spike_drain_s))
    return {c: _merge(ws) for c, ws in out.items()}


def _merge(windows: "list[tuple]") -> "list[tuple]":
    merged: "list[list]" = []
    for lo, hi in sorted(windows):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [tuple(w) for w in merged]


def attribute(record: dict, windows: "dict[str, list]") -> "str | None":
    """The cause of one violating record, or None (unattributed).
    Record-level evidence first (a replayed request indicts the
    preemption no matter when it completed), then window causes in
    :data:`CAUSES` priority order over the record's service interval.
    """
    rt = record.get("replayed_tokens")
    if isinstance(rt, (int, float)) and rt > 0:
        return "preempt_replay"
    wall = record.get("wall")
    if not isinstance(wall, (int, float)):
        return None
    lat = record.get("latency_s")
    start = wall - (float(lat) if isinstance(lat, (int, float)) else 0.0)
    for cause in CAUSES:
        for lo, hi in windows.get(cause, ()):
            if start <= hi and wall >= lo:
                return cause
    return None


def _phase_goodput(events_by_pid, phases: "list[dict]") -> None:
    """Annotate each phase span with the hardware-seconds and goodput
    (step-event seconds) that fell inside it, clipped per worker.

    This is the LEDGER'S goodput re-cut along the day's phase
    boundaries as a breakdown aid: the serving replay share and the
    named badput buckets stay fleet-level (the ledger is the
    authority); a phase's ``wall_s`` sums each worker's observed-span
    overlap with the phase, so mid-phase deaths shrink it honestly.
    """
    for ph in phases:
        ph["wall_s"] = 0.0
        ph["goodput_s"] = 0.0
    for pid, events in events_by_pid.items():
        if not isinstance(pid, int):
            continue
        walls = [ev["wall"] for ev in events
                 if isinstance(ev.get("wall"), (int, float))]
        if not walls:
            continue
        first, last = min(walls), max(walls)
        for ph in phases:
            ph["wall_s"] += max(0.0, min(last, ph["end"])
                                - max(first, ph["start"]))
        for ev in events:
            if ev.get("ev") not in ("train.step", "serve.step"):
                continue
            wall, dur = ev.get("wall"), ev.get("dur_s")
            if not isinstance(wall, (int, float)):
                continue
            dur = float(dur) if isinstance(dur, (int, float)) \
                and dur > 0 else 0.0
            for ph in phases:
                ph["goodput_s"] += max(
                    0.0, min(wall, ph["end"]) - max(wall - dur,
                                                    ph["start"]))
    for ph in phases:
        ph["wall_s"] = round(ph["wall_s"], 6)
        ph["goodput_s"] = round(min(ph["goodput_s"], ph["wall_s"]), 6)
        ph["goodput_frac"] = (round(ph["goodput_s"] / ph["wall_s"], 6)
                              if ph["wall_s"] > 0 else None)


def _rack_loss(events_by_pid) -> "dict | None":
    """The day's correlated-failure scorecard: kill → next generation
    (MTTR) and the restore tiers the reformed trainers reported."""
    kills = _walls(events_by_pid, "day.rack_kill")
    if not kills:
        return None
    wall, ev = kills[0]
    gen_starts = [w for w, _ in
                  _walls(events_by_pid, "recovery.generation_start")
                  if w > wall]
    restores = [(w, e) for w, e in
                _walls(events_by_pid, "recovery.restore_tier")
                if w > wall]
    tiers = sorted({str(e.get("tier")) for _, e in restores},
                   key=lambda t: TIER_RANK.get(t, 99))
    worst = max((TIER_RANK.get(str(e.get("tier")), 99)
                 for _, e in restores), default=None)
    deaths = [e for w, e in
              _walls(events_by_pid, "recovery.worker_death") if w >= wall]
    return {
        "domain": ev.get("domain"),
        "victims": ev.get("victims"),
        "kill_wall": wall,
        "deaths_observed": len(deaths),
        "mttr_s": (round(gen_starts[0] - wall, 6) if gen_starts
                   else None),
        "restore_tiers": tiers,
        "worst_tier_rank": worst,
        "warm": (worst is not None and worst <= TIER_RANK["peer"]),
    }


def itemize_slos(records, slos, evaluated, windows) -> float:
    """Itemize each SLO's budget spend by attributed cause: annotates
    every ``evaluated[slo.name]`` with ``by_cause`` (spends partition
    ``budget_consumed`` exactly) and ``unattributed``, and returns the
    worst unattributed share of bad records across the SLOs. Shared by
    :func:`audit_day` and ``tools/health_report.py``."""
    max_unattr = 0.0
    for slo in slos:
        res = evaluated[slo.name]
        n = max(res["requests"], 1)
        by_cause = {c: 0 for c in CAUSES}
        unattr = 0
        for r in records:
            if not slo.is_bad(r):
                continue
            cause = attribute(r, windows)
            if cause is None:
                unattr += 1
            else:
                by_cause[cause] += 1
        res["by_cause"] = {
            c: {"bad": k,
                "budget_consumed": round((k / n) / slo.error_budget, 6)}
            for c, k in by_cause.items()}
        frac = (unattr / res["bad"]) if res["bad"] else 0.0
        res["unattributed"] = {
            "bad": unattr,
            "budget_consumed": round((unattr / n) / slo.error_budget, 6),
            "frac_of_bad": round(frac, 6)}
        max_unattr = max(max_unattr, frac)
    return max_unattr


def audit_day(events_by_pid, *, slos=None,
              window_opts: "dict | None" = None) -> dict:
    """The full day audit from one run's event files
    (:func:`telemetry.events.read_run` output). Pure function of the
    logs — no in-process state, no clock reads."""
    from distributed_tensorflow_tpu.telemetry import goodput as _goodput
    from distributed_tensorflow_tpu.telemetry import slo as _slo

    ledger = _goodput.ledger_from_events(events_by_pid)
    records = day_records(events_by_pid)
    windows = cause_windows(events_by_pid, **(window_opts or {}))
    phases = phase_spans(events_by_pid)
    _phase_goodput(events_by_pid, phases)

    if slos is None:
        walls = [r["wall"] for r in records
                 if isinstance(r.get("wall"), (int, float))]
        span = (max(walls) - min(walls)) if len(walls) > 1 else 1.0
        slos = _slo.default_serving_slos(
            windows=_slo.windows_for_span(max(span, 1e-3)))
    evaluated = _slo.evaluate_records(records, slos)
    max_unattr = itemize_slos(records, slos, evaluated, windows)

    generated = max((int(e.get("generated", 0)) for _, e in
                     _walls(events_by_pid, "day.load")), default=None)
    completed = len(records)
    wall = ledger["wall_s"]
    return {
        "ledger": {
            "wall_s": round(wall, 6),
            "goodput_s": round(ledger["goodput_s"], 6),
            "goodput_frac": ledger["goodput_frac"],
            "badput_s": {b: round(v, 6)
                         for b, v in ledger["badput_s"].items()},
            "identity_error_s": round(ledger["identity_error_s"], 6),
            "identity_error_frac": (
                round(abs(ledger["identity_error_s"]) / wall, 6)
                if wall > 0 else 0.0),
            "workers": len(ledger["per_worker"]),
        },
        "slos": evaluated,
        "max_unattributed_frac": round(max_unattr, 6),
        "phases": phases,
        "rack_loss": _rack_loss(events_by_pid),
        "requests": {
            "generated": generated,
            "completed": completed,
            "dropped": (max(0, generated - completed)
                        if generated is not None else None)},
        "cause_windows": {c: [(round(lo, 6), round(hi, 6))
                              for lo, hi in ws]
                          for c, ws in windows.items()},
    }


def check_audit(audit: dict, *, identity_tol: float = 0.01,
                max_unattributed: float = 0.05,
                goodput_floor: "float | None" = None,
                require_warm_restore: bool = False,
                max_rack_mttr_s: "float | None" = None,
                require_no_drops: bool = True) -> "list[str]":
    """The day's CI gates over an :func:`audit_day` result; returns
    human-readable failures (empty = pass)."""
    fails: "list[str]" = []
    led = audit["ledger"]
    if led["identity_error_frac"] > identity_tol:
        fails.append(
            f"goodput identity broken: |wall - (goodput + badput)| = "
            f"{led['identity_error_s']:.3f}s is "
            f"{led['identity_error_frac']:.1%} of {led['wall_s']:.3f}s "
            f"hardware-seconds (tolerance {identity_tol:.0%})")
    if goodput_floor is not None and (
            led["goodput_frac"] is None
            or led["goodput_frac"] < goodput_floor):
        fails.append(f"day goodput_frac {led['goodput_frac']} below "
                     f"floor {goodput_floor}")
    for name, res in audit["slos"].items():
        frac = res.get("unattributed", {}).get("frac_of_bad", 0.0)
        if frac > max_unattributed:
            fails.append(
                f"SLO {name}: {frac:.1%} of budget spend unattributed "
                f"({res['unattributed']['bad']}/{res['bad']} bad "
                f"records match no cause window; cap "
                f"{max_unattributed:.0%})")
    rack = audit.get("rack_loss")
    if require_warm_restore:
        if rack is None:
            fails.append("no rack loss in the run (day scenario "
                         "requires one)")
        elif not rack["restore_tiers"]:
            fails.append("rack loss but no recovery.restore_tier "
                         "events — restore path unobserved")
        elif not rack["warm"]:
            fails.append(
                f"rack loss fell through the warm tiers: restored "
                f"from {rack['restore_tiers']} (placement must keep "
                f"host/peer recoverable)")
    if rack is not None and max_rack_mttr_s is not None:
        if rack["mttr_s"] is None or rack["mttr_s"] > max_rack_mttr_s:
            fails.append(f"rack-loss MTTR {rack['mttr_s']}s over "
                         f"budget {max_rack_mttr_s}s")
    req = audit["requests"]
    if require_no_drops and req["dropped"]:
        fails.append(f"{req['dropped']} requests dropped "
                     f"({req['generated']} generated, "
                     f"{req['completed']} completed)")
    return fails
