#!/usr/bin/env python
"""Multi-tenant routed serving: affinity router + supervised replicas.

Runs the full ISSUE-20 stack end to end, twice, on ONE seeded
multi-tenant workload (interactive + batch + a quota-capped tenant,
with an interactive traffic spike):

- **affinity phase** (chaos): N router-fed replicas under the recovery
  supervisor (``serving.replica.routed_replica`` — each tails its
  inbox file, exports live metrics, logs completions), with the router
  (``serving.router.Router``) running as its own process: it paces the
  seeded arrivals, admits under per-tenant quotas + weighted-fair
  priority classes, routes by prefix-cache affinity (least-loaded by
  scraped queue depth as fallback), journals every decision, acks from
  the fleet completion-log union, and re-routes unacked work off
  replicas whose metrics scrape goes stale. ``--kill-seed`` SIGKILLs a
  replica mid-load (supervisor chaos plan) AND SIGKILLs the router at
  a seeded wall time — the respawned router resumes from its journal
  without double-routing.
- **random phase** (clean): the SAME workload through ``--policy
  random`` — the same-seed baseline the affinity hit-rate is gated
  against.

``analyze`` then writes ``router-summary.json``: zero-dropped +
byte-identical-duplicate verdicts (the PR 9 completion-log contract
extended across replicas), affinity-vs-random measured hit rates,
per-tenant admit/reject/shed counts, per-class latency with the
interactive recovery + batch-starvation verdicts, the goodput identity
with the re-route cost priced in ``reroute_replay``, and the
journal's double-route audit. ``tools/chaos_sweep.py --router`` runs
this example across seeds and gates that summary.
"""

import argparse
import json
import os
import signal
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

#: workload shape shared by both phases (and by the chaos sweep):
#: spike multiplies INTERACTIVE arrival rates inside the window
WORKLOAD = dict(duration_s=22.0, spike=(6.0, 12.0, 4.0),
                sessions_per_tenant=6, session_prefix_blocks=3,
                block_size=8, rates={"acme": 2.5, "batchco": 1.2,
                                     "burst": 1.5})

#: chaos variant: arrivals must OUTLAST the gang-restart outage
#: (supervisor respawn + jax re-init + warmup is ~15-20s on a small
#: box) so the recovery window has post-outage samples to judge
CHAOS_WORKLOAD = dict(WORKLOAD, duration_s=44.0,
                      rates={"acme": 1.8, "batchco": 0.8,
                             "burst": 1.2})

#: arrivals this long after the disturbance ends (spike end, or the
#: last respawned replica's warmup under chaos) must meet the tenant
#: SLO again — the backlog needs a drain window first. The chaos lag
#: is longer: an outage parks ~15s of admitted arrivals at the router,
#: and the fleet needs the extra seconds to chew through that backlog
RECOVERY_LAG_S = 5.0
CHAOS_RECOVERY_LAG_S = 10.0


def workload_params(chaos: bool) -> dict:
    return CHAOS_WORKLOAD if chaos else WORKLOAD


def phase_tenants():
    """The three-tenant contract the example serves: a weighted
    interactive tenant, a batch tenant with a long SLO and early
    anti-starvation promotion, and a quota-capped interactive tenant
    whose overrun exercises ``serve.reject cause=quota``."""
    from distributed_tensorflow_tpu.serving.tenancy import TenantConfig
    return (
        TenantConfig("acme", pclass="interactive", weight=2.0,
                     slo_latency_s=2.0),
        TenantConfig("batchco", pclass="batch", weight=1.0,
                     slo_latency_s=15.0, starvation_frac=0.15),
        TenantConfig("burst", pclass="interactive", weight=1.0,
                     quota_tokens_per_s=40.0, quota_burst=80.0,
                     slo_latency_s=2.0),
    )


def router_main(run_dir: str, tdir: str, seed: int, policy: str,
                n_replicas: int, chaos: bool = False,
                tick_s: float = 0.04,
                tick_token_budget: int = 16,
                max_wall_s: float = 240.0):
    """The router process (spawn target; both incarnations run this —
    the second resumes from the journal the first left behind)."""
    from distributed_tensorflow_tpu.serving import replica as rep
    from distributed_tensorflow_tpu.serving import router as rt
    from distributed_tensorflow_tpu.telemetry import events as tv_events

    tv_events.configure(tdir, process_id="router")
    replicas = list(range(n_replicas))

    # wait until every replica's exporter has ticked once (its engine
    # is warm): arrivals must not start while the fleet is compiling
    mfile = {t: os.path.join(rep.replica_metrics_dir(run_dir, t),
                             "metrics-live.prom") for t in replicas}
    deadline = time.time() + 120.0
    while (not all(os.path.exists(p) for p in mfile.values())
           and time.time() < deadline
           and not os.path.exists(os.path.join(run_dir,
                                               "run-epoch.json"))):
        time.sleep(0.05)
    epoch = rep.run_epoch(run_dir)      # first router incarnation wins

    def clock():
        return time.time() - epoch

    def submit(replica, request, meta):
        # line-buffered append; the replica tolerates the torn tail of
        # a mid-write router SIGKILL by rewinding partial lines
        with open(rep.inbox_path(run_dir, replica), "a",
                  buffering=1) as f:
            f.write(json.dumps(rep.request_to_wire(request, meta))
                    + "\n")

    router = rt.Router(replicas=replicas, tenants=phase_tenants(),
                       submit_fn=submit, policy=policy, block_size=8,
                       tick_token_budget=tick_token_budget, seed=seed,
                       run_dir=run_dir, reroute_timeout_s=3.0,
                       clock=clock)
    wl = rt.seeded_tenant_workload(seed, tenants=phase_tenants(),
                                   **workload_params(chaos))
    import collections
    pending = collections.deque(wl)
    seen = {}              # replica -> last scrape mtime
    t_end = time.time() + max_wall_s
    while time.time() < t_end:
        now = clock()
        while pending and pending[0].arrival_s <= now:
            router.offer(pending.popleft())
        depths = {}
        stale = set()
        for t, p in mfile.items():
            try:
                m = os.path.getmtime(p)
            except OSError:
                continue
            seen[t] = m
            if time.time() - m > 1.5:
                stale.add(t)
            else:
                d = rt.parse_queue_depth(p)
                if d is not None:
                    depths[t] = d
        router.observe_depths(depths)
        router.dispatch(stale=stale)
        router.note_completed(rep.completed_ids_all(run_dir))
        router.tick_reroutes(stale=stale)
        if not pending and not router.queued and not router.inflight:
            break
        time.sleep(tick_s)
    router.emit_tenant_summary()
    stats = router.stats()
    stats["drained_clean"] = (not pending and not router.queued
                              and not router.inflight)
    tmp = os.path.join(run_dir, "router-stats.json.tmp")
    with open(tmp, "w") as f:
        json.dump(stats, f, indent=2, default=str)
    os.replace(tmp, os.path.join(run_dir, "router-stats.json"))
    for t in replicas:                  # release the fleet
        with open(rep.inbox_path(run_dir, t), "a", buffering=1) as f:
            f.write(json.dumps({"eof": True}) + "\n")
    router.close()
    tv_events.shutdown()
    print(f"[router] done: {stats['routes']} routed, "
          f"{stats['reroutes']} rerouted, "
          f"{stats['acked']} acked", flush=True)


def run_phase(phase_dir: str, seed: int, policy: str, workers: int,
              kill_seed=None, router_kill_s=None):
    """One phase: supervisor-run replica fleet + router process (killed
    and respawned once when ``router_kill_s`` is set)."""
    import multiprocessing as mp
    import threading

    from distributed_tensorflow_tpu.resilience import (
        RecoverySupervisor, seeded_kill_plan)
    from distributed_tensorflow_tpu.serving.replica import routed_replica

    os.makedirs(phase_dir, exist_ok=True)
    tdir = os.path.join(phase_dir, "telemetry")
    kill_plan = ()
    if kill_seed is not None:
        kill_plan = seeded_kill_plan(kill_seed, workers, kills=1,
                                     step_range=(40, 120))
        print(f"[{os.path.basename(phase_dir)}] replica kill plan "
              f"(seed {kill_seed}): {kill_plan}")

    ctx = mp.get_context("spawn")
    rargs = (phase_dir, tdir, seed, policy, workers,
             kill_seed is not None)
    router_proc = ctx.Process(target=router_main, args=rargs,
                              name="dtx-router")
    router_proc.start()
    router_kills = []

    def _chaos_router():
        time.sleep(router_kill_s)
        if router_proc.is_alive():
            print(f"[chaos] SIGKILL router pid {router_proc.pid} at "
                  f"t+{router_kill_s:.1f}s", flush=True)
            os.kill(router_proc.pid, signal.SIGKILL)
            router_proc.join()
            router_kills.append(time.time())
            r2 = ctx.Process(target=router_main, args=rargs,
                             name="dtx-router-2")
            r2.start()
            router_kills.append(r2)

    killer = None
    if router_kill_s is not None:
        killer = threading.Thread(target=_chaos_router, daemon=True)
        killer.start()

    sup = RecoverySupervisor(
        routed_replica, num_workers=workers, args=(phase_dir, seed),
        kwargs={"step_delay_s": 0.0},
        max_restarts=6, kill_plan=kill_plan,
        generation_timeout_s=300.0, telemetry_dir=tdir)
    result = sup.run()
    if killer is not None:
        killer.join(timeout=60.0)
    # join whichever router incarnation is current
    last = router_kills[-1] if (router_kills
                                and hasattr(router_kills[-1], "join")) \
        else router_proc
    last.join(timeout=90.0)
    if last.is_alive():
        last.terminate()
        last.join(timeout=10.0)
    for task, served, total in sorted(result.return_values):
        print(f"[{os.path.basename(phase_dir)}] replica {task}: "
              f"served {served} this generation")
    return {"restarts": sup.restarts_used,
            "router_killed": bool(router_kills)}


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

def _hit_rate(tdir: str) -> "tuple[float, int]":
    """Measured prefix-cache hit rate over a phase's ``serve.prefill``
    events (warmups excluded): hit tokens / prompt tokens."""
    from distributed_tensorflow_tpu.telemetry import events as tv_events
    cached = prompt = 0
    for events in tv_events.read_run(tdir).values():
        for ev in events:
            if ev.get("ev") != "serve.prefill" \
                    or str(ev.get("id", "")).startswith("warmup-"):
                continue
            prompt += int(ev.get("prompt_tokens") or 0)
            cached += int(ev.get("cached_tokens") or 0)
    return (cached / prompt if prompt else 0.0), prompt


def analyze(run_dir: str, seed: int, chaos: bool = False) -> dict:
    """Cross-phase verdicts -> ``router-summary.json`` (the chaos
    sweep's gate surface)."""
    from distributed_tensorflow_tpu.serving import replica as rep
    from distributed_tensorflow_tpu.serving import router as rt
    from distributed_tensorflow_tpu.telemetry import events as tv_events
    from distributed_tensorflow_tpu.telemetry import goodput

    aff = os.path.join(run_dir, "affinity")
    rnd = os.path.join(run_dir, "random")
    tenants = {t.name: t for t in phase_tenants()}
    wl = rt.seeded_tenant_workload(seed, tenants=phase_tenants(),
                                   **workload_params(chaos))

    # ---- zero dropped + byte-identical duplicates (affinity phase) --
    journal = rt.RouterJournal.replay(
        os.path.join(aff, rt.ROUTER_JOURNAL))
    rejected = {r["id"] for r in journal if r["kind"] == "reject"}
    route_counts: dict = {}
    for r in journal:
        if r["kind"] == "route":
            route_counts[r["id"]] = route_counts.get(r["id"], 0) + 1
    double_routes = sum(1 for n in route_counts.values() if n > 1)
    served_tokens: dict = {}
    duplicates = mismatched = 0
    import glob as _glob
    for path in sorted(_glob.glob(os.path.join(aff, "served-*.jsonl"))):
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                rid, toks = rec.get("id"), rec.get("tokens")
                if rid is None:
                    continue
                if rid in served_tokens:
                    duplicates += 1
                    if served_tokens[rid] != toks:
                        mismatched += 1
                else:
                    served_tokens[rid] = toks
    expected = {r.id for r in wl} - rejected
    dropped = sorted(expected - set(served_tokens))

    # ---- per-class latency + recovery/starvation verdicts -----------
    by_class: dict = {}     # pclass -> [(rid, lat)]
    reject_by: dict = {}
    sheds = 0
    spike_end = workload_params(chaos)["spike"][1]
    last_warm_wall = None   # when the LAST (re)spawned replica warmed
    for events in tv_events.read_run(
            os.path.join(aff, "telemetry")).values():
        for ev in events:
            name = ev.get("ev")
            if name == "serve.request" and ev.get("tenant"):
                lat = float(ev.get("dur_s") or 0.0)
                by_class.setdefault(ev.get("pclass"), []).append(
                    (ev.get("id"), lat))
            elif name == "serve.prefill" \
                    and str(ev.get("id", "")).startswith("warmup-"):
                w = float(ev.get("wall") or 0.0)
                if last_warm_wall is None or w > last_warm_wall:
                    last_warm_wall = w
            elif name == "serve.reject":
                key = (ev.get("tenant") or "-",
                       ev.get("cause") or "-")
                reject_by[key] = reject_by.get(key, 0) + 1
            elif name == "router.shed":
                sheds += 1

    def _pct(vals, q):
        if not vals:
            return None
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(q * (len(vals) - 1)))]

    # recovery window: arrivals after the LAST disturbance settle —
    # the spike end, or (under chaos) the moment the last respawned
    # replica finished its warmup, whichever is later — plus a drain
    # lag. Earlier arrivals carry the honest cost of the outage; the
    # gate is that service RECOVERS, not that kills are free.
    recover_rel = spike_end
    epoch_path = os.path.join(aff, "run-epoch.json")
    if last_warm_wall is not None and os.path.exists(epoch_path):
        with open(epoch_path) as f:
            epoch = float(json.load(f)["epoch"])
        recover_rel = max(recover_rel, last_warm_wall - epoch)
    recover_rel += CHAOS_RECOVERY_LAG_S if chaos else RECOVERY_LAG_S
    arrivals = {r.id: r.arrival_s for r in wl}

    def _window(pclass):
        return [lat for rid, lat in by_class.get(pclass, [])
                if arrivals.get(rid, -1.0) >= recover_rel]

    post = _window("interactive")
    batch_post = _window("batch")
    acme_slo = tenants["acme"].slo_latency_s
    interactive_recovered = (bool(post)
                             and (_pct(post, 0.99) or 9e9) <= acme_slo)
    batch_lats = [lat for _, lat in by_class.get("batch", [])]
    batch_slo = tenants["batchco"].slo_latency_s
    if chaos:
        # outage-spanning batch waits are the outage's cost, not
        # starvation; starvation = batch STILL past its SLO after the
        # fleet recovered
        batch_starved = (bool(batch_post)
                         and (_pct(batch_post, 0.99) or 9e9)
                         > batch_slo)
    else:
        batch_starved = bool(batch_lats) and max(batch_lats) > batch_slo

    # ---- affinity vs random hit rate (same seeded workload) ---------
    hit_aff, ptoks_aff = _hit_rate(os.path.join(aff, "telemetry"))
    hit_rnd, ptoks_rnd = _hit_rate(os.path.join(rnd, "telemetry"))

    # ---- goodput identity with the re-route cost priced -------------
    ledger = goodput.ledger_from_run(os.path.join(aff, "telemetry"))
    wall = ledger.get("wall_s") or 0.0
    identity_frac = (abs(ledger.get("identity_error_s") or 0.0)
                     / wall if wall > 0 else 0.0)

    stats_path = os.path.join(aff, "router-stats.json")
    router_stats = {}
    if os.path.exists(stats_path):
        with open(stats_path) as f:
            router_stats = json.load(f)

    summary = {
        "seed": seed,
        "requests": len(wl),
        "rejected_quota": len(rejected),
        "served_unique": len(served_tokens),
        "dropped": dropped,
        "duplicates": duplicates,
        "duplicates_mismatched": mismatched,
        "double_routes": double_routes,
        "reroutes": router_stats.get("reroutes", 0),
        "route_reasons": router_stats.get("route_reasons", {}),
        "sheds": sheds,
        "rejects_by_tenant_cause": {f"{t}/{c}": n for (t, c), n
                                    in sorted(reject_by.items())},
        "interactive_p50_s": _pct([lat for _, lat in
                                   by_class.get("interactive", [])],
                                  0.5),
        "interactive_p99_s": _pct([lat for _, lat in
                                   by_class.get("interactive", [])],
                                  0.99),
        "batch_p50_s": _pct(batch_lats, 0.5),
        "batch_p99_s": _pct(batch_lats, 0.99),
        "batch_max_s": max(batch_lats) if batch_lats else None,
        "interactive_recovered": interactive_recovered,
        "interactive_recovery_p99_s": _pct(post, 0.99),
        "recovery_window_start_s": round(recover_rel, 2),
        "recovery_samples": {"interactive": len(post),
                             "batch": len(batch_post)},
        "batch_recovery_p99_s": _pct(batch_post, 0.99),
        "batch_starved_past_slo": batch_starved,
        "affinity_hit_rate": round(hit_aff, 4),
        "random_hit_rate": round(hit_rnd, 4),
        "affinity_uplift": round(hit_aff - hit_rnd, 4),
        "prompt_tokens": {"affinity": ptoks_aff, "random": ptoks_rnd},
        "goodput_frac": ledger.get("goodput_frac"),
        "identity_error_frac": round(identity_frac, 6),
        "badput_reroute_replay_s": round(
            ledger["badput_s"].get("reroute_replay", 0.0), 4),
        "badput_recovery_s": round(
            ledger["badput_s"].get("recovery", 0.0), 4),
    }
    out = os.path.join(run_dir, "router-summary.json")
    with open(out + ".tmp", "w") as f:
        json.dump(summary, f, indent=2)
    os.replace(out + ".tmp", out)
    return summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--kill-seed", type=int, default=None,
                    help="SIGKILL one replica mid-load (supervisor "
                         "chaos plan) AND the router at a seeded wall "
                         "time")
    ap.add_argument("--skip-random", action="store_true",
                    help="skip the random-routing baseline phase")
    args = ap.parse_args()
    os.makedirs(args.run_dir, exist_ok=True)
    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()      # exported: spawned workers share it

    router_kill_s = None
    if args.kill_seed is not None:
        import random as _random
        rng = _random.Random(f"dtx-router-kill:{args.kill_seed}")
        # land inside the spike window, after warmup
        router_kill_s = 8.0 + 4.0 * rng.random()

    t0 = time.time()
    info = run_phase(os.path.join(args.run_dir, "affinity"),
                     args.seed, "affinity", args.workers,
                     kill_seed=args.kill_seed,
                     router_kill_s=router_kill_s)
    print(f"[affinity] phase done in {time.time() - t0:.1f}s: {info}")
    if not args.skip_random:
        # the baseline suffers the SAME kill plan — affinity-vs-random
        # is only a fair comparison if both phases lose the same caches
        t1 = time.time()
        info2 = run_phase(os.path.join(args.run_dir, "random"),
                          args.seed, "random", args.workers,
                          kill_seed=args.kill_seed,
                          router_kill_s=router_kill_s)
        print(f"[random] phase done in {time.time() - t1:.1f}s: "
              f"{info2}")
        summary = analyze(args.run_dir, args.seed,
                          chaos=args.kill_seed is not None)
        print(json.dumps(summary, indent=2))
        ok = (not summary["dropped"]
              and summary["duplicates_mismatched"] == 0
              and summary["double_routes"] == 0
              and summary["interactive_recovered"]
              and not summary["batch_starved_past_slo"]
              and summary["affinity_hit_rate"]
              > summary["random_hit_rate"])
        print(f"router verdict: {'OK' if ok else 'VIOLATIONS'}")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
