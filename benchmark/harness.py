"""Load a cell's files by the names in ``BENCHMARK.json``, set the
system up, measure a window, print the contract's line last.

Nothing here knows a cell, a configuration or a traffic mix by name. A
cell names a configuration (``configs`` entry -> its ``file``) and a
traffic mix (``<paths[0]>/traffic/<traffic>.json``). The configuration
file names its runner (``benchmark/runners/<runner>.py``), the mix its
generator (``benchmark/generators/<generator>.py``); each metric has a
file (``<paths[0]>/end_to_end/<name>.json`` or
``<paths[0]>/layer_metrics/<name>.json``) that names its reader
(``benchmark/readers/<reader>.py``) and the reader's arguments.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import time

from benchmark import metric_math, trace_reduce

TRACE_SLICE_S = 5.0
METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


class CompileClock:
    """Seconds JAX spent compiling or loading from the persistent cache
    (``backend_compile_duration`` covers both), the cache's misses, and
    the compilations since ``mark()`` (a copy of
    ``chip_smoke.CompileClock``)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.misses = 0
        self._marked = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, seconds: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.compiles += 1

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> None:
        self._marked = self.compiles

    def since_mark(self) -> int:
        return self.compiles - self._marked

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._event)


class Tracer:
    """Profiles the last ``TRACE_SLICE_S`` seconds of the window (all
    of a shorter one) when enabled; the runner tells it the window,
    ticks it, and closes it when nothing measured is left in flight."""

    def __init__(self, directory: str | None):
        self.directory = directory
        self.start_at = None
        self.running = False

    def window(self, open_s: float, close_s: float) -> None:
        if self.directory:
            self.start_at = max(open_s, close_s - TRACE_SLICE_S)

    def tick(self, now: float) -> None:
        if self.start_at is not None and now >= self.start_at:
            import jax
            self.start_at = None
            shutil.rmtree(self.directory, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            self.running = True

    def close(self) -> None:
        if self.running:
            import jax
            jax.profiler.stop_trace()
            self.running = False

    def summary(self) -> dict | None:
        if not self.directory:
            return None
        for base, _, files in os.walk(self.directory):
            for name in files:
                if name.endswith(".xplane.pb"):
                    return trace_reduce.reduce_xplane(
                        os.path.join(base, name))
        return None


def device_peak_bytes(device) -> int:
    """The most of a chip's memory this process has held. On the v5e
    ``peak_bytes_in_use`` counts live arrays and leaves out the
    executables' temporaries, which the runtime holds apart and reports
    as ``peak_bytes_reserved`` (for the serving programs the two add up
    to the compiler's arguments + temporaries: PERF.md section 6, PR 24)."""
    stats = device.memory_stats() or {}
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _named(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"benchmark: no {what} named {name!r}")


def load_cell(root: str, workload: str) -> tuple[dict, str, dict, dict, dict]:
    """``(manifest, benchmark directory, cell, configuration, mix)`` of
    the cell ``workload``, each file found by the name the manifest
    gives."""
    manifest = _load(os.path.join(root, "BENCHMARK.json"))
    bench_dir = os.path.join(root, manifest["paths"][0])
    cell = _named(manifest["workloads"], workload, "workload")
    config = _load(os.path.join(
        root, _named(manifest["configs"], cell["config"], "config")["file"]))
    traffic = _load(os.path.join(bench_dir, "traffic",
                                 cell["traffic"] + ".json"))
    return manifest, bench_dir, cell, config, traffic


def make_runner(config: dict, traffic: dict, seed: int, devices):
    """The configuration's runner over the mix's generator."""
    runner = importlib.import_module(f"benchmark.runners.{config['runner']}")
    generator = importlib.import_module(
        f"benchmark.generators.{traffic['generator']}")
    return runner.Runner(config, traffic, generator, seed, devices)


def cell_metrics(manifest: dict, group: str, cell: str) -> list[dict]:
    """The ``group`` metrics this cell reports: those with no
    ``workloads`` key, and those that list the cell."""
    return [m for m in manifest[group]
            if cell in m.get("workloads", [cell])]


def read_metrics(bench_dir: str, group: str, metrics: list[dict],
                 record: dict, trace: dict | None) -> dict:
    """Each metric through the reader its own file names; a reader
    that finds nothing to read returns ``None`` and the metric is left
    out."""
    out = {}
    for metric in metrics:
        spec = _load(os.path.join(bench_dir, METRIC_DIRS[group],
                                  metric["name"] + ".json"))
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(spec.get("args", {}), record, trace)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def run(root: str, workload: str, *, seed: int, seconds: float,
        trace: bool, process_start: float, require_chip: bool = True,
        out=sys.stdout) -> int:
    """One run of one cell; returns the exit code. ``require_chip`` is
    for the rehearsal test alone: the command always requires it."""
    manifest, bench_dir, cell, config, traffic = load_cell(root, workload)
    peaks = _load(os.path.join(os.path.dirname(__file__), "peaks.json"))
    marks = [("start", process_start)]     # the set-up split's boundaries

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    peak = peaks.get(device["kind"])
    if require_chip and (device["platform"] != "tpu" or peak is None):
        print(f"benchmark: needs a TPU listed in peaks.json, found "
              f"platform {device['platform']!r}, kind "
              f"{device['kind']!r}", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"benchmark: {workload} needs {cell['chips']} chips, JAX "
              f"reports {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:cell["chips"]]
    device["count"] = len(devices)

    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compile_cache)
    cache_dir = enable_compile_cache()
    # every program of a run goes to the cache, however quickly it
    # compiled, so that a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock()
    marks.append(("import", time.monotonic()))

    runner = make_runner(config, traffic, seed, devices)
    notes = {}
    runner.build()
    marks.append(("build", time.monotonic()))
    notes.update(runner.reference_check())
    marks.append(("reference", time.monotonic()))
    notes.update(runner.warm())
    marks.append(("warm", time.monotonic()))
    setup_compile_s, setup_misses = clock.seconds, clock.misses
    clock.mark()
    tracer = Tracer(os.path.join(root, ".cache", "bench_trace", workload)
                    if trace else None)
    record = runner.measure(seconds, tracer)
    compiles_in_window = clock.since_mark()
    notes.update(runner.verify(record))
    marks.append(("ramp", record["window_open_s"]))
    laps = {name: t - before for (_, before), (name, t)
            in zip(marks, marks[1:])}

    peak_bytes = max(device_peak_bytes(d) for d in devices)
    record.update(
        setup_s=record["window_open_s"] - process_start,
        compile_s=setup_compile_s,
        compile_cache_misses=float(setup_misses),
        compiles_in_window=float(compiles_in_window),
        memory_peak_bytes=float(peak_bytes),
        peak_flops=(peak["bf16_flops_per_s"] * len(devices)
                    if peak else None))
    clock.close()
    summary = tracer.summary()
    group = "per_layer" if trace else "end_to_end"
    metrics = read_metrics(bench_dir, group,
                           cell_metrics(manifest, group, workload),
                           record, summary)

    failures = list(runner.failures)
    if record["compiles_in_window"]:
        failures.append(f"{record['compiles_in_window']:.0f} programs "
                        f"compiled after warm-up")
    device["memory_peak_bytes"] = peak_bytes
    line = {"correct": not failures, "attempted": record["attempted"],
            "failed": record["failed"], "metrics": metrics,
            "device": device}
    if summary:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    print(json.dumps({
        "setup_split_s": laps, "cache_dir": cache_dir, "notes": notes,
        "failures": failures,
        "programs": summary["programs"] if summary else None,
        "counts": {k: v for k, v in record.items()
                   if isinstance(v, (int, float)) or v is None},
        "series_n_p50_p90": {
            k: [len(v), metric_math.percentile(v, 50),
                metric_math.percentile(v, 90)]
            for k, v in record.items() if isinstance(v, list)}}),
        file=out)
    print(json.dumps(line), file=out)
    return 0
