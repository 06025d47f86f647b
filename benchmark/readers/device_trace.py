"""Metrics reduced from the profiler's trace (``trace_reduce.reduce_xplane``).

``what`` is ``idle_share`` (1 - busy union over the traced slice, mean
over the chips, in %), ``step_ms_p50`` (median device time of the
program that takes most of the device's time), or ``ops_ms_per_step``
(summed device time on chip 0 of the operations whose name contains
one of ``match``, over the executions of that program in the slice).
"""

from benchmark import metric_math


def read(args: dict, record: dict, trace: dict | None) -> float | None:
    if not trace or not trace.get("window_s"):
        return None
    what = args["what"]
    if what == "idle_share":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if what == "step_ms_p50":
        p50 = metric_math.percentile(trace["main_program_s"], 50)
        return None if p50 is None else p50 * 1e3
    if what == "ops_ms_per_step":
        steps = len(trace["main_program_s"])
        if not steps:
            return None
        total = sum(s for name, s in trace["op_totals"].items()
                    if any(m in name for m in args["match"]))
        return total / steps * 1e3
    raise ValueError(f"unknown device_trace metric {what!r}")
