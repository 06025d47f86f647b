"""Metrics reduced from the runner's record: times on the benchmark's
clock, counts read from the program's public state after each step."""

from benchmark import metric_math


def read(args: dict, record: dict, trace: dict | None) -> float | None:
    return metric_math.reduce(record, args)
