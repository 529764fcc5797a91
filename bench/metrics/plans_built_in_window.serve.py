"""Dispatch plans the compiled stack built inside the window (plan-cache
misses, StackStats.plans_built)."""


def read(run):
    return run.counters["plans_built"]
