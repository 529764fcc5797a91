"""Kernel launches the executor made per forward call (StackStats)."""


def read(run):
    calls = run.counters.get("calls", 0)
    return run.counters["launches"] / calls if calls else None
