"""Median host time of the engine steps that admitted nothing (pure
decode ticks), on the host clock around ``step()``."""
from bench.harness.stats import percentile


def read(run):
    return percentile(run.host.get("tick_ms", []), 50)
