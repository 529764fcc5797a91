"""The chained decode kernel's device time against the least time its
work needs on this chip (``bench/harness/work.py``: decode_work)."""
from bench.harness.record import roofline_share


def read(run):
    return roofline_share(run, "lstm_decode")
