"""Share of the traced window in which no operation ran on the chip."""
from bench.harness.record import idle_share


def read(run):
    return idle_share(run)
