"""Model FLOPs of every frame the window completed, over the window, as a
share of the chip's bfloat16 peak."""
from bench.harness.record import mfu


def read(run):
    return mfu(run)
