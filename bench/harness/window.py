"""What every driver shares: the outcome of a run, host annotations, the
profiler around the window, and the seeded device inputs."""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import glob
import shutil
import time
from pathlib import Path
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from bench.harness.record import RunRecord
from bench.harness.trace import TraceSummary, reduce_file
from bench.reference.lstm import seed_words

now = time.perf_counter


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    setup_s: float
    metrics: Dict[str, float]            # end-to-end, by name
    checks: Dict[str, Tuple[float, float]]   # name -> (value, limit)
    problems: list                       # why the run is not correct
    memory_peak: int
    record: RunRecord
    readings: Dict[str, float] = dataclasses.field(default_factory=dict)
    control_readings: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    @property
    def correct(self) -> bool:
        return not self.problems and all(v <= lim for v, lim in
                                         self.checks.values())


def annotate(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def profiled(directory: Optional[Path], call: str):
    """Trace the enclosed window into ``directory`` when given; yields a
    holder whose ``summary`` is set once the trace is reduced.  ``call``
    names the host span of one unit of the driver's work
    (``trace.window_of``)."""
    holder = type("Trace", (), {"summary": None})()
    if directory is None:
        yield holder
        return
    shutil.rmtree(directory, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        yield holder
    finally:
        jax.profiler.stop_trace()
    files = sorted(glob.glob(str(directory / "plugins/profile/*/*.xplane.pb")))
    if not files:
        raise RuntimeError(f"profiler wrote no trace under {directory}")
    holder.summary: TraceSummary = reduce_file(files[-1], call=call)
    shutil.rmtree(directory, ignore_errors=True)


@functools.partial(jax.jit, static_argnums=(3,))
def _normal(lo, hi, index, shape):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(lo), hi),
                             index)
    return jax.random.normal(key, shape, jnp.float32)


def seeded_input(seed: int, index: int, shape) -> jax.Array:
    """Call ``index``'s input, drawn on the device from the seed."""
    lo, hi = seed_words(seed)
    return _normal(jnp.uint32(lo), jnp.uint32(hi), jnp.uint32(index),
                   tuple(shape))
