"""The control fails each cell's comparison: the plain reference computed
in the precision below the configuration's, put in the program's place,
reads above the limit of at least one of the numbers the cell compares.
At the configurations' full width, on a batch the CPU holds (the chip's
readings at the cells' own sizes are in PERF.md)."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.harness import check, offline, traffic
from bench.reference import lstm as reference
from bench.tests.conftest import HELD, cell_named

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]]
                         + sorted(HELD))
def test_control_reads_above_the_limit(name):
    cell = cell_named(name)
    config, mix = cell.config, cell.traffic
    spec = mix["check"]
    frames = mix.get("frames") or (
        max(traffic.quantile_sizes(mix["prompt_frames"], 2))
        + max(traffic.quantile_sizes(mix["new_frames"], 2)))
    params = offline.make_params(config, 20260517)
    xs = jax.random.normal(jax.random.PRNGKey(7),
                           (4, frames, config["input_size"]))
    prec = config["matmul_precision"]
    ref = np.asarray(reference.stack_forward(params, xs, precision=prec))
    ctrl = np.asarray(reference.stack_forward(params, xs,
                                              mode=spec["control"],
                                              precision=prec))
    values = check.readings(list(ctrl), list(ref))
    failed = {k: (values[k], lim) for k, lim in spec["limits"].items()
              if values[k] > lim}
    assert failed, (name, values, spec["limits"])
