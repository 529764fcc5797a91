"""The benchmark's CPU tests: the program's sources on the path, and a
small cell of each kind for the drivers to run in interpret mode."""
import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


#: cells whose driver and check the tests keep running although
#: BENCHMARK.json holds them back (PERF.md, Open questions):
#: name -> (configuration file, traffic mix)
HELD = {"bysdne.decode": ("bench/configs/bysdne.json", "stream_decode")}


def cell_named(name: str):
    """A cell of BENCHMARK.json, or one held back, with its files."""
    from bench.harness import cells

    if name in HELD:
        return cells.from_files(name, *HELD[name], ROOT)
    return cells.load_cell(name, ROOT)


@pytest.fixture
def small_cell():
    """A cell of BENCHMARK.json cut to a width the CPU runs in interpret
    mode: the same driver, traffic kind and check, smaller numbers."""
    def make(name: str, **traffic):
        cell = cell_named(name)
        # an input narrower than the width where the configuration's is
        x = 16 if cell.config["input_size"] == cell.config["hidden_size"] \
            else 8
        cfg = dict(cell.config, hidden_size=16, input_size=x, num_layers=2)
        mix = dict(cell.traffic, **traffic)
        if mix["loop"] == "closed":
            mix.update(batch=2, frames=12)
        else:
            mix.update(max_batch=2, warmup_waves=1)
            mix["check"] = dict(mix["check"], sample=4)
        return dataclasses.replace(cell, config=cfg, traffic=mix)

    return make
