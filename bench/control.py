"""Readings that set a cell's correctness limit: the program's and the
control's, seed by seed, in one process on the chip.

    python3 bench/control.py --config <config> --traffic <mix> \\
        [--mode <bf16|int8>] --seeds 1,2,3 --seconds <s>

For each seed it makes one run of the mix on the configuration (as
``bench/run.py --trace 0`` runs a cell of them) and, over the same inputs
the run checks, the control: the plain reference computed in the
precision below the configuration's (``bench/reference/lstm.py``; the
mix's ``check.control`` unless ``--mode`` names another).  It prints one JSON line per seed with the
program's readings and the control's (``harness/check.py``).  The limit lies
between the largest program reading and the smallest control reading
(PERF.md).  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
for _p in (CHECKOUT, CHECKOUT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench.harness import cells, device  # noqa: E402
from bench.reference.lstm import MODES  # noqa: E402
from bench.run import DRIVERS  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--mode", choices=MODES[1:])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = cells.from_files(f"{args.config}.{args.traffic}",
                            f"bench/configs/{args.config}.json",
                            args.traffic, CHECKOUT)
    try:
        devices = device.require_tpu(cell.chips)
    except device.NoChip as err:
        print(f"control: {err}", file=sys.stderr)
        return 2
    device.enable_compile_cache(CHECKOUT)
    compiles = device.CompileCounter()
    import importlib

    driver = importlib.import_module(DRIVERS[cell.traffic["loop"]])
    mode = args.mode or cell.traffic["check"]["control"]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = driver.run(cell, seed, args.seconds, None, devices, compiles,
                         time.perf_counter(), control=mode)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "mode": mode,
            "problems": out.problems, "attempted": out.attempted,
            "failed": out.failed,
            "program": out.readings, "control": out.control_readings,
            "limits": {k: lim for k, (_, lim) in out.checks.items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
