"""The knee sweep of a serving cell, once, on the chip.

    python3 bench/sweep.py --config <config> --traffic <mix> \
        --rates 1,2,4,8 --seconds <s> [--seed <n>]

Runs a serving mix (``bench/traffic/<mix>.json``) on a configuration
(``bench/configs/<config>.json``) at each arrival rate in turn, in one
process on one seed, and prints one JSON line per rate: requests
attempted and failed, the queue left when the last request arrived, how
long the engine took to drain after it, the time-to-first-frame and
frame-gap tails, the set-up time and the programs built inside the
window.  The knee is the highest rate whose backlog stays bounded: the
queue at the last arrival is under the slot count and the last third of
the requests wait no longer than the first third.  A cell of the mix runs
at 0.8 of it (PERF.md records the sweep).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
for _p in (CHECKOUT, CHECKOUT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench.harness import cells, device, serve, stats  # noqa: E402


def summary(rate: float, out) -> dict:
    host = out.record.host
    ttff = host["ttff_ms"]
    third = max(1, len(ttff) // 3)
    first, last = ttff[:third], ttff[-third:]
    return {"rate_per_s": rate, "attempted": out.attempted,
            "failed": out.failed,
            "queue_at_last_arrival": host["queue_at_last_arrival"][0],
            "drain_s": host["drain_s"][0],
            "ttff_p50_ms": stats.percentile(ttff, 50),
            "ttff_p90_ms": out.metrics.get("ttff_p90_ms"),
            "ttff_p90_first_third_ms": stats.percentile(first, 90),
            "ttff_p90_last_third_ms": stats.percentile(last, 90),
            "frame_gap_p99_ms": out.metrics.get("frame_gap_p99_ms"),
            "tick_ms_p50": stats.percentile(host["tick_ms"], 50),
            "setup_s": out.setup_s, "counters": out.record.counters,
            "readings": out.readings, "problems": out.problems}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = cells.from_files(f"{args.config}.{args.traffic}",
                            f"bench/configs/{args.config}.json",
                            args.traffic, CHECKOUT)
    if cell.traffic["loop"] != "open":
        print("sweep: only open-loop (serving) cells have a knee",
              file=sys.stderr)
        return 2
    try:
        devices = device.require_tpu(cell.chips)
    except device.NoChip as err:
        print(f"sweep: {err}", file=sys.stderr)
        return 2
    device.enable_compile_cache(CHECKOUT)
    compiles = device.CompileCounter()
    for rate in (float(r) for r in args.rates.split(",")):
        at = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                    rate_per_s=rate))
        out = serve.run(at, args.seed, args.seconds, None, devices, compiles,
                        time.perf_counter())
        print(json.dumps(summary(rate, out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
