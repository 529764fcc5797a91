"""Host-phase tracing in one sitting: a traced forward + prefill + 3
feedback decode ticks under ``jax.profiler``, written out as the
profiler's trace + a metrics snapshot.

``ExecutionPolicy(trace=True)`` binds a live Tracer to the compiled
stack; every forward/prefill/decode_tick call, plan, and each slot's
hoist/pack/slot_launch/scatter phase becomes a host-time span and a
``repro.<name>`` annotation in the profile, on the same clock as the
device operations those phases issued.  Open the profile in TensorBoard's
profile plugin or https://ui.perfetto.dev, or read it back with
``jax.profiler.ProfileData`` as this script does for its summary.

    PYTHONPATH=src python examples/trace_demo.py   (or: make trace-demo)

Writes <out-dir>/profile/plugins/profile/<run>/*.xplane.pb and
<out-dir>/metrics_snapshot.json.
"""
import argparse
import collections
import glob
import json
import os

import jax
import jax.numpy as jnp

from repro import rnn
from repro.configs.sharp_lstm import lstm_config
from repro.models.layers.lstm import init_lstm_stack
from repro.runtime.obs import PROFILER_PREFIX

H, T, L = 64, 24, 3


def host_ms_by_span(xplane: str) -> dict:
    """Total host ms of each ``repro.*`` annotation in a profile."""
    total = collections.Counter()
    for plane in jax.profiler.ProfileData.from_file(xplane).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROFILER_PREFIX):
                    total[e.name] += e.duration_ns / 1e6
    return dict(total)


def main(out_dir: str = "artifacts") -> dict:
    stack = init_lstm_stack(jax.random.PRNGKey(0), lstm_config(H, layers=L),
                            jnp.float32)
    cs = rnn.compile(stack, rnn.ExecutionPolicy(interpret=True, trace=True))
    xs = jax.random.normal(jax.random.PRNGKey(1), (1, T, H)) * 0.5

    profile_dir = os.path.join(out_dir, "profile")
    jax.profiler.start_trace(profile_dir)
    try:
        cs.forward(xs)                      # whole-sequence span tree
        ys, state = cs.prefill(xs)          # prefill + exact t=T state
        y_t = ys[:, -1:]
        for _ in range(3):                  # serving steady state: ticks
            y_t, state = cs.decode(y_t, state)
        jax.block_until_ready(y_t)
    finally:
        jax.profiler.stop_trace()

    paths = {
        "profile": sorted(glob.glob(os.path.join(
            profile_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1],
        "snapshot": os.path.join(out_dir, "metrics_snapshot.json"),
    }
    with open(paths["snapshot"], "w") as f:
        json.dump(cs.tracer.snapshot(), f, indent=1, sort_keys=True)

    print(cs.describe())
    print()
    print("host ms by span (from the profile):")
    for name, ms in sorted(host_ms_by_span(paths["profile"]).items()):
        print(f"  {name}: {ms:.2f}")
    print()
    for k, p in sorted(paths.items()):
        print(f"wrote {k}: {p}")
    return paths


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out-dir", default="artifacts",
                    help="where the profile + metrics snapshot land")
    main(ap.parse_args().out_dir)
