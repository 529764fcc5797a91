"""Smoke-run ``examples/trace_demo.py`` (the `make trace-demo` target CI
uploads artifacts from): it must execute end-to-end and leave behind a
jax.profiler trace holding the program's ``repro.*`` host phases, and a
metrics snapshot."""
import collections
import glob
import json
import os
import subprocess
import sys

import jax

from tests.conftest import REPO_ROOT, SRC


def test_trace_demo_writes_artifacts(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "examples", "trace_demo.py"),
         "--out-dir", str(tmp_path)],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "observability:" in proc.stdout
    assert "repro.slot_launch:" in proc.stdout

    # the profile: the host phases of the whole pipeline as repro.* events
    (path,) = glob.glob(str(tmp_path / "profile/plugins/profile/*/"
                                       "*.xplane.pb"))
    names = collections.Counter(
        e.name for plane in jax.profiler.ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name.startswith("repro."))
    assert {"repro.forward", "repro.prefill", "repro.decode_tick",
            "repro.plan", "repro.hoist", "repro.pack", "repro.slot_launch",
            "repro.scatter"} <= set(names)
    assert names["repro.decode_tick"] == 3

    # the metrics snapshot: the span count and the decode tick histogram
    snap = json.loads((tmp_path / "metrics_snapshot.json").read_text())
    assert snap["spans"] > 0
    assert snap["metrics"]["histograms"]["decode_tick_us"]["count"] == 3
    assert not (tmp_path / "trace.json").exists()
    assert not (tmp_path / "launch_costs.json").exists()
