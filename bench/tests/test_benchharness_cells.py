"""BENCHMARK.json against the benchmark's contract, cells found by name
(also ones added as new files), peaks by device kind, and the command's
refusal without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.harness import cells, record, traffic, work
from bench.tests.conftest import HELD, cell_named

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1].startswith("bench/")
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_moves():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    cell_names = [w["name"] for w in SPEC["workloads"]]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        reporting = e2e[m["moves"]].get("workloads", cell_names)
        assert set(m["workloads"]) <= set(reporting), m["name"]
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file() and c["reduced"] == []


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]]
                         + sorted(HELD))
def test_every_cell_loads_with_its_files(name):
    cell = cell_named(name)
    traffic.check_mix(cell.traffic)
    work.StackShape.of(cell.config)
    if name in HELD:
        assert name not in {w["name"] for w in SPEC["workloads"]}
        return
    names = {m.name for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer and all(callable(m.read) for m in cell.per_layer)


def test_a_cell_and_a_metric_are_added_as_files_alone(tmp_path):
    """A later change adds a mix, a metric reader and BENCHMARK.json
    entries; the harness finds them by name without an edit."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    mix = json.loads((tmp_path / "bench/traffic/offline_b64_t300.json")
                     .read_text())
    mix["batch"] = 8
    (tmp_path / "bench/traffic/offline_b8_t300.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench/metrics/dummy_share.b8.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec["workloads"].append({"name": "eesen.b8", "config": "eesen",
                              "traffic": "offline_b8_t300", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "dummy_share.b8", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "device",
                              "moves": "offline_frames_per_s",
                              "workloads": ["eesen.b8"]})
    for m in spec["end_to_end"]:
        if m["name"] == "offline_frames_per_s":
            m["workloads"].append("eesen.b8")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = cells.load_cell("eesen.b8", tmp_path)
    assert cell.traffic["batch"] == 8
    assert {m.name for m in cell.end_to_end} == {"offline_frames_per_s",
                                                 "setup_s"}
    readers = {m.name: m.read for m in cell.per_layer}
    assert readers["dummy_share.b8"](None) == 42.0
    assert "launches_per_call.offline" not in readers
    with pytest.raises(KeyError):
        cells.load_cell("eesen.nothing", tmp_path)


def test_unknown_device_kind_is_an_error():
    peaks = ROOT / "bench" / "peaks.json"
    assert work.load_peaks(peaks, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.load_peaks(peaks, "TPU v9 imaginary")


def test_readers_return_nothing_without_a_trace():
    run = record.RunRecord(peaks={}, window_s=1.0,
                           counters={}, host={},
                           work={"lstm_seq": (1.0, 1.0)}, model_flops=0.0)
    assert record.idle_share(run) is None
    assert record.roofline_share(run, "lstm_seq") is None
    assert record.mfu(run) is None


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "eesen.offline",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_to_run_without_a_chip():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
