"""chip_smoke.py: its phases at tiny widths in interpret mode, and its
refusal to run anywhere but on a TPU."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from repro.configs.sharp_lstm import BYSDNE, eesen_demo
from repro.rnn.compiled import StackStats

ROOT = Path(chip_smoke.__file__).resolve().parent


def _tiny(cfg, hidden=24, layers=2, **kw):
    """``cfg`` cut to a tiny width and depth (its dtype and direction kept)."""
    return dataclasses.replace(cfg, d_model=hidden, lstm_hidden=hidden,
                               lstm_input=hidden, n_layers=layers, **kw)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_serve_phase_tiny_interpret(dtype):
    out = chip_smoke.serve_phase(
        _tiny(BYSDNE, dtype=dtype), n_requests=3, min_len=3, max_len=9,
        must_len=5, max_new_frames=2, max_batch=2, interpret=True)
    assert out["requests"] == 6
    assert 5 in out["prompt_lens"]
    assert out["prefill_launches"] > 0 and out["decode_launches"] > 0
    assert out["planned_launches"] == (out["prefill_launches"]
                                       + out["decode_launches"])
    # interpret mode computes in f32: far inside the chip's bound
    assert out["rel_err_prefill"] < 1e-4
    assert out["rel_err_decode"] < 1e-4


def test_offline_phase_tiny_interpret():
    out = chip_smoke.offline_phase(_tiny(eesen_demo()), B=2, T=11,
                                   interpret=True)
    assert out["planned_launches"] > 0
    assert out["rel_err"] < 1e-4


def test_offline_phase_rejects_disagreement():
    with pytest.raises(chip_smoke.SmokeFailure, match="normalized error"):
        chip_smoke.offline_phase(_tiny(eesen_demo()), B=1, T=5,
                                 interpret=True, rel_tol=-1.0)


def test_degraded_launch_fails_the_phase():
    stats = StackStats(degraded_launches=1)
    stats.record_faults(["slot 0 fell back to per_step"])
    with pytest.raises(chip_smoke.SmokeFailure, match="slot 0 fell back"):
        chip_smoke._check_healthy(stats, "serve")


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """$JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache."""
    seen = {}
    monkeypatch.setattr(chip_smoke.jax.config, "update",
                        lambda name, value: seen.__setitem__(name, value))
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(ROOT / ".jax_cache")
    assert chip_smoke._enable_compile_cache() == want
    assert seen["jax_compilation_cache_dir"] == want


def test_main_returns_nonzero_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert out == ""


def test_script_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=120)
    assert res.returncode != 0
    assert "no TPU found" in res.stderr
    for line in res.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
