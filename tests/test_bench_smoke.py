"""Smoke-run the benchmark suites: ``benchmarks/run.py --suite kernels``
and ``--suite dispatch`` must execute end-to-end, write their JSON
artifacts, and show (a) the sequence-fused LSTM path beating the per-step
Pallas path and (b) dispatcher-packed prefill launching strictly fewer
kernels than per-request wavefront — the perf trajectory this repo
accumulates from PR 1 on."""
import json
import os
import re
import subprocess
import sys

from tests.conftest import REPO_ROOT, SRC


def test_kernel_suite_writes_json(tmp_path):
    out = tmp_path / "BENCH_kernels.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "benchmarks", "run.py"),
         "--suite", "kernels", "--json", str(out)],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]

    data = json.loads(out.read_text())
    assert data["suite"] == "kernels"
    rows = {r["name"]: r for r in data["rows"]}
    assert "kernel/lstm_seq/fused_pallas" in rows
    assert "kernel/lstm_seq/per_step_pallas" in rows
    # the tentpole claim, measured: 1 launch beats T launches
    assert "launches=1" in rows["kernel/lstm_seq/fused_pallas"]["derived"]
    fused = rows["kernel/lstm_seq/fused_pallas"]["us_per_call"]
    per_step = rows["kernel/lstm_seq/per_step_pallas"]["us_per_call"]
    assert fused < per_step, (fused, per_step)


def test_dispatch_suite_writes_json(tmp_path):
    out = tmp_path / "BENCH_dispatch.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "benchmarks", "run.py"),
         "--suite", "dispatch", "--json", str(out)],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]

    data = json.loads(out.read_text())
    assert data["suite"] == "dispatch"
    rows = {r["name"]: r for r in data["rows"]}
    # the dispatch claim, measured: packed prefill launches strictly fewer
    # kernels than per-request wavefront, at oracle-verified-equal outputs
    packed = rows["dispatch/packed_prefill"]
    naive = rows["dispatch/per_request_wavefront"]
    n_packed = int(re.search(r"launches=(\d+)", packed["derived"]).group(1))
    n_naive = int(re.search(r"launches=(\d+)", naive["derived"]).group(1))
    assert n_packed < n_naive, (n_packed, n_naive)
    assert "max_err" in packed["derived"]

    def launches(row, key="launches"):
        return int(re.search(rf"{key}=(\d+)", rows[row]["derived"]).group(1))

    # the decode claim, measured: a planned steady-state tick launches
    # strictly fewer kernels than the old L-per-tick loop (bit-equal gated
    # inside the bench before emission)
    tick = launches("dispatch/decode_planned_tick", "launches_per_tick")
    loop = launches("dispatch/decode_loop_tick", "launches_per_tick")
    assert tick < loop, (tick, loop)
    # ...and the loop baseline is fair: it runs the k active rows only
    # (no stale pool columns padded in), so the planned win is launch
    # structure, not wasted compute
    assert "retired rows skipped" in rows["dispatch/decode_loop_tick"][
        "derived"]
    # the cross-B claim, measured: packed mixed-B prefill launches fewer
    # kernels than the equal-signature unpacked plan
    assert (launches("dispatch/cross_b_packed_prefill")
            < launches("dispatch/cross_b_unpacked_prefill"))
    # the bidir claim (ISSUE-5), measured: the interleaved fwd/bwd
    # wavefront launches strictly fewer kernels than the retired per-layer
    # fused fallback on the same bidirectional admission wave (bit-equal
    # gated inside the bench before emission)
    assert (launches("dispatch/bidir_interleaved_prefill")
            < launches("dispatch/bidir_per_layer_fallback"))
    assert "bidirectional" in rows["dispatch/bidir_interleaved_prefill"][
        "derived"]
    # the robustness claim (ISSUE-6), measured: the degraded-mode rows ran
    # the guarded ladder (recovery oracle-equal gated inside the bench)
    # and priced each rung against the healthy fused path
    assert "fallback=fused" in rows["dispatch/fault_healthy_forward"][
        "derived"]
    for rung in ("per_step", "reference"):
        derived = rows[f"dispatch/fault_{rung}_fallback"]["derived"]
        assert f"fallback={rung}" in derived
        assert "degraded=" in derived
    # the observability claim (ISSUE-7), measured: tracing costs < 5% on
    # both the forward and the chained decode tick, per the bench's
    # drift-cancelling pairwise estimator (bit-identity gated inside the
    # bench before emission — the rows exist at all only because traced
    # outputs matched untraced bit-for-bit)
    for kind in ("forward", "decode_tick"):
        derived = rows[f"dispatch/obs_traced_{kind}"]["derived"]
        overhead = float(re.search(r"overhead=([+-][\d.]+)%",
                                   derived).group(1))
        assert overhead < 5.0, (kind, derived)
        assert rows[f"dispatch/obs_untraced_{kind}"]["us_per_call"] > 0
    # the static-analysis claim (ISSUE-8), measured: verify="plan" (the
    # default) costs < 5% on the steady-state forward — verification runs
    # once per plan-cache miss, so the amortized cost is noise (bit-
    # identity gated inside the bench) — and the one-time plancheck proof
    # itself was timed over the mixed-batch plan with all rules proven
    derived = rows["dispatch/verify_on_forward"]["derived"]
    overhead = float(re.search(r"overhead=([+-][\d.]+)%",
                               derived).group(1))
    assert overhead < 5.0, derived
    assert rows["dispatch/verify_off_forward"]["us_per_call"] > 0
    assert "rules proven" in rows["dispatch/verify_plancheck"]["derived"]
    # the calibration claim (ISSUE-9), measured: the replay-calibrated
    # cost table flipped the canonical forward from the analytic G-merged
    # wavefront to the fused schedule (flip asserted inside the bench,
    # bit-equal gated) AND the flipped plan is wall-clock no slower —
    # measured mode must beat the analytic default wherever the table
    # disagrees with it
    flip_a = rows["dispatch/costmodel_analytic_forward"]
    flip_m = rows["dispatch/costmodel_measured_forward"]
    assert "schedule=wavefront" in flip_a["derived"]
    assert "schedule=fused" in flip_m["derived"]
    assert (launches("dispatch/costmodel_measured_forward")
            < launches("dispatch/costmodel_analytic_forward"))
    assert flip_m["us_per_call"] <= flip_a["us_per_call"], \
        (flip_m["us_per_call"], flip_a["us_per_call"])
    # the precision rows: at the weight-bound H1536/B8/T64
    # shape the int8 payload keeps fp32's time block (the kernel holds
    # U's f32 upcast in VMEM), and the int8 forward stayed within its
    # documented rel-err bound vs the dequantized oracle (gated inside
    # the bench before emission — the row exists only because it passed)
    q8 = rows["dispatch/quant_int8_forward"]["derived"]
    fp = rows["dispatch/quant_fp32_forward"]["derived"]
    assert "precision=int8" in q8 and "precision=fp32" in fp
    bt_fp = int(re.search(r"bt=(\d+)", fp).group(1))
    bt_q8 = int(re.search(r"bt=(\d+)", q8).group(1))
    assert bt_q8 == bt_fp, (bt_q8, bt_fp)
    rel = float(re.search(r"max_rel_err=([\d.e+-]+)", q8).group(1))
    assert rel < 1e-5, q8
