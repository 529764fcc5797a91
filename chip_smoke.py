"""Chip smoke test: the recurrent main path, end to end, on one TPU.

    python chip_smoke.py

Phase A serves the paper's BYSDNE stack (unidirectional LSTM, H340, L5,
bfloat16 weights) through ``RecurrentServingEngine`` with compiled Pallas
kernels: 8 requests of 16-64 frames, 8 fed-back frames each, 4 slots.
Phase B runs the paper's EESEN stack (bidirectional LSTM, H340, L5,
float32) offline through ``rnn.compile(...).forward`` at B=8, T=300.
Weights and inputs are random, made from ``--seed``.

Each phase checks that every output is finite, that no launch degraded
down the guarded execution ladder, and that the outputs agree with the
plain float32 reference (``core.schedules.reference_stack``) run at
``highest`` matmul precision.  The agreement bound is on the error
normalized by the reference's largest magnitude:

    max |out - ref| / max |ref|  <=  REL_TOL = 2e-2

The TPU's DEFAULT matmul precision rounds float32 operands to bfloat16
(unit roundoff 2^-9) in the input GEMMs and, on the MXU, in the kernels'
recurrent dots.  Rounding every matmul operand so, against a float64
reference of the same stacks with this script's own seed-0 weights and
inputs, gives a normalized max error of 2.5e-3 (BYSDNE, B4, T72) and
5.5e-3 (EESEN, B8, T300).  The bound leaves over 3x headroom above that
worst case, while a wrong gate, step or state splice errs by O(1).  Served
frames fed back by decode are checked the same way, teacher-forced through
the reference.

The last line of standard output is one JSON object naming the device; it
is printed only when every phase passed.  The script exits non-zero, with
no such line, when JAX finds no TPU or any phase fails.  Wall times it
prints are cold (compilation included) and warm single runs: diagnostics,
not benchmark numbers.

JAX's persistent compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR``
when that is set, else to ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro import rnn  # noqa: E402
from repro.configs.sharp_lstm import BYSDNE, eesen_demo  # noqa: E402
from repro.core.schedules import reference_stack  # noqa: E402
from repro.models.layers.lstm import init_lstm_stack  # noqa: E402
from repro.serving.recurrent import (RecurrentRequest,  # noqa: E402
                                     RecurrentServingEngine)

REL_TOL = 2e-2


class SmokeFailure(RuntimeError):
    """A phase produced wrong, non-finite or degraded results."""


def _rel_err(out, ref) -> float:
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape:
        raise SmokeFailure(f"shape {out.shape} != reference {ref.shape}")
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _reference(params, xs):
    """The plain float32 oracle at highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(reference_stack)(params, xs)


def _check_healthy(stats, phase: str) -> None:
    if stats.degraded_launches or stats.faults_total:
        first = stats.faults[0] if stats.faults else "(no trail)"
        raise SmokeFailure(
            f"{phase}: {stats.degraded_launches} degraded launches, "
            f"{stats.faults_total} faults; first: {first}")


def serve_phase(cfg, *, n_requests: int = 8, min_len: int = 16,
                max_len: int = 64, must_len: int = 30,
                max_new_frames: int = 8, max_batch: int = 4, seed: int = 0,
                interpret: bool = False, rel_tol: float = REL_TOL) -> dict:
    """Serve ``cfg`` (a unidirectional LSTM config) through the recurrent
    serving engine with seeded weights and prompts, twice (cold, warm).

    Raises ``SmokeFailure`` unless every completion is ``ok`` and finite,
    no launch degraded, and prefill outputs and fed-back frames agree with
    the highest-precision reference."""
    params = init_lstm_stack(jax.random.PRNGKey(seed), cfg,
                             jnp.dtype(cfg.dtype))
    rng = np.random.default_rng(seed)
    lens = rng.integers(min_len, max_len + 1, size=n_requests)
    lens[0] = must_len
    prompts = [rng.standard_normal((int(t), cfg.lstm_input)
                                   ).astype(np.float32) for t in lens]
    engine = RecurrentServingEngine(cfg, params, max_batch=max_batch,
                                    interpret=interpret)

    walls = []
    for rnd in range(2):
        for i, frames in enumerate(prompts):
            engine.submit(RecurrentRequest(uid=rnd * n_requests + i,
                                           frames=frames,
                                           max_new_frames=max_new_frames))
        t0 = time.perf_counter()
        done = engine.run_to_completion()
        walls.append(time.perf_counter() - t0)
    stats = engine.compiled.stats
    _check_healthy(stats, "serve")
    if len(done) != 2 * n_requests:
        raise SmokeFailure(f"serve: {len(done)} completions for "
                           f"{2 * n_requests} requests")

    worst_prefill = worst_decode = 0.0
    for comp in done:
        if comp.status != "ok":
            raise SmokeFailure(f"serve: request {comp.uid} {comp.status}: "
                               f"{comp.error}")
        if not (np.isfinite(comp.outputs).all()
                and np.isfinite(comp.generated).all()):
            raise SmokeFailure(f"serve: request {comp.uid} non-finite")
        if len(comp.generated) != max_new_frames:
            raise SmokeFailure(f"serve: request {comp.uid} generated "
                               f"{len(comp.generated)}/{max_new_frames}")
        # decode feeds each top-layer frame back as the next input (the
        # prompt's last output first), so the whole served stream is the
        # stack's output over the prompt followed by those frames
        frames = prompts[comp.uid % n_requests]
        T = len(frames)
        xs = np.concatenate([frames, comp.outputs[-1:],
                             comp.generated[:-1]])[None]
        ref = np.asarray(_reference(params, jnp.asarray(xs)))[0]
        worst_prefill = max(worst_prefill, _rel_err(comp.outputs, ref[:T]))
        worst_decode = max(worst_decode, _rel_err(comp.generated, ref[T:]))
    worst = max(worst_prefill, worst_decode)
    if not worst <= rel_tol:
        raise SmokeFailure(
            f"serve: normalized error prefill {worst_prefill:.3e}, decode "
            f"{worst_decode:.3e} > {rel_tol:.0e}")
    return {"phase": "serve", "config": cfg.name, "dtype": cfg.dtype,
            "requests": 2 * n_requests, "prompt_lens": [int(t) for t in lens],
            "planned_launches": stats.launches,
            "prefill_launches": engine.packed_launches,
            "decode_launches": engine.decode_launches,
            "cold_wall_s": walls[0], "warm_wall_s": walls[1],
            "rel_err_prefill": worst_prefill, "rel_err_decode": worst_decode}


def offline_phase(cfg, *, B: int = 8, T: int = 300, seed: int = 0,
                  interpret: bool = False, rel_tol: float = REL_TOL) -> dict:
    """Run ``cfg`` whole-sequence through ``rnn.compile(...).forward``
    (fail-fast policy), twice (cold, warm).

    Raises ``SmokeFailure`` unless the output is finite, of shape
    (B, T, H·directions), and agrees with the highest-precision
    reference."""
    cs = rnn.compile(cfg, rnn.ExecutionPolicy(interpret=interpret,
                                              on_fault="raise"), seed=seed)
    xs = jax.random.normal(jax.random.PRNGKey(seed + 1),
                           (B, T, cfg.lstm_input), jnp.float32)
    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        ys = jax.block_until_ready(cs.forward(xs))
        walls.append(time.perf_counter() - t0)
    _check_healthy(cs.stats, "offline")
    dirs = 2 if cfg.bidirectional else 1
    want = (B, T, dirs * cfg.lstm_hidden)
    if tuple(ys.shape) != want:
        raise SmokeFailure(f"offline: output shape {tuple(ys.shape)} != "
                           f"{want}")
    if not np.isfinite(np.asarray(ys)).all():
        raise SmokeFailure("offline: non-finite outputs")
    err = _rel_err(ys, _reference(cs.params, xs))
    if not err <= rel_tol:
        raise SmokeFailure(f"offline: normalized error {err:.3e} > "
                           f"{rel_tol:.0e}")
    return {"phase": "offline", "config": cfg.name, "dtype": cfg.dtype,
            "B": B, "T": T, "planned_launches": cs.plan.launches,
            "cold_wall_s": walls[0], "warm_wall_s": walls[1],
            "rel_err": err}


def _enable_compile_cache() -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # kernels compile in well under the default 1 s threshold: keep them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this script runs only on a TPU", file=sys.stderr)
        return 2
    cache = _enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache}")

    try:
        for phase in (lambda: serve_phase(BYSDNE, seed=args.seed),
                      lambda: offline_phase(eesen_demo(), seed=args.seed)):
            print(json.dumps(phase()), flush=True)
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr)
        return 1
    n_cached = sum(len(files) for _, _, files in os.walk(cache))
    print(f"compile cache: {n_cached} files in {cache}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
