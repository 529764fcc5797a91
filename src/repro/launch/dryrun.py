import os
_SMALL = bool(os.environ.get("REPRO_DRYRUN_SMALL"))  # test mode: 16 devices
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + ("16" if _SMALL else "512"))
# ^ MUST precede any jax-importing import: jax locks the device count at init.

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, get_config, list_archs, supports_shape  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import (TrainSettings, init_opt_state, input_specs,  # noqa: E402
                                make_prefill_step, make_serve_step,
                                make_train_step)
from repro.models import transformer as tf  # noqa: E402
from repro.models.layers.common import sharding_ctx  # noqa: E402
from repro.sharding.partition import batch_spec, cache_specs, param_specs  # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves on placeholder devices exactly what a launch on a
real 256-chip pod (or 2-pod slice) would exercise: the sharding rules are
coherent, the collectives XLA inserts are supported, and the per-device
memory footprint is printed from ``compiled.memory_analysis()``.  Artifacts
(memory stats, cost analysis, gzipped optimized HLO for the roofline pass)
land in artifacts/dryrun/.
"""


def settings_for(cfg, shape) -> TrainSettings:
    if shape.mode != "train":
        return TrainSettings()
    # bound activation memory: <= ~64k global tokens per microbatch
    tokens = shape.global_batch * shape.seq_len
    micro = max(1, tokens // 65536)
    while shape.global_batch % micro:
        micro -= 1
    return TrainSettings(microbatches=micro)


def shardings_for(cfg, shape, mesh, specs, settings):
    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree)
    # Decode is latency-bound serial work (the paper's recurrent tail):
    # keep weights STATIONARY (TP-only) instead of FSDP-gathering them every
    # step — unless the model is too big to be 16-way resident (arctic,
    # qwen2).  PREFILL keeps FSDP: with ~1M tokens in flight, per-layer
    # weight gathers (1.4 GB) beat TP activation psums (17 GB); measured
    # difference is ~neutral because prefill's collective term is dominated
    # by attention-head resharding instead (EXPERIMENTS.md §Perf).
    tp_only = shape.mode == "decode" and cfg.num_params() <= 70e9
    p_spec = param_specs(specs["params"], mesh,
                         multi_pod_fsdp=True, fsdp=not tp_only)
    if shape.mode == "train":
        o_spec = param_specs(specs["opt_state"], mesh)
        b_spec = batch_spec(mesh, specs["batch"])
        in_sh = (ns(p_spec), ns(o_spec), ns(b_spec))
        out_sh = (ns(p_spec), ns(o_spec), None)
        donate = (0, 1)
    elif shape.mode == "prefill":
        b_spec = batch_spec(mesh, specs["batch"])
        cache_shape = jax.eval_shape(
            lambda: tf.init_cache(cfg, shape.global_batch, shape.seq_len))
        c_spec = cache_specs(cache_shape, mesh)
        in_sh = (ns(p_spec), ns(b_spec))
        out_sh = (None, ns(c_spec))
        donate = ()
    else:  # decode
        c_spec = cache_specs(specs["cache"], mesh)
        b_spec = batch_spec(mesh, specs["batch"])
        in_sh = (ns(p_spec), ns(c_spec), ns(b_spec))
        out_sh = (None, ns(c_spec))
        donate = (1,)
    return in_sh, out_sh, donate


def run_cell(arch: str, shape_name: str, multi_pod: bool, outdir: str,
             save_hlo: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell = f"{arch}__{shape_name}__{mesh_name}"
    if not supports_shape(cfg, shape):
        return {"cell": cell, "status": "skipped",
                "reason": "long_500k needs sub-quadratic attention"}

    t0 = time.time()
    if _SMALL:
        from repro.launch.mesh import make_mesh
        mesh = (make_mesh((2, 2, 4), ("pod", "data", "model")) if multi_pod
                else make_mesh((4, 4), ("data", "model")))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    settings = settings_for(cfg, shape)
    with sharding_ctx(mesh):
        specs = input_specs(cfg, shape, settings)
        in_sh, out_sh, donate = shardings_for(cfg, shape, mesh, specs, settings)
        if shape.mode == "train":
            step = make_train_step(cfg, settings)
            args = (specs["params"], specs["opt_state"], specs["batch"])
        elif shape.mode == "prefill":
            step = make_prefill_step(cfg, shape.seq_len)
            args = (specs["params"], specs["batch"])
        else:
            step = make_serve_step(cfg)
            args = (specs["params"], specs["cache"], specs["batch"])
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    n_dev = mesh.devices.size
    result = {
        "cell": cell,
        "status": "ok",
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "n_devices": n_dev,
        "mode": shape.mode,
        "microbatches": settings.microbatches,
        "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.output_size_in_bytes
                                      + mem.temp_size_in_bytes
                                      - mem.alias_size_in_bytes),
        },
        "cost_analysis": {k: v for k, v in (cost or {}).items()
                          if isinstance(v, (int, float)) and
                          k in ("flops", "bytes accessed", "transcendentals")},
    }
    os.makedirs(outdir, exist_ok=True)
    if save_hlo:
        hlo_path = os.path.join(outdir, f"{cell}.hlo.gz")
        with gzip.open(hlo_path, "wt") as f:
            f.write(compiled.as_text())
        result["hlo"] = hlo_path
    with open(os.path.join(outdir, f"{cell}.json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-hlo", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    r = run_cell(arch, shape, mp, args.out,
                                 save_hlo=not args.no_hlo)
                except Exception as e:  # a failing cell is a bug: surface it
                    r = {"cell": f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}",
                         "status": "FAILED", "error": f"{type(e).__name__}: {e}",
                         "trace": traceback.format_exc()[-2000:]}
                    with open(os.path.join(args.out, r["cell"] + ".json"), "w") as f:
                        json.dump(r, f, indent=1)
                results.append(r)
                status = r["status"]
                extra = ""
                if status == "ok":
                    gb = r["memory"]["peak_bytes_per_device"] / 2**30
                    extra = f"peak {gb:6.2f} GiB/dev  {r['compile_s']}s"
                elif status == "FAILED":
                    extra = r["error"][:120]
                print(f"[{status:7s}] {r['cell']:55s} {extra}", flush=True)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "FAILED" for r in results)
    print(f"\n== dry-run: {n_ok} ok, {n_skip} skipped (documented), {n_fail} FAILED ==")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
