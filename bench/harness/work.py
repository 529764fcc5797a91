"""Operations and bytes the traffic needs, computed from a config's sizes.

These count the work the mathematics requires, whatever implements it, so
a later change to the kernels cannot move them:

* model FLOPs per frame: 2 (X_l + H) 4H per layer and direction, where
  X_0 is the input width and X_l (l > 0) the previous layer's output
  width;
* ``lstm_seq`` (whole-sequence kernel) per call over B x T frames: the
  recurrent product 2 H 4H per frame, layer and direction; bytes are U
  read once per layer and direction, the gate pre-activations read in
  (float32) and h written out (float32);
* ``lstm_decode`` (one tick through the whole stack for k rows): U of
  every layer and W of layers 1..L-1 read once, the layer-0 input product
  read in, h and c of every layer read and written (float32), the top
  frame written.

Peaks come from ``bench/peaks.json``.  A kernel's roofline bound is the
larger of FLOPs over the bfloat16 MXU peak and bytes over HBM bandwidth:
using the bfloat16 peak for float32 arithmetic makes the bound lower, so a
share of it can only be understated.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

F32 = 4


@dataclasses.dataclass(frozen=True)
class StackShape:
    hidden: int
    input_size: int
    layers: int
    directions: int
    weight_bytes: int

    @classmethod
    def of(cls, config: dict) -> "StackShape":
        return cls(hidden=int(config["hidden_size"]),
                   input_size=int(config["input_size"]),
                   layers=int(config["num_layers"]),
                   directions=2 if config["bidirectional"] else 1,
                   weight_bytes={"float32": 4, "bfloat16": 2}[
                       config["weight_dtype"]])

    def layer_inputs(self):
        return [self.input_size] + [self.directions * self.hidden] * (
            self.layers - 1)


def model_flops_per_frame(s: StackShape) -> float:
    g = 4 * s.hidden
    return float(sum(s.directions * 2 * (x + s.hidden) * g
                     for x in s.layer_inputs()))


def seq_work(s: StackShape, frames: int, calls: int = 1):
    """(flops, bytes) of the whole-sequence kernels over ``frames`` frames
    (batch x length, summed) taken in ``calls`` calls."""
    g, cells = 4 * s.hidden, s.layers * s.directions
    flops = 2.0 * s.hidden * g * frames * cells
    per_frame = (g + s.hidden) * F32 * cells
    weights = s.hidden * g * s.weight_bytes * cells * calls
    return flops, float(weights + per_frame * frames)


def decode_work(s: StackShape, rows: int, ticks: int = 1):
    """(flops, bytes) of ``ticks`` chained decode ticks of ``rows`` rows
    in total (the sum of active rows over the ticks)."""
    g, L, H = 4 * s.hidden, s.layers, s.hidden
    ins = s.layer_inputs()[1:]
    flops = 2.0 * rows * (L * H * g + sum(x * g for x in ins))
    weights = (L * H * g + sum(x * g for x in ins)) * s.weight_bytes
    per_row = (g + 4 * L * H + H) * F32
    return flops, float(weights * ticks + per_row * rows)


def load_peaks(path: Path, device_kind: str) -> dict:
    table = json.loads(Path(path).read_text())
    kinds = table["devices"]
    if device_kind not in kinds:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(kinds)}")
    return kinds[device_kind]


def least_time_s(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound) of the roofline: bound is "compute" or "memory"."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
