"""Correctness: what the timed path produced, against the plain reference.

Two numbers are read over the answers a run checks (an answer is an
utterance of an offline call, or one served request's prompt outputs and
fed-back frames together):

    max_gap = the widest, over answers, of
              max |program - reference| / max |reference|   (per answer)
    rms_gap = sqrt(sum (program - reference)^2 / sum reference^2)
              (over every frame and feature of every answer)

A cell's traffic file says which of them it compares, and with which
limit (``check.limits``).  Served frames are checked teacher-forced:
the reference runs over the prompt followed by the frames the engine fed
back (the prompt's last output, then each generated frame but the last),
so every generated frame is compared with the reference's output at its
position.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np


def answer_gaps(outs: Sequence[np.ndarray], refs: Sequence[np.ndarray]
                ) -> List[float]:
    """Normalized gap of each answer; a non-finite output reads inf."""
    gaps = []
    for out, ref in zip(outs, refs):
        out = np.asarray(out, np.float64)
        ref = np.asarray(ref, np.float64)
        if out.shape != ref.shape:
            raise ValueError(f"answer shape {out.shape} != reference "
                             f"{ref.shape}")
        if not np.isfinite(out).all():
            gaps.append(float("inf"))
            continue
        gaps.append(float(np.max(np.abs(out - ref))
                          / max(float(np.max(np.abs(ref))), 1e-30)))
    return gaps


def rms_gap(outs: Sequence[np.ndarray], refs: Sequence[np.ndarray]
            ) -> float:
    """Root of the summed squared difference over the summed squared
    reference, over all answers; inf where an output is not finite."""
    num = den = 0.0
    for out, ref in zip(outs, refs):
        out = np.asarray(out, np.float64)
        ref = np.asarray(ref, np.float64)
        if not np.isfinite(out).all():
            return float("inf")
        num += float(np.sum((out - ref) ** 2))
        den += float(np.sum(ref ** 2))
    return float(np.sqrt(num / max(den, 1e-300)))


def readings(outs, refs) -> dict:
    """Every number the check can compare, over the same answers."""
    return {"max_gap": max(answer_gaps(outs, refs)),
            "rms_gap": rms_gap(outs, refs)}


def compared(values: dict, limits: dict) -> dict:
    """name -> (reading, limit) for the numbers a cell compares."""
    return {name: (values[name], float(limit))
            for name, limit in limits.items()}


def fed_back_inputs(prompt: np.ndarray, outputs: np.ndarray,
                    generated: np.ndarray) -> np.ndarray:
    """The stream a served request's stack saw: its prompt, then the
    frames decode fed back (the prompt's last output first)."""
    if len(generated) == 0:
        return np.asarray(prompt, np.float32)
    return np.concatenate([prompt, outputs[-1:], generated[:-1]]).astype(
        np.float32)


def pad_batch(seqs: Sequence[np.ndarray], length: int, rows: int
              ) -> np.ndarray:
    """Zero-pad sequences at the end into a fixed (rows, length, X) batch;
    the reference is causal, so padding after a sequence leaves its
    outputs unchanged, and one fixed shape compiles once."""
    if len(seqs) > rows or any(len(s) > length for s in seqs):
        raise ValueError("batch larger than the fixed check shape")
    x = np.zeros((rows, length, seqs[0].shape[-1]), np.float32)
    for i, s in enumerate(seqs):
        x[i, :len(s)] = s
    return x
