"""Each cell's driver at a small width in interpret mode: correct when the
program is sound, not correct when its timed path is broken underneath
(a state that never advances, half the batch left out, one answer
altered where it is produced), and open-loop latency counted from the due
time."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import device, offline, serve

SEED = 2 ** 31 + 11


def _run(cell, seconds=1.0):
    drv = offline if cell.traffic["loop"] == "closed" else serve
    return drv.run(cell, SEED, seconds, None, jax.devices(),
                   device.CompileCounter(), time.perf_counter(),
                   interpret=True)


SCORE_ONLY = {"new_frames": {"dist": "fixed", "value": 0},
              "prompt_frames": {"dist": "uniform", "lo": 8, "hi": 24}}


@pytest.mark.parametrize("name", ["eesen.offline", "bysdne.decode",
                                  "score_only"])
def test_sound_program_is_correct(small_cell, name):
    if name == "eesen.offline":
        out = _run(small_cell(name))
    elif name == "score_only":
        out = _run(small_cell("bysdne.decode", rate_per_s=6.0, **SCORE_ONLY))
    else:
        out = _run(small_cell(name, rate_per_s=6.0))
    assert out.correct, (out.problems, out.checks)
    assert out.attempted > 0 and out.failed == 0
    assert out.checks["max_gap"][0] < 1e-4
    assert all(v > 0 for v in out.metrics.values())


def _patch_forward(monkeypatch, fault):
    from repro.rnn.compiled import CompiledStack

    original = CompiledStack.forward

    def broken(self, xs):
        return fault(original(self, xs))

    monkeypatch.setattr(CompiledStack, "forward", broken)


@pytest.mark.parametrize("fault", ["stuck_state", "half_batch", "altered"])
def test_offline_fault_is_not_correct(small_cell, monkeypatch, fault):
    faults = {
        "stuck_state": lambda ys: jnp.broadcast_to(ys[:, :1], ys.shape),
        "half_batch": lambda ys: ys.at[ys.shape[0] // 2:].set(0.0),
        "altered": lambda ys: ys.at[0, 5, 3].add(0.05),
    }
    _patch_forward(monkeypatch, faults[fault])
    out = _run(small_cell("eesen.offline"))
    assert not out.correct
    assert out.failed > 0


def _patch_prefill(monkeypatch, fault):
    """Break every admission wave's outputs: ``fault(i, n, ys)`` gives
    request i of n its (1, T, H) outputs."""
    from repro.rnn.compiled import CompiledStack

    original = CompiledStack.prefill

    def broken(self, xs, priorities=None):
        res = original(self, xs, priorities)
        if not isinstance(res, list):
            return res
        return [(fault(i, len(res), ys), st)
                for i, (ys, st) in enumerate(res)]

    monkeypatch.setattr(CompiledStack, "prefill", broken)


def _patch_decode(monkeypatch, fault):
    """Break decode ticks: ``fault(tick, y)`` gives the (k, 1, H) frames."""
    from repro.rnn.compiled import CompiledStack

    original = CompiledStack.decode
    ticks = []

    def broken(self, x_t, state):
        y, st = original(self, x_t, state)
        ticks.append(1)
        return fault(len(ticks), y), st

    monkeypatch.setattr(CompiledStack, "decode", broken)


@pytest.mark.parametrize("fault", ["stuck_state", "half_batch", "altered"])
def test_decode_fault_is_not_correct(small_cell, monkeypatch, fault):
    from repro.kernels.lstm_cell import ops

    if fault == "stuck_state":
        # the chained decode tick hands back the state it was given
        monkeypatch.setattr(ops, "lstm_decode",
                            lambda xw0, Ws, bs, Us, h0, c0, **kw: (h0, c0))
    elif fault == "half_batch":
        _patch_decode(monkeypatch, lambda t, y: y.at[y.shape[0] // 2:].set(
            0.0) if y.shape[0] > 1 else y)
    else:
        _patch_decode(monkeypatch, lambda t, y: y.at[0, 0, 2].add(0.05)
                      if t % 7 == 3 else y)
    out = _run(small_cell("bysdne.decode", rate_per_s=12.0))
    assert not out.correct


@pytest.mark.parametrize("fault", ["stuck_state", "half_batch", "altered"])
def test_prefill_fault_is_not_correct(small_cell, monkeypatch, fault):
    faults = {
        "stuck_state": lambda i, n, ys: jnp.broadcast_to(ys[:, :1],
                                                         ys.shape),
        "half_batch": lambda i, n, ys: ys * 0.0 if i >= (n + 1) // 2 else ys,
        "altered": lambda i, n, ys: ys.at[0, 1, 2].add(0.05) if i == 0
        else ys,
    }
    _patch_prefill(monkeypatch, faults[fault])
    # arrivals well above what two slots serve, so waves hold two prompts
    out = _run(small_cell("bysdne.decode", rate_per_s=40.0, **SCORE_ONLY),
               seconds=0.5)
    assert not out.correct


def test_open_loop_latency_counts_from_due_time(small_cell, monkeypatch):
    """A slow engine does not move the due times: requests that queue
    behind slow steps read their whole wait."""
    from repro.serving.recurrent import RecurrentServingEngine

    original = RecurrentServingEngine.step
    slow = {"on": False}

    def step(self):
        if slow["on"]:
            time.sleep(0.05)
        return original(self)

    monkeypatch.setattr(RecurrentServingEngine, "step", step)
    original_warm = serve.warm_up

    def warm_then_slow(*a, **k):
        served = original_warm(*a, **k)
        slow["on"] = True
        return served

    monkeypatch.setattr(serve, "warm_up", warm_then_slow)
    cell = small_cell("bysdne.decode", rate_per_s=60.0, **SCORE_ONLY)
    out = _run(cell, seconds=0.5)
    ttff = out.record.host["ttff_ms"]
    # 30 arrivals in 0.5 s; each step admits at most 2 and takes >= 50 ms
    assert len(ttff) == 30
    assert ttff[-1] > 400.0
    assert out.metrics["ttff_p90_ms"] > 400.0
    assert np.all(np.asarray(out.record.host["late_ms"]) >= 0)
