"""The window's reads to the host: one scalar, the loss, a round while the
clock runs, and the last round's scalars once it has stopped, which the
record keeps as its counters beside the engine's compile stats."""
import time

import jax
import numpy as np

from bench import harness

KEYS = ("loss", "grad_norm", "divergence")


class _Scalar:
    """A round's metric that logs each read to the host."""
    ndim = 0

    def __init__(self, log, r, key, value):
        self.log, self.r, self.key, self.value = log, r, key, value

    def __float__(self):
        self.log.append(("fetch", self.r, self.key))
        return self.value


class _State:
    def __init__(self, log):
        self.log = log

    def block_until_ready(self):
        self.log.append(("block",))
        return self


class _Engine:
    """run_round's test double: each round sleeps a little and returns
    logging scalars (loss r, grad_norm r + 1, divergence r + 2)."""

    def __init__(self, log):
        self.log, self.rounds = log, 0

    def run_round(self, state, t, h, lr_fn):
        r, self.rounds = self.rounds, self.rounds + 1
        self.log.append(("round", r))
        time.sleep(0.005)
        return state, {k: _Scalar(self.log, r, k, float(r + i))
                       for i, k in enumerate(KEYS)}


class _System:
    lr_fn = None

    def __init__(self, log):
        self.eng = _Engine(log)

    @staticmethod
    def get_h(t):
        return 2


def test_window_reads_one_scalar_a_round():
    log = []
    _, stats = harness.window(_System(log), _State(log), 10, 0.03)
    n = stats["rounds"]
    assert n >= 2 and stats["steps"] == 2 * n and stats["t_end"] == 10 + 2 * n
    timed = [e for r in range(n) for e in (("round", r), ("fetch", r, "loss"))]
    assert log[:2 * n + 1] == timed + [("block",)]
    assert sorted(log[2 * n + 1:]) == sorted(("fetch", n - 1, k)
                                             for k in KEYS)
    assert stats["counters"] == {"loss": n - 1.0, "grad_norm": float(n),
                                 "divergence": n + 1.0}


def test_record_counters_hold_the_last_rounds_scalars(tiny_root,
                                                      monkeypatch):
    from repro.core.engine import RoundEngine

    kept = {}
    run_round, read_metrics = RoundEngine.run_round, harness.read_metrics

    def last_metrics(self, *a, **k):
        state, m = run_round(self, *a, **k)
        kept["metrics"] = m
        return state, m

    def keep_record(cell, record, traced):
        kept["record"] = record
        return read_metrics(cell, record, traced)

    monkeypatch.setattr(RoundEngine, "run_round", last_metrics)
    monkeypatch.setattr(harness, "read_metrics", keep_record)
    out = harness.run_cell(tiny_root, "tiny-decoder.h2", 2 ** 35 + 1, 0.2,
                           False, t_process=time.perf_counter(),
                           require_tpu=False)
    assert out["correct"] is True
    counters = kept["record"]["counters"]
    last = jax.device_get(kept["metrics"])
    for k in KEYS:
        assert counters[k] == float(np.asarray(last[k])), k
    assert set(counters) == {"compiles", "cache_hits", "compile_s", *KEYS}
    assert all(isinstance(v, float) for v in counters.values())
    assert counters["compiles"] >= 1 and counters["compile_s"] > 0
