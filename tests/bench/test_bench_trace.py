"""The reduction from a profiler trace to the per-layer metrics.

The interval arithmetic is checked on hand-made devices; the whole
reduction on a trace recorded on a TPU v5e in a ``bench/run.py --trace
1`` run (``tests/bench/data/``), whose numbers are pinned so that no
later change can move them unseen.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def _iv(*pairs):
    return np.asarray(pairs, dtype=float).reshape(-1, 2)


def test_merge_clip_subtract():
    m = tr.merge(_iv((5, 7), (0, 2), (1, 3), (7, 8)))
    np.testing.assert_array_equal(m, _iv((0, 3), (5, 8)))
    np.testing.assert_array_equal(tr.clip(m, 1, 6), _iv((1, 3), (5, 6)))
    np.testing.assert_array_equal(
        tr.subtract(_iv((0, 10)), _iv((1, 2), (4, 6), (9, 12))),
        _iv((0, 1), (2, 4), (6, 9)))
    assert tr.total(_iv((0, 3), (5, 8))) == 6
    assert tr.subtract(_iv(), _iv((0, 1))).shape == (0, 2)


def test_ops_are_known_by_instruction_name_not_operands():
    text = ("%fusion.7 = bf16[1,1024,4096]{2,1,0:T(8,128)(2,1)} fusion("
            "bf16[1,1024,4096]{2,1,0} %collective-permute-done.3, "
            "f32[4096]{0} %state__params.1), kind=kLoop, "
            "calls=%fused_computation.7")
    assert tr.op_name(text) == "fusion.7"
    assert tr.op_label(text) == (
        "fusion.7 fusion(collective-permute-done.3, state__params.1, "
        "fused_computation.7)")
    assert not tr.PERMUTE.search(tr.op_name(text))
    assert tr.PERMUTE.search("collective-permute-done.3")
    assert not tr.COLLECTIVE.search("fusion.7")


def _device():
    d = tr.Device()
    d.ops = [("fusion.1", 0, 40), ("collective-permute-done", 35, 55),
             ("fusion.2", 50, 60), ("copy", 70, 80),
             ("collective-permute-done.3", 100, 110),
             ("fusion.1", 120, 150)]
    d.modules = [("jit_step", 0, 60), ("jit_step", 70, 110),
                 ("jit_other", 112, 114), ("jit_step", 120, 150)]
    return d


def test_busy_exposed_permute_and_step_gaps():
    d = _device()
    lo, hi = 0.0, 160.0
    assert tr.busy(d, lo, hi) == 60 + 10 + 10 + 30
    # permute alone: 40..50 and 100..110
    assert tr.exposed_permute(d, lo, hi) == 20
    # idle between step runs: 60..70 and 110..120
    assert tr.step_gaps(d, lo, hi) == 20
    assert tr.step_module(d) == "jit_step"


def test_idle_gaps_are_labelled_by_the_open_host_span():
    d = _device()
    spans = [("bench.window", 0, 160), ("bench.step", 0, 65),
             ("bench.wait", 30, 64), ("bench.data", 64, 68),
             ("bench.dispatch", 68, 69), ("bench.wait", 110, 125)]
    gaps = tr.idle_gaps(d, spans, 0, 160, n=4)
    # longest first, ties in time order
    assert gaps == [["none", 20e-9], ["data", 10e-9], ["wait", 10e-9],
                    ["none", 10e-9]]


def test_top_ops_are_averaged_over_devices():
    a, b = _device(), _device()
    b.ops = [("fusion.1", 0, 100)]
    top = dict(tr.top_ops({0: a, 1: b}, 0, 160, n=1))
    assert top == {"fusion.1": pytest.approx((70 + 100) / 2 * 1e-9)}


# ------------------------------------------------- a trace from the chip


TRACES = sorted(DATA.glob("*.xplane.pb"))


@pytest.mark.parametrize("path", TRACES, ids=[p.name for p in TRACES])
def test_recorded_trace_reduces_to_pinned_numbers(path):
    """Pinned from the recording's own reduction (the ``.json`` beside
    it); the window, the devices and every metric's reader must give
    them again."""
    from bench import cells
    want = json.loads(path.with_suffix("").with_suffix(".json").read_text())
    t = tr.load(path)
    lo, hi = t.window()
    assert sorted(t.devices) == want["device_ids"]
    devs = [t.devices[i] for i in want["device_ids"]]
    for d in devs:
        assert 0 < tr.busy(d, lo, hi) <= hi - lo
    ctx = {"window": (lo, hi), "trace_devices": devs,
           **want["context"]}
    root = Path(__file__).resolve().parents[2]
    for name, value in want["metrics"].items():
        got = cells.reader(root, name)(ctx)
        assert got == pytest.approx(value, rel=1e-9, abs=1e-9), name
    labels = {g[0] for g in tr.idle_gaps(devs[0], t.spans, lo, hi)}
    assert labels <= {"data", "dispatch", "wait", "none"}
    top = tr.top_ops(dict(enumerate(devs)), lo, hi)
    assert 0 < sum(s for _, s in top) <= (hi - lo) * 1e-9
    assert [op for op, _ in top] == want["top_ops"]
