"""The metrics registry: instruments, the JSONL stream and the step
record ``train.py`` prints."""
import json

import pytest

from repro.obs import MetricsRegistry, format_step


class FakeClock:
    """Deterministic clock: +1.0 s per reading."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


class TestMetricsRegistry:
    def test_instruments(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        reg.counter("c").inc(2)
        reg.gauge("g").set(3.5)
        h = reg.histogram("h")
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        snap = reg.snapshot()
        assert snap["counters"]["c"] == 3.0
        assert snap["gauges"]["g"] == 3.5
        assert snap["histograms"]["h"]["count"] == 4
        assert snap["histograms"]["h"]["mean"] == pytest.approx(2.5)
        assert h.percentile(0) == 1.0 and h.percentile(100) == 4.0
        assert "# c" in reg.summary().splitlines()[1]

    def test_jsonl_flush_and_close(self, tmp_path):
        path = tmp_path / "m.jsonl"
        reg = MetricsRegistry(str(path), clock=FakeClock())
        reg.emit("heartbeat_missed", worker=3)
        # flushed immediately, before close (the crash-safety property)
        lines = open(path).read().splitlines()
        assert json.loads(lines[0]) == \
            {"event": "heartbeat_missed", "t": 1.0, "worker": 3}
        reg.close()
        reg.close()     # idempotent
        recs = [json.loads(ln) for ln in open(path)]
        assert recs[-1]["event"] == "summary"

    def test_log_step_single_code_path(self):
        reg = MetricsRegistry()
        rec = reg.log_step(step=10, loss=1.2345, tok_per_s=99.5)
        assert rec == {"step": 10, "loss": 1.2345, "tok_per_s": 99.5}
        # the human formatter renders the same record train.py prints
        assert format_step(rec) == \
            "step    10  loss 1.2345  tok/s 99.5"
        assert json.loads(json.dumps(rec))["loss"] == 1.2345
        assert reg.find("train_step")[0]["step"] == 10
        assert reg.counter("train/steps_logged").value == 1
