"""Observability: named step phases and the metrics registry.

  * :mod:`repro.obs.phases`   — the ``jax.named_scope`` phases every
    training runtime names its step's work with, and the map from a
    compiled program's instructions to those phases that reads a
    device trace (``train.py --trace DIR``, ``bench/phases.py``).
  * :mod:`repro.obs.metrics`  — counters / gauges / histograms +
    structured events → JSONL and a summary table; the one code path
    behind ``train.py``'s human and ``--json`` step records.
"""
from repro.obs import phases  # noqa: F401
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry, format_step)
