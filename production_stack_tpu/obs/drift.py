"""Perf-drift sentinel: step-time medians vs a committed baseline.

The device performance observatory (engine/perf_observatory.py) keeps
a bounded ring of recent per-kind step durations and exports their
medians as ``vllm:engine_step_time_median_seconds{kind}``. This
sentinel compares the scraped medians against a committed baseline
file and flips ``vllm:perf_drift{phase}`` when any server's median
drifts beyond the band — turning silent regressions (the
silent-XLA-fallback class) into an alertable gauge instead of a
number an operator derives by hand.

Baseline JSON (e.g. observability/perf_baseline.json)::

    {"band": 0.25, "phases": {"decode": 0.025, "prefill": 0.5}}

``band`` is the allowed relative deviation (0.25 = ±25 %); phases
absent from the baseline are never flagged.
"""

from __future__ import annotations

import json
from typing import Dict


class DriftSentinel:
    def __init__(self, phases: Dict[str, float], band: float = 0.25):
        self.phases = {str(k): float(v) for k, v in phases.items()
                       if float(v) > 0}
        self.band = float(band)
        if self.band <= 0:
            raise ValueError(f"band must be > 0, got {band}")

    @classmethod
    def load(cls, path: str) -> "DriftSentinel":
        with open(path) as fh:
            raw = json.load(fh)
        return cls(phases=raw.get("phases") or {},
                   band=float(raw.get("band", 0.25)))

    def evaluate(self, medians_by_server: Dict[str, Dict[str, float]],
                 ) -> Dict[str, dict]:
        """Per baseline phase: the worst observed median across
        servers, its relative drift, and whether the band tripped.
        Servers reporting no median for a phase (idle, no steps yet)
        contribute nothing — absence of data is not drift."""
        out: Dict[str, dict] = {}
        for phase, base in self.phases.items():
            worst_drift = 0.0
            worst_observed = None
            for medians in medians_by_server.values():
                observed = medians.get(phase)
                if observed is None or observed <= 0:
                    continue
                drift = abs(observed - base) / base
                if drift >= worst_drift:
                    worst_drift = drift
                    worst_observed = observed
            out[phase] = {
                "baseline_s": base,
                "observed_s": worst_observed,
                "drift": (round(worst_drift, 6)
                          if worst_observed is not None else None),
                "tripped": (worst_observed is not None
                            and worst_drift > self.band),
            }
        return out

    def flags(self, medians_by_server: Dict[str, Dict[str, float]],
              ) -> Dict[str, float]:
        """{phase: 0.0/1.0} — the ``vllm:perf_drift{phase}`` values."""
        return {phase: 1.0 if info["tripped"] else 0.0
                for phase, info in
                self.evaluate(medians_by_server).items()}
