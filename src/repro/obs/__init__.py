"""Fleet telemetry: per-query spans, streaming metrics, attribution.

``drive_fleet(..., telemetry=True)`` attaches a :class:`RunTelemetry` to
``ClusterResult.telemetry``: the run's :class:`SpanTable` (per-query stage
stamps from whichever engine served each query), the
:class:`MetricsRegistry` (per-node / per-model streaming-quantile
latency, error / re-route / retry counters), and the
:class:`FleetTimeline` of per-window registry snapshots.  See the module
docstrings of ``spans``/``metrics``/``attribution``/``export`` for the
individual layers, ``telemetry``/``slo`` for the fleet driver's stages
that fill them, and ``python -m repro.obs.dump`` for the artifact CLI.
"""
from __future__ import annotations

from repro.obs.attribution import (AttributionReport, PercentileAttribution,
                                   latency_attribution)
from repro.obs.export import run_lines, to_prometheus, write_jsonl
from repro.obs.metrics import (Counter, FleetTimeline, Gauge, Histogram,
                               MetricsRegistry, QuantileSketch,
                               WindowSnapshot, observe_fanout)
from repro.obs.diagnose import (BreachDiagnoser, ComponentEvidence,
                                Diagnosis, Verdict)
from repro.obs.slo import (DEFAULT_RULES, AlertEvent, BurnRateRule,
                           ControlAction, Incident, IncidentLog, SloEngine,
                           SloObjective)
from repro.obs.spans import COMPONENTS, STAGES, QuerySpan, SpanTable
from repro.obs.telemetry import RunTelemetry

__all__ = [
    "AttributionReport", "PercentileAttribution", "latency_attribution",
    "run_lines", "to_prometheus", "write_jsonl",
    "Counter", "FleetTimeline", "Gauge", "Histogram", "MetricsRegistry",
    "QuantileSketch", "WindowSnapshot", "observe_fanout",
    "COMPONENTS", "STAGES", "QuerySpan", "SpanTable",
    "BreachDiagnoser", "ComponentEvidence", "Diagnosis", "Verdict",
    "DEFAULT_RULES", "AlertEvent", "BurnRateRule", "ControlAction",
    "Incident", "IncidentLog", "SloEngine", "SloObjective",
    "RunTelemetry",
]
