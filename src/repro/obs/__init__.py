"""Observability plane: columnar flight recorder, decision ledger,
request-lifecycle tracing and exporters (see ``repro.obs.recorder``), and
the real serving path's host spans and JIT counter (``repro.obs.host``,
which imports JAX and so is not imported here).

Engines gate on :func:`resolve` (``telemetry=`` argument or the
``CHIRON_TELEMETRY`` environment variable); exports live in
``repro.obs.export`` and the terminal dashboard CLI runs as
``python -m repro.obs <run.jsonl>``.
"""
from repro.obs.recorder import FlightRecorder, resolve

__all__ = ["FlightRecorder", "resolve"]
