"""Observability: per-site FP8 health metrics riding the StatsBank
refresh, pluggable metrics sinks, the telemetry drain, and the names and
recorder of the program's trace spans.

Import layering (``core/statsbank.py`` imports ``repro.obs.metrics``, so
nothing here may import statsbank):

* :mod:`repro.obs.spans`     — span, scope and kernel names + the span /
  counter / compile-time recorder; imports nothing of the program, so
  every layer (the kernels included) may import it
* :mod:`repro.obs.metrics`   — metric math + telemetry site-state leaves
* :mod:`repro.obs.sinks`     — MetricsSink protocol + jsonl/csv/console
* :mod:`repro.obs.telemetry` — TelemetryState extraction + io_callback drain
* :mod:`repro.obs.doctor`    — checkpoint health reports (imports
  statsbank; import it directly, not through this package root)

The names below load their module on first use, so that importing
``repro.obs.spans`` does not import the numerics stack behind
``repro.obs.metrics``.
"""
import importlib

_EXPORTS = {
    "repro.obs.metrics": ("TELE_FIELDS", "ensure_telemetry", "has_telemetry",
                          "init_tele_state", "strip_telemetry"),
    "repro.obs.sinks": ("ConsoleSink", "CsvSink", "JsonlSink", "MemorySink",
                        "MetricsSink", "NullSink", "TeeSink", "make_sink"),
    "repro.obs.telemetry": ("Telemetry", "state_records", "telemetry_state"),
}
_MODULE_OF = {name: mod for mod, names in _EXPORTS.items() for name in names}

__all__ = sorted(_MODULE_OF)


def __getattr__(name):
    mod = _MODULE_OF.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
    return getattr(importlib.import_module(mod), name)
