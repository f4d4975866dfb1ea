"""telemetry/ — flight recorder, goodput accounting, cluster monitor.

Three pillars (docs/observability.md):
  * tracer.py  — per-thread span API + bounded ring + Chrome-trace dumps
    (on demand, on fatal exit, and automatically on watchdog anomalies);
  * goodput.py — classify every second of the train loop into
    {compute, input_wait, checkpoint, eval, stall, restart};
  * monitor.py — ``main.py monitor``: live rollup over every per-host
    metrics stream.
"""
from .goodput import CATEGORIES, GoodputMeter, goodput  # noqa: F401
from .tracer import (  # noqa: F401
    SCOPE_CATALOG, SPAN_CATALOG, SPAN_SCHEMA_VERSION, FlightRecorder,
    recorder, span)


def configure_from_config(cfg, writer=None, process_index: int = 0) -> None:
    """Wire the process-global recorder from an ExperimentConfig — called
    once per entry point (main.py run_*): sets the ring bound, the dump
    directory (``<log_root>/telemetry`` unless ``telemetry.trace_dir``
    overrides), the chief's metrics writer for ``trace_dump`` rows, and
    the anomaly-profiling knobs."""
    import os
    tcfg = cfg.telemetry
    dump_dir = tcfg.trace_dir or os.path.join(cfg.log_root, "telemetry")
    recorder.configure(
        dump_dir=dump_dir, writer=writer,
        ring=max(1024, tcfg.ring_events),
        enabled=tcfg.enabled,
        process_index=process_index,
        profile_on_anomaly=tcfg.profile_on_anomaly,
        profile_secs=tcfg.profile_secs)
