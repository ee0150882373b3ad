"""``repro.obs`` — the unified telemetry layer.

One substrate for everything the repo measures about itself:

* :mod:`~repro.obs.events` — the process-local span/event bus with
  wall + virtual dual clocks (``BUS``, ``enable``/``disable``).
* :mod:`~repro.obs.metrics` — counters / gauges / histograms with
  Prometheus-text and JSON exporters (``REGISTRY``).
* :mod:`~repro.obs.efficiency` — paper-grounded derived metrics: the
  measured share timeline p̂(t), Theorem-6 fluid ratio, L2 deviation
  from the fluid PM optimum, per-shape-bucket α residuals, device
  utilization.
* :mod:`~repro.obs.trace` — the one chrome-trace / perfetto exporter.
* :mod:`~repro.obs.dashboard` — live stdlib-http dashboard and static
  HTML report.

Quick start::

    from repro import obs
    obs.enable()                  # the bus and the registry are off by default
    obs.BUS.clear(); obs.REGISTRY.reset()
    ... run something instrumented ...
    obs.save_trace(obs.from_bus(obs.BUS), "run.trace.json")
    obs.save_html_report("run.html")

Until ``obs.enable()`` (and after ``obs.disable()``) every publish site
is an immediate return — numeric results are bit-identical with
telemetry off (enforced by ``tests/test_obs.py``).  ``obs.stage(name)``
marks a stage of host work on an active ``jax.profiler`` trace whether
or not the bus is on.
"""
from .dashboard import Dashboard, render_html, save_html_report
from .efficiency import (
    alpha_residuals,
    device_utilization,
    efficiency_summary,
    execution_alpha_residuals,
    fluid_ratio,
    fold_share_timeline,
    l2_share_deviation,
    measured_share_timeline,
    pm_reference_timeline,
    schedule_l2_deviation,
    schedule_share_timeline,
)
from .events import (
    BUS,
    CLOCKS,
    VIRTUAL,
    WALL,
    Event,
    EventBus,
    Span,
    disable,
    enable,
    enabled,
    get_bus,
    stage,
)
from .metrics import (
    DEFAULT_BUCKETS,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    Registry,
    get_registry,
)
from .trace import (
    SLICE_KEYS,
    counter_event,
    from_bus,
    from_execution_report,
    from_schedule,
    metadata_event,
    save_trace,
    slice_event,
)


def reset() -> None:
    """Clear the bus and the registry (the start-of-run hook)."""
    BUS.clear()
    REGISTRY.reset()


__all__ = [
    "BUS",
    "CLOCKS",
    "Counter",
    "DEFAULT_BUCKETS",
    "Dashboard",
    "Event",
    "EventBus",
    "Gauge",
    "Histogram",
    "REGISTRY",
    "Registry",
    "SLICE_KEYS",
    "Span",
    "VIRTUAL",
    "WALL",
    "alpha_residuals",
    "counter_event",
    "device_utilization",
    "disable",
    "efficiency_summary",
    "enable",
    "enabled",
    "execution_alpha_residuals",
    "fluid_ratio",
    "fold_share_timeline",
    "from_bus",
    "from_execution_report",
    "from_schedule",
    "get_bus",
    "get_registry",
    "l2_share_deviation",
    "measured_share_timeline",
    "metadata_event",
    "pm_reference_timeline",
    "render_html",
    "reset",
    "save_html_report",
    "save_trace",
    "schedule_l2_deviation",
    "schedule_share_timeline",
    "slice_event",
    "stage",
]
