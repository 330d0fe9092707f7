"""Run-wide observability: metrics registry, link-byte attribution,
predicted-vs-measured drift, and trace-timeline export.

See README "Observability" for the lifecycle; the pieces are:

  * :mod:`repro.obs.metrics`  — counters/gauges/quantile histograms;
  * :mod:`repro.obs.collect`  — per-dispatch link-byte attribution;
  * :mod:`repro.obs.drift`    — EWMA residuals + retune hints;
  * :mod:`repro.obs.timeline` — Chrome-trace/Perfetto + Prometheus text,
    and the program's host spans on the profiler's clock;
  * :mod:`repro.obs.compiles` — a timeline instant per compile or
    persistent-cache load (its ``jax.monitoring`` listener registers
    when this package is imported).
"""

from repro.obs.metrics import (  # noqa: F401
    Registry,
    disabled,
    dump_registry,
    enabled,
    get_registry,
    scope,
    set_enabled,
)
from repro.obs.timeline import (  # noqa: F401
    Timeline,
    dump_chrome_trace,
    export_prom,
    get_timeline,
    host_span,
    to_chrome_trace,
)
from repro.obs import compiles  # noqa: F401,E402  (registers the listener)
