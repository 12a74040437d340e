"""Online anomaly watchdog (docs/observe.md).

Detectors (detectors.py) run over the always-on telemetry time-series
(metrics/timeseries.py) and emit alert records ``{severity, signal,
evidence, window}``; the watchdog (watchdog.py) runs them next to the
launcher's rendezvous server, publishes alerts to the ``alerts`` KV
scope (``GET /alerts``, ``hvd_alerts_total``), and closes the loop: a
confirmed step-time or straggler alert auto-arms the timeline's trace
window — the existing ``HVD_TRACE_*`` machinery, armed
rank-consistently via a KV-broadcast start step (autoarm.py) — so the
alert ships with a trace to replay instead of a bare number.
"""

from __future__ import annotations

from .detectors import (  # noqa: F401
    comm_beta_drift,
    ewma_mad_regression,
    slo_burn_rate,
    straggler_drift,
    straggler_from_verdicts,
)
from .watchdog import Watchdog  # noqa: F401
