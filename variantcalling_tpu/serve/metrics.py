"""Request-level metrics for the ``vctpu serve`` daemon.

One recorder, two sinks:

- the daemon's OWN always-on :class:`MetricsRegistry` — admission reads
  its rolling quantiles for the SLO-aware early shed and ``/v1/status``
  / ``/v1/metrics`` render it, so the control loop works with
  ``VCTPU_OBS=0``;
- the open obs run's registry (when ``VCTPU_OBS=1``), so the daemon's
  request series land in the SAME stream/snapshot plumbing every other
  run uses — ``vctpu obs prom`` and the ``VCTPU_OBS_PROM_FILE``
  node-exporter textfile cover the daemon unchanged (PR 11).

Naming convention (docs/serving.md): per-endpoint series carry a
``.by_endpoint.<endpoint>`` suffix which the Prometheus renderer
(obs/prom.py) lifts into a real ``{endpoint="…"}`` label —
``serve.request_s.by_endpoint.filter`` becomes
``vctpu_serve_request_s{endpoint="filter",…}``.
"""

from __future__ import annotations

from variantcalling_tpu import knobs, obs
from variantcalling_tpu.obs.metrics import MetricsRegistry

#: request terminal statuses a counter family exists for
STATUSES = ("accepted", "ok", "failed", "shed", "deadline", "cancelled")


class ServeMetrics:
    """The daemon's request-metric recorder (module docstring)."""

    def __init__(self):
        self.registry = MetricsRegistry(
            window_s=knobs.get_float("VCTPU_OBS_WINDOW_S"))

    # -- recording ----------------------------------------------------------

    def add(self, name: str, n: int = 1) -> None:
        """One counter, in both sinks (``add(name, 0)`` declares it)."""
        self.registry.counter(name).add(n)
        obs.counter(name).add(n)  # no-op when obs is off

    def count(self, endpoint: str, status: str) -> None:
        self.add(f"serve.requests_{status}")
        self.add(f"serve.requests_{status}.by_endpoint.{endpoint}")

    def observe_latency(self, endpoint: str, dur_s: float) -> None:
        self.registry.histogram(
            f"serve.request_s.by_endpoint.{endpoint}").observe(dur_s)
        obs.histogram(f"serve.request_s.by_endpoint.{endpoint}").observe(dur_s)

    def set_gauge(self, name: str, value: float) -> None:
        self.registry.gauge(name).set(value)
        obs.gauge(name).set(value)

    def set_load(self, in_flight: int, queued: int) -> None:
        self.set_gauge("serve.in_flight", in_flight)
        self.set_gauge("serve.queued", queued)

    # -- reading (admission + status endpoints) -----------------------------

    def rolling_p50(self, endpoint: str) -> float | None:
        return self.registry.histogram(
            f"serve.request_s.by_endpoint.{endpoint}").rolling_quantile(0.5)

    def rolling_p99(self, endpoint: str) -> float | None:
        return self.registry.histogram(
            f"serve.request_s.by_endpoint.{endpoint}").rolling_quantile(0.99)

    def snapshot(self) -> dict:
        return self.registry.snapshot()
