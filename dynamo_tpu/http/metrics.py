"""Prometheus-style service metrics.

Re-design of the reference's HTTP metrics (lib/llm/src/http/service/
metrics.rs:36-311): request counters by (model, endpoint, status), an
inflight gauge with an RAII guard, and request-duration histograms, all
rendered in the Prometheus text exposition format at /metrics — no
prometheus client dependency needed.

The latency families are real fixed-bucket histograms
(observability/hist.py, log-spaced bounds + ``+Inf``), labeled by
``model``, ``endpoint`` and ``slo_class`` — the exact
``dynamo_tpu_http_service_*_seconds_bucket`` series the shipped Grafana
dashboard queries, and the frontend half of the SLO observatory
(docs/observability.md). ``slo_breaches_total`` counts requests the
flight recorder autopsied (observability/flight.py).

The family names below are module-level constants on purpose: the
dynflow ``dashboard-metric-without-producer`` rule reads them as this
module's advertised render surface, so a dashboard query with no
producer (or a renamed family with a stale panel) fails CI instead of
flatlining in Grafana.
"""

from __future__ import annotations

import logging
import time
from collections import defaultdict

from ..observability.hist import TIME_BUCKETS_S, HistogramVec

logger = logging.getLogger(__name__)

#: label slo_class when no admission gate classified the request
DEFAULT_SLO_CLASS = "interactive"

# rendered family names (suffixed onto the ``dynamo_tpu`` prefix)
REQUESTS_TOTAL = "http_service_requests_total"
INFLIGHT_REQUESTS = "http_service_inflight_requests"
REQUEST_DURATION_SECONDS = "http_service_request_duration_seconds"
FIRST_TOKEN_SECONDS = "http_service_first_token_seconds"
INTER_TOKEN_SECONDS = "http_service_inter_token_seconds"
TOKENS_TOTAL = "tokens_total"
SLO_BREACHES_TOTAL = "slo_breaches_total"

#: histogram label schema shared by the three latency families
_HIST_LABELS = ("model", "endpoint", "slo_class")


class Metrics:
    def __init__(self, prefix: str = "dynamo_tpu"):
        self.prefix = prefix
        self.requests_total: dict[tuple, int] = defaultdict(int)
        self.inflight: dict[tuple, int] = defaultdict(int)
        self.duration = HistogramVec(
            REQUEST_DURATION_SECONDS, _HIST_LABELS, TIME_BUCKETS_S
        )
        self.tokens_total: dict[tuple, int] = defaultdict(int)
        # serving-latency histograms (BASELINE targets: p50/p99 TTFT, ITL)
        self.first_token = HistogramVec(
            FIRST_TOKEN_SECONDS, _HIST_LABELS, TIME_BUCKETS_S
        )
        self.inter_token = HistogramVec(
            INTER_TOKEN_SECONDS, _HIST_LABELS, TIME_BUCKETS_S
        )
        # SLO observatory: breaches the flight recorder confirmed
        # ((model, slo_class) -> count; observability/flight.py calls
        # observe_breach when it writes the autopsy)
        self.slo_breaches: dict[tuple, int] = defaultdict(int)
        # extra scrape sources: () -> {metric_suffix: number}, rendered as
        # plain gauges — lets subsystems (e.g. the migration wrapper's
        # migrations_total) surface counters at /metrics without coupling
        self._sources: list = []
        # optional planner.TelemetryAggregator: first/inter-token
        # observations double as the embedded planner's TTFT/ITL
        # samples (the SLO evaluator's inputs)
        self.planner_telemetry = None

    def register_source(self, fn) -> None:
        self._sources.append(fn)

    def inflight_guard(self, model: str, endpoint: str,
                       slo_class: str = DEFAULT_SLO_CLASS) -> "InflightGuard":
        return InflightGuard(self, model, endpoint, slo_class)

    def observe_tokens(self, model: str, kind: str, n: int) -> None:
        self.tokens_total[(model, kind)] += n

    def observe_first_token(self, model: str, endpoint: str, v: float,
                            slo_class: str = DEFAULT_SLO_CLASS) -> None:
        self.first_token.labels(model, endpoint, slo_class).observe(v)
        if self.planner_telemetry is not None:
            self.planner_telemetry.record_ttft(v * 1e3)

    def observe_inter_token(self, model: str, endpoint: str, v: float,
                            slo_class: str = DEFAULT_SLO_CLASS) -> None:
        self.inter_token.labels(model, endpoint, slo_class).observe(v)
        if self.planner_telemetry is not None:
            self.planner_telemetry.record_itl(v * 1e3)

    def observe_breach(self, model: str, slo_class: str) -> None:
        """One SLO breach (flight-recorder confirmed — breach counting
        and autopsy persistence stay in lockstep)."""
        self.slo_breaches[(model, slo_class)] += 1

    def render(self) -> str:
        p = self.prefix
        lines = [
            f"# TYPE {p}_{REQUESTS_TOTAL} counter",
        ]
        for (model, endpoint, status), v in sorted(self.requests_total.items()):
            lines.append(
                f'{p}_{REQUESTS_TOTAL}{{model="{model}",endpoint="{endpoint}",status="{status}"}} {v}'
            )
        lines.append(f"# TYPE {p}_{INFLIGHT_REQUESTS} gauge")
        for (model, endpoint), v in sorted(self.inflight.items()):
            lines.append(
                f'{p}_{INFLIGHT_REQUESTS}{{model="{model}",endpoint="{endpoint}"}} {v}'
            )
        for vec in (self.duration, self.first_token, self.inter_token):
            lines.extend(vec.render(p))
        lines.append(f"# TYPE {p}_{TOKENS_TOTAL} counter")
        for (model, kind), v in sorted(self.tokens_total.items()):
            lines.append(f'{p}_{TOKENS_TOTAL}{{model="{model}",kind="{kind}"}} {v}')
        lines.append(f"# TYPE {p}_{SLO_BREACHES_TOTAL} counter")
        for (model, slo_class), v in sorted(self.slo_breaches.items()):
            lines.append(
                f'{p}_{SLO_BREACHES_TOTAL}{{model="{model}",slo_class="{slo_class}"}} {v}'
            )
        for src in self._sources:
            try:
                for k, v in sorted(src().items()):
                    lines.append(f"{p}_{k} {v}")
            except Exception:  # noqa: BLE001 — a bad source must not
                # break the whole exposition
                logger.warning("metrics source failed", exc_info=True)
        return "\n".join(lines) + "\n"


class InflightGuard:
    """RAII inflight gauge + status-coded counter (ref metrics.rs:187-311
    InflightGuard)."""

    def __init__(self, metrics: Metrics, model: str, endpoint: str,
                 slo_class: str = DEFAULT_SLO_CLASS):
        self._m = metrics
        self._key = (model, endpoint)
        self.slo_class = slo_class
        self._status = "error"
        self._start = time.monotonic()
        self._last_token_t: float | None = None
        #: first-token latency in ms once observed (the flight recorder
        #: reads it at finish to judge the request against its SLO)
        self.ttft_ms: float | None = None
        metrics.inflight[self._key] += 1

    def observe_token(self) -> None:
        """Per-streamed-chunk timing: the first call records TTFT, later
        calls record inter-token gaps."""
        now = time.monotonic()
        model, endpoint = self._key
        if self._last_token_t is None:
            ttft = now - self._start
            self.ttft_ms = ttft * 1e3
            self._m.observe_first_token(model, endpoint, ttft, self.slo_class)
        else:
            self._m.observe_inter_token(
                model, endpoint, now - self._last_token_t, self.slo_class
            )
        self._last_token_t = now

    def mark_ok(self) -> None:
        self._status = "success"

    def mark(self, status: str) -> None:
        self._status = status

    @property
    def status(self) -> str:
        return self._status

    @property
    def elapsed_ms(self) -> float:
        return (time.monotonic() - self._start) * 1e3

    def done(self) -> None:
        m, (model, endpoint) = self._m, self._key
        m.inflight[self._key] -= 1
        m.requests_total[(model, endpoint, self._status)] += 1
        m.duration.labels(model, endpoint, self.slo_class).observe(
            time.monotonic() - self._start
        )

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None and self._status == "error":
            self.mark_ok()
        self.done()
        return False
