"""The fleet gateway: one ingress, N QueryServer replicas, zero-downtime.

Same stack as the servers (aiohttp) so a fleet deploy adds one moving
part, not a new runtime. Responsibilities:

- **Routing.** ``POST /queries.json`` goes to the least-loaded routable
  replica (fewest in-flight proxied requests); ties break on a
  consistent hash of the query's sticky key, so equal-load fleets still
  route a user deterministically and per-replica caches see repeat
  traffic. A replica is *routable* when its ``/healthz`` probe passes
  and its circuit breaker admits traffic. When EVERY replica has failed
  its last probe, routing goes *panic mode* — health is ignored
  (breakers still apply), because a fleet-wide probe blackout is more
  often a probe artifact than a dead fleet.
- **Ejection / readmission.** A background probe loop GETs every
  replica's ``/healthz`` each ``probe_interval_s``; a failing or
  unreachable replica is ejected (counted) and readmitted when the
  probe passes again. Independently, each replica has a
  :class:`~predictionio_tpu.resilience.CircuitBreaker` fed by proxy
  outcomes — consecutive forward failures stop traffic within the
  breaker threshold, faster than the next probe.
- **Retry.** /queries.json is idempotent (pure reads), so a forward
  that dies (connection error or replica 5xx) is retried ONCE on a
  different replica — never on a 4xx (the client's error follows them
  to any replica), never for the non-idempotent admin proxies, and
  bounded by the PR-2 :class:`~predictionio_tpu.resilience.RetryBudget`
  so a dying fleet sees load drop, not double.
- **Drain.** SIGTERM stops the listener (new connections refused at
  TCP), keeps answering requests that arrive on established keep-alive
  connections — with ``Connection: close`` so clients migrate — waits
  for in-flight proxies to finish (bounded by ``drain_grace_s``), then
  exits. A gateway restart under a process supervisor is 5xx-free.
- **Federation.** ``GET /metrics`` merges every replica's scrape with
  the gateway's own ``pio_fleet_*``/``pio_gateway_*`` instruments
  (:mod:`.federation`) — the endpoint ``pio top --fleet`` reads.
- **Cross-tier tracing.** Every routed query is recorded as real spans
  on the ingress trace id: ``gateway.route`` (replica chosen,
  healthy-replica count, panic/retry attribution, final status) and one
  ``gateway.proxy`` per forward attempt (upstream wall time per
  replica) — the gateway hop is attributable per request.
  ``GET /traces/recent`` fan-in merges the gateway's own span ring with
  each replica's (fetched live from healthy replicas, served
  from the per-tick cache for dead ones — a SIGKILLed worker's last
  spans survive it); ``?trace_id=`` assembles one gateway→replica
  waterfall, which is where a federated p99 exemplar resolves.
- **Telemetry ring + fleet SLOs.** Each telemetry tick (probe cadence
  by default) the gateway federates the fleet's counters, evaluates
  fleet-level SLOs over the federated deltas (:mod:`obs.slo` burn-rate
  engine — availability, the paper's <10 ms p50, shed), and appends a
  snapshot (per-replica health/inflight/breaker, queue depth, burn
  rates) to the durable on-disk :class:`~predictionio_tpu.obs.tsring.
  TelemetryRing` — the history ``GET /telemetry/window?s=N`` and
  ``pio top --history`` serve, and the sensory input a future
  autoscaler reads.
- **Incident triggers.** A fleet SLO flipping to alerting, a replica
  breaker tripping open, or a 5xx escaping to a client (the zero-5xx
  invariant the chaos suite asserts) each fire the attached
  :class:`~predictionio_tpu.obs.incidents.IncidentRecorder`, whose
  sources capture the merged traces, ring tail, and rollout state at
  that moment (``docs/observability.md``).

Model-rollout admin (``GET /models``, ``POST /models/*``) proxies to one
healthy replica; the change lands in the shared registry and every other
replica adopts it through its registry-sync loop (``docs/fleet.md``).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import logging
import threading
import time
from typing import Any
from urllib.parse import urlsplit

import aiohttp
from aiohttp import web

from predictionio_tpu.fleet.federation import federate_metrics
from predictionio_tpu.fleet.supervisor import REPLICA_CLASS_CPU
from predictionio_tpu.obs.incidents import IncidentRecorder
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.obs.sampler import HostSampler
from predictionio_tpu.obs.slo import DEFAULT_WINDOWS, SLOEngine
from predictionio_tpu.obs.tsring import TelemetryRing
from predictionio_tpu.obs.tracing import (
    TRACE_HEADER,
    Tracer,
    mint_trace_id,
)
from predictionio_tpu.obs.web import (
    BreakerInstruments,
    OPENMETRICS_CONTENT_TYPE,
    PROMETHEUS_CONTENT_TYPE,
    _wants_exemplars,
    slo_response,
)
from predictionio_tpu.registry.router import routing_key, sticky_bucket
from predictionio_tpu.resilience import (
    CircuitBreaker,
    CircuitOpenError,
    OPEN,
    RetryBudget,
)
from predictionio_tpu.tools.top import parse_prometheus

logger = logging.getLogger(__name__)

# forward outcomes that justify trying a different replica: transport
# failures and replica-side 5xx. 4xx is the CLIENT's problem — it would
# fail identically everywhere, and re-dispatching it doubles load for
# nothing.
RETRIABLE_STATUSES = frozenset((500, 502, 503, 504))

# spans fetched per replica per telemetry tick: enough ring to cover a
# probe interval of traffic at fleet scale without the fan-in dominating
# the tick
TRACE_FANIN_LIMIT = 200


@dataclasses.dataclass
class GatewayConfig:
    ip: str = "0.0.0.0"
    port: int = 8000
    replica_urls: tuple[str, ...] = ()
    # /healthz probe cadence and per-probe timeout (ejection latency is
    # bounded by interval + timeout)
    probe_interval_s: float = 1.0
    probe_timeout_s: float = 2.0
    # per-forward total timeout (connect + response)
    request_timeout_s: float = 10.0
    # one-retry budget: each first attempt earns `ratio` tokens, each
    # retry spends 1 (resilience.RetryBudget semantics)
    retry_budget_ratio: float = 0.2
    # per-replica breaker: consecutive forward failures before the
    # gateway stops routing there without waiting for the next probe
    breaker_threshold: int = 3
    breaker_recovery_s: float = 5.0
    # consistent-hash tie-break key (same field the servers use for
    # sticky canary routing)
    sticky_key_field: str = "user"
    # replica class per replica_urls entry ("device" default); shorter
    # tuples pad with "device". cpu-fallback replicas absorb OVERFLOW
    # only: routed to when every healthy device-class replica already
    # carries >= cpu_overflow_inflight proxied queries (or none is
    # routable) — slower answers instead of sheds, never instead of the
    # fast path (docs/fleet.md §Replica classes)
    replica_classes: tuple[str, ...] = ()
    cpu_overflow_inflight: int = 4
    max_payload_bytes: int = 1 << 20
    shed_retry_after_s: float = 1.0
    drain_grace_s: float = 15.0
    # telemetry tick cadence (federate + SLO + ring append + trace
    # fan-in refresh); None follows probe_interval_s, 0 disables
    telemetry_interval_s: float | None = None
    # fleet SLO burn windows ((seconds, threshold), ...); None = the SRE
    # defaults (300s fast / 3600s slow). Elasticity tests and benches
    # shrink these so post-spike burn decays inside the run instead of
    # pinning the autoscaler's idle detector for five minutes
    slo_windows: tuple[tuple[float, float], ...] | None = None
    # upstream connection pool: keep-alive connections per replica (the
    # proxy hop must not pay a TCP handshake per query) and how long an
    # idle pooled connection survives
    upstream_pool_per_host: int = 32
    upstream_keepalive_s: float = 30.0
    # shared-nothing gateway tier (--gateways N): this gateway's stable
    # id (telemetry-ring writer namespace, peer attribution) and its
    # peers' base URLs for /traces/recent + /slo fan-in. Peers share the
    # replica set behind any TCP balancer; they never share state.
    gateway_id: str = "g0"
    peer_urls: tuple[str, ...] = ()


class Replica:
    """Gateway-side state for one backend QueryServer."""

    def __init__(
        self,
        url: str,
        breaker: CircuitBreaker,
        worker_class: str = "device",
        healthy: bool = True,
    ):
        self.url = url.rstrip("/")
        split = urlsplit(self.url)
        self.name = split.netloc or self.url
        self.worker_class = worker_class
        self.breaker = breaker
        # healthy-until-proven-otherwise: the first probe fires
        # immediately at startup, and the breaker bounds the damage of
        # routing to a replica that was never up. A replica JOINING at
        # runtime (scale-out) is the opposite case — its worker process
        # is still importing jax — so it joins unhealthy and earns
        # routing from its first passing probe.
        self.healthy = healthy
        # a replica that has never passed a probe is "not up yet", not
        # "ejected": startup must not inflate the ejection counter
        self.ever_ready = False
        self.inflight = 0

    def snapshot(self) -> dict[str, Any]:
        return {
            "url": self.url,
            "healthy": self.healthy,
            "workerClass": self.worker_class,
            "inflight": self.inflight,
            "breaker": self.breaker.snapshot(),
        }


class Gateway:
    def __init__(
        self,
        config: GatewayConfig,
        metrics: MetricsRegistry | None = None,
        tracer: Tracer | None = None,
        telemetry: TelemetryRing | None = None,
        incidents: IncidentRecorder | None = None,
    ):
        if not config.replica_urls:
            raise ValueError("gateway needs at least one replica URL")
        self.config = config
        self.metrics = metrics or MetricsRegistry()
        self.tracer = tracer or Tracer(ring_size=512)
        self.telemetry = telemetry
        self.incidents = incidents
        m = self.metrics
        self._breaker_instruments = BreakerInstruments(m)
        # membership funnel: every runtime add/retire mutates the replica
        # set, the breaker map, and the per-replica gauges under this one
        # lock, so the probe loop, routing, and the scrape never see them
        # disagree (docs/fleet.md §Autoscaling)
        self._membership_lock = threading.Lock()
        classes = tuple(config.replica_classes) + ("device",) * max(
            0, len(config.replica_urls) - len(config.replica_classes)
        )
        self.replicas: list[Replica] = []
        for url, worker_class in zip(config.replica_urls, classes):
            self._make_replica(url, worker_class, healthy=True)
        self.retry_budget = RetryBudget(ratio=config.retry_budget_ratio)
        self._m_replicas = m.gauge(
            "pio_fleet_replicas", "replicas configured behind this gateway"
        )
        self._m_replicas.set(len(self.replicas))
        self._m_up = m.gauge(
            "pio_fleet_replica_up",
            "1 when the replica's last /healthz probe passed",
            labelnames=("replica",),
        )
        self._m_inflight = m.gauge(
            "pio_fleet_replica_inflight",
            "queries currently proxied to the replica",
            labelnames=("replica",),
        )
        self._m_requests = m.counter(
            "pio_fleet_requests_total",
            "queries proxied, by replica and upstream status class",
            labelnames=("replica", "status"),
        )
        self._m_ejections = m.counter(
            "pio_fleet_ejections_total",
            "replicas ejected on a failed /healthz probe",
            labelnames=("replica",),
        )
        self._m_readmissions = m.counter(
            "pio_fleet_readmissions_total",
            "ejected replicas readmitted on a passing /healthz probe",
            labelnames=("replica",),
        )
        self._m_retries = m.counter(
            "pio_fleet_retries_total",
            "queries retried on a different replica after a forward failure",
        )
        self._m_no_replica = m.counter(
            "pio_fleet_no_replica_total",
            "queries shed because no routable replica existed",
        )
        self._m_panic = m.counter(
            "pio_fleet_panic_picks_total",
            "queries routed in panic mode: every replica failed its last "
            "probe, so health was ignored (breakers still applied)",
        )
        self._m_overflow = m.counter(
            "pio_fleet_overflow_picks_total",
            "queries routed to a cpu-fallback replica because every "
            "healthy device-class replica was saturated (slower answer "
            "instead of a shed)",
        )
        self._m_membership = m.counter(
            "pio_fleet_membership_changes_total",
            "runtime replica set changes through the membership funnel, "
            "by kind (join/retire)",
            labelnames=("kind",),
        )
        self._m_latency = m.histogram(
            "pio_gateway_request_seconds",
            "gateway e2e proxy wall time (ingress to upstream answer relayed)",
            labelnames=("endpoint",),
        )
        self._m_responses = m.counter(
            "pio_gateway_responses_total",
            "CLIENT-VISIBLE /queries.json outcomes by status class — what "
            "the retry already rescued is a 2xx here (pio_fleet_requests_"
            "total counts the per-attempt forwards)",
            labelnames=("status",),
        )
        self._m_telemetry_snapshots = m.counter(
            "pio_telemetry_snapshots_total",
            "fleet snapshots appended to the on-disk telemetry ring",
        )
        self._m_telemetry_errors = m.counter(
            "pio_telemetry_errors_total",
            "telemetry ticks that failed (federation, SLO, or ring append)",
        )
        self._m_telemetry_records = m.gauge(
            "pio_telemetry_ring_records",
            "records currently live in the telemetry ring (0 when no ring "
            "is attached)",
        )
        m.register_collector(self._collect)
        # fleet-level SLOs over the federated view (obs/slo.py burn-rate
        # engine): snapshots ride the telemetry tick AND the scrape
        self.slo = SLOEngine(m)
        self._last_federated: dict[str, list[tuple[dict[str, str], float]]] = {}
        self._add_fleet_slos()
        m.register_collector(self.slo.collect)
        self._slo_alerting: dict[str, bool] = {}
        # the gateway tier samples its own host threads (event loop +
        # executor pool): GET /profile/stacks answers "is the gateway or
        # the replica slow" without touching a replica
        self.sampler = HostSampler(metrics=m)
        # trace fan-in cache: replica name -> last fetched span dicts.
        # Refreshed per telemetry tick and on /traces/recent; NEVER
        # cleared on fetch failure — a dead replica's final spans are
        # exactly the evidence an incident bundle needs.
        self._replica_spans: dict[str, list[dict[str, Any]]] = {}
        # gateway-peer fan-in cache (--gateways N): peer base url ->
        # spans it served on its LOCAL /traces/recent. Same
        # keep-on-failure rule — a dead peer's last view is evidence.
        self._peer_spans: dict[str, list[dict[str, Any]]] = {}
        self._session: aiohttp.ClientSession | None = None
        self._probe_task: asyncio.Task | None = None
        self._telemetry_task: asyncio.Task | None = None
        self._runner: web.AppRunner | None = None
        self._draining = False
        self._inflight_requests = 0
        # high-water mark since the last telemetry tick: the instant
        # inflight gauge aliases badly under bursty event-loop scheduling
        # (a tick can sample 0 mid-flood); the autoscaler needs "was
        # there concurrency since I last looked", not "at this instant"
        self._inflight_peak = 0
        self._stop_event = asyncio.Event()
        self._drain_task: asyncio.Task | None = None

    # ------------------------------------------------------------- plumbing
    def _collect(self) -> None:
        replicas = self.replicas
        self._m_replicas.set(len(replicas))
        for r in replicas:
            self._m_up.set(1.0 if r.healthy else 0.0, replica=r.name)
            self._m_inflight.set(float(r.inflight), replica=r.name)
        # reconcile-against-live-set (same discipline as pio_ann_index_*):
        # a retired replica's series must not outlive its membership —
        # covers any write that raced the retire funnel
        live = [r.name for r in replicas]
        self._m_up.prune("replica", live)
        self._m_inflight.prune("replica", live)
        state_gauge = self.metrics.get("pio_breaker_state")
        if state_gauge is not None and hasattr(state_gauge, "remove"):
            live_breakers = {r.breaker.name for r in replicas}
            for (bname,), _v in state_gauge.collect():
                if bname.startswith("replica:") and bname not in live_breakers:
                    state_gauge.remove(breaker=bname)
        if self.telemetry is not None:
            self._m_telemetry_records.set(
                float(getattr(self.telemetry, "approx_count", 0))
            )

    def _http(self) -> aiohttp.ClientSession:
        if self._session is None or self._session.closed:
            # pooled keep-alive upstream connector: the proxy hop's
            # budget is ~1 ms, a TCP handshake per forward would be most
            # of it. Bounded per replica so one slow backend can't
            # starve the pool fleet-wide; unbounded overall because the
            # replica set itself is the bound.
            self._session = aiohttp.ClientSession(
                connector=aiohttp.TCPConnector(
                    limit=0,
                    limit_per_host=self.config.upstream_pool_per_host,
                    keepalive_timeout=self.config.upstream_keepalive_s,
                ),
                timeout=aiohttp.ClientTimeout(
                    total=self.config.request_timeout_s
                ),
            )
        return self._session

    # ---------------------------------------------------------- fleet SLOs
    def _add_fleet_slos(self) -> None:
        """Fleet-level objectives evaluated over federated counter deltas
        (the replicas' own /slo endpoints rate each process in isolation;
        these rate what CLIENTS of the fleet experience)."""

        def availability() -> tuple[float, float]:
            # CLIENT-VISIBLE outcomes only: a forward that failed and was
            # rescued by the retry is a success here (rating per-attempt
            # forwards would flip this SLO to alerting during a chaos
            # kill whose zero-5xx invariant is actually holding). Sheds
            # are 503 responses, so they are already counted as bad.
            total = bad = 0.0
            for key, v in self._m_responses.collect():
                labels = dict(zip(self._m_responses.labelnames, key))
                total += v
                if labels.get("status") == "5xx":
                    bad += v
            return total, bad

        def latency() -> tuple[float, float]:
            # the paper's <10 ms p50 target, fleet-wide: over-threshold
            # fraction from the FEDERATED request histogram (the
            # replicas' cumulative buckets summed series-wise; 0.01 sits
            # exactly on a ladder bound so good = the 0.01 bucket)
            total = good = 0.0
            for labels, v in self._last_federated.get(
                "pio_request_seconds_bucket", ()
            ):
                if labels.get("endpoint") != "/queries.json":
                    continue
                le = labels.get("le")
                if le == "+Inf":
                    total += v
                elif le == "0.01":
                    good += v
            return total, max(0.0, total - good)

        def shed() -> tuple[float, float]:
            total = sum(v for _key, v in self._m_responses.collect())
            return total, self._m_no_replica.total()

        windows = self.config.slo_windows or DEFAULT_WINDOWS
        self.slo.add(
            "fleet-availability",
            "fraction of fleet queries answered without a 5xx, transport "
            "error, or shed",
            objective=0.999,
            source=availability,
            windows=windows,
        )
        self.slo.add(
            "fleet-latency",
            "fraction of fleet queries under the paper's 10 ms target "
            "(federated replica histograms)",
            objective=0.50,
            source=latency,
            windows=windows,
        )
        self.slo.add(
            "fleet-shed",
            "fraction of fleet queries NOT shed for want of a routable "
            "replica",
            objective=0.99,
            source=shed,
            windows=windows,
        )

    # --------------------------------------------------- incident plumbing
    def _trigger_incident(self, kind: str, context: dict[str, Any]) -> None:
        """Fire the flight recorder WITHOUT stalling the event loop: a
        capture does real disk I/O (ring tail, registry read, bundle
        write), and it fires exactly when the fleet is degraded — the
        worst moment to block every in-flight proxy. Off-loop callers
        fall back to inline capture."""
        if self.incidents is None:
            return
        # profile-on-alert: the incident leaves with the gateway's folded
        # host stacks attached — snapshotted NOW (cheap, in-memory), not
        # on the executor, so the stacks show the moment of the alert
        texts = {"stacks_folded": self.sampler.folded()}
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            # pio-lint: disable=async-blocking-call -- RuntimeError branch: no loop is running here, inline capture cannot stall one
            self.incidents.trigger(kind, context=context, texts=texts)
            return
        loop.run_in_executor(
            None,
            lambda: self.incidents.trigger(kind, context=context, texts=texts),
        )

    def _on_breaker_transition(self, name: str, old: str, new: str) -> None:
        if new == OPEN:
            self._trigger_incident(
                "breaker-trip",
                {"breaker": name, "from": old, "to": new},
            )

    def _note_transition(
        self, event: str, replica: Replica, **tags: Any
    ) -> None:
        """The single funnel for replica state transitions: counter +
        health-event span (the eject/readmit timeline incident bundles
        and ``/traces/recent`` replay) — the ``fleet-unattributed-proxy``
        lint rule holds every transition to this path."""
        if event == "eject":
            self._m_ejections.inc(replica=replica.name)
        elif event == "readmit":
            self._m_readmissions.inc(replica=replica.name)
        self.tracer.record_span(
            "gateway.health",
            "gateway",
            0.0,
            trace_id=mint_trace_id(),
            status=event,
            replica=replica.name,
            **tags,
        )

    # ----------------------------------------------------- fleet membership
    def _make_replica(
        self, url: str, worker_class: str, healthy: bool
    ) -> Replica:
        """Construct + register one replica: breaker watched (state
        gauge), trip listener chained (incident trigger), appended to the
        routing set. The only place replicas are born."""
        breaker = self._breaker_instruments.watch(
            CircuitBreaker(
                name=f"replica:{urlsplit(url.rstrip('/')).netloc or url}",
                failure_threshold=self.config.breaker_threshold,
                recovery_timeout_s=self.config.breaker_recovery_s,
            )
        )
        # a breaker tripping OPEN is an incident trigger: by the time an
        # operator looks, the consecutive failures that tripped it are
        # only in the flight recorder
        breaker.chain_listener(self._on_breaker_transition)
        replica = Replica(url, breaker, worker_class=worker_class, healthy=healthy)
        self.replicas = [*self.replicas, replica]
        return replica

    def add_replica(self, url: str, worker_class: str = "device") -> Replica:
        """Scale-out membership: one locked funnel adds the replica to
        the routing set, the breaker map, and the probe loop's view in
        one step. The replica joins UNHEALTHY — no query routes to it
        until its first ``/healthz`` probe passes (a worker paying its
        jax import must not eat traffic)."""
        with self._membership_lock:
            name = urlsplit(url.rstrip("/")).netloc or url
            for r in self.replicas:
                if r.name == name:
                    raise ValueError(f"replica {name!r} already routed")
            replica = self._make_replica(url, worker_class, healthy=False)
            self._m_replicas.set(len(self.replicas))
            self._m_membership.inc(kind="join")
            self._note_transition("join", replica, worker_class=worker_class)
            return replica

    def retire_replica(self, url_or_name: str) -> Replica | None:
        """Scale-in membership: remove the replica from routing through
        the same locked funnel. New requests stop routing to it
        immediately; requests already forwarded hold the Replica object
        and complete normally (the worker drains them after its SIGTERM)
        — the ordering that makes scale-in 5xx-free. Its live-set gauges
        (up/inflight/breaker state) drop from the exposition; its span
        cache is dropped too (a planned retire is not incident
        evidence). Returns the retired replica, or None when unknown."""
        name = urlsplit(url_or_name.rstrip("/")).netloc or url_or_name
        with self._membership_lock:
            victim = next((r for r in self.replicas if r.name == name), None)
            if victim is None:
                return None
            self.replicas = [r for r in self.replicas if r is not victim]
            self._breaker_instruments.unwatch(victim.breaker)
            self._m_up.remove(replica=victim.name)
            self._m_inflight.remove(replica=victim.name)
            self._replica_spans.pop(victim.name, None)
            self._m_replicas.set(len(self.replicas))
            self._m_membership.inc(kind="retire")
            self._note_transition(
                "retire", victim, worker_class=victim.worker_class
            )
            return victim

    def replica_shape(self) -> dict[str, int]:
        """Routable-set census by replica class (the ``gateway`` side of
        the autoscaler's shape; the supervisor's ``live_specs`` is the
        process side)."""
        shape: dict[str, int] = {}
        for r in self.replicas:
            shape[r.worker_class] = shape.get(r.worker_class, 0) + 1
        return shape

    def cached_spans(self) -> list[dict[str, Any]]:
        """Sync merged-trace snapshot (gateway ring + per-tick replica
        caches) — what incident sources capture without touching the
        network mid-incident. Each span is tagged with its ``source``
        tier."""
        out = [
            {**s, "source": "gateway"} for s in self.tracer.recent(None)
        ]
        # list() first: incident captures read this from an executor
        # thread while the telemetry loop mutates the cache on the loop
        for name, spans in list(self._replica_spans.items()):
            out.extend({**s, "source": name} for s in spans)
        out.sort(key=lambda s: s.get("startTime", 0.0), reverse=True)
        return out

    # -------------------------------------------------------------- routing
    def pick_replica(
        self,
        key: str,
        exclude: frozenset[str] = frozenset(),
        meta: dict[str, Any] | None = None,
    ) -> Replica | None:
        """Least-loaded routable replica; consistent-hash tie-break.

        Claims a breaker slot (``allow()``) on the winner — the caller
        MUST pair the pick with ``record_success``/``record_failure``.
        ``meta``, when given, is filled with routing attribution (panic
        mode, healthy count) for the ``gateway.route`` span.
        """
        pool = [r for r in self.replicas if r.name not in exclude]
        candidates = [r for r in pool if r.healthy]
        if meta is not None:
            meta["healthy"] = len(candidates)
        if not candidates and pool:
            # panic routing: EVERY replica failed its last probe. Probes
            # are advisory — one can time out against a loaded-but-alive
            # worker — and when the whole fleet looks down at once, the
            # probes being wrong is likelier than the fleet being dead.
            # Route across all of them; the per-replica breakers still
            # gate backends that are truly gone.
            candidates = pool
            self._m_panic.inc()
            if meta is not None:
                meta["panic"] = True
        if not candidates:
            return None
        chosen = None
        for group in self._class_preference(candidates):
            chosen = self._pick_admitted(group, key)
            if chosen is not None:
                break
        if chosen is None:
            return None
        if chosen.worker_class == REPLICA_CLASS_CPU and any(
            r.worker_class != REPLICA_CLASS_CPU for r in candidates
        ):
            # the device class was saturated (or breaker-refused): this
            # query degrades to a slower cpu-fallback answer, not a shed
            self._m_overflow.inc()
            if meta is not None:
                meta["overflow"] = True
        return chosen

    def _class_preference(self, candidates: list[Replica]) -> list[list[Replica]]:
        """Cost/latency-aware routing order: device-bound replicas carry
        traffic while any has headroom; cpu-fallback replicas absorb
        overflow only; a fully saturated fleet falls back to least-loaded
        across everything (queueing beats shedding)."""
        cpu = [r for r in candidates if r.worker_class == REPLICA_CLASS_CPU]
        device = [r for r in candidates if r.worker_class != REPLICA_CLASS_CPU]
        if not cpu or not device:
            return [candidates]
        thresh = max(1, self.config.cpu_overflow_inflight)
        under_dev = [r for r in device if r.inflight < thresh]
        under_cpu = [r for r in cpu if r.inflight < thresh]
        if under_dev:
            return [g for g in (under_dev, under_cpu, candidates) if g]
        if under_cpu:
            return [under_cpu, candidates]
        return [candidates]

    @staticmethod
    def _pick_admitted(group: list[Replica], key: str) -> Replica | None:
        """Least-loaded within the group, consistent-hash tie-break,
        first replica whose breaker admits the request."""
        if not group:
            return None
        low = min(r.inflight for r in group)
        tied = sorted(
            (r for r in group if r.inflight == low),
            key=lambda r: r.name,
        )
        # rotate the tie list by the sticky hash: same key -> same replica
        # while loads stay equal, different keys spread uniformly
        start = int(sticky_bucket(key) * len(tied)) % len(tied)
        for i in range(len(tied)):
            r = tied[(start + i) % len(tied)]
            try:
                r.breaker.allow()
            except CircuitOpenError:
                continue
            return r
        # every tied replica's breaker refused; try the rest by load
        rest = sorted(
            (r for r in group if r.inflight != low),
            key=lambda r: (r.inflight, r.name),
        )
        for r in rest:
            try:
                r.breaker.allow()
            except CircuitOpenError:
                continue
            return r
        return None

    async def _forward(
        self,
        replica: Replica,
        method: str,
        path: str,
        body: bytes | None,
        headers: dict[str, str],
    ) -> tuple[int, bytes, str]:
        """One proxied request, recorded as a ``gateway.proxy`` span on
        the request's trace id (upstream wall time = span duration).
        Returns (status, body, content_type); raises on transport
        failure. Replica accounting (inflight, breaker, counters) is the
        caller's job — retry logic needs to see the raw outcome."""
        replica.inflight += 1
        t0 = time.perf_counter()
        status: Any = "error"
        try:
            async with self._http().request(
                method, f"{replica.url}{path}", data=body, headers=headers
            ) as resp:
                payload = await resp.read()
                status = resp.status
                return (
                    resp.status,
                    payload,
                    resp.headers.get("Content-Type", "application/json"),
                )
        finally:
            replica.inflight -= 1
            self.tracer.record_span(
                "gateway.proxy",
                "gateway",
                time.perf_counter() - t0,
                trace_id=headers.get(TRACE_HEADER),
                replica=replica.name,
                path=path,
                upstream_status=status,
            )

    @staticmethod
    def _status_class(status: int) -> str:
        return f"{status // 100}xx"

    def _record_outcome(self, replica: Replica, status: int) -> None:
        self._m_requests.inc(
            replica=replica.name, status=self._status_class(status)
        )
        if status in RETRIABLE_STATUSES:
            # replica-side trouble: feeds the breaker like a transport
            # failure (a 503-shedding replica needs backing off from too)
            replica.breaker.record_failure()
        else:
            # 2xx obviously; 4xx too — the *replica* answered fine, the
            # client's request was bad. 4xx must not trip a breaker.
            replica.breaker.record_success()

    # --------------------------------------------------------------- routes
    async def handle_queries(self, request: web.Request) -> web.Response:
        t0 = time.perf_counter()
        resp: web.Response | None = None
        try:
            resp = await self._handle_queries_inner(request)
            return resp
        finally:
            self._m_latency.observe(
                time.perf_counter() - t0, endpoint="/queries.json"
            )
            # client-visible outcome (an escaping exception becomes
            # aiohttp's 500): the fleet-availability SLO's input
            status = resp.status if resp is not None else 500
            self._m_responses.inc(status=self._status_class(status))

    async def _handle_queries_inner(self, request: web.Request) -> web.Response:
        if (
            self.config.max_payload_bytes
            and request.content_length is not None
            and request.content_length > self.config.max_payload_bytes
        ):
            return web.json_response(
                {"message": "query payload too large"}, status=413
            )
        body = await request.read()
        # sticky key for the consistent-hash tie-break; a non-JSON body
        # still routes (the replica will 400 it properly)
        try:
            key = routing_key(json.loads(body), self.config.sticky_key_field)
        except (ValueError, TypeError):
            key = body.decode("utf-8", errors="replace")
        trace_id = request.headers.get(TRACE_HEADER) or mint_trace_id()
        headers = {
            "Content-Type": "application/json",
            TRACE_HEADER: trace_id,
        }
        self._inflight_requests += 1
        if self._inflight_requests > self._inflight_peak:
            self._inflight_peak = self._inflight_requests
        try:
            resp = await self._route_query(key, body, headers, trace_id)
        finally:
            self._inflight_requests -= 1
        resp.headers[TRACE_HEADER] = trace_id
        if resp.status >= 500:
            # the zero-5xx invariant (docs/fleet.md) just broke for a
            # real client: capture the fleet state while the evidence —
            # the dead replica's cached spans, the ring history — is
            # still warm
            self._trigger_incident(
                "fleet-5xx",
                {"status": resp.status, "traceId": trace_id},
            )
        if self._draining:
            # drain keeps ANSWERING: the listener is closed (new
            # connections refused at TCP), but a request arriving on an
            # established keep-alive connection is served — 503ing it
            # would be the 5xx the drain exists to avoid. Connection:
            # close winds the keep-alive down so the client reconnects
            # elsewhere and the drain converges.
            resp.force_close()
        return resp

    async def _route_query(
        self,
        key: str,
        body: bytes,
        headers: dict[str, str],
        trace_id: str,
    ) -> web.Response:
        with self.tracer.span(
            "gateway.route", kind="gateway", trace_id=trace_id
        ) as route_span:
            resp = await self._route_query_inner(
                key, body, headers, route_span
            )
            route_span.tags["status"] = resp.status
            return resp

    async def _route_query_inner(
        self,
        key: str,
        body: bytes,
        headers: dict[str, str],
        route_span: Any,
    ) -> web.Response:
        self.retry_budget.record_attempt()
        pick_meta: dict[str, Any] = {}
        first = self.pick_replica(key, meta=pick_meta)
        route_span.tags.update(pick_meta)
        if first is None:
            self._m_no_replica.inc()
            route_span.tags["shed"] = True
            return self._unavailable(
                "no healthy replica available", self.config.shed_retry_after_s
            )
        route_span.tags["replica"] = first.name
        route_span.tags["breaker"] = first.breaker.snapshot()["state"]
        failure: tuple[int, bytes, str] | None = None
        try:
            status, payload, ctype = await self._forward(
                first, "POST", "/queries.json", body, headers
            )
        except (aiohttp.ClientError, asyncio.TimeoutError) as exc:
            first.breaker.record_failure()
            self._m_requests.inc(replica=first.name, status="error")
            logger.warning("forward to %s failed: %s", first.name, exc)
        else:
            self._record_outcome(first, status)
            if status not in RETRIABLE_STATUSES:
                return web.Response(
                    body=payload, status=status, content_type=_bare(ctype)
                )
            failure = (status, payload, ctype)
        # one retry on a DIFFERENT replica — /queries.json is idempotent
        # (pure read), so re-dispatch cannot double-apply anything
        if self.retry_budget.try_spend():
            retry_meta: dict[str, Any] = {}
            second = self.pick_replica(
                key, exclude=frozenset((first.name,)), meta=retry_meta
            )
            if second is not None:
                self._m_retries.inc()
                route_span.tags["retried"] = True
                route_span.tags["retry_replica"] = second.name
                if retry_meta.get("panic"):
                    route_span.tags["panic"] = True
                try:
                    status, payload, ctype = await self._forward(
                        second, "POST", "/queries.json", body, headers
                    )
                except (aiohttp.ClientError, asyncio.TimeoutError) as exc:
                    second.breaker.record_failure()
                    self._m_requests.inc(replica=second.name, status="error")
                    logger.warning(
                        "retry forward to %s failed: %s", second.name, exc
                    )
                else:
                    self._record_outcome(second, status)
                    return web.Response(
                        body=payload, status=status, content_type=_bare(ctype)
                    )
        if failure is not None:
            # relay the replica's own 5xx rather than masking it
            status, payload, ctype = failure
            return web.Response(
                body=payload, status=status, content_type=_bare(ctype)
            )
        return self._unavailable(
            "replica unavailable and retry failed",
            self.config.shed_retry_after_s,
        )

    async def _proxy_admin(
        self, request: web.Request, method: str, path: str
    ) -> web.Response:
        """Single-dispatch proxy for the non-idempotent rollout admin
        surface: exactly ONE replica sees the request (the registry is
        the fan-out — every other replica adopts the state change via
        its sync loop). Never retried: a promote that timed out may
        still have landed."""
        replica = self.pick_replica(path)
        if replica is None:
            return self._unavailable(
                "no healthy replica available", self.config.shed_retry_after_s
            )
        body = await request.read() if request.can_read_body else None
        trace_id = request.headers.get(TRACE_HEADER) or mint_trace_id()
        try:
            status, payload, ctype = await self._forward(
                replica,
                method,
                path,
                body,
                {"Content-Type": "application/json", TRACE_HEADER: trace_id},
            )
        except (aiohttp.ClientError, asyncio.TimeoutError) as exc:
            replica.breaker.record_failure()
            self._m_requests.inc(replica=replica.name, status="error")
            return self._unavailable(
                f"replica {replica.name} unreachable: {exc}",
                self.config.shed_retry_after_s,
            )
        self._record_outcome(replica, status)
        return web.Response(body=payload, status=status, content_type=_bare(ctype))

    async def handle_models(self, request: web.Request) -> web.Response:
        return await self._proxy_admin(request, "GET", "/models")

    async def handle_models_post(self, request: web.Request) -> web.Response:
        action = request.match_info["action"]
        if action not in ("candidate", "promote", "rollback"):
            return web.json_response({"message": "unknown action"}, status=404)
        return await self._proxy_admin(request, "POST", f"/models/{action}")

    async def handle_profile_capture(self, request: web.Request) -> web.Response:
        """Fan a device capture out to exactly ONE replica (the
        single-flight lives server-side; a broadcast would trip every
        replica's 409 rail at once). ``?ms=`` and friends pass through."""
        path = "/profile/capture"
        if request.query_string:
            path += "?" + request.query_string
        return await self._proxy_admin(request, "POST", path)

    async def handle_profile_stacks(self, request: web.Request) -> web.Response:
        """The GATEWAY's own host stacks (folded; ``?format=json`` for
        the structured view) — replica stacks live on each replica's own
        /profile/stacks."""
        if request.query.get("format") == "json":
            body = self.sampler.snapshot()
            body["hotspots"] = self.sampler.hotspots()
            return web.json_response(body)
        return web.Response(
            text=self.sampler.folded(), content_type="text/plain"
        )

    async def handle_metrics(self, request: web.Request) -> web.Response:
        """Federated fleet scrape: every reachable replica's /metrics
        merged (counters summed, histogram buckets added) plus the
        gateway's own pio_fleet_* instruments. An OpenMetrics-negotiated
        scrape (Accept or ``?exemplars=1``) federates the replicas'
        exemplar-decorated expositions and carries the clauses through
        the merge — a federated p99 exemplar still resolves to a trace
        id, which ``/traces/recent?trace_id=`` assembles cross-tier."""
        exemplars = _wants_exemplars(request)
        text = await self._federate(exemplars=exemplars)
        return web.Response(
            text=text,
            headers={
                "Content-Type": (
                    OPENMETRICS_CONTENT_TYPE
                    if exemplars
                    else PROMETHEUS_CONTENT_TYPE
                )
            },
        )

    async def _federate(self, exemplars: bool = False) -> str:
        """Fetch + merge the fleet's expositions; refreshes the cached
        federated parse the fleet SLO sources read."""
        texts = [self.metrics.render_prometheus(exemplars=exemplars)]
        results = await asyncio.gather(
            *(self._fetch_metrics(r, exemplars=exemplars) for r in self.replicas)
        )
        texts.extend(t for t in results if t is not None)
        merged = federate_metrics(texts, exemplars=exemplars)
        self._last_federated = parse_prometheus(merged)
        return merged

    async def _fetch_metrics(
        self, replica: Replica, exemplars: bool = False
    ) -> str | None:
        suffix = "?exemplars=1" if exemplars else ""
        try:
            # the telemetry plane's own traffic: this fetch FEEDS
            # federation/the ring; a span per scrape per replica would
            # flood the span ring with the instrument's own data
            # pio-lint: disable=fleet-unattributed-proxy -- telemetry plane fetch
            async with self._http().get(
                f"{replica.url}/metrics{suffix}",
                timeout=aiohttp.ClientTimeout(total=self.config.probe_timeout_s),
            ) as resp:
                if resp.status != 200:
                    return None
                return await resp.text()
        except (aiohttp.ClientError, asyncio.TimeoutError):
            return None

    # ----------------------------------------------------- trace fan-in
    async def _fetch_traces(self, replica: Replica) -> None:
        """Refresh one replica's span cache. Failures keep the stale
        cache — a SIGKILLed replica's final spans are incident evidence,
        not staleness."""
        try:
            # fan-in that fills the span cache; tracing the trace fetch
            # would recurse the instrument into its own data
            # pio-lint: disable=fleet-unattributed-proxy -- trace fan-in fetch
            async with self._http().get(
                f"{replica.url}/traces/recent?limit={TRACE_FANIN_LIMIT}",
                timeout=aiohttp.ClientTimeout(total=self.config.probe_timeout_s),
            ) as resp:
                if resp.status != 200:
                    return
                data = await resp.json()
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError):
            return
        spans = data.get("spans")
        if isinstance(spans, list):
            self._replica_spans[replica.name] = spans

    async def _fetch_peer_traces(self, peer_url: str) -> None:
        """Refresh one gateway peer's span cache from its LOCAL view
        (``?local=1`` stops the fan-in recursing peer->peer->peer).
        Failures keep the stale cache: a lost peer's final spans are the
        gateway-peer-loss evidence, not staleness."""
        try:
            # peer fan-in fetch, same health-plane exemption as _fetch_traces
            # pio-lint: disable=fleet-unattributed-proxy -- gateway-peer trace fan-in
            async with self._http().get(
                f"{peer_url}/traces/recent"
                f"?limit={TRACE_FANIN_LIMIT}&local=1",
                timeout=aiohttp.ClientTimeout(total=self.config.probe_timeout_s),
            ) as resp:
                if resp.status != 200:
                    return
                data = await resp.json()
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError):
            return
        spans = data.get("spans")
        if isinstance(spans, list):
            self._peer_spans[peer_url] = spans

    def _peer_cached_spans(self) -> list[dict[str, Any]]:
        out: list[dict[str, Any]] = []
        for url, spans in list(self._peer_spans.items()):
            out.extend(
                {**s, "gatewayPeer": url} if "gatewayPeer" not in s else s
                for s in spans
            )
        return out

    async def merged_recent(
        self,
        limit: int = 100,
        trace_id: str | None = None,
        peers: bool = True,
    ) -> list[dict[str, Any]]:
        """The fan-in merged trace view: gateway ring + every replica's,
        refreshed live from healthy replicas (dead ones serve from the
        telemetry tick's cache), plus — in a multi-gateway tier — every
        peer gateway's local view, so one ``/traces/recent`` answers for
        the whole tier no matter which gateway the balancer picked. With
        ``trace_id``, the assembled cross-tier waterfall: that trace's
        spans only, oldest first."""
        fetches = [self._fetch_traces(r) for r in self.replicas if r.healthy]
        if peers:
            fetches += [
                self._fetch_peer_traces(u) for u in self.config.peer_urls
            ]
        await asyncio.gather(*fetches)
        merged = self.cached_spans()
        if peers and self.config.peer_urls:
            # peers also fan in from the shared replica set; drop spans
            # this gateway already holds (same trace id + name + start)
            seen = {
                (s.get("traceId"), s.get("name"), s.get("startTime"))
                for s in merged
            }
            merged += [
                s
                for s in self._peer_cached_spans()
                if (s.get("traceId"), s.get("name"), s.get("startTime"))
                not in seen
            ]
            merged.sort(key=lambda s: s.get("startTime", 0.0), reverse=True)
        if trace_id is not None:
            waterfall = [s for s in merged if s.get("traceId") == trace_id]
            waterfall.sort(key=lambda s: s.get("startTime", 0.0))
            return waterfall
        return merged[: max(0, limit)]

    async def handle_traces(self, request: web.Request) -> web.Response:
        try:
            limit = int(request.query.get("limit", 100))
        except ValueError:
            return web.json_response(
                {"message": "limit must be an integer"}, status=400
            )
        trace_id = request.query.get("trace_id") or None
        local = request.query.get("local") not in (None, "", "0")
        spans = await self.merged_recent(
            limit=limit, trace_id=trace_id, peers=not local
        )
        return web.json_response({"spans": spans})

    # ----------------------------------------------------- telemetry ring
    def fleet_snapshot(self) -> dict[str, Any]:
        """One telemetry-ring record: per-replica state + federated
        counters + SLO burn — the queue-depth/burn/utilization history
        the ROADMAP-2 autoscaler will read."""
        fed = self._last_federated
        counters = {
            key: sum(v for _labels, v in fed.get(name, ()))
            for key, name in (
                ("requests", "pio_fleet_requests_total"),
                ("retries", "pio_fleet_retries_total"),
                ("no_replica", "pio_fleet_no_replica_total"),
                ("panic_picks", "pio_fleet_panic_picks_total"),
                ("overflow_picks", "pio_fleet_overflow_picks_total"),
                # the workers' own admission-control sheds, federated
                ("load_shed", "pio_load_shed_total"),
            )
        }
        counters["errors_5xx"] = sum(
            v
            for labels, v in fed.get("pio_fleet_requests_total", ())
            if labels.get("status") in ("5xx", "error")
        )
        inflight_now = sum(r.inflight for r in self.replicas)
        gauges = {
            "queue_depth": sum(
                v for _labels, v in fed.get("pio_queue_depth", ())
            ),
            "inflight": inflight_now,
            # peak concurrency since the previous TELEMETRY TICK — the
            # alias-proof pressure signal the autoscaler reads. This
            # getter is side-effect-free: incident captures also call it
            # (the 'fleet' evidence source), and a capture mid-spike must
            # not consume the high-water mark out from under the ring
            "inflight_peak": max(self._inflight_peak, inflight_now),
        }
        slo: dict[str, Any] = {}
        for report in self.slo.evaluate():
            slo[report["name"]] = {
                "alerting": report["alerting"],
                "burn": {
                    str(int(w["window_s"])): w["burn_rate"]
                    for w in report["windows"]
                },
            }
        return {
            "kind": "fleet",
            "gateway": self.config.gateway_id,
            "replicas": {
                r.name: {
                    "healthy": r.healthy,
                    "ever_ready": r.ever_ready,
                    "inflight": r.inflight,
                    "class": r.worker_class,
                    "breaker": r.breaker.snapshot()["state"],
                }
                for r in self.replicas
            },
            "shape": self.replica_shape(),
            "counters": counters,
            "gauges": gauges,
            "slo": slo,
        }

    async def _telemetry_tick(self) -> None:
        await self._federate()
        await asyncio.gather(
            *(self._fetch_traces(r) for r in self.replicas if r.healthy)
        )
        self.slo.tick()
        record = self.fleet_snapshot()
        # SLO alert *transitions* trigger the flight recorder (level
        # triggers would re-fire every tick of a long burn; the rate
        # limiter bounds it anyway, but the transition is the incident)
        for name, state in record["slo"].items():
            was = self._slo_alerting.get(name, False)
            now_alerting = bool(state["alerting"])
            self._slo_alerting[name] = now_alerting
            if now_alerting and not was:
                self._trigger_incident("slo-alert", {"slo": name, **state})
        if self.telemetry is not None:
            # ring append is locked file I/O; the ring is thread-safe, so
            # hand it off rather than stall every in-flight proxy
            await asyncio.get_running_loop().run_in_executor(
                None, self.telemetry.append, record
            )
            self._m_telemetry_snapshots.inc()
        # ONLY the telemetry tick consumes the inflight high-water mark
        # (reset to the current level so a sustained plateau stays
        # visible on the next record)
        self._inflight_peak = self._inflight_requests

    async def _telemetry_loop(self) -> None:
        interval = self.config.telemetry_interval_s
        if interval is None:
            interval = self.config.probe_interval_s
        if interval <= 0:
            return
        while True:
            try:
                await self._telemetry_tick()
            except asyncio.CancelledError:
                raise
            except Exception:
                self._m_telemetry_errors.inc()
                logger.exception("telemetry tick failed")
            await asyncio.sleep(interval)

    async def handle_telemetry(self, request: web.Request) -> web.Response:
        if self.telemetry is None:
            return web.json_response(
                {"message": "no telemetry ring attached"}, status=404
            )
        try:
            seconds = float(request.query.get("s", 600))
        except ValueError:
            return web.json_response(
                {"message": "s must be a number"}, status=400
            )
        # window() replays on-disk segments (open + json decode); keep the
        # history endpoint off the proxy loop
        records = await asyncio.get_running_loop().run_in_executor(
            None, self.telemetry.window, seconds
        )
        return web.json_response(
            {"windowS": seconds, "records": records}
        )

    async def handle_slo(self, request: web.Request) -> web.Response:
        local = request.query.get("local") not in (None, "", "0")
        if local or not self.config.peer_urls:
            return slo_response(self.slo)
        # multi-gateway tier: each peer rates the traffic the balancer
        # sent IT; the fan-in view answers for the tier from any member.
        # A peer that cannot answer is reported, not hidden — a silent
        # gap here is exactly the balancer-misroute blind spot.
        report = self.slo.report()
        report["gateway"] = self.config.gateway_id
        peers: dict[str, Any] = {}
        for url in self.config.peer_urls:
            try:
                # peer fan-in fetch, same health-plane exemption as the
                # trace fan-in: an SLO scrape is not client traffic
                # pio-lint: disable=fleet-unattributed-proxy -- gateway-peer /slo fan-in
                async with self._http().get(
                    f"{url}/slo?local=1",
                    timeout=aiohttp.ClientTimeout(
                        total=self.config.probe_timeout_s
                    ),
                ) as resp:
                    if resp.status == 200:
                        peers[url] = await resp.json()
                    else:
                        peers[url] = {"error": f"status {resp.status}"}
            except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as exc:
                peers[url] = {"error": type(exc).__name__}
        report["peers"] = peers
        return web.json_response(report)

    async def handle_healthz(self, request: web.Request) -> web.Response:
        healthy = sum(1 for r in self.replicas if r.healthy)
        ready = healthy > 0 and not self._draining
        return web.json_response(
            {
                "ready": ready,
                "draining": self._draining,
                "replicasHealthy": healthy,
                "replicasTotal": len(self.replicas),
            },
            status=200 if ready else 503,
        )

    async def handle_status(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "status": "alive",
                "role": "gateway",
                "draining": self._draining,
                "replicas": [r.snapshot() for r in self.replicas],
                "retryBudgetTokens": self.retry_budget.tokens,
            }
        )

    async def handle_stop(self, request: web.Request) -> web.Response:
        self._stop_event.set()
        return web.json_response({"message": "Stopping."})

    @staticmethod
    def _unavailable(message: str, retry_after_s: float) -> web.Response:
        return web.json_response(
            {"message": message},
            status=503,
            headers={"Retry-After": str(max(1, round(retry_after_s)))},
        )

    # ---------------------------------------------------------------- probes
    async def _probe_loop(self) -> None:
        while True:
            try:
                await asyncio.gather(
                    *(self._probe(r) for r in self.replicas)
                )
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("probe pass failed")
            await asyncio.sleep(self.config.probe_interval_s)

    async def _probe(self, replica: Replica) -> None:
        try:
            # probe GETs are the health plane's own traffic (one per
            # replica per second); their OUTCOME transitions route
            # through _note_transition below, which attributes this fn
            async with self._http().get(
                f"{replica.url}/healthz",
                timeout=aiohttp.ClientTimeout(total=self.config.probe_timeout_s),
            ) as resp:
                ok = resp.status == 200
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
            ok = False
        if ok:
            if not replica.healthy:
                replica.healthy = True
                if replica.ever_ready:
                    self._note_transition("readmit", replica)
                    logger.info("replica %s readmitted", replica.name)
                else:
                    self._note_transition("up", replica)
                    logger.info("replica %s up", replica.name)
            replica.ever_ready = True
        elif replica.healthy:
            replica.healthy = False
            if replica.ever_ready:
                self._note_transition("eject", replica)
                logger.warning(
                    "replica %s ejected (failed /healthz)", replica.name
                )
            else:
                logger.info("replica %s not ready yet", replica.name)

    # ------------------------------------------------------------- lifecycle
    def make_app(self) -> web.Application:
        app = web.Application()
        app.add_routes(
            [
                web.get("/", self.handle_status),
                web.get("/healthz", self.handle_healthz),
                web.get("/metrics", self.handle_metrics),
                web.get("/slo", self.handle_slo),
                web.get("/traces/recent", self.handle_traces),
                web.get("/telemetry/window", self.handle_telemetry),
                web.post("/queries.json", self.handle_queries),
                web.get("/models", self.handle_models),
                web.post("/models/{action}", self.handle_models_post),
                web.post("/profile/capture", self.handle_profile_capture),
                web.get("/profile/stacks", self.handle_profile_stacks),
                web.post("/stop", self.handle_stop),
            ]
        )

        async def _start_loops(app: web.Application) -> None:
            self.sampler.start()
            self._probe_task = asyncio.ensure_future(self._probe_loop())
            self._telemetry_task = asyncio.ensure_future(
                self._telemetry_loop()
            )

        async def _cleanup(app: web.Application) -> None:
            self.sampler.stop()
            tasks = [self._probe_task, self._telemetry_task]
            self._probe_task = None
            self._telemetry_task = None
            for task in tasks:
                if task is not None:
                    task.cancel()
            await asyncio.gather(
                *(t for t in tasks if t is not None), return_exceptions=True
            )
            if self._session is not None and not self._session.closed:
                await self._session.close()
            self._session = None

        app.on_startup.append(_start_loops)
        app.on_cleanup.append(_cleanup)
        return app

    async def start(self) -> None:
        self._runner = web.AppRunner(self.make_app(), access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.config.ip, self.config.port)
        await site.start()
        logger.info(
            "fleet gateway on %s:%d (%d replicas)",
            self.config.ip,
            self.config.port,
            len(self.replicas),
        )

    async def drain(self) -> None:
        """Stop accepting, answer in-flight, then return. Idempotent."""
        if self._draining:
            return
        self._draining = True
        logger.info(
            "gateway drain: listener closing, %d in flight",
            self._inflight_requests,
        )
        if self._runner is not None:
            for site in list(self._runner.sites):
                try:
                    await site.stop()
                except Exception:
                    pass
        deadline = time.monotonic() + max(0.0, self.config.drain_grace_s)
        while self._inflight_requests > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        if self._inflight_requests:
            logger.warning(
                "gateway drain grace expired with %d requests in flight",
                self._inflight_requests,
            )

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    async def run_until_stopped(self) -> None:
        await self.start()
        await self._stop_event.wait()
        await self.drain()
        await self.stop()

    def begin_drain(self) -> None:
        """Signal-handler entry: drain, then release run_until_stopped.
        The task is held on its own attribute — the event loop keeps only
        a weak reference, and a GC'd drain task would leave SIGTERM
        hanging forever."""

        async def _go() -> None:
            await self.drain()
            self._stop_event.set()

        self._drain_task = asyncio.ensure_future(_go())


def _bare(content_type: str) -> str:
    """aiohttp's Response(content_type=...) rejects parameters; strip
    ``; charset=...`` from a proxied upstream header."""
    return content_type.split(";", 1)[0].strip() or "application/json"


class GatewayGroup:
    """The autoscaler's view of a multi-gateway tier: membership changes
    (add/retire) fan out to EVERY gateway — all peers route over the
    same replica set, so a scale-out a single gateway learned about
    would silently halve itself behind the balancer. Everything else
    (ring reads, shape) delegates to the primary. Shared-nothing
    otherwise: peers never exchange routing state."""

    def __init__(self, gateways: list[Gateway]):
        if not gateways:
            raise ValueError("GatewayGroup needs at least one gateway")
        self.gateways = list(gateways)
        self.primary = gateways[0]

    def add_replica(self, url: str, worker_class: str = "device") -> None:
        for gw in self.gateways:
            gw.add_replica(url, worker_class)

    def retire_replica(self, url_or_name: str) -> None:
        for gw in self.gateways:
            gw.retire_replica(url_or_name)

    def __getattr__(self, name: str) -> Any:
        return getattr(self.primary, name)


__all__ = [
    "Gateway",
    "GatewayConfig",
    "GatewayGroup",
    "Replica",
    "RETRIABLE_STATUSES",
]
