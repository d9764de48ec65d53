"""Prometheus-style metrics for the in-process server.

The TPU-native analog of Triton's GPU metrics endpoint (the reference's
MetricsManager scrapes ``nv_gpu_utilization`` / ``nv_gpu_memory_*`` from the
server's /metrics — reference metrics_manager.h:44-91), grown into the full
observability surface:

- per-model counters (success/failure/inference counts, success AND failure
  cumulative durations, the per-phase queue/compute_input/compute_infer/
  compute_output breakdown the statistics extension measures),
- per-model latency **histograms** (request duration, queue time) and the
  batch-size distribution,
- live gauges (batcher queue depth per model, in-flight requests, draining),
- resilience counters (requests shed with retryable 503s, drain events) and
  — when clients in this process attach a :class:`ResilienceMetricsObserver`
  to their retry policy / circuit breaker — client-side retry counters and
  per-endpoint circuit state,
- per-TPU-device HBM usage via ``device.memory_stats()`` where the PJRT
  runtime exposes it,
- the continuous-batching LM engine's series (serve/lm, bound into this
  registry at add_model time): ``ctpu_lm_kv_blocks_{used,free}`` (paged
  KV pool occupancy), ``ctpu_lm_lanes`` / ``ctpu_lm_active_lanes``
  (autoscaled decode lane count vs lanes streaming),
  ``ctpu_lm_tokens_total`` and ``ctpu_lm_prefill_chunks_total``, plus
  the KV **prefix cache** and **preemption** series (:data:`LM_PREFIX_HELP`
  below): ``ctpu_lm_prefix_{hits,misses,evictions}_total`` (blocks
  adopted / shareable-but-cold / evicted under pool pressure),
  ``ctpu_lm_prefix_cached_blocks``, the prefill-compute accounting pair
  ``ctpu_lm_prefill_tokens_total`` / ``ctpu_lm_prefill_tokens_saved_total``
  (the perf CLI's ``prefix_hit_pct`` numerators), and
  ``ctpu_lm_preemptions_total`` / ``ctpu_lm_swapped_blocks`` (lanes
  swapped to the host store under priority pressure), and the
  **speculative decoding** series (:data:`LM_SPEC_HELP`):
  ``ctpu_lm_spec_{proposed,accepted,rejected}_tokens_total`` +
  ``ctpu_lm_spec_acceptance_rate`` — draft/verify outcomes when a model
  enables ``speculative={...}``.

Every label value passes through :func:`escape_label`: the exposition format
reserves ``\\``, ``"`` and newline inside quoted label values, and a model
name containing any of them must not corrupt the whole scrape.
"""

import bisect
import threading
import time

from client_tpu.utils import escape_label  # noqa: F401  (canonical re-export)

# Request/queue duration buckets (microseconds) and batch-size buckets.
DURATION_BUCKETS_US = (
    50, 100, 250, 500, 1000, 2500, 5000, 10000, 25000, 50000,
    100000, 250000, 500000, 1000000, 2500000, 10000000,
)
BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)

# CircuitBreaker state -> gauge value (closed/half-open/open).
CIRCUIT_STATE_VALUES = {"closed": 0, "half-open": 1, "open": 2}

# Endpoint health state -> gauge value (client_tpu.utils server states).
ENDPOINT_STATE_VALUES = {"READY": 0, "NOT_READY": 1, "UNREACHABLE": 2}

# Endpoint membership phase -> gauge value (client_tpu.balance.pool).
ENDPOINT_PHASE_VALUES = {"active": 0, "probation": 1, "retiring": 2}

# LM prefix-cache + preemption series (written by serve/lm/prefix.py and
# serve/lm/engine.py into whichever registry the engine is bound to; the
# help text lives here so the catalog has one source of truth).
LM_PREFIX_HELP = {
    "ctpu_lm_prefix_hits_total":
        "Prompt-prefix KV blocks adopted by reference from the cache",
    "ctpu_lm_prefix_misses_total":
        "Shareable full prompt blocks that had no cached match",
    "ctpu_lm_prefix_evictions_total":
        "Cached prefix blocks evicted under pool pressure",
    "ctpu_lm_prefix_cached_blocks":
        "KV blocks currently held warm by the prefix cache",
    "ctpu_lm_prefill_tokens_total":
        "Prompt tokens actually computed by prefill chunks",
    "ctpu_lm_prefill_tokens_saved_total":
        "Prompt tokens skipped via prefix-cache adoption",
    "ctpu_lm_preemptions_total":
        "Decode lanes preempted (KV swapped out) under priority pressure",
    "ctpu_lm_swapped_blocks":
        "KV blocks currently parked in the host-side swap store",
}

# Speculative-decoding series (written by serve/lm/engine.py's verify
# pass when a model enables ``speculative={...}``; serve/lm/spec.py owns
# the drafter/adaptive-k policy).  Acceptance rate is the cumulative
# accepted/proposed ratio — the per-lane adaptive controller uses its
# own rolling window.
LM_SPEC_HELP = {
    "ctpu_lm_spec_proposed_tokens_total":
        "Draft tokens proposed to the speculative verify tick",
    "ctpu_lm_spec_accepted_tokens_total":
        "Draft tokens the verify tick accepted (target-model-exact)",
    "ctpu_lm_spec_rejected_tokens_total":
        "Draft tokens the verify tick rejected (KV rewound, not leaked)",
    "ctpu_lm_spec_acceptance_rate":
        "Cumulative speculative acceptance rate (accepted / proposed)",
}

# SLO watchdog + flight recorder series (written by serve/slo.py and
# serve/flight.py into the engine registry; one help catalog so
# /metrics, README and tests agree).
SLO_HELP = {
    "ctpu_slo_p50_ms":
        "Windowed p50 request latency per model/tenant (sketch quantile)",
    "ctpu_slo_p95_ms":
        "Windowed p95 request latency per model/tenant (sketch quantile)",
    "ctpu_slo_p99_ms":
        "Windowed p99 request latency per model/tenant (sketch quantile)",
    "ctpu_slo_error_rate":
        "Windowed server-fault rate per model/tenant (5xx/transport only)",
    "ctpu_slo_breaches_total":
        "SLO objective breaches (by model/tenant and objective kind)",
    "ctpu_flight_dumps_total":
        "Flight-recorder dumps written (by trigger reason)",
}

# Fleet cache-tier series (written by serve/fleet.py and the fleet hooks
# in serve/lm/engine.py + model_runtime into whichever registry the tier
# is bound to; one help catalog so /metrics, README and tests agree).
FLEET_HELP = {
    "ctpu_fleet_peer_hits_total":
        "Peer lookups answered with content (by op: cache/prefix)",
    "ctpu_fleet_peer_misses_total":
        "Peer lookups every reachable peer missed (by op)",
    "ctpu_fleet_peer_errors_total":
        "Peer RPCs that failed or timed out (circuit strikes)",
    "ctpu_fleet_peer_skips_total":
        "Peer lookups skipped behind an open per-peer circuit",
    "ctpu_fleet_prefix_blocks_total":
        "KV prefix blocks installed from a peer replica's cache tier",
    "ctpu_fleet_prefix_tokens_saved_total":
        "Prefill tokens skipped via peer-fetched KV prefix blocks",
    "ctpu_fleet_cache_hits_total":
        "Unary responses served from a peer replica's response cache",
    "ctpu_fleet_store_blocks":
        "KV blocks exported into this replica's host-side fleet store",
    "ctpu_fleet_gossip_rounds_total":
        "Fleet gossip rounds pushed (tenant counters + digest summaries)",
    "ctpu_fleet_sessions_migrated_total":
        "Parked LM streams exported to the fleet tier at planned retire",
    "ctpu_fleet_seq_snapshots_total":
        "Durable sequence snapshots pushed to peer replicas",
    "ctpu_fleet_seq_resumes_total":
        "Sequences resumed from a fleet-replicated snapshot",
    "ctpu_fleet_seq_stale_total":
        "Stale sequence snapshots rejected by the replicated store",
    "ctpu_fleet_seq_heals_total":
        "Skips-ahead gaps healed by re-looking up a fresher snapshot",
    "ctpu_fleet_replicated_items_total":
        "Anti-entropy items proactively pushed to peers (by kind)",
    "ctpu_fleet_replicated_bytes_total":
        "Anti-entropy payload bytes proactively pushed to peers",
    "ctpu_fleet_pressure_queue_depth":
        "Gossiped per-replica queued+inflight work (autoscaling signal)",
    "ctpu_fleet_pressure_prefix":
        "Gossiped per-replica prefix-affinity pressure (hot chains held)",
    "ctpu_fleet_seq_quorum_acks_total":
        "Durable sequence steps acked with write quorum satisfied",
    "ctpu_fleet_seq_quorum_refusals_total":
        "Durable sequence steps refused (503) for unreachable quorum",
}

# Continuous-profiler series (written by serve/prof.py's PhaseProfiler
# into whichever registry the profiler is bound to; engine label is the
# profiler name — "serve" for the unary engine, "lm" for an LM
# scheduler, "perf_client" for the perf harness's client-side splits).
PROF_HELP = {
    "ctpu_prof_ticks_total":
        "Profiler ticks committed (by engine and tick kind)",
    "ctpu_prof_phase_seconds_total":
        "Cumulative seconds attributed to each profiled phase",
    "ctpu_prof_mfu_pct":
        "Model FLOP utilization over measured device time (vs the "
        "chip's published bf16 peak; absent off-TPU)",
    "ctpu_prof_compute_share_pct":
        "Share of measured device time attributed to each model",
    "ctpu_prof_host_pauses_total":
        "Pauses of the interpreter the profiler's pulse timed (a wake-up "
        "prof.PAUSE_S late: the GIL was held, or the process had no CPU)",
    "ctpu_prof_host_pause_seconds_total":
        "Seconds of those pauses",
    "ctpu_prof_stalls_total":
        "Stall records (by engine and cause: upload, call, compile, "
        "host_pause, device, host)",
}

# Autoscaler control-loop series (written by serve/autoscale.py into the
# registry it is constructed with).
AUTOSCALE_HELP = {
    "ctpu_autoscale_scale_ups_total":
        "Autoscaler scale-up actions taken (replicas spawned)",
    "ctpu_autoscale_scale_downs_total":
        "Autoscaler scale-down actions taken (replicas drained+retired)",
    "ctpu_autoscale_flap_suppressed_total":
        "Autoscaler decisions suppressed by cooldown/hysteresis",
    "ctpu_autoscale_replicas":
        "Current replica count the autoscaler is steering",
}


def format_labels(labels):
    """{'model': 'm'} -> '{model="m"}' with every value escaped."""
    if not labels:
        return ""
    inner = ",".join(
        f'{k}="{escape_label(v)}"' for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


class Histogram:
    """Fixed-bucket histogram (Prometheus semantics: cumulative buckets at
    render time, plus sum and count).  Not internally locked — callers
    (ModelStats) guard observations with their own lock."""

    __slots__ = ("buckets", "counts", "sum", "count")

    def __init__(self, buckets=DURATION_BUCKETS_US):
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)  # +Inf tail
        self.sum = 0.0
        self.count = 0

    def observe(self, value):
        self.counts[bisect.bisect_left(self.buckets, value)] += 1
        self.sum += value
        self.count += 1

    def snapshot(self):
        """(bucket_bounds, cumulative_counts, sum, count)."""
        cumulative = []
        total = 0
        for c in self.counts:
            total += c
            cumulative.append(total)
        return self.buckets, cumulative, self.sum, self.count


class Registry:
    """Thread-safe counter/gauge registry rendering to exposition format.

    One instance per engine holds server-side series (sheds, drain); the
    module-level :data:`RESILIENCE` registry holds client-side series fed
    by :class:`ResilienceMetricsObserver` so in-process clients' retry and
    circuit activity is scrapeable from the same /metrics payload.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._families = {}  # name -> {"type","help","samples":{labels:v}}

    def _family(self, name, type_, help_):
        fam = self._families.get(name)
        if fam is None:
            fam = {"type": type_, "help": help_, "samples": {}}
            self._families[name] = fam
        return fam

    def inc(self, name, labels=None, value=1, help_=""):
        key = format_labels(labels)
        with self._lock:
            samples = self._family(name, "counter", help_)["samples"]
            samples[key] = samples.get(key, 0) + value

    def set(self, name, labels=None, value=0.0, help_=""):
        key = format_labels(labels)
        with self._lock:
            self._family(name, "gauge", help_)["samples"][key] = value

    def get(self, name, labels=None):
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                return None
            return fam["samples"].get(format_labels(labels))

    def remove(self, name, labels=None):
        """Drop one labeled sample (gauges for departed label values —
        e.g. an evicted endpoint's phase/state — must not sit on /metrics
        at their last value forever, nor accumulate without bound under
        membership churn)."""
        key = format_labels(labels)
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                fam["samples"].pop(key, None)

    def render_into(self, lines):
        with self._lock:
            for name in sorted(self._families):
                fam = self._families[name]
                lines.append(f"# HELP {name} {fam['help'] or name}")
                lines.append(f"# TYPE {name} {fam['type']}")
                for labels, value in sorted(fam["samples"].items()):
                    lines.append(f"{name}{labels} {_fmt(value)}")


# Client-side resilience series (retries, circuit state) for clients
# living in the same process as the server — the hermetic/in-process
# deployment this framework's fake-server role serves.
RESILIENCE = Registry()


class ResilienceMetricsObserver:
    """Adapter feeding resilience events into a metrics registry.

    Attach one instance per endpoint as BOTH the retry-policy observer and
    the circuit-breaker observer::

        obs = ResilienceMetricsObserver("127.0.0.1:8000")
        breaker = CircuitBreaker(observer=obs)
        policy = RetryPolicy(circuit_breaker=breaker, observer=obs)
    """

    def __init__(self, endpoint, registry=None):
        self.endpoint = endpoint
        self.registry = registry if registry is not None else RESILIENCE
        self.registry.set(
            "ctpu_client_circuit_state", {"endpoint": endpoint}, 0,
            help_="Circuit breaker state per endpoint "
                  "(0=closed, 1=half-open, 2=open)",
        )

    # retry-policy hooks -----------------------------------------------------

    def on_backoff(self, attempt, delay_s, exc):
        self.registry.inc(
            "ctpu_client_retries_total", {"endpoint": self.endpoint},
            help_="Client retry attempts (one per backoff sleep)",
        )

    def on_giveup(self, attempt, exc):
        self.registry.inc(
            "ctpu_client_request_failures_total",
            {"endpoint": self.endpoint},
            help_="Client calls that exhausted their retry policy",
        )

    def on_success(self, attempt):
        pass

    # circuit-breaker hook ---------------------------------------------------

    def on_state_change(self, old, new):
        self.registry.set(
            "ctpu_client_circuit_state", {"endpoint": self.endpoint},
            CIRCUIT_STATE_VALUES.get(new, -1),
            help_="Circuit breaker state per endpoint "
                  "(0=closed, 1=half-open, 2=open)",
        )
        self.registry.inc(
            "ctpu_client_circuit_transitions_total",
            {"endpoint": self.endpoint, "to": new},
            help_="Circuit breaker state transitions",
        )


class BalancerMetricsObserver:
    """Adapter feeding replica-set routing events into a metrics registry.

    Attach one instance as the ``observer`` of a
    ``client_tpu.balance.EndpointPool``::

        obs = BalancerMetricsObserver()
        pool = EndpointPool(urls, observer=obs)

    Series (all per-endpoint): ``ctpu_client_routed_total`` (requests the
    balancer sent to each replica — the convergence proof when replicas
    die), ``ctpu_client_failovers_total`` (attempts that failed retryably
    on a replica and rotated off it), ``ctpu_client_endpoint_state``
    (the pool's READY/NOT_READY/UNREACHABLE health view),
    ``ctpu_client_endpoint_phase`` (membership lifecycle:
    active/probation/retiring), ``ctpu_client_membership_changes_total``
    (discovery add/retire/unretire/promote/retain/evict events),
    ``ctpu_client_pool_endpoints`` (pool size per phase), and the
    streaming-reconnect pair ``ctpu_client_stream_reconnects_total`` /
    ``ctpu_client_stream_replayed_requests_total``.
    """

    def __init__(self, registry=None):
        self.registry = registry if registry is not None else RESILIENCE

    def on_route(self, endpoint):
        self.registry.inc(
            "ctpu_client_routed_total", {"endpoint": endpoint},
            help_="Requests routed to each replica by the client balancer",
        )

    def on_failover(self, endpoint):
        self.registry.inc(
            "ctpu_client_failovers_total", {"endpoint": endpoint},
            help_="Attempts that failed retryably on a replica and were "
                  "failed over",
        )

    def on_endpoint_state(self, endpoint, state):
        self.registry.set(
            "ctpu_client_endpoint_state", {"endpoint": endpoint},
            ENDPOINT_STATE_VALUES.get(state, -1),
            help_="Pool health view per endpoint "
                  "(0=ready, 1=not-ready/draining, 2=unreachable)",
        )

    # membership / discovery hooks -------------------------------------------

    def on_endpoint_phase(self, endpoint, phase):
        self.registry.set(
            "ctpu_client_endpoint_phase", {"endpoint": endpoint},
            ENDPOINT_PHASE_VALUES.get(phase, -1),
            help_="Pool membership phase per endpoint "
                  "(0=active, 1=probation, 2=retiring)",
        )

    def on_membership(self, op, endpoint):
        self.registry.inc(
            "ctpu_client_membership_changes_total",
            {"op": op, "endpoint": endpoint},
            help_="Discovery-driven membership events "
                  "(add/retire/unretire/promote/retain/evict)",
        )
        if op == "evict":
            # the endpoint is gone: its per-endpoint gauges must not park
            # at their last value (counters stay — they are history)
            labels = {"endpoint": endpoint}
            self.registry.remove("ctpu_client_endpoint_phase", labels)
            self.registry.remove("ctpu_client_endpoint_state", labels)
            self.registry.remove("ctpu_fleet_pressure_queue_depth", labels)
            self.registry.remove("ctpu_fleet_pressure_prefix", labels)

    def on_endpoint_pressure(self, endpoint, pressure):
        """Gossiped autoscaling signals (probe-piggybacked; see
        ``FleetTier.local_summary`` / ``EndpointPool.set_pressure``)."""
        labels = {"endpoint": endpoint}
        self.registry.set(
            "ctpu_fleet_pressure_queue_depth", labels,
            float(pressure.get("queue_depth", 0) or 0),
            help_=FLEET_HELP["ctpu_fleet_pressure_queue_depth"],
        )
        self.registry.set(
            "ctpu_fleet_pressure_prefix", labels,
            float(pressure.get("prefix_hot", 0) or 0),
            help_=FLEET_HELP["ctpu_fleet_pressure_prefix"],
        )

    def on_pool_size(self, active, probation, retiring):
        for phase, count in (
            ("active", active), ("probation", probation),
            ("retiring", retiring),
        ):
            self.registry.set(
                "ctpu_client_pool_endpoints", {"phase": phase}, count,
                help_="Replica-set pool size per membership phase",
            )

    # streaming-reconnect hooks ----------------------------------------------

    def on_stream_reconnect(self, endpoint):
        self.registry.inc(
            "ctpu_client_stream_reconnects_total", {"endpoint": endpoint},
            help_="Streams that died connection-level on this replica and "
                  "reconnected to a fresh one",
        )

    def on_stream_replayed(self, endpoint, count):
        self.registry.inc(
            "ctpu_client_stream_replayed_requests_total",
            {"endpoint": endpoint}, value=count,
            help_="Unacknowledged stream requests replayed onto this "
                  "replica after a reconnect",
        )


def _fmt(value):
    if isinstance(value, float) and not value.is_integer():
        return f"{value:.6f}"
    return str(int(value))


class _FamilyBuffer:
    """Groups samples per metric family so the exposition output keeps all
    lines of one family contiguous (required by the text format — parsers
    keying families by name reject or drop interleaved groups)."""

    def __init__(self):
        self._families = {}  # name -> [type, help, [sample lines]]

    def declare(self, name, type_, help_):
        self._families.setdefault(name, [type_, help_, []])

    def add(self, name, labels, value):
        self._families[name][2].append(
            f"{name}{format_labels(labels)} {_fmt(value)}"
        )

    def add_raw(self, name, line):
        self._families[name][2].append(line)

    def emit(self, lines):
        for name, (type_, help_, samples) in self._families.items():
            lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} {type_}")
            lines.extend(samples)


def initialized_devices():
    """``jax.devices()`` if this process has already initialised a JAX
    backend, else ``[]``.

    Asking must never be the call that opens the chip: a TPU belongs to
    one process at a time, and a numpy-only replica or a load worker
    that looked would take it from the server that needs it, or fail
    against the one that holds it.  It would also stall the first
    /metrics scrape of every numpy-only server on a backend start-up."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return []
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return []
    return jax.devices()


def _device_lines(buf):
    devices = initialized_devices()
    declared = False
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        labels = {"device": d.id, "kind": d.device_kind}
        used = stats.get("bytes_in_use")
        limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
        peak = stats.get("peak_bytes_in_use")
        if not declared and (
            used is not None or limit is not None or peak is not None
        ):
            declared = True
            buf.declare(
                "ctpu_tpu_memory_used_bytes", "gauge",
                "Device HBM bytes in use",
            )
            buf.declare(
                "ctpu_tpu_memory_total_bytes", "gauge",
                "Device HBM byte capacity",
            )
            buf.declare(
                "ctpu_tpu_memory_peak_bytes", "gauge",
                "Peak device HBM bytes",
            )
        if used is not None:
            buf.add("ctpu_tpu_memory_used_bytes", labels, used)
        if limit is not None:
            buf.add("ctpu_tpu_memory_total_bytes", labels, limit)
        if peak is not None:
            buf.add("ctpu_tpu_memory_peak_bytes", labels, peak)


def _histogram_lines(buf, name, labels, snapshot):
    buckets, cumulative, total, count = snapshot
    for bound, c in zip(buckets, cumulative[:-1]):
        le = format_labels(dict(labels, le=bound))
        buf.add_raw(name, f"{name}_bucket{le} {c}")
    inf = format_labels(dict(labels, le="+Inf"))
    buf.add_raw(name, f"{name}_bucket{inf} {cumulative[-1]}")
    lbl = format_labels(labels)
    buf.add_raw(name, f"{name}_sum{lbl} {_fmt(total)}")
    buf.add_raw(name, f"{name}_count{lbl} {count}")


_COUNTER_HELP = [
    ("ctpu_inference_request_success", "Successful inference requests"),
    ("ctpu_inference_request_failure", "Failed inference requests"),
    ("ctpu_inference_count", "Inferences performed (batch aware)"),
    ("ctpu_inference_exec_count", "Model executions (batches count once)"),
    ("ctpu_inference_duration_us",
     "Cumulative successful request duration"),
    ("ctpu_inference_fail_duration_us",
     "Cumulative failed request duration"),
    ("ctpu_inference_queue_duration_us",
     "Cumulative scheduling-queue wait"),
    ("ctpu_inference_compute_input_duration_us",
     "Cumulative input-preparation time"),
    ("ctpu_inference_compute_infer_duration_us",
     "Cumulative model-execution time"),
    ("ctpu_inference_compute_output_duration_us",
     "Cumulative output-rendering time"),
]

_HISTOGRAM_HELP = [
    ("ctpu_request_duration_us",
     "Per-request end-to-end duration distribution"),
    ("ctpu_queue_duration_us",
     "Per-request dynamic-batcher queue-time distribution"),
    ("ctpu_batch_size", "Execution batch-size (rows) distribution"),
]


def render_metrics(engine):
    """The /metrics payload (Prometheus text exposition format).

    All samples of one metric family are emitted as a single contiguous
    block (HELP/TYPE then every sample) — the text format requires it, and
    family-keyed parsers drop or reject interleaved groups."""
    buf = _FamilyBuffer()
    for name, help_ in _COUNTER_HELP:
        buf.declare(name, "counter", help_)
    stats = engine.statistics()
    # engine.statistics() returns the HTTP-format bare list of model entries
    model_stats = stats if isinstance(stats, list) else stats.get(
        "model_stats", []
    )
    for ms in model_stats:
        labels = {"model": ms.get("name", ""), "version": ms.get("version", "")}
        agg = ms.get("inference_stats", {})
        success = agg.get("success", {})
        fail = agg.get("fail", {})
        buf.add(
            "ctpu_inference_request_success", labels,
            int(success.get("count", 0)),
        )
        buf.add(
            "ctpu_inference_request_failure", labels,
            int(fail.get("count", 0)),
        )
        buf.add("ctpu_inference_count", labels, int(ms.get("inference_count", 0)))
        buf.add(
            "ctpu_inference_exec_count", labels,
            int(ms.get("execution_count", 0)),
        )
        buf.add(
            "ctpu_inference_duration_us", labels,
            int(success.get("ns", 0)) // 1000,
        )
        buf.add(
            "ctpu_inference_fail_duration_us", labels,
            int(fail.get("ns", 0)) // 1000,
        )
        for phase in ("queue", "compute_input", "compute_infer",
                      "compute_output"):
            buf.add(
                f"ctpu_inference_{phase}_duration_us", labels,
                int(agg.get(phase, {}).get("ns", 0)) // 1000,
            )
    # per-model histograms (request/queue durations, batch sizes)
    for name, help_ in _HISTOGRAM_HELP:
        buf.declare(name, "histogram", help_)
    for name, version, model_stats_obj in engine.stats_objects():
        labels = {"model": name, "version": version}
        request_us, queue_us, batch_rows = model_stats_obj.histograms()
        _histogram_lines(buf, "ctpu_request_duration_us", labels, request_us)
        _histogram_lines(buf, "ctpu_queue_duration_us", labels, queue_us)
        _histogram_lines(buf, "ctpu_batch_size", labels, batch_rows)
    # live gauges: scheduler queue depth, in-flight work, drain state
    buf.declare(
        "ctpu_queue_depth", "gauge",
        "Requests waiting in the dynamic batcher",
    )
    for name, depth in sorted(engine.queue_depths().items()):
        buf.add("ctpu_queue_depth", {"model": name}, depth)
    tenant_depths = getattr(engine, "tenant_queue_depths", None)
    if tenant_depths is not None:
        buf.declare(
            "ctpu_tenant_queue_depth", "gauge",
            "Requests waiting per tenant fair-queue lane",
        )
        for (model, tenant), depth in sorted(tenant_depths().items()):
            buf.add(
                "ctpu_tenant_queue_depth",
                {"model": model, "tenant": tenant}, depth,
            )
    buf.declare(
        "ctpu_inflight_requests", "gauge", "Requests currently executing"
    )
    buf.add("ctpu_inflight_requests", None, engine.inflight_count())
    buf.declare("ctpu_draining", "gauge", "1 once graceful drain has begun")
    buf.add("ctpu_draining", None, 0 if engine.ready() else 1)
    _device_lines(buf)
    busy = getattr(engine, "busy", None)
    if busy is not None:
        buf.declare(
            "ctpu_server_busy_ns", "counter",
            "Wall-clock ns with >=1 model execution in flight (duty cycle: "
            "rate(ctpu_server_busy_ns)/1e9 = utilization)",
        )
        buf.add("ctpu_server_busy_ns", None, busy.busy_ns())
    buf.declare(
        "ctpu_scrape_timestamp_seconds", "gauge",
        "Wall time of this scrape",
    )
    buf.add_raw(
        "ctpu_scrape_timestamp_seconds",
        f"ctpu_scrape_timestamp_seconds {time.time():.3f}",
    )
    lines = []
    buf.emit(lines)
    # engine-side resilience counters (sheds, drain events) + any client
    # resilience series registered in this process — each registry renders
    # its families as contiguous blocks of its own
    engine.metrics.render_into(lines)
    RESILIENCE.render_into(lines)
    return "\n".join(lines) + "\n"
