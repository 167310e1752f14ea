"""Prometheus-style metrics: registry, counters/gauges, live collectors.

Capability parity with the reference's three metric surfaces:
- notebook metrics collector that scrapes live state at collect time
  (ref notebook-controller/pkg/metrics/metrics.go:22-99 — a custom
  Collect() lists StatefulSets with the notebook-name label instead of
  maintaining a gauge imperatively), plus created/culled counters;
- profile reconcile counters with component/kind/severity labels
  (ref profile-controller/controllers/monitoring.go:19-77);
- KFAM request counters + a /metrics route
  (ref access-management/kfam/monitoring.go, routers.go:82-86).

No prometheus_client dependency: exposition is the stable text format,
rendered directly. Collectors are callables run at scrape time, so the
"running notebooks" gauge can never drift from the store's truth.
"""

from __future__ import annotations

import threading
from typing import Callable, Iterable

from kubeflow_tpu.controlplane.store import Store
from kubeflow_tpu.obs.metrics import (
    LATENCY_BUCKETS,
    Histogram,
    format_float,
)


def _escape_label_value(v: str) -> str:
    # Prometheus exposition format: backslash, double-quote and newline
    # must be escaped inside label values.
    return (
        v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    )


def _fmt_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{k}="{_escape_label_value(str(v))}"'
        for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


class Counter:
    """Monotonic counter with optional labels."""

    def __init__(self, name: str, help: str, registry: "Registry | None" = None):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._values: dict[tuple[tuple[str, str], ...], float] = {}
        if registry is not None:
            registry.register(self)

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels: str) -> float:
        # Under the lock: a bare dict read races concurrent inc/set
        # rehashing the table (CPython mostly saves us, but "mostly" is
        # not a memory model — and PEP 703 builds drop the GIL).
        with self._lock:
            return self._values.get(tuple(sorted(labels.items())), 0.0)

    def samples(self) -> Iterable[tuple[dict[str, str], float]]:
        with self._lock:
            return [(dict(k), v) for k, v in self._values.items()]

    def expositions(self) -> Iterable[tuple[str, dict[str, str], float]]:
        """(sample_name, labels, value) in exposition order — the one
        render protocol shared with obs.metrics.Histogram (which emits
        _bucket/_sum/_count under this same hook)."""
        for labels, v in sorted(self.samples(),
                                key=lambda s: sorted(s[0].items())):
            yield self.name, labels, v

    TYPE = "counter"


class Gauge(Counter):
    TYPE = "gauge"

    def set(self, value: float, **labels: str) -> None:
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = value


class Registry:
    """Holds metrics and scrape-time collectors; renders exposition text."""

    def __init__(self):
        self._metrics: list[Counter] = []
        self._collectors: list[Callable[[], bool | None]] = []
        self._lock = threading.Lock()

    def register(self, metric: Counter) -> None:
        with self._lock:
            if any(m.name == metric.name for m in self._metrics):
                raise ValueError(
                    f"metric {metric.name!r} already registered")
            self._metrics.append(metric)

    def get(self, name: str):
        """The registered metric named `name`, or None — the
        get-or-create hook obs.get_or_create_histogram builds on."""
        with self._lock:
            for m in self._metrics:
                if m.name == name:
                    return m
        return None

    def register_collector(self, fn: Callable[[], bool | None]) -> None:
        """`fn` refreshes gauges from live state; runs on every render
        (the reference's custom Collect→scrape pattern). One that
        returns False has read its last (what it reads is gone) and is
        dropped."""
        with self._lock:
            self._collectors.append(fn)

    def render(self) -> str:
        with self._lock:
            collectors = list(self._collectors)
            metrics = list(self._metrics)
        done = [fn for fn in collectors if fn() is False]
        if done:
            with self._lock:
                self._collectors = [
                    fn for fn in self._collectors if fn not in done]
        lines: list[str] = []
        for m in metrics:
            lines.append(f"# HELP {m.name} {m.help}")
            lines.append(f"# TYPE {m.name} {m.TYPE}")
            # No samples yet → emit nothing (a synthetic unlabeled 0 would
            # create a timeseries that goes stale once labeled samples
            # appear; prometheus_client behaves the same way).
            for name, labels, v in m.expositions():
                lines.append(
                    f"{name}{_fmt_labels(labels)} {format_float(v)}")
        return "\n".join(lines) + "\n"


class ControlPlaneMetrics:
    """The platform's metric set, wired into controllers at assembly.

    Names keep the reference's vocabulary (notebook_create_total,
    notebook_cull_total, running gauge scraped live; reconcile counters
    labeled kind/severity).
    """

    def __init__(self, store: Store, registry: Registry | None = None):
        self.registry = registry or Registry()
        self.store = store
        self.notebooks_running = Gauge(
            "notebook_running", "Current running notebooks per namespace "
            "(scraped live from StatefulSets, ref metrics.go:74-99)",
            self.registry)
        self.tpu_hosts_running = Gauge(
            "tpu_hosts_running", "Current TPU-slice host pods per namespace",
            self.registry)
        self.notebook_created = Counter(
            "notebook_create_total", "Notebook StatefulSets created",
            self.registry)
        self.notebook_culled = Counter(
            "notebook_cull_total", "Notebooks culled for idleness",
            self.registry)
        self.reconcile_total = Counter(
            "reconcile_total", "Reconcile outcomes by controller kind "
            "(ref monitoring.go:62-77)", self.registry)
        self.request_total = Counter(
            "request_total", "HTTP requests by service/method/code "
            "(ref kfam/monitoring.go)", self.registry)
        # Latency layer (ISSUE 1): the reference never measured how long
        # anything took; these three are the control plane's hot paths.
        self.reconcile_duration = Histogram(
            "reconcile_duration_seconds",
            "Reconcile wall time by controller kind", self.registry,
            buckets=LATENCY_BUCKETS)
        self.workqueue_latency = Histogram(
            "workqueue_queue_latency_seconds",
            "Time a key waited in a controller workqueue before a "
            "worker picked it up", self.registry,
            buckets=LATENCY_BUCKETS)
        self.workqueue_depth = Gauge(
            "workqueue_depth",
            "Keys waiting (ready + delayed) per controller workqueue",
            self.registry)
        self.request_duration = Histogram(
            "request_duration_seconds",
            "Platform HTTP request latency by service/method",
            self.registry, buckets=LATENCY_BUCKETS)
        self.registry.register_collector(self._scrape)

    def _scrape(self) -> None:
        """Live scrape (never drifts): running notebooks = STS with the
        notebook-name label and ready replicas; TPU hosts = their pods."""
        running: dict[str, int] = {}
        hosts: dict[str, int] = {}
        for sts in self.store.list("StatefulSet"):
            if "notebook-name" not in sts.metadata.labels:
                continue
            ns = sts.metadata.namespace
            if sts.ready_replicas > 0:
                running[ns] = running.get(ns, 0) + 1
                if sts.spec.gang:
                    hosts[ns] = hosts.get(ns, 0) + sts.ready_replicas
        # Reset namespaces that emptied out, then set current values.
        for labels, _ in self.notebooks_running.samples():
            self.notebooks_running.set(
                float(running.get(labels.get("namespace", ""), 0)), **labels)
        for ns, n in running.items():
            self.notebooks_running.set(float(n), namespace=ns)
        for labels, _ in self.tpu_hosts_running.samples():
            self.tpu_hosts_running.set(
                float(hosts.get(labels.get("namespace", ""), 0)), **labels)
        for ns, n in hosts.items():
            self.tpu_hosts_running.set(float(n), namespace=ns)

    # -- hooks for controllers --------------------------------------------

    def record_reconcile(self, kind: str, ok: bool, *,
                         severity: str | None = None) -> None:
        """severity overrides the ok→info/error mapping (e.g. "conflict"
        for optimistic-concurrency retries, which are neither)."""
        self.reconcile_total.inc(
            kind=kind,
            severity=severity or ("info" if ok else "error"))

    def record_reconcile_duration(self, kind: str, seconds: float) -> None:
        self.reconcile_duration.observe(seconds, kind=kind)

    def record_queue_latency(self, kind: str, seconds: float) -> None:
        self.workqueue_latency.observe(seconds, kind=kind)

    def record_request(self, service: str, method: str, code: int,
                       seconds: float | None = None) -> None:
        self.request_total.inc(service=service, method=method,
                               code=str(code))
        if seconds is not None:
            self.request_duration.observe(seconds, service=service,
                                          method=method)


def scan_usage(store: Store) -> tuple[list[tuple[str, str]],
                                      dict[str, int]]:
    """One store walk shared by the dashboard summary and the history
    sampler (a drifted copy of the 'TPU host in use' filter would
    silently desynchronize the summary tiles from the chart's live
    point): [(namespace, topology)] per running TPU-host pod, plus
    notebooks per namespace."""
    from kubeflow_tpu.controlplane import webhook as wh

    pods: list[tuple[str, str]] = []
    nbs: dict[str, int] = {}
    for pod in store.list("Pod"):
        topo = pod.metadata.labels.get(wh.TOPOLOGY_LABEL)
        if topo and pod.phase == "Running":
            pods.append((pod.metadata.namespace, topo))
    for nb in store.list("Notebook"):
        ns = nb.metadata.namespace
        nbs[ns] = nbs.get(ns, 0) + 1
    return pods, nbs


class MetricsHistory:
    """Ring-buffered cluster-usage time series for the dashboard charts.

    The reference's dashboard serves cluster resource charts over
    5/15/30/60/180-minute windows from Stackdriver
    (ref centraldashboard/app/metrics_service.ts:2-8, routes
    api.ts:29-102, impl stackdriver_metrics_service.ts:15-60). The
    TPU-native platform has no cloud monitoring dependency, so the
    history lives here: periodic samples of per-namespace TPU-host and
    notebook counts scanned from the store, kept per NAMESPACE so the
    serving endpoint can apply the same visibility scoping as the
    point-in-time summary (cluster-wide series would leak cross-tenant
    occupancy to non-admins).
    """

    WINDOWS_MIN = (5, 15, 30, 60, 180)

    def __init__(self, store: Store, *, cadence_s: float = 30.0,
                 clock: Callable[[], float] | None = None):
        import collections
        import time as _time

        self.store = store
        self.cadence_s = cadence_s
        self._clock = clock or _time.time
        # retention = the longest window + one slack sample
        self._samples: collections.deque = collections.deque(
            maxlen=int(self.WINDOWS_MIN[-1] * 60 / cadence_s) + 2)
        self._lock = threading.Lock()

    def _scan(self) -> tuple[dict[str, int], dict[str, int]]:
        pods, nbs = scan_usage(self.store)
        tpu: dict[str, int] = {}
        for ns, _topo in pods:
            tpu[ns] = tpu.get(ns, 0) + 1
        return tpu, nbs

    def sample(self) -> None:
        """Scan the store once and append a ring point. Calls within
        half a cadence collapse to one sample, so the ring fills at
        CADENCE rate and its retention math holds even if multiple
        samplers ever run. The dashboard's background task is the ONLY
        caller today; request-time freshness is series(live=...),
        which never stores."""
        now = self._clock()
        with self._lock:
            if self._samples and \
                    now - self._samples[-1][0] < self.cadence_s / 2:
                return
            tpu, nbs = self._scan()
            self._samples.append((now, tpu, nbs))

    def series(self, window_min: int,
               visible: set[str] | None = None,
               live: "bool | tuple" = False) -> list[dict]:
        """Points within the window, each summed over `visible`
        namespaces (None = cluster-wide, the admin view). `live`
        appends a now-point WITHOUT storing it, so a chart always ends
        at the present even between cadence ticks — True scans here; a
        (tpu_by_ns, notebooks_by_ns) tuple reuses a scan the caller
        already paid for (the dashboard handler's summary walk)."""
        if window_min not in self.WINDOWS_MIN:
            raise ValueError(
                f"window must be one of {self.WINDOWS_MIN} minutes")
        if not isinstance(live, bool) and not (
                isinstance(live, (tuple, list)) and len(live) == 2
                and all(isinstance(d, dict) for d in live)):
            # Without this check a malformed tuple surfaces as an
            # opaque TypeError deep inside pt() — name the contract.
            raise ValueError(
                "live must be True, False, or a (tpu_by_namespace, "
                "notebooks_by_namespace) pair of dicts")
        now = self._clock()
        cutoff = now - window_min * 60

        def pt(t, tpu, nbs):
            return {
                "t": round(t, 3),
                "tpuHostsInUse": sum(
                    n for ns, n in tpu.items()
                    if visible is None or ns in visible),
                "notebooks": sum(
                    n for ns, n in nbs.items()
                    if visible is None or ns in visible),
            }

        with self._lock:
            pts = [pt(t, tpu, nbs)
                   for t, tpu, nbs in self._samples if t >= cutoff]
            if live is True:
                pts.append(pt(now, *self._scan()))
            elif live:
                pts.append(pt(now, *live))
        return pts
