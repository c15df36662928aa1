"""Fleet observability plane: live export, pod rollup, memory, budget.

PR 6 gave one process spans, counters and Perfetto dumps; PR 9 made
every driver run a multi-controller pod — this module is the layer that
makes the *fleet* observable instead of each process privately:

  * **Live export** — ``render_prometheus()`` serializes every declared
    counter and gauge (telemetry.REGISTRY) in Prometheus text format,
    per job_id, while runs are in flight. ``start_exporter(port=...)``
    serves it over HTTP from a background thread
    (``TPUBackend(metrics_port=...)``); ``start_exporter(path=...)`` is
    the portless-CI mode: the same text re-written atomically on an
    interval, scrapeable as a file. ``parse_prometheus()`` is the
    strict line-grammar check the tier-1 gate runs — no external dep.
  * **Device-memory watermarks** — ``memory_watermark()`` reads JAX
    device memory stats where the platform provides them and falls back
    to the byte accountant (``account_bytes``/``release_bytes`` — fed
    from array shapes by the device-resident accumulator) on CPU.
    ``enable_memory_sampling()`` attaches the watermark to every closing
    trace span, so pipeline phases carry their memory high-water mark
    and an OOM degradation event records the watermark that triggered it
    (runtime/retry.py attaches it to ``block_oom_degradations``).
  * **Privacy-budget odometer** — every
    ``BudgetAccountant._register_mechanism`` appends one ordered audit
    record (job, metric label, mechanism kind, weight/sensitivity,
    process provenance; epsilon/delta shares resolve once
    compute_budgets fills the shared MechanismSpec). ``odometer_report``
    reconciles the records against the ledger: record count ==
    ``mechanism_count`` and the eps shares sum to the ledger's spent
    epsilon, exactly — the audit substrate the planned PLD accountant
    replays compositions from. ``persist_odometer`` writes the trail
    through the BlockJournal (CRC-verified, process-scoped), wired at
    driver teardown by runtime/entry.py.
  * **Cross-process rollup** — ``export_process_state(dir)`` writes one
    atomic JSON per controller (counters, gauges, timings, health,
    odometer, trace events) named by jax process index — the same
    ``(job_id, process_index)`` scoping the journal uses.
    ``aggregate_directory(dir)`` merges them on the host, collective-
    free: counters sum, health keys by (job, process), and
    ``merge_trace_payloads`` rewrites each controller's events onto a
    distinct Perfetto ``pid`` track with a named process_name metadata
    row, so a pod run reads as ONE timeline. Each per-process buffer
    enters the merge exactly once (files are keyed by process index),
    so an incident recorded by one controller can never double-count.
    ``write_pod_rollup`` is the drain/teardown gather: process 0 waits
    for its siblings' files and writes the merged ``obs__pod.json``.

Everything here is host-side and numpy/stdlib only — importable without
jax, collective-free by construction (a controller that died mid-run
still left its last atomic export on disk, and the rollup proceeds with
whatever files exist).
"""

import contextlib
import dataclasses
import glob
import http.server
import json
import logging
import os
import re
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from pipelinedp_tpu.runtime.concurrency import guarded_by

# ---------------------------------------------------------------------------
# Prometheus text rendering + the strict line-grammar parser
# ---------------------------------------------------------------------------

# Every exported sample is prefixed so scrapes from co-located services
# never collide in one Prometheus namespace.
PROM_PREFIX = "pdp_"

_PROM_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_PROM_HELP_RE = re.compile(rf"^# HELP ({_PROM_NAME}) (.*)$")
_PROM_TYPE_RE = re.compile(rf"^# TYPE ({_PROM_NAME}) (counter|gauge)$")
_PROM_SAMPLE_RE = re.compile(
    rf"^({_PROM_NAME})"
    r"(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\.)*\")"
    r"(?:,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\.)*\")*)\})?"
    r" (-?(?:[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?|\+?Inf|NaN))$")
_PROM_LABEL_RE = re.compile(
    r"([a-zA-Z_][a-zA-Z0-9_]*)=\"((?:[^\"\\\n]|\\.)*)\"")


def _prom_escape_help(text: str) -> str:
    return text.replace("\\", "\\\\").replace("\n", "\\n")


def _prom_escape_label(text: str) -> str:
    return (text.replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _prom_number(value: float) -> str:
    f = float(value)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def render_prometheus() -> str:
    """The process's declared counters and gauges as Prometheus text.

    One ``# HELP``/``# TYPE`` pair per declared metric (zero-valued
    counters export as 0 — a scraper can tell "never fired" from "not
    exported"), counter samples unlabeled, gauge samples labeled
    ``job_id="..."`` when the gauge was set under a job scope. Gauges
    refresh the sampled sources (memory watermark, per-job health
    state, budget remaining) before rendering, so a scrape mid-run sees
    current levels, not the last explicit set.
    """
    from pipelinedp_tpu.runtime import telemetry

    refresh_gauges()
    counters = telemetry.snapshot()
    gauges = telemetry.gauge_snapshot()
    lines: List[str] = []
    for metric in telemetry.REGISTRY.values():
        name = PROM_PREFIX + metric.name
        lines.append(f"# HELP {name} {_prom_escape_help(metric.help)}")
        lines.append(f"# TYPE {name} {metric.kind}")
        if metric.kind == "counter":
            lines.append(f"{name} {_prom_number(counters.get(metric.name, 0))}")
        else:
            by_job = gauges.get(metric.name, {})
            if not by_job:
                continue
            for job in sorted(by_job):
                if job:
                    lines.append(
                        f'{name}{{job_id="{_prom_escape_label(job)}"}} '
                        f"{_prom_number(by_job[job])}")
                else:
                    lines.append(f"{name} {_prom_number(by_job[job])}")
    return "\n".join(lines) + "\n"


def parse_prometheus(text: str) -> Dict[str, Dict[str, Any]]:
    """Strictly parses Prometheus text (the tier-1 grammar gate).

    Every line must be a ``# HELP``, a ``# TYPE counter|gauge``, a
    sample ``name{label="v",...} number``, or blank — anything else
    raises ValueError naming the offending line. Returns
    ``{metric_name: {"type": ..., "help": ..., "samples":
    {label_string_or_"": value}}}``. A sample for an undeclared (no
    TYPE line) metric is rejected too: the exporter always declares
    before it samples.
    """
    out: Dict[str, Dict[str, Any]] = {}
    for lineno, line in enumerate(text.splitlines(), start=1):
        if not line.strip():
            continue
        m = _PROM_HELP_RE.match(line)
        if m:
            out.setdefault(m.group(1), {"samples": {}})["help"] = m.group(2)
            continue
        m = _PROM_TYPE_RE.match(line)
        if m:
            out.setdefault(m.group(1), {"samples": {}})["type"] = m.group(2)
            continue
        m = _PROM_SAMPLE_RE.match(line)
        if m:
            name, labels, number = m.group(1), m.group(2), m.group(3)
            if name not in out or "type" not in out[name]:
                raise ValueError(
                    f"prometheus line {lineno}: sample for {name!r} "
                    f"before its # TYPE declaration")
            if labels:
                parsed = _PROM_LABEL_RE.findall(labels)
                label_key = ",".join(f"{k}={v}" for k, v in parsed)
            else:
                label_key = ""
            out[name]["samples"][label_key] = float(number)
            continue
        raise ValueError(
            f"prometheus line {lineno} fails the grammar: {line!r}")
    for name, entry in out.items():
        if "type" not in entry:
            raise ValueError(f"metric {name!r} has HELP but no TYPE line")
    return out


# ---------------------------------------------------------------------------
# Background exporters (HTTP scrape endpoint + atomic-file mode)
# ---------------------------------------------------------------------------


class _ScrapeHandler(http.server.BaseHTTPRequestHandler):

    def do_GET(self):  # noqa: N802 - BaseHTTPRequestHandler API
        payload = render_prometheus().encode()
        self.send_response(200)
        self.send_header("Content-Type",
                         "text/plain; version=0.0.4; charset=utf-8")
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    def log_message(self, fmt, *args):
        # Scrapes land every few seconds; stderr noise helps no one.
        pass


class MetricsExporter:
    """One live metrics export: an HTTP scrape endpoint OR an
    atomically re-written file.

    ``port`` mode binds 127.0.0.1:port (0 = ephemeral; read ``.port``)
    and serves ``render_prometheus()`` on every GET from a daemon
    thread. ``path`` mode re-renders every ``interval_s`` seconds and
    publishes write-then-rename, so a scraper (or a CI assertion) can
    never observe a torn half-written exposition — the portless
    equivalent for sandboxes that cannot open listening sockets.
    """

    def __init__(self, port: Optional[int] = None,
                 path: Optional[str] = None,
                 interval_s: float = 0.25):
        if (port is None) == (path is None):
            raise ValueError(
                "MetricsExporter: exactly one of port= (HTTP scrape "
                "endpoint) or path= (atomic-file mode) must be given")
        self._server: Optional[http.server.ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.path = path
        self.interval_s = float(interval_s)
        if port is not None:
            self._server = http.server.ThreadingHTTPServer(
                ("127.0.0.1", int(port)), _ScrapeHandler)
            self.port = int(self._server.server_address[1])
            self._thread = threading.Thread(
                target=self._server.serve_forever,
                name="pdp-metrics-http", daemon=True)
        else:
            self.port = None
            self._write_file()  # the file exists before start() returns
            self._thread = threading.Thread(
                target=self._file_loop, name="pdp-metrics-file",
                daemon=True)
        self._thread.start()

    def _write_file(self) -> None:
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(render_prometheus())
        os.replace(tmp, self.path)

    def _file_loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self._write_file()
            except OSError as e:
                logging.warning(
                    "metrics file exporter: write to %s failed (%s); "
                    "will retry next interval", self.path, e)

    def scrape(self) -> str:
        """The current exposition text (same bytes a scraper would get)."""
        return render_prometheus()

    @property
    def endpoint(self) -> str:
        if self.port is not None:
            return f"http://127.0.0.1:{self.port}/metrics"
        return self.path

    def stop(self) -> None:
        self._stop.set()
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        with _exporters_lock:
            if self in _exporters:
                _exporters.remove(self)


_exporters_lock = threading.Lock()
_exporters: List[MetricsExporter] = []
_GUARDED_BY = guarded_by("_exporters_lock", "_exporters")


def start_exporter(port: Optional[int] = None,
                   path: Optional[str] = None,
                   interval_s: float = 0.25) -> MetricsExporter:
    """Starts a MetricsExporter and registers it for stop_all_exporters
    (TPUBackend(metrics_port=/metrics_path=) routes here)."""
    exporter = MetricsExporter(port=port, path=path, interval_s=interval_s)
    with _exporters_lock:
        _exporters.append(exporter)
    return exporter


def stop_all_exporters() -> None:
    """Stops every exporter started via start_exporter (test teardown,
    process shutdown)."""
    with _exporters_lock:
        exporters = list(_exporters)
    for exporter in exporters:
        exporter.stop()


# ---------------------------------------------------------------------------
# Device-memory watermarks
# ---------------------------------------------------------------------------

_mem_lock = threading.Lock()
_acct_live_bytes = 0
_acct_peak_bytes = 0
# The accumulator/executor account from worker threads while scrapes and
# span closes read; lock-discipline enforced.
_GUARDED_BY = guarded_by("_mem_lock", "_acct_live_bytes",
                         "_acct_peak_bytes")


def account_bytes(n: int) -> None:
    """Adds n bytes to the byte-accounted live set (the CPU fallback for
    platforms without device memory stats). Callers pass array nbytes at
    upload/accumulate time and release_bytes at drop time."""
    global _acct_live_bytes, _acct_peak_bytes
    with _mem_lock:
        _acct_live_bytes += int(n)
        if _acct_live_bytes > _acct_peak_bytes:
            _acct_peak_bytes = _acct_live_bytes


def release_bytes(n: int) -> None:
    global _acct_live_bytes
    with _mem_lock:
        _acct_live_bytes = max(_acct_live_bytes - int(n), 0)


def account_arrays(*arrays) -> int:
    """account_bytes over the nbytes of the given arrays; returns the
    total so the caller can release_bytes the same amount later."""
    total = sum(int(getattr(a, "nbytes", 0) or 0) for a in arrays
                if a is not None)
    if total:
        account_bytes(total)
    return total


def _device_memory_stats() -> Optional[Dict[str, int]]:
    """Summed live/peak bytes across the locally-addressable devices,
    from the platform's memory stats — None where unsupported (CPU) or
    before jax is imported (never drags the backend up)."""
    import sys
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        live = peak = 0
        found = False
        for device in jax.local_devices():
            stats = device.memory_stats()
            if not stats:
                continue
            found = True
            live += int(stats.get("bytes_in_use", 0))
            peak += int(stats.get("peak_bytes_in_use",
                                  stats.get("bytes_in_use", 0)))
        return {"live_bytes": live, "peak_bytes": peak} if found else None
    except Exception:  # noqa: BLE001 - absent/partial memory-stats support means "unsupported platform", exactly what the byte-accounted fallback exists for
        return None


def device_bytes_limit(devices=None) -> Optional[int]:
    """Summed ``bytes_limit`` of the given devices' memory stats (default:
    every locally-addressable device) — None where the platform reports
    none (CPU) or before jax is imported."""
    import sys
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        total = 0
        for device in (jax.local_devices() if devices is None else devices):
            stats = device.memory_stats()
            if stats and stats.get("bytes_limit"):
                total += int(stats["bytes_limit"])
        return total or None
    except Exception:  # noqa: BLE001 - absent/partial memory-stats support means "no platform limit": the service's memory_limit_bytes and large_p's CPU row budget exist for exactly that
        return None


def memory_watermark() -> Dict[str, Any]:
    """{"live_bytes", "peak_bytes", "source"}: the device runtime's own
    memory stats where available ("device"), else the byte-accounted
    fallback fed from array shapes ("accounted")."""
    stats = _device_memory_stats()
    if stats is not None:
        return {**stats, "source": "device"}
    with _mem_lock:
        return {"live_bytes": _acct_live_bytes,
                "peak_bytes": _acct_peak_bytes,
                "source": "accounted"}


def _span_memory_attrs() -> Dict[str, int]:
    wm = memory_watermark()
    return {"mem_live_bytes": wm["live_bytes"],
            "mem_peak_bytes": wm["peak_bytes"]}


def enable_memory_sampling() -> None:
    """Attaches mem_live_bytes/mem_peak_bytes to every closing trace
    span (per-phase memory attribution on the Perfetto timeline). Costs
    one watermark read per span close — enable together with tracing,
    not on the untraced hot path."""
    from pipelinedp_tpu.runtime import trace
    trace.set_memory_sampler(_span_memory_attrs)


def disable_memory_sampling() -> None:
    from pipelinedp_tpu.runtime import trace
    trace.set_memory_sampler(None)


# ---------------------------------------------------------------------------
# Privacy-budget odometer
# ---------------------------------------------------------------------------

# Journal key of a persisted odometer trail (never collides with block
# geometry keys, skipped by compact()'s geometry regex).
ODOMETER_KEY = "__odometer__"

_odo_lock = threading.Lock()
_odo_records: List["OdometerRecord"] = []
_odo_seq = 0
_GUARDED_BY = guarded_by("_odo_lock", "_odo_records", "_odo_seq")

_odo_local = threading.local()


@dataclasses.dataclass
class OdometerRecord:
    """One mechanism registration, in ledger order.

    eps/delta are read through the SHARED MechanismSpec (the same object
    compute_budgets fills), so a record created at graph-build time
    reports the final share once the budget is computed — and None
    before, never a stale copy.
    """
    seq: int
    job_id: Optional[str]
    metric: Optional[str]
    mechanism_kind: str
    weight: float
    sensitivity: float
    count: int
    process_index: int
    _spec: Any = dataclasses.field(repr=False)
    _accountant_ref: Any = dataclasses.field(repr=False)

    @property
    def eps(self) -> Optional[float]:
        return getattr(self._spec, "_eps", None)

    @property
    def delta(self) -> Optional[float]:
        return getattr(self._spec, "_delta", None)

    @property
    def noise_std(self) -> Optional[float]:
        """The calibrated noise stddev, once the budget is computed.

        PLD-composed spend rebuilds (accounting/compose.py) prefer this
        over re-deriving a scale from the (eps, delta) share, so the
        rebuilt PLD is the PLD of the mechanism that actually ran."""
        return getattr(self._spec, "_noise_standard_deviation", None)

    def accountant(self):
        return self._accountant_ref()

    def to_dict(self) -> Dict[str, Any]:
        return {
            "seq": self.seq,
            "job_id": self.job_id,
            "metric": self.metric,
            "mechanism_kind": self.mechanism_kind,
            "weight": self.weight,
            "sensitivity": self.sensitivity,
            "count": self.count,
            "process_index": self.process_index,
            "eps": self.eps,
            "delta": self.delta,
            "noise_std": self.noise_std,
        }


@contextlib.contextmanager
def mechanism_label(metric: str):
    """Labels mechanism registrations inside the scope with the DP
    metric they serve (count/sum/mean/...): combiners wrap each
    request_budget so odometer records carry metric provenance, not
    just the noise kind."""
    prev = getattr(_odo_local, "label", None)
    _odo_local.label = metric
    try:
        yield
    finally:
        _odo_local.label = prev


def record_mechanism(accountant, mechanism) -> None:
    """BudgetAccountant._register_mechanism hook: appends one ordered
    audit record (see module docstring). Never raises — the odometer is
    an observer of the ledger, not a participant."""
    global _odo_seq
    from pipelinedp_tpu.runtime import health

    h = health.current()
    spec = getattr(mechanism, "mechanism_spec", None)
    record = OdometerRecord(
        seq=0,
        job_id=h.job_id if h is not None else None,
        metric=getattr(_odo_local, "label", None),
        mechanism_kind=str(getattr(spec, "mechanism_type", "")),
        weight=float(getattr(mechanism, "weight", 1.0)),
        sensitivity=float(getattr(mechanism, "sensitivity", 1.0)),
        count=int(getattr(spec, "_count", 1) or 1),
        process_index=health._process_index(),
        _spec=spec,
        _accountant_ref=weakref.ref(accountant),
    )
    with _odo_lock:
        record.seq = _odo_seq
        _odo_seq += 1
        _odo_records.append(record)


def _records_snapshot() -> List[OdometerRecord]:
    with _odo_lock:
        return list(_odo_records)


def prune_odometer(accountant=None, job_id: Optional[str] = None) -> int:
    """Removes one accountant's (identity, via weakref) and/or one
    job's records from the in-memory trail; returns how many went.

    The resident multi-tenant service calls this once a job's trail has
    been charged to its TenantLedger of record: without pruning, a
    long-running process accumulates every job's records forever and
    each completion's odometer_report(accountant=...) scan costs
    O(total mechanisms ever registered). At least one filter is
    required — an unfiltered wipe of the whole trail is reset_epoch()'s
    job, with its active-job-scope guard."""
    if accountant is None and job_id is None:
        raise ValueError(
            "prune_odometer: pass accountant= and/or job_id= — an "
            "unfiltered prune of the full trail is a reset, which "
            "telemetry.reset()/reset_epoch() own (with the live-job "
            "guard this bypass would lose).")
    with _odo_lock:
        kept = []
        removed = 0
        for record in _odo_records:
            if ((accountant is None or record.accountant() is accountant)
                    and (job_id is None or record.job_id == job_id)):
                removed += 1
            else:
                kept.append(record)
        _odo_records[:] = kept
    return removed


def fold_spend(shares) -> float:
    """THE epsilon/delta spend fold: a plain left-to-right float64
    accumulation from 0.0 in iteration order.

    Every "bit-exact naive sum" in the package — the accountant's
    ``spent_epsilon``, the tenant ledger's spend/snapshot/admission
    numbers and ``odometer_report`` — goes through this one function,
    so they reconcile with ``==`` whatever the element type. Builtin
    ``sum()`` is NOT this fold: on Python >= 3.12 it is compensated
    for exact ``float`` items and plain for ``np.float64``, i.e. two
    algorithms chosen by the type a share happens to arrive as.
    """
    total = 0.0
    for share in shares:
        total += share
    return total


def odometer_report(accountant=None,
                    job_id: Optional[str] = None) -> Dict[str, Any]:
    """Spent-vs-remaining over the ordered audit trail.

    Filters to one accountant's records (identity, via weakref) and/or
    one job's. Returns ``records`` (ordered dicts), ``mechanisms`` (the
    record count), ``spent_epsilon``/``spent_delta`` (the sum of
    computed shares, weighted by mechanism count — exactly the ledger's
    apportionment), ``pending`` (records whose budget is not computed
    yet), and — when an accountant is given — ``total_epsilon``,
    ``remaining_epsilon`` and ``reconciled``: record count ==
    ``accountant.mechanism_count`` AND the eps shares sum bit-exactly to
    ``accountant.spent_epsilon()``. A False ``reconciled`` means a
    registration bypassed the hook (or crossed processes without the
    rollup) and the audit trail cannot be trusted for replay.
    """
    records = _records_snapshot()
    if accountant is not None:
        records = [r for r in records if r.accountant() is accountant]
    if job_id is not None:
        records = [r for r in records if r.job_id == job_id]
    computed = [r for r in records if r.eps is not None]
    pending = len(records) - len(computed)
    spent_eps = fold_spend(r.eps * r.count for r in computed)
    spent_delta = fold_spend(r.delta * r.count for r in computed
                             if r.delta)
    report: Dict[str, Any] = {
        "records": [r.to_dict() for r in records],
        "mechanisms": len(records),
        "spent_epsilon": spent_eps,
        "spent_delta": spent_delta,
        "pending": pending,
    }
    if accountant is not None:
        total = float(getattr(accountant, "_total_epsilon", 0.0))
        ledger_spent = accountant.spent_epsilon() if hasattr(
            accountant, "spent_epsilon") else None
        report["total_epsilon"] = total
        report["remaining_epsilon"] = max(total - spent_eps, 0.0)
        report["ledger_spent_epsilon"] = ledger_spent
        report["reconciled"] = (
            len(records) == accountant.mechanism_count and
            (ledger_spent is None or ledger_spent == spent_eps))
    return report


def persist_odometer(journal, job_id: str,
                     records: Optional[List[Dict[str, Any]]] = None) -> None:
    """Writes an ordered audit trail through the BlockJournal
    (key ``__odometer__``): CRC-verified, fsync-then-rename, scoped to
    the journal's controller process — the same durability and
    (job_id, process_index) isolation block results get. Called by
    runtime/entry.py at driver teardown when a journal is configured;
    idempotent (the trail only grows, and a re-write supersedes).

    By default the process's full in-memory trail is written; pass
    ``records`` (ordered dicts in the ``OdometerRecord.to_dict`` /
    ``load_odometer`` shape) to persist an explicit trail instead —
    the multi-tenant service's TenantLedger does, so one tenant's
    ledger of record never absorbs a co-resident tenant's records."""
    from pipelinedp_tpu.runtime.journal import BlockRecord

    rows = (records if records is not None else
            [r.to_dict() for r in _records_snapshot()])
    n = len(rows)

    def _col(key, none_value=None):
        return [none_value if r.get(key) is None else r[key] for r in rows]

    record = BlockRecord(
        ids=np.asarray(_col("seq"), dtype=np.int64),
        outputs={
            "eps": np.asarray(_col("eps", np.nan), dtype=np.float64),
            "delta": np.asarray(_col("delta", np.nan), dtype=np.float64),
            "noise_std": np.asarray(_col("noise_std", np.nan),
                                    dtype=np.float64),
            "weight": np.asarray(_col("weight"), np.float64),
            "sensitivity": np.asarray(_col("sensitivity"), np.float64),
            "count": np.asarray(_col("count"), np.int64),
            "process_index": np.asarray(_col("process_index"), np.int32),
            "job_id": np.asarray(_col("job_id", ""), dtype=np.str_),
            "metric": np.asarray(_col("metric", ""), dtype=np.str_),
            "mechanism_kind": np.asarray(_col("mechanism_kind", ""),
                                         dtype=np.str_),
        } if n else {})
    journal.put(job_id, ODOMETER_KEY, record)


def load_odometer(journal, job_id: str) -> List[Dict[str, Any]]:
    """Reads a persisted audit trail back (ordered dicts; [] when none
    was persisted). A corrupt record quarantines exactly like a block
    record — an unverifiable audit trail is never replayed as truth."""
    record = journal.get(job_id, ODOMETER_KEY)
    if record is None or record.ids.size == 0:
        return []
    out = []
    for i, seq in enumerate(record.ids):
        eps = float(record.outputs["eps"][i])
        delta = float(record.outputs["delta"][i])
        # Trails persisted before the column existed load as None.
        noise_std = (float(record.outputs["noise_std"][i])
                     if "noise_std" in record.outputs else np.nan)
        out.append({
            "seq": int(seq),
            "job_id": str(record.outputs["job_id"][i]) or None,
            "metric": str(record.outputs["metric"][i]) or None,
            "mechanism_kind": str(record.outputs["mechanism_kind"][i]),
            "weight": float(record.outputs["weight"][i]),
            "sensitivity": float(record.outputs["sensitivity"][i]),
            "count": int(record.outputs["count"][i]),
            "process_index": int(record.outputs["process_index"][i]),
            "eps": None if np.isnan(eps) else eps,
            "delta": None if np.isnan(delta) else delta,
            "noise_std": None if np.isnan(noise_std) else noise_std,
        })
    return out


# ---------------------------------------------------------------------------
# Gauge refresh (the sampled levels a scrape must see current)
# ---------------------------------------------------------------------------


def refresh_gauges() -> None:
    """Re-samples the gauges whose sources are queryable rather than
    event-driven: memory watermark, per-job health state, budget
    remaining. Event-driven gauges (queue depth, live devices) are set
    at their call sites and pass through unchanged."""
    from pipelinedp_tpu.runtime import health
    from pipelinedp_tpu.runtime import telemetry

    wm = memory_watermark()
    telemetry.set_gauge("device_memory_live_bytes", wm["live_bytes"],
                        job_id=None)
    telemetry.set_gauge("device_memory_peak_bytes", wm["peak_bytes"],
                        job_id=None)
    for job, snap in health.snapshot_all().items():
        telemetry.set_gauge("job_health_state",
                            health.HealthState[snap["state"]].value,
                            job_id=job)
    seen = set()
    for r in _records_snapshot():
        acc = r.accountant()
        if acc is None or id(acc) in seen:
            continue
        seen.add(id(acc))
        report = odometer_report(accountant=acc)
        telemetry.set_gauge("budget_epsilon_remaining",
                            report["remaining_epsilon"],
                            job_id=r.job_id)


# ---------------------------------------------------------------------------
# Cross-process rollup (collective-free host-side gather)
# ---------------------------------------------------------------------------

_OBS_PREFIX = "obs__p"
POD_ROLLUP_NAME = "obs__pod.json"


def _atomic_json_write(path: str, payload: Dict[str, Any]) -> str:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    return path


def export_process_state(directory: str,
                         process_index: Optional[int] = None) -> str:
    """Writes this controller's full observability state to
    ``<directory>/obs__p<index>.json`` (atomic): counters, gauges,
    timings, per-job health snapshots, odometer records and the trace
    buffer (already exported under the process index as its Perfetto
    pid). The drain/teardown half of the pod rollup — every controller
    calls this; aggregate_directory/write_pod_rollup merge."""
    from pipelinedp_tpu.runtime import health
    from pipelinedp_tpu.runtime import telemetry
    from pipelinedp_tpu.runtime import trace

    pi = health._process_index() if process_index is None else int(
        process_index)
    os.makedirs(directory, exist_ok=True)
    summary = trace.trace_summary()
    payload = {
        "process_index": pi,
        "counters": telemetry.snapshot(),
        "gauges": telemetry.gauge_snapshot(),
        "timings": telemetry.timing_snapshot(),
        "job_timings": telemetry.job_timing_snapshot(),
        "health": health.snapshot_all(),
        "odometer": [r.to_dict() for r in _records_snapshot()],
        "memory": memory_watermark(),
        "trace": trace.to_trace_events(
            pid=pi, process_name=f"pipelinedp-tpu p{pi}"),
        "dropped_events": summary["dropped_events"],
        "truncated": summary["truncated"],
    }
    return _atomic_json_write(
        os.path.join(directory, f"{_OBS_PREFIX}{pi}.json"), payload)


def read_process_states(directory: str) -> List[Dict[str, Any]]:
    """The per-process exports of a directory, ordered by process index.
    Each index is read exactly once (file names are keyed by it), which
    is what makes the merge double-count-free by construction."""
    states = {}
    for path in glob.glob(os.path.join(directory, f"{_OBS_PREFIX}*.json")):
        m = re.match(rf"^{_OBS_PREFIX}(\d+)\.json$",
                     os.path.basename(path))
        if not m:
            continue
        with open(path) as f:
            states[int(m.group(1))] = json.load(f)
    return [states[pi] for pi in sorted(states)]


def merge_trace_payloads(
        payloads: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Merges per-process Perfetto payloads into ONE trace.

    Events keep the pid their export stamped (the jax process index),
    so each controller renders as its own named track group — a pod run
    reads as one timeline with per-controller rows. Timestamps stay in
    each process's own monotonic epoch (clock domains are per host;
    cross-process ordering is causal through the instants, not through
    ts). Each payload contributes its events exactly once.
    """
    events: List[Dict[str, Any]] = []
    for payload in payloads:
        events.extend(payload.get("traceEvents", []))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def aggregate_directory(directory: str) -> Dict[str, Any]:
    """Merges every per-process export in ``directory`` into the pod
    view: counters summed across controllers, gauges/timings/health/
    odometer keyed by (name-or-job, process index), one merged Perfetto
    trace with a distinct pid track per controller."""
    states = read_process_states(directory)
    counters: Dict[str, int] = {}
    gauges: Dict[str, Dict[str, float]] = {}
    health: Dict[str, Any] = {}
    job_timings: Dict[str, Any] = {}
    odometer: List[Dict[str, Any]] = []
    memory: Dict[str, Any] = {}
    truncated = False
    for state in states:
        pi = state["process_index"]
        for name, value in state.get("counters", {}).items():
            counters[name] = counters.get(name, 0) + int(value)
        for name, by_job in state.get("gauges", {}).items():
            for job, value in by_job.items():
                gauges.setdefault(name, {})[
                    f"{job}@p{pi}" if job else f"@p{pi}"] = value
        for job, snap in state.get("health", {}).items():
            health[f"{job}@p{pi}"] = snap
        for job, stats in state.get("job_timings", {}).items():
            job_timings[f"{job}@p{pi}"] = stats
        for record in state.get("odometer", []):
            odometer.append(record)
        memory[f"p{pi}"] = state.get("memory")
        truncated = truncated or bool(state.get("truncated"))
    odometer.sort(key=lambda r: (r["process_index"], r["seq"]))
    return {
        "processes": [s["process_index"] for s in states],
        "counters": counters,
        "gauges": gauges,
        "health": health,
        "job_timings": job_timings,
        "odometer": odometer,
        "memory": memory,
        "truncated": truncated,
        "trace": merge_trace_payloads(
            [s["trace"] for s in states if s.get("trace")]),
    }


def write_pod_rollup(directory: str, num_processes: int,
                     timeout_s: float = 30.0) -> Optional[str]:
    """Process 0's teardown gather: waits (bounded) for every sibling's
    export file, merges, writes ``obs__pod.json``. Collective-free — a
    controller that died simply never shows up, and the rollup proceeds
    over the files that exist (logged). Returns the rollup path, or
    None when not even this process's own export was found."""
    deadline = time.monotonic() + timeout_s
    expected = {
        os.path.join(directory, f"{_OBS_PREFIX}{pi}.json")
        for pi in range(num_processes)
    }
    while time.monotonic() < deadline:
        if all(os.path.exists(p) for p in expected):
            break
        time.sleep(0.05)
    missing = sorted(p for p in expected if not os.path.exists(p))
    if missing:
        logging.warning(
            "pod rollup: %d/%d controller export(s) missing after "
            "%.0fs (%s); merging the files that exist.", len(missing),
            num_processes, timeout_s,
            ", ".join(os.path.basename(p) for p in missing))
    merged = aggregate_directory(directory)
    if not merged["processes"]:
        return None
    return _atomic_json_write(
        os.path.join(directory, POD_ROLLUP_NAME), merged)


# ---------------------------------------------------------------------------
# Epoch reset (wired from telemetry.reset)
# ---------------------------------------------------------------------------


def reset_epoch() -> None:
    """Clears the odometer and byte-accounting watermarks and detaches
    the span memory sampler — telemetry.reset() calls this so ONE
    coordinated reset clears every observability surface together."""
    global _acct_live_bytes, _acct_peak_bytes, _odo_seq
    with _mem_lock:
        _acct_live_bytes = 0
        _acct_peak_bytes = 0
    with _odo_lock:
        _odo_records.clear()
        _odo_seq = 0
    disable_memory_sampling()
