"""Measured step-time attribution (docs/OBSERVABILITY.md
"Step-time attribution & goodput").

Periodically (every ``telemetry.timeline.every_n_steps``; off the hot
path — only the captured step pays) captures a ``jax.profiler`` trace of
ONE step, parses the device trace events into categories, and publishes
a **measured** per-step decomposition:

* ``deepspeed_tpu_timeline_category_seconds{category}`` — where the
  step's wall went: compute by the model region that wrote it
  (``regions.REGIONS``: ``mlp``, ``attn_qkv``, ``head``, ``optimizer``
  ..., read from the compiled programs' own region tables;
  ``unscoped`` what no table knows, never a guess by name), the Mosaic
  kernels (``attention``, or a kernel's own name), each collective kind
  (``all_reduce``, ``all_gather``, ``reduce_scatter``, ``all_to_all``,
  ``collective_permute``), ``host_gap`` (wall − device busy), and
  ``pipe_bubble`` (the structural bubble share carved out of the gap
  when a pipe schedule runs). Every trace instant is attributed to
  exactly ONE category (overlapped collectives attribute to the compute
  hiding them), so the categories sum to the step wall.
* measured overlapped-vs-exposed collective seconds — the counterpart
  to the *structural* ``deepspeed_tpu_train_overlapped_fraction``
  (telemetry/overlap.py models it; this measures it).
* a per-capture Chrome-trace artifact merging the host span ring and
  the device ops into ONE Perfetto file.

Graceful fallback: when the profiler yields no device trace (CPU /
interpreter — the XLA op timeline is populated on TPU/GPU backends
only), the capture falls back to the span-derived host timeline and
stamps ``measured: false``. A capture NEVER crashes or re-raises into a
step: trace stop, parse, artifact write and metric publish are each
exception-isolated, and a flight dump taken mid-capture sees the last
*completed* record (never a torn in-progress one).
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .regions import REGIONS, UNSCOPED, lookup_region, program_name

#: compute categories shadow collectives in the sweep: a collective
#: running under compute is *overlapped* (hidden) and the instant
#: belongs to the compute hiding it.  Compute is the attention kernels,
#: the model regions (telemetry/regions.py) and what no region table
#: knows; a Mosaic kernel that is not attention is a category of its own
#: name (``grouped_matmul``, ``kda_step``), compute like these
COMPUTE_CATEGORIES = ("attention",) + REGIONS + (UNSCOPED,)
COLLECTIVE_CATEGORIES = ("all_reduce", "all_gather", "reduce_scatter",
                         "all_to_all", "collective_permute")
CATEGORY_PRIORITY = COMPUTE_CATEGORIES + COLLECTIVE_CATEGORIES
#: every category a record (measured or fallback) may carry, the kernels'
#: own names aside
ALL_CATEGORIES = CATEGORY_PRIORITY + ("host_compute", "host_gap",
                                      "pipe_bubble")

#: the attention kernels, by the names they have in a device trace
_ATTENTION_PAT = ("flash", "splash", "paged_attn", "paged_decode",
                  "window_decode", "mla_decode")
_KERNEL_PREFIX = "dstpu_"


def categorize_op(name: str, region: str = UNSCOPED) -> str:
    """Map one device trace-event (HLO op) name to a category: a
    collective by its opcode, a Mosaic kernel by its name, anything else
    the ``region`` its program's table gives it — ``unscoped`` where no
    table knows it, never a guess: on a TPU a gemm is ``fusion.167``.
    """
    n = str(name).lower()
    # collectives first: a fusion name can embed "dot" AND "all-reduce",
    # and the collective is the scarcer signal
    for pat, cat in (("all-reduce", "all_reduce"), ("all_reduce", "all_reduce"),
                     ("allreduce", "all_reduce"),
                     ("all-gather", "all_gather"), ("all_gather", "all_gather"),
                     ("allgather", "all_gather"),
                     ("reduce-scatter", "reduce_scatter"),
                     ("reduce_scatter", "reduce_scatter"),
                     ("all-to-all", "all_to_all"), ("all_to_all", "all_to_all"),
                     ("alltoall", "all_to_all"),
                     ("collective-permute", "collective_permute"),
                     ("collective_permute", "collective_permute"),
                     ("ppermute", "collective_permute")):
        if pat in n:
            return cat
    if any(p in n for p in _ATTENTION_PAT):
        return "attention"
    if n.startswith(_KERNEL_PREFIX):
        return n[len(_KERNEL_PREFIX):].split(".")[0]
    return region


def decompose_events(events: Sequence[Dict[str, Any]], wall_s: float,
                     pipe_bubble_fraction: float = 0.0,
                     regions: Optional[Dict] = None) -> Dict[str, Any]:
    """Attribute a step's wall clock over device trace events.

    ``events``: ``{"name", "ts", "dur"}`` dicts in SECONDS (any common
    epoch); with ``regions`` (``regions.region_index()``) an event's
    ``"program"`` and ``"text"`` — the run of the program that encloses
    it and the instruction's whole text, as ``parse_xplane`` leaves them
    — find its compute category in its program's region table. Interval
    sweep, each instant attributed to exactly one category
    (:data:`CATEGORY_PRIORITY` order, a kernel's own name after
    ``attention`` — compute shadows collectives), so ``sum(categories)
    == wall_s`` by construction (``host_gap`` is the uncovered
    remainder; if device busy exceeds the host wall — clock skew —
    everything is scaled down by ``scale``).
    """
    wall_s = max(0.0, float(wall_s))
    points: List[Tuple[float, int, str]] = []
    raw_busy: Dict[str, float] = {}
    for ev in events:
        dur = float(ev.get("dur", 0.0) or 0.0)
        if dur <= 0:
            continue
        ts = float(ev.get("ts", 0.0) or 0.0)
        cat = categorize_op(ev.get("name", ""), event_region(ev, regions))
        raw_busy[cat] = raw_busy.get(cat, 0.0) + dur
        points.append((ts, +1, cat))
        points.append((ts + dur, -1, cat))
    kernels = sorted(set(raw_busy) - set(CATEGORY_PRIORITY))
    priority = (COMPUTE_CATEGORIES[:1] + tuple(kernels)
                + CATEGORY_PRIORITY[1:])
    categories = {c: 0.0 for c in priority}
    busy_union = coll_union = exposed_coll = 0.0
    if points:
        points.sort(key=lambda p: (p[0], -p[1]))
        active = {c: 0 for c in priority}
        n_compute = n_coll = 0
        prev = points[0][0]
        for t, delta, cat in points:
            seg = t - prev
            if seg > 0 and (n_compute or n_coll):
                busy_union += seg
                for c in priority:
                    if active[c]:
                        categories[c] += seg
                        break
                if n_coll:
                    coll_union += seg
                    if not n_compute:
                        exposed_coll += seg
            prev = t
            active[cat] += delta
            if cat in COLLECTIVE_CATEGORIES:
                n_coll += delta
            else:
                n_compute += delta
    scale = 1.0
    if busy_union > wall_s > 0:
        scale = wall_s / busy_union
        categories = {c: v * scale for c, v in categories.items()}
        busy_union, coll_union, exposed_coll = (
            busy_union * scale, coll_union * scale, exposed_coll * scale)
    host_gap = max(0.0, wall_s - busy_union)
    bubble = 0.0
    if pipe_bubble_fraction > 0:
        # the measured gap, split by the structural (P-1)/(M+P-1) claim:
        # a pipe bubble IS device idleness, so it can only come out of
        # the measured gap — never exceed it
        bubble = min(host_gap, pipe_bubble_fraction * wall_s)
        host_gap -= bubble
    categories["pipe_bubble"] = bubble
    categories["host_gap"] = host_gap
    return {
        "categories": categories,
        "collective_busy_seconds": {k: v * scale for k, v in raw_busy.items()
                                    if k in COLLECTIVE_CATEGORIES},
        "exposed_collective_seconds": exposed_coll,
        "overlapped_collective_seconds": max(0.0, coll_union - exposed_coll),
        "device_busy_seconds": busy_union,
        "scale": scale,
    }


def event_region(ev: Dict[str, Any], regions: Optional[Dict]) -> str:
    """The region of a device event by its program's table; ``unscoped``
    without a table or without the event's program and text."""
    if regions is None or "text" not in ev:
        return UNSCOPED
    return lookup_region(regions, ev.get("program", ""), ev["text"])[0]


# ---------------------------------------------------------- xplane parse
#: control-flow operations contain their bodies' events: counted, a
#: ``while`` (compute) would shadow every collective inside it
_CONTAINER_OPS = ("while", "conditional", "call")


def _device_trace_events(log_dir: str) -> Tuple[List[Dict[str, Any]],
                                                List[Dict[str, Any]]]:
    """The newest ``xplane.pb`` under ``log_dir``, parsed; ``([], [])``
    when there is none — the caller treats that as "no device trace" and
    falls back."""
    planes = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                              recursive=True), key=os.path.getmtime)
    return parse_xplane(planes[-1]) if planes else ([], [])


def parse_xplane(path: str) -> Tuple[List[Dict[str, Any]],
                                     List[Dict[str, Any]]]:
    """One ``.xplane.pb``, read with ``jax.profiler.ProfileData``, as
    normalized device events (seconds) plus Chrome events for the merged
    artifact; ``([], [])`` when it holds no device plane.

    A device is a plane ``/device:<kind>:<n>`` other than the host CPU's.
    On a TPU its line ``XLA Ops`` holds one event per executed HLO
    operation, named by the instruction's text (``%fusion.3 = bf16[..]
    fusion(..)``: the name kept is ``fusion.3``), and ``Async XLA Ops``
    the DMA side of asynchronous collectives, which overlaps compute;
    a backend without those lines contributes every line of the plane.
    ``XLA Modules`` holds one event per executed program: an operation's
    event carries the name of the run that encloses it (``"program"``)
    and the instruction's whole text (``"text"``), which is what finds
    it in its program's region table (telemetry/regions.py)."""
    import bisect

    from jax.profiler import ProfileData

    events, artifact = [], []
    profile = ProfileData.from_file(path)
    for pid, plane in enumerate(profile.planes):
        name = plane.name
        if not name.startswith("/device:") or ":CPU" in name.upper():
            continue
        lines = list(plane.lines)
        has_ops = any(ln.name == "XLA Ops" for ln in lines)
        runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ln in lines if ln.name == "XLA Modules"
                      for ev in ln.events)
        starts = [r[0] for r in runs]
        artifact.append({"ph": "M", "name": "process_name", "pid": pid,
                         "args": {"name": name}})
        for tid, line in enumerate(lines):
            if has_ops and line.name not in ("XLA Ops", "Async XLA Ops"):
                continue
            dma = line.name == "Async XLA Ops"
            artifact.append({"ph": "M", "name": "thread_name", "pid": pid,
                             "tid": tid, "args": {"name": line.name}})
            for ev in line.events:
                op = ev.name.split(" = ", 1)[0].strip().lstrip("%")
                if ev.duration_ns <= 0 or op.split(".")[0] in _CONTAINER_OPS:
                    continue
                if dma and categorize_op(op) not in COLLECTIVE_CATEGORIES:
                    continue  # an async copy is a DMA, not compute
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                run = (runs[i][2] if i >= 0 and ev.start_ns < runs[i][1]
                       else "")
                artifact.append({"ph": "X", "name": op, "pid": pid,
                                 "tid": tid, "ts": ev.start_ns / 1e3,
                                 "dur": ev.duration_ns / 1e3,
                                 "args": {"program": program_name(run)}})
                events.append({"name": op, "ts": ev.start_ns / 1e9,
                               "dur": ev.duration_ns / 1e9,
                               "program": run, "text": ev.name})
    return (events, artifact) if events else ([], [])


# ----------------------------------------------------- last-record slot
_last_lock = threading.Lock()
_last_record: Optional[Dict[str, Any]] = None


def last_timeline_record() -> Optional[Dict[str, Any]]:
    """The last COMPLETED capture record, process-wide (flight-dump
    hook; an in-progress capture is never visible here)."""
    with _last_lock:
        return dict(_last_record) if _last_record is not None else None


def _set_last_record(rec: Dict[str, Any]) -> None:
    global _last_record
    with _last_lock:
        _last_record = rec


class StepTimeline:
    """Cadence-gated profiler capture of single steps.

    Constructed by ``Telemetry`` from ``telemetry.timeline``; the serving
    engine builds one directly (it takes no telemetry block). All
    ``deepspeed_tpu_timeline_*`` metrics are single-owner HERE.
    """

    def __init__(self, every_n_steps: int = 0, artifact_dir: str = "",
                 registry=None):
        if registry is None:
            from .registry import get_registry

            registry = get_registry()
        self.every_n_steps = max(0, int(every_n_steps))
        self.artifact_dir = artifact_dir
        self._force = False
        self._active = False
        self._my_last: Optional[Dict[str, Any]] = None
        #: every category published so far (a kernel's own name among them)
        self._published: set = set()
        self._m_cat = registry.gauge(
            "deepspeed_tpu_timeline_category_seconds",
            "measured step-time decomposition from the last profiler "
            "capture: seconds of the step wall attributed to each "
            "category (categories sum to the step wall)",
            labelnames=("category",))
        self._m_exposed = registry.gauge(
            "deepspeed_tpu_timeline_exposed_collective_seconds",
            "MEASURED collective seconds not overlapped by compute in "
            "the last captured step (counterpart to the structural "
            "deepspeed_tpu_train_overlapped_fraction model)")
        self._m_overlapped = registry.gauge(
            "deepspeed_tpu_timeline_overlapped_collective_seconds",
            "MEASURED collective seconds hidden under compute in the "
            "last captured step")
        self._m_measured = registry.gauge(
            "deepspeed_tpu_timeline_measured",
            "1 when the last capture parsed a device trace, 0 when it "
            "fell back to the span-derived host timeline (CPU/interpreter)")
        self._m_captures = registry.counter(
            "deepspeed_tpu_timeline_captures_total",
            "timeline captures taken, by whether a device trace was "
            "parsed (measured=true) or the host fallback ran",
            labelnames=("measured",))
        self._m_overhead = registry.counter(
            "deepspeed_tpu_timeline_capture_overhead_seconds_total",
            "cumulative seconds spent starting/stopping/parsing profiler "
            "captures (the bounded-overhead contract, made observable)")

    # ------------------------------------------------------------ cadence
    def should_capture(self, step: int) -> bool:
        if self._active:
            return False
        if self._force:
            return True
        return self.every_n_steps > 0 and step % self.every_n_steps == 0

    def force_next(self) -> None:
        """Arm a one-shot capture regardless of cadence."""
        self._force = True

    def last_record(self) -> Optional[Dict[str, Any]]:
        """This timeline's own last completed record (None before the
        first capture; see :func:`last_timeline_record` for the
        process-wide slot the flight recorder reads)."""
        return dict(self._my_last) if self._my_last is not None else None

    # ------------------------------------------------------------ capture
    @contextlib.contextmanager
    def capture(self, step: int, pipe_struct: Optional[Dict[str, Any]] = None,
                sync: Optional[Callable[[], None]] = None,
                regions: Optional[Callable[[], Dict]] = None):
        """Wrap ONE step. Exception-safe: the profiler trace is always
        stopped, an exception inside the step propagates unchanged (no
        half-step record is published), and no lock is held while user
        code runs — a flight dump mid-capture cannot deadlock.
        ``regions``: what the engine hands over to name compute by model
        region (``regions.region_index``), asked after the step, on a
        captured step only: the first ask builds the noted programs'
        tables, inside the capture's overhead."""
        if self._active:
            yield
            return
        self._active = True
        self._force = False
        from .spans import _now_us
        from .tracing import start_trace, stop_trace

        overhead_t0 = time.perf_counter()
        tmpdir = tempfile.mkdtemp(prefix="dstpu_timeline_")
        started = False
        try:
            started = start_trace(tmpdir)
        except Exception:
            started = False
        t0 = time.perf_counter()
        t0_us = _now_us()
        ok = False
        try:
            yield
            ok = True
        finally:
            try:
                if sync is not None:
                    sync()
            # dstpu-lint: allow[swallow] the device sync only tightens
            # the capture window; a failed sync still yields a usable
            # (slightly host-skewed) record and must not fail the step
            except Exception:
                pass
            wall = time.perf_counter() - t0
            t1_us = _now_us()
            if started:
                stop_trace()  # swallows its own failures
            try:
                if ok:
                    self._finish(step, wall, t0_us, t1_us,
                                 tmpdir if started else None, pipe_struct,
                                 overhead_t0, regions)
            # dstpu-lint: allow[swallow] attribution must never fail the
            # step it measures; a failed parse leaves the prior record
            except Exception:
                pass
            shutil.rmtree(tmpdir, ignore_errors=True)
            self._active = False

    def _finish(self, step: int, wall: float, t0_us: float, t1_us: float,
                trace_dir: Optional[str], pipe_struct,
                overhead_t0: float, regions=None) -> None:
        bubble = 0.0
        if pipe_struct:
            try:
                bubble = float(pipe_struct.get("bubble_fraction", 0.0) or 0.0)
            except Exception:
                bubble = 0.0
        events: List[Dict[str, Any]] = []
        artifact_events: List[Dict[str, Any]] = []
        if trace_dir is not None:
            try:
                events, artifact_events = _device_trace_events(trace_dir)
            except Exception:
                events, artifact_events = [], []
        measured = bool(events)
        if measured:
            index = None
            if regions is not None:
                try:
                    index = regions()
                except Exception:
                    index = None  # compute then reads ``unscoped``
            dec = decompose_events(events, wall, pipe_bubble_fraction=bubble,
                                   regions=index)
            record = {"step": step, "measured": True, "wall_seconds": wall,
                      **dec}
            # the artifact names each device operation ``<category>:<name>``
            ops = (a for a in artifact_events if a.get("ph") == "X")
            for ev, shown in zip(events, ops):
                shown["name"] = (categorize_op(ev["name"],
                                               event_region(ev, index))
                                 + ":" + ev["name"])
        else:
            record = {"step": step, "measured": False, "wall_seconds": wall,
                      "categories": self._host_fallback(wall, t0_us, t1_us),
                      "exposed_collective_seconds": None,
                      "overlapped_collective_seconds": None}
        record["ts"] = time.time()
        record["artifact"] = self._write_artifact(step, t0_us, t1_us,
                                                  artifact_events)
        # publish: zero every known category first so a fallback capture
        # doesn't leave stale measured numbers standing next to it
        for c in self._published.union(ALL_CATEGORIES):
            self._m_cat.set(0.0, category=c)
        for c, v in record["categories"].items():
            self._m_cat.set(v, category=c)
        self._published.update(record["categories"])
        self._m_measured.set(1.0 if measured else 0.0)
        if measured:
            self._m_exposed.set(record["exposed_collective_seconds"])
            self._m_overlapped.set(record["overlapped_collective_seconds"])
        self._m_captures.inc(measured="true" if measured else "false")
        overhead = max(0.0, (time.perf_counter() - overhead_t0) - wall)
        record["capture_overhead_seconds"] = overhead
        self._m_overhead.inc(overhead)
        self._my_last = record
        _set_last_record(record)

    def _host_fallback(self, wall: float, t0_us: float,
                       t1_us: float) -> Dict[str, float]:
        """Span-derived host timeline: union of span coverage inside the
        captured window vs the uncovered gap. Sums to wall exactly."""
        covered = 0.0
        try:
            from .spans import get_span_recorder

            ivals = []
            for sp in get_span_recorder().spans():
                a = max(sp.ts_us, t0_us)
                b = min(sp.ts_us + sp.dur_us, t1_us)
                if b > a:
                    ivals.append((a, b))
            ivals.sort()
            cur_a = cur_b = None
            for a, b in ivals:
                if cur_b is None or a > cur_b:
                    if cur_b is not None:
                        covered += cur_b - cur_a
                    cur_a, cur_b = a, b
                else:
                    cur_b = max(cur_b, b)
            if cur_b is not None:
                covered += cur_b - cur_a
            covered = min(wall, covered / 1e6)
        except Exception:
            covered = 0.0
        return {"host_compute": covered, "host_gap": max(0.0, wall - covered)}

    def _write_artifact(self, step: int, t0_us: float, t1_us: float,
                        device_events: List[Dict[str, Any]]) -> Optional[str]:
        """ONE Perfetto file per capture: the span ring's host events
        (window-filtered) merged with the device ops, device timestamps
        re-based onto the span clock."""
        if not self.artifact_dir:
            return None
        try:
            from .spans import get_span_recorder

            margin = 2e5  # 200 ms of pre/post context around the step
            host = [ev for ev in get_span_recorder().trace_events()
                    if t0_us - margin <= float(ev.get("ts", 0)) <= t1_us + margin]
            merged = list(host)
            xs = [float(ev["ts"]) for ev in device_events
                  if ev.get("ph") == "X" and "ts" in ev]
            offset = (t0_us - min(xs)) if xs else 0.0
            for ev in device_events:
                ev = dict(ev)
                ev["pid"] = 1000000 + int(ev.get("pid", 0) or 0)
                if "ts" in ev:
                    ev["ts"] = float(ev["ts"]) + offset
                merged.append(ev)
            os.makedirs(self.artifact_dir, exist_ok=True)
            path = os.path.join(self.artifact_dir,
                                f"timeline_step{int(step):08d}.json")
            with open(path, "w") as f:
                json.dump({"displayTimeUnit": "ms", "traceEvents": merged}, f)
            return path
        except Exception:
            return None


def capture_thunk(fn: Callable[[], Any], step: int = 0,
                  timeline: Optional[StepTimeline] = None,
                  pipe_struct: Optional[Dict[str, Any]] = None,
                  sync: Optional[Callable[[], None]] = None,
                  artifact_dir: str = "",
                  regions: Optional[Callable[[], Dict]] = None
                  ) -> Tuple[Any, Optional[Dict[str, Any]]]:
    """One-shot attribution of an arbitrary callable (a caller that owns
    no engine-side timeline). Returns
    ``(fn(), record)``; the record is None only if the capture machinery
    itself failed."""
    tl = timeline if timeline is not None else StepTimeline(
        every_n_steps=0, artifact_dir=artifact_dir)
    tl.force_next()
    with tl.capture(step, pipe_struct=pipe_struct, sync=sync,
                    regions=regions):
        out = fn()
    return out, tl.last_record()
