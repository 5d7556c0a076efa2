"""Recompilation sentinel and set-up ledger.

Silent XLA recompilation is the TPU-specific failure mode host timers
cannot name: a steady-state training step that suddenly takes seconds is
indistinguishable from a stalled collective unless someone counts
compiles.  And set-up — what a process spends before its first useful
step — is paid at every start and restart, with nothing but a wall clock
to say where it went.  One ``jax.monitoring`` listener feeds both:

* every stage of making a program runnable is kept as an interval, under
  the program's ``fun_name``: ``jaxpr_trace`` (the Python trace),
  ``mlir_lower`` (lowering, Mosaic's included), and the backend event as
  ``xla_compile`` or ``cache_load``.  JAX 0.9.0 fires
  ``/jax/core/compile/backend_compile_duration`` on a persistent-cache
  HIT too, with the retrieval time: ``deepspeed_tpu_compiles_total``,
  the compile-time histogram and :func:`compile_counts` count both, and
  the span's name and its ``cache`` attribute (``"hit"`` / ``"miss"``)
  tell them apart — a program is a hit when its
  ``/jax/compilation_cache/cache_hits`` event came just before on that
  thread.  With ``package_import`` and the engines' ``*_engine_init``
  (:func:`setup_span`) these are the parts of :func:`setup_ledger`,
  which partitions any stretch of the process;
* compiles are attributed to *steps* through :class:`RecompileSentinel`:
  each engine feeds its step's arg-shape signature
  (``compile/backend.py:shape_signature``) to ``observe_step``, which
  classifies a compile as **expected** (a signature component never seen
  before, or an announced re-jit — ``expect_recompile``) or
  **steady-state** (same shapes, still recompiled: weak-type churn,
  donation mismatch, non-hashable static args) and warns loudly on the
  latter; the ``recompile`` event names the programs compiled.

Where ``jax.monitoring`` is unavailable (stripped builds), the sentinel
falls back to the shape signature alone: a never-seen signature counts
as one recompile; steady-state recompiles are then invisible, which the
sentinel reports once at construction.

Everything is host-side bookkeeping that runs when JAX traces, lowers or
compiles — in a steady window, never.  The ledger lives apart from the
span ring: clearing or overflowing the ring loses nothing.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import (Any, Dict, Hashable, Iterable, List, Optional, Tuple,
                    Union)

from ..utils.logging import logger
from .registry import MetricsRegistry, get_registry
from .spans import get_span_recorder, perf_to_us

#: compile times run sub-second (tiny CPU repro) to minutes (big TPU
#: programs) — the default latency buckets top out too low
COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                   60.0, 120.0, 300.0, 600.0)

#: the part of set-up each span belongs to (``setup_ledger``'s keys)
_PART_OF_SPAN = {
    "package_import": "import",
    "train_engine_init": "engine_init",
    "serve_engine_init": "engine_init",
    "jaxpr_trace": "trace",
    "mlir_lower": "lower",
    "xla_compile": "compile",
    "cache_load": "cache_load",
}
SETUP_PARTS = tuple(dict.fromkeys(_PART_OF_SPAN.values()))

#: intervals a part keeps: its first ``_KEEP`` (set-up comes first in a
#: process) and its latest ``_KEEP`` (what an enclosing interval that is
#: still to arrive may absorb).  Every eager ``jnp`` call is a program of
#: its own: the benchmark's cells keep 800 - 3,700 intervals over their
#: whole set-up, nearly all of them traces (PERF.md section 5)
_KEEP = 16384
#: a traced ``jnp`` primitive reports like a program; only traces this
#: long reach the span ring, so a recompile cannot flush the flight
#: recorder's history (the ledger keeps all of them)
_RING_MIN_TRACE_S = 1e-3


class _Part:
    """One part's time as a union of disjoint intervals in order of time,
    each ``(start, end, fun_name)`` on ``time.perf_counter``'s clock."""

    __slots__ = ("intervals", "cut", "seconds", "events", "event_seconds")

    def __init__(self):
        self.intervals: List[Tuple[float, float, str]] = []
        #: start of the first interval let go; None while all are kept
        self.cut: Optional[float] = None
        self.seconds = 0.0  # of the union
        self.events = 0  # as reported, nested ones too
        self.event_seconds = 0.0


_lock = threading.Lock()
_parts: Dict[str, _Part] = {p: _Part() for p in SETUP_PARTS}
#: (part, fun_name) -> [intervals, seconds] of the union's intervals
_totals: Dict[Tuple[str, str], List[float]] = {}
_origin: Optional[float] = None
_miss_stamps: List[float] = []  # the first _KEEP cache_misses events
_misses = 0
#: fun_name -> what its owner said of the program (``note_program``)
_notes: Dict[str, Dict[str, Any]] = {}
#: fun_names of the latest backend compiles, for the recompile event
_compiled: "deque[str]" = deque(maxlen=16)
_tls = threading.local()  # .hit: a cache_hits event awaits its program
#: compiles already attributed to some step by SOME sentinel: observe_step
#: claims its delta here so co-located loops (train + serve in one
#: process) never each count the same compile.  Attribution to the
#: *right* loop is still best-effort — the process-wide stream carries no
#: per-compile context — so a compile can land on whichever loop observes
#: first; it just cannot land twice.
_claimed = 0
_listener_ok: Optional[bool] = None  # None = not yet attempted
_published = -1  # the ledger's events when the gauge was last set

#: live sentinels, notified of announced re-jits (weak: engines own them)
_SENTINELS: "weakref.WeakSet[RecompileSentinel]" = weakref.WeakSet()


def _compiles() -> int:
    return _parts["compile"].events + _parts["cache_load"].events


def _keep(part: str, start: float, end: float, name: str) -> None:
    """Add one interval to ``part`` (caller holds the lock).  Intervals
    arrive as they END, so an outer trace arrives after the ``jnp``
    primitives and inner ``jit``s traced inside it, each of which reported
    too: it absorbs them, and the union holds one interval a program."""
    p = _parts[part]
    p.events += 1
    p.event_seconds += end - start
    ivs = p.intervals
    while ivs and ivs[-1][1] > start:
        s0, e0, n0 = ivs.pop()
        tot = _totals[part, n0]
        tot[0] -= 1
        tot[1] -= e0 - s0
        if not tot[0]:
            del _totals[part, n0]
        p.seconds -= e0 - s0
        if s0 < start:  # overlapped from another thread, not enclosed
            start, name = s0, n0
    ivs.append((start, end, name))
    tot = _totals.setdefault((part, name), [0, 0.0])
    tot[0] += 1
    tot[1] += end - start
    p.seconds += end - start
    if len(ivs) > 2 * _KEEP:
        dropped = ivs.pop(_KEEP)
        if p.cut is None:
            p.cut = dropped[0]


def setup_span(name: str, start: float, end: Optional[float] = None,
               **attrs) -> None:
    """Record ``[start, end]`` (``time.perf_counter`` stamps; ``end`` now
    when not given) as one interval of set-up: into the ledger under the
    span's part, and into the span ring when it is on.  The first
    ``package_import`` is the ledger's origin."""
    global _origin
    if end is None:
        end = time.perf_counter()
    part = _PART_OF_SPAN[name]
    with _lock:
        if name == "package_import" and _origin is None:
            _origin = start
        _keep(part, start, end, attrs.get("fun_name", name))
    if name == "jaxpr_trace" and end - start < _RING_MIN_TRACE_S:
        return
    rec = get_span_recorder()
    if rec.enabled:
        rec.record(name, perf_to_us(start), (end - start) * 1e6,
                   cat="setup" if part in ("import", "engine_init")
                   else "compile", seconds=end - start, **attrs)


def note_program(fun_name: str, **facts) -> None:
    """Keep ``facts`` with the ledger's entry of the program ``fun_name``
    (``setup_ledger()["notes"]``): what only its owner knows of it — the
    train step's recomputation policy and the temporaries XLA gave it."""
    with _lock:
        _notes.setdefault(fun_name, {}).update(facts)


def _on_duration_event(event: str, duration_secs: float, **kw) -> None:
    now = time.perf_counter()
    seconds = float(duration_secs)
    start = now - seconds
    fun = str(kw.get("fun_name", ""))
    try:
        stage = event.rpartition("/")[2]
        if stage == "jaxpr_trace_duration":
            setup_span("jaxpr_trace", start, now, fun_name=fun)
        elif stage == "jaxpr_to_mlir_module_duration":
            setup_span("mlir_lower", start, now, fun_name=fun)
        elif stage == "backend_compile_duration":
            hit = getattr(_tls, "hit", False)
            _tls.hit = False
            if hit:
                setup_span("cache_load", start, now, fun_name=fun,
                           cache="hit")
            else:
                setup_span("xla_compile", start, now, fun_name=fun,
                           cache="miss")
            _compiled.append(fun)
            reg = get_registry()
            reg.counter("deepspeed_tpu_compiles_total",
                        "XLA backend compiles observed via jax.monitoring "
                        "(persistent-cache loads included)").inc()
            reg.histogram("deepspeed_tpu_compile_seconds",
                          "wall time of each XLA backend compile or "
                          "persistent-cache load",
                          buckets=COMPILE_BUCKETS).observe(seconds)
    # dstpu-lint: allow[swallow] the listener runs inside jax's compile
    # path forever; a telemetry hiccup must never break compilation itself
    except Exception:
        pass


def _on_event(event: str, **_kw) -> None:
    global _misses
    if event == "/jax/compilation_cache/cache_hits":
        _tls.hit = True  # its backend_compile_duration comes next
    elif event == "/jax/compilation_cache/cache_misses":
        with _lock:
            _misses += 1
            if len(_miss_stamps) < _KEEP:
                _miss_stamps.append(time.perf_counter())


def install_compile_listener() -> bool:
    """Register the jax.monitoring listeners once per process; returns
    whether compile events are observable on this jax build."""
    global _listener_ok
    if _listener_ok is None:
        try:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_duration_event)
            jax.monitoring.register_event_listener(_on_event)
            _listener_ok = True
        except Exception as e:
            logger.warning(f"recompile sentinel: jax.monitoring unavailable "
                           f"({e}); falling back to arg-shape signatures "
                           f"(steady-state recompiles not detectable)")
            _listener_ok = False
    return _listener_ok


def compile_counts() -> Tuple[int, float]:
    """(process compile count, total compile seconds) so far: every
    backend compile event, a persistent-cache load among them."""
    with _lock:
        return _compiles(), (_parts["compile"].event_seconds
                             + _parts["cache_load"].event_seconds)


def compile_path_seconds() -> float:
    """Seconds the process has spent making programs runnable: the
    unions of trace, lowering and compile or cache load."""
    with _lock:
        return sum(_parts[p].seconds
                   for p in ("trace", "lower", "compile", "cache_load"))


def _partition(intervals: List[Tuple[float, float, str]], a: float,
               b: float) -> Dict[str, float]:
    """Seconds of ``[a, b]`` per part, an instant under several of the
    ``(start, end, part)`` intervals going to the one that began last."""
    out = dict.fromkeys(SETUP_PARTS, 0.0)
    edges = []
    for i, (s, e, _p) in enumerate(intervals):
        s, e = max(s, a), min(e, b)
        if s < e:
            edges.append((s, 1, i))
            edges.append((e, 0, i))
    edges.sort()
    open_: set = set()
    t0 = a
    for t, opens, i in edges:
        if open_ and t > t0:
            inner = max(open_, key=lambda j: intervals[j][0])
            out[intervals[inner][2]] += t - t0
        t0 = t
        (open_.add if opens else open_.discard)(i)
    out["unnamed"] = max(0.0, (b - a) - sum(out.values()))
    return out


def setup_ledger(a: Optional[float] = None,
                 b: Optional[float] = None) -> Dict[str, Any]:
    """The set-up ledger, read-only.  ``parts`` partitions the stretch
    ``[a, b]`` of the process (``time.perf_counter`` stamps; from the
    origin to now when not given): seconds per part — ``import``,
    ``engine_init``, ``trace``, ``lower``, ``compile``, ``cache_load`` —
    where an instant under several intervals goes to the innermost (a
    stage inside an engine's constructor is the stage's), so the parts
    never exceed ``b - a``, and ``unnamed`` is the rest.  ``parts`` and
    ``cache_misses`` (the counter over the same stretch) are None where
    the stretch reaches past what the ledger kept.  ``programs`` is the
    whole process's ``(part, fun_name) -> (intervals, seconds)``, ``notes``
    what the owners of programs said of them (:func:`note_program`) and
    ``traces_after`` counts the programs whose trace ended after ``b``;
    ``events`` is what the listener was called with, a part."""
    with _lock:
        origin = _origin
        kept = [(s, e, part) for part, p in _parts.items()
                for s, e, _n in p.intervals]
        trace_ends = [e for _s, e, _n in _parts["trace"].intervals]
        traces = sum(n for (part, _f), (n, _s) in _totals.items()
                     if part == "trace")
        cuts = [p.cut for p in _parts.values() if p.cut is not None]
        if len(_miss_stamps) < _misses:
            cuts.append(_miss_stamps[-1])
        stamps = list(_miss_stamps)
        programs = {k: (int(n), s) for k, (n, s) in _totals.items()}
        events = {part: p.events for part, p in _parts.items()}
        notes = {k: dict(v) for k, v in _notes.items()}
    a = (origin or 0.0) if a is None else a
    b = time.perf_counter() if b is None else b
    whole = not cuts or b <= min(cuts)
    return {
        "origin": origin,
        "parts": _partition(kept, a, b) if whole else None,
        "cache_misses": (sum(a <= t <= b for t in stamps) if whole
                         else None),
        "traces_after": (traces - sum(e <= b for e in trace_ends) if whole
                         else None),
        "programs": programs,
        "notes": notes,
        "events": events,
        "kept": len(kept),
    }


def publish_setup_seconds(registry: Optional[MetricsRegistry] = None) -> None:
    """``deepspeed_tpu_setup_seconds{part}``: the process so far by part
    (left as it was once the ledger no longer keeps the whole of it).
    Nothing to do, and nothing done, while no new interval has come: an
    engine calls this at every reporting boundary."""
    global _published
    with _lock:
        events = sum(p.events for p in _parts.values())
    if events == _published:
        return
    _published = events
    parts = setup_ledger()["parts"]
    if parts is None:
        return
    gauge = (registry or get_registry()).gauge(
        "deepspeed_tpu_setup_seconds",
        "seconds of this process spent in each part of set-up (import, "
        "engine_init, trace, lower, compile, cache_load), an instant "
        "counted once, for the innermost", labelnames=("part",))
    for part in SETUP_PARTS:
        gauge.set(parts[part], part=part)


def expect_recompile(reason: str = "") -> None:
    """Announce a deliberate re-jit (compile pass, batch-size change) to
    every live sentinel so the next step's compile is not flagged as a
    steady-state recompilation."""
    for s in list(_SENTINELS):
        s.expect_recompile(reason)


Signature = Union[Hashable, Iterable[Hashable]]


class RecompileSentinel:
    """Per-loop compile attribution over the process compile stream.

    ``observe_step(signature)`` once per step, AFTER the step's dispatch
    (host-side; the signature is built from arg shapes, never device
    values).  ``signature`` is one hashable token or an iterable of
    component tokens — a step whose work mixes programs (serving:
    prefill buckets + decode) passes the component set, so a new bucket
    alone explains a compile without resetting the whole signature."""

    def __init__(self, loop: str = "train",
                 registry: Optional[MetricsRegistry] = None,
                 steady_after: int = 3):
        self.loop = loop
        self.steady_after = max(0, int(steady_after))
        self.monitoring = install_compile_listener()
        reg = registry or get_registry()
        self._m_recompiles = reg.counter(
            "deepspeed_tpu_recompiles_total",
            "steps that triggered XLA compilation", labelnames=("loop",))
        self._m_steady = reg.counter(
            "deepspeed_tpu_steady_recompiles_total",
            "steady-state steps that recompiled with unchanged shapes",
            labelnames=("loop",))
        self._seen: set = set()
        #: steps since the last signature change or announced re-jit —
        #: NOT since the last recompile: the worst pathology (a recompile
        #: on EVERY step with unchanged shapes) must keep counting as
        #: steady, or it could never reach the warn threshold
        self._steady_steps = 0
        #: incident-edge latch: a sustained steady-recompile run counts
        #: every step but logs once (a stuck loop must not flood the log)
        self._in_steady = False
        self._expected: Optional[str] = None
        _SENTINELS.add(self)

    def expect_recompile(self, reason: str = "") -> None:
        global _claimed
        self._expected = reason or "announced"
        # pre-claim compiles up to the announcement: eager re-jit work
        # between now and the next step belongs to the announcement, for
        # every sentinel (compiles are a process-wide stream)
        with _lock:
            _claimed = _compiles()

    @staticmethod
    def _parts(signature: Signature) -> Tuple[Hashable, ...]:
        if isinstance(signature, (tuple, list, set, frozenset)):
            return tuple(signature)
        return (signature,)

    def observe_step(self, signature: Signature,
                     step: Optional[Any] = None) -> bool:
        """Record one step; True when the step triggered compilation."""
        global _claimed
        parts = self._parts(signature)
        new = [p for p in parts if p not in self._seen]
        self._seen.update(new)
        if self.monitoring:
            # claim this window's compiles so a co-located sentinel
            # cannot attribute the same ones to its own next step
            with _lock:
                delta = _compiles() - _claimed
                _claimed += delta
                programs = list(_compiled)[-delta:] if delta > 0 else []
            recompiled = delta > 0
        else:  # shape-signature fallback: a fresh shape implies a compile
            delta = len(new)
            programs = []
            recompiled = bool(new)
        expected = bool(new) or self._expected is not None
        if expected:
            # signature change / announced re-jit: restart the steady
            # window — compiles are explainable until it refills
            self._steady_steps = 0
        if not recompiled:
            self._steady_steps += 1
            self._in_steady = False
            self._expected = None
            return False
        self._m_recompiles.inc(loop=self.loop)
        rec = get_span_recorder()
        if rec.enabled:
            rec.event("recompile", cat="compile", loop=self.loop,
                      step=step, compiles=delta, expected=expected,
                      reason=(self._expected or
                              ("new_shapes" if new else "steady_state")),
                      signature=str(new or list(parts))[:256],
                      programs=",".join(programs)[:256])
        if not expected and self._steady_steps >= self.steady_after:
            self._m_steady.inc(loop=self.loop)
            if not self._in_steady:  # log the incident edge only
                logger.warning(
                    f"recompile sentinel [{self.loop}]: step"
                    f"{'' if step is None else ' ' + str(step)} triggered "
                    f"{delta} XLA compile(s) after {self._steady_steps} "
                    f"steady steps with UNCHANGED arg shapes "
                    f"{str(list(parts))[:256]} — suspect weak_type churn, "
                    f"donation/sharding mismatch, or non-hashable static "
                    f"args")
            self._in_steady = True
        # unchanged shapes: the steady window keeps growing THROUGH a
        # steady recompile, so an every-step recompile loop stays
        # counted instead of resetting itself below the threshold
        self._steady_steps += 1
        self._expected = None
        return True

    @property
    def recompiles(self) -> float:
        return self._m_recompiles.value(loop=self.loop)

    @property
    def steady_recompiles(self) -> float:
        return self._m_steady.value(loop=self.loop)
