"""The program's own named spans of a traced run, on the trace's clock.

``deepspeed_tpu/telemetry/spans.py`` enters a ``jax.profiler.TraceAnnotation``
for every span the program records, so a traced run's ``.xplane.pb`` holds
them on its host plane beside the benchmark's ``bench.*`` spans, on the same
clock as the device's operations.  ``trace_reduce.py`` keeps ``bench.*``
only; this keeps the host events whose name (cut at ``#``) is one of
``SERVE_SPANS``, the spans of the serving engine's step, clipped to the
window ``trace_reduce.Reduced.window()`` gives.  The runtime's own events
on that plane (``shard_args`` and the like) are left out by name.

A reader is handed the reduced trace, not the file it came from, so the file
is found where ``harness.Context.xplane_path()`` finds it — the newest
``out/trace-*/plugins/profile/*/*.xplane.pb`` beside this module; the result
line is made before ``Context.close()`` removes it — and parsed once a
process.  A program that records no such span (the parent of the PR that
added them) gives empty lists, and the readers then read nothing.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
from typing import Dict, List, Optional, Sequence

from benchmark.manifest import HERE
from benchmark.trace_reduce import Interval, Op

TRACES = os.path.join(HERE, "out", "trace-*", "plugins", "profile", "*",
                      "*.xplane.pb")
#: what ``InferenceEngineV2.step()`` records in and round a step
#: (``docs/OBSERVABILITY.md``, "Span names")
SERVE_SPANS = frozenset((
    "serve_step", "step_admit", "prefill", "decode", "multi_decode",
    "spec_propose", "spec_verify", "dispatch", "device_wait", "step_emit"))


class ProgramSpans:
    """Spans by name, each list sorted by start.  Spans of one name never
    overlap (the program nests spans of different names only)."""

    def __init__(self, spans: Sequence[Op]):
        self.by_name: Dict[str, List[Op]] = {}
        for sp in sorted(spans, key=lambda o: o.start):
            self.by_name.setdefault(sp.name, []).append(sp)
        self._starts = {n: [sp.start for sp in sps]
                        for n, sps in self.by_name.items()}

    def named(self, name: str) -> List[Op]:
        return self.by_name.get(name, [])

    def clipped(self, window: Interval) -> "ProgramSpans":
        """The spans that lie whole inside ``window``."""
        lo, hi = window
        return ProgramSpans([sp for sps in self.by_name.values() for sp in sps
                             if sp.start >= lo and sp.end <= hi])

    def inside(self, name: str, parent: Op) -> List[Op]:
        """The spans called ``name`` that lie inside ``parent``."""
        sps = self.named(name)
        i = bisect.bisect_left(self._starts.get(name, []), parent.start)
        out = []
        while i < len(sps) and sps[i].start < parent.end:
            if sps[i].end <= parent.end:
                out.append(sps[i])
            i += 1
        return out


def from_profile(profile) -> ProgramSpans:
    spans: List[Op] = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#")[0]
                if name in SERVE_SPANS:
                    start = ev.start_ns * 1e-9
                    spans.append(Op(name, start,
                                    start + ev.duration_ns * 1e-9))
    return ProgramSpans(spans)


@functools.lru_cache(maxsize=2)
def _parsed(path: str, mtime_ns: int) -> ProgramSpans:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def from_file(path: str) -> ProgramSpans:
    return _parsed(path, os.stat(path).st_mtime_ns)


def newest_trace() -> Optional[str]:
    found = glob.glob(TRACES)
    return max(found, key=os.path.getmtime) if found else None


def of_run(reduced) -> Optional[ProgramSpans]:
    """The program's spans inside the window of this run's reduced trace;
    None when the run left no trace file to read."""
    path = newest_trace()
    if path is None:
        return None
    return from_file(path).clipped(reduced.window())


def where_passes(spans: ProgramSpans, step: Op, where) -> bool:
    """``where``: ``{"has": [names], "lacks": [names]}`` — a step is chosen
    by the spans it holds (a decode-only step has ``decode``, lacks
    ``prefill``)."""
    if not where:
        return True
    return (all(spans.inside(n, step) for n in where.get("has", []))
            and not any(spans.inside(n, step) for n in where.get("lacks", [])))
