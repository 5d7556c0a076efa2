"""From a profiler trace (``.xplane.pb``) to device intervals, per-operation
seconds and the benchmark's own host spans — with nothing but JAX.

``jax.profiler.ProfileData.from_file`` reads the file: planes, their lines,
events with a start and a duration in nanoseconds.  On a TPU each chip is a
plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per executed
HLO operation, named by the instruction's whole text (``%fusion.3 = bf16[..]
fusion(..)``: the name kept here is ``fusion.3``; a Mosaic kernel is named
after its ``name=``, e.g. ``dstpu_flash_fwd.7``).  Control-flow operations —
``while``, ``conditional``, ``call`` — contain their bodies' events, so
per-operation seconds are *self* times.  ``Async XLA Ops`` holds the DMA side
of ``*-start`` / ``*-done`` pairs (copies, slices, collectives), which overlap
the compute line; ``XLA Modules`` holds one event per executed program
(``jit__train_batch_body(<hash>)``).  ``jax.profiler.TraceAnnotation`` spans
are events on the host plane's ``python3`` line, on the same clock.

    python -m benchmark.trace_reduce --dump <file.xplane.pb>

prints the structure of a trace: look at one by hand before writing a reader.
"""

from __future__ import annotations

import bisect
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench."
COLLECTIVE_PREFIXES = ("all-gather", "all-reduce", "reduce-scatter",
                       "all-to-all", "collective-permute",
                       "collective-broadcast")
NO_SPAN = "_no_span_"

Interval = Tuple[float, float]  # seconds, [start, end)


class Op:
    __slots__ = ("name", "start", "end", "self_s")

    def __init__(self, name: str, start: float, end: float):
        self.name, self.start, self.end = name, start, end
        self.self_s = end - start


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The part of a merged, sorted interval list inside [lo, hi)."""
    if not merged or hi <= lo:
        return []
    starts = [s for s, _ in merged]
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    out = []
    while i < len(merged) and merged[i][0] < hi:
        s, e = max(merged[i][0], lo), min(merged[i][1], hi)
        if e > s:
            out.append((s, e))
        i += 1
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Merged ``a`` minus merged ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(ops: List[Op]) -> None:
    """Subtract from every operation the time of the operations it contains
    (a ``while`` holds its body), so per-operation sums count no time twice."""
    stack: List[Op] = []
    eps = 2e-9  # timestamps are whole and half nanoseconds held as floats
    for op in sorted(ops, key=lambda o: (o.start, -(o.end - o.start))):
        while stack and stack[-1].end <= op.start + eps:
            stack.pop()
        if stack and op.end <= stack[-1].end + eps:
            stack[-1].self_s -= op.end - op.start
        stack.append(op)


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVE_PREFIXES)


def short_name(text: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..)`` -> ``fusion.3``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


class Reduced:
    """One trace, reduced.  Times are seconds on the trace's clock."""

    def __init__(self, device_ops: Dict[str, List[Op]],
                 modules: Dict[str, List[Op]],
                 spans: List[Op],
                 async_ops: Optional[Dict[str, List[Op]]] = None):
        self.device_ops = device_ops      # device plane name -> operations
        self.async_ops = async_ops or {}  # device plane name -> DMA side
        self.modules = modules            # device plane name -> module runs
        self.spans = sorted(spans, key=lambda o: o.start)  # bench.* spans
        for ops in device_ops.values():
            self_times(ops)
        self.busy = {d: merge((o.start, o.end) for o in ops)
                     for d, ops in device_ops.items()}

    # ------------------------------------------------------------ window
    def window(self) -> Interval:
        """From the first to the last of the benchmark's spans: the traced
        part of the measured window (set-up and tear-down left out)."""
        if self.spans:
            return (self.spans[0].start, max(o.end for o in self.spans))
        busy = [iv for iv in self.busy.values() if iv]
        if not busy:
            return (0.0, 0.0)
        return (min(iv[0][0] for iv in busy), max(iv[-1][1] for iv in busy))

    def window_seconds(self) -> float:
        lo, hi = self.window()
        return hi - lo

    def busy_seconds(self) -> float:
        """Seconds in which an operation ran, inside the window, averaged
        over the devices that ran any."""
        lo, hi = self.window()
        per = [total(clip(iv, lo, hi)) for iv in self.busy.values() if iv]
        return sum(per) / len(per) if per else 0.0

    def devices(self) -> List[str]:
        return sorted(d for d, ops in self.device_ops.items() if ops)

    # --------------------------------------------------------- selections
    def ops_in_window(self) -> List[Op]:
        lo, hi = self.window()
        return [o for d in self.devices() for o in self.device_ops[d]
                if o.start >= lo and o.end <= hi]

    def modules_in_window(self) -> List[Op]:
        """Runs of whole device programs (``XLA Modules``) inside the window."""
        lo, hi = self.window()
        return [m for d in self.devices() for m in self.modules.get(d, [])
                if m.start >= lo and m.end <= hi]

    def op_seconds(self, match) -> float:
        """Self seconds of the operations ``match(name)`` accepts, inside
        the window, averaged over devices."""
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(o.self_s for o in self.ops_in_window()
                   if match(o.name)) / len(devs)

    def span_list(self, name: str) -> List[Op]:
        return [o for o in self.spans if o.name == name]

    def busy_inside(self, lo: float, hi: float) -> float:
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(total(clip(self.busy[d], lo, hi)) for d in devs) / len(devs)

    def exposed_collective_seconds(self) -> float:
        """Collective time during which no other operation ran on that
        device, inside the window, averaged over devices."""
        lo, hi = self.window()
        devs = self.devices()
        out = 0.0
        for d in devs:
            ops = [o for o in self.device_ops[d]
                   if o.start >= lo and o.end <= hi]
            dma = [o for o in self.async_ops.get(d, [])
                   if o.start >= lo and o.end <= hi]
            coll = merge((o.start, o.end) for o in ops + dma
                         if is_collective(o.name))
            # leaves only: a while that contains a collective is not compute
            comp = merge((o.start, o.end) for o in ops
                         if not is_collective(o.name)
                         and abs(o.self_s - (o.end - o.start)) < 1e-9)
            out += total(subtract(coll, comp))
        return out / len(devs) if devs else 0.0

    # ---------------------------------------------------------- breakdown
    def idle_gaps(self) -> Dict[str, float]:
        """Idle seconds of the first device inside the window, by the
        benchmark span the host was in when the gap began (innermost)."""
        devs = self.devices()
        if not devs:
            return {}
        lo, hi = self.window()
        gaps = subtract([(lo, hi)], clip(self.busy[devs[0]], lo, hi))
        out: Dict[str, float] = {}
        for s, e in gaps:
            owner, width = NO_SPAN, None
            for sp in self.spans:
                if sp.start > s:
                    break
                if sp.end > s and (width is None
                                   or sp.end - sp.start < width):
                    owner, width = sp.name, sp.end - sp.start
            out[owner] = out.get(owner, 0.0) + (e - s)
        return out

    def breakdown(self) -> Dict[str, List[List[object]]]:
        per: Dict[str, float] = {}
        devs = self.devices()
        for o in self.ops_in_window():
            per[o.name] = per.get(o.name, 0.0) + o.self_s / len(devs)
        top = sorted(per.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle_gaps().items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _events(line) -> Iterable[Tuple[str, float, float]]:
    for ev in line.events:
        start = ev.start_ns * 1e-9
        yield ev.name, start, start + ev.duration_ns * 1e-9


def reduce_profile(profile) -> Reduced:
    device_ops: Dict[str, List[Op]] = {}
    async_ops: Dict[str, List[Op]] = {}
    modules: Dict[str, List[Op]] = {}
    spans: List[Op] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        Op(short_name(n), s, e) for n, s, e in _events(line))
                elif line.name == ASYNC_LINE:
                    async_ops.setdefault(plane.name, []).extend(
                        Op(short_name(n), s, e) for n, s, e in _events(line))
                elif line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        Op(n, s, e) for n, s, e in _events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Op(n.split("#")[0], s, e)
                             for n, s, e in _events(line)
                             if n.startswith(ANNOTATION_PREFIX))
    return Reduced(device_ops, modules, spans, async_ops)


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def dump(path: str, out=sys.stdout, per_line: int = 6) -> None:
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    for plane in prof.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines", file=out)
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events", file=out)
            seen = set()
            shown = 0
            for ev in evs:
                key = ev.name.split(".")[0][:40]
                if key in seen and not ("dstpu" in ev.name):
                    continue
                seen.add(key)
                stats = []
                try:
                    for k, v in ev.stats:
                        stats.append(f"{k}={str(v)[:80]}")
                except Exception as e:  # a stat type this JAX cannot decode
                    stats.append(f"<stats unreadable: {e}>")
                print(f"    {ev.name[:100]!r} start_ns={ev.start_ns:.0f} "
                      f"dur_ns={ev.duration_ns:.0f} {stats[:8]}", file=out)
                shown += 1
                if shown >= per_line * 6:
                    break


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--dump":
        dump(sys.argv[2])
    else:
        print(__doc__)
        sys.exit(1)
