"""Device-idle ms per step of the program (a span called ``per``), split
into the host's part (``host`` true) and the rest.

The serving engine waits (``after``: ``device_wait``) only for the program
it dispatched last, so when a wait returns the device has nothing left and
stays idle until the host's next program call (``until``: ``dispatch``)
begins, and for the launch latency after that.  The host's part of an idle
gap is that stretch from the end of a wait to the start of the next call:
sampling, emitting, the benchmark's loop between steps, admission, building
and uploading the next inputs.  The rest is the runtime's: completion
latency before the wait returned, launch latency after the call began, and
the hair-width seams between a running program's operations.  The two sum
to ``(window_s - busy_s) / steps``.

Host and device clocks of a trace differ by milliseconds, and by another
amount in every trace (three traces of one program read the end of a wait
1.4 ms after, 1.8 ms after and 1.7 ms *before* the device went idle), so
nothing here subtracts a host time from a device time.  A stretch is
measured on the host's clock alone and a gap on the device's alone; the
clocks only have to say which gap a stretch fell in.  For that the offset
is estimated first (``clock_offset``): every stretch lies inside one gap,
since the device idles from before the wait returns until after the call
begins, and the offset that puts the most stretches inside gaps is taken."""

import bisect

from benchmark import program_spans, trace_reduce

#: offsets tried, host clock less device clock: +-10 ms in steps of 0.1 ms.
#: The programs between two drains are tens of ms long, so no other drain
#: comes into reach
OFFSETS_S = [1e-4 * k for k in range(-100, 101)]


def clock_offset(stretches, idle):
    """The offset (host clock less device clock) at which the most of the
    host's ``stretches`` lie whole inside one of the device's ``idle``
    gaps; the middle one where several do as well."""
    starts = [a for a, _b in idle]

    def inside(s, e):
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and idle[i][1] >= e

    scores = [sum(inside(s - d, e - d) for s, e in stretches)
              for d in OFFSETS_S]
    best = [d for d, n in zip(OFFSETS_S, scores) if n == max(scores)]
    return best[len(best) // 2]


def _gap_of(idle, starts, s, e):
    """Index of the idle gap that overlaps [s, e) most; None if none does."""
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    best, most = None, 0.0
    while i < len(idle) and idle[i][0] < e:
        over = min(e, idle[i][1]) - max(s, idle[i][0])
        if over > most:
            best, most = i, over
        i += 1
    return best


def read(ctx, after, until, host, per="serve_step"):
    tr = ctx["trace"]
    spans = program_spans.of_run(tr)
    devs = tr.devices()
    if spans is None or not devs:
        return None
    steps = len(spans.named(per))
    calls = [sp.start for sp in spans.named(until)]
    if not steps or not calls or not spans.named(after):
        return None
    lo, hi = tr.window()
    idle = trace_reduce.subtract([(lo, hi)],
                                 trace_reduce.clip(tr.busy[devs[0]], lo, hi))
    stretches = []
    for wait in spans.named(after):
        j = bisect.bisect_left(calls, wait.end)
        if j < len(calls):
            stretches.append((wait.end, calls[j]))
    off = clock_offset(stretches, idle)
    starts = [a for a, _b in idle]
    hosts = {}  # index of an idle gap -> the host's seconds of it
    for s, e in stretches:
        i = _gap_of(idle, starts, s - off, e - off)
        if i is not None:
            a, b = idle[i]
            hosts[i] = min(b - a, hosts.get(i, 0.0) + e - s)
    secs = sum(hosts.values())
    if not host:
        secs = trace_reduce.total(idle) - secs
    return 1e3 * secs / steps
