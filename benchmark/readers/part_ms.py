"""Self seconds of XLA's own device operations — neither ``dstpu_*`` kernels
nor collectives: the population of ``xla_compute_ms_per_step`` — by the part
of the model that wrote them, inside the traced window, averaged over
devices, in ms.

The part of an operation is not in the trace (its event carries the
instruction's text and two times).  The program under test keeps, for every
program it dispatched, a table ``(instruction, result shape) -> (part, phase,
mixed)`` built from the compiled program's own text
(``deepspeed_tpu.telemetry.regions``, where a part is a *region*); this
reader asks for the tables in-process, as ``setup_part`` asks for the set-up
ledger, goes back to the trace file for the instructions' texts, as
``vocab_ops_ms`` does, and finds an operation's program from the ``XLA
Modules`` event that encloses it on its device.  An operation no table knows,
or whose key two programs of one name give to different parts, is
``unscoped``.  No table at all (a program from before the tables): no reading.

``parts``: the parts to sum (empty: any); ``phases``: of ``forward`` /
``backward`` / ``replay`` (empty: any); ``module_contains``: only operations
inside runs of programs whose name holds one of these.  Divided as ``op_ms``
/ ``module_ms`` / ``op_ms_per_unit`` divide: per event of ``per_span``; per
run of the ``module_contains`` programs (``per_module``); per unit of a
step-record sum (``span`` + ``per_keys`` x ``per_scale``).  ``share``: the
sum as a percentage of the whole population's (same ``module_contains``).

The first call of a run prints one ``parts:`` line: seconds to build the
tables and to re-read the trace, the share of time under ``mixed`` fusions,
every part's seconds, and the longest unscoped operations by their text.
"""

import bisect
import time

from benchmark import program_spans, trace_reduce

UNSCOPED = "unscoped"


class PartOp(trace_reduce.Op):
    __slots__ = ("text", "device", "program", "part", "phase", "mixed")


def _tables():
    """(the tables' merged index, the package's lookup) or None where the
    program keeps no tables or has noted no program."""
    try:
        from deepspeed_tpu.telemetry import regions
    except ImportError:
        return None
    index = regions.region_index()
    return (index, regions.lookup_region) if index else None


def _load(ctx):
    """Every operation of the population inside the window, its part found;
    once a run."""
    if "part_ops" in ctx:
        return ctx["part_ops"]
    ctx["part_ops"] = None
    tr = ctx["trace"]
    path = program_spans.newest_trace()
    if path is None or not tr.devices():
        return None
    t0 = time.perf_counter()
    found = _tables()
    t_tables = time.perf_counter() - t0
    if found is None:
        return None
    ops = load_ops(path, tr.window(), *found)
    t_read = time.perf_counter() - t0 - t_tables
    ctx["part_ops"] = ops
    print(detail_line(ops, len(tr.devices()), t_tables, t_read), flush=True)
    return ops


def load_ops(path, window, index, lookup):
    """The trace file's device operations inside ``window`` that are neither
    kernels nor collectives, with self times, programs and parts."""
    from jax.profiler import ProfileData

    lo, hi = window
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        ops, runs = [], []
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    op = PartOp(trace_reduce.short_name(ev.name), start,
                                start + ev.duration_ns * 1e-9)
                    op.text, op.device = ev.name, plane.name
                    ops.append(op)
            elif line.name == trace_reduce.MODULES_LINE:
                runs.extend((ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                            for ev in line.events)
        trace_reduce.self_times(ops)
        runs.sort()
        starts = [r[0] for r in runs]
        for op in ops:
            if (op.start < lo or op.end > hi or "dstpu_" in op.name
                    or trace_reduce.is_collective(op.name)):
                continue
            i = bisect.bisect_right(starts, op.start) - 1
            op.program = (runs[i][2] if i >= 0 and op.start < runs[i][1]
                          else "")
            op.part, op.phase, op.mixed = lookup(index, op.program, op.text)
            out.append(op)
    return out


def detail_line(ops, n_devices, t_tables, t_read, top=10):
    by_part, unscoped = {}, {}
    mixed = whole = 0.0
    for op in ops:
        by_part[op.part] = by_part.get(op.part, 0.0) + op.self_s
        whole += op.self_s
        if op.mixed:
            mixed += op.self_s
        if op.part == UNSCOPED:
            key = (op.program.split("(")[0], op.text[:160])
            unscoped[key] = unscoped.get(key, 0.0) + op.self_s
    parts = ", ".join(f"{p} {s / n_devices:.4f}" for p, s in
                      sorted(by_part.items(), key=lambda kv: -kv[1]))
    longest = "; ".join(f"{s / n_devices:.4f} s {prog}: {text}" for
                        (prog, text), s in
                        sorted(unscoped.items(), key=lambda kv: -kv[1])[:top])
    return (f"parts: tables in {t_tables:.3f} s, trace re-read in "
            f"{t_read:.3f} s; {len(ops)} operations, "
            f"{whole / n_devices:.4f} s a device, "
            f"{100.0 * mixed / whole if whole else 0.0:.2f} % under mixed "
            f"fusions; seconds a device by part: {parts}; longest unscoped: "
            f"{longest}")


def read(ctx, parts=(), phases=(), module_contains=(), per_span=None,
         per_module=False, span=None, per_keys=(), per_scale=1.0,
         share=False):
    ops = _load(ctx)
    tr = ctx["trace"]
    n_dev = len(tr.devices())
    if ops is None or not n_dev:
        return None
    if module_contains:
        ops = [o for o in ops if any(c in o.program for c in module_contains)]
    mine = [o for o in ops if (not parts or o.part in parts)
            and (not phases or o.phase in phases)]
    secs = sum(o.self_s for o in mine) / n_dev
    if share:
        whole = sum(o.self_s for o in ops) / n_dev
        return 100.0 * secs / whole if whole else None
    if per_span is not None:
        units = len(tr.span_list(per_span))
    elif per_module:
        units = sum(any(c in m.name for c in module_contains)
                    for m in tr.modules_in_window()) / n_dev
    else:
        spans = tr.span_list(span)
        steps = ctx["result"].get("steps", [])[:len(spans)]
        units = sum(s.get(k, 0) for s in steps for k in per_keys) * per_scale
    if not mine or not units:
        return None  # the cell's programs have no such part: no reading
    return 1e3 * secs / units
