"""One part of this run's set-up, from the program's own ledger
(``deepspeed_tpu.telemetry.compile_sentinel.setup_ledger``): the seconds of
``[origin, origin + setup_s]`` that went to ``part`` — ``import``,
``engine_init``, ``trace``, ``lower``, ``compile``, ``cache_load``, or
``unnamed``, the rest — or, with ``what: count``, the cache-miss events in
that stretch (``part: cache_misses``).  The origin is the first line of the
program's package, ``setup_s`` the run's own.  The parts are a partition:
together they may not exceed ``setup_s``, and a ledger in which they do is
an error, never clipped.  None where the program keeps no ledger (a parent
from before it), the run has no ``setup_s``, or the ledger no longer holds
the whole stretch.

The first call of a run also prints one ``setup:`` line: the parts, the five
programs that cost most to make runnable, and the traces that ended after
the cut (a program retraced inside the window).
"""

PARTS = ("import", "engine_init", "trace", "lower", "compile", "cache_load")
STAGES = ("trace", "lower", "compile", "cache_load")
SLACK_S = 1e-6


def _ledger(ctx):
    """The ledger over this run's set-up, asked for once a run."""
    if "setup_ledger" not in ctx:
        ctx["setup_ledger"] = _ask(ctx)
        if ctx["setup_ledger"] is not None:
            print(detail_line(ctx["setup_ledger"]), flush=True)
    return ctx["setup_ledger"]


def _ask(ctx):
    setup_s = ctx["result"].get("end_to_end", {}).get("setup_s")
    try:
        from deepspeed_tpu.telemetry import compile_sentinel
    except ImportError:
        return None
    ask = getattr(compile_sentinel, "setup_ledger", None)
    if ask is None or setup_s is None:
        return None
    origin = ask(0.0, 0.0)["origin"]
    if origin is None:
        return None
    return check(ask(origin, origin + setup_s), setup_s)


def check(ledger, setup_s):
    """The ledger if it partitions ``setup_s``; None if it holds no
    partition of the stretch; an error if its parts are no partition."""
    parts = ledger["parts"]
    if parts is None:
        return None
    low = [p for p in (*PARTS, "unnamed") if parts[p] < 0.0]
    named = sum(parts[p] for p in PARTS)
    if low or named > setup_s + SLACK_S:
        raise ValueError(
            f"set-up ledger is no partition of setup_s {setup_s}: parts "
            f"{parts} (negative: {low}; named parts sum to {named})")
    return dict(ledger, setup_s=setup_s)


def program(fun_name):
    """``jit(step)`` of the lowering and the compile is ``step``'s trace."""
    for wrap in ("jit(", "pmap("):
        if fun_name.startswith(wrap) and fun_name.endswith(")"):
            return fun_name[len(wrap):-1]
    return fun_name


def top_programs(ledger, n=5):
    """The ``n`` programs with the most trace + lowering + compile-or-load
    seconds: ``(name, trace_s, lower_s, compile_or_load_s, hit|miss)``."""
    by_program = {}
    for (part, fun_name), (_count, seconds) in ledger["programs"].items():
        if part in STAGES:
            row = by_program.setdefault(program(fun_name),
                                        dict.fromkeys(STAGES, 0.0))
            row[part] += seconds
    rows = sorted(by_program.items(), key=lambda kv: -sum(kv[1].values()))
    return [(name, r["trace"], r["lower"], r["compile"] + r["cache_load"],
             "miss" if r["compile"] > 0.0 else "hit") for name, r in rows[:n]]


def detail_line(ledger):
    parts = ledger["parts"]
    tops = "; ".join(f"{name} {t:.3f} + {lo:.3f} + {c:.3f} {cache}"
                     for name, t, lo, c, cache in top_programs(ledger))
    events = " ".join(f"{p} {n}" for p, n in ledger["events"].items() if n)
    return (f"setup: {ledger['setup_s']:.3f} s from the ledger's origin: "
            + ", ".join(f"{p} {parts[p]:.3f}" for p in (*PARTS, "unnamed"))
            + f"; cache misses {ledger['cache_misses']}; top programs "
            f"(trace + lower + compile or load, s): {tops}; "
            f"{ledger['traces_after']} traces ended after the cut; "
            f"listener events: {events}; {ledger['kept']} intervals kept")


def read(ctx, part, what="seconds"):
    ledger = _ledger(ctx)
    if ledger is None:
        return None
    if what == "count":
        if part != "cache_misses":
            raise ValueError(f"no count of {part!r}: only of cache_misses")
        return ledger["cache_misses"]
    if what != "seconds":
        raise ValueError(f"what is 'seconds' or 'count', not {what!r}")
    return ledger["parts"][part]
