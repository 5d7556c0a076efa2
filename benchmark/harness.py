"""What every generator shares: the run's context, the profiler window, the
benchmark's own annotations, seeds, counters and the result line.

Nothing here knows a cell, a configuration or a metric by name.
"""

from __future__ import annotations

import gc
import glob
import os
import shutil
import time
from typing import Any, Dict, List, Optional

import jax

MASK31 = 0x7FFFFFFF


def seed31(seed: int) -> int:
    """Any non-negative seed folded into 31 bits (JAX keys and the program's
    config take a signed 32-bit seed; the driver's seeds are larger)."""
    s = int(seed)
    out = 0
    while s:
        out ^= s & MASK31
        s >>= 31
    return out


def place_compile_cache() -> None:
    """JAX's persistent compilation cache at the place the program fixed
    (PR 21): ``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache/`` in
    the checkout.  Every program is cached, whatever it cost to compile, so
    a cell's second run in a checkout compiles nothing."""
    from deepspeed_tpu.utils.platform import ensure_compile_cache

    placed = ensure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    where = placed or jax.config.jax_compilation_cache_dir or "off (not a tpu)"
    print(f"benchmark: compile cache at {where}", flush=True)


def install_compile_counter() -> None:
    from deepspeed_tpu.telemetry.compile_sentinel import \
        install_compile_listener

    if not install_compile_listener():
        raise RuntimeError("jax.monitoring compile events are not observable")


def compiles() -> int:
    from deepspeed_tpu.telemetry.compile_sentinel import compile_counts

    return compile_counts()[0]


def annotate(name: str):
    """The benchmark's own host span in the profiler's trace."""
    return jax.profiler.TraceAnnotation(name)


class GcWatch:
    """Times the interpreter's garbage collections while it is open, so a
    long step or block can be told from one the collector held up: a pause
    is ``(start, seconds, generation)`` on ``time.perf_counter``'s clock."""

    def __init__(self):
        self.pauses: List[tuple] = []
        self._t0 = 0.0

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((self._t0, time.perf_counter() - self._t0,
                                info["generation"]))

    def start(self) -> "GcWatch":
        gc.callbacks.append(self._on_gc)
        return self

    def stop(self) -> None:
        gc.callbacks.remove(self._on_gc)

    def inside(self, lo: float, hi: float) -> float:
        """Seconds of collection inside [lo, hi]."""
        return sum(max(0.0, min(hi, t + d) - max(lo, t))
                   for t, d, _ in self.pauses)

    def summary(self) -> str:
        if not self.pauses:
            return "no garbage collection"
        worst = max(self.pauses, key=lambda p: p[1])
        return (f"{len(self.pauses)} garbage collections, "
                f"{1e3 * sum(p[1] for p in self.pauses):.1f} ms in all, "
                f"longest {1e3 * worst[1]:.1f} ms (generation {worst[2]})")


class Context:
    """One run of one cell."""

    def __init__(self, manifest, cell, config, traffic, seed, seconds, trace,
                 rehearse, devices, t_process_start, out_dir):
        self.manifest = manifest
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.rehearse = bool(rehearse)
        self.devices = list(devices)
        self.t_process_start = t_process_start
        self.out_dir = out_dir
        self.trace_dir = os.path.join(
            out_dir, f"trace-{cell['name']}") if trace else None
        self._tracing = False
        self._detail: List[str] = []
        self.t_trace0: Optional[float] = None
        self.t_trace1: Optional[float] = None

    # -------------------------------------------------------------- sizes
    @property
    def window_seconds(self) -> float:
        """A traced run measures a shorter window (``trace_seconds`` of the
        traffic file): traces are large and tracing slows the host."""
        if self.trace:
            return min(self.seconds,
                       float(self.traffic.get("trace_seconds", 8.0)))
        return self.seconds

    def model_sizes(self) -> Dict[str, Any]:
        """The configuration's sizes, or its tiny preset in a rehearsal."""
        cfg = dict(self.config)
        if self.rehearse:
            cfg.update(self.config.get("tiny", {}))
        return cfg

    def family(self):
        return self.manifest.module("families", self.config["family"])

    # ------------------------------------------------------------- detail
    def say(self, text: str) -> None:
        print(text, flush=True)
        self._detail.append(text)

    def note(self, text: str) -> None:
        """Detail too long for the output: to the file only."""
        self._detail.append(text)

    # ----------------------------------------------------------- profiler
    def start_trace(self) -> None:
        if not self.trace:
            return
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        # no Python-function tracing (100k+ events a second, and it slows
        # the host loop it is measuring); TraceAnnotation spans stay
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._tracing = True
        self.t_trace0 = time.perf_counter()

    def stop_trace(self) -> None:
        if self._tracing:
            self.t_trace1 = time.perf_counter()
            jax.profiler.stop_trace()
            self._tracing = False

    def xplane_path(self) -> Optional[str]:
        if not self.trace_dir:
            return None
        found = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None

    def close(self) -> None:
        self.stop_trace()
        if self._detail:
            os.makedirs(self.out_dir, exist_ok=True)
            path = os.path.join(
                self.out_dir, f"{self.cell['name']}-seed{self.seed}-"
                f"trace{int(self.trace)}.log")
            with open(path, "w", encoding="utf-8") as f:
                f.write("\n".join(self._detail) + "\n")
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (a floor: live buffers, not
    XLA's temporaries — PERF.md section 7)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def result_line(ctx: Context, result: Dict[str, Any],
                device: Dict[str, Any]) -> Dict[str, Any]:
    """The contract's last line.  ``result`` is what the generator returned:
    ``correct``, ``attempted``, ``failed``, ``end_to_end`` (name -> value),
    and whatever its readers need (``window``, ``steps``, ``blocks`` ...)."""
    man = ctx.manifest
    device = dict(device, memory_peak_bytes=memory_peak_bytes(ctx.devices))
    line: Dict[str, Any] = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
    }
    if ctx.rehearse:
        line["rehearsal"] = True
        line["counts"] = result.get("counts", {})
        line["device"] = {k: device[k] for k in ("platform", "kind", "count")}
        return line
    metrics: Dict[str, Any] = {}
    if not ctx.trace:
        for m in man.end_to_end(ctx.cell["name"]):
            if m["name"] not in result["end_to_end"]:
                raise KeyError(f"generator reported no {m['name']}")
            metrics[m["name"]] = {"value": result["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    else:
        from benchmark import trace_reduce

        xplane = ctx.xplane_path()
        if xplane is None:
            raise RuntimeError("traced run left no .xplane.pb")
        reduced = trace_reduce.reduce_file(xplane)
        device["busy_s"] = reduced.busy_seconds()
        device["window_s"] = reduced.window_seconds()
        read_ctx = {"trace": reduced, "result": result, "config": ctx.config,
                    "traffic": ctx.traffic, "device": device,
                    "family": ctx.family(), "chips": len(ctx.devices)}
        for m in man.per_layer(ctx.cell["name"]):
            spec = man.layer_metric(m["name"])
            reader = man.module("readers", spec["reader"])
            value = reader.read(read_ctx, **spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["breakdown"] = reduced.breakdown()
    line["metrics"] = metrics
    line["device"] = device
    return line
