"""Step/phase annotations for the XLA profiler.

Thin wrappers over ``jax.profiler.StepTraceAnnotation`` /
``TraceAnnotation`` that degrade to no-ops when the profiler API is
absent (old jax, stripped builds) — callers never guard.  Annotated
ranges show up on the TraceMe timeline of a ``jax.profiler`` capture
(TensorBoard/XProf), which is how per-phase device time is attributed
when host wall-clock timers only see dispatch.
"""

from __future__ import annotations

import contextlib
from typing import Optional


def profiler_available() -> bool:
    try:
        import jax.profiler  # noqa: F401

        return hasattr(jax.profiler, "TraceAnnotation")
    except Exception:
        return False


@contextlib.contextmanager
def _noop():
    yield


def step_trace(step_num: int, **kwargs):
    """``with step_trace(step): ...`` around one training/serving step.

    Steps annotated this way get first-class step slicing in XProf
    (the profiler groups device ops under the step number)."""
    try:
        import jax.profiler

        return jax.profiler.StepTraceAnnotation("step", step_num=int(step_num),
                                                **kwargs)
    except Exception:
        return _noop()


def annotate(name: str, **kwargs):
    """``with annotate("fwd"): ...`` around a phase inside a step."""
    try:
        import jax.profiler

        return jax.profiler.TraceAnnotation(name, **kwargs)
    except Exception:
        return _noop()


def start_trace(log_dir: str) -> bool:
    """Start a profiler capture; False when unavailable."""
    try:
        import jax.profiler

        jax.profiler.start_trace(log_dir)
        return True
    except Exception:
        return False


def stop_trace() -> None:
    try:
        import jax.profiler

        jax.profiler.stop_trace()
    # dstpu-lint: allow[swallow] stopping a not-started/foreign trace at
    # dump time is best-effort cleanup
    except Exception:
        pass


class PhaseTimer:
    """Context manager that annotates a phase for the profiler, reports
    its host wall time to a callback (usually a histogram ``observe``),
    and records the range as a span (cat ``phase`` unless ``cat`` says
    otherwise) in the trace ring — one context, three sinks.  ``attrs``
    ride on the span only."""

    def __init__(self, name: str, sink=None, cat: str = "phase", **attrs):
        self.name = name
        self.sink = sink
        self.cat = cat
        self.attrs = attrs
        self._ann = None
        self._t0: Optional[float] = None
        self._t0_us: float = 0.0

    def __enter__(self):
        import time

        self._ann = annotate(self.name)
        self._ann.__enter__()
        from .spans import _notify_phase, _now_us

        _notify_phase(self.name, "enter")
        self._t0_us = _now_us()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import time

        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self.sink is not None:
            self.sink(self.name, dt)
        from .spans import _notify_phase, get_span_recorder

        _notify_phase(self.name, "exit")
        rec = get_span_recorder()
        if rec.enabled:
            rec.record(self.name, self._t0_us, dt * 1e6, cat=self.cat,
                       **self.attrs)
        return False
