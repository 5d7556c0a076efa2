#!/usr/bin/env python
"""Measured-goodput report + perf-regression gate.

``--demo`` runs the step-time-attribution and goodput-accounting story
end-to-end on a tiny CPU model (docs/OBSERVABILITY.md "Step-time
attribution & goodput") and hard-gates its invariants:

* **Step-time attribution** — a forced ``StepTimeline`` capture around
  one train step must yield a decomposition whose categories sum to the
  step's wall clock within tolerance, with the ``measured`` flag honest
  (CPU/interpreter backends yield no device timeline -> the record must
  say ``measured: false`` and fall back to the span-derived host
  timeline, never crash).  When a device trace IS available the
  measured exposed/overlapped split must be internally consistent and
  sane against the structural ``overlapped_fraction``.
* **Goodput ledger** — after steps + checkpoint save/load + eval, the
  badput buckets (+ computed idle residual) must sum to the engine
  lifetime within tolerance, the compile bucket must have absorbed the
  demo's XLA compiles, and ``goodput_fraction`` must clear a small
  floor (compile dominates a tiny CPU demo, so the floor is low; the
  arithmetic, not the throughput, is the gate).
* **Artifacts** — each capture leaves a merged Chrome-trace JSON (host
  spans + device ops in ONE Perfetto file) that must parse and carry
  ``traceEvents``.

Writes ``goodput_report.json`` under ``--out``, prints ONE JSON summary
line, exits non-zero when any check fails — the acceptance gate for the
measured-goodput subsystem.

Knobs: ``--out DIR`` (default ./goodput_demo), ``--steps N`` (default
8), ``--seed S``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

HIDDEN = 16
#: categories-sum-to-wall tolerance: relative to wall plus an absolute
#: floor for micro-second-scale CPU steps
SUM_RTOL, SUM_ATOL = 0.01, 1e-3
#: goodput floor for the tiny demo: compile dominates an 8-step CPU
#: run, so this gates the accounting arithmetic, not throughput
GOODPUT_FLOOR = 0.02
#: buckets-sum-to-lifetime tolerance (idle is a computed residual, so
#: the sum is exact up to fp noise; keep a loose belt anyway)
LIFETIME_RTOL = 0.02


def _mlp_spec(hidden: int = HIDDEN, nlayers: int = 2):
    """Tiny MLP ModelSpec (mirrors tests/unit/simple_model.py, which
    tools must not import)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.runtime.module import ModelSpec

    def init_params(rng):
        keys = jax.random.split(rng, nlayers)
        return {f"layer_{i}": {
            "w": jax.random.normal(k, (hidden, hidden)) * 0.1,
            "b": jnp.zeros((hidden,))} for i, k in enumerate(keys)}

    def forward(params, x):
        for i in range(nlayers):
            layer = params[f"layer_{i}"]
            x = x @ layer["w"] + layer["b"]
            if i < nlayers - 1:
                x = jax.nn.relu(x)
        return x

    def loss_fn(params, batch, rng):
        x, y = batch
        return jnp.mean((forward(params, x) - y) ** 2)

    return ModelSpec(init_params, loss_fn)


def _check(checks, name, ok, detail=""):
    checks.append({"check": name, "ok": bool(ok), "detail": str(detail)})
    status = "ok" if ok else "FAIL"
    print(f"  [{status}] {name}" + (f" — {detail}" if detail else ""))
    return ok


def run_demo(out: str, steps: int, seed: int = 0) -> int:
    import shutil

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.telemetry.exporter import snapshot_metrics

    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    artifact_dir = os.path.join(out, "timeline")

    cfg = {
        "train_micro_batch_size_per_gpu": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "seed": 7 + seed,
        "telemetry": {
            "enabled": True,
            # capture every 4th step: the demo proves the periodic path
            # AND the forced path below
            "timeline": {"every_n_steps": 4, "artifact_dir": artifact_dir},
            "goodput": {"run_file": os.path.join(out, "goodput_run.json")},
            # keep incident dumps inside --out, never the CWD
            "flight_recorder": {"path": os.path.join(out, "flight")},
        },
    }
    engine, *_ = deepspeed_tpu.initialize(model=_mlp_spec(), config=cfg)

    rng = np.random.RandomState(seed)
    w = (np.random.RandomState(42).randn(HIDDEN, HIDDEN) * 0.3
         ).astype(np.float32)

    def batch():
        xs = rng.randn(1, 8, HIDDEN).astype(np.float32)
        return jnp.asarray(xs), jnp.asarray(xs @ w)

    print(f"goodput report: {steps} steps + save/load + eval -> {out}")
    for _ in range(steps):
        engine.train_batch(batch())
    _, forced = engine.capture_timeline(batch())
    engine.save_checkpoint(os.path.join(out, "ckpt"))
    engine.load_checkpoint(os.path.join(out, "ckpt"))
    engine.eval_batch(batch())
    summary = engine.goodput_summary()
    periodic = engine.timeline_record()
    engine.close()

    checks = []
    # ---------------------------------------------------- timeline gates
    _check(checks, "timeline_capture_produced", forced is not None)
    rec = forced or {}
    cats = rec.get("categories") or {}
    wall = float(rec.get("wall_seconds") or 0.0)
    gap = abs(sum(cats.values()) - wall)
    _check(checks, "categories_sum_to_wall",
           cats and gap <= SUM_RTOL * wall + SUM_ATOL,
           f"|sum-wall|={gap:.2e} wall={wall:.4f}")
    on_cpu = jax.default_backend() == "cpu"
    measured = bool(rec.get("measured"))
    _check(checks, "measured_flag_honest",
           (not measured) if on_cpu else True,
           f"backend={jax.default_backend()} measured={measured}")
    if measured:
        # device-trace path: the exposed/overlapped split must cover the
        # collective busy time and never exceed it
        exp = float(rec.get("exposed_collective_seconds") or 0.0)
        ovl = float(rec.get("overlapped_collective_seconds") or 0.0)
        coll = sum(v for k, v in cats.items()
                   if k in ("all_reduce", "all_gather", "reduce_scatter",
                            "all_to_all", "collective_permute"))
        _check(checks, "measured_overlap_consistent",
               exp >= 0 and ovl >= 0 and exp <= wall + SUM_ATOL
               and exp + SUM_ATOL >= coll * 0.0,  # exposed ⊆ wall
               f"exposed={exp:.4f} overlapped={ovl:.4f} coll_cat={coll:.4f}")
        rep = engine.overlap_report()
        if rep is not None and (exp + ovl) > 0:
            # structural golden: measured overlapped share vs the
            # byte-model overlapped_fraction, loosely (same order)
            m_frac = ovl / (exp + ovl)
            _check(checks, "measured_overlap_vs_structural",
                   abs(m_frac - rep.overlapped_fraction) < 0.5,
                   f"measured={m_frac:.2f} "
                   f"structural={rep.overlapped_fraction:.2f}")
    else:
        _check(checks, "fallback_is_host_timeline",
               set(cats) >= {"host_compute", "host_gap"}
               and all(cats.get(c, 0.0) == 0.0
                       for c in ("gemm", "attention")),
               sorted(k for k, v in cats.items() if v))
    _check(checks, "periodic_capture_fired",
           periodic is not None
           and (periodic.get("step") == steps or forced is not None),
           f"last capture step={periodic.get('step') if periodic else None}")
    arts = (sorted(os.listdir(artifact_dir))
            if os.path.isdir(artifact_dir) else [])
    _check(checks, "chrome_trace_artifacts_written", bool(arts), arts[:4])
    art_ok, n_events = False, 0
    if arts:
        try:
            with open(os.path.join(artifact_dir, arts[-1])) as f:
                trace = json.load(f)
            evs = trace.get("traceEvents") or []
            n_events = len(evs)
            art_ok = n_events > 0 and all(
                "ts" in e and "name" in e for e in evs
                if e.get("ph") == "X")
        except Exception:
            art_ok = False
    _check(checks, "chrome_trace_parses", art_ok, f"{n_events} events")

    # ----------------------------------------------------- goodput gates
    _check(checks, "goodput_summary_produced", summary is not None)
    s = summary or {}
    buckets = s.get("buckets") or {}
    lifetime = float(s.get("lifetime_seconds") or 0.0)
    bgap = abs(sum(buckets.values()) - lifetime)
    _check(checks, "buckets_sum_to_lifetime",
           buckets and bgap <= LIFETIME_RTOL * max(lifetime, 1e-9),
           f"|sum-lifetime|={bgap:.2e} lifetime={lifetime:.3f}")
    _check(checks, "productive_steps_counted",
           s.get("productive_steps") == steps + 1,  # +1 forced capture
           f"productive={s.get('productive_steps')} expected={steps + 1}")
    _check(checks, "checkpoint_phases_accounted",
           buckets.get("checkpoint_save", 0) > 0
           and buckets.get("checkpoint_load", 0) > 0,
           f"save={buckets.get('checkpoint_save', 0):.4f} "
           f"load={buckets.get('checkpoint_load', 0):.4f}")
    _check(checks, "eval_accounted", buckets.get("eval", 0) > 0,
           f"eval={buckets.get('eval', 0):.4f}")
    _check(checks, "compile_absorbed", buckets.get("compile", 0) > 0,
           f"compile={buckets.get('compile', 0):.3f}")
    frac = float(s.get("goodput_fraction") or 0.0)
    _check(checks, "goodput_fraction_above_floor", frac >= GOODPUT_FLOOR,
           f"{frac:.3f} >= {GOODPUT_FLOOR}")
    run_path = os.path.join(out, "goodput_run.json")
    run_rec = {}
    if os.path.exists(run_path):
        with open(run_path) as f:
            run_rec = json.load(f)
    _check(checks, "union_run_file_persisted",
           run_rec.get("productive_steps") == steps + 1
           and run_rec.get("attempts") == 1,
           f"run={ {k: run_rec.get(k) for k in ('high_water', 'productive_steps', 'attempts')} }")

    # ------------------------------------------------------ metric gates
    snap = snapshot_metrics()
    names = set(snap)
    need = {"deepspeed_tpu_timeline_category_seconds",
            "deepspeed_tpu_timeline_measured",
            "deepspeed_tpu_timeline_captures_total",
            "deepspeed_tpu_goodput_seconds_total",
            "deepspeed_tpu_goodput_fraction"}
    _check(checks, "metrics_registered", need <= names,
           sorted(need - names))

    ok = all(c["ok"] for c in checks)
    report = {"demo": "goodput_report", "ok": ok, "out": out,
              "steps": steps, "seed": seed,
              "backend": jax.default_backend(),
              "timeline": rec, "goodput": s, "run_file": run_rec,
              "checks": checks}
    with open(os.path.join(out, "goodput_report.json"), "w") as f:
        json.dump(report, f, indent=2, default=str)
    print(json.dumps({k: v for k, v in report.items()
                      if k in ("demo", "ok", "out", "steps", "backend")}))
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--demo", action="store_true",
                    help="run the measured-goodput gate on a tiny CPU model")
    ap.add_argument("--out", default="./goodput_demo")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not args.demo:
        ap.print_help()
        return 2
    if args.steps < 4:
        ap.error("--steps must be >= 4 (the periodic capture cadence)")
    return run_demo(os.path.abspath(args.out), args.steps, seed=args.seed)


if __name__ == "__main__":
    sys.exit(main())
