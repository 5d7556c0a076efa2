#!/usr/bin/env python
"""Telemetry dump / demo CLI.

``--demo`` runs the full observability path end-to-end on a tiny CPU
model: a few training steps through ``DeepSpeedTPUEngine`` (fused +
incremental API, so fwd/bwd/step AND train_batch phase timings land in
the registry), a small shared-prefix serving run through
``InferenceEngineV2`` (prefill/decode latency histograms, prefix-cache
counters), explicit collectives through ``deepspeed_tpu.comm`` verbs
(comms per-op totals + algorithmic bus bytes), then writes the
Prometheus textfile + JSONL event log and verifies the output: every
metric name passes ``tools/check_metric_names.py`` and the exposition
text round-trips through the parser.

Prints ONE JSON summary line (paths, metric counts, MFU, serving
percentiles) and exits non-zero if a required metric family is missing
— this is the acceptance gate for the telemetry subsystem, and a
smoke-debuggable artifact generator for dashboard work.

Knobs: ``--out DIR`` (default ./telemetry_demo), ``--steps N`` training
steps (default 6), ``--serve-requests N`` (default 4).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a multi-device virtual mesh makes the comms demo meaningful (bus
# factors are 0 on a 1-rank axis); must be set before jax initializes
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402


def _mlp_spec(hidden: int = 16, nlayers: int = 2):
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


def _train_demo(out_dir: str, steps: int):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu

    engine, *_ = deepspeed_tpu.initialize(
        model=_mlp_spec(),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "steps_per_print": 2,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
            "comms_logger": {"enabled": True},
            "telemetry": {
                "enabled": True,
                "prometheus_path": os.path.join(out_dir, "metrics.prom"),
                "jsonl_path": os.path.join(out_dir, "events.jsonl"),
                "export_interval": 2,
                "stall_watchdog": {"enabled": True, "multiple": 3.0},
                "flight_recorder": {"enabled": True,
                                    "path": os.path.join(out_dir, "flight")},
                "numerics": {"enabled": True, "min_history": 2},
            },
        })
    B = engine.config.train_batch_size
    hidden = 16
    rng = np.random.RandomState(0)

    def batch(seed, gas_dim=True):
        x = rng.randn(B, hidden).astype(np.float32)
        y = (x @ np.eye(hidden, dtype=np.float32) * 0.5)
        if gas_dim:
            return (jnp.asarray(x[None]), jnp.asarray(y[None]))
        return (jnp.asarray(x), jnp.asarray(y))

    for i in range(steps):  # fused path: train_batch phase + MFU window
        engine.train_batch(batch(i))
    for i in range(2):  # incremental path: fwd/bwd/step phase timers
        loss = engine.forward(batch(i, gas_dim=False))
        engine.backward(loss)
        engine.step()
    return engine


def _numerics_demo(engine, out_dir: str):
    """Numerics observatory end-to-end: poison one batch with NaNs, let
    the next reporting boundary's stats pull trip the `nonfinite`
    sentinel (anomaly counter + flight dump with the per-leaf
    breakdown), then save a checkpoint and read the incident back out
    of the tag's commit manifest — the full anomaly → dump → manifest
    triage loop, in-process."""
    import jax.numpy as jnp

    B = engine.config.train_batch_size
    hidden = 16
    x = np.full((1, B, hidden), np.nan, np.float32)
    y = np.zeros((1, B, hidden), np.float32)
    for _ in range(2):  # two steps always cross a steps_per_print=2 boundary
        engine.train_batch((jnp.asarray(x), jnp.asarray(y)))
    report = engine.numerics_report()
    ckpt_dir = os.path.join(out_dir, "ckpt")
    engine.save_checkpoint(ckpt_dir, tag="numerics_demo")

    from deepspeed_tpu.resilience.commit import manifest_meta

    incident = manifest_meta(ckpt_dir, "numerics_demo").get(
        "numerics_incident")
    return report, incident


def _serving_demo(n_requests: int):
    from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                      RaggedInferenceConfig,
                                                      RaggedRequest)
    from deepspeed_tpu.models.llama import llama_model

    model = llama_model("tiny", max_seq_len=128)
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        page_size=16, num_pages=64, max_seqs=4, max_pages_per_seq=8,
        enable_prefix_cache=True))
    rng = np.random.RandomState(0)
    vocab = model.config.vocab_size
    prefix = rng.randint(1, vocab, 32).tolist()
    # sequential first request registers the prefix pages; the rest hit
    eng.generate_all([RaggedRequest(
        prompt_ids=prefix + rng.randint(1, vocab, 8).tolist(),
        max_new_tokens=4)])
    eng.generate_all([RaggedRequest(
        prompt_ids=prefix + rng.randint(1, vocab, 8).tolist(),
        max_new_tokens=4) for _ in range(max(1, n_requests - 1))])
    return eng.cache_stats()


def _comms_demo(topology):
    """Record real trace-time collectives through the comm verbs (an
    8-virtual-device CPU mesh gives the bus factors a non-trivial n)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu import comm

    mesh = topology.mesh
    n = topology.axis_size("data")
    x = jnp.ones((8 * n, 8), jnp.float32)

    def body(a):
        s = comm.all_reduce(a, "sum", "data")
        g = comm.all_gather(a, "data")
        r = comm.reduce_scatter(s, "sum", "data")
        return r + g[:r.shape[0]]

    from deepspeed_tpu.utils.jax_compat import shard_map

    smap = shard_map(body, mesh=mesh, in_specs=P("data"),
                     out_specs=P("data"), check_vma=False)
    np.asarray(jax.jit(smap)(x))
    return comm.get_comms_logger()


REQUIRED_FAMILIES = (
    "deepspeed_tpu_train_phase_seconds_bucket",   # training phase timings
    "deepspeed_tpu_train_mfu",                    # MFU gauge
    "deepspeed_tpu_serving_prefill_seconds_bucket",
    "deepspeed_tpu_serving_decode_seconds_bucket",  # latency histograms
    "deepspeed_tpu_comm_ops_total",               # comms per-op totals
    "deepspeed_tpu_comm_bytes_total",
    "deepspeed_tpu_memory_bytes_in_use",          # memory ledger gauges
    "deepspeed_tpu_memory_component_bytes",
    "deepspeed_tpu_train_numerics_boundaries_total",  # numerics observatory
    "deepspeed_tpu_train_numerics_anomalies_total",   # (the demo trips one)
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--demo", action="store_true",
                    help="run the tiny-CPU end-to-end demo workload")
    ap.add_argument("--out", default="./telemetry_demo")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--serve-requests", type=int, default=4)
    args = ap.parse_args(argv)
    if not args.demo:
        ap.error("only --demo mode is implemented; pass --demo")
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)

    from deepspeed_tpu.telemetry import get_registry, parse_prometheus_text

    engine = _train_demo(out_dir, args.steps)
    numerics, incident = _numerics_demo(engine, out_dir)
    cache = _serving_demo(args.serve_requests)
    cl = _comms_demo(engine.topology)
    if cl is not None:
        cl.publish(get_registry(), axis_sizes=engine.topology.axis_sizes)
        cl.log_summary(axis_sizes=engine.topology.axis_sizes)

    tm = engine.telemetry
    if tm.jsonl is not None:
        tm.jsonl.emit("demo_complete", steps=args.steps,
                      serve_requests=args.serve_requests)
    from deepspeed_tpu.telemetry import get_memory_ledger, setup_ledger

    # read the ledger BEFORE close(): close releases the engine's
    # component slots (they would otherwise pin the TrainState forever)
    mem = get_memory_ledger().collect()
    engine.close()  # final forced export + handle release

    # ---- verify the artifacts ------------------------------------------
    prom_path = os.path.join(out_dir, "metrics.prom")
    jsonl_path = os.path.join(out_dir, "events.jsonl")
    samples = parse_prometheus_text(open(prom_path).read())
    names = {n for n, _labels in samples}
    missing = [f for f in REQUIRED_FAMILIES if f not in names]

    from check_metric_names import check as lint_check

    lint_errors = lint_check(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # runtime names must pass the same rule the static lint enforces
    import re

    name_re = re.compile(r"^deepspeed_tpu_[a-z][a-z0-9_]*(_bucket|_sum|_count)?$")
    bad_names = sorted(n for n in names if not name_re.match(n))

    reg = get_registry()
    dec = reg.get("deepspeed_tpu_serving_decode_seconds")
    summary = {
        "prometheus_path": prom_path,
        "jsonl_path": jsonl_path,
        "jsonl_lines": sum(1 for _ in open(jsonl_path)),
        "metric_samples": len(samples),
        "metric_families": len(names),
        "mfu": reg.get("deepspeed_tpu_train_mfu").value(),
        # what a recomputed block keeps and the step's temporaries
        "train_step_program": setup_ledger()["notes"].get(
            "_train_batch_body"),
        "decode_latency_s": dec.percentiles() if dec.count() else None,
        "prefix_hit_rate": cache["prefix_hit_rate"],
        "memory": {
            "bytes_in_use": mem["bytes_in_use"],
            "unattributed_bytes": mem["unattributed_bytes"],
            "components": {k: v["device"] + v["host"]
                           for k, v in mem["components"].items()},
            "watermarks": mem["watermarks"],
        },
        "numerics": {
            "boundaries": numerics["boundaries"] if numerics else 0,
            "anomaly_counts": numerics["anomaly_counts"] if numerics else {},
            "first_nonfinite_leaf": ((numerics.get("last_report") or {})
                                     .get("first_nonfinite_leaf")
                                     if numerics else None),
            "divergence_ok": ((numerics.get("divergence") or {}).get("ok")
                              if numerics else None),
            "incident_annotated": bool(incident),
        },
        "missing_required": missing,
        "lint_errors": lint_errors,
        "bad_runtime_names": bad_names,
        "ok": not (missing or lint_errors or bad_names)
        and bool(incident),
    }
    print(json.dumps(summary, default=float))
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
