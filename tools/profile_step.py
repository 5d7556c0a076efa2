"""Capture an XLA op-level profile of one train_batch and print top ops.

Usage: python tools/profile_step.py [--size 160m] [--seq 1024] [--bs 16]
       [--steps 3] [--outdir /tmp/dstpu_trace]

Writes a jax.profiler trace (xplane) and prints the top-N ops by self
time, parsed with tensorboard_plugin_profile's converter — no TensorBoard
UI needed.  Works on CPU (for plumbing tests) and TPU (real numbers).
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# --platform must take effect BEFORE backend init
if "--platform" in sys.argv:
    os.environ["JAX_PLATFORMS"] = sys.argv[sys.argv.index("--platform") + 1]

import jax

import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="160m")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--bs", type=int, default=16)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--outdir", default="/tmp/dstpu_trace")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--platform", default=None, help="cpu | tpu (pin early)")
    ap.add_argument("--stage", type=int, default=1,
                    help="ZeRO stage — stage 3 captures the gather/compute "
                         "overlap trace the prefetch bet needs")
    ap.add_argument("--offload", action="store_true",
                    help="host-offload optimizer states (boundary overlap)")
    args = ap.parse_args()

    import deepspeed_tpu
    from deepspeed_tpu.models.llama import llama_model

    zero_cfg = {"stage": args.stage}
    if args.offload:
        zero_cfg["offload_optimizer"] = {"device": "cpu"}
    model = llama_model(args.size, max_seq_len=args.seq)
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": args.bs,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": zero_cfg,
        "gradient_clipping": 1.0,
    })
    rng = np.random.RandomState(0)
    batch = {"input_ids": jnp.asarray(rng.randint(
        0, model.config.vocab_size,
        (1, args.bs * engine.topology.dp_world_size, args.seq)).astype(np.int32))}

    for _ in range(3):  # compile + warm
        loss = engine.train_batch(batch)
    float(loss)

    with jax.profiler.trace(args.outdir):
        for _ in range(args.steps):
            loss = engine.train_batch(batch)
        float(loss)
    print(f"trace written to {args.outdir}")
    report(args.outdir, args.top)


def report(outdir: str, top: int) -> None:
    """Parse the newest xplane.pb and print the top ops by self time."""
    planes = sorted(glob.glob(f"{outdir}/**/*.xplane.pb", recursive=True),
                    key=os.path.getmtime)
    if not planes:
        print("no xplane.pb captured (profiler unsupported on this backend?)")
        return
    from tensorflow.python.profiler.internal import _pywrap_profiler_plugin

    try:
        raw = _pywrap_profiler_plugin.xspace_to_tools_data(
            [planes[-1]], "op_profile")
    except Exception as e:  # tool name varies across versions
        print(f"op_profile conversion failed ({e}); trying overview")
        raw = _pywrap_profiler_plugin.xspace_to_tools_data(
            [planes[-1]], "overview_page")
    data = raw[0] if isinstance(raw, tuple) else raw
    import json

    try:
        parsed = json.loads(data)
    except Exception:
        # op_profile returns a serialized proto on some versions; fall back
        # to the framework_op_stats csv-like tool
        raw = _pywrap_profiler_plugin.xspace_to_tools_data(
            [planes[-1]], "framework_op_stats")
        data = raw[0] if isinstance(raw, tuple) else raw
        print(data[:4000] if isinstance(data, (str, bytes)) else data)
        return

    # op_profile json: byProgram/byCategory tree of {name, metrics}
    def walk(node, out):
        m = node.get("metrics") or {}
        if m.get("selfTimePs"):
            out.append((m["selfTimePs"], node.get("name", "?")))
        for c in node.get("children", []) or []:
            walk(c, out)

    ops = []
    root = (parsed.get("byCategory") or parsed.get("byProgram") or parsed)
    walk(root, ops)
    if not ops:
        print("trace parsed but carries no per-op metrics — the XLA op "
              "profile is populated on TPU/GPU backends only; rerun on the "
              "chip for real numbers")
        return
    ops.sort(reverse=True)
    total = sum(t for t, _ in ops) or 1
    print(f"{'self time':>12}  {'%':>6}  op")
    for t, name in ops[:top]:
        print(f"{t/1e6:9.3f} ms  {100*t/total:5.1f}%  {name[:90]}")


if __name__ == "__main__":
    main()
