#!/usr/bin/env python3
"""Compile the engine's fused train step for a v5e mesh — with no chip.

    python tools/aot_train_step.py --mesh data=4 --stage 3 --layers 2
    python tools/aot_train_step.py --mesh model=2,data=2 --stage 1 --gas 2
    python tools/aot_train_step.py --mesh data=1 --layers 1 --gas 4
    python tools/aot_train_step.py --config benchmark/configs/<name>.json \
        --traffic benchmark/traffic/<name>.json [--remat 0|1]

libtpu compiles against a ``v5e:2x2`` topology description on a machine that
has no TPU, so the real ``deepspeed_tpu.initialize`` -> ``train_batch`` program
(Mistral-7B widths, the Mosaic kernels, the SPMD partitioner, the collectives)
can be lowered and compiled from the sandbox.  What this catches before any
chip time is spent: lowering refusals ("Mosaic kernels cannot be automatically
partitioned"), out-of-memory programs, donation that does not alias, a kernel
that is missing from the step.  Printed per run: compile seconds, XLA's
per-device memory analysis, the kernel names in the lowered text, the number
of kernel call sites and the collective counts of the optimized HLO.

``--config`` compiles a benchmark training cell's own step instead: the
model its family builds (``benchmark/families/<family>.py``) at the
configuration's sizes, the engine options and mesh it states, and — with
``--traffic`` — the cell's sequence length, micro-batch and accumulation
steps.  ``--remat`` overrides the configuration's ``remat`` key, so that a cut
can quote the analysis with and without recomputation.

It compiles; it does not run.  No time, rate or numeric result comes from here
— ``chip_smoke.py`` on the chip is the proof that the step is right.

The engine is built over abstract state: ``_init_state`` is swapped for a
version that returns ``ShapeDtypeStruct`` leaves carrying the plan's shardings,
which is the one place engine construction would execute on a device.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _abstract(tree, shardings, dtype=None):
    return jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype,
                                          sharding=s), tree, shardings)


def _abstract_init_state(self):
    """engine._init_state without touching a device."""
    from deepspeed_tpu.runtime.engine import TrainState

    init_rng, self._rng = jax.random.split(self._rng)
    shapes = jax.eval_shape(self.model.init_params, init_rng)
    plan = self.zero_plan
    params = _abstract(shapes, plan.tree_shardings(shapes, "master"),
                       jnp.float32)
    opt = jax.eval_shape(self.optimizer.init, params)
    rep = self.topology.replicated()

    def scalar(dt):
        return jax.ShapeDtypeStruct((), dt, sharding=rep)

    return TrainState(
        step=scalar(jnp.int32), micro_step=scalar(jnp.int32), params=params,
        opt_state=_abstract(opt, plan.tree_shardings(opt, "master")),
        grad_acc=_abstract(shapes, plan.tree_shardings(shapes, "grad"),
                           self.grad_accum_dtype),
        loss_scale=None, skipped_steps=scalar(jnp.int32),
        global_grad_norm=scalar(jnp.float32))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default="data=1",
                    help="axis=size,... over 1 or 4 devices of a v5e:2x2")
    ap.add_argument("--stage", type=int, default=1)
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--gas", type=int, default=1)
    ap.add_argument("--zero", default="",
                    help="extra zero_optimization keys, key=true,...")
    ap.add_argument("--config", default="",
                    help="a benchmark training configuration file: its "
                         "family, sizes, engine options and mesh")
    ap.add_argument("--traffic", default="",
                    help="with --config: the cell's traffic file (sequence "
                         "length, micro-batch, accumulation steps)")
    ap.add_argument("--remat", type=int, choices=(0, 1), default=None,
                    help="with --config: override its remat key")
    args = ap.parse_args()

    from jax.experimental import topologies

    import deepspeed_tpu
    import deepspeed_tpu.utils.platform as plat
    from deepspeed_tpu.models.families import mistral_model
    from deepspeed_tpu.parallel.mesh import initialize_topology
    from deepspeed_tpu.runtime.config import MeshConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedTPUEngine

    plat.platform = lambda: "tpu"  # compiled kernels, not interpret mode
    # a cache entry written from here makes a chip call's runs cold (PR 26)
    jax.config.update("jax_enable_compilation_cache", False)
    DeepSpeedTPUEngine._init_state = _abstract_init_state
    mesh = {k: int(v) for k, v in (kv.split("=")
                                   for kv in args.mesh.split(","))}
    bench = None
    if args.config:
        import importlib.util
        import json

        with open(args.config, encoding="utf-8") as f:
            bench = json.load(f)
        if args.traffic:
            with open(args.traffic, encoding="utf-8") as f:
                tr = json.load(f)
            args.seq = int(tr["sequence_length"])
            args.micro = int(tr["micro_batch_per_chip"])
            args.gas = int(tr["gradient_accumulation_steps"])
        if args.remat is not None:
            bench["remat"] = bool(args.remat)
        mesh = dict(bench["mesh"])
        args.layers = int(bench["num_hidden_layers"])
        spec = importlib.util.spec_from_file_location(
            "aot_family", os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(
                    args.config))), "families", bench["family"] + ".py"))
        family = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(family)
    n = 1
    for v in mesh.values():
        n *= v
    devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices[:n]
    topo = initialize_topology(MeshConfig(**mesh), devices=devices)
    zero = {"stage": args.stage}
    zero.update({k: v == "true" for k, v in (
        kv.split("=") for kv in args.zero.split(",") if kv)})
    config = {"bf16": {"enabled": True}, "gradient_clipping": 1.0,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "zero_optimization": zero}
    if bench is not None:
        model = family.build(bench, args.layers, args.seq, jnp.float32)
        config = dict(bench["engine"])
        zero = config["zero_optimization"]
    else:
        model = mistral_model("7b", max_seq_len=args.seq,
                              n_layers=args.layers)
    config.update({"train_micro_batch_size_per_gpu": args.micro,
                   "gradient_accumulation_steps": args.gas, "mesh": mesh,
                   "steps_per_print": 10 ** 9})
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config,
                                               topology=topo)
    rep = topo.replicated()
    batch = jax.ShapeDtypeStruct(
        (args.gas, args.micro * topo.dp_world_size, args.seq), jnp.int32,
        sharding=rep)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)

    step_args = (engine.state, batch, key)
    if getattr(engine, "_moe_counters", False):
        # an expert share's step takes its counters' running sum
        step_args += (jax.ShapeDtypeStruct(engine._moe_shape(), jnp.int32,
                                           sharding=rep),)
    t0 = time.time()
    with topo.mesh:
        lowered = engine._train_batch.lower(*step_args)
        text = lowered.as_text()
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    gib = 2.0 ** 30
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    what = f"config={bench['name']} remat={bool(bench.get('remat'))} " \
        if bench is not None else ""
    print(f"aot_train_step: {what}{devices[0].device_kind!r} x{n} mesh={mesh} "
          f"zero={zero} layers={args.layers} seq={args.seq} "
          f"micro={args.micro} gas={args.gas}")
    print(f"  compiled in {time.time() - t0:.1f} s")
    print(f"  per-device memory (XLA analysis): arguments "
          f"{mem.argument_size_in_bytes / gib:.2f} GiB, outputs "
          f"{mem.output_size_in_bytes / gib:.2f} GiB (aliased "
          f"{mem.alias_size_in_bytes / gib:.2f}), temporaries "
          f"{mem.temp_size_in_bytes / gib:.2f} GiB, total "
          f"{total / gib:.2f} GiB of 15.75")
    hlo = compiled.as_text()
    # a scanned body's call counts once: a replay that remat leaves in the
    # backward pass is a site more, whatever the trip count
    print(f"  Mosaic kernels in the lowered step: "
          f"{sorted(set(re.findall(r'dstpu_[a-z_]+', text)))}, "
          f"{len(re.findall(r'custom_call_target=.tpu_custom_call', hlo))} "
          f"call sites in the optimized HLO")
    print("  collectives in the optimized HLO: " + ", ".join(
        f"{k} {len(re.findall(rf' {k}(-start)?[.0-9]*[(]', hlo))}"
        for k in ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")))
    return 0


if __name__ == "__main__":
    # the programs are lowered here, outside any engine call: beneath the
    # frame an engine makes their first dispatch under (compile/deep_frame.py)
    from deepspeed_tpu.compile.deep_frame import under_deep_frame

    sys.exit(under_deep_frame(main))
