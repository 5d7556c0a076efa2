#!/usr/bin/env python3
"""Compile the engine's fused train step for a v5e mesh — with no chip.

    python tools/aot_train_step.py --mesh data=4 --stage 3 --layers 2
    python tools/aot_train_step.py --mesh model=2,data=2 --stage 1 --gas 2
    python tools/aot_train_step.py --mesh data=1 --layers 1 --gas 4

libtpu compiles against a ``v5e:2x2`` topology description on a machine that
has no TPU, so the real ``deepspeed_tpu.initialize`` -> ``train_batch`` program
(Mistral-7B widths, the Mosaic kernels, the SPMD partitioner, the collectives)
can be lowered and compiled from the sandbox.  What this catches before any
chip time is spent: lowering refusals ("Mosaic kernels cannot be automatically
partitioned"), out-of-memory programs, donation that does not alias, a kernel
that is missing from the step.  Printed per run: compile seconds, XLA's
per-device memory analysis, the kernel names in the lowered text, and the
collective counts of the optimized HLO.

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
    args = ap.parse_args()

    from jax.experimental import topologies

    import deepspeed_tpu
    import deepspeed_tpu.utils.platform as plat
    from deepspeed_tpu.models.families import mistral_model
    from deepspeed_tpu.parallel.mesh import initialize_topology
    from deepspeed_tpu.runtime.config import MeshConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedTPUEngine

    plat.platform = lambda: "tpu"  # compiled kernels, not interpret mode
    DeepSpeedTPUEngine._init_state = _abstract_init_state
    mesh = {k: int(v) for k, v in (kv.split("=")
                                   for kv in args.mesh.split(","))}
    n = 1
    for v in mesh.values():
        n *= v
    devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices[:n]
    topo = initialize_topology(MeshConfig(**mesh), devices=devices)
    zero = {"stage": args.stage}
    zero.update({k: v == "true" for k, v in (
        kv.split("=") for kv in args.zero.split(",") if kv)})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=mistral_model("7b", max_seq_len=args.seq, n_layers=args.layers),
        config={"train_micro_batch_size_per_gpu": args.micro,
                "gradient_accumulation_steps": args.gas,
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": zero, "mesh": mesh},
        topology=topo)
    rep = topo.replicated()
    batch = jax.ShapeDtypeStruct(
        (args.gas, args.micro * topo.dp_world_size, args.seq), jnp.int32,
        sharding=rep)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)

    t0 = time.time()
    with topo.mesh:
        lowered = engine._train_batch.lower(engine.state, batch, key)
        text = lowered.as_text()
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    gib = 2.0 ** 30
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"aot_train_step: {devices[0].device_kind!r} x{n} mesh={mesh} "
          f"zero={zero} layers={args.layers} seq={args.seq} "
          f"micro={args.micro} gas={args.gas}")
    print(f"  compiled in {time.time() - t0:.1f} s")
    print(f"  per-device memory (XLA analysis): arguments "
          f"{mem.argument_size_in_bytes / gib:.2f} GiB, outputs "
          f"{mem.output_size_in_bytes / gib:.2f} GiB (aliased "
          f"{mem.alias_size_in_bytes / gib:.2f}), temporaries "
          f"{mem.temp_size_in_bytes / gib:.2f} GiB, total "
          f"{total / gib:.2f} GiB of 15.75")
    print(f"  Mosaic kernels in the lowered step: "
          f"{sorted(set(re.findall(r'dstpu_[a-z_]+', text)))}")
    hlo = compiled.as_text()
    print("  collectives in the optimized HLO: " + ", ".join(
        f"{k} {len(re.findall(rf' {k}(-start)?[.0-9]*[(]', hlo))}"
        for k in ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
