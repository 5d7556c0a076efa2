#!/usr/bin/env python3
"""One cell of the benchmark, once, in one new process.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is the entry of ``workloads`` in ``BENCHMARK.json`` with that name;
its configuration, traffic mix and per-layer metrics are found by name (see
``manifest.py``).  The process builds the weights on the device from the seed,
warms this cell's shapes, checks the program against the float32 reference,
measures for ``--seconds`` and prints one JSON object as its last line:
the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics (from
a profiler trace of a shorter window) with ``--trace 1``.  Earlier lines carry
per-step / per-request detail, also written under ``benchmark/out/``.

No chip — platform not ``tpu``, fewer devices than the cell's ``chips``, or a
``device_kind`` that ``peaks.json`` does not hold — is exit code 2 naming what
was found, never a CPU run.  These four options are all the command reads: no
other option and no environment variable.  ``rehearse.py`` beside this file
runs a cell's control flow at its tiny preset on whatever platform JAX selects
and prints no device metric.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark import manifest as manifest_mod  # noqa: E402

EXIT_NO_CHIP = 2
EXIT_NO_PROGRAM = 3


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, manifest_path: str = manifest_mod.DEFAULT_MANIFEST,
         rehearse: bool = False) -> int:
    """``manifest_path`` and ``rehearse`` are for ``rehearse.py`` and the
    tests; the command itself always measures ``BENCHMARK.json``'s cell."""
    args = parse_args(argv)
    if args.seed < 0:
        print("benchmark: --seed must be >= 0", file=sys.stderr)
        return 1
    man = manifest_mod.Manifest(manifest_path)
    cell = man.cell(args.workload)
    config = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    if rehearse:
        traffic.update(traffic.get("tiny", {}))
    generator = man.module("generators", traffic["kind"])

    # the program under test lives beside the manifest; a directory that
    # holds only BENCHMARK.json and the benchmark has nothing to measure
    for base in (man.root, os.path.dirname(HERE)):
        if os.path.isdir(os.path.join(base, "deepspeed_tpu")):
            if base not in sys.path:
                sys.path.insert(0, base)
            break
    try:
        import deepspeed_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program under test is not importable ({e}); "
              "no result", file=sys.stderr)
        return EXIT_NO_PROGRAM

    import jax

    from benchmark import harness

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"benchmark: workload={cell['name']} config={cell['config']} "
          f"traffic={cell['traffic']} seed={args.seed} "
          f"seconds={args.seconds} trace={args.trace} "
          f"platform={device['platform']} device_kind={device['kind']!r} "
          f"count={device['count']}", flush=True)
    need = int(cell["chips"])
    if not rehearse and (device["platform"] != "tpu" or len(devs) < need):
        print(f"benchmark: workload {cell['name']} needs {need} tpu "
              f"device(s); found platform {device['platform']!r} with "
              f"{len(devs)} — no chip, no result", file=sys.stderr)
        return EXIT_NO_CHIP
    if not rehearse:
        from benchmark import roofline

        try:
            roofline.peaks(device["kind"])
        except KeyError as e:
            print(f"benchmark: {e.args[0]} — no result", file=sys.stderr)
            return EXIT_NO_CHIP
    if len(devs) < need:
        print(f"benchmark: rehearsal needs {need} devices, found "
              f"{len(devs)} (set XLA_FLAGS=--xla_force_host_platform_"
              f"device_count={need})", file=sys.stderr)
        return EXIT_NO_CHIP

    ctx = harness.Context(
        manifest=man, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        rehearse=rehearse, devices=devs[:need],
        t_process_start=T_PROCESS_START, out_dir=os.path.join(HERE, "out"))
    harness.place_compile_cache()
    harness.install_compile_counter()
    try:
        result = generator.run(ctx)
        line = harness.result_line(ctx, result, device)
    finally:
        ctx.close()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
