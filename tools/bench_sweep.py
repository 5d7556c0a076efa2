"""Run the benchmark rung ladder and collect one JSON record per rung.

Usage (on a machine with the TPU reachable):

    python tools/bench_sweep.py            # all rungs
    python tools/bench_sweep.py flagship   # just the headline rung

Writes ``docs/BENCH_SWEEP.json`` (list of {rung, env, result|error}) and
prints a compact table.  Each rung is a bench.py invocation with the
env-selectable knobs (size/seq/bs/stage/offload), so the sweep measures
exactly what the driver's bench measures.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _contract_gate() -> str:
    """Refuse to sweep against stale golden contracts (ROADMAP item 5):
    a perf artifact measured under program contracts that no longer match
    the tree is exactly the silent lie the contracts exist to prevent.
    Runs ``tools/check_contracts.py`` in a subprocess (it pins its own
    CPU harness) and returns the ``contract_set_hash`` stamped into every
    sweep record — same provenance bench.py already carries.  Skippable
    with DSTPU_SWEEP_SKIP_CONTRACTS=1 (the hash is stamped regardless).
    """
    # contract_set_hash is stdlib-only; load by file path so the sweep
    # driver itself never imports jax.  The module comes from THIS tree
    # (next to the tool — ROOT may be redirected to an artifact dir);
    # the hash is computed over ROOT's goldens.
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "dstpu_contracts_hash",
        os.path.join(here, "deepspeed_tpu", "analysis", "contracts.py"))
    contracts = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(contracts)
    h = contracts.contract_set_hash(ROOT)
    if os.environ.get("DSTPU_SWEEP_SKIP_CONTRACTS") == "1":
        print("bench_sweep: contract check SKIPPED "
              "(DSTPU_SWEEP_SKIP_CONTRACTS=1)", file=sys.stderr)
        return h
    print("bench_sweep: checking golden contracts before sweeping...",
          file=sys.stderr, flush=True)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "check_contracts.py")],
        capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        print(proc.stdout[-3000:], file=sys.stderr)
        sys.exit("bench_sweep: REFUSING to sweep — golden contracts are "
                 "stale (see violations above).  Fix the regression or "
                 "regenerate with tools/check_contracts.py "
                 "--update-goldens, then re-run.")
    return h

RUNGS = {
    # headline: the round-3 configuration; bs unpinned so the
    # ladder can probe 32 first (OOM falls back to 16/8)
    "flagship": {"DSTPU_BENCH_SIZE": "160m", "DSTPU_BENCH_SEQ": "1024",
                 "DSTPU_BENCH_STEPS": "20"},
    # a shape that should feed the MXU better (hidden 2048)
    "1b": {"DSTPU_BENCH_SIZE": "1b", "DSTPU_BENCH_SEQ": "1024",
           "DSTPU_BENCH_STEPS": "10"},
    # fp32 master + m + v for 1.1B params is ~13GB before activations —
    # two fallbacks if the pure-HBM rung OOMs: bf16 exp_avg (-2.2GB,
    # stays on-chip) and host-offloaded optimizer states (ZeRO-Infinity)
    "1b-mu16": {"DSTPU_BENCH_SIZE": "1b", "DSTPU_BENCH_SEQ": "1024",
                "DSTPU_BENCH_STEPS": "10", "DSTPU_BENCH_MU_DTYPE": "bf16"},
    "1b-offload": {"DSTPU_BENCH_SIZE": "1b", "DSTPU_BENCH_SEQ": "1024",
                   "DSTPU_BENCH_BS": "8", "DSTPU_BENCH_STEPS": "5",
                   "DSTPU_BENCH_OFFLOAD": "1"},
    # ZeRO-3 on the same model/chip: settles the stage-3 XLA-prefetch bet
    "160m-zero3": {"DSTPU_BENCH_SIZE": "160m", "DSTPU_BENCH_SEQ": "1024",
                   "DSTPU_BENCH_BS": "16", "DSTPU_BENCH_STEPS": "20",
                   "DSTPU_BENCH_STAGE": "3"},
    # the A/B for the manual prefetch (2x-unrolled layer scan): compare
    # against 160m-zero3 — if XLA already overlaps, the delta is ~0
    "160m-zero3-prefetch": {"DSTPU_BENCH_SIZE": "160m",
                            "DSTPU_BENCH_SEQ": "1024",
                            "DSTPU_BENCH_BS": "16", "DSTPU_BENCH_STEPS": "20",
                            "DSTPU_BENCH_STAGE": "3",
                            "DSTPU_BENCH_PREFETCH": "1"},
    # compute/collective overlap A/Bs (runtime/zero/overlap.py): compare
    # against 160m-zero1 / 160m-zero3-prefetch — every rung record now
    # carries overlapped_fraction + the exposed-seconds estimate, so the
    # perf trajectory records EXPOSURE, not just walls (a wall delta
    # with an unchanged fraction is not an overlap regression)
    "160m-zero1-overlap": {"DSTPU_BENCH_SIZE": "160m",
                           "DSTPU_BENCH_SEQ": "1024",
                           "DSTPU_BENCH_BS": "16", "DSTPU_BENCH_STEPS": "20",
                           "DSTPU_BENCH_STAGE": "1",
                           "DSTPU_BENCH_OVERLAP": "1"},
    "160m-zero3-overlap": {"DSTPU_BENCH_SIZE": "160m",
                           "DSTPU_BENCH_SEQ": "1024",
                           "DSTPU_BENCH_BS": "16", "DSTPU_BENCH_STEPS": "20",
                           "DSTPU_BENCH_STAGE": "3",
                           "DSTPU_BENCH_PREFETCH": "1",
                           "DSTPU_BENCH_OVERLAP": "1"},
    # compressed overlap (docs/COMM.md "Compressed overlap"): int8 codes
    # + per-bucket EF residuals riding the in-loop exchange — compare
    # against the fp 160m-zero{1,3}-overlap rungs; the wire claim is
    # proven by bench.py --ab-overlap, these measure the wall on chip
    "160m-zero1-overlap-int8": {"DSTPU_BENCH_SIZE": "160m",
                                "DSTPU_BENCH_SEQ": "1024",
                                "DSTPU_BENCH_BS": "16",
                                "DSTPU_BENCH_STEPS": "20",
                                "DSTPU_BENCH_STAGE": "1",
                                "DSTPU_BENCH_OVERLAP": "1",
                                "DSTPU_BENCH_OVERLAP_COMPRESSION": "int8"},
    "160m-zero3-overlap-int8": {"DSTPU_BENCH_SIZE": "160m",
                                "DSTPU_BENCH_SEQ": "1024",
                                "DSTPU_BENCH_BS": "16",
                                "DSTPU_BENCH_STEPS": "20",
                                "DSTPU_BENCH_STAGE": "3",
                                "DSTPU_BENCH_PREFETCH": "1",
                                "DSTPU_BENCH_OVERLAP": "1",
                                "DSTPU_BENCH_OVERLAP_COMPRESSION": "int8"},
    # pipeline-parallel training (runtime/pipe/engine.py): the 2-stage
    # 1F1B pipe scan over the same 160m trunk — compare against flagship
    # (pipe claims 2 chips; data absorbs the rest).  Bit-exactness, EF
    # parity and the hop wire claim are proven by bench.py --ab-pipe on
    # the CPU tier; these rungs measure the wall on chip, and each
    # record carries pipe_bubble_fraction so a wall delta with an
    # unchanged bubble is not a schedule regression
    "160m-pipe2": {"DSTPU_BENCH_SIZE": "160m", "DSTPU_BENCH_SEQ": "1024",
                   "DSTPU_BENCH_BS": "16", "DSTPU_BENCH_STEPS": "20",
                   "DSTPU_BENCH_PIPE": "2"},
    # + int8 activation hops (EF on) and the bubble-overlapped int8
    # in-scan grad reduce — the full compressed-pipe configuration
    "160m-pipe2-int8hop": {"DSTPU_BENCH_SIZE": "160m",
                           "DSTPU_BENCH_SEQ": "1024",
                           "DSTPU_BENCH_BS": "16", "DSTPU_BENCH_STEPS": "20",
                           "DSTPU_BENCH_PIPE": "2",
                           "DSTPU_BENCH_PIPE_HOP": "int8",
                           "DSTPU_BENCH_OVERLAP": "1",
                           "DSTPU_BENCH_OVERLAP_COMPRESSION": "int8"},
    # optimizer offload boundary cost on hardware
    "160m-offload": {"DSTPU_BENCH_SIZE": "160m", "DSTPU_BENCH_SEQ": "1024",
                     "DSTPU_BENCH_BS": "16", "DSTPU_BENCH_STEPS": "10",
                     "DSTPU_BENCH_OFFLOAD": "1"},
    # dropless-MoE kernel throughput (VERDICT r3 weak #3: MoE perf was
    # unmeasured anywhere); 8 experts top-2 on the 160m trunk, ~600M
    # params total, ~320M active — MFU counts active flops only
    "moe-8x160m": {"DSTPU_BENCH_MODEL": "mixtral", "DSTPU_BENCH_SIZE": "8x160m",
                   "DSTPU_BENCH_SEQ": "1024", "DSTPU_BENCH_BS": "8",
                   "DSTPU_BENCH_STEPS": "10"},
    # long-sequence MFU: the Ulysses headline regime (attention-heavy);
    # remat + bf16 accumulation to fit seq=8k activations on one chip
    "160m-seq8k": {"DSTPU_BENCH_SIZE": "160m", "DSTPU_BENCH_SEQ": "8192",
                   "DSTPU_BENCH_BS": "2", "DSTPU_BENCH_STEPS": "10",
                   "DSTPU_BENCH_REMAT": "1", "DSTPU_BENCH_ACC": "bf16"},
    # serving: continuous-batching decode tok/s on the paged v2 engine
    # (runs tools/bench_inference.py instead of bench.py)
    "serving-160m": {"_tool": "bench_inference", "DSTPU_IBENCH_SIZE": "160m",
                     "DSTPU_IBENCH_PROMPT": "512", "DSTPU_IBENCH_GEN": "128",
                     "DSTPU_IBENCH_NREQ": "32"},
    # quantized serving: int8 KV pages + int8 weight-only matmuls — the
    # FastGen-style memory-bound regime where quantization buys capacity
    "serving-160m-int8": {"_tool": "bench_inference",
                          "DSTPU_IBENCH_SIZE": "160m",
                          "DSTPU_IBENCH_PROMPT": "512",
                          "DSTPU_IBENCH_GEN": "128",
                          "DSTPU_IBENCH_NREQ": "32",
                          "DSTPU_IBENCH_KVQ": "1", "DSTPU_IBENCH_WQ": "8"},
    # chunked prefill (Dynamic SplitFuse): same load, 128-token chunks —
    # compare per-step latency tail vs serving-160m
    "serving-160m-chunked": {"_tool": "bench_inference",
                             "DSTPU_IBENCH_SIZE": "160m",
                             "DSTPU_IBENCH_PROMPT": "512",
                             "DSTPU_IBENCH_GEN": "128",
                             "DSTPU_IBENCH_NREQ": "32",
                             "DSTPU_IBENCH_CHUNK": "128"},
    # tiered KV cache (serving/kv_tier.py): prefix families cycling
    # through a device prefix cache capped below the working set, host
    # tier off vs on — prefill tokens computed at the FIXED device pool
    # is the figure of merit; the run hard-gates bit-identity and zero
    # steady-state recompiles
    "serving-160m-kvtier": {"_tool": "bench_serving",
                            "_args": ["--ab-kv-tier"],
                            "DSTPU_SBENCH_SIZE": "160m",
                            "DSTPU_SBENCH_PREFIX": "256",
                            "DSTPU_SBENCH_SUFFIX": "32",
                            "DSTPU_SBENCH_GEN": "32"},
    # NVMe third KV tier (serving/kv_tier.py): same tiered A/B but with
    # the host tier itself byte-budgeted and the file-backed third tier
    # under it — demote/promote traffic must be real and the run
    # additionally hard-gates zero corrupt NVMe records
    "serving-160m-nvme": {"_tool": "bench_serving",
                          "_args": ["--ab-kv-tier"],
                          "DSTPU_SBENCH_SIZE": "160m",
                          "DSTPU_SBENCH_PREFIX": "256",
                          "DSTPU_SBENCH_SUFFIX": "32",
                          "DSTPU_SBENCH_GEN": "32",
                          "DSTPU_SBENCH_NVME": "1"},
    # fused multi-step decode (decode_horizon): K tokens per host
    # round-trip through one on-device decode scan — host syncs per
    # token is the figure of merit; the run hard-gates bit-identity
    # vs the K=1 loop and zero steady-state recompiles
    "serving-160m-multistep": {"_tool": "bench_serving",
                               "_args": ["--ab-multistep"],
                               "DSTPU_SBENCH_SIZE": "160m",
                               "DSTPU_SBENCH_PREFIX": "256",
                               "DSTPU_SBENCH_SUFFIX": "32",
                               "DSTPU_SBENCH_GEN": "128",
                               "DSTPU_SBENCH_HORIZON": "8"},
}


def main() -> int:
    names = sys.argv[1:] or list(RUNGS)
    # test hook: JSON dict merged over every rung (e.g. shrink sizes on CPU)
    overrides = json.loads(os.environ.get("DSTPU_SWEEP_OVERRIDES", "{}"))
    contract_hash = _contract_gate()
    out = []
    # DSTPU_SWEEP_CPU=1 passes --cpu: without it a bench tool exits
    # non-zero on a CPU
    args = ["--cpu"] if os.environ.get("DSTPU_SWEEP_CPU") == "1" else []
    for name in names:
        # ambient DSTPU_BENCH_* exports must not silently reshape a rung:
        # the rung definition + DSTPU_SWEEP_OVERRIDES are the only knobs
        ambient = {k: v for k, v in os.environ.items()
                   if not (k.startswith("DSTPU_BENCH_")
                           or k.startswith("DSTPU_IBENCH_"))}
        rung = dict(RUNGS[name])
        tool = rung.pop("_tool", None)
        extra_args = rung.pop("_args", [])
        env = {**ambient, **rung, **overrides}
        script = os.path.join(ROOT, "tools", tool + ".py") if tool \
            else os.path.join(ROOT, "bench.py")
        print(f"=== rung {name}: {rung}", file=sys.stderr, flush=True)
        rec = {"rung": name, "env": rung,
               "contract_set_hash": contract_hash}
        try:
            # budget: the hang-proof ladder's worst case is
            # 3 rungs x (rung_timeout + 240s post-hang probe) + a CPU
            # fallback run — keep the rung budget small enough that the
            # whole ladder plus fallback fits the rung-set timeout
            env.setdefault("DSTPU_BENCH_RUNG_TIMEOUT", "600")
            proc = subprocess.run(
                [sys.executable, script, *extra_args, *args],
                capture_output=True, text=True, env=env, timeout=5400)
            line = (proc.stdout.strip().splitlines() or [""])[-1]
            try:
                rec["result"] = json.loads(line)
            except ValueError:
                rec["error"] = (proc.stderr[-500:] or "no output")
        except subprocess.TimeoutExpired:
            # one hung rung must not discard the completed rungs' results
            rec["error"] = "rung timed out after 5400s"
        out.append(rec)
        print(json.dumps(rec), file=sys.stderr)
        # write incrementally, MERGING over any previous sweep file: a
        # session runs one rung per invocation, and each must extend the
        # artifact, not clobber the earlier rungs' records
        path = os.path.join(ROOT, "docs", "BENCH_SWEEP.json")
        merged = []
        try:
            with open(path) as f:
                merged = [r for r in json.load(f)
                          if r.get("rung") not in {o["rung"] for o in out}]
        except (OSError, ValueError):
            pass
        with open(path, "w") as f:
            json.dump(merged + out, f, indent=1)
    for rec in out:
        r = rec.get("result", {})
        ovl = (f" ovl={r.get('overlapped_fraction')}"
               if r.get("overlapped_fraction") is not None else "")
        print(f"{rec['rung']:>14}: "
              + (f"{r.get('value')} {r.get('unit')} mfu={r.get('mfu')} "
                 f"backend={r.get('backend')}{ovl}" if r else
                 f"ERROR {rec.get('error', '')[:120]}"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
