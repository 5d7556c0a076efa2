"""Benchmark: llama causal-LM training throughput on the local chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The comparator: the reference's headline sustained utilization is 54% of
hardware peak (Ulysses blog, BASELINE.md) — ``vs_baseline`` is our achieved
model-flops-utilization divided by 0.54, i.e. >1.0 means we beat the
reference's utilization on our hardware.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

def _int_env(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


def _pin_cpu() -> None:
    """Run on the CPU platform (``--cpu`` and the deterministic A/B tiers).
    Called before anything imports jax."""
    os.environ["JAX_PLATFORMS"] = "cpu"


def _device_or_exit(allow_cpu: bool):
    """The first device of the platform JAX selected.  A CPU is not a
    fallback: unless ``--cpu`` asked for it, it is exit code 2 and no
    JSON (shared with tools/bench_inference.py and bench_serving.py)."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not allow_cpu:
        print(f"{os.path.basename(sys.argv[0])}: platform is "
              f"{dev.platform!r} ({dev.device_kind}): no accelerator, no "
              "benchmark.  --cpu runs the tiny CPU rung, which is not a "
              "device number.", file=sys.stderr)
        sys.exit(2)
    return dev


def _peak_for(device) -> float:
    # canonical per-generation table lives in telemetry/mfu.py (one copy,
    # shared with the engine's MFU gauge and tools/tune_mfu.py); imported
    # lazily so --cpu pinning happens before any jax-touching import
    from deepspeed_tpu.telemetry.mfu import peak_flops_for_device

    return peak_flops_for_device(device)



def build_model_and_config(size: str, seq: int, micro_bs: int, env=None,
                           attn_impl=None):
    """Model + ds-config for a bench rung.

    ``env``: mapping of DSTPU_BENCH_* knobs (default os.environ)."""
    env = os.environ if env is None else env
    # big models need remat + bf16 grad accumulation + tiled loss to fit
    # one chip's HBM; 160m runs leaner without them
    big = size in ("1b", "7b", "13b", "70b")
    remat = env.get("DSTPU_BENCH_REMAT", "1" if big else "0") == "1"
    acc = env.get("DSTPU_BENCH_ACC", "bf16" if big else "fp32")
    if env.get("DSTPU_BENCH_LOSS_CHUNK"):
        chunk = int(env["DSTPU_BENCH_LOSS_CHUNK"])
    elif big and seq > 2:
        # largest divisor of seq-1 (the shifted-label length) up to 512;
        # a near-prime seq-1 would degenerate into thousands of tiny
        # chunks — then materializing the logits beats tiling
        n = seq - 1
        chunk = max(d for d in range(1, min(n, 512) + 1) if n % d == 0)
        if chunk < 32:
            chunk = 0
    else:
        chunk = 0
    over = {}
    if remat:
        over.update(remat=True,
                    remat_policy=env.get("DSTPU_BENCH_REMAT_POLICY",
                                         "nothing_saveable"))
    if chunk:
        over["loss_chunk"] = chunk
    attn_impl = attn_impl or env.get("DSTPU_BENCH_ATTN")
    if attn_impl:
        over["attn_impl"] = attn_impl
    # family knob (VERDICT r3 weak #3: MoE perf must be measurable on the
    # same harness): mixtral routes tokens through the dropless MoE path;
    # flops_per_token counts only the active (top-k) experts
    family = env.get("DSTPU_BENCH_MODEL", "llama")
    # pipeline rungs (docs/PIPELINE.md): DSTPU_BENCH_PIPE=P runs the
    # 1F1B pipe scan over P stages; DSTPU_BENCH_PIPE_HOP compresses the
    # activation hops (int8/fp8, EF on by default)
    pipe = int(env.get("DSTPU_BENCH_PIPE", "0") or 0)
    if pipe > 1:
        if family != "llama":
            raise ValueError(
                f"DSTPU_BENCH_PIPE={pipe} supports only the llama family "
                f"(got DSTPU_BENCH_MODEL={family!r})")
        from deepspeed_tpu.models.llama import llama_config
        from deepspeed_tpu.runtime.pipe.engine import pipelined_causal_lm

        num_micro = int(env.get("DSTPU_BENCH_PIPE_MICRO", "4") or 4)
        model = pipelined_causal_lm(llama_config(size, max_seq_len=seq,
                                                 **over),
                                    num_microbatches=num_micro)
    elif family == "mixtral":
        from deepspeed_tpu.models.mixtral import mixtral_model

        # dropless: the grouped-matmul MoE path — the capacity-factor
        # default would drop overflow tokens and run dispatch einsums,
        # a different algorithm than the top_k-priced MFU metric
        model = mixtral_model(size, max_seq_len=seq, moe_drop_tokens=False,
                              **over)
    elif family == "llama":
        from deepspeed_tpu.models.llama import llama_model

        model = llama_model(size, max_seq_len=seq, **over)
    else:
        # the family name is interpolated into the published metric — a
        # typo must not run llama and label the artifact with another name
        raise ValueError(f"unknown DSTPU_BENCH_MODEL {family!r}")
    # stage/offload rungs are env-selectable (VERDICT r3 next #2): stage-3
    # and the offload boundary must be measurable on the same model/chip,
    # not hardcoded out of the artifact
    stage = int(env.get("DSTPU_BENCH_STAGE", "1") or 1)
    zero_cfg = {"stage": stage}
    if env.get("DSTPU_BENCH_OFFLOAD") == "1":
        zero_cfg["offload_optimizer"] = {"device": "cpu"}
    if env.get("DSTPU_BENCH_PREFETCH") == "1":
        # stage-3 manual prefetch A/B (explicit in-loop gathers on the
        # 2x-unrolled layer scan)
        zero_cfg["zero3_param_prefetch"] = True
    if env.get("DSTPU_BENCH_OVERLAP") == "1":
        # compute/collective overlap A/B (runtime/zero/overlap.py):
        # per-layer-bucket grad reduce inside the backward loop
        zero_cfg["overlap_grad_reduce"] = True
    if env.get("DSTPU_BENCH_OVERLAP_BUCKET_MB"):
        zero_cfg["overlap_bucket_mb"] = float(
            env["DSTPU_BENCH_OVERLAP_BUCKET_MB"])
    if env.get("DSTPU_BENCH_OVERLAP_COMPRESSION"):
        # compressed overlap A/B (docs/COMM.md "Compressed overlap"):
        # int8/fp8 codes + per-bucket EF residuals inside the loop
        zero_cfg["overlap_compression"] = \
            env["DSTPU_BENCH_OVERLAP_COMPRESSION"]
    opt_params = {"lr": 1e-4, "weight_decay": 0.1}
    if env.get("DSTPU_BENCH_MU_DTYPE"):
        # bf16 exp_avg: -2 bytes/param of optimizer HBM (helps the 1b
        # model fit one chip without offload)
        opt_params["mu_dtype"] = env["DSTPU_BENCH_MU_DTYPE"]
    if env.get("DSTPU_BENCH_FUSED_OPT") == "1":
        opt_params["fused_kernel"] = True
    config = {
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": opt_params},
        "bf16": {"enabled": True},
        "zero_optimization": zero_cfg,
        "gradient_clipping": 1.0,
        "data_types": {"grad_accum_dtype": acc},
    }
    if env.get("DSTPU_BENCH_NUMERICS", "1") == "1":
        # numerics observatory (docs/OBSERVABILITY.md): per-layer health
        # stats ride the fused step as extra tiny outputs, pulled only at
        # the steps_per_print boundary.  Shared here so the estimator
        # compiles the same program the bench runs; the cadence is pinned
        # low enough that even the short CPU rung crosses a boundary.
        config["telemetry"] = {"enabled": True,
                               "numerics": {"enabled": True}}
        config["steps_per_print"] = int(env.get("DSTPU_BENCH_SPP", "5") or 5)
    if pipe > 1:
        # pipe stages claim their axis; data absorbs the remaining chips
        config["mesh"] = {"pipe": pipe, "data": -1}
        if env.get("DSTPU_BENCH_PIPE_HOP"):
            config["pipeline"] = {
                "hop_compression": env["DSTPU_BENCH_PIPE_HOP"]}
    return model, config, {"family": family, "stage": stage,
                           "zero_cfg": zero_cfg, "pipe": pipe}


def _run(size: str, seq: int, micro_bs: int, steps: int,
         attn_impl=None) -> dict:
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import flops_per_token

    model, config, _meta = build_model_and_config(
        size, seq, micro_bs, attn_impl=attn_impl)
    family, stage, zero_cfg = _meta["family"], _meta["stage"], _meta["zero_cfg"]
    engine, *_ = deepspeed_tpu.initialize(model=model, config=config)
    dp = engine.topology.dp_world_size
    n_chips = engine.topology.world_size

    rng = np.random.RandomState(0)
    vocab = model.config.vocab_size

    def batch():
        ids = rng.randint(0, vocab, (1, micro_bs * dp, seq)).astype(np.int32)
        return {"input_ids": jnp.asarray(ids)}

    # warmup / compile.  Several steps, not one: donation-variant compiles
    # and device-queue ramp land in steps 2-4, and a single warmup step let
    # them pollute the timed window
    warmup = int(os.environ.get("DSTPU_BENCH_WARMUP", "5"))
    # run-level goodput of this bench process (buckets sum to the
    # ledger's lifetime): warmup/compile is badput, the timed window is
    # productive — created HERE so its lifetime covers both phases
    gp = None
    try:
        from deepspeed_tpu.telemetry.goodput import GoodputLedger
        from deepspeed_tpu.telemetry.registry import MetricsRegistry

        gp = GoodputLedger(registry=MetricsRegistry())
    except Exception:
        pass
    loss = None
    t_warm0 = time.perf_counter()
    for _ in range(warmup):
        loss = engine.train_batch(batch())
    if loss is not None:
        float(loss)

    warmup_dt = time.perf_counter() - t_warm0

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch())
    final_loss = float(loss)  # pulls the value: the window ends with the work
    dt = time.perf_counter() - t0
    assert np.isfinite(final_loss)

    # measured step-time attribution (telemetry/timeline.py): one extra
    # profiled step OUTSIDE the timed window — the decomposition says
    # where the wall went (CPU runs stamp measured: false honestly)
    timeline_rec = None
    try:
        from deepspeed_tpu.telemetry.timeline import capture_thunk

        _, timeline_rec = capture_thunk(
            lambda: float(engine.train_batch(batch())),
            step=engine.global_steps,
            pipe_struct=getattr(engine, "_pipe_struct", None))
    except Exception as e:  # attribution must never sink a bench run
        print(f"bench: timeline capture failed ({e}); omitting", file=sys.stderr)

    tokens = steps * micro_bs * dp * seq
    tok_per_sec_chip = tokens / dt / n_chips
    model_flops = flops_per_token(model.config, seq) * tokens
    dev = jax.devices()[0]
    mfu = model_flops / dt / (n_chips * _peak_for(dev))

    tag = f"zero{stage}" \
        + (f"-pipe{_meta['pipe']}" if _meta.get("pipe") else "") \
        + ("-offload" if "offload_optimizer" in zero_cfg else "")
    result = {
        "metric": f"{family}-{size} bf16 {tag} tokens/sec/chip "
                  f"(seq={seq}, bs={micro_bs}, mfu={mfu:.3f})",
        "value": round(tok_per_sec_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.54, 3),
        "backend": jax.default_backend(),
        "device_kind": str(getattr(dev, "device_kind", "unknown")),
        "mfu": round(mfu, 4),
    }
    if stage != 1 or "offload_optimizer" in zero_cfg:
        # the 0.54 comparator was measured under the zero1-style dense
        # regime; flag it so non-default rungs aren't read as regressions
        result["comparator_note"] = "vs_baseline divides by the 0.54 zero1 comparator"
    # a --cpu run measures other hardware AND another rung: it stamps
    # itself out of any device trajectory
    result["comparable"] = jax.default_backend() != "cpu"
    # exposure accounting (telemetry/overlap.py): the perf trajectory
    # records how much of the grad exchange is overlap-scheduled, not
    # just walls — a wall regression with an unchanged fraction is not
    # an overlap regression (tools/bench_sweep.py carries these into
    # every rung record)
    rep = engine.overlap_report()
    if rep is not None:
        result["overlapped_fraction"] = round(rep.overlapped_fraction, 4)
        result["exposed_collective_seconds_per_step_est"] = round(
            rep.exposed_seconds_per_step, 6)
    # measured decomposition of one profiled step (estimated-vs-measured
    # semantics: docs/OBSERVABILITY.md "Step-time attribution & goodput")
    if timeline_rec is not None:
        result["timeline"] = {
            "measured": timeline_rec["measured"],
            "wall_seconds": round(timeline_rec["wall_seconds"], 6),
            "categories": {k: round(v, 6)
                           for k, v in timeline_rec["categories"].items()},
            "exposed_collective_seconds":
                timeline_rec["exposed_collective_seconds"],
            "overlapped_collective_seconds":
                timeline_rec["overlapped_collective_seconds"],
        }
    if gp is not None:
        try:
            gp.observe_phase("compile", warmup_dt)
            for _ in range(steps):
                gp.observe_step(dt / steps)
            result["goodput"] = gp.summary()
        except Exception as e:
            print(f"bench: goodput ledger failed ({e}); omitting",
                  file=sys.stderr)
    # schedule-shape provenance for pipe rungs: the bubble is structural
    # ((P-1)/(M+P-1)), so a wall regression with an unchanged bubble is
    # not a schedule regression
    struct = getattr(engine, "_pipe_struct", None)
    if struct:
        result["pipe_bubble_fraction"] = round(struct["bubble_fraction"], 4)
        result["pipe_stages"] = struct["stages"]
    # numerics annex: a perf rung doubles as a training-health artifact —
    # layer-norm medians, anomaly counts, and the cross-rank divergence
    # verdict are stamped into the bench JSON so a throughput number that
    # rode a silently-diverging or overflow-storming run is self-labelled
    num = None
    try:
        num = engine.numerics_report()
    except Exception as e:  # the annex must never sink a bench run
        print(f"bench: numerics report failed ({e}); omitting",
              file=sys.stderr)
    if num:
        last = num.get("last_report") or {}
        div = num.get("divergence")

        def _layer_median(key):
            vals = (last.get("layers") or {}).get(key) or []
            return round(float(np.median(vals)), 6) if vals else None

        result["numerics"] = {
            "boundaries": num["boundaries"],
            "anomaly_counts": num["anomaly_counts"],
            "grad_norm_median": num.get("grad_norm_median"),
            "grad_layer_norm_median": _layer_median("grad_norm"),
            "act_layer_norm_median": _layer_median("act_norm"),
            "param_layer_norm_median": _layer_median("param_norm"),
            "grad_nonfinite": last.get("grad_nonfinite"),
            "divergence_ok": None if div is None else bool(div.get("ok")),
            "first_diverging_leaf": (div or {}).get("first_diverging_leaf"),
        }
    # provenance: which program contracts (tests/contracts/*.json) this
    # result ran under — a perf claim is only comparable to another run
    # with the same contract-set hash (same collectives, same donation)
    from deepspeed_tpu.analysis.contracts import contract_set_hash

    result["contract_set_hash"] = contract_set_hash(
        os.path.dirname(os.path.abspath(__file__)))
    return result


def _ab_compression() -> None:
    """Deterministic CPU *training* tier (the trainer's sibling of
    ``bench_serving.py --ab-speculative``): fixed tiny model/seq/batch on
    the 8-virtual-device harness, pinned seeds, median-of-k walls,
    ``comparable: true`` — run as an A/B of the compressed-collective
    layer (docs/COMM.md).

    Arm A: stage-1 + hierarchical grad reduce, full-precision hops (the
    explicit-verb path, so the comms logger sees every byte).
    Arm B: the same with the int8 inter-slice exchange
    (``zero_quantized_gradients``).

    Machine-checked claims in the JSON:
      * determinism — arm A re-run from scratch reproduces its loss curve
        bit-for-bit (pinned seeds, CPU);
      * ``wire_reduction`` — logical/wire byte ratio of the compressed
        collectives from the comms-logger columns (>= 2x is the
        acceptance bar; int8 + block scales gives ~3.9x);
      * ``loss_parity_max_rel`` — seed-matched quantized-vs-fp curve gap.
    """
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.models.llama import llama_model
    from deepspeed_tpu.parallel.mesh import reset_topology

    steps = _int_env("DSTPU_BENCH_AB_STEPS", 6)
    repeats = _int_env("DSTPU_BENCH_AB_REPEATS", 3)
    seq, micro_bs = 32, 1

    cl = comm.configure_comms_logger(enabled=True)

    def run(qgz: bool):
        reset_topology()
        cl.reset()
        model = llama_model("tiny", max_seq_len=seq)
        engine, *_ = deepspeed_tpu.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": micro_bs,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1,
                                  "zero_hierarchical_grad_reduce": True,
                                  "zero_hierarchy_inner": 2,
                                  "zero_quantized_gradients": qgz},
        })
        dp = engine.topology.dp_world_size
        rng = np.random.RandomState(0)  # pinned: both arms see one stream
        vocab = model.config.vocab_size
        batches = [{"input_ids": jnp.asarray(
            rng.randint(0, vocab, (1, micro_bs * dp, seq)).astype(np.int32))}
            for _ in range(steps)]
        losses = [float(engine.train_batch(b)) for b in batches]
        # bytes are TRACE-time: captured once while the curve ran compiles
        logical = sum(r[1] for axes in cl.comms_dict.values()
                      for r in axes.values())
        wire = sum(r[2] for axes in cl.comms_dict.values()
                   for r in axes.values())
        comp_logical = sum(r[3] for axes in cl.comms_dict.values()
                           for r in axes.values())
        comp_wire = sum(r[4] for axes in cl.comms_dict.values()
                        for r in axes.values())
        # steady-state walls: same shapes, no recompiles
        walls = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            for b in batches:
                loss = engine.train_batch(b)
            jax.block_until_ready(loss)
            walls.append(time.perf_counter() - t0)
        return {"losses": losses, "logical": logical, "wire": wire,
                "comp_logical": comp_logical, "comp_wire": comp_wire,
                "wall_median_s": sorted(walls)[len(walls) // 2]}

    fp = run(qgz=False)
    fp2 = run(qgz=False)  # determinism gate: pinned seeds reproduce exactly
    assert fp["losses"] == fp2["losses"], "CPU tier is not deterministic"
    q = run(qgz=True)
    cl.configure(enabled=False)

    parity = max(abs(a - b) / max(abs(a), 1e-9)
                 for a, b in zip(fp["losses"], q["losses"]))
    wire_reduction = (q["comp_logical"] / q["comp_wire"]
                      if q["comp_wire"] else 1.0)
    from deepspeed_tpu.analysis.contracts import contract_set_hash

    print(json.dumps({
        "metric": "ab-compression: hierarchical stage-1 grad reduce, "
                  "int8 vs fp inter-slice exchange (tiny llama, "
                  f"seq={seq}, steps={steps})",
        "value": round(wire_reduction, 3),
        "unit": "x wire-bytes reduction (compressed collectives)",
        "comparable": True,  # deterministic pinned-seed CPU tier
        "backend": jax.default_backend(),
        "wire_reduction": round(wire_reduction, 3),
        "total_bytes_fp": fp["wire"],
        "total_bytes_int8": q["wire"],
        "total_wire_reduction": round(fp["wire"] / max(q["wire"], 1), 3),
        "loss_parity_max_rel": round(parity, 5),
        "loss_parity_ok": parity < 0.05,
        "final_loss_fp": fp["losses"][-1],
        "final_loss_int8": q["losses"][-1],
        "wall_median_s": {"fp": round(fp["wall_median_s"], 4),
                          "int8": round(q["wall_median_s"], 4)},
        "contract_set_hash": contract_set_hash(
            os.path.dirname(os.path.abspath(__file__))),
    }))


def _ab_overlap() -> None:
    """Deterministic CPU *training* tier for the compute/collective
    overlap (docs/COMM.md "Overlap & scheduling"): fixed tiny scanned
    llama on the 8-virtual-device harness, pinned seeds, median-of-k
    walls, ``comparable: true``.

    Arms, per ZeRO stage in {1, 3}:
      * ``off``        — the legacy GSPMD step (no wrap);
      * ``unbucketed`` — overlap wrap with ``overlap_bucket_mb=0``
        (per-leaf buckets, no coalescing);
      * ``on``         — overlap wrap, default buckets (+
        ``zero3_param_prefetch`` at stage 3);
      * ``int8``       — COMPRESSED overlap (docs/COMM.md "Compressed
        overlap"): the in-loop exchange moves int8 codes + scales with
        ONE error-feedback residual per bucket in train state (stage 1
        via ``zero_quantized_gradients``, stage 3 via
        ``overlap_compression``), plus its own unbucketed twin.

    Machine-checked claims in the JSON:
      * determinism — the ``on`` AND ``int8`` arms re-run from scratch
        reproduce their loss curves bit-for-bit;
      * ``identical_to_unbucketed`` — per compression setting, bucketed
        vs unbucketed losses are BIT-EXACT (fp: scheduling only; int8:
        block-aligned coalescing + layout-stable hop-1 residuals);
      * ``loss_parity_max_rel`` — ``on`` vs ``off`` is fp reassociation
        noise, asserted < 1e-4; ``int8`` vs ``on`` is codec noise,
        asserted at the PR-11 tolerance (< 0.05);
      * ``wire_reduction`` — compressed-subset logical/wire bytes from
        the comms logger during the ``int8`` arm, gated >= 2x vs the
        fp32-overlap payloads;
      * ``overlapped_fraction`` per arm (0 for ``off``), the bucket
        count, compression + residual bytes, traceable to the
        ``train_step_zero*_overlap*`` goldens via ``contract_set_hash``.
    """
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.models.llama import llama_model
    from deepspeed_tpu.parallel.mesh import reset_topology

    steps = _int_env("DSTPU_BENCH_AB_STEPS", 6)
    repeats = _int_env("DSTPU_BENCH_AB_REPEATS", 3)
    seq, micro_bs = 32, 1
    cl = comm.configure_comms_logger(enabled=True)

    def run(stage, overlap, bucket_mb=4.0, prefetch=False,
            compressed=False):
        reset_topology()
        cl.reset()
        model = llama_model("tiny", max_seq_len=seq)
        zero_cfg = {"stage": stage, "overlap_grad_reduce": overlap,
                    "overlap_bucket_mb": bucket_mb}
        if prefetch:
            zero_cfg["zero3_param_prefetch"] = True
        if compressed:
            if stage <= 2:
                zero_cfg["zero_quantized_gradients"] = True
            else:
                zero_cfg["overlap_compression"] = "int8"
        engine, *_ = deepspeed_tpu.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": micro_bs,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": zero_cfg,
        })
        dp = engine.topology.dp_world_size
        rng = np.random.RandomState(0)  # pinned: every arm sees one stream
        vocab = model.config.vocab_size
        batches = [{"input_ids": jnp.asarray(
            rng.randint(0, vocab, (1, micro_bs * dp, seq)).astype(np.int32))}
            for _ in range(steps)]
        losses = [float(engine.train_batch(b)) for b in batches]
        # compressed-subset bytes are TRACE-time (captured while the
        # curve ran its compiles): what the quantized payloads moved vs
        # what fp32 would have moved for the same payloads
        comp_logical = sum(r[3] for axes in cl.comms_dict.values()
                           for r in axes.values())
        comp_wire = sum(r[4] for axes in cl.comms_dict.values()
                        for r in axes.values())
        walls = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            for b in batches:
                loss = engine.train_batch(b)
            jax.block_until_ready(loss)
            walls.append(time.perf_counter() - t0)
        rep = engine.overlap_report()
        # measured exposed-collective seconds (profiled extra step,
        # outside the timed window) next to the modeled byte-model
        # number; None when the backend yields no device trace (CPU)
        measured_exposed, tl_measured = None, False
        try:
            from deepspeed_tpu.telemetry.timeline import capture_thunk

            _, tl_rec = capture_thunk(
                lambda: float(engine.train_batch(batches[0])))
            if tl_rec is not None and tl_rec["measured"]:
                tl_measured = True
                measured_exposed = round(
                    tl_rec["exposed_collective_seconds"], 6)
        except Exception:
            pass  # attribution must never sink the A/B
        return {"losses": losses,
                "wall_median_s": sorted(walls)[len(walls) // 2],
                "overlapped_fraction": (round(rep.overlapped_fraction, 4)
                                        if rep else 0.0),
                "exposed_seconds_per_step_est": (
                    round(rep.exposed_seconds_per_step, 6) if rep else None),
                "exposed_seconds_per_step_measured": measured_exposed,
                "timeline_measured": tl_measured,
                "buckets": rep.buckets if rep else 0,
                "compression": rep.compression if rep else None,
                "residual_bytes": rep.residual_bytes if rep else 0,
                "comp_logical": comp_logical, "comp_wire": comp_wire}

    out = {"metric": "ab-overlap: per-layer-bucket grad reduce + stage-3 "
                     f"gather prefetch vs the post-backward block, with a "
                     f"compressed (int8-in-loop + EF) arm (tiny llama, "
                     f"seq={seq}, steps={steps})",
           "unit": "overlapped fraction of grad-exchange bytes",
           "comparable": True,  # deterministic pinned-seed CPU tier
           "stages": {}}
    worst_parity = 0.0
    worst_qparity = 0.0
    worst_wire = float("inf")
    for stage in (1, 3):
        off = run(stage, overlap=False)
        unb = run(stage, overlap=True, bucket_mb=0.0,
                  prefetch=(stage == 3))
        on = run(stage, overlap=True, prefetch=(stage == 3))
        on2 = run(stage, overlap=True, prefetch=(stage == 3))
        assert on["losses"] == on2["losses"], \
            f"stage {stage}: CPU tier is not deterministic"
        identical = on["losses"] == unb["losses"]
        assert identical, (
            f"stage {stage}: bucketed overlap diverged from the "
            f"unbucketed path — scheduling changed the math\n"
            f"on:  {on['losses']}\nunb: {unb['losses']}")
        q = run(stage, overlap=True, prefetch=(stage == 3),
                compressed=True)
        q2 = run(stage, overlap=True, prefetch=(stage == 3),
                 compressed=True)
        assert q["losses"] == q2["losses"], \
            f"stage {stage}: compressed arm is not deterministic"
        q_unb = run(stage, overlap=True, bucket_mb=0.0,
                    prefetch=(stage == 3), compressed=True)
        q_identical = q["losses"] == q_unb["losses"]
        assert q_identical, (
            f"stage {stage}: compressed bucketed overlap diverged from "
            f"its unbucketed twin — the block-aligned coalesce / "
            f"layout-stable residual contract broke\n"
            f"int8:  {q['losses']}\nunb:   {q_unb['losses']}")
        assert q["compression"] == "int8", q["compression"]
        # wire claim: the quantized in-loop payloads move >= 2x fewer
        # bytes than the same payloads at fp32 width (the fp32-overlap
        # arm's wire volume for the compressed subset)
        wire_reduction = (q["comp_logical"] / q["comp_wire"]
                          if q["comp_wire"] else 0.0)
        assert wire_reduction >= 2.0, (
            f"stage {stage}: compressed overlap wire reduction "
            f"{wire_reduction:.2f}x < 2x")
        worst_wire = min(worst_wire, wire_reduction)
        parity = max(abs(a - b) / max(abs(a), 1e-9)
                     for a, b in zip(off["losses"], on["losses"]))
        worst_parity = max(worst_parity, parity)
        qparity = max(abs(a - b) / max(abs(a), 1e-9)
                      for a, b in zip(on["losses"], q["losses"]))
        worst_qparity = max(worst_qparity, qparity)
        out["stages"][f"zero{stage}"] = {
            "contract": ("train_step_zero1_overlap" if stage == 1
                         else "train_step_zero3_prefetch"),
            "contract_int8": ("train_step_zero1_overlap_int8" if stage == 1
                              else "train_step_zero3_prefetch_int8"),
            "identical_to_unbucketed": identical,
            "int8_identical_to_unbucketed": q_identical,
            "loss_parity_max_rel_vs_off": round(parity, 7),
            "loss_parity_max_rel_int8_vs_fp_overlap": round(qparity, 7),
            "final_loss_off": off["losses"][-1],
            "final_loss_on": on["losses"][-1],
            "final_loss_int8": q["losses"][-1],
            "overlapped_fraction": on["overlapped_fraction"],
            "overlapped_fraction_int8": q["overlapped_fraction"],
            # modeled (byte-model) vs measured (device-trace) exposure:
            # est comes from the overlap report, measured from one
            # profiled step (null on CPU — measured: false)
            "exposed_seconds_per_step_est": {
                "on": on["exposed_seconds_per_step_est"],
                "int8": q["exposed_seconds_per_step_est"]},
            "exposed_seconds_per_step_measured": {
                "on": on["exposed_seconds_per_step_measured"],
                "int8": q["exposed_seconds_per_step_measured"]},
            "timeline_measured": on["timeline_measured"],
            "buckets": on["buckets"],
            "wire_reduction_int8": round(wire_reduction, 3),
            "residual_bytes_int8": q["residual_bytes"],
            "wall_median_s": {"off": round(off["wall_median_s"], 4),
                              "unbucketed": round(unb["wall_median_s"], 4),
                              "on": round(on["wall_median_s"], 4),
                              "int8": round(q["wall_median_s"], 4)},
        }
    cl.configure(enabled=False)
    assert worst_parity < 1e-4, \
        f"overlap-on vs overlap-off loss gap {worst_parity} is not " \
        "reassociation-sized"
    assert worst_qparity < 0.05, \
        f"int8-overlap vs fp32-overlap loss gap {worst_qparity} exceeds " \
        "the PR-11 codec tolerance"
    import jax as _jax

    out["backend"] = _jax.default_backend()
    out["value"] = out["stages"]["zero1"]["overlapped_fraction"]
    out["loss_parity_ok"] = worst_parity < 1e-4 and worst_qparity < 0.05
    out["wire_reduction_min"] = round(worst_wire, 3)
    out["wire_reduction_ok"] = worst_wire >= 2.0
    from deepspeed_tpu.analysis.contracts import contract_set_hash

    out["contract_set_hash"] = contract_set_hash(
        os.path.dirname(os.path.abspath(__file__)))
    print(json.dumps(out))


def _ab_pipe() -> None:
    """Deterministic CPU *training* tier for pipeline parallelism
    (docs/PIPELINE.md): fixed tiny llama on the 8-virtual-device
    harness, pinned seeds, median-of-k walls, ``comparable: true``.

    Arms, at EQUAL global batch (8 rows/step):
      * ``control`` — single-stage (pipe=1) with the pipe schedule
        FORCED, data=2: the same scan/ppermute program shape with
        identity hops, so any pipe-vs-control gap is the schedule's
        math, not a different program;
      * ``pipe2``   — 2 stages x 2 data, full-precision hops;
      * ``int8hop`` — 2 stages x 2 data, int8 activation hops with
        error feedback (``pipeline.hop_compression``) PLUS the
        bubble-overlapped int8 in-scan grad reduce (stage 1 +
        ``overlap_grad_reduce`` + ``overlap_compression``).

    Machine-checked claims in the JSON:
      * determinism — the control arm re-run from scratch reproduces
        its loss curve bit-for-bit;
      * ``pipe_bit_exact`` — pipe2 vs control losses are BIT-EXACT (the
        1F1B schedule is a reassociation-free reshuffle of the same
        microbatch math; arms share initial params by value because
        jitted init is sharding-dependent under non-partitionable
        threefry);
      * ``hop_wire_reduction`` — logical/wire bytes of the compressed
        ppermute rows from the comms logger during the int8 arm,
        gated >= 2x;
      * ``loss_parity_max_rel`` — int8hop vs pipe2 codec gap, < 0.05;
      * ``bubble_fraction`` — the published (P-1)/(M+P-1) schedule
        bubble, traceable to the ``train_step_pipe2`` golden via
        ``contract_set_hash``.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.models.llama import llama_config
    from deepspeed_tpu.parallel.mesh import (MeshConfig, initialize_topology,
                                             reset_topology)
    from deepspeed_tpu.runtime.pipe.engine import pipelined_causal_lm

    steps = _int_env("DSTPU_BENCH_AB_STEPS", 6)
    repeats = _int_env("DSTPU_BENCH_AB_REPEATS", 3)
    seq, vocab, micro_bs, num_micro = 32, 64, 4, 2
    cl = comm.configure_comms_logger(enabled=True)
    ref_params = {}

    def run(mesh_cfg, n_dev, extra_cfg, force_schedule=False):
        reset_topology()
        cl.reset()
        topo = initialize_topology(mesh_cfg, jax.devices()[:n_dev])
        cfg = llama_config("tiny", max_seq_len=seq, vocab_size=vocab,
                           n_layers=2, attn_impl="xla")
        model = pipelined_causal_lm(cfg, num_microbatches=num_micro,
                                    force_schedule=force_schedule)
        config = {"train_micro_batch_size_per_gpu": micro_bs,
                  "gradient_accumulation_steps": 1,
                  "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
        config.update(extra_cfg)
        engine, *_ = deepspeed_tpu.initialize(model=model, config=config,
                                              topology=topo)
        # equal-global-batch arms must share initial params BY VALUE:
        # jitted init with out_shardings draws DIFFERENT randoms per
        # mesh under the non-partitionable threefry
        if not ref_params:
            ref_params["p"] = jax.device_get(engine.state.params)
        else:
            shared = jax.tree_util.tree_map(
                lambda r, p: jax.device_put(r, p.sharding),
                ref_params["p"], engine.state.params)
            engine.state = dataclasses.replace(engine.state, params=shared)
        dp = engine.topology.dp_world_size
        rng = np.random.RandomState(0)  # pinned: every arm sees one stream
        batches = [{"input_ids": jnp.asarray(
            rng.randint(0, vocab, (1, micro_bs * dp, seq)).astype(np.int32))}
            for _ in range(steps)]
        losses = [float(engine.train_batch(b)) for b in batches]
        # hop bytes are TRACE-time: the compressed-subset columns of the
        # ppermute rows are exactly the int8 activation hops (plain fp
        # hops go through lax.ppermute and never log)
        hop_rows = cl.comms_dict.get("ppermute", {})
        hop_logical = sum(r[3] for r in hop_rows.values())
        hop_wire = sum(r[4] for r in hop_rows.values())
        walls = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            for b in batches:
                loss = engine.train_batch(b)
            jax.block_until_ready(loss)
            walls.append(time.perf_counter() - t0)
        # measured bubble/exposure from one profiled step (outside the
        # timed window) next to the structural (P-1)/(M+P-1) claim;
        # None when the backend yields no device trace (CPU)
        struct = getattr(engine, "_pipe_struct", None)
        measured_exposed, measured_bubble, tl_measured = None, None, False
        try:
            from deepspeed_tpu.telemetry.timeline import capture_thunk

            _, tl_rec = capture_thunk(
                lambda: float(engine.train_batch(batches[0])),
                pipe_struct=struct)
            if tl_rec is not None and tl_rec["measured"]:
                tl_measured = True
                measured_exposed = round(
                    tl_rec["exposed_collective_seconds"], 6)
                measured_bubble = round(
                    tl_rec["categories"].get("pipe_bubble", 0.0), 6)
        except Exception:
            pass  # attribution must never sink the A/B
        return {"losses": losses, "hop_logical": hop_logical,
                "hop_wire": hop_wire,
                "wall_median_s": sorted(walls)[len(walls) // 2],
                "exposed_seconds_per_step_measured": measured_exposed,
                "pipe_bubble_seconds_measured": measured_bubble,
                "timeline_measured": tl_measured,
                "pipe_struct": struct}

    ctl = run(MeshConfig(data=2), 2, {"mesh": {"data": 2}},
              force_schedule=True)
    ctl2 = run(MeshConfig(data=2), 2, {"mesh": {"data": 2}},
               force_schedule=True)
    assert ctl["losses"] == ctl2["losses"], "CPU tier is not deterministic"
    pipe = run(MeshConfig(pipe=2, data=2), 4, {"mesh": {"pipe": 2, "data": 2}})
    bit_exact = ctl["losses"] == pipe["losses"]
    assert bit_exact, (
        "pipe=2 diverged from the single-stage control at equal global "
        "batch — the 1F1B schedule changed the math\n"
        f"ctl:  {ctl['losses']}\npipe: {pipe['losses']}")
    # block=64 matches the tiny model's hidden dim: the default 128-wide
    # blocks would PAD each 64-element hop row to 128 codes and cap the
    # measurable reduction at 1.94x on this toy — a harness artifact, not
    # a codec property (real hidden dims are multiples of 128)
    q = run(MeshConfig(pipe=2, data=2), 4,
            {"mesh": {"pipe": 2, "data": 2},
             "pipeline": {"hop_compression": {"format": "int8",
                                              "block": 64}},
             "zero_optimization": {"stage": 1, "overlap_grad_reduce": True,
                                   "overlap_compression": "int8",
                                   "overlap_bucket_mb": 1}})
    cl.configure(enabled=False)
    parity = max(abs(a - b) / max(abs(a), 1e-9)
                 for a, b in zip(pipe["losses"], q["losses"]))
    assert parity < 0.05, (
        f"int8-hop loss gap {parity} vs the fp pipe arm exceeds the codec "
        "tolerance")
    hop_reduction = (q["hop_logical"] / q["hop_wire"]
                     if q["hop_wire"] else 0.0)
    assert hop_reduction >= 2.0, (
        f"int8 activation hops moved only {hop_reduction:.2f}x fewer "
        "wire bytes (< 2x): the compressed ppermute fell back to fp")
    struct = q["pipe_struct"] or {}
    from deepspeed_tpu.analysis.contracts import contract_set_hash

    print(json.dumps({
        "metric": "ab-pipe: 2-stage 1F1B pipeline vs single-stage control "
                  "at equal global batch, int8 activation hops + "
                  f"bubble-overlapped int8 grad reduce (tiny llama, "
                  f"seq={seq}, steps={steps})",
        "value": round(hop_reduction, 3),
        "unit": "x wire-bytes reduction (int8 activation hops)",
        "comparable": True,  # deterministic pinned-seed CPU tier
        "backend": jax.default_backend(),
        "pipe_bit_exact": bit_exact,
        "loss_parity_max_rel": round(parity, 7),
        "loss_parity_ok": parity < 0.05,
        "hop_wire_reduction": round(hop_reduction, 3),
        "hop_bytes_logical": q["hop_logical"],
        "hop_bytes_wire": q["hop_wire"],
        "bubble_fraction": struct.get("bubble_fraction"),
        # measured (device-trace) columns next to the modeled ones:
        # null on CPU, where the profiler yields no device timeline
        "pipe_bubble_seconds_measured": {
            "control": ctl["pipe_bubble_seconds_measured"],
            "pipe2": pipe["pipe_bubble_seconds_measured"],
            "int8hop": q["pipe_bubble_seconds_measured"]},
        "exposed_seconds_per_step_measured": {
            "control": ctl["exposed_seconds_per_step_measured"],
            "pipe2": pipe["exposed_seconds_per_step_measured"],
            "int8hop": q["exposed_seconds_per_step_measured"]},
        "timeline_measured": q["timeline_measured"],
        "stages": struct.get("stages"),
        "num_micro": struct.get("num_micro"),
        "final_loss_control": ctl["losses"][-1],
        "final_loss_pipe2": pipe["losses"][-1],
        "final_loss_int8hop": q["losses"][-1],
        "wall_median_s": {"control": round(ctl["wall_median_s"], 4),
                          "pipe2": round(pipe["wall_median_s"], 4),
                          "int8hop": round(q["wall_median_s"], 4)},
        "contract": "train_step_pipe2",
        "contract_set_hash": contract_set_hash(
            os.path.dirname(os.path.abspath(__file__))),
    }))


def _release_device_memory() -> None:
    """Free every live device array before retrying a smaller rung.

    A failed rung's engine (params + fp32 master + Adam state, ~2 GB for
    the 160m model) is pinned by the exception traceback's frames while
    the handler runs, and jax frees buffers asynchronously after that —
    so without an explicit sweep the NEXT rung's init races against the
    previous rung's deallocation and can OOM at a size that fits fine in
    a fresh process (observed: bs=8 OOM inside the ladder, fine alone).
    """
    import gc

    import jax

    # drop traceback -> frame -> engine references first, then delete
    # whatever arrays remain alive (nothing is reused across rungs)
    gc.collect()
    for arr in jax.live_arrays():
        try:
            arr.delete()
        except Exception:
            pass


def main(allow_cpu: bool = False) -> int:
    """One rung, in this process, on the platform JAX selected.  A CPU is
    not a fallback: without ``--cpu`` it is a non-zero exit and no JSON.
    A kernel that fails to lower fails the run — there is no XLA-attention
    retry; only an out-of-memory rung steps down the batch ladder."""
    on_tpu = _device_or_exit(allow_cpu).platform != "cpu"
    size = os.environ.get("DSTPU_BENCH_SIZE", "160m" if on_tpu else "tiny")
    seq = int(os.environ.get("DSTPU_BENCH_SEQ", 1024 if on_tpu else 64))
    steps = int(os.environ.get("DSTPU_BENCH_STEPS", 20 if on_tpu else 3))
    if os.environ.get("DSTPU_BENCH_BS"):
        ladder = [int(os.environ["DSTPU_BENCH_BS"])]
    else:
        # larger micro-batch feeds the MXU better (M = bs*seq rows); step
        # down on OOM so a too-ambitious first rung can't zero the bench
        ladder = [32, 16, 8] if on_tpu else [2]
    attn = os.environ.get("DSTPU_BENCH_ATTN")
    for i, bs in enumerate(ladder):
        try:
            result = _run(size, seq, bs, steps, attn_impl=attn)
            break
        except Exception as e:
            msg = str(e)
            oom = "RESOURCE_EXHAUSTED" in msg or "memory" in msg.lower()
            if not oom or i + 1 >= len(ladder):
                raise
            _release_device_memory()
            print(f"bench: bs={bs} OOM; trying bs={ladder[i + 1]}",
                  file=sys.stderr)
    print(json.dumps(result))
    return 0


def _pin_cpu_mesh() -> None:
    """The deterministic A/B tiers: CPU, 8 virtual devices."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    _pin_cpu()


if __name__ == "__main__":
    if "--ab-overlap" in sys.argv:
        _pin_cpu_mesh()
        _ab_overlap()
    elif "--ab-pipe" in sys.argv:
        # 2-stage x 2-data pipe mesh + the single-stage control
        _pin_cpu_mesh()
        _ab_pipe()
    elif "--ab-compression" in sys.argv:
        # hierarchy split of the data axis
        _pin_cpu_mesh()
        _ab_compression()
    else:
        cpu = "--cpu" in sys.argv
        if cpu:
            _pin_cpu()
        sys.exit(main(allow_cpu=cpu))
