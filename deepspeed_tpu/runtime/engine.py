"""The training engine.

TPU-native analogue of ``DeepSpeedEngine`` (reference runtime/engine.py:205).
The reference wraps a live torch module and orchestrates fwd/bwd/step with
hooks; here the engine owns a **TrainState pytree** and two compiled
programs:

  * ``_micro_step``: fwd+bwd of one micro-batch, gradients accumulated into a
    (ZeRO-sharded) fp32 buffer — the analogue of ``engine.forward`` +
    ``engine.backward`` (engine.py:2216/2466) with IPG bucketing replaced by
    XLA-scheduled reduce-scatter.
  * ``_apply_step``: grad-norm/clip/overflow + optimizer update at the
    gradient-accumulation boundary — ``_take_model_step`` (engine.py:2568).

Memory partitioning (ZeRO stages) is purely a property of the shardings that
these programs are compiled with (see zero/strategy.py).

API compatibility: ``engine(batch)`` / ``engine.backward(loss)`` /
``engine.step()`` drive the same micro/boundary cadence as the reference;
``train_batch(batch)`` is the native fused path (scan over micro-batches in
one program) and is what benchmarks should use.
"""

from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import Any, Callable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .. import comm
from ..compile.deep_frame import first_call_beneath, under_deep_frame
from ..parallel.mesh import MeshTopology
from ..telemetry.compile_sentinel import (expect_recompile, note_program,
                                          publish_setup_seconds, setup_span)
from ..telemetry.flight import dump_on_exception
from ..telemetry.regions import region, region_index
from ..telemetry.spans import record_event, span
from ..utils.jax_compat import shard_map
from ..utils.logging import log_dist, logger
from ..utils.platform import on_tpu
from ..utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER,
                           STEP_GLOBAL_TIMER, SynchronizedWallClockTimer,
                           ThroughputTimer)
from .config import DeepSpeedConfig
from .dataloader import RepeatingLoader
from .lr_schedules import LRSchedulerShim, get_schedule
from .module import ModelSpec, as_model_spec
from .optimizers import build_optimizer
from .precision import (LossScaleState, cast_tree, check_overflow,
                        clip_by_global_norm, global_grad_norm,
                        loss_scale_summary, nonfinite_count,
                        update_loss_scale)
from .zero.strategy import ZeroShardingPlan


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """All mutable training state, as one pytree."""

    step: jnp.ndarray  # optimizer (global) steps taken
    micro_step: jnp.ndarray  # micro-steps since last boundary
    params: Any  # fp32 master (stage>=1: ZeRO-sharded)
    opt_state: Any
    grad_acc: Any  # accumulation buffer, grad_accum_dtype
    loss_scale: Optional[LossScaleState]
    skipped_steps: jnp.ndarray
    global_grad_norm: jnp.ndarray  # from the last boundary
    #: compressed-collective error-feedback residuals, ONE leaf per
    #: bucket, axis-sharded [.., W, S] (each rank's row is its own
    #: compensation).  Carried here — not in a step-local dict — so
    #: residuals survive donation, checkpoint and preemption-resume
    #: bit-identically (docs/COMM.md "Compressed overlap").  Slots:
    #: "overlap" (in-loop compressed overlap), "reduce" (post-backward
    #: qgZ/hierarchical EF).  {} when no compressed path carries EF.
    comm_errors: Any = dataclasses.field(default_factory=dict)


class DeepSpeedTPUEngine:
    def __init__(self,
                 model: Any,
                 config: DeepSpeedConfig,
                 topology: Optional[MeshTopology] = None,
                 example_batch: Any = None,
                 loss_fn: Optional[Callable] = None,
                 partition_rules=None,
                 training_data=None,
                 client_optimizer=None,
                 lr_scheduler=None,
                 seed: Optional[int] = None):
        t_init = time.perf_counter()
        self.config = config
        self.topology = topology or MeshTopology(config.mesh)
        config.resolve_batch_size(self.topology.dp_world_size)
        self.model: ModelSpec = as_model_spec(model, example_batch, loss_fn, partition_rules)

        self.zero_plan = ZeroShardingPlan(self.topology, config.zero_config,
                                          self.model.partition_rules())
        self._configure_zeropp(config)
        self._configure_pipeline(config)
        self.compute_dtype = config.compute_dtype
        self.grad_accum_dtype = {
            "fp32": jnp.float32, "fp16": jnp.float16, "bf16": jnp.bfloat16,
        }[config.gradient_accumulation_dtype]
        self.fp16_enabled = config.fp16.enabled
        self.bf16_enabled = config.bf16.enabled

        # optimizer + schedule.  A client lr_scheduler must be a pure
        # ``step -> lr`` callable so it can compile into the update; a client
        # optimizer must be an optax GradientTransformation.  Anything else
        # (e.g. a torch optimizer/scheduler from a ported script) cannot
        # silently take effect — reject it loudly.
        if lr_scheduler is not None and not callable(lr_scheduler):
            raise TypeError(
                "lr_scheduler must be a callable step->lr schedule (it is compiled "
                "into the update); torch-style scheduler objects are not supported. "
                f"Got {type(lr_scheduler)}")
        self.lr_schedule = lr_scheduler if lr_scheduler is not None else get_schedule(
            config.scheduler.type, config.scheduler.params,
            float(config.optimizer.params.get("lr", 1e-3)))
        if client_optimizer is not None:
            if not isinstance(client_optimizer, optax.GradientTransformation):
                raise TypeError(
                    "optimizer must be an optax.GradientTransformation; torch "
                    f"optimizers are not supported on TPU. Got {type(client_optimizer)}")
            self.optimizer = client_optimizer
            self.base_lr = float(config.optimizer.params.get("lr", 1e-3))
        else:
            self.optimizer, self.base_lr = build_optimizer(
                config.optimizer.type, config.optimizer.params, self.lr_schedule)
        self.lr_scheduler = LRSchedulerShim(self.lr_schedule)

        # observability
        self.telemetry = None
        if config.telemetry.enabled:
            from ..telemetry import Telemetry

            self.telemetry = Telemetry(config.telemetry, loop="train")
            self._init_train_metrics()
        # timer sink: every phase timer stop() lands in the phase
        # histogram, making the registry the single sink for step metrics
        self.timers = SynchronizedWallClockTimer(
            sink=(self._observe_phase if self.telemetry is not None else None))
        self.tput_timer = ThroughputTimer(batch_size=config.train_batch_size or 1,
                                          steps_per_output=config.steps_per_print)
        self.monitor = None
        if config.tensorboard.enabled or config.csv_monitor.enabled \
                or config.wandb.enabled or config.comet.enabled:
            from ..monitor.monitor import MonitorMaster

            self.monitor = MonitorMaster(config)
        if config.comms_logger.enabled:
            comm.configure_comms_logger(
                enabled=True, verbose=config.comms_logger.verbose,
                prof_all=config.comms_logger.prof_all,
                prof_ops=config.comms_logger.prof_ops)
        self.flops_profiler = None
        if config.flops_profiler.enabled:
            from ..profiling.flops_profiler import FlopsProfiler

            self.flops_profiler = FlopsProfiler(self, config.flops_profiler)

        # optimizer-state host offload (ZeRO-Offload / -Infinity / ZenFlow /
        # SuperOffload — all share the host-master data path)
        self.offload_optimizer = None
        off_cfg = config.zero_config.offload_optimizer
        zf_cfg = config.zero_config.zenflow
        if off_cfg.enabled or zf_cfg.enabled:
            if self.fp16_enabled and (zf_cfg.enabled or off_cfg.super_offload):
                # plain ZeRO-Offload handles fp16 (unscale via the host
                # denominator + host overflow skip, _apply_step_offload);
                # the selective/async update paths do not thread the skip
                raise NotImplementedError(
                    "fp16 loss scaling is supported with plain "
                    "offload_optimizer but not with zenflow/super_offload; "
                    "use bf16 there")
            opt_cfg = {"type": config.optimizer.type,
                       "params": config.optimizer.params}
            if zf_cfg.enabled:
                from .zenflow import ZenFlowOptimizer

                if off_cfg.device == "nvme":
                    raise NotImplementedError(
                        "zenflow keeps optimizer state in host RAM; it does "
                        "not spill to NVMe — drop offload_optimizer.device="
                        "'nvme' or disable zenflow")
                if off_cfg.super_offload:
                    logger.warning("zenflow enabled: super_offload / "
                                   "cpu_worker_count are ignored")
                self.offload_optimizer = ZenFlowOptimizer(
                    abstract_params=None,  # set in _init_state
                    optimizer_config=opt_cfg, zenflow_config=zf_cfg,
                    grad_clip=config.gradient_clipping)
            elif off_cfg.super_offload:
                from .superoffload import SuperOffloadOptimizer

                self.offload_optimizer = SuperOffloadOptimizer(
                    abstract_params=None, optimizer_config=opt_cfg,
                    grad_clip=config.gradient_clipping,
                    nvme_path=(off_cfg.nvme_path if off_cfg.device == "nvme" else None),
                    cpu_worker_count=off_cfg.cpu_worker_count)
            else:
                from .zero.offload import HostOffloadedOptimizer

                self.offload_optimizer = HostOffloadedOptimizer(
                    abstract_params=None,  # set in _init_state
                    optimizer_config=opt_cfg,
                    grad_clip=config.gradient_clipping,
                    nvme_path=(off_cfg.nvme_path if off_cfg.device == "nvme" else None))

        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data)

        self.global_steps = 0
        self.micro_steps = 0
        self._cached_loss = None
        # True while the incremental API (forward/backward) has written the
        # grad-accumulation buffer without reaching a step() boundary; lets
        # train_batch reset a stale buffer exactly when needed instead of
        # memsetting it every fused step.
        self._acc_dirty = False
        self._rng = jax.random.PRNGKey(seed if seed is not None else config.seed)

        # numerics observatory (telemetry/numerics.py): the fused step
        # carries an in-graph stats tree as an extra output, pulled only
        # at the steps_per_print boundary.  Fused stats gate off under
        # optimizer offload (that path's boundary update runs on host and
        # its device program is micro-steps only) — the sentinel still
        # observes the host-available scalars there.  Activation stats
        # additionally need a transformer-config model (the per-layer
        # scan emits them) and gate off under qgZ/hierarchical reduce
        # (per-chunk vmap'd stats would need their own reduce) and the
        # pipe paths (the pipe engine owns per-STAGE stats instead).
        self._numerics = (self.telemetry.numerics
                          if self.telemetry is not None else None)
        self._numerics_fused = (self._numerics is not None
                                and self.offload_optimizer is None)
        self._numerics_act = False
        self._last_numerics = None
        self._div_fn = None

        self.state = self._init_state()
        self._build_overlap_plan()
        _mc = getattr(self.model, "config", None)
        self._numerics_act = (
            self._numerics_fused
            and bool(getattr(config.telemetry.numerics, "activation_stats",
                             True))
            and _mc is not None and hasattr(_mc, "numerics_act_stats")
            and not (self._qgz or self._hier_inner)
            and getattr(self, "_pipe_hop_spec", None) is None
            and getattr(self, "_pipe_plan", None) is None
            and not self._pipe_schedule_active())
        # expert-share counters (moe/sharded_moe.MOE_TRAIN_COUNTERS): a model
        # whose config has an expert share and a stack of layer_types returns
        # them from its loss; the fused step adds them to a device-resident
        # sum that only moe_stats() reads.  Plain fused path only
        self._moe_counters = (
            _mc is not None and bool(getattr(_mc, "moe_held_count", 0))
            and bool(getattr(_mc, "layer_types", ()))
            and hasattr(_mc, "moe_counters")
            and self.offload_optimizer is None
            and not (self._qgz or self._hier_inner)
            and getattr(self, "_overlap_plan", None) is None
            and getattr(self, "_pipe_hop_spec", None) is None
            and getattr(self, "_pipe_plan", None) is None
            and not self._pipe_schedule_active())
        self._moe_acc = None
        self._moe_steps = 0
        # leaves the optimizer leaves as they are (ModelSpec.buffers)
        self._buffer_mask = None
        buffers = tuple(getattr(self.model, "buffers", ()) or ())
        if buffers:
            import re

            from .zero.strategy import _path_str

            self._buffer_mask = jax.tree_util.tree_map_with_path(
                lambda path, _: any(re.search(b, _path_str(path))
                                    for b in buffers), self.state.params)
        self._init_comm_errors()
        self._compile_steps()
        self._wire_memory_ledger()
        # ZeRO-Infinity param offload (reference offload_param config): the
        # fp32 master lives in pinned host memory; the step streams it.
        # The optimizer-offload path already keeps the master in host RAM
        # (numpy) so the two are mutually exclusive by construction.
        if config.zero_config.offload_param.enabled:
            if self.offload_optimizer is not None:
                logger.warning(
                    "offload_param: the optimizer-offload path already keeps "
                    "the fp32 master in host RAM (numpy) — the offload_param "
                    "setting is subsumed and the pinned-host pass is skipped")
            else:
                from ..compile.backend import PASS_REGISTRY

                PASS_REGISTRY["offload_params"](self)
        # resilience (docs/RESILIENCE.md): preemption watcher + startup
        # auto-resume from the latest VERIFIED checkpoint.  Last in init:
        # the resume reshards into the fully-built engine (any mesh/stage).
        self.resilience = None
        if config.resilience.enabled:
            from ..resilience import ResilienceManager

            gp = (self.telemetry.goodput if self.telemetry is not None
                  else None)
            if (gp is not None and not config.telemetry.goodput.run_file
                    and config.resilience.save_dir):
                # union-of-attempts ledger rides the checkpoint dir by
                # default: every attempt of a resilient run finds the
                # same file, so productive steps survive preemptions
                gp.attach_run_file(os.path.join(
                    config.resilience.save_dir, "goodput_run.json"))
            self.resilience = ResilienceManager(config.resilience)
            self.resilience.maybe_auto_resume(self)
        log_dist(f"DeepSpeedTPUEngine initialized: zero_stage={config.zero_config.stage} "
                 f"dtype={self.compute_dtype.__name__} mesh={self.topology.axis_sizes} "
                 f"micro_bs={config.train_micro_batch_size_per_gpu} "
                 f"gas={config.gradient_accumulation_steps}")
        setup_span("train_engine_init", t_init)
        publish_setup_seconds()

    def _configure_zeropp(self, config: DeepSpeedConfig) -> None:
        """ZeRO++ wiring (reference engine.py:1101-1113 config keys).

        qwZ: per-layer weight gathers move int8 (model-cooperative — the
        transformer core's ``_qwz`` gather points); qgZ: gradient reduction
        over the data axis rides an int8 all-to-all (zero/zeropp.py); hpZ is
        pure sharding, handled in ZeroShardingPlan."""
        zc = config.zero_config
        self._qgz = False
        self._qwz = False
        if zc.zero_quantized_weights:
            model_cfg = getattr(self.model, "config", None)
            if zc.stage == 3 and model_cfg is not None \
                    and hasattr(model_cfg, "qwz") \
                    and self.topology.pipe_parallel_size == 1:
                # per-engine flag, applied around tracing (_model_loss): a
                # shared model object must not become sticky-quantized for
                # other engines, and the pipe shard_map body cannot host the
                # forced-gather sharding constraints
                self._qwz = True
                log_dist("ZeRO++ qwZ: int8 quantized weight gathers enabled")
            else:
                logger.warning(
                    "zero_quantized_weights needs stage 3, a models/* "
                    "transformer (qwZ gather points), and no pipeline "
                    "parallelism; ignoring")
        self._zero3_prefetch = False
        if zc.zero3_param_prefetch:
            model_cfg = getattr(self.model, "config", None)
            if zc.stage == 3 and model_cfg is not None \
                    and hasattr(model_cfg, "zero3_prefetch") \
                    and getattr(model_cfg, "scan_layers", False) \
                    and self.topology.pipe_parallel_size == 1:
                self._zero3_prefetch = True
                log_dist("stage-3 manual param prefetch: 2x-unrolled layer "
                         "scan (per-layer gathers overlap compute)")
            else:
                logger.warning(
                    "zero3_param_prefetch needs stage 3, a models/* "
                    "transformer with scan_layers, and no pipeline "
                    "parallelism; ignoring")
        if zc.zero_quantized_gradients:
            from ..parallel.mesh import (DATA_AXIS, EXPERT_AXIS, REPL_AXIS,
                                         SEQ_AXIS)

            others = [self.topology.axis_size(a)
                      for a in (REPL_AXIS, EXPERT_AXIS, SEQ_AXIS)]
            if zc.stage in (1, 2) and self.topology.axis_size(DATA_AXIS) > 1 \
                    and all(s == 1 for s in others):
                self._qgz = True
                log_dist("ZeRO++ qgZ: int8 all-to-all gradient reduce enabled")
            else:
                logger.warning(
                    "zero_quantized_gradients needs stage 1/2 with data-axis-"
                    "only batch parallelism (repl/expert/sequence == 1); "
                    "falling back to the XLA fp reduce")
        self._hier_inner = 0
        if zc.zero_hierarchical_grad_reduce:
            from ..parallel.mesh import (DATA_AXIS, EXPERT_AXIS, REPL_AXIS,
                                         SEQ_AXIS)
            from ..utils.groups import hierarchy_split

            others = [self.topology.axis_size(a)
                      for a in (REPL_AXIS, EXPERT_AXIS, SEQ_AXIS)]
            world = self.topology.axis_size(DATA_AXIS)
            try:
                if zc.stage not in (1, 2) or any(s != 1 for s in others):
                    raise ValueError("needs stage 1/2 with data-axis-only "
                                     "batch parallelism")
                inner, outer = hierarchy_split(
                    world, zc.zero_hierarchy_inner or None)
                self._hier_inner = inner
                log_dist(
                    f"hierarchical grad reduce: {inner}x{outer} two-hop "
                    f"over '{DATA_AXIS}'"
                    + (", int8 inter-slice exchange" if self._qgz else
                       ", full-precision hops"))
            except ValueError as e:
                logger.warning(
                    f"zero_hierarchical_grad_reduce disabled ({e}); "
                    "falling back to the "
                    + ("qgZ all-to-all reduce" if self._qgz
                       else "XLA fp reduce"))
        # in-loop overlap compression (docs/COMM.md "Compressed overlap"):
        # an explicit overlap_compression knob wins; with qgZ also on it
        # defaults to the qgZ wire format + error feedback, so
        # zero_quantized_gradients composes with overlap_grad_reduce
        # instead of standing the wrap down.  False forces the exact wrap.
        self._overlap_spec = None
        raw = zc.overlap_compression
        if raw not in (None, False):
            from ..comm.collectives.codec import CompressionSpec

            spec = CompressionSpec.parse(raw)
            if not isinstance(raw, CompressionSpec) \
                    and not (isinstance(raw, dict)
                             and "error_feedback" in raw):
                # EF is the default contract for this path; an explicit
                # dict key or an already-built spec is the opt-out
                spec = dataclasses.replace(spec, error_feedback=True)
            self._overlap_spec = spec
        elif raw is None and self._qgz:
            from ..comm.collectives.codec import CompressionSpec

            self._overlap_spec = CompressionSpec(format="int8",
                                                 error_feedback=True)

    def _pipe_schedule_active(self) -> bool:
        """True when the model runs the scan-based pipe schedule
        (runtime/pipe/engine.py) on this engine: a pipe ModelSpec on a
        pipe>1 mesh (at pipe=1 it runs the plain loss)."""
        return (getattr(self.model, "num_microbatches", None) is not None
                and self.topology.pipe_parallel_size > 1)

    def _configure_pipeline(self, config: DeepSpeedConfig) -> None:
        """Pipe perf wiring (docs/PIPELINE.md): resolve the
        ``pipeline.hop_compression`` codec for the per-tick activation
        ``ppermute`` (EF + compress_backward default ON — the explicit
        dict key or a prebuilt spec is the opt-out) and the structural
        schedule numbers (``bubble_fraction`` = (P-1)/(M+P-1)) the
        telemetry layer publishes."""
        self._pipe_hop_spec = None
        self._pipe_struct = None
        sched = self._pipe_schedule_active()
        raw = config.pipeline.hop_compression
        if raw not in (None, False):
            if not sched:
                logger.warning(
                    "pipeline.hop_compression is set but no pipe scan "
                    "schedule is active (pipe="
                    f"{self.topology.pipe_parallel_size}, model="
                    f"{type(self.model).__name__}); ignoring")
            else:
                from ..comm.collectives.codec import CompressionSpec

                spec = CompressionSpec.parse(raw)
                explicit = isinstance(raw, CompressionSpec)
                if not explicit and not (isinstance(raw, dict)
                                         and "error_feedback" in raw):
                    spec = dataclasses.replace(spec, error_feedback=True)
                if not explicit and not (isinstance(raw, dict)
                                         and "compress_backward" in raw):
                    # both waves ride the codec: the backward-wave
                    # transpose moves the same activation bytes
                    spec = dataclasses.replace(spec, compress_backward=True)
                self._pipe_hop_spec = spec
                log_dist(f"pipe hop compression: {spec.format} activation "
                         "hops"
                         + (" + EF" if spec.error_feedback else ""))
        if sched:
            pp = self.topology.pipe_parallel_size
            M = int(self.model.num_microbatches)
            spec = self._pipe_hop_spec
            self._pipe_struct = {
                "stages": pp,
                "num_micro": M,
                "bubble_fraction": (pp - 1) / (M + pp - 1),
                "hop_compression": (spec.format if spec is not None
                                    else None),
                "hop_error_feedback": bool(spec is not None
                                           and spec.error_feedback),
            }

    def _overlap_unsupported_reason(self) -> Optional[str]:
        """Why the overlap wrap cannot apply on this engine (None = ok).

        The wrap runs the scanned block in a shard_map over the data
        axis; everything it cannot express is excluded loudly here
        instead of failing deep inside tracing."""
        from ..parallel.mesh import (DATA_AXIS, EXPERT_AXIS, REPL_AXIS,
                                     SEQ_AXIS)

        mc = getattr(self.model, "config", None)
        params = self.state.params
        if not (isinstance(params, dict) and "layers" in params
                and mc is not None and hasattr(mc, "overlap_plan")):
            return "needs a models/* transformer (stacked layer tree)"
        pipe_sched = self._pipe_schedule_active()
        if self.topology.pipe_parallel_size != 1 and not pipe_sched:
            return ("pipe: pipeline parallelism without the pipe scan "
                    "schedule (runtime/pipe) has no in-scan reduce point")
        if pipe_sched:
            # the pipe variant (runtime/pipe/overlap.py): per-tick
            # stage-grad reduces ride inside the pipe scan.  Supported:
            # ZeRO <= 1 pure pipe x data with a dense models/* core.
            from ..parallel.mesh import MODEL_AXIS
            zc = self.config.zero_config
            if zc.stage >= 2:
                return (f"pipe: ZeRO stage {zc.stage} shards gradients "
                        "over data, but the in-scan pipe reduce delivers "
                        "full replicated layer grads (supported: stage <= 1)")
            if self._qgz or self._hier_inner:
                return ("pipe: the qgZ/hierarchical explicit reducers do "
                        "not compose with the in-scan pipe reduce")
            if getattr(mc, "moe_experts", 0):
                return ("pipe: MoE expert axes do not compose with the "
                        "in-scan pipe reduce")
            others = [(a, self.topology.axis_size(a))
                      for a in (REPL_AXIS, EXPERT_AXIS, SEQ_AXIS)]
            if any(s != 1 for _a, s in others):
                return ("pipe: the in-scan reduce needs pipe x data only "
                        f"batch parallelism (got {dict(others)})")
            if (self.topology.axis_size(MODEL_AXIS) > 1
                    or self.topology.axis_size(SEQ_AXIS) > 1):
                return ("pipe: TP/SP runs the pipe body partial-manual; "
                        "the in-scan reduce needs the fully manual body")
            if self.topology.axis_size(DATA_AXIS) <= 1:
                return "data axis is 1: there is no grad exchange to overlap"
            return None
        others = [(a, self.topology.axis_size(a))
                  for a in (REPL_AXIS, EXPERT_AXIS, SEQ_AXIS)]
        if any(s != 1 for _a, s in others):
            return ("needs data-axis-only batch parallelism "
                    f"(got {dict(others)})")
        if self.topology.axis_size(DATA_AXIS) <= 1:
            return "data axis is 1: there is no grad exchange to overlap"
        if (self._qgz or self._hier_inner) and self._overlap_spec is None:
            # reachable via overlap_compression=False, or hierarchical
            # WITHOUT qgZ (full-precision hops: no in-loop codec derives;
            # under qgZ the default spec composes the wrap instead —
            # docs/COMM.md "Compressed overlap")
            return ("qgZ/hierarchical explicit reducers own the grad "
                    "exchange and no in-loop compression is resolved "
                    "(set zero_quantized_gradients or overlap_compression "
                    "to compose; overlap rides their bucketed collectives)")
        if self._qwz:
            return "zero_quantized_weights owns the stage-3 gathers"
        if getattr(mc, "moe_experts", 0):
            return ("MoE aux loss is batch-dependent; the wrap cannot "
                    "claim it replicated")
        if getattr(mc, "attn_impl", "xla") not in ("auto", "xla", "flash"):
            return (f"attn_impl={mc.attn_impl!r} manages its own "
                    "sequence-axis collectives")
        return None

    def _build_overlap_plan(self) -> None:
        """Fine-grained compute/collective overlap (ROADMAP item 3,
        runtime/zero/overlap.py): run the scanned transformer block in
        a data-axis shard_map so each layer-bucket's grad reduce is an
        explicit collective inside the backward loop
        (``overlap_grad_reduce``) and the stage-3 param all-gathers are
        explicit at the body top, prefetched one layer ahead by the
        2x-unrolled scan (``zero3_param_prefetch``).  Also derives the
        structural exposure split the telemetry layer publishes
        (``deepspeed_tpu_train_overlapped_fraction`` /
        ``_exposed_collective_seconds``)."""
        self._overlap_plan = None
        self._overlap_struct = None
        self._pipe_plan = None
        zc = self.config.zero_config
        wanted = bool(zc.overlap_grad_reduce
                      or (getattr(self, "_zero3_prefetch", False)
                          and zc.stage >= 3))
        params = self.state.params
        has_layers = isinstance(params, dict) and "layers" in params
        reason = self._overlap_unsupported_reason() if wanted else None
        if not wanted and self.config.zero_config.overlap_compression \
                not in (None, False):
            logger.warning(
                "overlap_compression is set but the overlap wrap is not "
                "requested (overlap_grad_reduce / zero3_param_prefetch "
                "are off) — the in-loop exchange stays uncompressed; "
                "enable overlap_grad_reduce to compose")
        if wanted and reason is not None:
            logger.warning(f"compute/collective overlap disabled: {reason}")
        if wanted and reason is None and self._pipe_schedule_active():
            # pipe variant (runtime/pipe/overlap.py): per-tick stage-grad
            # reduces inside the pipe scan; composes with
            # overlap_compression (the bucketed exchange moves codes).
            # EF stays with the HOP residual slot — the straight-through
            # bucket reduce keeps one owner per comm_errors key.
            from .pipe.overlap import build_pipe_overlap_plan

            comp = self._overlap_spec
            if comp is not None and comp.error_feedback:
                comp = dataclasses.replace(comp, error_feedback=False)
            self._pipe_plan = build_pipe_overlap_plan(
                self.topology, jax.eval_shape(lambda: params["layers"]),
                bucket_bytes=int(zc.overlap_bucket_mb * 2**20),
                num_micro=int(self.model.num_microbatches),
                grad_dtype=self.grad_accum_dtype,
                compression=comp)
        elif wanted and reason is None:
            from ..parallel.mesh import DATA_AXIS
            from .zero.overlap import build_overlap_plan

            self._overlap_plan = build_overlap_plan(
                self.zero_plan, jax.eval_shape(lambda: params["layers"]),
                bucket_bytes=int(zc.overlap_bucket_mb * 2**20),
                axis=DATA_AXIS, stage=zc.stage,
                grad_dtype=self.grad_accum_dtype,
                compression=self._overlap_spec,
                hier_inner=getattr(self, "_hier_inner", 0))
        if not has_layers:
            return
        # structural exposure split: grad-exchange bytes per micro-step,
        # split into wrap-covered (overlap-scheduled) vs post-backward
        # tail — the deterministic source for overlapped_fraction
        itemsize = np.dtype(self.grad_accum_dtype).itemsize
        layer_bytes = sum(
            l.size for l in jax.tree_util.tree_leaves(params["layers"])
        ) * itemsize
        total_bytes = sum(
            l.size for l in jax.tree_util.tree_leaves(params)) * itemsize
        plan = self._overlap_plan if self._overlap_plan is not None \
            else self._pipe_plan
        covered = layer_bytes if plan is not None else 0
        comp = plan.compression if plan is not None else None
        self._overlap_struct = {
            "total_bytes": int(total_bytes),
            "overlapped_bytes": int(covered),
            "tail_bytes": int(total_bytes - covered),
            "buckets": (len(plan.buckets) if plan is not None else 0),
            "compression": (comp.format if comp is not None else None),
            "residual_bytes": (plan.residual_bytes()
                               if comp is not None
                               and hasattr(plan, "residual_bytes") else 0),
        }

    def _init_comm_errors(self) -> None:
        """Populate ``TrainState.comm_errors`` (docs/COMM.md "Compressed
        overlap"): per-bucket error-feedback residual leaves for the
        in-loop compressed overlap and/or the post-backward qgZ/hier EF
        reduce.  Runs after the overlap plan is built and BEFORE step
        compilation, so the state pytree the jitted programs donate is
        fixed.  A checkpoint that predates the residuals restores them
        as zeros with the loader's loud per-key warning (the documented
        reset); a checkpoint that has them resumes bit-identically."""
        errors = {}
        plan = getattr(self, "_overlap_plan", None)
        if plan is not None and plan.error_feedback:
            errors["overlap"] = plan.init_errors()
        hop_spec = getattr(self, "_pipe_hop_spec", None)
        if hop_spec is not None and hop_spec.error_feedback:
            pipe_errors = self._init_pipe_hop_errors()
            if pipe_errors is not None:
                errors["pipe"] = pipe_errors
        reduce_errors = self._init_reduce_errors()
        if reduce_errors:
            errors["reduce"] = reduce_errors
        if errors:
            self.state = dataclasses.replace(self.state, comm_errors=errors)

    def _init_pipe_hop_errors(self):
        """EF residual slot for the compressed pipe activation hop
        (``comm_errors["pipe"]``): global ``[pp, Dw, T, b, S, H]`` fp32
        split over pipe x data — per tick, each device's own hop
        residual.  Shapes come from the config (``b`` = per-device
        micro batch / num_microbatches, ``S`` = max_seq_len): training
        batches must arrive at exactly that shape for EF to engage
        (docs/PIPELINE.md); on mismatch the hop runs straight-through
        for the step with a one-time warning."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.mesh import DATA_AXIS, PIPE_AXIS

        mc = getattr(self.model, "config", None)
        M = getattr(self.model, "num_microbatches", None)
        mbs = self.config.train_micro_batch_size_per_gpu
        if mc is None or M is None:
            return None
        if not mbs or mbs % int(M) != 0:
            logger.warning(
                "pipe: hop error feedback disabled — "
                f"train_micro_batch_size_per_gpu ({mbs}) must divide into "
                f"num_microbatches ({M}) to size the per-tick residual "
                "slot; the hop runs straight-through")
            self._pipe_hop_spec = dataclasses.replace(
                self._pipe_hop_spec, error_feedback=False)
            if self._pipe_struct is not None:
                self._pipe_struct["hop_error_feedback"] = False
            return None
        pp = self.topology.pipe_parallel_size
        W = self.topology.axis_size(DATA_AXIS)
        T = int(M) + pp - 1
        b = int(mbs) // int(M)
        S, H = int(mc.max_seq_len), int(mc.hidden_size)
        # batch-shape gate for _micro_grads: EF only engages when the
        # traced batch matches the residual layout
        self._pipe_eslot_batch = (int(mbs) * self.topology.dp_world_size, S)
        sh = NamedSharding(self.topology.mesh, P(PIPE_AXIS, DATA_AXIS))
        return jax.device_put(
            jnp.zeros((pp, W, T, b, S, H), jnp.float32), sh)

    def _init_reduce_errors(self):
        """Residual layout for the POST-backward qgZ / hierarchical EF
        path (``grad_reduce_error_feedback``): one ``[W, S_k]`` fp32
        leaf per flat-path bucket, mirroring exactly the bucket
        assignment ``quantized_grad_reduce`` / ``hierarchical_grad_reduce``
        derive in-body (flatten order, compute-dtype byte sizes,
        QBLOCK-aligned coalesce layout)."""
        zc = self.config.zero_config
        overlap_compressed = (
            getattr(self, "_overlap_plan", None) is not None
            and self._overlap_plan.compression is not None)
        if (not zc.grad_reduce_error_feedback or overlap_compressed
                or not (self._qgz or self._hier_inner)):
            return {}
        if self._hier_inner and not self._qgz:
            # full-precision hierarchical hops have no lossy point —
            # residual state would be dead fp32 HBM, never read
            logger.warning(
                "grad_reduce_error_feedback: the hierarchical reduce runs "
                "full-precision hops without zero_quantized_gradients — "
                "nothing to compensate; no residual state allocated")
            return {}
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..comm.collectives.bucketer import assign_buckets
        from ..parallel.mesh import DATA_AXIS
        from .zero.strategy import _path_str
        from .zero.zeropp import QBLOCK, _scatter_dim

        W = self.topology.axis_size(DATA_AXIS)
        flat, _ = jax.tree_util.tree_flatten_with_path(self.state.params)
        itemsize = np.dtype(self.compute_dtype).itemsize
        sizes, elems = [], []
        for path, leaf in flat:
            pstr = _path_str(path)
            shape = tuple(leaf.shape)
            pspec = self.zero_plan.param_spec(pstr, shape)
            if self._hier_inner:
                sd = -1  # hierarchical: every leaf rides the flat path
            else:
                cs = P(DATA_AXIS, *tuple(pspec))
                sd = _scatter_dim(self.zero_plan.grad_spec(pstr, shape),
                                  cs, DATA_AXIS)
            if sd >= 0:
                continue  # scattered path: single-hop, EF-free
            # the in-body reducers see each leaf's TP-LOCAL block (the
            # chunk specs carry the param's TP entries), so the residual
            # layout must be sized from the local shard shape
            local = []
            for dim, entry in enumerate(shape):
                axes = (tuple(pspec)[dim] if dim < len(tuple(pspec))
                        else None)
                axes = (tuple(axes) if isinstance(axes, (tuple, list))
                        else (axes,) if axes is not None else ())
                div = int(np.prod([self.topology.axis_size(a)
                                   for a in axes]) or 1)
                local.append(entry // div if div else entry)
            n = int(np.prod(local) or 1)
            sizes.append(n * itemsize)
            elems.append(-(-n // QBLOCK) * QBLOCK)
        if not sizes:
            return {}
        buckets = assign_buckets(
            sizes, int(zc.overlap_bucket_mb * 2**20))
        sh = NamedSharding(self.topology.mesh, P(DATA_AXIS))
        return {
            f"b{k:03d}": jax.device_put(
                jnp.zeros((W, sum(elems[i] for i in idxs)), jnp.float32),
                sh)
            for k, idxs in enumerate(buckets)}

    # ------------------------------------------------------------------ init
    def _init_state(self) -> TrainState:
        """Initialize params already sharded: the analogue of ``zero.Init``
        (reference partition_parameters.py:878) — params are *born
        partitioned*; no full replica ever materializes (jit with
        out_shardings on the init function)."""
        init_rng, self._rng = jax.random.split(self._rng)

        abstract = jax.eval_shape(self.model.init_params, init_rng)
        param_shardings = self.zero_plan.tree_shardings(abstract, "master")

        if self.offload_optimizer is not None:
            # compute-dtype params on device; fp32 master + moments on host
            compute_shardings = self.zero_plan.tree_shardings(abstract, "param")
            init_fn = jax.jit(
                lambda rng: cast_tree(self.model.init_params(rng), jnp.float32),
                out_shardings=param_shardings)
            with self.topology.mesh:
                master = init_fn(init_rng)
            self.offload_optimizer.leaves, self.offload_optimizer.treedef = \
                jax.tree_util.tree_flatten(jax.eval_shape(lambda: master))
            self.offload_optimizer.initialize_master(master)
            with self.topology.mesh:
                params = jax.jit(lambda p: cast_tree(p, self.compute_dtype),
                                 out_shardings=compute_shardings)(master)
            del master
            opt_state = ()
        else:
            init_fn = jax.jit(
                lambda rng: cast_tree(self.model.init_params(rng), jnp.float32),
                out_shardings=param_shardings)
            with self.topology.mesh:
                params = init_fn(init_rng)

                # moments shard like the master weights (ZeRO stage>=1
                # partitions optimizer state); the plan's path-regex rules
                # match the mu/nu subtrees because they mirror the param tree
                abstract_opt = jax.eval_shape(self.optimizer.init, params)
                opt_shardings = self.zero_plan.tree_shardings(abstract_opt, "master")
                opt_state = jax.jit(
                    self.optimizer.init, out_shardings=opt_shardings)(params)
        grad_acc = jax.jit(
            lambda p: jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, self.grad_accum_dtype), p),
            out_shardings=self.zero_plan.tree_shardings(abstract, "grad"))(params)

        loss_scale = LossScaleState.create(self.config.fp16) if self.fp16_enabled else None
        # scalars live replicated on the mesh so the whole TrainState shares
        # one device set (mixing committed single-device scalars with mesh
        # arrays is a jit error)
        rep = self.topology.replicated()
        scalar = lambda v, dt: jax.device_put(jnp.asarray(v, dt), rep)  # noqa: E731
        if loss_scale is not None:
            loss_scale = jax.device_put(loss_scale, rep)
        return TrainState(
            step=scalar(0, jnp.int32),
            micro_step=scalar(0, jnp.int32),
            params=params,
            opt_state=opt_state,
            grad_acc=grad_acc,
            loss_scale=loss_scale,
            skipped_steps=scalar(0, jnp.int32),
            global_grad_norm=scalar(0.0, jnp.float32),
        )

    # ------------------------------------------------------------- programs
    def _model_loss(self, p, batch, rng, act_stats=False, moe_counters=False):
        """model.loss_fn with the engine's qwZ / stage-3-prefetch flags
        applied for the duration of the trace (not a permanent config
        mutation — engines may share a model object).

        ``act_stats``: numerics-observatory per-layer activation stats —
        set ONLY by the training trace (``_micro_grads``); the loss then
        returns ``(loss, [L, 3] act)`` (models/transformer.py).  The
        eval path never sets it, so eval losses stay scalar.
        ``moe_counters``: likewise for an expert share's counters — the
        loss then returns ``(loss, act or None, counters)``."""
        mc = getattr(self.model, "config", None)
        has_q = mc is not None and hasattr(mc, "qwz")
        has_pf = mc is not None and hasattr(mc, "zero3_prefetch")
        has_ov = mc is not None and hasattr(mc, "overlap_plan")
        has_hop = mc is not None and hasattr(mc, "pipe_hop_spec")
        has_pp = mc is not None and hasattr(mc, "pipe_overlap_plan")
        has_nm = mc is not None and hasattr(mc, "numerics_act_stats")
        has_moe = mc is not None and hasattr(mc, "moe_counters")
        if not (has_q or has_pf or has_ov or has_hop or has_pp or has_nm
                or has_moe):
            return self.model.loss_fn(p, batch, rng)
        old_q = mc.qwz if has_q else None
        old_pf = mc.zero3_prefetch if has_pf else None
        old_ov = mc.overlap_plan if has_ov else None
        old_hop = mc.pipe_hop_spec if has_hop else None
        old_pp = mc.pipe_overlap_plan if has_pp else None
        old_nm = mc.numerics_act_stats if has_nm else None
        old_moe = mc.moe_counters if has_moe else None
        if has_moe:
            mc.moe_counters = bool(moe_counters)
        if has_q:
            mc.qwz = self._qwz
        if has_pf:
            mc.zero3_prefetch = getattr(self, "_zero3_prefetch", False)
        if has_ov:
            mc.overlap_plan = getattr(self, "_overlap_plan", None)
        if has_hop:
            mc.pipe_hop_spec = getattr(self, "_pipe_hop_spec", None)
        if has_pp:
            mc.pipe_overlap_plan = getattr(self, "_pipe_plan", None)
        if has_nm:
            mc.numerics_act_stats = bool(act_stats)
        try:
            return self.model.loss_fn(p, batch, rng)
        finally:
            if has_q:
                mc.qwz = old_q
            if has_pf:
                mc.zero3_prefetch = old_pf
            if has_ov:
                mc.overlap_plan = old_ov
            if has_hop:
                mc.pipe_hop_spec = old_hop
            if has_pp:
                mc.pipe_overlap_plan = old_pp
            if has_nm:
                mc.numerics_act_stats = old_nm
            if has_moe:
                mc.moe_counters = old_moe

    def _fetch_params(self, master_params):
        """Host-offloaded masters (offload_param): stream them into device
        memory for compute — mixed memory spaces cannot feed dot_general
        directly (same contract as the opt-moment device_put)."""
        dev = getattr(self, "_param_dev_shardings", None)
        if dev is None:
            return master_params
        return jax.tree_util.tree_map(
            lambda x, s: x if s == "keep" else jax.device_put(x, s),
            master_params, dev)

    def _compute_params(self, master_params):
        """fp32 master -> compute-dtype copy, constrained to the live-param
        sharding (stage 3: still sharded; XLA all-gathers per-layer at use,
        in compute dtype — the fetch/release of the reference's
        PartitionedParameterCoordinator, for free)."""
        with region("optimizer"):  # (the master's cast: the step's, once)
            p = cast_tree(self._fetch_params(master_params),
                          self.compute_dtype)
            return self.zero_plan.constrain(p, "param")

    def _micro_grads(self, state: TrainState, batch, rng, compute_params=None,
                     want_overflow=False):
        """One micro-batch's gradients (accum dtype, grad-sharded) + loss
        + the updated compressed-collective EF residuals (None when no
        compressed path carries error feedback on this trace) + a numerics
        ``extras`` dict: ``"act"`` ([L, 3] per-layer activation stats when
        the observatory's act stats ride this trace, else None), ``"moe"``
        (an expert share's int32 counters, else None) and
        ``"overflow"`` (the fp16 finiteness verdict over the post-cast
        grads — computed ONCE here and threaded both to the EF residual
        gate and, with ``want_overflow``, to ``_apply_step_body``'s skip
        decision, which otherwise recomputes the same full-tree
        reduction).

        ``compute_params``: pre-cast compute-dtype params — the fused
        gas>1 scan casts the fp32 master ONCE outside the scan instead of
        re-casting every micro-step (params only change at the boundary)."""
        if compute_params is None:
            compute_params = self._compute_params(state.params)
        act_on = getattr(self, "_numerics_act", False)
        moe_on = getattr(self, "_moe_counters", False)

        def scaled_loss_fn(p, b=None):
            out = self._model_loss(p, b if b is not None else batch, rng,
                                   act_stats=act_on, moe_counters=moe_on)
            if moe_on:
                # the counters ride the slot of the activation stats
                loss, act = out[0], {"act": out[1], "moe": out[2]}
            else:
                loss, act = out if act_on else (out, None)
            if self.fp16_enabled:
                # scale in fp32: the default scale (2^16) overflows float16
                return (loss.astype(jnp.float32) * state.loss_scale.cur_scale,
                        (loss, act))
            return loss, (loss, act)

        new_comm = None
        plan = getattr(self, "_overlap_plan", None)
        pipe_plan = getattr(self, "_pipe_plan", None)
        hop_spec = getattr(self, "_pipe_hop_spec", None)
        pipe_ef = hop_spec is not None and hop_spec.error_feedback \
            and "pipe" in (state.comm_errors or {})
        if pipe_ef:
            ids = batch["input_ids"] if isinstance(batch, dict) else batch
            if tuple(ids.shape[:2]) != getattr(self, "_pipe_eslot_batch",
                                               tuple(ids.shape[:2])):
                from ..utils.logging import warning_once

                warning_once(
                    f"pipe: batch shape {tuple(ids.shape[:2])} does not "
                    "match the hop-EF residual layout "
                    f"{self._pipe_eslot_batch}; the hop runs "
                    "straight-through for this step")
                pipe_ef = False
        if pipe_plan is not None or pipe_ef:
            # pipe comm channels (runtime/pipe/overlap.py module
            # docstring): "g" carries each tick's reduced stage gradient
            # out as its cotangent; "e" carries the hop-EF residuals
            # (in: last step's, out-cotangent: this step's)
            p2 = dict(compute_params)
            comm_in = {}
            if pipe_plan is not None:
                comm_in["g"] = pipe_plan.grad_slots()
            if pipe_ef:
                comm_in["e"] = state.comm_errors["pipe"]
            p2["_pipe_comm"] = comm_in
            grads, (loss, act) = jax.grad(scaled_loss_fn, has_aux=True)(p2)
            grads = dict(grads)
            comm_g = grads.pop("_pipe_comm")
            if pipe_plan is not None:
                grads["layers"] = pipe_plan.merge_grads(comm_g["g"])
            if pipe_ef:
                new_comm = dict(state.comm_errors)
                new_comm["pipe"] = comm_g["e"]
        elif plan is not None and plan.compression is not None:
            # compressed overlap (docs/COMM.md "Compressed overlap"): the
            # in-loop hook owns the layer-grad exchange.  The gslot/eslot
            # channels enter as differentiable params-tree leaves; their
            # "gradients" are the reduced buckets and the new residuals
            # (the cotangent-channel contract, runtime/zero/overlap.py).
            p2 = dict(compute_params)
            p2["_overlap_comm"] = {"g": plan.grad_slots(),
                                   "e": plan.eslot_state(state.comm_errors)}
            grads, (loss, act) = jax.grad(scaled_loss_fn, has_aux=True)(p2)
            grads = dict(grads)
            comm_g = grads.pop("_overlap_comm")
            grads["layers"] = plan.merge_comm_grads(grads["layers"],
                                                    tuple(comm_g["g"]))
            if plan.error_feedback:
                new_comm = dict(state.comm_errors)
                new_comm["overlap"] = comm_g["e"]
        elif self._qgz or self._hier_inner:
            grads, loss, act, new_comm = self._qgz_grads(
                scaled_loss_fn, compute_params, batch, state.comm_errors)
            if new_comm is not None:
                new_comm = {**state.comm_errors, **new_comm}
        else:
            grads, (loss, act) = jax.grad(scaled_loss_fn,
                                          has_aux=True)(compute_params)
        with region("optimizer"):  # (the gradients' casts and their check)
            grads = cast_tree(grads, self.grad_accum_dtype)
            grads = self.zero_plan.constrain(grads, "grad")
            bad = None
            if self.fp16_enabled and (new_comm is not None or want_overflow):
                # ONE finiteness verdict per micro-step, on the POST-CAST
                # grads (exactly the tree _apply_step_body's skip decision
                # checks; the cast can only create nonfinites, never remove
                # them, so this is conservative for the residual gate too)
                bad = check_overflow(grads)
        if new_comm is not None and self.fp16_enabled:
            # an fp16 overflow step must not poison the carried residuals:
            # the backward's inf/nan rides the quantize (scale=inf -> NaN
            # codes) into comp - sent, and the optimizer's overflow skip
            # (_apply_step_body) never touches comm_errors — so gate the
            # residual update on the same finiteness signal and keep the
            # previous residuals on overflow steps
            new_comm = jax.tree_util.tree_map(
                lambda n, o: jnp.where(bad, o, n),
                new_comm, state.comm_errors)
        if getattr(self, "_overlap_struct", None) is not None:
            # trace-time span-timeline event for the gradient bytes the
            # overlap hook does NOT cover (the post-backward tail) — the
            # exposure accountant reads these against the compute spans
            from .zero.overlap import record_tail_reduce

            record_tail_reduce(self._overlap_struct["tail_bytes"])
        moe = None
        if moe_on:
            act, moe = act["act"], act["moe"]
        return grads, loss, new_comm, {"act": act, "overflow": bad,
                                       "moe": moe}

    def _micro_step_body(self, state: TrainState, batch, rng,
                         compute_params=None, with_act=False,
                         with_moe=False):
        """One accumulation micro-step.  ``with_act`` (numerics scan
        path only) and ``with_moe`` (fused scan only) return ``(state,
        (loss, act or None, counters or None))`` so the gas>1 scan can
        stack the per-layer activation stats and an expert share's
        counters; the incremental API keeps the plain ``(state, loss)``
        shape."""
        grads, loss, new_comm, extras = self._micro_grads(
            state, batch, rng, compute_params=compute_params)
        with region("optimizer"):  # (the accumulate)
            new_acc = jax.tree_util.tree_map(jnp.add, state.grad_acc, grads)
        state = dataclasses.replace(
            state, grad_acc=new_acc, micro_step=state.micro_step + 1,
            comm_errors=(new_comm if new_comm is not None
                         else state.comm_errors))
        loss = loss.astype(jnp.float32)
        if with_act or with_moe:
            return state, (loss, extras["act"] if with_act else None,
                           extras["moe"] if with_moe else None)
        return state, loss

    def _qgz_grads(self, scaled_loss_fn, compute_params, batch,
                   comm_errors=None):
        """Explicit compressed gradient reduce: compute PER-DATA-SHARD
        partial gradients (vmap over batch chunks — embarrassingly parallel,
        XLA inserts no gradient collective) and reduce them through
        ``comm/collectives``: either qgZ's int8 all-to-all (reference
        all_to_all_quant_reduce, runtime/comm/coalesced_collectives.py:31)
        or the hierarchical two-hop when ``zero_hierarchical_grad_reduce``
        split the data axis (int8 inter-slice hop iff qgZ is also on).

        ``comm_errors``: with ``grad_reduce_error_feedback`` the per-bucket
        residuals under the "reduce" key thread into the flat-path
        reducers and the updated set returns as the last value of the
        ``(grads, loss, act, new_comm)`` 4-tuple (None otherwise) —
        carried in train state so checkpoint/resume keeps them (the EF
        lifecycle contract)."""
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import DATA_AXIS
        from .zero.zeropp import quantized_grad_reduce

        W = self.topology.axis_size(DATA_AXIS)
        ef_slot = (comm_errors or {}).get("reduce") or None
        ef_keys = sorted(ef_slot) if ef_slot else []
        if isinstance(batch, dict) and batch.get("attention_mask") is not None:
            # mean-of-chunk-masked-means != global masked mean when valid
            # token counts differ across chunks; don't silently change the
            # objective — use the exact fp reduce for masked batches
            # (residuals ride through unchanged for that step)
            from ..utils.logging import warning_once

            warning_once("qgZ: batch carries attention_mask — per-chunk "
                         "masked means would reweight the loss; falling back "
                         "to the fp gradient reduce for this step")
            grads, (loss, act) = jax.grad(scaled_loss_fn,
                                          has_aux=True)(compute_params)
            return grads, loss, act, None

        def chunk(x):
            if x.shape[0] % W != 0:
                raise ValueError(f"qgZ: batch dim {x.shape[0]} not divisible "
                                 f"by data axis {W}")
            return x.reshape(W, x.shape[0] // W, *x.shape[1:])

        batch_c = jax.tree_util.tree_map(chunk, batch)
        grads_c, (losses, acts) = jax.vmap(
            lambda b: jax.grad(scaled_loss_fn, has_aux=True)(compute_params, b)
        )(batch_c)
        # act stats stay None under qgZ (the engine gates them off for
        # this path: per-chunk stats would need a second reduce)
        del acts
        # chunk specs: leading data axis + the param's TP spec (stage<=2:
        # live params carry no zero axes)
        from .zero.strategy import _path_str

        chunk_specs = jax.tree_util.tree_map_with_path(
            lambda path, g: P(DATA_AXIS, *tuple(self.zero_plan.param_spec(
                _path_str(path), g.shape[1:]))), grads_c)
        grads_c = jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, jax.sharding.NamedSharding(self.topology.mesh, s)),
            grads_c, chunk_specs)
        if self._hier_inner:
            # NOTE: the hierarchical reduce reassembles the FULL gradient
            # (hop-3 all-gather) for every leaf; stage-2 data-scattered
            # accumulation leaves then pay a reshard in constrain() that
            # the qgZ scattered path avoids — see docs/COMM.md (known
            # trade; a scattered hierarchical variant is future work)
            from ..comm.collectives import (CompressionSpec,
                                            hierarchical_grad_reduce)

            spec = (CompressionSpec(format="int8",
                                    error_feedback=bool(ef_keys))
                    if self._qgz else None)
            result = hierarchical_grad_reduce(
                grads_c, chunk_specs, self.topology.mesh,
                inner=self._hier_inner,
                compression=spec,
                bucket_bytes=int(
                    self.config.zero_config.overlap_bucket_mb * 2**20),
                errors=([ef_slot[k] for k in ef_keys]
                        if (ef_keys and spec is not None) else None))
            if ef_keys and spec is not None:
                grads, new_errs = result
                return grads, jnp.mean(losses), None, {
                    "reduce": dict(zip(ef_keys, new_errs))}
            return result, jnp.mean(losses), None, None
        # target = the accumulation buffer's sharding: data-sharded leaves
        # come back as the SCATTERED partition (one all_to_all, no hop-2
        # gather — reference all_to_all_quant_reduce returns the partition)
        target_specs = jax.tree_util.tree_map_with_path(
            lambda path, g: self.zero_plan.grad_spec(_path_str(path),
                                                     g.shape[1:]), grads_c)
        result = quantized_grad_reduce(
            grads_c, chunk_specs, self.topology.mesh,
            target_specs=target_specs,
            bucket_bytes=int(
                self.config.zero_config.overlap_bucket_mb * 2**20),
            errors=([ef_slot[k] for k in ef_keys] if ef_keys else None))
        if ef_keys:
            grads, new_errs = result
            return grads, jnp.mean(losses), None, {
                "reduce": dict(zip(ef_keys, new_errs))}
        return result, jnp.mean(losses), None, None

    def _apply_step_body(self, state: TrainState, grads_src=None,
                         overflow=None) -> TrainState:
        """``_apply_update`` as the ``optimizer`` region of the step's
        program (telemetry/regions.py)."""
        with region("optimizer"):
            return self._apply_update(state, grads_src, overflow)

    def _apply_update(self, state: TrainState, grads_src=None,
                      overflow=None) -> TrainState:
        """Boundary update.  ``grads_src``: gradients to apply instead of
        ``state.grad_acc`` — the fused gas=1 path feeds the micro-step's
        gradients straight through, skipping the accumulation-buffer
        read/modify/write entirely.  ``overflow``: a precomputed fp16
        finiteness verdict over ``grads_src`` (``_micro_grads`` already
        ran the full-tree reduction for the EF residual gate; the
        unscale/clip below cannot turn a nonfinite leaf finite, so
        re-checking here would be a duplicate pass over the gradients).
        gas>1 always recomputes: the accumulation-buffer SUM can
        overflow even when every micro-step's grads were finite."""
        gas = self.config.gradient_accumulation_steps or 1
        denom = jnp.asarray(float(gas), jnp.float32)
        if self.fp16_enabled:
            denom = denom * state.loss_scale.cur_scale

        if getattr(self, "_opt_dev_shardings", None) is not None:
            # host-offloaded moments (compile offload_adam_states pass):
            # stream them into device memory for the update; results return
            # to host via out_shardings (TPU) or _repin_opt_state (host
            # platforms).  "keep" entries (scalar leaves) never moved.
            opt_state = jax.tree_util.tree_map(
                lambda x, s: x if s == "keep" else jax.device_put(x, s),
                state.opt_state, self._opt_dev_shardings)
            state = dataclasses.replace(state, opt_state=opt_state)

        grads = jax.tree_util.tree_map(
            lambda g: (g.astype(jnp.float32) / denom),
            state.grad_acc if grads_src is None else grads_src)
        grads = self.zero_plan.constrain(grads, "master")
        # host-offloaded master: stream to device BEFORE the overflow cond —
        # branches returning different memory spaces break lowering
        fetched_params = self._fetch_params(state.params)

        norm = global_grad_norm(grads)
        clip = self.config.gradient_clipping
        if clip > 0:
            grads = clip_by_global_norm(grads, norm, clip)

        def do_update(operand):
            params, opt_state, grads = operand
            direct = getattr(self.optimizer, "direct_update", None)
            if direct is not None:
                # fused-kernel path: new params come straight out of the
                # kernel, skipping the updates-delta + apply_updates passes
                if self.topology.world_size > 1:
                    # Adam is elementwise: run the kernel on each device's
                    # LOCAL master/grad shard via shard_map — no gather.
                    # Replicated leaves (P()) update redundantly but
                    # identically on every device.
                    from jax.sharding import PartitionSpec as P

                    # specs for the moments must come from the OPT_STATE
                    # tree's own paths ("m/<leaf path>"), exactly as its
                    # initial shardings were derived — reusing the param
                    # tree's specs diverges whenever a partition rule
                    # anchors on the path start (auto_tp's '^...$' rules),
                    # and a mismatch reshards m/v through an all-to-all
                    # every step
                    pspecs = self.zero_plan.tree_specs(params, "master")
                    sspecs = self.zero_plan.tree_specs(opt_state, "master")
                    fn = shard_map(direct, mesh=self.topology.mesh,
                                   in_specs=(pspecs, sspecs, pspecs),
                                   out_specs=(pspecs, sspecs),
                                   check_vma=False)
                    new_params, new_opt = fn(grads, opt_state, params)
                else:
                    new_params, new_opt = direct(grads, opt_state, params)
            else:
                updates, new_opt = self.optimizer.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
            if getattr(self, "_buffer_mask", None) is not None:
                # a buffer is state, not a parameter: no update, no decay
                new_params = jax.tree_util.tree_map(
                    lambda keep, old, new: old if keep else new,
                    self._buffer_mask, params, new_params)
            return new_params, new_opt, jnp.asarray(0, jnp.int32)

        def skip_update(operand):
            params, opt_state, _ = operand
            return params, opt_state, jnp.asarray(1, jnp.int32)

        if self.fp16_enabled:
            if overflow is None:
                overflow = check_overflow(grads)
            new_params, new_opt, skipped = jax.lax.cond(
                overflow, skip_update, do_update,
                (fetched_params, state.opt_state, grads))
            new_scale = update_loss_scale(state.loss_scale, overflow, self.config.fp16)
        else:
            new_params, new_opt, skipped = do_update(
                (fetched_params, state.opt_state, grads))
            new_scale = state.loss_scale

        # Fused gas=1 path: the acc buffer was never written this step and is
        # still zeros, so pass it through (free under donation).  Stale
        # accumulation from an ABANDONED incremental micro-step is reset at
        # the API boundary instead (train_batch checks _acc_dirty) — an
        # unconditional zeros_like here would be a model-sized HBM memset on
        # the hot path, since the donated output buffer must really be
        # written for the next step to read.
        # The fresh zeros carry the buffer's planned sharding: left to the
        # compiler they come back replicated (seen on four chips under
        # {model: 2, data: 2}: 1/1 instead of 1/2 per device, and one extra
        # compile when step 2 meets the changed input sharding).
        zero_acc = (state.grad_acc if grads_src is not None
                    else self.zero_plan.constrain(
                        jax.tree_util.tree_map(jnp.zeros_like, state.grad_acc),
                        "grad"))
        return dataclasses.replace(
            state,
            params=new_params,
            opt_state=new_opt,
            grad_acc=zero_acc,
            loss_scale=new_scale,
            step=state.step + (1 - skipped),
            micro_step=jnp.asarray(0, jnp.int32),
            skipped_steps=state.skipped_steps + skipped,
            global_grad_norm=norm,
        )

    def _train_batch_body(self, state: TrainState, batches, rng,
                          moe_acc=None):
        """Fused full step: scan micro-batches then apply.  ``batches`` has a
        leading gradient-accumulation dim.  At gas=1 the micro-batch's
        gradients feed the update directly — no accumulation-buffer
        round-trip (the buffer stays zeros).

        With the numerics observatory on (``_numerics_fused``) a THIRD
        output rides the fused step: the in-graph stats tree
        (``_numerics_tree``) — device-resident until the existing
        steps_per_print boundary pulls it, so the hot path gains zero
        host syncs.

        With an expert share's counters on (``_moe_counters``) the step
        takes their running sum ``moe_acc`` and returns it, this step's
        added, as its LAST output: device-resident until ``moe_stats()``."""
        if getattr(self, "_moe_counters", False):
            out, moe = self._train_batch_core(state, batches, rng)
            # the sum comes back as it went in (replicated), so that a
            # reset sum and a returned one meet one compiled program
            return (*out, jax.lax.with_sharding_constraint(
                moe_acc + moe, self.topology.replicated()))
        return self._train_batch_core(state, batches, rng)[0]

    def _train_batch_core(self, state: TrainState, batches, rng):
        """-> (the fused step's outputs, this step's expert-share counters
        summed over its micro-batches or None)."""
        gas = self.config.gradient_accumulation_steps or 1
        nm = getattr(self, "_numerics_fused", False)
        moe_on = getattr(self, "_moe_counters", False)
        if gas == 1:
            batch = jax.tree_util.tree_map(lambda x: x[0], batches)
            # same rng stream as the scan path (split, don't use raw) so a
            # seeded run reproduces across both paths
            grads, loss, new_comm, extras = self._micro_grads(
                state, batch, jax.random.split(rng, 1)[0],
                want_overflow=self.fp16_enabled)
            if new_comm is not None:
                state = dataclasses.replace(state, comm_errors=new_comm)
            state = self._apply_step_body(state, grads_src=grads,
                                          overflow=extras["overflow"])
            loss = loss.astype(jnp.float32)
            if not nm:
                return (state, loss), extras["moe"]
            return (state, loss, self._numerics_tree(
                state, grads, loss, extras["act"])), extras["moe"]
        act_on = nm and getattr(self, "_numerics_act", False)
        res = self._micro_scan_body(state, batches, rng, with_act=act_on,
                                    with_moe=moe_on)
        state, loss = res[0], res[1]
        act = res[2] if act_on else None
        moe = res[-1] if moe_on else None
        if nm:
            grads = state.grad_acc  # pre-apply: apply zeroes the buffer
            state = self._apply_step_body(state)
            return (state, loss, self._numerics_tree(state, grads, loss,
                                                     act)), moe
        state = self._apply_step_body(state)
        return (state, loss), moe

    def _micro_scan_body(self, state: TrainState, batches, rng,
                         with_act=False, with_moe=False):
        """-> (state, mean loss[, act if ``with_act``][, the expert-share
        counters summed over the micro-batches if ``with_moe``])."""
        gas = self.config.gradient_accumulation_steps or 1
        rngs = jax.random.split(rng, gas)
        compute_params = self._compute_params(state.params)

        def body(st, xs):
            batch, r = xs
            return self._micro_step_body(st, batch, r,
                                         compute_params=compute_params,
                                         with_act=with_act,
                                         with_moe=with_moe)

        # (the accumulation loop itself — its slices of the batch, the
        # buffer it carries — is the optimizer's; the model names its own)
        with region("optimizer"):
            state, ys = jax.lax.scan(body, state, (batches, rngs))
        if not (with_act or with_moe):
            return state, jnp.mean(ys)
        losses, acts, moe = ys  # acts: [gas, L, 3]
        out = (state, jnp.mean(losses))
        if with_act:
            # fold the per-micro-step rows the way each column means:
            # norms average, max-abs maxes, nonfinite counts sum
            out += (jnp.stack([jnp.mean(acts[..., 0], axis=0),
                               jnp.max(acts[..., 1], axis=0),
                               jnp.sum(acts[..., 2], axis=0)], axis=-1),)
        if with_moe:
            out += (jnp.sum(moe, axis=0),)
        return out

    def _numerics_tree(self, state: TrainState, grads, loss, act):
        """In-graph numerics stats tree (telemetry/numerics.py) — the
        fused step's third output.  Pure jnp over trees the step already
        computed; the host never touches it until the steps_per_print
        boundary pulls the whole tree in one device_get.  ``grads`` are
        the pre-unscale accumulated gradients, so magnitude stats carry
        ``inv_scale = 1/(gas * loss_scale)`` to report TRUE values;
        ``state`` is post-apply (its grad_norm/skipped_steps are this
        boundary's)."""
        from ..telemetry import numerics as _nm

        gas = self.config.gradient_accumulation_steps or 1
        inv = jnp.float32(1.0 / float(gas))
        if self.fp16_enabled:
            inv = inv / state.loss_scale.cur_scale
        stats = {
            "step": state.step,
            "loss": loss,
            "grad_norm": state.global_grad_norm,
            "skipped_steps": state.skipped_steps,
            "grad": _nm.tree_health(grads, inv_scale=inv),
            "param": _nm.tree_health(state.params),
            "opt_nonfinite": nonfinite_count(state.opt_state),
            "grad_leaf_nonfinite": _nm.leaf_nonfinite(grads),
        }
        if isinstance(grads, dict) and "layers" in grads:
            gl = _nm.stacked_health(grads["layers"], inv_scale=inv)
            if gl is not None:
                stats["grad_layers"] = gl
        if isinstance(state.params, dict) and "layers" in state.params:
            pl = _nm.stacked_health(state.params["layers"])
            if pl is not None:
                stats["param_layers"] = pl
        ef = _nm.ef_residual_norms(state.comm_errors)
        if ef:
            stats["ef_residual"] = ef
        plan = getattr(self, "_overlap_plan", None)
        if plan is not None and "overlap" in (state.comm_errors or {}):
            stats["ef_bucket"] = plan.residual_norms(state.comm_errors)
        if state.loss_scale is not None:
            stats["loss_scale"] = loss_scale_summary(state.loss_scale)
        if act is not None:
            stats["act_layers"] = act
        return stats

    def _compile_steps(self, opt_state_memory_kind: Optional[str] = None,
                       param_memory_kind: Optional[str] = None) -> None:
        # the offload mode is sticky: once offload_adam_states /
        # offload_params set it, later recompiles (e.g. a subsequent
        # offload_activation pass) must keep the state host-resident
        # rather than silently reverting
        if opt_state_memory_kind is not None:
            self._opt_offload_kind = opt_state_memory_kind
        if param_memory_kind is not None:
            self._param_offload_kind = param_memory_kind
        opt_state_memory_kind = getattr(self, "_opt_offload_kind", None)
        param_memory_kind = getattr(self, "_param_offload_kind", None)
        # rebuilt jit wrappers legitimately compile on the next call —
        # announce it so the sentinel does not flag a steady-state recompile
        expect_recompile("engine._compile_steps")
        self._step_program_noted = False
        #: the programs built here that have been called: a program's first
        #: call traces and lowers it, beneath the deep frame
        #: (compile/deep_frame.py)
        self._lowered_programs: set = set()
        donate = dict(donate_argnums=(0,))
        self._micro_step = jax.jit(self._micro_step_body, **donate)
        self._eval_fn = None
        if self.offload_optimizer is not None:
            # The boundary update runs on host (C++ SIMD Adam); the device
            # program is micro-steps only.  Opt-in on TPU: pin the
            # grad-accumulation OUTPUTS to pinned host memory so XLA streams
            # grads D2H inside the program, overlapped with the backward
            # wave (reference overlaps grad copies with backward via swap
            # streams, zero/stage3.py).  OPT-IN because the grad_acc is the
            # micro-step scan's carry: XLA's memory-space propagation could
            # instead host-place the buffer for the whole scan and turn
            # every accumulate into a host round-trip — until measured on a
            # real chip (gas>1), the default stays the post-program D2H with
            # parallel copy_to_host_async.  The input zeros stay
            # device-resident (_apply_step_offload re-zeros with memory
            # kind "device").
            import os as _os

            if (on_tpu()
                    and _os.environ.get("DSTPU_OFFLOAD_HOST_GRADS") == "1"):
                state_sh = jax.tree_util.tree_map(
                    lambda x: x.sharding if hasattr(x, "sharding") else None,
                    self.state)
                host_acc = jax.tree_util.tree_map(
                    lambda s: s.with_memory_kind("pinned_host"),
                    state_sh.grad_acc)
                state_sh = dataclasses.replace(state_sh, grad_acc=host_acc)
                self._train_batch = jax.jit(self._micro_scan_body,
                                            out_shardings=(state_sh, None),
                                            **donate)
            else:
                self._train_batch = jax.jit(self._micro_scan_body, **donate)
            self._apply_step = None
            return
        if opt_state_memory_kind is not None or param_memory_kind is not None:
            # Host-resident state (offload_adam_states pass / ZeRO-Infinity
            # offload_param): the moments are fetched to device inside the
            # step (_apply_step_body device_put); host-placed PARAM inputs
            # stream in implicitly.  Results return to host either via
            # out_shardings (TPU: XLA streams them back inside the program)
            # or via the eager _repin_* fallback (host platforms, where
            # memory-kind out_shardings are not lowerable).  "keep" marks
            # scalar leaves that never left device memory (annotating their
            # placement trips the SPMD partitioner).
            if opt_state_memory_kind is not None:
                self._opt_dev_shardings = jax.tree_util.tree_map(
                    lambda x: x.sharding.with_memory_kind("device")
                    if hasattr(x, "sharding") and getattr(x, "ndim", 0) >= 1
                    else "keep",
                    self.state.opt_state)
            if param_memory_kind is not None:
                self._param_dev_shardings = jax.tree_util.tree_map(
                    lambda x: x.sharding.with_memory_kind("device")
                    if hasattr(x, "sharding") and getattr(x, "ndim", 0) >= 1
                    else "keep",
                    self.state.params)
            if on_tpu():
                state_sh = jax.tree_util.tree_map(
                    lambda x: x.sharding if hasattr(x, "sharding") else None,
                    self.state)
                self._opt_host_shardings = None
                self._param_host_shardings = None
                self._apply_step = jax.jit(self._apply_step_body,
                                           out_shardings=state_sh, **donate)
                # third output slot = the numerics stats tree (XLA places
                # the small scalars/vectors itself)
                out_sh = ((state_sh, None, None)
                          if getattr(self, "_numerics_fused", False)
                          else (state_sh, None))
                if getattr(self, "_moe_counters", False):
                    out_sh += (self.topology.replicated(),)
                self._train_batch = jax.jit(self._train_batch_body,
                                            out_shardings=out_sh,
                                            **donate)
                return
            if opt_state_memory_kind is not None:
                self._opt_host_shardings = jax.tree_util.tree_map(
                    lambda x: x.sharding if hasattr(x, "sharding") else "keep",
                    self.state.opt_state)
            if param_memory_kind is not None:
                self._param_host_shardings = jax.tree_util.tree_map(
                    lambda x: x.sharding if hasattr(x, "sharding") else "keep",
                    self.state.params)
        self._apply_step = jax.jit(self._apply_step_body, **donate)
        self._train_batch = jax.jit(self._train_batch_body, **donate)

    def _repin_opt_state(self) -> None:
        """After a boundary step, spill host-offloaded optimizer moments /
        master params back to host memory (they are HBM-resident only
        inside the step program; TPU returns them via out_shardings, host
        platforms eagerly here)."""
        if getattr(self, "_opt_host_shardings", None) is not None:
            self.state = dataclasses.replace(
                self.state,
                opt_state=jax.tree_util.tree_map(
                    lambda x, s: x if s == "keep" else jax.device_put(x, s),
                    self.state.opt_state, self._opt_host_shardings))
        if getattr(self, "_param_host_shardings", None) is not None:
            self.state = dataclasses.replace(
                self.state,
                params=jax.tree_util.tree_map(
                    lambda x, s: x if s == "keep" else jax.device_put(x, s),
                    self.state.params, self._param_host_shardings))

    def compile(self, backend: str = "xla", passes=None):
        """Apply DeepCompile-style passes to the step programs (reference
        ``engine.compile()``, engine.py:4243; see compile/backend.py)."""
        from ..compile import compile_engine

        return compile_engine(self, backend=backend, passes=passes)

    # --------------------------------------------------- state offload API
    def offload_states(self, include=None, device: str = "cpu",
                       pin_memory: bool = True,
                       non_blocking: bool = False) -> None:
        """Move the whole TrainState to host RAM and free the HBM copies
        (reference ``engine.offload_states``, engine.py:4358 — used to park
        a model, e.g. between RLHF phases).  ``reload_states`` restores it;
        training calls in between raise."""
        del include, device, pin_memory, non_blocking  # full-state, host-only
        if getattr(self, "_host_state", None) is not None:
            return
        self._host_state_shardings = jax.tree_util.tree_map(
            lambda x: x.sharding if hasattr(x, "sharding") else "keep",
            self.state)
        self._host_state = jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x))
            if hasattr(x, "sharding") else x, self.state)
        for leaf in jax.tree_util.tree_leaves(self.state):
            if hasattr(leaf, "delete"):
                leaf.delete()
        self.state = None
        log_dist("offload_states: TrainState moved to host; HBM freed")

    def reload_states(self, non_blocking: bool = False) -> None:
        """Undo ``offload_states`` (reference ``engine.reload_states``)."""
        del non_blocking
        if getattr(self, "_host_state", None) is None:
            return
        with self.topology.mesh:
            self.state = jax.tree_util.tree_map(
                lambda h, s: h if s == "keep" else jax.device_put(h, s),
                self._host_state, self._host_state_shardings)
        self._host_state = None
        self._host_state_shardings = None
        log_dist("reload_states: TrainState restored to device")

    # ------------------------------------------------------- offloaded step
    def _apply_step_offload(self) -> None:
        """Boundary update on the host: pull reduced grads, run C++ Adam on
        the fp32 master, push compute-dtype params back (reference
        ZeRO-Offload data path, stage3 _optimizer_step with CPU-Adam)."""
        import dataclasses as _dc

        import numpy as np

        state = self.state
        gas = float(self.config.gradient_accumulation_steps or 1)
        # dstpu-lint: allow[host-sync] offload boundary IS host-side by
        # design: the C++ Adam needs the step count for the LR schedule
        lr = float(self.lr_schedule(int(state.step)))
        grad_leaves = jax.tree_util.tree_leaves(state.grad_acc)
        # kick off every leaf's D2H copy before touching any of them: the
        # transfers run in parallel instead of leaf-serial device_get
        # (reference: swap/offload grad copies overlapped with backward)
        for g in grad_leaves:
            if hasattr(g, "copy_to_host_async"):
                g.copy_to_host_async()
        # dstpu-lint: allow[host-sync] the host optimizer consumes grads on
        # host; the D2H copies were already overlapped via copy_to_host_async
        grads_flat = [np.asarray(jax.device_get(g)) for g in grad_leaves]

        denom = gas
        new_loss_scale = state.loss_scale
        if self.fp16_enabled:
            # reference ZeRO-Offload fp16 path (zero/stage_1_and_2.py loss
            # scaler + CPU-Adam): grads arrive scaled by cur_scale; the
            # overflow check runs on the HOST copy (free — the bytes are
            # already here for the C++ Adam), the unscale rides the
            # denominator, and an overflow skips the whole host update
            # before any master state is touched.
            overflow = any(not np.isfinite(g).all() for g in grads_flat)
            new_loss_scale = update_loss_scale(
                state.loss_scale, jnp.asarray(overflow), self.config.fp16)
            if overflow:
                # dstpu-lint: allow[host-sync] rare skip-path log; the
                # scale state lives replicated and is already host-visible
                log_dist(f"offload fp16: overflow, skipping step; scale "
                         f"{float(state.loss_scale.cur_scale):.0f} -> "
                         f"{float(new_loss_scale.cur_scale):.0f}")
                self.state = _dc.replace(
                    state,
                    grad_acc=self._zero_like_tree(state.grad_acc,
                                                  force_device=True),
                    micro_step=jnp.asarray(0, jnp.int32),
                    loss_scale=new_loss_scale,
                    skipped_steps=state.skipped_steps + 1,
                    global_grad_norm=jnp.asarray(0.0, jnp.float32))
                return
            # dstpu-lint: allow[host-sync] host update divides by the scale
            # on host; grads are already host-resident at this point
            denom = gas * float(state.loss_scale.cur_scale)

        master, norm = self.offload_optimizer.apply_step(grads_flat, lr, denom)

        leaves, treedef = jax.tree_util.tree_flatten(state.params)
        # Bucketed batched device_put: transfers within a bucket are issued
        # together (not leaf-serial) and async — the next forward's
        # host-side work overlaps the push, the double-buffering the
        # reference gets from its swap streams.  Bucketing (not one giant
        # batch) bounds the transient host copy of converted compute-dtype
        # params: offload hosts are RAM-budgeted for masters+moments, and a
        # full extra model copy at the boundary could tip them over.
        bucket_bytes = 64 << 20
        new_leaves = []
        i = 0
        while i < len(leaves):
            j, acc_bytes = i, 0
            while j < len(leaves) and (j == i or acc_bytes < bucket_bytes):
                acc_bytes += leaves[j].size * leaves[j].dtype.itemsize
                j += 1
            # the copy is REQUIRED even when dtypes match: on CPU backends
            # device_put zero-copies aligned numpy buffers, and cpu_adam
            # mutates self.master in place next step — a view would change
            # the live params behind XLA's back.  Bucketing bounds the
            # transient to bucket_bytes.
            # dstpu-lint: allow[host-sync] host->host copy of the numpy
            # master (required, see above) — not a device sync
            host_arrs = [np.array(master[k], dtype=leaves[k].dtype)
                         .reshape(leaves[k].shape) for k in range(i, j)]
            new_leaves.extend(jax.device_put(
                host_arrs, [leaves[k].sharding for k in range(i, j)]))
            i = j
        new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
        # zeros go to DEVICE memory even when the grad outputs stream to
        # pinned host (TPU): the next step's accumulation reads them there
        zero_acc = self._zero_like_tree(state.grad_acc, force_device=True)
        self.state = _dc.replace(
            state, params=new_params, grad_acc=zero_acc,
            step=state.step + 1, micro_step=jnp.asarray(0, jnp.int32),
            loss_scale=new_loss_scale,
            global_grad_norm=jnp.asarray(norm, jnp.float32))

    # ------------------------------------------------------------ public API
    def _next_training_batch(self):
        # re-wrap when the loader object was swapped (deepspeed_io rebuild)
        if getattr(self, "_train_iter_src", None) is not self.training_dataloader:
            self._train_iter = RepeatingLoader(self.training_dataloader)
            self._train_iter_src = self.training_dataloader
        try:
            return next(self._train_iter)
        except StopIteration:
            raise ValueError(
                "training dataloader is empty (fewer samples than one "
                "global batch with drop_last?)") from None

    def _next_rng(self):
        self._rng, out = jax.random.split(self._rng)
        return out

    @staticmethod
    def _zero_like_tree(tree, force_device: bool = False):
        """Zeros preserving each leaf's sharding.  ``force_device``: place in
        device memory even when the source buffer is pinned-host-resident
        (grad buffers must be re-zeroed on device for the next step)."""

        def sharding_of(x):
            sh = getattr(x, "sharding", None)
            if force_device and sh is not None:
                try:
                    return sh.with_memory_kind("device")
                except Exception:
                    return sh
            return sh

        return jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, x.dtype, device=sharding_of(x)),
            tree)

    #: consecutive non-finite losses tolerated while the DYNAMIC fp16 loss
    #: scaler is skipping steps: enough for a full backoff from 2^32 to the
    #: floor; persistent NaN divergence skips forever and must still abort
    _SANITY_MAX_SKIP_RUN = 50

    def _skipped_steps_snapshot(self) -> Optional[int]:
        """Pre-step skip count when the fp16 overflow tolerance applies
        (dynamic scaling only — a static scale never recovers, so a
        non-finite loss there is immediately fatal); None = no tolerance."""
        # dstpu-lint: allow[host-sync] config scalar, not a device value
        if (self.config.sanity_checks and self.fp16_enabled
                and float(self.config.fp16.loss_scale) == 0.0):
            # dstpu-lint: allow[host-sync] opt-in sanity path: its host
            # sync cost is the documented price of the guard
            return int(self.state.skipped_steps)
        return None

    def _sanity_check_maybe(self, loss,
                            skipped_before: Optional[int] = None) -> None:
        """Reference is_sanity_checks_enabled (engine.py:1119): fail FAST on
        a non-finite loss instead of training on garbage; the host sync it
        costs is why this is opt-in.  Covers both train_batch and the
        forward/backward/step loop.

        fp16 exception: an overflow step the dynamic-loss-scaler SKIPPED
        (scale comes down, training recovers) is the mechanism working —
        tolerated, but only for ``_SANITY_MAX_SKIP_RUN`` consecutive
        non-finite losses: a diverged model NaNs (and therefore skips)
        every step forever, and that must still abort."""
        if not self.config.sanity_checks or loss is None:
            return
        # dstpu-lint: allow[host-sync] the docstring above: this sync is
        # exactly why sanity_checks is opt-in
        lv = float(loss)
        if np.isfinite(lv):
            self._sanity_skip_run = 0
            return
        # dstpu-lint: allow[host-sync] opt-in sanity path (see above)
        if (skipped_before is not None
                and int(self.state.skipped_steps) > skipped_before):
            self._sanity_skip_run = getattr(self, "_sanity_skip_run", 0) + 1
            if self._sanity_skip_run <= self._SANITY_MAX_SKIP_RUN:
                return  # overflow handled by the loss scaler
        # dstpu-lint: allow[host-sync] terminal error path: the job is dead,
        # the sync enriches the post-mortem
        raise FloatingPointError(
            f"sanity_checks: non-finite loss {lv} at step "
            f"{self.global_steps} (grad norm "
            f"{float(self.state.global_grad_norm):.3g}, "
            f"consecutive tolerated skips "
            f"{getattr(self, '_sanity_skip_run', 0)})")

    def start_profiler_trace(self, log_dir: str) -> None:
        """Start an XLA/TPU profiler trace (reference nvtx ranges +
        torch.profiler story, utils/nvtx.py): the trace captures device
        timelines, fusions, and memory, viewable in TensorBoard/XProf."""
        jax.profiler.start_trace(log_dir)

    def stop_profiler_trace(self) -> None:
        jax.block_until_ready(self.state.step)  # flush in-flight steps
        jax.profiler.stop_trace()

    def _timeline_sync(self) -> None:
        """Device fence for timeline captures: the capture window must
        close only after the traced step's device work has retired, or
        the decomposition under-counts compute and over-counts host gap."""
        jax.block_until_ready(self.state.step)

    def capture_timeline(self, batch=None,
                         data_iter: Optional[Iterator] = None):
        """Force a step-time attribution capture around ONE train_batch
        and return ``(loss, record)`` — the report entry point
        (tools/goodput_report.py; no cadence configuration needed).
        ``record`` is None when telemetry or the timeline is disabled."""
        tl = self.telemetry.timeline if self.telemetry is not None else None
        if tl is None:
            return self.train_batch(batch=batch, data_iter=data_iter), None
        tl.force_next()
        loss = self.train_batch(batch=batch, data_iter=data_iter)
        return loss, tl.last_record()

    def timeline_record(self):
        """Last completed step-time attribution record, or None."""
        tl = self.telemetry.timeline if self.telemetry is not None else None
        return tl.last_record() if tl is not None else None

    def goodput_summary(self):
        """Current goodput/badput ledger summary, or None."""
        gp = self.telemetry.goodput if self.telemetry is not None else None
        return gp.summary() if gp is not None else None

    def train_batch(self, batch=None, data_iter: Optional[Iterator] = None):
        """One full optimizer step (the native fused path).

        ``batch`` leaves must carry a leading dim of
        ``gradient_accumulation_steps`` (use ``stack_microbatches``), or pass
        ``data_iter`` to pull gas micro-batches.
        """
        if batch is None:
            gas = self.config.gradient_accumulation_steps or 1
            if data_iter is not None:
                micro = [next(data_iter) for _ in range(gas)]
            elif self.training_dataloader is not None:
                # the dataloader is an iterable, not an iterator: keep one
                # live iterator and wrap around at epoch end (reference
                # RepeatingLoader, runtime/dataloader.py)
                micro = [self._next_training_batch() for _ in range(gas)]
            else:
                raise ValueError("train_batch needs a batch or a data iterator")
            batch = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *micro)
        if self.flops_profiler is not None:
            self.flops_profiler.start_profile_maybe(self.global_steps, batch)
        self.tput_timer.start()
        skipped_before = self._skipped_steps_snapshot()
        if self._acc_dirty:
            # abandoned incremental micro-step(s): reset the stale
            # accumulation so the fused path's still-zeros invariant holds
            # (gas>1 scans accumulate ON TOP of this buffer, gas=1 passes it
            # through untouched)
            with self.topology.mesh:
                self.state = dataclasses.replace(
                    self.state,
                    grad_acc=self._zero_like_tree(self.state.grad_acc),
                    micro_step=jnp.asarray(0, jnp.int32))
            # void the abandoned micro-steps in the host counter too, or
            # is_gradient_accumulation_boundary() stays phase-shifted for
            # any later incremental-API use
            gas_ = self.config.gradient_accumulation_steps or 1
            self.micro_steps -= self.micro_steps % gas_
            self._acc_dirty = False
        from ..telemetry.tracing import _noop as _no_trace

        t0 = time.perf_counter()
        trace = (self.telemetry.step_trace(self.global_steps)
                 if self.telemetry is not None else _no_trace())
        # periodic step-time attribution: only the captured step pays the
        # profiler start/stop + parse cost (off the hot path; the capture
        # context is exception-safe and never re-raises into the step)
        tl = self.telemetry.timeline if self.telemetry is not None else None
        capturing = tl is not None and tl.should_capture(self.global_steps)
        # the captured step pays profiler start/stop + parse: its wall
        # time is self-inflicted overhead, so it must not feed the stall
        # watchdog's median (nor rate as a data stall in goodput)
        self._timeline_captured = capturing
        cap = (tl.capture(self.global_steps,
                          pipe_struct=getattr(self, "_pipe_struct", None),
                          sync=self._timeline_sync, regions=region_index)
               if capturing else _no_trace())
        try:
            with cap, trace, span("train_batch", cat="train",
                                  step=self.global_steps):
                with self.topology.mesh:
                    if getattr(self, "_moe_counters", False):
                        # the counters' running sum goes in and comes out,
                        # device-resident (no sync): moe_stats() reads it
                        if self._moe_acc is None:
                            self._moe_acc = self._moe_zeros()
                        *out, self._moe_acc = first_call_beneath(
                            self._lowered_programs, "train_batch",
                            self._train_batch, self.state, batch,
                            self._next_rng(), self._moe_acc)
                        self._moe_steps += 1
                    else:
                        out = first_call_beneath(
                            self._lowered_programs, "train_batch",
                            self._train_batch, self.state, batch,
                            self._next_rng())
                    if getattr(self, "_numerics_fused", False):
                        # stats stay device-resident (no sync): pulled at
                        # the steps_per_print boundary by _report_telemetry
                        self.state, loss, self._last_numerics = out
                    else:
                        self.state, loss = out
                self._repin_opt_state()
                if self.offload_optimizer is not None:
                    self._apply_step_offload()
                self.global_steps += 1
                self.micro_steps += self.config.gradient_accumulation_steps or 1
                self._sanity_check_maybe(loss, skipped_before)
                # dispatch is async: drain the device queue at reporting
                # boundaries so the throughput window [boundary, boundary]
                # measures real wall time
                if self.global_steps % self.config.steps_per_print == 0 or \
                        self.config.wall_clock_breakdown:
                    jax.block_until_ready(loss)
        except Exception as e:
            # black box first, then propagate: the flight dump is the
            # only record of what the process was doing when it died
            # (RESOURCE_EXHAUSTED upgrades to a full OOM incident report)
            dump_on_exception("engine.train_batch", e)
            raise
        self.tput_timer.stop()
        if self.telemetry is not None and self.telemetry.sentinel is not None:
            # observed BEFORE the reporting path below: its occasional
            # cost-analysis compiles must not masquerade as this step's
            from ..compile.backend import shape_signature

            self.telemetry.sentinel.observe_step(
                [("train_batch", shape_signature(batch))],
                step=self.global_steps)
        if self.flops_profiler is not None:
            self.flops_profiler.stop_profile_maybe(self.global_steps)
        if self.telemetry is not None:
            self._report_telemetry(loss, batch, time.perf_counter() - t0)
        self._report(loss)
        if self.resilience is not None:
            # pending preemption notice -> emergency save + resumable
            # exit, honored HERE (a consistent step boundary), never
            # mid-step (raises PreemptionInterrupt, a SystemExit)
            self.resilience.at_step_boundary(self)
        return loss

    def forward(self, batch):
        """DeepSpeed-compat micro-step: computes loss AND gradients in one
        fused fwd+bwd (cached); ``backward`` then only accounts the
        micro-step.  Matches reference cadence, avoids double forward."""
        if self.flops_profiler is not None:
            self.flops_profiler.start_profile_maybe(self.global_steps, batch)
        self.timers(FORWARD_GLOBAL_TIMER).start()
        with span("forward", cat="train", micro_step=self.micro_steps), \
                self.topology.mesh:
            self.state, loss = first_call_beneath(
                self._lowered_programs, "micro_step", self._micro_step,
                self.state, batch, self._next_rng())
        self._acc_dirty = True
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        self._cached_loss = loss
        return loss

    __call__ = forward

    def backward(self, loss=None):
        """Gradient work already fused into forward (XLA compiles fwd+bwd as
        one program); this advances the micro-step counter (reference
        engine.backward, engine.py:2466)."""
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        # a point event, not a span: the gradient work is fused into the
        # forward program, so a duration here would read as "backward is
        # free" in a trace — the marker records only the cadence
        record_event("backward", cat="train", micro_step=self.micro_steps,
                     fused_into="forward")
        self.micro_steps += 1
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss if loss is not None else self._cached_loss

    def is_gradient_accumulation_boundary(self) -> bool:
        gas = self.config.gradient_accumulation_steps or 1
        return self.micro_steps % gas == 0

    def step(self):
        """Apply the optimizer at the gas boundary (reference engine.step,
        engine.py:2641)."""
        self.timers(STEP_GLOBAL_TIMER).start()
        if self.is_gradient_accumulation_boundary():
            skipped_before = self._skipped_steps_snapshot()
            try:
                with span("optimizer_step", cat="train",
                          step=self.global_steps):
                    if self.offload_optimizer is not None:
                        self._apply_step_offload()
                    else:
                        with self.topology.mesh:
                            self.state = first_call_beneath(
                                self._lowered_programs, "apply_step",
                                self._apply_step, self.state)
                        self._repin_opt_state()
            except Exception as e:
                dump_on_exception("engine.step", e)
                raise
            self._acc_dirty = False  # buffer consumed and re-zeroed
            self.global_steps += 1
            self._sanity_check_maybe(self._cached_loss, skipped_before)
            self.lr_scheduler.step()
            if self.config.wall_clock_breakdown:
                jax.block_until_ready(self.state.step)
            if self.telemetry is not None:
                self._report_telemetry(self._cached_loss, None)
            self._report(self._cached_loss)
            if self.resilience is not None:
                self.resilience.at_step_boundary(self)
        self.timers(STEP_GLOBAL_TIMER).stop()
        if self.flops_profiler is not None:
            self.flops_profiler.stop_profile_maybe(self.global_steps)

    def eval_batch(self, batch):
        if self._eval_fn is None:
            def _eval(params, batch):
                p = self._compute_params(params)
                if self.model.apply_fn is not None:
                    return self.model.apply_fn(p, batch)
                return self._model_loss(p, batch, None)

            self._eval_fn = jax.jit(_eval)
        t0 = time.perf_counter()
        with span("eval_batch", cat="eval"):
            with self.topology.mesh:
                out = first_call_beneath(
                    self._lowered_programs, "eval", self._eval_fn,
                    self.state.params, batch)
        gp = self.telemetry.goodput if self.telemetry is not None else None
        if gp is not None:
            # eval wall time is badput in the goodput ledger (dispatch
            # time only on an async backend — honest lower bound)
            gp.observe_phase("eval", time.perf_counter() - t0)
        return out

    # ------------------------------------------------------------- data path
    def deepspeed_io(self, dataset, batch_size: Optional[int] = None,
                     collate_fn=None, num_local_io_workers=None, data_sampler=None):
        """Build the distributed dataloader (reference deepspeed_io,
        engine.py:2029)."""
        from .dataloader import DeepSpeedDataLoader

        return DeepSpeedDataLoader(
            dataset,
            batch_size=batch_size or self.config.train_micro_batch_size_per_gpu,
            topology=self.topology,
            collate_fn=collate_fn,
            seed=self.config.seed)

    def stack_microbatches(self, micro_batches):
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *micro_batches)

    # ---------------------------------------------------------- observability
    def _init_train_metrics(self) -> None:
        """Register the training metric family on the telemetry registry
        (get-or-create: many engines per process share the series)."""
        reg = self.telemetry.registry
        self._m_phase = reg.histogram(
            "deepspeed_tpu_train_phase_seconds",
            "host wall time per training phase (fwd/bwd/step/train_batch)",
            labelnames=("phase",))
        self._m_loss = reg.gauge("deepspeed_tpu_train_loss",
                                 "last reported training loss")
        self._m_lr = reg.gauge("deepspeed_tpu_train_lr",
                               "current learning rate")
        self._m_grad_norm = reg.gauge("deepspeed_tpu_train_grad_norm",
                                      "global gradient norm at the last boundary")
        self._m_loss_scale = reg.gauge("deepspeed_tpu_train_loss_scale",
                                       "fp16 dynamic loss scale (1 when off)")
        self._m_samples_ps = reg.gauge(
            "deepspeed_tpu_train_samples_per_second",
            "throughput over the last reporting window")
        self._m_tokens_ps = reg.gauge(
            "deepspeed_tpu_train_tokens_per_second",
            "token throughput over the last reporting window "
            "(0 when the batch carries no [B, T] integer ids)")
        self._m_mfu = reg.gauge(
            "deepspeed_tpu_train_mfu",
            "model FLOPs utilization vs per-generation peak "
            "(telemetry/mfu.py table)")
        self._m_overlap_frac = reg.gauge(
            "deepspeed_tpu_train_overlapped_fraction",
            "bytes-weighted share of the step's gradient exchange issued "
            "inside the backward loop (overlap-scheduled) vs the "
            "post-backward tail (telemetry/overlap.py)")
        self._m_exposed = reg.counter(
            "deepspeed_tpu_train_exposed_collective_seconds_estimated",
            "cumulative ESTIMATED seconds of exposed (non-overlapped) "
            "gradient collectives: wire bytes x bus factor over the "
            "nominal per-generation interconnect bandwidth (a model — "
            "the MEASURED counterpart is "
            "deepspeed_tpu_timeline_exposed_collective_seconds)")
        self._m_moe_picks = reg.gauge(
            "deepspeed_tpu_train_moe_held_picks_per_step",
            "picks that landed on this chip's held experts, all expert "
            "layers, per step over the last reporting window "
            "(engine.moe_stats())")
        self._m_moe_pad = reg.gauge(
            "deepspeed_tpu_train_moe_pad_share",
            "rows the grouped expert matmuls ran (blocks that hold picks) "
            "over the held picks, last reporting window; 1 = no padding")
        self._m_moe_load = reg.gauge(
            "deepspeed_tpu_train_moe_load_max_over_mean",
            "the fullest held expert's picks over the mean held expert's, "
            "averaged over expert layers, last reporting window: the "
            "imbalance the dropless path carries")
        self._m_pipe_bubble = reg.gauge(
            "deepspeed_tpu_train_pipe_bubble_fraction",
            "structural share of pipe-schedule ticks that are warm-up/"
            "drain bubbles, (P-1)/(M+P-1); 0 when no pipe schedule runs "
            "(docs/PIPELINE.md)")
        self._m_comp_residual = reg.gauge(
            "deepspeed_tpu_comm_compression_residual_bytes",
            "bytes of compressed-collective error-feedback residual "
            "state carried in TrainState.comm_errors (per-bucket; "
            "docs/COMM.md 'Compressed overlap')")
        self._m_comp_residual_norm = reg.gauge(
            "deepspeed_tpu_comm_compression_residual_norm",
            "L2 norm of the compressed-collective error-feedback "
            "residual state per comm_errors slot (in-graph, pulled at "
            "the reporting boundary; a norm growing without bound means "
            "error feedback is diverging, not compensating)",
            labelnames=("slot",))
        self._m_steps = reg.counter("deepspeed_tpu_train_steps_total",
                                    "optimizer steps taken")
        self._m_skipped = reg.counter(
            "deepspeed_tpu_train_skipped_steps_total",
            "fp16 overflow steps skipped by the loss scaler")
        self._win_time = 0.0
        self._win_steps = 0
        self._win_tokens = 0
        self._skipped_pub = 0
        self._flops_per_step: Optional[float] = None

    def _observe_phase(self, name: str, dt: float) -> None:
        self._m_phase.observe(dt, phase=name)

    def _wire_memory_ledger(self) -> None:
        """Attach the TrainState's components to the process memory
        ledger (telemetry/memory.py) so HBM is attributable by name.

        Providers read ``self.state`` dynamically: the ledger sees the
        post-donation buffers of the LATEST step, a parked engine
        (``offload_states``) reports 0 device bytes, and host-offloaded
        masters/moments report as host bytes.  Components cover the
        whole TrainState — params (the fp32 master unless the optimizer
        is host-offloaded, in which case the device copy is compute
        dtype and the master is host-side), gradients, optimizer state,
        and the scalar leaves — so the component sum equals the state's
        structural bytes exactly.

        Wiring first clears ALL training component names (a rebuilt
        engine with a different offload config must not leave a stale
        sibling's slot summing into the attribution), records what it
        attached, and ``close()`` detaches exactly those — otherwise the
        process-lifetime ledger would keep this engine's TrainState
        alive through the provider closures."""
        self._ledger_components = []
        if self.telemetry is None or self.telemetry.ledger is None:
            return
        led = self.telemetry.ledger
        for name in ("params", "master_params", "optimizer_state", "grads",
                     "train_scalars"):
            led.detach(name)

        def _attach(name, provider, **kw):
            led.attach(name, provider, **kw)
            self._ledger_components.append((name, provider))

        led.update_context(
            zero_stage=self.config.zero_config.stage,
            offload_optimizer=self.offload_optimizer is not None,
            offload_param=self.config.zero_config.offload_param.enabled,
            compute_dtype=self.compute_dtype.__name__,
            gas=self.config.gradient_accumulation_steps or 1,
            micro_batch=self.config.train_micro_batch_size_per_gpu)

        def _state_part(attr):
            return lambda: (None if self.state is None
                            else getattr(self.state, attr))

        if self.offload_optimizer is not None:
            _attach("params", _state_part("params"))
            _attach("master_params", lambda: {
                "host": self.offload_optimizer.master_bytes()})
            _attach("optimizer_state", lambda: {
                "host": self.offload_optimizer.moment_bytes()})
        else:
            # no separate live copy: state.params IS the fp32 master
            _attach("master_params", _state_part("params"))
            _attach("optimizer_state", _state_part("opt_state"))
        _attach("grads", _state_part("grad_acc"))
        _attach("train_scalars", lambda: None if self.state is None else (
            self.state.step, self.state.micro_step, self.state.loss_scale,
            self.state.skipped_steps, self.state.global_grad_norm))

    @staticmethod
    def _batch_tokens(batch) -> int:
        """Token count of one (possibly gas-stacked) batch: the size of
        the first integer leaf of rank >= 2 ([B, T] or [gas, B, T] ids);
        0 when the model is not token-based."""
        for leaf in jax.tree_util.tree_leaves(batch):
            if (getattr(leaf, "ndim", 0) >= 2
                    and jnp.issubdtype(getattr(leaf, "dtype", jnp.float32),
                                       jnp.integer)):
                return int(np.prod(leaf.shape))
        return 0

    def _model_flops_per_step(self, batch) -> float:
        """FLOPs one optimizer step spends on the MODEL, cached after the
        first call.  Preferred source: the analytic model cost
        (transformer.flops_per_token, the benchmark's count) —
        rematerialization cannot inflate it.  Fallback: XLA's cost analysis of the compiled fused
        step (hardware flops: includes remat + optimizer, so MFU reads a
        few points high there)."""
        if self._flops_per_step is not None:
            return self._flops_per_step
        mc = getattr(self.model, "config", None)
        toks = self._batch_tokens(batch)
        if mc is not None and hasattr(mc, "n_layers") and toks:
            from ..models.transformer import flops_per_token

            leaf = next(l for l in jax.tree_util.tree_leaves(batch)
                        if getattr(l, "ndim", 0) >= 2
                        and jnp.issubdtype(l.dtype, jnp.integer))
            self._flops_per_step = flops_per_token(
                mc, int(leaf.shape[-1])) * toks
        else:
            from ..profiling.flops_profiler import cost_analysis_of

            # the cost analysis lowers+compiles the step out of band —
            # announce it so the sentinel doesn't blame the next step
            expect_recompile("cost_analysis")
            with self.topology.mesh:
                costs = under_deep_frame(
                    cost_analysis_of, self._train_batch, self.state, batch,
                    jax.random.PRNGKey(0))
            self._flops_per_step = float(costs.get("flops", 0.0))
        return self._flops_per_step

    def _note_step_program(self, batch) -> None:
        """Once a build of the step, at a reporting boundary: what a
        recomputed block keeps (the model's ``remat_policy``; None without
        ``remat``) and the temporaries XLA gave the fused step, on the
        set-up ledger's ``_train_batch_body`` entry.  The step has run with
        these very arguments, so ``jit`` hands back the jaxpr and the
        executable it holds: nothing is lowered or compiled for this."""
        self._step_program_noted = True
        mc = getattr(self.model, "config", None)
        args = (self.state, batch, jax.random.PRNGKey(0))
        if getattr(self, "_moe_counters", False):
            args += (self._moe_acc,)
        with self.topology.mesh:
            mem = under_deep_frame(self._train_batch.lower,
                                   *args).compile().memory_analysis()
        note_program(
            "_train_batch_body",
            remat_policy=(getattr(mc, "remat_policy", None)
                          if getattr(mc, "remat", False) else None),
            temp_size_in_bytes=mem.temp_size_in_bytes)

    def _report_telemetry(self, loss, batch,
                          step_dt: Optional[float] = None) -> None:
        """Per-step registry updates + boundary-cadence export.

        Cheap host-side observations (phase time, watchdog) land every
        step; anything needing a device value (loss, grad norm) or an
        export write waits for the steps_per_print boundary, where
        train_batch has already drained the dispatch queue — no extra
        syncs on the hot path.  ``step_dt=None`` marks the incremental
        fwd/bwd/step path: phase times arrived via the timer sink
        already, so only the boundary publication runs."""
        tm = self.telemetry
        self._m_steps.inc()
        if step_dt is not None:
            self._m_phase.observe(step_dt, phase="train_batch")
            captured = getattr(self, "_timeline_captured", False)
            self._timeline_captured = False
            # a timeline-captured step's wall includes profiler overhead:
            # keep it out of the watchdog median and never rate it a stall
            stalled = (False if captured
                       else tm.observe_step_time(step_dt, self.global_steps))
            if tm.goodput is not None:
                # run-level goodput: classify this step's wall (compile
                # carve-out, stall badput, cross-attempt recompute →
                # restart); overflow-skip steps stay productive
                tm.goodput.observe_step(step_dt, step=self.global_steps,
                                        stalled=stalled)
            self._win_time += step_dt
            self._win_steps += 1
            self._win_tokens += self._batch_tokens(batch)
        if self.global_steps % self.config.steps_per_print != 0:
            return
        if loss is not None:
            # dstpu-lint: allow[host-sync] boundary cadence only (the
            # steps_per_print gate above); train_batch already drained the
            # dispatch queue at this boundary
            self._m_loss.set(float(loss))
        self._m_lr.set(self.get_lr()[0])
        # dstpu-lint: allow[host-sync] boundary cadence, queue drained
        self._m_grad_norm.set(float(self.state.global_grad_norm))
        self._m_loss_scale.set(self.loss_scale())
        if tm.ledger is not None:
            # structural attribution + watermarks -> gauges (host-side
            # tree walk; boundary cadence keeps it off the hot path)
            tm.ledger.publish()
        publish_setup_seconds()
        if (batch is not None and step_dt is not None
                and not self._step_program_noted):
            self._note_step_program(batch)
        # dstpu-lint: allow[host-sync] boundary cadence, queue drained
        skipped = int(self.state.skipped_steps)
        if skipped > self._skipped_pub:
            self._m_skipped.inc(skipped - self._skipped_pub)
            self._skipped_pub = skipped
        report = self.overlap_report()
        if report is not None:
            self._m_overlap_frac.set(report.overlapped_fraction)
            if self._win_steps > 0:
                self._m_exposed.inc(
                    report.exposed_seconds_per_step * self._win_steps)
        # structural (schedule-derived, no sync): pipe bubble share
        pipe_struct = getattr(self, "_pipe_struct", None)
        if pipe_struct is not None:
            self._m_pipe_bubble.set(pipe_struct["bubble_fraction"])
        # structural (shape-derived, no sync): EF residual state bytes
        self._m_comp_residual.set(sum(
            int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
            for l in jax.tree_util.tree_leaves(self.state.comm_errors)))
        # numerics observatory: pull the fused step's stats tree (one
        # boundary-cadence device_get), feed the anomaly sentinel, run
        # the cross-rank divergence audit at its cadence
        self._numerics_boundary(loss)
        self._moe_boundary()
        if self._win_time > 0:
            bs = self.config.train_batch_size or 1
            self._m_samples_ps.set(self._win_steps * bs / self._win_time)
            self._m_tokens_ps.set(self._win_tokens / self._win_time)
            from ..telemetry import mfu as _mfu

            # batch=None marks a boundary reached via the incremental
            # step() API: reuse the cached flops if a fused step already
            # derived them, but never run (and cache) the cost analysis
            # against a None batch — that would pin the MFU gauge to 0
            # for the engine's lifetime
            flops = (self._model_flops_per_step(batch)
                     if batch is not None
                     else (self._flops_per_step or 0.0))
            if flops > 0:
                self._m_mfu.set(_mfu(flops * self._win_steps, self._win_time,
                                     n_chips=self.topology.world_size))
        self._win_time, self._win_steps, self._win_tokens = 0.0, 0, 0
        cl = comm.get_comms_logger()
        if cl is not None and cl.enabled:
            cl.publish(tm.registry, axis_sizes=self.topology.axis_sizes)
        if self.monitor is not None:
            self.monitor.write_registry(tm.registry, self.global_steps)
        tm.export(self.global_steps)

    def _moe_shape(self) -> Tuple[int, int]:
        """Of the counters' sum: ``[expert layers, held + 3]``."""
        from ..models.layer_types import stack_runs

        mc = self.model.config
        layers = sum(n for _k, ffn, n in stack_runs(mc) if ffn == "experts")
        return layers, mc.moe_held_count + 3

    def _moe_zeros(self):
        """The counters' sum at zero, int32, placed as the step returns it."""
        return jax.device_put(np.zeros(self._moe_shape(), np.int32),
                              self.topology.replicated())

    def moe_stats(self) -> Optional[dict]:
        """What the expert share's layers did since the last call, pulled
        from the device (one ``device_get``) and reset: per expert layer the
        picks on each held expert (``picks``), the rows each of the three
        grouped-matmul kernels ran (``rows_run``: the blocks that hold
        picks), the rows of the worst-case buffer their grids span
        (``rows_grid``) and the layer's calls (``calls``: one a micro-batch),
        all summed over ``steps`` fused steps.  None for a model without an
        expert share.  The engine calls it at the ``steps_per_print``
        boundary into the ``deepspeed_tpu_train_moe_*`` gauges; nothing else
        syncs on the counters."""
        if not getattr(self, "_moe_counters", False):
            return None
        held = self.model.config.moe_held_count
        steps, self._moe_steps = self._moe_steps, 0
        if self._moe_acc is None:
            layers, width = self._moe_shape()
            rows = [[0] * width for _ in range(layers)]
        else:
            # dstpu-lint: allow[host-sync] the caller's cadence: the
            # steps_per_print boundary or an explicit read, never a step
            rows = jax.device_get(self._moe_acc).tolist()
            self._moe_acc = self._moe_zeros()
        return {"steps": steps,
                "picks": [r[:held] for r in rows],
                "rows_run": [r[held] for r in rows],
                "rows_grid": [r[held + 1] for r in rows],
                "calls": [r[held + 2] for r in rows]}

    def _moe_boundary(self) -> None:
        st = self.moe_stats()
        if not st or not st["steps"]:
            return
        total = sum(sum(layer) for layer in st["picks"])
        self._m_moe_picks.set(total / st["steps"])
        if total > 0:
            self._m_moe_pad.set(sum(st["rows_run"]) / total)
            self._m_moe_load.set(sum(
                max(layer) * len(layer) / max(sum(layer), 1)
                for layer in st["picks"]) / len(st["picks"]))

    def _numerics_boundary(self, loss) -> None:
        """Numerics-observatory boundary (called from _report_telemetry
        INSIDE the steps_per_print gate): pull the fused step's stats
        tree in one device_get, shape it into the sentinel's report, set
        the EF-residual-norm gauges, and run the cross-data-rank
        divergence audit at its configured cadence."""
        nm = self._numerics
        if nm is None:
            return
        report: dict = {"step": self.global_steps}
        stats = self._last_numerics
        if stats is not None:
            # dstpu-lint: allow[host-sync] boundary cadence only (the
            # steps_per_print gate in _report_telemetry); train_batch
            # already drained the dispatch queue at this boundary
            host = jax.device_get(stats)
            from ..telemetry.numerics import shape_boundary_report

            report.update(shape_boundary_report(host))
        else:
            # offload / incremental path: no fused stats tree — the
            # sentinel still watches the host-available scalars
            # dstpu-lint: allow[host-sync] boundary cadence, queue drained
            report["loss"] = None if loss is None else float(loss)
            # dstpu-lint: allow[host-sync] boundary cadence, queue drained
            report["grad_norm"] = float(self.state.global_grad_norm)
            # dstpu-lint: allow[host-sync] boundary cadence, queue drained
            report["skipped_steps"] = int(self.state.skipped_steps)
            if self.state.loss_scale is not None:
                report["loss_scale"] = self.loss_scale()
        for slot, v in (report.get("ef_residual_norm") or {}).items():
            self._m_comp_residual_norm.set(v, slot=slot)
        for bucket, v in (report.get("ef_bucket_norm") or {}).items():
            self._m_comp_residual_norm.set(v, slot=f"overlap/{bucket}")
        cfg = nm.config
        every = int(getattr(cfg, "divergence_every", 1) or 0)
        if (bool(getattr(cfg, "divergence_audit", True)) and every > 0
                and nm.boundaries % every == 0):
            div = self.divergence_audit()
            if div is not None:
                report["divergence"] = div
        nm.observe_boundary(report)

    def divergence_audit(self) -> Optional[dict]:
        """Cross-data-rank divergence audit (telemetry/numerics.py):
        bit-exact uint32 checksums over the master params, compared
        across the data axis.  At ZeRO <= 1 every data rank's copy of a
        data-replicated leaf must be BIT-IDENTICAL; a mismatch names the
        first diverging leaf — silent data corruption or a diverging
        collective, caught before it spreads through the next
        all-reduce.  Returns the verdict dict, or None when structurally
        inapplicable (single data rank, ZeRO >= 2 sharded masters, no
        eligible leaves).

        Each device computes the checksum of ITS local copy; model-axis
        shards all-reduce within their data row, so the per-device
        verdicts are per-data-rank.  Audits the process-local device
        set.  Boundary cadence: one small jitted reduction (compiled
        once — announced to the recompile sentinel) + one uint32 pull
        per (leaf, local device)."""
        from ..parallel.mesh import DATA_AXIS
        from ..telemetry.numerics import compare_rank_checksums

        if self.topology.axis_size(DATA_AXIS) < 2 \
                or self.config.zero_config.stage > 1:
            return None

        def _data_free(leaf):
            # data-SHARDED leaves legitimately differ per rank; audit
            # only leaves replicated over the data axis
            spec = getattr(getattr(leaf, "sharding", None), "spec", None)
            if spec is None:
                return False
            names = []
            for el in spec:
                if el is None:
                    continue
                names.extend(el if isinstance(el, tuple) else (el,))
            return DATA_AXIS not in names

        from ..telemetry.numerics import _path_str
        flat, _ = jax.tree_util.tree_flatten_with_path(self.state.params)
        eligible = {_path_str(p): leaf for p, leaf in flat
                    if _data_free(leaf)}
        if not eligible:
            return None
        if self._div_fn is None:
            from ..telemetry.numerics import leaf_checksums

            expect_recompile("numerics.divergence_audit")
            self._div_fn = jax.jit(leaf_checksums)
        with self.topology.mesh:
            sums = self._div_fn(eligible)
        # dstpu-lint: allow[host-sync] host mesh-topology metadata, not a
        # device value
        mesh_devs = np.asarray(self.topology.mesh.devices)
        ax = list(self.topology.mesh.axis_names).index(DATA_AXIS)
        coord = {}
        for idx in np.ndindex(mesh_devs.shape):
            coord[mesh_devs[idx].id] = int(idx[ax])
        per_rank: dict = {}
        for path, arr in sums.items():
            for sh in arr.addressable_shards:
                r = coord.get(sh.device.id)
                if r is None:
                    continue
                # dstpu-lint: allow[host-sync] boundary-cadence audit; one
                # uint32 scalar per (leaf, local device)
                per_rank.setdefault(r, {})[path] = int(np.asarray(sh.data))
        return compare_rank_checksums(per_rank)

    def numerics_report(self) -> Optional[dict]:
        """Numerics observatory summary (tools/telemetry_dump.py): the
        sentinel's rolling-window summary plus a fresh divergence-audit
        verdict.  None when the observatory is off."""
        if self._numerics is None:
            return None
        out = dict(self._numerics.summary())
        out["divergence"] = self.divergence_audit()
        return out

    def close(self) -> None:
        """Flush and release observability sinks (telemetry exporters,
        monitor writer handles).  Idempotent.

        Emits the comms-logger per-op summary first (rank 0, once): the
        trace-time bus-bandwidth totals exist only in the logger's dict
        and would otherwise be silently lost at teardown unless the
        user called ``log_summary()`` by hand."""
        cl = comm.get_comms_logger()
        if (cl is not None and cl.enabled and cl.comms_dict
                and not getattr(self, "_comms_summary_emitted", False)
                and comm.get_rank() == 0):
            cl.log_summary(axis_sizes=self.topology.axis_sizes)
            self._comms_summary_emitted = True
        if self.telemetry is not None:
            self.telemetry.export(self.global_steps, force=True)
            self.telemetry.close()
        if self.monitor is not None:
            self.monitor.close()
        if self.resilience is not None:
            # restore the previous signal handlers — a later engine (or
            # the embedding process) owns SIGTERM/SIGINT again
            self.resilience.close()
        # release our ledger slots AFTER the final export (so it still
        # shows them) — the provider closures would otherwise keep this
        # engine's TrainState reachable for the process lifetime.
        # provider identity guards: slots a newer engine claimed stay.
        if getattr(self, "_ledger_components", None):
            from ..telemetry.memory import get_memory_ledger

            led = get_memory_ledger()
            for name, prov in self._ledger_components:
                led.detach(name, provider=prov)
            self._ledger_components = []

    def _report(self, loss) -> None:
        cfg = self.config
        if self.monitor is not None and loss is not None:
            step = self.global_steps
            # dstpu-lint: allow[host-sync] monitor writers are file/HTTP IO
            # already; the loss sync is noise next to the write itself
            self.monitor.write_events([
                ("Train/Samples/train_loss", float(loss), step),
                ("Train/Samples/lr", self.get_lr()[0], step),
            ])
        if cfg.wall_clock_breakdown and self.global_steps % cfg.steps_per_print == 0:
            self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER])

    def overlap_report(self):
        """Current exposure split of the gradient exchange
        (``telemetry/overlap.py``), or None when the model has no
        stacked layer tree / no data parallelism.  Deterministic: a
        property of the compiled program structure, not runtime
        jitter."""
        from ..parallel.mesh import DATA_AXIS
        from ..telemetry.overlap import structural_report

        dev = jax.devices()[0]
        return structural_report(
            getattr(self, "_overlap_struct", None),
            world=self.topology.axis_size(DATA_AXIS),
            device_kind=str(getattr(dev, "device_kind", "cpu")),
            gas=self.config.gradient_accumulation_steps or 1)

    def get_lr(self):
        # dstpu-lint: allow[host-sync] reporting/checkpoint API, not the
        # per-step path; callers are boundary-cadence
        return [float(self.lr_schedule(int(self.state.step)))]

    def get_global_grad_norm(self) -> float:
        return float(self.state.global_grad_norm)

    def loss_scale(self) -> float:
        if self.state.loss_scale is None:
            return 1.0
        # dstpu-lint: allow[host-sync] reporting accessor, boundary cadence
        return float(self.state.loss_scale.cur_scale)

    @property
    def skipped_steps(self) -> int:
        return int(self.state.skipped_steps)

    def get_params(self, dtype=None):
        p = self.state.params
        return cast_tree(p, dtype) if dtype is not None else p

    # -------------------------------------------------------------- ckpt API
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[dict] = None,
                        partitioned: Optional[bool] = None, **kw):
        """Partitioned layout (per-process shard files, reference per-rank
        zero partition files) when multi-host or requested; simple
        consolidated layout otherwise."""
        tag = tag or f"global_step{self.global_steps}"
        if partitioned is None:
            partitioned = jax.process_count() > 1
        rcfg = self.config.resilience
        keep_n = rcfg.keep_n if rcfg.enabled else None
        if self._numerics is not None:
            # numerics observatory rides client_state: the sentinel's
            # rolling window survives resume (a loss spike right after
            # restart is judged against the pre-restart median, not an
            # empty history).  setdefault — a caller-provided slot wins.
            client_state = dict(client_state or {})
            client_state.setdefault("numerics", self._numerics.state_dict())

        def _save():
            if partitioned:
                from ..checkpoint.partitioned import save_partitioned
                from .checkpoint_engine.engines import make_checkpoint_engine

                return save_partitioned(
                    self, save_dir, tag, client_state or {},
                    checkpoint_engine=make_checkpoint_engine(self.config),
                    keep_n=keep_n)
            from ..checkpoint.saving import save_checkpoint

            return save_checkpoint(self, save_dir, tag=tag,
                                   client_state=client_state or {},
                                   keep_n=keep_n)

        t0 = time.perf_counter()
        try:
            with span("checkpoint_save", cat="ckpt", tag=tag,
                      partitioned=partitioned):
                if rcfg.enabled and rcfg.io_retries:
                    from ..resilience.commit import io_retry

                    # a failed+retried save restages from scratch (the
                    # commit protocol resets tmp.<tag>), so retry is safe
                    return io_retry(_save, retries=rcfg.io_retries,
                                    base_delay_s=rcfg.io_retry_base_s,
                                    what=f"checkpoint save '{tag}'")
                return _save()
        finally:
            gp = (self.telemetry.goodput if self.telemetry is not None
                  else None)
            if gp is not None:
                gp.observe_phase("checkpoint_save",
                                 time.perf_counter() - t0)

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None, **kw):
        """Verified load: the tag is resolved through the resilience
        commit protocol — checksums checked, corrupt newest tags
        counted + skipped in favor of the previous good one (explicit
        corrupt tags raise ``CorruptCheckpointError``); legacy
        checkpoints without a manifest load unverified."""
        import os

        from ..checkpoint.partitioned import META_FILE, load_partitioned
        from ..checkpoint.saving import load_checkpoint
        from ..resilience.commit import resolve_tag

        resolved, _report = resolve_tag(load_dir, tag)
        if resolved is None:
            # resolution already walked (and incident-logged) every
            # candidate; re-entering the loaders would re-resolve and
            # double-count the corruption metric
            logger.warning(f"no loadable checkpoint in {load_dir}; "
                           "nothing loaded")
            return None, {}
        inc = (_report.get("meta") or {}).get("numerics_incident") \
            if isinstance(_report, dict) else None
        if inc:
            # resume-time triage: this tag was the first save after the
            # anomaly sentinel fired — say WHAT fired and WHERE before
            # the operator burns a day rediscovering it
            first = (inc.get("anomalies") or [{}])[0]
            layer = first.get("first_nonfinite_layer")
            leaf = (first.get("first_nonfinite_leaf")
                    or first.get("first_diverging_leaf"))
            logger.warning(
                f"resuming from '{resolved}' which carries a numerics "
                f"incident: kinds={inc.get('kinds')} "
                f"step={inc.get('step')} first_nonfinite_layer={layer} "
                f"leaf={leaf}")
        t0 = time.perf_counter()
        try:
            with span("checkpoint_load", cat="ckpt", tag=resolved):
                if os.path.exists(os.path.join(load_dir, resolved, META_FILE)):
                    ret = load_partitioned(self, load_dir, tag=resolved)
                else:
                    ret = load_checkpoint(self, load_dir, tag=resolved)
                if self._numerics is not None and isinstance(ret, tuple) \
                        and len(ret) > 1:
                    # restore the sentinel's rolling window (see
                    # save_checkpoint); absent slot -> no-op reset-free
                    self._numerics.load_state_dict(
                        (ret[1] or {}).get("numerics"))
                return ret
        finally:
            gp = (self.telemetry.goodput if self.telemetry is not None
                  else None)
            if gp is not None:
                # auto-resume wraps this in override("restart"): a
                # preemption-recovery load is restart badput, not
                # routine checkpoint I/O
                gp.observe_phase("checkpoint_load",
                                 time.perf_counter() - t0)

    # batch-size accessors (reference engine API)
    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self.config.gradient_accumulation_steps

    def train_batch_size(self) -> int:
        return self.config.train_batch_size

    def set_train_batch_size(self, train_batch_size: int) -> None:
        """Adjust the global batch by changing ONLY gradient accumulation
        (reference ``engine.set_train_batch_size``, engine.py — micro batch
        and DP width stay fixed).  The next ``train_batch`` call retraces
        with the new gas (its leading batch dim changes)."""
        denom = (self.config.train_micro_batch_size_per_gpu
                 * self.topology.dp_world_size)
        if train_batch_size % denom != 0:
            raise ValueError(
                f"train_batch_size {train_batch_size} not divisible by "
                f"micro_batch*dp = {denom}")
        self.config.gradient_accumulation_steps = train_batch_size // denom
        self.config.train_batch_size = train_batch_size
        # gas is a trace-time constant (apply's grad denominator): rebuild
        # the jit wrappers so cached programs with the old gas can't serve
        # the DS-compat cadence (state avals alone wouldn't force a retrace)
        self._compile_steps()

    def set_train_micro_batch_size(self, micro_batch_size: int) -> None:
        """Change the micro-batch size, keeping gas (reference
        ``engine.set_train_micro_batch_size``); train_batch follows."""
        self.config.train_micro_batch_size_per_gpu = int(micro_batch_size)
        self.config.train_batch_size = (
            micro_batch_size * (self.config.gradient_accumulation_steps or 1)
            * self.topology.dp_world_size)
        self._compile_steps()

    def no_sync(self):
        """Reference ``engine.no_sync`` context (engine.py): inside it,
        micro-steps must not pay a cross-data-replica gradient reduction;
        invalid under ZeRO >= 2 (sharded grads REQUIRE the reduce-scatter
        — same assert as the reference).

        Under SPMD the gradient psum is placed by the XLA partitioner
        inside the compiled micro/fused program, and the fused
        ``train_batch`` path already amortizes scheduling across the gas
        scan — so there is no per-micro-step Python-issued allreduce to
        suppress; the context's value here is the stage guard and API
        compatibility for ported scripts."""
        import contextlib

        if self.config.zero_config.stage >= 2:
            raise AssertionError(
                "no_sync is not compatible with ZeRO stage >= 2: gradients "
                "are partitioned and every micro-step's reduce-scatter is "
                "load-bearing (reference engine.no_sync assert)")

        @contextlib.contextmanager
        def ctx():
            yield

        return ctx()

    def zero_optimization_stage(self) -> int:
        return self.config.zero_config.stage
