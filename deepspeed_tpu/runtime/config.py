"""DeepSpeed-compatible JSON configuration.

Accepts the same ``ds_config.json`` surface as the reference
(``deepspeed/runtime/config.py``): the batch-size triangle
(train_batch_size = micro_batch * grad_accum * dp_world_size), optimizer /
scheduler blocks, fp16/bf16 blocks, zero_optimization, and the feature
sub-configs.  TPU-specific additions live under the ``"mesh"`` key
(axis sizes for data/model/pipe/sequence/expert parallelism).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional

from .config_utils import AUTO, ConfigModel
from ..serving.config import ServingConfig
from ..utils.logging import logger

TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"


@dataclasses.dataclass
class FP16Config(ConfigModel):
    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = 0.0  # 0 => dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0


@dataclasses.dataclass
class BF16Config(ConfigModel):
    enabled: bool = False
    # Keep a master fp32 copy of params for the optimizer (reference
    # BF16_Optimizer semantics, runtime/bf16_optimizer.py:35).
    master_weights: bool = True


@dataclasses.dataclass
class OffloadConfig(ConfigModel):
    """Param/optimizer offload target (reference zero/offload_config.py)."""

    device: str = "none"  # none | cpu | nvme
    nvme_path: str = "/tmp/dstpu_nvme"
    pin_memory: bool = True
    buffer_count: int = 5
    buffer_size: int = 100_000_000
    ratio: float = 1.0
    max_in_cpu: int = 1_000_000_000
    # SuperOffload (reference runtime/superoffload/): fan the host Adam out
    # over a pool of CPU optimizer workers
    super_offload: bool = False
    cpu_worker_count: int = 4

    @property
    def enabled(self) -> bool:
        return self.device not in ("none", None)

    def validate(self) -> None:
        if self.super_offload and not self.enabled:
            raise ValueError("super_offload requires offload_optimizer.device="
                             "'cpu' (or 'nvme'); got device='none'")


@dataclasses.dataclass
class ZenFlowConfig(ConfigModel):
    """zenflow block inside zero_optimization (reference
    runtime/zenflow/zenflow_config.py:12)."""

    enabled: bool = False
    topk_ratio: float = 0.1  # fraction of columns on the immediate fast path
    update_interval: int = 4  # deferred CPU pass cadence (boundaries)
    full_warm_up_rounds: int = 0  # full synchronous updates first
    overlap_step: bool = True  # run the deferred pass in a background thread

    def validate(self) -> None:
        if not (0.0 < self.topk_ratio <= 1.0):
            raise ValueError(f"topk_ratio must be in (0, 1], got {self.topk_ratio}")
        if self.update_interval < 1:
            raise ValueError("update_interval must be >= 1")


@dataclasses.dataclass
class ZeroConfig(ConfigModel):
    """zero_optimization block (reference zero/config.py).

    Accepted-but-delegated knobs: ``reduce_bucket_size`` /
    ``allgather_bucket_size`` / ``overlap_comm`` / ``contiguous_gradients``
    / ``round_robin_gradients`` / ``stage3_prefetch_bucket_size`` /
    ``stage3_max_live_parameters`` / ``stage3_max_reuse_distance`` /
    ``sub_group_size`` exist in the reference because its hook-driven
    runtime hand-schedules buckets, overlap, and prefetch.  Here the
    collectives are compiled into the step program and the XLA
    latency-hiding scheduler owns those decisions — the keys are accepted
    for config compatibility and carry no behavior.  Knobs that DO reach
    mechanisms: ``stage``, ``offload_param`` / ``offload_optimizer``,
    ``stage3_param_persistence_threshold``, ``zero_quantized_weights`` /
    ``zero_quantized_gradients`` / ``zero_hpz_partition_size``,
    ``mics_shard_size``, ``zenflow``."""

    stage: int = 0
    overlap_comm: bool = True
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = 500_000_000
    allgather_bucket_size: int = 500_000_000
    offload_param: OffloadConfig = dataclasses.field(default_factory=OffloadConfig)
    offload_optimizer: OffloadConfig = dataclasses.field(default_factory=OffloadConfig)
    sub_group_size: int = 1_000_000_000
    stage3_max_live_parameters: int = 1_000_000_000
    stage3_max_reuse_distance: int = 1_000_000_000
    stage3_prefetch_bucket_size: int = 50_000_000
    # Params at or below this many elements keep an unpartitioned live copy
    # at stage 3 (reference persistence_threshold, default 1e5 there because
    # every fetch pays fixed Python-hook + NCCL-launch overhead).  Default 0
    # here: XLA compiles per-layer gathers into the step with no per-op
    # launch cost, so persistence is purely an opt-in memory/latency trade.
    stage3_param_persistence_threshold: int = 0
    stage3_gather_16bit_weights_on_model_save: bool = False
    #: MANUAL stage-3 prefetch: run the layer scan 2x-unrolled
    #: (models/transformer.py) so consecutive layers' param gathers and
    #: compute can overlap, instead of leaving scheduling slack entirely
    #: to XLA.  With the overlap wrap active (it is, whenever this knob
    #: or overlap_grad_reduce is on and the model supports it), the
    #: gathers are EXPLICIT in-loop collectives issued at the body top
    #: (runtime/zero/overlap.py) — the unrolled pair of gather->compute
    #: chains is the double buffer.  Off by default (no benchmark cell
    #: sets it); the reference's analogue is the
    #: PartitionedParameterCoordinator prefetch.
    zero3_param_prefetch: bool = False
    #: issue each layer-bucket's gradient reduce inside the BACKWARD
    #: scan, as soon as the bucket's cotangents materialize
    #: (runtime/zero/overlap.py custom_vjp hook; Domino-style — the
    #: collective rides the dataflow graph, no post-backward block).
    #: Scheduling only: bit-exact with the unbucketed path
    #: (tests/unit/test_overlap.py::test_overlap_bit_exact_and_parity_zero1
    #: and ``_zero3_and_prefetch``).  Needs a models/* transformer.  With
    #: qgZ (or ``overlap_compression``) also set, the in-loop exchange
    #: itself compresses — docs/COMM.md "Compressed overlap"; with
    #: ``overlap_compression: false`` the wrap stands down under qgZ /
    #: hierarchical and those bucketed explicit reducers own the
    #: exchange (see overlap_bucket_mb).
    overlap_grad_reduce: bool = False
    #: compress the IN-LOOP bucketed gradient exchange (docs/COMM.md
    #: "Compressed overlap"): None (default) derives it — int8 +
    #: error feedback when ``zero_quantized_gradients`` is also on,
    #: exact fp otherwise; "int8"/"fp8" or a CompressionSpec kwargs
    #: dict forces a codec (error_feedback defaults ON for this path —
    #: pass {"format": ..., "error_feedback": false} to drop the
    #: residual); False forces the exact fp exchange even under qgZ
    #: (the wrap then stands down and qgZ keeps its post-backward
    #: bucketed reduce).  Residuals live in TrainState.comm_errors —
    #: ONE per bucket — and survive checkpoint/preemption-resume.
    overlap_compression: Any = None
    #: size target (MB) for the ONE shared bucketer
    #: (comm/collectives/bucketer.py): the overlap hook's per-layer
    #: reduce groups AND the leaf coalescing inside the explicit
    #: compressed reducers (qgZ / hierarchical — one collective and one
    #: error-feedback residual per bucket).  0 = per-leaf (no
    #: coalescing, the pre-bucketing behavior).
    overlap_bucket_mb: float = 4.0
    # ZeRO++ style knobs: quantized weight gather / hierarchical partition
    zero_quantized_weights: bool = False
    zero_quantized_gradients: bool = False
    zero_hpz_partition_size: int = 1
    #: hierarchical two-hop gradient reduce (comm/collectives/hierarchical):
    #: intra-slice reduce-scatter -> inter-slice exchange -> intra-slice
    #: all-gather over a split of the data axis.  With
    #: zero_quantized_gradients also on, the inter-slice hop moves int8
    #: codes + block scales (the ZeRO++ 4x cross-slice reduction shape).
    zero_hierarchical_grad_reduce: bool = False
    #: intra-slice group size for that split (0 = auto:
    #: utils/groups.hierarchy_split — local device count, else ~sqrt)
    zero_hierarchy_inner: int = 0
    #: error feedback on the POST-BACKWARD qgZ / hierarchical gradient
    #: reduce (the path that runs when the in-loop overlap wrap is off
    #: or unsupported): per-bucket residuals carried in
    #: TrainState.comm_errors["reduce"], so checkpoint/resume keeps
    #: them (docs/COMM.md).  Off by default — it changes the reduce's
    #: numerics vs HEAD (convergence improves, bit-compat breaks).
    grad_reduce_error_feedback: bool = False
    # MiCS-style replica-group sharding: shard within groups of this size,
    # replicate across groups (reference zero/mics.py).
    mics_shard_size: int = -1
    mics_hierarchical_params_gather: bool = False
    round_robin_gradients: bool = False
    ignore_unused_parameters: bool = True
    # ZenFlow stall-free offload (reference runtime/zenflow/zenflow_config.py)
    zenflow: ZenFlowConfig = dataclasses.field(default_factory=ZenFlowConfig)

    def validate(self) -> None:
        if self.stage not in (0, 1, 2, 3):
            raise ValueError(f"zero_optimization.stage must be 0-3, got {self.stage}")
        if self.overlap_bucket_mb < 0:
            raise ValueError("zero_optimization.overlap_bucket_mb must be "
                             f">= 0, got {self.overlap_bucket_mb}")
        if self.overlap_compression not in (None, False):
            from ..comm.collectives.codec import CompressionSpec

            try:
                CompressionSpec.parse(self.overlap_compression)
            except (TypeError, ValueError) as e:
                raise ValueError(
                    f"zero_optimization.overlap_compression: {e}") from e

    @classmethod
    def deprecated_fields(cls):
        return {"cpu_offload": "offload_optimizer"}


@dataclasses.dataclass
class OptimizerConfig(ConfigModel):
    type: str = "adamw"
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class SchedulerConfig(ConfigModel):
    type: Optional[str] = None
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class MeshConfig(ConfigModel):
    """TPU mesh axis sizes. -1 on ``data`` means 'all remaining devices'."""

    pipe: int = 1
    # MiCS replica groups (zero_optimization.mics_shard_size sets data and
    # lets repl absorb the rest): ZeRO shards within 'data', replicates
    # across 'repl'
    repl: int = 1
    data: int = -1
    expert: int = 1
    sequence: int = 1
    model: int = 1
    # How ICI/DCN axes are stacked for multi-slice: 'ici_major' keeps model/
    # sequence axes on the fastest links.
    axis_order: str = "pipe,repl,data,expert,sequence,model"


@dataclasses.dataclass
class PipelineConfig(ConfigModel):
    """``pipeline`` block: knobs for the scan-based pipe schedule
    (runtime/pipe/, docs/PIPELINE.md).

    ``hop_compression`` puts the per-tick activation ``ppermute`` (and
    its backward-wave transpose) on a quantized wire — "int8"/"fp8", a
    dict ({"format", "block", "error_feedback", "compress_backward"}),
    or None/False for the exact fp hop.  Error feedback on the backward
    hop defaults ON (residuals live in ``TrainState.comm_errors["pipe"]``
    and follow the checkpoint/donation lifecycle contract); pass
    ``{"error_feedback": false}`` explicitly to run straight-through.
    """

    hop_compression: Any = None

    def validate(self) -> None:
        if self.hop_compression not in (None, False):
            from ..comm.collectives.codec import CompressionSpec

            try:
                CompressionSpec.parse(self.hop_compression)
            except (TypeError, ValueError) as e:
                raise ValueError(f"pipeline.hop_compression: {e}") from e


@dataclasses.dataclass
class ActivationCheckpointingConfig(ConfigModel):
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native: jax.remat policy name (see runtime/activation_checkpointing)
    policy: str = "nothing_saveable"


@dataclasses.dataclass
class FlopsProfilerConfig(ConfigModel):
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


@dataclasses.dataclass
class StallWatchdogConfig(ConfigModel):
    """stall_watchdog sub-block of ``telemetry``: flag steps exceeding
    ``multiple`` x the rolling median over the last ``window`` steps."""

    enabled: bool = True
    multiple: float = 3.0
    window: int = 32

    def validate(self) -> None:
        if self.multiple <= 1.0:
            raise ValueError(f"stall_watchdog.multiple must be > 1, "
                             f"got {self.multiple}")
        if self.window < 2:
            raise ValueError("stall_watchdog.window must be >= 2")


@dataclasses.dataclass
class SpanTraceConfig(ConfigModel):
    """``spans`` sub-block of ``telemetry``: the host-side span ring
    (telemetry/spans.py) feeding Chrome-trace dumps and the flight
    recorder.  ``profiler_annotations`` nests each span in a
    ``jax.profiler.TraceAnnotation`` so XProf captures carry the same
    names."""

    enabled: bool = True
    ring_size: int = 4096
    profiler_annotations: bool = True

    def validate(self) -> None:
        if self.ring_size < 16:
            raise ValueError("telemetry.spans.ring_size must be >= 16")


@dataclasses.dataclass
class FlightRecorderConfig(ConfigModel):
    """``flight_recorder`` sub-block of ``telemetry``: dump the span
    ring + recent log events + a registry snapshot to a timestamped
    JSONL on exception-in-step, watchdog trip, or demand (``path`` is
    the dump DIRECTORY, default ./flight_recorder)."""

    enabled: bool = True
    path: str = ""
    events: int = 256

    def validate(self) -> None:
        if self.events < 16:
            raise ValueError("telemetry.flight_recorder.events must be >= 16")


@dataclasses.dataclass
class RecompileSentinelConfig(ConfigModel):
    """``recompile_sentinel`` sub-block of ``telemetry``: count XLA
    compiles per step (telemetry/compile_sentinel.py) and warn when a
    step recompiles after ``steady_after`` steady steps with unchanged
    arg shapes."""

    enabled: bool = True
    steady_after: int = 3

    def validate(self) -> None:
        if self.steady_after < 0:
            raise ValueError(
                "telemetry.recompile_sentinel.steady_after must be >= 0")


@dataclasses.dataclass
class MemoryLedgerConfig(ConfigModel):
    """``memory`` sub-block of ``telemetry``: the HBM memory ledger
    (telemetry/memory.py).  When enabled the engines attribute device
    bytes to named components (params / master params / grads /
    optimizer state / KV pool), track per-phase peak watermarks off the
    span enters/exits, and upgrade RESOURCE_EXHAUSTED step failures to
    OOM incident reports through the flight recorder.
    ``top_buffers`` bounds the live-buffer table in an incident."""

    enabled: bool = True
    top_buffers: int = 10

    def validate(self) -> None:
        if self.top_buffers < 1:
            raise ValueError("telemetry.memory.top_buffers must be >= 1")


@dataclasses.dataclass
class TimelineConfig(ConfigModel):
    """``timeline`` sub-block of ``telemetry``: measured step-time
    attribution (telemetry/timeline.py).  Every ``every_n_steps`` the
    engine captures a ``jax.profiler`` trace of ONE step and publishes
    the ``deepspeed_tpu_timeline_*`` decomposition (0 = no periodic
    captures; one-shot captures via ``engine.capture_timeline()`` still
    work).  ``artifact_dir`` receives one merged
    host-span + device-op Chrome-trace file per capture ("" = no
    artifact files)."""

    enabled: bool = True
    every_n_steps: int = 0
    artifact_dir: str = ""

    def validate(self) -> None:
        if self.every_n_steps < 0:
            raise ValueError(
                "telemetry.timeline.every_n_steps must be >= 0")


@dataclasses.dataclass
class GoodputConfig(ConfigModel):
    """``goodput`` sub-block of ``telemetry``: the run-level goodput /
    badput ledger (telemetry/goodput.py).  ``run_file`` is the
    cross-attempt union ledger for preempted runs; when left "" on a
    resilient engine it defaults into the resilience ``save_dir`` so a
    relaunched attempt attributes recomputed steps to restart badput."""

    enabled: bool = True
    run_file: str = ""


@dataclasses.dataclass
class NumericsConfig(ConfigModel):
    """``numerics`` sub-block of ``telemetry``: the numerics observatory
    (telemetry/numerics.py; docs/OBSERVABILITY.md "Numerics
    observatory").  When enabled the fused train step carries per-layer
    / per-leaf health stats (grad/param norm, max-abs, nonfinite count,
    EF-residual norm per comm slot, loss-scale state) as EXTRA DEVICE
    OUTPUTS, pulled only at the ``steps_per_print`` boundary where the
    anomaly sentinel runs its detectors.  ``activation_stats``
    additionally threads a ``[L, 3]`` activation-health side output
    through the transformer layer scan (per-stage through the pipe
    scan).  The divergence audit checksums master params across the
    data axis every ``divergence_every``-th boundary (ZeRO stage <= 1
    only — ranks must be bit-identical there; higher stages skip it).

    Detector knobs: spikes fire when the boundary value exceeds
    ``*_factor`` x the rolling median of the last ``history`` healthy
    boundaries (armed after ``min_history``); ``overflow_storm`` is the
    skipped-step delta between boundaries that rates as a storm;
    ``stagnant_boundaries``/``stagnant_tol`` flag a loss pinned within
    tolerance for that many consecutive boundaries (0 disables)."""

    enabled: bool = False
    activation_stats: bool = True
    history: int = 64
    min_history: int = 8
    loss_spike_factor: float = 3.0
    grad_spike_factor: float = 10.0
    overflow_storm: int = 3
    stagnant_boundaries: int = 8
    stagnant_tol: float = 0.0
    divergence_audit: bool = True
    divergence_every: int = 1

    def validate(self) -> None:
        if self.history < 2:
            raise ValueError("telemetry.numerics.history must be >= 2")
        if self.min_history < 2:
            raise ValueError("telemetry.numerics.min_history must be >= 2")
        if self.loss_spike_factor <= 1.0 or self.grad_spike_factor <= 1.0:
            raise ValueError(
                "telemetry.numerics spike factors must be > 1")
        if self.overflow_storm < 1:
            raise ValueError("telemetry.numerics.overflow_storm must be >= 1")
        if self.stagnant_boundaries < 0 or self.stagnant_tol < 0:
            raise ValueError(
                "telemetry.numerics stagnant knobs must be >= 0")
        if self.divergence_every < 1:
            raise ValueError(
                "telemetry.numerics.divergence_every must be >= 1")


@dataclasses.dataclass
class TelemetryConfig(ConfigModel):
    """``telemetry`` block: the unified metrics registry + export paths
    (see deepspeed_tpu/telemetry/ and docs/OBSERVABILITY.md).

    ``enabled`` turns on registry collection in the engines; each export
    sink is then individually opt-in: ``prometheus_path`` rewrites a
    Prometheus textfile every ``export_interval`` steps,
    ``prometheus_port`` serves /metrics over HTTP (0 = off),
    ``jsonl_path`` appends snapshot events to a JSON-lines log.
    ``trace_annotations`` wraps steps in ``jax.profiler`` step/phase
    annotations (no-op without a live profiler capture).  ``spans``,
    ``flight_recorder``, ``recompile_sentinel`` and ``memory`` configure
    the timeline/memory side (all default-on once ``enabled`` is set;
    see docs/OBSERVABILITY.md "Tracing & flight recorder" and "Memory
    ledger & OOM forensics")."""

    enabled: bool = False
    prometheus_path: str = ""
    prometheus_port: int = 0
    jsonl_path: str = ""
    export_interval: int = 10
    trace_annotations: bool = True
    stall_watchdog: StallWatchdogConfig = dataclasses.field(
        default_factory=StallWatchdogConfig)
    spans: SpanTraceConfig = dataclasses.field(
        default_factory=SpanTraceConfig)
    flight_recorder: FlightRecorderConfig = dataclasses.field(
        default_factory=FlightRecorderConfig)
    recompile_sentinel: RecompileSentinelConfig = dataclasses.field(
        default_factory=RecompileSentinelConfig)
    memory: MemoryLedgerConfig = dataclasses.field(
        default_factory=MemoryLedgerConfig)
    timeline: TimelineConfig = dataclasses.field(
        default_factory=TimelineConfig)
    goodput: GoodputConfig = dataclasses.field(
        default_factory=GoodputConfig)
    numerics: NumericsConfig = dataclasses.field(
        default_factory=NumericsConfig)

    def validate(self) -> None:
        if self.export_interval < 1:
            raise ValueError("telemetry.export_interval must be >= 1")
        if not (0 <= self.prometheus_port < 65536):
            raise ValueError(f"telemetry.prometheus_port out of range: "
                             f"{self.prometheus_port}")


@dataclasses.dataclass
class CommsLoggerConfig(ConfigModel):
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class MonitorConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJob"


@dataclasses.dataclass
class TensorBoardConfig(MonitorConfig):
    pass


@dataclasses.dataclass
class WandbConfig(ConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed_tpu"


@dataclasses.dataclass
class CometConfig(ConfigModel):
    """Reference monitor/config.py CometConfig (comet_ml writer)."""

    enabled: bool = False
    samples_log_interval: int = 100
    project: Optional[str] = None
    workspace: Optional[str] = None
    api_key: Optional[str] = None
    experiment_name: Optional[str] = None
    experiment_key: Optional[str] = None
    online: Optional[bool] = None
    mode: Optional[str] = None


@dataclasses.dataclass
class CSVConfig(MonitorConfig):
    pass


@dataclasses.dataclass
class AIOConfig(ConfigModel):
    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True
    use_gds: bool = False


@dataclasses.dataclass
class CheckpointConfig(ConfigModel):
    tag_validation: str = "Warn"
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write_pipeline: bool = False
    async_save: bool = False
    writer: str = ""  # "" | nebula | datastates (async engine flavors)


@dataclasses.dataclass
class ResilienceConfig(ConfigModel):
    """``resilience`` block: preemption-aware emergency checkpoints,
    verified atomic commits, auto-resume and checkpoint-I/O retries
    (see ``deepspeed_tpu/resilience/`` and ``docs/RESILIENCE.md``).

    ``save_dir`` is both where emergency checkpoints go and where
    ``auto_resume`` looks for the latest *verified* checkpoint on
    engine startup.  ``keep_n`` bounds the committed tags kept on disk
    (partial ``tmp.*`` staging dirs are always garbage-collected).
    ``watch_signals`` installs SIGTERM/SIGINT handlers for the
    preemption watcher (off for embedded/test use — ``notify()`` still
    works)."""

    enabled: bool = False
    save_dir: str = ""
    auto_resume: bool = True
    emergency_save: bool = True
    keep_n: int = 3
    io_retries: int = 3
    io_retry_base_s: float = 0.1
    watch_signals: bool = True

    def validate(self) -> None:
        if self.keep_n < 1:
            raise ValueError(f"resilience.keep_n must be >= 1, got {self.keep_n}")
        if self.io_retries < 0:
            raise ValueError("resilience.io_retries must be >= 0")
        if self.enabled and (self.auto_resume or self.emergency_save) \
                and not self.save_dir:
            raise ValueError(
                "resilience.enabled with auto_resume/emergency_save needs "
                "resilience.save_dir (where checkpoints live)")


@dataclasses.dataclass
class HybridEngineConfig(ConfigModel):
    """hybrid_engine block (reference runtime/hybrid_engine.py config):
    RLHF-style flip-flopping between training and generation on one copy
    of the weights."""

    enabled: bool = False
    max_out_tokens: int = 512
    inference_tp_size: int = 1
    release_inference_cache: bool = False
    pin_parameters: bool = True
    tp_gather_partition_size: int = 8


@dataclasses.dataclass
class GradientCompressionConfig(ConfigModel):
    """1-bit / compressed-communication style gradient compression."""

    enabled: bool = False
    bits: int = 8  # int8 compressed allreduce over ICI
    error_feedback: bool = True


@dataclasses.dataclass
class DeepSpeedConfig:
    """Parsed top-level config.

    Mirrors reference ``DeepSpeedConfig`` (runtime/config.py): constructed
    from a dict or a json path, resolves the batch-size triangle against the
    data-parallel world size.
    """

    raw: Dict[str, Any]
    train_batch_size: Optional[int]
    train_micro_batch_size_per_gpu: Optional[int]
    gradient_accumulation_steps: Optional[int]
    steps_per_print: int
    gradient_clipping: float
    prescale_gradients: bool
    gradient_predivide_factor: float
    communication_data_type: Optional[str]
    seed: int
    wall_clock_breakdown: bool
    memory_breakdown: bool
    sanity_checks: bool
    dump_state: bool
    fp16: FP16Config
    bf16: BF16Config
    zero_config: ZeroConfig
    optimizer: OptimizerConfig
    scheduler: SchedulerConfig
    mesh: MeshConfig
    pipeline: PipelineConfig
    activation_checkpointing: ActivationCheckpointingConfig
    flops_profiler: FlopsProfilerConfig
    comms_logger: CommsLoggerConfig
    telemetry: TelemetryConfig
    tensorboard: TensorBoardConfig
    wandb: WandbConfig
    comet: CometConfig
    csv_monitor: CSVConfig
    aio: AIOConfig
    checkpoint: CheckpointConfig
    compression: GradientCompressionConfig
    hybrid_engine: HybridEngineConfig
    resilience: ResilienceConfig
    serving: ServingConfig
    zero_allow_untested_optimizer: bool
    gradient_accumulation_dtype: str

    def __init__(self, config: Any, dp_world_size: Optional[int] = None):
        if isinstance(config, str):
            with open(config) as f:
                config = json.load(f)
        if config is None:
            config = {}
        if not isinstance(config, dict):
            raise TypeError(f"config must be a dict or json path, got {type(config)}")
        self.raw = config

        g = config.get
        self.train_batch_size = _maybe_int(g(TRAIN_BATCH_SIZE))
        self.train_micro_batch_size_per_gpu = _maybe_int(g(TRAIN_MICRO_BATCH_SIZE_PER_GPU))
        self.gradient_accumulation_steps = _maybe_int(g(GRADIENT_ACCUMULATION_STEPS))
        self.steps_per_print = max(1, int(g("steps_per_print", 10) or 1))
        self.gradient_clipping = float(g("gradient_clipping", 0.0))
        self.prescale_gradients = bool(g("prescale_gradients", False))
        self.gradient_predivide_factor = float(g("gradient_predivide_factor", 1.0))
        self.communication_data_type = g("communication_data_type")
        self.seed = int(g("seed", 1234))
        self.wall_clock_breakdown = bool(g("wall_clock_breakdown", False))
        self.memory_breakdown = bool(g("memory_breakdown", False))
        # reference is_sanity_checks_enabled (engine.py:1119): opt-in NaN
        # guard — costs a host sync per step, so off by default
        self.sanity_checks = bool(g("sanity_checks", False))
        self.dump_state = bool(g("dump_state", False))
        self.zero_allow_untested_optimizer = bool(g("zero_allow_untested_optimizer", False))
        self.gradient_accumulation_dtype = g("data_types", {}).get(
            "grad_accum_dtype", "fp32") or "fp32"

        self.fp16 = FP16Config.from_dict(g("fp16"))
        self.bf16 = BF16Config.from_dict(g("bf16") or g("bfloat16"))
        self.zero_config = ZeroConfig.from_dict(g("zero_optimization"))
        self.optimizer = OptimizerConfig.from_dict(g("optimizer"))
        self.scheduler = SchedulerConfig.from_dict(g("scheduler"))
        self.mesh = MeshConfig.from_dict(g("mesh"))
        self.pipeline = PipelineConfig.from_dict(g("pipeline"))
        self.activation_checkpointing = ActivationCheckpointingConfig.from_dict(
            g("activation_checkpointing"))
        self.flops_profiler = FlopsProfilerConfig.from_dict(g("flops_profiler"))
        self.comms_logger = CommsLoggerConfig.from_dict(g("comms_logger"))
        self.telemetry = TelemetryConfig.from_dict(g("telemetry"))
        self.tensorboard = TensorBoardConfig.from_dict(g("tensorboard"))
        self.wandb = WandbConfig.from_dict(g("wandb"))
        self.comet = CometConfig.from_dict(g("comet"))
        self.csv_monitor = CSVConfig.from_dict(g("csv_monitor"))
        self.aio = AIOConfig.from_dict(g("aio"))
        self.checkpoint = CheckpointConfig.from_dict(g("checkpoint"))
        self.compression = GradientCompressionConfig.from_dict(g("gradient_compression"))
        self.hybrid_engine = HybridEngineConfig.from_dict(g("hybrid_engine"))
        self.resilience = ResilienceConfig.from_dict(g("resilience"))
        # fleet front tier (serving/config.py): router + replica pools;
        # parsed here so one ds-config json describes the whole process.
        # Nested blocks (serving.speculative, serving.kv_tier — the
        # tiered KV cache) coerce + validate inside ServingConfig.
        self.serving = ServingConfig.from_dict(g("serving"))

        if self.fp16.enabled and self.bf16.enabled:
            raise ValueError("fp16 and bf16 cannot both be enabled")

        if dp_world_size is not None:
            self.resolve_batch_size(dp_world_size)

    # -- batch-size triangle ------------------------------------------------
    def resolve_batch_size(self, dp_world_size: int) -> None:
        """Resolve train_batch = micro_batch * grad_accum * dp_world_size.

        Same rules as reference ``DeepSpeedConfig._configure_train_batch_size``:
        any two determine the third; one alone assumes the others are 1/derived;
        none => micro=1, gas=1.
        """
        tb, mb, gas = (self.train_batch_size, self.train_micro_batch_size_per_gpu,
                       self.gradient_accumulation_steps)
        if all(v is not None for v in (tb, mb, gas)):
            if tb != mb * gas * dp_world_size:
                raise ValueError(
                    f"Batch-size inconsistency: train_batch_size={tb} != "
                    f"micro({mb}) * gas({gas}) * dp({dp_world_size})")
        elif tb is not None and mb is not None:
            gas = tb // (mb * dp_world_size)
            if gas * mb * dp_world_size != tb:
                raise ValueError(
                    f"train_batch_size {tb} not divisible by micro*dp = {mb * dp_world_size}")
        elif tb is not None and gas is not None:
            mb = tb // (gas * dp_world_size)
            if mb * gas * dp_world_size != tb:
                raise ValueError(
                    f"train_batch_size {tb} not divisible by gas*dp = {gas * dp_world_size}")
        elif mb is not None:
            gas = gas or 1
            tb = mb * gas * dp_world_size
        elif tb is not None:
            mb = tb // dp_world_size
            gas = 1
            if mb * dp_world_size != tb:
                raise ValueError(f"train_batch_size {tb} not divisible by dp {dp_world_size}")
        else:
            mb, gas = 1, 1
            tb = mb * gas * dp_world_size
        self.train_batch_size, self.train_micro_batch_size_per_gpu = tb, mb
        self.gradient_accumulation_steps = gas

    # ----------------------------------------------------------------------
    @property
    def zero_enabled(self) -> bool:
        return self.zero_config.stage > 0

    @property
    def compute_dtype(self):
        import jax.numpy as jnp

        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    def print_config(self) -> None:
        logger.info(f"DeepSpeedTPU config: {json.dumps(self.raw, indent=2, default=str)}")


def _maybe_int(v: Any) -> Optional[int]:
    if v is None or v == AUTO:
        return None
    return int(v)
