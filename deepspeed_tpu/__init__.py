"""DeepSpeed-TPU: a TPU-native training & inference framework.

A ground-up JAX/XLA/Pallas re-design of the capability surface of DeepSpeed
(reference: xylian86/DeepSpeed).  The public entry points mirror the
reference API (``deepspeed/__init__.py``): ``initialize`` (:78),
``init_distributed``, ``init_inference`` (:302), ``add_config_arguments``
(:279) — but the execution model is SPMD over a ``jax.sharding.Mesh``:
ZeRO stages are sharding rules, collectives are XLA ops over ICI, kernels
are Pallas.
"""

from __future__ import annotations

import time as _time

_T_IMPORT = _time.perf_counter()  # the set-up ledger's origin

from typing import Any, Optional, Tuple  # noqa: E402

__version__ = "0.1.0"

from . import comm  # noqa: F401
from .accelerator import get_accelerator  # noqa: F401
from .runtime.config import DeepSpeedConfig  # noqa: F401
from .runtime.engine import DeepSpeedTPUEngine, TrainState  # noqa: F401
from .runtime.module import ModelSpec  # noqa: F401
from .parallel.mesh import MeshTopology, initialize_topology, get_topology  # noqa: F401
from .utils.logging import logger  # noqa: F401
from .utils.platform import ensure_compile_cache


def initialize(args: Any = None,
               model: Any = None,
               optimizer: Any = None,
               model_parameters: Any = None,
               training_data: Any = None,
               lr_scheduler: Any = None,
               distributed_port: Optional[int] = None,
               mpu: Any = None,
               dist_init_required: Optional[bool] = None,
               collate_fn: Any = None,
               config: Any = None,
               config_params: Any = None,
               example_batch: Any = None,
               loss_fn: Any = None,
               partition_rules: Any = None,
               topology: Optional[MeshTopology] = None,
               ) -> Tuple[DeepSpeedTPUEngine, Any, Any, Any]:
    """Create a training engine (reference ``deepspeed.initialize``,
    __init__.py:78).

    Returns ``(engine, optimizer, dataloader, lr_scheduler)`` like the
    reference.  ``optimizer``/``lr_scheduler`` handles are views into the
    engine (the update itself is compiled into the engine's step program).
    """
    config = config if config is not None else config_params
    if config is None and args is not None and hasattr(args, "deepspeed_config"):
        config = args.deepspeed_config

    comm.init_distributed()
    ensure_compile_cache()
    ds_config = config if isinstance(config, DeepSpeedConfig) else DeepSpeedConfig(config)
    # MiCS (reference zero/mics.py): shard within groups of mics_shard_size,
    # replicate across — expressed as data=mics_shard_size, repl=remainder
    mics = ds_config.zero_config.mics_shard_size
    if mics and mics > 0:
        if ds_config.mesh.data == -1:
            ds_config.mesh.data = mics
            ds_config.mesh.repl = -1
        elif ds_config.mesh.data != mics:
            from .utils.logging import logger as _logger

            _logger.warning(
                f"mics_shard_size={mics} ignored: mesh.data={ds_config.mesh.data} "
                "is set explicitly — leave mesh.data unset (-1) to let MiCS "
                "derive data=shard_size, repl=remainder")
    # a model prepared by tp_model_init carries its TP degree; honor it when
    # the config leaves the model axis at the default
    autotp = getattr(model, "_autotp_size", None)
    if autotp and autotp > 1 and ds_config.mesh.model == 1:
        # mesh.data keeps its value: -1 (the default) absorbs the remaining
        # devices; an explicit size stays the user's choice
        ds_config.mesh.model = int(autotp)
    if topology is None:
        topology = initialize_topology(ds_config.mesh)

    # elasticity (reference elasticity/elasticity.py:233): with elastic
    # config enabled, micro-batch and grad-accum are DERIVED from the
    # current world size so the global batch stays identical across resizes
    # — the core of elastic resume.
    ecfg = (ds_config.raw or {}).get("elasticity", {})
    if ecfg.get("enabled"):
        from .elasticity.elasticity import compute_elastic_config

        # use the RESOLVED attributes: "auto" values mean unset
        explicit_batch = any(v is not None for v in (
            ds_config.train_batch_size,
            ds_config.train_micro_batch_size_per_gpu,
            ds_config.gradient_accumulation_steps))
        if explicit_batch and not ecfg.get("ignore_non_elastic_batch_info"):
            raise ValueError(
                "elasticity is enabled but batch sizes are set explicitly; "
                "remove them or set elasticity.ignore_non_elastic_batch_info "
                "(reference elasticity v0.1/0.2 contract)")
        batch, _, info = compute_elastic_config(
            ds_config.raw, world_size=topology.dp_world_size)
        ds_config.train_batch_size = batch
        ds_config.train_micro_batch_size_per_gpu = info["micro_batch_per_gpu"]
        ds_config.gradient_accumulation_steps = info["gradient_accumulation_steps"]
        logger.info(
            f"elasticity: world={topology.dp_world_size} -> train_batch="
            f"{batch} micro={info['micro_batch_per_gpu']} "
            f"gas={info['gradient_accumulation_steps']}")

    if model_parameters is not None and not callable(model_parameters):
        # Reference signature parity: ``model_parameters`` is what the
        # optimizer trains.  Functionally that means: start the engine
        # from THIS pytree (e.g. a distilled student from
        # compression.student_initialization, or imported HF weights)
        # instead of the model's random init.  Shardings still come from
        # the engine's plan; values are adopted leaf-for-leaf.
        import copy as _copy

        from .runtime.module import as_model_spec as _as_spec

        model = _copy.copy(_as_spec(model, example_batch=example_batch,
                                    loss_fn=loss_fn,
                                    partition_rules=partition_rules))
        model.init_params = lambda rng, _given=model_parameters: _given

    engine_cls = DeepSpeedTPUEngine
    if ds_config.hybrid_engine.enabled:
        from .runtime.hybrid_engine import DeepSpeedHybridEngine

        engine_cls = DeepSpeedHybridEngine
    engine = engine_cls(
        model=model,
        config=ds_config,
        topology=topology,
        example_batch=example_batch,
        loss_fn=loss_fn,
        partition_rules=partition_rules,
        training_data=training_data,
        client_optimizer=optimizer,
        lr_scheduler=lr_scheduler,
    )
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_distributed(dist_backend: str = "xla", **kwargs) -> None:
    comm.init_distributed(dist_backend=dist_backend, **kwargs)


def init_inference(model: Any = None, config: Any = None, **kwargs):
    """Create an inference engine (reference ``init_inference``,
    __init__.py:302).

    ``model`` may also be a Hugging Face checkpoint DIRECTORY (reference
    inference loads published checkpoints via its model implementations):
    the config.json picks the family, weights are imported into the native
    tree, and the engine serves them."""
    import os as _os

    from .inference.engine import InferenceEngine, InferenceConfig

    ensure_compile_cache()
    cfg = config if isinstance(config, InferenceConfig) else InferenceConfig.from_dict(
        config if isinstance(config, dict) else {})
    for k, v in kwargs.items():
        if hasattr(cfg, k):
            setattr(cfg, k, v)
    params = kwargs.get("params")
    if isinstance(model, str) and _os.path.isdir(model):
        from .checkpoint.hf_import import load_hf_model
        from .models.llama import llama_model

        mcfg, params = load_hf_model(model, dtype=cfg.jnp_dtype)
        model = llama_model(config=mcfg)
    return InferenceEngine(model, cfg, params=params)


def tp_model_init(model: Any, tp_size: int = 1, dtype: Any = None,
                  config: Any = None, example_batch: Any = None):
    """Shard a model with automatic tensor parallelism for training
    (reference ``deepspeed.tp_model_init``, __init__.py:380)."""
    from .runtime.tensor_parallel import tp_model_init as _tp_model_init

    return _tp_model_init(model, tp_size=tp_size, dtype=dtype, config=config,
                          example_batch=example_batch)


def default_inference_config():
    """Default inference config as a dict (reference
    ``default_inference_config``, __init__.py:295) — edit and pass back to
    ``init_inference``."""
    from .inference.engine import InferenceConfig

    return InferenceConfig().to_dict()


def add_config_arguments(parser):
    """Augment an argparse parser with the standard flags (reference
    ``add_config_arguments``, __init__.py:279)."""
    group = parser.add_argument_group("DeepSpeed-TPU", "DeepSpeed-TPU configuration")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the config JSON")
    group.add_argument("--local_rank", type=int, default=0,
                       help="Local process index (set by the launcher)")
    return parser


from .telemetry.compile_sentinel import setup_span as _setup_span  # noqa: E402

_setup_span("package_import", _T_IMPORT)
