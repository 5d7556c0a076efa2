"""HLO cost contracts: machine-checked program shape for the hot paths.

A *contract* pins what a compiled program is allowed to look like: its
collective op counts by kind, FLOPs, bytes accessed, donated-input
count, argument shape signature (``compile/backend.py``), structural
state bytes, and — for the train programs — the recompile count of a
3-step replay.  Contracts are extracted by lowering representative tiny
programs on CPU (``jax.jit(...).lower().compile()``, 8 virtual devices,
the same harness as tier-1) and stored as golden JSON under
``tests/contracts/``.

Why: an earlier round recorded CPU runs as chip numbers and nothing
caught it; an extra all-gather, a lost fusion, or a steady-state recompile is
invisible until someone eyeballs a trace (ROADMAP item 5).  With the
goldens in tier-1, "stage-3 train step grew all-gather 24→26" is a
named test failure at review time — and the upcoming overlap /
quantized-collective work can assert "same collectives, fewer exposed"
without a TPU.

Drivers: ``tools/check_contracts.py`` (standalone + ``--update-goldens``)
and ``tools/dstpu_lint.py --all`` (merged report).  jax imports are
function-local so importing this module stays cheap for the lint
drivers.
"""

from __future__ import annotations

import json
import math
import os
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

#: collective opcodes counted in optimized HLO (async ``-start`` forms
#: count once; their ``-done`` halves are ignored)
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

#: relative tolerances for the scalar cost fields — XLA cost analysis is
#: deterministic for an identical program, but minor layout/fusion
#: nondeterminism must not flap tier-1; collectives/donation/shapes
#: compare EXACTLY
DEFAULT_TOLERANCES = {"flops": 0.05, "bytes_accessed": 0.10,
                      "temp_bytes": 0.10}

#: goldens live here, relative to the repo root
CONTRACTS_DIR = os.path.join("tests", "contracts")


# ------------------------------------------------------------- extraction
def collective_counts(hlo_text: str) -> Dict[str, int]:
    """Count collective ops by kind in optimized HLO text.

    The result type is either a plain shape (``s8[8,128]{1,0}``) or — when
    XLA's collective combiner merged several ops — a tuple of shapes
    (``(s8[...], f32[...])``); a combined op counts ONCE (it is one wire
    transaction, which is what the contract pins)."""
    out = {}
    tuple_ty = r"\([^()]*\)"  # tuple result types contain no nested parens
    for kind in COLLECTIVE_KINDS:
        out[kind] = len(re.findall(
            rf"=\s*(?:{tuple_ty}|\S+)\s+{kind}(?:-start)?\(", hlo_text))
    return out


def donated_input_count(stablehlo_text: str) -> int:
    """Donated input leaves, from the lowering's aliasing attributes."""
    return len(re.findall(r"tf\.aliasing_output", stablehlo_text))


def s8_collective_count(hlo_text: str) -> int:
    """Collective ops moving int8 codes: ops whose result type (plain or
    combiner tuple) mentions ``s8[`` — what "int8 on the wire" means in
    optimized HLO.  The compressed-overlap goldens pin this so a silent
    fall-back to fp32 wire (a lost optimization_barrier, a folded
    convert) is a named diff, not a perf mystery."""
    tuple_ty = r"\([^()]*\)"
    count = 0
    for kind in COLLECTIVE_KINDS:
        for m in re.finditer(
                rf"=\s*({tuple_ty}|\S+)\s+{kind}(?:-start)?\(", hlo_text):
            if "s8[" in m.group(1):
                count += 1
    return count


def shape_signature_strings(*trees: Any) -> List[str]:
    """The ``compile/backend.py`` shape signature, as stable strings."""
    from ..compile.backend import shape_signature

    return [f"{dtype}{list(shape)}"
            for shape, dtype in shape_signature(*trees)]


def _cost_dict(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost or {})


def extract_contract(jit_fn, args: Sequence[Any],
                     mesh: Any = None,
                     want_s8: bool = False,
                     want_temp: bool = False) -> Dict[str, Any]:
    """Lower + compile ``jit_fn(*args)`` and extract its contract dict
    (the compared section only; callers add replay/state fields).
    ``want_s8``: also pin :func:`s8_collective_count` from the SAME
    compile (the compressed-overlap programs; opt-in so pre-existing
    goldens keep their key set byte-identical).  ``want_temp``: also pin
    XLA's temporaries (``memory_analysis().temp_size_in_bytes``) — the
    serving programs, whose KV pools must be updated in place: a pool
    handed through the layer scan as operand and stacked output shows up
    here as a second pool."""
    import contextlib

    ctx = mesh if mesh is not None else contextlib.nullcontext()
    with ctx:
        lowered = jit_fn.lower(*args)
        compiled = lowered.compile()
    cost = _cost_dict(compiled)
    hlo = compiled.as_text()
    out = {
        "collectives": collective_counts(hlo),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "donated_inputs": donated_input_count(lowered.as_text()),
        "arg_shapes": shape_signature_strings(*args),
    }
    if want_s8:
        out["s8_collectives"] = s8_collective_count(hlo)
    if want_temp:
        out["temp_bytes"] = int(compiled.memory_analysis().temp_size_in_bytes)
    return out


# ------------------------------------------------- representative programs
def _mlp_spec(hidden: int = 16, nlayers: int = 2):
    """The tiny MLP regression model (mirrors tests/unit/simple_model.py;
    re-stated here because package code must not import the test tree)."""
    import jax
    import jax.numpy as jnp

    from ..runtime.module import ModelSpec

    def init_params(rng):
        keys = jax.random.split(rng, nlayers)
        params = {}
        for i, k in enumerate(keys):
            params[f"layer_{i}"] = {
                "w": jax.random.normal(k, (hidden, hidden)) * 0.1,
                "b": jnp.zeros((hidden,)),
            }
        return params

    def loss_fn(params, batch, rng):
        x, y = batch
        for i in range(nlayers):
            layer = params[f"layer_{i}"]
            x = x @ layer["w"] + layer["b"]
            if i < nlayers - 1:
                x = jax.nn.relu(x)
        return jnp.mean((x - y.astype(x.dtype)) ** 2)

    return ModelSpec(init_params, loss_fn)


def _train_batch_arrays(hidden: int = 16, batch: int = 16):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(0)
    xs = rng.randn(1, batch, hidden).astype(np.float32)  # leading gas dim
    ys = xs * 0.5
    return jnp.asarray(xs), jnp.asarray(ys)


def _train_program(stage: int, offload: bool = False, qgz: bool = False,
                   replay: bool = True, hier: bool = False) -> Dict[str, Any]:
    import jax

    import deepspeed_tpu
    from ..telemetry.memory import tree_bytes

    zero_cfg: Dict[str, Any] = {"stage": stage}
    if offload:
        zero_cfg["offload_optimizer"] = {"device": "cpu"}
    if qgz:
        zero_cfg["zero_quantized_gradients"] = True
    if hier:
        # pinned inner=2 (not auto): the golden must not depend on the
        # harness's local-device heuristic
        zero_cfg["zero_hierarchical_grad_reduce"] = True
        zero_cfg["zero_hierarchy_inner"] = 2
    engine, *_ = deepspeed_tpu.initialize(model=_mlp_spec(), config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": zero_cfg,
    })
    batch = _train_batch_arrays()
    args = (engine.state, batch, jax.random.PRNGKey(0))
    dev_b, host_b = tree_bytes(engine.state)
    extras = {"state_bytes_device": int(dev_b),
              "state_bytes_host": int(host_b)}
    replay_fn = (lambda: _replay_train(engine, batch)) if replay else None
    return {"fn": engine._train_batch, "args": args,
            "mesh": engine.topology.mesh, "extras": extras,
            "replay": replay_fn}


def _replay_train(engine, batch, steps: int = 3) -> Dict[str, Any]:
    """Run the tiny train loop for ``steps`` same-shape steps and count
    XLA backend compiles AFTER the first step.  The contract pins this
    at 0: shape-signature churn (weak types, donation mismatch,
    non-hashable statics) shows up here as a nonzero count — the
    machine-checked form of what the PR 3 sentinel only warns about at
    runtime."""
    from ..telemetry.compile_sentinel import (compile_counts,
                                              install_compile_listener)

    monitoring = install_compile_listener()
    engine.train_batch(batch)  # warmup step: compiles are expected here
    c0, _ = compile_counts()
    for _ in range(2):
        engine.train_batch(batch)
    c1, _ = compile_counts()
    return {"steps": 3,
            "compiles_after_warmup": (int(c1 - c0) if monitoring else None)}


def _v2_engine(horizon: int = 1):
    import jax

    from ..inference.v2 import (InferenceEngineV2, RaggedInferenceConfig,
                                SpeculativeConfig)
    from ..models.llama import llama_model

    model = llama_model("tiny", max_seq_len=64)
    params = model.init_params(jax.random.PRNGKey(0))
    # a fused decode horizon and a proposer are mutually exclusive (the
    # engine stands the horizon down): the multistep program gets a
    # speculation-free engine, every other program keeps the verify path
    spec = (SpeculativeConfig(mode="off") if horizon > 1
            else SpeculativeConfig(mode="ngram", k=3))
    return InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, num_pages=32, max_seqs=2,
        max_pages_per_seq=8, decode_horizon=horizon,
        speculative=spec), params=params)


def _v2_extras(eng) -> Dict[str, Any]:
    from ..telemetry.memory import tree_bytes

    pool_dev, _ = tree_bytes(eng._pools)
    return {"param_bytes": int(eng.param_bytes),
            "kv_pool_bytes": int(pool_dev)}


def _prefill_program() -> Dict[str, Any]:
    import jax.numpy as jnp
    import numpy as np

    eng = _v2_engine()
    ps = eng.block.page_size
    bucket = eng._bucket(13)
    ids = np.zeros((bucket,), np.int32)
    rows = np.full((bucket // ps,), eng.block.trash_page, np.int32)
    args = (eng.params, eng._pools, jnp.asarray(ids), jnp.asarray(rows),
            jnp.int32(13))
    return {"fn": eng._prefill, "args": args, "mesh": None,
            "want_temp": True, "extras": _v2_extras(eng), "replay": None}


def _decode_program() -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    eng = _v2_engine()
    B = eng.block.max_seqs
    args = (eng.params, eng._pools,
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(eng._page_table),
            jnp.asarray(np.zeros((B,), bool)),
            jnp.asarray(np.zeros((B,), np.float32)),
            jnp.asarray(np.zeros((B,), np.int32)),
            jax.random.PRNGKey(0))
    return {"fn": eng._decode, "args": args, "mesh": None,
            "want_temp": True, "extras": _v2_extras(eng), "replay": None}


def _multi_decode_program() -> Dict[str, Any]:
    """Fused multi-step decode (model_runner.paged_multi_decode): the
    K-step on-device decode scan with in-scan sampling and per-row
    EOS/budget masking — pins its collective counts, the donated pool
    buffers (a lost donation doubles the KV pool's HBM), and a 3-step
    same-shape replay across MIXED per-row produced lengths at 0
    recompiles (mixed budgets/EOS are data, never shapes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eng = _v2_engine(horizon=4)
    B, K = eng.block.max_seqs, eng._horizon
    args = (eng.params, eng._pools,
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(eng._page_table),
            jnp.asarray(np.zeros((B,), bool)),
            jnp.asarray(np.zeros((B,), np.float32)),
            jnp.asarray(np.full((B,), -1, np.int32)),
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(np.zeros((B,), np.int32)),
            jax.random.PRNGKey(0), K)
    return {"fn": eng._multi, "args": args, "mesh": None,
            "want_temp": True, "extras": _v2_extras(eng),
            "replay": lambda: _replay_multi_decode(eng, K)}


def _replay_multi_decode(eng, K: int) -> Dict[str, Any]:
    """Dispatch the fused decode scan 3 times with the SAME shapes but
    DIFFERENT per-row budget/EOS mixes (mixed produced lengths) and
    count XLA backend compiles after the first dispatch — pinned at 0:
    every acceptance outcome of the horizon must reuse one compiled
    program, like the speculative verify width does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..telemetry.compile_sentinel import (compile_counts,
                                              install_compile_listener)

    monitoring = install_compile_listener()
    B = eng.block.max_seqs
    key = jax.random.PRNGKey(0)

    def dispatch(budgets, eos):
        _toks, produced, eng._pools = eng._multi(
            eng.params, eng._pools,
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(eng._page_table),
            jnp.asarray(np.ones((B,), bool)),
            jnp.asarray(np.zeros((B,), np.float32)),
            jnp.asarray(np.asarray(eos, np.int32)),
            jnp.asarray(np.asarray(budgets, np.int32)),
            jnp.asarray(np.arange(B, dtype=np.int32)),
            key, K)
        jax.block_until_ready(produced)

    dispatch([1 + (i % K) for i in range(B)], [-1] * B)  # warmup
    c0, _ = compile_counts()
    dispatch([K - (i % K) for i in range(B)], [-1] * B)
    dispatch([max(1, K // 2)] * B, [0] * B)  # EOS-capable rows
    c1, _ = compile_counts()
    return {"steps": 3,
            "compiles_after_warmup": (int(c1 - c0) if monitoring else None)}


def _verify_program() -> Dict[str, Any]:
    import jax.numpy as jnp
    import numpy as np

    eng = _v2_engine()
    B, W = eng.block.max_seqs, eng.spec.k + 1
    args = (eng.params, eng._pools,
            jnp.asarray(np.zeros((B, W), np.int32)),
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(eng._page_table),
            jnp.asarray(np.zeros((B,), bool)),
            jnp.asarray(np.ones((B,), np.int32)))
    return {"fn": eng._verify, "args": args, "mesh": None,
            "want_temp": True, "extras": _v2_extras(eng), "replay": None}


def _moe_dispatch_program() -> Dict[str, Any]:
    """Quantized expert-parallel MoE dispatch: the explicit all-to-all
    shard_map path (moe/ep_dispatch.py) with the comm/collectives int8
    codec on the token payloads — pins 5 all-to-alls (codes + scales
    each way, exact routing metadata) so a regression to full-precision
    dispatch (or a lost/duplicated exchange) is a named tier-1 diff."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..moe.ep_dispatch import moe_ffn_ep
    from ..moe.sharded_moe import MoEConfig
    from ..parallel.mesh import initialize_topology
    from ..runtime.config import MeshConfig

    topo = initialize_topology(MeshConfig(expert=4, data=2),
                               jax.devices()[:8])
    B, S, H, F, E = 8, 4, 16, 32, 4
    cfg = MoEConfig(num_experts=E, top_k=2, drop_tokens=False,
                    ep_a2a_compression="int8")
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, S, H).astype(np.float32))
    gate_w = jnp.asarray(rng.randn(H, E).astype(np.float32) * 0.1)
    wg = jnp.asarray(rng.randn(E, H, F).astype(np.float32) * 0.1)
    wu = jnp.asarray(rng.randn(E, H, F).astype(np.float32) * 0.1)
    wd = jnp.asarray(rng.randn(E, F, H).astype(np.float32) * 0.1)

    def dispatch(x, gate_w, wg, wu, wd):
        return moe_ffn_ep(x, gate_w,
                          {"w_gate": wg, "w_up": wu, "w_down": wd}, cfg)

    return {"fn": jax.jit(dispatch), "args": (x, gate_w, wg, wu, wd),
            "mesh": topo.mesh, "extras": {}, "replay": None}


def _train_overlap_program(stage: int, prefetch: bool = False,
                           compressed: bool = False) -> Dict[str, Any]:
    """Fused train step with the compute/collective overlap wrap
    (runtime/zero/overlap.py) on a tiny SCANNED llama — the MLP spec has
    no layer scan, and the overlap contract exists precisely to pin the
    in-loop collective structure (bucketed grad reduce; stage 3: explicit
    prefetched gathers + reduce-scatters).  Replay is pinned at 0
    recompiles: the wrap must not introduce shape-signature churn.

    ``compressed``: the compressed-overlap variant (docs/COMM.md
    "Compressed overlap") — stage 1 via ``zero_quantized_gradients``
    (the qgZ compose), stage 3 via ``overlap_compression`` — which
    additionally pins the s8-on-wire collective count and the donated
    EF-residual state bytes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from ..models.llama import llama_model
    from ..telemetry.memory import tree_bytes

    zero_cfg: Dict[str, Any] = {"stage": stage, "overlap_grad_reduce": True}
    if prefetch:
        zero_cfg["zero3_param_prefetch"] = True
    if compressed:
        if stage <= 2:
            zero_cfg["zero_quantized_gradients"] = True
        else:
            zero_cfg["overlap_compression"] = "int8"
    model = llama_model("tiny", max_seq_len=16, vocab_size=64, n_layers=2,
                        attn_impl="xla")
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": zero_cfg,
    })
    dp = engine.topology.dp_world_size
    ids = np.random.RandomState(0).randint(0, 64, (1, dp, 16)).astype(np.int32)
    batch = {"input_ids": jnp.asarray(ids)}
    args = (engine.state, batch, jax.random.PRNGKey(0))
    dev_b, host_b = tree_bytes(engine.state)
    extras = {"state_bytes_device": int(dev_b),
              "state_bytes_host": int(host_b)}
    report = engine.overlap_report()
    if report is not None:
        extras["overlap_buckets"] = int(report.buckets)
        extras["overlapped_fraction"] = round(report.overlapped_fraction, 6)
    if compressed:
        # s8_collectives itself is pinned by extract_contract (want_s8)
        # from the ONE compile — no second lowering here
        extras["comm_residual_bytes"] = sum(
            int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
            for l in jax.tree_util.tree_leaves(engine.state.comm_errors))
    return {"fn": engine._train_batch, "args": args,
            "mesh": engine.topology.mesh, "extras": extras,
            "want_s8": compressed,
            "replay": lambda: _replay_train(engine, batch)}


def _train_pipe_program() -> Dict[str, Any]:
    """Pipeline-parallel train step (runtime/pipe/engine.py): 2 stages x
    2 data on 4 of the 8 virtual CPU devices, int8-compressed activation
    hops with error feedback, and the bubble-overlapped int8 grad reduce
    (stage 1 + overlap_grad_reduce + overlap_compression).  Pins the
    collective-permute count (the hop ring — a lost ppermute means the
    schedule degenerated), the s8-on-wire count (hops + in-scan bucket
    reduces; a silent fp32 fall-back is a named diff), the donated
    leaves (the pipe EF slot rides TrainState.comm_errors and must stay
    donated), the computed (P-1)/(M+P-1) bubble fraction, and a 3-step
    replay at 0 recompiles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from ..models.llama import llama_config
    from ..parallel.mesh import initialize_topology
    from ..runtime.config import MeshConfig
    from ..runtime.pipe.engine import pipelined_causal_lm
    from ..telemetry.memory import tree_bytes

    topo = initialize_topology(MeshConfig(pipe=2, data=2),
                               jax.devices()[:4])
    cfg = llama_config("tiny", max_seq_len=16, vocab_size=64, n_layers=2,
                       attn_impl="xla")
    model = pipelined_causal_lm(cfg, num_microbatches=2)
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "pipeline": {"hop_compression": "int8"},
        "zero_optimization": {"stage": 1, "overlap_grad_reduce": True,
                              "overlap_compression": "int8",
                              "overlap_bucket_mb": 1},
    }, topology=topo)
    dp = engine.topology.dp_world_size
    ids = np.random.RandomState(0).randint(
        0, 64, (1, 2 * dp, 16)).astype(np.int32)
    batch = {"input_ids": jnp.asarray(ids)}
    args = (engine.state, batch, jax.random.PRNGKey(0))
    dev_b, host_b = tree_bytes(engine.state)
    extras = {"state_bytes_device": int(dev_b),
              "state_bytes_host": int(host_b),
              "pipe_bubble_fraction": round(
                  float(engine._pipe_struct["bubble_fraction"]), 6),
              "comm_residual_bytes": sum(
                  int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                  for l in jax.tree_util.tree_leaves(
                      engine.state.comm_errors))}
    return {"fn": engine._train_batch, "args": args,
            "mesh": engine.topology.mesh, "extras": extras,
            "want_s8": True,
            "replay": lambda: _replay_train(engine, batch)}


#: name -> (builder, description).  The builder returns the dict
#: consumed by :func:`extract_program`; descriptions land in the golden
#: JSON so a diff reader knows what program regressed.
PROGRAM_BUILDERS: Dict[str, Tuple[Callable[[], Dict[str, Any]], str]] = {
    "train_step_zero0": (
        lambda: _train_program(0),
        "fused train step, ZeRO stage 0 (replicated; grad psum over data)"),
    "train_step_zero1": (
        lambda: _train_program(1),
        "fused train step, ZeRO stage 1 (optimizer state sharded)"),
    "train_step_zero3": (
        lambda: _train_program(3),
        "fused train step, ZeRO stage 3 (params sharded; per-use gathers)"),
    "train_step_zero3_offload": (
        lambda: _train_program(3, offload=True, replay=False),
        "micro-step scan with host-offloaded optimizer (ZeRO-Offload: "
        "device program is fwd+bwd+accumulate only)"),
    "train_step_zero1_qgz": (
        lambda: _train_program(1, qgz=True, replay=False),
        "fused train step, ZeRO stage 1 + ZeRO++ qgZ int8 all-to-all "
        "gradient reduce"),
    "train_step_zero1_hier": (
        lambda: _train_program(1, qgz=True, hier=True, replay=False),
        "fused train step, ZeRO stage 1 + hierarchical two-hop gradient "
        "reduce (2x4 split of the data axis: intra-slice reduce-scatter, "
        "int8 inter-slice exchange, intra-slice all-gather)"),
    "train_step_zero1_overlap": (
        lambda: _train_overlap_program(1),
        "fused train step, ZeRO stage 1 + compute/collective overlap "
        "(tiny scanned llama; per-layer-bucket grad all-reduce issued "
        "inside the backward scan via the data-axis shard_map wrap)"),
    "train_step_zero3_prefetch": (
        lambda: _train_overlap_program(3, prefetch=True),
        "fused train step, ZeRO stage 3 + overlap + zero3_param_prefetch "
        "(tiny scanned llama; explicit in-loop param all-gathers, "
        "2x-unrolled double buffer, per-layer reduce-scatter in the "
        "backward loop)"),
    "train_step_zero1_overlap_int8": (
        lambda: _train_overlap_program(1, compressed=True),
        "fused train step, ZeRO stage 1 + COMPRESSED overlap "
        "(zero_quantized_gradients composed with overlap_grad_reduce: "
        "per-layer-bucket int8 two-hop grad reduce inside the backward "
        "scan, ONE error-feedback residual per bucket in train state; "
        "pins s8-on-wire collective count, bucket count, donated "
        "residual bytes, replay recompiles == 0)"),
    "train_step_zero3_prefetch_int8": (
        lambda: _train_overlap_program(3, prefetch=True, compressed=True),
        "fused train step, ZeRO stage 3 + overlap + prefetch + "
        "overlap_compression=int8 (per-layer QUANTIZED reduce-scatters "
        "in the backward loop with per-bucket EF residuals; fp param "
        "gathers untouched)"),
    "train_step_pipe2": (
        _train_pipe_program,
        "pipeline-parallel train step: 2 stages x 2 data, int8 activation "
        "hops with error feedback through the differentiated ppermute, "
        "bubble-overlapped int8 layer-bucket grad reduce inside the pipe "
        "scan; pins permute count, s8-on-wire count, donated EF slot, "
        "(P-1)/(M+P-1) bubble fraction, replay recompiles == 0"),
    "moe_dispatch_quantized": (
        _moe_dispatch_program,
        "expert-parallel dropless MoE dispatch with int8-quantized "
        "all-to-alls (ep=4, data=2; routing metadata exact)"),
    "prefill": (
        _prefill_program,
        "engine_v2 paged prefill, one bucket-16 prompt"),
    "decode": (
        _decode_program,
        "engine_v2 paged decode + on-device sampling, all slots"),
    "decode_multistep": (
        _multi_decode_program,
        "engine_v2 fused multi-step decode: K=4 on-device decode scan "
        "with in-scan sampling and per-row EOS/budget masking, ONE "
        "[B, K] host pull per dispatch"),
    "paged_verify": (
        _verify_program,
        "engine_v2 speculative batched verify (width k+1) + greedy argmax"),
}


def extract_program(name: str) -> Dict[str, Any]:
    """Build + lower one named program; returns its full golden dict."""
    import jax

    builder, description = PROGRAM_BUILDERS[name]
    prog = builder()
    contract = extract_contract(prog["fn"], prog["args"], prog["mesh"],
                                want_s8=prog.get("want_s8", False),
                                want_temp=prog.get("want_temp", False))
    contract.update(prog["extras"])
    if prog["replay"] is not None:
        contract["replay"] = prog["replay"]()
    return {
        "program": name,
        "contract": contract,
        "tolerances": {k: v for k, v in DEFAULT_TOLERANCES.items()
                       if k in contract},
        "info": {
            "description": description,
            "backend": jax.devices()[0].platform,
            "device_count": jax.device_count(),
            "jax_version": jax.__version__,
        },
    }


def extract_all(programs: Optional[Sequence[str]] = None
                ) -> Dict[str, Dict[str, Any]]:
    names = list(programs) if programs else list(PROGRAM_BUILDERS)
    unknown = [n for n in names if n not in PROGRAM_BUILDERS]
    if unknown:
        raise KeyError(f"unknown contract program(s) {unknown}; known: "
                       f"{sorted(PROGRAM_BUILDERS)}")
    return {name: extract_program(name) for name in names}


# ------------------------------------------------------------------ diffs
def _rel_close(a: float, b: float, tol: float) -> bool:
    if a == b:
        return True
    denom = max(abs(a), abs(b), 1e-12)
    return abs(a - b) / denom <= tol


def diff_contract(name: str, golden: Dict[str, Any],
                  got: Dict[str, Any]) -> List[str]:
    """Named, actionable differences between a golden and an extracted
    contract.  Empty list = contract holds."""
    errs: List[str] = []
    g, n = golden.get("contract", {}), got.get("contract", {})
    tol = {**DEFAULT_TOLERANCES, **golden.get("tolerances", {})}

    gc, nc = g.get("collectives", {}), n.get("collectives", {})
    for kind in COLLECTIVE_KINDS:
        a, b = int(gc.get(kind, 0)), int(nc.get(kind, 0))
        if a != b:
            verb = "grew" if b > a else "dropped"
            errs.append(f"{name}: {verb} {kind} {a} -> {b} "
                        f"({b - a:+d} collective(s) vs the golden contract)")
    for field in ("flops", "bytes_accessed", "temp_bytes"):
        if field not in g and field not in n:
            continue  # temp_bytes: the serving programs only
        a, b = float(g.get(field, 0.0)), float(n.get(field, 0.0))
        if not (math.isfinite(a) and math.isfinite(b)
                and _rel_close(a, b, tol.get(field, 0.0))):
            errs.append(f"{name}: {field} {a:.6g} -> {b:.6g} "
                        f"(beyond the {tol.get(field, 0.0):.0%} tolerance)")
    a, b = g.get("donated_inputs"), n.get("donated_inputs")
    if a != b:
        errs.append(f"{name}: donated inputs {a} -> {b} (a lost donation "
                    "doubles that buffer's HBM)")
    if g.get("arg_shapes") != n.get("arg_shapes"):
        errs.append(f"{name}: arg shape signature changed "
                    f"{g.get('arg_shapes')} -> {n.get('arg_shapes')} "
                    "(every caller recompiles)")
    for field in ("state_bytes_device", "state_bytes_host", "param_bytes",
                  "kv_pool_bytes", "overlap_buckets", "overlapped_fraction",
                  "s8_collectives", "comm_residual_bytes",
                  "pipe_bubble_fraction"):
        if field in g or field in n:
            a, b = g.get(field), n.get(field)
            if a != b:
                errs.append(f"{name}: {field} {a} -> {b}")
    gr, nr = g.get("replay"), n.get("replay")
    if gr is not None or nr is not None:
        ga = (gr or {}).get("compiles_after_warmup")
        na = (nr or {}).get("compiles_after_warmup")
        # None = jax.monitoring unavailable on one side; not comparable
        if ga is not None and na is not None and ga != na:
            errs.append(
                f"{name}: {(nr or {}).get('steps', 3)}-step replay "
                f"recompiled {na}x after warmup (golden {ga}) — "
                "shape-signature churn in the steady-state step")
    return errs


def diff_all(goldens: Dict[str, Dict[str, Any]],
             got: Dict[str, Dict[str, Any]]) -> List[str]:
    errs: List[str] = []
    for name in sorted(set(goldens) | set(got)):
        if name not in goldens:
            errs.append(f"{name}: extracted but no golden checked in — "
                        "run tools/check_contracts.py --update-goldens")
        elif name not in got:
            errs.append(f"{name}: golden exists but the program is gone "
                        "from PROGRAM_BUILDERS (delete the golden or "
                        "restore the program)")
        else:
            errs.extend(diff_contract(name, goldens[name], got[name]))
    return errs


# ---------------------------------------------------------------- goldens
def goldens_dir(root: str) -> str:
    return os.path.join(root, CONTRACTS_DIR)


def load_goldens(root: str) -> Dict[str, Dict[str, Any]]:
    d = goldens_dir(root)
    out: Dict[str, Dict[str, Any]] = {}
    if not os.path.isdir(d):
        return out
    for fn in sorted(os.listdir(d)):
        if fn.endswith(".json"):
            with open(os.path.join(d, fn)) as f:
                data = json.load(f)
            out[data.get("program", fn[:-5])] = data
    return out


def write_goldens(root: str, contracts: Dict[str, Dict[str, Any]]) -> List[str]:
    d = goldens_dir(root)
    os.makedirs(d, exist_ok=True)
    written = []
    for name, data in sorted(contracts.items()):
        path = os.path.join(d, f"{name}.json")
        with open(path, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
            f.write("\n")
        written.append(path)
    return written
