"""Transformer model core.

The reference ships no model zoo for training (users bring torch modules) but
its inference engine implements llama/gpt/bert/mixtral families
(``inference/v2/model_implementations``, ``module_inject/containers``).  Here
models are first-class: a single configurable decoder/encoder core that the
family front-ends (llama.py, gpt2.py, bert.py, mixtral.py) instantiate.

TPU-first choices:
  * layer params are STACKED on a leading [n_layers, ...] dim and executed
    with ``lax.scan`` — one compiled block regardless of depth.
  * attention/MLP keep everything in [B, S, H] bf16 matmuls for the MXU;
    rotary embeddings are computed inline (fuses into the QK matmul chain).
  * TP is a set of partition rules over the "model" mesh axis (column-
    parallel QKV/up, row-parallel O/down — Megatron layout, the same
    sharding AutoTP infers in the reference, module_inject/auto_tp.py:193).
  * activation checkpointing = ``jax.checkpoint`` policy on the scanned
    block (reference runtime/activation_checkpointing/checkpointing.py).
  * sequence parallelism (Ulysses all-to-all / ring attention) plugs in via
    ``attn_impl`` (see sequence/ and ops/pallas/flash_attention.py).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..runtime.activation_checkpointing.checkpointing import (DEFAULT_POLICY,
                                                              get_policy)
from ..telemetry.regions import region

MODEL_AXIS = "model"
SEQ_AXIS = "sequence"


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: Optional[int] = None  # GQA; None => MHA
    intermediate_size: Optional[int] = None  # None => 4x (gelu) / llama 8/3 rule
    max_seq_len: int = 2048
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    activation: str = "swiglu"  # swiglu | gelu
    position: str = "rope"  # rope | learned | alibi | none
    causal: bool = True
    #: bloom-style word_embeddings_layernorm on a PRE-norm model (post_norm
    #: models get an embedding norm implicitly)
    embed_norm: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dropout: float = 0.0
    use_bias: bool = False  # gpt2/bert style proj biases
    qkv_bias: bool = False  # bias on q/k/v only (qwen2 style)
    rotary_pct: float = 1.0  # fraction of head_dim under rope (phi/neox)
    parallel_block: bool = False  # x + attn(ln x) + mlp(ln x), shared ln (falcon/phi)
    # norms in a parallel block: 1 = one shared input norm (falcon-7b/phi);
    # 2 = separate attn/mlp norms (falcon-40b/180b ln_attn+ln_mlp)
    parallel_norms: int = 1
    # post-norm (original-transformer/BERT ordering): norm AFTER each
    # residual add — norm1(x + attn(x)), norm2(h + ffn(h)); embeddings get
    # their own LayerNorm and there is no final norm.  Encoder-style: the
    # generative engines (KV cache, pipeline) reject it.
    post_norm: bool = False
    # segment-embedding table size for post-norm encoders (BERT
    # type_vocab_size); 0 disables the table
    type_vocab_size: int = 2
    dtype: Any = jnp.float32  # params storage dtype at init (engine recasts)
    remat: bool = False
    #: what a recomputed block keeps (a name of runtime/
    #: activation_checkpointing/checkpointing.py::POLICY_MAP): by default the
    #: outputs of its matrix products and kernels, the elementwise work
    #: replayed; "nothing_saveable" keeps the block's input alone
    remat_policy: str = DEFAULT_POLICY
    attn_impl: str = "auto"  # auto | xla | flash | ulysses | ring
    scan_layers: bool = True
    # MoE (mixtral-style: every layer's MLP is replaced when num_experts > 0)
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    #: qwen2-moe shared expert: its FFN width (0 = off); output is added to
    #: the routed MoE output, scaled by sigmoid(x @ shared_gate) per token
    moe_shared_expert: int = 0
    #: renormalize kept top-k gate probs to sum 1 (mixtral/reference
    #: normalize_gate_probabilities); qwen2-moe ships norm_topk_prob=false
    moe_norm_topk: bool = True
    moe_drop_tokens: bool = True  # False => dropless sort+grouped-matmul path
    #: this chip's share of an expert-parallel deployment (MoEConfig
    #: held_first / held_count; 0 = every expert is held): the router keeps
    #: ``moe_experts`` outputs, the expert weights hold ``moe_held_count``
    moe_held_first: int = 0
    moe_held_count: int = 0
    #: False: the shared expert is added as it is, with no sigmoid gate
    moe_shared_gate: bool = True
    #: router scoring ("softmax" | "sigmoid", MoEConfig.scoring), whether the
    #: router has a per-expert selection bias (``mlp/router_bias``: a buffer,
    #: not a parameter — ``ModelSpec.buffers``) and the factor on the picks'
    #: weights (routed_scaling_factor)
    moe_scoring: str = "softmax"
    moe_router_bias: bool = False
    moe_routed_scale: float = 1.0
    #: expert-share counters (engine-set per trace, like numerics_act_stats):
    #: ``causal_lm_loss`` also returns the int32 counters of each expert
    #: layer (``moe.sharded_moe.MOE_TRAIN_COUNTERS``), which the fused step
    #: sums on the device (``engine.moe_stats()``)
    moe_counters: bool = False
    #: EP dispatch: "auto" = explicit all-to-all shard_map when the mesh
    #: has an expert axis (moe/ep_dispatch.py); "spmd" = partitioner-driven
    moe_ep_dispatch: str = "auto"
    #: quantize the EP dispatch/return all-to-alls ("int8"/"fp8"/None; the
    #: comm/collectives wire format — EQuARX's biggest win, docs/COMM.md)
    moe_a2a_compression: Optional[Any] = None
    #: quantize the ring-attention K/V rotations ("int8"/"fp8"/None);
    #: only meaningful with attn_impl="ring"
    ring_compression: Optional[Any] = None
    #: stage-3 manual param prefetch (engine-set per trace, like qwz):
    #: the layer scan runs 2x-unrolled, so each trip holds two
    #: independent gather->compute chains and layer i+1's param
    #: all-gather can overlap layer i's compute (the compiled analogue of
    #: the reference's PartitionedParameterCoordinator prefetch,
    #: partitioned_param_coordinator.py:285).  With an ``overlap_plan``
    #: installed, the gathers are additionally issued EXPLICITLY at the
    #: body top by the plan's hook, so the two chains start independent.
    zero3_prefetch: bool = False
    #: ZeRO overlap hook (engine-set per trace, like qwz): a
    #: runtime/zero/overlap.OverlapPlan threading every layer's param
    #: slices through a custom_vjp whose bwd issues each bucket's grad
    #: reduce inside the backward loop (and, under zero3_prefetch,
    #: whose fwd forces the param gathers at the scan-body top)
    overlap_plan: Optional[Any] = None
    #: pipe activation-hop codec (engine-set per trace, like overlap_plan):
    #: a CompressionSpec routing the per-tick ``ppermute`` (and its
    #: backward-wave transpose) through the quantized collective verbs
    #: (comm/collectives/compressed.py); None = exact fp hop
    pipe_hop_spec: Optional[Any] = None
    #: bubble-overlapped pipe grad reduce (engine-set per trace): a
    #: runtime/pipe/overlap.PipeOverlapPlan hooking each tick's stage
    #: apply so the per-stage layer-bucket grad reduces ride inside the
    #: pipe scan (drain-tick bubbles are free comm time)
    pipe_overlap_plan: Optional[Any] = None
    # PR-MoE residual experts (reference moe/layer.py use_residual): a dense
    # MLP runs beside the MoE and a learned 2-way coefficient mixes them
    moe_use_residual: bool = False
    # ALST-style tiled logits+loss: sequence chunk size (0 = off)
    loss_chunk: int = 0
    #: numerics observatory (engine-set per trace, like qwz): the layer
    #: scan emits a stacked [L, 3] (l2_norm, max_abs, nonfinite) side
    #: output over each block's activations and causal_lm_loss returns
    #: (loss, act) — carried as extra fused-step outputs, pulled only at
    #: the steps_per_print boundary (telemetry/numerics.py)
    numerics_act_stats: bool = False
    # ZeRO++ qwZ: per-layer weight gathers move int8 codes + block scales
    # instead of bf16 (set by the engine when zero_quantized_weights is on)
    qwz: bool = False
    # weight-only quantized inference (reference inference/quantization/):
    # big matmul weights stored as int8/int4 codes + group scales; 0 = off.
    # Set by InferenceEngineV2 on ITS OWN config copy, never on a shared one.
    wq_bits: int = 0
    wq_group: int = 128

    #: set when structured head pruning changed n_heads (compression
    #: redundancy_clean): head_dim is then no longer hidden/n_heads
    head_dim_override: Optional[int] = None

    #: the stack as a repeated *period* of layer types (models/layer_types.py:
    #: each type defines its parameters, its mixer and the cache it keeps).
    #: ``("attn",)`` is the homogeneous stack: ``params["layers"]`` one tree
    #: stacked ``[n_layers, ...]``.  A longer period makes ``params["layers"]``
    #: a tuple, one tree per position of the period, each stacked
    #: ``[n_layers / len(period), ...]``
    layer_period: Tuple[str, ...] = ("attn",)
    #: the stack as a LIST of layer types, one name per layer, where the
    #: published pattern is no repeated period (LFM2: attention at layers 2,
    #: 6, 10, 14, 18, 21 of 24).  ``params["layers"]`` is then a tuple with
    #: one tree per *run* of layers alike in mixer and feed-forward part
    #: (``layer_types.stack_runs``), each stacked ``[layers of the run,
    #: ...]``; the first ``dense_layers`` layers carry a dense feed-forward
    #: part of width ``dense_ffn_size`` — the prologue — and the others the
    #: configuration's (experts where ``moe_experts``).  Trained through
    #: ``transformer_forward``; empty: ``layer_period`` describes the stack
    layer_types: Tuple[str, ...] = ()
    #: the stack as a sequence of RUNS, each a repeated period of layer types
    #: — ``((("mamba", "swa"), 8), (("mamba", "dattn"), 1), (("gmu",
    #: "xattn"), 7))`` — where the published pattern is neither one period
    #: nor a list worth unrolling.  ``params["layers"]`` is then a tuple with
    #: one entry per run, each a tuple with one tree per position of the
    #: run's period, stacked ``[repeats, ...]``.  Served
    #: (``model_runner._scan_layers`` scans run by run); ``layer_period`` is
    #: the stack of one run
    layer_runs: Tuple[Tuple[Tuple[str, ...], int], ...] = ()
    dense_layers: int = 0
    dense_ffn_size: int = 0
    #: taps of the gated short convolution's depthwise causal kernel (type
    #: "conv"; LFM2's ``conv_L_cache``)
    conv_taps: int = 3
    #: RMSNorm over head_dim on each q head and each k head, before rotary
    qk_norm: bool = False
    #: generation by diffusion over blocks (``inference/v2/block_diffusion``):
    #: positions come in blocks of this many at absolute multiples of it, the
    #: attention mask is causal between blocks and bidirectional inside one,
    #: and a position not yet decided holds ``mask_token_id``.  0: one token a
    #: step under the causal mask.  A power of two that divides the page
    block_length: int = 0
    mask_token_id: int = 0
    #: attention output gate: ``wo (attn * sigmoid(wg h))``
    attn_gate: bool = False
    #: delta-rule linear-attention layers (type "kda"): heads x head_dim for
    #: q, k and v alike, the causal depthwise convolution's kernel size and
    #: the rank of the low-rank decay and gate projections
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_rank: int = 128
    #: a window layer's reach (type "swa"): a query sees itself and the
    #: ``sliding_window - 1`` positions before it
    sliding_window: int = 0
    #: selective state-space layers (type "mamba", Mamba-1): inner width, the
    #: state's size per channel, the causal depthwise convolution's kernel
    #: size and the rank of the step projection
    ssm_inner: int = 0
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_dt_rank: int = 0
    #: latent attention (type "mla"): the ranks of the query's and the
    #: key/value's down-projections, a head's query/key width without and
    #: with rotary, and a head's value width; what a token leaves in the
    #: cache is ``kv_lora_rank + qk_rope_head_dim`` values a layer
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: YaRN rotary tables (``yarn_inv_freq``): ``rope_factor`` over
    #: ``rope_original_max`` positions (0: plain rotary), the blend's two
    #: rotation counts, and ``mscale_all_dim`` (the softmax scale is
    #: multiplied by ``(0.1 * mscale_all_dim * ln(factor) + 1) ** 2``)
    rope_factor: float = 1.0
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.0
    #: a query at position t is scaled by ``1 + beta * ln(1 + floor(t /
    #: rope_original_max))`` (``llama_4_scaling_beta``; 0: not at all)
    attn_scale_beta: float = 0.0
    #: grouped-query layers whose cache follows the layer's TYPE (types
    #: "gqa_full" and "gqa_window", ``layer_types.gqa_shape``): a key head is
    #: ``head_dim`` wide and a value head ``v_head_dim``; a full layer has
    #: ``n_kv_heads`` K/V heads and rotates with ``rope_theta``, a window
    #: layer ``swa_kv_heads`` and ``swa_rope_theta`` (0: the full layers');
    #: ``rotary_pct`` of a head is rotated; ``swa_sink``: one learned scalar a
    #: query head joins the denominator of a window layer's softmax;
    #: ``attn_value_scale`` multiplies the values
    swa_kv_heads: int = 0
    swa_rope_theta: float = 0.0
    swa_sink: bool = False
    attn_value_scale: float = 1.0
    #: what else may follow the type (Laguna): a window layer's query heads
    #: and the share of a head it rotates (0: the full layers' ``n_heads`` and
    #: ``rotary_pct``); a full layer rotates under YaRN where ``rope_factor``
    #: > 1 (a window layer's table is plain), and ``rope_attention_factor``
    #: (0: none) multiplies the cos and sin of its rotated lanes — queries and
    #: keys alike, so the cache keeps it; ``attn_head_gate``: one learned
    #: scalar a query head on the mixer's output, ``wo (attn_n * sigmoid(h
    #: wg)_n)``, ``wg [H, heads of the type]``
    swa_n_heads: int = 0
    swa_rotary_pct: float = 0.0
    rope_attention_factor: float = 0.0
    attn_head_gate: bool = False
    #: a residual of ``hc_mult`` streams mixed by manifold-constrained
    #: hyper-connections (mHC, arXiv 2512.24880; Xing4.0): a token's residual
    #: is ``X [hc_mult, hidden]`` and every sublayer (a mixer, a feed-forward
    #: part) reads ``H_pre X``, and writes ``H_res X + H_post^T y`` back —
    #: ``H_res`` the Sinkhorn projection (``hc_sinkhorn_iters`` rounds, rows
    #: first, ``hc_eps`` in each denominator) of ``exp`` of a matrix clamped to
    #: ``-+hc_clamp`` (``init_hyper_connections``: the parameters;
    #: ``inference/v2/model_runner._stream_read`` / ``_stream_write``: the
    #: form served).  1: one stream, ``x + y``, and no such parameter
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: float = 30.0
    #: EVA attention (type "eva"; EvaByte): a query sees the positions of its
    #: own window of ``eva_window`` exactly and every *closed* window through
    #: one pooled summary a chunk of ``eva_chunk`` positions, under one softmax
    #: (``layer_types.eva_mix``: the equations).  What a layer caches follows:
    #: the open window's keys and values and a summary row a closed chunk
    eva_window: int = 0
    eva_chunk: int = 0
    #: prediction heads (EvaByte's ``num_pred_heads``): the head is ``[hidden,
    #: pred_heads * vocab_size]``, head ``i`` at position ``t`` predicts token
    #: ``t + 1 + i``, and the logits are float32 (``fp32_logits``).  The serving
    #: programs sample the next token from head 0 and return the others' picks
    pred_heads: int = 1

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.hidden_size // self.n_heads

    @property
    def ffn_size(self) -> int:
        if self.intermediate_size:
            return self.intermediate_size
        if self.activation == "swiglu":
            # llama 8/3 rule rounded to 256
            return ((int(self.hidden_size * 8 / 3) + 255) // 256) * 256
        return 4 * self.hidden_size


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _nrm(cfg: TransformerConfig, k, *shape, s=0.02):
    return (jax.random.normal(k, shape) * s).astype(cfg.dtype)


def init_embed_head(cfg: TransformerConfig, keys) -> Dict[str, Any]:
    """Everything outside the layers: embeddings, final norm, head."""
    H, V = cfg.hidden_size, cfg.vocab_size
    dt = cfg.dtype
    nrm = functools.partial(_nrm, cfg)

    p: Dict[str, Any] = {
        "embed": {"tok": nrm(keys[0], V, H)},
    }
    if not cfg.post_norm:
        p["final_norm"] = {"scale": jnp.ones((H,), dt)}
        if cfg.norm == "layernorm":
            p["final_norm"]["bias"] = jnp.zeros((H,), dt)
        if cfg.embed_norm:  # bloom word_embeddings_layernorm
            p["embed"]["norm"] = {"scale": jnp.ones((H,), dt)}
            if cfg.norm == "layernorm":
                p["embed"]["norm"]["bias"] = jnp.zeros((H,), dt)
    else:
        # post-norm models norm the EMBEDDINGS instead of the final hidden
        p["embed"]["norm"] = {"scale": jnp.ones((H,), dt)}
        if cfg.type_vocab_size > 0:
            p["embed"]["type"] = nrm(jax.random.fold_in(keys[0], 1),
                                     cfg.type_vocab_size, H)
        if cfg.norm == "layernorm":
            p["embed"]["norm"]["bias"] = jnp.zeros((H,), dt)
    if cfg.position == "learned":
        p["embed"]["pos"] = nrm(keys[1], cfg.max_seq_len, H)
    if not cfg.tie_embeddings:
        p["lm_head"] = {"w": nrm(keys[2], H, V * cfg.pred_heads)}
    return p


def init_layer_stack(cfg: TransformerConfig, keys, L: int,
                     attn: bool = True) -> Dict[str, Any]:
    """``L`` layers' parameters stacked ``[L, ...]``: norms, the feed-forward
    part (dense or experts) and, with ``attn``, the attention projections
    (a layer type with another mixer adds its own)."""
    H = cfg.hidden_size
    D, NH, KVH = cfg.head_dim, cfg.n_heads, cfg.kv_heads
    F = cfg.ffn_size
    dt = cfg.dtype
    nrm = functools.partial(_nrm, cfg)

    proj_out_std = 0.02 / math.sqrt(2 * cfg.n_layers)
    layers: Dict[str, Any] = {
        "mlp": {},
        "norm1": {"scale": jnp.ones((L, H), dt)},
    }
    if attn:
        layers["attn"] = {
            "wq": nrm(keys[3], L, H, NH * D),
            "wk": nrm(keys[4], L, H, KVH * D),
            "wv": nrm(keys[5], L, H, KVH * D),
            "wo": nrm(keys[6], L, NH * D, H, s=proj_out_std),
        }
        if cfg.attn_gate:
            layers["attn"]["wg"] = nrm(jax.random.fold_in(keys[3], 1),
                                       L, H, NH * D)
        if cfg.qk_norm:
            layers["attn"]["q_norm"] = jnp.ones((L, D), dt)
            layers["attn"]["k_norm"] = jnp.ones((L, D), dt)
    # falcon-7b/phi share norm1 across both branches; falcon-40b-style
    # parallel blocks (parallel_norms=2) carry separate attn/mlp norms
    if not cfg.parallel_block or cfg.parallel_norms >= 2:
        layers["norm2"] = {"scale": jnp.ones((L, H), dt)}
    if cfg.moe_experts > 0:
        E = cfg.moe_experts
        held = cfg.moe_held_count or E  # an expert share holds fewer
        layers["mlp"]["router"] = nrm(keys[7], L, H, E)
        if cfg.moe_router_bias:
            # drawn, not zero, so that it moves picks; nothing updates it
            layers["mlp"]["router_bias"] = nrm(
                jax.random.fold_in(keys[7], 1), L, E)
        layers["mlp"]["w_gate"] = nrm(keys[8], L, held, H, F)
        layers["mlp"]["w_up"] = nrm(keys[10], L, held, H, F)
        layers["mlp"]["w_down"] = nrm(keys[9], L, held, F, H, s=proj_out_std)
        if cfg.moe_use_residual:  # PR-MoE: dense residual MLP + mixer
            layers["mlp"]["res_w_up"] = nrm(keys[11], L, H, F)
            layers["mlp"]["res_w_down"] = nrm(keys[12], L, F, H, s=proj_out_std)
            layers["mlp"]["coef"] = jnp.zeros((L, H, 2), dt)
        if cfg.moe_shared_expert > 0:  # qwen2-moe: always-on shared expert
            Fs = cfg.moe_shared_expert
            layers["mlp"]["shared_w_gate"] = nrm(keys[13], L, H, Fs)
            layers["mlp"]["shared_w_up"] = nrm(keys[14], L, H, Fs)
            layers["mlp"]["shared_w_down"] = nrm(keys[15], L, Fs, H,
                                                 s=proj_out_std)
            if cfg.moe_shared_gate:
                layers["mlp"]["shared_gate"] = jnp.zeros((L, H, 1), dt)
    elif cfg.activation == "swiglu":
        layers["mlp"]["w_gate"] = nrm(keys[7], L, H, F)
        layers["mlp"]["w_up"] = nrm(keys[8], L, H, F)
        layers["mlp"]["w_down"] = nrm(keys[9], L, F, H, s=proj_out_std)
    else:
        layers["mlp"]["w_up"] = nrm(keys[8], L, H, F)
        layers["mlp"]["w_down"] = nrm(keys[9], L, F, H, s=proj_out_std)
    if attn and (cfg.use_bias or cfg.qkv_bias):
        layers["attn"]["bq"] = jnp.zeros((L, NH * D), dt)
        layers["attn"]["bk"] = jnp.zeros((L, KVH * D), dt)
        layers["attn"]["bv"] = jnp.zeros((L, KVH * D), dt)
    if cfg.use_bias:
        if attn:
            layers["attn"]["bo"] = jnp.zeros((L, H), dt)
        layers["mlp"]["b_up"] = jnp.zeros((L, F), dt)
        layers["mlp"]["b_down"] = jnp.zeros((L, H), dt)
    if cfg.norm == "layernorm":
        layers["norm1"]["bias"] = jnp.zeros((L, H), dt)
        if "norm2" in layers:
            layers["norm2"]["bias"] = jnp.zeros((L, H), dt)
    if cfg.hc_mult > 1:
        layers["hc"] = init_hyper_connections(
            cfg, jax.random.fold_in(keys[0], 58), L)
    return layers


#: a layer's two sublayers, each with hyper-connection parameters of its own
HC_SUBLAYERS = ("mixer", "ffn")


def init_hyper_connections(cfg: TransformerConfig, rng, L: int
                           ) -> Dict[str, Any]:
    """The mixing parameters of ``L`` layers of a residual of ``n =
    cfg.hc_mult`` streams, per sublayer: ``phi [L, n H, 2n + n^2]`` (the
    projection of the normed, flattened residual onto the dynamic part of
    ``H_pre | H_post | H_res``), ``alpha [L, 3]`` (its weight in each of the
    three) and ``b [L, 2n + n^2]`` (the static part, in ``phi``'s column
    order).

    Drawn so that each mechanism decides the output visibly — ``phi`` normal
    ``1 / sqrt(n H)`` over a unit-RMS input gives a projection of unit
    variance, ``alpha`` 1 keeps it whole, the biases of ``H_pre`` and
    ``H_post`` normal 0.5, those of ``H_res`` normal 2.5 — where the paper's
    training initialisation (``alpha`` 0.01, ``b_res`` the identity) would
    make every coefficient a constant a comparison cannot tell from a
    projection left out.  ``H_res``'s argument then spreads by 2.7, and ONE
    Sinkhorn round leaves its rows 0.45 off their sums and the matrix 0.3 from
    the 20th round's; with biases of 0.5 one round lands within an eighth of
    the 20th, which on the chip read as near the reference as the served
    stack's own bfloat16 activations do (``PERF.md`` section 6, PR 58).  The
    served stack is seeded, not trained."""
    n, H = cfg.hc_mult, cfg.hidden_size
    width = 2 * n + n * n
    b_scale = jnp.concatenate([jnp.full((2 * n,), 0.5), jnp.full((n * n,),
                                                                 2.5)])
    out = {}
    for j, part in enumerate(HC_SUBLAYERS):
        k = jax.random.split(jax.random.fold_in(rng, j), 2)
        out[part] = {
            "phi": _nrm(cfg, k[0], L, n * H, width, s=1.0 / math.sqrt(n * H)),
            "alpha": jnp.ones((L, 3), cfg.dtype),
            "b": (jax.random.normal(k[1], (L, width)) * b_scale
                  ).astype(cfg.dtype),
        }
    return out


def init_transformer_params(cfg: TransformerConfig, rng) -> Dict[str, Any]:
    keys = jax.random.split(rng, 16)
    p = init_embed_head(cfg, keys)
    if cfg.layer_types:
        from .layer_types import init_runs

        p["layers"] = init_runs(cfg, rng)
        return p
    if cfg.layer_runs:
        from .layer_types import init_period_runs

        p["layers"] = init_period_runs(cfg, rng)
        return p
    if cfg.layer_period == ("attn",):
        p["layers"] = init_layer_stack(cfg, keys, cfg.n_layers)
        return p
    from .layer_types import layer_type

    n, rem = divmod(cfg.n_layers, len(cfg.layer_period))
    if rem:
        raise ValueError(f"n_layers {cfg.n_layers} is not a whole number of "
                         f"periods {cfg.layer_period}")
    p["layers"] = tuple(
        layer_type(kind).init(cfg, jax.random.fold_in(rng, 100 + j), n)
        for j, kind in enumerate(cfg.layer_period))
    return p


# ---------------------------------------------------------------------------
# partition rules: Megatron TP layout over the "model" axis
# ---------------------------------------------------------------------------
def transformer_partition_rules(cfg: TransformerConfig) -> List[Tuple[str, P]]:
    if cfg.layer_types:
        # a run's leaves by the run's own configuration (a dense run's
        # mlp/w_down has one dim fewer than an expert run's)
        from .layer_types import run_config, stack_runs

        rules: List[Tuple[str, P]] = []
        for j, (_kind, ffn, _n) in enumerate(stack_runs(cfg)):
            rcfg = dataclasses.replace(run_config(cfg, ffn), layer_types=())
            rules += [(rule if rule.startswith(("embed", "lm_head"))
                       else f"layers/{j}/{rule}", spec)
                      for rule, spec in transformer_partition_rules(rcfg)
                      if j == 0 or not rule.startswith(("embed", "lm_head"))]
        return rules
    lead = (None,)  # stacked layer dim
    rules = [
        (r"embed/tok", P(MODEL_AXIS, None)),  # vocab-sharded embedding
        (r"embed/pos", P(None, None)),
        (r"attn/w[qkv]$", P(*lead, None, MODEL_AXIS)),  # column parallel
        (r"attn/b[qkv]$", P(*lead, MODEL_AXIS)),
        (r"attn/wo$", P(*lead, MODEL_AXIS, None)),  # row parallel
        (r"lm_head/w", P(None, MODEL_AXIS)),
    ]
    if cfg.moe_experts > 0:
        rules += [
            (r"mlp/router$", P(*lead, None, None)),  # gate replicated
            (r"mlp/w_(gate|up)$", P(*lead, "expert", None, MODEL_AXIS)),
            (r"mlp/shared_w_(gate|up)$", P(*lead, None, MODEL_AXIS)),
            (r"mlp/shared_w_down$", P(*lead, MODEL_AXIS, None)),
            (r"mlp/shared_gate$", P(*lead, None, None)),
            (r"mlp/w_down$", P(*lead, "expert", MODEL_AXIS, None)),
            (r"mlp/res_w_up$", P(*lead, None, MODEL_AXIS)),  # PR-MoE dense
            (r"mlp/res_w_down$", P(*lead, MODEL_AXIS, None)),
            (r"mlp/coef$", P(*lead, None, None)),
        ]
    else:
        rules += [
            (r"mlp/w_(gate|up)$", P(*lead, None, MODEL_AXIS)),
            (r"mlp/b_up$", P(*lead, MODEL_AXIS)),
            (r"mlp/w_down$", P(*lead, MODEL_AXIS, None)),
        ]
    return rules


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _qwz(cfg: TransformerConfig, w, *tp_entries):
    """ZeRO++ qwZ gather point (reference partition_parameters.py:704): the
    stage-3-sharded weight is int8-quantized on its shard, the CODES cross
    the forced sharding boundary (XLA all-gathers s8 + fp32 block scales,
    ~2x fewer bytes than bf16), and dequantization happens on the gathered
    value right before the matmul.  ``tp_entries``: the weight's TP spec —
    model-axis sharding is preserved through the gather."""
    if not cfg.qwz:
        return w
    from ..parallel.mesh import get_topology
    from ..runtime.zero.zeropp import qwz_gather

    return qwz_gather(w, P(*tp_entries), get_topology().mesh, w.dtype)


def _mm(cfg: TransformerConfig, x, leaf, *tp_entries):
    """``x @ W`` through the weight-access seam: W is either a plain array
    (optionally qwZ-gathered) or a weight-only-quantized {"wq", "scale"}
    dict (reference inference/quantization weight-only path) — then the
    matmul runs the Pallas in-VMEM-dequant kernel."""
    if isinstance(leaf, dict) and "wq" in leaf:
        from ..ops.pallas.wq_matmul import wq_matmul

        return wq_matmul(x, leaf["wq"], leaf["scale"], bits=cfg.wq_bits,
                         group=cfg.wq_group)
    return x @ _qwz(cfg, leaf, *tp_entries)


def _norm(x, scale, bias, kind: str, eps: float):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
        out = xf * scale.astype(jnp.float32)
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def _rope(x, theta: float, positions, pct: float = 1.0):
    """Rotary embedding on [..., S, NH, D]; ``pct`` < 1 rotates only the
    leading fraction of the head dim (phi/gpt-neox partial rotary)."""
    d_full = x.shape[-1]
    d = d_full if pct >= 1.0 else (int(d_full * pct) // 2) * 2
    x_rot, x_pass = x[..., :d], x[..., d:]
    freqs = jnp.exp(-jnp.arange(0, d, 2, dtype=jnp.float32) / d * math.log(theta))
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs  # [B,S,1,d/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = out.astype(x.dtype)
    return out if d == d_full else jnp.concatenate([out, x_pass], axis=-1)


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0,
                  blend: bool = True) -> jnp.ndarray:
    """``[dim / 2]`` float32 rotary frequencies under YaRN: pair ``i`` turns
    at ``theta_i = theta ** (-2i / dim)`` where it makes more than
    ``beta_fast`` rotations over the original context, at ``theta_i /
    factor`` where it makes fewer than ``beta_slow``, and at a linear blend
    between (``f_i = theta_i (1 - r_i) + theta_i / factor r_i``, ``r_i`` the
    ramp from the first pair index to the second).  ``blend`` False: the
    plain table."""
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    th = jnp.exp(-2.0 * i / dim * math.log(theta))
    if not blend or not original_max or factor == 1.0:
        return th

    def pair_of(rotations: float) -> float:
        return dim * math.log(original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    r = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return th * (1.0 - r) + th / factor * r


def rope_interleaved(x, inv_freq, positions):
    """Rotary embedding of pairs ``(2i, 2i + 1)`` of ``x [..., T, NH, d]`` by
    angle ``positions * inv_freq[i]``; ``positions [..., T]``.  The pairs'
    even members come out first and the odd ones second (``[d/2 | d/2]``),
    as the published code leaves them: every query and key goes through
    here, so their products are those of the pairs rotated in place."""
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    ang = positions[..., None, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def alibi_slopes(n_heads: int) -> jnp.ndarray:
    """ALiBi per-head slopes (Press et al.; numerically matches HF bloom's
    build_alibi_tensor): geometric 2^(-8/p) powers for the closest power
    of two p, plus interpolated odd-index slopes for the extra heads."""
    p = 2 ** math.floor(math.log2(n_heads))
    base = [2 ** (-(2 ** -(math.log2(p) - 3)) * (i + 1)) for i in range(p)]
    if p < n_heads:
        base += [2 ** (-(2 ** -(math.log2(2 * p) - 3)) * (i + 1))
                 for i in range(0, 2 * (n_heads - p), 2)]
    return jnp.asarray(base, jnp.float32)


def xla_attention(q, k, v, causal: bool, mask=None, bias=None):
    """Plain attention in XLA: [B, S, NH, D].  fp32 softmax.  ``bias``:
    additive pre-softmax scores bias (e.g. ALiBi), broadcastable to
    [B, NH, S_q, S_k]."""
    d = q.shape[-1]
    scores = jnp.einsum("bsnd,btnd->bnst", q, k).astype(jnp.float32) / math.sqrt(d)
    if bias is not None:
        scores = scores + bias
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        cmask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        scores = jnp.where(cmask, scores, -1e30)
    if mask is not None:  # [B, S_k] padding mask, 1 = keep
        scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,btnd->bsnd", probs, v)


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def flash_on_mesh(q, k, v, causal, mask=None, alibi=None,
                  head_axes=(MODEL_AXIS,)):
    """The Pallas flash kernel on this device's block of the mesh.

    GSPMD cannot partition a Mosaic kernel (lowering refuses anything but
    one device or a fully-manual region), so on a mesh the call runs under
    ``shard_map``: batch over the batch axes, heads over ``head_axes``
    (the model axis; Ulysses adds the sequence axis), the sequence whole.
    Inside an enclosing partial ``shard_map`` (ZeRO overlap wrap, pipe
    stages) only the axes it left automatic are mapped here."""
    from ..ops.pallas.flash_attention import flash_attention
    from ..parallel.mesh import BATCH_AXES, peek_topology

    def call(q, k, v, mask, alibi):
        return flash_attention(q, k, v, causal=causal, segment_mask=mask,
                               alibi_slopes=alibi)

    topo = peek_topology()
    if topo is None or topo.mesh.size == 1:
        return call(q, k, v, mask, alibi)
    from ..utils.jax_compat import shard_map

    # inside an enclosing shard_map the context mesh carries its manual axes
    ctx = jax.sharding.get_abstract_mesh()
    bound = frozenset() if ctx.empty else frozenset(ctx.manual_axes)
    free = frozenset(topo.mesh.axis_names) - bound
    if not free:  # already fully manual: the arrays ARE the local block
        return call(q, k, v, mask, alibi)
    b_ax = tuple(a for a in BATCH_AXES if a in free) or None
    h_ax = tuple(a for a in head_axes if a in free) or None
    qkv = P(b_ax, None, h_ax, None)
    return shard_map(
        call, ctx if bound else topo.mesh,
        in_specs=(qkv, qkv, qkv, None if mask is None else P(b_ax, None),
                  None if alibi is None else P(h_ax)),
        out_specs=qkv, check_vma=False, axis_names=free)(q, k, v, mask, alibi)


# GQA-native (reads grouped kv heads via index maps) and builds the ALiBi
# bias from block indices (no [S, S] materialization)
flash_on_mesh.handles_gqa = True
flash_on_mesh.handles_alibi = True


def _pick_attn(cfg: TransformerConfig) -> Callable:
    impl = cfg.attn_impl
    if impl == "auto":
        # on the chip "auto" can only mean the kernel; a kernel that does
        # not lower fails the step instead of yielding to xla_attention
        from ..utils.platform import on_tpu

        impl = "flash" if on_tpu() else "xla"
    if impl == "flash":
        return flash_on_mesh
    if cfg.position == "alibi":
        # ulysses/ring carry no bias input
        if impl != "xla":
            from ..utils.logging import warning_once

            warning_once(f"attn_impl={impl!r} has no ALiBi bias input; "
                         "using the XLA attention path")
        return xla_attention
    if impl == "ulysses":
        from ..sequence.ulysses import ulysses_attention

        return ulysses_attention
    if impl == "ring":
        from ..sequence.ring_attention import ring_attention

        if cfg.ring_compression is not None:
            import functools

            return functools.partial(ring_attention,
                                     compression=cfg.ring_compression)
        return ring_attention
    if impl == "fpdt":
        from ..sequence.fpdt import fpdt_attention

        def _chunk(s: int, cap: int = 1024) -> int:
            # largest divisor of s that is <= cap (gcd(s, cap) degenerates to
            # 1 for s coprime with cap, e.g. odd sequence lengths)
            return max(d for d in range(1, min(s, cap) + 1) if s % d == 0)

        return lambda q, k, v, causal, mask=None: fpdt_attention(
            q, k, v, causal=causal, mask=mask,
            chunk_size=_chunk(q.shape[1]))
    return xla_attention


def head_projection(cfg: TransformerConfig, h, leaf, bias, heads: int,
                    dim: int, pinned: bool = False):
    """``h [..., H] @ W [H, heads * dim]`` (``+ bias`` unless None) viewed by
    head: ``[..., heads, dim]``.

    ``pinned`` (the paged programs, ``inference/v2/model_runner.py``): the
    product stays a plain ``[rows, H] x [H, heads * dim]`` one behind an
    optimization barrier.  Left free, XLA:TPU folds the reshape into the
    product — the heads become a spatial dimension of a convolution, which
    reads the weight ``[heads, dim, H]``: a slice of the stack into a buffer
    and a transposition of all of it (33.5 MB a layer at Mistral-7B's
    ``wq``) on every decode step.  Pinned, the weight is read once, as it is
    stored, inside the product, like ``wo``.  The same operands and float32
    accumulation either way; a differentiated program is left free."""
    y = _mm(cfg, h, leaf, None, MODEL_AXIS)
    if pinned:
        y = jax.lax.optimization_barrier(y)
    if bias is not None:
        y = y + bias
    return y.reshape(*h.shape[:-1], heads, dim)


def attn_qkv(cfg: TransformerConfig, layer, x, positions,
             pinned: bool = False):
    """norm1 + QKV projection + rope — shared by the training forward and the
    paged inference programs (inference/v2/model_runner.py), which alone pin
    the products (``head_projection``).

    x: [B, T, H] -> q [B, T, NH, D], k/v [B, T, KVH, D] (pre-GQA-repeat).
    """
    NH, KVH, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    a = layer["attn"]
    qb = cfg.use_bias or cfg.qkv_bias
    # post-norm: projections read the RAW residual stream; the norm comes
    # after the residual add in _block
    with region("norm"):
        h = x if cfg.post_norm else _norm(
            x, layer["norm1"]["scale"], layer["norm1"].get("bias"), cfg.norm,
            cfg.norm_eps)
    with region("attn_qkv"):
        # (``+ 0`` without a bias: the training programs' text as it was)
        q, k, v = (head_projection(cfg, h, a[w], a[b] if qb else 0, n, D,
                                   pinned=pinned)
                   for w, b, n in (("wq", "bq", NH), ("wk", "bk", KVH),
                                   ("wv", "bv", KVH)))
        if cfg.qk_norm:
            q = _norm(q, a["q_norm"], None, "rmsnorm", cfg.norm_eps)
            k = _norm(k, a["k_norm"], None, "rmsnorm", cfg.norm_eps)
        if cfg.position == "rope":
            q = _rope(q, cfg.rope_theta, positions, cfg.rotary_pct)
            k = _rope(k, cfg.rope_theta, positions, cfg.rotary_pct)
    return q, k, v


def mlp_block(cfg: TransformerConfig, layer, x, training: bool = True):
    """norm2 + FFN (dense swiglu/gelu or MoE) with residual; returns
    (x + ffn(norm(x)), aux_loss).  Shared by training and inference paths.

    parallel_block (falcon-7b/phi) shares ONE input layernorm between the
    attention and MLP branches — there is no norm2 in those checkpoints
    (XLA CSEs the duplicate _norm with the one inside attn_qkv).  Falcon's
    new decoder architecture (40b/180b) runs parallel branches with
    SEPARATE norms (cfg.parallel_norms == 2: ln_attn/ln_mlp -> norm1/norm2)."""
    h, aux = mlp_delta(cfg, layer, x, training)
    return x + h, aux


def mlp_delta(cfg: TransformerConfig, layer, x, training: bool = True):
    """``mlp_block`` without its residual add: (ffn(norm(x)), aux_loss) — what
    a residual of several streams writes back through its own mixing
    (``inference/v2/model_runner._stream_write``)."""
    if cfg.parallel_block and cfg.parallel_norms < 2:
        ln = layer["norm1"]
    else:
        ln = layer["norm2"]
    with region("norm"):
        h = _norm(x, ln["scale"], ln.get("bias"), cfg.norm, cfg.norm_eps)
    return _ffn(cfg, layer, h, training)


def _ffn(cfg: TransformerConfig, layer, h, training: bool = True):
    """The raw FFN (no norm, no residual) — mlp_block wraps it pre-norm;
    the post-norm block applies norm2 AFTER the residual add instead."""
    m = layer["mlp"]
    aux = jnp.asarray(0.0, jnp.float32)
    if cfg.moe_experts > 0:
        from ..moe.sharded_moe import MoEConfig, moe_ffn

        moe_cfg = MoEConfig(num_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                            capacity_factor=cfg.moe_capacity_factor,
                            aux_loss_coef=cfg.moe_aux_coef,
                            drop_tokens=cfg.moe_drop_tokens,
                            norm_topk=cfg.moe_norm_topk,
                            held_first=cfg.moe_held_first,
                            held_count=cfg.moe_held_count,
                            scoring=cfg.moe_scoring,
                            routed_scale=cfg.moe_routed_scale,
                            ep_dispatch=cfg.moe_ep_dispatch,
                            ep_a2a_compression=cfg.moe_a2a_compression)
        moe_out, aux = moe_ffn(h, m["router"], m, moe_cfg,
                               activation=cfg.activation, training=training,
                               router_bias=m.get("router_bias"))
        if cfg.moe_shared_expert > 0:
            # qwen2-moe: the shared expert sees every token; its output is
            # gated by a per-token sigmoid scalar and ADDED to the routed
            # output (reference qwen_v2_moe model implementation)
            with region("shared_expert"):
                sh = _mm(cfg, jax.nn.silu(
                    _mm(cfg, h, m["shared_w_gate"], None, MODEL_AXIS))
                    * _mm(cfg, h, m["shared_w_up"], None, MODEL_AXIS),
                    m["shared_w_down"], MODEL_AXIS, None)
                if cfg.moe_shared_gate:
                    sgate = jax.nn.sigmoid(
                        (h @ m["shared_gate"]).astype(jnp.float32))
                    sh = (sgate * sh.astype(jnp.float32)
                          ).astype(moe_out.dtype)
                moe_out = moe_out + sh
        if cfg.moe_use_residual:
            # PR-MoE (reference moe/layer.py use_residual): dense MLP beside
            # the MoE, mixed by a learned per-token 2-way coefficient
            act = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
            with region("shared_expert"):
                res = _mm(cfg, act(_mm(cfg, h, m["res_w_up"], None,
                                       MODEL_AXIS)),
                          m["res_w_down"], MODEL_AXIS, None)  # plain dense MLP
                coef = jax.nn.softmax((h @ m["coef"]).astype(jnp.float32), -1)
                h = (moe_out * coef[..., 0:1] + res * coef[..., 1:2]).astype(moe_out.dtype)
        else:
            h = moe_out
        return h, aux
    with region("mlp"):
        return _dense_ffn(cfg, m, h), aux


def _dense_ffn(cfg: TransformerConfig, m, h):
    """The dense feed-forward part of ``_ffn``."""
    if cfg.activation == "swiglu":
        h = _mm(cfg, jax.nn.silu(_mm(cfg, h, m["w_gate"], None, MODEL_AXIS))
                * _mm(cfg, h, m["w_up"], None, MODEL_AXIS),
                m["w_down"], MODEL_AXIS, None)
    else:
        # "gelu" = tanh approximation (HF gelu_new: gpt2/phi); "gelu_exact"
        # = erf form (HF gelu: opt/falcon) — importing one as the other is
        # a systematic ~3e-3 per-activation drift
        if cfg.activation == "relu":
            act = jax.nn.relu
        elif cfg.activation == "gelu_exact":
            act = functools.partial(jax.nn.gelu, approximate=False)
        else:
            act = jax.nn.gelu
        h = _mm(cfg, act(_mm(cfg, h, m["w_up"], None, MODEL_AXIS)
                         + (m["b_up"] if cfg.use_bias else 0)),
                m["w_down"], MODEL_AXIS, None)
        if cfg.use_bias:
            h = h + m["b_down"]
    return h


def attn_mixer(cfg: TransformerConfig, layer, x, positions, mask, attn_fn):
    """The attention mixer over whole sequences, [B, S, H] -> the delta the
    block adds to its residual stream (norm1, q/k/v, ``attn_fn``, output
    projection).  The "attn" layer type's training form."""
    B, S, H = x.shape
    NH, KVH, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    a = layer["attn"]

    q, k, v = attn_qkv(cfg, layer, x, positions)
    with region("attn_glue"):
        attn = _attend(cfg, attn_fn, q, k, v, positions, mask)
        attn = attn.reshape(B, S, NH * D)
    with region("attn_out"):
        return _mm(cfg, attn, a["wo"], MODEL_AXIS, None) \
            + (a["bo"] if cfg.use_bias else 0)


def _attend(cfg: TransformerConfig, attn_fn, q, k, v, positions, mask):
    """``attn_fn`` over ``attn_qkv``'s heads, with what it does not handle
    itself made for it first (the GQA repeat, the ALiBi bias)."""
    NH, KVH = cfg.n_heads, cfg.kv_heads
    if not getattr(attn_fn, "handles_gqa", False):
        # GQA-aware impls (flash) read each kv head once through the kernel
        # index map; everyone else gets the materialized repeat
        k = _repeat_kv(k, NH // KVH)
        v = _repeat_kv(v, NH // KVH)
    if cfg.position == "alibi":
        # score(i, j) += -slope_h * (i - j): linear distance penalty
        # (softmax-equivalent to HF bloom's key-indexed formulation,
        # which differs only by a per-row constant)
        if getattr(attn_fn, "handles_alibi", False):
            # flash: bias built in-kernel from block indices
            return attn_fn(q, k, v, cfg.causal, mask, alibi=alibi_slopes(NH))
        rel = (positions[:, None, :, None]
               - positions[:, None, None, :]).astype(jnp.float32)
        return attn_fn(q, k, v, cfg.causal, mask,
                       bias=-alibi_slopes(NH)[None, :, None, None] * rel)
    return attn_fn(q, k, v, cfg.causal, mask)


def _block(cfg: TransformerConfig, x, layer, positions, mask, attn_fn):
    """One transformer block, [B, S, H] -> [B, S, H]."""
    attn_delta = attn_mixer(cfg, layer, x, positions, mask, attn_fn)
    if cfg.parallel_block:
        # falcon/phi: attention and MLP both read the block input
        out, aux = mlp_block(cfg, layer, x)
        return out + attn_delta, aux
    if cfg.post_norm:
        # BERT/original-transformer ordering: norm AFTER each residual add
        with region("norm"):
            h = _norm(x + attn_delta, layer["norm1"]["scale"],
                      layer["norm1"].get("bias"), cfg.norm, cfg.norm_eps)
        ffn, aux = _ffn(cfg, layer, h)
        with region("norm"):
            out = _norm(h + ffn, layer["norm2"]["scale"],
                        layer["norm2"].get("bias"), cfg.norm, cfg.norm_eps)
        return out, aux
    return mlp_block(cfg, layer, x + attn_delta)


def _one_stream(cfg: TransformerConfig, what: str) -> None:
    if cfg.hc_mult > 1:
        raise NotImplementedError(
            f"{what} carries one residual stream: a residual of "
            f"hc_mult={cfg.hc_mult} streams (hyper-connections) is served "
            "only, through the paged programs (inference/v2/model_runner)")


def transformer_forward(cfg: TransformerConfig, params, input_ids, mask=None,
                        token_type_ids=None, with_act_stats=False,
                        with_moe_counters=False):
    """[B, S] int tokens -> ([B, S, H] final hidden states, aux loss).

    A stack described by ``cfg.layer_types`` runs each layer as its type
    defines it (``layer_types.run_stack``); ``with_moe_counters`` then adds,
    last, the int32 counters of its expert-share layers ``[expert layers,
    held + 3]`` (None without a share).

    ``with_act_stats`` (numerics observatory): additionally return a
    stacked ``[L, 3]`` per-layer activation-health side output
    (``telemetry.numerics.activation_stats`` rows over each block's
    output) as a third element.  Computed OUTSIDE the (possibly
    overlap-wrapped, possibly remat'd) block call, so the overlap hook's
    shard_map specs and the remat policy are untouched."""
    _one_stream(cfg, "the training forward")
    with region("embed"):
        x = params["embed"]["tok"][input_ids]
    B, S = input_ids.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    with region("embed"):
        if cfg.position == "learned":
            x = x + params["embed"]["pos"][:S][None]
        if "type" in params["embed"]:  # BERT segment embeddings
            tt = (token_type_ids if token_type_ids is not None
                  else jnp.zeros_like(input_ids))
            x = x + params["embed"]["type"][tt]
        if "norm" in params["embed"]:  # post-norm models norm the embeddings
            x = _norm(x, params["embed"]["norm"]["scale"],
                      params["embed"]["norm"].get("bias"), cfg.norm,
                      cfg.norm_eps)
    attn_fn = _pick_attn(cfg)
    if with_act_stats:
        # lazy: telemetry must stay an optional dependency of the model code
        from ..telemetry.numerics import activation_stats as _act_row

    if cfg.layer_types:
        from .layer_types import run_stack

        x, aux, act, moe = run_stack(cfg, params["layers"], x, positions,
                                     mask, attn_fn, with_act_stats)
        with region("head"):
            hidden = _norm(x, params["final_norm"]["scale"],
                           params["final_norm"].get("bias"), cfg.norm,
                           cfg.norm_eps)
        return ((hidden, aux) + ((act,) if with_act_stats else ())
                + ((moe,) if with_moe_counters else ()))
    if with_moe_counters:
        raise ValueError("moe counters ride a stack of cfg.layer_types")

    plan = getattr(cfg, "overlap_plan", None)
    # compressed-overlap comm state (runtime/zero/overlap.py): the engine
    # injects per-bucket gslot/eslot stacks under this params key; they
    # ride the layer scan as extra xs so each trip sees its layer's
    # slices.  Absent (eval / exact overlap) the wrap runs comm-free.
    comm_state = (params.get("_overlap_comm")
                  if isinstance(params, dict) else None)
    if plan is None or getattr(plan, "compression", None) is None:
        comm_state = None
    if plan is None:
        block = lambda x, layer, comm_s=None: _block(cfg, x, layer, positions, mask, attn_fn)  # noqa: E731
    else:
        # ZeRO overlap wrap (runtime/zero/overlap.py): the block runs in
        # a shard_map over the data axis, where each layer-bucket's grad
        # reduce is an explicit collective issued inside the backward
        # loop (and, at stage 3, the param gathers are explicit at the
        # body top — prefetched one layer ahead by the 2x unroll below)
        wrapped = plan.wrap_block(
            lambda x, pos, m, layer: _block(cfg, x, layer, pos, m, attn_fn),
            has_mask=mask is not None)
        block = lambda x, layer, comm_s=None: wrapped(x, positions, mask, layer, comm_s)  # noqa: E731
    if cfg.remat:
        block = jax.checkpoint(block, policy=get_policy(cfg.remat_policy))

    # (the layer loop under ``stack``: the loop's own slices of the stacked
    # weights and updates of the residuals have no other home)
    if cfg.scan_layers:
        # stage-3 manual prefetch (zero3_prefetch, engine-set per trace):
        # unroll the layer scan 2x so each trip holds TWO independent
        # gather->compute chains — layer i+1's param all-gather has no
        # data dependence on layer i's compute and the latency-hiding
        # scheduler overlaps them.  Unlike carrying gathered params across
        # iterations (tried: the carry becomes a bwd residual and
        # materializes EVERY gathered layer, defeating stage 3), unroll
        # keeps residuals sharded and per-layer — same memory, real slack.
        unroll = 2 if cfg.zero3_prefetch else 1
        if comm_state is not None:
            def scan_body(carry, xs):
                layer, comm_s = xs
                y, aux = block(carry, layer, comm_s)
                return y, ((aux, _act_row(y)) if with_act_stats else aux)

            with region("stack"):
                x, ys = jax.lax.scan(scan_body, x,
                                     (params["layers"], comm_state),
                                     unroll=unroll)
        else:
            def scan_body(carry, layer):
                y, aux = block(carry, layer)
                return y, ((aux, _act_row(y)) if with_act_stats else aux)

            with region("stack"):
                x, ys = jax.lax.scan(scan_body, x, params["layers"],
                                     unroll=unroll)
        auxs, act = ys if with_act_stats else (ys, None)
        aux = jnp.sum(auxs)
    else:
        aux = jnp.asarray(0.0, jnp.float32)
        act_rows = []
        for i in range(cfg.n_layers):
            with region("stack"):
                layer = jax.tree_util.tree_map(lambda a: a[i],
                                               params["layers"])
                if comm_state is not None:
                    comm_s = jax.tree_util.tree_map(lambda a: a[i],
                                                    comm_state)
                    x, a = block(x, layer, comm_s)
                else:
                    x, a = block(x, layer)
            aux = aux + a
            if with_act_stats:
                act_rows.append(_act_row(x))
        act = jnp.stack(act_rows) if with_act_stats else None

    if cfg.post_norm:
        # each block already ends in norm2; a final norm would re-normalize
        return (x, aux, act) if with_act_stats else (x, aux)
    with region("head"):
        hidden = _norm(x, params["final_norm"]["scale"],
                       params["final_norm"].get("bias"), cfg.norm,
                       cfg.norm_eps)
    return (hidden, aux, act) if with_act_stats else (hidden, aux)


def logits_fn(cfg: TransformerConfig, params, hidden):
    with region("head"):
        if cfg.tie_embeddings:
            return hidden @ params["embed"]["tok"].T
        w = params["lm_head"]["w"]
        if cfg.pred_heads > 1:
            # several prediction heads side by side, logits in float32
            return jnp.dot(hidden, w, preferred_element_type=jnp.float32)
        if isinstance(w, dict):  # weight-only quantized head
            out = _mm(cfg, hidden, w)
        else:
            out = hidden @ w
        b = params["lm_head"].get("b")  # phi-style biased head
        return out if b is None else out + b


def causal_lm_loss(cfg: TransformerConfig, params, batch, rng=None):
    """Next-token cross entropy.  batch: dict(input_ids, optional labels,
    optional attention_mask) or a raw [B, S] token array.

    With ``cfg.numerics_act_stats`` set (engine-set per trace), returns
    ``(loss, act)`` where ``act`` is the forward's stacked ``[L, 3]``
    per-layer activation-health side output — the engine carries it as
    an extra fused-step output for the numerics observatory."""
    if isinstance(batch, dict):
        ids = batch["input_ids"]
        labels = batch.get("labels", ids)
        mask = batch.get("attention_mask")
    else:
        ids, labels, mask = batch, batch, None
    with_act = bool(getattr(cfg, "numerics_act_stats", False))
    # expert-share counters (cfg.moe_counters, engine-set per trace): the
    # loss then returns (loss, act or None, counters)
    with_moe = bool(getattr(cfg, "moe_counters", False))
    if with_moe:
        *fwd, moe = transformer_forward(cfg, params, ids, mask,
                                        with_act_stats=with_act,
                                        with_moe_counters=True)
    else:
        fwd = transformer_forward(cfg, params, ids, mask,
                                  with_act_stats=with_act)
    hidden, aux = fwd[0], fwd[1]
    act = fwd[2] if with_act else None

    def _out(loss):
        if with_moe:
            return loss, act, moe
        return (loss, act) if with_act else loss

    with region("loss"):
        return _out(_lm_loss(cfg, params, hidden, labels, mask) + aux)


def _lm_loss(cfg: TransformerConfig, params, hidden, labels, mask):
    """The next-token cross entropy of ``causal_lm_loss`` from the final
    hidden states on."""
    hidden = hidden[:, :-1]
    targets = labels[:, 1:]
    m = mask[:, 1:].astype(jnp.float32) if mask is not None else None

    if cfg.loss_chunk and hidden.shape[1] > cfg.loss_chunk:
        if hidden.shape[1] % cfg.loss_chunk == 0:
            # ALST-style tiled logits+loss (reference TiledFusedLogitsLoss,
            # runtime/sequence_parallel/ulysses_sp.py:960): never materialize
            # the full [B, S, V] logits — scan over sequence chunks, remat
            # inside
            nll_sum, cnt = _tiled_nll(cfg, params, hidden, targets, m,
                                      cfg.loss_chunk)
            return nll_sum / jnp.maximum(cnt, 1.0)
        from ..utils.logging import warning_once

        warning_once(
            f"loss_chunk={cfg.loss_chunk} does not divide sequence "
            f"{hidden.shape[1]} (seq_len-1); falling back to materializing "
            f"full [B, S, V] logits — pick a loss_chunk dividing seq_len-1")

    logits = logits_fn(cfg, params, hidden)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = nll_pick(logp, targets)
    if m is not None:
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


def nll_pick(logp: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """-logp[target] as a one-hot contraction, NOT take_along_axis: the
    gather's transpose is a vocab-dim scatter-add the SPMD partitioner can
    only reshard by full rematerialization under sequence sharding; the
    contraction transposes to a broadcast
    multiply, which shards cleanly.  XLA fuses the one-hot (iota+compare)
    into the reduction — no materialized [.., V] buffer."""
    onehot = jax.nn.one_hot(targets, logp.shape[-1], dtype=logp.dtype)
    return -jnp.sum(logp * onehot, axis=-1)


def _tiled_nll(cfg: TransformerConfig, params, hidden, targets, mask, chunk: int):
    B, S, H = hidden.shape
    n = S // chunk
    h_c = hidden.reshape(B, n, chunk, H).transpose(1, 0, 2, 3)
    t_c = targets.reshape(B, n, chunk).transpose(1, 0, 2)
    m_c = (mask.reshape(B, n, chunk).transpose(1, 0, 2)
           if mask is not None else jnp.ones((n, B, chunk), jnp.float32))

    @jax.checkpoint
    def chunk_nll(h, t, m):
        logits = logits_fn(cfg, params, h)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.sum(nll_pick(logp, t) * m), jnp.sum(m)

    def body(carry, xs):
        s, c = carry
        ds, dc = chunk_nll(*xs)
        return (s + ds, c + dc), None

    (nll_sum, cnt), _ = jax.lax.scan(
        body, (jnp.asarray(0.0, jnp.float32), jnp.asarray(0.0, jnp.float32)),
        (h_c, t_c, m_c))
    return nll_sum, cnt


# ---------------------------------------------------------------------------
# KV-cache decode path (inference)
# ---------------------------------------------------------------------------
def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int, dtype=None):
    """[L, B, max_len, KVH, D] per k/v (reference inference KV handling,
    csrc/transformer/inference kv path / inference/v2 blocked KV)."""
    dtype = dtype or cfg.dtype
    shape = (cfg.n_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "length": jnp.zeros((), jnp.int32)}


def _block_decode(cfg: TransformerConfig, x, layer, k_cache, v_cache, position):
    """One block for one new token slice x: [B, T, H] attending to the cache
    (which already contains this token's k/v after update).  Returns
    (y, new_k, new_v) where new_k/new_v are this layer's updated cache."""
    B, T, H = x.shape
    NH, KVH, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    a = layer["attn"]

    positions = position[:, None] + jnp.arange(T)[None, :]
    q, k, v = attn_qkv(cfg, layer, x, positions)

    # write new k/v into the cache at [position, position+T)
    def upd(cache, new):
        return jax.lax.dynamic_update_slice(
            cache, new.astype(cache.dtype), (0, position[0], 0, 0))

    with region("attn_glue"):
        k_cache = upd(k_cache, k)
        v_cache = upd(v_cache, v)

        kk = _repeat_kv(k_cache, NH // KVH)
        vv = _repeat_kv(v_cache, NH // KVH)
        S = kk.shape[1]
        scores = jnp.einsum("btnd,bsnd->bnts", q, kk).astype(jnp.float32) / math.sqrt(D)
        # causal vs cache: token t may see cache slots <= position + t
        limit = (position[:, None, None, None] + jnp.arange(T)[None, None, :, None])
        slot = jnp.arange(S)[None, None, None, :]
        if cfg.position == "alibi":
            scores = scores - alibi_slopes(NH)[None, :, None, None] \
                * (limit - slot).astype(jnp.float32)
        scores = jnp.where(slot <= limit, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        attn = jnp.einsum("bnts,bsnd->btnd", probs, vv).reshape(B, T, NH * D)
    with region("attn_out"):
        attn_delta = _mm(cfg, attn, a["wo"], MODEL_AXIS, None) \
            + (a["bo"] if cfg.use_bias else 0)
    if cfg.parallel_block:
        out, _ = mlp_block(cfg, layer, x, training=False)
        return out + attn_delta, k_cache, v_cache
    out, _ = mlp_block(cfg, layer, x + attn_delta, training=False)
    return out, k_cache, v_cache


def forward_with_cache(cfg: TransformerConfig, params, input_ids, cache,
                       position):
    """Prefill or decode: run [B, T] tokens against/into the cache starting
    at ``position`` ([B] int32, same value per batch row for dense decode).
    Returns (logits [B, T, V], new_cache)."""
    if cfg.post_norm:
        raise NotImplementedError(
            "post_norm models (BERT-style encoders) have no KV-cache "
            "generative path; use transformer_forward + mlm_logits")
    _one_stream(cfg, "the dense-cache forward")
    B, T = input_ids.shape
    with region("embed"):
        x = params["embed"]["tok"][input_ids]
        if cfg.position == "learned":
            pos_idx = position[0] + jnp.arange(T)
            x = x + jnp.take(params["embed"]["pos"], pos_idx, axis=0)[None]
        if "norm" in params["embed"]:  # bloom word_embeddings_layernorm
            x = _norm(x, params["embed"]["norm"]["scale"],
                      params["embed"]["norm"].get("bias"), cfg.norm,
                      cfg.norm_eps)

    def scan_body(carry, inputs):
        x = carry
        layer, k_c, v_c = inputs
        y, k_c, v_c = _block_decode(cfg, x, layer, k_c, v_c, position)
        return y, (k_c, v_c)

    with region("stack"):
        x, (new_k, new_v) = jax.lax.scan(
            scan_body, x, (params["layers"], cache["k"], cache["v"]))
    with region("head"):
        hidden = _norm(x, params["final_norm"]["scale"],
                       params["final_norm"].get("bias"), cfg.norm,
                       cfg.norm_eps)
    logits = logits_fn(cfg, params, hidden)
    new_cache = {"k": new_k, "v": new_v, "length": position[0] + T}
    return logits, new_cache


def param_count(cfg: TransformerConfig) -> int:
    """Total STORED parameter count: embeddings (tied or not), attention,
    and ALL experts' MLPs — what weight-bytes math needs.
    ``flops_per_token`` instead prices only the ACTIVE (top-k) params."""
    if cfg.layer_types:
        from .layer_types import stack_matmul_params

        return (cfg.vocab_size * cfg.hidden_size
                * (1 if cfg.tie_embeddings else 2)
                + stack_matmul_params(cfg, active=False))
    mlp = cfg.hidden_size * cfg.ffn_size * (3 if cfg.activation == "swiglu" else 2)
    if cfg.moe_experts > 0:
        mlp = mlp * cfg.moe_experts + cfg.hidden_size * cfg.moe_experts
        if cfg.moe_use_residual:
            mlp += 2 * cfg.hidden_size * cfg.ffn_size + 2 * cfg.hidden_size
        if cfg.moe_shared_expert > 0:
            mlp += 3 * cfg.hidden_size * cfg.moe_shared_expert + cfg.hidden_size
    return (cfg.vocab_size * cfg.hidden_size * (1 if cfg.tie_embeddings else 2)
            + cfg.n_layers * (
                cfg.hidden_size * cfg.head_dim * (cfg.n_heads + 2 * cfg.kv_heads)
                + cfg.n_heads * cfg.head_dim * cfg.hidden_size
                + mlp))


def flops_per_token(cfg: TransformerConfig, seq_len: int) -> float:
    """Training (forward + backward) model FLOPs per token, by the rules
    ``benchmark/roofline.py`` states, so the engine's MFU gauge and the
    benchmark's ``train_mfu_pct`` are one count: a ``[m, k] x [k, n]``
    matmul is ``2*m*k*n``, backward twice the forward, recomputation never
    credited; the embedding look-up is a gather; the head is one
    ``[hidden, vocab]`` matmul tied or not; causal attention does half the
    work of full attention.

    For MoE layers the matmul weights count the router plus only the
    ``top_k`` experts a token actually flows through — total expert params
    would overstate MFU by experts/top_k on the MLP term (mixtral 8x: 4x).
    A stack of ``layer_types`` is counted layer by layer
    (``layer_types.stack_matmul_params``); an expert share counts the picks
    it expects, ``top_k * held / experts`` a token.
    """
    if cfg.layer_types:
        from .layer_types import layer_type, stack_matmul_params

        keys = seq_len / 2 if cfg.causal else seq_len
        attends = sum(bool(layer_type(k).pages(cfg)) for k in cfg.layer_types)
        return 3.0 * (2.0 * (cfg.hidden_size * cfg.vocab_size
                             + stack_matmul_params(cfg, active=True))
                      + attends * 2 * 2 * keys * cfg.n_heads * cfg.head_dim)
    mlp = cfg.hidden_size * cfg.ffn_size * (3 if cfg.activation == "swiglu" else 2)
    if cfg.moe_experts > 0:
        mlp = mlp * cfg.moe_top_k + cfg.hidden_size * cfg.moe_experts
        if cfg.moe_use_residual:  # PR-MoE: dense res MLP + 2-way mixer
            mlp += 2 * cfg.hidden_size * cfg.ffn_size + 2 * cfg.hidden_size
        if cfg.moe_shared_expert > 0:  # always-on shared expert + its gate
            mlp += 3 * cfg.hidden_size * cfg.moe_shared_expert + cfg.hidden_size
    matmul = (cfg.hidden_size * cfg.vocab_size
              + cfg.n_layers * (
                  cfg.hidden_size * cfg.head_dim * (cfg.n_heads + 2 * cfg.kv_heads)
                  + cfg.n_heads * cfg.head_dim * cfg.hidden_size
                  + mlp))
    keys = seq_len / 2 if cfg.causal else seq_len  # mean keys a token attends
    attn = cfg.n_layers * 2 * 2 * keys * cfg.n_heads * cfg.head_dim
    return 3.0 * (2.0 * matmul + attn)
