"""Layer types: a kind of layer defined once.

A type says what a layer of its kind holds (``init``), what its mixer computes
over whole sequences (``mix``: the form the training forward runs, and
differentiates), which mixer the serving programs run for it (``mixer``: the
key under which ``inference/v2/model_runner`` keeps that mixer's chunk and
decode forms), and what it keeps per sequence between calls: K/V pages in the
paged pool (``kv_pages``) and/or fixed-size state in per-sequence slots
(``state``).  The cache manager sizes its pools from these, so a model with
fewer attention layers than layers gets a pool with fewer layers.

A stack names its layers one of two ways.  ``TransformerConfig.layer_period``
is a repeated *period* of types (served: ``model_runner._scan_layers``); every
layer's feed-forward part is the configuration's (``mlp_block``: dense or
experts).  ``TransformerConfig.layer_types`` is the published list, one type a
layer, behind a prologue: the first ``dense_layers`` layers carry a dense
feed-forward part, the others the configuration's.  Such a stack is cut into
*runs* of layers alike in mixer and feed-forward part (``stack_runs``), each
run's parameters stacked and scanned; ``run_stack`` is the training forward's
layer loop (``transformer_forward``), and each layer is ``x + mix(x)`` then
``mlp_block`` — the definitions here and no other copy.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from .transformer import (MODEL_AXIS, TransformerConfig, _mm, _nrm, _norm,
                          attn_mixer, init_layer_stack, mlp_block)


@dataclasses.dataclass(frozen=True)
class LayerType:
    name: str
    #: (cfg, rng, n) -> the parameters of n layers stacked [n, ...]
    init: Callable[[TransformerConfig, Any, int], Dict[str, Any]]
    mixer: str
    kv_pages: bool
    #: cfg -> {pool leaf: (per-sequence shape, dtype or None for the served
    #: dtype)}: state kept in slots, one per decode row, beside the pages
    state: Callable[[TransformerConfig], Dict[str, Tuple[tuple, Any]]]
    #: (cfg, layer, x [B, S, H], positions, mask, attn_fn) -> what the mixer
    #: adds to the residual stream, over whole sequences and differentiable
    mix: Callable[..., Any] = None


def _init_attn(cfg: TransformerConfig, rng, n: int) -> Dict[str, Any]:
    return init_layer_stack(cfg, jax.random.split(rng, 16), n)


def _init_kda(cfg: TransformerConfig, rng, n: int) -> Dict[str, Any]:
    keys = jax.random.split(rng, 32)
    layers = init_layer_stack(cfg, keys, n, attn=False)
    H, NH, D, R = (cfg.hidden_size, cfg.kda_heads, cfg.kda_head_dim,
                   cfg.kda_rank)
    N, dt = NH * D, cfg.dtype

    def nrm(i, *shape, s=0.02):
        return _nrm(cfg, keys[16 + i], *shape, s=s)

    # a decay rate exp(A_log) in [1, 16] times a step softplus(dt_bias) in
    # [1e-3, 1e-1] (log-uniform), as state-space layers are initialised
    step = jnp.exp(jax.random.uniform(keys[30], (n, N))
                   * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    layers["kda"] = {
        "wq": nrm(0, n, H, N), "wk": nrm(1, n, H, N), "wv": nrm(2, n, H, N),
        # depthwise causal convolution over time, q | k | v channels
        "conv": nrm(3, n, cfg.kda_conv, 3 * N, s=0.5),
        "f_down": nrm(4, n, H, R), "f_up": nrm(5, n, R, N),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "a_log": jnp.log(jax.random.uniform(
            keys[31], (n, NH), minval=1.0, maxval=16.0)).astype(dt),
        "w_beta": nrm(6, n, H, NH),
        "g_down": nrm(7, n, H, R), "g_up": nrm(8, n, R, N),
        "o_norm": jnp.ones((n, D), dt),
        "wo": nrm(9, n, N, H, s=0.02 / math.sqrt(2 * cfg.n_layers)),
    }
    return layers


def _kda_state(cfg: TransformerConfig) -> Dict[str, Tuple[tuple, Any]]:
    NH, D = cfg.kda_heads, cfg.kda_head_dim
    return {
        # S^T per head, value-major (ops/pallas/kda.py), float32 always
        "kda_s": ((NH, D, D), jnp.float32),
        # the last conv - 1 rows of the q | k | v projections
        "kda_conv": ((cfg.kda_conv - 1, 3 * NH * D), None),
    }


def _kda_mix(*_a, **_k):
    raise NotImplementedError(
        "a delta-rule linear-attention layer (type 'kda') is served only: "
        "training it needs the backward of the delta-rule scan "
        "(ops/pallas/kda.py: dstpu_kda_chunk), which does not exist")


def _init_conv(cfg: TransformerConfig, rng, n: int) -> Dict[str, Any]:
    keys = jax.random.split(rng, 32)
    layers = init_layer_stack(cfg, keys, n, attn=False)
    H = cfg.hidden_size
    layers["conv"] = {
        # B | C | u, no bias
        "w_in": _nrm(cfg, keys[16], n, H, 3 * H),
        # one causal kernel of conv_taps per channel, the last tap on the
        # token itself; drawn at 1 / sqrt(taps) so that the mixer's output
        # has the scale of its input
        "kernel": _nrm(cfg, keys[17], n, cfg.conv_taps, H,
                       s=1.0 / math.sqrt(cfg.conv_taps)),
        "w_out": _nrm(cfg, keys[18], n, H, H,
                      s=0.02 / math.sqrt(2 * cfg.n_layers)),
    }
    return layers


def _conv_mix(cfg: TransformerConfig, layer, x, positions, mask, attn_fn):
    """The gated short convolution (LFM2): ``B, C, u = split(z W_in)``,
    ``c_t = sum_j k_j * (B u)_{t - (taps-1) + j}`` with zeros before the
    sequence's start, ``(C c) W_out``.  No activation; the taps are shifted
    multiply-adds that XLA fuses with the gates."""
    del positions, attn_fn
    if mask is not None:
        raise NotImplementedError(
            "the convolution mixer takes whole sequences: an attention_mask "
            "(padding inside a sequence) has no form here")
    c = layer["conv"]
    with jax.named_scope("conv"):  # its operations carry the name in a trace
        z = _norm(x, layer["norm1"]["scale"], layer["norm1"].get("bias"),
                  cfg.norm, cfg.norm_eps)
        b, g, u = jnp.split(_mm(cfg, z, c["w_in"], None, MODEL_AXIS), 3,
                            axis=-1)
        v = b * u
        taps, S = cfg.conv_taps, x.shape[1]
        vp = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
        k = c["kernel"].astype(v.dtype)
        conv = sum(k[j] * vp[:, j:j + S] for j in range(taps))
        return _mm(cfg, g * conv, c["w_out"], MODEL_AXIS, None)


def _conv_state(cfg: TransformerConfig) -> Dict[str, Tuple[tuple, Any]]:
    # the last taps - 1 rows of B u: what a decode step would convolve with
    return {"conv_tail": ((cfg.conv_taps - 1, cfg.hidden_size), None)}


ATTN = LayerType("attn", _init_attn, mixer="attn", kv_pages=True,
                 state=lambda cfg: {}, mix=attn_mixer)
KDA = LayerType("kda", _init_kda, mixer="kda", kv_pages=False,
                state=_kda_state, mix=_kda_mix)
#: trained only: the paged programs have no form of this mixer yet; the type
#: declares the state a serving PR has to keep
CONV = LayerType("conv", _init_conv, mixer="conv", kv_pages=False,
                 state=_conv_state, mix=_conv_mix)
_TYPES = {"attn": ATTN, "kda": KDA, "conv": CONV}


def layer_type(kind: str) -> LayerType:
    try:
        return _TYPES[kind]
    except KeyError:
        raise ValueError(f"unknown layer type {kind!r}; known: "
                         f"{sorted(_TYPES)}") from None


def period_types(cfg: TransformerConfig) -> Tuple[LayerType, ...]:
    if cfg.layer_types:
        raise NotImplementedError(
            "a stack of cfg.layer_types is trained, not served: the paged "
            "programs (inference/v2/model_runner) run a repeated "
            "layer_period and have no form of the 'conv' mixer")
    return tuple(layer_type(k) for k in cfg.layer_period)


# ------------------------------------------------- a stack of cfg.layer_types
def stack_runs(cfg: TransformerConfig) -> Tuple[Tuple[str, str, int], ...]:
    """``cfg.layer_types`` as runs ``(type, "dense" | "experts", layers)`` of
    consecutive layers alike in both: the prologue's ``dense_layers`` layers
    are dense, the others the configuration's."""
    if len(cfg.layer_types) != cfg.n_layers:
        raise ValueError(f"layer_types names {len(cfg.layer_types)} layers, "
                         f"n_layers is {cfg.n_layers}")
    if cfg.post_norm or cfg.parallel_block:
        raise ValueError("a stack of layer_types is pre-norm and sequential")
    runs = []
    for i, kind in enumerate(cfg.layer_types):
        layer_type(kind)
        ffn = "experts" if cfg.moe_experts and i >= cfg.dense_layers \
            else "dense"
        if runs and runs[-1][:2] == [kind, ffn]:
            runs[-1][2] += 1
        else:
            runs.append([kind, ffn, 1])
    return tuple(tuple(r) for r in runs)


def run_config(cfg: TransformerConfig, ffn: str) -> TransformerConfig:
    """The configuration a run's layers read: a dense run of a model with
    experts sees no experts and the prologue's width."""
    if ffn == "experts" or not cfg.moe_experts:
        return cfg
    return dataclasses.replace(cfg, moe_experts=0,
                               intermediate_size=cfg.dense_ffn_size or None)


def init_runs(cfg: TransformerConfig, rng) -> Tuple[Dict[str, Any], ...]:
    return tuple(
        layer_type(kind).init(run_config(cfg, ffn),
                              jax.random.fold_in(rng, 100 + j), n)
        for j, (kind, ffn, n) in enumerate(stack_runs(cfg)))


def run_stack(cfg: TransformerConfig, stack, x, positions, mask, attn_fn,
              with_act_stats: bool = False):
    """The layer loop of ``transformer_forward`` over a stack of
    ``cfg.layer_types``: each run scanned (a run of one layer unrolled), each
    layer ``x + mix(x)`` then ``mlp_block``.  Returns (x, the float auxiliary
    losses summed, ``[L, 3]`` activation rows or None, the int32 counters of
    the expert-share layers ``[expert layers, held + 3]`` or None)."""
    if with_act_stats:
        from ..telemetry.numerics import activation_stats as act_row
    aux = jnp.asarray(0.0, jnp.float32)
    acts, counters = [], []
    for (kind, ffn, n), layers in zip(stack_runs(cfg), stack):
        rcfg, mix = run_config(cfg, ffn), layer_type(kind).mix

        def block(x, layer, rcfg=rcfg, mix=mix):
            h = x + mix(rcfg, layer, x, positions, mask, attn_fn)
            return mlp_block(rcfg, layer, h)

        if cfg.remat:
            block = jax.checkpoint(block, policy=getattr(
                jax.checkpoint_policies, cfg.remat_policy, None))

        def body(carry, layer, block=block):
            y, a = block(carry, layer)
            return y, ((a, act_row(y)) if with_act_stats else a)

        if n == 1 or not cfg.scan_layers:
            rows = []
            for i in range(n):
                x, y = body(x, jax.tree_util.tree_map(lambda a: a[i], layers))
                rows.append(y)
            ys = jax.tree_util.tree_map(lambda *r: jnp.stack(r), *rows)
        else:
            x, ys = jax.lax.scan(body, x, layers)
        a, act = ys if with_act_stats else (ys, None)
        if with_act_stats:
            acts.append(act)
        # an expert share has no auxiliary loss: its counters take the slot
        if ffn == "experts" and cfg.moe_held_count:
            counters.append(a)
        else:
            aux = aux + jnp.sum(a)
    return (x, aux, jnp.concatenate(acts) if acts else None,
            jnp.concatenate(counters) if counters else None)


def layers_of(cfg: TransformerConfig, mixer: str) -> int:
    """How many of the model's layers run ``mixer``."""
    types = period_types(cfg)
    return (cfg.n_layers // len(types)) * sum(t.mixer == mixer for t in types)


def state_leaves(cfg: TransformerConfig) -> Dict[str, Tuple[int, tuple, Any]]:
    """{pool leaf: (layers that keep it, per-sequence shape, dtype)} over the
    whole model; empty for a model that keeps only pages."""
    out: Dict[str, Tuple[int, tuple, Any]] = {}
    for t in set(period_types(cfg)):
        for name, (shape, dtype) in t.state(cfg).items():
            out[name] = (layers_of(cfg, t.mixer), shape, dtype)
    return out


def stack_matmul_params(cfg: TransformerConfig, active: bool) -> float:
    """Matmul weights over the layers of ``cfg.layer_types``: those a token
    meets (``active``: the router and the picks an expert layer expects to
    compute here, ``top_k * held / experts``) or those stored."""
    H, D = cfg.hidden_size, cfg.head_dim
    mixers = {"attn": H * D * (cfg.n_heads + 2 * cfg.kv_heads)
              + cfg.n_heads * D * H, "conv": 4 * H * H}
    total = 0.0
    for kind, ffn, n in stack_runs(cfg):
        r = run_config(cfg, ffn)
        mlp = H * r.ffn_size * (3 if r.activation == "swiglu" else 2)
        if ffn == "experts":
            held = cfg.moe_held_count or cfg.moe_experts
            mlp = mlp * (cfg.moe_top_k * held / cfg.moe_experts if active
                         else held) + H * cfg.moe_experts
        total += n * (mixers[kind] + mlp)
    return total
