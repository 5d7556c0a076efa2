"""Layer types: a kind of layer defined once.

A stack is a repeated *period* of layer types (``TransformerConfig.
layer_period``).  A type says what a layer of its kind holds (``init``), which
mixer the serving programs run for it (``mixer``: the key under which
``inference/v2/model_runner`` keeps that mixer's chunk and decode forms), and
what it keeps per sequence between calls: K/V pages in the paged pool
(``kv_pages``) and/or fixed-size state in per-sequence slots (``state``).
Every type's feed-forward part is the configuration's (``mlp_block``: dense or
experts).  The cache manager sizes its pools from these, so a model with
fewer attention layers than layers gets a pool with fewer layers.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from .transformer import TransformerConfig, _nrm, init_layer_stack


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


ATTN = LayerType("attn", _init_attn, mixer="attn", kv_pages=True,
                 state=lambda cfg: {})
KDA = LayerType("kda", _init_kda, mixer="kda", kv_pages=False,
                state=_kda_state)
_TYPES = {"attn": ATTN, "kda": KDA}


def layer_type(kind: str) -> LayerType:
    try:
        return _TYPES[kind]
    except KeyError:
        raise ValueError(f"unknown layer type {kind!r}; known: "
                         f"{sorted(_TYPES)}") from None


def period_types(cfg: TransformerConfig) -> Tuple[LayerType, ...]:
    return tuple(layer_type(k) for k in cfg.layer_period)


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
