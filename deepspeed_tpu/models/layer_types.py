"""Layer types: a kind of layer defined once.

A type says what a layer of its kind holds (``init``), what its mixer computes
over whole sequences (``mix``: the form the training forward runs, and
differentiates), which mixer the serving programs run for it (``mixer``: the
key under which ``inference/v2/model_runner`` keeps that mixer's chunk and
decode forms), and what it keeps per sequence between calls: pages in the
paged pool (``pages``: the pool leaves it keeps and each leaf's width a token
— keys and values of ``kv_heads * head_dim`` for an attention layer, one
latent row for a latent-attention layer) and/or fixed-size state in
per-sequence slots (``state``).  The cache manager sizes its pools from these,
so a model with fewer attention layers than layers gets a pool with fewer
layers, and a model that caches a latent gets no K/V pool at all.  What a
grouped-query layer keeps follows its type, not the model: a ``gqa_full``
layer keeps pages of its own K/V head count with keys wider than values, a
``gqa_window`` layer a ring of another head count (``gqa_shape``).

A stack names its layers one of three ways.  ``TransformerConfig.layer_period``
is a repeated *period* of types and ``layer_runs`` a sequence of runs, each a
repeated period (served: ``model_runner._scan_layers`` scans run by run, and
``served_runs`` reads both as runs); every layer's feed-forward part is the
configuration's (``mlp_block``: dense or experts), but for the runs that make
the first ``dense_layers`` layers of ``layer_runs``, which carry a dense part
of width ``dense_ffn_size`` (``served_run_configs``).
``TransformerConfig.layer_types`` is the published list, one type a
layer, behind a prologue: the first ``dense_layers`` layers carry a dense
feed-forward part, the others the configuration's.  Such a stack is cut into
*runs* of layers alike in mixer and feed-forward part (``stack_runs``), each
run's parameters stacked and scanned; ``run_stack`` is the training forward's
layer loop (``transformer_forward``), and each layer is ``x + mix(x)`` then
``mlp_block`` — the definitions here and no other copy.

A type also answers the serving engine: which of its features the type's
cache cannot serve and why (``refuses``), how a sequence's pages grow over it
(``pooled``, ``ring``: ``inference/v2/ragged.page_rows``) and what a step over
it counts (``touches``).  The engine reads the folds below and names no mixer.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas.mla_attention import latent_pages_per_block
from ..ops.pallas.paged_attention import n_blocks, pages_per_block
from ..runtime.activation_checkpointing.checkpointing import get_policy
from ..telemetry.regions import region
from .transformer import (MODEL_AXIS, TransformerConfig, _mm, _nrm, _norm,
                          _rope, attn_mixer, attn_qkv, init_layer_stack,
                          mlp_block, yarn_inv_freq)


#: the serving engine's features a cache may be unable to serve, one name each
FEATURES = ("prefix_cache", "whole_prompt_prefill", "speculation", "kv_quant",
            "kv_tier", "decode_horizon", "bundle_export", "bundle_import",
            "block_generation")


@dataclasses.dataclass(frozen=True)
class Touch:
    """One count of what a step over a type's cache touches, ``count(n, g)``
    with ``g`` the pool's geometry (``page_size``, ``block_pages`` of the
    decode kernel, ``long_row_tokens``).  ``at`` says when, and what ``n`` is:
    ``decode`` — the rows each decode row attends (0: not active), added to
    the step a decode call; ``last_chunk`` — the same for the one row of a
    prompt's last chunk (0 on an earlier one), on the step and the chunk's
    span; ``chunk`` — the cached rows a chunk attends, on its span alone;
    ``held`` — ``[2, sequences in slots]``, the positions each has cached and
    the pages it holds, set at the step's end.  ``cumulative``: summed in
    ``decode_stats()`` too."""
    name: str
    count: Callable[[Any, Any], int]
    at: Tuple[str, ...] = ("decode",)
    cumulative: bool = False


def _total(n, g) -> int:
    return int(n.sum())


@dataclasses.dataclass(frozen=True)
class LayerType:
    name: str
    #: (cfg, rng, n) -> the parameters of n layers stacked [n, ...]
    init: Callable[[TransformerConfig, Any, int], Dict[str, Any]]
    mixer: str
    #: cfg -> {pool leaf: values a token keeps in it}: the type's page
    #: format, each leaf ``[layers, pages + 1, page_size, width]`` in the
    #: served dtype; empty for a type that keeps no pages
    pages: Callable[[TransformerConfig], Dict[str, int]]
    #: cfg -> {pool leaf: (per-sequence shape, dtype or None for the served
    #: dtype)}: state kept in slots, one per decode row, beside the pages
    state: Callable[[TransformerConfig], Dict[str, Tuple[tuple, Any]]]
    #: (cfg, layer, x [B, S, H], positions, mask, attn_fn) -> what the mixer
    #: adds to the residual stream, over whole sequences and differentiable
    mix: Callable[..., Any] = None
    #: the serving forms take and return the values that cross layers beside
    #: the pools (``model_runner._scan_layers``: the stack's layer index, the
    #: memory a state-space layer leaves for the gated memory units)
    crosses: bool = False
    #: {feature of ``FEATURES``: why this type's cache cannot serve it}, the
    #: engine's words (``{kinds}``: the stack's state leaves)
    refuses: Dict[str, str] = dataclasses.field(default_factory=dict,
                                                hash=False, compare=False)
    #: cfg -> (window, chunk) where pages hold an open window's rows and a
    #: pooled row a chunk (``ragged.EvaRows``); None: a row a position
    pooled: Optional[Callable[[TransformerConfig], Tuple[int, int]]] = None
    #: cfg -> positions of a ring in the slot, which the decode kernel reads
    #: as whole pages; None: no ring
    ring: Optional[Callable[[TransformerConfig], int]] = None
    #: the layers after it read its pages and run for a prompt's last token
    #: only: an earlier chunk stops at its K/V write
    cross_decoder: bool = False
    #: (page_size, row values, itemsize) -> pages a block of its decode kernel
    block_pages: Callable[[int, int, int], int] = pages_per_block
    #: cfg -> what a step over a layer of this type counts
    touches: Callable[[TransformerConfig], Tuple[Touch, ...]] = \
        lambda cfg: ()


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
    with region("norm"):
        z = _norm(x, layer["norm1"]["scale"], layer["norm1"].get("bias"),
                  cfg.norm, cfg.norm_eps)
    with region("conv_mixer"):
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


def _init_mla(cfg: TransformerConfig, rng, n: int) -> Dict[str, Any]:
    keys = jax.random.split(rng, 32)
    layers = init_layer_stack(cfg, keys, n, attn=False)
    H, NH, R = cfg.hidden_size, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    layers["attn"] = {
        "w_dq": _nrm(cfg, keys[16], n, H, cfg.q_lora_rank),
        "q_norm": jnp.ones((n, cfg.q_lora_rank), cfg.dtype),
        "w_uq": _nrm(cfg, keys[17], n, cfg.q_lora_rank, NH * (dn + dr)),
        # the latent and, beside it, the one rotary key all heads share
        "w_dkv": _nrm(cfg, keys[18], n, H, R + dr),
        "kv_norm": jnp.ones((n, R), cfg.dtype),
        # per head [k_nope | v]
        "w_ukv": _nrm(cfg, keys[19], n, R, NH * (dn + dv)),
        "wo": _nrm(cfg, keys[20], n, NH * dv, H,
                   s=0.02 / math.sqrt(2 * cfg.n_layers)),
    }
    return layers


def latent_width(cfg: TransformerConfig) -> int:
    """Lanes a token's ``[latent | rotary key]`` row takes in the pool: whole
    lane tiles of 128 (256 + 64 -> 384).  The device lays a minor dimension
    of 320 out as 384 lanes whatever it is declared, and the decode kernel
    can only cut a page out of an operand whose rows are whole tiles; the
    lanes past ``kv_lora_rank + qk_rope_head_dim`` hold zeros and enter no
    product."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def _kv_pages(cfg: TransformerConfig) -> Dict[str, int]:
    width = cfg.kv_heads * cfg.head_dim
    return {"k": width, "v": width}


def _no_pages(cfg: TransformerConfig) -> Dict[str, int]:
    return {}


def _served_only(kind: str, what: str):
    def mix(*_a, **_k):
        raise NotImplementedError(
            f"a layer of type {kind!r} is served only: training it needs "
            f"{what}, which does not exist")
    return mix


_NO_SCAN_BWD = ("the backward of the selective scan (ops/pallas/ssm.py: "
                "dstpu_ssm_chunk)")


def _init_mamba(cfg: TransformerConfig, rng, n: int) -> Dict[str, Any]:
    keys = jax.random.split(rng, 32)
    layers = init_layer_stack(cfg, keys, n, attn=False)
    H, DI, N, K, R = (cfg.hidden_size, cfg.ssm_inner, cfg.ssm_state,
                      cfg.ssm_conv, cfg.ssm_dt_rank)
    dt = cfg.dtype

    def nrm(i, *shape, s=0.02):
        return _nrm(cfg, keys[16 + i], *shape, s=s)

    # a decay rate -A = 1 .. N along the state index times a step
    # softplus(b_dt) in [1e-3, 1e-1] (log-uniform), as Mamba initialises
    step = jnp.exp(jax.random.uniform(keys[30], (n, DI))
                   * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    layers["mamba"] = {
        "w_in": nrm(0, n, H, 2 * DI),                       # u | z
        # one causal kernel of ssm_conv taps per channel, the last tap on
        # the token itself, at 1 / sqrt(taps) as the other convolutions here
        "conv": nrm(1, n, K, DI, s=1.0 / math.sqrt(K)),
        "conv_b": nrm(2, n, DI),
        "w_x": nrm(3, n, DI, R + 2 * N),                    # delta | B | C
        "w_dt": nrm(4, n, R, DI),
        "b_dt": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        # [state, channel]: the layout of the state the kernels keep
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None],
            (n, N, DI)).astype(dt),
        "d": jnp.ones((n, DI), dt),
        "w_out": nrm(5, n, DI, H, s=0.02 / math.sqrt(2 * cfg.n_layers)),
    }
    return layers


def _mamba_state(cfg: TransformerConfig) -> Dict[str, Tuple[tuple, Any]]:
    return {
        # state-major [state, channel]: channels on the lanes, float32 always
        "ssm_s": ((cfg.ssm_state, cfg.ssm_inner), jnp.float32),
        # the last conv - 1 rows of u
        "ssm_conv": ((cfg.ssm_conv - 1, cfg.ssm_inner), None),
    }


def _diff_params(cfg: TransformerConfig, keys, n: int, layers) -> None:
    """What the differential form adds to ``layers["attn"]``: the output
    bias, the four vectors of the learned part of lambda and the scale of
    the RMSNorm over a pair's 2 * head_dim values.  Biases are drawn, not
    zero, so that a comparison sees them."""
    D, a = cfg.head_dim, layers["attn"]
    for i, name in enumerate(("lam_q1", "lam_k1", "lam_q2", "lam_k2")):
        a[name] = _nrm(cfg, keys[20 + i], n, D, s=0.1)
    a["sub_norm"] = jnp.ones((n, 2 * D), cfg.dtype)
    a["bo"] = _nrm(cfg, keys[24], n, cfg.hidden_size)
    for i, name in enumerate(("bq", "bk", "bv")):
        if name in a:
            a[name] = _nrm(cfg, keys[25 + i], *a[name].shape)


def _init_dattn(cfg: TransformerConfig, rng, n: int) -> Dict[str, Any]:
    keys = jax.random.split(rng, 32)
    layers = init_layer_stack(cfg, keys, n)
    _diff_params(cfg, keys, n, layers)
    return layers


def _init_xattn(cfg: TransformerConfig, rng, n: int) -> Dict[str, Any]:
    keys = jax.random.split(rng, 32)
    layers = init_layer_stack(cfg, keys, n, attn=False)
    H, ND = cfg.hidden_size, cfg.n_heads * cfg.head_dim
    layers["attn"] = {
        "wq": _nrm(cfg, keys[3], n, H, ND),
        "bq": jnp.zeros((n, ND), cfg.dtype),
        "wo": _nrm(cfg, keys[6], n, ND, H,
                   s=0.02 / math.sqrt(2 * cfg.n_layers)),
    }
    _diff_params(cfg, keys, n, layers)
    return layers


def _init_gmu(cfg: TransformerConfig, rng, n: int) -> Dict[str, Any]:
    keys = jax.random.split(rng, 32)
    layers = init_layer_stack(cfg, keys, n, attn=False)
    H, DI = cfg.hidden_size, cfg.ssm_inner
    layers["gmu"] = {
        "w_in": _nrm(cfg, keys[16], n, H, DI),
        "w_out": _nrm(cfg, keys[17], n, DI, H,
                      s=0.02 / math.sqrt(2 * cfg.n_layers)),
    }
    return layers


def _window_state(cfg: TransformerConfig) -> Dict[str, Tuple[tuple, Any]]:
    # a ring of the last sliding_window positions' keys and values: position
    # t at row t % window (no position encoding: the order is not needed)
    shape = (cfg.sliding_window, cfg.kv_heads * cfg.head_dim)
    return {"win_k": (shape, None), "win_v": (shape, None)}


_NO_BLOCK_FORM = ("the block program has a form of the 'attn' mixer over K "
                  "and V pages alone, not of recurrent state, rings or a "
                  "latent")
#: what state in a sequence's slot cannot be served with: each would answer
#: wrongly (a cached or exported page says nothing of the state that goes
#: with it; a rejected draft cannot be rolled out of a state)
_STATE_REFUSES = {
    "prefix_cache": "this model keeps recurrent state or a window's ring in "
                    "its slots ({kinds}) and a cached page carries none of "
                    "it; serve it with the prefix cache off",
    "whole_prompt_prefill": "a model with recurrent state or a window's ring "
                            "({kinds}) is prefilled through the chunk "
                            "program, which carries both from chunk to "
                            "chunk; set prefill_chunk > 0",
    "speculation": "paged_verify cannot roll a rejected draft out of "
                   "recurrent state or a window's ring ({kinds})",
    "block_generation": _NO_BLOCK_FORM,
    **dict.fromkeys(("bundle_export", "bundle_import"),
                    "a bundle holds pages, and this model keeps recurrent "
                    "state too ({kinds})"),
}
_RING_REFUSES = dict(
    _STATE_REFUSES,
    kv_quant="the window layers read keys and values from a window's ring "
             "({kinds}) as they are stored (the differential layers in pairs "
             "of heads, a key wider than its value split); serve it with "
             "kv_quant off")


def _pages_held(name: str) -> Touch:
    """``name``: the positions of the pages the sequences in slots hold."""
    return Touch(name, lambda n, g: g.page_size * int(n[1].sum()),
                 at=("held",))


def _window_touch(name: str):
    """``name``: the cached positions a window layer's decode reads, a ring
    holding ``sliding_window`` of a context at most."""
    return lambda cfg: (Touch(name, lambda n, g: int(
        np.minimum(n, cfg.sliding_window).sum())),)


ATTN = LayerType("attn", _init_attn, mixer="attn", pages=_kv_pages,
                 state=lambda cfg: {}, mix=attn_mixer)
KDA = LayerType("kda", _init_kda, mixer="kda", pages=_no_pages,
                state=_kda_state,
                mix=_served_only("kda", "the backward of the delta-rule scan "
                                 "(ops/pallas/kda.py: dstpu_kda_chunk)"),
                refuses=_STATE_REFUSES)
#: trained only: the paged programs have no form of this mixer yet; the type
#: declares the state a serving PR has to keep
CONV = LayerType("conv", _init_conv, mixer="conv", pages=_no_pages,
                 state=_conv_state, mix=_conv_mix)
#: Phi-4-mini-flash (SambaY), served only.  A Mamba-1 selective state-space
#: layer: float32 state and a convolution tail in the sequence's slot; the
#: last one's scan output is the memory the gated memory units read
MAMBA = LayerType("mamba", _init_mamba, mixer="mamba", pages=_no_pages,
                  state=_mamba_state, mix=_served_only("mamba", _NO_SCAN_BWD),
                  crosses=True, refuses=_STATE_REFUSES,
                  # the rows whose state the step kernel moves
                  touches=lambda cfg: (Touch("ssm_rows", lambda n, g: int(
                      (n > 0).sum())),))
#: differential attention over the last ``sliding_window`` positions, kept as
#: a ring in the sequence's slot: no pages, no page accounting
SWA = LayerType("swa", _init_dattn, mixer="swa", pages=_no_pages,
                state=_window_state,
                mix=_served_only("swa", "a window mask in the flash backward"),
                crosses=True, refuses=_RING_REFUSES,
                ring=lambda cfg: cfg.sliding_window,
                touches=_window_touch("window_tokens"))
#: differential attention over the whole context: the one layer that writes
#: pages, which the cross-attention layers after it read
DATTN = LayerType("dattn", _init_dattn, mixer="dattn", pages=_kv_pages,
                  state=lambda cfg: {},
                  mix=_served_only("dattn", "the differential form in the "
                                   "training forward"), crosses=True,
                  cross_decoder=True,
                  # the visible pages of the one pool layer (once, however
                  # many layers read them) and the rows the cross-decoder runs
                  touches=lambda cfg: (
                      Touch("shared_kv_pages", lambda n, g: int(
                          (-(-n // g.page_size)).sum()),
                          at=("decode", "last_chunk")),
                      Touch("xdec_rows", lambda n, g: int((n > 0).sum()),
                            at=("decode", "last_chunk"))))
#: a gated memory unit: the last state-space layer's scan output, gated
GMU = LayerType("gmu", _init_gmu, mixer="gmu", pages=_no_pages,
                state=lambda cfg: {}, mix=_served_only("gmu", _NO_SCAN_BWD),
                crosses=True)
#: differential attention of its own queries to the pages ``dattn`` wrote
XATTN = LayerType("xattn", _init_xattn, mixer="xattn", pages=_no_pages,
                  state=lambda cfg: {},
                  mix=_served_only("xattn", "the differential form in the "
                                   "training forward"), crosses=True)
#: latent attention (MLA), served only.  A token leaves one row in the pool:
#: the normalised ``kv_lora_rank``-wide latent and, beside it, the rotary key
#: all heads share, already rotated — no K pool, no V pool.  A chunk expands
#: keys and values from the window's latents; a decode row attends the
#: latents themselves, the up-projections absorbed into its query and output
MLA = LayerType("mla", _init_mla, mixer="mla",
                pages=lambda cfg: {"latent": latent_width(cfg)},
                state=lambda cfg: {},
                mix=_served_only("mla", "the latent form in the training "
                                 "forward (and a cut of this family that "
                                 "fits a chip at 16 B a parameter)"),
                # (the prefix cache, copy-on-write and bundles work over
                # latent pages as over any page: a cached page holds its
                # positions' latents and rotated keys, valid for every
                # request that shares the prefix)
                refuses={
                    "whole_prompt_prefill":
                        "a latent-attention model is prefilled through the "
                        "chunk program, which expands keys and values from "
                        "the window's latents; whole-prompt prefill has no "
                        "form of the 'mla' mixer; set prefill_chunk > 0",
                    "speculation": "paged_verify has no form of the 'mla' "
                                   "mixer (a window of queries against "
                                   "latent pages)",
                    "kv_quant": "int8 codes and per-head scales exist for K "
                                "and V pools; a latent pool has no heads to "
                                "scale, serve it with kv_quant off",
                    "kv_tier": "the host tier's page format is K and V; a "
                               "latent pool is not spilled",
                    "block_generation": _NO_BLOCK_FORM},
                block_pages=latent_pages_per_block,
                # once, not a layer (the kernel's bytes are x layers): the
                # cached positions the decode kernel reads, and the positions
                # of the blocks it walks for them, the masked ones of a row's
                # last block included
                touches=lambda cfg: (
                    Touch("latent_kv_tokens", _total, cumulative=True),
                    Touch("latent_block_slots", lambda n, g: int(
                        g.block_pages * g.page_size * n_blocks(
                            n, g.page_size, g.block_pages).sum()),
                        cumulative=True),
                    Touch("ctx_tokens", _total, at=("chunk",)),
                    _pages_held("latent_tokens_in_use")))


@dataclasses.dataclass(frozen=True)
class GqaShape:
    """What a grouped-query layer of one type computes with and keeps: K/V
    heads, a key head's and a value head's width, the leading lanes of a head
    that are rotated and the rotary base, the window (0: the whole context)
    and whether a sink joins the softmax; its query heads, the share of a
    head it was asked to rotate (what ``_rope`` cuts ``rot`` from) and, where
    its rotary table is YaRN's, ``(factor, original positions, beta_fast,
    beta_slow, the factor on the cos and sin of the rotated lanes)`` (None:
    the plain table)."""
    kv_heads: int
    k_dim: int
    v_dim: int
    rot: int
    theta: float
    window: int
    sink: bool
    heads: int
    pct: float
    yarn: Optional[Tuple[float, int, float, float, float]] = None

    @property
    def split(self) -> int:
        """Rotary lanes of a head that the cache keeps apart from its plain
        lanes (``ops/pallas/paged_attention.split_keys``): ``rot`` where a
        head has both parts, else 0 — the row is the heads side by side."""
        return self.rot if 0 < self.rot < self.k_dim else 0

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.k_dim)

    @property
    def k_width(self) -> int:
        return self.kv_heads * self.k_dim

    @property
    def v_width(self) -> int:
        return self.kv_heads * self.v_dim

    def rotate(self, x, positions):
        """``x [B, T, heads, k_dim]`` at ``positions [B, T]`` with the first
        ``rot`` lanes of every head rotated by the type's table, half-split
        pairs: ``_rope``'s plain table, or under YaRN the angles of
        ``yarn_inv_freq`` with cos and sin times the attention factor."""
        if self.yarn is None:
            return _rope(x, self.theta, positions, self.pct)
        *table, mult = self.yarn
        ang = positions[:, :, None, None].astype(jnp.float32) \
            * yarn_inv_freq(self.rot, self.theta, *table)
        cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
        x1, x2 = jnp.split(x[..., :self.rot].astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1).astype(x.dtype)
        return jnp.concatenate([out, x[..., self.rot:]], axis=-1)


def _rot_lanes(head_dim: int, pct: float) -> int:
    return head_dim if pct >= 1.0 else (int(head_dim * pct) // 2) * 2


def gqa_shape(cfg: TransformerConfig, kind: str) -> GqaShape:
    """The shape of the ``gqa_full`` or the ``gqa_window`` layers of ``cfg``:
    defined here once, read by the types' parameters, pages and rings and by
    the serving forms."""
    D = cfg.head_dim
    if kind == "gqa_full":
        yarn = (cfg.rope_factor, cfg.rope_original_max, cfg.rope_beta_fast,
                cfg.rope_beta_slow, cfg.rope_attention_factor or 1.0) \
            if cfg.rope_factor > 1.0 else None
        return GqaShape(cfg.kv_heads, D, cfg.v_head_dim or D,
                        _rot_lanes(D, cfg.rotary_pct), cfg.rope_theta, 0,
                        False, cfg.n_heads, cfg.rotary_pct, yarn)
    pct = cfg.swa_rotary_pct or cfg.rotary_pct
    return GqaShape(cfg.swa_kv_heads or cfg.kv_heads, D, cfg.v_head_dim or D,
                    _rot_lanes(D, pct), cfg.swa_rope_theta or cfg.rope_theta,
                    cfg.sliding_window, cfg.swa_sink,
                    cfg.swa_n_heads or cfg.n_heads, pct)


def _init_gqa(kind: str):
    def init(cfg: TransformerConfig, rng, n: int) -> Dict[str, Any]:
        keys = jax.random.split(rng, 32)
        layers = init_layer_stack(cfg, keys, n, attn=False)
        sh = gqa_shape(cfg, kind)
        H, NH = cfg.hidden_size, sh.heads
        layers["attn"] = {
            "wq": _nrm(cfg, keys[16], n, H, NH * sh.k_dim),
            "wk": _nrm(cfg, keys[17], n, H, sh.k_width),
            "wv": _nrm(cfg, keys[18], n, H, sh.v_width),
            "wo": _nrm(cfg, keys[19], n, NH * sh.v_dim, H,
                       s=0.02 / math.sqrt(2 * cfg.n_layers)),
        }
        if sh.sink:
            # drawn, not zero, and wide, so that a comparison sees them:
            # beside a window of near-equal scores a sink of b takes e^b /
            # (window + e^b) of a row, a tenth of it at b = 2.7 of 128 keys
            layers["attn"]["sink"] = _nrm(cfg, keys[20], n, NH, s=2.0)
        if cfg.attn_head_gate:
            # over a normed input of H values a draw of 0.02 gives the gate's
            # argument a spread of 0.02 sqrt(H): heads differ, and a dropped
            # gate moves what a comparison sees
            layers["attn"]["wg"] = _nrm(cfg, keys[21], n, H, NH)
        return layers
    return init


def _gqa_pages(cfg: TransformerConfig) -> Dict[str, int]:
    sh = gqa_shape(cfg, "gqa_full")
    return {"k": sh.k_width, "v": sh.v_width}


def _gqa_ring(cfg: TransformerConfig) -> Dict[str, Tuple[tuple, Any]]:
    # position t at row t % window, keys stored rotated: the order is not
    # needed, only which rows are live
    sh = gqa_shape(cfg, "gqa_window")
    return {"win_k": ((sh.window, sh.k_width), None),
            "win_v": ((sh.window, sh.v_width), None)}


_NO_GQA_BWD = ("a key wider than its value, a sink and a window in the flash "
               "backward (ops/pallas/flash_attention.py: forward only)")
#: grouped-query attention whose cache follows the type (MiMo-V2-Flash),
#: served only.  A full layer: pages of ``n_kv_heads`` heads, a key row
#: ``[the heads' plain lanes | the heads' rotary lanes]`` (rotated) beside a
#: narrower value row (scaled)
GQA_FULL = LayerType("gqa_full", _init_gqa("gqa_full"), mixer="gqa_full",
                     pages=_gqa_pages, state=lambda cfg: {},
                     mix=_served_only("gqa_full", _NO_GQA_BWD),
                     # the positions the full layers' kernel reads (once, not
                     # a layer) and the rows with a long context
                     touches=lambda cfg: (
                         Touch("full_kv_tokens", _total),
                         Touch("long_rows", lambda n, g: int(
                             (n > g.long_row_tokens).sum())),
                         Touch("ctx_tokens", _total, at=("chunk",)),
                         _pages_held("page_tokens_in_use")))
#: its window layer: ``swa_kv_heads`` heads, ``swa_rope_theta``, a learned
#: sink a query head, the last ``sliding_window`` positions as a ring in the
#: sequence's slot: no pages, no page accounting
GQA_WINDOW = LayerType("gqa_window", _init_gqa("gqa_window"),
                       mixer="gqa_window", pages=_no_pages, state=_gqa_ring,
                       mix=_served_only("gqa_window", _NO_GQA_BWD),
                       refuses=_RING_REFUSES,
                       ring=lambda cfg: cfg.sliding_window,
                       touches=_window_touch("window_kv_tokens"))
# ----------------------------------------------------- EVA attention (EvaByte)
#: the scales at which a seeded EVA layer is drawn, so that the mechanism
#: decides the output visibly at every width (the stack is seeded, not
#: trained): queries and keys ``QK / sqrt(hidden)`` a weight, so that their
#: lanes have a spread of ``QK`` over a normed input and a score ``sigma q . k``
#: one of ``QK^2`` — a softmax over n rows then has an entropy near ``ln n -
#: QK^4 / 2``, far from flat; values ``1 / sqrt(hidden)``; ``adaptive_phi``
#: ``PHI`` a lane, so that a chunk's pooling argument ``sigma k . phi`` spreads
#: by ``QK x PHI`` and the pooling is far from a mean; ``adaptive_mu_k`` ``MU``
#: a lane, which moves a summary's score against a query by ``QK x MU`` and
#: stands beside a pooled key's own lanes (``QK`` times the pooling weights'
#: root sum of squares)
EVA_QK_SCALE = 1.5
EVA_PHI_SCALE = 1.0
EVA_MU_SCALE = 0.5


def _init_eva(cfg: TransformerConfig, rng, n: int) -> Dict[str, Any]:
    keys = jax.random.split(rng, 32)
    layers = init_layer_stack(cfg, keys, n)
    H, NH, D = cfg.hidden_size, cfg.n_heads, cfg.head_dim
    a = layers["attn"]
    for j, (name, s) in enumerate((("wq", EVA_QK_SCALE), ("wk", EVA_QK_SCALE),
                                   ("wv", 1.0))):
        a[name] = _nrm(cfg, keys[18 + j], n, H, NH * D, s=s / math.sqrt(H))
    a["adaptive_phi"] = _nrm(cfg, keys[16], n, NH, D, s=EVA_PHI_SCALE)
    a["adaptive_mu_k"] = _nrm(cfg, keys[17], n, NH, D, s=EVA_MU_SCALE)
    return layers


def eva_pool(a, k, v):
    """One summary a chunk: ``k``, ``v`` ``[..., C, NH, D]`` the chunk's keys
    (rotated) and values -> ``(k~, v~)`` ``[..., NH, D]``, ``k~ = sum_m a_m
    k_m + mu``, ``v~ = sum_m a_m v_m``, ``a = softmax_m(sigma k_m . phi)``, a
    head at a time and in float32; ``a``: the layer's ``attn`` parameters."""
    with region("eva_pool"):
        kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
        s = jnp.einsum("...cnd,nd->...cn", kf,
                       a["adaptive_phi"].astype(jnp.float32)) \
            / math.sqrt(k.shape[-1])
        w = jax.nn.softmax(s, axis=-2)
        ks = jnp.einsum("...cn,...cnd->...nd", w, kf) \
            + a["adaptive_mu_k"].astype(jnp.float32)
        vs = jnp.einsum("...cn,...cnd->...nd", w, vf)
        return ks.astype(k.dtype), vs.astype(v.dtype)


def eva_mix(cfg: TransformerConfig, layer, x, positions, mask, attn_fn):
    """EVA attention over whole sequences that start at position 0, ``[B, S,
    H]`` -> the delta the block adds: a query at ``t`` sees the keys of its
    own window ``floor(t / W)`` up to itself and, of every earlier window, one
    summary a chunk (``eva_pool``), under one float32 softmax.  The plain
    form: ``[S, S / C + S]`` scores a head, for the tests and the CPU."""
    del mask, attn_fn  # whole sequences without padding, its own attention
    B, S, _ = x.shape
    W, C, D = cfg.eva_window, cfg.eva_chunk, cfg.head_dim
    q, k, v = attn_qkv(cfg, layer, x, positions)
    with region("eva_glue"):
        pad = ((0, 0), (0, -S % C), (0, 0), (0, 0))
        ks, vs = eva_pool(layer["attn"], *(
            jnp.pad(y, pad).reshape(B, -1, C, *y.shape[2:]) for y in (k, v)))
        t = positions[:, :, None]
        m = positions[:, None, :]
        c = jnp.arange(ks.shape[1])[None, None, :]
        vis = jnp.concatenate([(c // (W // C) < t // W),
                               (m <= t) & (m // W == t // W)], axis=-1)
        s = jnp.einsum("btnd,bsnd->bnts", q, jnp.concatenate([ks, k], axis=1)
                       ).astype(jnp.float32) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(vis[:, None], s, -1e30), axis=-1)
        o = jnp.einsum("bnts,bsnd->btnd", p.astype(v.dtype),
                       jnp.concatenate([vs, v], axis=1)).reshape(B, S, -1)
    with region("attn_out"):
        return _mm(cfg, o, layer["attn"]["wo"], MODEL_AXIS, None)


#: EVA attention (EvaByte).  What a layer keeps of a sequence says two things
#: at once, both in the K and V page leaves: the open window's keys and values
#: (at most ``eva_window`` rows, given back when the window closes) and one
#: summary row a whole chunk of ``eva_chunk`` positions (``ragged.EvaRows``:
#: the page accounting; rows advance once a chunk, not once a token)
def _eva_touches(cfg: TransformerConfig) -> Tuple[Touch, ...]:
    W, C = cfg.eva_window, cfg.eva_chunk
    # of (positions cached, pages held): summary rows written and open-window
    # rows, a layer; their sum; the pages
    held = {"eva_summary_rows_in_use": lambda n, p: n // C,
            "eva_window_rows_in_use": lambda n, p: n % W,
            "eva_rows_in_use": lambda n, p: n // C + n % W,
            "eva_pages_in_use": lambda n, p: p}
    return (Touch("eva_rows_attended", _total),  # once, not a layer
            Touch("ctx_tokens", _total, at=("chunk",)),
            *(Touch(name, lambda n, g, f=f: int(f(*n).sum()), at=("held",))
              for name, f in held.items()))


EVA = LayerType(
    "eva", _init_eva, mixer="eva", pages=_kv_pages, state=lambda cfg: {},
    mix=eva_mix, pooled=lambda cfg: (cfg.eva_window, cfg.eva_chunk),
    touches=_eva_touches,
    # each would answer wrongly over a cache whose rows are pooled chunks and
    # a window that empties
    refuses={
        "prefix_cache": "an 'eva' layer's pages hold a window's rows that "
                        "are given back when it closes and summaries that "
                        "are visible only past their window; a cached page "
                        "keyed by its tokens says neither; serve it with the "
                        "prefix cache off",
        "whole_prompt_prefill": "an 'eva' stack is prefilled through the "
                                "chunk program, in chunks that tile "
                                "eva_window (a chunk never straddles a "
                                "window) and are whole pages of summaries; "
                                "set prefill_chunk > 0",
        "speculation": "paged_verify cannot roll a rejected draft out of a "
                       "pooled chunk or a closed window ('eva')",
        "kv_quant": "an 'eva' layer pools a chunk's keys and values from the "
                    "rows as they are stored; int8 codes have no form of it; "
                    "serve it with kv_quant off",
        "decode_horizon": "a row's open pages go back to the allocator when "
                          "its window closes, between steps; the fused scan "
                          "reserves pages by position; serve an 'eva' stack "
                          "with decode_horizon 1",
        "bundle_export": "a bundle's pages are a page a page_size positions, "
                         "and an 'eva' stack's are summaries and an open "
                         "window (ragged.EvaRows)",
        "bundle_import": "an 'eva' stack's pages are summaries and an open "
                         "window, not a page a page_size positions"})
_TYPES = {"gqa_full": GQA_FULL, "gqa_window": GQA_WINDOW, "attn": ATTN, "kda": KDA, "conv": CONV, "mamba": MAMBA, "swa": SWA,
          "dattn": DATTN, "gmu": GMU, "xattn": XATTN, "mla": MLA, "eva": EVA}


def layer_type(kind: str) -> LayerType:
    try:
        return _TYPES[kind]
    except KeyError:
        raise ValueError(f"unknown layer type {kind!r}; known: "
                         f"{sorted(_TYPES)}") from None


def served_runs(cfg: TransformerConfig
                ) -> Tuple[Tuple[Tuple[LayerType, ...], int], ...]:
    """The stack as the serving programs scan it: runs ``(period of types,
    repeats)``; ``layer_period`` is one run."""
    if cfg.layer_types:
        raise NotImplementedError(
            "a stack of cfg.layer_types (one type a layer behind a dense "
            "prologue) is trained through run_stack; the paged programs "
            "(inference/v2/model_runner) serve cfg.layer_period or "
            "cfg.layer_runs (a dense prologue as runs of its own: "
            "cfg.dense_layers), and have no form of the 'conv' mixer")
    runs = cfg.layer_runs or (
        (cfg.layer_period, cfg.n_layers // len(cfg.layer_period)),)
    if sum(len(period) * n for period, n in runs) != cfg.n_layers:
        raise ValueError(f"the runs {runs} do not make n_layers "
                         f"{cfg.n_layers}")
    return tuple((tuple(layer_type(k) for k in period), int(n))
                 for period, n in runs)


def served_run_configs(cfg: TransformerConfig
                       ) -> Tuple[TransformerConfig, ...]:
    """The configuration each run of ``served_runs`` reads its feed-forward
    part from: the runs that make the first ``cfg.dense_layers`` layers of
    ``cfg.layer_runs`` are the prologue, dense at ``dense_ffn_size``; the
    others the configuration's.  A served layer's feed-forward part is what
    its parameters are (``model_runner._ffn`` reads a layer without a router
    as dense), so the programs take no list of runs beside the stack."""
    out, done = [], 0
    for types, n in served_runs(cfg):
        dense = bool(cfg.layer_runs) and done < cfg.dense_layers
        done += len(types) * n
        if dense and done > cfg.dense_layers:
            raise ValueError(
                f"dense_layers {cfg.dense_layers} ends inside a run of "
                f"{cfg.layer_runs}: the prologue is whole runs")
        out.append(run_config(cfg, "dense" if dense else "experts"))
    return tuple(out)


def init_period_runs(cfg: TransformerConfig, rng):
    """``params["layers"]`` of a stack of ``cfg.layer_runs``: per run, per
    position of its period, that type's parameters stacked ``[repeats,
    ...]``."""
    return tuple(
        tuple(t.init(rcfg, jax.random.fold_in(rng, 100 * (r + 1) + j), n)
              for j, t in enumerate(types))
        for r, ((types, n), rcfg) in enumerate(
            zip(served_runs(cfg), served_run_configs(cfg))))


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


#: the longest run of recomputed blocks that keep residuals to be unrolled
#: (the hybrids' periods are runs of three; a program grows with every layer
#: unrolled)
UNROLLED_KEEPING_RUN = 4


def run_stack(cfg: TransformerConfig, stack, x, positions, mask, attn_fn,
              with_act_stats: bool = False):
    """The layer loop of ``transformer_forward`` over a stack of
    ``cfg.layer_types``: each run scanned (a run of one layer unrolled, and
    a run of up to ``UNROLLED_KEEPING_RUN`` recomputed blocks that keep
    residuals), each layer ``x + mix(x)`` then ``mlp_block``.  Returns (x,
    the float auxiliary losses summed, ``[L, 3]`` activation rows or None,
    the int32 counters of the expert-share layers ``[expert layers, held +
    3]`` or None)."""
    if with_act_stats:
        from ..telemetry.numerics import activation_stats as act_row
    aux = jnp.asarray(0.0, jnp.float32)
    acts, counters = [], []
    for (kind, ffn, n), layers in zip(stack_runs(cfg), stack):
        rcfg, mix = run_config(cfg, ffn), layer_type(kind).mix

        def block(x, layer, rcfg=rcfg, mix=mix):
            h = x + mix(rcfg, layer, x, positions, mask, attn_fn)
            return mlp_block(rcfg, layer, h)

        keeps = False
        if cfg.remat:
            policy = get_policy(cfg.remat_policy)
            block = jax.checkpoint(block, policy=policy)
            # a scan stacks what its recomputed blocks keep: each kept
            # buffer is copied into its stack a layer and held beside it.
            # A short run is unrolled instead: each is read where it was made
            keeps = policy not in (
                None, jax.checkpoint_policies.nothing_saveable)

        def body(carry, layer, block=block):
            y, a = block(carry, layer)
            return y, ((a, act_row(y)) if with_act_stats else a)

        # (the loop under ``stack``: its own slices of the stacked weights
        # and the residual adds have no other home)
        with region("stack"):
            if (n == 1 or not cfg.scan_layers
                    or (keeps and n <= UNROLLED_KEEPING_RUN)):
                rows = []
                for i in range(n):
                    x, y = body(x, jax.tree_util.tree_map(lambda a: a[i],
                                                          layers))
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
    return sum(n * sum(t.mixer == mixer for t in types)
               for types, n in served_runs(cfg))


def page_layers(cfg: TransformerConfig) -> int:
    """How many of the model's layers keep pages: the pool's layers."""
    return sum(n * sum(bool(t.pages(cfg)) for t in types)
               for types, n in served_runs(cfg))


def page_leaves(cfg: TransformerConfig) -> Dict[str, Tuple[int, int]]:
    """{pool leaf: (layers that keep it, values a token keeps in it)} over
    the whole model: the page format the cache manager builds."""
    out: Dict[str, Tuple[int, int]] = {}
    for types, n in served_runs(cfg):
        for t in types:
            for name, width in t.pages(cfg).items():
                layers, w = out.get(name, (0, width))
                if w != width:
                    raise ValueError(f"page leaf {name!r} has widths {w} "
                                     f"and {width} in one model")
                out[name] = (layers + n, width)
    return out


def state_leaves(cfg: TransformerConfig) -> Dict[str, Tuple[int, tuple, Any]]:
    """{pool leaf: (layers that keep it, per-sequence shape, dtype)} over the
    whole model; empty for a model that keeps only pages."""
    out: Dict[str, Tuple[int, tuple, Any]] = {}
    for t in _served_types(cfg):
        for name, (shape, dtype) in t.state(cfg).items():
            out[name] = (layers_of(cfg, t.mixer), shape, dtype)
    return out


def _served_types(cfg: TransformerConfig) -> Tuple[LayerType, ...]:
    """The stack's types, each once, in the order the stack meets them."""
    return tuple(dict.fromkeys(
        t for types, _ in served_runs(cfg) for t in types))


def unsupported(cfg: TransformerConfig) -> Dict[str, str]:
    """{engine feature: why this stack's caches cannot serve it}: what its
    types refuse, the first to name a feature giving the reason."""
    kinds, out = sorted(state_leaves(cfg)), {}
    for t in _served_types(cfg):
        for feature, why in t.refuses.items():
            out.setdefault(feature, why.format(kinds=kinds))
    return out


def step_touches(cfg: TransformerConfig) -> Dict[str, Tuple[Touch, ...]]:
    """{when (``Touch.at``): what a step over this stack counts then}, each
    name once."""
    named = {c.name: c for t in _served_types(cfg) for c in t.touches(cfg)}
    return {at: tuple(c for c in named.values() if at in c.at)
            for at in ("decode", "last_chunk", "chunk", "held")}


def pooled_rows(cfg: TransformerConfig) -> Optional[Tuple[int, int]]:
    """(window, chunk) of a stack whose pages hold pooled chunks beside an
    open window, None for one whose pages grow a row a position."""
    types = _served_types(cfg)
    pooled = [t for t in types if t.pooled]
    if not pooled:
        return None
    if len(types) > 1:
        raise NotImplementedError(
            f"a stack that mixes {pooled[0].name!r} layers with others: the "
            "page accounting of a sequence (ragged.EvaRows) is one for all "
            "of its layers")
    return pooled[0].pooled(cfg)


def ring_positions(cfg: TransformerConfig) -> int:
    """The positions of the rings the stack's window layers keep in a
    sequence's slot, 0 for a stack without."""
    return max((t.ring(cfg) for t in _served_types(cfg) if t.ring), default=0)


def chunk_stops_early(cfg: TransformerConfig) -> bool:
    """Whether a chunk that is not a prompt's last stops inside the stack (at
    a cross-decoder's K/V write), in a program of its own."""
    return any(t.cross_decoder for t in _served_types(cfg))


def page_block(cfg: TransformerConfig
               ) -> Tuple[str, Callable[[int, int, int], int]]:
    """(the pool leaf that is a page of the decode kernel, ``(page_size, its
    row's values, itemsize) -> pages a block of that kernel holds``)."""
    paged = next(t for t in _served_types(cfg) if t.pages(cfg))
    return next(iter(paged.pages(cfg))), paged.block_pages


def bundle_signature(cfg: TransformerConfig) -> Tuple[int, int, int]:
    """(layers, heads, width) of a page as a ``KVPageBundle`` carries it: K
    and V by head, or the one leaf of a format that has no heads."""
    pages = page_leaves(cfg)
    if set(pages) == {"k", "v"}:
        return (cfg.n_layers, cfg.kv_heads, cfg.head_dim)
    (layers, width), = pages.values()
    return (layers, 1, width)


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
