"""Continuous-batching inference engine.

Reference parity: ``InferenceEngineV2`` (inference/v2/engine_v2.py) with
its ragged batch scheduler (``DSStateManager``/``RaggedBatchWrapper``,
inference/v2/ragged/): requests enter a queue, are admitted when KV pages
and a decode slot are available, prefill and decode interleave, finished
sequences release their pages immediately so new requests can start while
others are mid-generation.

The device work is two compiled programs (model_runner.py); everything
here is host-side bookkeeping between steps.  DECODE samples on device
(greedy argmax / Gumbel-max temperature inside the jitted program) and
returns only [max_seqs] token ids — fetching the full [max_seqs, vocab]
logits to the host every step moves ~1MB where 32 bytes suffice.  Prefill
(once per admitted request) still returns logits and samples on host.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import time
import types
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...compile.deep_frame import first_call_beneath
from ...models.layer_types import (FEATURES, bundle_signature,
                                   chunk_stops_early, gqa_shape, page_block,
                                   page_layers, page_leaves, served_runs,
                                   state_leaves, step_touches, unsupported)
from ...models.transformer import TransformerConfig
from ...moe.sharded_moe import MOE_COUNTERS
from ...ops.pallas.paged_attention import n_blocks
from ...runtime.config_utils import ConfigModel
from ...telemetry import get_registry
from ...telemetry.compile_sentinel import (RecompileSentinel,
                                           compile_counts,
                                           publish_setup_seconds, setup_span)
from ...telemetry.compile_sentinel import \
    expect_recompile as sentinel_expect_recompile
from ...telemetry.flight import dump_on_exception, get_flight_recorder
from ...telemetry.regions import region, region_index
from ...telemetry.reqtrace import get_reqtrace_ledger, slo_exemplar
from ...telemetry.spans import begin_span, end_span, record_event, span
from ...telemetry.tracing import PhaseTimer
from ...telemetry.watchdog import StallWatchdog
from ...utils.logging import logger
from ...utils.platform import ensure_compile_cache
from .model_runner import (pad_pages_pow2, paged_copy_page, paged_decode,
                           paged_gather_pages, paged_multi_decode,
                           paged_prefill, paged_prefill_chunk,
                           paged_read_rows, paged_scatter_pages, paged_verify,
                           sample_tokens)
from .packed_inputs import PackedProgram, pack_inputs
from .ragged import (PRIORITY_NORMAL, BlockAllocator, KVBlockConfig,
                     KVPageBundle, PagedKVCache, PrefixCache, RejectedError,
                     SequenceState, StateSlots, page_rows)
from .block_diffusion import block_policy
from .speculative import (SpeculativeConfig, build_proposer, longest_accepted)


@dataclasses.dataclass
class RaggedInferenceConfig(ConfigModel):
    dtype: str = "bf16"
    page_size: int = 16
    num_pages: int = 256
    max_seqs: int = 8
    max_pages_per_seq: int = 16
    min_prefill_bucket: int = 16
    #: chunked prefill (FastGen Dynamic SplitFuse): process prompts in
    #: chunks of this many tokens (rounded up to page_size) so decode
    #: steps interleave between chunks — bounded per-step latency for
    #: running streams.  0 = whole-prompt prefill.
    prefill_chunk: int = 0
    # weight-only quantization (reference inference/quantization/): 0 = off
    quant_bits: int = 0
    quant_group: int = 128
    quant_min_size: int = 1 << 14  # per-matrix eligibility floor
    #: int8 KV pages + per-(page,slot,head) scales: half the KV pool HBM
    kv_quant: bool = False
    #: automatic prefix caching: retired/preempted sequences leave their
    #: full KV pages in a content-hash index; new requests map the longest
    #: cached page-aligned prefix straight into their page table and
    #: prefill only the uncached suffix.  GREEDY decoding is bit-exact
    #: vs. cache-off, EXCEPT under kv_quant (the suffix attends
    #: dequantized cached pages where a whole-prompt prefill attends
    #: fresh full-precision keys — the same inherent cross-chunk
    #: approximation chunked prefill has).  Temperature sampling stays
    #: distributionally correct but not stream-identical: a fully-cached
    #: prompt samples its first token on the device RNG (decode entry)
    #: instead of the host RNG
    enable_prefix_cache: bool = False
    #: cap on cached-but-UNREFERENCED pages retained for reuse (LRU);
    #: 0 = bounded only by the pool itself
    prefix_cache_pages: int = 0
    #: tiered KV cache (serving/kv_tier.py, docs/SERVING.md "Tiered KV
    #: cache"): a ``KVTierConfig`` (or its dict form) enabling host-RAM
    #: spill & restore of cold prefix pages — prefix-cache LRU
    #: evictions are captured (D2H, async at step boundaries, pages
    #: ref-pinned until the copy commits) into a byte-budgeted host LRU
    #: and restored CRC-verified bit-identical when a later prefix walk
    #: reaches past the device hit.  Requires ``enable_prefix_cache``.
    #: Typed ``Any`` to keep this module import-light — the block's
    #: home is ``serving/config.py`` (serving imports inference, never
    #: the reverse at module scope)
    kv_tier: Any = None
    #: recompile sentinel for the serving loop (telemetry/
    #: compile_sentinel.py): attribute XLA compiles to steps via the
    #: step's program shapes and warn on steady-state recompilation.
    #: The serving engine takes no `telemetry` config block, so the
    #: knob lives here; `sentinel_steady_after` mirrors
    #: telemetry.recompile_sentinel.steady_after
    recompile_sentinel: bool = True
    sentinel_steady_after: int = 3
    #: step-time attribution (telemetry/timeline.py): every N engine
    #: steps, capture one profiler trace and publish the measured
    #: decomposition (0 = only on explicit `force_timeline_capture()`).
    #: The serving engine takes no `telemetry` block, so — like the
    #: sentinel above — the knob lives here
    timeline_every_n_steps: int = 0
    #: where per-capture merged Chrome traces land ("" = no artifacts)
    timeline_artifact_dir: str = ""
    #: memory ledger (telemetry/memory.py): attach the weight copy + KV
    #: page pool to the process ledger and watch prefill/decode phase
    #: watermarks.  The serving engine takes no `telemetry` block, so —
    #: like the sentinel above — the knob lives here
    memory_ledger: bool = True
    #: speculative decoding (speculative.py): multi-token-per-step
    #: decode — a proposer drafts up to k tokens, ONE batched verify
    #: program scores them all, the longest prefix matching the model's
    #: own greedy choices is accepted (+ the model's correction token),
    #: rejected tokens' pages roll back through the allocator.  GREEDY
    #: decoding is bit-identical to the non-speculative baseline;
    #: non-greedy sequences fall back to the plain decode program
    #: (sampling guard) so the output distribution is never touched
    speculative: SpeculativeConfig = dataclasses.field(
        default_factory=SpeculativeConfig)
    #: fused multi-step decode (docs/SERVING.md "Multi-step decode"):
    #: decode up to this many tokens per host round-trip via an
    #: on-device ``lax.scan`` over the decode body — ONE ``[B, K]``
    #: token pull per dispatch instead of one ``[B]`` pull per token,
    #: with per-row EOS/length/deadline masking computed in-scan
    #: (finished rows write to the trash page and stop consuming
    #: pages).  Greedy AND sampled streams are bit-identical across
    #: horizons (the sampling key folds per position, never per
    #: dispatch).  1 = the classic one-step decode loop.  Engines with
    #: speculative decoding enabled stand the horizon down loudly —
    #: one designed exclusive decode path at a time, like the
    #: sampling guard
    decode_horizon: int = 1
    #: bounded request queue (admission control): once this many
    #: requests wait for admission, ``put()`` raises
    #: :class:`RejectedError` (load shedding — the submitter backs off
    #: ``retry_after_s`` instead of growing the queue without bound).
    #: <= 0 = unbounded (the pre-SLO behavior)
    max_queue_depth: int = 0
    #: latency SLOs (seconds; <= 0 = untracked): TTFT / TPOT observations
    #: past these thresholds count the
    #: ``deepspeed_tpu_serving_slo_{ttft,tpot}_violations_total``
    #: counters and emit an ``slo_violation`` trace event
    slo_ttft_s: float = 0.0
    slo_tpot_s: float = 0.0

    @property
    def jnp_dtype(self):
        return {"fp32": jnp.float32, "bf16": jnp.bfloat16,
                "fp16": jnp.float16}[self.dtype]

    @property
    def block(self) -> KVBlockConfig:
        return KVBlockConfig(page_size=self.page_size, num_pages=self.num_pages,
                             max_seqs=self.max_seqs,
                             max_pages_per_seq=self.max_pages_per_seq)


@dataclasses.dataclass
class RaggedRequest:
    prompt_ids: List[int]
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 => greedy
    eos_id: Optional[int] = None
    uid: Optional[int] = None
    #: priority class (``ragged.PRIORITY_*``, smaller = more urgent):
    #: orders admission, picks preemption victims under KV pressure
    #: (lowest class first), and gates load shedding under overload
    priority: int = PRIORITY_NORMAL
    #: wall-clock budget in seconds from enqueue (None = no deadline):
    #: past it the engine expires the request at the next step boundary
    #: with ``finish_reason="deadline"`` instead of letting it wait (or
    #: decode) forever
    deadline_s: Optional[float] = None
    #: fleet trace id minted by ``FleetRouter.submit`` (None when the
    #: engine is driven standalone): rides the request span, every
    #: lifecycle trace event, and the KV-migration wire so one request
    #: is ONE connected trace across replicas
    trace_id: Optional[str] = None
    #: a model that generates by diffusion over blocks (``block_diffusion``):
    #: the passes that denoise a block, a divisor of the model's block length
    #: (None = the block length: one position a pass); ``max_new_tokens`` is
    #: then the fixed generation length
    denoising_steps: Optional[int] = None


#: what a ``serve_step`` span carries at its end
_STEP_COUNTS = ("chunks", "prefill_tokens", "decode_rows",
                "decode_kv_blocks", "admitted", "preempted", "queue_len",
                "input_transfers")


#: a decoding row whose context is over this many tokens is a long one
#: (``long_rows`` on the step of a stack of full and window layers)
LONG_ROW_TOKENS = 8192


def _horizon_pages_needed(length: int, budget: int, page_size: int) -> int:
    """Pages a decode row needs to emit ``budget`` more tokens: its t-th
    token this dispatch (1-indexed) writes KV at position
    ``length - 2 + t``, so the page table must cover position
    ``length - 2 + budget`` — the headroom-reservation arithmetic of
    the fused multi-step decode (pure, unit-tested)."""
    return (length - 2 + budget) // page_size + 1


def _shrink_horizon(k: int, cap: int) -> int:
    """Walk the halving chain ``K, ceil(K/2), ...`` down to the smallest
    value still covering ``cap`` (floor 1).  The dispatch horizon only
    ever takes values ON the chain, so the fused scan's compiled-shape
    set is O(log K) — short row budgets and pool pressure shrink the
    dispatch instead of minting arbitrary scan lengths (pure,
    unit-tested)."""
    while k > 1 and (k + 1) // 2 >= cap:
        k = (k + 1) // 2
    return max(1, k)


def _deadline_clamp(budget: int, deadline_left: float,
                    tpot_est: Optional[float]) -> int:
    """Clamp a row's effective horizon when its deadline lands
    mid-horizon: at ~``tpot_est`` seconds per fused step, emit only the
    tokens that fit the remaining budget (floor 1 — a single step would
    emit one token before the boundary sweep too).  Without an
    estimate (first dispatch) the budget passes through: the boundary
    sweep still expires the row, at most one horizon late (pure,
    unit-tested)."""
    if tpot_est is None or tpot_est <= 0.0:
        return budget
    return min(budget, max(1, int(deadline_left / tpot_est)))


class InferenceEngineV2:
    """Paged continuous batching over a models/* transformer."""

    @classmethod
    def from_pretrained(cls, model_dir: str,
                        config: Optional["RaggedInferenceConfig"] = None,
                        **kw) -> "InferenceEngineV2":
        """Serve a published Hugging Face checkpoint directory with paged
        continuous batching (the reference's inference-v2 checkpoint
        loading, model_implementations/*)."""
        from ...checkpoint.hf_import import load_hf_model
        from ...models.llama import llama_model

        cfg = config or RaggedInferenceConfig()
        mcfg, params = load_hf_model(model_dir, dtype=cfg.jnp_dtype)
        return cls(llama_model(config=mcfg), config=cfg, params=params, **kw)

    def __init__(self, model: Any, config: Optional[RaggedInferenceConfig] = None,
                 params: Any = None, seed: int = 0, proposer: Any = None):
        t_init = time.perf_counter()
        ensure_compile_cache()
        self.config = config or RaggedInferenceConfig()
        if isinstance(self.config.speculative, dict):  # hand-built configs
            self.config.speculative = SpeculativeConfig.from_dict(
                self.config.speculative)
        if not hasattr(model, "config") or not isinstance(model.config, TransformerConfig):
            raise TypeError("InferenceEngineV2 needs a models/* model carrying "
                            "a TransformerConfig")
        # own COPY of the model config: quantization flags must not leak
        # into other engines sharing the model object
        self.cfg: TransformerConfig = dataclasses.replace(model.config)
        if self.cfg.post_norm:
            raise NotImplementedError(
                "InferenceEngineV2 serves causal decoders; post_norm "
                "(BERT-style encoder) models have no generative path")
        if self.cfg.moe_experts > 0:
            # serving never drops a token: capacity routing is a training
            # device (set on this copy, like wq_bits below)
            self.cfg.moe_drop_tokens = False
        #: fixed-size per-sequence state some layer types keep beside pages
        self._state = state_leaves(self.cfg)
        #: {feature: why} of what the stack's layer types cannot serve
        self.unsupported = unsupported(self.cfg)
        self._refuse_asked(self.unsupported, proposer)
        block = self.config.block
        #: how a sequence's pages grow over what the stack's layers cache
        #: (``ragged.PageRows``, or the class the layer types declare)
        self.rows = page_rows(self.cfg, block, self.config.prefill_chunk)
        if params is None:
            params = model.init_params(jax.random.PRNGKey(seed))
        # deferred: runtime.precision pulls runtime.config, which imports
        # serving.config -> inference.v2 — a top-level import here would
        # close that cycle during runtime.config's own initialization
        from ...runtime.precision import cast_tree

        self.params = cast_tree(params, self.config.jnp_dtype)
        self.param_bytes = sum(l.size * l.dtype.itemsize for l in
                               jax.tree_util.tree_leaves(self.params))
        if self.config.quant_bits and self.cfg.hc_mult > 1:
            raise ValueError(
                "quant_bits: the hyper-connections' projection (layers/hc/"
                "*/phi) is read as it is stored, in the served dtype; "
                "weight-only quantization has no form of it")
        if self.config.quant_bits:
            from ..quantization import quantize_inference_params

            self.cfg.wq_bits = int(self.config.quant_bits)
            self.cfg.wq_group = int(self.config.quant_group)
            self.params, _, self.param_bytes = quantize_inference_params(
                self.params, self.cfg.wq_bits, self.cfg.wq_group,
                min_size=self.config.quant_min_size)
        self._pools = PagedKVCache.init(
            page_layers(self.cfg), self.cfg.kv_heads,
            self.cfg.head_dim, block, self.config.jnp_dtype,
            kv_quant=self.config.kv_quant, state=self._state,
            counters=({"moe_stats": len(MOE_COUNTERS)}
                      if self.cfg.moe_held_count else None),
            pages=page_leaves(self.cfg))
        #: pages a block of the decode kernel holds, from the pool's own
        #: geometry as the kernel of the stack's page format takes it
        #: (``decode_kv_blocks``)
        leaf, block_pages = page_block(self.cfg)
        page_leaf = self._pools[leaf]
        self._kv_block_pages = block_pages(
            block.page_size, page_leaf.shape[-1], page_leaf.dtype.itemsize)
        #: what a step over the stack's caches counts, by when
        #: (``layer_types.Touch``), and the geometry its counts read
        self._touches = step_touches(self.cfg)
        self._geometry = types.SimpleNamespace(
            page_size=block.page_size, block_pages=self._kv_block_pages,
            long_row_tokens=LONG_ROW_TOKENS)
        self.state_slots = StateSlots(block.max_seqs if self._state else 0)
        #: the expert share's counters as the device last reported them
        #: (``moe_stats`` wraps at 2**32; the host adds differences)
        self._moe_seen = np.zeros((len(MOE_COUNTERS),), np.int64)
        self.block = block
        # A learned-position model cannot attend past its position table; cap
        # the paged window to the model's trained context.
        self.max_seq_len = min(block.max_seq_len, self.cfg.max_seq_len)
        self.allocator = BlockAllocator(
            block.num_pages,
            cache_pages=(self.config.prefix_cache_pages
                         if self.config.enable_prefix_cache else 0))
        self.prefix_cache = (PrefixCache(block.page_size, self.allocator)
                             if self.config.enable_prefix_cache else None)
        # tiered KV cache (serving/kv_tier.py): host-RAM spill & restore
        # of cold prefix pages.  Deferred import like the admission hook
        # in put(): serving imports inference, never the reverse at
        # module scope.
        self.kv_tier = None
        self._pending_spills: List[Tuple[int, Any]] = []
        self._pending_spill_keys: set = set()
        self._prefetched = True  # armed per step (see step())
        tier_cfg = self.config.kv_tier
        if isinstance(tier_cfg, dict):
            from ...serving.config import KVTierConfig

            tier_cfg = self.config.kv_tier = KVTierConfig.from_dict(tier_cfg)
        if tier_cfg is not None and tier_cfg.enabled:
            if not self.config.enable_prefix_cache:
                raise ValueError(
                    "kv_tier.enabled requires enable_prefix_cache: the "
                    "host tier captures prefix-cache LRU evictions")
            tier_cfg.validate()
            from ...serving.kv_tier import HostKVTier

            self.kv_tier = HostKVTier(tier_cfg)
            self.allocator.spill_hook = self._capture_evicted_page
        # serving counters (cache_stats / publish_metrics): token-level
        # admission vs. computation, so hit_rate is FLOP-meaningful
        self._stats = {"prefill_admitted_tokens": 0,
                       "prefill_computed_tokens": 0,
                       "prefix_hit_tokens": 0}
        # decode-phase counters (decode_stats): tokens produced over model
        # invocations is what speculation buys, over host syncs what the
        # fused horizon buys
        self._dstats = {"decode_model_invocations": 0, "decode_tokens": 0,
                        "decode_host_syncs": 0, "decode_horizon_shrinks": 0,
                        "decode_kv_blocks": 0,
                        "spec_proposed_tokens": 0, "spec_accepted_tokens": 0,
                        "spec_verify_calls": 0, "spec_rollback_pages": 0,
                        "spec_fallback_requests": 0,
                        **dict.fromkeys(MOE_COUNTERS, 0),
                        "state_slot_preemptions": 0,
                        **{c.name: 0 for at in self._touches.values()
                           for c in at if c.cumulative}}
        self._init_serving_metrics()
        #: the one question asked of the model: does it generate by blocks
        #: (``block_diffusion.BlockPolicy``, which owns the decode phase of
        #: such a model's step; None: one token a row a step)
        self.blocks = block_policy(self, proposer)
        self._uid = itertools.count()
        self._admit_counter = itertools.count()
        self._enqueue_counter = itertools.count()
        self._rng = np.random.RandomState(seed)

        self._queue: List[SequenceState] = []
        self._slots: List[Optional[SequenceState]] = [None] * block.max_seqs
        #: set by drain(): the engine is retiring, put() refuses admissions
        self._draining = False
        # host mirror of the device page tables, trash-filled
        self._page_table = np.full((block.max_seqs, self.rows.table_pages),
                                   block.trash_page, dtype=np.int32)

        cfg = self.cfg

        def _decode_and_sample(params, pools, last, pos, table, act, temps,
                               sids, key):
            logits, pools = paged_decode(cfg, params, pools, last, pos,
                                         table, act)
            # sample_tokens folds the key per (request uid, position)
            # INSIDE the program — no extra dispatch, and the SAME fold
            # the fused multi-step scan uses, so decode horizons are
            # stream-identical (greedy and sampled alike) and a sampled
            # stream keeps its noise through preemption / migration
            if cfg.pred_heads > 1:
                # the next token from head 0, the further heads' picks beside
                # it: [B, pred_heads] int32 crosses, never the logits
                V = cfg.vocab_size
                tok = sample_tokens(logits[:, :V], temps, key, sids, pos + 1)
                with region("sample"):
                    more = jnp.argmax(logits[:, V:].reshape(
                        logits.shape[0], -1, V), axis=-1).astype(jnp.int32)
                return jnp.concatenate([tok[:, None], more], axis=1), pools
            return sample_tokens(logits, temps, key, sids, pos + 1), pools

        # every serving program takes its host inputs packed
        # (packed_inputs.py): ``_dispatch`` moves them across in one piece
        self._decode = PackedProgram(_decode_and_sample, rest=1)
        self._prefill = PackedProgram(lambda *a: paged_prefill(cfg, *a))
        self._prefill_chunk = PackedProgram(
            lambda *a: paged_prefill_chunk(cfg, *a))
        #: a stack with a cross-decoder (whose pages the layers after it
        #: read) runs that decoder for a prompt's last token only: a chunk
        #: that is not the prompt's last stops at that layer's K/V write, in
        #: a program of its own
        self._chunk_stops_early = chunk_stops_early(cfg)
        self._prefill_chunk_part = PackedProgram(
            lambda *a: paged_prefill_chunk(cfg, *a, final=False))
        self._copy_page = jax.jit(paged_copy_page, donate_argnums=(0,))
        ps = self.block.page_size
        self._chunk = (-(-self.config.prefill_chunk // ps) * ps
                       if self.config.prefill_chunk > 0 else 0)
        self._sample_key = jax.random.PRNGKey(seed)
        self._decode_steps = 0
        # speculative decoding: an explicit ``proposer=`` argument wins
        # (and enables speculation regardless of mode); otherwise the
        # config block builds one.  The verify program has ONE compiled
        # width (k + 1) so every acceptance outcome reuses it.
        self.spec = self.config.speculative
        if proposer is not None:
            if self.spec.k < 1:  # the one field the engine still uses
                raise ValueError("speculative.k must be >= 1")
            self._proposer = proposer
        else:
            self.spec.validate()  # directly-built configs skip from_dict
            self._proposer = build_proposer(self.spec)
        self._spec_fallback_uids: set = set()
        self._spec_fallback_warned = False
        if self._proposer is not None:
            def _verify_and_greedy(params, pools, ids, pos, table, act, nv):
                logits, pools = paged_verify(cfg, params, pools, ids, pos,
                                             table, act, nv)
                # greedy argmax on device: [B, W] int32 crosses the link,
                # not [B, W, vocab] logits (same economics as decode)
                with region("sample"):
                    return (jnp.argmax(logits.astype(jnp.float32), axis=-1)
                            .astype(jnp.int32), pools)

            self._verify = PackedProgram(_verify_and_greedy)
        # fused multi-step decode (docs/SERVING.md "Multi-step decode"):
        # one designed exclusive decode path at a time — a configured
        # proposer owns the decode loop, so the horizon stands down
        # LOUDLY (the multi-step twin of the sampling guard)
        self._horizon = int(self.config.decode_horizon)
        if self._horizon < 1:
            raise ValueError(
                f"decode_horizon must be >= 1, got {self._horizon}")
        if self._proposer is not None and self._horizon > 1:
            logger.warning(
                f"multi-step decode: speculative decoding is enabled and "
                f"owns the decode loop — decode_horizon {self._horizon} "
                "stands down to 1 (disable speculative.mode to fuse "
                "decode steps)")
            self._horizon = 1
        #: EMA of per-token decode wall time: the deadline clamp's TPOT
        #: estimate (None until a WARM dispatch lands — a dispatch that
        #: compiled its horizon shape would seed the EMA with XLA
        #: compile seconds and poison the clamp for ~10 dispatches)
        self._tpot_ema: Optional[float] = None
        self._warm_horizons: set = set()
        if self._horizon > 1:
            def _multi_fn(params, pools, last, pos, table, act, temps,
                          eos, budg, sids, key, horizon):
                return paged_multi_decode(cfg, params, pools, last, pos,
                                          table, act, temps, eos, budg,
                                          sids, key, horizon)

            # horizon is static (the scan length); the engine only ever
            # dispatches halving-chain values, so the compiled-shape
            # set stays O(log decode_horizon)
            self._multi = PackedProgram(_multi_fn, rest=2, static_rest=(1,))
        else:
            self._multi = None
        # request lifecycle bookkeeping: enqueue/first-token stamps + the
        # open request span, keyed by uid (survives preemption, which
        # resets the SequenceState but not the request)
        self._req_meta: Dict[int, Dict[str, Any]] = {}
        # per-step program signature parts for the recompile sentinel:
        # each prefill bucket / chunk size and the decode program are
        # components — a compile during a step that introduced no new
        # component after warmup is a steady-state recompilation
        self._step_parts: set = set()
        #: the parts ``_dispatch`` has seen, over the engine's life
        self._lowered_parts: set = set()
        #: ``step()`` calls so far: the ``step`` every span and event of a
        #: step carries (``_decode_steps`` counts decode dispatches only)
        self._step_id = 0
        #: this step's seconds by span name, and the part of each that a
        #: child span (``device_wait``) covers: span less child = self time
        self._phase_s: Dict[str, float] = {}
        self._child_s: Dict[str, float] = {}
        self._step_counts: Dict[str, int] = dict.fromkeys(_STEP_COUNTS, 0)
        #: rates decode-only steps (a decode pull, no prefill chunk), whose
        #: ``serve_step`` is one decode program and the host round it; a
        #: step that carries chunks is several programs long and no stall,
        #: one that only dispatches returns in milliseconds
        self._watchdog = StallWatchdog(name="serve", on_stall=self._on_stall)
        self._sentinel = (RecompileSentinel(
            loop="serve", steady_after=self.config.sentinel_steady_after)
            if self.config.recompile_sentinel else None)
        # step-time attribution: periodic (timeline_every_n_steps) or
        # on-demand (force_timeline_capture); only the captured step
        # pays the profiler cost
        from ...telemetry.timeline import StepTimeline

        self._timeline = StepTimeline(
            every_n_steps=self.config.timeline_every_n_steps,
            artifact_dir=self.config.timeline_artifact_dir)
        self._wire_memory_ledger()
        setup_span("serve_engine_init", t_init)
        publish_setup_seconds()

    def _refuse_asked(self, unsupported: Dict[str, str], proposer: Any
                      ) -> None:
        """What this configuration asks for that ``unsupported`` (``{feature:
        why}``: what the stack's layer types declare, ``layer_types
        .unsupported``, or a policy of the step) cannot serve: each would
        answer wrongly, so the first is refused here, by name — how it was
        asked for, then the reason."""
        conf, tier = self.config, self.config.kv_tier
        asked = {
            "prefix_cache": conf.enable_prefix_cache and "enable_prefix_cache",
            "whole_prompt_prefill": conf.prefill_chunk <= 0
            and "prefill_chunk 0",
            "speculation": (proposer is not None
                            or conf.speculative.mode != "off")
            and "speculative decoding",
            "kv_quant": conf.kv_quant and "kv_quant",
            "kv_tier": tier is not None and (
                tier.get("enabled") if isinstance(tier, dict)
                else tier.enabled) and "kv_tier",
            "decode_horizon": conf.decode_horizon > 1
            and f"decode_horizon {conf.decode_horizon}",
        }
        for feature in FEATURES:
            if asked.get(feature) and feature in unsupported:
                raise ValueError(f"{asked[feature]}: {unsupported[feature]}")

    def _wire_memory_ledger(self) -> None:
        """Attach the serving engine's HBM residents to the process
        memory ledger (telemetry/memory.py): the weight copy, the KV
        page pool, and — informationally, it is a sub-slice of the pool
        — the bytes pinned by prefix-cache LRU pages.  Providers read
        ``self`` dynamically so the donated pool buffers of the latest
        step are measured.  Co-located engines replace each other's
        components (latest owner wins); ``close()`` detaches exactly
        what this engine attached so a torn-down engine's weights and
        KV pool are not kept alive by the process-lifetime ledger."""
        self._ledger_components = []
        if not self.config.memory_ledger:
            return
        from ...telemetry.memory import get_memory_ledger

        led = get_memory_ledger()
        led.install_phase_watch()  # prefill/decode peak watermarks

        def _attach(name, provider, **kw):
            led.attach(name, provider, **kw)
            self._ledger_components.append((name, provider))

        _attach("serving_params", lambda: self.params)
        _attach("kv_pool", lambda: self._pools)
        _attach("kv_prefix_pinned",
                lambda: {"device": self._pinned_page_bytes()},
                informational=True)
        if self.kv_tier is not None:
            # spilled pages are real host RAM this engine owns
            _attach("kv_host_tier",
                    lambda: {"host": self.kv_tier.host_bytes})
        led.update_context(
            kv_num_pages=self.block.num_pages,
            kv_page_size=self.block.page_size,
            kv_max_seqs=self.block.max_seqs,
            kv_quant=self.config.kv_quant,
            prefix_cache=self.config.enable_prefix_cache,
            # (a residual of several streams: hyper-connections)
            **({"mhc_streams": self.cfg.hc_mult}
               if self.cfg.hc_mult > 1 else {}))

    def _pinned_page_bytes(self) -> int:
        """Device bytes held by prefix-cache-pinned (LRU) pages: the
        pool's per-page cost times the parked-page count."""
        from ...telemetry.memory import tree_bytes

        dev, _host = tree_bytes(self._pools)
        return dev * self.allocator.lru_pages // (self.block.num_pages + 1)

    # -- telemetry -----------------------------------------------------------
    def _init_serving_metrics(self) -> None:
        """Register the serving metric family on the process telemetry
        registry (get-or-create: several engines in one process share
        the cumulative series; ``cache_stats`` keeps the per-engine view
        via ``self._stats`` and the allocator/prefix-cache counters)."""
        reg = get_registry()
        self._m_queue = reg.gauge(
            "deepspeed_tpu_serving_queue_depth",
            "requests waiting for admission")
        self._m_occupancy = reg.gauge(
            "deepspeed_tpu_serving_batch_occupancy",
            "occupied decode slots / max_seqs")
        self._m_prefill_h = reg.histogram(
            "deepspeed_tpu_serving_prefill_seconds",
            "host wall time of one prefill call (a chunk or a whole "
            "prompt): input building, the one transfer of the inputs and "
            "an asynchronous dispatch; a prompt's last call also waits for "
            "the device and samples the first token")
        self._m_decode_h = reg.histogram(
            "deepspeed_tpu_serving_decode_seconds",
            "host wall time of one decode dispatch: the inputs' transfer, "
            "dispatch, restore-prefetch and the wait for the tokens (the "
            "whole horizon when decode_horizon > 1)")
        self._m_step_phase_h = reg.histogram(
            "deepspeed_tpu_serving_step_phase_seconds",
            "host wall time per phase of one engine step, summed over the "
            "step's spans of that name: serve_step (the whole step), "
            "step_admit, prefill, decode / multi_decode / spec_verify, "
            "dispatch and device_wait (inside prefill and decode), "
            "step_emit",
            labelnames=("phase",))
        self._m_requests = reg.counter(
            "deepspeed_tpu_serving_requests_total", "requests enqueued")
        self._m_gen_tokens = reg.counter(
            "deepspeed_tpu_serving_tokens_generated_total",
            "tokens produced by the decode program")
        self._m_admitted = reg.counter(
            "deepspeed_tpu_serving_prefill_admitted_tokens_total",
            "prompt tokens admitted for prefill")
        self._m_computed = reg.counter(
            "deepspeed_tpu_serving_prefill_computed_tokens_total",
            "prompt tokens actually computed (admitted minus prefix hits)")
        self._m_hit_tokens = reg.counter(
            "deepspeed_tpu_serving_prefix_hit_tokens_total",
            "prompt tokens served from the prefix cache")
        self._m_cache_hits = reg.counter(
            "deepspeed_tpu_serving_prefix_cache_hits_total",
            "prefix-cache page lookups that matched")
        self._m_cache_misses = reg.counter(
            "deepspeed_tpu_serving_prefix_cache_misses_total",
            "admission walks ending on a missing page")
        self._m_cache_evict = reg.counter(
            "deepspeed_tpu_serving_prefix_cache_evictions_total",
            "cached pages evicted (LRU or cap trim)")
        self._m_cached_pages = reg.gauge(
            "deepspeed_tpu_serving_prefix_cached_pages",
            "pages currently parked in the prefix cache")
        self._m_preemptions = reg.counter(
            "deepspeed_tpu_serving_preemptions_total",
            "sequences evicted to the queue under KV-pool pressure")
        # KV page-pool occupancy: used + free == num_pages; pinned pages
        # (cached-but-unreferenced LRU) are a subset of free — allocatable,
        # but evicting them costs future prefix hits
        self._m_kv_used = reg.gauge(
            "deepspeed_tpu_serving_kv_pages_used",
            "KV pool pages referenced by live sequences")
        self._m_kv_free = reg.gauge(
            "deepspeed_tpu_serving_kv_pages_free",
            "allocatable KV pool pages (truly free + cached-unreferenced)")
        self._m_kv_pinned = reg.gauge(
            "deepspeed_tpu_serving_kv_pages_pinned",
            "cached-but-unreferenced pages parked in the prefix-cache LRU")
        self._m_ttft_h = reg.histogram(
            "deepspeed_tpu_serving_ttft_seconds",
            "time to first token: enqueue to first sampled token "
            "(includes queue wait)")
        self._m_tpot_h = reg.histogram(
            "deepspeed_tpu_serving_tpot_seconds",
            "mean time per output token after the first, observed once "
            "per finished request")
        # speculative decoding family (speculative.py; all still valid —
        # flat zeros — with speculation off, like the cache counters)
        self._m_invocations = reg.counter(
            "deepspeed_tpu_serving_decode_model_invocations_total",
            "decode-phase model program calls (plain decode steps + "
            "speculative verify calls) — tokens/invocation is the "
            "speculative figure of merit")
        self._m_spec_proposed = reg.counter(
            "deepspeed_tpu_serving_spec_proposed_tokens_total",
            "draft tokens proposed for verification")
        self._m_spec_accepted = reg.counter(
            "deepspeed_tpu_serving_spec_accepted_tokens_total",
            "draft tokens accepted (matched the model's greedy choice)")
        self._m_spec_rollback = reg.counter(
            "deepspeed_tpu_serving_spec_rollback_pages_total",
            "draft-reserved KV pages rolled back after rejection")
        self._m_spec_fallback = reg.counter(
            "deepspeed_tpu_serving_spec_fallback_requests_total",
            "non-greedy requests routed to the plain decode program by "
            "the sampling guard (speculation never changes the "
            "sampling distribution)")
        self._m_spec_tps = reg.histogram(
            "deepspeed_tpu_serving_spec_tokens_per_step",
            "tokens emitted per sequence per verify call (accepted "
            "prefix + the model's correction token; >= 1)")
        self._m_spec_rate = reg.gauge(
            "deepspeed_tpu_serving_spec_acceptance_rate",
            "cumulative accepted / proposed draft tokens")
        self._m_spec_verify_h = reg.histogram(
            "deepspeed_tpu_serving_spec_verify_seconds",
            "one batched speculative verify program wall time")
        # fused multi-step decode family (decode_horizon > 1,
        # docs/SERVING.md "Multi-step decode"): the dispatch economics
        # of the K-step decode scan — tokens banked per device
        # round-trip, round-trips paid, horizons shrunk under pressure
        self._m_tokens_per_dispatch = reg.histogram(
            "deepspeed_tpu_serving_decode_tokens_per_dispatch",
            "tokens emitted per decode-phase device dispatch (a fused "
            "multi-step scan emits up to horizon x batch per dispatch; "
            "the K=1 loop at most batch)")
        self._m_host_syncs = reg.counter(
            "deepspeed_tpu_serving_decode_host_syncs_total",
            "decode-phase host round-trips (device token pulls): the "
            "fused multi-step scan pays ONE per horizon where the K=1 "
            "loop pays one per token")
        self._m_horizon_shrink = reg.counter(
            "deepspeed_tpu_serving_decode_horizon_shrink_total",
            "multi-step dispatches whose horizon was shrunk below "
            "decode_horizon (KV-pool headroom pressure or short row "
            "budgets) instead of preempting mid-scan")
        # serving-SLO family (docs/OBSERVABILITY.md): deadline expiry,
        # queue wait, and TTFT/TPOT SLO-violation accounting live on the
        # engine; the shed + breaker halves of the family live on the
        # fleet tier (serving/admission.py, serving/router.py)
        self._m_deadline = reg.counter(
            "deepspeed_tpu_serving_slo_deadline_exceeded_total",
            "requests expired past their deadline at a step boundary "
            '(finish_reason="deadline")')
        self._m_queue_wait_h = reg.histogram(
            "deepspeed_tpu_serving_slo_queue_wait_seconds",
            "enqueue -> admission wait, observed per admission (a "
            "preempted sequence re-admitting observes again)")
        self._m_ttft_viol = reg.counter(
            "deepspeed_tpu_serving_slo_ttft_violations_total",
            "first tokens arriving later than slo_ttft_s")
        self._m_tpot_viol = reg.counter(
            "deepspeed_tpu_serving_slo_tpot_violations_total",
            "finished requests whose mean inter-token time exceeded "
            "slo_tpot_s")
        self._m_kv_blocks = reg.counter(
            "deepspeed_tpu_serving_decode_kv_blocks_total",
            "blocks of KV pages the paged decode kernel's loops walked, a "
            "layer call (visible pages / (pages a block x this) is the "
            "fill of a block)")
        # the second kind of cache (state slots) and the expert share
        self._m_state_slots = reg.gauge(
            "deepspeed_tpu_serving_state_slots_in_use",
            "state slots held by scheduled sequences (a model whose layers "
            "keep recurrent state; 0 otherwise)")
        self._m_state_preempt = reg.counter(
            "deepspeed_tpu_serving_state_slot_preemptions_total",
            "preemptions that dropped a sequence's recurrent state (its "
            "re-prefill recomputes it)")
        self._m_moe_picks = reg.counter(
            "deepspeed_tpu_serving_moe_local_picks_total",
            "router picks that landed on an expert this chip holds")
        self._m_moe_touched = reg.counter(
            "deepspeed_tpu_serving_moe_experts_touched_total",
            "held experts with at least one pick, summed over expert-layer "
            "calls (the weights a step has to read)")
        self._m_moe_padded = reg.counter(
            "deepspeed_tpu_serving_moe_padded_rows_total",
            "rows of the sorted and padded buffer the grouped matmul ran "
            "(whole blocks per touched expert)")
        self._m_moe_grid = reg.counter(
            "deepspeed_tpu_serving_moe_grid_rows_total",
            "rows of the worst-case buffer the grouped matmul's grid spans "
            "(1 - padded / grid is the share of it that was skipped)")
        # last-published absolutes for the per-engine cache counters, so
        # the process-cumulative registry counters only receive deltas
        self._cache_pub = {"hits": 0, "misses": 0, "evictions": 0}

    def _phase(self, name: str, hist, **attrs) -> PhaseTimer:
        """Profiler annotation + wall-time histogram + trace-ring span
        for one serving phase (prefill/decode); ``attrs`` land on the
        span only."""
        def sink(_n, dt):
            hist.observe(dt)
            self._phase_s[name] = self._phase_s.get(name, 0.0) + dt

        return PhaseTimer(name, sink=sink, step=self._step_id, **attrs)

    def _step_span(self, name: str, parent: str = "", **attrs) -> PhaseTimer:
        """One span (cat ``serve``) of the current step: ring + profiler
        annotation, its seconds folded into the step's totals whether or
        not the ring is on.  ``parent`` names the phase this span sits
        inside, whose self time is its own less this."""
        def sink(_n, dt):
            self._phase_s[name] = self._phase_s.get(name, 0.0) + dt
            if parent:
                self._child_s[parent] = self._child_s.get(parent, 0.0) + dt

        return PhaseTimer(name, sink=sink, cat="serve", step=self._step_id,
                          **attrs)

    def _on_stall(self, _loop: str, step, ratio: float) -> None:
        """Watchdog incident edge: the decomposition of the stalled step
        from the spans just recorded, largest self time first.  A stall in
        ``device_wait`` is the device's or the runtime's.  Only decode-only
        steps are rated, and in those nothing is in flight when the inputs
        are uploaded and the program is called, so a stall anywhere else is
        the host's.  (A step that carries chunks is several programs long
        and is not rated.)"""
        ph, counts = self._phase_s, self._step_counts
        total = ph["serve_step"]
        self_ms = {(n + " self" if n in self._child_s else n):
                   1e3 * (v - self._child_s.get(n, 0.0))
                   for n, v in ph.items() if n != "serve_step"}
        self_ms["other"] = 1e3 * total - sum(self_ms.values())
        order = sorted(self_ms, key=self_ms.get, reverse=True)
        median_ms = 1e3 * total / ratio
        logger.warning(
            f"serve step {step}: {1e3 * total:.0f} ms (median "
            f"{median_ms:.0f}): "
            + ", ".join(f"{n} {self_ms[n]:.0f}" for n in order)
            + f"; {counts['chunks']} chunks, {counts['decode_rows']} rows")
        fields = dict(step=step, phase=order[0].replace(" self", ""),
                      ms=1e3 * total, median_ms=median_ms,
                      chunks=counts["chunks"], rows=counts["decode_rows"],
                      **{n.replace(" ", "_") + "_ms": v
                         for n, v in self_ms.items()})
        record_event("serve_stall", cat="serve", **fields)
        flight = get_flight_recorder()
        if flight is not None:
            flight.note("serve_stall", **fields)

    # -- request lifecycle bookkeeping ---------------------------------------
    def _reqtrace(self, seq: SequenceState):
        """The fleet ledger entry for ``seq`` (None when the engine runs
        standalone — every reqtrace hook below is then a no-op)."""
        if seq is None or seq.trace_id is None:
            return None
        led = get_reqtrace_ledger()
        return None if led is None else led.get(seq.trace_id)

    def _note_tokens(self, seq: SequenceState, n: int = 1,
                     t: Optional[float] = None) -> None:
        """Account ``n`` newly emitted tokens against the request: the
        first one closes the TTFT window (enqueue -> first token,
        queue wait included).  ``t`` is the token's emit timestamp — a
        fused multi-step dispatch passes per-token timestamps
        RECONSTRUCTED from the horizon (token j landed ~j+1 device
        steps in), so TTFT/TPOT and their SLO-violation checks never
        see a K-token burst stamped at one instant."""
        m = self._req_meta.get(seq.uid)
        if m is None:
            return
        now = t if t is not None else time.perf_counter()
        if m["t_first"] is None:
            m["t_first"] = now
            ttft = now - m["t0"]
            self._m_ttft_h.observe(ttft)
            tr = self._reqtrace(seq)
            if tr is not None:
                # ledger TTFT is set-once from FIRST submission (a
                # re-dispatched request keeps its original clock); the
                # histogram above keeps per-(re)enqueue semantics
                tr.note_first_token(now)
                tr.transition("decode",
                              getattr(self, "trace_owner", "engine"), now)
            if 0 < self.config.slo_ttft_s < ttft:
                self._m_ttft_viol.inc()
                slo_exemplar("deepspeed_tpu_serving_slo_ttft_violations_total",
                             seq.trace_id, uid=seq.uid,
                             ttft_s=round(ttft, 6))
                self._slo_violation("ttft", ttft, self.config.slo_ttft_s,
                                    seq.uid, seq.trace_id)
        m["t_last"] = now
        m["n"] += n

    def _slo_violation(self, kind: str, value: float, limit: float,
                       uid: int, trace_id: Optional[str] = None) -> None:
        """One call site for the ``slo_violation`` event (TTFT and TPOT
        both thread through here — the name lint wants a single owner)."""
        record_event("slo_violation", cat="serve", kind=kind,
                     value=round(value, 6), limit=limit, uid=uid,
                     **({} if trace_id is None else {"trace_id": trace_id}))

    def _finish_request(self, seq: SequenceState) -> None:
        """Close the request span and observe TPOT (mean inter-token
        time after the first — the decode-side latency SLO)."""
        m = self._req_meta.pop(seq.uid, None)
        if m is None:
            return
        if m["n"] > 1 and m["t_first"] is not None:
            tpot = (m["t_last"] - m["t_first"]) / (m["n"] - 1)
            self._m_tpot_h.observe(tpot)
            if 0 < self.config.slo_tpot_s < tpot:
                self._m_tpot_viol.inc()
                slo_exemplar("deepspeed_tpu_serving_slo_tpot_violations_total",
                             seq.trace_id, uid=seq.uid,
                             tpot_s=round(tpot, 6))
                self._slo_violation("tpot", tpot, self.config.slo_tpot_s,
                                    seq.uid, seq.trace_id)
        end_span(m["span"], generated=m["n"],
                 total_s=round(time.perf_counter() - m["t0"], 6))
        if seq.trace_id is not None:
            led = get_reqtrace_ledger()
            if led is not None:
                led.finish(seq.trace_id, seq.finish_reason or "complete")

    def _pool_occupancy(self) -> Dict[str, int]:
        """Current KV page-pool occupancy, attached to every admission/
        preemption event so scheduling decisions are explainable from
        the event log alone."""
        a = self.allocator
        return {"pages_used": a.used_pages, "pages_free": a.free_pages,
                "pages_pinned": a.lru_pages}

    def _publish_pool_gauges(self) -> None:
        occ = self._pool_occupancy()
        self._m_kv_used.set(occ["pages_used"])
        self._m_kv_free.set(occ["pages_free"])
        self._m_kv_pinned.set(occ["pages_pinned"])

    def _sync_cache_counters(self) -> None:
        """Forward allocator/prefix-cache counter deltas to the registry
        (those objects stay the per-engine source of truth; re-homing
        them wholesale would break per-engine ``cache_stats``)."""
        self._publish_pool_gauges()
        pub = self._cache_pub
        ev = self.allocator.evictions
        if ev > pub["evictions"]:
            self._m_cache_evict.inc(ev - pub["evictions"])
            pub["evictions"] = ev
        if self.prefix_cache is not None:
            h, m = self.prefix_cache.hits, self.prefix_cache.misses
            if h > pub["hits"]:
                self._m_cache_hits.inc(h - pub["hits"])
                pub["hits"] = h
            if m > pub["misses"]:
                self._m_cache_misses.inc(m - pub["misses"])
                pub["misses"] = m
        self._m_cached_pages.set(self.allocator.cached_pages)

    # -- request API ---------------------------------------------------------
    def put(self, request: RaggedRequest, *, record_shed: bool = True
            ) -> int:
        """Queue a request; returns its uid.

        ``record_shed=False`` hands shed accounting to the caller: a
        multi-candidate placer (the fleet router) tries several engines
        and must count at most ONE shed per request, not one per
        refusing engine."""
        if self._draining:
            raise RuntimeError("engine is draining/retired: no new "
                               "admissions (route to another replica)")
        uid = request.uid if request.uid is not None else next(self._uid)
        n = len(request.prompt_ids)
        if n == 0:
            raise ValueError("empty prompt")
        if n >= self.max_seq_len:
            raise ValueError(f"prompt length {n} >= max_seq_len "
                             f"{self.max_seq_len}")
        if self.blocks is not None:
            self.blocks.check_request(request)
        elif request.denoising_steps is not None:
            raise ValueError("denoising_steps: this model generates one "
                             "token a step, not by blocks")
        if (self.config.max_queue_depth > 0
                and len(self._queue) >= self.config.max_queue_depth):
            # bounded queue: shed LOUDLY instead of growing the queue
            # into an OOM/preemption storm.  Deferred import: admission
            # (serving tier) owns the shed counter; serving imports
            # inference, never the reverse at module scope.
            from ...serving.admission import (record_shed as _record_shed,
                                              retry_after_hint)

            hint = retry_after_hint(len(self._queue))
            if record_shed:
                _record_shed(request.priority, "engine_queue_full", hint,
                             uid=request.uid, trace_id=request.trace_id)
            raise RejectedError("engine_queue_full", retry_after_s=hint,
                                priority=request.priority)
        now = time.perf_counter()
        self._queue.append(SequenceState(
            uid=uid, tokens=list(request.prompt_ids), prompt_len=n,
            max_new_tokens=request.max_new_tokens,
            temperature=request.temperature, eos_id=request.eos_id,
            priority=int(request.priority),
            deadline=(now + max(0.0, float(request.deadline_s))
                      if request.deadline_s is not None else 0.0),
            enqueue_order=next(self._enqueue_counter),
            queued_at=now, trace_id=request.trace_id,
            denoising_steps=request.denoising_steps or 0))
        self._req_meta[uid] = {
            "t0": now, "t_first": None, "t_last": None,
            "n": 0,
            "span": begin_span("request", cat="serve", uid=uid,
                               prompt_tokens=n, priority=request.priority,
                               max_new_tokens=request.max_new_tokens,
                               **({} if request.trace_id is None
                                  else {"trace_id": request.trace_id,
                                        "replica": getattr(
                                            self, "trace_owner", "engine")}))}
        self._m_requests.inc()
        self._m_queue.set(len(self._queue))
        return uid

    def has_work(self) -> bool:
        return bool(self._queue) or any(s is not None for s in self._slots)

    @property
    def queue_depth(self) -> int:
        """Requests waiting for admission (what the queue-depth gauge
        publishes) — the router's load signal."""
        return len(self._queue)

    @property
    def active_count(self) -> int:
        """Occupied decode slots (what the batch-occupancy gauge
        publishes, un-normalized)."""
        return sum(1 for s in self._slots if s is not None)

    def inflight_uids(self) -> List[int]:
        """uids of every unfinished request this engine owns: admitted
        (in a slot) first, then queued."""
        return ([s.uid for s in self._slots if s is not None]
                + [s.uid for s in self._queue])

    def ready_uids(self) -> List[int]:
        """uids of admitted sequences that are decode-ready (prefill
        complete, first token sampled) — the migration candidates a
        disaggregated router streams from prefill to decode replicas."""
        return [s.uid for s in self._slots
                if s is not None and self._ready_to_decode(s)]

    # -- KV-page migration (export / import / release) -----------------------
    def _find_slotted(self, uid: int) -> SequenceState:
        seq = next((s for s in self._slots
                    if s is not None and s.uid == uid), None)
        if seq is None:
            raise KeyError(f"uid {uid} is not in a decode slot (queued or "
                           "unknown sequences have no KV pages to export)")
        return seq

    def read_state(self, uid: int) -> Dict[str, np.ndarray]:
        """The recurrent state an admitted sequence holds now, per state
        leaf ``[layers that keep it, *per-sequence shape]`` (``{}`` for a
        model that keeps only pages): a host copy of its slot, for a check
        against a reference.  After ``m`` returned tokens the state has
        taken in the prompt and the first ``m - 1`` of them."""
        slot = self._find_slotted(uid).slot
        # dstpu-lint: allow[host-sync] a checking aid, never on a serving path
        return {name: np.asarray(self._pools[name][:, slot])
                for name in self._state}

    def read_kv(self, uid: int) -> List[Dict[str, Any]]:
        """What an admitted sequence's grouped-query layers (``gqa_full`` /
        ``gqa_window``) have cached now, a layer of the stack at a time:
        ``{"first": the first position held, "k": [positions, KVH, k_dim],
        "v": [positions, KVH, v_dim]}`` — a full layer's pages from position
        0, a window layer's ring in position order (the last
        ``sliding_window`` positions at most) — host copies with the key
        rows' split undone, for a check against a reference.  After ``m``
        returned tokens the cache holds the prompt and the first ``m - 1``
        of them."""
        from ...ops.pallas.paged_attention import merged_keys

        seq = self._find_slotted(uid)
        if self.blocks is not None:  # the committed blocks' K/V
            return self.blocks.read_kv(seq)
        n, ps = seq.length - 1, self.block.page_size
        sentinel_expect_recompile("read_kv")
        pages = paged_gather_pages(self._pools, seq.pages[:-(-n // ps)], 1)
        out, seen = [], collections.Counter()
        for types, reps in served_runs(self.cfg):
            for _ in range(reps):
                for t in types:
                    sh, l = gqa_shape(self.cfg, t.mixer), seen[t.mixer]
                    seen[t.mixer] += 1
                    if sh.window:  # the type's ring leaves, keys then values
                        m = min(n, sh.window)
                        rows = (np.arange(n - m, n)) % sh.window
                        # dstpu-lint: allow[host-sync] a checking aid
                        k, v = (np.asarray(self._pools[nm][l, seq.slot])[rows]
                                for nm in t.state(self.cfg))
                    else:  # its page leaves
                        m = n
                        k, v = (pages[nm][l].reshape(-1, pages[nm].shape[-1])
                                [:n] for nm in t.pages(self.cfg))
                    out.append({
                        "first": n - m,
                        "k": np.asarray(merged_keys(
                            np.asarray(k, np.float32), sh.split,
                            sh.kv_heads)),
                        "v": np.asarray(v, np.float32).reshape(
                            m, sh.kv_heads, sh.v_dim)})
        return out

    def read_latent(self, uid: int) -> np.ndarray:
        """The latent rows an admitted sequence has cached now, ``[layers,
        positions, kv_lora_rank + qk_rope_head_dim]`` (each ``[c | k_rope]``,
        the lane padding cut off): a host copy of its pages in position
        order, for a check against a reference.  After ``m`` returned tokens
        the cache holds the prompt and the first ``m - 1`` of them."""
        seq = self._find_slotted(uid)
        n, ps = seq.length - 1, self.block.page_size
        # the gather runs op-by-op outside the step programs, as an export's
        sentinel_expect_recompile("read_latent")
        (rows,) = paged_gather_pages(self._pools, seq.pages[:-(-n // ps)],
                                     self.cfg.kv_heads).values()

        width = self.cfg.kv_lora_rank + self.cfg.qk_rope_head_dim
        return rows.reshape(rows.shape[0], -1, rows.shape[-1])[:, :n, :width]

    def read_eva(self, uid: int) -> np.ndarray:
        """The rows an admitted sequence's EVA-attention layers hold for a
        query now, ``[layers, rows, 2 * heads * head_dim]`` (each ``[key |
        value]``): the closed windows' summaries in chunk order, then the open
        window's rows in position order — a host copy, for a check against a
        reference.  After ``m`` returned tokens the cache holds the prompt
        and the first ``m - 1`` of them."""
        seq, ev = self._find_slotted(uid), self.rows
        n, ps = seq.length - 1, self.block.page_size
        n_vis, n_open = ev.visible(n), n % ev.window
        sentinel_expect_recompile("read_eva")
        pages = seq.pages[:n_vis // ps] \
            + seq.pages[seq.n_sum:seq.n_sum + -(-n_open // ps)]
        leaves = tuple(page_leaves(self.cfg))  # keys, then values
        got = paged_read_rows(self._pools, leaves, pages,
                              self.block.trash_page)
        return np.concatenate([got[nm] for nm in leaves],
                              axis=-1)[:, :n_vis + n_open]

    def export_sequence(self, uid: int) -> KVPageBundle:
        """Serialize an admitted sequence's KV pages + scheduling state
        into a :class:`KVPageBundle` (host arrays, bit-exact).  The
        sequence KEEPS running here — callers release it only after a
        successful import elsewhere, so a failed handoff loses nothing."""
        if "bundle_export" in self.unsupported:
            raise NotImplementedError("KVPageBundle export: "
                                      + self.unsupported["bundle_export"])
        seq = self._find_slotted(uid)
        if self.blocks is not None:
            self.blocks.refuse_export()
        ps = self.block.page_size
        immutable = seq.prefilled // ps  # pages never written again
        keys = list(seq.page_keys[:min(immutable, len(seq.page_keys))])
        bundle = KVPageBundle(
            uid=seq.uid, tokens=list(seq.tokens), prompt_len=seq.prompt_len,
            max_new_tokens=seq.max_new_tokens, temperature=seq.temperature,
            eos_id=seq.eos_id, prefilled=seq.prefilled,
            decode_entry=seq.decode_entry, page_size=ps, page_keys=keys,
            priority=seq.priority, deadline=seq.deadline,
            src_pages=self.allocator.export_meta(seq.pages),
            arrays=paged_gather_pages(self._pools, seq.pages,
                                      self.cfg.kv_heads),
            model_sig=bundle_signature(self.cfg),
            kv_quant=bool(self.config.kv_quant), dtype=self.config.dtype)
        tr = self._reqtrace(seq)
        if tr is not None:
            # the handoff starts here: the ledger phase flips to
            # kv_transfer, and the bundle carries the trace context —
            # trace id, clock-free ledger snapshot, per-hop stamp list
            # (the wire codec appends wall stamps as the bytes move)
            tr.transition("kv_transfer",
                          getattr(self, "trace_owner", "engine"))
            bundle.trace = {"trace_id": seq.trace_id,
                            "snapshot": tr.wire_snapshot(), "hops": []}
        elif seq.trace_id is not None:
            bundle.trace = {"trace_id": seq.trace_id, "snapshot": None,
                            "hops": []}
        record_event("kv_export", cat="serve", uid=uid,
                     pages=len(seq.pages), tokens=len(seq.tokens),
                     **({} if seq.trace_id is None
                        else {"trace_id": seq.trace_id}))
        # the gather runs op-by-op outside the step programs: announce
        # its compiles so no sentinel flags them as steady-state
        sentinel_expect_recompile("kv_export")
        return bundle

    def _check_bundle(self, b: KVPageBundle) -> None:
        if "bundle_import" in self.unsupported:
            raise ValueError("KVPageBundle import: "
                             + self.unsupported["bundle_import"])
        sig = bundle_signature(self.cfg)
        if tuple(b.model_sig) != sig:
            raise ValueError(f"bundle model_sig {tuple(b.model_sig)} != "
                             f"engine {sig}")
        if b.page_size != self.block.page_size:
            raise ValueError(f"bundle page_size {b.page_size} != "
                             f"{self.block.page_size}")
        if bool(b.kv_quant) != bool(self.config.kv_quant):
            raise ValueError("kv_quant mismatch between bundle and engine")
        if str(b.dtype) != str(self.config.dtype):
            # checked here, not just in the fresh-page scatter: an
            # all-adopted import never scatters, and sharing pages
            # across precisions would silently break bit-identity
            raise ValueError(f"bundle dtype {b.dtype!r} != engine dtype "
                             f"{self.config.dtype!r}")
        if b.n_pages > self.block.max_pages_per_seq:
            raise ValueError(f"bundle spans {b.n_pages} pages > "
                             f"max_pages_per_seq {self.block.max_pages_per_seq}")
        if len(b.tokens) >= self.max_seq_len:
            raise ValueError(f"bundle length {len(b.tokens)} >= max_seq_len "
                             f"{self.max_seq_len}: nothing left to decode")
        ready = (b.generated > 0 or b.decode_entry) \
            and b.prefilled >= len(b.tokens) - 1
        if not ready:
            raise ValueError(
                "bundle is not decode-ready (mid-prefill handoff is not "
                "supported: re-dispatch the request instead)")

    def import_sequence(self, bundle: KVPageBundle) -> bool:
        """Adopt a migrated sequence: place its KV pages in this pool
        (sharing content-matched registered pages instead of copying —
        ref-count adoption) and schedule it straight into a decode slot.

        Returns ``False`` — with the engine untouched — when no slot or
        not enough pages are free (the caller tries another replica);
        raises ``ValueError`` on genuine incompatibility (different
        model geometry / page size / kv_quant / dtype)."""
        self._check_bundle(bundle)
        slot = next((i for i, s in enumerate(self._slots) if s is None), None)
        if slot is None:
            return False
        n = bundle.n_pages
        keys = list(bundle.page_keys)
        adopt_keys: List[Any] = [None] * n
        if self.prefix_cache is not None:
            for j, k in enumerate(keys[:n]):
                adopt_keys[j] = k
        try:
            pages, reused = self.allocator.adopt(adopt_keys)
        except MemoryError:
            return False
        fresh = [j for j, r in enumerate(reused) if not r]
        if fresh:
            # dtype mismatches raise inside the scatter — but only after
            # pages were allocated; free them so a refused import does
            # not leak pool capacity
            try:
                self._pools = paged_scatter_pages(
                    self._pools, [pages[j] for j in fresh],
                    {k: v[:, fresh] for k, v in bundle.arrays.items()})
            except ValueError:
                self.allocator.free(pages)
                raise
            # op-by-op scatter outside the step programs (see export)
            sentinel_expect_recompile("kv_import")
        if self.prefix_cache is not None:
            # publish freshly-written FULL pages locally (first writer
            # wins) so the importing replica's cache warms too; adopted
            # pages are already registered here
            for j in fresh:
                if j < len(keys):
                    self.allocator.register(pages[j], keys[j])
        trace_id = None
        if bundle.trace is not None:
            trace_id = bundle.trace.get("trace_id")
        seq = SequenceState(
            uid=bundle.uid, tokens=list(bundle.tokens),
            prompt_len=bundle.prompt_len,
            max_new_tokens=bundle.max_new_tokens,
            temperature=bundle.temperature, eos_id=bundle.eos_id,
            slot=slot, pages=pages, prefilled=bundle.prefilled,
            decode_entry=bundle.decode_entry, page_keys=keys,
            registered_upto=len(keys),
            priority=bundle.priority, deadline=bundle.deadline,
            enqueue_order=next(self._enqueue_counter), trace_id=trace_id)
        seq.admit_order = next(self._admit_counter)
        self._slots[slot] = seq
        self._page_table[slot, :] = self.block.trash_page
        self._page_table[slot, :len(pages)] = pages
        now = time.perf_counter()
        if trace_id is not None:
            led = get_reqtrace_ledger()
            if led is not None:
                tr = led.get(trace_id)
                if tr is None and bundle.trace.get("snapshot") is not None:
                    # cross-process import: re-anchor the sender's
                    # ledger here, wire transit folded into kv_transfer
                    tr = led.adopt(bundle.trace["snapshot"],
                                   transit_s=float(bundle.trace.get(
                                       "transit_s", 0.0)))
                if tr is not None:
                    tr.transition("decode",
                                  getattr(self, "trace_owner", "engine"),
                                  now)
        # TTFT belongs to the exporting engine (it sampled the first
        # token); local TPOT accounting restarts at the handoff
        self._req_meta[bundle.uid] = {
            "t0": now, "t_first": now if bundle.generated > 0 else None,
            "t_last": now, "n": bundle.generated,
            "span": begin_span("request_migrated", cat="serve",
                               uid=bundle.uid, tokens=len(bundle.tokens),
                               adopted_pages=sum(reused),
                               **({} if trace_id is None
                                  else {"trace_id": trace_id,
                                        "replica": getattr(
                                            self, "trace_owner",
                                            "engine")}))}
        record_event("kv_import", cat="serve", uid=bundle.uid, slot=slot,
                     pages=n, adopted=sum(reused),
                     **({} if trace_id is None else {"trace_id": trace_id}),
                     **self._pool_occupancy())
        self._publish_pool_gauges()
        return True

    def release_sequence(self, uid: int, reason: str = "migrated") -> None:
        """Drop an admitted sequence WITHOUT finishing it (its pages are
        freed, its request span closed) — the source side of a completed
        migration, after ``import_sequence`` succeeded elsewhere."""
        seq = self._find_slotted(uid)
        self.allocator.free(seq.pages)
        self._page_table[seq.slot, :] = self.block.trash_page
        self._release_slot(seq.slot)
        seq.slot, seq.pages = -1, []
        m = self._req_meta.pop(uid, None)
        if m is not None:
            end_span(m["span"], released=reason, generated=m["n"])
        self._publish_pool_gauges()

    # -- tiered KV cache: host-RAM spill & restore ---------------------------
    def _capture_evicted_page(self, page: int, key: Any) -> bool:
        """``BlockAllocator.spill_hook``: decide whether an LRU-evicted
        prefix page is captured for the host tier.  Capturing only
        QUEUES the page (bounded by ``kv_tier.spill_inflight``) — the
        allocator pins it via refcount so it cannot be handed out, and
        therefore never overwritten, until :meth:`_drain_spills` commits
        the D2H copy at the next step boundary."""
        tier = self.kv_tier
        if tier is None or key is None:
            return False
        if len(self._pending_spills) >= tier.config.spill_inflight:
            tier.note_capture_dropped()
            return False
        if tier.has(key) or key in self._pending_spill_keys:
            # same chain key => bit-identical content (the programs are
            # deterministic): the copy already sits in the host tier, or
            # is already queued this drain window — don't pin a second
            # page and D2H the same bytes twice
            return False
        self._pending_spills.append((page, key))
        self._pending_spill_keys.add(key)
        return True

    def _drain_spills(self) -> None:
        """Commit pending host-tier spills in ONE batched D2H gather
        (step boundary, off the hot device path): gather the pinned
        pages' slices across every pool leaf — the exact-dtype
        ``paged_gather_pages`` layout KV migration uses — stamp the
        wire format's per-page CRC32, insert into the host LRU, then
        release the pins so the pages rejoin the free list."""
        if not self._pending_spills:
            return
        from ...serving.kv_tier import batch_page_crcs, page_slices

        pend, self._pending_spills = self._pending_spills, []
        self._pending_spill_keys = set()
        t0 = time.perf_counter()
        # bucket the gather rows to powers of two (trash-padded) so the
        # op-by-op path keeps a small fixed compiled-shape set
        rows = pad_pages_pow2([p for p, _ in pend], self.block.trash_page)
        self._step_parts.add(("kv_spill", len(rows)))
        sentinel_expect_recompile("kv_tier_spill")
        arrays = paged_gather_pages(self._pools, rows, self.cfg.kv_heads)
        arrays = {n: a[:, :len(pend)] for n, a in arrays.items()}
        crcs = batch_page_crcs(arrays)
        for j, (page, key) in enumerate(pend):
            self.kv_tier.insert(key, page_slices(arrays, j), crcs[j])
            self.allocator.release_spill_pin(page)
        self.kv_tier.note_spill(len(pend), time.perf_counter() - t0)

    def flush_spills(self) -> None:
        """Commit any pending host-tier spills NOW (tests, retirement) —
        the engine otherwise drains them at the next step boundary."""
        self._drain_spills()

    def _current_match(self, seq: SequenceState):
        """Memoized device prefix match for a queued sequence: walked
        only when the registry generation moved, and RESUMED from the
        memo's end when only registrations happened (see _admit)."""
        if seq.match_gen != self.allocator.generation:
            resume = (seq.cached_match
                      if seq.match_evict_gen
                      == self.allocator.evict_generation else None)
            seq.cached_match = self.prefix_cache.match(seq.tokens,
                                                       resume=resume)
            seq.match_gen = self.allocator.generation
            seq.match_evict_gen = self.allocator.evict_generation
        return seq.cached_match

    def _tier_restore(self, tokens: List[int], shared: List[int],
                      keys: List[Any], park: bool = False
                      ) -> Tuple[List[int], List[Any], List[int]]:
        """Extend a device prefix match with HOST-tier pages: continue
        the chain-key walk into the host LRU past the device hit,
        allocate fresh pages, H2D-scatter the restored KV (the same
        ``paged_scatter_pages`` path KV import uses, bucketed so one
        compiled shape set serves all restores), and REGISTER the pages
        under their chain keys — from here on they behave exactly like
        device cache hits (suffix-only prefill, CoW on a full hit,
        bit-identical streams).

        Returns ``(shared, keys, restored)`` — new lists; ``restored``
        pages arrive REFERENCED (their alloc ref), exactly like the
        claimed device matches the admission holds — the caller keeps
        the refs as the sequence's own, or frees them to re-park if it
        blocks.  With ``park=True`` (the prefetch path) the refs are
        dropped here: the pages sit registered + LRU-parked at the MRU
        end, and the eventual admission maps them as device hits.  The
        prefetch path spends only truly-free pages and never overflows
        the LRU cap — prefetch must not evict content admission is
        about to need."""
        tier = self.kv_tier
        ps = self.block.page_size
        n_full = len(tokens) // ps
        if tier is None or len(shared) >= n_full:
            return shared, keys, []
        host_keys = self.prefix_cache.host_extend(tokens, keys, tier)
        # miss accounting (admission attempts only — prefetch re-walks a
        # blocked head every step and must not inflate the rate): the
        # tier missed when the walk needed pages it does not hold — an
        # EMPTY extension past a short device match included
        missed = len(shared) + len(host_keys) < n_full
        if not host_keys:
            if missed and not park:
                tier.note_miss()
            return shared, keys, []
        if not park:
            # hopeless-admission guard: every non-device-matched page
            # (restored or computed, +1 for a possible CoW duplicate)
            # must come out of the pool — if even that total cannot fit,
            # the admission will block regardless, and restoring now
            # would churn restore -> block -> park -> trim every step
            n_total = -(-len(tokens) // ps)
            if n_total - len(shared) + 1 > self.allocator.free_pages:
                return shared, keys, []
        cap = self.allocator.free_pages
        if park:
            cap = min(self.allocator.uncached_free_pages,
                      (self.allocator.cache_cap - self.allocator.lru_pages
                       if self.allocator.cache_cap > 0 else cap))
        if cap <= 0:
            return shared, keys, []
        entries = []
        for k in host_keys[:cap]:
            e = tier.get(k)  # CRC-verified; a corrupt page refuses
            if e is None:    # loudly and the chain ends here (miss)
                break
            entries.append(e)
        if len(entries) < min(len(host_keys), cap):
            missed = True  # a corrupt refusal cut the chain
        if missed and not park:
            tier.note_miss()
        if not entries:
            return shared, keys, []
        host_keys = host_keys[:len(entries)]
        t0 = time.perf_counter()
        fresh = self.allocator.alloc(len(entries))
        rows = pad_pages_pow2(fresh, self.block.trash_page)
        arrays: Dict[str, Any] = {}
        for name in entries[0]:
            parts = [e[name] for e in entries]
            if len(rows) > len(entries):
                pad_shape = (parts[0].shape[0], len(rows) - len(entries)) \
                    + parts[0].shape[2:]
                parts.append(np.zeros(pad_shape, dtype=parts[0].dtype))
            arrays[name] = np.concatenate(parts, axis=1)
        self._step_parts.add(("kv_restore", len(rows)))
        sentinel_expect_recompile("kv_tier_restore")
        # pad rows point at the trash page: scattered zeros land where
        # every step already writes garbage
        self._pools = paged_scatter_pages(self._pools, rows, arrays)
        for p, k in zip(fresh, host_keys):
            self.allocator.register(p, k)
        tier.note_restore(len(entries), time.perf_counter() - t0)
        if park:
            self.allocator.free(fresh)  # park at the LRU MRU end,
            # registered: the next admission maps them as device hits
            return shared + fresh, keys + host_keys, []
        return shared + fresh, keys + host_keys, fresh

    def _prefetch_restores(self) -> None:
        """Host-tier restore prefetch for queued-but-not-admitted
        requests: while the current batch decodes on device, the host
        walks the head-of-queue prefixes into the host tier and stages
        their pages back into the device pool (the H2D scatter chains
        behind the in-flight decode program).  At most once per step."""
        if self._prefetched:
            return
        self._prefetched = True
        tier = self.kv_tier
        if tier is None or not self._queue:
            return
        n = tier.config.prefetch_requests
        if n <= 0:
            return
        heads = sorted(self._queue,
                       key=lambda s: (s.priority, s.enqueue_order))[:n]
        for seq in heads:
            shared, keys = self._current_match(seq)
            self._tier_restore(seq.tokens, shared, keys, park=True)

    def tier_stats(self) -> Dict[str, float]:
        """Host-tier counters (``HostKVTier.stats``); empty dict with
        the tier off — dashboards need no conditional wiring."""
        return dict(self.kv_tier.stats()) if self.kv_tier else {}

    # -- replica retirement --------------------------------------------------
    def drain(self, max_steps: int = 10_000) -> Dict[str, Any]:
        """Stop admission and run every ADMITTED sequence to completion.

        Returns ``{"finished": {uid: SequenceState}, "pending":
        [SequenceState, ...]}``: ``finished`` holds the final states
        (full token lists, ``done`` flags) of the sequences that were
        in flight; ``pending`` are queued-but-never-admitted requests,
        returned UN-RUN for the caller to re-dispatch elsewhere.  After
        ``drain()`` the engine refuses new ``put()`` calls — this is
        clean replica retirement (``close()`` alone would drop in-flight
        work)."""
        self._draining = True
        pending = list(self._queue)
        self._queue.clear()
        for s in pending:
            m = self._req_meta.pop(s.uid, None)
            if m is not None:
                end_span(m["span"], requeued=True)
        inflight = {s.uid: s for s in self._slots if s is not None}
        steps = 0
        while any(s is not None for s in self._slots) or self._queue:
            if steps >= max_steps:
                logger.warning("engine_v2.drain: max_steps reached with "
                               "work pending")
                break
            self.step()
            steps += 1
        self._m_queue.set(len(self._queue))
        self._drain_spills()  # retirement commits captures, frees pins
        record_event("engine_drain", cat="serve", finished=len(inflight),
                     requeued=len(pending), steps=steps)
        return {"finished": inflight, "pending": pending}

    def abort_all(self, reason: str = "abort") -> List[int]:
        """Free every queued and admitted request WITHOUT running them
        (pages released, request spans closed); returns their uids.
        The hard-stop half of retirement — used after KV migration has
        moved what it could off a preempted replica, and by ``close()``
        so dropped work is never silent."""
        uids = [s.uid for s in self._queue]
        self._queue.clear()
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            self.allocator.free(s.pages)
            self._page_table[i, :] = self.block.trash_page
            self._release_slot(i)
            s.slot, s.pages = -1, []
            uids.append(s.uid)
        for uid in uids:
            self._spec_fallback_uids.discard(uid)
            m = self._req_meta.pop(uid, None)
            if m is not None:
                end_span(m["span"], aborted=reason, generated=m["n"])
        if uids:
            self._m_queue.set(0)
            self._publish_pool_gauges()
        return uids

    # -- scheduling ----------------------------------------------------------
    def _bucket(self, n: int) -> int:
        # power-of-two growth from a page-size multiple keeps every bucket a
        # multiple of page_size (prefill scatters whole pages)
        ps = self.block.page_size
        b = max(self.config.min_prefill_bucket, ps)
        b = -(-b // ps) * ps  # round up: prefill scatters whole pages
        while b < n:
            b *= 2
        # cap at the page-rounded model window (self.max_seq_len, not
        # block.max_seq_len): a learned-position model must not be prefetched
        # past its position table; paged_prefill clamps the residual < ps
        cap = -(-self.max_seq_len // ps) * ps
        return min(b, cap)

    def _preempt(self, seq: SequenceState) -> None:
        """Evict a running sequence to the queue head; it will re-prefill its
        prefix (recompute, the reference scheduler's KV-pressure relief)
        when pages free up — hitting the prefix cache it just populated,
        so with caching on the "recompute" is mostly a table lookup."""
        self.allocator.free(seq.pages)
        self._page_table[seq.slot, :] = self.block.trash_page
        self._release_slot(seq.slot)
        if self._state:
            # the state goes with the slot: the re-prefill recomputes it
            self._dstats["state_slot_preemptions"] += 1
            self._m_state_preempt.inc()
        if seq.block is not None:  # a half-denoised block is redone
            self.blocks.drop(seq)
        seq.slot, seq.pages, seq.prefilled, seq.n_sum = -1, [], 0, 0
        seq.page_keys, seq.registered_upto, seq.decode_entry = [], 0, False
        seq.cached_match, seq.match_gen, seq.match_evict_gen = None, -1, -1
        seq.queued_at = time.perf_counter()
        self._queue.insert(0, seq)
        self._m_preemptions.inc()
        self._step_counts["preempted"] += 1
        tr = self._reqtrace(seq)
        if tr is not None:
            # back to queue_wait; the re-run prefill chunks will ledger
            # as recompute (work the eviction bought, not first prefill)
            tr.note_preempt(getattr(self, "trace_owner", "engine"),
                            seq.queued_at)
        occ = self._pool_occupancy()
        record_event("preempt", cat="serve", step=self._step_id, uid=seq.uid,
                     prefix_tokens=seq.length,
                     **({} if seq.trace_id is None
                        else {"trace_id": seq.trace_id}), **occ)
        # preemptions are rare and always a capacity question — log the
        # occupancy that forced this one so "why was this request
        # preempted" is answerable without a trace dump
        logger.info(
            f"serving: preempted uid={seq.uid} (prefix {seq.length} tokens) "
            f"under KV-pool pressure: {occ['pages_used']} pages used, "
            f"{occ['pages_free']} free ({occ['pages_pinned']} of them "
            f"prefix-cache pinned) of {self.block.num_pages}")

    def _admit(self) -> List[SequenceState]:
        admitted = []
        ps = self.block.page_size
        for i, slot in enumerate(self._slots):
            if not self._queue:
                break
            if slot is not None:
                continue
            # admission head: highest priority class first, FCFS within
            # a class (enqueue_order; preempted sequences keep their
            # original stamp, so they re-admit at the front of their
            # class — the old insert-at-head behavior, now per class)
            seq = min(self._queue,
                      key=lambda s: (s.priority, s.enqueue_order))
            shared: List[int] = []
            keys: List[Any] = []
            if self.prefix_cache is not None:
                # memoized while the registry is unchanged: a blocked
                # head of queue must not re-hash its prompt every step.
                # Registrations only EXTEND a valid match, so unless an
                # eviction happened the walk resumes from the memo's end
                shared, keys = self._current_match(seq)
                # CLAIM the matched pages (+1 ref) before any further
                # allocation: the tier restore's alloc below — and this
                # admission's own alloc — must never evict a page this
                # sequence is about to map (an evicted-then-reused
                # match would alias two prefix positions onto one
                # physical page).  Released again if the admission
                # blocks; share()/free() touch neither registry
                # generation, so the memo above stays valid.
                for p in shared:
                    self.allocator.share(p)
                # the host tier extends the device hit: spilled pages
                # are restored (H2D, CRC-verified, registered) and from
                # here on the admission treats them as device hits.
                # Restored pages arrive referenced (alloc), exactly
                # like the claimed matches above.
                shared, keys, _restored = self._tier_restore(
                    seq.tokens, shared, keys)
            n_sum, n_rest = self.rows.admit_pages(seq.length)
            n_total = n_sum + n_rest
            m = len(shared)
            # fully-cached prompt (page-aligned): the last cached page is
            # replaced by a private COPY-ON-WRITE duplicate — the decode
            # program recomputes only the final prompt token and writes
            # its KV into the copy, never into the shared page
            full_hit = m > 0 and m * ps >= seq.length
            need_new = n_total - m + (1 if full_hit else 0)
            # exact admission check: every matched page is already
            # referenced (claimed above), so free_pages alone is the
            # allocatable budget — nothing here touches the LRU
            def _fits() -> bool:
                return need_new <= self.allocator.free_pages

            while not _fits():
                # priority admission: under pool pressure a high class
                # preempts strictly-lower-class running sequences
                # (lowest class, then youngest — cheapest prefix to
                # recompute) instead of waiting behind them.  _fits()
                # recomputes per eviction; a victim's ref drop on a
                # CLAIMED page changes nothing (we still hold it).
                victims = [s for s in self._slots
                           if s is not None and s.priority > seq.priority]
                if not victims:
                    break
                # futility guard: if even reclaiming EVERY victim's
                # pages cannot cover the head (optimistic upper bound —
                # shared pages may free less), evict nobody: a
                # mass-recompute that still fails to admit is the worst
                # outcome under exactly the pressure this path serves
                if need_new > (self.allocator.free_pages
                               + sum(len(v.pages) for v in victims)):
                    break
                self._preempt(max(victims,
                                  key=lambda s: (s.priority, s.admit_order)))
            if not _fits():
                if shared:
                    # blocked: release the claims — device matches and
                    # restored pages alike park (registered, MRU end) so
                    # the next attempt re-maps them as plain device hits
                    self.allocator.free(shared)
                break  # head-of-line blocking, like the reference's FCFS
            # the claims above ARE this sequence's references: one ref
            # per ``shared`` page is held from here on
            self._queue.remove(seq)
            seq.cached_match, seq.match_gen, seq.match_evict_gen = None, -1, -1
            if seq.queued_at > 0.0:
                self._m_queue_wait_h.observe(
                    time.perf_counter() - seq.queued_at)
            fresh = self.allocator.alloc(need_new)
            if full_hit:
                src, dst = shared[-1], fresh[-1]
                self._step_parts.add("copy_page")
                self._pools = self._copy_page(self._pools, jnp.int32(src),
                                              jnp.int32(dst))
                self.allocator.free([src])  # drop our ref on the original
                seq.pages = shared[:-1] + [dst]
                seq.prefilled = seq.length - 1
                seq.decode_entry = True
            else:
                seq.pages = shared + fresh
                seq.prefilled = m * ps
            seq.page_keys = keys
            # matched pages are already registered; the CoW copy stays
            # private (the original remains the canonical cached page)
            seq.registered_upto = n_total if full_hit else m
            if self.prefix_cache is not None:
                self.prefix_cache.count(m, seq.length // ps)
            self._stats["prefill_admitted_tokens"] += seq.length
            self._stats["prefix_hit_tokens"] += seq.prefilled
            self._stats["prefill_computed_tokens"] += seq.length - seq.prefilled
            self._m_admitted.inc(seq.length)
            self._m_hit_tokens.inc(seq.prefilled)
            self._m_computed.inc(seq.length - seq.prefilled)
            seq.slot = i
            seq.admit_order = next(self._admit_counter)
            seq.n_sum = n_sum
            self.rows.write_table(seq, self._page_table[i],
                                  self.block.trash_page)
            tr = self._reqtrace(seq)
            if tr is not None:
                # queue_wait closes here; "prefill" self-classifies as
                # recompute after a preemption or re-dispatch
                tr.transition("prefill",
                              getattr(self, "trace_owner", "engine"))
            record_event("admit", cat="serve", step=self._step_id,
                         uid=seq.uid, slot=i,
                         cache_hit_pages=m, new_pages=len(fresh),
                         full_hit=full_hit,
                         **({} if seq.trace_id is None
                            else {"trace_id": seq.trace_id}),
                         **self._pool_occupancy())
            admitted.append(seq)
            self._slots[i] = seq
            if self._state:
                self.state_slots.claim(i, seq.uid)
                self._m_state_slots.set(self.state_slots.in_use)
        self._publish_pool_gauges()
        return admitted

    def _register_pages(self, seq: SequenceState) -> None:
        """Offer every fully-written, not-yet-registered page of ``seq``
        to the prefix-cache registry (first writer wins).  Called after
        each KV-writing program, BEFORE any retire can free the pages —
        a registered page freed later parks in the LRU with its content
        intact."""
        if self.prefix_cache is None:
            return
        full = seq.prefilled // self.block.page_size
        if full <= seq.registered_upto:
            return
        seq.page_keys = self.prefix_cache.page_keys(seq.tokens, full,
                                                    seq.page_keys)
        for j in range(seq.registered_upto, full):
            self.allocator.register(seq.pages[j], seq.page_keys[j])
        seq.registered_upto = full

    def _emit_sampled(self, seq: SequenceState, logits, out) -> None:
        """Sample off prefix-end logits, append, record, maybe retire —
        shared by the whole-prompt and final-chunk prefill paths."""
        with self._step_span("device_wait", parent="prefill",
                             what="first_token", uid=seq.uid):
            # dstpu-lint: allow[host-sync] host sampling of the prefix-end
            # logits: one [vocab] row per ADMISSION, not per decode step
            logits = np.asarray(logits, np.float32)
        heads = None
        if self.cfg.pred_heads > 1:  # head 0 samples; the others' picks ride
            logits = logits.reshape(self.cfg.pred_heads, -1)
            # dstpu-lint: allow[host-sync] ``logits`` is the host copy above
            heads = [int(i) for i in np.argmax(logits[1:], axis=-1)]
            logits = logits[0]
        tok = self._sample(seq, logits)
        seq.tokens.append(tok)
        self._note_tokens(seq)
        out[seq.uid] = {"tokens": [tok], "done": False}
        if heads is not None:
            out[seq.uid]["heads"] = [heads]
        self._maybe_finish(seq, tok)
        if seq.done:
            out[seq.uid]["done"] = True
            out[seq.uid]["finish_reason"] = seq.finish_reason

    def _ready_to_decode(self, seq: SequenceState) -> bool:
        """KV written for tokens[0:length-1] AND a token has been sampled
        off the prefix end — mid-chunked-prefill sequences (and preempted
        ones re-prefilling their prefix) must not enter the decode batch.
        Exception: a fully-cached prompt (decode_entry) starts decoding
        immediately — its first decode step recomputes the final prompt
        token's KV (into its CoW page) and samples the first token.
        A model that generates by blocks: its whole blocks are prefilled."""
        if self.blocks is not None:
            return seq.prefilled >= self.blocks.prefill_end(seq)
        return ((seq.generated > 0 or seq.decode_entry)
                and seq.prefilled >= seq.length - 1)

    def _sample(self, seq: SequenceState, logits: np.ndarray) -> int:
        if seq.temperature <= 0.0:
            return int(np.argmax(logits))
        z = logits.astype(np.float64) / seq.temperature
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    def _release_slot(self, slot: int) -> None:
        """Empty a decode row, and the state slot that is that row."""
        self._slots[slot] = None
        if self._state:
            self.state_slots.release(slot)
            self._m_state_slots.set(self.state_slots.in_use)

    def _retire(self, seq: SequenceState) -> None:
        self.allocator.free(seq.pages)
        self._page_table[seq.slot, :] = self.block.trash_page
        self._release_slot(seq.slot)
        seq.slot, seq.pages, seq.done = -1, [], True
        self._spec_fallback_uids.discard(seq.uid)
        self._finish_request(seq)

    # -- deadlines -----------------------------------------------------------
    def _expire(self, seq: SequenceState,
                out: Dict[int, Dict[str, Any]]) -> None:
        """Retire one past-deadline sequence (queued or admitted) with
        ``finish_reason="deadline"``: its pages free immediately, the
        request span closes, and the expiry is a *finished* step-output
        record — the stream ends loudly, it does not hang."""
        seq.finish_reason = "deadline"
        self._m_deadline.inc()
        slo_exemplar("deepspeed_tpu_serving_slo_deadline_exceeded_total",
                     seq.trace_id, uid=seq.uid, generated=seq.generated)
        record_event("deadline_expired", cat="serve", step=self._step_id,
                     uid=seq.uid,
                     generated=seq.generated, priority=seq.priority,
                     **({} if seq.trace_id is None
                        else {"trace_id": seq.trace_id}))
        if seq.slot >= 0:
            self._retire(seq)  # single owner of the slotted teardown
        else:
            self.allocator.free(seq.pages)  # queued: normally none
            seq.pages, seq.done = [], True
            self._spec_fallback_uids.discard(seq.uid)
            self._finish_request(seq)
        out[seq.uid] = {"tokens": [], "done": True,
                        "finish_reason": "deadline"}

    def _expire_deadlines(self, out: Dict[int, Dict[str, Any]]) -> None:
        """Step-boundary deadline sweep over the queue AND the decode
        slots: a request whose ``deadline_s`` budget ran out stops
        consuming pool pages and decode slots NOW — under overload the
        pool drains toward work that can still meet its SLO."""
        now = time.perf_counter()
        for seq in [s for s in self._queue
                    if s.deadline and now >= s.deadline]:
            self._queue.remove(seq)
            self._expire(seq, out)
        for seq in list(self._slots):
            if seq is not None and seq.deadline and now >= seq.deadline:
                self._expire(seq, out)

    def _finish_reason_for(self, seq: SequenceState, token: int) -> str:
        """THE finish predicate ("" = keep running) — also stops
        mid-round emission in ``_spec_step`` via ``_should_finish``, so
        any new condition added here automatically drops accepted draft
        tokens past the boundary too.  Deadline expiry is NOT here: it
        happens at the step boundary (``_expire_deadlines``), never
        mid-emission."""
        if seq.generated >= seq.max_new_tokens:
            return "length"
        if seq.eos_id is not None and token == seq.eos_id:
            return "eos"
        if seq.length >= self.max_seq_len:
            return "max_seq_len"
        return ""

    def _should_finish(self, seq: SequenceState, token: int) -> bool:
        return bool(self._finish_reason_for(seq, token))

    def _maybe_finish(self, seq: SequenceState, token: int) -> None:
        reason = self._finish_reason_for(seq, token)
        if reason:
            seq.finish_reason = reason
            self._retire(seq)

    def _dispatch(self, part, program: PackedProgram, inputs, *rest, phase):
        """``program`` over ``(params, pools, *inputs, *rest)`` as the part
        ``part`` of this step (what the recompile sentinel hears of), the
        call itself in a ``dispatch`` span of the phase ``phase``.
        ``inputs`` is what the host built for this call, numpy arrays and
        numpy scalars: they cross the link here, packed into one new array
        and moved in ONE transfer (``input_transfers`` on the step), and
        nowhere else.  Being a copy, what crosses is out of reach of a
        later write to a mirror the engine keeps (``_page_table``, a
        chunk's view of its row, a block's ids), zero copy on the CPU
        backend included.  ``rest`` (the sampling key, a static horizon)
        is on the device or static already and passes through.  A part's
        first dispatch traces and lowers its program: that one runs beneath
        the deep frame, no later one (compile/deep_frame.py says why), and
        leaves the note its region table is built from when someone asks
        (telemetry/regions.py)."""
        self._step_parts.add(part)
        self._step_counts["input_transfers"] += 1
        packed, layout = pack_inputs(inputs)
        packed = jax.device_put(packed)
        with self._step_span("dispatch", parent=phase):
            return first_call_beneath(self._lowered_parts, part, program.run,
                                      self.params, self._pools, layout,
                                      packed, *rest)

    def _run_prefill_chunk(self, seq: SequenceState, start: int, c_n: int,
                           C: int):
        """One start-offset prefill call covering tokens
        [start, start+c_n) in a C-token program (C a page multiple) —
        shared by chunked prefill and the cached-prefix suffix path.
        Returns the logits of token start+c_n-1."""
        ids = np.zeros((C,), np.int32)
        ids[:c_n] = seq.tokens[start:start + c_n]
        rows, prev = self.rows.chunk_tables(
            seq, self._page_table[seq.slot], start, c_n, C,
            self.block.trash_page)
        final = not self._chunk_stops_early or start + c_n >= seq.length
        part = (("prefill_chunk", C, int(prev.shape[0]))
                + (() if final else ("part",)))
        inputs = (ids, rows, prev, np.int32(start), np.int32(c_n))
        if self._state:  # the state is carried in the sequence's slot
            inputs += (np.int32(seq.slot),)
        program = self._prefill_chunk if final else self._prefill_chunk_part
        logits, self._pools = self._dispatch(part, program, inputs,
                                             phase="prefill")
        seq.prefilled = start + c_n
        self._register_pages(seq)
        self._give_back(seq, "prefill")
        return logits

    # -- the engine step -----------------------------------------------------
    def step(self) -> Dict[int, Dict[str, Any]]:
        """Admit + prefill new sequences, decode one token for running ones.

        Returns {uid: {"tokens": [newly generated], "done": bool}};
        finished records also carry ``"finish_reason"``
        ("length"/"eos"/"max_seq_len"/"deadline").  Past-deadline
        requests (queued or running) expire FIRST, at the step boundary,
        before admission.

        A step that raises dumps the flight recorder (when one is
        installed) before propagating; a step that compiled is reported
        to the recompile sentinel with the set of program shapes it
        dispatched (prefill buckets/chunks, decode, page copies)."""
        self._step_parts = set()
        self._prefetched = False
        self._step_id += 1
        self._phase_s, self._child_s = {}, {}
        counts = self._step_counts = dict.fromkeys(_STEP_COUNTS, 0)
        compiles0 = compile_counts()[0]
        captured = self._timeline.should_capture(self._decode_steps)
        t0 = time.perf_counter()
        try:
            with span("serve_step", cat="serve",
                      step=self._step_id) as step_attrs:
                if captured:
                    # periodic step-time attribution: only this step pays
                    # the profiler start/stop + parse (capture context is
                    # exception-safe; a failed step still propagates)
                    with self._timeline.capture(self._decode_steps,
                                                regions=region_index):
                        out = self._step_impl()
                else:
                    out = self._step_impl()
                # idle / prefill-only steps still restore-prefetch for the
                # queue head (the decode-overlap call site won if it ran)
                self._prefetch_restores()
                counts["queue_len"] = len(self._queue)
                if self._state:
                    counts["state_slots_in_use"] = self.state_slots.in_use
                if self._touches["held"]:
                    # what the sequences in slots have cached and hold
                    counts.update(self._touched("held", np.array(
                        [[s.prefilled, len(s.pages)]
                         for s in self._slots if s is not None],
                        np.int64).reshape(-1, 2).T))
                step_attrs.update(counts)
        except Exception as e:
            dump_on_exception("engine_v2.step", e)
            raise
        self._phase_s["serve_step"] = time.perf_counter() - t0
        if self._step_parts and self._sentinel is not None:
            self._sentinel.observe_step(frozenset(self._step_parts),
                                        step=self._decode_steps)
        for name, secs in self._phase_s.items():
            self._m_step_phase_h.observe(secs, phase=name)
        if (counts["decode_rows"] and not counts["chunks"] and not captured
                and compile_counts()[0] == compiles0):
            # decode-only steps alone are rated (__init__ says why); one
            # that compiled, or that a timeline capture wrapped, is no
            # measure of a step: out of the median, never a stall
            self._watchdog.observe(self._phase_s["serve_step"],
                                   step=self._step_id)
        return out

    def force_timeline_capture(self) -> None:
        """Arm the step-time attribution capture for the NEXT ``step()``
        regardless of cadence; ``timeline_record()`` then holds it."""
        self._timeline.force_next()

    def timeline_record(self) -> Optional[Dict[str, Any]]:
        """Last completed step-time attribution record, or None."""
        return self._timeline.last_record()

    def _step_impl(self) -> Dict[int, Dict[str, Any]]:
        out: Dict[int, Dict[str, Any]] = {}
        ps = self.block.page_size

        with self._step_span("step_admit"):
            # step boundary: commit last step's captured evictions to the
            # host tier (one batched D2H gather) and unpin their pages
            self._drain_spills()
            self._expire_deadlines(out)
            admitted = self._admit()
            self._m_queue.set(len(self._queue))
            self._m_occupancy.set(
                sum(1 for s in self._slots if s is not None)
                / max(1, self.block.max_seqs))
        counts = self._step_counts
        counts["admitted"] = len(admitted)
        if self._chunk:
            # Dynamic-SplitFuse-style chunked prefill: ONE chunk per
            # pending-prefill sequence per step; decode for ready
            # sequences runs below in the SAME step, between chunks.
            # A cached-prefix admission starts mid-prompt: seq.prefilled
            # was set to the mapped prefix end, so the first chunk is
            # already suffix-only.
            pending = [s for s in self._slots if s is not None
                       and not self._ready_to_decode(s)]
            for seq in pending:
                start = seq.prefilled  # page-aligned: chunk % ps == 0
                # (by blocks: the prompt's whole blocks, and no token)
                end = (seq.length if self.blocks is None
                       else self.blocks.prefill_end(seq))
                c_n = min(self._chunk, end - start)
                counts["chunks"] += 1
                counts["prefill_tokens"] += c_n
                # the cached rows the chunk attends and, where it is the
                # prompt's last, what its one decoding row reads
                last = start + c_n if start + c_n >= seq.length else 0
                attrs = {**self._touched("chunk", np.array(
                             [self.rows.context(start)])),
                         **self._touched("last_chunk", np.array([last]),
                                         note=True)}
                with self._phase("prefill", self._m_prefill_h, uid=seq.uid,
                                 start=start, tokens=c_n, **attrs):
                    logits = self._run_prefill_chunk(seq, start, c_n,
                                                     self._chunk)
                    if seq.prefilled >= seq.length and self.blocks is None:
                        self._emit_sampled(seq, logits, out)
        else:
            for seq in admitted:
                if seq.decode_entry:
                    continue  # fully cached: enters via the decode program
                if seq.prefilled:
                    # cached prefix: suffix-only prefill through the
                    # start-offset program, bucketed like whole prompts
                    # so the shape set stays fixed
                    n_suf = seq.length - seq.prefilled
                    counts["chunks"] += 1
                    counts["prefill_tokens"] += n_suf
                    with self._phase("prefill", self._m_prefill_h,
                                     uid=seq.uid, start=seq.prefilled,
                                     tokens=n_suf):
                        logits = self._run_prefill_chunk(
                            seq, seq.prefilled, n_suf, self._bucket(n_suf))
                        self._emit_sampled(seq, logits, out)
                    continue
                # seq.length, not prompt_len: a preempted sequence
                # re-prefills its whole prefix (prompt + tokens generated
                # before eviction)
                n = seq.length
                bucket = self._bucket(n)
                ids = np.zeros((bucket,), np.int32)
                ids[:n] = seq.tokens
                rows = np.full((bucket // ps,), self.block.trash_page,
                               np.int32)
                rows[:len(seq.pages)] = seq.pages
                counts["chunks"] += 1
                counts["prefill_tokens"] += n
                with self._phase("prefill", self._m_prefill_h, uid=seq.uid,
                                 tokens=n, bucket=bucket):
                    logits, self._pools = self._dispatch(
                        ("prefill", bucket), self._prefill,
                        (ids, rows, np.int32(n)), phase="prefill")
                    seq.prefilled = n
                    self._register_pages(seq)
                    self._emit_sampled(seq, logits, out)

        active = [s for s in self._slots
                  if s is not None and self._ready_to_decode(s)]
        if not active:
            return out

        if self.blocks is not None:
            return self.blocks.step(active, out)

        # grow page tables where the pending token crosses a page boundary
        for seq in list(active):
            if seq.slot >= 0:  # (not already preempted this step)
                self._grow_pages(seq, seq.length - 1)
        active = [s for s in self._slots
                  if s is not None and self._ready_to_decode(s)]
        if not active:
            return out

        # speculative split: greedy sequences go through the batched
        # verify program (multi-token), non-greedy ones LOUDLY fall back
        # to the plain decode program — the sampling guard: the verify
        # accept rule is exact only for argmax, and silently speculating
        # a sampled stream would change its distribution
        if self._proposer is not None:
            spec_seqs = [s for s in active if s.temperature <= 0.0]
            decode_seqs = [s for s in active if s.temperature > 0.0]
            for seq in decode_seqs:
                if seq.uid not in self._spec_fallback_uids:
                    self._spec_fallback_uids.add(seq.uid)
                    self._dstats["spec_fallback_requests"] += 1
                    self._m_spec_fallback.inc()
                    if not self._spec_fallback_warned:
                        self._spec_fallback_warned = True
                        logger.warning(
                            "speculative decoding: non-greedy sampling "
                            "params fall back to the plain decode program "
                            "(distribution-preserving; acceptance gains "
                            "apply to greedy requests only)")
            if spec_seqs:
                decode_seqs += self._spec_step(spec_seqs, out)
        else:
            decode_seqs = active

        if decode_seqs and self._horizon > 1:
            # fused multi-step decode: K tokens per host round-trip
            # through ONE on-device scan (docs/SERVING.md "Multi-step
            # decode"); speculative engines never reach here (the
            # horizon stood down at construction)
            self._multi_decode(decode_seqs, out)
        elif decode_seqs:
            last, pos, act, temps, sids = self._decode_inputs(decode_seqs)
            self._decode_steps += 1
            counts["decode_rows"] += len(decode_seqs)
            # what a row reads, not where it is
            lengths = np.where(act, self.rows.rows_attended(pos), 0)
            self._note_kv_blocks(lengths)
            self._touched("decode", lengths, note=True)
            with self._phase("decode", self._m_decode_h,
                             batch=len(decode_seqs)):
                tokens, self._pools = self._dispatch(
                    "decode", self._decode,
                    (last, pos, self._page_table, act, temps, sids),
                    self._sample_key, phase="decode")
                # restore-prefetch rides the in-flight decode: the host
                # walks queued prefixes into the host tier while the
                # device decodes, and the H2D scatter chains behind the
                # decode program; the token fetch below waits only on
                # decode's own output
                self._prefetch_restores()
                with self._step_span("device_wait", parent="decode",
                                     what="decode_tokens"):
                    # dstpu-lint: allow[host-sync] THE designed sync of the
                    # K=1 decode path: [B] int32 tokens cross, never
                    # [B,vocab] logits; decode_horizon > 1 amortizes this
                    # to one [B,K] pull per horizon (_multi_decode)
                    tokens, = self._pull(tokens)
            self._m_gen_tokens.inc(len(decode_seqs))
            self._m_invocations.inc()
            self._m_host_syncs.inc()
            self._m_tokens_per_dispatch.observe(len(decode_seqs))
            self._dstats["decode_model_invocations"] += 1
            self._dstats["decode_host_syncs"] += 1
            self._dstats["decode_tokens"] += len(decode_seqs)

            with self._step_span("step_emit"):
                for seq in decode_seqs:
                    if self.cfg.pred_heads > 1:
                        tok = int(tokens[seq.slot, 0])
                        # dstpu-lint: allow[host-sync] ``tokens`` is the
                        # host array the designed pull above brought
                        further = [int(t) for t in tokens[seq.slot, 1:]]
                        out.setdefault(seq.uid, {"tokens": [], "done": False}
                                       ).setdefault("heads", []).append(
                                           further)
                    else:
                        tok = int(tokens[seq.slot])
                    seq.tokens.append(tok)
                    self._note_tokens(seq)
                    # the decode step wrote KV for the token it consumed
                    seq.prefilled = seq.length - 1
                    self._give_back(seq, "decode")
                    if (self.prefix_cache is not None
                            and seq.prefilled % ps == 0):
                        # the decode write completed a page: publish it so
                        # a preempted-then-readmitted (or forked) sequence
                        # can remap instead of recomputing
                        self._register_pages(seq)
                    rec = out.setdefault(seq.uid,
                                         {"tokens": [], "done": False})
                    rec["tokens"].append(tok)
                    self._maybe_finish(seq, tok)
                    rec["done"] = seq.done
                    if seq.done:
                        rec["finish_reason"] = seq.finish_reason
                self._sync_cache_counters()
            return out
        with self._step_span("step_emit"):
            self._sync_cache_counters()
        return out

    def _grow_pages(self, seq: SequenceState, pos: int) -> None:
        """Give ``seq`` the page that position ``pos`` — the one its pending
        token, or its block, will occupy — falls on, where it crosses a page
        boundary.  Under pool pressure, preempt running sequences (youngest
        first) to recompute later — never crash mid-step (reference: the v2
        scheduler holds requests back under KV pressure rather than
        failing); ``seq`` itself may be the one preempted (``seq.slot`` is
        then -1)."""
        need = self.rows.needs(seq, pos)
        if need <= 0:
            return
        while self.allocator.free_pages < need:
            victims = [s for s in self._slots
                       if s is not None and s is not seq]
            # evict the lowest priority class first, then the
            # most recently admitted (cheapest prefix to
            # recompute) — interactive work decodes through
            # pool pressure at batch work's expense.  Never
            # upward: when every other slotted sequence is MORE
            # urgent than the requester, the requester preempts
            # ITSELF (mirrors the admission-side victim rule)
            victim = (max(victims,
                          key=lambda s: (s.priority, s.admit_order))
                      if victims else seq)
            if victim is not seq and victim.priority < seq.priority:
                victim = seq
            self._preempt(victim)
            if victim is seq:
                return
        self.rows.take(seq, pos, self.allocator.alloc(need),
                       self._page_table[seq.slot], self.block.trash_page)

    def _give_back(self, seq: SequenceState, where: str) -> None:
        """After a chunk (``where`` = ``prefill``) or a decode step wrote
        ``seq``'s rows: the pages it no longer needs go back to the allocator
        (``rows.give_back``: an open window's when it closes), and a window
        that closed leaves its count on the step and an event."""
        drop, closed = self.rows.give_back(seq, where == "prefill")
        if drop:
            self.allocator.free(drop)
            self.rows.write_table(seq, self._page_table[seq.slot],
                                  self.block.trash_page)
        if closed:
            count, event = self.rows.CLOSED
            self._step_counts[count] = self._step_counts.get(count, 0) + 1
            record_event(event, cat="serve", step=self._step_id, uid=seq.uid,
                         where=where, windows_closed=closed,
                         pages_freed=len(drop),
                         **({} if seq.trace_id is None
                            else {"trace_id": seq.trace_id}))

    def _pull(self, *arrays) -> List[np.ndarray]:
        """Host copies of a decode call's results.  With an expert share
        its counters (the pools' ``moe_stats``, 20 bytes, to which the
        decode and chunk programs since the last pull added: picks on held
        experts, held experts touched, rows the grouped matmul ran,
        expert-layer calls, rows its grid spans) come in the same
        ``device_get`` — every copy starts before any is waited for — and
        their increase is noted on the step."""
        if "moe_stats" not in self._pools:
            # dstpu-lint: allow[host-sync] the caller's designed sync
            return [np.asarray(a) for a in arrays]
        # dstpu-lint: allow[host-sync] the caller's designed sync
        *out, now = jax.device_get((*arrays, self._pools["moe_stats"]))
        now = now.astype(np.int64)
        # dstpu-lint: allow[host-sync] ``now`` is a host array (and
        # ``moe_stats`` wraps at 2**32)
        delta = ((now - self._moe_seen) % (1 << 32)).tolist()
        self._moe_seen = now
        for name, d in zip(MOE_COUNTERS, delta):
            self._dstats[name] += d
            self._step_counts[name] = d
        self._m_moe_picks.inc(delta[0])
        self._m_moe_touched.inc(delta[1])
        self._m_moe_padded.inc(delta[2])
        self._m_moe_grid.inc(delta[4])
        return out

    def _note_kv_blocks(self, lengths: np.ndarray) -> None:
        """``decode_kv_blocks``: the blocks the paged decode kernel walks
        in one layer call over rows of ``lengths`` visible tokens (0 = the
        row is not active) — the kernel's own ``n_blocks``."""
        n = int(n_blocks(lengths, self.block.page_size,
                         self._kv_block_pages).sum())
        self._step_counts["decode_kv_blocks"] += n
        self._dstats["decode_kv_blocks"] += n
        self._m_kv_blocks.inc(n)

    def _touched(self, at: str, n: np.ndarray, note: bool = False
                 ) -> Dict[str, int]:
        """What the stack's layer types declare a step counts ``at`` that
        moment (``layer_types.Touch``) over ``n`` — what each row reads or
        holds, from the host's own book; ``note``: added to the step's counts
        (and to ``decode_stats()`` where the count is cumulative)."""
        got = {c.name: c.count(n, self._geometry) for c in self._touches[at]}
        for c in self._touches[at] if note else ():
            self._step_counts[c.name] = \
                self._step_counts.get(c.name, 0) + got[c.name]
            if c.cumulative:
                self._dstats[c.name] += got[c.name]
        return got

    def _decode_inputs(self, seqs: List[SequenceState]):
        """Dense ``[max_seqs]`` dispatch arrays for a decode-phase
        batch — ONE assembly shared by the K=1 and fused paths (the two
        are asserted stream-identical; independently-built inputs could
        silently diverge)."""
        B = self.block.max_seqs
        last = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        act = np.zeros((B,), bool)
        temps = np.zeros((B,), np.float32)
        sids = np.zeros((B,), np.int32)
        for seq in seqs:
            last[seq.slot] = seq.tokens[-1]
            pos[seq.slot] = seq.length - 1
            act[seq.slot] = True
            temps[seq.slot] = max(seq.temperature, 0.0)
            sids[seq.slot] = seq.uid % (1 << 31)  # stable sampling id
        return last, pos, act, temps, sids

    # -- fused multi-step decode ---------------------------------------------
    def _multi_decode(self, seqs: List[SequenceState],
                      out: Dict[int, Dict[str, Any]]) -> None:
        """One fused multi-step decode dispatch (docs/SERVING.md
        "Multi-step decode"): clamp each row's effective horizon
        (remaining max_new / model window / deadline), shrink the
        dispatch horizon along the halving chain under KV-pool
        pressure — never preempting mid-scan — pre-reserve every row's
        page headroom, run the K-step on-device scan, then advance ALL
        published state (tokens, prefilled, page registration,
        retirement) from the ONE ``[B, K]`` host pull.  Prefix-cache
        registration, deadline expiry, admission, spill drains, and
        restore-prefetch all stay at host boundaries, exactly as for
        the K=1 loop."""
        ps = self.block.page_size
        B = self.block.max_seqs
        now = time.perf_counter()
        budgets: Dict[int, int] = {}
        for seq in seqs:
            b = min(self._horizon,
                    seq.max_new_tokens - seq.generated,
                    self.max_seq_len - seq.length)
            if seq.deadline > 0.0:
                # deadline lands mid-horizon: clamp the row's effective
                # K so a fused dispatch cannot overshoot the deadline
                # by K x TPOT; the boundary sweep then expires it on
                # time with the tokens it legitimately produced
                b = _deadline_clamp(b, seq.deadline - now, self._tpot_ema)
            budgets[seq.uid] = max(1, b)

        # dispatch horizon: the smallest halving-chain value covering
        # the largest row budget (short tails don't scan dead
        # iterations), shrunk further while the TRULY-free pool cannot
        # cover the headroom — headroom backs tokens a row may never
        # produce (mid-horizon EOS), so like speculative draft
        # reservation it never evicts prefix-cache LRU content; the
        # horizon shrinks instead.  k=1 always fits: the page-boundary
        # loop in _step_impl already guaranteed every pending token's
        # page (claiming LRU pages there exactly like the K=1 loop).
        k = _shrink_horizon(self._horizon, max(budgets.values()))

        def _extra_pages(k_: int) -> int:
            return sum(
                max(0, _horizon_pages_needed(
                    s.length, min(k_, budgets[s.uid]), ps) - len(s.pages))
                for s in seqs)

        while k > 1 and _extra_pages(k) > self.allocator.uncached_free_pages:
            k = (k + 1) // 2
        if k < self._horizon:
            self._m_horizon_shrink.inc()
            self._dstats["decode_horizon_shrinks"] += 1
            record_event("horizon_shrink", cat="serve", step=self._step_id,
                         horizon=k,
                         configured=self._horizon,
                         **self._pool_occupancy())

        # pre-reserve each row's horizon headroom; a refused
        # reservation (spill pins landed between the check and here)
        # clamps THAT row to the headroom it already holds — the
        # dispatch never fails and nothing is preempted mid-scan
        for seq in seqs:
            b = min(k, budgets[seq.uid])
            extra = _horizon_pages_needed(seq.length, b, ps) \
                - len(seq.pages)
            if extra > 0:
                fresh = self.allocator.try_alloc(extra, uncached_only=True)
                if fresh is None:
                    b = max(1, len(seq.pages) * ps - seq.length + 1)
                else:
                    base = len(seq.pages)
                    seq.pages.extend(fresh)
                    self._page_table[seq.slot, base:base + extra] = fresh
            budgets[seq.uid] = b

        last, pos, act, temps, sids = self._decode_inputs(seqs)
        eos = np.full((B,), -1, np.int32)
        budg = np.zeros((B,), np.int32)
        for seq in seqs:
            if seq.eos_id is not None:
                eos[seq.slot] = seq.eos_id
            budg[seq.slot] = budgets[seq.uid]

        self._decode_steps += 1
        self._step_counts["decode_rows"] += len(seqs)
        warm = k in self._warm_horizons
        self._warm_horizons.add(k)
        t0 = time.perf_counter()
        with self._phase("multi_decode", self._m_decode_h,
                         batch=len(seqs), horizon=k):
            toks, produced, self._pools = self._dispatch(
                ("multi_decode", k), self._multi,
                (last, pos, self._page_table, act, temps, eos, budg, sids),
                self._sample_key, k, phase="multi_decode")
            # restore-prefetch rides the in-flight scan, like K=1
            self._prefetch_restores()
            with self._step_span("device_wait", parent="multi_decode",
                                 what="decode_tokens"):
                # dstpu-lint: allow[host-sync] THE designed sync per horizon
                # [B,K] int32 tokens + [B] produced counts cross the link
                # once per K tokens — the fused form of the per-step
                # decode sync, amortized K-fold
                toks, produced = self._pull(toks, produced)
        t1 = time.perf_counter()

        # the scan ALWAYS executes k iterations (finished rows run
        # masked, they don't shorten the program): per-device-step wall
        # is wall / k, not wall / produced — dividing by produced would
        # inflate the estimate on every stream tail
        per_step = (t1 - t0) / k
        # row b ran its first produced[b] iterations active, one token
        # longer each
        t = np.arange(k)[:, None]
        self._note_kv_blocks(np.where(t < produced, pos + 1 + t, 0))
        # EMA of per-token decode wall, the deadline clamp's estimate —
        # updated only from WARM dispatches: a dispatch that compiled
        # its horizon shape measures XLA compile time, not decode time
        if warm:
            self._tpot_ema = (per_step if self._tpot_ema is None
                              else 0.5 * self._tpot_ema + 0.5 * per_step)
        total = int(produced.sum())
        self._m_gen_tokens.inc(total)
        self._m_invocations.inc()
        self._m_host_syncs.inc()
        self._m_tokens_per_dispatch.observe(total)
        self._dstats["decode_model_invocations"] += 1
        self._dstats["decode_host_syncs"] += 1
        self._dstats["decode_tokens"] += total

        with self._step_span("step_emit"):
            for seq in seqs:
                n = int(produced[seq.slot])
                rec = out.setdefault(seq.uid, {"tokens": [], "done": False})
                reason = ""
                for j in range(n):
                    tok = int(toks[seq.slot, j])
                    seq.tokens.append(tok)
                    rec["tokens"].append(tok)
                    # token j landed ~(j+1) device steps into the dispatch:
                    # reconstructed per-token emit timestamps, so
                    # TTFT/TPOT and the SLO-violation checks never see a
                    # K-token burst stamped at one instant
                    self._note_tokens(seq, t=t0 + (j + 1) * per_step)
                    reason = self._finish_reason_for(seq, tok)
                    if reason:
                        break  # the scan stopped the row here by contract
                # the scan wrote KV for every token it consumed; the last
                # emitted token is the pending one, exactly like K=1
                seq.prefilled = seq.length - 1
                self._register_pages(seq)
                if reason:
                    seq.finish_reason = reason
                    self._retire(seq)  # frees unused horizon headroom too
                rec["done"] = seq.done
                if seq.done:
                    rec["finish_reason"] = seq.finish_reason

    # -- speculative decoding ------------------------------------------------
    def _spec_step(self, seqs: List[SequenceState],
                   out: Dict[int, Dict[str, Any]]
                   ) -> List[SequenceState]:
        """One speculative decode round for greedy-ready sequences:
        propose -> reserve -> ONE batched verify -> accept longest
        prefix + bonus token -> roll back rejected pages.  Returns the
        sequences it did NOT run — the whole batch when every proposal
        came up empty — for the caller's plain decode program.

        Every sequence emits at least one token per round (the model's
        own greedy choice rides in the verify output even on a total
        miss or an empty draft), so speculation never does worse than
        plain decode in tokens per model invocation.  Mixed accept
        lengths coexist in one batch: acceptance is per-row host logic
        over the per-position argmax the program returns."""
        ps = self.block.page_size
        k = self.spec.k
        W = k + 1
        B = self.block.max_seqs

        # -- propose + reserve (host) --
        drafts: Dict[int, List[int]] = {}
        with self._step_span("spec_propose", seqs=len(seqs)):
            for seq in seqs:
                d = list(self._proposer.propose(seq.tokens, k))[:k]
                # cap to the model window, the page-table width, and the
                # request's remaining budget (emitting past max_new /
                # max_seq_len would be discarded — don't verify it)
                cap = min(self.max_seq_len - seq.length,
                          len(self._page_table[seq.slot]) * ps
                          - seq.length,
                          seq.max_new_tokens - seq.generated - 1)
                if len(d) > cap:
                    d = d[:max(cap, 0)]
                if d:
                    # reserve pages for the draft window, spending ONLY
                    # truly-free pages: draft tokens may be rejected, so
                    # neither prefix-cache LRU content nor other
                    # sequences (no preemption) are sacrificed for them
                    need = (seq.length - 1 + len(d)) // ps + 1
                    extra = need - len(seq.pages)
                    while (extra > 0
                           and extra > self.allocator.uncached_free_pages):
                        d.pop()
                        need = (seq.length - 1 + len(d)) // ps + 1
                        extra = need - len(seq.pages)
                    if extra > 0:
                        fresh = self.allocator.alloc(extra)
                        base = len(seq.pages)
                        seq.pages.extend(fresh)
                        self._page_table[seq.slot,
                                         base:base + extra] = fresh
                drafts[seq.uid] = d
                self._dstats["spec_proposed_tokens"] += len(d)
                self._m_spec_proposed.inc(len(d))

        if not any(drafts.values()):
            # nothing to verify (proposer drew blanks everywhere): the
            # plain decode program emits the same one greedy token per
            # row at 1/W the program width — hand the batch back so
            # low-acceptance traffic never pays for verify it can't use
            return list(seqs)

        # -- one batched verify call --
        ids = np.zeros((B, W), np.int32)
        pos = np.zeros((B,), np.int32)
        act = np.zeros((B,), bool)
        nv = np.ones((B,), np.int32)
        for seq in seqs:
            row = [seq.tokens[-1]] + drafts[seq.uid]
            ids[seq.slot, :len(row)] = row
            pos[seq.slot] = seq.length - 1
            act[seq.slot] = True
            nv[seq.slot] = len(row)
        self._step_counts["decode_rows"] += len(seqs)
        with self._phase("spec_verify", self._m_spec_verify_h,
                         batch=len(seqs), width=W):
            greedy, self._pools = self._dispatch(
                ("verify", W), self._verify,
                (ids, pos, self._page_table, act, nv), phase="spec_verify")
            with self._step_span("device_wait", parent="spec_verify",
                                 what="decode_tokens"):
                # dstpu-lint: allow[host-sync] one [B,W] int32 pull per
                # verify round; acceptance is per-row host logic by design
                greedy = np.asarray(greedy)  # [B, W] argmax per position
        self._m_invocations.inc()
        self._m_host_syncs.inc()
        self._dstats["decode_model_invocations"] += 1
        self._dstats["decode_host_syncs"] += 1
        self._dstats["spec_verify_calls"] += 1

        # -- accept + emit + rollback (host) --
        rollback_pages = 0
        with self._step_span("step_emit"):
            for seq in seqs:
                accepted, bonus = longest_accepted(drafts[seq.uid],
                                                   greedy[seq.slot])
                base_len = seq.length  # L: tokens before this round
                self._dstats["spec_accepted_tokens"] += len(accepted)
                self._m_spec_accepted.inc(len(accepted))
                rec = out.setdefault(seq.uid, {"tokens": [], "done": False})
                emitted = 0
                for tok in accepted + [bonus]:
                    seq.tokens.append(tok)
                    emitted += 1
                    rec["tokens"].append(tok)
                    self._note_tokens(seq)
                    if self._should_finish(seq, tok):
                        break  # drop accepted tokens past a finish boundary
                self._m_gen_tokens.inc(emitted)
                self._dstats["decode_tokens"] += emitted
                self._m_spec_tps.observe(emitted)
                # KV is valid through the accepted region (the bonus token is
                # the pending one, exactly like a plain decode step)
                seq.prefilled = min(seq.length - 1,
                                    base_len + len(accepted))
                self._register_pages(seq)
                self._maybe_finish(seq, seq.tokens[-1])
                rec["done"] = seq.done
                if seq.done:
                    rec["finish_reason"] = seq.finish_reason
                if not seq.done:
                    # rollback: pages reserved for rejected draft tokens are
                    # released; rejected KV inside kept pages is overwritten
                    # by the next window before any query can attend it
                    needed = (seq.prefilled - 1) // ps + 1
                    if needed < len(seq.pages):
                        drop = seq.pages[needed:]
                        self.allocator.free(drop)
                        del seq.pages[needed:]
                        self._page_table[seq.slot, needed:] = \
                            self.block.trash_page
                        rollback_pages += len(drop)
        if rollback_pages:
            self._dstats["spec_rollback_pages"] += rollback_pages
            self._m_spec_rollback.inc(rollback_pages)
            record_event("spec_rollback", cat="serve", step=self._step_id,
                         pages=rollback_pages, seqs=len(seqs))
        prop = self._dstats["spec_proposed_tokens"]
        if prop:
            self._m_spec_rate.set(
                self._dstats["spec_accepted_tokens"] / prop)
        return []

    def close(self) -> None:
        """Release this engine's memory-ledger slots (provider identity
        guards: slots a newer co-located engine claimed stay attached).
        Idempotent; safe without the ledger enabled.

        In-flight/queued requests are NOT finished by close(): they are
        aborted LOUDLY (warning + closed request spans) — call
        ``drain()`` first for clean retirement that runs admitted
        sequences to completion and hands queued ones back."""
        # pending spill captures die with the engine (their host tier
        # does too): detach the hook FIRST — abort_all below frees
        # sequence pages, and cap trims there must not capture fresh
        # pins after this release — then drop the pins so a post-close
        # allocator audit sees a clean pool
        if self.kv_tier is not None:
            self.allocator.spill_hook = None
        for page, _key in self._pending_spills:
            self.allocator.release_spill_pin(page)
        self._pending_spills = []
        self._pending_spill_keys = set()
        dropped = self.abort_all(reason="close")
        if dropped:
            logger.warning(
                f"engine_v2.close: aborted {len(dropped)} unfinished "
                f"request(s) (uids {dropped[:8]}{'…' if len(dropped) > 8 else ''}) "
                "— call drain() before close() to retire cleanly")
        comps = getattr(self, "_ledger_components", [])
        if comps:
            from ...telemetry.memory import get_memory_ledger

            led = get_memory_ledger()
            for name, prov in comps:
                led.detach(name, provider=prov)
        self._ledger_components = []

    # -- serving metrics -----------------------------------------------------
    def cache_stats(self) -> Dict[str, float]:
        """Prefix-cache and prefill-work counters (cumulative).  Valid —
        all zeros for the cache-specific entries — with caching off, so
        dashboards need no conditional wiring."""
        self._sync_cache_counters()
        s: Dict[str, float] = dict(self._stats)
        s["cache_hits"] = self.prefix_cache.hits if self.prefix_cache else 0
        s["cache_misses"] = (self.prefix_cache.misses
                             if self.prefix_cache else 0)
        s["cache_evictions"] = self.allocator.evictions
        s["cached_pages"] = self.allocator.cached_pages
        adm = s["prefill_admitted_tokens"]
        s["prefix_hit_rate"] = (s["prefix_hit_tokens"] / adm) if adm else 0.0
        return s

    def decode_stats(self) -> Dict[str, float]:
        """Decode-phase counters (cumulative; all-zero spec entries with
        speculation off): model invocations, host syncs, tokens produced,
        the speculative propose/accept/rollback tallies and the MoE and
        state-slot counts."""
        return dict(self._dstats)

    def assert_no_leaks(self) -> None:
        """Exact allocator audit against this engine's live sequences
        (ragged.BlockAllocator.assert_no_leaks): every KV page's
        refcount must equal its live references, every refcount-0 page
        must be free or LRU-parked.  Tests and ``fleet_drill`` call this
        after speculative rollback / migration / preemption churn."""
        self.allocator.assert_no_leaks(
            [s.pages for s in self._slots if s is not None])
        if self._state:
            self.state_slots.assert_no_leaks(
                {i: s.uid for i, s in enumerate(self._slots)
                 if s is not None})

    def reset_cache_stats(self) -> None:
        """Zero the counters (cache CONTENTS are kept) — the benchmark
        calls this after warm-up so compile-wave admissions don't pollute
        the rates.
        The registry counters stay cumulative (Prometheus counters never
        go backwards); only the delta baseline resets with the sources."""
        self._stats = {k: 0 for k in self._stats}
        self._dstats = {k: 0 for k in self._dstats}
        self.allocator.evictions = 0
        if self.prefix_cache is not None:
            self.prefix_cache.hits = self.prefix_cache.misses = 0
        if self.kv_tier is not None:
            # tier CONTENTS are kept (like the device cache); only the
            # counters re-baseline so a measured window counts its own
            # spill/restore traffic
            t = self.kv_tier
            t.spilled_pages = t.restored_pages = 0
            t.hits = t.misses = 0
            t.host_evictions = t.corrupt_pages = t.dropped_spills = 0
        self._cache_pub = {"hits": 0, "misses": 0, "evictions": 0}

    def publish_metrics(self, monitor, step: int) -> None:
        """Surface the serving counters through a monitor/* writer
        (MonitorMaster or any object with ``write_events``)."""
        publish_setup_seconds()
        monitor.write_events([(f"serving/{k}", float(v), int(step))
                              for k, v in self.cache_stats().items()])

    def generate_all(self, requests: List[RaggedRequest],
                     max_steps: int = 10_000) -> Dict[int, List[int]]:
        """Convenience: run requests to completion, returning full
        generations keyed by uid."""
        uids = [self.put(r) for r in requests]
        got: Dict[int, List[int]] = {u: [] for u in uids}
        for _ in range(max_steps):
            if not self.has_work():
                break
            for uid, rec in self.step().items():
                got[uid].extend(rec["tokens"])
        else:
            logger.warning("generate_all: max_steps reached with work pending")
        return got
