"""Generation by diffusion over blocks: the decode phase of a model whose
``TransformerConfig.block_length`` is not 0 (``models/sdar_moe.py``).

Beside ``speculative.py`` — the other place where a step commits other than
one token a row — and like it a policy of the engine's step, not a program:
``InferenceEngineV2`` asks one question of the model (does it generate by
blocks), builds this policy if so, and hands it the decode phase of every
step.  Admission, the page allocator, chunked prefill, preemption and the
counters stay the engine's.

The loop (the family's published one; greedy, static low-confidence reveal):

- the sequence is cut into blocks of ``B`` positions at absolute multiples of
  ``B``.  The whole blocks of what a sequence holds, ``len // B * B`` tokens,
  are prefilled by the chunk program under the block mask (causal between
  blocks, bidirectional inside one) and **prefill yields no token**;
- each later block starts as the tokens left over (the prompt's last ``L mod
  B``, first block only) followed by masked positions, and is denoised in
  *passes* of the block program (``model_runner.paged_block_pass``): a pass
  runs the block's ``B`` positions against the kept K/V of every earlier block
  and its own fresh K/V and reveals the ``B / denoising_steps`` masked
  positions of highest confidence; a revealed token is never masked again;
- when no position is masked, **one more pass over the finished block — the
  commit — writes the K/V that are kept**, the block's tokens are delivered
  and the next block begins.  A block so costs ``denoising_steps + 1`` passes
  for ``B`` tokens, and a row is delivered 0 tokens by most passes and up to
  ``B`` by the pass that commits.

A row's block in progress is its row of the policy's host arrays (``B`` token
ids, which of them are masked; ``SequenceState.block`` names the row, None
between blocks); the K/V in flight live IN PLACE in the row's
page (``B`` divides the page: a block never straddles one), overwritten pass
by pass, so a preempted row simply drops its block and redoes it after its
whole blocks are prefilled again.  Pages grow by block, not by token.
``max_new_tokens`` is the fixed generation length: the positions of a last
block beyond it are denoised as the loop does and not delivered.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from ...telemetry import get_registry
from ...telemetry.compile_sentinel import \
    expect_recompile as sentinel_expect_recompile
from ...telemetry.spans import record_event
from .model_runner import paged_block_pass, paged_gather_pages
from .packed_inputs import PackedProgram
from .ragged import SequenceState

#: what the policy counts, cumulative (``decode_stats()``) and a step (the
#: ``serve_step`` span; ``block_passes`` there is 0 or 1)
COUNTERS = ("block_passes", "row_passes", "commit_row_passes",
            "tokens_revealed", "tokens_committed", "blocks_dropped")


def block_policy(engine, proposer) -> Optional["BlockPolicy"]:
    """The policy for ``engine``'s model, or None for a model that generates
    one token a row a step.  What cannot work with blocks is refused here, by
    name, in the model's words."""
    cfg, config = engine.cfg, engine.config
    B = cfg.block_length
    if not B:
        return None
    why = (f"this model generates by diffusion over blocks of {B} "
           "(block_length)")
    if B & (B - 1) or config.block.page_size % B:
        raise ValueError(
            f"block_length {B}: {why}, and a block is a power of two of "
            f"positions that never straddles a page of "
            f"{config.block.page_size}")
    if not 0 <= cfg.mask_token_id < cfg.vocab_size:
        raise ValueError(f"mask_token_id {cfg.mask_token_id} is not a row of "
                         f"the vocabulary of {cfg.vocab_size}")
    if "block_generation" in engine.unsupported:
        raise NotImplementedError(
            f"{why}; {engine.unsupported['block_generation']}")
    # what the configuration may not ask of such a model, refused by the
    # engine's one routine in the model's words
    engine._refuse_asked({
        "speculation": f"{why} — a pass already scores a block of positions "
                       "at once, and a draft of next tokens has no meaning "
                       "under a mask that is bidirectional inside a block",
        "decode_horizon": f"{why}; which pass commits, what is delivered and "
                          "which page a block needs are decided on the host "
                          "between passes",
        "kv_quant": f"{why}; a block's K/V are rewritten in place pass by "
                    "pass and read back by its own queries, and int8 pages "
                    "would put every pass through the round-trip — serve it "
                    "with kv_quant off",
        "prefix_cache": f"{why}. A full page's K/V do depend only on the "
                        "tokens up to the page's end (a block never straddles "
                        "a page), so a cached page would be valid — but a "
                        "fully cached prompt enters through the one-token "
                        "decode program, which this model does not have; "
                        "serve it with the prefix cache off",
        "whole_prompt_prefill": f"{why}, and its prompts are prefilled "
                                "through the chunk program, which has the "
                                "block mask; whole-prompt prefill is causal; "
                                "set prefill_chunk > 0"}, proposer)
    return BlockPolicy(engine)


class BlockPolicy:
    """One engine's block-generation state and its decode phase."""

    def __init__(self, engine):
        self.e = engine
        cfg = engine.cfg
        self.B = cfg.block_length
        self.mask_id = cfg.mask_token_id
        #: for a check against a reference: ``{uid: [a pass's {"start",
        #: "ids", "masked" (before it), "ids_after", "masked_after"}, ...]}``
        #: of every pass from now on (None: not kept)
        self.passes: Optional[Dict[int, List[Dict[str, Any]]]] = None
        #: the blocks in progress, a decode row each (``seq.block`` names a
        #: sequence's row; a row no sequence names holds what was left there):
        #: positions ``start ... start + B - 1``, their token ids (a masked
        #: one: the mask id), which are masked, how many of the leading ones
        #: the prompt gave (never delivered), and the positions a pass reveals
        R = engine.block.max_seqs
        self.ids = np.full((R, self.B), self.mask_id, np.int32)
        self.masked = np.zeros((R, self.B), bool)
        self.start = np.zeros((R,), np.int32)
        self.given = np.zeros((R,), np.int32)
        self.n_reveal = np.zeros((R,), np.int32)
        def _block_pass(*a):  # the program's name in a device trace
            return paged_block_pass(cfg, *a)

        self._program = PackedProgram(_block_pass)
        engine._dstats.update(dict.fromkeys(COUNTERS, 0))
        reg = get_registry()
        self._m_row_passes = reg.counter(
            "deepspeed_tpu_serving_block_row_passes_total",
            "rows x passes of the block program (a row takes part in every "
            "pass of its block, denoising or commit)")
        self._m_commit_passes = reg.counter(
            "deepspeed_tpu_serving_block_commit_row_passes_total",
            "row-passes that revealed nothing: the pass over a finished "
            "block that writes the K/V kept")
        self._m_revealed = reg.counter(
            "deepspeed_tpu_serving_block_tokens_revealed_total",
            "masked positions revealed by denoising passes")
        self._m_dropped = reg.counter(
            "deepspeed_tpu_serving_block_blocks_dropped_total",
            "half-denoised blocks dropped by a preemption (redone after the "
            "row's whole blocks are prefilled again)")

    # ------------------------------------------------------------- requests
    def check_request(self, request) -> None:
        """What a request may ask of a model that generates by blocks."""
        B, steps = self.B, request.denoising_steps
        if steps is not None and (steps < 1 or B % steps):
            raise ValueError(
                f"denoising_steps {steps}: a pass reveals block_length / "
                f"denoising_steps positions, so it divides {B}")
        if request.temperature > 0.0:
            raise ValueError(
                "temperature > 0: the reveal rule is greedy (the arg-max "
                "token and its probability as confidence); sampling inside "
                "it is not implemented")
        if request.eos_id is not None:
            raise ValueError(
                "eos_id: max_new_tokens is the fixed generation length of a "
                "model that generates by blocks")
        end = -(-(len(request.prompt_ids) + request.max_new_tokens) // B) * B
        if end > self.e.max_seq_len:
            raise ValueError(
                f"prompt {len(request.prompt_ids)} + max_new_tokens "
                f"{request.max_new_tokens}, in whole blocks of {B}, is {end} "
                f"> max_seq_len {self.e.max_seq_len}")

    def prefill_end(self, seq: SequenceState) -> int:
        """The whole blocks of what ``seq`` holds: what the chunk program
        prefills (the rest opens the next block)."""
        return seq.length // self.B * self.B

    def refuse_export(self) -> None:
        raise NotImplementedError(
            "KVPageBundle export: a sequence that generates by blocks has a "
            "block in progress whose K/V in flight are not a bundle's to "
            "carry; re-dispatch the request instead")

    def drop(self, seq: SequenceState) -> None:
        """Preemption: the half-denoised block is dropped and redone."""
        seq.block = None
        self.e._dstats["blocks_dropped"] += 1
        self.e._step_counts["blocks_dropped"] = \
            self.e._step_counts.get("blocks_dropped", 0) + 1
        self._m_dropped.inc()

    def read_kv(self, seq: SequenceState) -> List[Dict[str, Any]]:
        """``InferenceEngineV2.read_kv`` for this model: the K/V of the
        COMMITTED blocks (``seq.prefilled`` positions), a layer at a time,
        ``{"first": 0, "k" | "v": [positions, KVH, D]}`` float32."""
        e, n = self.e, seq.prefilled
        # the gather runs op-by-op outside the step programs, as an export's
        sentinel_expect_recompile("read_kv")
        pages = paged_gather_pages(
            e._pools, seq.pages[:-(-n // e.block.page_size)], e.cfg.kv_heads)
        return [{"first": 0, **{
            nm: np.asarray(pages[nm][l], np.float32).reshape(
                -1, *pages[nm].shape[3:])[:n] for nm in ("k", "v")}}
            for l in range(pages["k"].shape[0])]

    # ----------------------------------------------------------------- step
    def _open(self, seq: SequenceState) -> None:
        """Start ``seq``'s next block in its row of the host's arrays: the
        tokens left over from the prompt, then masked positions."""
        B, start, r = self.B, self.prefill_end(seq), seq.slot
        given = seq.length - start
        self.ids[r] = self.mask_id
        self.ids[r, :given] = seq.tokens[start:]
        self.masked[r] = np.arange(B) >= given
        self.start[r], self.given[r] = start, given
        self.n_reveal[r] = B // (seq.denoising_steps or B)
        seq.block = r
        if given:
            # prompt tokens that no chunk prefilled: this block's passes do
            record_event("block_open", cat="serve", step=self.e._step_id,
                         uid=seq.uid, start=start, prompt_tokens=given)

    def step(self, seqs: List[SequenceState],
             out: Dict[int, Dict[str, Any]]) -> Dict[int, Dict[str, Any]]:
        """One pass of the block program over every row that is ready (its
        whole blocks prefilled), then the host's half: which rows committed,
        what each is delivered, which finished.  A row that a pass delivers
        nothing has no record in ``out``.

        The blocks in progress are rows of ``[max_seqs, B]`` host arrays (a
        sequence's ``block`` names its row), so a pass loops over the rows
        that open a block or commit one, not over every row."""
        e, B = self.e, self.B
        counts = e._step_counts
        # pages grow by block: the page a row's new block falls on, before
        # the pass (a row preempted for it drops out of this pass)
        for seq in seqs:
            if seq.block is None and seq.slot >= 0:
                self._open(seq)
                e._grow_pages(seq, int(self.start[seq.slot]))
        if counts["preempted"]:
            seqs = [s for s in seqs if s.slot >= 0]
            if not seqs:
                return out
        act = np.zeros((e.block.max_seqs,), bool)
        act[[s.slot for s in seqs]] = True
        pending = self.masked.any(axis=1)   # rows a pass still denoises
        commit = act & ~pending
        n_commit = int(commit.sum())
        n_masked = int(self.masked[act].sum())

        e._decode_steps += 1
        counts["decode_rows"] += len(seqs)
        # what the paged kernel reads in one layer call: every position
        # through the block's last, once a row (its B queries share them)
        lengths = np.where(act, self.start + B, 0)
        counts["block_kv_tokens"] = int(lengths.sum())
        e._note_kv_blocks(lengths)
        with e._phase("decode", e._m_decode_h, batch=len(seqs)), \
                e._step_span("block_pass", parent="decode", rows=len(seqs),
                             commit_rows=n_commit, masked_positions=n_masked):
            new_ids, new_masked, e._pools = e._dispatch(
                "block_pass", self._program,
                (self.ids, self.masked, self.start, e._page_table, act,
                 self.n_reveal), phase="block_pass")
            with e._step_span("device_wait", parent="block_pass",
                              what="block_tokens"):
                # THE designed sync of a pass: [R, B] token ids and [R, B]
                # flags cross the link, never [R x B, vocab] logits
                new_ids, new_masked = e._pull(new_ids, new_masked)

        delivered = 0
        with e._step_span("step_emit"):
            if self.passes is not None:
                for seq in seqs:
                    r = seq.slot
                    self.passes.setdefault(seq.uid, []).append({
                        "start": int(self.start[r]),
                        "ids": self.ids[r].copy(),
                        "masked": self.masked[r].copy(),
                        "ids_after": new_ids[r].copy(),
                        "masked_after": new_masked[r].copy()})
            # a denoising pass: the block as the reveal rule left it
            denoised = act & pending
            self.ids[denoised] = new_ids[denoised]
            self.masked[denoised] = new_masked[denoised]
            revealed = n_masked - int(self.masked[act].sum())
            # the commit: this pass wrote the K/V that are kept
            for r in np.flatnonzero(commit):
                seq = e._slots[r]
                left = seq.max_new_tokens - seq.generated
                toks = self.ids[r, self.given[r]:].tolist()[:left]
                seq.tokens.extend(toks)
                seq.prefilled, seq.block = int(self.start[r]) + B, None
                rec = out.setdefault(seq.uid, {"tokens": [], "done": False})
                rec["tokens"].extend(toks)
                delivered += len(toks)
                e._note_tokens(seq, len(toks))
                if seq.generated >= seq.max_new_tokens:
                    seq.finish_reason = "length"
                    e._retire(seq)
                    rec["done"], rec["finish_reason"] = True, "length"
            e._sync_cache_counters()

        # (``blocks_dropped``, the last of COUNTERS, is ``drop``'s)
        for name, n in zip(COUNTERS, (1, len(seqs), n_commit, revealed,
                                      delivered)):
            e._dstats[name] += n
            counts[name] = n
        counts["page_tokens_in_use"] = e.block.page_size * sum(
            len(s.pages) for s in e._slots if s is not None)
        self._m_row_passes.inc(len(seqs))
        self._m_commit_passes.inc(n_commit)
        self._m_revealed.inc(revealed)
        e._m_gen_tokens.inc(delivered)
        e._m_invocations.inc()
        e._m_host_syncs.inc()
        e._m_tokens_per_dispatch.observe(delivered)
        e._dstats["decode_model_invocations"] += 1
        e._dstats["decode_host_syncs"] += 1
        e._dstats["decode_tokens"] += delivered
        return out
