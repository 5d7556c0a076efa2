"""``serve_requests`` for a served model that generates by diffusion over
blocks: requests that carry a quality tier (``denoising_steps``), fixed
generation lengths by tier, and a check that replays every pass.

This kind plays its traffic through the same ``run`` as the five kinds beside
it — arrivals, warm-up, loop, arithmetic — and reads from the configuration
file ``"reference"``: the module ``reference/<name>.py`` (``forward(desc,
params, ids, logits_at, **controls) -> (logits [len(logits_at), V], kv)``,
``kv`` each layer's ``(keys [S, G, d], values [S, G, d])`` float32: what a
token leaves in the cache; ``confidence`` and ``reveal``: the reveal rule).

One more length distribution, ``{"dist": "tiers", "values": [...], "shares":
[...]}``: ``round(share x n)`` requests of each value (the last what is left of
``n``), laid in the stratified order ``schedule_seed`` gives.  The traffic
file's ``denoising_steps`` is such a spec: each request's passes a block, drawn
in an order of its own from ``schedule_seed + 1``, so every run of
``balance_group`` arrivals holds the tiers in their shares and no seed changes
who gets which.  Prompt ids are drawn below the mask token's id.

A step returns most rows nothing and a committing row up to a block of tokens;
``stats.request_latencies`` takes deliveries ``(time, n)`` as they come, so
``tpot_p50_ms`` is what it is in every serving cell: window time over tokens
delivered after the first delivery.  The prompt's left-over ``L mod B`` tokens
are not prefilled by a chunk: they open the first block, and the program says
so with a ``block_open`` event, which this kind counts as prefill progress
(``serve_requests`` counts that from ``prefill`` spans alone) and keeps out of
``chunk_tokens``.

The check (outside the window, through ``put`` / ``step``, so the programs
measured are the programs checked; logits and not tokens are compared): check
prompts of every remainder modulo the block length, both tiers,
``check_blocks`` blocks each.  The engine keeps, for the check, every pass's
block state before and after it (``engine.blocks.passes``); the reference
replays each denoising pass FROM THE ENGINE'S OWN STATE — one forward over the
committed tokens and the block as it stood — and holds

- ``regret`` (largest) and ``mean_regret``: each revealed token's regret
  against the reference's logits at its masked position, over max |logit|;
- ``reveal_shortfall``: how far the reference's log-confidence of the least
  confident position the engine revealed lies under that of the most confident
  masked position it passed over, over max |logit| — the reveal rule, not only
  the arg-max: 0 where the engine revealed the reference's own choice — the
  MEAN over the passes that had a choice (more masked positions than the pass
  reveals).  With seeded random weights every confidence is alike, so a
  program in bfloat16 now and then prefers a position whose confidence the
  reference puts a hair lower (the largest single shortfall reads the same
  for the program and for a reference in float8), while a rule that reveals
  the wrong positions falls short in every pass: the mean tells them apart;
- ``kv_error_blocks``: when a check request has committed its checked blocks
  and is still admitted, its cached rows (``engine.read_kv``) over the
  GENERATED blocks against the reference's keys and values of the final
  tokens — what the commit pass wrote; ``kv_error_prompt``: the same over the
  prompt's whole blocks — what the chunk program wrote under the block mask;
  ``kv_error_first``: the first layer's rows alone, every position (the
  embedding through one norm, one projection, the head norm and the rotation:
  the cache's own precision and nothing else).  Each the largest, over
  layers, of ``|rows - rows_ref|_F / |rows_ref|_F`` of a layer's keys and of
  its values, the rows of every check prompt taken together (sixteen generated
  positions of one prompt are few enough for one flipped expert pick to move
  their error alone by half).

``negative_control`` in the traffic file (no committed file has it) plants a
fault, and the line must read ``correct: false``:

- ``{"reference": {"weights_dtype": <dtype>}}`` or ``{"reference": {"mask":
  "causal"}}`` — the reference with that control stands in for the program: it
  replays the same passes from the same states, its own reveal is read as the
  program's, its keys and values as the program's;
- ``{"program": {"commit": "stale"}}`` — the program itself, with the commit
  pass's K/V never written: the last denoising pass's K/V (computed while its
  last positions still held the mask token) left standing in their place;
- ``{"program": {"reveal": "least"}}`` — the program itself, revealing the
  LEAST confident masked positions of a pass (their tokens still the arg-max).

It also keeps what the program says of each step: the attributes of the
``serve_step`` span (``STEP_KEYS``) are added to the window's step records
(``decode_rows`` and ``chunks`` as the program counts them: a row-pass is a
row, whatever it was delivered), and each chunk's ``(tokens, start)`` as
``chunk_spans`` for the flash kernel's count.
"""

from __future__ import annotations

import types
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, stats

STEP_KEYS = ("chunks", "decode_rows", "block_passes", "row_passes",
             "commit_row_passes", "tokens_revealed", "tokens_committed",
             "blocks_dropped", "block_kv_tokens", "page_tokens_in_use",
             "moe_local_picks", "moe_experts_touched", "moe_padded_rows",
             "moe_layer_calls")


class TieredPrompt(list):
    """A prompt that carries its request's passes a block."""
    steps = None


def tiered_lengths(plain):
    """``serve_requests._lengths`` with the ``tiers`` distribution beside the
    ones it has (``plain``)."""
    def lengths(spec: Dict[str, Any], n: int, order: List[int]) -> List[int]:
        if spec["dist"] != "tiers":
            return plain(spec, n, order)
        counts = [int(round(s * n)) for s in spec["shares"][:-1]]
        counts.append(n - sum(counts))
        vals = sorted(v for v, c in zip(spec["values"], counts)
                      for _ in range(max(c, 0)))
        return [int(vals[i]) for i in order]
    return lengths


def tiered_requests(make, lengths, mask_id: int):
    """``serve_requests.make_requests`` with a tier a request and prompt ids
    below the mask token's."""
    def make_requests(traffic, seed, seconds, vocab):
        reqs = make(traffic, seed, seconds, min(vocab, mask_id))
        rng = np.random.default_rng(int(traffic.get("schedule_seed", 0)) + 1)
        group = int(traffic.get("balance_group", 8))
        # each part of the schedule (the pre-roll, the window) draws its own
        # full multiset, as the prompts and outputs do
        for part in ([r for r in reqs if r["due"] < 0.0],
                     [r for r in reqs if r["due"] >= 0.0]):
            if not part:
                continue
            part.sort(key=lambda r: r["index"])
            steps = lengths(traffic["denoising_steps"], len(part),
                            stats.balanced_order(len(part), group, rng))
            for r, s in zip(part, steps):
                r["prompt"] = TieredPrompt(r["prompt"])
                r["prompt"].steps = r["steps"] = int(s)
        return reqs
    return make_requests


def _controls(spec: Dict[str, Any]) -> Dict[str, Any]:
    import jax.numpy as jnp

    out = dict(spec)
    if "weights_dtype" in out:
        out["weights_dtype"] = getattr(jnp, out["weights_dtype"])
    return out


def stale_commit() -> None:
    """The negative control on the program's side: a row whose block has no
    masked position — the commit — is run as a row that is not active, so its
    K/V go to the trash page and the last denoising pass's stand."""
    from deepspeed_tpu.inference.v2 import block_diffusion

    block_pass = block_diffusion.paged_block_pass

    def faulty(cfg, params, pools, ids, masked, start, table, active, n):
        return block_pass(cfg, params, pools, ids, masked, start, table,
                          active & masked.any(axis=1), n)

    block_diffusion.paged_block_pass = faulty


def least_confident_reveal() -> None:
    """The negative control on the reveal rule: a pass reveals the positions
    the rule would have revealed LAST (each with the rule's own token)."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import model_runner

    rule = model_runner.reveal_tokens

    def faulty(logits, ids, masked, n_reveal):
        tokens, _ = rule(logits, ids, masked,
                         jnp.full_like(n_reveal, ids.shape[1]))
        # what stays masked when the rule reveals all but n: the n least sure
        _, least = rule(logits, ids, masked,
                        jnp.sum(masked, axis=1).astype(n_reveal.dtype)
                        - n_reveal)
        return jnp.where(least, tokens, ids), masked & jnp.logical_not(least)

    model_runner.reveal_tokens = faulty


def check_against_reference(reference, ctx, engine, desc, vocab: int
                            ) -> Dict[str, Any]:
    """A request asks for one block more than is checked, so that it is
    still admitted — its pages still its own — when the last checked block
    has been committed; then it is released."""
    from deepspeed_tpu.inference.v2 import RaggedRequest

    tr = ctx.traffic
    B, mask_id = desc["block_length"], desc["mask_token_id"]
    control = _controls(tr.get("negative_control", {}).get("reference", {}))
    n_blocks = int(tr["check_blocks"])
    rng = np.random.default_rng(ctx.seed + 1)
    asked: Dict[int, Dict[str, Any]] = {}
    kept: Dict[int, List[Dict[str, Any]]] = {}
    engine.blocks.passes = {}
    for n, steps in zip(tr["check_prompt_tokens"], tr["check_steps"]):
        ids = rng.integers(0, min(vocab, mask_id), int(n),
                           dtype=np.int64).tolist()
        # the tokens of n_blocks blocks from the prompt's end, one block more
        want = (n_blocks + 1) * B - int(n) % B
        uid = engine.put(RaggedRequest(prompt_ids=ids, max_new_tokens=want,
                                       denoising_steps=int(steps)))
        asked[uid] = {"prompt": ids, "got": [], "steps": int(steps),
                      "want": want - B}
    while engine.has_work():
        for uid, o in engine.step().items():
            a = asked.get(uid)
            if a is None or uid in kept:
                continue
            a["got"] += o["tokens"]
            if len(a["got"]) >= a["want"]:
                a["got"] = a["got"][:a["want"]]
                kept[uid] = engine.read_kv(uid)
                engine.release_sequence(uid, reason="checked")
    passes, engine.blocks.passes = engine.blocks.passes, None

    regrets: List[float] = []
    shortfalls: List[float] = []
    agree = 0
    # (region, layer, "k" | "v") -> [sum |rows - ref|^2, sum |ref|^2]
    sq: Dict[Any, List[float]] = {}
    for uid, a in asked.items():
        if uid not in kept:
            raise RuntimeError(f"check request returned {len(a['got'])} "
                               f"tokens and never stood at {a['want']}")
        final = a["prompt"] + a["got"]  # whole blocks: what was committed
        end = len(final)
        assert end % B == 0 and kept[uid][0]["k"].shape[0] == end
        for p in passes[uid]:
            start, masked = int(p["start"]), np.asarray(p["masked"], bool)
            if not masked.any() or start >= end:
                continue  # a commit pass, or the block past the checked ones
            fed = final[:start] + [int(t) for t in p["ids"]]
            ref, _ = reference.forward(desc, engine.params, fed,
                                       range(start, start + B))
            ref = np.asarray(ref)
            after = np.asarray(p["ids_after"])
            shown = masked & ~np.asarray(p["masked_after"], bool)
            if control:  # the planted fault stands in the program's place
                off, _ = reference.forward(desc, engine.params, fed,
                                           range(start, start + B), **control)
                after, still = reference.reveal(
                    off, p["ids"], masked, B // a["steps"])
                shown = masked & ~still
            scale = float(np.abs(ref).max())
            top, conf = reference.confidence(ref)
            for i in np.flatnonzero(shown):
                regrets.append(float(ref[i].max() - ref[i, after[i]]) / scale)
                agree += int(after[i] == top[i])
            over = masked & ~shown
            if over.any():  # the pass had a choice
                shortfalls.append(max(0.0, float(
                    conf[over].max() - conf[shown].min())) / scale)
        _, ref_kv = reference.forward(desc, engine.params, final)
        mine = kept[uid]
        if control:
            _, off_kv = reference.forward(desc, engine.params, final,
                                          **control)
            mine = [{"k": np.asarray(k), "v": np.asarray(v)}
                    for k, v in off_kv]
        p0 = len(a["prompt"]) // B * B
        for l, (got, kv) in enumerate(zip(mine, ref_kv)):
            for name, lo, hi in (("blocks", p0, end), ("prompt", 0, p0),
                                 ("first", 0, end if l == 0 else 0)):
                for nm, rows in zip("kv", kv):
                    rows = np.asarray(rows, np.float64)[lo:hi]
                    acc = sq.setdefault((name, l, nm), [0.0, 0.0])
                    acc[0] += float(np.sum(
                        (np.asarray(got[nm], np.float64)[lo:hi] - rows) ** 2))
                    acc[1] += float(np.sum(rows ** 2))
    errs = {name: max(float(np.sqrt(d / r)) for (n, _, _), (d, r)
                      in sq.items() if n == name and r > 0.0)
            for name in ("blocks", "prompt", "first")}
    return {"regrets": regrets, "max_regret": max(regrets),
            "mean_regret": sum(regrets) / len(regrets),
            "reveal_shortfall": sum(shortfalls) / len(shortfalls),
            "argmax_agree": agree, "positions": len(regrets),
            "prompt_tokens": [len(a["prompt"]) for a in asked.values()],
            "kv_error_blocks": errs["blocks"],
            "kv_error_prompt": errs["prompt"],
            "kv_error_first": errs["first"]}


LIMITS = ("mean_regret", "reveal_shortfall", "kv_error_blocks",
          "kv_error_prompt", "kv_error_first")


def run(ctx: harness.Context) -> Dict[str, Any]:
    from deepspeed_tpu.inference import v2

    man = ctx.manifest
    serve = man.module("generators", "serve_requests")
    reference = man.module("reference", ctx.config["reference"])
    control = ctx.traffic.get("negative_control", {})
    if control:
        ctx.say(f"serve: NEGATIVE CONTROL {control}: this run must read "
                "correct: false")
    if control.get("program", {}).get("commit") == "stale":
        stale_commit()
    if control.get("program", {}).get("reveal") == "least":
        least_confident_reveal()
    serve._lengths = tiered_lengths(serve._lengths)
    serve.make_requests = tiered_requests(
        serve.make_requests, serve._lengths,
        ctx.family().describe(ctx.model_sizes())["mask_token_id"])
    checks: List[Dict[str, Any]] = []

    def check(*args):
        checks.append(check_against_reference(reference, *args))
        return checks[-1]

    serve.check_against_reference = check

    drains: List[Dict[str, Any]] = []
    drain = serve._drain

    def keeping_drain(recorder):
        spans = drain(recorder)
        steps = [sp.attrs for sp in spans if sp.name == "serve_step"]
        chunks = [[int(sp.attrs["tokens"]), int(sp.attrs.get("start", 0))]
                  for sp in spans
                  if sp.name == "prefill" and sp.cat == "phase"]
        # a prompt's left-over tokens open its first block: prefill progress
        # that no chunk made
        opened = [types.SimpleNamespace(
            name="prefill", cat="phase", attrs={
                "uid": sp.attrs["uid"], "start": sp.attrs["start"],
                "tokens": sp.attrs["prompt_tokens"]})
            for sp in spans if sp.name == "block_open"]
        drains.append(dict(
            steps[-1] if steps else {}, chunk_spans=chunks,
            opened_tokens=sum(o.attrs["tokens"] for o in opened)))
        return spans + opened

    serve._drain = keeping_drain

    plain_request = v2.RaggedRequest

    def request(prompt_ids, max_new_tokens, denoising_steps=None):
        return plain_request(
            prompt_ids=list(prompt_ids), max_new_tokens=max_new_tokens,
            denoising_steps=denoising_steps or getattr(prompt_ids, "steps",
                                                       None))

    v2.RaggedRequest = request
    try:
        result = serve.run(ctx)
    finally:
        v2.RaggedRequest = plain_request
    chk, tr = checks[-1], ctx.traffic
    limits = {k: float(tr[k + "_tolerance"]) for k in LIMITS}
    ctx.say("serve: the check's passes replayed from the engine's own block "
            "states, and the cached rows of the check requests against the "
            "reference's at the same positions: "
            + ", ".join(f"{k} {chk[k]:.3e} (limit {v})"
                        for k, v in limits.items()))
    result["correct"] = bool(result["correct"]
                             and all(chk[k] < v for k, v in limits.items()))
    # the first drain empties the ring of the warm-up and the check; each
    # later one follows one step() of the loop, the window's steps last
    per_step = drains[1:]
    steps = result.get("steps", [])
    for rec, attrs in zip(steps, per_step[len(per_step) - len(steps):]):
        rec.update({k: attrs[k] for k in STEP_KEYS + ("chunk_spans",)
                    if k in attrs})
        rec["chunks"] = len(attrs["chunk_spans"])
        rec["chunk_tokens"] -= attrs["opened_tokens"]
    slots = int(result["engine_config"]["max_seqs"])
    passes = [s for s in steps if s.get("block_passes")]
    if passes:
        # a slot is taken by a row in the pass or by a sequence whose prompt
        # a chunk call of the step prefills (one a sequence a step)
        taken = sum(1 for s in steps
                    if s.get("decode_rows", 0) + s["chunks"] >= slots)
        rows, commits, shown, out = (sum(s.get(k, 0) for s in passes) for k in (
            "row_passes", "commit_row_passes", "tokens_revealed",
            "tokens_committed"))
        ctx.say(f"serve: {taken} of {len(steps)} steps of the window had "
                f"every one of {slots} slots in the pass or prefilling; "
                f"{len(passes)} passes, {rows} row-passes "
                f"({commits} of them commits, {commits / max(rows, 1):.3f}), "
                f"{shown} positions revealed, {out} tokens delivered: "
                f"{rows / max(out, 1):.3f} row-passes a delivered token; "
                f"blocks dropped {sum(s.get('blocks_dropped', 0) for s in steps)}; "
                "cached positions the block program read a pass "
                f"{np.mean([s.get('block_kv_tokens', 0) for s in passes]):.0f}, "
                "positions of pages held "
                f"{np.median([s.get('page_tokens_in_use', 0) for s in passes]):.0f}")
        held = [s for s in steps if s.get("moe_layer_calls")]
        if held:
            picks, calls, ran = (sum(s[k] for s in held) for k in (
                "moe_local_picks", "moe_layer_calls", "moe_padded_rows"))
            ctx.say(f"serve: the experts over the window's steps: {picks} "
                    f"picks in {calls} layer calls ({picks / calls:.1f} a "
                    f"call), {ran} rows run ({ran / max(picks, 1):.3f} a pick)")
    return result
