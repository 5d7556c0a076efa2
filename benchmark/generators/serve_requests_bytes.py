"""``serve_requests`` for a served model whose head predicts several positions
at once and whose cache is not a row a position (EvaByte: eight heads of 320
bytes; an open window's rows and a summary row a closed chunk), with the
reference and the cache's accessor taken from the configuration.

This kind plays the same traffic through the same ``run`` as the kinds beside
it — arrivals, warm-up, loop, arithmetic, all unchanged — and reads from the
configuration file:

- ``"reference"``: the module ``reference/<name>.py`` (``forward(desc, params,
  ids, logits_from=n, **controls) -> (logits [S - n, heads, V], rows)``,
  ``rows`` each layer's ``[held rows, width]`` float32: what a sequence of
  ``S`` positions holds in the cache);
- ``"cache"``: ``{"accessor": the engine's method that returns an admitted
  sequence's held rows ``[layers, rows, width]``}``.

What ``step()`` returns for a request here is its tokens and, beside each, the
further heads' picks (``"heads"``: one list a returned token).

The check sends seeded prompts through ``put`` / ``step`` on the timed engine
and holds what came back to the reference in six readings, each with a limit
in the traffic file:

- ``regret`` (``regret_tolerance``, held by ``serve_requests``) and
  ``mean_regret``: the largest and the mean, over the checked tokens, of how
  far the reference's head-0 logit of the byte the engine chose lies under the
  reference's largest, over max |logit| (the reference is fed the engine's own
  earlier bytes);
- ``heads_regret`` and ``heads_mean_regret``: the same over the picks of the
  further heads, each against its own head's logits at the same position;
- ``rows_error``: the largest, over check prompts and layers, of ``|rows -
  rows_ref|_F / |rows_ref|_F`` of what the engine holds for the request when
  its last checked token has come (the closed windows' summaries, then the
  open window's rows), and ``rows_error_first``: the same over the FIRST
  layer's rows alone — the embedding through one norm, one projection, one
  rotation and for a summary one pooling: nothing but the arithmetic's and the
  cache's own precision shows there.

``negative_control`` in the traffic file (no committed file has it) puts the
reference with one control of its ``forward`` in the program's place
(``{"reference": {"weights_dtype": "float8_e4m3fn"}}``, ``{"summaries":
false}``, ``{"pool": "mean"}``, ``{"mu": false}``, ``{"exact": true}``): it
is fed what the float32 reference is fed, its arg-max at each checked position
and head is read as the program's picks are, its rows as the program's, and
the line must read ``correct: false``.

It also keeps what the program says of each step: the attributes of the
``serve_step`` span named in ``STEP_KEYS`` are added to the window's step
records, and of the step's ``prefill`` spans — a chunk call each — the cached
rows the calls attended, summed as ``ctx_tokens``, and the products
``CALL_KEYS`` names; a program that records none of them (a parent commit)
leaves the records as they were.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark import harness

STEP_KEYS = ("eva_summary_rows_in_use", "eva_window_rows_in_use",
             "eva_rows_in_use", "eva_pages_in_use", "eva_rows_attended",
             "eva_windows_closed")
#: what the generator keeps of a step's ``prefill`` spans, a chunk call each:
#: the rows before the calls, their tokens, and the products the chunk form's
#: operations are counted from (``eva_counts.eva_chunk_ops_bytes``)
CALL_KEYS = ("ctx_tokens", "eva_chunk_tokens", "eva_chunk_tokens_x_ctx",
             "eva_chunk_causal_pairs")
LIMITS = ("mean_regret", "heads_regret", "heads_mean_regret", "rows_error",
          "rows_error_first")


def row_errors(kept: np.ndarray, ref_rows) -> List[float]:
    """``kept``: the program's held rows of one sequence ``[L, n, width]``;
    ``ref_rows``: the reference's, per layer ``[n, width]``.  -> each layer's
    relative Frobenius error."""
    kept = np.asarray(kept, np.float32)
    errs = []
    for got, ref in zip(kept, ref_rows):
        ref = np.asarray(ref, np.float32)
        if got.shape != ref.shape:
            raise RuntimeError(f"the engine holds rows {got.shape}, the "
                               f"reference {ref.shape}")
        errs.append(float(np.linalg.norm(got - ref) / np.linalg.norm(ref)))
    return errs


def _controls(spec: Dict[str, Any]) -> Dict[str, Any]:
    import jax.numpy as jnp

    out = dict(spec)
    if "weights_dtype" in out:
        out["weights_dtype"] = getattr(jnp, out["weights_dtype"])
    return out


def _regret(row: np.ndarray, pick: int) -> float:
    return float(row.max() - row[pick]) / float(np.abs(row).max())


def check_against_reference(reference, ctx, engine, desc, vocab: int
                            ) -> Dict[str, Any]:
    """A request asks for one token more than is checked, so that it is still
    admitted — its pages still its own — when the last checked token has
    come; then it is released."""
    from deepspeed_tpu.inference.v2 import RaggedRequest

    tr = ctx.traffic
    read_rows = getattr(engine, ctx.config["cache"]["accessor"])
    control = _controls(tr.get("negative_control", {}).get("reference", {}))
    want = int(tr["check_decode_steps"]) + 1
    rng = np.random.default_rng(ctx.seed + 1)
    asked: Dict[int, List[int]] = {}
    got: Dict[int, List[int]] = {}
    more: Dict[int, List[List[int]]] = {}
    kept: Dict[int, np.ndarray] = {}
    for n in tr["check_prompt_tokens"]:
        ids = rng.integers(0, vocab, int(n), dtype=np.int64).tolist()
        uid = engine.put(RaggedRequest(prompt_ids=ids,
                                       max_new_tokens=want + 1))
        asked[uid], got[uid], more[uid] = ids, [], []
    while engine.has_work():
        for uid, o in engine.step().items():
            if uid not in got or uid in kept:
                continue
            got[uid] += o["tokens"]
            more[uid] += o["heads"]
            if len(got[uid]) == want:
                kept[uid] = read_rows(uid)
                engine.release_sequence(uid, reason="checked")
    regrets: List[float] = []
    head_regrets: List[float] = []
    agree = 0
    by_layer: List[float] = []
    for uid, prompt in asked.items():
        toks, heads = got[uid], more[uid]
        if uid not in kept:
            raise RuntimeError(f"check request returned {len(toks)} tokens "
                               f"and never stood at {want}")
        fed, first = prompt + toks[:-1], len(prompt) - 1
        ref, ref_rows = reference.forward(desc, engine.params, fed,
                                          logits_from=first)
        ref, mine = np.asarray(ref), kept[uid]
        if control:  # the planted fault stands in the program's place
            off, off_rows = reference.forward(
                desc, engine.params, fed, logits_from=first, **control)
            picks = np.argmax(np.asarray(off), axis=-1)
            toks, heads = picks[:, 0].tolist(), picks[:, 1:].tolist()
            mine = np.stack([np.asarray(r) for r in off_rows])
        for row, tok, further in zip(ref, toks, heads):
            regrets.append(_regret(row[0], tok))
            agree += int(tok == int(np.argmax(row[0])))
            head_regrets += [_regret(row[1 + i], p)
                             for i, p in enumerate(further)]
        errs = row_errors(mine, ref_rows)
        by_layer = [max(pair) for pair in zip(errs, by_layer or errs)]
    return {"regrets": regrets, "max_regret": max(regrets),
            "mean_regret": sum(regrets) / len(regrets),
            "heads_regret": max(head_regrets),
            "heads_mean_regret": sum(head_regrets) / len(head_regrets),
            "argmax_agree": agree, "positions": len(regrets),
            "prompt_tokens": [len(x) for x in asked.values()],
            "rows_error": max(by_layer), "rows_error_first": by_layer[0],
            "rows_error_by_layer": by_layer}


def run(ctx: harness.Context) -> Dict[str, Any]:
    man = ctx.manifest
    serve = man.module("generators", "serve_requests")
    reference = man.module("reference", ctx.config["reference"])
    control = ctx.traffic.get("negative_control", {})
    if control:
        ctx.say(f"serve: NEGATIVE CONTROL {control}: this run must read "
                "correct: false")
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
        calls = [(int(sp.attrs["tokens"]), int(sp.attrs["ctx_tokens"]))
                 for sp in spans
                 if sp.name == "prefill" and "ctx_tokens" in sp.attrs]
        drains.append(dict(steps[-1] if steps else {}, **({
            "ctx_tokens": sum(c for _, c in calls),
            "eva_chunk_tokens": sum(n for n, _ in calls),
            "eva_chunk_tokens_x_ctx": sum(n * c for n, c in calls),
            "eva_chunk_causal_pairs": sum(n * (n + 1) // 2
                                          for n, _ in calls)}
            if calls else {})))
        return spans

    serve._drain = keeping_drain
    result = serve.run(ctx)
    chk, tr = checks[-1], ctx.traffic
    limits = {k: float(tr[k + "_tolerance"]) for k in LIMITS}
    ctx.say("serve: the check's regrets (head 0: largest "
            f"{chk['max_regret']:.3e}) and the held rows of the check "
            "requests against the reference's: "
            + ", ".join(f"{k} {chk[k]:.3e} (limit {v})"
                        for k, v in limits.items())
            + "; rows_error by layer "
            + " ".join(f"{e:.1e}" for e in chk["rows_error_by_layer"]))
    result["correct"] = bool(result["correct"]
                             and all(chk[k] < v for k, v in limits.items()))
    # the first drain empties the ring of the warm-up and the check; each
    # later one follows one step() of the loop, the window's steps last
    per_step = drains[1:]
    steps = result.get("steps", [])
    keys = STEP_KEYS + CALL_KEYS
    for rec, attrs in zip(steps, per_step[len(per_step) - len(steps):]):
        rec.update({k: attrs[k] for k in keys if k in attrs})
    if any("eva_rows_attended" in s or "ctx_tokens" in s for s in steps):
        for s in steps:  # a step without a chunk, or without a decode row
            for k in CALL_KEYS:
                s.setdefault(k, 0)
            s.setdefault("eva_rows_attended", 0)
            s.setdefault("eva_windows_closed", 0)
        held = [s["eva_rows_in_use"] for s in steps
                if "eva_rows_in_use" in s]
        full = sum(1 for s in steps if s["decode_rows"]
                   == result["engine_config"]["max_seqs"])
        pages = [s["eva_pages_in_use"] for s in steps
                 if "eva_pages_in_use" in s] or [0]
        ctx.say(f"serve: over the window's {len(steps)} steps: rows held "
                f"{min(held)} - {max(held)} (summaries and open rows of the "
                f"admitted sequences, a layer) in {min(pages)} - {max(pages)} "
                f"pages, rows attended a decode step "
                f"{np.mean([s['eva_rows_attended'] for s in steps]):.0f}, "
                f"windows closed "
                f"{sum(s['eva_windows_closed'] for s in steps)}, steps with "
                f"every slot decoding {full}")
    return result
