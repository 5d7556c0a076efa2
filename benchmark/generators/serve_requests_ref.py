"""``serve_requests`` with the reference the configuration names.

``serve_requests.py`` binds ``reference/dense_lm.py`` in code; a block that is
not the dense one needs its own reference.  This kind plays the same traffic
through the same ``run`` — arrivals, warm-up, loop, arithmetic, all unchanged
— with the check against the module ``reference/<name>.py`` that the
configuration file's ``"reference"`` key names (``forward(desc, params, ids)
-> (logits, states)``).

The check is ``serve_requests``'s — seeded prompts through ``put`` / ``step``,
each greedy token's regret against the reference's logits — and one thing
more, because tokens cannot see it: what the linear-attention layers keep.
When a check request has returned its last checked token, and is still
admitted, its recurrent state is read from the engine (``read_state``) and
held to the reference's state after the same tokens, in two readings with a
limit each in the traffic file:

- ``state_error``: the largest, over check prompts and layers, of
  ``|S - S_ref|_F / |S_ref|_F`` — the state is the right one (slot, carried
  from chunk to chunk and on into the decode program), to what bf16
  activations allow;
- ``state_bf16_share``: the share of the state's float32 values that a
  bfloat16 holds exactly (low 16 bits zero).  A state kept in float32 reads
  about 2**-16 here; one kept in, or rounded through, bfloat16 reads 1.  The
  error above cannot tell the two apart — the rounding is no larger than
  what the bf16 activations already put into the state — and the
  configuration states float32.

It also keeps what the program says of each step: the attributes of the
``serve_step`` span the engine records per ``step()`` (``telemetry/spans.py``)
— the expert share's counters (``moe_local_picks``, ``moe_experts_touched``,
``moe_padded_rows``, ``moe_layer_calls``) and ``state_slots_in_use`` — are
added to the window's step records, where the readers of the per-layer
metrics find them.  A program that records none of them (a dense model, or a
parent commit) leaves the records as they were.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark import harness

STEP_KEYS = ("moe_local_picks", "moe_experts_touched", "moe_padded_rows",
             "moe_layer_calls", "state_slots_in_use")


def state_readings(kept: np.ndarray, ref_states) -> Dict[str, float]:
    """``kept``: the program's ``kda_s`` of one sequence ``[L, NH, V, K]``
    float32 (S transposed); ``ref_states``: the reference's ``[NH, K, V]``
    per layer."""
    kept = np.ascontiguousarray(kept, np.float32)
    errs = [float(np.linalg.norm(got - np.asarray(ref).transpose(0, 2, 1))
                  / np.linalg.norm(np.asarray(ref)))
            for got, ref in zip(kept, ref_states)]
    return {"state_error": max(errs),
            "state_bf16_share": float(np.mean(
                kept.view(np.uint32) & 0xFFFF == 0))}


def check_against_reference(reference, ctx, engine, desc, vocab: int
                            ) -> Dict[str, Any]:
    """``serve_requests.check_against_reference`` with the state beside the
    tokens: a request asks for one token more than is checked, so that it is
    still admitted — its state still its own — when the last checked token
    has come; then it is released."""
    from deepspeed_tpu.inference.v2 import RaggedRequest

    tr = ctx.traffic
    want = int(tr["check_decode_steps"]) + 1
    rng = np.random.default_rng(ctx.seed + 1)
    asked: Dict[int, List[int]] = {}
    got: Dict[int, List[int]] = {}
    kept: Dict[int, np.ndarray] = {}
    for n in tr["check_prompt_tokens"]:
        ids = rng.integers(0, vocab, int(n), dtype=np.int64).tolist()
        uid = engine.put(RaggedRequest(prompt_ids=ids,
                                       max_new_tokens=want + 1))
        asked[uid], got[uid] = ids, []
    while engine.has_work():
        for uid, o in engine.step().items():
            if uid not in got or uid in kept:
                continue
            got[uid] += o["tokens"]
            if len(got[uid]) == want:
                kept[uid] = engine.read_state(uid)["kda_s"]
                engine.release_sequence(uid, reason="checked")
    regrets: List[float] = []
    agree = 0
    state = {"state_error": 0.0, "state_bf16_share": 0.0}
    for uid, prompt in asked.items():
        toks = got[uid]
        if uid not in kept:
            raise RuntimeError(f"check request returned {len(toks)} tokens "
                               f"and never stood at {want}")
        ref, ref_states = reference.forward(desc, engine.params,
                                            prompt + toks[:-1])
        ref = np.asarray(ref)
        for row, tok in zip(ref[len(prompt) - 1:], toks):
            regrets.append(float(row.max() - row[tok])
                           / float(np.abs(row).max()))
            agree += int(tok == int(np.argmax(row)))
        for k, v in state_readings(kept[uid], ref_states).items():
            state[k] = max(state[k], v)
    return {"regrets": regrets, "max_regret": max(regrets),
            "argmax_agree": agree, "positions": len(regrets),
            "prompt_tokens": [len(x) for x in asked.values()], **state}


def run(ctx: harness.Context) -> Dict[str, Any]:
    man = ctx.manifest
    serve = man.module("generators", "serve_requests")
    reference = man.module("reference", ctx.config["reference"])
    checks: List[Dict[str, Any]] = []

    def check(*args):
        checks.append(check_against_reference(reference, *args))
        return checks[-1]

    serve.check_against_reference = check

    drains: List[List[Dict[str, Any]]] = []
    drain = serve._drain

    def keeping_drain(recorder):
        spans = drain(recorder)
        drains.append([sp.attrs for sp in spans if sp.name == "serve_step"])
        return spans

    serve._drain = keeping_drain
    result = serve.run(ctx)
    chk, tr = checks[-1], ctx.traffic
    limits = {k: float(tr[k + "_tolerance"])
              for k in ("state_error", "state_bf16_share")}
    ctx.say("serve: recurrent state of the check requests against the "
            "reference's after the same tokens: " + ", ".join(
                f"{k} {chk[k]:.3e} (limit {v})" for k, v in limits.items()))
    result["correct"] = bool(result["correct"]
                             and all(chk[k] < v for k, v in limits.items()))
    # the first drain empties the ring of the warm-up and the check; each
    # later one follows one step() of the loop, the window's steps last
    per_step = [d[-1] if d else {} for d in drains[1:]]
    steps = result.get("steps", [])
    for rec, attrs in zip(steps, per_step[len(per_step) - len(steps):]):
        rec.update({k: attrs[k] for k in STEP_KEYS if k in attrs})
    return result
