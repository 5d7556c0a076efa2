"""``serve_requests`` for a served model that keeps recurrent state, with the
reference, the state leaf and its layout taken from the configuration.

``serve_requests_ref.py`` names Solar-Open2's ``kda_s`` and its step counters
in code; this kind plays the same traffic through the same ``run`` —
arrivals, warm-up, loop, arithmetic, the regret check, all unchanged — and
reads from the configuration file what that one fixes:

- ``"reference"``: the module ``reference/<name>.py`` (``forward(desc, params,
  ids, logits_from=n, **controls) -> (logits [S - n, V], states)``);
- ``"state"``: ``{"leaf": the pool leaf ``engine.read_state`` returns,
  "layout": "as_reference" | "state_major"}`` — ``state_major``: the program
  keeps each of the reference's per-layer states transposed in its last two
  axes.

The check is ``serve_requests_ref``'s: seeded prompts through ``put`` /
``step``, each greedy token's regret against the reference's logits; and when
a check request has returned its last checked token and is still admitted,
its state (``read_state``) against the reference's after the same tokens —
``state_error`` (the largest relative Frobenius error over prompts and
layers) and ``state_bf16_share`` (the share of the state's float32 values a
bfloat16 holds exactly: ~2**-16 for a float32 state, 1 for one kept in or
rounded through bfloat16), each under its limit in the traffic file.

``negative_control`` in the traffic file (no committed file has it) puts a
planted fault in the program's place, and the line must read ``correct:
false``:

- ``{"reference": {<control of forward>}}`` — ``weights_dtype`` (a dtype's
  name: the reference computed in the nearest precision below the served
  one), ``window``, ``lambda_scale``: the reference with that control stands
  in for the program.  It is fed what the float32 reference is fed (the
  prompt and the program's earlier tokens), its argmax at each checked
  position is read as the program's token is, and its states as the
  program's are;
- ``{"program": {"state_dtype": "bfloat16"}}`` — the program itself, with
  every state write of its two scan kernels rounded through that type.

It also keeps what the program says of each step: the attributes of the
``serve_step`` span (``STEP_KEYS``) are added to the window's step records,
and the ``xdec_rows`` of the step's ``prefill`` spans — the prompt tokens the
cross-decoder ran for — as ``xdec_prefill_rows``; a program that records none
of them leaves the records as they were.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark import harness

STEP_KEYS = ("state_slots_in_use", "ssm_rows", "window_tokens",
             "shared_kv_pages", "xdec_rows")


def state_readings(kept: np.ndarray, ref_states, layout: str
                   ) -> Dict[str, float]:
    """``kept``: the program's state leaf of one sequence ``[L, ...]``
    float32; ``ref_states``: the reference's, per layer."""
    kept = np.ascontiguousarray(kept, np.float32)
    errs = []
    for got, ref in zip(kept, ref_states):
        ref = np.asarray(ref, np.float32)
        if layout == "state_major":
            ref = np.swapaxes(ref, -1, -2)
        elif layout != "as_reference":
            raise ValueError(f"unknown state layout {layout!r}")
        errs.append(float(np.linalg.norm(got - ref) / np.linalg.norm(ref)))
    return {"state_error": max(errs),
            "state_bf16_share": float(np.mean(
                kept.view(np.uint32) & 0xFFFF == 0))}


def _controls(spec: Dict[str, Any]) -> Dict[str, Any]:
    import jax.numpy as jnp

    out = dict(spec)
    for key in ("weights_dtype", "state_dtype"):
        if key in out:
            out[key] = getattr(jnp, out[key])
    return out


def round_program_state(dtype_name: str) -> None:
    """The negative control on the program's side: every state the two scan
    kernels return goes through ``dtype_name``'s mantissa (by
    ``reduce_precision``: a cast there and back is the compiler's to drop)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import ssm

    bits = jnp.finfo(getattr(jnp, dtype_name)).nmant

    def rounded(fn):
        def call(*a, **k):
            y, s = fn(*a, **k)
            return y, jax.lax.reduce_precision(s, exponent_bits=8,
                                               mantissa_bits=bits)
        return call

    ssm.ssm_chunk, ssm.ssm_step = rounded(ssm.ssm_chunk), rounded(ssm.ssm_step)


def check_against_reference(reference, ctx, engine, desc, vocab: int
                            ) -> Dict[str, Any]:
    """A request asks for one token more than is checked, so that it is still
    admitted — its state still its own — when the last checked token has
    come; then it is released."""
    from deepspeed_tpu.inference.v2 import RaggedRequest

    tr, st = ctx.traffic, ctx.config["state"]
    control = _controls(tr.get("negative_control", {}).get("reference", {}))
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
                kept[uid] = engine.read_state(uid)[st["leaf"]]
                engine.release_sequence(uid, reason="checked")
    regrets: List[float] = []
    agree = 0
    state = {"state_error": 0.0, "state_bf16_share": 0.0}
    for uid, prompt in asked.items():
        toks = got[uid]
        if uid not in kept:
            raise RuntimeError(f"check request returned {len(toks)} tokens "
                               f"and never stood at {want}")
        fed, first = prompt + toks[:-1], len(prompt) - 1
        ref, ref_states = reference.forward(desc, engine.params, fed,
                                            logits_from=first)
        mine = kept[uid]
        if control:  # the planted fault stands in the program's place
            off, off_states = reference.forward(
                desc, engine.params, fed, logits_from=first, **control)
            toks = [int(np.argmax(row)) for row in off]
            mine = np.stack([np.swapaxes(np.asarray(s), -1, -2)
                             if st["layout"] == "state_major"
                             else np.asarray(s) for s in off_states])
        for row, tok in zip(ref, toks):
            regrets.append(float(row.max() - row[tok])
                           / float(np.abs(row).max()))
            agree += int(tok == int(np.argmax(row)))
        for k, v in state_readings(mine, ref_states, st["layout"]).items():
            state[k] = max(state[k], v)
    return {"regrets": regrets, "max_regret": max(regrets),
            "argmax_agree": agree, "positions": len(regrets),
            "prompt_tokens": [len(x) for x in asked.values()], **state}


def run(ctx: harness.Context) -> Dict[str, Any]:
    man = ctx.manifest
    serve = man.module("generators", "serve_requests")
    reference = man.module("reference", ctx.config["reference"])
    control = ctx.traffic.get("negative_control", {})
    if control:
        ctx.say(f"serve: NEGATIVE CONTROL {control}: this run must read "
                "correct: false")
    if "program" in control:
        round_program_state(control["program"]["state_dtype"])
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
        rows = [sp.attrs["xdec_rows"] for sp in spans
                if sp.name == "prefill" and "xdec_rows" in sp.attrs]
        drains.append(dict(steps[-1] if steps else {},
                           **({"xdec_prefill_rows": sum(rows)} if rows
                              else {})))
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
    per_step = drains[1:]
    steps = result.get("steps", [])
    keys = STEP_KEYS + ("xdec_prefill_rows",)
    for rec, attrs in zip(steps, per_step[len(per_step) - len(steps):]):
        rec.update({k: attrs[k] for k in keys if k in attrs})
    if any("xdec_prefill_rows" in s or "xdec_rows" in s for s in steps):
        for s in steps:  # a step whose chunks were none a prompt's last
            s.setdefault("xdec_prefill_rows", 0)
    return result
