"""``serve_requests`` for a served model whose attention layers cache a latent,
with the reference and the cache's accessor taken from the configuration.

This kind plays the same traffic through the same ``run`` as the three kinds
beside it — arrivals, warm-up, loop, arithmetic, the regret check, all
unchanged — and reads from the configuration file:

- ``"reference"``: the module ``reference/<name>.py`` (``forward(desc, params,
  ids, logits_from=n, **controls) -> (logits [S - n, V], rows)``, ``rows`` each
  layer's ``[S, width]`` float32: what a token leaves in the cache);
- ``"cache"``: ``{"accessor": the engine's method that returns an admitted
  sequence's cached rows ``[layers, positions, width]``}``.

The check is ``serve_requests``'s — seeded prompts through ``put`` / ``step``,
each greedy token's regret against the reference's logits — with one more
reading of the regrets and two of what the layers cached, all three against
the reference.

``mean_regret`` is the mean of the tokens' regrets.  ``serve_requests`` holds
the LARGEST regret to ``regret_tolerance``, and with a router in every layer
the largest is a flipped pick's: where bf16 rounding moves the fourth and
fifth of 128 scores past each other, an expert of weight ~1/4 is swapped for
another, the layers after it route differently again, and that one token's
logits land a third of max |logit| from the reference's (the same on the CPU
without a kernel: PERF.md, PR 40).  The largest regret of the program then
lies above that of a reference computed a precision lower, and no limit lies
between; the mean over the check's tokens is moved little by one such token
and much by a fault that touches every token.

What the layers cached is read because tokens see it only through a softmax.
When a check request has returned its last checked token and is still
admitted, its cached rows are read from the engine and held to the
reference's at the same positions, in two readings with a limit each in the
traffic file:

- ``latent_error``: the largest, over check prompts and layers, of ``|rows -
  rows_ref|_F / |rows_ref|_F`` — the rows are the right ones (page, position,
  rotation, carried from the chunk program to the decode program), to what
  bf16 activations allow;
- ``latent_error_first``: the same over the FIRST layer's rows alone.  A
  first-layer row is the embedding through one norm, one projection and one
  rotation: no softmax and no router lies before it, so nothing but the
  arithmetic's and the cache's own precision shows there, ten times under
  what the eighth layer's rows carry.  It is the reading that holds the cache
  to the bfloat16 the configuration states: rows kept in, or rounded through,
  anything coarser — with a scale or without — stand further from the
  reference's than bf16 rows do, which the error at depth (what eight layers
  of bf16 activations and a router's flipped picks put there) cannot see.

``negative_control`` in the traffic file (no committed file has it) puts a
planted fault in the program's place, and the line must read ``correct:
false``:

- ``{"reference": {<control of forward>}}`` — ``weights_dtype`` (a dtype's
  name), ``rope``, ``softmax_scale``, ``router``: the reference with that
  control stands in for the program.  It is fed what the float32 reference is
  fed (the prompt and the program's earlier tokens), its argmax at each
  checked position is read as the program's token is, and its rows as the
  program's are;
- ``{"program": {"latent_dtype": "float8_e4m3fn"}}`` — the program itself,
  with every row it writes to the latent pool rounded through that type's
  mantissa: a cache kept below the precision the configuration states.

It also keeps what the program says of each step: the attributes of the
``serve_step`` span (``STEP_KEYS``) are added to the window's step records,
and the ``ctx_tokens`` of the step's ``prefill`` spans — the cached positions
its chunks attended — summed as ``ctx_tokens``; a program that records none
of them (a parent commit) leaves the records as they were.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark import harness

STEP_KEYS = ("latent_kv_tokens", "latent_tokens_in_use", "moe_local_picks",
             "moe_experts_touched", "moe_padded_rows", "moe_layer_calls")


def latent_errors(kept: np.ndarray, ref_rows) -> List[float]:
    """``kept``: the program's cached rows of one sequence ``[L, n, width]``;
    ``ref_rows``: the reference's, per layer ``[S >= n, width]``.  -> each
    layer's relative Frobenius error."""
    kept = np.asarray(kept, np.float32)
    errs = []
    for got, ref in zip(kept, ref_rows):
        ref = np.asarray(ref, np.float32)[:got.shape[0]]
        errs.append(float(np.linalg.norm(got - ref) / np.linalg.norm(ref)))
    return errs


def _controls(spec: Dict[str, Any]) -> Dict[str, Any]:
    import jax.numpy as jnp

    out = dict(spec)
    if "weights_dtype" in out:
        out["weights_dtype"] = getattr(jnp, out["weights_dtype"])
    return out


def round_program_latent(dtype_name: str) -> None:
    """The negative control on the program's side: every row the latent
    layers write to the pool goes through ``dtype_name``'s mantissa (by
    ``reduce_precision``: a cast there and back is the compiler's to drop)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import model_runner

    bits = jnp.finfo(getattr(jnp, dtype_name)).nmant
    project = model_runner._mla_project

    def rounded(*a, **k):
        q_nope, q_rope, row = project(*a, **k)
        return q_nope, q_rope, jax.lax.reduce_precision(
            row, exponent_bits=8, mantissa_bits=bits)

    model_runner._mla_project = rounded


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
                kept[uid] = read_rows(uid)
                engine.release_sequence(uid, reason="checked")
    regrets: List[float] = []
    agree = 0
    by_layer: List[float] = []
    for uid, prompt in asked.items():
        toks = got[uid]
        if uid not in kept:
            raise RuntimeError(f"check request returned {len(toks)} tokens "
                               f"and never stood at {want}")
        fed, first = prompt + toks[:-1], len(prompt) - 1
        ref, ref_rows = reference.forward(desc, engine.params, fed,
                                          logits_from=first)
        mine = kept[uid]
        if control:  # the planted fault stands in the program's place
            off, off_rows = reference.forward(
                desc, engine.params, fed, logits_from=first, **control)
            toks = [int(np.argmax(row)) for row in np.asarray(off)]
            mine = np.stack([np.asarray(r) for r in off_rows])
        for row, tok in zip(np.asarray(ref), toks):
            regrets.append(float(row.max() - row[tok])
                           / float(np.abs(row).max()))
            agree += int(tok == int(np.argmax(row)))
        errs = latent_errors(mine, ref_rows)
        by_layer = [max(pair) for pair in zip(errs, by_layer or errs)]
    return {"regrets": regrets, "max_regret": max(regrets),
            "mean_regret": sum(regrets) / len(regrets),
            "argmax_agree": agree, "positions": len(regrets),
            "prompt_tokens": [len(x) for x in asked.values()],
            "latent_error": max(by_layer), "latent_error_first": by_layer[0],
            "latent_error_by_layer": by_layer}


def run(ctx: harness.Context) -> Dict[str, Any]:
    man = ctx.manifest
    serve = man.module("generators", "serve_requests")
    reference = man.module("reference", ctx.config["reference"])
    control = ctx.traffic.get("negative_control", {})
    if control:
        ctx.say(f"serve: NEGATIVE CONTROL {control}: this run must read "
                "correct: false")
    if "program" in control:
        round_program_latent(control["program"]["latent_dtype"])
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
        ctxs = [sp.attrs["ctx_tokens"] for sp in spans
                if sp.name == "prefill" and "ctx_tokens" in sp.attrs]
        drains.append(dict(steps[-1] if steps else {},
                           **({"ctx_tokens": sum(ctxs)} if ctxs else {})))
        return spans

    serve._drain = keeping_drain
    result = serve.run(ctx)
    chk, tr = checks[-1], ctx.traffic
    limits = {k: float(tr[k + "_tolerance"])
              for k in ("mean_regret", "latent_error", "latent_error_first")}
    ctx.say("serve: the check's mean regret, and the cached rows of the "
            "check requests against the reference's at the same positions: "
            + ", ".join(f"{k} {chk[k]:.3e} (limit {v})"
                        for k, v in limits.items())
            + "; latent_error by layer "
            + " ".join(f"{e:.1e}" for e in chk["latent_error_by_layer"]))
    result["correct"] = bool(result["correct"]
                             and all(chk[k] < v for k, v in limits.items()))
    # the first drain empties the ring of the warm-up and the check; each
    # later one follows one step() of the loop, the window's steps last
    per_step = drains[1:]
    steps = result.get("steps", [])
    keys = STEP_KEYS + ("ctx_tokens",)
    for rec, attrs in zip(steps, per_step[len(per_step) - len(steps):]):
        rec.update({k: attrs[k] for k in keys if k in attrs})
    held = [s for s in steps if s.get("moe_layer_calls")]
    if held:  # the seed's router decides what the held experts are given
        picks, calls, rows = (sum(s[k] for s in held) for k in (
            "moe_local_picks", "moe_layer_calls", "moe_padded_rows"))
        ctx.say(f"serve: the expert share over the window's steps: {picks} "
                f"held picks in {calls} layer calls ({picks / calls:.1f} a "
                f"call), {rows} rows run ({rows / max(picks, 1):.3f} a pick)")
    if any("latent_kv_tokens" in s or "ctx_tokens" in s for s in steps):
        for s in steps:  # a step without a chunk, or without a decode row
            s.setdefault("ctx_tokens", 0)
            s.setdefault("latent_kv_tokens", 0)
    return result
