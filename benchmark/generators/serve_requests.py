"""Serving traffic through ``InferenceEngineV2``'s ``put`` / ``step`` loop,
from one thread, timed by the benchmark's own clock.

Traffic parameters (all data; a new mix is a new file):

``arrivals``
    ``{"process": "trace", "rate_per_s": r, "preroll_s": p}`` — open loop,
    a fixed trace: requests are *due* on one schedule whether or not the
    engine keeps up; the schedule starts ``preroll_s`` before the window
    (set-up) so the window opens in steady state.  ``{"process": "backlog",
    "count": n}`` — closed backlog: the whole queue is present when the
    window opens.
``prompt_tokens`` / ``output_tokens``
    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``,
    ``{"dist": "uniform", "min": a, "max": b}`` or ``{"dist": "fixed",
    "value": v}``.
``reports``
    which end-to-end metric takes which measured quantity: ``tpot_p50_ms``,
    ``ttft_mean_ms``, ``ttft_p50_ms``, ``tokens_per_s``, ``setup_s``.
``check_prompt_tokens`` / ``check_decode_steps``
    the prompts of the reference check and how many decode steps follow the
    token their prefill samples (0: a cell that never decodes).
``tpot_min_gaps``
    fewest token gaps inside the window for a request to give a TPOT sample
    (16 if absent); ``ttft_share``: the part of the window whose arrivals
    give TTFT samples (0.8 if absent).

The schedule is a fixed trace, not a random process: the gaps are the
mid-quantiles of an exponential distribution (the gaps a Poisson process at
that rate would have), the prompt and output lengths the mid-quantiles of
their distributions, and their stratified order follows the traffic file's
``schedule_seed``, not ``--seed``.  Every seed runs the same arrivals and
sizes, and gives the token ids and the weights.  Measured on the chip (PR
23): with ~100 requests in a run, the same multisets in another random order
moved ``tpot_p50_ms`` by 3 % and ``ttft_p90_ms`` by 10 % from seed to seed,
where one seed repeated to 0.4 % and 0.1-4 %: at this sample size the order
*is* the work.  Another order is another traffic file (another
``schedule_seed``), that is, another cell; a bound set on this trace says
nothing of another.

The clock: a request's time to first token runs from when it was *due*, not
from when this loop got round to ``put`` — the wait a stall imposes on later
arrivals counts — and the loop's lateness is printed.  A token's time is the
return of the ``step()`` that produced it.

What is taken from the program: ``put`` / ``step`` / ``has_work`` and what
``step()`` returns, and the spans and events the program records in its own
ring (``telemetry/spans.py``): one ``prefill`` span per chunk call, carrying
``uid``, ``start`` and ``tokens``, and one ``preempt`` event per preemption.
Prefill progress is counted from those spans, never from a copy of the
scheduler's policy, so a scheduler that runs other chunks in another order
is measured, not refused.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, stats
from benchmark.reference import dense_lm


# ------------------------------------------------------------- the traffic
def _icdf(spec: Dict[str, Any]):
    if spec["dist"] == "lognormal":
        return stats.lognormal_icdf(spec["median"], spec["sigma"])
    if spec["dist"] == "uniform":
        return stats.uniform_icdf(spec["min"], spec["max"])
    if spec["dist"] == "fixed":
        return lambda p: spec["value"]
    raise ValueError(f"unknown distribution {spec['dist']!r}")


def _lengths(spec: Dict[str, Any], n: int, order: List[int]) -> List[int]:
    vals = sorted(stats.stratified(n, _icdf(spec), spec.get("min"),
                                   spec.get("max")))
    return [int(round(vals[i])) for i in order]


def _arrival_times(arr: Dict[str, Any], start: float, span: float,
                   rng, group: int = 8) -> List[float]:
    """Due times in [start, start + span): a fixed multiset of exponential
    gaps in a stratified order (every run of ``group`` gaps holds one from
    each ``group``-quantile), scaled to fill the span exactly."""
    n = int(round(arr["rate_per_s"] * span))
    if n <= 0:
        return []
    gaps = stats.stratified(n, stats.exponential_icdf(1.0))
    gaps = [gaps[i] for i in stats.balanced_order(n, group, rng)]
    scale = span / sum(gaps)
    t, out = 0.0, []
    for g in gaps:
        out.append(t + g * scale / 2.0)
        t += g * scale
    return [start + x for x in out]


def make_requests(traffic: Dict[str, Any], seed: int, seconds: float,
                  vocab: int) -> List[Dict[str, Any]]:
    """The run's requests, a pure function of (traffic, seed, seconds):
    ``due`` is seconds relative to the window's start (negative: pre-roll).
    Arrivals and sizes follow ``schedule_seed``; token ids follow ``seed``."""
    ids_rng = np.random.default_rng(seed)
    rng = np.random.default_rng(int(traffic.get("schedule_seed", 0)))
    group = int(traffic.get("balance_group", 8))
    arr = traffic["arrivals"]
    if arr["process"] == "backlog":
        dues = [0.0] * int(arr["count"])
        parts = [len(dues)]
    elif arr["process"] != "trace":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    else:
        pre = float(arr.get("preroll_s", 0.0))
        d_pre = _arrival_times(arr, -pre, pre, rng, group) if pre > 0 else []
        d_win = _arrival_times(arr, 0.0, seconds, rng, group)
        dues, parts = d_pre + d_win, [len(d_pre), len(d_win)]
    prompts: List[int] = []
    outputs: List[int] = []
    for n in parts:  # each part draws its own full multiset
        if n:
            prompts += _lengths(traffic["prompt_tokens"], n,
                                stats.balanced_order(n, group, rng))
            outputs += _lengths(traffic["output_tokens"], n,
                                stats.balanced_order(n, group, rng))
    reqs = []
    for i, (due, p, o) in enumerate(zip(dues, prompts, outputs)):
        reqs.append({"index": i, "due": float(due), "want": int(o),
                     "prompt": ids_rng.integers(0, vocab, max(p, 1),
                                                dtype=np.int64).tolist()})
    return sorted(reqs, key=lambda r: r["due"])


def window_bucket(tokens: int, page_size: int, max_pages: int) -> int:
    """Pages in the chunk program's window for a context of ``tokens``: the
    engine rounds the page count up to a power of two (few shapes to warm)."""
    used = -(-tokens // page_size)
    b = 1
    while b < used:
        b *= 2
    return min(b, max_pages)


def warm_prompt_lengths(min_prompt: int, max_prompt: int, chunk: int,
                        page_size: int, max_pages: int) -> List[int]:
    """Fewest prompt lengths whose chunked prefill touches every window
    bucket any prompt in [min, max] can touch."""
    def buckets(n: int) -> set:
        return {window_bucket(min(start + chunk, n), page_size, max_pages)
                for start in range(0, n, chunk)}

    need = set()
    for n in range(min_prompt, max_prompt + 1):
        need |= buckets(n)
    picked, have = [], set()
    for n in range(max_prompt, min_prompt - 1, -1):
        new = buckets(n) - have
        if new:
            picked.append(n)
            have |= new
        if have >= need:
            break
    return picked


# --------------------------------------------------------------- the check
def check_against_reference(ctx, engine, desc, vocab: int) -> Dict[str, Any]:
    """Seeded prompts through ``put`` / ``step`` — the chunk and decode
    programs the window measures — against the float32 reference.

    The engine returns tokens, not logits, so each greedy token is held to
    the reference's logits at its position (the reference is fed the
    engine's own earlier tokens): its *regret* is how far the reference's
    logit of the chosen token lies under the reference's largest, over
    max |logit|.  An engine whose logits are within e of the reference's
    picks a token of regret at most 2e; a wrong page, mask, rotation or
    position picks one the reference ranks anywhere."""
    from deepspeed_tpu.inference.v2 import RaggedRequest

    tr = ctx.traffic
    steps = int(tr["check_decode_steps"])
    rng = np.random.default_rng(ctx.seed + 1)
    asked: Dict[int, List[int]] = {}
    got: Dict[int, List[int]] = {}
    for n in tr["check_prompt_tokens"]:
        ids = rng.integers(0, vocab, int(n), dtype=np.int64).tolist()
        uid = engine.put(RaggedRequest(prompt_ids=ids,
                                       max_new_tokens=steps + 1))
        asked[uid], got[uid] = ids, []
    while engine.has_work():
        for uid, o in engine.step().items():
            if uid in got:
                got[uid] += o["tokens"]
    regrets: List[float] = []
    agree = 0
    for uid, prompt in asked.items():
        toks = got[uid]
        if len(toks) != steps + 1:
            raise RuntimeError(f"check request returned {len(toks)} tokens "
                               f"of {steps + 1}")
        ref = np.asarray(dense_lm.logits(desc, engine.params,
                                         prompt + toks[:-1]))
        for row, tok in zip(ref[len(prompt) - 1:], toks):
            regrets.append(float(row.max() - row[tok])
                           / float(np.abs(row).max()))
            agree += int(tok == int(np.argmax(row)))
    return {"regrets": regrets, "max_regret": max(regrets),
            "argmax_agree": agree, "positions": len(regrets),
            "prompt_tokens": [len(x) for x in asked.values()]}


def _drain(recorder) -> list:
    """The spans and events the program recorded since the last call."""
    spans = recorder.spans()
    recorder.clear()
    return spans


# ----------------------------------------------------------------- the run
def run(ctx: harness.Context) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig,
                                            RaggedRequest)
    from deepspeed_tpu.telemetry.spans import get_span_recorder

    tr, sizes = ctx.traffic, ctx.model_sizes()
    family = ctx.family()
    desc = family.describe(sizes)
    vocab = desc["vocab_size"]
    ecfg = dict(ctx.config["engine"])
    if ctx.rehearse:
        ecfg.update(ctx.config.get("tiny_engine", {}))
    ps, chunk = int(ecfg["page_size"]), int(ecfg["prefill_chunk"])
    max_pages = int(ecfg["max_pages_per_seq"])
    n_layers = int(sizes["num_hidden_layers"])
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[ecfg["dtype"]]
    recorder = get_span_recorder()
    if not recorder.enabled:
        raise RuntimeError("the program's span recorder is off: prefill "
                           "progress cannot be read")

    t0 = time.perf_counter()
    model = family.build(sizes, n_layers, ps * max_pages, dtype)
    key = jax.random.PRNGKey(harness.seed31(ctx.seed))
    with harness.annotate("bench.init_params"):
        params = jax.jit(model.init_params)(key)  # on device, served dtype
        engine = InferenceEngineV2(model, RaggedInferenceConfig(**ecfg),
                                   params=params,
                                   seed=harness.seed31(ctx.seed))
    del params
    ctx.say(f"serve: {ctx.config['name']} layers={n_layers} weights "
            f"{engine.param_bytes / 1e9:.3f} GB engine={ecfg}; built in "
            f"{time.perf_counter() - t0:.2f} s")

    seconds = ctx.window_seconds
    reqs = make_requests(tr, ctx.seed, seconds, vocab)
    p_min = min(len(r["prompt"]) for r in reqs)
    p_max = max(len(r["prompt"]) + r["want"] for r in reqs)
    if p_max >= ps * max_pages:
        raise ValueError(f"a context of {p_max} tokens does not fit "
                         f"{ps * max_pages}")

    # ---- warm-up: every chunk-window bucket this traffic can touch, and
    # the decode program; nothing else
    t0 = time.perf_counter()
    wrng = np.random.default_rng(ctx.seed + 2)
    warm = warm_prompt_lengths(p_min, min(p_max, ps * max_pages - 3), chunk,
                               ps, max_pages)
    # a second token loads the decode program: only where the traffic decodes
    warm_new = min(2, max(r["want"] for r in reqs))
    for n in warm:
        engine.put(RaggedRequest(
            prompt_ids=wrng.integers(0, vocab, n, dtype=np.int64).tolist(),
            max_new_tokens=warm_new))
    while engine.has_work():
        engine.step()
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    chk = check_against_reference(ctx, engine, desc, vocab)
    tol = float(tr["regret_tolerance"])
    engine.assert_no_leaks()
    engine.reset_cache_stats()
    ctx.say(f"serve: warmed prompts {warm} in {t_warm:.2f} s; reference "
            f"check in {time.perf_counter() - t0:.2f} s through put/step, "
            f"prompts {chk['prompt_tokens']}: over {chk['positions']} greedy "
            f"tokens, largest regret against the float32 reference "
            f"{chk['max_regret']:.3e} of max |logit| (tolerance {tol}), "
            f"the reference's own argmax at {chk['argmax_agree']}/"
            f"{chk['positions']}; per token "
            + " ".join(f"{e:.1e}" for e in chk["regrets"]))

    # ---- the loop
    book: Dict[int, Dict[str, Any]] = {}   # uid -> request record
    steps: List[Dict[str, Any]] = []
    lateness: List[float] = []
    preempted = 0
    span_errors = 0
    backlog = tr["arrivals"]["process"] == "backlog"
    preroll = 0.0 if backlog else float(tr["arrivals"].get("preroll_s", 0.0))
    nxt = 0

    def put_due(now_rel: float) -> None:
        nonlocal nxt
        while nxt < len(reqs) and reqs[nxt]["due"] <= now_rel:
            r = reqs[nxt]
            uid = engine.put(RaggedRequest(prompt_ids=r["prompt"],
                                           max_new_tokens=r["want"]))
            lateness.append(now_rel - r["due"])
            book[uid] = {"index": r["index"], "due": r["due"],
                         "put": now_rel, "admitted": None, "first": None,
                         "last": None, "tokens": 0, "want": r["want"],
                         "deliveries": [],
                         "prompt_len": len(r["prompt"]), "prefilled": 0,
                         "bad_token": False}
            nxt += 1

    _drain(recorder)
    gc_watch = harness.GcWatch().start()
    t_loop0 = time.perf_counter()
    t_w0 = t_loop0 + preroll
    if backlog:
        put_due(0.0)
        t_w0 = time.perf_counter()
    compiles0 = None
    in_window = False
    span = "preroll.step"
    while True:
        now = time.perf_counter()
        if not in_window and now >= t_w0:
            ctx.start_trace()
            in_window, span = True, "bench.step"
            compiles0 = harness.compiles()
            now = time.perf_counter()
            if backlog:
                t_w0 = now
        if now - t_w0 >= seconds:
            break
        with harness.annotate("bench.put" if in_window else "preroll.put"):
            put_due(now - t_w0)
        if not engine.has_work():
            wait = (reqs[nxt]["due"] - (now - t_w0)) if nxt < len(reqs) \
                else seconds - (now - t_w0)
            time.sleep(min(max(wait, 0.0), 0.005))
            continue
        t_s = time.perf_counter()
        with harness.annotate(span):
            out = engine.step()
        t_e = time.perf_counter()
        rec = {"t0": t_s - t_w0, "t1": t_e - t_w0, "chunks": 0,
               "chunk_tokens": 0, "recompute_tokens": 0, "decode_rows": 0,
               "decode_pages": 0, "new_tokens": 0, "window": in_window}
        for sp in _drain(recorder):
            b = book.get(sp.attrs.get("uid"))
            if b is None:
                continue
            if sp.name == "preempt":
                preempted += 1
            elif sp.name == "prefill" and sp.cat == "phase":
                # tokens [start, start + n) went through a prefill program;
                # those past what was counted before are prompt tokens
                # served, the rest re-computation after a preemption
                end = int(sp.attrs.get("start", 0)) + int(sp.attrs["tokens"])
                new = max(0, min(end, b["prompt_len"]) - b["prefilled"])
                b["prefilled"] += new
                rec["chunks"] += 1
                rec["chunk_tokens"] += new
                rec["recompute_tokens"] += int(sp.attrs["tokens"]) - new
                if b["admitted"] is None:
                    b["admitted"] = t_s - t_w0
        for u, o in out.items():
            b = book.get(u)
            if b is None:
                continue
            toks = o["tokens"]
            n_dec = len(toks)
            if b["first"] is None and toks:
                b["first"] = t_e - t_w0
                n_dec -= 1
                if b["prefilled"] != b["prompt_len"]:
                    span_errors += 1
            for j in range(n_dec):
                length = b["prompt_len"] + b["tokens"] + (len(toks) - n_dec) + j
                rec["decode_pages"] += -(-length // ps)
            rec["decode_rows"] += n_dec
            rec["new_tokens"] += len(toks)
            b["tokens"] += len(toks)
            b["bad_token"] |= any(not 0 <= t < vocab for t in toks)
            if toks:
                b["last"] = t_e - t_w0
                b["deliveries"].append((t_e - t_w0, len(toks)))
            if o.get("done"):
                b["finish_reason"] = o.get("finish_reason")
        steps.append(rec)
    t_end = time.perf_counter()
    gc_watch.stop()
    ctx.stop_trace()
    compiles_in_window = harness.compiles() - (compiles0 or 0)
    stats_c, stats_d = engine.cache_stats(), engine.decode_stats()
    engine.abort_all("window closed")
    engine.assert_no_leaks()
    engine.close()

    # ---- the arithmetic
    wsteps = [s for s in steps if s["window"]]
    recs = sorted(book.values(), key=lambda b: b["index"])
    min_gaps = int(tr.get("tpot_min_gaps", 16))
    lat = stats.request_latencies(recs, 0.0, t_end - t_w0,
                                  float(tr.get("ttft_share", 0.8)), min_gaps)
    finished = [b for b in recs if b["tokens"] >= b["want"]
                and b["last"] is not None]
    wrong = [b for b in recs if b["bad_token"] or b["tokens"] > b["want"]
             or (b.get("finish_reason") not in (None, "length"))]
    win_tokens = sum(s["chunk_tokens"] + s["new_tokens"] for s in wsteps)
    span_s = (wsteps[-1]["t1"] - 0.0) if wsteps else float("nan")
    q: Dict[str, float] = {"setup_s": t_w0 - ctx.t_process_start}
    if wsteps:
        q["tokens_per_s"] = win_tokens / span_s
    if lat["tpot"]:
        q["tpot_p50_ms"] = 1e3 * stats.percentile(lat["tpot"], 50)
    if lat["tpot_finished"]:
        q["tpot_finished_p50_ms"] = 1e3 * stats.percentile(
            lat["tpot_finished"], 50)
    if lat["ttft"]:
        for p in (50, 90):
            q[f"ttft_p{p}_ms"] = 1e3 * stats.percentile(lat["ttft"], p)
        q["ttft_mean_ms"] = 1e3 * sum(lat["ttft"]) / len(lat["ttft"])
    if backlog:
        attempted = sum(1 for b in recs if b["admitted"] is not None)
    else:
        attempted = sum(1 for b in recs if 0.0 <= b["due"])
    # a request due in the window that got no first token is failed; in a
    # closed backlog most of the queue is *meant* to outlast the window
    failed = len(wrong) + (0 if backlog else lat["missed"])

    for b in recs:
        ctx.note("request " + " ".join(
            f"{k}={b[k] if not isinstance(b[k], float) else round(b[k], 4)}"
            for k in ("index", "due", "put", "admitted", "first", "last",
                      "tokens", "want", "prompt_len")))
    for s in steps:
        ctx.note("step " + " ".join(f"{k}={round(v, 4) if isinstance(v, float) else v}"
                                    for k, v in s.items()))
    waits = [b["admitted"] - b["due"] for b in recs
             if b["admitted"] is not None and b["due"] >= 0.0]
    occ = [s["decode_rows"] for s in wsteps]
    w_len = t_end - t_w0
    no_first = sum(1 for b in recs if b["first"] is None)
    unfinished = sum(1 for b in recs if b["tokens"] < b["want"])
    halves = [[b["first"] - b["due"] for b in recs if b["first"] is not None
               and lo <= b["due"] < hi] for lo, hi in
              ((0.0, 0.4 * w_len), (0.4 * w_len, 0.8 * w_len))]
    ctx.say(f"serve: window {t_end - t_w0:.3f} s, {len(wsteps)} steps "
            f"({len(steps) - len(wsteps)} in the pre-roll); requests "
            f"{len(recs)} put of {len(reqs)}, due in window {attempted}, "
            f"finished {len(finished)}, TTFT samples {len(lat['ttft'])} "
            f"(missed {lat['missed']}), TPOT samples {len(lat['tpot'])} "
            f"(requests with >= {min_gaps} token "
            f"gaps inside the window; {len(lat['tpot_finished'])} finished "
            f"in it, PR 23's sample); "
            f"tokens in window: prefill "
            f"{sum(s['chunk_tokens'] for s in wsteps)} + generated "
            f"{sum(s['new_tokens'] for s in wsteps)} (recomputed after "
            f"preemption, not counted: "
            f"{sum(s['recompute_tokens'] for s in wsteps)}); preempted "
            f"{preempted}, "
            f"first tokens before the spans had the whole prompt "
            f"{span_errors}, wrong-length or bad "
            f"tokens {len(wrong)}; compiles in window {compiles_in_window}")
    if lateness:
        ctx.say(f"serve: generator lateness (put - due) p50 "
                f"{1e3 * stats.percentile(lateness, 50):.1f} ms, max "
                f"{1e3 * max(lateness):.1f} ms; decode rows a step: mean "
                f"{np.mean(occ) if occ else 0:.1f}, max "
                f"{max(occ) if occ else 0}; queue wait p50 "
                f"{1e3 * stats.percentile(waits, 50) if waits else 0:.1f} ms")
    if wsteps:
        # where a stall came from: the longest steps, what each carried, and
        # how much of each the interpreter's collector took
        typical = stats.percentile([s["t1"] - s["t0"] for s in steps], 50)
        longest = sorted(steps, key=lambda s: s["t0"] - s["t1"])[:4]
        ctx.say(f"serve: host: {gc_watch.summary()} in the pre-roll and the "
                f"window; median step {1e3 * typical:.1f} ms; longest steps "
                + "; ".join(
                    f"{1e3 * (s['t1'] - s['t0']):.0f} ms at {s['t0']:.2f} s "
                    f"({s['chunks']} chunks, {s['decode_rows']} rows, gc "
                    f"{1e3 * gc_watch.inside(s['t0'] + t_w0, s['t1'] + t_w0):.0f} ms)"
                    for s in longest))
    if all(halves):
        ctx.say(f"serve: at the window's end {no_first} requests without a "
                f"first token, {unfinished} unfinished; TTFT p50 of requests "
                f"due in the first 40 % {1e3 * stats.percentile(halves[0], 50):.1f}"
                f" ms, in the next 40 % "
                f"{1e3 * stats.percentile(halves[1], 50):.1f} ms (a growing "
                "backlog shows as a rise)")
    ctx.say("serve: quantities " + " ".join(
        f"{k}={v:.4f}" for k, v in sorted(q.items())))
    correct = (chk["max_regret"] < tol and not wrong and span_errors == 0
               and all(b["tokens"] == b["want"] for b in finished))
    return {
        "correct": correct, "attempted": attempted, "failed": failed,
        "end_to_end": {name: q[k] for k, name in tr["reports"].items()},
        "counts": {"requests": len(recs), "finished": len(finished),
                   "steps": len(wsteps), "window_tokens": win_tokens,
                   "compiles_in_window": compiles_in_window,
                   "preempted": preempted},
        # for the readers
        "kind": "serve", "desc": desc, "n_layers": n_layers,
        "engine_config": ecfg, "steps": wsteps, "requests": recs,
        "quantities": q, "queue_waits": waits, "ttft_samples": lat["ttft"],
        "compiles_in_window": compiles_in_window,
        "cache_stats": stats_c, "decode_stats": stats_d,
        "window": (0.0, t_end - t_w0),
    }
