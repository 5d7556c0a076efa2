"""The serve step measured from inside (`inference/v2/engine_v2.py`):
`serve_step` and its phases as spans that carry the step they belong to,
`dispatch` round every program call and `device_wait` round every pull from
the device, the serving stall watchdog
that names the phase a stalled step lost its time in, and the span ring's
`span()` yielding the attributes it will record.  Tiny llama on the CPU:
counts and structure only, no time means anything here.
"""

import logging
import os
import time

import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceConfig,
                                                  RaggedRequest)
from deepspeed_tpu.models.llama import llama_model
from deepspeed_tpu.telemetry import (MetricsRegistry, SpanRecorder,
                                     get_registry, get_span_recorder,
                                     set_registry, set_span_recorder)
from deepspeed_tpu.telemetry.watchdog import StallWatchdog

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: spans of a step that sit inside `serve_step` (a `request` span crosses
#: steps; `xla_compile` is recorded by the compile listener)
STEP_CATS = ("serve", "phase")
DECODE_PHASES = ("decode", "multi_decode", "spec_verify")

ENGINES = {
    "chunked": dict(prefill_chunk=8),
    "whole_prompt": dict(),
    "horizon4": dict(prefill_chunk=8, decode_horizon=4),
}


@pytest.fixture
def ring():
    old = get_span_recorder()
    rec = SpanRecorder(ring_size=4096)
    set_span_recorder(rec)
    yield rec
    set_span_recorder(old)


@pytest.fixture
def registry():
    old = get_registry()
    reg = MetricsRegistry()
    set_registry(reg)
    yield reg
    set_registry(old)


def _engine(**over):
    cfg = dict(dtype="fp32", page_size=8, num_pages=32, max_seqs=4,
               max_pages_per_seq=8)
    cfg.update(over)
    model = llama_model("tiny", max_seq_len=64)
    return model, InferenceEngineV2(model, RaggedInferenceConfig(**cfg),
                                    seed=0)


def _prompts(model, lengths=(9, 20, 5)):
    rng = np.random.RandomState(0)
    return [rng.randint(1, model.config.vocab_size, n).tolist()
            for n in lengths]


def _run(eng, ring, prompts, new_tokens=6):
    """Every step of a short run: (step id, what step() returned, the spans
    and events recorded during it, the queue's length after it)."""
    for p in prompts:
        eng.put(RaggedRequest(prompt_ids=p, max_new_tokens=new_tokens))
    steps = []
    while eng.has_work():
        ring.clear()
        out = eng.step()
        steps.append((eng._step_id, out, ring.spans(), len(eng._queue)))
    return steps


@pytest.fixture(params=sorted(ENGINES))
def run(request, ring, registry):
    model, eng = _engine(**ENGINES[request.param])
    steps = _run(eng, ring, _prompts(model))
    yield request.param, eng, steps
    eng.close()


# ------------------------------------------------------------ the span ring
def test_span_yields_the_attributes_it_records():
    rec = SpanRecorder(ring_size=16)
    with rec.span("serve_step", cat="serve", step=3) as attrs:
        attrs["chunks"] = 2
    (sp,) = rec.spans()
    assert sp.attrs == {"step": 3, "chunks": 2}
    off = SpanRecorder(ring_size=16, enabled=False)
    with off.span("serve_step", step=4) as attrs:
        attrs["chunks"] = 1  # a caller needs no guard with the ring off
    assert off.spans() == []


# ---------------------------------------------------- one step, from inside
def test_every_span_of_a_step_carries_its_step(run):
    _name, _eng, steps = run
    assert [s[0] for s in steps] == \
        list(range(steps[0][0], steps[0][0] + len(steps)))
    seen = set()
    for sid, _out, spans, _q in steps:
        inside = [sp for sp in spans
                  if sp.cat in STEP_CATS and sp.name != "request"]
        assert inside and all(sp.attrs["step"] == sid for sp in inside), \
            [(sp.name, sp.attrs) for sp in inside]
        seen |= {sp.name for sp in inside}
    assert {"serve_step", "step_admit", "prefill", "dispatch", "device_wait",
            "step_emit", "admit"} <= seen
    assert seen & set(DECODE_PHASES)


def test_serve_step_holds_its_children_and_counts_what_step_returned(run):
    _name, _eng, steps = run
    first_tokens = set()
    for _sid, out, spans, queue_len in steps:
        (top,) = [sp for sp in spans if sp.name == "serve_step"]
        assert top.cat == "serve"
        lo, hi = top.ts_us, top.ts_us + top.dur_us
        # `serve_stall` is the watchdog's verdict on a step that has ended:
        # recorded after `serve_step` closed, and on a loaded machine any
        # of these millisecond steps may be rated one
        kids = [sp for sp in spans if sp.cat in STEP_CATS
                and sp.name not in ("serve_step", "request", "serve_stall")]
        assert all(lo <= sp.ts_us and sp.ts_us + sp.dur_us <= hi
                   for sp in kids)
        # device_wait lies inside the phase that dispatched what it awaits,
        # after that phase's one dispatch
        phases = [p for p in kids if p.name in DECODE_PHASES + ("prefill",)]
        for w in (sp for sp in kids if sp.name == "device_wait"):
            assert any(p.ts_us <= w.ts_us and w.ts_us + w.dur_us
                       <= p.ts_us + p.dur_us for p in phases)
        calls = [sp for sp in kids if sp.name == "dispatch"]
        assert all(sp.cat == "serve" for sp in calls)
        assert len(calls) == len(phases)
        for p in phases:
            (d,) = [c for c in calls if p.ts_us <= c.ts_us
                    and c.ts_us + c.dur_us <= p.ts_us + p.dur_us]
            assert all(d.ts_us + d.dur_us <= w.ts_us for w in kids
                       if w.name == "device_wait" and p.ts_us <= w.ts_us
                       and w.ts_us + w.dur_us <= p.ts_us + p.dur_us)
        prefills = [sp for sp in kids if sp.name == "prefill"]
        a = top.attrs
        assert a["chunks"] == len(prefills)
        assert a["prefill_tokens"] == sum(sp.attrs["tokens"]
                                          for sp in prefills)
        assert a["admitted"] == len([sp for sp in kids
                                     if sp.name == "admit"])
        assert a["preempted"] == len([sp for sp in kids
                                      if sp.name == "preempt"])
        assert a["queue_len"] == queue_len
        # rows decoded = requests that got tokens from a decode program:
        # a request's first token comes from its last prefill call
        firsts = {sp.attrs["uid"] for sp in kids if sp.name == "device_wait"
                  and sp.attrs["what"] == "first_token"}
        assert not firsts & first_tokens
        first_tokens |= firsts
        decoded = [u for u, o in out.items()
                   if len(o["tokens"]) > (1 if u in firsts else 0)]
        assert a["decode_rows"] == len(decoded)
    assert len(first_tokens) == 3


@pytest.mark.parametrize("horizon", [1, 4])
def test_serve_step_counts_the_blocks_the_paged_kernel_walks(ring, registry,
                                                             horizon):
    """`decode_kv_blocks` is the kernel module's own `n_blocks` summed over
    the rows (and, fused, the iterations) the step decoded: rows of one,
    two and three blocks, one of them crossing a block boundary."""
    from deepspeed_tpu.ops.pallas.paged_attention import (n_blocks,
                                                          pages_per_block)

    cfg = dict(dtype="fp32", page_size=8, num_pages=128, max_seqs=4,
               max_pages_per_seq=72, prefill_chunk=64, decode_horizon=horizon)
    model = llama_model("tiny", max_seq_len=576)
    eng = InferenceEngineV2(model, RaggedInferenceConfig(**cfg), seed=0)
    pool = eng._pools["k"]
    nb = pages_per_block(8, pool.shape[-1], pool.dtype.itemsize)
    assert eng._kv_block_pages == nb and nb * 8 == 256
    lengths = (9, 253, 520)
    prompts = _prompts(model, lengths)
    steps = _run(eng, ring, prompts, new_tokens=7)
    have = dict(enumerate(lengths))  # uids count from 0 in put() order
    total = 0
    for _sid, out, spans, _q in steps:
        (top,) = [sp for sp in spans if sp.name == "serve_step"]
        firsts = {sp.attrs["uid"] for sp in spans if sp.name == "device_wait"
                  and sp.attrs["what"] == "first_token"}
        want = 0
        for uid, o in out.items():
            first = int(uid in firsts)
            # the t-th decoded token attends have + first + t tokens
            want += sum(int(n_blocks(have[uid] + first + t, 8, nb))
                        for t in range(len(o["tokens"]) - first))
            have[uid] += len(o["tokens"])
        assert top.attrs["decode_kv_blocks"] == want
        assert want >= top.attrs["decode_rows"]
        total += want
    # 6 decoded tokens a request over 1 block, 1 then 2 (the row of 253 + 1
    # tokens passes 256 after its third) and 3 blocks
    assert total == 6 * 1 + (3 * 1 + 3 * 2) + 6 * 3
    assert eng.decode_stats()["decode_kv_blocks"] == total
    assert registry.get(
        "deepspeed_tpu_serving_decode_kv_blocks_total").value() == total
    eng.close()


def test_device_wait_once_per_pull_and_once_per_first_token(run):
    _name, _eng, steps = run
    for _sid, out, spans, queue_len in steps:
        waits = [sp for sp in spans if sp.name == "device_wait"]
        assert all(sp.cat == "serve" for sp in waits)
        pulls = [sp for sp in waits if sp.attrs["what"] == "decode_tokens"]
        firsts = [sp for sp in waits if sp.attrs["what"] == "first_token"]
        assert len(pulls) + len(firsts) == len(waits)
        assert len(pulls) == len([sp for sp in spans
                                  if sp.name in DECODE_PHASES])
        # one per first token, naming the request that got it
        last_chunks = [sp for sp in spans if sp.name == "prefill"
                       and any(w.ts_us >= sp.ts_us and w.ts_us + w.dur_us
                               <= sp.ts_us + sp.dur_us for w in firsts)]
        assert sorted(w.attrs["uid"] for w in firsts) == \
            sorted(sp.attrs["uid"] for sp in last_chunks)
        assert all(len(out[w.attrs["uid"]]["tokens"]) >= 1 for w in firsts)


def test_prefill_span_is_what_the_benchmark_counts_prompt_tokens_from(
        ring, registry):
    model, eng = _engine(prefill_chunk=8)
    prompts = _prompts(model, (9, 20))
    steps = _run(eng, ring, prompts, new_tokens=2)
    eng.close()
    spans = [sp for _sid, _o, ss, _q in steps for sp in ss
             if sp.name == "prefill"]
    assert all(sp.cat == "phase" for sp in spans)
    assert all(set(sp.attrs) == {"uid", "start", "tokens", "step"}
               for sp in spans)
    by_uid = {}
    for sp in spans:
        by_uid.setdefault(sp.attrs["uid"], []).append(
            (sp.attrs["start"], sp.attrs["tokens"]))
    # one span per chunk call: chunks of 8 that tile each prompt exactly
    assert sorted(by_uid.values()) == sorted(
        [[(s, min(8, len(p) - s)) for s in range(0, len(p), 8)]
         for p in prompts])


def test_step_phase_histogram_takes_each_phase_once_a_step(ring, registry):
    model, eng = _engine(prefill_chunk=8)
    steps = _run(eng, ring, _prompts(model, (9,)), new_tokens=3)
    eng.close()
    h = registry.get("deepspeed_tpu_serving_step_phase_seconds")
    assert h.count(phase="serve_step") == len(steps)
    assert h.count(phase="step_admit") == len(steps)
    # three steps: a chunk; the last chunk, which samples the first token,
    # and a decode beside it; a decode.  A step's two waits are one reading
    assert len(steps) == 3
    assert h.count(phase="prefill") == 2
    assert h.count(phase="decode") == 2 == h.count(phase="step_emit")
    assert h.count(phase="device_wait") == 2
    assert h.count(phase="dispatch") == 3  # a step's two calls: one reading
    assert h.sum(phase="serve_step") >= h.sum(phase="decode") \
        >= 0.0 < h.sum(phase="device_wait")


# ------------------------------------------------- a stall names its phase
class _SlowPull:
    """What a decode program returns, whose pull to the host takes long."""

    def __init__(self, tokens, seconds):
        self.tokens, self.seconds = tokens, seconds

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.seconds)
        return np.asarray(self.tokens)


def _stall_in_pull(eng, seconds):
    real = eng._decode.run  # what `_dispatch` calls of a program

    def slow(*args):
        tokens, pools = real(*args)
        eng._decode.run = real
        return _SlowPull(tokens, seconds), pools

    eng._decode.run = slow


def _stall_in_admission(eng, seconds):
    real = eng._admit

    def slow():
        time.sleep(seconds)
        eng._admit = real
        return real()

    eng._admit = slow


@pytest.mark.parametrize("where,patch", [("device_wait", _stall_in_pull),
                                         ("step_admit", _stall_in_admission)])
def test_a_stalled_step_names_its_phase(where, patch, ring, registry, caplog):
    model, eng = _engine(prefill_chunk=8)
    eng.put(RaggedRequest(prompt_ids=_prompts(model, (9,))[0],
                          max_new_tokens=12))
    for _ in range(4):  # both programs compiled, decoding
        eng.step()
    # a median no CPU hiccup reaches: only the patched step rates a stall
    eng._watchdog = StallWatchdog(name="serve", registry=registry,
                                  on_stall=eng._on_stall)
    for _ in range(6):
        eng._watchdog.observe(0.05)
    from deepspeed_tpu.utils.logging import logger

    logger.propagate = True  # the package's logger keeps to itself
    try:
        with caplog.at_level(logging.WARNING, logger="DeepSpeedTPU"):
            eng.step()
            assert not [r for r in caplog.records
                        if "serve step" in r.message]
            patch(eng, 0.5)
            ring.clear()
            eng.step()
            stalled = eng._step_id
            eng.step()
    finally:
        logger.propagate = False
    eng.close()
    lines = [r.message for r in caplog.records]
    assert any("stall watchdog [serve]" in m and f"step {stalled}" in m
               for m in lines)
    (line,) = [m for m in lines if m.startswith(f"serve step {stalled}:")]
    # largest self time first: the phase the step lost its time in
    assert line.split("): ", 1)[1].startswith(where + " ")
    assert "0 chunks, 1 rows" in line and "decode self" in line \
        and "dispatch" in line
    (ev,) = [sp for sp in ring.spans() if sp.name == "serve_stall"]
    assert ev.attrs["phase"] == where and ev.attrs["step"] == stalled
    assert ev.attrs[where + "_ms"] >= 500.0 > ev.attrs["decode_self_ms"]
    assert ev.attrs["ms"] >= ev.attrs[where + "_ms"]
    assert registry.get("deepspeed_tpu_stalled_steps_total").value(
        loop="serve") == 1


def test_only_decode_only_steps_that_did_not_compile_are_rated(
        ring, registry):
    """The watchdog is fed `serve_step` of steps that pulled decode tokens
    and carried no chunk: a step with chunks is several programs long, a
    step that only dispatches a chunk returns at once, a step that compiled
    is no measure of a step, an idle step has nothing to rate."""
    model, eng = _engine(prefill_chunk=8)
    eng.step()  # nothing queued
    assert len(eng._watchdog._times) == 0
    eng.put(RaggedRequest(prompt_ids=_prompts(model, (20,))[0],
                          max_new_tokens=6))
    compiled = rated = 0
    while eng.has_work():
        ring.clear()
        n = len(eng._watchdog._times)
        eng.step()
        c = eng._step_counts
        did_compile = any(sp.name == "xla_compile" for sp in ring.spans())
        compiled += did_compile
        want = bool(c["decode_rows"]) and not c["chunks"] and not did_compile
        assert len(eng._watchdog._times) == n + want
        if want:
            assert eng._watchdog._times[-1] == eng._phase_s["serve_step"]
            rated += 1
    eng.close()
    assert compiled >= 2  # the chunk program and the decode program
    assert rated >= 3
    assert registry.get("deepspeed_tpu_stalled_steps_total").value(
        loop="serve") == 0


def test_a_slow_step_that_carries_a_chunk_is_no_stall(ring, registry):
    model, eng = _engine(prefill_chunk=8)
    eng.generate_all([RaggedRequest(prompt_ids=_prompts(model, (20,))[0],
                                    max_new_tokens=3)])  # compiles all
    eng._watchdog = StallWatchdog(name="serve", registry=registry,
                                  on_stall=eng._on_stall)
    for _ in range(6):
        eng._watchdog.observe(0.001)
    eng.put(RaggedRequest(prompt_ids=_prompts(model, (20,))[0],
                          max_new_tokens=3))
    _stall_in_admission(eng, 0.05)
    eng.step()  # 50x the median, and its first chunk
    assert eng._step_counts["chunks"] == 1
    assert len(eng._watchdog._times) == 6
    eng.close()
    assert [sp for sp in ring.spans() if sp.name == "serve_stall"] == []


# ----------------------------------------------------------- the ring off
def test_ring_off_same_tokens_and_the_watchdog_still_fed(ring, registry):
    model, eng = _engine(prefill_chunk=8)
    prompts = _prompts(model)
    want = eng.generate_all([RaggedRequest(prompt_ids=p, max_new_tokens=5)
                             for p in prompts])
    eng.close()
    ring.configure(enabled=False)
    ring.clear()
    _model, off = _engine(prefill_chunk=8)
    got = off.generate_all([RaggedRequest(prompt_ids=p, max_new_tokens=5)
                            for p in prompts])
    assert ring.spans() == []
    assert sorted(got.values()) == sorted(want.values())
    assert off._phase_s["serve_step"] > 0.0
    assert len(off._watchdog._times) > 0
    off.close()


# ------------------------------------------- timeline.py reads with JAX alone
def test_timeline_parses_the_recorded_v5e_trace_without_tensorflow():
    from deepspeed_tpu.telemetry import timeline

    path = os.path.join(REPO, "benchmark", "fixtures", "small_v5e.xplane.pb")
    events, artifact = timeline.parse_xplane(path)
    names = [e["name"] for e in events]
    assert len([n for n in names if n.startswith("dstpu_flash_fwd")]) == 6
    assert not any(n.split(".")[0] in ("while", "conditional", "call")
                   or " = " in n or n.startswith("%") for n in names)
    t0 = min(e["ts"] for e in events)
    wall = max(e["ts"] + e["dur"] for e in events) - t0
    dec = timeline.decompose_events(events, wall)
    cats = dec["categories"]
    assert sum(cats.values()) == pytest.approx(wall, rel=1e-9)
    # (no region table for a trace recorded before there were any: XLA's
    # own operations are unscoped, never guessed at by name)
    assert cats["attention"] > cats["unscoped"] > 0.0
    assert all(e["program"].startswith("jit_step(") and " = " in e["text"]
               for e in events)
    # what trace_reduce reads from the same file, less the hair-width gaps
    # between a while's body operations
    assert dec["device_busy_seconds"] == pytest.approx(1.776449e-4, rel=5e-3)
    assert {ev["ph"] for ev in artifact} == {"M", "X"}
    src = open(timeline.__file__).read()
    assert "tensorflow" not in src


def test_cpu_capture_falls_back_to_the_span_ring_and_says_so(ring):
    from deepspeed_tpu.telemetry.timeline import StepTimeline, capture_thunk

    def work():
        with ring.span("serve_step", cat="serve"):
            time.sleep(0.01)

    _out, rec = capture_thunk(work, step=1, timeline=StepTimeline(
        registry=MetricsRegistry()))
    assert rec["measured"] is False  # no device plane in a CPU trace
    cats = rec["categories"]
    assert cats["host_compute"] >= 0.01  # the ring's spans, not a guess of 0
    assert sum(cats.values()) == pytest.approx(rec["wall_seconds"], abs=1e-6)
