"""Shared-prefix serving bench: prefix-cache and speculative A/B.

Realistic serving traffic shares prompt prefixes (system prompts,
few-shot templates) across thousands of requests.  This bench measures
what the serving optimizations buy on exactly that shape, always as an
A/B on the same weights checked token-for-token identical:

* default — automatic prefix caching: ``enable_prefix_cache`` off vs on;
  prefill tokens admitted vs computed is the FLOP story.
* ``--ab-speculative`` — speculative decoding (n-gram self-speculation):
  ``speculative.mode`` off vs on; **decode tokens per model invocation**
  is the figure of merit, with end-to-end tokens/s as the wall-clock
  check.  This is the *deterministic CPU tier*: pinned seeds, fixed
  model/seq/batch, generations asserted identical across repeats, wall
  time as median-of-k — the emitted JSON carries ``comparable: true``
  plus machine-readable ``decode_model_invocations`` /
  ``accepted_tokens_per_step`` so the speculative claim is
  machine-checked, not eyeballed.
* ``--ab-multistep`` — fused multi-step decode (``decode_horizon``,
  docs/SERVING.md "Multi-step decode"): ``decode_horizon`` 1 vs K on
  identical greedy traffic; **decode host syncs per token** is the
  figure of merit (the fused scan pays ONE ``[B, K]`` pull per horizon
  where the K=1 loop pays one ``[B]`` pull per token).  Deterministic
  CPU tier: the run hard-gates ``identical_generations`` (the fused
  scan is bit-identical to K single steps by contract), a >= 3x
  host-sync reduction per token at the default K=8, and ZERO
  steady-state recompiles in the measured region.
* ``--ab-kv-tier`` — tiered KV cache (host-RAM spill & restore,
  serving/kv_tier.py): several prefix FAMILIES cycle through a device
  prefix cache capped BELOW the distinct-prefix working set, host tier
  off vs on; **prefill tokens computed** at the fixed device pool size
  is the figure of merit (the tier must recover the prefix savings the
  cap destroyed).  Same deterministic CPU tier contract as
  ``--ab-speculative``; the run additionally asserts bit-identical
  generations between the legs, >= 1.5x prefill-token reduction, and
  ZERO steady-state recompiles (the sentinel counter) in the measured
  region.

Prints ONE JSON line.  Knobs (env):
    DSTPU_SBENCH_SIZE    model size (default 160m on TPU, tiny on CPU)
    DSTPU_SBENCH_PREFIX  shared prefix tokens    (default 256; spec: 32)
    DSTPU_SBENCH_SUFFIX  unique suffix tokens    (default 16; spec: 8)
    DSTPU_SBENCH_GEN     new tokens per request  (default 64 TPU / 8 CPU;
                         spec: 96)
    DSTPU_SBENCH_NREQ    total requests          (default 32; spec: 8)
    DSTPU_SBENCH_SLOTS   concurrent decode slots (default 8)
    DSTPU_SBENCH_CHUNK   chunked-prefill tokens  (default 0 = whole)
    DSTPU_SBENCH_K       speculative draft tokens per step (default 8)
    DSTPU_SBENCH_REPEATS median-of-k wall-time repeats     (default 3)
    DSTPU_SBENCH_NVME    1 = --ab-kv-tier caps the host tier and adds
                         the file-backed NVMe third tier under it
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from bench import _device_or_exit, _int_env as _int, _pin_cpu

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stamp_contract_hash(result: dict) -> dict:
    """Provenance: tie the bench artifact to the exact program contracts
    (tests/contracts/*.json) it ran under — see docs/STATIC_ANALYSIS.md."""
    from deepspeed_tpu.analysis.contracts import contract_set_hash

    result["contract_set_hash"] = contract_set_hash(_REPO)
    return result


def _capture_serving_timeline(eng, prompt, max_new_tokens: int = 2):
    """Force a step-time attribution capture on ONE short generate
    (OUTSIDE any timed window) and return the record, or None.  Only the
    first engine step of the generate is profiled (force_next arms a
    single capture)."""
    try:
        from deepspeed_tpu.inference.v2.engine_v2 import RaggedRequest

        eng.force_timeline_capture()
        eng.generate_all([RaggedRequest(prompt_ids=list(prompt),
                                        max_new_tokens=max_new_tokens)])
        return eng.timeline_record()
    except Exception:
        return None  # attribution must never sink a bench


def _observability_sections(timeline_rec, goodput_ledger,
                            warmup_s: float, measured_s: float,
                            measured_steps: int) -> dict:
    """``timeline`` + ``goodput`` sections for the bench JSON
    (docs/OBSERVABILITY.md "Step-time attribution & goodput").  The
    timeline record stamps ``measured: false`` honestly on CPU; the
    goodput ledger (created at leg start so its lifetime covers the
    phases) books warmup/compile as badput and the timed window as
    productive steps."""
    sections = {}
    if timeline_rec is not None:
        sections["timeline"] = {
            "measured": timeline_rec["measured"],
            "wall_seconds": round(timeline_rec["wall_seconds"], 6),
            "categories": {k: round(v, 6)
                           for k, v in timeline_rec["categories"].items()},
            "exposed_collective_seconds":
                timeline_rec["exposed_collective_seconds"],
            "overlapped_collective_seconds":
                timeline_rec["overlapped_collective_seconds"],
        }
    if goodput_ledger is not None:
        try:
            goodput_ledger.observe_phase("compile", max(0.0, warmup_s))
            n = max(1, int(measured_steps))
            for _ in range(n):
                goodput_ledger.observe_step(measured_s / n)
            sections["goodput"] = goodput_ledger.summary()
        # dstpu-lint: allow[swallow] observability sections are a bench
        # annex; a broken ledger must not sink the benchmark numbers
        except Exception:
            pass
    return sections


def _reqtrace_annex(model, params, page: int) -> dict:
    """``reqtrace`` section for the bench JSON: a short fleet-routed
    wave on a FRESH request-trace ledger (docs/OBSERVABILITY.md
    "Request tracing") — writes the merged multi-replica trace artifact
    (``DSTPU_SBENCH_TRACE_OUT``, default ./bench_serving_trace.json)
    and reports per-phase ledger medians.  Runs OUTSIDE every timed
    window, on the bench's own model and weights."""
    try:
        import statistics

        from deepspeed_tpu.inference.v2 import (RaggedInferenceConfig,
                                                RaggedRequest)
        from deepspeed_tpu.serving import ServingConfig, build_fleet
        from deepspeed_tpu.telemetry.reqtrace import (ReqTraceLedger,
                                                      set_reqtrace_ledger,
                                                      write_merged_trace)

        led = ReqTraceLedger()
        set_reqtrace_ledger(led)
        fleet = build_fleet(
            model, ServingConfig(enabled=True, prefill_replicas=1,
                                 decode_replicas=1, disaggregated=True,
                                 prefill_chunk=page),
            engine_config=RaggedInferenceConfig(
                page_size=page, num_pages=64, max_seqs=4,
                max_pages_per_seq=12, enable_prefix_cache=True),
            params=params)
        rng = np.random.RandomState(2)
        vocab = model.config.vocab_size
        prefix = rng.randint(1, vocab, 2 * page).tolist()
        uids = [fleet.submit(RaggedRequest(
            prompt_ids=prefix + rng.randint(1, vocab, 3 + i).tolist(),
            max_new_tokens=4)) for i in range(3)]
        for _ in range(400):
            if not fleet.has_work():
                break
            fleet.step()
        out_path = os.path.abspath(os.environ.get(
            "DSTPU_SBENCH_TRACE_OUT", "bench_serving_trace.json"))
        write_merged_trace(out_path, ledger=led)
        per_phase = {}
        for u in uids:
            tr = led.lookup(fleet.request_state(u)["trace_id"])
            if tr is None:
                continue
            for p, s in tr.phase_seconds().items():
                per_phase.setdefault(p, []).append(s)
        medians = {p: round(statistics.median(v), 6)
                   for p, v in sorted(per_phase.items())}
        return {"reqtrace": {"merged_trace_path": out_path,
                             "phase_medians_s": medians}}
    except Exception:
        return {}  # tracing must never sink the benchmark numbers


def _new_goodput_ledger():
    """Fresh private-registry ledger, or None when telemetry is broken."""
    try:
        from deepspeed_tpu.telemetry.goodput import GoodputLedger
        from deepspeed_tpu.telemetry.registry import MetricsRegistry

        return GoodputLedger(registry=MetricsRegistry())
    except Exception:
        return None


def main() -> None:
    import jax

    from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                      RaggedInferenceConfig,
                                                      RaggedRequest)
    from deepspeed_tpu.models.llama import llama_model

    on_tpu = jax.default_backend() != "cpu"
    size = os.environ.get("DSTPU_SBENCH_SIZE", "160m" if on_tpu else "tiny")
    n_prefix = _int("DSTPU_SBENCH_PREFIX", 256)
    n_suffix = _int("DSTPU_SBENCH_SUFFIX", 16)
    gen = _int("DSTPU_SBENCH_GEN", 64 if on_tpu else 8)
    nreq = _int("DSTPU_SBENCH_NREQ", 32)
    slots = _int("DSTPU_SBENCH_SLOTS", 8)
    chunk = _int("DSTPU_SBENCH_CHUNK", 0)

    page = 16
    seq_len = n_prefix + n_suffix + gen
    pages_per_seq = -(-seq_len // page) + 1
    model = llama_model(size, max_seq_len=seq_len + page)
    params = model.init_params(jax.random.PRNGKey(0))

    rng = np.random.RandomState(0)
    vocab = model.config.vocab_size
    prefix = rng.randint(1, vocab, n_prefix).tolist()
    requests = [prefix + rng.randint(1, vocab, n_suffix).tolist()
                for _ in range(nreq)]
    # warmup workload: DIFFERENT shared prefix, same shapes — compiles the
    # whole-prompt, suffix-chunk, and decode programs without seeding the
    # measured cache state with the real prefix
    warm_prefix = rng.randint(1, vocab, n_prefix).tolist()
    warm = [warm_prefix + rng.randint(1, vocab, n_suffix).tolist()
            for _ in range(2)]

    def run(cache: bool):
        eng = InferenceEngineV2(model, RaggedInferenceConfig(
            page_size=page, max_pages_per_seq=pages_per_seq,
            num_pages=pages_per_seq * slots + 2 * pages_per_seq,
            max_seqs=slots, prefill_chunk=chunk,
            enable_prefix_cache=cache), params=params)
        # sequentially, so the second warm request HITS the warm prefix
        # and compiles the suffix-only prefill program — batching them
        # would admit both before either registered its pages
        tw0 = time.perf_counter()
        for p in warm:
            eng.generate_all([RaggedRequest(prompt_ids=p, max_new_tokens=2)])
        warm_dt = time.perf_counter() - tw0
        eng.reset_cache_stats()
        t0 = time.perf_counter()
        got = eng.generate_all([RaggedRequest(prompt_ids=p,
                                              max_new_tokens=gen)
                                for p in requests])
        dt = time.perf_counter() - t0
        toks = [got[u] for u in sorted(got)]
        assert sum(len(t) for t in toks) == nreq * gen
        st = eng.cache_stats()  # read BEFORE the capture generate below
        tl = _capture_serving_timeline(eng, warm[0]) if cache else None
        return toks, dt, st, warm_dt, tl

    gp = _new_goodput_ledger()  # lifetime covers both legs below
    toks_off, dt_off, st_off, warm_off, _ = run(False)
    toks_on, dt_on, st_on, warm_on, tl_rec = run(True)
    identical = toks_off == toks_on
    mismatched = sum(1 for a, b in zip(toks_off, toks_on) if a != b)

    out_tokens = nreq * gen
    reduction = (st_off["prefill_computed_tokens"]
                 / max(st_on["prefill_computed_tokens"], 1))
    dev = jax.devices()[0]
    from deepspeed_tpu.accelerator import get_accelerator

    # peak HBM alongside tokens/s: process-aggregate accelerator stats
    # (on CPU fallback this is host RSS — still the capacity signal)
    mem_stats = get_accelerator().aggregate_memory_stats()
    result = {
        "metric": f"llama-{size} shared-prefix serving tok/s with prefix "
                  f"cache (prefix={n_prefix}, suffix={n_suffix}, gen={gen}, "
                  f"nreq={nreq}, slots={slots}, chunk={chunk})",
        "value": round(out_tokens / dt_on, 1),
        "unit": "tokens/s",
        "tokens_per_s": {"cache_off": round(out_tokens / dt_off, 1),
                         "cache_on": round(out_tokens / dt_on, 1)},
        "speedup": round(dt_off / dt_on, 2),
        "prefill_tokens": {
            "admitted": int(st_on["prefill_admitted_tokens"]),
            "computed_cache_off": int(st_off["prefill_computed_tokens"]),
            "computed_cache_on": int(st_on["prefill_computed_tokens"])},
        "prefill_reduction": round(reduction, 2),
        "prefix_hit_rate": round(st_on["prefix_hit_rate"], 3),
        "cache": {"hits": int(st_on["cache_hits"]),
                  "misses": int(st_on["cache_misses"]),
                  "evictions": int(st_on["cache_evictions"])},
        "identical_generations": identical,
        "mismatched_requests": mismatched,
        "peak_hbm_bytes": int(mem_stats.get("peak_bytes_in_use", 0)),
        "hbm_bytes_in_use": int(mem_stats.get("bytes_in_use", 0)),
        "backend": jax.default_backend(),
        "device_kind": str(getattr(dev, "device_kind", "unknown")),
    }
    result.update(_observability_sections(
        tl_rec, gp, warm_off + warm_on, dt_off + dt_on, measured_steps=2))
    result.update(_reqtrace_annex(model, params, page))
    print(json.dumps(_stamp_contract_hash(result)))
    # hard identity gate on CPU only: XLA-CPU is deterministic across the
    # two paths, while kernel backends may flip a near-tie greedy pick at
    # ULP level (docs/SERVING.md) — there the mismatch COUNT is the signal
    if not identical and jax.default_backend() == "cpu":
        sys.exit(1)


def main_speculative() -> None:
    """Speculative-decoding A/B on the shared-prefix workload
    (deterministic CPU tier — see module docstring)."""
    import statistics

    import jax

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig,
                                            RaggedRequest, SpeculativeConfig)
    from deepspeed_tpu.models.llama import llama_model

    on_tpu = jax.default_backend() != "cpu"
    size = os.environ.get("DSTPU_SBENCH_SIZE", "160m" if on_tpu else "tiny")
    n_prefix = _int("DSTPU_SBENCH_PREFIX", 32)
    n_suffix = _int("DSTPU_SBENCH_SUFFIX", 8)
    gen = _int("DSTPU_SBENCH_GEN", 96)
    nreq = _int("DSTPU_SBENCH_NREQ", 8)
    slots = _int("DSTPU_SBENCH_SLOTS", 8)
    k = _int("DSTPU_SBENCH_K", 8)
    repeats = max(1, _int("DSTPU_SBENCH_REPEATS", 3))

    page = 16
    seq_len = n_prefix + n_suffix + gen
    pages_per_seq = -(-seq_len // page) + 1
    model = llama_model(size, max_seq_len=seq_len + page)
    params = model.init_params(jax.random.PRNGKey(0))  # pinned seed

    rng = np.random.RandomState(0)  # pinned workload seed
    vocab = model.config.vocab_size
    prefix = rng.randint(1, vocab, n_prefix).tolist()
    requests = [prefix + rng.randint(1, vocab, n_suffix).tolist()
                for _ in range(nreq)]
    warm_prefix = rng.randint(1, vocab, n_prefix).tolist()
    warm = [warm_prefix + rng.randint(1, vocab, n_suffix).tolist()
            for _ in range(2)]

    class _EchoProposer:
        def propose(self, tokens, k_):
            return [int(tokens[-1])] * k_

    def run(spec: bool):
        """One leg: fresh engine per repeat (no cache/jit state leaks
        between repeats), warmup excluded from timing, token streams
        asserted identical ACROSS repeats (the determinism proof), wall
        time reported as the median."""
        toks_ref, stats, times = None, None, []
        warm_s, tl = 0.0, None
        for _ in range(repeats):
            eng = InferenceEngineV2(model, RaggedInferenceConfig(
                dtype="fp32" if not on_tpu else "bf16",
                page_size=page, max_pages_per_seq=pages_per_seq,
                num_pages=pages_per_seq * slots + 2 * pages_per_seq,
                max_seqs=slots, enable_prefix_cache=True,
                speculative=SpeculativeConfig(
                    mode="ngram" if spec else "off", k=k)), params=params)
            tw0 = time.perf_counter()
            for p in warm:
                eng.generate_all([RaggedRequest(prompt_ids=p,
                                                max_new_tokens=4)])
            if spec:
                # a speculative engine runs TWO decode-phase programs —
                # verify on drafting rounds, plain decode on all-empty
                # rounds — and the 4-token warmup requests draft (or
                # don't) at the whim of the tiny model, so force one
                # request through EACH program (lossless for any
                # proposer) to keep both compiles out of the timed region
                prop = eng._proposer
                eng._proposer = None  # plain decode
                eng.generate_all([RaggedRequest(prompt_ids=warm[0],
                                                max_new_tokens=4)])
                eng._proposer = _EchoProposer()  # always-drafting: verify
                eng.generate_all([RaggedRequest(prompt_ids=warm[1],
                                                max_new_tokens=4)])
                eng._proposer = prop
            warm_s += time.perf_counter() - tw0
            eng.reset_cache_stats()
            t0 = time.perf_counter()
            got = eng.generate_all([RaggedRequest(prompt_ids=p,
                                                  max_new_tokens=gen)
                                    for p in requests])
            times.append(time.perf_counter() - t0)
            toks = [got[u] for u in sorted(got)]
            assert sum(len(t) for t in toks) == nreq * gen
            if toks_ref is None:
                toks_ref, stats = toks, eng.decode_stats()
                # stats are read: the capture generate below can no
                # longer pollute the leg's invocation counts
                tl = _capture_serving_timeline(eng, warm[0])
            else:
                assert toks == toks_ref, \
                    "non-deterministic generations across repeats"
            eng.assert_no_leaks()
        return toks_ref, statistics.median(times), stats, warm_s, tl

    gp = _new_goodput_ledger()  # lifetime covers both legs below
    toks_off, dt_off, st_off, warm_off, _ = run(False)
    toks_on, dt_on, st_on, warm_on, tl_rec = run(True)
    identical = toks_off == toks_on
    mismatched = sum(1 for a, b in zip(toks_off, toks_on) if a != b)

    out_tokens = nreq * gen
    inv_off = int(st_off["decode_model_invocations"])
    inv_on = int(st_on["decode_model_invocations"])
    tpi_off = st_off["decode_tokens_per_invocation"]
    tpi_on = st_on["decode_tokens_per_invocation"]
    dev = jax.devices()[0]
    result = {
        "metric": f"llama-{size} shared-prefix speculative decoding A/B "
                  f"(prefix={n_prefix}, suffix={n_suffix}, gen={gen}, "
                  f"nreq={nreq}, slots={slots}, k={k}, "
                  f"median_of={repeats})",
        "value": round(tpi_on / max(tpi_off, 1e-9), 2),
        "unit": "x decode tokens per model invocation",
        # deterministic CPU tier contract: pinned seeds, fixed
        # model/seq/batch, per-leg determinism asserted above,
        # median-of-k wall times — the numbers below are comparable
        # run-to-run on the same backend
        "comparable": True,
        "tier": ("tpu" if on_tpu else "cpu-deterministic"),
        "tokens_per_s": {"spec_off": round(out_tokens / dt_off, 1),
                         "spec_on": round(out_tokens / dt_on, 1)},
        "speedup": round(dt_off / dt_on, 2),
        "decode_model_invocations": {"spec_off": inv_off,
                                     "spec_on": inv_on},
        "decode_tokens_per_invocation": {"spec_off": round(tpi_off, 2),
                                         "spec_on": round(tpi_on, 2)},
        "invocation_reduction": round(inv_off / max(inv_on, 1), 2),
        # decode tokens the spec engine banked per verify/decode call,
        # normalized per sequence: the accepted-draft + bonus average
        "accepted_tokens_per_step": round(
            st_on["decode_tokens"] / max(inv_on, 1) / min(slots, nreq), 2),
        "spec": {
            "proposed_tokens": int(st_on["spec_proposed_tokens"]),
            "accepted_tokens": int(st_on["spec_accepted_tokens"]),
            "acceptance_rate": round(st_on["spec_acceptance_rate"], 3),
            "verify_calls": int(st_on["spec_verify_calls"]),
            "rollback_pages": int(st_on["spec_rollback_pages"])},
        "identical_generations": identical,
        "mismatched_requests": mismatched,
        "backend": jax.default_backend(),
        "device_kind": str(getattr(dev, "device_kind", "unknown")),
    }
    result.update(_observability_sections(
        tl_rec, gp, warm_off + warm_on,
        (dt_off + dt_on) * repeats, measured_steps=2 * repeats))
    print(json.dumps(_stamp_contract_hash(result)))
    # lossless contract: greedy speculative decoding must be
    # bit-identical to the baseline — hard gate on CPU (XLA-CPU is
    # deterministic; kernel backends may flip ULP-level near-ties)
    if not identical and jax.default_backend() == "cpu":
        sys.exit(1)


def main_multistep() -> None:
    """Fused multi-step decode A/B on the shared-prefix workload
    (deterministic CPU tier — see module docstring): ``decode_horizon``
    1 vs K, same weights, same greedy traffic, ``nreq == slots`` so
    every request is admitted up front and the decode phase dominates.
    """
    import statistics

    import jax

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig,
                                            RaggedRequest)
    from deepspeed_tpu.models.llama import llama_model
    from deepspeed_tpu.telemetry import get_registry

    on_tpu = jax.default_backend() != "cpu"
    size = os.environ.get("DSTPU_SBENCH_SIZE", "160m" if on_tpu else "tiny")
    n_prefix = _int("DSTPU_SBENCH_PREFIX", 32)
    n_suffix = _int("DSTPU_SBENCH_SUFFIX", 8)
    gen = _int("DSTPU_SBENCH_GEN", 64)
    nreq = _int("DSTPU_SBENCH_NREQ", 8)
    slots = _int("DSTPU_SBENCH_SLOTS", 8)
    horizon = _int("DSTPU_SBENCH_HORIZON", 8)
    repeats = max(1, _int("DSTPU_SBENCH_REPEATS", 3))

    page = 16
    seq_len = n_prefix + n_suffix + gen
    pages_per_seq = -(-seq_len // page) + 1
    model = llama_model(size, max_seq_len=seq_len + page)
    params = model.init_params(jax.random.PRNGKey(0))  # pinned seed

    rng = np.random.RandomState(0)  # pinned workload seed
    vocab = model.config.vocab_size
    prefix = rng.randint(1, vocab, n_prefix).tolist()
    requests = [prefix + rng.randint(1, vocab, n_suffix).tolist()
                for _ in range(nreq)]
    warm_prefix = rng.randint(1, vocab, n_prefix).tolist()
    warm = [warm_prefix + rng.randint(1, vocab, n_suffix).tolist()
            for _ in range(2)]

    def steady_recompiles() -> float:
        m = get_registry().get("deepspeed_tpu_steady_recompiles_total")
        return m.total() if m is not None else 0.0

    def run(h: int):
        """One leg: fresh engine per repeat, warmup (full-length so the
        whole horizon halving chain compiles out of the timed region)
        excluded from timing, token streams asserted identical ACROSS
        repeats, wall time as the median."""
        toks_ref, stats, times = None, None, []
        steady_delta, warm_s, tl = 0.0, 0.0, None
        for _ in range(repeats):
            eng = InferenceEngineV2(model, RaggedInferenceConfig(
                dtype="fp32" if not on_tpu else "bf16",
                page_size=page, max_pages_per_seq=pages_per_seq,
                num_pages=pages_per_seq * slots + 2 * pages_per_seq,
                max_seqs=slots, enable_prefix_cache=True,
                decode_horizon=h), params=params)
            # warm sequentially at the FULL generation length: the
            # fused leg's shrink chain (K, K/2, ..., 1) compiles on the
            # tail of the warm streams, not in the measured region
            tw0 = time.perf_counter()
            for p in warm:
                eng.generate_all([RaggedRequest(prompt_ids=p,
                                                max_new_tokens=gen)])
            warm_s += time.perf_counter() - tw0
            eng.reset_cache_stats()
            s0 = steady_recompiles()
            t0 = time.perf_counter()
            got = eng.generate_all([RaggedRequest(prompt_ids=p,
                                                  max_new_tokens=gen)
                                    for p in requests])
            times.append(time.perf_counter() - t0)
            steady_delta = max(steady_delta,
                               steady_recompiles() - s0)
            toks = [got[u] for u in sorted(got)]
            assert sum(len(t) for t in toks) == nreq * gen
            if toks_ref is None:
                toks_ref, stats = toks, eng.decode_stats()
                # stats are read: the capture generate below can no
                # longer pollute the leg's sync counts
                tl = _capture_serving_timeline(eng, warm[0])
            else:
                assert toks == toks_ref, \
                    "non-deterministic generations across repeats"
            eng.assert_no_leaks()
            eng.close()
        return toks_ref, statistics.median(times), stats, steady_delta, \
            warm_s, tl

    gp = _new_goodput_ledger()  # lifetime covers both legs below
    toks_off, dt_off, st_off, steady_off, warm_off, _ = run(1)
    toks_on, dt_on, st_on, steady_on, warm_on, tl_rec = run(horizon)
    identical = toks_off == toks_on
    mismatched = sum(1 for a, b in zip(toks_off, toks_on) if a != b)

    out_tokens = nreq * gen
    syncs_off = int(st_off["decode_host_syncs"])
    syncs_on = int(st_on["decode_host_syncs"])
    # identical traffic on both legs: syncs-per-token reduction is the
    # plain sync-count ratio
    sync_reduction = syncs_off / max(syncs_on, 1)
    steady = max(steady_off, steady_on)
    dev = jax.devices()[0]
    result = {
        "metric": f"llama-{size} fused multi-step decode A/B "
                  f"(prefix={n_prefix}, suffix={n_suffix}, gen={gen}, "
                  f"nreq={nreq}, slots={slots}, horizon={horizon}, "
                  f"median_of={repeats})",
        "value": round(sync_reduction, 2),
        "unit": "x fewer decode host syncs per token",
        # deterministic CPU tier contract (see --ab-speculative)
        "comparable": True,
        "tier": ("tpu" if on_tpu else "cpu-deterministic"),
        "tokens_per_s": {"horizon_1": round(out_tokens / dt_off, 1),
                         f"horizon_{horizon}": round(out_tokens / dt_on, 1)},
        "speedup": round(dt_off / dt_on, 2),
        "decode_host_syncs": {"horizon_1": syncs_off,
                              f"horizon_{horizon}": syncs_on},
        "decode_tokens_per_host_sync": {
            "horizon_1": round(st_off["decode_tokens_per_host_sync"], 2),
            f"horizon_{horizon}": round(
                st_on["decode_tokens_per_host_sync"], 2)},
        "host_sync_reduction": round(sync_reduction, 2),
        "horizon_shrinks": int(st_on["decode_horizon_shrinks"]),
        "identical_generations": identical,
        "mismatched_requests": mismatched,
        "steady_state_recompiles": int(steady),
        "backend": jax.default_backend(),
        "device_kind": str(getattr(dev, "device_kind", "unknown")),
    }
    result.update(_observability_sections(
        tl_rec, gp, warm_off + warm_on,
        (dt_off + dt_on) * repeats, measured_steps=2 * repeats))
    print(json.dumps(_stamp_contract_hash(result)))
    # hard gates on the deterministic CPU tier: bit-identity (the fused
    # scan's headline contract), the >= 3x host-sync bar at K=8, and
    # zero steady-state recompiles — machine-checked, not eyeballed
    if jax.default_backend() == "cpu" and (
            not identical or sync_reduction < 3.0 or steady > 0):
        sys.exit(1)


def main_kv_tier() -> None:
    """Tiered-KV-cache A/B on a multi-family shared-prefix workload
    (deterministic CPU tier — see module docstring).

    Workload shape: ``families`` distinct shared prefixes, visited
    round-robin in ``rounds`` waves of ``nreq`` unique-suffix requests
    each.  The device prefix cache is capped at ~1.5 families' pages,
    so by the time a family comes around again the LRU has evicted it —
    tier-off recomputes the whole prefix, tier-on restores it from host
    RAM and computes only the suffix."""
    import statistics

    import jax

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig,
                                            RaggedRequest)
    from deepspeed_tpu.models.llama import llama_model
    from deepspeed_tpu.serving.config import KVTierConfig
    from deepspeed_tpu.telemetry import get_registry

    on_tpu = jax.default_backend() != "cpu"
    size = os.environ.get("DSTPU_SBENCH_SIZE", "160m" if on_tpu else "tiny")
    n_prefix = _int("DSTPU_SBENCH_PREFIX", 64)
    n_suffix = _int("DSTPU_SBENCH_SUFFIX", 16)
    gen = _int("DSTPU_SBENCH_GEN", 8)
    n_fam = _int("DSTPU_SBENCH_FAMILIES", 4)
    rounds = max(2, _int("DSTPU_SBENCH_ROUNDS", 3))
    per_fam = _int("DSTPU_SBENCH_NREQ", 2)  # requests per family per round
    slots = _int("DSTPU_SBENCH_SLOTS", 4)
    repeats = max(1, _int("DSTPU_SBENCH_REPEATS", 3))
    # DSTPU_SBENCH_NVME=1: cap the host tier itself (at the device cache
    # capacity, below the spilled working set) and hang the NVMe third
    # tier under it — the same A/B then also proves file demote/promote
    # keeps bit-identity at a bounded host-RAM budget
    nvme = os.environ.get("DSTPU_SBENCH_NVME", "") not in ("", "0")

    page = 16
    seq_len = n_prefix + n_suffix + gen
    pages_per_seq = -(-seq_len // page) + 1
    prefix_pages = n_prefix // page
    # the acceptance geometry: device cache capped BELOW the
    # distinct-prefix working set (n_fam x prefix_pages)
    cache_cap = prefix_pages + max(1, prefix_pages // 2)
    model = llama_model(size, max_seq_len=seq_len + page)
    params = model.init_params(jax.random.PRNGKey(0))  # pinned seed

    rng = np.random.RandomState(0)  # pinned workload seed
    vocab = model.config.vocab_size
    families = [rng.randint(1, vocab, n_prefix).tolist()
                for _ in range(n_fam)]
    suffixes = [[[rng.randint(1, vocab, n_suffix).tolist()
                  for _ in range(per_fam)] for _ in range(n_fam)]
                for _ in range(rounds)]
    # warm-pass suffixes: same LENGTH, different content — replaying
    # round 0 verbatim would take the fully-cached (copy-on-write
    # decode-entry) path and never compile the restore + suffix-only
    # prefill programs the measured rounds run
    warm_sufs = [[rng.randint(1, vocab, n_suffix).tolist()
                  for _ in range(per_fam)] for _ in range(n_fam)]

    def steady_recompiles() -> float:
        m = get_registry().get("deepspeed_tpu_steady_recompiles_total")
        return m.total() if m is not None else 0.0

    def _tier_cfg(tmp_dirs):
        if not nvme:
            return KVTierConfig(enabled=True)
        import tempfile
        mc = model.config
        # one spilled page record: per-layer K+V of
        # [page, n_kv_heads, head_dim] at the leg's dtype width
        page_rec = (mc.n_layers * 2 * page * mc.n_kv_heads
                    * (mc.hidden_size // mc.n_heads)
                    * (2 if on_tpu else 4))
        d = tempfile.mkdtemp(prefix="dstpu_sbench_nvme_")
        tmp_dirs.append(d)
        return KVTierConfig(enabled=True,
                            host_bytes=cache_cap * page_rec,
                            nvme_enabled=True, nvme_dir=d)

    def run(tier: bool):
        """One leg: fresh engine per repeat, warmup (cold fill + one
        warm-restore pass) excluded from timing, token streams asserted
        identical ACROSS repeats, wall time as the median."""
        toks_ref, stats, tstats, times = None, None, None, []
        steady_delta, warm_s, tl = 0.0, 0.0, None
        tmp_dirs = []  # fresh NVMe dir per repeat: no stale-record hits
        for _ in range(repeats):
            eng = InferenceEngineV2(model, RaggedInferenceConfig(
                dtype="fp32" if not on_tpu else "bf16",
                page_size=page, max_pages_per_seq=pages_per_seq,
                num_pages=pages_per_seq * slots + 2 * pages_per_seq,
                max_seqs=slots, enable_prefix_cache=True,
                prefix_cache_pages=cache_cap,
                kv_tier=(_tier_cfg(tmp_dirs) if tier else None)),
                params=params)

            def play(r, sufs=None):
                got_rounds = []
                for f in range(n_fam):
                    got = eng.generate_all(
                        [RaggedRequest(prompt_ids=families[f] + s,
                                       max_new_tokens=gen)
                         for s in (sufs or suffixes[r])[f]])
                    got_rounds.append([got[u] for u in sorted(got)])
                return got_rounds

            tw0 = time.perf_counter()
            all_toks = [play(0)]   # cold fill: compiles + populates host
            # warm pass: fresh suffixes on the now-evicted families
            # compile the restore scatter + suffix-only prefill shapes
            all_toks.append(play(0, sufs=warm_sufs))
            eng.flush_spills()
            warm_s += time.perf_counter() - tw0
            eng.reset_cache_stats()
            s0 = steady_recompiles()
            t0 = time.perf_counter()
            for r in range(1, rounds):
                all_toks.append(play(r))
            times.append(time.perf_counter() - t0)
            steady_delta = max(steady_delta, steady_recompiles() - s0)
            if toks_ref is None:
                toks_ref = all_toks
                stats, tstats = eng.cache_stats(), eng.tier_stats()
                # stats are read: the capture generate below can no
                # longer pollute the leg's prefill-token counts
                tl = _capture_serving_timeline(
                    eng, families[0] + warm_sufs[0][0])
            else:
                assert all_toks == toks_ref, \
                    "non-deterministic generations across repeats"
            eng.assert_no_leaks()
            eng.close()
        for d in tmp_dirs:
            shutil.rmtree(d, ignore_errors=True)
        return toks_ref, statistics.median(times), stats, tstats, \
            steady_delta, warm_s, tl

    gp = _new_goodput_ledger()  # lifetime covers both legs below
    toks_off, dt_off, st_off, _, steady_off, warm_off, _tl = run(False)
    toks_on, dt_on, st_on, ts_on, steady_on, warm_on, tl_rec = run(True)
    identical = toks_off == toks_on
    flat_off = [t for rnd in toks_off for fam in rnd for t in fam]
    flat_on = [t for rnd in toks_on for fam in rnd for t in fam]
    mismatched = sum(1 for a, b in zip(flat_off, flat_on) if a != b)

    out_tokens = (rounds - 1) * n_fam * per_fam * gen  # measured region
    reduction = (st_off["prefill_computed_tokens"]
                 / max(st_on["prefill_computed_tokens"], 1))
    steady = max(steady_off, steady_on)
    dev = jax.devices()[0]
    result = {
        "metric": f"llama-{size} tiered-KV-cache A/B, device cache capped "
                  f"below working set (families={n_fam}, prefix={n_prefix}, "
                  f"suffix={n_suffix}, gen={gen}, per_fam={per_fam}, "
                  f"rounds={rounds}, cache_cap={cache_cap} pages, "
                  f"working_set={n_fam * prefix_pages} pages, "
                  f"median_of={repeats})",
        "value": round(reduction, 2),
        "unit": "x prefill-token reduction at fixed device pool",
        # deterministic CPU tier contract (see --ab-speculative)
        "comparable": True,
        "tier": ("tpu" if on_tpu else "cpu-deterministic"),
        "tokens_per_s": {"tier_off": round(out_tokens / dt_off, 1),
                         "tier_on": round(out_tokens / dt_on, 1)},
        "speedup": round(dt_off / dt_on, 2),
        "prefill_tokens": {
            "admitted": int(st_on["prefill_admitted_tokens"]),
            "computed_tier_off": int(st_off["prefill_computed_tokens"]),
            "computed_tier_on": int(st_on["prefill_computed_tokens"])},
        "prefill_reduction": round(reduction, 2),
        "prefix_hit_rate": round(st_on["prefix_hit_rate"], 3),
        "kv_tier": {
            "spilled_pages": int(ts_on["spilled_pages"]),
            "restored_pages": int(ts_on["restored_pages"]),
            "host_pages": int(ts_on["host_pages"]),
            "host_bytes": int(ts_on["host_bytes"]),
            "hit_rate": round(ts_on["hit_rate"], 3),
            "corrupt_pages": int(ts_on["corrupt_pages"]),
            "dropped_spills": int(ts_on["dropped_spills"])},
        "nvme": nvme,
        "identical_generations": identical,
        "mismatched_requests": mismatched,
        "steady_state_recompiles": int(steady),
        "backend": jax.default_backend(),
        "device_kind": str(getattr(dev, "device_kind", "unknown")),
    }
    if nvme:
        result["kv_nvme"] = {
            k: (round(v, 3) if k == "nvme_hit_rate" else int(v))
            for k, v in ts_on.items() if k.startswith("nvme_")}
    result.update(_observability_sections(
        tl_rec, gp, warm_off + warm_on,
        (dt_off + dt_on) * repeats,
        measured_steps=2 * repeats * (rounds - 1)))
    print(json.dumps(_stamp_contract_hash(result)))
    # hard gates on the deterministic CPU tier: bit-identity, the
    # >= 1.5x acceptance bar, and zero steady-state recompiles — the
    # tier's claims are machine-checked, not eyeballed.  The NVMe arm
    # additionally requires real file demote/promote traffic with zero
    # corrupt records
    nvme_ok = (not nvme) or (
        ts_on.get("nvme_spilled_pages", 0) > 0
        and ts_on.get("nvme_restored_pages", 0) > 0
        and ts_on.get("nvme_corrupt_pages", 0) == 0)
    if jax.default_backend() == "cpu" and (
            not identical or reduction < 1.5 or steady > 0
            or not nvme_ok):
        sys.exit(1)


if __name__ == "__main__":
    # in-process on the platform JAX selects; a CPU nobody asked for is
    # a non-zero exit, not a fallback (bench._device_or_exit)
    if "--cpu" in sys.argv:
        _pin_cpu()
    _device_or_exit(allow_cpu="--cpu" in sys.argv)
    if "--ab-speculative" in sys.argv:
        main_speculative()
    elif "--ab-kv-tier" in sys.argv:
        main_kv_tier()
    elif "--ab-multistep" in sys.argv:
        main_multistep()
    else:
        main()
