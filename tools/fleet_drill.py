#!/usr/bin/env python
"""Fleet drill: prove routed disaggregated serving is lossless and
bit-identical under replica failure.

``--demo`` runs the whole serving-fleet story on CPU with a tiny fp32
llama (greedy decoding), against a single-engine control on the same
weights:

* **Disaggregation leg** — 1 prefill + 2 decode replicas; requests are
  routed by prefix-cache-affinity hashing, chunk-prefilled on the
  prefill replica, and their KV pages migrate to decode replicas
  (ref-count adoption on import).
* **Kill leg** — one decode replica is hard-killed mid-stream (its
  engine state, including every in-flight KV page, is gone).  The
  router re-dispatches the lost streams; every request must complete
  and every stream must be **bit-identical** to the single-engine
  control.
* **Preemption leg** — a second wave of requests; the surviving decode
  replica gets a PR-5 maintenance notice mid-stream.  The router
  evacuates it (KV migration where possible, re-dispatch otherwise);
  streams again complete bit-identically, with the fleet degraded to
  the prefill replica decoding as a mixed fallback.
* **Overload leg** (fresh SLO fleet) — a burst past the bounded queue:
  low-priority submissions are shed loudly (``RejectedError`` with a
  retry-after hint, counted in ``slo_shed_total``), high-priority ones
  are never shed; a chaos ``PoolSqueeze`` then drives the KV pool over
  the shed threshold and proves the pool-pressure rule too.
* **Deadline leg** — requests with an exhausted ``deadline_s`` budget
  expire at the step boundary with ``finish_reason="deadline"``
  (counted in ``slo_deadline_exceeded_total``) instead of waiting
  forever; undeadlined requests in the same wave run to completion
  bit-identically.
* **Slow-replica leg** — a chaos ``SlowReplica`` drags one decode
  replica's step latency; the circuit breaker trips (sustained MEDIAN
  step latency > k x the same-role fleet median — a lone spike lifts
  only p95 and never trips), the replica is drained of placement, its
  streams finish
  elsewhere **bit-identical** to the control, and after the cooldown
  the breaker recovers through half-open probing on live traffic.
* **Tiered-KV leg** (fresh 1+1 fleet) — the device prefix cache is
  capped BELOW the leg's distinct-prefix working set with the host-RAM
  KV tier on (``serving.kv_tier`` through ``build_fleet``): families
  cycle, cold prefixes spill to host on LRU eviction and restore
  (CRC-verified) when their family returns; streams must be
  **bit-identical** to an UNCAPPED single-engine control, the
  allocator audit must stay green with in-flight spill pins accounted,
  and the host-tier occupancy must surface in replica ``health()``.
* **Tracing leg** (fresh fleet, fresh request-trace ledger) — the
  disaggregated prefill→decode handoff plus a mid-stream replica kill
  must each read as ONE connected trace per request in the merged
  fleet Perfetto artifact (``fleet_trace.json``: prefill, KV transit,
  decode and recompute as distinct slices keyed by the router-minted
  ``trace_id``); every request's phase ledger must sum to its
  end-to-end latency; and the forced TTFT violations (unmeetable
  ``slo_ttft_s``) must carry exemplars resolving to traces present in
  the artifact.
* **NVMe-tier leg** (fresh 1+1 fleet) — the host-RAM tier itself is
  budgeted at three page records with the NVMe third tier on: cold
  families demote host -> ``.kvpage`` file on LRU pressure and promote
  back (CRC re-verified) when they return; streams must be
  **bit-identical** to the uncapped single-engine control with zero
  corrupt records and no leaked pages.
* **Cross-process leg** — a REAL child-process replica is spawned
  behind the socket transport; the autoscaler grows it into the fleet
  under queue pressure, live decode rebalancing migrates running
  streams across the process boundary, and the scale-down path retires
  it mid-run via drain/evacuation (its streams come BACK over the
  socket).  Every stream must complete **bit-identical** to the
  single-engine control, the allocator audit must pass on BOTH sides
  of the socket (the remote audited over the wire), and the child must
  exit 0.
* **Metric-name lint** — the run registers the
  ``deepspeed_tpu_serving_fleet_*`` + ``deepspeed_tpu_serving_slo_*``
  + ``deepspeed_tpu_serving_kv_tier_*`` +
  ``deepspeed_tpu_serving_kv_nvme_*`` +
  ``deepspeed_tpu_serving_transport_*`` +
  ``deepspeed_tpu_serving_autoscale_*`` families, then
  ``tools/check_metric_names.py`` must pass over the tree and see
  them.

Writes ``fleet_drill.json`` under ``--out``, prints ONE JSON summary
line, and exits non-zero when any check fails — the acceptance gate for
the serving-fleet subsystem.

Knobs: ``--out DIR`` (default ./fleet_drill_demo), ``--requests N``
(default 6), ``--new-tokens N`` (default 10), ``--seed S`` (default 7:
threads through prompt generation AND every chaos injector, so any
failure replays from the seed logged in the summary).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_DIR = os.path.dirname(_TOOLS_DIR)
sys.path.insert(0, _REPO_DIR)
if _TOOLS_DIR not in sys.path:  # in-process entrypoint call (tests)
    sys.path.insert(1, _TOOLS_DIR)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

PAGE_SIZE = 8
PREFIX_TOKENS = 16  # two full pages shared per request family


def _check(checks, name, ok, detail=""):
    checks.append({"check": name, "ok": bool(ok), "detail": str(detail)})
    print(f"  [{'ok' if ok else 'FAIL'}] {name}"
          + (f" — {detail}" if detail else ""))
    return ok


def _build(n_requests: int, new_tokens: int, seed: int = 7):
    import jax
    import numpy as np

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig,
                                            RaggedRequest)
    from deepspeed_tpu.models.llama import llama_model
    from deepspeed_tpu.serving import ServingConfig, build_fleet

    model = llama_model("tiny", max_seq_len=128)
    params = model.init_params(jax.random.PRNGKey(0))
    base = RaggedInferenceConfig(dtype="fp32", page_size=PAGE_SIZE,
                                 num_pages=64, max_seqs=4,
                                 max_pages_per_seq=12,
                                 enable_prefix_cache=True)
    serving = ServingConfig(enabled=True, prefill_replicas=1,
                            decode_replicas=2, disaggregated=True,
                            affinity_pages=2, prefill_chunk=PAGE_SIZE)
    fleet = build_fleet(model, serving, engine_config=base, params=params)

    rng = np.random.RandomState(seed)
    vocab = model.config.vocab_size
    prefix = list(rng.randint(0, vocab, PREFIX_TOKENS))

    def make_requests(n, salt, **kw):
        rq = np.random.RandomState(seed * 100 + salt)
        return [RaggedRequest(
            prompt_ids=prefix + list(rq.randint(0, vocab, 3 + i)),
            max_new_tokens=new_tokens, **kw) for i in range(n)]

    def control_run(requests):
        """Fresh single engine on the same weights; greedy, so the
        fleet must reproduce these streams token-for-token."""
        eng = InferenceEngineV2(model, base, params=params)
        got = eng.generate_all([RaggedRequest(
            prompt_ids=list(r.prompt_ids),
            max_new_tokens=r.max_new_tokens) for r in requests])
        eng.close()
        return [got[i] for i in range(len(requests))]

    def build_slo_fleet():
        """Fresh 1-prefill + 2-decode fleet with the overload knobs on:
        bounded queue, pool-pressure shedding, tight breaker windows.
        Prefix cache off so a PoolSqueeze can drive occupancy to 1.0
        (no LRU-parked pages keeping ``free_pages`` high)."""
        slo_base = RaggedInferenceConfig(dtype="fp32", page_size=PAGE_SIZE,
                                         num_pages=48, max_seqs=4,
                                         max_pages_per_seq=12)
        slo_serving = ServingConfig(
            enabled=True, prefill_replicas=1, decode_replicas=2,
            disaggregated=True, affinity_pages=2, prefill_chunk=PAGE_SIZE,
            max_queue_depth=4, shed_occupancy=0.85, protect_priority=0,
            breaker_latency_factor=3.0, breaker_window=16,
            breaker_min_samples=4, breaker_consec_errors=3,
            breaker_cooldown_pumps=6, breaker_probe_steps=3,
            breaker_min_latency_s=0.0005)
        fl = build_fleet(model, slo_serving, engine_config=slo_base,
                         params=params)
        ctl = InferenceEngineV2(model, slo_base, params=params)

        def slo_control(requests):
            # one long-lived control engine: generate_all returns only
            # this call's uids (auto-increment => sorted = submit order)
            got = ctl.generate_all([RaggedRequest(
                prompt_ids=list(r.prompt_ids),
                max_new_tokens=r.max_new_tokens) for r in requests])
            return [got[u] for u in sorted(got)]

        return fl, slo_control

    def build_tier_fleet():
        """Fresh 1-prefill + 1-decode fleet with the device prefix
        cache capped BELOW the tier leg's working set and the host-RAM
        KV tier on — the ``serving.kv_tier`` block flows through
        ``build_fleet`` to every replica.  The control is an UNCAPPED
        single engine (no tier): the tier must make the capped fleet
        reproduce its streams bit-identically."""
        from deepspeed_tpu.serving import KVTierConfig

        tier_base = RaggedInferenceConfig(
            dtype="fp32", page_size=PAGE_SIZE, num_pages=48, max_seqs=4,
            max_pages_per_seq=12, enable_prefix_cache=True,
            prefix_cache_pages=3)  # 1.5 families of 2 prefix pages
        tier_serving = ServingConfig(
            enabled=True, prefill_replicas=1, decode_replicas=1,
            disaggregated=True, affinity_pages=2, prefill_chunk=PAGE_SIZE,
            kv_tier=KVTierConfig(enabled=True))
        fl = build_fleet(model, tier_serving, engine_config=tier_base,
                         params=params)
        uncapped = RaggedInferenceConfig(
            dtype="fp32", page_size=PAGE_SIZE, num_pages=64, max_seqs=4,
            max_pages_per_seq=12, enable_prefix_cache=True)
        ctl = InferenceEngineV2(model, uncapped, params=params)

        def tier_control(requests):
            got = ctl.generate_all([RaggedRequest(
                prompt_ids=list(r.prompt_ids),
                max_new_tokens=r.max_new_tokens) for r in requests])
            return [got[u] for u in sorted(got)]

        return fl, tier_control

    def make_tier_waves(new_tokens, n_fams=3, per_fam=2, rounds=2,
                        salt=12):
        """Distinct-prefix FAMILY waves for the tier leg: each wave is
        one family's burst; families cycle over ``rounds`` so the
        capped device cache must evict (spill) a family before it comes
        around again (restore)."""
        rq = np.random.RandomState(seed * 100 + salt)
        fams = [list(rq.randint(0, vocab, PREFIX_TOKENS))
                for _ in range(n_fams)]
        waves = []
        for _r in range(rounds):
            for f in fams:
                waves.append([RaggedRequest(
                    prompt_ids=f + list(rq.randint(0, vocab, 3 + i)),
                    max_new_tokens=new_tokens) for i in range(per_fam)])
        return waves

    def build_mp_fleet():
        """One-replica MIXED fleet with live decode rebalancing on,
        plus the spawn spec for a cross-process peer: the child
        re-derives the SAME weights from ``init_params(PRNGKey(0))``
        and the same engine config, so a stream decodes bit-identically
        on either side of the socket."""
        from deepspeed_tpu.inference.v2 import InferenceEngineV2 as Eng
        from deepspeed_tpu.serving.replica import EngineReplica
        from deepspeed_tpu.serving.router import FleetRouter

        mp_serving = ServingConfig(
            enabled=True, disaggregated=False, rebalance_enabled=True,
            rebalance_load_gap=1, rebalance_max_per_pump=2)
        local = EngineReplica("local0", Eng(model, base, params=params))
        fl = FleetRouter([local], mp_serving)
        spec = {"model": "tiny", "max_seq_len": 128, "seed": 0,
                "engine_config": base, "platform": "cpu"}
        return fl, spec

    def build_nvme_fleet(nvme_dir):
        """Fresh 1-prefill + 1-decode fleet with BOTH spill tiers
        capped: the device prefix cache below the working set (as the
        tier leg) AND the host-RAM tier budgeted at three page records,
        with the NVMe third tier on under ``nvme_dir`` — cold families
        must demote host -> file and promote back (CRC-verified,
        bit-identical) when they return.  Control stays the UNCAPPED
        single engine."""
        from deepspeed_tpu.serving import KVTierConfig

        mc = model.config
        # one spilled prefix-page record: per-layer K+V blocks of
        # [page_size, n_kv_heads, head_dim] fp32
        page_nb = (mc.n_layers * 2 * PAGE_SIZE * mc.n_kv_heads
                   * (mc.hidden_size // mc.n_heads) * 4)
        nvme_base = RaggedInferenceConfig(
            dtype="fp32", page_size=PAGE_SIZE, num_pages=48, max_seqs=4,
            max_pages_per_seq=12, enable_prefix_cache=True,
            prefix_cache_pages=3)
        nvme_serving = ServingConfig(
            enabled=True, prefill_replicas=1, decode_replicas=1,
            disaggregated=True, affinity_pages=2, prefill_chunk=PAGE_SIZE,
            kv_tier=KVTierConfig(enabled=True,
                                 host_bytes=3 * page_nb + 64,
                                 nvme_enabled=True, nvme_dir=nvme_dir))
        fl = build_fleet(model, nvme_serving, engine_config=nvme_base,
                         params=params)
        uncapped = RaggedInferenceConfig(
            dtype="fp32", page_size=PAGE_SIZE, num_pages=64, max_seqs=4,
            max_pages_per_seq=12, enable_prefix_cache=True)
        ctl = InferenceEngineV2(model, uncapped, params=params)

        def nvme_control(requests):
            got = ctl.generate_all([RaggedRequest(
                prompt_ids=list(r.prompt_ids),
                max_new_tokens=r.max_new_tokens) for r in requests])
            return [got[u] for u in sorted(got)]

        return fl, nvme_control

    def build_trace_fleet():
        """Fresh 1-prefill + 2-decode disaggregated fleet on a FRESH
        request-trace ledger, with an unmeetable TTFT SLO
        (``slo_ttft_s`` = 1µs) so every stream records a violation
        exemplar — the tracing leg proves each exemplar resolves to a
        trace in the merged artifact."""
        from deepspeed_tpu.telemetry.reqtrace import (ReqTraceLedger,
                                                      set_reqtrace_ledger)

        led = ReqTraceLedger()
        set_reqtrace_ledger(led)
        tr_base = RaggedInferenceConfig(
            dtype="fp32", page_size=PAGE_SIZE, num_pages=64, max_seqs=4,
            max_pages_per_seq=12, enable_prefix_cache=True,
            slo_ttft_s=1e-6)
        tr_serving = ServingConfig(
            enabled=True, prefill_replicas=1, decode_replicas=2,
            disaggregated=True, affinity_pages=2, prefill_chunk=PAGE_SIZE)
        return build_fleet(model, tr_serving, engine_config=tr_base,
                           params=params), led

    def build_multistep_fleet():
        """Fresh 1-prefill + 1-decode fleet with the fused multi-step
        decode horizon applied fleet-wide (``serving.decode_horizon``
        flows through ``build_fleet`` to every replica): the decode
        pool pulls K tokens per host round-trip and must reproduce the
        single-engine K=1 control's greedy streams bit-identically."""
        ms_serving = ServingConfig(
            enabled=True, prefill_replicas=1, decode_replicas=1,
            disaggregated=True, affinity_pages=2, prefill_chunk=PAGE_SIZE,
            decode_horizon=8)
        return build_fleet(model, ms_serving, engine_config=base,
                           params=params)

    return (fleet, make_requests, control_run, build_slo_fleet,
            build_tier_fleet, make_tier_waves, build_multistep_fleet,
            build_trace_fleet, build_nvme_fleet, build_mp_fleet)


def run_demo(out: str, n_requests: int, new_tokens: int,
             seed: int = 7) -> int:
    from deepspeed_tpu.telemetry import get_registry

    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    print(f"fleet drill: {n_requests} requests x {new_tokens} tokens, "
          f"1 prefill + 2 decode replicas, seed {seed} -> {out}")
    (fleet, make_requests, control_run, build_slo_fleet,
     build_tier_fleet, make_tier_waves, build_multistep_fleet,
     build_trace_fleet, build_nvme_fleet, build_mp_fleet) = \
        _build(n_requests, new_tokens, seed)
    reg = get_registry()

    def counter(name):
        m = reg.get(name)  # get, not get-or-create: some slo_* metrics
        return m.total() if m is not None else 0.0  # carry labels

    checks = []

    # ---- leg 1: disaggregated serving + mid-stream decode-replica kill
    reqs = make_requests(n_requests, salt=1)
    want = control_run(reqs)
    uids = [fleet.submit(r) for r in reqs]
    mid_stream = False
    for _ in range(200):
        fleet.step()
        states = [fleet.request_state(u) for u in uids]
        on_decode = [s for s in states if (s["replica"] or "").startswith("decode")]
        if on_decode and all(1 <= len(s["emitted"]) < new_tokens
                             for s in states):
            mid_stream = True
            break
    _check(checks, "streams_mid_flight_on_decode_pool", mid_stream,
           f"{len([1 for s in states if s['replica']])} placed")
    hosts = {}
    for u in uids:
        rep = fleet.request_state(u)["replica"] or ""
        if rep.startswith("decode"):
            hosts[rep] = hosts.get(rep, 0) + 1
    victim = max(hosts, key=hosts.get) if hosts else "decode0"
    d0, r0 = counter("deepspeed_tpu_serving_fleet_replica_deaths_total"), \
        counter("deepspeed_tpu_serving_fleet_redispatches_total")
    print(f"  killing {victim} mid-stream "
          f"(hosting {hosts.get(victim, 0)} stream(s))")
    fleet.kill_replica(victim)
    for _ in range(400):
        if not fleet.has_work():
            break
        fleet.step()
    got = [fleet.request_state(u)["emitted"] for u in uids]
    _check(checks, "all_streams_complete_after_kill",
           not fleet.has_work()
           and all(not fleet.request_state(u)["failed"] for u in uids))
    _check(checks, "kill_leg_bit_identical_to_single_engine",
           got == want,
           f"{sum(g == w for g, w in zip(got, want))}/{len(want)} match")
    _check(checks, "replica_death_detected",
           counter("deepspeed_tpu_serving_fleet_replica_deaths_total") == d0 + 1)
    _check(checks, "streams_recovered_via_redispatch",
           counter("deepspeed_tpu_serving_fleet_redispatches_total") > r0,
           f"{counter('deepspeed_tpu_serving_fleet_redispatches_total') - r0} "
           "re-dispatched")
    _check(checks, "kv_migrations_ran",
           counter("deepspeed_tpu_serving_fleet_migrations_total")
           >= n_requests,
           f"{counter('deepspeed_tpu_serving_fleet_migrations_total')} "
           "migrations, "
           f"{counter('deepspeed_tpu_serving_fleet_migrated_pages_total')} "
           "pages")

    # ---- leg 2: preemption notice on the surviving decode replica
    reqs2 = make_requests(max(2, n_requests // 2), salt=2)
    want2 = control_run(reqs2)
    uids2 = [fleet.submit(r) for r in reqs2]
    for _ in range(3):
        fleet.step()
    survivors = [n for n, r in fleet.replicas.items()
                 if r.alive and not r.retired and r.role == "decode"]
    p0 = counter("deepspeed_tpu_serving_fleet_replica_preemptions_total")
    if survivors:
        print(f"  preemption notice -> {survivors[0]}")
        fleet.replicas[survivors[0]].watcher.notify("maintenance-sim")
    for _ in range(400):
        if not fleet.has_work():
            break
        fleet.step()
    got2 = [fleet.request_state(u)["emitted"] for u in uids2]
    _check(checks, "preempted_replica_evacuated",
           bool(survivors)
           and counter("deepspeed_tpu_serving_fleet_replica_preemptions_total")
           == p0 + 1, survivors)
    _check(checks, "preempt_leg_bit_identical_to_single_engine",
           got2 == want2,
           f"{sum(g == w for g, w in zip(got2, want2))}/{len(want2)} match")

    # ---- allocator integrity: after two legs of KV churn (migration,
    # re-dispatch, evacuation) no surviving replica may hold a leaked
    # page or refcount — the BlockAllocator debug audit is exact
    leak_errs = []
    for name, rep in fleet.replicas.items():
        if not rep.alive:
            continue  # a hard-killed replica's state is gone by design
        try:
            rep.engine.assert_no_leaks()
        except AssertionError as e:
            leak_errs.append(f"{name}: {e}")
    _check(checks, "allocator_no_leaks_after_churn", not leak_errs,
           leak_errs[:2] if leak_errs else
           f"{sum(1 for r in fleet.replicas.values() if r.alive)} "
           "replicas audited")

    # ======== SLO legs: fresh fleet with overload knobs on ========
    from deepspeed_tpu.inference.v2 import (PRIORITY_BATCH,
                                            PRIORITY_INTERACTIVE,
                                            RejectedError)
    from deepspeed_tpu.resilience.chaos import PoolSqueeze, SlowReplica

    slo_fleet, slo_control = build_slo_fleet()

    # ---- leg 3: overload -> bounded-queue shedding by priority
    print("  leg 3: overload (bounded queue + pool squeeze)")
    shed0 = counter("deepspeed_tpu_serving_slo_shed_total")
    lows = make_requests(4, salt=3, priority=PRIORITY_BATCH)
    low_uids = [slo_fleet.submit(r) for r in lows]  # fills queue to 4
    shed_lows = 0
    for r in make_requests(2, salt=4, priority=PRIORITY_BATCH):
        try:
            slo_fleet.submit(r)
        except RejectedError as e:
            shed_lows += 1
            _check(checks, "shed_carries_retry_hint_and_reason",
                   e.retry_after_s > 0 and e.reason == "queue_full",
                   f"reason={e.reason} retry_after={e.retry_after_s}")
    highs = make_requests(2, salt=5, priority=PRIORITY_INTERACTIVE)
    high_shed = 0
    high_uids = []
    for r in highs:
        try:
            high_uids.append(slo_fleet.submit(r))
        except RejectedError:
            high_shed += 1
    _check(checks, "overload_sheds_only_low_priority",
           shed_lows == 2 and high_shed == 0,
           f"{shed_lows} low shed, {high_shed} high shed")
    want_slo = slo_control(lows + highs)
    for _ in range(400):
        if not slo_fleet.has_work():
            break
        slo_fleet.step()
    got_slo = [slo_fleet.request_state(u)["emitted"]
               for u in low_uids + high_uids]
    _check(checks, "admitted_overload_streams_bit_identical",
           got_slo == want_slo,
           f"{sum(g == w for g, w in zip(got_slo, want_slo))}"
           f"/{len(want_slo)} match")
    # pool-pressure rule: squeeze the prefill pool's free pages, then a
    # low-priority submit sheds while a high-priority one is admitted
    pf = slo_fleet.replicas["prefill0"]
    with PoolSqueeze(pf.engine, pf.engine.allocator.num_pages):
        try:
            slo_fleet.submit(make_requests(1, salt=6,
                                           priority=PRIORITY_BATCH)[0])
            squeezed_shed = False
        except RejectedError as e:
            squeezed_shed = (e.reason == "pool_pressure")
        hp = make_requests(1, salt=7, priority=PRIORITY_INTERACTIVE)[0]
        hp_uid = slo_fleet.submit(hp)  # protected: admitted, waits
    for _ in range(200):  # squeeze released: the protected request runs
        if not slo_fleet.has_work():
            break
        slo_fleet.step()
    _check(checks, "pool_squeeze_sheds_low_admits_high",
           squeezed_shed
           and slo_fleet.request_state(hp_uid)["emitted"]
           == slo_control([hp])[0])
    shed_delta = counter("deepspeed_tpu_serving_slo_shed_total") - shed0
    _check(checks, "every_shed_counted", shed_delta == shed_lows + 1,
           f"slo_shed_total +{shed_delta} for {shed_lows + 1} sheds")

    # ---- leg 4: deadlines fire at the step boundary
    print("  leg 4: deadlines")
    dl0 = counter("deepspeed_tpu_serving_slo_deadline_exceeded_total")
    doomed = make_requests(2, salt=8, priority=PRIORITY_BATCH,
                           deadline_s=0.0)
    healthy = make_requests(2, salt=9)
    doomed_uids = [slo_fleet.submit(r) for r in doomed]
    healthy_uids = [slo_fleet.submit(r) for r in healthy]
    want_h = slo_control(healthy)
    for _ in range(200):
        if not slo_fleet.has_work():
            break
        slo_fleet.step()
    doomed_states = [slo_fleet.request_state(u) for u in doomed_uids]
    _check(checks, "deadlines_fire_with_finish_reason",
           all(s["done"] and s["finish_reason"] == "deadline"
               and s["emitted"] == [] for s in doomed_states),
           [s["finish_reason"] for s in doomed_states])
    dl_delta = counter(
        "deepspeed_tpu_serving_slo_deadline_exceeded_total") - dl0
    _check(checks, "every_expiry_counted", dl_delta == len(doomed_uids),
           f"slo_deadline_exceeded_total +{dl_delta}")
    _check(checks, "undeadlined_wave_bit_identical",
           [slo_fleet.request_state(u)["emitted"]
            for u in healthy_uids] == want_h)

    # ---- leg 5: slow replica -> breaker trip -> bit-identical finish
    # -> half-open recovery on live traffic
    print("  leg 5: slow replica (gray failure)")
    trips0 = counter("deepspeed_tpu_serving_slo_breaker_trips_total")
    rec0 = counter("deepspeed_tpu_serving_slo_breaker_recoveries_total")
    # interactive priority: the SLO fleet's bounded queue stays armed
    # (max_queue_depth=4) and this wave is submitted in one burst —
    # protected traffic must ride through, which is itself the contract
    wave = make_requests(n_requests, salt=10, priority=PRIORITY_INTERACTIVE)
    want_w = slo_control(wave)
    wave_uids = [slo_fleet.submit(r) for r in wave]
    for _ in range(200):  # get streams decoding on the decode pool
        slo_fleet.step()
        states = [slo_fleet.request_state(u) for u in wave_uids]
        if any((s["replica"] or "").startswith("decode")
               and 1 <= len(s["emitted"]) < new_tokens for s in states):
            break
    hosts = {}
    for s in states:
        if (s["replica"] or "").startswith("decode"):
            hosts[s["replica"]] = hosts.get(s["replica"], 0) + 1
    slow_name = max(hosts, key=hosts.get) if hosts else "decode0"
    print(f"    injecting 80ms step delay into {slow_name} "
          f"(hosting {hosts.get(slow_name, 0)} stream(s))")
    slow = slo_fleet.replicas[slow_name]
    slow.inject_chaos(SlowReplica(delay_s=0.08, seed=seed))
    tripped = False
    for _ in range(100):
        slo_fleet.step()
        if slow.breaker == "open":
            tripped = True
            break
    _check(checks, "slow_replica_breaker_tripped", tripped,
           f"{slow_name} p50={slow.step_p50() * 1e3:.1f}ms "
           f"p95={slow.step_p95() * 1e3:.1f}ms")
    _check(checks, "breaker_trip_counted",
           counter("deepspeed_tpu_serving_slo_breaker_trips_total")
           == trips0 + 1)
    slow.clear_chaos()  # the operator fixed the host
    for _ in range(400):
        if not slo_fleet.has_work():
            break
        slo_fleet.step()
    got_w = [slo_fleet.request_state(u)["emitted"] for u in wave_uids]
    _check(checks, "slow_leg_bit_identical_to_single_engine",
           got_w == want_w,
           f"{sum(g == w for g, w in zip(got_w, want_w))}/{len(want_w)} "
           "match")
    # recovery: cooldown -> half_open probe on live traffic -> closed
    wave2 = make_requests(max(2, n_requests // 2), salt=11,
                          priority=PRIORITY_INTERACTIVE)
    want_w2 = slo_control(wave2)
    w2_uids = [slo_fleet.submit(r) for r in wave2]
    for _ in range(400):
        if not slo_fleet.has_work() and slow.breaker == "closed":
            break
        slo_fleet.step()
    _check(checks, "breaker_recovered_via_half_open_probe",
           slow.breaker == "closed" and slow.accepts_new()
           and counter("deepspeed_tpu_serving_slo_breaker_recoveries_total")
           == rec0 + 1, f"breaker={slow.breaker}")
    _check(checks, "post_recovery_wave_bit_identical",
           [slo_fleet.request_state(u)["emitted"]
            for u in w2_uids] == want_w2)
    slo_leaks = []
    for name, rep in slo_fleet.replicas.items():
        if rep.alive:
            try:
                rep.engine.assert_no_leaks()
            except AssertionError as e:
                slo_leaks.append(f"{name}: {e}")
    _check(checks, "slo_fleet_no_leaks", not slo_leaks, slo_leaks[:2])

    # ---- leg 6: tiered KV cache — capped device cache + host-RAM tier
    print("  leg 6: tiered KV cache (host-RAM spill & restore)")
    tier_fleet, tier_control = build_tier_fleet()
    sp0 = counter("deepspeed_tpu_serving_kv_tier_spilled_pages_total")
    rs0 = counter("deepspeed_tpu_serving_kv_tier_restored_pages_total")
    got_tier, want_tier = [], []
    for wave in make_tier_waves(new_tokens):
        want_tier.extend(tier_control(wave))
        wave_uids = [tier_fleet.submit(r) for r in wave]
        for _ in range(300):
            if not tier_fleet.has_work():
                break
            tier_fleet.step()
        got_tier.extend(tier_fleet.request_state(u)["emitted"]
                        for u in wave_uids)
    sp = counter("deepspeed_tpu_serving_kv_tier_spilled_pages_total") - sp0
    rs = counter("deepspeed_tpu_serving_kv_tier_restored_pages_total") - rs0
    _check(checks, "kv_tier_spills_and_restores_ran", sp > 0 and rs > 0,
           f"{sp:.0f} pages spilled, {rs:.0f} restored")
    _check(checks, "kv_tier_streams_bit_identical_to_uncapped_control",
           got_tier == want_tier,
           f"{sum(g == w for g, w in zip(got_tier, want_tier))}"
           f"/{len(want_tier)} match")
    tier_leaks = []
    for name, rep in tier_fleet.replicas.items():
        try:
            rep.engine.assert_no_leaks()  # accounts in-flight spill pins
        except AssertionError as e:
            tier_leaks.append(f"{name}: {e}")
    _check(checks, "kv_tier_no_leaks_after_churn", not tier_leaks,
           tier_leaks[:2] if tier_leaks else
           f"{len(tier_fleet.replicas)} replicas audited (spill pins "
           "accounted)")
    tier_health = tier_fleet.health()
    _check(checks, "kv_tier_occupancy_in_replica_health",
           any(h.get("kv_tier_host_pages", 0) > 0
               for h in tier_health.values()),
           {n: h.get("kv_tier_host_pages") for n, h in tier_health.items()})

    # ---- leg 7: fused multi-step decode pool vs single-step control
    print("  leg 7: fused multi-step decode (decode_horizon=8)")
    ms_reqs = make_requests(4, salt=21)
    # control FIRST: its K=1 engine pays one host sync per token on the
    # same process-shared counter the fused pool is measured against
    want_ms = control_run(ms_reqs)
    ms_fleet = build_multistep_fleet()
    sync0 = counter("deepspeed_tpu_serving_decode_host_syncs_total")
    ms_uids = [ms_fleet.submit(r) for r in ms_reqs]
    for _ in range(300):
        if not ms_fleet.has_work():
            break
        ms_fleet.step()
    got_ms = [ms_fleet.request_state(u)["emitted"] for u in ms_uids]
    ms_tokens = len(ms_reqs) * new_tokens
    ms_syncs = counter("deepspeed_tpu_serving_decode_host_syncs_total") \
        - sync0
    _check(checks, "multistep_pool_bit_identical_to_single_step_control",
           got_ms == want_ms,
           f"{sum(g == w for g, w in zip(got_ms, want_ms))}"
           f"/{len(want_ms)} match")
    _check(checks, "multistep_decode_amortizes_host_syncs",
           0 < ms_syncs <= ms_tokens / 2,
           f"{ms_syncs:.0f} decode host pulls for {ms_tokens} tokens")
    ms_leaks = []
    for name, rep in ms_fleet.replicas.items():
        try:
            rep.engine.assert_no_leaks()
        except AssertionError as e:
            ms_leaks.append(f"{name}: {e}")
    _check(checks, "multistep_no_leaks_after_horizon_churn", not ms_leaks,
           ms_leaks[:2] if ms_leaks else
           f"{len(ms_fleet.replicas)} replicas audited")

    # ---- leg 8: fleet-wide request tracing — fresh disaggregated fleet
    # + mid-stream kill on a fresh ledger; every request must read as ONE
    # connected trace in the merged artifact, its phase ledger must sum
    # to end-to-end latency, and the forced TTFT violations must carry
    # exemplars that resolve INTO the artifact
    print("  leg 8: request tracing (merged fleet trace + phase ledger)")
    from deepspeed_tpu.telemetry.reqtrace import write_merged_trace

    tr_fleet, tr_led = build_trace_fleet()
    tr_reqs = make_requests(n_requests, salt=31)
    tr_uids = [tr_fleet.submit(r) for r in tr_reqs]
    tr_states = []
    for _ in range(200):
        tr_fleet.step()
        tr_states = [tr_fleet.request_state(u) for u in tr_uids]
        if any((s["replica"] or "").startswith("decode")
               and 1 <= len(s["emitted"]) < new_tokens for s in tr_states):
            break
    tr_hosts = {}
    for s in tr_states:
        if (s["replica"] or "").startswith("decode"):
            tr_hosts[s["replica"]] = tr_hosts.get(s["replica"], 0) + 1
    tr_victim = max(tr_hosts, key=tr_hosts.get) if tr_hosts else "decode0"
    print(f"    killing {tr_victim} mid-stream for the recompute slice")
    tr_fleet.kill_replica(tr_victim)
    for _ in range(400):
        if not tr_fleet.has_work():
            break
        tr_fleet.step()
    tids = [tr_fleet.request_state(u)["trace_id"] for u in tr_uids]
    _check(checks, "trace_ids_minted_and_fleet_unique",
           all(tids) and len(set(tids)) == len(tids),
           f"{len(set(tids))} unique / {len(tids)}")
    redisp_tids = [t for t, u in zip(tids, tr_uids)
                   if tr_fleet.request_state(u)["redispatches"] >= 1]
    ledger_ok, ledger_err = True, f"{len(tids)} ledgers closed"
    for tid in tids:
        tr = tr_led.lookup(tid)
        if tr is None or not tr.done:
            ledger_ok, ledger_err = False, f"{tid}: missing or still open"
            break
        gap = abs(sum(tr.phase_seconds().values()) - tr.elapsed_s())
        if gap > 1e-3:
            ledger_ok, ledger_err = \
                False, f"{tid}: phases off end-to-end by {gap:.6f}s"
            break
    _check(checks, "ledger_phases_sum_to_end_to_end", ledger_ok,
           ledger_err)
    trace_path = os.path.join(out, "fleet_trace.json")
    n_ev = write_merged_trace(trace_path, ledger=tr_led)
    with open(trace_path) as f:
        tr_events = json.load(f)["traceEvents"]
    schema_bad = [e for e in tr_events if not all(
        k in e for k in ("ph", "ts", "dur", "pid", "tid", "name"))]
    _check(checks, "merged_trace_event_schema",
           n_ev > 0 and len(tr_events) == n_ev and not schema_bad,
           f"{n_ev} events -> {trace_path}")
    tr_slices = {}
    for e in tr_events:
        e_tid = (e.get("args") or {}).get("trace_id")
        if e.get("ph") == "X" and e_tid:
            tr_slices.setdefault(e_tid, set()).add(e["name"])
    need = {"prefill", "kv_transfer", "decode"}
    connected = [t for t in tids if need <= tr_slices.get(t, set())]
    _check(checks, "every_request_one_connected_trace",
           len(connected) == len(tids),
           f"{len(connected)}/{len(tids)} traces carry {sorted(need)}")
    _check(checks, "redispatch_produces_recompute_slice",
           bool(redisp_tids)
           and all("recompute" in tr_slices.get(t, set())
                   for t in redisp_tids),
           f"{len(redisp_tids)} stream(s) re-dispatched")
    exs = [e for ring in tr_led.exemplars().values() for e in ring]
    resolved = [e for e in exs if e["trace_id"] in tr_slices]
    _check(checks, "slo_exemplars_resolve_into_merged_artifact",
           bool(exs) and len(resolved) == len(exs),
           f"{len(resolved)}/{len(exs)} exemplars resolve "
           f"({sorted(tr_led.exemplars())})")

    # ---- leg 9: NVMe third tier — host budget capped at 3 page records
    print("  leg 9: NVMe third KV tier (host -> file demote & promote)")
    nvme_dir = os.path.join(out, "kv_nvme")
    nvme_fleet, nvme_control = build_nvme_fleet(nvme_dir)
    nsp0 = counter("deepspeed_tpu_serving_kv_nvme_spilled_pages_total")
    nrs0 = counter("deepspeed_tpu_serving_kv_nvme_restored_pages_total")
    nbad0 = counter("deepspeed_tpu_serving_kv_nvme_corrupt_pages_total")
    got_nv, want_nv = [], []
    for wave in make_tier_waves(new_tokens, salt=14):
        want_nv.extend(nvme_control(wave))
        wave_uids = [nvme_fleet.submit(r) for r in wave]
        for _ in range(300):
            if not nvme_fleet.has_work():
                break
            nvme_fleet.step()
        got_nv.extend(nvme_fleet.request_state(u)["emitted"]
                      for u in wave_uids)
    nsp = counter("deepspeed_tpu_serving_kv_nvme_spilled_pages_total") - nsp0
    nrs = counter("deepspeed_tpu_serving_kv_nvme_restored_pages_total") - nrs0
    nbad = counter("deepspeed_tpu_serving_kv_nvme_corrupt_pages_total") \
        - nbad0
    _check(checks, "kv_nvme_demotes_and_promotes_ran",
           nsp > 0 and nrs > 0,
           f"{nsp:.0f} pages demoted to file, {nrs:.0f} promoted back")
    _check(checks, "kv_nvme_no_corrupt_records", nbad == 0,
           f"{nbad:.0f} refused")
    nvme_files = [f for f in os.listdir(nvme_dir)
                  if f.endswith(".kvpage")] if os.path.isdir(nvme_dir) \
        else []
    _check(checks, "kv_nvme_records_on_disk", bool(nvme_files),
           f"{len(nvme_files)} .kvpage files under {nvme_dir}")
    _check(checks, "kv_nvme_streams_bit_identical_to_uncapped_control",
           got_nv == want_nv,
           f"{sum(g == w for g, w in zip(got_nv, want_nv))}"
           f"/{len(want_nv)} match")
    nv_stats = {}
    for name, rep in nvme_fleet.replicas.items():
        tier = getattr(rep.engine, "kv_tier", None)
        if tier is not None:
            nv_stats[name] = {k: v for k, v in tier.stats().items()
                              if k.startswith("nvme_")}
    _check(checks, "kv_nvme_occupancy_in_tier_stats",
           any(s.get("nvme_spilled_pages", 0) > 0
               for s in nv_stats.values()),
           {n: s.get("nvme_pages") for n, s in nv_stats.items()})
    nv_leaks = []
    for name, rep in nvme_fleet.replicas.items():
        try:
            rep.engine.assert_no_leaks()
        except AssertionError as e:
            nv_leaks.append(f"{name}: {e}")
    _check(checks, "kv_nvme_no_leaks_after_churn", not nv_leaks,
           nv_leaks[:2] if nv_leaks else
           f"{len(nvme_fleet.replicas)} replicas audited")

    # ---- leg 10: cross-process replica — KV over a real socket, elastic
    # grow (autoscaler spawns the remote into the fleet), live decode
    # rebalancing across the process boundary, then scale-down
    # evacuating the remote's streams BACK over the socket; hard-gated
    # bit-identical against the single-engine control
    print("  leg 10: cross-process replica (socket transport + elastic "
          "scale)")
    from deepspeed_tpu.serving import (AutoscaleConfig, FleetAutoscaler,
                                       RemoteEngineProxy,
                                       spawn_engine_server)
    from deepspeed_tpu.serving.replica import EngineReplica

    mp_fleet, mp_spec = build_mp_fleet()
    print("    spawning child engine server (cold JAX import; "
          "this takes a while)...")
    proc, address = spawn_engine_server(mp_spec)
    proxy = RemoteEngineProxy(address, seed=seed)
    mp_reqs = make_requests(6, salt=41)
    want_mp = control_run(mp_reqs)
    fs0 = counter("deepspeed_tpu_serving_transport_frames_sent_total")
    bs0 = counter("deepspeed_tpu_serving_transport_bytes_sent_total")
    rb0 = counter("deepspeed_tpu_serving_fleet_rebalanced_total")
    ad0 = counter("deepspeed_tpu_serving_fleet_replicas_added_total")
    gr0 = counter("deepspeed_tpu_serving_autoscale_grow_total")
    sh0 = counter("deepspeed_tpu_serving_autoscale_shrink_total")
    scaler = FleetAutoscaler(
        mp_fleet,
        AutoscaleConfig(enabled=True, min_replicas=1, max_replicas=2,
                        grow_queue_per_replica=1.0, grow_streak=1,
                        grow_on_ttft_violations=False,
                        shrink_queue_per_replica=0.25, shrink_streak=3,
                        cooldown_pumps=2),
        spawn_replica=lambda i: EngineReplica(f"remote{i}", proxy),
        seed=seed)
    mp_uids = [mp_fleet.submit(r) for r in mp_reqs]
    remote_saw = 0
    for _ in range(400):
        if not mp_fleet.has_work():
            break
        mp_fleet.step()
        scaler.evaluate()
        for name, rep in mp_fleet.replicas.items():
            if name.startswith("remote") and rep.alive and not rep.retired:
                remote_saw = max(remote_saw, rep.load())
    got_mp = [mp_fleet.request_state(u)["emitted"] for u in mp_uids]
    # grow/shrink can legitimately cycle under these aggressive knobs
    # (evacuated streams re-queue and re-trigger pressure), so gate on
    # "at least one" of each, not an exact count
    _check(checks, "mp_autoscaler_grew_remote_replica_into_fleet",
           counter("deepspeed_tpu_serving_autoscale_grow_total") >= gr0 + 1
           and counter("deepspeed_tpu_serving_fleet_replicas_added_total")
           >= ad0 + 1,
           f"replicas now {sorted(mp_fleet.replicas)}")
    _check(checks, "mp_rebalance_moved_streams_across_socket",
           counter("deepspeed_tpu_serving_fleet_rebalanced_total") > rb0
           and remote_saw > 0,
           f"{counter('deepspeed_tpu_serving_fleet_rebalanced_total') - rb0:.0f}"
           f" stream(s) rebalanced, remote peak load {remote_saw}")
    _check(checks, "mp_scale_down_evacuated_remote_mid_run",
           counter("deepspeed_tpu_serving_autoscale_shrink_total")
           >= sh0 + 1
           and any(r.retired for n, r in mp_fleet.replicas.items()
                   if n.startswith("remote")),
           "remote retired via drain/evacuation")
    _check(checks, "mp_all_streams_complete_no_drops",
           not mp_fleet.has_work()
           and all(not mp_fleet.request_state(u)["failed"]
                   for u in mp_uids))
    _check(checks, "mp_bit_identical_to_single_engine",
           got_mp == want_mp,
           f"{sum(g == w for g, w in zip(got_mp, want_mp))}"
           f"/{len(want_mp)} match")
    mp_frames = \
        counter("deepspeed_tpu_serving_transport_frames_sent_total") - fs0
    mp_bytes = \
        counter("deepspeed_tpu_serving_transport_bytes_sent_total") - bs0
    _check(checks, "mp_kv_actually_crossed_the_wire",
           mp_frames > 0 and mp_bytes > 0,
           f"{mp_frames:.0f} frames / {mp_bytes:.0f} B sent")
    mp_leaks = []
    try:
        mp_fleet.replicas["local0"].engine.assert_no_leaks()
    except AssertionError as e:
        mp_leaks.append(f"local0: {e}")
    try:
        proxy.assert_no_leaks()  # audits the CHILD engine over the wire
    except AssertionError as e:
        mp_leaks.append(f"remote: {e}")
    _check(checks, "mp_no_leaks_both_sides_of_socket", not mp_leaks,
           mp_leaks[:2] if mp_leaks else "local + remote audited")
    proxy.close()  # shuts the child server down cleanly
    proc.join(timeout=60)
    _check(checks, "mp_child_process_exited_clean", proc.exitcode == 0,
           f"exitcode {proc.exitcode}")

    # ---- metric-name lint over the tree (fleet family included)
    import check_metric_names as lint

    errors = lint.check(_REPO_DIR)
    fleet_names = sorted(n for n in lint.collect(_REPO_DIR)
                         if n.startswith("deepspeed_tpu_serving_fleet_"))
    _check(checks, "check_metric_names_passes", not errors,
           errors[:3] if errors else f"{len(fleet_names)} fleet metrics")
    _check(checks, "fleet_metric_family_registered", len(fleet_names) >= 8,
           fleet_names[:4])
    slo_names = sorted(n for n in lint.collect(_REPO_DIR)
                       if n.startswith("deepspeed_tpu_serving_slo_"))
    _check(checks, "slo_metric_family_registered", len(slo_names) >= 8,
           slo_names[:4])
    tier_names = sorted(n for n in lint.collect(_REPO_DIR)
                        if n.startswith("deepspeed_tpu_serving_kv_tier_"))
    _check(checks, "kv_tier_metric_family_registered",
           len(tier_names) >= 5, tier_names[:4])
    reqtrace_names = sorted(
        n for n in lint.collect(_REPO_DIR)
        if n.startswith("deepspeed_tpu_serving_reqtrace_"))
    _check(checks, "reqtrace_metric_family_registered",
           len(reqtrace_names) >= 4, reqtrace_names[:4])
    ms_family = ("deepspeed_tpu_serving_decode_tokens_per_dispatch",
                 "deepspeed_tpu_serving_decode_host_syncs_total",
                 "deepspeed_tpu_serving_decode_horizon_shrink_total")
    ms_names = sorted(n for n in lint.collect(_REPO_DIR) if n in ms_family)
    _check(checks, "multistep_metric_family_registered",
           len(ms_names) == len(ms_family), ms_names)
    tp_names = sorted(n for n in lint.collect(_REPO_DIR)
                      if n.startswith("deepspeed_tpu_serving_transport_"))
    _check(checks, "transport_metric_family_registered",
           len(tp_names) >= 8, tp_names[:4])
    as_names = sorted(n for n in lint.collect(_REPO_DIR)
                      if n.startswith("deepspeed_tpu_serving_autoscale_"))
    _check(checks, "autoscale_metric_family_registered",
           len(as_names) >= 4, as_names[:4])
    nv_names = sorted(n for n in lint.collect(_REPO_DIR)
                      if n.startswith("deepspeed_tpu_serving_kv_nvme_"))
    _check(checks, "kv_nvme_metric_family_registered",
           len(nv_names) >= 5, nv_names[:4])

    ok = all(c["ok"] for c in checks)
    summary = {"demo": "fleet_drill", "ok": ok, "out": out, "seed": seed,
               "requests": n_requests + len(reqs2),
               "victim": victim, "slow_replica": slow_name,
               "mp_child_exit": proc.exitcode,
               "nvme_stats": nv_stats,
               "health": fleet.health(),
               "slo_health": slo_fleet.health(),
               "fleet_metrics": fleet_names, "slo_metrics": slo_names,
               "trace_artifact": trace_path, "reqtrace": tr_led.summary(),
               "checks": checks}
    with open(os.path.join(out, "fleet_drill.json"), "w") as f:
        json.dump(summary, f, indent=2)
    print(json.dumps({k: v for k, v in summary.items()
                      if k not in ("checks", "health", "slo_health",
                                   "fleet_metrics", "slo_metrics",
                                   "reqtrace")}))
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--demo", action="store_true",
                    help="run the disaggregation + kill + preemption drill "
                         "on a tiny CPU model")
    ap.add_argument("--out", default="./fleet_drill_demo")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=10)
    ap.add_argument("--seed", type=int, default=7,
                    help="threads through prompt generation and every "
                         "chaos injector; logged in the summary so any "
                         "failure replays exactly")
    args = ap.parse_args(argv)
    if not args.demo:
        ap.print_help()
        return 2
    if args.requests < 2 or args.new_tokens < 4:
        ap.error("need --requests >= 2 and --new-tokens >= 4 for a "
                 "meaningful mid-stream kill")
    return run_demo(os.path.abspath(args.out), args.requests,
                    args.new_tokens, seed=args.seed)


if __name__ == "__main__":
    sys.exit(main())
