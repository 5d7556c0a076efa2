"""Serving fleet tests: router, KV-page migration, replica lifecycle.

Fast tier: pure routing policy (affinity hashing determinism, HRW
stability, least-loaded tie-breaks), allocator ref-count adoption,
bundle wire-format round trip, config validation, and the close()
loudness fix — all host logic, no model steps.

Slow tier: engine-level oracles — KV page export/import round-trips
bit-identically (including copy-on-write pages), a disaggregated fleet
reproduces single-engine greedy streams token-for-token, a replica
death mid-stream recovers every request via re-dispatch, and drain()
finishes in-flight work while handing queued requests back.
"""

from types import SimpleNamespace

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (BlockAllocator, InferenceEngineV2,
                                        PrefixCache, RaggedInferenceConfig,
                                        RaggedRequest)
from deepspeed_tpu.serving import ServingConfig
from deepspeed_tpu.serving.kv_transfer import (bundle_from_bytes,
                                               bundle_to_bytes,
                                               migrate_sequence)
from deepspeed_tpu.serving.router import (affinity_key, build_fleet,
                                          hrw_score, pick_replica)


def _cand(name, load=0):
    return SimpleNamespace(name=name, load=lambda load=load: load)


# ----------------------------- fast: routing policy -------------------------
def test_affinity_key_deterministic_and_prefix_grouped():
    ps = 8
    prompt = list(range(40))
    assert affinity_key(prompt, ps) == affinity_key(list(prompt), ps)
    # same leading pages, different tail beyond affinity_pages => same key
    other = prompt[:2 * ps] + [99] * 10
    assert (affinity_key(prompt, ps, affinity_pages=2)
            == affinity_key(other, ps, affinity_pages=2))
    # divergence INSIDE the hashed pages changes the key
    assert (affinity_key(prompt, ps, affinity_pages=2)
            != affinity_key([1] + prompt[1:], ps, affinity_pages=2))
    # sub-page prompts still hash (whole prompt), deterministically
    assert affinity_key([1, 2, 3], ps) == affinity_key([1, 2, 3], ps)
    assert affinity_key([1, 2, 3], ps) != affinity_key([1, 2, 4], ps)


def test_hrw_pick_deterministic_and_stable():
    key = affinity_key(list(range(16)), 8)
    cands = [_cand(n) for n in ("a", "b", "c")]
    first, via = pick_replica(key, cands, load_gap=4)
    assert via == "affinity"
    for _ in range(3):  # deterministic across calls and candidate order
        again, _ = pick_replica(key, list(reversed(cands)), load_gap=4)
        assert again.name == first.name
    # HRW stability: removing a NON-chosen replica keeps the placement
    losers = [c for c in cands if c.name != first.name]
    kept, _ = pick_replica(key, [c for c in cands if c is not losers[0]],
                           load_gap=4)
    assert kept.name == first.name


def test_least_loaded_fallback_and_tie_break():
    key = affinity_key(list(range(16)), 8)
    hot = max(("a", "b", "c"), key=lambda n: (hrw_score(key, n), n))
    cold = sorted(n for n in ("a", "b", "c") if n != hot)
    # favorite within the gap: affinity wins despite nonzero load
    cands = [_cand(hot, 4)] + [_cand(n, 1) for n in cold]
    got, via = pick_replica(key, cands, load_gap=4)
    assert (got.name, via) == (hot, "affinity")
    # favorite too hot: least-loaded, ties broken by name (deterministic)
    cands = [_cand(hot, 9)] + [_cand(n, 1) for n in cold]
    got, via = pick_replica(key, cands, load_gap=4)
    assert (got.name, via) == (cold[0], "least_loaded")


# ----------------------------- fast: ref-count adoption ---------------------
def test_allocator_adopt_shares_registered_and_allocs_fresh():
    a = BlockAllocator(8)
    pc = PrefixCache(2, a)
    keys = pc.page_keys(list(range(8)), 4)
    owned = a.alloc(2)
    for p, k in zip(owned, keys[:2]):
        a.register(p, k)
    pages, reused = a.adopt([keys[0], keys[1], keys[2], None])
    assert reused == [True, True, False, False]
    assert pages[:2] == owned  # adopted the canonical local pages
    assert a.refcount(owned[0]) == 2 and a.refcount(owned[1]) == 2
    assert a.refcount(pages[2]) == 1 and a.refcount(pages[3]) == 1


def test_allocator_adopt_revives_lru_and_is_all_or_nothing():
    a = BlockAllocator(4)
    pc = PrefixCache(2, a)
    keys = pc.page_keys(list(range(8)), 4)
    owned = a.alloc(3)
    for p, k in zip(owned, keys[:3]):
        a.register(p, k)
    a.free(owned)  # all parked in the LRU, free_pages == 4
    # adoption revives parked pages instead of evicting them for fresh
    pages, reused = a.adopt([keys[0], None])
    assert reused == [True, False] and pages[0] == owned[0]
    assert a.evictions <= 1  # fresh page may evict ONE lru page, not keys[0]
    assert a.lookup(keys[0]) == owned[0]
    # all-or-nothing: over-capacity adopt leaves refcounts untouched
    before = [a.refcount(p) for p in range(4)]
    with pytest.raises(MemoryError):
        a.adopt([keys[1], None, None, None])
    assert [a.refcount(p) for p in range(4)] == before


def test_serving_config_validation():
    cfg = ServingConfig.from_dict({"enabled": True, "prefill_replicas": 2,
                                   "decode_replicas": 3})
    assert (cfg.prefill_replicas, cfg.decode_replicas) == (2, 3)
    with pytest.raises(ValueError):
        ServingConfig.from_dict({"enabled": True, "disaggregated": True,
                                 "prefill_replicas": 0})
    with pytest.raises(ValueError):
        ServingConfig.from_dict({"affinity_pages": 0})
    with pytest.raises(ValueError):
        ServingConfig.from_dict({"prefill_replicas": 0,
                                 "decode_replicas": 0})
    # the ds-config json surface parses the block
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    ds = DeepSpeedConfig({"serving": {"enabled": True, "load_gap": 2}})
    assert ds.serving.enabled and ds.serving.load_gap == 2


# ----------------------------- engine fixtures ------------------------------
@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from deepspeed_tpu.models.llama import llama_model

    model = llama_model("tiny", max_seq_len=128)
    return model, model.init_params(jax.random.PRNGKey(0))


def _engine(model, params, cache=True, **kw):
    cfg = RaggedInferenceConfig(dtype="fp32", page_size=8, num_pages=64,
                                max_seqs=4, max_pages_per_seq=12,
                                enable_prefix_cache=cache, **kw)
    return InferenceEngineV2(model, cfg, params=params)


def _prompt(n, seed=0, vocab=256):
    return list(np.random.RandomState(seed).randint(0, vocab, n))


# ----------------------------- fast: close() loudness -----------------------
def test_close_aborts_inflight_loudly(tiny_model):
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    model, params = tiny_model
    eng = _engine(model, params)
    eng.put(RaggedRequest(prompt_ids=_prompt(12), max_new_tokens=4))
    assert eng.has_work()
    messages = []
    handler = logging.Handler()
    handler.emit = lambda rec: messages.append(rec.getMessage())
    ds_logger.addHandler(handler)  # the package logger propagates nowhere
    try:
        eng.close()
    finally:
        ds_logger.removeHandler(handler)
    assert not eng.has_work()  # aborted, not leaked
    assert any("aborted 1 unfinished" in m for m in messages), messages


def test_bundle_bytes_roundtrip_without_engine():
    from deepspeed_tpu.inference.v2 import KVPageBundle

    arrays = {"k": np.arange(2 * 3 * 8 * 4 * 4, dtype=np.float32)
              .reshape(2, 3, 8, 4, 4),
              "v": np.ones((2, 3, 8, 4, 4), np.float32) * 0.5}
    b = KVPageBundle(uid=7, tokens=list(range(20)), prompt_len=18,
                     max_new_tokens=8, temperature=0.0, eos_id=None,
                     prefilled=19, decode_entry=False, page_size=8,
                     page_keys=[b"\x01" * 32, b"\x02" * 32],
                     src_pages=[{"page": 3, "refcount": 1, "key": b"\x01" * 32},
                                {"page": 5, "refcount": 2, "key": None},
                                {"page": 9, "refcount": 1, "key": None}],
                     arrays=arrays, model_sig=(2, 4, 4), kv_quant=False,
                     dtype="fp32")
    rt = bundle_from_bytes(bundle_to_bytes(b))
    assert rt.uid == 7 and rt.tokens == b.tokens and rt.prefilled == 19
    assert rt.page_keys == b.page_keys and rt.model_sig == (2, 4, 4)
    assert rt.src_pages[0]["key"] == b"\x01" * 32
    for leaf in arrays:
        assert rt.arrays[leaf].dtype == arrays[leaf].dtype
        assert np.array_equal(rt.arrays[leaf], arrays[leaf])
    assert rt.trace is None  # no trace attached -> none invented


def _trace_bundle(trace):
    from deepspeed_tpu.inference.v2 import KVPageBundle

    arrays = {"k": np.arange(1 * 1 * 8 * 2 * 2, dtype=np.float32)
              .reshape(1, 1, 8, 2, 2)}
    return KVPageBundle(uid=3, tokens=list(range(10)), prompt_len=9,
                        max_new_tokens=4, temperature=0.0, eos_id=None,
                        prefilled=9, decode_entry=False, page_size=8,
                        page_keys=[b"\x07" * 32],
                        src_pages=[{"page": 1, "refcount": 1,
                                    "key": b"\x07" * 32}],
                        arrays=arrays, model_sig=(1, 2, 2), kv_quant=False,
                        dtype="fp32", trace=trace)


def test_bundle_wire_preserves_trace_context():
    """The optional trace block survives the CRC-guarded wire: id and
    ledger snapshot intact, one hop appended with send/receive stamps,
    and transit measured on the receive side."""
    snap = {"trace_id": "r1-7", "elapsed_s": 0.25,
            "phases": [["prefill", "prefill0", 0.25]]}
    rt = bundle_from_bytes(bundle_to_bytes(_trace_bundle(snap)))
    assert rt.trace is not None
    assert rt.trace["trace_id"] == "r1-7"
    assert rt.trace["phases"] == [["prefill", "prefill0", 0.25]]
    hops = rt.trace["hops"]
    assert len(hops) == 1
    assert "sent_unix" in hops[0] and "recv_unix" in hops[0]
    assert rt.trace["transit_s"] >= 0.0
    # a second hop (re-migration) appends, never overwrites
    rt2 = bundle_from_bytes(bundle_to_bytes(_trace_bundle(rt.trace)))
    assert len(rt2.trace["hops"]) == 2


def test_bundle_wire_legacy_no_trace_imports_with_null_trace():
    """A bundle serialized WITHOUT a trace block (legacy sender) must
    import cleanly with ``trace=None`` — the block is optional by
    construction, not a new wire version."""
    wire = bundle_to_bytes(_trace_bundle(None))
    assert b'"trace_crc"' not in wire  # header simply omits the block
    rt = bundle_from_bytes(wire)
    assert rt.trace is None
    assert rt.uid == 3 and np.array_equal(
        rt.arrays["k"].ravel(), np.arange(32, dtype=np.float32))


def test_bundle_wire_torn_trace_block_refused_by_name():
    """A trace block whose CRC no longer matches (torn/bit-flipped in
    transport) is refused with an error naming the trace block — never
    silently imported with a wrong trace."""
    from deepspeed_tpu.serving.kv_transfer import (CorruptBundleError,
                                                   _MAGIC)
    import json as _json

    wire = bundle_to_bytes(_trace_bundle({"trace_id": "r1-9", "hops": []}))
    off = len(_MAGIC)
    hlen = int.from_bytes(wire[off:off + 8], "little")
    header = _json.loads(wire[off + 8:off + 8 + hlen].decode())
    header["trace"]["trace_id"] = "r1-FORGED"  # flip a byte, keep old CRC
    hdr = _json.dumps(header).encode()
    torn = (_MAGIC + len(hdr).to_bytes(8, "little") + hdr
            + wire[off + 8 + hlen:])
    with pytest.raises(CorruptBundleError, match="trace block"):
        bundle_from_bytes(torn)
    # page payload itself is intact: stripping the trace keys imports fine
    header.pop("trace"), header.pop("trace_crc")
    hdr = _json.dumps(header).encode()
    ok = (_MAGIC + len(hdr).to_bytes(8, "little") + hdr
          + wire[off + 8 + hlen:])
    assert bundle_from_bytes(ok).trace is None


# ------------------- fast: rebalance / elastic membership -------------------
class _StubEngine:
    """Pure-python engine for routing-policy tests: holds decode-ready
    uids, moves them via the real migrate_sequence plumbing."""

    def __init__(self, uids=(), queue=0):
        from types import SimpleNamespace as NS

        self.block = NS(page_size=8)
        self.allocator = NS(free_pages=32, num_pages=64)
        self.queue_depth = queue
        self.uids = list(uids)
        self.imported = []
        self.released = []
        self.trace_owner = None

    @property
    def active_count(self):
        return len(self.uids)

    def has_work(self):
        return bool(self.uids) or self.queue_depth > 0

    def ready_uids(self):
        return list(self.uids)

    def export_sequence(self, uid):
        return SimpleNamespace(uid=uid, n_pages=2, trace=None)

    def import_sequence(self, bundle):
        self.uids.append(bundle.uid)
        self.imported.append(bundle.uid)
        return True

    def release_sequence(self, uid, reason=""):
        self.uids.remove(uid)
        self.released.append(uid)

    def abort_all(self, reason="abort"):
        out, self.uids = list(self.uids), []
        return out


def _stub_fleet(*engines, config=None, role=None):
    from deepspeed_tpu.serving.replica import ROLE_MIXED, EngineReplica
    from deepspeed_tpu.serving.router import FleetRouter

    reps = [EngineReplica(f"s{i}", e, role=role or ROLE_MIXED)
            for i, e in enumerate(engines)]
    return FleetRouter(reps, config or ServingConfig())


def test_rebalance_moves_bounded_load_off_hot_replica():
    cfg = ServingConfig(rebalance_enabled=True, rebalance_load_gap=4,
                        rebalance_max_per_pump=2)
    hot, cold = _StubEngine(uids=[1, 2, 3, 4, 5, 6]), _StubEngine()
    router = _stub_fleet(hot, cold, config=cfg)
    router._rebalance_decode()
    # bounded per pump, routed through the real migration plumbing
    assert cold.imported == [1, 2] and hot.released == [1, 2]
    assert sorted(hot.uids) == [3, 4, 5, 6]
    # gap now 4, NOT > rebalance_load_gap: hysteresis holds, no move
    router._rebalance_decode()
    assert cold.imported == [1, 2]


def test_rebalance_skips_deadline_starved_streams():
    from deepspeed_tpu.serving.router import _RequestRecord

    cfg = ServingConfig(rebalance_enabled=True, rebalance_load_gap=2,
                        rebalance_max_per_pump=8,
                        rebalance_min_deadline_s=0.5)
    hot, cold = _StubEngine(uids=[1, 2, 3, 4]), _StubEngine()
    router = _stub_fleet(hot, cold, config=cfg)
    # uid 2 has ~no deadline budget left: the move costs time it
    # doesn't have — it must stay put while the others go
    starved = RaggedRequest(prompt_ids=[1], uid=2, deadline_s=1e-9)
    router._requests[2] = _RequestRecord(starved)
    router._rebalance_decode()
    assert 2 in hot.uids and 2 not in cold.imported
    assert sorted(cold.imported) == [1, 3, 4]


def test_rebalance_p50_signal_spots_warm_replica():
    """The latency rule relieves a warm (gray-degrading) replica at a
    LOWER threshold than the breaker declares it failed."""
    cfg = ServingConfig(rebalance_enabled=True, rebalance_p50_factor=2.0,
                        breaker_enabled=True)
    eng = [_StubEngine(uids=[1]), _StubEngine(uids=[2]),
           _StubEngine(uids=[3])]
    router = _stub_fleet(*eng, config=cfg)
    reps = list(router.replicas.values())
    need = cfg.breaker_min_samples
    for r in reps:  # equal load; only latency distinguishes them
        for _ in range(need):
            r._record_step(0.01, error=False)
    assert router._hot_decode_replica(reps) is None  # healthy: no pick
    # a WARM replica is slow on every step: the rolling MEDIAN moves
    for _ in range(2 * need + 1):
        reps[1]._record_step(10 * cfg.breaker_min_latency_s, error=False)
    assert router._hot_decode_replica(reps) is reps[1]


def test_add_replica_checks_name_and_geometry():
    router = _stub_fleet(_StubEngine())
    from deepspeed_tpu.serving.replica import EngineReplica

    router.add_replica(EngineReplica("joined", _StubEngine()))
    assert set(router.replicas) == {"s0", "joined"}
    with pytest.raises(ValueError, match="already in"):
        router.add_replica(EngineReplica("joined", _StubEngine()))
    wrong = _StubEngine()
    wrong.block.page_size = 16
    with pytest.raises(ValueError, match="one geometry"):
        router.add_replica(EngineReplica("odd", wrong))


def test_rebalance_config_validation():
    with pytest.raises(ValueError):
        ServingConfig(rebalance_enabled=True,
                      rebalance_max_per_pump=0).validate()
    # rebalance must fire BELOW the breaker's latency threshold, or the
    # breaker recomputes everything before rebalancing ever helps
    with pytest.raises(ValueError, match="breaker_latency_factor"):
        ServingConfig(rebalance_enabled=True, breaker_enabled=True,
                      rebalance_p50_factor=50.0).validate()


# ----------------------------- fast: autoscaler -----------------------------
def _autoscaler(router, spawn=None, **kw):
    from deepspeed_tpu.serving import AutoscaleConfig
    from deepspeed_tpu.serving.autoscale import FleetAutoscaler

    kw.setdefault("enabled", True)
    return FleetAutoscaler(router, AutoscaleConfig(**kw),
                           spawn_replica=spawn)


def test_autoscaler_grows_on_sustained_queue_pressure():
    from deepspeed_tpu.serving.replica import EngineReplica

    router = _stub_fleet(_StubEngine(queue=9))
    spawned = []

    def spawn(i):
        spawned.append(i)
        return EngineReplica(f"auto{i}", _StubEngine())

    a = _autoscaler(router, spawn, grow_queue_per_replica=4.0,
                    grow_streak=2, grow_on_ttft_violations=False,
                    max_replicas=2, cooldown_pumps=3)
    assert a.evaluate() is None  # streak 1: pressure must SUSTAIN
    assert a.evaluate() == "grow"
    assert spawned == [0] and "auto0" in router.replicas
    assert a.grown == ["auto0"]
    # cooldown: the fresh replica absorbs load before signals re-arm;
    # then max_replicas caps growth even under pressure
    for _ in range(10):
        a.evaluate()
    assert len(router.replicas) == 2


def test_autoscaler_grows_on_new_ttft_violations():
    from deepspeed_tpu.serving.replica import EngineReplica
    from deepspeed_tpu.telemetry import get_registry

    router = _stub_fleet(_StubEngine(queue=1))
    a = _autoscaler(router,
                    lambda i: EngineReplica(f"auto{i}", _StubEngine()),
                    grow_queue_per_replica=100.0, grow_streak=99,
                    max_replicas=2)
    assert a.evaluate() is None  # queue alone is quiet
    get_registry().counter(
        "deepspeed_tpu_serving_slo_ttft_violations_total",
        "ttft violations").inc(3)
    assert a.evaluate() == "grow"  # latency debt is the leading signal


def test_autoscaler_shrinks_lifo_via_evacuation_never_drops():
    from deepspeed_tpu.serving.replica import EngineReplica

    base, extra = _StubEngine(), _StubEngine(uids=[7, 8])
    router = _stub_fleet(base)
    router.add_replica(EngineReplica("auto0", extra))
    a = _autoscaler(router, shrink_queue_per_replica=0.5,
                    shrink_streak=2, min_replicas=1, cooldown_pumps=0,
                    grow_streak=99, grow_on_ttft_violations=False)
    a.grown = ["auto0"]
    assert a.evaluate() is None
    assert a.evaluate() == "shrink"
    r = router.replicas["auto0"]
    assert r.retired and not extra.uids  # engine left empty...
    assert sorted(base.imported) == [7, 8]  # ...streams MIGRATED out
    assert a.grown == []
    # min_replicas floor: never shrinks the last replica
    for _ in range(8):
        assert a.evaluate() is None
    assert not router.replicas["s0"].retired


def test_autoscaler_spawn_failure_backs_off_bounded():
    router = _stub_fleet(_StubEngine(queue=50))

    def bad_spawn(i):
        raise RuntimeError("factory broke")

    a = _autoscaler(router, bad_spawn, grow_queue_per_replica=1.0,
                    grow_streak=1, max_replicas=4, cooldown_pumps=0)
    fails, skips = 0, 0
    for _ in range(40):
        a.evaluate()
        if a._spawn_backoff and a._spawn_failures:
            skips += 1
        fails = a._spawn_failures
    # pressure is constant, but attempts decay exponentially: far
    # fewer than 40 factory calls, and the backoff keeps growing
    assert 0 < fails < 8 and skips > fails
    assert len(router.replicas) == 1


def test_autoscale_config_validation():
    from deepspeed_tpu.serving import AutoscaleConfig

    with pytest.raises(ValueError):
        AutoscaleConfig(min_replicas=3, max_replicas=2).validate()
    with pytest.raises(ValueError, match="hysteresis"):
        AutoscaleConfig(grow_queue_per_replica=1.0,
                        shrink_queue_per_replica=2.0).validate()
    sc = ServingConfig(autoscale={"enabled": True, "max_replicas": 3})
    sc.validate()
    assert sc.autoscale.max_replicas == 3


# ----------------------------- slow: engine oracles -------------------------
@pytest.mark.slow
@pytest.mark.parametrize("cache", [False, True])
def test_kv_export_import_bit_identical_roundtrip(tiny_model, cache):
    """Export a mid-decode sequence, import into a fresh engine: page
    contents must round-trip bit-identically and the continued stream
    must match the uninterrupted one token-for-token."""
    from deepspeed_tpu.inference.v2.model_runner import paged_gather_pages

    model, params = tiny_model
    src = _engine(model, params, cache=cache)
    uid = src.put(RaggedRequest(prompt_ids=_prompt(20, seed=1),
                                max_new_tokens=8))
    for _ in range(3):  # prefill + 2 decode steps: mid-stream
        src.step()
    bundle = src.export_sequence(uid)
    assert bundle.n_pages == len(src._find_slotted(uid).pages)

    dst = _engine(model, params, cache=cache)
    assert dst.import_sequence(bundle)
    got = paged_gather_pages(dst._pools, dst._find_slotted(uid).pages,
                             dst.cfg.kv_heads)
    for leaf, arr in bundle.arrays.items():
        assert got[leaf].dtype == arr.dtype
        assert np.array_equal(got[leaf], arr), leaf

    # streams: source continues undisturbed, the import continues too
    src_rest, dst_rest = [], []
    for _ in range(20):
        for u, rec in src.step().items():
            src_rest.extend(rec["tokens"])
        for u, rec in dst.step().items():
            dst_rest.extend(rec["tokens"])
        if not src.has_work() and not dst.has_work():
            break
    assert src_rest == dst_rest and len(dst_rest) > 0


@pytest.mark.slow
def test_kv_export_import_covers_copy_on_write_page(tiny_model):
    """A fully-cached prompt admits via a copy-on-write page
    (decode_entry); its bundle must transfer that private page by value
    and the migrated stream must match the donor engine's."""
    from deepspeed_tpu.inference.v2.model_runner import paged_gather_pages

    model, params = tiny_model
    src = _engine(model, params, cache=True)
    prompt = _prompt(16, seed=2)  # page-aligned: full-hit on re-admission
    first = src.generate_all([RaggedRequest(prompt_ids=list(prompt),
                                            max_new_tokens=6)])
    uid = src.put(RaggedRequest(prompt_ids=list(prompt), max_new_tokens=6))
    # drive admission WITHOUT a decode step: the full cache hit maps a
    # private copy-on-write last page (decode_entry), still unwritten —
    # the migration case where the CoW page must move by value
    src._admit()
    seq = src._find_slotted(uid)
    assert seq.decode_entry and seq.generated == 0
    bundle = src.export_sequence(uid)
    # the CoW page (last) is NOT adoptable — transferred by value
    assert len(bundle.page_keys) < bundle.n_pages

    dst = _engine(model, params, cache=True)
    assert dst.import_sequence(bundle)
    got = paged_gather_pages(dst._pools, dst._find_slotted(uid).pages,
                             dst.cfg.kv_heads)
    for leaf, arr in bundle.arrays.items():
        assert np.array_equal(got[leaf], arr), leaf
    src.release_sequence(uid)
    toks = []
    for _ in range(20):
        for _u, rec in dst.step().items():
            toks.extend(rec["tokens"])
        if not dst.has_work():
            break
    assert toks == first[0], (toks, first[0])


@pytest.mark.slow
def test_import_rejects_dtype_mismatch(tiny_model):
    """A dtype-mismatched bundle must raise even when every page could
    be adopted by content key (the scatter — the only other dtype
    check — never runs on an all-adopted import)."""
    import dataclasses

    model, params = tiny_model
    src = _engine(model, params, cache=True)
    uid = src.put(RaggedRequest(prompt_ids=_prompt(20, seed=5),
                                max_new_tokens=8))
    for _ in range(3):  # prefill + 2 decode steps: mid-stream
        src.step()
    bundle = dataclasses.replace(src.export_sequence(uid), dtype="bf16")
    dst = _engine(model, params, cache=True)
    with pytest.raises(ValueError, match="dtype"):
        dst.import_sequence(bundle)


@pytest.mark.slow
def test_planned_retirement_spares_redispatch_budget(tiny_model):
    """retire_replica(migrate=False) hands queued work back without
    consuming the max_redispatch replica-loss budget: with
    max_redispatch=0 every drained-back request must still complete."""
    model, params = tiny_model
    base = RaggedInferenceConfig(dtype="fp32", page_size=8, num_pages=64,
                                 max_seqs=4, max_pages_per_seq=12)
    reqs = [RaggedRequest(prompt_ids=_prompt(10 + i, seed=40 + i),
                          max_new_tokens=4) for i in range(4)]
    control = InferenceEngineV2(model, base, params=params)
    want = control.generate_all([RaggedRequest(prompt_ids=list(r.prompt_ids),
                                               max_new_tokens=r.max_new_tokens)
                                 for r in reqs])
    fleet = build_fleet(
        model, ServingConfig(enabled=True, prefill_replicas=1,
                             decode_replicas=1, disaggregated=False,
                             max_redispatch=0),
        engine_config=base, params=params)
    uids = [fleet.submit(r) for r in reqs]
    victim = next(fleet.request_state(u)["replica"] for u in uids)
    fleet.retire_replica(victim, migrate=False)  # nothing admitted yet:
    for _ in range(200):                         # all its work requeues
        if not fleet.has_work():
            break
        fleet.step()
    assert not fleet.has_work()
    states = [fleet.request_state(u) for u in uids]
    assert not any(s["failed"] for s in states)
    assert all(s["redispatches"] == 0 for s in states)  # planned: uncharged
    assert [s["emitted"] for s in states] == [want[i] for i in range(4)]


@pytest.mark.slow
def test_disaggregated_fleet_matches_single_engine(tiny_model):
    model, params = tiny_model
    base = RaggedInferenceConfig(dtype="fp32", page_size=8, num_pages=64,
                                 max_seqs=4, max_pages_per_seq=12,
                                 enable_prefix_cache=True)
    shared = _prompt(16, seed=3)
    reqs = [RaggedRequest(prompt_ids=shared + _prompt(3 + i, seed=10 + i),
                          max_new_tokens=6) for i in range(3)]
    control = InferenceEngineV2(model, base, params=params)
    want = control.generate_all([RaggedRequest(prompt_ids=list(r.prompt_ids),
                                               max_new_tokens=r.max_new_tokens)
                                 for r in reqs])
    fleet = build_fleet(
        model, ServingConfig(enabled=True, prefill_replicas=1,
                             decode_replicas=1, prefill_chunk=8),
        engine_config=base, params=params)
    got = fleet.run_all(reqs)
    assert [got[i] for i in range(3)] == [want[i] for i in range(3)]
    # disaggregation actually ran: the decode pool carried the decoding.
    # (The prefill engine may decode each sequence at most once — the
    # SplitFuse step that finishes a prefill interleaves one decode
    # before the router can migrate; steady-state decode must move.)
    assert fleet.replicas["decode0"].engine._decode_steps >= 3
    assert fleet.replicas["prefill0"].engine._decode_steps <= len(reqs)


@pytest.mark.slow
def test_redispatch_after_replica_death(tiny_model):
    model, params = tiny_model
    base = RaggedInferenceConfig(dtype="fp32", page_size=8, num_pages=64,
                                 max_seqs=4, max_pages_per_seq=12,
                                 enable_prefix_cache=True)
    shared = _prompt(16, seed=4)
    reqs = [RaggedRequest(prompt_ids=shared + _prompt(3 + i, seed=20 + i),
                          max_new_tokens=8) for i in range(3)]
    control = InferenceEngineV2(model, base, params=params)
    want = control.generate_all([RaggedRequest(prompt_ids=list(r.prompt_ids),
                                               max_new_tokens=r.max_new_tokens)
                                 for r in reqs])
    fleet = build_fleet(
        model, ServingConfig(enabled=True, prefill_replicas=1,
                             decode_replicas=2, prefill_chunk=8),
        engine_config=base, params=params)
    uids = [fleet.submit(r) for r in reqs]
    for _ in range(60):
        fleet.step()
        states = [fleet.request_state(u) for u in uids]
        if any((s["replica"] or "").startswith("decode")
               and 1 <= len(s["emitted"]) < 8 for s in states):
            break
    victims = [s["replica"] for s in states
               if (s["replica"] or "").startswith("decode")]
    assert victims, states
    fleet.kill_replica(victims[0])
    for _ in range(200):
        if not fleet.has_work():
            break
        fleet.step()
    assert not fleet.has_work()
    got = [fleet.request_state(u)["emitted"] for u in uids]
    assert got == [want[i] for i in range(3)]
    assert any(fleet.request_state(u)["redispatches"] >= 1 for u in uids)
    assert not any(fleet.request_state(u)["failed"] for u in uids)


@pytest.mark.slow
def test_engine_drain_finishes_inflight_and_returns_queued(tiny_model):
    model, params = tiny_model
    eng = _engine(model, params, cache=False)
    # more requests than decode slots: some stay queued at drain time
    uids = [eng.put(RaggedRequest(prompt_ids=_prompt(10 + i, seed=30 + i),
                                  max_new_tokens=4)) for i in range(6)]
    eng.step()  # admits up to max_seqs=4; 2 remain queued
    result = eng.drain()
    finished, pending = result["finished"], result["pending"]
    assert len(finished) + len(pending) == 6
    assert all(s.done for s in finished.values())
    assert all(s.generated == 4 for s in finished.values())
    assert all(s.generated == 0 for s in pending)  # handed back UN-run
    assert not eng.has_work()
    with pytest.raises(RuntimeError):  # retired: no new admissions
        eng.put(RaggedRequest(prompt_ids=_prompt(8), max_new_tokens=2))
    assert set(finished) | {s.uid for s in pending} == set(uids)
