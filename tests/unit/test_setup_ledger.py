"""The set-up ledger (``telemetry/compile_sentinel.py``): every interval
goes in through the listener's own entry points — ``_on_duration_event``
and ``_on_event`` as ``jax.monitoring`` calls them, ``setup_span`` as the
package and the engines do — and comes out through ``setup_ledger``.

Synthetic cases run on a fake clock over a fresh store; the others read
the process's own ledger, which the suite's earlier tests have fed.
"""

import time

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.telemetry import compile_sentinel as cs
from deepspeed_tpu.telemetry import (MetricsRegistry, SpanRecorder,
                                     get_span_recorder, set_span_recorder)

EVENT = {
    "trace": "/jax/core/compile/jaxpr_trace_duration",
    "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "compile": "/jax/core/compile/backend_compile_duration",
    "cache_load": "/jax/core/compile/backend_compile_duration",
}
HITS = "/jax/compilation_cache/cache_hits"
MISSES = "/jax/compilation_cache/cache_misses"
STAGES = tuple(EVENT)


class Clock:
    """``time.perf_counter`` under the test's hand."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now


@pytest.fixture
def ledger(monkeypatch):
    """A fresh store on a fake clock; ``feed(part, start, end, name)``
    delivers one interval the way its part arrives in a process."""
    clock = Clock()
    monkeypatch.setattr(cs, "time", clock)
    monkeypatch.setattr(cs, "_parts", {p: cs._Part() for p in cs.SETUP_PARTS})
    monkeypatch.setattr(cs, "_totals", {})
    monkeypatch.setattr(cs, "_origin", None)
    monkeypatch.setattr(cs, "_miss_stamps", [])
    monkeypatch.setattr(cs, "_misses", 0)
    monkeypatch.setattr(cs, "_claimed", 0)

    def feed(part, start, end, name="f"):
        if part == "import":
            cs.setup_span("package_import", start, end)
        elif part == "engine_init":
            cs.setup_span("train_engine_init", start, end)
        else:
            clock.now = end
            if part == "cache_load":
                cs._on_event(HITS)
            cs._on_duration_event(EVENT[part], end - start, fun_name=name)

    feed.clock = clock
    return feed


@pytest.fixture
def ring():
    """A fresh span ring in the process default's place."""
    old = get_span_recorder()
    rec = SpanRecorder(ring_size=64)
    set_span_recorder(rec)
    yield rec
    set_span_recorder(old)


def parts(a, b):
    got = cs.setup_ledger(a, b)["parts"]
    return {k: round(v, 9) for k, v in got.items() if v}


# ------------------------------------------------------------ the partition
@pytest.mark.parametrize("stage", STAGES)
def test_nested_events_of_a_stage_are_counted_once(ledger, stage):
    # two primitives and an inner jit report inside the outer function,
    # each before it, as JAX delivers them
    ledger(stage, 1.0, 1.5, "sin")
    ledger(stage, 2.0, 4.0, "inner")
    ledger(stage, 4.5, 5.0, "cos")
    ledger(stage, 0.0, 10.0, "outer")
    assert parts(0.0, 20.0) == {stage: 10.0, "unnamed": 10.0}
    got = cs.setup_ledger(0.0, 20.0)
    assert got["programs"] == {(stage, "outer"): (1, 10.0)}
    assert got["events"][stage] == 4 and got["kept"] == 1


@pytest.mark.parametrize("stage", STAGES)
def test_a_stage_inside_a_constructor_is_the_stages(ledger, stage):
    ledger(stage, 2.0, 5.0)
    ledger("engine_init", 1.0, 8.0)
    assert parts(0.0, 10.0) == {"engine_init": 4.0, stage: 3.0,
                                "unnamed": 3.0}
    # a stretch that cuts both: the parts never exceed it
    assert parts(4.0, 6.0) == {stage: 1.0, "engine_init": 1.0}
    assert parts(8.0, 9.0) == {"unnamed": 1.0}


def test_every_part_at_once_sums_to_the_stretch(ledger):
    ledger("import", 0.0, 3.0)
    ledger("trace", 4.0, 5.0)
    ledger("trace", 5.5, 7.0, "g")
    ledger("lower", 7.0, 7.5)
    ledger("compile", 7.5, 9.0)
    ledger("cache_load", 9.5, 10.0, "g")
    ledger("engine_init", 3.5, 11.0)
    got = cs.setup_ledger(0.0, 12.0)
    assert got["origin"] == 0.0
    assert {k: round(v, 9) for k, v in got["parts"].items()} == {
        "import": 3.0, "engine_init": 2.5, "trace": 2.5, "lower": 0.5,
        "compile": 1.5, "cache_load": 0.5, "unnamed": 1.5}
    assert sum(got["parts"].values()) == pytest.approx(12.0)
    for a, b in ((0.0, 4.2), (4.2, 9.7), (6.0, 6.1), (11.5, 30.0)):
        cut = cs.setup_ledger(a, b)["parts"]
        assert all(v >= 0.0 for v in cut.values())
        assert sum(cut.values()) == pytest.approx(b - a)
    # trace + lowering + compile-or-load, for the goodput ledger
    assert cs.compile_path_seconds() == pytest.approx(5.0)


def test_intervals_that_overlap_across_threads_share_their_instants(ledger):
    ledger("trace", 0.0, 2.0, "a")
    ledger("trace", 1.0, 3.0, "b")  # began inside a, ended after it
    ledger("compile", 2.5, 4.0)
    assert parts(0.0, 4.0) == {"trace": 2.5, "compile": 1.5}
    assert cs.setup_ledger()["programs"][("trace", "a")] == (1, 3.0)


# ------------------------------------------------------------ kept bounded
def test_two_thousand_nested_events_leave_one_interval(ledger):
    for i in range(2000):
        ledger("trace", 1.0 + i * 1e-3, 1.0 + i * 1e-3 + 5e-4, "add")
    assert cs.setup_ledger(0.0, 4.0)["kept"] == 2000
    ledger("trace", 0.5, 3.5, "body")
    got = cs.setup_ledger(0.0, 4.0)
    assert got["kept"] == 1 and got["events"]["trace"] == 2001
    assert got["programs"] == {("trace", "body"): (1, 3.0)}
    assert parts(0.0, 4.0) == {"trace": 3.0, "unnamed": 1.0}


def test_more_intervals_than_kept_leave_exact_totals_and_refuse(
        ledger, monkeypatch):
    monkeypatch.setattr(cs, "_KEEP", 8)
    for i in range(40):  # 40 programs back to back, half a second each
        ledger("lower", float(i), i + 0.5, f"p{i % 4}")
    got = cs.setup_ledger(0.0, 7.9)
    assert got["kept"] == 16  # the first 8 and the latest 8
    assert got["parts"]["lower"] == pytest.approx(4.0)
    assert sum(s for _n, s in got["programs"].values()) == pytest.approx(20.0)
    assert sum(n for n, _s in got["programs"].values()) == 40
    assert cs.compile_path_seconds() == pytest.approx(20.0)
    # the ninth interval was let go at t = 8: no reading past it
    for b in (8.2, 39.0, 100.0):
        refused = cs.setup_ledger(0.0, b)
        assert refused["parts"] is None and refused["cache_misses"] is None
    # an encloser still absorbs what arrived last
    ledger("lower", 35.9, 41.0, "late")
    assert cs.setup_ledger()["kept"] == 13
    assert cs.compile_path_seconds() == pytest.approx(18.0 + 5.1)


def test_cache_misses_are_counted_over_the_stretch(ledger):
    for t in (1.0, 2.0, 3.0):
        ledger.clock.now = t
        cs._on_event(MISSES)
        ledger("compile", t - 0.5, t)
    assert cs.setup_ledger(0.0, 2.5)["cache_misses"] == 2
    assert cs.setup_ledger(0.0, 9.0)["cache_misses"] == 3
    assert cs.setup_ledger(2.5, 9.0)["cache_misses"] == 1


def test_traces_that_end_after_the_stretch_are_counted(ledger):
    ledger("trace", 1.0, 2.0)
    ledger("trace", 5.0, 6.0)
    ledger("trace", 7.0, 8.0)
    assert cs.setup_ledger(0.0, 4.0)["traces_after"] == 2
    assert cs.setup_ledger(0.0, 9.0)["traces_after"] == 0


# ------------------------------------------------- what was there, unchanged
def test_compile_counts_counts_every_backend_event(ledger):
    ledger("compile", 0.0, 2.0)
    ledger("cache_load", 3.0, 3.25)
    ledger("trace", 4.0, 5.0)
    ledger("compile", 6.0, 6.5)
    assert cs.compile_counts() == (3, pytest.approx(2.75))


def test_clearing_or_filling_the_ring_loses_nothing(ledger, ring):
    ledger("compile", 0.0, 2.0, "jit(step)")
    ledger("cache_load", 3.0, 3.5, "jit(step)")
    ledger("trace", 4.0, 4.0 + 1e-4, "add")  # too short for the ring
    ledger("engine_init", 0.0, 4.5)
    spans = {s.name: s for s in ring.spans()}
    assert sorted(spans) == ["cache_load", "train_engine_init", "xla_compile"]
    assert spans["xla_compile"].attrs["cache"] == "miss"
    assert spans["xla_compile"].attrs["fun_name"] == "jit(step)"
    assert spans["xla_compile"].cat == "compile"
    assert spans["xla_compile"].dur_us == pytest.approx(2e6)
    assert spans["cache_load"].attrs["cache"] == "hit"
    assert spans["train_engine_init"].cat == "setup"
    before = cs.setup_ledger(0.0, 5.0)
    ring.clear()
    for _ in range(2 * ring._ring.maxlen):
        ring.event("filler")
    after = cs.setup_ledger(0.0, 5.0)
    assert after["parts"] == before["parts"]
    assert after["programs"] == before["programs"]


def test_the_recompile_event_names_the_programs(ledger, ring):
    s = cs.RecompileSentinel(loop="setup_t", registry=MetricsRegistry())
    s.observe_step(["warm"], step=0)
    ledger("compile", 1.0, 2.0, "jit(decode)")
    ledger("cache_load", 2.0, 2.5, "jit(chunk)")
    assert s.observe_step(["warm"], step=1)
    ev = [sp for sp in ring.spans() if sp.name == "recompile"][-1]
    assert ev.attrs["compiles"] == 2
    assert ev.attrs["programs"] == "jit(decode),jit(chunk)"


# ------------------------------------------------------- the real process
def test_package_import_is_the_origin_and_comes_first():
    got = cs.setup_ledger()
    assert got["origin"] == deepspeed_tpu._T_IMPORT
    assert got["programs"][("import", "package_import")][0] == 1
    with cs._lock:
        starts = [iv[0] for p in cs._parts.values() for iv in p.intervals]
    assert min(starts) == got["origin"]
    assert got["parts"]["import"] > 0.0


def test_each_engine_leaves_one_init_interval():
    from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                      RaggedInferenceConfig)
    from deepspeed_tpu.models.llama import llama_model
    from deepspeed_tpu.telemetry import get_registry
    from tests.unit.simple_model import simple_mlp_spec

    def count(name):
        return cs.setup_ledger()["programs"].get(("engine_init", name),
                                                 (0, 0.0))[0]

    n_train, n_serve = count("train_engine_init"), count("serve_engine_init")
    t0 = time.perf_counter()
    engine, *_ = deepspeed_tpu.initialize(
        model=simple_mlp_spec(),
        config={"train_micro_batch_size_per_gpu": 4,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
    t1 = time.perf_counter()
    eng = InferenceEngineV2(
        llama_model("tiny", max_seq_len=64),
        RaggedInferenceConfig(dtype="fp32", page_size=8, num_pages=16,
                              max_seqs=2, max_pages_per_seq=4))
    t2 = time.perf_counter()
    try:
        assert count("train_engine_init") == n_train + 1
        assert count("serve_engine_init") == n_serve + 1
        # the constructors' stretches: one engine_init interval each, and
        # parts that are a partition of the stretch.  How much of a stretch
        # the span covers is a wall-clock share, which a loaded machine
        # moves (0.113 s of a 0.200 s constructor outside every span under
        # six workers): held to no limit here
        for a, b in ((t0, t1), (t1, t2)):
            got = cs.setup_ledger(a, b)["parts"]
            assert got["engine_init"] > 0.0
            assert sum(got.values()) == pytest.approx(b - a)
            assert all(v >= 0.0 for v in got.values())
        gauge = get_registry().gauge("deepspeed_tpu_setup_seconds",
                                     labelnames=("part",))
        assert gauge.value(part="engine_init") > 0.0
        assert gauge.value(part="import") == pytest.approx(
            cs.setup_ledger()["parts"]["import"])
    finally:
        engine.close()
        eng.close()


def test_the_train_step_notes_what_it_keeps_and_compiles_nothing_for_it():
    """At its first reporting boundary the engine puts the recomputation
    policy and the step's temporaries on the ledger's ``_train_batch_body``
    entry, from the executable ``jit`` already holds."""
    from deepspeed_tpu.models.llama import llama_model
    from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import \
        DEFAULT_POLICY

    engine, *_ = deepspeed_tpu.initialize(
        model=llama_model("tiny", max_seq_len=16, remat=True),
        config={"train_micro_batch_size_per_gpu": 2, "steps_per_print": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "telemetry": {"enabled": True}})
    batch = {"input_ids": jnp.zeros((1, 16, 16), jnp.int32)}
    try:
        engine.train_batch(batch)
        assert not engine._step_program_noted
        engine.train_batch(batch)
        note = cs.setup_ledger()["notes"]["_train_batch_body"]
        assert note["remat_policy"] == DEFAULT_POLICY
        assert note["temp_size_in_bytes"] > 0
        before = cs.setup_ledger()["events"]
        engine._note_step_program(batch)
        after = cs.setup_ledger()["events"]
        assert [after[p] - before[p]
                for p in ("lower", "compile", "cache_load")] == [0, 0, 0]
    finally:
        engine.close()


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compilation cache in ``tmp_path`` (the suite runs
    with it off: ``tests/conftest.py``)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (True, str(tmp_path), 0.0, 0)):
        jax.config.update(n, v)
    cc.reset_cache()
    yield tmp_path
    for n, v in old.items():
        jax.config.update(n, v)
    cc.reset_cache()


def test_a_real_jit_reads_a_miss_then_a_hit(persistent_cache):
    assert cs.install_compile_listener()

    def setup_ledger_probe(x):
        return jnp.tanh(x) * 3.0 + 1.0

    x = jnp.arange(8.0)
    name = "jit(setup_ledger_probe)"

    def read():
        got = cs.setup_ledger()
        return ({part: got["programs"].get((part, name), (0, 0.0))
                 for part in ("compile", "cache_load")},
                got["cache_misses"], cs.compile_counts()[0])

    t0 = time.perf_counter()
    jax.jit(setup_ledger_probe)(x).block_until_ready()
    first, misses1, n1 = read()
    assert first["compile"][0] == 1 and first["compile"][1] > 0.0
    assert first["cache_load"] == (0, 0.0)
    in_stretch = cs.setup_ledger(t0, time.perf_counter())
    assert in_stretch["cache_misses"] == 1
    assert in_stretch["parts"]["compile"] > 0.0
    assert in_stretch["parts"]["trace"] > 0.0
    assert in_stretch["parts"]["lower"] > 0.0

    jax.clear_caches()
    t1 = time.perf_counter()
    jax.jit(setup_ledger_probe)(x).block_until_ready()
    second, misses2, n2 = read()
    assert second["compile"] == first["compile"]
    assert second["cache_load"][0] == 1 and second["cache_load"][1] > 0.0
    assert misses2 == misses1
    assert n2 == n1 + 1  # compile_counts() counts the load, as it did
    warm = cs.setup_ledger(t1, time.perf_counter())
    assert warm["cache_misses"] == 0 and warm["parts"]["compile"] == 0.0
    assert warm["parts"]["cache_load"] > 0.0


# ------------------------------------------------------------------- cost
def test_what_one_listener_callback_costs(ledger, ring, monkeypatch):
    """The figure PERF.md section 6 multiplies by the ledger's own count
    of events: a traced primitive's event through the listener (too short
    for the ring), then its enclosing function's, on the real clock."""
    monkeypatch.setattr(cs, "time", time)
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        cs._on_duration_event(EVENT["trace"], 1e-7, fun_name="add")
    per_event = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    cs._on_duration_event(EVENT["trace"], time.perf_counter() - t0 + 1.0,
                          fun_name="body")
    absorb = time.perf_counter() - t0
    print(f"setup ledger: {1e6 * per_event:.2f} us a nested trace event, "
          f"{1e6 * absorb / n:.2f} us more each when the outer absorbs them")
    assert cs.setup_ledger()["kept"] == 1
    assert per_event < 1e-4
