"""Model regions (``telemetry/regions.py``): the scopes are metadata and
nothing else; the table a compiled program's text gives; what no table knows
is ``unscoped``; the timeline reads compute by region and still sums to the
wall; an engine leaves one note a distinct program, at its first dispatch,
and the tables outlive it.

Nothing here times anything: what a region's operations cost is a time on
the chip (PERF.md section 5).
"""

import contextlib
import os
import re
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceConfig, RaggedRequest)
from deepspeed_tpu.models import (lfm2_moe_model, mistral_model,  # noqa: E402
                                  phi4_flash_model, solar_open2_model)
from deepspeed_tpu.parallel.mesh import initialize_topology  # noqa: E402
from deepspeed_tpu.runtime.config import MeshConfig  # noqa: E402
from deepspeed_tpu.telemetry import regions  # noqa: E402
from deepspeed_tpu.telemetry.timeline import (categorize_op,  # noqa: E402
                                              decompose_events)

PS = 8
CUT = ("conv", "full_attention", "conv", "conv", "conv")


# ------------------------------------------------------------- tiny programs
def _train(model):
    """One fused train step of ``model`` through ``initialize``."""
    topo = initialize_topology(MeshConfig(data=1), devices=jax.devices()[:1])
    engine, *_ = deepspeed_tpu.initialize(model=model, topology=topo, config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 2, "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1}, "gradient_clipping": 1.0,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "mesh": {"data": 1}, "seed": 0, "steps_per_print": 10 ** 9})
    batch = np.random.default_rng(0).integers(0, 256, (2, 2, 24),
                                              dtype=np.int32)
    engine.train_batch(batch)
    return engine


def _serve(model, steps=None, **over):
    """A chunked prompt and a short one through a tiny serving engine."""
    cfg = dict(dtype="fp32", page_size=PS, max_pages_per_seq=16,
               prefill_chunk=16, max_seqs=4, num_pages=96)
    cfg.update(over)
    engine = InferenceEngineV2(model, RaggedInferenceConfig(**cfg), seed=0)
    rng = np.random.RandomState(0)
    for n in (40, 5):
        engine.put(RaggedRequest(prompt_ids=rng.randint(1, 200, n).tolist(),
                                 max_new_tokens=steps or 4))
    while engine.has_work():
        engine.step()
    return engine


def _lfm2(**over):
    return lfm2_moe_model("tiny", max_seq_len=24, layer_types=CUT,
                          dense_layers=1, moe_held_first=2, moe_held_count=4,
                          **over)


FAMILIES = {
    # family -> (its train step's model, its serving model and engine options)
    "dense": (lambda: mistral_model("tiny"),
              lambda: (mistral_model("tiny", max_seq_len=PS * 16), {})),
    "moe": (_lfm2,
            lambda: (solar_open2_model("tiny", moe_held_first=4,
                                       moe_held_count=4, max_seq_len=128),
                     {"num_pages": 80})),
    "hybrid": (lambda: _lfm2(remat=True),
               lambda: (phi4_flash_model("tiny", max_seq_len=PS * 32),
                        {"max_pages_per_seq": 32, "prefill_chunk": 32,
                         "num_pages": 160})),
}
PROGRAMS = {"train": "jit__train_batch_body", "chunk": "jit__lambda",
            "decode": "jit__decode_and_sample"}

_METADATA = re.compile(r", metadata=\{[^}]*\}")
_NAME = re.compile(r"%[\w.\-]+")


def _instructions(text):
    """A compiled program's computations and instructions, ``metadata={..}``
    stripped (the tables of files and frames in the header are metadata
    too), every name replaced by the order of its first appearance: an
    instruction is named after the last component of its ``op_name`` and
    numbered among those of its name (``%broadcast_in_dim.31``), so the
    numbers follow the metadata while the instructions, their operands,
    fusions and order do not."""
    lines = [_METADATA.sub("", ln) for ln in text.splitlines()]
    first = next(i for i, ln in enumerate(lines)
                 if ln.rstrip().endswith("{") and "(" in ln)
    order = {}
    return [_NAME.sub(lambda m: order.setdefault(m.group(0),
                                                 f"%{len(order)}"), ln)
            for ln in lines[first:]]


def _noted_texts(family, kind):
    """The compiled texts of the programs ``family``'s ``kind`` of engine
    dispatched, by the module's name (a chunk program's buckets are all
    ``jit__lambda``), in the order of their dispatch."""
    regions.reset_regions()
    jax.clear_caches()
    train, serve = FAMILIES[family]
    if kind == "train":
        engine = _train(train())
    else:
        model, over = serve()
        engine = _serve(model, **over)
    texts = {}
    for traced in regions._notes:
        text = traced.lower().compile().as_text()
        name = re.match(r"HloModule\s+([\w.\-]+)", text).group(1)
        texts.setdefault(name, []).append(text)
    engine.close()
    regions.reset_regions()
    return texts


@pytest.fixture(scope="module")
def texts():
    """``(family, train | serve) -> (the texts with scopes, without)``."""
    cache = {}

    def get(family, kind):
        if (family, kind) not in cache:
            scoped = _noted_texts(family, kind)
            real = regions._scope
            regions._scope = lambda name: contextlib.nullcontext()
            try:
                bare = _noted_texts(family, kind)
            finally:
                regions._scope = real
                jax.clear_caches()
            cache[family, kind] = (scoped, bare)
        return cache[family, kind]
    return get


# ------------------------------------------------- (a) scopes are metadata
@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_scopes_are_metadata(texts, family, program):
    scoped, bare = texts(family, "train" if program == "train" else "serve")
    name = PROGRAMS[program]
    assert len(scoped[name]) == len(bare[name]) >= 1
    for with_scopes, without in zip(scoped[name], bare[name]):
        assert "region." in with_scopes and "region." not in without
        assert _instructions(with_scopes) == _instructions(without)


# ----------------------------------------------------------- (b) the table
def _rows(texts, family, program="train"):
    scoped, _ = texts(family, "train" if program == "train" else "serve")
    name, rows = regions.parse_program_text(
        max(scoped[PROGRAMS[program]], key=len))
    assert name == PROGRAMS[program]
    return rows


def test_every_dot_of_a_train_step_has_its_region(texts):
    rows = _rows(texts, "dense")
    dots = {k: v for k, v in rows.items() if k[0].startswith("dot")}
    assert len(dots) >= 20
    # (the XLA form of attention, which the CPU tier runs, is attn_glue's)
    assert {v[0] for v in dots.values()} == {
        "attn_qkv", "attn_glue", "attn_out", "mlp", "head"}
    assert {v[1] for v in dots.values()} == {"forward", "backward"}
    by_region = {}
    for (region, phase, _mixed) in dots.values():
        by_region.setdefault(region, []).append(phase)
    # a product's transposes are two more products, in the backward pass
    for region in ("attn_qkv", "attn_out", "mlp", "head"):
        assert by_region[region].count("backward") == \
            2 * by_region[region].count("forward"), region


def test_the_adam_update_is_the_optimizers_and_a_replay_is_a_replay(texts):
    rows = _rows(texts, "dense")
    regions_of = {v[0] for v in rows.values()}
    assert {"embed", "norm", "stack", "loss", "optimizer"} <= regions_of
    assert not {v[1] for v in rows.values()} & {"replay"}
    # the update's square roots are the optimizer's and no one else's
    roots = [v for k, v in rows.items() if k[0].startswith("sqrt")]
    assert roots and all(v[0] == "optimizer" for v in roots)
    remat = _rows(texts, "hybrid")
    replayed = {v[0] for v in remat.values() if v[1] == "replay"}
    assert {"conv_mixer", "attn_qkv", "router", "moe_route", "norm"} \
        <= replayed
    # what is outside the recomputed blocks is never replayed
    assert not replayed & {"head", "loss", "optimizer", "embed"}


def test_serving_programs_have_their_regions(texts):
    decode = {v[0] for v in _rows(texts, "moe", "decode").values()}
    assert {"embed", "norm", "attn_qkv", "attn_glue", "attn_out", "router",
            "moe_route", "moe_glue", "shared_expert", "state_glue", "stack",
            "head", "sample"} <= decode
    chunk = {v[0] for v in _rows(texts, "hybrid", "chunk").values()}
    assert {"state_glue", "attn_glue", "mlp", "head"} <= chunk
    assert "sample" not in chunk  # (a chunk's token is picked on the host)


FUSED = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[8,64], param_1: bf16[64,32], param_2: bf16[8]) -> bf16[8,32] {
  %param_0 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0)
  %param_2 = bf16[8]{0} parameter(2)
  %broadcast.1 = bf16[8,64]{1,0} broadcast(%param_2), dimensions={0}, metadata={op_name="jit(step)/region.stack/while/body/region.norm/mul"}
  %multiply.1 = bf16[8,64]{1,0} multiply(%param_0, %broadcast.1), metadata={op_name="jit(step)/region.stack/while/body/region.norm/mul"}
  %param_1 = bf16[64,32]{1,0} parameter(1)
  %convolution.1 = bf16[8,32]{1,0} convolution(%multiply.1, %param_1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/region.stack/while/body/region.mlp/dot_general"}
  ROOT %tanh.1 = bf16[8,32]{1,0} tanh(%convolution.1), metadata={op_name="jit(step)/region.stack/while/body/region.mlp/tanh"}
}

%fused_computation.2 (param_0.1: bf16[8,32]) -> (bf16[8,32], f32[8]) {
  %param_0.1 = bf16[8,32]{1,0} parameter(0)
  %add.1 = bf16[8,32]{1,0} add(%param_0.1, %param_0.1), metadata={op_name="jit(step)/transpose(jvp(region.stack))/while/body/checkpoint/rematted_computation/region.norm/add"}
  %reduce.1 = f32[8]{0} reduce(%add.1), metadata={op_name="jit(step)/transpose(jvp(region.stack))/while/body/checkpoint/rematted_computation/region.norm/reduce_sum"}
  ROOT %tuple.1 = (bf16[8,32]{1,0}, f32[8]{0}) tuple(%add.1, %reduce.1)
}

ENTRY %main.1 (p0: bf16[8,64], p1: bf16[64,32], p2: bf16[8]) -> bf16[8,32] {
  %p0 = bf16[8,64]{1,0} parameter(0), metadata={op_name="x"}
  %p1 = bf16[64,32]{1,0} parameter(1)
  %p2 = bf16[8]{0} parameter(2)
  %fusion.7 = bf16[8,32]{1,0:T(8,128)(2,1)S(1)} fusion(%p0, %p1, %p2), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/region.stack/while/body/region.norm/mul"}
  %fusion.8 = (bf16[8,32]{1,0:T(8,128)(2,1)}, f32[8]{0:T(128)}) fusion(%fusion.7), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/transpose(jvp(region.stack))/while/body/checkpoint/rematted_computation/region.norm/add"}
  %copy.3 = bf16[8,32]{0,1} copy(%fusion.7)
  %copy-start.4 = (bf16[8]{0}, bf16[8]{0}, u32[]) copy-start(%p2)
  %copy-done.4 = bf16[8]{0} copy-done(%copy-start.4)
  %while.2 = bf16[8,32]{1,0} while(%copy.3), condition=%c, body=%b, metadata={op_name="jit(step)/transpose(jvp(region.stack))/while"}
  ROOT %dstpu_flash_fwd.3 = bf16[8,32]{1,0} custom-call(%while.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/region.attn_glue/dstpu_flash_fwd"}
}
"""


def test_a_fusion_takes_its_products_region_and_says_when_it_is_mixed():
    name, rows = regions.parse_program_text(FUSED)
    assert name == "jit_step"
    # a norm fused into a gemm: the gemm's region, though the fusion's own
    # metadata names the norm; mixed, since two regions wrote it
    assert rows["fusion.7", "bf16[8,32]"] == ("mlp", "forward", True)
    # no product and a tuple for a root: its own metadata; one region inside
    assert rows["fusion.8", "(bf16[8,32],f32[8])"] == ("norm", "replay",
                                                        False)
    assert rows["while.2", "bf16[8,32]"] == ("stack", "backward", False)
    # a copy XLA made itself, with no op_name: of the instruction it feeds
    assert rows["copy.3", "bf16[8,32]"] == ("stack", "backward", False)
    # ... and where nothing it feeds or is fed by names a region, unscoped
    assert rows["copy-done.4", "bf16[8]"] == ("unscoped", "forward", False)
    assert rows["copy-start.4", "(bf16[8],bf16[8],u32[])"][0] == "unscoped"
    assert rows["dstpu_flash_fwd.3", "bf16[8,32]"][0] == "attn_glue"
    # a device event's name is the instruction's text, layouts and all
    event = ("%fusion.7 = bf16[8,32]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8,64]"
             "{1,0:T(8,128)(2,1)} %p0, bf16[64,32]{1,0} %p1), kind=kOutput")
    assert regions.instruction_key(event) == ("fusion.7", "bf16[8,32]",
                                              "fusion")
    assert regions.op_name_entry("jit(f)/jvp(region.stack)/while/body/"
                                 "region.nosuch/mul") == ("unscoped",
                                                          "forward")
    with pytest.raises(KeyError):
        regions.region("nosuch")


# ------------------------------------------------------- (c) no table knows
def test_what_no_table_knows_and_what_two_tables_dispute_is_unscoped():
    a = {"program": "jit__lambda", "rows": {
        ("fusion.1", "bf16[8]"): ("mlp", "forward", False),
        ("fusion.2", "bf16[8]"): ("norm", "forward", False)}}
    b = {"program": "jit__lambda", "rows": {
        ("fusion.1", "bf16[8]"): ("mlp", "forward", True),
        ("fusion.2", "bf16[8]"): ("attn_qkv", "forward", False),
        ("fusion.2", "bf16[16]"): ("attn_qkv", "forward", False)}}
    c = {"program": "jit__decode_and_sample", "rows": {
        ("fusion.2", "bf16[8]"): ("head", "forward", False)}}
    index = regions.region_index([a, b, c])
    run = "jit__lambda(9018761753900999714)"

    def look(program, text):
        return regions.lookup_region(index, program, text)[0]
    assert look(run, "%fusion.1 = bf16[8]{0:T(128)} fusion()") == "mlp"
    assert look(run, "%fusion.2 = bf16[8]{0} fusion()") == "unscoped"
    assert look(run, "%fusion.2 = bf16[16]{0} fusion()") == "attn_qkv"
    assert look(run, "%fusion.3 = bf16[8]{0} fusion()") == "unscoped"
    assert look("jit__decode_and_sample(7)",
                "%fusion.2 = bf16[8]{0} fusion()") == "head"
    assert look("", "%fusion.1 = bf16[8]{0} fusion()") == "unscoped"
    # a name is never guessed from: on a TPU a gemm is fusion.167
    for name in ("dot_general.5", "fusion.matmul", "softmax.12", "copy.4",
                 "transpose.8"):
        assert categorize_op(name) == "unscoped"
    assert categorize_op("fusion.167", "mlp") == "mlp"
    assert categorize_op("dstpu_grouped_matmul.5", "moe_glue") == \
        "grouped_matmul"
    assert categorize_op("dstpu_mla_decode.2") == "attention"
    assert categorize_op("all-gather.3", "stack") == "all_gather"


# ------------------------------------------- (d) the timeline, by region
def test_decompose_with_a_lookup_still_sums_to_the_wall():
    index = regions.region_index([{"program": "jit_step", "rows": {
        ("fusion.1", "bf16[8]"): ("mlp", "forward", False),
        ("fusion.2", "bf16[8]"): ("norm", "forward", True)}}])

    def ev(name, ts, dur):
        return {"name": name, "ts": ts, "dur": dur, "program": "jit_step(3)",
                "text": f"%{name} = bf16[8]{{0}} fusion()"}
    events = [ev("fusion.1", 0.0, 0.4), ev("fusion.2", 0.4, 0.1),
              ev("fusion.9", 0.5, 0.1), ev("dstpu_flash_fwd.1", 0.6, 0.1),
              ev("dstpu_kda_step.4", 0.7, 0.05),
              ev("all-reduce.1", 0.3, 0.6)]
    d = decompose_events(events, 1.0, regions=index)
    cats = d["categories"]
    assert sum(cats.values()) == pytest.approx(1.0)
    assert cats["mlp"] == pytest.approx(0.4)
    assert cats["norm"] == pytest.approx(0.1)
    assert cats["unscoped"] == pytest.approx(0.1)
    assert cats["attention"] == pytest.approx(0.1)
    assert cats["kda_step"] == pytest.approx(0.05)
    assert cats["all_reduce"] == pytest.approx(0.15)  # the exposed part
    assert d["exposed_collective_seconds"] == pytest.approx(0.15)
    assert cats["host_gap"] == pytest.approx(0.1)
    # without a lookup every compute operation is unscoped, never a guess
    bare = decompose_events(events, 1.0)["categories"]
    assert bare["unscoped"] == pytest.approx(0.6)
    assert sum(bare.values()) == pytest.approx(1.0)


# --------------------------------------------- (e) one note a program, once
def test_first_dispatches_note_a_program_once_and_the_tables_outlive_close():
    regions.reset_regions()
    engine = _serve(mistral_model("tiny", max_seq_len=PS * 16), steps=50)
    noted = regions.noted_programs()
    assert noted == len(engine._lowered_parts) >= 3
    assert engine._step_id >= 50
    # fifty more steps over the same programs: the count stands still
    rng = np.random.RandomState(1)
    engine.put(RaggedRequest(prompt_ids=rng.randint(1, 200, 5).tolist(),
                             max_new_tokens=50))
    while engine.has_work():
        engine.step()
    assert regions.noted_programs() == noted
    engine.close()
    del engine
    tables = regions.region_tables()
    assert len(tables) == noted == regions.noted_programs()
    assert {t["program"] for t in tables} == {"jit__lambda",
                                              "jit__decode_and_sample"}
    assert all(t["rows"] and t["seconds"] > 0.0 for t in tables)
    # asked again: the same tables, nothing built
    assert [t["seconds"] for t in regions.region_tables()] == \
        [t["seconds"] for t in tables]
    index = regions.region_index()
    assert {"mlp", "attn_qkv", "head", "sample"} <= \
        {v[0] for v in index.values()}
    regions.reset_regions()


def test_a_train_engines_note_does_not_keep_the_engine():
    import gc
    import weakref

    regions.reset_regions()
    engine = _train(mistral_model("tiny"))
    assert regions.noted_programs() == 1
    engine.train_batch(np.zeros((2, 2, 24), np.int32))
    assert regions.noted_programs() == 1
    engine.close()
    alive = weakref.ref(engine)
    del engine
    gc.collect()
    assert alive() is None
    (table,) = regions.region_tables()
    assert table["program"] == "jit__train_batch_body"
    regions.reset_regions()
