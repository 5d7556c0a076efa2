"""``readers/part_ms.py`` on a trace recorded on a v5e beside the region
tables of the process that ran it (fixtures/small_parts_v5e.*: three fused
train steps of a small Mistral through ``deepspeed_tpu.initialize``;
``.expected.json`` says how it was recorded and holds what the reader read
from it then), and on hand-built operations."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import manifest as manifest_mod  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

FIX = os.path.join(os.path.dirname(HERE), "fixtures")
STEP = "bench.train_batch"
PARTS = {  # the training metrics' parts, as layer_metrics/*.json give them
    "train_dense_gemm_ms_per_step": ["attn_qkv", "attn_out", "mlp"],
    "train_head_loss_ms_per_step": ["head", "loss"],
    "optimizer_ms_per_step": ["optimizer"],
    "conv_mixer_ms_per_step": ["conv_mixer"],
    "moe_route_ms_per_step": ["router", "moe_route", "moe_glue"],
    "train_stack_other_ms_per_step": ["stack", "norm", "attn_glue", "embed"],
    "xla_unscoped_ms_per_step": ["unscoped"],
}


@pytest.fixture(scope="module")
def man():
    return manifest_mod.Manifest()


@pytest.fixture(scope="module")
def part_ms(man):
    return man.module("readers", "part_ms")


@pytest.fixture(scope="module")
def recorded(part_ms):
    from deepspeed_tpu.telemetry import regions

    path = os.path.join(FIX, "small_parts_v5e.xplane.pb")
    with open(os.path.join(FIX, "small_parts_v5e.tables.json")) as f:
        rows = json.load(f)["rows"]
    with open(os.path.join(FIX, "small_parts_v5e.expected.json")) as f:
        want = json.load(f)
    tables = {}
    for program, name, shape, region, phase, mixed in rows:
        tables.setdefault(program, {"program": program, "rows": {}})[
            "rows"][name, shape] = (region, phase, mixed)
    index = regions.region_index(list(tables.values()))
    red = tr.reduce_file(path)
    ops = part_ms.load_ops(path, red.window(), index, regions.lookup_region)
    return {"trace": red, "part_ops": ops, "result": {}}, want, index


def test_the_metric_files_say_what_this_test_says(man):
    for name, parts in PARTS.items():
        args = man.layer_metric(name)["args"]
        assert args["parts"] == parts and args["per_span"] == STEP
        assert args["phases"] == ["forward", "backward"]
    replay = man.layer_metric("train_replay_ms_per_step")["args"]
    assert replay["parts"] == [] and replay["phases"] == ["replay"]
    # no part is read by two of the training metrics
    flat = [p for parts in PARTS.values() for p in parts]
    assert len(flat) == len(set(flat))


def test_the_parts_and_the_unscoped_rest_sum_to_xla_compute(man, part_ms,
                                                            recorded):
    ctx, want, _ = recorded
    whole = man.module("readers", "op_ms").read(
        ctx, **man.layer_metric("xla_compute_ms_per_step")["args"])
    assert whole == pytest.approx(want["xla_compute_ms_per_step"], rel=1e-9)
    got = {name: part_ms.read(ctx, parts=parts, per_span=STEP,
                              phases=["forward", "backward"])
           for name, parts in PARTS.items()}
    got["train_replay_ms_per_step"] = part_ms.read(
        ctx, phases=["replay"], per_span=STEP)
    # the model has no convolution mixer and no experts: no reading there
    assert got.pop("conv_mixer_ms_per_step") is None
    assert got.pop("moe_route_ms_per_step") is None
    assert all(v is not None and v > 0.0 for v in got.values())
    assert sum(got.values()) == pytest.approx(whole, rel=1e-9)


def test_the_recorded_numbers_repeat(part_ms, recorded):
    ctx, want, _ = recorded
    ops = ctx["part_ops"]
    assert len(ops) == want["operations"]
    by_part = {}
    for o in ops:
        by_part[o.part] = by_part.get(o.part, 0.0) + o.self_s
    assert by_part == pytest.approx(want["seconds_by_part"], rel=1e-9)
    assert {"mlp", "attn_qkv", "attn_out", "head", "loss", "optimizer",
            "stack", "norm", "embed"} <= set(by_part)
    assert part_ms.read(ctx, phases=["replay"], per_span=STEP) == \
        pytest.approx(want["replay_ms_per_step"], rel=1e-9)
    assert part_ms.read(ctx, parts=PARTS["train_dense_gemm_ms_per_step"],
                        phases=["forward", "backward"], per_span=STEP) == \
        pytest.approx(want["dense_gemm_ms_per_step"], rel=1e-9)
    assert part_ms.read(ctx, parts=["optimizer"], per_span=STEP) == \
        pytest.approx(want["optimizer_ms_per_step"], rel=1e-9)
    share = part_ms.read(ctx, parts=["unscoped"], share=True)
    assert share == pytest.approx(want["unscoped_share"], rel=1e-9)
    assert sum(o.self_s for o in ops if o.mixed) == \
        pytest.approx(want["mixed_s"], rel=1e-9)
    # neither a kernel nor a collective is in the population
    assert not any("dstpu_" in o.name or tr.is_collective(o.name)
                   for o in ops)
    # every operation lies inside a program's run, the step's nearly all
    assert all(o.program for o in ops)
    step = sum(o.self_s for o in ops
               if o.program.startswith("jit__train_batch_body("))
    assert step > 0.99 * sum(o.self_s for o in ops)
    line = part_ms.detail_line(ops, 1, 0.5, 0.25)
    assert line.startswith("parts: tables in 0.500 s, trace re-read in "
                           "0.250 s; ")
    assert "longest unscoped: " in line and "% under mixed fusions" in line


def _op(part_ms, name, start, end, program, part, phase="forward",
        mixed=False):
    op = part_ms.PartOp(name, start, end)
    op.text, op.device, op.program = f"%{name} = f32[8] fusion()", "d", program
    op.part, op.phase, op.mixed = part, phase, mixed
    return op


def test_the_three_divisions_and_the_share(part_ms):
    dev = "/device:TPU:0"
    steps = [tr.Op("bench.step", 0.0, 5.0), tr.Op("bench.step", 5.0, 10.0)]
    runs = [tr.Op("jit__decode_and_sample(7)", 0.0, 2.0),
            tr.Op("jit__lambda(9)", 2.0, 5.0),
            tr.Op("jit__decode_and_sample(7)", 5.0, 7.0)]
    red = tr.Reduced({dev: [tr.Op("fusion.1", 0.0, 1.0)]}, {dev: runs}, steps)
    dec, chunk = "jit__decode_and_sample(7)", "jit__lambda(9)"
    ops = [_op(part_ms, "fusion.1", 0.0, 1.0, dec, "mlp"),
           _op(part_ms, "fusion.2", 1.0, 2.0, dec, "unscoped"),
           _op(part_ms, "fusion.1", 2.0, 4.0, chunk, "mlp"),
           _op(part_ms, "fusion.3", 4.0, 5.0, chunk, "head"),
           _op(part_ms, "fusion.1", 5.0, 6.5, dec, "mlp", mixed=True)]
    ctx = {"trace": red, "part_ops": ops,
           "result": {"steps": [{"chunk_tokens": 512}, {"chunk_tokens": 0}]}}
    # per run of the decode program: (1.0 + 1.5) s over two runs
    assert part_ms.read(ctx, parts=["mlp"], per_module=True,
                        module_contains=["jit__decode"]) == \
        pytest.approx(1250.0)
    # per thousand chunk tokens, inside the chunk program alone
    assert part_ms.read(ctx, parts=["mlp"], module_contains=["jit__lambda"],
                        span="bench.step", per_keys=["chunk_tokens"],
                        per_scale=0.001) == pytest.approx(2000.0 / 0.512)
    # per event of a span, every program
    assert part_ms.read(ctx, parts=["mlp", "head"], per_span="bench.step") \
        == pytest.approx(1e3 * 5.5 / 2)
    assert part_ms.read(ctx, parts=["unscoped"], share=True) == \
        pytest.approx(100.0 * 1.0 / 6.5)
    # a share of nothing unscoped is 0, a reading; a part the programs do
    # not have is none
    assert part_ms.read(ctx, parts=["unscoped"], share=True,
                        module_contains=["jit__lambda"]) == 0.0
    assert part_ms.read(ctx, parts=["router"], per_span="bench.step") is None
    # no tables at all (a program from before them): no reading
    assert part_ms.read({"trace": red, "part_ops": None, "result": {}},
                        parts=["mlp"], per_span="bench.step") is None
