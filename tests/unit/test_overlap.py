"""Compute/collective overlap tests (runtime/zero/overlap.py,
comm/collectives/bucketer.py, telemetry/overlap.py; docs/COMM.md
"Overlap & scheduling").

Fast tier: the bucketer as a pure function, the plan builder, the
exposure accounting math, the latency-hiding flag helpers, and the
``grad-overlap`` lint rule.  Slow tier (engine oracles, like
test_zeropp): bit-exact loss parity of the overlap scheduling knobs at
ZeRO 1 and 3 — with and without int8 compression — plus the in-loop
collective structure in compiled HLO.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.collectives.bucketer import (assign_buckets,
                                                     coalesce_flat,
                                                     leaf_bytes, split_flat)
from deepspeed_tpu.models.llama import llama_model
from deepspeed_tpu.parallel.mesh import MeshConfig, initialize_topology

SEQ = 16
VOCAB = 64


def _engine(zero_extra, mesh=None, n_layers=4, **model_over):
    model = llama_model("tiny", max_seq_len=SEQ, vocab_size=VOCAB,
                        n_layers=n_layers, attn_impl="xla", **model_over)
    mesh = mesh or {"data": 8}
    initialize_topology(MeshConfig(**mesh), jax.devices()[:8])
    cfg = {"train_micro_batch_size_per_gpu": 4,
           "gradient_accumulation_steps": 1,
           "optimizer": {"type": "Adam", "params": {"lr": 5e-3}},
           "zero_optimization": dict(zero_extra),
           "mesh": mesh}
    return deepspeed_tpu.initialize(
        model=model, config=cfg, topology=deepspeed_tpu.get_topology())[0]


def _ids(n, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, VOCAB, (1, n, SEQ)).astype(np.int32))


def _losses(engine, steps=4, bs=8):
    return [float(engine.train_batch({"input_ids": _ids(bs, seed=i)}))
            for i in range(steps)]


# --------------------------------------------------------------- bucketer
def test_assign_buckets_properties():
    """Deterministic, order-stable, size-bounded, exhaustive."""
    sizes = [100, 50, 900, 10, 10, 500, 2000, 1]
    buckets = assign_buckets(sizes, 1000)
    # same input -> same output (pure function of the flatten order)
    assert buckets == assign_buckets(sizes, 1000)
    # covers every index exactly once, in order
    flat = [i for b in buckets for i in b]
    assert flat == list(range(len(sizes)))
    # size bound: a bucket closes once it reaches the target, so no
    # bucket exceeds target + its last (largest-possible) leaf
    for b in buckets:
        total = sum(sizes[i] for i in b)
        assert total < 1000 + max(sizes) or len(b) == 1
    # bucket_bytes <= 0 -> per-leaf (the pre-bucketing behavior)
    assert assign_buckets(sizes, 0) == [[i] for i in range(len(sizes))]
    assert assign_buckets([], 1000) == []


def test_coalesce_split_roundtrip():
    rng = np.random.RandomState(0)
    leaves = [jnp.asarray(rng.randn(4, 6).astype(np.float32)),
              jnp.asarray(rng.randn(7).astype(np.float32)),
              jnp.asarray(rng.randn(2, 3, 5).astype("bfloat16"))]
    flat, layout = coalesce_flat(leaves)
    assert flat.dtype == jnp.float32
    assert flat.size == sum(l.size for l in leaves)
    back = split_flat(flat, layout, [l.dtype for l in leaves])
    for a, b in zip(leaves, back):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert leaf_bytes(jnp.zeros((3, 4), jnp.bfloat16)) == 24


# ------------------------------------------------------------- plan build
def test_overlap_plan_build_and_struct(devices8):
    e = _engine({"stage": 1, "overlap_grad_reduce": True})
    plan = e._overlap_plan
    assert plan is not None
    # every layer leaf assigned to exactly one bucket, in order
    n = len(plan.paths)
    assert sorted(i for b in plan.buckets for i in b) == list(range(n))
    assert all(d is None for d in plan.gather_dims)  # stage 1: no gathers
    struct = e._overlap_struct
    assert struct["overlapped_bytes"] > 0
    assert struct["total_bytes"] > struct["overlapped_bytes"]  # embed tail
    rep = e.overlap_report()
    assert 0.0 < rep.overlapped_fraction < 1.0
    assert rep.buckets == len(plan.buckets)
    assert rep.exposed_seconds_per_step > 0

    # bucket_mb=0 -> per-leaf buckets
    e0 = _engine({"stage": 1, "overlap_grad_reduce": True,
                  "overlap_bucket_mb": 0})
    assert len(e0._overlap_plan.buckets) == len(e0._overlap_plan.paths)


def test_overlap_plan_stage3_gather_dims(devices8):
    e = _engine({"stage": 3, "overlap_grad_reduce": True})
    plan = e._overlap_plan
    assert plan is not None
    # the big matmul leaves must enter the body as ZeRO shards with an
    # explicit gather dim; their in-body spec shards exactly that dim
    gathered = [d for d in plan.gather_dims if d is not None]
    assert len(gathered) >= 7, plan.gather_dims
    for spec, d in zip(plan.leaf_specs, plan.gather_dims):
        if d is not None:
            assert tuple(spec)[d] == "data"


def test_overlap_disabled_reasons(devices8):
    # qgZ + overlap now COMPOSES (compressed overlap, docs/COMM.md):
    # the wrap takes the exchange with int8 + EF in-loop...
    e = _engine({"stage": 1, "overlap_grad_reduce": True,
                 "zero_quantized_gradients": True})
    assert e._overlap_plan is not None
    assert e._overlap_plan.compression is not None
    assert e._overlap_plan.error_feedback
    assert "overlap" in e.state.comm_errors
    # ...unless overlap_compression=False forces the exact wrap, which
    # stands down under qgZ exactly as before (the reducers own it)
    e0 = _engine({"stage": 1, "overlap_grad_reduce": True,
                  "zero_quantized_gradients": True,
                  "overlap_compression": False})
    assert e0._overlap_plan is None
    assert e0._overlap_struct["overlapped_bytes"] == 0
    # non-transformer models have no hook point
    from deepspeed_tpu.analysis.contracts import _mlp_spec

    initialize_topology(MeshConfig(data=8), jax.devices()[:8])
    e2, *_ = deepspeed_tpu.initialize(model=_mlp_spec(), config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 1, "overlap_grad_reduce": True}})
    assert e2._overlap_plan is None and e2._overlap_struct is None


# ------------------------------------------------------------- accounting
def test_overlap_reports():
    from deepspeed_tpu.telemetry.overlap import (interconnect_bytes_per_s,
                                                 report_from_spans,
                                                 structural_report)

    struct = {"total_bytes": 1000, "overlapped_bytes": 900, "buckets": 3}
    rep = structural_report(struct, world=8, device_kind="cpu")
    assert rep.overlapped_fraction == pytest.approx(0.9)
    assert rep.exposed_bytes == 100
    # bus factor 2(n-1)/n for all_reduce over the nominal cpu bandwidth
    assert rep.exposed_seconds_per_step == pytest.approx(
        100 * 2 * 7 / 8 / interconnect_bytes_per_s("cpu"))
    assert structural_report(struct, world=1) is None
    assert structural_report(None, world=8) is None

    # span-derived view: bucket events dedupe by index across retraces
    from deepspeed_tpu.telemetry.spans import SpanRecorder

    rec = SpanRecorder()
    for _trace in range(2):
        rec.event("grad_bucket_reduce", cat="comm", bytes=450, bucket=0,
                  overlapped=True)
        rec.event("grad_bucket_reduce", cat="comm", bytes=450, bucket=1,
                  overlapped=True)
        rec.event("grad_tail_reduce", cat="comm", bytes=100,
                  overlapped=False)
    rep2 = report_from_spans(rec, world=8, device_kind="cpu")
    assert rep2.total_bytes == 1000 and rep2.overlapped_bytes == 900
    assert rep2.buckets == 2
    assert report_from_spans(SpanRecorder(), world=8) is None


# -------------------------------------------------------------- lint rule
def test_grad_overlap_lint_rule(tmp_path):
    import os

    from deepspeed_tpu.analysis import lint

    rel = os.path.join("deepspeed_tpu", "runtime", "zero", "zeropp.py")
    bad = tmp_path / "zeropp.py"
    bad.write_text(
        "def quantized_grad_reduce(grads, specs, mesh):\n"
        "    return [reduce_one(g) for g in grads]\n")
    out = lint.scan_file(str(bad), rel)
    assert any(v.rule == "grad-overlap" and "monolithic" in v.message
               for v in out), out
    # the compressed in-loop reducer has the same contract: a rewrite
    # that quantizes + reduces leaf-by-leaf without the shared bucketer
    # (a monolithic quantized reduce reappearing) fails BY NAME
    rel_ov = os.path.join("deepspeed_tpu", "runtime", "zero", "overlap.py")
    bad_ov = tmp_path / "overlap.py"
    bad_ov.write_text(
        "def _compressed_bucket_reduce(leaves, error, spec, axis, inner):\n"
        "    return [quantized_all_reduce(l, spec) for l in leaves], None\n")
    out_ov = lint.scan_file(str(bad_ov), rel_ov)
    assert any(v.rule == "grad-overlap" and "quantized" in v.message
               for v in out_ov), out_ov
    # the real tree is clean (also enforced package-wide by tier-1's
    # dstpu_lint run; this pins the rule itself)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    for r in (rel, rel_ov):
        real = lint.scan_file(os.path.join(root, r), r)
        assert not [v for v in real if v.rule == "grad-overlap"]


# -------------------------------------------------- engine oracles (slow)
@pytest.mark.slow
def test_overlap_bit_exact_and_parity_zero1(devices8):
    """The overlap scheduling knobs are pure scheduling: bucketed ==
    unbucketed BIT-EXACT.  vs the legacy GSPMD step the wrap pins a
    canonical per-shard summation order, so parity is reassociation-
    sized (GSPMD's own strategy already differs between stages at
    HEAD)."""
    l_off = _losses(_engine({"stage": 1}))
    l_on = _losses(_engine({"stage": 1, "overlap_grad_reduce": True}))
    l_unb = _losses(_engine({"stage": 1, "overlap_grad_reduce": True,
                             "overlap_bucket_mb": 0}))
    assert l_on == l_unb, "bucketing changed the math"
    for a, b in zip(l_off, l_on):
        assert abs(a - b) / max(abs(a), 1e-9) < 1e-4, (l_off, l_on)
    assert l_on[0] == l_off[0], "forward pass must be bit-identical"


@pytest.mark.slow
def test_overlap_bit_exact_zero3_and_prefetch(devices8):
    l_on = _losses(_engine({"stage": 3, "overlap_grad_reduce": True}))
    l_pf = _losses(_engine({"stage": 3, "overlap_grad_reduce": True,
                            "zero3_param_prefetch": True}))
    assert l_on == l_pf, "the 2x-unrolled prefetch changed the math"
    l_off = _losses(_engine({"stage": 3}))
    for a, b in zip(l_off, l_on):
        assert abs(a - b) / max(abs(a), 1e-9) < 1e-4, (l_off, l_on)


@pytest.mark.slow
def test_overlap_bit_exact_with_int8_qgz(devices8):
    """With qgZ + overlap_compression=False the explicit bucketed
    reducers own the exchange and the wrap stands down — the overlap
    flag must not change a single bit on that arm.  The DEFAULT compose
    (compressed overlap) is covered by test_compressed_overlap_*."""
    z = {"stage": 1, "zero_quantized_gradients": True}
    l_off = _losses(_engine(dict(z)))
    l_on = _losses(_engine(dict(z, overlap_grad_reduce=True,
                                overlap_compression=False)))
    assert l_on == l_off


@pytest.mark.slow
def test_overlap_stands_down_for_qwz_stage3(devices8):
    e = _engine({"stage": 3, "zero_quantized_weights": True,
                 "overlap_grad_reduce": True})
    assert e._overlap_plan is None  # qwZ owns the stage-3 gathers
    ls = _losses(e)
    assert np.isfinite(ls).all()


def _hlo_of(e, bs=8):
    with e.topology.mesh:
        return e._train_batch.lower(
            e.state, {"input_ids": _ids(bs)}, jax.random.PRNGKey(0)
        ).compile().as_text()


def _loop_collectives(hlo):
    """{kind: (in_loop, top_level)} by reachability from while bodies."""
    comps, name = {}, None
    for ln in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w\.\-]+) \(.*\{", ln)
        if m:
            name = m.group(1)
            comps[name] = []
        if name:
            comps[name].append(ln)
    bodies = set(re.findall(r"body=%([\w\.\-]+)", hlo))
    reach = set(bodies)
    frontier = list(bodies)
    while frontier:
        c = frontier.pop()
        joined = "\n".join(comps.get(c, []))
        for o in comps:
            if o not in reach and re.search(
                    rf"%{re.escape(o)}(?![\w.\-])", joined):
                reach.add(o)
                frontier.append(o)
    out = {}
    for kind in ("all-reduce", "all-gather", "reduce-scatter"):
        inside = outside = 0
        for k, v in comps.items():
            t = "\n".join(v)
            c = len(re.findall(
                rf"=\s*(?:\([^()]*\)|\S+)\s+{kind}(?:-start)?\(", t))
            if k in reach:
                inside += c
            else:
                outside += c
        out[kind] = (inside, outside)
    return out


@pytest.mark.slow
def test_overlap_in_loop_collective_structure(devices8):
    """THE tentpole property: the grad exchange rides the layer loops.
    Stage 1: one explicit psum per layer leaf inside the backward scan
    (the off arm reduces the stacked grads at top level).  Stage 3: the
    wrap's explicit reduce-scatters and all-gathers live in the loops;
    the off arm has no reduce-scatter anywhere."""
    on1 = _loop_collectives(_hlo_of(_engine(
        {"stage": 1, "overlap_grad_reduce": True})))
    # >= one in-loop all-reduce per layer leaf (9 on this llama block)
    assert on1["all-reduce"][0] >= 9, on1

    e3 = _engine({"stage": 3, "overlap_grad_reduce": True,
                  "zero3_param_prefetch": True})
    on3 = _loop_collectives(_hlo_of(e3))
    off3 = _loop_collectives(_hlo_of(_engine({"stage": 3})))
    assert on3["reduce-scatter"][0] > 0, on3
    assert on3["reduce-scatter"][1] == 0, on3  # none escape the loops
    assert on3["all-gather"][0] > 0, on3
    assert off3["reduce-scatter"] == (0, 0), off3


@pytest.mark.slow
def test_overlap_gauges_and_events(devices8):
    """Boundary telemetry: the overlapped-fraction gauge and the
    exposure counter publish, and the span ring carries the bucket /
    tail collective events the accountant reads."""
    from deepspeed_tpu.telemetry.spans import (SpanRecorder,
                                               set_span_recorder)

    rec = SpanRecorder()
    set_span_recorder(rec)
    try:
        model = llama_model("tiny", max_seq_len=SEQ, vocab_size=VOCAB,
                            n_layers=2, attn_impl="xla")
        initialize_topology(MeshConfig(data=8), jax.devices()[:8])
        engine, *_ = deepspeed_tpu.initialize(
            model=model,
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": 1,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 1,
                                          "overlap_grad_reduce": True},
                    "steps_per_print": 1,
                    "telemetry": {"enabled": True}},
            topology=deepspeed_tpu.get_topology())
        engine.train_batch({"input_ids": _ids(8)})
        assert 0.0 < engine._m_overlap_frac.value() < 1.0
        assert engine._m_exposed.value() > 0
        names = {sp.name for sp in rec.spans()}
        assert "grad_bucket_reduce" in names
        assert "grad_tail_reduce" in names
        from deepspeed_tpu.telemetry.overlap import report_from_spans

        rep = report_from_spans(rec, world=8)
        assert rep is not None and 0.0 < rep.overlapped_fraction < 1.0
        engine.close()
    finally:
        set_span_recorder(None)


@pytest.mark.slow
def test_bucketed_all_reduce_one_residual_per_bucket(devices8):
    """comm/collectives.bucketed_all_reduce: leaves coalesce into flat
    buckets — one collective chain and ONE error-feedback residual per
    bucket — and the reduced values match the exact mean within codec
    tolerance."""
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.comm.collectives import (CompressionSpec,
                                                bucketed_all_reduce)
    from deepspeed_tpu.utils.jax_compat import shard_map

    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    rng = np.random.RandomState(0)
    # ~3 leaves / ~two buckets at a 4 KiB target
    leaves = [rng.randn(8, 16, 16).astype(np.float32),
              rng.randn(8, 7).astype(np.float32),
              rng.randn(8, 33).astype(np.float32)]
    spec = CompressionSpec(format="int8", error_feedback=True)

    def body(*shards):
        outs, errs = bucketed_all_reduce(
            [s[0] for s in shards], op="mean", axis="data", spec=spec,
            bucket_bytes=1 << 10)
        return tuple(outs) + tuple(e[None] for e in errs)

    n_buckets = 2
    fn = shard_map(
        body, mesh=mesh,
        in_specs=tuple(P("data") for _ in leaves),
        out_specs=tuple(P() for _ in leaves)
        + tuple(P("data") for _ in range(n_buckets)),
        check_vma=False)
    with mesh:
        out = fn(*[jnp.asarray(l) for l in leaves])
    reduced, errors = out[:len(leaves)], out[len(leaves):]
    assert len(errors) == n_buckets
    for l, r in zip(leaves, reduced):
        exact = l.mean(axis=0)
        err = np.abs(np.asarray(r) - exact).max()
        assert err <= np.abs(l).max() / 50, err  # int8 blockwise tolerance
    # per-bucket residual structure is stable: feeding the residuals
    # back round-trips (shape contract of the EF API)
    assert errors[0].shape[0] == 8


# ------------------------------------------- compressed overlap (slow)
@pytest.mark.slow
def test_compressed_overlap_parity_and_bucketing_zero1(devices8):
    """THE PR-15 tentpole contract at stage 1: qgZ + overlap composes —
    the in-loop exchange is int8 + EF, deterministic, bucketed ==
    unbucketed BIT-EXACT (block-aligned coalescing + layout-stable
    hop-1 residuals), and loss parity vs the fp32-overlap arm is codec-
    sized (the PR-11 tolerance)."""
    z = {"stage": 1, "overlap_grad_reduce": True,
         "zero_quantized_gradients": True}
    l_c = _losses(_engine(dict(z)))
    l_c2 = _losses(_engine(dict(z)))
    assert l_c == l_c2, "compressed overlap is not deterministic"
    l_u = _losses(_engine(dict(z, overlap_bucket_mb=0)))
    assert l_c == l_u, "bucketing changed the compressed math"
    l_fp = _losses(_engine({"stage": 1, "overlap_grad_reduce": True}))
    assert l_c[0] == l_fp[0], "forward must be bit-identical"
    par = max(abs(a - b) / max(abs(a), 1e-9) for a, b in zip(l_fp, l_c))
    assert par < 0.05, (l_fp, l_c)


@pytest.mark.slow
def test_compressed_overlap_stage3_and_hier(devices8):
    """Stage 3 (overlap_compression knob): the in-loop psum_scatters
    become quantized reduce-scatters, per-leaf regardless of bucketing
    (bit-exact), at codec-sized parity.  Hierarchical: the in-loop
    reduce takes the three-hop shape and stays parity-close."""
    z3 = {"stage": 3, "overlap_grad_reduce": True,
          "zero3_param_prefetch": True, "overlap_compression": "int8"}
    e3 = _engine(dict(z3))
    assert e3._overlap_plan.compression is not None
    assert sum(d is not None for d in e3._overlap_plan.gather_dims) >= 7
    l3 = _losses(e3)
    assert l3 == _losses(_engine(dict(z3, overlap_bucket_mb=0)))
    l3fp = _losses(_engine({"stage": 3, "overlap_grad_reduce": True,
                            "zero3_param_prefetch": True}))
    par = max(abs(a - b) / max(abs(a), 1e-9) for a, b in zip(l3fp, l3))
    assert par < 0.05, (l3fp, l3)

    zh = {"stage": 1, "overlap_grad_reduce": True,
          "zero_quantized_gradients": True,
          "zero_hierarchical_grad_reduce": True, "zero_hierarchy_inner": 2}
    eh = _engine(dict(zh))
    assert eh._overlap_plan.hier_inner == 2
    lh = _losses(eh)
    l_c = _losses(_engine({"stage": 1, "overlap_grad_reduce": True,
                           "zero_quantized_gradients": True}))
    par_h = max(abs(a - b) / max(abs(a), 1e-9) for a, b in zip(l_c, lh))
    assert par_h < 0.05, (l_c, lh)


@pytest.mark.slow
def test_compressed_overlap_in_loop_s8(devices8):
    """The wire claim in compiled HLO: with compression on, the layer
    loops carry s8-operand collectives and the stage<=2 per-leaf fp
    psums are GONE from the loops (replaced by the two-hop, whose codes
    ride all_to_all/all_gather)."""
    from deepspeed_tpu.analysis.contracts import s8_collective_count

    e = _engine({"stage": 1, "overlap_grad_reduce": True,
                 "zero_quantized_gradients": True})
    hlo = _hlo_of(e)
    assert s8_collective_count(hlo) >= 1
    on1 = _loop_collectives(hlo)
    fp1 = _loop_collectives(_hlo_of(_engine(
        {"stage": 1, "overlap_grad_reduce": True})))
    # fp arm: >= 9 in-loop psums; compressed arm: the per-leaf psums are
    # replaced by the bucket's quantized exchange (far fewer all-reduces
    # in-loop; the remaining ones are the model's own e.g. norm/loss)
    assert on1["all-reduce"][0] < fp1["all-reduce"][0], (on1, fp1)


@pytest.mark.slow
def test_compressed_overlap_resume_parity(devices8):
    """The EF-residual lifecycle contract (chaos-drill shape): train,
    checkpoint mid-run, resume into a FRESH engine — the residuals ride
    TrainState.comm_errors through the checkpoint, so the post-resume
    steps are bit-identical to an uninterrupted run."""
    import tempfile

    import numpy as _np

    z = {"stage": 1, "overlap_grad_reduce": True,
         "zero_quantized_gradients": True}
    batches = [{"input_ids": _ids(8, seed=i)} for i in range(4)]
    e_ctrl = _engine(dict(z))
    ctrl = [float(e_ctrl.train_batch(b)) for b in batches]

    d = tempfile.mkdtemp()
    e1 = _engine(dict(z))
    part1 = [float(e1.train_batch(b)) for b in batches[:2]]
    r_saved = _np.asarray(jax.device_get(
        e1.state.comm_errors["overlap"]["b000"]))
    assert _np.abs(r_saved).max() > 0, "EF residual never populated"
    e1.save_checkpoint(d, tag="mid")
    e2 = _engine(dict(z))
    e2.load_checkpoint(d, tag="mid")
    r_loaded = _np.asarray(jax.device_get(
        e2.state.comm_errors["overlap"]["b000"]))
    assert (r_saved == r_loaded).all(), "residual round-trip not bit-exact"
    part2 = [float(e2.train_batch(b)) for b in batches[2:]]
    assert ctrl == part1 + part2, (ctrl, part1 + part2)


@pytest.mark.slow
def test_qgz_post_backward_ef_resume_parity(devices8):
    """Same lifecycle contract for the POST-backward qgZ path
    (grad_reduce_error_feedback): residuals live under
    comm_errors['reduce'] and checkpoint/resume keeps the trajectory
    bit-identical; the EF arm stays parity-close to plain qgZ."""
    import tempfile

    z = {"stage": 1, "zero_quantized_gradients": True,
         "grad_reduce_error_feedback": True}
    batches = [{"input_ids": _ids(8, seed=i)} for i in range(4)]
    e_ctrl = _engine(dict(z))
    ctrl = [float(e_ctrl.train_batch(b)) for b in batches]
    e_q = _engine({"stage": 1, "zero_quantized_gradients": True})
    lq = [float(e_q.train_batch(b)) for b in batches]
    par = max(abs(a - b) / max(abs(a), 1e-9) for a, b in zip(lq, ctrl))
    assert par < 0.05, (lq, ctrl)

    d = tempfile.mkdtemp()
    e1 = _engine(dict(z))
    part1 = [float(e1.train_batch(b)) for b in batches[:2]]
    assert "reduce" in e1.state.comm_errors
    e1.save_checkpoint(d, tag="mid")
    e2 = _engine(dict(z))
    e2.load_checkpoint(d, tag="mid")
    part2 = [float(e2.train_batch(b)) for b in batches[2:]]
    assert ctrl == part1 + part2, (ctrl, part1 + part2)


@pytest.mark.slow
def test_compressed_overlap_gauges(devices8):
    """The residual-bytes gauge publishes and the bucket events carry
    the compressed marker."""
    from deepspeed_tpu.telemetry.spans import (SpanRecorder,
                                               set_span_recorder)

    rec = SpanRecorder()
    set_span_recorder(rec)
    try:
        model = llama_model("tiny", max_seq_len=SEQ, vocab_size=VOCAB,
                            n_layers=2, attn_impl="xla")
        initialize_topology(MeshConfig(data=8), jax.devices()[:8])
        engine, *_ = deepspeed_tpu.initialize(
            model=model,
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": 1,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "zero_optimization": {
                        "stage": 1, "overlap_grad_reduce": True,
                        "zero_quantized_gradients": True},
                    "steps_per_print": 1,
                    "telemetry": {"enabled": True}},
            topology=deepspeed_tpu.get_topology())
        engine.train_batch({"input_ids": _ids(8)})
        assert engine._m_comp_residual.value() > 0
        rep = engine.overlap_report()
        assert rep.compression == "int8"
        assert rep.residual_bytes > 0
        ev = [sp for sp in rec.spans() if sp.name == "grad_bucket_reduce"]
        assert ev and any(sp.attrs.get("compressed") for sp in ev)
        engine.close()
    finally:
        set_span_recorder(None)


@pytest.mark.slow
def test_compressed_overlap_fp16_overflow_keeps_residuals_finite(devices8):
    """Review finding: an fp16 overflow step must not poison the carried
    EF residuals — the optimizer skip never touches comm_errors, so the
    engine gates the residual update on the same finiteness signal.  The
    2^20 initial dynamic scale overflows the first backwards;
    the residuals must stay finite throughout and training must
    recover once the scaler backs off."""
    model = llama_model("tiny", max_seq_len=SEQ, vocab_size=VOCAB,
                        n_layers=2, attn_impl="xla")
    initialize_topology(MeshConfig(data=8), jax.devices()[:8])
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "fp16": {"enabled": True, "initial_scale_power": 20},
                "zero_optimization": {"stage": 1,
                                      "overlap_grad_reduce": True,
                                      "zero_quantized_gradients": True}},
        topology=deepspeed_tpu.get_topology())
    for i in range(10):
        engine.train_batch({"input_ids": _ids(8, seed=i % 6)})
        res = np.asarray(jax.device_get(
            engine.state.comm_errors["overlap"]["b000"]))
        assert np.isfinite(res).all(), f"residuals poisoned at step {i}"
    assert int(engine.state.skipped_steps) >= 1, \
        "test premise broken: no overflow step ever happened"
    assert int(engine.state.step) >= 1, "training never recovered"
