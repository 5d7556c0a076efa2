"""Tier-1 gates for the static-analysis subsystem (docs/STATIC_ANALYSIS.md).

Three layers, mirroring test_metric_names.py's pattern of gating the tree
AND unit-testing the analyzer itself so a silently-broken scanner can't
green-light a bad tree:

* hazard lint: the package is clean (zero unexplained suppressions), and
  each rule fires on fixture snippets — including the acceptance
  mutation: an ``.item()`` seeded into the decode loop turns the lint
  red with a message naming the rule and the hot path.
* HLO contracts: extraction on a toy shard_map program yields the known
  collective counts; the checked-in goldens (>= 6 programs) hold against
  a fresh extraction on this CPU harness; a seeded all-gather mutation
  produces a named, actionable diff; extraction + golden serialization
  round-trips byte-identically (--update-goldens is idempotent); and the
  3-step train-loop replay pins recompiles-after-warmup at 0.
"""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _load_by_path(name, *rel):
    path = os.path.join(REPO, *rel)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _hazard_lint():
    return _load_by_path("dstpu_hazard_lint", "deepspeed_tpu", "analysis",
                         "lint.py")


# ------------------------------------------------------------ hazard lint
def test_package_hazard_clean_with_documented_suppressions():
    """The tree lints clean, and every allow marker carries a reason —
    the 'zero unexplained suppressions' acceptance gate."""
    hl = _hazard_lint()
    violations = hl.check(REPO)
    assert not violations, "\n".join(str(v) for v in violations)
    sups = hl.suppressions(REPO)
    assert sups, "expected documented suppressions from the remediation pass"
    for rel, ln, rules, reason in sups:
        assert reason.strip(), f"{rel}:{ln}: allow[{rules}] without a reason"


def _write_tree(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    (tmp_path / "tools").mkdir(exist_ok=True)
    return str(tmp_path)


def test_hazard_item_in_decode_loop_fails(tmp_path):
    """The acceptance mutation: an .item() seeded into the engine_v2 step
    loop exits non-zero, naming the rule and the hot path."""
    hl = _hazard_lint()
    root = _write_tree(tmp_path, {
        "deepspeed_tpu/inference/v2/engine_v2.py":
            "def _step_impl(self):\n"
            "    tok = logits.item()\n"
            "    return tok\n"})
    violations = hl.check(root)
    assert len(violations) == 1
    v = violations[0]
    assert v.rule == "host-sync" and ".item()" in v.message
    assert "_step_impl" in v.message
    # the same sync OUTSIDE any hot root passes (not reachable)
    root2 = _write_tree(tmp_path / "cold", {
        "deepspeed_tpu/inference/v2/engine_v2.py":
            "def _debug_dump(self):\n    return logits.item()\n"})
    assert hl.check(root2) == []


def test_hazard_blocking_socket_in_step_root_fails(tmp_path):
    """Seeded fail-by-name: a blocking socket ``recv`` reachable from a
    router/engine step root is a host-sync-class hazard (``socket-hot``)
    — the cross-process transport keeps ALL socket I/O on its sender
    thread precisely so the real tree stays clean of this."""
    hl = _hazard_lint()
    root = _write_tree(tmp_path, {
        "deepspeed_tpu/serving/router.py":
            "def step(self):\n"
            "    return self._poll_remote()\n"
            "def _poll_remote(self):\n"
            "    data = self._sock.recv(4096)\n"
            "    return data\n"})
    violations = hl.check(root)
    assert [v.rule for v in violations] == ["socket-hot"]
    assert ".recv()" in violations[0].message
    assert "_poll_remote" in violations[0].message
    # accept() inside an engine step root fails too
    root2 = _write_tree(tmp_path / "acc", {
        "deepspeed_tpu/inference/v2/engine_v2.py":
            "def step(self):\n"
            "    conn, _ = self.listener.accept()\n"
            "    return conn\n"})
    violations = hl.check(root2)
    assert [v.rule for v in violations] == ["socket-hot"]
    # the SAME call outside any hot root passes: the server/sender
    # threads are exactly where blocking socket I/O belongs
    root3 = _write_tree(tmp_path / "cold", {
        "deepspeed_tpu/serving/router.py":
            "def _sender_thread(self):\n"
            "    return self._sock.recv(4096)\n"})
    assert hl.check(root3) == []


def test_hazard_reachability_through_helpers(tmp_path):
    """A sync hidden two calls deep under train_batch is still found."""
    hl = _hazard_lint()
    root = _write_tree(tmp_path, {
        "deepspeed_tpu/runtime/engine.py":
            "def train_batch(self, batch):\n"
            "    self._report(1.0)\n"
            "def _report(self, loss):\n"
            "    self._publish(loss)\n"
            "def _publish(self, loss):\n"
            "    v = float(loss)\n"})
    violations = hl.check(root)
    assert [v.rule for v in violations] == ["host-sync"]
    assert "_publish" in violations[0].message


def test_hazard_pipe_tick_body_is_hot(tmp_path):
    """Pipe gates: a host sync seeded inside the pipe tick body
    (_pipe_body runs T = M + P - 1 times per step) fails by name, and
    the pipe overlap reducer must keep routing leaves through the
    shared bucketer — losing it is the monolithic-fp-all-reduce
    regression, named after the pipeline."""
    hl = _hazard_lint()
    root = _write_tree(tmp_path, {
        "deepspeed_tpu/runtime/pipe/engine.py":
            "def _pipe_body(params, ids, labels, stage_arr, pipe_comm):\n"
            "    s = float(stage_arr)\n"
            "    return s\n"})
    violations = hl.check(root)
    assert [v.rule for v in violations] == ["host-sync"]
    assert "_pipe_body" in violations[0].message

    root2 = _write_tree(tmp_path / "mono", {
        "deepspeed_tpu/runtime/pipe/overlap.py":
            "def reduce_stage_grads(self, dlayers):\n"
            "    return psum_tree(dlayers)\n"})
    violations = hl.check(root2)
    assert [v.rule for v in violations] == ["grad-overlap"]
    assert "monolithic fp post-backward all-reduce" in violations[0].message
    root3 = _write_tree(tmp_path / "ok", {
        "deepspeed_tpu/runtime/pipe/overlap.py":
            "def reduce_stage_grads(self, dlayers):\n"
            "    return coalesce_flat(bucketed_map(dlayers))\n"})
    assert hl.check(root3) == []


def test_hazard_numerics_stats_pull_is_boundary_cadence_only(tmp_path):
    """The numerics observatory's contract: the in-graph stats tree is
    device-resident until the steps_per_print boundary pulls it.  An
    eager `.item()` on the stats tree seeded into the fused train_batch
    path fails the host-sync rule by name — turning numerics on must not
    grow the hot path a per-step sync."""
    hl = _hazard_lint()
    root = _write_tree(tmp_path, {
        "deepspeed_tpu/runtime/engine.py":
            "def train_batch(self, batch):\n"
            "    state, loss, stats = self._fused(batch)\n"
            "    self._last_numerics = stats\n"
            "    gn = stats['grad_norm'].item()\n"
            "    return loss\n"})
    violations = hl.check(root)
    assert [v.rule for v in violations] == ["host-sync"]
    assert ".item()" in violations[0].message
    assert "train_batch" in violations[0].message
    # the legitimate shape — one documented device_get at the reporting
    # boundary, off the per-step path — lints clean
    root2 = _write_tree(tmp_path / "boundary", {
        "deepspeed_tpu/runtime/engine.py":
            "def train_batch(self, batch):\n"
            "    state, loss, stats = self._fused(batch)\n"
            "    self._last_numerics = stats\n"
            "    self._numerics_boundary()\n"
            "    return loss\n"
            "def _numerics_boundary(self):\n"
            "    # dstpu-lint: allow[host-sync] boundary cadence pull\n"
            "    host = jax.device_get(self._last_numerics)\n"
            "    return host\n"})
    assert hl.check(root2) == []


def test_hazard_rules_fire_and_allowlist_suppresses(tmp_path):
    hl = _hazard_lint()
    root = _write_tree(tmp_path, {
        "deepspeed_tpu/runtime/worker.py":
            "import time, random\n"
            "t0 = time.time()\n"
            "x = random.randint(0, 3)\n"
            "def f(acc=[]):\n"
            "    try:\n"
            "        pass\n"
            "    except Exception:\n"
            "        pass\n",
        "deepspeed_tpu/runtime/zero/strategy.py":
            "def specs(tree):\n"
            "    return [k for k in set(tree)]\n"})
    rules = sorted(v.rule for v in hl.check(root))
    assert rules == ["mutable-default", "pytree-order", "swallow",
                     "unseeded-random", "wall-clock"], rules

    # every violation suppressible with a REASONED marker; reasonless
    # markers and unknown rules are themselves violations
    root2 = _write_tree(tmp_path / "ok", {
        "deepspeed_tpu/runtime/worker.py":
            "import time, random\n"
            "t0 = time.time()  # dstpu-lint: allow[wall-clock] record stamp\n"
            "# dstpu-lint: allow[unseeded-random] fixture only\n"
            "x = random.randint(0, 3)\n"})
    assert hl.check(root2) == []
    root3 = _write_tree(tmp_path / "bad", {
        "deepspeed_tpu/runtime/worker.py":
            "import time\n"
            "t0 = time.time()  # dstpu-lint: allow[wall-clock]\n"
            "t1 = time.time()  # dstpu-lint: allow[wall-clok] typoed rule\n"})
    msgs = "\n".join(v.message for v in hl.check(root3))
    assert "without a reason" in msgs
    assert "unknown rule" in msgs


def test_hazard_docstring_marker_is_not_a_suppression(tmp_path):
    """A marker EXAMPLE quoted in a docstring must neither suppress the
    violation below it nor count as a documented suppression."""
    hl = _hazard_lint()
    root = _write_tree(tmp_path, {
        "deepspeed_tpu/runtime/engine.py":
            "def train_batch(self, loss):\n"
            '    """Example:\n'
            "    # dstpu-lint: allow[host-sync] docs only\n"
            '    """\n'
            "    return float(loss)\n"})
    violations = hl.check(root)
    assert [v.rule for v in violations] == ["host-sync"]
    assert hl.suppressions(root) == []


def test_hazard_nested_def_reported_once(tmp_path):
    """A sync inside a nested def is one violation, not one per
    reachability path."""
    hl = _hazard_lint()
    root = _write_tree(tmp_path, {
        "deepspeed_tpu/runtime/engine.py":
            "def train_batch(self, x):\n"
            "    def inner():\n"
            "        return float(x)\n"
            "    return inner()\n"})
    violations = hl.check(root)
    assert len(violations) == 1, violations


def test_hazard_marker_rides_comment_block_and_statement(tmp_path):
    """A marker whose reason wraps, sitting above a multi-line statement,
    still covers syncs on the statement's later lines."""
    hl = _hazard_lint()
    root = _write_tree(tmp_path, {
        "deepspeed_tpu/runtime/engine.py":
            "def train_batch(self, loss, scale):\n"
            "    # dstpu-lint: allow[host-sync] boundary cadence; the\n"
            "    # queue is already drained here\n"
            "    log(f'{float(loss)} '\n"
            "        f'{float(scale)}')\n"})
    assert hl.check(root) == []


def test_hazard_slo_exemplar_contract_fails_by_name(tmp_path):
    """The exemplar-coverage contract: a `deepspeed_tpu_serving_slo_*`
    `.inc()` inside a function that never calls `slo_exemplar` fails by
    name — for BOTH counter idioms (name/attribute bound at
    registration, and an accessor function returning a registration)."""
    hl = _hazard_lint()
    root = _write_tree(tmp_path, {
        "deepspeed_tpu/serving/slo_x.py":
            "from deepspeed_tpu.telemetry.reqtrace import slo_exemplar\n"
            "class Shed:\n"
            "    def __init__(self, reg):\n"
            "        self._m_shed = reg.counter(\n"
            "            'deepspeed_tpu_serving_slo_shed_total', 'h',\n"
            "            labelnames=('reason',))\n"
            "    def bad(self):\n"
            "        self._m_shed.inc(reason='queue_full')\n"
            "    def good(self, tid):\n"
            "        self._m_shed.inc(reason='queue_full')\n"
            "        slo_exemplar('deepspeed_tpu_serving_slo_shed_total',\n"
            "                     tid, reason='queue_full')\n"
            "def ttft_counter(reg):\n"
            "    return reg.counter(\n"
            "        'deepspeed_tpu_serving_slo_ttft_violations_total', 'h')\n"
            "def also_bad(reg):\n"
            "    ttft_counter(reg).inc()\n"})
    vs = [v for v in hl.check(root) if v.rule == "slo-exemplar"]
    msgs = "\n".join(v.message for v in vs)
    assert len(vs) == 2, msgs                    # bad + also_bad, not good
    assert "deepspeed_tpu_serving_slo_shed_total.inc() in 'bad'" in msgs
    assert ("deepspeed_tpu_serving_slo_ttft_violations_total.inc() "
            "in 'also_bad'") in msgs
    assert "offending trace_id" in msgs

    # no-single-request increments (breaker recovery) suppress with a
    # REASONED marker like every other rule
    root2 = _write_tree(tmp_path / "ok", {
        "deepspeed_tpu/serving/slo_x.py":
            "class B:\n"
            "    def __init__(self, reg):\n"
            "        self._m_rec = reg.counter(\n"
            "            'deepspeed_tpu_serving_slo_breaker_recoveries_total'"
            ", 'h')\n"
            "    def recover(self):\n"
            "        # dstpu-lint: allow[slo-exemplar] a recovery clears a\n"
            "        # replica-level state; there is no offending request\n"
            "        self._m_rec.inc()\n"})
    assert [v for v in hl.check(root2) if v.rule == "slo-exemplar"] == []


# ---------------------------------------------------------- HLO contracts
@pytest.fixture(scope="module")
def contracts_mod():
    from deepspeed_tpu.analysis import contracts

    return contracts


@pytest.fixture(scope="module")
def extracted(contracts_mod):
    """One full extraction shared by the golden/idempotency/replay tests
    (it lowers + compiles every program; don't repeat it per test)."""
    devs = __import__("jax").devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return contracts_mod.extract_all()


def test_toy_contract_extraction_counts_collectives(contracts_mod, devices8):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.utils.jax_compat import shard_map

    mesh = Mesh(np.array(devices8).reshape(8), ("data",))

    def body(x):
        return jax.lax.psum(x, "data") + jax.lax.all_gather(
            x, "data").sum(axis=0)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), check_vma=False),
                 donate_argnums=(0,))
    x = jax.device_put(jnp.ones((8, 4)), NamedSharding(mesh, P("data")))
    c = contracts_mod.extract_contract(fn, (x,), mesh)
    assert c["collectives"]["all-reduce"] == 1
    assert c["collectives"]["all-gather"] == 1
    assert c["collectives"]["all-to-all"] == 0
    assert c["flops"] > 0 and c["bytes_accessed"] > 0
    assert c["arg_shapes"] == ["float32[8, 4]"]

    def body2(x):  # the seeded mutation: one extra all-gather
        return jax.lax.psum(x, "data") + jax.lax.all_gather(
            x, "data").sum(axis=0) + jax.lax.all_gather(
            x * 2.0, "data").sum(axis=0)

    fn2 = jax.jit(shard_map(body2, mesh=mesh, in_specs=P("data"),
                            out_specs=P("data"), check_vma=False))
    c2 = contracts_mod.extract_contract(fn2, (x,), mesh)
    errs = contracts_mod.diff_contract(
        "toy", {"contract": c, "tolerances": {"flops": 10, "bytes_accessed": 10}},
        {"contract": c2})
    joined = "\n".join(errs)
    assert "toy: grew all-gather 1 -> 2" in joined, joined


def test_golden_contracts_hold(contracts_mod, extracted):
    """The headline tier-1 gate: every checked-in golden matches a fresh
    extraction; >= 6 programs covering train stages 0/1/3 + the serving
    programs (acceptance criteria)."""
    goldens = contracts_mod.load_goldens(REPO)
    assert len(goldens) >= 6, sorted(goldens)
    for required in ("train_step_zero0", "train_step_zero1",
                     "train_step_zero3", "prefill", "decode",
                     "paged_verify", "decode_multistep",
                     "train_step_zero1_hier",
                     "moe_dispatch_quantized", "train_step_zero1_overlap",
                     "train_step_zero3_prefetch",
                     "train_step_zero1_overlap_int8",
                     "train_step_zero3_prefetch_int8",
                     "train_step_pipe2"):
        assert required in goldens, f"missing golden for {required}"
    errors = contracts_mod.diff_all(goldens, extracted)
    assert not errors, "\n".join(errors)


def test_compressed_collective_contracts_pin_wire_shape(contracts_mod,
                                                        extracted):
    """The PR-11 programs pin the compressed-collective wire shape: the
    hierarchical train step keeps its reduce-scatter + all-gather hops
    and the quantized MoE dispatch keeps its all-to-alls (codes + scales
    ride combined ops; a fallback to full-precision dispatch or a
    lost/duplicated exchange changes these counts)."""
    hier = extracted["train_step_zero1_hier"]["contract"]["collectives"]
    assert hier["reduce-scatter"] >= 1, hier
    assert hier["all-gather"] >= 2, hier
    moe = extracted["moe_dispatch_quantized"]["contract"]["collectives"]
    assert moe["all-to-all"] >= 1, moe
    # the compressed-overlap programs (this PR) pin s8 ON THE WIRE inside
    # the loop: int8 codes ride combined collective ops, and the
    # residual state is a real donated train-state leaf
    ov1 = extracted["train_step_zero1_overlap_int8"]["contract"]
    assert ov1["s8_collectives"] >= 1, ov1
    assert ov1["collectives"]["all-to-all"] >= 1, ov1  # the two-hop hop 1
    assert ov1["comm_residual_bytes"] > 0, ov1
    ov3 = extracted["train_step_zero3_prefetch_int8"]["contract"]
    assert ov3["s8_collectives"] >= 1, ov3
    # the fp psum_scatters are GONE: the quantized reduce-scatter is an
    # all_to_all of codes + scales
    assert ov3["collectives"]["reduce-scatter"] == 0, ov3
    assert ov3["collectives"]["all-to-all"] >= 1, ov3


def test_pipe_contract_pins_hops_and_bubble(contracts_mod, extracted):
    """The pipe program pins the hop ring and the schedule shape: int8
    codes ride the collective-permutes (a silent fp32 hop fall-back
    changes s8_collectives), the EF residual slot is real state bytes,
    and the computed (P-1)/(M+P-1) bubble fraction diffs by name when
    the schedule degenerates."""
    c = extracted["train_step_pipe2"]["contract"]
    assert c["collectives"]["collective-permute"] >= 1, c
    assert c["s8_collectives"] >= 1, c
    assert c["comm_residual_bytes"] > 0, c
    assert abs(c["pipe_bubble_fraction"] - 1.0 / 3.0) < 1e-5, c
    replay = c.get("replay")
    assert replay is not None and replay["steps"] == 3
    if replay["compiles_after_warmup"] is not None:
        assert replay["compiles_after_warmup"] == 0, replay

    import copy

    golden = copy.deepcopy(extracted["train_step_pipe2"])
    golden["contract"]["pipe_bubble_fraction"] = 0.5
    golden["contract"]["collectives"]["collective-permute"] -= 1
    errs = contracts_mod.diff_contract(
        "train_step_pipe2", golden, extracted["train_step_pipe2"])
    joined = "\n".join(errs)
    assert "pipe_bubble_fraction" in joined, joined
    assert "collective-permute" in joined, joined


def test_seeded_collective_mutation_is_named(contracts_mod, extracted):
    """Tampering the stage-3 golden (as if the step grew two all-gathers)
    produces the named, actionable failure from the ISSUE."""
    import copy

    golden = copy.deepcopy(extracted["train_step_zero3"])
    golden["contract"]["collectives"]["all-gather"] -= 2
    errs = contracts_mod.diff_contract("train_step_zero3", golden,
                                       extracted["train_step_zero3"])
    assert len(errs) == 1
    g = golden["contract"]["collectives"]["all-gather"]
    assert f"grew all-gather {g} -> {g + 2}" in errs[0]
    assert "train_step_zero3" in errs[0]


@pytest.mark.parametrize("program", ["prefill", "moe_dispatch_quantized",
                                     "train_step_zero1_hier",
                                     "train_step_zero1_overlap",
                                     "train_step_zero3_prefetch",
                                     "train_step_zero1_overlap_int8",
                                     "train_step_zero3_prefetch_int8",
                                     "train_step_pipe2",
                                     "decode_multistep"])
def test_update_goldens_idempotent(contracts_mod, extracted, tmp_path,
                                   program):
    """Writing goldens twice — the second time from a fresh extraction of
    the same program — is byte-identical (covers the PR-11 compressed-
    collective programs AND the overlap/prefetch programs: their engine
    + replay setup must not leak state between extractions)."""
    first = {program: extracted[program]}
    contracts_mod.write_goldens(str(tmp_path), first)
    path = os.path.join(contracts_mod.goldens_dir(str(tmp_path)),
                        f"{program}.json")
    with open(path) as f:
        bytes1 = f.read()
    again = contracts_mod.extract_program(program)
    contracts_mod.write_goldens(str(tmp_path), {program: again})
    with open(path) as f:
        bytes2 = f.read()
    assert bytes1 == bytes2
    # and the round-trip loads back as the same contract
    loaded = contracts_mod.load_goldens(str(tmp_path))
    assert contracts_mod.diff_all(loaded, {program: again}) == []


def test_train_replay_recompile_contract(contracts_mod, extracted):
    """ROADMAP item 5 follow-through: the 3-step replay of the tiny train
    loop compiles ONLY on the first step — shape-signature churn the PR 3
    sentinel merely warns about at runtime is a hard failure here."""
    for prog in ("train_step_zero0", "train_step_zero1", "train_step_zero3"):
        replay = extracted[prog]["contract"].get("replay")
        assert replay is not None, prog
        assert replay["steps"] == 3
        if replay["compiles_after_warmup"] is not None:
            assert replay["compiles_after_warmup"] == 0, (
                f"{prog}: steady-state steps recompiled "
                f"{replay['compiles_after_warmup']}x")


def test_multistep_decode_replay_and_donation_contract(contracts_mod,
                                                       extracted):
    """The fused multi-step decode program's contract: the KV pool
    buffers stay donated (a lost donation doubles the pool's HBM), and
    the 3-dispatch replay across MIXED per-row produced lengths —
    different budget/EOS mixes, same shapes — compiles exactly once."""
    c = extracted["decode_multistep"]["contract"]
    assert c["donated_inputs"] >= 2, c  # the k/v pool leaves
    replay = c.get("replay")
    assert replay is not None and replay["steps"] == 3
    if replay["compiles_after_warmup"] is not None:
        assert replay["compiles_after_warmup"] == 0, (
            "fused decode recompiled across mixed produced-lengths: "
            f"{replay['compiles_after_warmup']}x (budgets/EOS must be "
            "data, never shapes)")


# -------------------------------------------------------- unified driver
def test_dstpu_lint_driver_merges_and_gates(tmp_path):
    import tools.dstpu_lint as dl

    # the real tree passes the AST sections
    assert dl.main(["--root", REPO]) == 0
    # a seeded violation turns the merged exit code red
    root = _write_tree(tmp_path, {
        "deepspeed_tpu/runtime/engine.py":
            "def train_batch(self, loss):\n    return loss.item()\n"})
    assert dl.main(["--root", root]) == 1


def test_check_metric_names_shim_back_compat():
    """The moved metric lint keeps its old entry point and API."""
    shim = _load_by_path("check_metric_names_shim", "tools",
                         "check_metric_names.py")
    assert shim.check(REPO) == []
    assert "deepspeed_tpu_train_phase_seconds" in shim.collect(REPO)
    assert shim.METRIC_NAME_RE.match("deepspeed_tpu_ok_total")
