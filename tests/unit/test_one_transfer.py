"""A program call's host inputs cross the link in one transfer
(``InferenceEngineV2._dispatch``, ``inference/v2/packed_inputs.py``): every
serving program call — decode, a chunk and a chunk part, a whole-prompt
bucket, a fused horizon, a verify call, a block pass — issues exactly one
``jax.device_put``, of one packed int32 array, counted as ``input_transfers``
on the step; the tokens are those of the form the engine had before (one
``jnp.asarray`` an array into a program that takes its inputs apart, which
lives on here as the reference); what crosses is a copy, which no later write
to a mirror the engine keeps can reach; and the inputs come out of the
program's slices bit for bit.

Nothing here times anything: what the one transfer is worth is a time on the
chip's host (PERF.md section 6, PR 53).
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceConfig, RaggedRequest)
from deepspeed_tpu.inference.v2.packed_inputs import (PackedProgram,  # noqa: E402
                                                      pack_inputs,
                                                      unpack_inputs)
from deepspeed_tpu.inference.v2.speculative import SpeculativeConfig  # noqa: E402
from deepspeed_tpu.models import (mistral_model, phi4_flash_model,  # noqa: E402
                                  solar_open2_model)
from deepspeed_tpu.telemetry import (SpanRecorder, get_span_recorder,  # noqa: E402
                                     set_span_recorder)

PS = 8
#: every jitted program a serving step can call, by the attribute that holds it
PROGRAMS = ("_decode", "_prefill", "_prefill_chunk", "_prefill_chunk_part",
            "_verify", "_multi")


def _mistral(**over):
    model = mistral_model("tiny", max_seq_len=PS * 16)
    cfg = dict(dtype="fp32", page_size=PS, num_pages=96, max_seqs=4,
               max_pages_per_seq=16)
    cfg.update(over)
    return InferenceEngineV2(model, RaggedInferenceConfig(**cfg),
                             params=model.init_params(jax.random.PRNGKey(0)),
                             seed=0)


def _solar():
    model = solar_open2_model("tiny", moe_held_first=4, moe_held_count=4,
                              max_seq_len=128)
    return InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=PS, max_pages_per_seq=16, prefill_chunk=16,
        max_seqs=4, num_pages=80), seed=0)


def _phi():
    model = phi4_flash_model("tiny", max_seq_len=PS * 32)
    return InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=PS, max_pages_per_seq=32, prefill_chunk=32,
        max_seqs=4, num_pages=160), seed=0)


def _sdar():
    from benchmark.families import sdar_moe as family

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sdar-30b-a3b-pp8-serve.json")) as f:
        config = json.load(f)
    tiny, ecfg = dict(config, **config["tiny"]), config["tiny_engine"]
    model = family.build(tiny, tiny["num_hidden_layers"],
                         ecfg["page_size"] * ecfg["max_pages_per_seq"],
                         jnp.float32)
    return InferenceEngineV2(
        model, RaggedInferenceConfig(**ecfg),
        params=model.init_params(jax.random.PRNGKey(3)), seed=0)


#: name -> (the engine, the part every run of it must have dispatched)
ENGINES = {
    "mistral_chunked": (lambda: _mistral(prefill_chunk=16), "prefill_chunk"),
    "mistral_whole_prompt": (_mistral, "prefill"),
    "mistral_horizon": (lambda: _mistral(prefill_chunk=16, decode_horizon=4),
                        "multi_decode"),
    "mistral_verify": (lambda: _mistral(
        prefill_chunk=16, speculative=SpeculativeConfig(mode="ngram", k=2)),
        "verify"),
    "solar_state_slots": (_solar, "prefill_chunk"),
    "phi_chunk_part": (_phi, "part"),
    "sdar_block_pass": (_sdar, "block_pass"),
}


def _requests(name):
    """Prompts of more than one chunk and of less, a sampled row beside the
    greedy ones; the verify engine's are greedy and repeat themselves, so
    that the n-gram proposer has drafts to verify."""
    rng = np.random.RandomState(1)
    if name == "mistral_verify":
        return [RaggedRequest(prompt_ids=[5, 6, 7, 8] * 5, max_new_tokens=60),
                RaggedRequest(prompt_ids=[9, 3] * 6, max_new_tokens=60)]
    if name == "sdar_block_pass":
        return [RaggedRequest(prompt_ids=rng.randint(1, 50, n).tolist(),
                              max_new_tokens=16) for n in (9, 14)]
    # a fused horizon emits four tokens a step: more of them, for 16 steps
    new = 72 if name == "mistral_horizon" else 20
    temps = (0.0, 0.7, 1.3)
    return [RaggedRequest(prompt_ids=rng.randint(1, 50, n).tolist(),
                          max_new_tokens=new, temperature=t)
            for n, t in zip((37, 20, 5), temps)]


def _count_programs(eng):
    """Every call `_dispatch` makes of a program (its ``run``), with what
    it is handed behind the parameters and the pools."""
    log = []

    def counted(run):
        def call(params, pools, *args):
            log.append(args)
            return run(params, pools, *args)
        return call

    programs = [getattr(eng, attr, None) for attr in PROGRAMS]
    if eng.blocks is not None:
        programs.append(eng.blocks._program)
    for program in filter(None, programs):
        program.run = counted(program.run)
    return log


def _run(eng, requests, monkeypatch=None):
    """Every step of a run: (the step's counts, program calls, device_put
    calls), the parts dispatched, and each request's tokens."""
    calls = _count_programs(eng)
    puts = []
    if monkeypatch is not None:
        real = jax.device_put

        def device_put(x, *a, **k):
            puts.append(x)
            return real(x, *a, **k)
        monkeypatch.setattr(jax, "device_put", device_put)
    uids = [eng.put(r) for r in requests]
    got = {u: [] for u in uids}
    steps, parts = [], set()
    for _ in range(400):
        if not eng.has_work():
            break
        c0, p0 = len(calls), len(puts)
        for u, o in eng.step().items():
            got[u] += o["tokens"]
        steps.append((dict(eng._step_counts), calls[c0:], puts[p0:]))
        parts |= eng._step_parts
    assert not eng.has_work()
    return steps, parts, [got[u] for u in uids]


@pytest.fixture(scope="module", params=sorted(ENGINES))
def run(request):
    """One run an engine of today's form, its ``jax.device_put`` counted."""
    build, part = ENGINES[request.param]
    mp = pytest.MonkeyPatch()
    eng = build()
    try:
        steps, parts, tokens = _run(eng, _requests(request.param), mp)
    finally:
        mp.undo()
        eng.close()
    return request.param, part, steps, parts, tokens


# --------------------------------------------- one transfer a program call
def test_every_program_call_issues_exactly_one_transfer(run):
    name, part, steps, parts, _tokens = run
    assert len(steps) >= 16
    # the part this engine is here for was dispatched
    assert any(part == p or (isinstance(p, tuple) and part in p)
               for p in parts), parts
    assert sum(len(calls) for _c, calls, _p in steps) >= 16
    for counts, calls, puts in steps:
        assert counts["input_transfers"] == len(calls) == len(puts), \
            (name, counts, len(calls), len(puts))
        for packed, (layout, on_device, *rest) in zip(puts, calls):
            # one put of the whole call's host inputs in one int32 array ...
            assert isinstance(packed, np.ndarray) and packed.ndim == 1
            assert packed.dtype == np.int32 and len(layout) >= 3
            assert packed.size == sum(math.prod(shape) for shape, _ in layout)
            # ... and the program is handed that array on the device, the
            # sampling key and a static horizon behind it
            assert isinstance(on_device, jax.Array)
            assert on_device.shape == packed.shape
            assert all(isinstance(r, (jax.Array, int)) for r in rest)
            assert len(rest) <= 2


def test_a_decode_only_step_reads_one_and_a_chunk_beside_it_two(run):
    name, _part, steps, _parts, _tokens = run
    decode_only = [c for c, _calls, _p in steps
                   if c["decode_rows"] and not c["chunks"]]
    assert decode_only
    if name == "mistral_verify":
        # a verify call, and a plain decode call for rows without drafts
        assert {c["input_transfers"] for c in decode_only} <= {1, 2}
    else:
        assert {c["input_transfers"] for c in decode_only} == {1}
    for c, _calls, _p in steps:
        if c["chunks"] and name != "mistral_verify":
            assert c["input_transfers"] == c["chunks"] + bool(c["decode_rows"])


def test_the_step_span_carries_the_count():
    old = get_span_recorder()
    ring = SpanRecorder(ring_size=4096)
    set_span_recorder(ring)
    eng = _mistral(prefill_chunk=16)
    try:
        eng.put(RaggedRequest(prompt_ids=list(range(1, 21)),
                              max_new_tokens=4))
        seen = []
        while eng.has_work():
            ring.clear()
            eng.step()
            (top,) = [s for s in ring.spans() if s.name == "serve_step"]
            seen.append((top.attrs["chunks"], top.attrs["decode_rows"],
                         top.attrs["input_transfers"]))
    finally:
        set_span_recorder(old)
        eng.close()
    # two chunks of a 20-token prompt, the row decoding from the step of
    # its last chunk on: a chunk call and a decode call, then decode alone
    assert seen[:2] == [(1, 0, 1), (1, 1, 2)]
    assert seen[2:] and all(s == (0, 1, 1) for s in seen[2:])


# ------------------------------------------ the same tokens as the old form
def _one_upload_an_array(self, part, program, inputs, *rest, phase):
    """``_dispatch`` as it was before the one transfer: the program over
    its inputs apart, every array and every scalar uploaded by a
    ``jnp.asarray`` of its own."""
    self._step_parts.add(part)
    apart = self.__dict__.setdefault("_apart", {})
    if program not in apart:
        apart[program] = program.apart(tuple(
            2 + len(inputs) + i for i, r in enumerate(rest)
            if isinstance(r, int)))
    args = tuple(jnp.asarray(a) for a in inputs)
    self._handed_table = lambda: np.asarray(args[2])
    with self._step_span("dispatch", parent=phase):
        return apart[program](self.params, self._pools, *args, *rest)


def test_token_streams_equal_those_of_one_upload_an_array(run, monkeypatch):
    name, _part, steps, parts, tokens = run
    monkeypatch.setattr(InferenceEngineV2, "_dispatch", _one_upload_an_array)
    eng = ENGINES[name][0]()
    try:
        old_steps, old_parts, old_tokens = _run(eng, _requests(name))
    finally:
        eng.close()
    assert old_tokens == tokens
    assert all(len(t) >= 12 for t in tokens)
    assert old_parts == parts and len(old_steps) == len(steps)
    assert all(c["input_transfers"] == 0 and not calls
               for c, calls, _p in old_steps)


# ------------------------------------------------ what crosses is a copy
def _aligned_copy(a, align=64):
    """``a`` in a buffer aligned as the CPU backend wants it for zero copy."""
    buf = np.zeros(a.nbytes + align, np.uint8)
    off = (-buf.ctypes.data) % align
    out = buf[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


@pytest.mark.parametrize("form", ["one_transfer", "one_upload_an_array"])
def test_a_write_to_the_page_table_after_dispatch_reaches_nothing(
        form, monkeypatch):
    """Between ``_dispatch``'s return and the pull of the tokens the page
    table mirror is overwritten: the table the program was handed keeps its
    values and the tokens are those of an undisturbed run.  Under the old
    form the same write shows through (where the backend takes an aligned
    host buffer without a copy, as the CPU's does): the case that says the
    test can see what it is for."""
    requests = [RaggedRequest(prompt_ids=list(range(3, 24)),
                              max_new_tokens=16)]
    clean = _mistral(prefill_chunk=16)
    try:
        _s, _p, want = _run(clean, requests)
    finally:
        clean.close()

    probe = _aligned_copy(np.arange(64, dtype=np.int32))
    on_device = jax.device_put(probe)
    probe[:] = -1
    zero_copy = int(on_device[0]) == -1
    if form == "one_upload_an_array":
        if not zero_copy:
            pytest.skip("this backend copies an aligned host buffer")
        monkeypatch.setattr(InferenceEngineV2, "_dispatch",
                            _one_upload_an_array)

    eng = _mistral(prefill_chunk=16)
    eng._page_table = _aligned_copy(eng._page_table)
    rows, width = eng._page_table.shape
    kept, untouched = [], []
    real_run, real_prefetch, real_pull = (eng._decode.run,
                                          eng._prefetch_restores, eng._pull)

    def run(params, pools, layout, packed, key):
        # the table is the third input, behind two rows of ``max_seqs``
        eng._handed_table = lambda: np.asarray(packed)[
            2 * rows:2 * rows + rows * width].reshape(rows, width)
        return real_run(params, pools, layout, packed, key)

    def prefetch():
        if eng._step_counts["decode_rows"] and len(kept) < eng._decode_steps:
            # between the decode call and the pull of its tokens: scribble
            # over the mirror, and look at the table the program was handed
            kept.append(eng._page_table.copy())
            eng._page_table[...] = eng.block.trash_page
            untouched.append(np.array_equal(eng._handed_table(), kept[-1]))
        return real_prefetch()

    def pull(*arrays):
        out = real_pull(*arrays)
        eng._page_table[...] = kept[-1]  # the host's book, for the next step
        return out

    eng._decode.run, eng._prefetch_restores, eng._pull = run, prefetch, pull
    try:
        _s, _p, got = _run(eng, requests)
    finally:
        eng.close()
    assert len(untouched) >= 12
    assert all(untouched) if form == "one_transfer" else not any(untouched)
    if form == "one_transfer":
        assert got == want


# ------------------------------------------- packed and cut apart, bit for bit
CASES = {
    "temperatures_as_bits": [np.array([0.0, 0.7, 1.3, -0.0, 1e-45, np.inf],
                                      np.float32)],
    "sampling_ids_to_the_last_bit": [np.array([0, 1, 2**31 - 1, -2**31],
                                              np.int32)],
    "a_mask_as_zeros_and_ones": [np.array([True, False, False, True])],
    "scalars_as_single_elements": [np.int32(2**31 - 1), np.float32(0.7),
                                   np.bool_(True), np.int32(0)],
    "a_decode_call": [np.arange(4, dtype=np.int32),
                      np.arange(4, dtype=np.int32) * 7,
                      np.arange(32, dtype=np.int32).reshape(4, 8)[::-1],
                      np.array([True, False, True, False]),
                      np.array([0.0, 0.7, 1.3, 0.0], np.float32),
                      np.array([3, 2**31 - 1, 0, 9], np.int32)],
    "a_view_of_a_table_row": [np.arange(64, dtype=np.int32).reshape(4, 16)[2][:8],
                              np.int32(16), np.int32(5)],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_inputs_come_out_of_the_program_as_they_went_in(case):
    inputs = CASES[case]
    packed, layout = pack_inputs(inputs)
    assert packed.dtype == np.int32 and packed.ndim == 1
    assert not any(np.shares_memory(packed, a) for a in inputs)
    back = jax.jit(unpack_inputs, static_argnums=1)(packed, layout)
    assert len(back) == len(inputs)
    for a, b in zip(inputs, back):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype
        assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("bad", [np.zeros(3, np.int64), np.zeros(3), 5,
                                 np.zeros(3, np.int8)])
def test_an_input_of_another_width_is_refused(bad):
    with pytest.raises(TypeError, match="int32, float32 or bool"):
        pack_inputs([np.zeros(2, np.int32), bad])


def test_a_program_called_or_lowered_with_its_inputs_apart_is_the_same():
    """Tests and tools hand a program its inputs apart: the same program
    runs, under the name it has in a trace."""
    def _scale_and_mask(params, pools, x, scale, mask, key):
        return jnp.where(mask, x * scale + params, 0) + key[0], pools

    program = PackedProgram(_scale_and_mask, rest=1)
    x = np.arange(6, dtype=np.int32)
    scale, mask = np.float32(1.5), np.array([True, False] * 3)
    key = jnp.arange(2, dtype=jnp.uint32)
    out, _ = program(jnp.float32(2.0), jnp.zeros(()), x, scale, mask, key)
    want = np.where(mask, x * 1.5 + 2.0, 0)
    assert np.array_equal(np.asarray(out), want)
    packed, layout = pack_inputs((x, scale, mask))
    again, _ = program.run(jnp.float32(2.0), jnp.zeros(()), layout, packed,
                           key)
    assert np.array_equal(np.asarray(again), want)
    S = jax.ShapeDtypeStruct
    text = program.lower(S((), jnp.float32), S((), jnp.float32),
                         S((6,), jnp.int32), S((), jnp.float32),
                         S((6,), jnp.bool_), S((2,), jnp.uint32)).as_text()
    assert "@jit__scale_and_mask" in text
    assert "tensor<13xi32>" in text  # 6 + 1 + 6 elements in one argument
