"""The deep frame beneath which the engines trace and lower their programs
(``compile/deep_frame.py``): the helper is transparent, its frame is as large
as it says, every engine makes a program's first dispatch beneath it and no
later one, and a program lowered beneath it is the program lowered without it.

Nothing here times anything: what the frame is for is a time on the chip's
host (PERF.md section 6, PR 52), and a timing on a shared CPU is no test.
"""

import json
import os
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.compile import deep_frame
from deepspeed_tpu.compile.deep_frame import (DEEP_FRAME_SLOTS,
                                              first_call_beneath,
                                              under_deep_frame)
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig, RaggedRequest)
from deepspeed_tpu.models import mistral_model
from tests.unit.simple_model import random_batch, simple_mlp_spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PS, MP, B, CHUNK = 8, 8, 2, 16


# ------------------------------------------------------------ the helper
def _callers():
    """The code objects of the frames beneath the caller, innermost first."""
    codes, f = [], sys._getframe(1)
    while f is not None:
        codes.append(f.f_code)
        f = f.f_back
    return codes


def test_positional_and_keyword_arguments_and_the_return_value():
    def fn(a, b, *rest, c=3, **more):
        return a, b, rest, c, more

    assert under_deep_frame(fn, 1, 2, 4, c=5, d=6) == (1, 2, (4,), 5,
                                                        {"d": 6})
    assert under_deep_frame(lambda: None) is None


def test_a_keyword_named_as_the_helper_names_its_argument_passes_through():
    assert under_deep_frame(lambda fn: fn, fn=7) == 7


def test_an_exception_keeps_its_traceback():
    def fails():
        raise ValueError("from beneath the frame")

    with pytest.raises(ValueError, match="from beneath the frame") as info:
        under_deep_frame(fails)
    names = [f.name for f in traceback.extract_tb(info.tb)]
    assert names[-2:] == ["under_deep_frame", "fails"]


def test_nested_calls_each_have_their_frame():
    def depth(n):
        if n == 0:
            return _callers().count(under_deep_frame.__code__)
        return under_deep_frame(depth, n - 1)

    assert under_deep_frame(depth, 3) == 4


def test_the_frame_reserves_what_the_docstring_states():
    def code_of_the_frame():
        return sys._getframe(1).f_code

    code = under_deep_frame(code_of_the_frame)
    assert code is under_deep_frame.__code__
    assert code.co_stacksize >= DEEP_FRAME_SLOTS
    # "a frame of DEEP_FRAME_SLOTS value-stack slots", 8 bytes each: a MiB
    assert DEEP_FRAME_SLOTS * 8 >= 1 << 20
    assert "DEEP_FRAME_SLOTS" in deep_frame.__doc__


def test_the_first_call_of_a_key_is_beneath_the_frame_and_no_later_one():
    def beneath(*args, **kwargs):
        return under_deep_frame.__code__ in _callers(), args, kwargs

    seen = set()
    assert first_call_beneath(seen, "a", beneath, 1, k=2) == (True, (1,),
                                                              {"k": 2})
    assert first_call_beneath(seen, "a", beneath, 3) == (False, (3,), {})
    assert first_call_beneath(seen, ("a", 1), beneath)[0]
    assert seen == {"a", ("a", 1)}
    # a key that raised has been seen: the retry is a plain call
    with pytest.raises(ZeroDivisionError):
        first_call_beneath(seen, "b", lambda: 1 / 0)
    assert not first_call_beneath(seen, "b", beneath)[0]


# ------------------------------------------------ the engines' dispatches
class _Recording:
    """In a program's place: keeps the callers of each dispatch."""

    def __init__(self, program):
        self.program, self.calls = program, []

    def __call__(self, *args, **kwargs):
        self.calls.append(_callers())
        return self.program(*args, **kwargs)


def _mistral_engine():
    model = mistral_model("tiny", max_seq_len=PS * MP)
    return InferenceEngineV2(
        model, RaggedInferenceConfig(
            dtype="fp32", page_size=PS, num_pages=64, max_seqs=B,
            max_pages_per_seq=MP, prefill_chunk=CHUNK),
        params=model.init_params(jax.random.PRNGKey(0)))


def _serve(eng, prompt_tokens, new):
    eng.put(RaggedRequest(
        prompt_ids=np.random.default_rng(0).integers(
            0, 50, prompt_tokens).tolist(), max_new_tokens=new))
    for _ in range(1000):
        if not eng.has_work():
            break
        eng.step()
    assert not eng.has_work()


def _sdar_engine():
    sys.path.insert(0, ROOT)
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


def _dispatches(program):
    """The callers of every dispatch of ``program`` while the same work is
    done twice over: the second time no part of a step is new."""
    if program in ("decode", "chunk"):
        eng = _mistral_engine()
        attr = {"decode": "_decode", "chunk": "_prefill_chunk"}[program]
        packed = getattr(eng, attr)  # `_dispatch` calls its `run`
        stub = packed.run = _Recording(packed.run)
        for _ in range(2):  # two chunks, of two windows: two parts
            _serve(eng, 20, 3)
    elif program == "block_pass":
        eng = _sdar_engine()
        stub = eng.blocks._program.run = _Recording(eng.blocks._program.run)
        for _ in range(2):
            _serve(eng, 9, 5)
    else:
        engine, *_ = deepspeed_tpu.initialize(
            model=simple_mlp_spec(), config={
                "train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
        stub = engine._train_batch = _Recording(engine._train_batch)
        for seed in range(4):
            engine.train_batch(random_batch(batch_size=16, seed=seed, gas=1))
    return stub.calls


@pytest.mark.parametrize("program", ["decode", "chunk", "block_pass",
                                     "train_batch"])
def test_a_programs_first_dispatch_alone_is_beneath_the_frame(program):
    calls = _dispatches(program)
    assert len(calls) >= 4 and len(calls) % 2 == 0
    assert under_deep_frame.__code__ in calls[0]
    assert not any(under_deep_frame.__code__ in c
                   for c in calls[len(calls) // 2:])


# --------------------------------------------------- the program's text
def _arr(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_lowered_beneath_the_frame_is_the_text_lowered_without(program):
    eng = _mistral_engine()
    if program == "decode":
        jitted, args = eng._decode, (
            _arr((B,)), _arr((B,)), _arr((B, MP)), _arr((B,), jnp.bool_),
            _arr((B,), jnp.float32), _arr((B,)), _arr((2,), jnp.uint32))
    else:
        jitted, args = eng._prefill_chunk, (
            _arr((CHUNK,)), _arr((CHUNK // PS,)), _arr((MP,)), _arr(()),
            _arr(()))
    plain = jitted.lower(eng.params, eng._pools, *args).as_text()
    jax.clear_caches()  # so that the second lowering is traced anew
    beneath = under_deep_frame(jitted.lower, eng.params, eng._pools,
                               *args).as_text()
    assert "stablehlo" in plain
    assert beneath == plain
