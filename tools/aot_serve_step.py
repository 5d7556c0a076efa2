#!/usr/bin/env python3
"""Compile a serving cell's decode and chunk programs for a v5e — with no chip.

    python tools/aot_serve_step.py
    python tools/aot_serve_step.py --num-pages 5632 --windows 32,256
    python tools/aot_serve_step.py --config benchmark/configs/mistral-7b-serve.json
    python tools/aot_serve_step.py --config benchmark/configs/solar-open2-250b-ep8-serve.json
    python tools/aot_serve_step.py --config benchmark/configs/mimo-v2-flash-ep16-serve.json
    python tools/aot_serve_step.py --config benchmark/configs/laguna-s-2.1-ep8-serve.json
    python tools/aot_serve_step.py --config benchmark/configs/xing4-29b-a4b-pp7-serve.json
    python tools/aot_serve_step.py --config benchmark/configs/evabyte-6.5b-pp4-serve.json

Reads a serving configuration file of the benchmark (model widths, depth and
the ``engine`` block: page size, ``num_pages``, ``max_seqs``, chunk), builds
``InferenceEngineV2`` over abstract weights and pools, and compiles the
engine's own jitted programs — ``_decode`` at ``max_seqs`` rows and
``_prefill_chunk`` at each window bucket — against a ``v5e:2x2`` topology
description, one device of it.  Printed per program: XLA's memory analysis
(arguments, outputs, aliased, temporaries, total of 15.75 GiB), the Mosaic
kernels in it, and every instruction of the optimized HLO that materializes
an array of at least one layer's K pool: with the pools carried and donated
these are the in-place scatters of the fresh K/V alone, and the pools' bytes
are aliased input to output.  The pools are every leaf the cache manager
keeps: pages and, for a model whose layers keep recurrent state, the state
slots ("pool-sized" is then one layer of the smallest large leaf).
``--num-pages`` / ``--max-seqs`` ask what another size would cost.  Exit 1
when a program does not keep the pools in place.

Beside them, every ``copy`` of at least 4 MB whose operand is a leaf of
``params['layers']``, a slice of one or an async copy of one (name, shape,
layout, MB, the leaf) and their sum: a weight the program re-lays out on every
call (``weight_copies``; a loop body counts once).  A projection whose result
is used by head is pinned as a plain product for that
(``models/transformer.py::head_projection``): 0 in every decode program.
``--hlo-dir`` writes each program's optimized HLO to a file there.  Neither
moves the exit code.

It compiles; it does not run.  No time or numeric result comes from here.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

GIB = 2.0 ** 30
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}
#: `%name = dtype[dims]{layout} opcode(` of an optimized-HLO instruction
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\][^ ]* "
                    r"([\w\-]+)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$")
#: opcodes that name a buffer and move nothing
_FREE = ("parameter", "get-tuple-element", "tuple", "while", "bitcast")


def big_instructions(hlo: str, floor: int, no_pool=None):
    """(bytes, name, opcode, shape, in_place) of every instruction of the
    optimized HLO that materializes an array of >= ``floor`` bytes:
    instructions inside a fusion's body are left out (only the fusion's
    result is a buffer), as are parameters, tuples, the loop and bitcasts.
    ``in_place`` is set where libtpu recorded that the result shares its
    operand's buffer (``aliasing_operands``: a scatter that writes a few
    rows of the pool it was given)."""
    fused = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", hlo))
    out, skip = [], False
    for line in hlo.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            skip = c.group(1) in fused
            continue
        m = _INSTR.match(line)
        if (skip or not m or m.group(2) not in _DTYPE_BYTES
                or m.group(4) in _FREE):
            continue
        dims = [int(d) for d in m.group(3).split(",") if d]
        nbytes = int(np.prod(dims, dtype=np.int64)) * _DTYPE_BYTES[m.group(2)]
        if nbytes >= floor and not (no_pool and no_pool(dims)):
            out.append((nbytes, m.group(1), m.group(4),
                        f"{m.group(2)}[{m.group(3)}]",
                        '"aliasing_operands":{"lists":[{' in line))
    return out


#: `%name = <shape> opcode(<operands>)<attributes>` of any instruction, a
#: tuple-shaped one included
_ANY_INSTR = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\("
                        r"([^)]*)\)(.*)$")
_ARRAY = re.compile(r"^(\w+)\[([\d,]*)\](\{[\d,]*)?")
_CALLED = re.compile(r"(?:calls|body|to_apply)=%([\w.\-]+)")
#: opcodes whose result is still "the weight, or a slice of it"
_SAME_BYTES = ("bitcast", "dynamic-slice", "slice", "copy-start", "copy-done")
WEIGHT_COPY_FLOOR = 4 * 2 ** 20


def _computations(hlo: str):
    """``{computation: {instruction: (opcode, operand names, operand text,
    shape text, attributes, is_root)}}`` of an optimized HLO module, the
    entry computation's name, and ``{callee: (caller, instruction)}``."""
    comps, callers, entry, comp = {}, {}, None, None
    for line in hlo.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            comp = c.group(1)
            comps[comp] = {}
            if line.startswith("ENTRY"):
                entry = comp
            continue
        m = _ANY_INSTR.match(line) if comp else None
        if not m:
            continue
        root, name, shape, op, args, attrs = m.groups()
        comps[comp][name] = (op, re.findall(r"%([\w.\-]+)", args), args,
                             shape, attrs, bool(root))
        for callee in _CALLED.findall(attrs):
            callers[callee] = (comp, name)
    return comps, entry, callers


def _weight_of(comps, entry, callers, comp: str, name: str):
    """The path of the ``params['layers']`` leaf that ``name`` (in ``comp``)
    is — whole, sliced, or copied asynchronously — or None.  Followed through
    a fusion that only slices, a fused computation's parameters and a loop's
    carried tuple back to the entry computation's parameters."""
    for _ in range(64):
        if name not in comps.get(comp, {}):
            return None
        op, operands, args, _, attrs, _ = comps[comp][name]
        if op == "parameter" and comp == entry:
            path = re.search(r'op_name="([^"]*)"', attrs)
            path = path.group(1).replace("\\'", "'") if path else name
            return path if "params['layers']" in path else None
        if op == "parameter":
            comp, site = callers.get(comp, (None, None))
            if comp is None or comps[comp][site][0] not in ("fusion", "call"):
                return None
            name = comps[comp][site][1][int(args)]
        elif op in ("fusion", "call"):
            callee = _CALLED.search(attrs).group(1)
            body = comps.get(callee, {})
            if any(i[0] not in _SAME_BYTES + ("parameter", "constant")
                   for i in body.values()):
                return None
            comp, name = callee, next(n for n, i in body.items() if i[5])
        elif op == "get-tuple-element":
            if comps[comp][operands[0]][0] != "parameter" \
                    or comp not in callers:
                return None
            # a loop body's carried tuple: what the loop was given
            index = int(re.search(r"index=(\d+)", attrs).group(1))
            comp, loop = callers[comp]
            given = comps[comp][comps[comp][loop][1][0]]
            if comps[comp][loop][0] != "while" or given[0] != "tuple":
                return None
            name = given[1][index]
        elif op in _SAME_BYTES and operands:
            name = operands[0]
        else:
            return None
    return None


def weight_copies(hlo: str, floor: int = WEIGHT_COPY_FLOOR):
    """(bytes, name, shape, layout, leaf) of every ``copy`` in the optimized
    HLO — inside a fusion's body too — of at least ``floor`` bytes whose
    operand is a leaf of ``params['layers']``, a slice of one or an async
    copy of one: a weight re-laid out by the program on every call."""
    comps, entry, callers = _computations(hlo)
    out = []
    for comp, instrs in comps.items():
        for name, (op, operands, _, shape, _, _) in instrs.items():
            m = _ARRAY.match(shape) if op == "copy" and operands else None
            if not m or m.group(1) not in _DTYPE_BYTES:
                continue
            dims = [int(d) for d in m.group(2).split(",") if d]
            nbytes = (int(np.prod(dims, dtype=np.int64))
                      * _DTYPE_BYTES[m.group(1)])
            leaf = nbytes >= floor and _weight_of(
                comps, entry, callers, comp, operands[0])
            if leaf:
                out.append((nbytes, name, f"{m.group(1)}[{m.group(2)}]",
                            (m.group(3) or "{") + "}", leaf))
    return out


def abstract_engine(config: dict, engine_overrides: dict):
    """``InferenceEngineV2`` of a benchmark serving config with no array
    behind it: weights and pools are ``ShapeDtypeStruct`` leaves."""
    import importlib

    import deepspeed_tpu.runtime.precision as precision
    import deepspeed_tpu.utils.platform as plat
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    from deepspeed_tpu.inference.v2 import ragged

    plat.platform = lambda: "tpu"  # compiled kernels, not interpret mode
    # what is compiled here cannot be loaded without a chip: keep it out of
    # the cache the chip runs read
    jax.config.update("jax_enable_compilation_cache", False)
    ecfg = dict(config["engine"], **engine_overrides)
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[ecfg["dtype"]]
    family = importlib.import_module(
        f"benchmark.families.{config['family']}")
    model = family.build(
        config, config["num_hidden_layers"],
        ecfg["page_size"] * ecfg["max_pages_per_seq"], dtype)

    init_params, init_pools = model.init_params, ragged.PagedKVCache.init
    model.init_params = lambda key: jax.eval_shape(init_params, key)
    ragged.PagedKVCache.init = staticmethod(
        lambda *a, **kw: jax.eval_shape(lambda: init_pools(*a, **kw)))
    precision.cast_tree = lambda tree, dt: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, dt if jnp.issubdtype(a.dtype, jnp.floating)
            else a.dtype), tree)
    return InferenceEngineV2(model, RaggedInferenceConfig(**ecfg))


def report(name: str, lowered, pool_bytes: int, layer_pool_bytes: int,
           no_pool=None, hlo_dir: str = "") -> dict:
    t0 = time.time()
    compiled = lowered.compile()
    hlo = compiled.as_text()
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(os.path.join(hlo_dir, re.sub(
                r"[^\w.]+", "_", name).strip("_") + ".hlo.txt"), "w") as f:
            f.write(hlo)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{name}: compiled in {time.time() - t0:.1f} s")
    print(f"  memory (XLA analysis): arguments "
          f"{mem.argument_size_in_bytes / GIB:.2f} GiB, outputs "
          f"{mem.output_size_in_bytes / GIB:.2f} GiB, aliased "
          f"{mem.alias_size_in_bytes / GIB:.2f} GiB (pools "
          f"{pool_bytes / GIB:.2f}), temporaries "
          f"{mem.temp_size_in_bytes / GIB:.3f} GiB, total "
          f"{total / GIB:.2f} GiB of 15.75")
    print(f"  Mosaic kernels: "
          f"{sorted(set(re.findall(r'dstpu_[a-z_]+', lowered.as_text())))}")
    big = big_instructions(hlo, layer_pool_bytes, no_pool)
    print(f"  instructions that materialize >= one layer of a pool "
          f"({layer_pool_bytes / 1e6:.1f} MB): {len(big)}")
    for nbytes, iname, op, shape, in_place in sorted(big, reverse=True):
        print(f"    {nbytes / 1e6:9.1f} MB  {op:10s} {iname}  {shape}  "
              + ("in place: shares its operand's buffer" if in_place
                 else "A BUFFER OF ITS OWN"))
    copies = weight_copies(hlo)
    print(f"  copies of a layer weight (>= {WEIGHT_COPY_FLOOR / 1e6:.1f} MB, "
          f"once a loop body): {len(copies)} = "
          f"{sum(c[0] for c in copies) / 1e6:.1f} MB")
    for nbytes, iname, shape, layout, leaf in sorted(copies, reverse=True):
        print(f"    {nbytes / 1e6:9.1f} MB  copy       {iname}  {shape}"
              f"{layout}  of {leaf}")
    return {"temp": mem.temp_size_in_bytes, "alias": mem.alias_size_in_bytes,
            "copied": [b for b in big if not b[4]]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        REPO, "benchmark", "configs", "mistral-7b-serve.json"))
    ap.add_argument("--num-pages", type=int, default=0,
                    help="pool size to compile for, if not the file's")
    ap.add_argument("--max-seqs", type=int, default=0,
                    help="decode rows (and state slots), if not the file's")
    ap.add_argument("--windows", default="",
                    help="chunk-program window buckets in pages, a,b,...; "
                    "default: the smallest that holds a chunk and "
                    "max_pages_per_seq")
    ap.add_argument("--hlo-dir", default="",
                    help="write each program's optimized HLO to a file here")
    args = ap.parse_args()

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with open(args.config) as f:
        config = json.load(f)
    engine = abstract_engine(config, {
        k: v for k, v in (("num_pages", args.num_pages),
                          ("max_seqs", args.max_seqs)) if v})
    device = topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
    one_chip = SingleDeviceSharding(device)

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, pools = on_chip(engine.params), on_chip(engine._pools)
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(pools))
    # the page leaves the layer types declared: K and V (and their scales
    # under kv_quant), or one latent leaf
    from deepspeed_tpu.inference.v2.model_runner import PAGE_LEAVES

    paged = {n: a for n, a in pools.items() if n in PAGE_LEAVES}
    k = next(iter(paged.values()))
    token_bytes = sum(
        a.shape[0] * -(-a.shape[-1] // 128) * 128 * a.dtype.itemsize
        for a in paged.values())
    # the floor for "pool-sized": one layer of the smallest large leaf (the
    # K pool, or a layer's state slots where the model keeps state)
    layer_pool_bytes = min(
        a.size * a.dtype.itemsize // a.shape[0] for a in pools.values()
        if a.size * a.dtype.itemsize >= 256 * 2 ** 20)
    block, ps, C = engine.block, engine.block.page_size, engine._chunk
    # (an 'eva' stack's table row is [summary pages | open-window pages])
    B, MP = block.max_seqs, engine._page_table.shape[1]
    print(f"aot_serve_step: {device.device_kind!r}, {config['name']}: "
          f"{k.shape[0]} layers, pools "
          f"{ {n: a.shape for n, a in pools.items()} } = "
          f"{pool_bytes / GIB:.2f} GiB, weights "
          f"{engine.param_bytes / GIB:.2f} GiB, max_seqs {B}, "
          f"max_pages_per_seq {MP}, chunk {C}; a cached token is "
          f"{token_bytes} B over the layers as laid out (rows padded to "
          f"whole lane tiles), "
          f"{sum(a.shape[0] * a.shape[-1] * a.dtype.itemsize for a in paged.values())}"
          f" B as declared")
    rings = {n: a for n, a in pools.items() if n.startswith("win_")}
    if rings:  # window layers keep a ring a slot, not pages
        print("  a slot's rings: " + ", ".join(
            f"{n} {a.shape[0]} layers x {a.shape[2]} rows x {a.shape[3]} = "
            f"{a.shape[0] * a.shape[2] * a.shape[3] * a.dtype.itemsize} B"
            for n, a in sorted(rings.items())) + " = "
            f"{sum(a.size // a.shape[1] * a.dtype.itemsize for a in rings.values())}"
            " B a slot over the layers as declared, "
            f"{sum(a.shape[0] * a.shape[2] * -(-a.shape[3] // 128) * 128 * a.dtype.itemsize for a in rings.values())}"
            " B as laid out")

    i32 = jnp.int32
    key = arr((2,), jnp.uint32)
    # where a layer of the smallest pool leaf is tens of MB, two kinds of
    # buffer are as large and are no pool: one layer's slice of a stacked
    # weight (XLA prefetches it) and rows of logits over the vocabulary
    weights = {(1, *a.shape[1:]) for a in jax.tree_util.tree_leaves(params)}
    V = engine.cfg.vocab_size

    def no_pool(dims):
        return tuple(dims) in weights or (
            dims[-1] == V and V > max(a.shape[-1] for a in pools.values()))
    if engine.blocks is not None:
        # a model that generates by blocks has the block program in the
        # decode program's place: max_seqs rows of block_length positions
        Bk = engine.cfg.block_length
        results = {"block_pass": report(
            f"block pass [{B} rows x {Bk} positions x {MP} pages]",
            engine.blocks._program.lower(
                params, pools, arr((B, Bk), i32), arr((B, Bk), jnp.bool_),
                arr((B,), i32), arr((B, MP), i32), arr((B,), jnp.bool_),
                arr((B,), i32)),
            pool_bytes, layer_pool_bytes, no_pool, args.hlo_dir)}
    else:
        results = {"decode": report(
            f"decode [{B} rows x {MP} pages]",
            engine._decode.lower(
                params, pools, arr((B,), i32), arr((B,), i32),
                arr((B, MP), i32), arr((B,), jnp.bool_),
                arr((B,), jnp.float32), arr((B,), i32), key),
            pool_bytes, layer_pool_bytes, no_pool, args.hlo_dir)}
    # a stack with a cross-decoder is handed the whole table row (one query
    # reads it through the decode kernel): one shape, and a program of its
    # own for the chunks that are not a prompt's last
    from deepspeed_tpu.models.layer_types import chunk_stops_early

    stops_early = chunk_stops_early(engine.cfg)
    windows = ([int(w) for w in args.windows.split(",")] if args.windows
               else [MP] if stops_early
               else sorted({max(1, C // ps), MP}))
    rows = C // ps
    from deepspeed_tpu.inference.v2.ragged import EvaRows

    if isinstance(engine.rows, EvaRows) and not args.windows:
        # a chunk's table: the closed windows' summary pages and the open
        # window's earlier pages, in the engine's power-of-two buckets; its
        # rows: the open pages it writes, then the summary pages
        ev = engine.rows
        rows += C // (ps * ev.chunk)
        most = ev.visible(block.max_seq_len) // ps + (ev.window - C) // ps
        windows = [max(1, ev.open_cap // 4)]
        while windows[-1] < most:
            windows.append(2 * windows[-1])
    # a model whose layers keep recurrent state is handed the sequence's slot
    slot = (arr((), i32),) if engine._state else ()
    for w in windows:
        results[f"chunk{w}"] = report(
            f"chunk [{C} tokens, window {w} pages]",
            engine._prefill_chunk.lower(
                params, pools, arr((C,), i32), arr((rows,), i32),
                arr((w,), i32), arr((), i32), arr((), i32), *slot),
            pool_bytes, layer_pool_bytes, no_pool, args.hlo_dir)
        if stops_early:
            results[f"chunk{w}.part"] = report(
                f"chunk, not a prompt's last [{C} tokens, window {w} pages]",
                engine._prefill_chunk_part.lower(
                    params, pools, arr((C,), i32), arr((C // ps,), i32),
                    arr((w,), i32), arr((), i32), arr((), i32), *slot),
                pool_bytes, layer_pool_bytes, no_pool, args.hlo_dir)
    ok = all(r["temp"] < GIB and r["alias"] >= pool_bytes
             and not r["copied"] for r in results.values())
    print("aot_serve_step: " + (
        "every program keeps the pools in place (temporaries under 1 GiB, "
        "pools aliased, no pool-sized buffer but the pools)" if ok else
        "NOT in place: see the temporaries, aliased bytes and instructions "
        "above"))
    return 0 if ok else 1


if __name__ == "__main__":
    # the programs are lowered here, outside any engine call: beneath the
    # frame an engine makes their first dispatch under (compile/deep_frame.py)
    from deepspeed_tpu.compile.deep_frame import under_deep_frame

    sys.exit(under_deep_frame(main))
