"""Model regions: device time of XLA's own operations, read by the part of
the model that wrote them (docs/OBSERVABILITY.md "Regions").

A Mosaic kernel has a name a trace can be searched for; an XLA fusion is
``fusion.167`` and its event in a device trace carries the instruction's text
and two times, no scope.  So the scope comes from the program:

* :func:`region` opens a ``jax.named_scope`` whose component is marked
  (``region.mlp``: not to be mistaken for jax's own ``jit(..)``, ``while``,
  ``body``, ``checkpoint``, ``transpose(jvp(..))``) where the model code
  writes the work.  :data:`REGIONS` is the closed vocabulary.  A scope is
  trace-time metadata: the compiled program is the same but for
  ``metadata={..}``.
* an engine leaves a :func:`note_dispatch` at a program's first dispatch:
  the traced program (its jaxpr, which ``jit`` holds anyway; no array, and
  nothing of the engine), one small record a distinct program.
* :func:`region_tables` builds, on first ask, for every program so noted
  ``(instruction name, result shape) -> (region, phase, mixed)`` from the
  compiled program's own text, whose instructions — fused ones included —
  carry ``metadata={op_name="jit(..)/../region.mlp/dot_general"}``.  Nothing
  is lowered, compiled or parsed before someone asks; the tables outlive the
  engines.  :func:`region_index` merges them into one lookup for a reader of
  device events (``timeline.decompose_events``, the benchmark's
  ``part_ms``).
"""

from __future__ import annotations

import collections
import re
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

#: the parts of a model a device operation can belong to, outermost last:
#: each a thing a ``perf_opt`` issue could be written about
REGIONS = (
    "embed",          # token / position tables' gathers
    "norm",           # the norms before a mixer and a feed-forward part
    "attn_qkv",       # projections in, q/k norms, rotary
    "attn_glue",      # masks, transposes and K/V writes round a kernel,
                      # page gathers, XLA attention, a gate a head, the sink
    "attn_out",       # the output projection
    "mlp",            # the dense feed-forward part
    "router",         # router logits, scores, top-k
    "moe_route",      # the picks' sort, row maps, pad
    "moe_glue",       # rows to and from the buffer where XLA moves them,
                      # SwiGLU over the buffer, the experts' einsums
    "shared_expert",  # the shared expert and PR-MoE's residual MLP
    "conv_mixer",     # the gated short convolution (LFM2)
    "state_glue",     # what surrounds the recurrent-state kernels (delta
                      # rule, selective scan, gated memory unit)
    "latent_expand",  # latent attention's projections, gather and absorbs
    "mhc",            # a residual of several streams' mixing (hyper-
                      # connections): the norm over the streams, the
                      # coefficients' projection, the Sinkhorn rounds, the
                      # read-in and the write-back
    "eva_pool",       # EVA attention's summaries: a chunk's pooling softmax,
                      # the pooled key and value, their writes to the pages
    "eva_glue",       # what surrounds EVA attention's kernels: the composed
                      # page table, the open window's row writes, the gathers
                      # of summaries and open rows for a chunk, the XLA form
    "head",           # final norm and logits
    "loss",           # log-softmax, the pick, the mean
    "sample",         # arg-max, temperature sampling, the reveal rule
    "optimizer",      # unscale, norm, clip, accumulate, update, casts
    "stack",          # a layer loop itself: the stacked weights' slices,
                      # the residuals' updates, the residual adds
)
#: an operation no table knows, or that two tables give to different regions
UNSCOPED = "unscoped"

_MARK = "region."
_SCOPE_NAMES = {r: _MARK + r for r in REGIONS}
_scope = jax.named_scope  # (a test swaps this for a no-op)


def region(name: str):
    """``with region("mlp"):`` — the operations traced inside belong to the
    region ``name`` of :data:`REGIONS`; the innermost region wins."""
    return _scope(_SCOPE_NAMES[name])


# ------------------------------------------------------------ program notes
_lock = threading.Lock()
#: traced programs no one has asked of yet: the newest, so that a process
#: that builds engines without end and never asks keeps a bounded number
_notes: collections.deque = collections.deque(maxlen=256)
_tables: List[Dict[str, Any]] = []     # built: {"program", "rows", "seconds"}


def abstract_args(args):
    """``args`` with every array a ``ShapeDtypeStruct`` of its shape and
    dtype (static arguments as they are), taken BEFORE a call that donates
    its buffers.  A committed array keeps its sharding and one that is not
    committed has none, as the call itself saw them: the lowering is then
    the one ``jit`` holds, and its executable with it."""
    def one(a):
        if isinstance(a, jax.Array):
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=a.sharding if a.committed else None)
        if isinstance(a, np.ndarray):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)
        return a
    return jax.tree_util.tree_map(one, args)


def note_dispatch(jitted, args, kwargs) -> None:
    """Leave what :func:`region_tables` needs to ask for the compiled text
    of ``jitted`` over ``(args, kwargs)`` (``abstract_args``' result), right
    AFTER the program's first call: ``jit`` then holds the jaxpr, and
    tracing again is a look-up.  What is kept is the traced program — no
    array, and nothing of the engine that built ``jitted``.  A plain
    function (a test's stub of a program) leaves no note."""
    trace = getattr(jitted, "trace", None)
    if trace is None:
        return
    traced = trace(*args, **kwargs)
    with _lock:
        _notes.append(traced)


def noted_programs() -> int:
    """Programs noted over the process's life, asked of or not."""
    with _lock:
        return len(_notes) + len(_tables)


def region_tables() -> List[Dict[str, Any]]:
    """One table a noted program, built now for those not yet asked of:
    ``{"program": the module's name, "rows": {(instruction, shape): (region,
    phase, mixed)}, "seconds": what the build took}``.  The program ran, so
    its lowering and executable are ``jit``'s own (or the persistent
    cache's): the cost is the text and its parse."""
    with _lock:
        pending = list(_notes)
        _notes.clear()
    for traced in pending:
        t0 = time.perf_counter()
        text = traced.lower().compile().as_text()
        program, rows = parse_program_text(text)
        with _lock:
            _tables.append({"program": program, "rows": rows,
                            "seconds": time.perf_counter() - t0})
    with _lock:
        return list(_tables)


def reset_regions() -> None:
    """Forget every note and table (tests)."""
    with _lock:
        _notes.clear()
        _tables.clear()


Key = Tuple[str, str, str]          # program, instruction, shape
Entry = Tuple[str, str, bool]       # region, phase, mixed


def region_index(tables: Optional[List[Dict[str, Any]]] = None
                 ) -> Dict[Key, Entry]:
    """The tables as one lookup ``(program, instruction, shape) -> (region,
    phase, mixed)``.  Programs that share a name (a chunk program's buckets
    are all ``jit__lambda``) share keys: where two of them give a key to
    different regions it is :data:`UNSCOPED`, never a guess."""
    if tables is None:
        tables = region_tables()
    out: Dict[Key, Entry] = {}
    for table in tables:
        program = table["program"]
        for (name, shape), entry in table["rows"].items():
            key = (program, name, shape)
            seen = out.get(key)
            if seen is not None and seen[0] != entry[0]:
                entry = (UNSCOPED, seen[1], True)
            out[key] = entry
    return out


def lookup_region(index: Dict[Key, Entry], program: str, text: str) -> Entry:
    """The entry of a device event: ``program`` as its ``XLA Modules`` event
    names it (``jit__lambda(9018761753900999714)``), ``text`` the event's
    name — the instruction's whole text."""
    name, shape, _op = instruction_key(text)
    return index.get((program_name(program), name, shape),
                     (UNSCOPED, "forward", False))


# ------------------------------------------------------------- text parsing
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REGION = re.compile(re.escape(_MARK) + r"(\w+)")
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_LOOP = re.compile(r"\b(?:body|condition)=%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_PROGRAM_RUN = re.compile(r"\(\d+\)$")
_NAME = re.compile(r"%([\w.\-]+)")
#: instructions that say nothing of where a fusion's work was written
_SILENT = ("parameter", "constant", "bitcast", "get-tuple-element", "tuple")
_PRODUCTS = ("dot", "convolution")
#: a program's inputs and constants: no one wrote them, and nothing is
#: inferred for them or through them
_LEAVES = ("parameter", "constant")


def program_name(module_event: str) -> str:
    """``jit__lambda(9018761753900999714)`` -> ``jit__lambda``."""
    return _PROGRAM_RUN.sub("", module_event.strip())


def instruction_key(text: str) -> Tuple[str, str, str]:
    """``%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(..), kind=..`` ->
    (``fusion.3``, ``bf16[8,128]``, ``fusion``): the name, the result shape
    without layouts (a tuple's in parentheses) and the opcode — of a line
    of a compiled program's text and of a device event's name alike."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text.strip().lstrip("%"), "", ""
    name = head.split()[-1].lstrip("%")
    rest = rest.lstrip()
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        end += 1
    else:
        end = rest.find(" ")
        if end < 0:
            end = len(rest)
    shape = _LAYOUT.sub("", rest[:end]).replace(" ", "")
    opcode = rest[end:].lstrip().split("(", 1)[0].strip()
    return name, shape, opcode


def _operands(line: str, opcode: str) -> List[str]:
    """The names of an instruction's operands, from its line of a compiled
    program's text (``.. fusion(%a, %b), kind=..`` -> ``[a, b]``)."""
    start = line.find(" " + opcode + "(")
    if start < 0:
        return []
    start += len(opcode) + 2
    depth, end = 1, start
    while end < len(line) and depth:
        depth += (line[end] == "(") - (line[end] == ")")
        end += 1
    return _NAME.findall(line[start:end])


def op_name_entry(op_name: str) -> Tuple[str, str]:
    """(region, phase) of an ``op_name`` path: the innermost marked
    component (the last one: a transform wraps what is outside it, as in
    ``transpose(jvp(region.stack))/while/body/region.mlp/dot_general``);
    ``replay`` under a rematerialised computation, ``backward`` under a
    transpose, else ``forward``."""
    marks = _REGION.findall(op_name)
    found = marks[-1] if marks and marks[-1] in _SCOPE_NAMES else UNSCOPED
    if "rematted_computation" in op_name:
        return found, "replay"
    return found, "backward" if "transpose(" in op_name else "forward"


def _named(entry):
    """``entry`` if it names a region, else None."""
    return entry if entry is not None and entry[0] != UNSCOPED else None


class _Instr(NamedTuple):
    name: str
    shape: str
    opcode: str
    entry: Optional[Tuple[str, str]]  # of its own op_name; None: it has none
    calls: Optional[str]              # a fusion's computation
    is_root: bool
    operands: List[str]


def parse_program_text(text: str):
    """A compiled program's text -> (the module's name, ``{(instruction,
    shape): (region, phase, mixed)}`` over every instruction of every
    computation).  A fusion takes the region of the ``dot`` or
    ``convolution`` it holds, else of its root (of the last instruction
    that names a region where the root is a tuple), else its own; ``mixed``
    where the instructions fused into it were written in different
    regions.  An instruction XLA made itself carries no ``op_name`` at all
    (a layout copy before a kernel, an asynchronous copy's two halves, a
    tuple): it takes the entry of the instruction it feeds — its first user
    in its computation — else of the one that feeds it; what neither names
    stays :data:`UNSCOPED` — but for what XLA hangs on a loop itself (a
    slice of an operand sunk into the body), which is the loop's.  An
    ``op_name`` that is no path (a copy of an argument carries the
    argument's name) is none."""
    module = ""
    comps: Dict[str, List[_Instr]] = {}
    loops: Dict[str, Tuple[str, str]] = {}  # a loop's body and condition
    cur: Optional[List[_Instr]] = None
    for line in text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
            elif not module:
                m = _MODULE.match(line)
                if m:
                    module = m.group(1)
            continue
        if line.startswith("}"):
            cur = None
            continue
        if " = " not in line:
            continue
        name, shape, opcode = instruction_key(line)
        m = _OP_NAME.search(line)
        calls = _CALLS.search(line) if opcode == "fusion" else None
        written = m is not None and "/" in m.group(1)
        if opcode == "while" and written:
            for comp in _LOOP.findall(line):
                loops[comp] = op_name_entry(m.group(1))
        cur.append(_Instr(name, shape, opcode,
                          op_name_entry(m.group(1)) if written else None,
                          calls.group(1) if calls else None,
                          line.lstrip().startswith("ROOT "),
                          _operands(line, opcode)))

    resolved: Dict[str, Tuple[Optional[Tuple[str, str]], frozenset]] = {}

    def of_computation(comp: str):
        """-> (the entry its fusion takes, the regions written inside)."""
        if comp in resolved:
            return resolved[comp]
        resolved[comp] = (None, frozenset())  # (a cycle cannot occur)
        product = root = last = None
        inside = set()
        for ins in comps.get(comp, ()):
            entry = ins.entry
            if ins.calls:
                inner, regions = of_computation(ins.calls)
                entry = _named(inner) or entry
                inside |= regions
            if ins.opcode in _SILENT or entry is None:
                continue
            if entry[0] != UNSCOPED:
                inside.add(entry[0])
                last = entry
            if ins.opcode in _PRODUCTS and product is None:
                product = entry
            if ins.is_root:
                root = entry
        if root is None or root[0] == UNSCOPED:
            root = last or root
        resolved[comp] = (product or root, frozenset(inside))
        return resolved[comp]

    rows: Dict[Tuple[str, str], Entry] = {}
    for comp, instrs in comps.items():
        found: Dict[str, Optional[Tuple[str, str]]] = {}
        mixed_of: Dict[str, bool] = {}
        for ins in instrs:
            entry = ins.entry
            if ins.calls:
                inner, regions = of_computation(ins.calls)
                entry = _named(inner) or entry
                mixed_of[ins.name] = len(regions) > 1
            found[ins.name] = entry
        # what XLA made itself: from the instruction it feeds (users come
        # later in a scheduled computation), else from the one that feeds it
        user: Dict[str, str] = {}
        for ins in reversed(instrs):
            for operand in ins.operands:
                user[operand] = ins.name  # (ends at the first user)
        made = {ins.name for ins in instrs
                if found[ins.name] is None and ins.opcode not in _LEAVES}

        for ins in reversed(instrs):
            if ins.name in made and ins.name in user:
                found[ins.name] = _named(found.get(user[ins.name]))
        for ins in instrs:
            if ins.name in made and found[ins.name] is None:
                found[ins.name] = next(
                    (found[o] for o in ins.operands if _named(found.get(o))),
                    loops.get(comp))
        for ins in instrs:
            region_, phase = found[ins.name] or (UNSCOPED, "forward")
            rows[(ins.name, ins.shape)] = (region_, phase,
                                           mixed_of.get(ins.name, False))
    return module, rows
