"""A program call's host inputs as ONE int32 buffer, and the program that
takes them so.

On the chip's host every transfer to the device costs a quarter of a
millisecond before a byte moves, whatever its size (six of them were 1.65 ms
of a decode step's 2.7 ms of host time, serial with the device: PERF.md
section 6, PR 53).  So what the host built for one program call — a decode
step's five ``[max_seqs]`` rows and the page table, a chunk's ids, rows,
table row and offsets — is laid end to end in one int32 array
(:func:`pack_inputs`), crosses in one transfer, and is cut apart again
inside the program at static offsets (:func:`unpack_inputs`), which XLA
fuses into the first reads.  A float32 input crosses as its bits (a view,
never a conversion), a bool as 0 / 1.  The packed array is new at every
call, so a later write to a mirror the engine keeps (its page table) can
never reach what the device reads.

:class:`PackedProgram` is a serving program in this form: ``run`` is the
jitted program over ``(params, pools, layout, packed, *rest)``, which the
engine's ``_dispatch`` calls; called or lowered with the inputs apart, as
tests and tools do, it packs them first and runs the same program.
"""

import math
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: one input of a call: its shape and its dtype
Layout = Tuple[Tuple[Tuple[int, ...], np.dtype], ...]

_INT32 = np.dtype(np.int32)
_FLOAT32 = np.dtype(np.float32)
_BOOL = np.dtype(bool)


def layout_of(inputs: Sequence[Any]) -> Layout:
    """The static half of a packed call: each input's shape and dtype
    (arrays, numpy scalars and ``ShapeDtypeStruct``s alike)."""
    layout = tuple((tuple(a.shape), np.dtype(a.dtype)) for a in inputs)
    for _shape, dtype in layout:
        if dtype not in (_INT32, _FLOAT32, _BOOL):
            raise TypeError(f"a program's host inputs are int32, float32 "
                            f"or bool, not {dtype}")
    return layout


def _bits(a: np.ndarray) -> np.ndarray:
    if a.dtype == _INT32:
        return a
    if a.dtype == _FLOAT32:
        return a.view(np.int32)
    return a.astype(np.int32)  # bool (layout_of has refused the rest)


def pack_inputs(inputs: Sequence[Any]) -> Tuple[np.ndarray, Layout]:
    """``inputs`` end to end in one new int32 array, and their layout."""
    arrays = [np.asarray(a) for a in inputs]
    layout = layout_of(arrays)
    return np.concatenate([_bits(a).reshape(-1) for a in arrays]), layout


def unpack_inputs(packed: jax.Array, layout: Layout):
    """Inside a program: the inputs back out of ``packed``, bit for bit."""
    out, off = [], 0
    for shape, dtype in layout:
        n = math.prod(shape)
        a = jax.lax.slice(packed, (off,), (off + n,)).reshape(shape)
        off += n
        if dtype == _FLOAT32:
            a = jax.lax.bitcast_convert_type(a, jnp.float32)
        elif dtype == _BOOL:
            a = a != 0
        out.append(a)
    return out


class PackedProgram:
    """``fn(params, pools, *inputs, *rest)`` jitted over packed inputs, the
    pools donated.  ``rest`` counts the trailing arguments that are not host
    inputs (a sampling key on the device, a static horizon); ``static_rest``
    names those of them that are static."""

    def __init__(self, fn: Callable, rest: int = 0,
                 static_rest: Sequence[int] = ()):
        def run(params, pools, layout, packed, *tail):
            return fn(params, pools, *unpack_inputs(packed, layout), *tail)

        # the program keeps the name it has in traces and in the set-up
        # ledger (``jit__decode_and_sample``, ``jit__lambda_``)
        run.__name__ = run.__qualname__ = fn.__name__
        self._fn, self._rest = fn, rest
        self.run = jax.jit(run, donate_argnums=(1,),
                           static_argnums=(2, *(4 + i for i in static_rest)))

    def _split(self, args):
        n = len(args) - self._rest
        return args[:n], args[n:]

    def __call__(self, params, pools, *args):
        inputs, tail = self._split(args)
        packed, layout = pack_inputs(inputs)
        return self.run(params, pools, layout, packed, *tail)

    def apart(self, static_argnums: Sequence[int] = ()):
        """The function jitted over its inputs apart, as a serving program
        was before they were packed: the reference of a test that compares
        token streams, or pins the text of what runs behind the slices."""
        return jax.jit(self._fn, donate_argnums=(1,),
                       static_argnums=tuple(static_argnums))

    def lower(self, params, pools, *args):
        inputs, tail = self._split(args)
        layout = layout_of(inputs)
        size = sum(math.prod(shape) for shape, _dtype in layout)
        return self.run.lower(params, pools, layout,
                              jax.ShapeDtypeStruct((size,), jnp.int32), *tail)
