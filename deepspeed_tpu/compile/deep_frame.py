"""One deep interpreter frame beneath which programs are traced and lowered.

CPython 3.11+ keeps a thread's interpreter frames on a data stack of 16 KiB
chunks and unmaps a chunk the moment its first frame returns.  jax's trace
and lowering recurse hundreds of frames deep, so somewhere in them a loop
calls from the last bytes of a chunk, and maps and unmaps 16 KiB at every
call.  Where that falls follows the byte depth of the Python stack beneath a
``jit``'s first call, so a local added to a caller moved a start by seconds.

A frame of :data:`DEEP_FRAME_SLOTS` value-stack slots does not fit a 16 KiB
chunk: the interpreter gives it a chunk of its own, the next power of two
over its size, and every frame beneath it lives in that chunk's remainder —
as many bytes again, which no recursion of jax's reaches.  Entering the
frame is itself one map and unmap (0.15 - 0.26 ms on the chip's host), too
much for every step: the engines put a program's first dispatch beneath it,
the one that traces and lowers, and no later one (:func:`first_call_beneath`).
PERF.md section 6 (PR 52) has the numbers.  An interpreter without the
chunked stack runs this as a plain call.
"""

from ..telemetry.regions import abstract_args, note_dispatch

#: value-stack slots of :func:`under_deep_frame`'s frame, 8 bytes each
DEEP_FRAME_SLOTS = 1 << 17


def under_deep_frame(fn, /, *args, **kwargs):
    """``fn(*args, **kwargs)``, every frame of it in this frame's chunk."""
    return fn(*args, **kwargs)


under_deep_frame.__code__ = under_deep_frame.__code__.replace(
    co_stacksize=DEEP_FRAME_SLOTS)


def first_call_beneath(seen: set, key, fn, /, *args, **kwargs):
    """``fn(*args, **kwargs)``: beneath the deep frame the first time the
    caller's set ``seen`` meets ``key``, a plain call ever after.  The first
    call of a jitted ``fn`` is also where its program is noted for whoever
    asks of its regions later (``telemetry/regions.py``): the arguments'
    shapes are taken before the call, which may donate them."""
    if key in seen:
        return fn(*args, **kwargs)
    seen.add(key)
    shapes = abstract_args((args, kwargs))
    out = under_deep_frame(fn, *args, **kwargs)
    note_dispatch(fn, *shapes)
    return out
