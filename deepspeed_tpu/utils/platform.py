"""Which device this process runs on, and where its compiles are cached.

The ONE place that decides "TPU or not": every Pallas kernel's
compiled-vs-interpret choice and every ``auto`` implementation pick goes
through :func:`on_tpu`.  Only two platforms are supported — ``tpu`` (the
product) and ``cpu`` (the test tier: 8 virtual devices, kernels
interpreted).  Anything else raises, so a mis-registered plugin can never
make the kernels run interpreted, or be bypassed, without saying so.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

#: fixed in-checkout compile cache used when ``JAX_COMPILATION_CACHE_DIR``
#: is not set (listed in .gitignore).  The path is part of the cache key,
#: so it is never built from a temp name, pid or time.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def platform() -> str:
    """The platform JAX selected (``jax.default_backend()``)."""
    return jax.default_backend()


def on_tpu() -> bool:
    """True on ``tpu``, False on ``cpu``; any other platform raises."""
    p = platform()
    if p == "tpu":
        return True
    if p == "cpu":
        return False
    raise RuntimeError(
        f"unsupported JAX platform {p!r}: deepspeed_tpu runs compiled on "
        "'tpu' and interpreted on 'cpu' (tests) — refusing to guess which "
        "of the two an unknown platform should get")


def holds_tpu() -> bool:
    """True when THIS process has initialized the TPU backend.  It then owns
    the host's chips: a child process that needs them fails or hangs, so
    code about to spawn one checks here first.  Never initializes a backend
    itself."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized() and on_tpu()


def pallas_interpret() -> bool:
    """``interpret=`` for every ``pl.pallas_call``: only the CPU test tier
    interprets."""
    return not on_tpu()


def ensure_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache, before the first compile.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself — nothing is set
    in code (returns None).  Unset: ``jax_compilation_cache_dir`` becomes
    :data:`DEFAULT_COMPILE_CACHE_DIR` unless the caller already configured
    one.  Only on the TPU: its programs take minutes to compile, the CPU
    tier's take seconds and XLA:CPU logs kilobytes on every cached load.
    Asks JAX for the platform, so in a multi-process job call it after
    ``jax.distributed.initialize``.  Returns the directory this call set,
    if any."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    if jax.config.jax_compilation_cache_dir or not on_tpu():
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
