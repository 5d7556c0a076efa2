"""The platform helper and the compile-cache placement
(deepspeed_tpu/utils/platform.py): the one place that decides TPU-or-not,
and the one place that says where compiles are cached."""

import os

import jax
import pytest

from deepspeed_tpu.utils import platform as plat


def test_cpu_interprets_tpu_compiles_unknown_raises(monkeypatch):
    assert plat.platform() == "cpu"  # the test tier
    assert plat.on_tpu() is False and plat.pallas_interpret() is True
    monkeypatch.setattr(plat, "platform", lambda: "tpu")
    assert plat.on_tpu() is True and plat.pallas_interpret() is False
    # a plugin under another name must not silently get interpret mode
    # (or the kernels) — it is an error
    monkeypatch.setattr(plat, "platform", lambda: "made-up")
    with pytest.raises(RuntimeError, match="made-up"):
        plat.on_tpu()
    with pytest.raises(RuntimeError, match="made-up"):
        plat.pallas_interpret()


def test_auto_never_means_xla_on_tpu(monkeypatch):
    """On the chip `auto` can only mean the kernel: the attention pick
    returns the flash path, and asking the paged engine for the XLA gather
    path raises."""
    from deepspeed_tpu.inference.v2 import model_runner
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  _pick_attn, flash_on_mesh,
                                                  xla_attention)

    cfg = TransformerConfig(hidden_size=64, n_heads=4, n_layers=1)
    assert _pick_attn(cfg) is xla_attention
    assert model_runner._use_paged_kernel() is False
    monkeypatch.setattr(plat, "platform", lambda: "tpu")
    assert _pick_attn(cfg) is flash_on_mesh
    assert model_runner._use_paged_kernel() is True
    monkeypatch.setenv("DSTPU_PAGED_KERNEL", "0")
    with pytest.raises(RuntimeError, match="DSTPU_PAGED_KERNEL=0"):
        model_runner._use_paged_kernel()


def test_compile_cache_env_set_means_nothing_set_in_code(monkeypatch):
    monkeypatch.setattr(plat, "platform", lambda: "tpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert plat.ensure_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_env_unset_means_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        want = os.path.join(repo, ".jax_cache")
        # the CPU tier compiles in seconds and is left uncached
        assert plat.ensure_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.setattr(plat, "platform", lambda: "tpu")
        assert plat.ensure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        # the same path on every call: it is part of the cache key
        assert plat.DEFAULT_COMPILE_CACHE_DIR == want
        # a directory the caller configured is left alone
        jax.config.update("jax_compilation_cache_dir", "/callers/choice")
        assert plat.ensure_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == "/callers/choice"
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_holds_tpu_false_on_cpu_and_spawn_guards(monkeypatch):
    """A chip belongs to one process: code about to spawn a child that
    needs it checks holds_tpu() and refuses with a clear message."""
    from deepspeed_tpu.launcher.runner import build_launch_commands
    from deepspeed_tpu.serving.transport import (TransportError,
                                                 spawn_engine_server)

    assert plat.holds_tpu() is False
    # the caller names the platform; there is no silent CPU default
    with pytest.raises(ValueError, match="platform"):
        spawn_engine_server({"model": "tiny"})
    monkeypatch.setattr(plat, "holds_tpu", lambda: True)
    with pytest.raises(TransportError, match="holds the TPU"):
        spawn_engine_server({"model": "tiny", "platform": "tpu"})
    # two ranks on this host would each claim every local chip
    two_local = {"localhost": 1, "127.0.0.1": 1}
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(ValueError, match="one process"):
        build_launch_commands(two_local, "t.py", [])
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert len(build_launch_commands(two_local, "t.py", [])) == 2
