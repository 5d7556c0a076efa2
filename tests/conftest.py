"""Test harness configuration.

The reference simulates multi-node as multi-process-single-host with a
file-store rendezvous (tests/unit/common.py DistributedTest).  The TPU
analogue: ONE process with 8 virtual CPU devices
(``--xla_force_host_platform_device_count``) and real XLA collectives over a
``jax.sharding.Mesh`` — the "Gloo-equivalent" device-free CI mode
(SURVEY.md §4).
"""

import os

# Must happen before any CPU backend is created.  Tests always run on the
# virtual CPU mesh (set DSTPU_TEST_PLATFORM to override, e.g. to run on a
# real chip).
_platform = os.environ.get("DSTPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import sys  # noqa: E402

# CI wrappers run this suite under `timeout ... | tee log` and count
# progress dots from the log.  Two buffering layers can eat that
# progress when the timeout SIGTERMs the interpreter mid-run: the plain
# stdio block buffer, and — with pytest's default fd-capture — the
# dup'd stream the terminal reporter writes through (which `python -u`
# does NOT reach).  Line-buffer the visible streams here, and flush the
# terminal reporter after every test below, so every completed test's
# dot is already on disk when the axe falls.
for _stream in (sys.stdout, sys.stderr):
    try:
        _stream.reconfigure(line_buffering=True)
    except (AttributeError, ValueError):
        pass

import jax  # noqa: E402
import pytest  # noqa: E402

# The tests never take a persistent compile cache (the package places one
# only on the TPU; this also covers a JAX_COMPILATION_CACHE_DIR from the
# environment): the recompile sentinel's tests count backend compiles, which
# a warm cache would hide.
jax.config.update("jax_enable_compilation_cache", False)

_terminal_reporter = None


def pytest_configure(config):
    global _terminal_reporter
    _terminal_reporter = config.pluginmanager.get_plugin("terminalreporter")


@pytest.hookimpl(trylast=True)
def pytest_runtest_logreport(report):
    # runs on every phase report; by teardown the test's progress dot has
    # been written to the reporter's (possibly capture-dup'd) stream
    if report.when == "teardown" and _terminal_reporter is not None:
        try:
            _terminal_reporter._tw.flush()
        except Exception:
            pass


# The <2-minute smoke tier for perf-round edit loops (README "Testing"):
# engine/config/mesh cores in full plus one representative each from the
# pipeline, MoE-EP and ZeRO-3 structural suites.  Run: pytest -m smoke
_SMOKE = (
    "unit/test_engine.py",
    "unit/test_config.py",
    "unit/test_mesh_and_comm.py",
    "unit/test_pipeline.py::test_pipeline_loss_matches_dense",
    "unit/test_pipeline.py::test_partition_balanced",
    "unit/test_moe_ep.py::test_ep_dropless_matches_spmd_exactly",
    "unit/test_zeropp.py::test_stage3_gathers_stay_inside_layer_loop",
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        if any(item.nodeid.startswith(p) for p in _SMOKE):
            item.add_marker(pytest.mark.smoke)


@pytest.fixture(autouse=True)
def _reset_topology():
    """Each test builds its own mesh topology."""
    from deepspeed_tpu.parallel import mesh

    mesh.reset_topology()
    yield
    mesh.reset_topology()


@pytest.fixture
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]
