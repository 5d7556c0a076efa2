"""Aux subsystem tests: launcher, elasticity, activation checkpointing
(reference tests/unit/{launcher,elasticity})."""

import numpy as np
import pytest

from deepspeed_tpu.elasticity.elasticity import (compute_elastic_config,
                                                 ensure_immutable_elastic_config,
                                                 get_compatible_gpus)
from deepspeed_tpu.launcher.runner import (build_launch_commands, filter_hosts,
                                           parse_hostfile)


# ------------------------------ launcher -----------------------------------
def test_parse_hostfile():
    hosts = parse_hostfile("worker-1 slots=4\nworker-2 slots=8\n# comment\n",
                           is_text=True)
    assert hosts == {"worker-1": 4, "worker-2": 8}


def test_parse_hostfile_duplicate_raises():
    with pytest.raises(ValueError):
        parse_hostfile("a slots=1\na slots=2", is_text=True)


def test_filter_include_exclude():
    hosts = parse_hostfile("a slots=1\nb slots=1\nc slots=1", is_text=True)
    assert list(filter_hosts(hosts, include="a@c")) == ["a", "c"]
    assert list(filter_hosts(hosts, exclude="b")) == ["a", "c"]
    with pytest.raises(ValueError):
        filter_hosts(hosts, include="zzz")
    with pytest.raises(ValueError):
        filter_hosts(hosts, exclude="a@b@c")


def test_build_launch_commands_env():
    hosts = parse_hostfile("h1 slots=4\nh2 slots=4", is_text=True)
    cmds = build_launch_commands(hosts, "train.py", ["--foo", "1"])
    assert len(cmds) == 2
    joined = " ".join(cmds[0])
    assert "DSTPU_COORDINATOR=h1:29500" in joined
    assert "DSTPU_NUM_PROCESSES=2" in joined
    assert "DSTPU_PROCESS_ID=0" in joined
    assert "DSTPU_PROCESS_ID=1" in " ".join(cmds[1])
    assert cmds[0][0] == "ssh"


def test_single_host_local_command():
    cmds = build_launch_commands({"localhost": 8}, "t.py", [])
    assert cmds[0][0] == "bash"


# ------------------------------ elasticity ---------------------------------
def test_elastic_batch_divisibility():
    batch, gpus = get_compatible_gpus([2, 4], max_train_batch_size=64,
                                      min_gpus=1, max_gpus=64)
    assert batch <= 64
    for g in gpus:
        assert batch % g == 0


def test_compute_elastic_config_resolves_micro_batch():
    cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 128,
                          "micro_batch_sizes": [2, 4], "min_gpus": 1,
                          "max_gpus": 32}}
    batch, gpus, info = compute_elastic_config(cfg, world_size=gpus_pick(cfg))
    assert info["micro_batch_per_gpu"] in (2, 4)
    assert batch == info["micro_batch_per_gpu"] * \
        info["gradient_accumulation_steps"] * gpus_pick(cfg)


def gpus_pick(cfg):
    batch, gpus, _ = compute_elastic_config(cfg)
    return gpus[len(gpus) // 2]


def test_elastic_disabled_raises():
    with pytest.raises(ValueError):
        compute_elastic_config({"elasticity": {"enabled": False}})


def test_elastic_immutability():
    a = {"elasticity": {"enabled": True, "max_train_batch_size": 100}}
    b = {"elasticity": {"enabled": True, "max_train_batch_size": 200}}
    ensure_immutable_elastic_config(a, a)
    with pytest.raises(ValueError):
        ensure_immutable_elastic_config(a, b)


# -------------------------- activation checkpointing ------------------------
def test_checkpoint_module_api():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.runtime.activation_checkpointing import checkpointing

    checkpointing.configure(policy="nothing_saveable")

    def f(x):
        return jnp.sum(jnp.tanh(x @ x.T))

    x = jnp.ones((8, 8))
    out = checkpointing.checkpoint(f, x)
    g = jax.grad(lambda x: checkpointing.checkpoint(f, x))(x)
    assert np.isfinite(float(out))
    assert g.shape == x.shape


# ------------------------- multinode runners --------------------------------
def test_multinode_runner_commands():
    """Command construction for every backend (reference
    tests/unit/launcher/test_multinode_runner.py over
    multinode_runner.py:55-411)."""
    from collections import OrderedDict

    from deepspeed_tpu.launcher.multinode_runner import RUNNERS, get_runner

    hosts = OrderedDict([("worker-0", 1), ("worker-1", 1)])
    for name, cls in RUNNERS.items():
        r = get_runner(name, hosts, master_port=1234,
                       export_env={"FOO": "bar"})
        cmd = r.get_cmd("train.py", ["--x", "1"])
        joined = " ".join(cmd)
        assert cmd[0] == cls.launcher_binary, (name, cmd)
        assert "train.py" in joined and "--x" in joined, (name, cmd)
        # every backend must deliver coordinator + world size
        assert "DSTPU_COORDINATOR" in joined, (name, cmd)
        assert "worker-0:1234" in joined, (name, cmd)
        assert "DSTPU_NUM_PROCESSES" in joined and "2" in joined, (name, cmd)
        assert "FOO" in joined, (name, cmd)

    # backend-specific shapes
    slurm = get_runner("slurm", hosts).get_cmd("t.py", [])
    assert "--ntasks" in slurm and "worker-0,worker-1" in " ".join(slurm)
    ompi = get_runner("openmpi", hosts).get_cmd("t.py", [])
    assert "-n" in ompi and "worker-0:1,worker-1:1" in " ".join(ompi)
    pdsh = get_runner("pdsh", hosts).get_cmd("t.py", [])
    assert "DSTPU_PROCESS_ID=%n" in " ".join(pdsh)  # pdsh rank substitution

    with pytest.raises(ValueError, match="unknown launcher"):
        get_runner("nope", hosts)


def test_comm_env_rank_discovery(monkeypatch):
    """comm.init_distributed resolves rank/size from MPI/SLURM env when
    DSTPU_* is absent (the runners' rank contract)."""
    from deepspeed_tpu.comm import comm as C

    captured = {}

    def fake_init(coordinator_address, num_processes, process_id):
        captured.update(addr=coordinator_address, n=num_processes,
                        pid=process_id)

    monkeypatch.setattr(C, "_INITIALIZED", False)
    monkeypatch.setattr(C.jax.distributed, "initialize", fake_init)
    monkeypatch.setenv("DSTPU_COORDINATOR", "w0:29500")
    monkeypatch.delenv("DSTPU_NUM_PROCESSES", raising=False)
    monkeypatch.delenv("DSTPU_PROCESS_ID", raising=False)
    monkeypatch.setenv("SLURM_NTASKS", "4")
    monkeypatch.setenv("SLURM_PROCID", "3")
    C.init_distributed()
    assert captured == {"addr": "w0:29500", "n": 4, "pid": 3}
    monkeypatch.setattr(C, "_INITIALIZED", True)  # leave global as the suite expects


def test_set_random_seed():
    """Reference runtime/utils.py set_random_seed: host RNGs seeded, device
    key returned."""
    import random

    import numpy as np

    from deepspeed_tpu.runtime.utils import set_random_seed

    k1 = set_random_seed(1234)
    a = (random.random(), np.random.rand())
    k2 = set_random_seed(1234)
    b = (random.random(), np.random.rand())
    assert a == b
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))


