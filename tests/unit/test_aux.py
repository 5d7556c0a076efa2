"""Aux subsystem tests: launcher, elasticity, autotuner, activation
checkpointing, eigenvalue (reference tests/unit/{launcher,elasticity,
autotuning})."""

import numpy as np
import pytest

from deepspeed_tpu.autotuning.autotuner import Autotuner
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


# ------------------------------ autotuner ----------------------------------
def test_autotuner_picks_working_config():
    from tests.unit.simple_model import random_batch, simple_mlp_spec

    tuner = Autotuner(
        model_factory=simple_mlp_spec,
        base_config={"optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
        batch_factory=lambda mb: random_batch(batch_size=mb * 8, gas=1),
        tuning_space={"zero_stage": [0, 1], "micro_batch": [2, 4]},
        steps_per_trial=1)
    result = tuner.tune()
    assert result["best"] is not None
    assert result["throughput"] > 0
    assert len(result["trials"]) == 4


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


def test_autotuner_model_based_mode(devices8):
    """Model-based tuning (reference ModelBasedTuner): seeds + cost-model
    proposals find the grid's best without exhausting it."""
    import deepspeed_tpu
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from tests.unit.simple_model import random_batch, simple_mlp_spec

    tuner = Autotuner(
        model_factory=simple_mlp_spec,
        base_config={"optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
        batch_factory=lambda bs: random_batch(batch_size=bs * 8, gas=1),
        tuning_space={"zero_stage": [0, 1, 2], "micro_batch": [1, 2]},
        steps_per_trial=2, max_trials=5, mode="model")
    out = tuner.tune()
    assert out["best"] in [{"zero_stage": s, "micro_batch": m}
                           for s in (0, 1, 2) for m in (1, 2)]
    ran = [r for r in tuner.results if not r.get("pruned")]
    assert 3 <= len(ran) <= 5  # seeds + proposals, under budget
    assert out["throughput"] > 0


def test_autotuner_memory_pruning(monkeypatch, devices8):
    """Candidates whose analytical state floor exceeds HBM are skipped
    without compiling (reference fast-mode memory estimators)."""
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from tests.unit.simple_model import random_batch, simple_mlp_spec

    tuner = Autotuner(
        model_factory=simple_mlp_spec,
        base_config={"optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
        batch_factory=lambda bs: random_batch(batch_size=bs * 8, gas=1),
        tuning_space={"zero_stage": [0, 1], "micro_batch": [1]},
        steps_per_trial=1, mode="grid")
    # pretend the device has 1KB of HBM: every stage-0 candidate's floor
    # exceeds it; sharded stages divide by the mesh and may also exceed
    monkeypatch.setattr(tuner, "_device_memory", lambda: 1024)
    with pytest.raises(RuntimeError, match="all autotuning trials failed"):
        tuner.tune()
    assert all(r.get("pruned") for r in tuner.results), tuner.results


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


# -- parallel experiment scheduler (reference autotuning/scheduler.py:32) ---
def _tracking_runner(delay=0.05, tputs=None):
    """Mock runner that records concurrency and returns canned metrics."""
    import threading as _th
    import time as _t

    lock = _th.Lock()
    state = {"cur": 0, "peak": 0, "calls": []}

    def runner(exp, res):
        with lock:
            state["cur"] += 1
            state["peak"] = max(state["peak"], state["cur"])
            state["calls"].append(exp["name"])
        _t.sleep(delay)
        with lock:
            state["cur"] -= 1
        if tputs is None:
            return 100.0
        v = tputs.get(exp["name"], None)
        if isinstance(v, Exception):
            raise v
        return v

    return runner, state


def test_scheduler_respects_slots_and_max_parallel():
    """Concurrent trials over mock hosts: concurrency reaches the cap but
    never exceeds min(slot capacity, max_parallel)."""
    from deepspeed_tpu.autotuning.scheduler import Node, ResourceManager

    runner, state = _tracking_runner()
    rm = ResourceManager([Node("h0", 2), Node("h1", 2)], runner,
                         slots_per_exp=1, max_parallel=3)
    assert rm.parallel_peak() == 3
    rm.schedule_experiments([{"name": f"e{i}", "config": {"i": i}}
                             for i in range(10)])
    finished = rm.run()
    assert len(finished) == 10
    assert state["peak"] <= 3, state
    assert state["peak"] >= 2, f"never ran concurrently: {state}"
    # all slots restored
    assert all(n.free == n.slots for n in rm.nodes)


def test_scheduler_multi_slot_experiments_fit_per_node():
    """An experiment never spans nodes: 2-slot trials on 2-slot nodes run
    one per node."""
    from deepspeed_tpu.autotuning.scheduler import Node, ResourceManager

    runner, state = _tracking_runner()
    rm = ResourceManager([Node("h0", 2), Node("h1", 2)], runner,
                         slots_per_exp=2)
    rm.schedule_experiments([{"name": f"e{i}"} for i in range(6)])
    rm.run()
    assert state["peak"] <= 2
    assert all(n.free == n.slots for n in rm.nodes)


def test_scheduler_dedup_failures_and_early_stop():
    from deepspeed_tpu.autotuning.scheduler import Node, ResourceManager

    # dedup: the same experiment name scheduled twice runs once
    runner, state = _tracking_runner(delay=0.0)
    rm = ResourceManager([Node("h0", 1)], runner)
    rm.schedule_experiments([{"name": "same"}, {"name": "same"}])
    assert len(rm.run()) == 1

    # failures recorded, scheduler survives
    runner, _ = _tracking_runner(
        delay=0.0, tputs={"ok": 5.0, "bad": RuntimeError("boom")})
    rm = ResourceManager([Node("h0", 1)], runner)
    rm.schedule_experiments([{"name": "bad"}, {"name": "ok"}])
    recs = {r["name"]: r for r in rm.run()}
    assert recs["bad"]["throughput"] is None and "boom" in recs["bad"]["error"]
    assert recs["ok"]["throughput"] == 5.0

    # early stop: monotonically worse results drop the queued tail
    tputs = {f"e{i}": float(100 - i) for i in range(12)}
    runner, _ = _tracking_runner(delay=0.0, tputs=tputs)
    rm = ResourceManager([Node("h0", 1)], runner)
    rm.schedule_experiments([{"name": f"e{i}"} for i in range(12)])
    finished = rm.run(early_stop_patience=3)
    assert len(finished) < 12, "early stop never dropped the queue"


def test_autotuner_tune_parallel_picks_best(devices8):
    """tune_parallel over mock hosts: grid candidates dispatched through
    the ResourceManager; best survives; model mode refuses (sequential)."""
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.autotuning.scheduler import Node
    from tests.unit.simple_model import random_batch, simple_mlp_spec

    def make(mode="grid"):
        return Autotuner(
            model_factory=simple_mlp_spec,
            base_config={"optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
            batch_factory=lambda bs: random_batch(batch_size=bs * 8, gas=1),
            tuning_space={"zero_stage": [0, 1], "micro_batch": [1, 2, 4]},
            mode=mode)

    def runner(exp, res):
        c = exp["cand"]
        return 100.0 * c["micro_batch"] - 10.0 * c["zero_stage"]

    out = make().tune_parallel(runner, nodes=[Node("h0", 2), Node("h1", 2)],
                               max_parallel=4)
    assert out["best"] == {"zero_stage": 0, "micro_batch": 4}
    assert out["config"]["train_micro_batch_size_per_gpu"] == 4

    with pytest.raises(ValueError, match="sequential"):
        make("model").tune_parallel(runner)


def test_tune_parallel_refuses_local_subprocess_trials_when_holding_tpu(
        monkeypatch, tmp_path):
    """A parent that has touched the TPU holds the chip; a local trial
    subprocess that needs it would fail or hang — refused up front, and a
    parent that has not touched it skips HBM pruning to stay off JAX."""
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.autotuning.scheduler import (Node,
                                                    SubprocessTrialRunner)
    from deepspeed_tpu.utils import platform as plat
    from tests.unit.simple_model import simple_mlp_spec

    tuner = Autotuner(model_factory=simple_mlp_spec, base_config={},
                      batch_factory=lambda bs: None,
                      tuning_space={"micro_batch": [1, 2]}, mode="grid")
    runner = SubprocessTrialRunner(str(tmp_path / "trial.py"),
                                   results_dir=str(tmp_path / "res"))
    monkeypatch.setattr(plat, "holds_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="holds the chip"):
        tuner.tune_parallel(runner)
    # remote-only trials do not need this host's chips
    monkeypatch.setattr(tuner, "_pruned_pool", lambda: pytest.fail(
        "pruning would touch jax.devices() in the parent"))
    monkeypatch.setattr(SubprocessTrialRunner, "__call__",
                        lambda self, exp, res: 1.0)
    assert tuner.tune_parallel(runner, nodes=[Node("far", 1)])["best"]


def test_subprocess_trial_runner(tmp_path):
    """Real out-of-process trial: config handed via JSON file, metrics read
    from the last JSON stdout line (reference user_script contract)."""
    from deepspeed_tpu.autotuning.scheduler import (Node, Reservation,
                                                    SubprocessTrialRunner)

    script = tmp_path / "user_script.py"
    script.write_text(
        "import argparse, json, os\n"
        "p = argparse.ArgumentParser(); p.add_argument('--exp_config')\n"
        "a = p.parse_args()\n"
        "cfg = json.load(open(a.exp_config))\n"
        "print('noise line')\n"
        "print(json.dumps({'throughput': 7.0 * cfg['train_micro_batch_size_per_gpu'],\n"
        "                  'slots': os.environ['DSTPU_TRIAL_SLOTS']}))\n")
    runner = SubprocessTrialRunner(str(script),
                                   results_dir=str(tmp_path / "results"))
    node = Node("localhost", 2)
    node.free -= 1
    tput = runner({"name": "t0",
                   "config": {"train_micro_batch_size_per_gpu": 3}},
                  Reservation(node, 1))
    assert tput == 21.0
    assert (tmp_path / "results" / "t0" / "exp.json").exists()


def test_autotuner_tunes_fused_kernel():
    """fused_kernel rides the tuning space into the trial's optimizer
    params (single-device trials use the Pallas path when True)."""
    from tests.unit.simple_model import random_batch, simple_mlp_spec

    tuner = Autotuner(
        model_factory=simple_mlp_spec,
        base_config={"optimizer": {"type": "FusedAdam",
                                   "params": {"lr": 1e-3}}},
        batch_factory=lambda mb: random_batch(batch_size=mb * 8, gas=1),
        tuning_space={"fused_kernel": [False, True], "micro_batch": [2]},
        steps_per_trial=1)
    cfg_on = tuner._trial_config({"fused_kernel": True, "micro_batch": 2})
    assert cfg_on["optimizer"]["params"]["fused_kernel"] is True
    assert cfg_on["optimizer"]["params"]["lr"] == 1e-3  # params merged
    result = tuner.tune()
    assert result["best"] is not None and len(result["trials"]) == 2


def test_trial_runner_cross_host_launcher(tmp_path):
    """Cross-host dispatch (reference ResourceManager + pdsh/ssh launcher,
    autotuning/scheduler.py:32): a trial reserved on a remote node is
    launched through the launcher template with the trial env crossing as
    env(1) tokens; local nodes bypass the launcher."""
    import os
    import sys

    from deepspeed_tpu.autotuning.scheduler import (Node, Reservation,
                                                    SubprocessTrialRunner)

    fake_ssh = tmp_path / "fake_ssh.py"
    # mirror REAL ssh semantics: the trailing args are space-joined into
    # ONE string interpreted by the remote shell — this is what catches
    # unquoted paths/metachars (json-derived exp names contain both)
    fake_ssh.write_text(
        "import os, sys\n"
        "open(os.environ['FAKE_SSH_LOG'], 'a').write(sys.argv[1] + '\\n')\n"
        "os.execvp('/bin/sh', ['/bin/sh', '-c', ' '.join(sys.argv[2:])])\n")
    trial = tmp_path / "trial.py"
    trial.write_text(
        "import json, os, sys\n"
        "cfg = json.load(open(sys.argv[sys.argv.index('--exp_config') + 1]))\n"
        "print(json.dumps({'throughput': cfg['bs'] * 10.0,"
        " 'host': os.environ['DSTPU_TRIAL_HOST'],"
        " 'slots': os.environ['DSTPU_TRIAL_SLOTS']}))\n")
    log = tmp_path / "hosts.log"
    os.environ["FAKE_SSH_LOG"] = str(log)
    try:
        runner = SubprocessTrialRunner(
            str(trial), results_dir=str(tmp_path / "results"),
            launcher=[sys.executable, str(fake_ssh), "{host}"])
        # a default exp name is json.dumps(config): spaces AND quotes must
        # survive the remote shell (the repo quoting contract)
        remote = runner({"name": '{"bs": 4}', "config": {"bs": 4}},
                        Reservation(Node("worker-7", 4), 2))
        assert remote == 40.0
        assert log.read_text().splitlines() == ["worker-7"]
        local = runner({"name": "e2", "config": {"bs": 2}},
                       Reservation(Node("localhost", 4), 1))
        assert local == 20.0
        assert log.read_text().splitlines() == ["worker-7"]  # no new entry
    finally:
        os.environ.pop("FAKE_SSH_LOG", None)
