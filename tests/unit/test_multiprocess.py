"""REAL multi-process distributed tests (reference tests/unit/common.py
DistributedTest spawns worker processes with a file-store rendezvous).

Everything else in this suite simulates multi-host as one process with 8
virtual devices; these tests spawn TWO actual processes that rendezvous
through ``comm.init_distributed``'s launcher env contract
(DSTPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID) and exercise the code that
only runs when ``jax.process_count() > 1``:

  * cross-process collectives through the engine (data-parallel training
    step over a 2-process mesh, loss identical on both ranks);
  * ``monitored_barrier``'s coordination-service path against the REAL
    distributed client (wait_at_barrier or KV fallback);
  * the multi-host partitioned checkpoint writer (per-process shard files
    + load back).
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm import comm

    comm.init_distributed()  # env contract: DSTPU_COORDINATOR/.../PROCESS_ID
    assert jax.process_count() == 2, jax.process_count()
    rank = jax.process_index()

    # REAL coordination-service barrier (single-process tests can't reach it)
    comm.monitored_barrier("mp-entry", timeout_s=60.0)

    from tests.unit.simple_model import random_batch, simple_mlp_spec

    engine, *_ = deepspeed_tpu.initialize(
        model=simple_mlp_spec(),
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": int(os.environ["T_STAGE"])},
                "mesh": {"data": 2}})
    losses = []
    fixed = random_batch(batch_size=16, seed=0, gas=1)
    for i in range(10):
        losses.append(float(engine.train_batch(fixed)))
    assert losses[-1] < losses[0], losses
    # data-parallel math: both ranks must see the IDENTICAL loss
    print(f"RANK{rank} LOSSES {' '.join(f'{l:.6f}' for l in losses)}",
          flush=True)

    # multi-host partitioned checkpoint (jax.process_count() > 1 path)
    ckpt = os.environ["T_CKPT"]
    engine.save_checkpoint(ckpt, "mp")  # partitioned=None -> multi-host auto
    comm.monitored_barrier("mp-saved", timeout_s=60.0)
    engine2, *_ = deepspeed_tpu.initialize(
        model=simple_mlp_spec(),
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": int(os.environ["T_STAGE"])},
                "mesh": {"data": 2}})
    engine2.load_checkpoint(ckpt, "mp")
    for a, b in zip(jax.tree_util.tree_leaves(engine.state.params),
                    jax.tree_util.tree_leaves(engine2.state.params)):
        # multi-host arrays: only this process's shards are addressable
        for sa, sb in zip(a.addressable_shards, b.addressable_shards):
            np.testing.assert_allclose(np.asarray(sa.data),
                                       np.asarray(sb.data), rtol=1e-6)
    print(f"RANK{rank} CKPT-OK", flush=True)
""")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_two_process_train_barrier_checkpoint(tmp_path, stage):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    port = _free_port()
    env_base = {k: v for k, v in os.environ.items()
                if not k.startswith(("DSTPU_", "XLA_FLAGS"))}
    procs = []
    for r in range(2):
        env = dict(env_base,
                   DSTPU_COORDINATOR=f"127.0.0.1:{port}",
                   DSTPU_NUM_PROCESSES="2", DSTPU_PROCESS_ID=str(r),
                   T_STAGE=str(stage), T_CKPT=str(tmp_path / "ckpt"),
                   PYTHONPATH=REPO)
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:  # a hung rank must not leak past the test
            if p.poll() is None:
                p.kill()
                p.wait()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"
        assert f"RANK{r} CKPT-OK" in out, out[-2000:]
    # identical loss trajectory on both ranks (true data-parallel reduce)
    l0 = [ln for ln in outs[0].splitlines() if "LOSSES" in ln][0].split()[2:]
    l1 = [ln for ln in outs[1].splitlines() if "LOSSES" in ln][0].split()[2:]
    assert l0 == l1, (l0, l1)

    # RESIZE-RESUME: the 2-process partitioned checkpoint reloads in THIS
    # single process on the 8-virtual-device mesh (the elastic/universal
    # reshard story across real process counts)
    import jax
    import numpy as np

    import deepspeed_tpu
    from tests.unit.simple_model import random_batch, simple_mlp_spec

    engine, *_ = deepspeed_tpu.initialize(
        model=simple_mlp_spec(),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": stage}})
    engine.load_checkpoint(str(tmp_path / "ckpt"), "mp")
    assert engine.global_steps == 10  # the workers' training step count
    # the reloaded leaves must BYTE-match the workers' saved shards — a
    # silently-skipped or misassembled leaf would still train finitely
    from deepspeed_tpu.checkpoint.partitioned import _assemble

    full = _assemble(str(tmp_path / "ckpt" / "mp"), prefix=".params")
    import re as _re

    for key, want in full.items():
        cur = engine.state.params
        parts = _re.findall(r"\['([^']+)'\]", key)
        for p in parts:
            cur = cur[p]
        got = np.asarray(jax.device_get(cur))
        np.testing.assert_allclose(got, want.reshape(got.shape), rtol=1e-6,
                                   err_msg=key)
    loss = float(engine.train_batch(random_batch(batch_size=16, seed=3,
                                                 gas=1)))
    assert np.isfinite(loss)


def test_launcher_cli_end_to_end(tmp_path):
    """The `deepspeed`-CLI analogue actually launches the job: a 2-entry
    hostfile (both local) -> launcher assigns the coordinator env contract
    -> two REAL worker processes rendezvous, train data-parallel, and
    write per-rank proof files."""

    worker = tmp_path / "train.py"
    worker.write_text(textwrap.dedent("""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        from deepspeed_tpu.comm import comm
        comm.init_distributed()
        assert jax.process_count() == 2
        import numpy as np, jax.numpy as jnp
        import deepspeed_tpu
        from tests.unit.simple_model import random_batch, simple_mlp_spec
        engine, *_ = deepspeed_tpu.initialize(
            model=simple_mlp_spec(),
            config={"train_micro_batch_size_per_gpu": 8,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                    "mesh": {"data": 2}})
        loss = float(engine.train_batch(random_batch(batch_size=16, gas=1)))
        out = sys.argv[1]
        with open(f"{out}/rank{jax.process_index()}.ok", "w") as f:
            f.write(f"{loss:.6f}")
    """))
    hf = tmp_path / "hostfile"
    hf.write_text("localhost slots=1\n127.0.0.1 slots=1\n")
    # the launcher passes the environment through for all-local jobs:
    # strip the pytest harness's 8-virtual-device XLA_FLAGS and stale
    # contract vars so each worker sees 1 local device.  Run the CLI in a
    # subprocess session so a hung worker can't hang pytest.
    env = {k: v for k, v in os.environ.items()
           if not (k.startswith("DSTPU_") or k == "XLA_FLAGS")}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--hostfile", str(hf), "--master_port", str(_free_port()),
         str(worker), str(tmp_path)],
        env=env, cwd=REPO, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        out, _ = proc.communicate(timeout=300)
    except subprocess.TimeoutExpired:
        import signal

        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
        raise
    assert proc.returncode == 0, out[-3000:]
    losses = [(tmp_path / f"rank{r}.ok").read_text() for r in range(2)]
    assert losses[0] == losses[1], losses  # same reduced loss on both ranks
