"""The command line: no chip is a refusal, and a cell, a configuration, a
traffic mix and a per-layer metric added as new files in another directory
are found and run with no edit to a file that is there."""

import json
import os
import shutil
import subprocess
import sys

from benchmark import manifest as manifest_mod

ROOT = os.path.dirname(manifest_mod.HERE)
RUN = os.path.join(manifest_mod.HERE, "run.py")
REHEARSE = os.path.join(manifest_mod.HERE, "rehearse.py")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(*args, env=ENV, cwd=ROOT, script=RUN):
    return subprocess.run([sys.executable, script, *args], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=600)


def test_no_chip_is_a_nonzero_exit_naming_the_platform():
    cell = manifest_mod.Manifest().data["workloads"][0]["name"]
    p = _run("--workload", cell, "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode == 2
    assert "'cpu'" in p.stderr and "no chip" in p.stderr
    assert not p.stdout.strip().splitlines()[-1].startswith("{")


def test_unknown_workload_is_refused():
    p = _run("--workload", "no-such-cell", "--seed", "1")
    assert p.returncode != 0


def test_the_command_takes_the_four_options_and_no_other():
    cell = manifest_mod.Manifest().data["workloads"][0]["name"]
    for extra in (["--rehearse"], ["--manifest", "BENCHMARK.json"]):
        p = _run("--workload", cell, "--seed", "1", "--seconds", "1",
                 "--trace", "0", *extra)
        assert p.returncode == 2 and "unrecognized arguments" in p.stderr


def test_directory_with_only_the_benchmark_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(manifest_mod.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    cell = manifest_mod.Manifest().data["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "rehearse.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1", "--trace",
         "0"], env=ENV, cwd=tmp_path, capture_output=True,
        text=True, timeout=600)
    assert p.returncode != 0
    assert "{" not in (p.stdout.strip().splitlines() or [""])[-1]


def test_a_cell_added_as_new_files_runs_with_no_edit(tmp_path):
    """New configuration, traffic mix, per-layer metric and reader, one new
    entry each in a copy of the manifest; every existing file untouched (they
    are found beside the harness)."""
    man = manifest_mod.Manifest()
    data = json.loads(json.dumps(man.data))
    bench = tmp_path / "benchmark"
    for sub in ("configs", "traffic", "layer_metrics", "readers"):
        (bench / sub).mkdir(parents=True)
    base_cfg = man.config("opt-6.7b-train")
    base_cfg.pop("_file")
    cfg = dict(base_cfg, name="opt-new-train")
    cfg["tiny"] = dict(cfg["tiny"], num_hidden_layers=1)
    (bench / "configs" / "opt-new-train.json").write_text(json.dumps(cfg))
    tr = man.traffic("sft-2k")
    tr["tiny"] = dict(tr["tiny"], sequence_length=16)
    (bench / "traffic" / "sft-new.json").write_text(json.dumps(tr))
    (bench / "readers" / "blocks_counted.py").write_text(
        "def read(ctx):\n    return float(len(ctx['result']['blocks']))\n")
    metric = {"name": "blocks_in_window", "layer": "Train entry",
              "unit": "count", "better": "higher",
              "source": "program_counter", "moves": "train_tok_per_s_chip",
              "workloads": ["opt-new-cell"]}
    (bench / "layer_metrics" / "blocks_in_window.json").write_text(
        json.dumps(dict({k: v for k, v in metric.items() if k != "workloads"},
                        reader="blocks_counted", args={})))
    data["configs"].append({"name": "opt-new-train", "source": cfg["source"],
                            "file": "benchmark/configs/opt-new-train.json",
                            "reduced": cfg["reduced"], "why": "test"})
    data["workloads"].append({"name": "opt-new-cell",
                              "config": "opt-new-train", "traffic": "sft-new",
                              "chips": 1, "why": "test"})
    for m in data["end_to_end"]:
        if m["name"] == "train_tok_per_s_chip":
            m["workloads"] = m["workloads"] + ["opt-new-cell"]
    data["per_layer"].append(metric)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    new = manifest_mod.Manifest(str(tmp_path / "BENCHMARK.json"))
    assert new.config("opt-new-train")["name"] == "opt-new-train"
    assert new.traffic("sft-new")["kind"] == "train_steps"
    assert [m["name"] for m in new.per_layer("opt-new-cell")
            if m["name"] == "blocks_in_window"]
    assert new.module("readers", "blocks_counted").read(
        {"result": {"blocks": [1, 2, 3]}}) == 3.0

    p = _run("--manifest", str(tmp_path / "BENCHMARK.json"), "--workload",
             "opt-new-cell", "--seed", str(2 ** 31 + 5), "--seconds", "0.3",
             "--trace", "0", script=REHEARSE)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "metrics" not in line          # a rehearsal prints no device metric
