"""The set-up reader (``readers/setup_part.py``) on a recorded ledger — what
``compile_sentinel.setup_ledger`` returned over the set-up of one traced run
of a tiny serving engine — and two rehearsals whose lines read as before."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest as manifest_mod
from deepspeed_tpu.telemetry import compile_sentinel

ROOT = os.path.dirname(manifest_mod.HERE)
REHEARSE = os.path.join(manifest_mod.HERE, "rehearse.py")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")

SETUP_S = 12.5
RECORDED = {
    "origin": 100.0,
    "parts": {"import": 3.25, "engine_init": 1.5, "trace": 2.0,
              "lower": 0.75, "compile": 0.5, "cache_load": 1.25,
              "unnamed": 3.25},
    "cache_misses": 1,
    "traces_after": 2,
    "programs": {
        ("import", "package_import"): (1, 3.25),
        ("engine_init", "serve_engine_init"): (1, 2.0),
        ("trace", "_decode_and_sample"): (1, 1.25),
        ("lower", "jit(_decode_and_sample)"): (1, 0.5),
        ("cache_load", "jit(_decode_and_sample)"): (1, 1.0),
        ("trace", "<lambda>"): (2, 0.5),
        ("lower", "jit(<lambda>)"): (2, 0.125),
        ("compile", "jit(<lambda>)"): (1, 0.5),
        ("cache_load", "jit(<lambda>)"): (1, 0.25),
        ("trace", "convert_element_type"): (3, 0.25),
        ("lower", "jit(convert_element_type)"): (1, 0.125),
    },
    "events": {"import": 1, "engine_init": 1, "trace": 420, "lower": 4,
               "compile": 1, "cache_load": 2},
    "kept": 16,
}


@pytest.fixture
def reader():
    return manifest_mod.Manifest().module("readers", "setup_part")


def _ctx(setup_s=SETUP_S):
    e2e = {} if setup_s is None else {"setup_s": setup_s}
    return {"result": {"end_to_end": e2e}}


def _program_returns(monkeypatch, ledger):
    asked = []

    def setup_ledger(a=None, b=None):
        asked.append((a, b))
        return dict(ledger)

    monkeypatch.setattr(compile_sentinel, "setup_ledger", setup_ledger)
    return asked


@pytest.mark.parametrize("part", [*RECORDED["parts"]])
def test_each_part_is_the_ledgers(reader, monkeypatch, part):
    asked = _program_returns(monkeypatch, RECORDED)
    assert reader.read(_ctx(), part=part) == RECORDED["parts"][part]
    # the stretch is the run's own set-up, from the ledger's origin
    assert asked[-1] == (100.0, 100.0 + SETUP_S)


def test_the_counter_over_the_same_stretch(reader, monkeypatch):
    _program_returns(monkeypatch, RECORDED)
    assert reader.read(_ctx(), part="cache_misses", what="count") == 1
    with pytest.raises(ValueError):
        reader.read(_ctx(), part="trace", what="count")
    with pytest.raises(ValueError):
        reader.read(_ctx(), part="trace", what="median")


def test_every_metric_file_reads_through_one_ask(monkeypatch, capsys):
    """The eight metrics as the harness reads them: a module a metric, one
    context a run — the program is asked once and one detail line printed."""
    asked = _program_returns(monkeypatch, RECORDED)
    man = manifest_mod.Manifest()
    names = [m["name"] for m in man.data["per_layer"]
             if m["layer"] == "Set-up"]
    assert len(names) == 8
    ctx, got = _ctx(), {}
    for name in names:
        spec = man.layer_metric(name)
        got[name] = man.module("readers", spec["reader"]).read(
            ctx, **spec["args"])
    seconds = [v for k, v in got.items() if k.endswith("_s")]
    assert len(seconds) == 7 and sum(seconds) == pytest.approx(SETUP_S)
    assert got["setup_unnamed_s"] == 3.25 and got["setup_cache_misses"] == 1
    assert len(asked) == 2  # the origin, then the stretch
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("setup:")]
    assert len(lines) == 1
    assert "_decode_and_sample 1.250 + 0.500 + 1.000 hit" in lines[0]
    assert "<lambda> 0.500 + 0.125 + 0.750 miss" in lines[0]
    assert "2 traces ended after the cut" in lines[0]
    assert lines[0].index("_decode_and_sample") < lines[0].index("<lambda>")


def test_no_reading_without_setup_s_or_without_a_ledger(reader, monkeypatch):
    _program_returns(monkeypatch, RECORDED)
    assert reader.read(_ctx(setup_s=None), part="trace") is None
    # the ledger let go of part of the stretch: no reading, never a short one
    _program_returns(monkeypatch, dict(RECORDED, parts=None,
                                       cache_misses=None))
    assert reader.read(_ctx(), part="trace") is None
    # a program from before the ledger
    monkeypatch.delattr(compile_sentinel, "setup_ledger")
    assert reader.read(_ctx(), part="trace") is None
    assert reader.read(_ctx(), part="cache_misses", what="count") is None


def test_parts_that_exceed_setup_s_raise(reader, monkeypatch):
    _program_returns(monkeypatch, RECORDED)
    with pytest.raises(ValueError, match="no partition"):
        reader.read(_ctx(setup_s=9.0), part="trace")
    low = dict(RECORDED, parts=dict(RECORDED["parts"], lower=-0.25))
    _program_returns(monkeypatch, low)
    with pytest.raises(ValueError, match="negative"):
        reader.read(_ctx(), part="trace")


def test_the_programs_own_ledger_partitions_a_stretch(reader):
    """Not recorded: this process's ledger, asked the way a run asks."""
    import time

    import deepspeed_tpu

    setup_s = time.perf_counter() - deepspeed_tpu._T_IMPORT
    ctx = _ctx(setup_s)
    vals = {p: reader.read(ctx, part=p) for p in (*reader.PARTS, "unnamed")}
    assert vals["import"] > 0.0 and min(vals.values()) >= 0.0
    assert sum(vals.values()) == pytest.approx(setup_s)


@pytest.mark.parametrize("cell", ["opt6.7b-sft-1chip",
                                  "mistral7b-chat-steady"])
def test_a_rehearsal_prints_its_line_as_before(cell):
    p = subprocess.run(
        [sys.executable, REHEARSE, "--workload", cell, "--seed", "1",
         "--seconds", "2", "--trace", "0"], env=ENV, cwd=ROOT,
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "rehearsal",
                         "counts", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True and line["attempted"] > 0
    assert line["counts"]["compiles_in_window"] == 0
    assert "setup:" not in p.stdout  # a rehearsal reads no per-layer metric
