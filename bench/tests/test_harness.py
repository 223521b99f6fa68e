"""CPU rehearsals of the harness at a tiny width: the cell's closed loop
and the generator's open loop end to end, the command's refusals, the
control, and the faults that ``correct`` must catch. The command refuses
a CPU, so these call the harness's functions directly and skip only its
look for a chip."""

import importlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.control import readings

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
SEED = 2 ** 31 + 11


def tiny(loop: str) -> harness.Cell:
    """``comfs_ingest`` at d=64; ``open`` swaps in an open-loop mix with
    queries (``data/open_serve.json``), which the one generator drives
    from data alone."""
    over = {"config": {"sketch": {"d": 64, "pool_capacity": 512}},
            "mix": {"ingest": {"max_edges_per_s": 60000}}}
    if loop == "open":
        over["mix"] = json.loads((DATA / "open_serve.json").read_text())
    return harness.Cell("comfs_ingest", overrides=over)


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_cell_rehearsal(loop):
    cell = tiny(loop)
    out = harness.run(cell, SEED, 1.5, False, time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["window"]["compiles"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def _patch_unchanged(monkeypatch):
    """Ingest returns the state it was given."""
    ingest = importlib.import_module("repro.sketch.ingest")
    monkeypatch.setattr(ingest, "_dispatch_stacked",
                        lambda spec, state, *a, **k: state)


def _patch_half_batch(monkeypatch):
    """Half of every ingest batch is left out."""
    ingest = importlib.import_module("repro.sketch.ingest")
    part = ingest._partition_stack

    def half(spec, batch):
        n = len(np.asarray(batch.src)) // 2
        return part(spec, type(batch)(**{f: np.asarray(getattr(batch, f))[:n]
                                         for f in ingest._FIELDS}))
    monkeypatch.setattr(ingest, "_partition_stack", half)


def _patch_answer(monkeypatch):
    """One answer of every query dispatch is altered where it is made."""
    import repro.sketch as skt
    query = skt.query

    def altered(*a, **k):
        out = np.array(query(*a, **k))
        out[0] += 1
        return out
    monkeypatch.setattr(skt, "query", altered)


@pytest.mark.parametrize("fault", [_patch_unchanged, _patch_half_batch,
                                   _patch_answer])
@pytest.mark.parametrize("loop", ["closed", "open"])
def test_faults_make_correct_false(monkeypatch, loop, fault):
    fault(monkeypatch)
    out = harness.run(tiny(loop), SEED, 1.0, False, time.perf_counter())
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_control_fails_where_the_program_passes():
    """The control (the reference at 9-bit fingerprints in the program's
    place) goes through ``harness.compare`` and comes out not correct, on
    both of its numbers, where the program is correct."""
    cell = tiny("closed")
    m = harness.measure(cell, SEED + 1, 1.0, False, time.perf_counter())
    r = readings(cell, m)
    assert r["program"] == {"answers_wrong": 0, "state_wrong": 0,
                            "correct": True}
    assert r["control"]["correct"] is False
    assert r["control"]["answers_wrong"] > 0
    assert r["control"]["state_wrong"] > 0


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "comfs_ingest",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_cpu():
    p = _command(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_benchmark_file_names_existing_files():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bm["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bm["workloads"]:
        wl = json.loads((ROOT / "bench" / "workloads"
                         / f"{w['name']}.json").read_text())
        assert (wl["config"], wl["traffic"]) == (w["config"], w["traffic"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bm["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
