"""CPU rehearsals of the harness at a tiny width: the cells' closed and
open loops and the generator's open loop end to end, the command's
refusals, the control, the faults that ``correct`` must catch, and the
serving cell's per-layer readers. The command refuses a CPU, so these
call the harness's functions directly and skip only its look for a
chip."""

import importlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

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
    from data alone; ``serve`` is ``comfs_serve`` with its own traffic
    file, at d=64 and a reduced query rate."""
    over = {"config": {"sketch": {"d": 64, "pool_capacity": 512}},
            "mix": {"ingest": {"max_edges_per_s": 60000}}}
    if loop == "serve":
        over["mix"] = {"queries": {"per_s": 120}}
        return harness.Cell("comfs_serve", overrides=over)
    if loop == "open":
        over["mix"] = json.loads((DATA / "open_serve.json").read_text())
    return harness.Cell("comfs_ingest", overrides=over)


@pytest.mark.parametrize("loop", ["closed", "open", "serve"])
def test_cell_rehearsal(loop):
    cell = tiny(loop)
    out = harness.run(cell, SEED, 1.5, False, time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["window"]["compiles"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end()}
    if loop == "serve":
        assert set(out["metrics"]) == {"query_p50_ms", "query_p95_ms",
                                       "setup_s"}
        assert out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


class _EchoServer:
    """Answers every query with 7 at once and ingests nothing."""

    def __init__(self):
        self.pending, self.batches = [], 0

    def ingest(self, batch):
        self.batches += 1

    def submit(self, kind, **args):
        r = SimpleNamespace(answer=None)
        self.pending.append(r)
        return r

    def flush(self):
        for r in self.pending:
            r.answer = 7
        self.pending.clear()


def test_open_loop_ends_on_a_last_ingest_batch(monkeypatch):
    """A schedule whose last event is an ingest batch, due after the last
    query, ends once that batch is in, with every query answered."""
    from bench import loadgen
    monkeypatch.setattr(loadgen, "edge_batch", lambda cols: cols)
    plan = SimpleNamespace(
        ingest_due=np.array([0.0, 0.05, 0.1]), batches=[{}] * 3, batch=4,
        warm_edges=0, max_per_flush=2,
        queries={"due": np.array([0.01, 0.02, 0.03]),
                 "reqs": [("label", {"lv": 1, "direction": "out"})] * 3})
    server = _EchoServer()
    res = loadgen.open_window(server, plan, loadgen.Spans(), lambda: None)
    assert server.batches == 3 and res["failed"] == 0
    assert list(res["answers"]) == [7, 7, 7]
    assert res["t1"] - res["t0"] < 1.0


def _serve_ctx(empty: bool) -> SimpleNamespace:
    """What a per-layer reader is handed, made by hand: four queries in
    two flushes (30 and 50 ms) taken up 10, 0, 20 and 60 ms after they
    were due, two ingest batches 5 and 15 ms late, query programs of 8 ms
    in the two flushes, one ingest launch of 70 ms and one plane rebuild
    of 6 ms, and a device busy for 0.6 s of a 1 s window; ``empty``: a
    window with no flush, query or batch and a trace with no program."""
    if empty:
        return SimpleNamespace(
            spans={}, window={"queue_s": np.zeros(0),
                              "ingest_late_s": np.zeros(0)},
            trace={"modules": {}, "host_n": {}, "window_s": 0.0,
                   "busy_s": 0.0})
    return SimpleNamespace(
        spans={"flush": [0.03, 0.05], "ingest": [0.003]},
        window={"queue_s": np.array([0.01, 0.0, 0.02, 0.06]),
                "ingest_late_s": np.array([0.005, 0.015])},
        trace={"modules": {"_edge_pallas": [0.004, 2],
                           "_vertex_pallas": [0.002, 2],
                           "_label_pallas": [0.002, 1],
                           "_ingest_stacked_lsketch": [0.07, 1],
                           "_build_planes": [0.006, 1]},
               "host_n": {"flush": 2, "ingest": 1}, "window_s": 1.0,
               "busy_s": 0.6})


@pytest.mark.parametrize("name,want", [
    ("flush_ms", 40.0), ("query_queue_ms", 15.0), ("ingest_late_ms", 10.0),
    ("query_programs_ms", 4.0), ("ingest_program_ms.serve", 70.0),
    ("planes_ms.serve", 6.0), ("device_idle.serve", 40.0)])
def test_serve_readers(name, want):
    """Each of the serving cell's per-layer readers (those split from an
    ingest cell's by their base file) gives its number on a known window
    and nothing where its input is empty."""
    read = harness.load_metric(name)
    assert read(_serve_ctx(False)) == pytest.approx(want)
    assert read(_serve_ctx(True)) is None


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
@pytest.mark.parametrize("loop", ["closed", "open", "serve"])
def test_faults_make_correct_false(monkeypatch, loop, fault):
    fault(monkeypatch)
    out = harness.run(tiny(loop), SEED, 1.0, False, time.perf_counter())
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def _control_fails(loop: str) -> None:
    cell = tiny(loop)
    m = harness.measure(cell, SEED + 1, 1.0, False, time.perf_counter())
    r = readings(cell, m)
    assert r["program"] == {"answers_wrong": 0, "state_wrong": 0,
                            "correct": True}
    assert r["control"]["correct"] is False
    assert r["control"]["answers_wrong"] > 0
    assert r["control"]["state_wrong"] > 0


def test_control_fails_where_the_program_passes():
    """The control (the reference at 9-bit fingerprints in the program's
    place) goes through ``harness.compare`` and comes out not correct, on
    both of its numbers, where the program is correct."""
    _control_fails("closed")


def test_control_fails_on_the_served_queries():
    """The same on the serving cell, whose answers are the window's own
    queries at their own stream positions."""
    _control_fails("serve")


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
        assert harness.metric_file(m["name"]).is_file()
