"""The reduction from a profiler trace to per-layer numbers."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import rooflines, trace_reduce

DATA = Path(__file__).parent / "data"

RAW = {
    "devices": {"/device:TPU:0": {
        "modules": [("jit__ingest_stacked_lsketch(1)", 1.0, 0.4),
                    ("jit__ingest_stacked_lsketch(1)", 1.2, 0.2),
                    ("jit__build_planes(7)", 2.0, 0.2),
                    ("jit__reduce_all(3)", 2.4, 0.3),  # crosses the end
                    ("jit__edge_pallas", 3.0, 0.1)],  # after the window
        "ops": [("cond.12", 1.0, 0.4),  # a container: holds the next two
                ("fusion.1", 1.0, 0.3),
                ("sketch_insert_walk.1", 1.2, 0.2),
                ("fusion.3", 2.0, 0.2),
                ("copy.4", 2.4, 0.3)]}},
    "host": [("window", 0.5, 2.0), ("ingest", 0.5, 0.6),
             ("flush", 1.7, 0.2), ("wait", 2.2, 0.3)],
}


def test_busy_is_the_union_of_device_intervals():
    red = trace_reduce.reduce(RAW)
    assert red["window_s"] == pytest.approx(2.0)
    # [1.0, 1.4] + [2.0, 2.2] + [2.4, 2.5] (clipped to the window)
    assert red["busy_s"] == pytest.approx(0.7)


def test_module_and_kernel_sums():
    red = trace_reduce.reduce(RAW)
    # the second launch overlaps the first: its queued time is not its own
    assert red["modules"]["_ingest_stacked_lsketch"] == pytest.approx(
        [0.4, 2])
    assert red["modules"]["_reduce_all"] == pytest.approx([0.1, 1])
    assert "cond.12" not in red["ops"]
    assert red["modules"]["_build_planes"] == pytest.approx([0.2, 1])
    assert "_edge_pallas" not in red["modules"]
    assert red["kernels"]["sketch_insert_walk"] == pytest.approx([0.2, 1])
    assert red["ops"]["copy.4"] == pytest.approx([0.1, 1])
    # one program at a time: the programs' times never pass the busy time
    assert sum(t for t, _ in red["modules"].values()) <= red["busy_s"] + 1e-12


def test_program_time_per_launch_needs_every_launch():
    """``ingest_program_ms`` averages over the traced window's launches only
    where they match its ingest calls (one each, give or take one at each
    end); where launches were dropped inside it, it is left out."""
    from types import SimpleNamespace
    from bench.harness import load_metric
    read = load_metric("ingest_program_ms")
    red = trace_reduce.reduce(RAW)
    assert red["host_n"]["ingest"] == 1
    assert read(SimpleNamespace(trace=red)) == pytest.approx(200.0)
    red["host_n"]["ingest"] = 9
    assert read(SimpleNamespace(trace=red)) is None


def test_a_cut_device_trace_shrinks_the_window():
    """Ten ingest calls, one program each, of which the device recorded
    the first six: busy time and per-call numbers are taken over the part
    recorded, and the rest is not read as idle."""
    mods = [("jit__ingest_stacked_lsketch(1)", 1.0 + i, 0.5)
            for i in range(6)]
    raw = {"devices": {"/device:TPU:0": {
               "modules": mods, "ops": [("fusion.1", t, d)
                                        for _, t, d in mods]}},
           "host": [("window", 0.9, 10.0)] + [
               ("ingest", 0.95 + i, 0.1) for i in range(10)]}
    red = trace_reduce.reduce(raw)
    assert red["cut"] and red["full_window_s"] == pytest.approx(10.0)
    assert red["window_s"] == pytest.approx(6.5 - 0.9)
    assert red["busy_s"] == pytest.approx(3.0)
    assert red["host_n"]["ingest"] == 6
    assert red["modules"]["_ingest_stacked_lsketch"] == pytest.approx(
        [3.0, 6])


def test_a_whole_trace_is_not_cut():
    red = trace_reduce.reduce(RAW)
    assert not red["cut"] and red["window_s"] == red["full_window_s"]
    red = trace_reduce.reduce(recorded())
    assert not red["cut"]


def test_idle_gaps_go_to_the_host_span_open_at_their_middle():
    red = trace_reduce.reduce(RAW)
    idle = dict(red["idle_gaps"])
    # gaps [0.5, 1.0] (ingest), [1.4, 2.0] (flush at 1.7), [2.2, 2.4]
    assert idle == pytest.approx({"ingest": 0.5, "flush": 0.6,
                                  "wait": 0.2})
    assert red["window_s"] - red["busy_s"] == pytest.approx(
        sum(idle.values()))
    bd = trace_reduce.breakdown(red)
    assert bd["device_ops"][0][0] in ("fusion.1", "fusion.2")
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_names():
    assert trace_reduce.module_name("jit__vertex_pallas(12)") == \
        "_vertex_pallas"
    assert trace_reduce.op_name(
        "%sketch_insert_walk.1 = (s32[1]) custom-call(s32[1] %x)") == \
        "sketch_insert_walk.1"


def test_union_and_gaps_helpers():
    iv = np.array([[0.0, 1.0], [0.5, 2.0], [3.0, 4.0]])
    assert trace_reduce.union_length(iv) == pytest.approx(3.0)
    g = trace_reduce.gaps(iv, -1.0, 5.0)
    assert g.tolist() == [[-1.0, 0.0], [2.0, 3.0], [4.0, 5.0]]


def test_peaks_table_has_its_source_and_refuses_unknown_devices():
    table = json.loads((Path(rooflines.__file__).parent
                        / "peaks.json").read_text())
    assert table["source"]
    assert rooflines.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        rooflines.peaks("cpu")


def recorded() -> dict:
    """A trace recorded on one TPU v5 lite chip (three ingest batches of
    ``comfs_ingest``), as ``trace_reduce.load`` returned it."""
    d = json.loads((DATA / "tiny_trace.json").read_text())
    unpack = lambda evs: [(n, t * 1e-9, dur * 1e-9) for n, t, dur in evs]
    return {"devices": {k: {"modules": unpack(v["modules"]),
                            "ops": unpack(v["ops"])}
                        for k, v in d["devices"].items()},
            "host": unpack(d["host"]), "dropped": d["dropped"]}


def test_recorded_chip_trace():
    raw = recorded()
    red = trace_reduce.reduce(raw)
    (dev,) = raw["devices"].values()
    (w0, w1), = [(t, t + d) for n, t, d in raw["host"] if n == "window"]
    # busy: the union of the program intervals, by a plain sweep
    ends, busy = [], 0.0
    cur = None
    for _, t, d in sorted(dev["modules"], key=lambda e: e[1]):
        a, b = max(t, w0), min(t + d, w1)
        if b <= a:
            continue
        if cur is None or a > cur[1]:
            busy += 0 if cur is None else cur[1] - cur[0]
            cur = [a, b]
        else:
            cur[1] = max(cur[1], b)
    busy += cur[1] - cur[0]
    assert red["busy_s"] == pytest.approx(busy)
    assert red["window_s"] == pytest.approx(w1 - w0)
    # three ingest launches, each with one key-walk kernel
    assert red["modules"]["_ingest_stacked_lsketch"][1] == 3
    assert red["kernels"]["sketch_insert_walk"][1] == 3
    total = sum(d for n, t, d in dev["modules"]
                if trace_reduce.module_name(n) == "_build_planes")
    assert red["modules"]["_build_planes"][0] == pytest.approx(total)
    idle = dict(red["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(red["window_s"] - busy)
    assert set(idle) <= {"ingest", "wait", "other"}


def test_load_reads_benchmark_spans(tmp_path):
    """``load`` on a trace recorded here: the benchmark's annotations come
    back by name (this backend has no device plane)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:window"):
        with jax.profiler.TraceAnnotation("bench:ingest"):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    raw = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in raw["host"]]
    assert names.count("window") == 1 and names.count("ingest") == 1
