"""The readings of the program's own spans, counters and named scopes
(``bench/program_trace.py``, ``bench/hlo_scopes.py``)."""

import numpy as np
import pytest

from bench import program_trace, trace_reduce

ING = "jit__ingest_stacked_lsketch(1)"

# a walk launch A, a scan launch B, a plane build, and a launch C that the
# window's end cuts; the build reuses an instruction name of the ingest
# program, which must not take the ingest program's scope
RAW = {
    "devices": {"/device:TPU:0": {
        "modules": [(ING, 1.0, 1.0), (ING, 3.0, 2.0),
                    ("jit__build_planes(2)", 5.2, 0.3), (ING, 9.5, 1.0)],
        "ops": [("fusion.1", 1.0, 0.2), ("copy_select_fusion", 1.2, 0.3),
                ("sketch_insert_walk.1", 1.5, 0.3), ("copy.9", 1.8, 0.1),
                ("fusion.1", 3.0, 0.2), ("copy_select_fusion", 3.2, 0.3),
                ("while.3", 3.5, 1.3),  # a container: holds fusion.7
                ("fusion.7", 3.5, 1.1), ("copy.9", 4.8, 0.2),
                ("fusion.1", 5.2, 0.3),
                ("fusion.1", 9.5, 0.3)]}},
    "host": [("window", 0.0, 10.0), ("ingest", 0.4, 2.7),
             ("ingest", 3.1, 2.2), ("wait", 6.0, 3.8)],
}
PROGRAM = {
    "spans": [("server.ingest", 0.45, 2.6), ("ingest.dispatch", 0.48, 0.07),
              ("ingest.partition", 0.6, 0.1), ("planes.prewarm", 0.7, 2.3),
              ("planes.wait", 0.75, 2.15),
              ("server.ingest", 3.15, 2.1), ("planes.prewarm", 4.0, 1.2),
              ("planes.build", 4.0, 0.1)],
    "op_scopes": {ING: {"fusion.1": "ingest.plan",
                        "copy_select_fusion": "ingest.reclaim",
                        "sketch_insert_walk.1": "ingest.walk",
                        "fusion.7": "ingest.scan"}},
}


def _split(raw=RAW, prog=PROGRAM):
    red = trace_reduce.reduce(raw)
    return red, program_trace.split(raw, red, prog)


def test_scopes_and_launches():
    red, sp = _split()
    assert sp["scopes"][program_trace.INGEST] == pytest.approx(
        {"ingest.plan": 0.7, "ingest.reclaim": 0.6, "ingest.walk": 0.3,
         "ingest.scan": 1.1, "unscoped": 0.3})
    assert sp["scopes"]["_build_planes"] == pytest.approx({"unscoped": 0.3})
    walk, scan = sp["ingest_launches"]  # C lies partly outside the window
    assert (walk["start"], walk["scan"], scan["scan"]) == (1.0, False, True)
    assert walk["scopes"] == pytest.approx(
        {"ingest.plan": 0.2, "ingest.reclaim": 0.3, "ingest.walk": 0.3,
         "unscoped": 0.1})
    assert scan["scopes"] == pytest.approx(
        {"ingest.plan": 0.2, "ingest.reclaim": 0.3, "ingest.scan": 1.1,
         "unscoped": 0.2})
    assert sp["unscoped_ops"] == [("copy.9", pytest.approx(0.3))]
    r = program_trace.readings(None, sp)
    assert r["walk_launch_ms"] == pytest.approx(1000.0)
    assert r["scan_launch_ms"] == pytest.approx(2000.0)
    assert r["ring_reclaim_ms"] == pytest.approx(300.0)
    assert r["ingest_unscoped_ms"] == pytest.approx(150.0)
    # the launches' program time is the time trace_reduce counts for them
    full = [l["program_s"] for l in sp["ingest_launches"]]
    total, n = red["modules"]["_ingest_stacked_lsketch"]
    assert sum(full) == pytest.approx(total - 0.5) and n == 3


def test_the_last_launch_of_a_cut_trace_is_not_whole():
    """Ten ingest calls, of which the device recorded three launches, the
    third cut short where it stopped recording."""
    mods = [(ING, 1.0, 0.5), (ING, 2.0, 0.5), (ING, 3.0, 0.2)]
    raw = {"devices": {"/device:TPU:0": {
               "modules": mods,
               "ops": [("fusion.1", t, d) for _, t, d in mods]}},
           "host": [("window", 0.9, 10.0)] + [
               ("ingest", 0.95 + i, 0.1) for i in range(10)]}
    red, sp = _split(raw)
    assert red["cut"]
    assert [r["start"] for r in sp["ingest_launches"]] == [1.0, 2.0]
    assert sp["scopes"][program_trace.INGEST] == pytest.approx(
        {"ingest.plan": 1.2})


def test_idle_gaps_go_to_the_innermost_open_span():
    """Gaps [0, 1], [2, 3], [5, 5.2] and [5.5, 9.5]: the first two inside
    open program spans, the third after a closed ``planes.build`` inside
    its open parent, the last under no program span."""
    red, sp = _split()
    assert dict(sp["idle_spans"]) == pytest.approx(
        {"ingest/ingest.dispatch": 1.0, "ingest/planes.wait": 1.0,
         "ingest/planes.prewarm": 0.2, "wait": 4.0})
    by_bench = {}
    for name, s in sp["idle_spans"]:
        b = name.split("/")[0]
        by_bench[b] = by_bench.get(b, 0.0) + s
    assert by_bench == pytest.approx(dict(red["idle_gaps"]))
    assert sum(by_bench.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_per_call_sums_and_self_times():
    snap = {"spans": [
        ("server.ingest", 0.0, 10.0, -1), ("ingest.dispatch", 0.0, 1.0, 0),
        ("ingest.partition", 1.0, 3.0, 0), ("planes.prewarm", 3.0, 9.0, 0),
        ("planes.wait", 3.5, 8.5, 3),
        ("server.ingest", 10.0, 14.0, -1), ("ingest.partition", 10.0, 11.0, 5),
        ("planes.prewarm", 11.0, 13.0, 5), ("planes.wait", 11.0, 12.0, 7),
        ("ingest.dispatch", 14.0, 15.0, -1)]}
    per_call = program_trace.per_call
    assert per_call(snap, "server.ingest", "planes.wait") == [5.0, 1.0]
    assert per_call(snap, "server.ingest", "planes.prewarm",
                    self_time=True) == [1.0, 1.0]
    assert per_call(snap, "server.ingest", "ingest.dispatch") == [1.0, 0.0]
    r = program_trace.readings(snap, None)
    assert r["partition_ms"] == pytest.approx(1500.0)
    assert r["prewarm_wait_ms"] == pytest.approx(3000.0)


def test_scan_batch_share_reads_the_partition_counters():
    snap = {"spans": [], "counts": {"ingest.batches": 8,
                                    "ingest.boundary_batches": 5,
                                    "ingest.scan_batches": 3}}
    assert program_trace.readings(snap, None)["scan_batch_share"] == \
        pytest.approx(37.5)
    snap["counts"] = {"ingest.batches": 4}
    assert program_trace.readings(snap, None)["scan_batch_share"] == 0.0


def test_readings_are_none_without_spans_or_scopes():
    """A program without the spans and scopes (or a trace without its HLO)
    reads as nothing, not as a wrong number."""
    none = dict.fromkeys(program_trace.READINGS)
    assert program_trace.readings(None, None) == none
    _, bare = _split(prog={"spans": [], "op_scopes": {}})
    assert all(set(r["scopes"]) == {"unscoped"}
               for r in bare["ingest_launches"])
    assert program_trace.readings({"spans": []}, bare) == none
    snap = {"spans": [("server.ingest", 0.0, 1.0, -1)], "counts": {}}
    assert program_trace.readings(snap, bare) == none


def test_a_recorded_trace_gives_spans_and_scopes(tmp_path):
    """On a trace recorded here: the program's spans come back by name,
    and the scopes of a jitted program's instructions from the HLO the
    trace holds, a loop body's operations taking the loop's scope."""
    import jax
    import jax.numpy as jnp
    from repro.sketch import trace

    @jax.jit
    def step(x):
        with jax.named_scope("ingest.reclaim"):
            y = jnp.where(x > 0, x, 0) * 2
        with jax.named_scope("ingest.scan"):
            y = jax.lax.fori_loop(0, 3, lambda i, c: c * 3 + 1, y)
        return y - 1

    x = jnp.ones((16, 16))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    trace.reset()
    trace.enable()
    try:
        with trace.span("server.ingest"):
            with trace.span("ingest.partition"):
                step(x).block_until_ready()
    finally:
        trace.disable()
        jax.profiler.stop_trace()
    prog = program_trace.load(trace_reduce.find_xplane(str(tmp_path)))
    assert [n for n, _, _ in prog["spans"]] == ["server.ingest",
                                                "ingest.partition"]
    (scopes,) = [v for k, v in prog["op_scopes"].items()
                 if k.startswith("jit_step(")]
    got = set(scopes.values())
    assert got == {"ingest.reclaim", "ingest.scan"}
    assert any(n.startswith("while") and s == "ingest.scan"
               for n, s in scopes.items())
    snap = trace.snapshot()
    assert snap["spans"][1][3] == 0
    assert np.isfinite(snap["spans"][0][2])
