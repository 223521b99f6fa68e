"""Multi-device behaviors via subprocess (8 fake CPU devices): the dry-run
lower+compile machinery on a small mesh, sharded train-step numerics vs
single-device, checkpoint resharding across different mesh shapes, and the
mesh-resident sketch layer (DESIGN.md §9) — collective query parity with
the host paths, ingest residency, and the named_shardings divisibility
branches. The exhaustive collective sweep (kinds x shards x window
positions incl. wraparound + pool overflow, plus compile-count pins) is
marked ``slow`` and rides the conformance CI job."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(code: str, timeout: int = 480) -> str:
    env = {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "HOME": "/tmp"}
    # keep the backend pin (when the host has one): without it jax probes
    # every plugin backend in the child, which can dwarf the actual test
    # on boxes with accelerator toolchains installed
    if "JAX_PLATFORMS" in os.environ:
        env["JAX_PLATFORMS"] = os.environ["JAX_PLATFORMS"]
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_dryrun_machinery_small_mesh():
    """lower+compile a reduced arch through the real dry-run path on a
    (2,2)x2-pod mesh of fake devices; roofline terms must be positive."""
    stdout = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import repro.launch.dryrun as dr
        import repro.launch.mesh as mesh_mod

        def tiny_mesh(*, multi_pod=False):
            shape = (2, 2, 2) if multi_pod else (2, 2)
            axes = ("pod", "data", "model") if multi_pod else ("data", "model")
            return mesh_mod.make_mesh(shape, axes)

        mesh_mod.make_production_mesh = tiny_mesh
        dr.make_production_mesh = tiny_mesh

        import repro.configs as configs
        orig = configs.get
        configs.get = lambda name, reduced=False: orig(name, reduced=True)

        import repro.configs.shapes as sh
        import dataclasses
        sh.SHAPES_BY_NAME["train_4k"] = dataclasses.replace(
            sh.SHAPES_BY_NAME["train_4k"], seq_len=64, global_batch=8)
        sh.SHAPES_BY_NAME["decode_32k"] = dataclasses.replace(
            sh.SHAPES_BY_NAME["decode_32k"], seq_len=64, global_batch=8)

        for arch, shape in [("smollm-135m", "train_4k"),
                            ("kimi-k2-1t-a32b", "train_4k"),
                            ("smollm-135m", "decode_32k")]:
            for mp in (False, True):
                rec = dr.lower_cell(arch, shape, mp)
                rl = rec["roofline"]
                assert rl["compute_s"] > 0, (arch, shape, mp)
                print("OK", arch, shape, "multipod" if mp else "pod",
                      rl["dominant"])
    """)
    assert stdout.count("OK") == 6


def test_checkpoint_reshards_across_meshes():
    """Train state saved under mesh (4,2) restores under (2,4) and matches."""
    stdout = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import tempfile
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.checkpoint import CheckpointManager

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                "b": jnp.ones((4,))}
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d)

        mesh_a = jax.make_mesh((4, 2), ("data", "model"))
        sh_a = {"w": NamedSharding(mesh_a, P("data", "model")),
                "b": NamedSharding(mesh_a, P("data"))}
        placed = jax.device_put(tree, sh_a)
        mgr.save(1, placed, extra={"step": 1})

        mesh_b = jax.make_mesh((2, 4), ("data", "model"))
        sh_b = {"w": NamedSharding(mesh_b, P("data", "model")),
                "b": NamedSharding(mesh_b, P(None))}
        restored, _ = mgr.restore(tree, shardings=sh_b)
        for k in tree:
            assert np.array_equal(np.asarray(tree[k]),
                                  np.asarray(restored[k])), k
        assert restored["w"].sharding.mesh.shape["data"] == 2
        print("RESHARD_OK")
    """)
    assert "RESHARD_OK" in stdout


# --------------------------------------------------------------------------
# mesh-resident sketch layer (DESIGN.md §9)
# --------------------------------------------------------------------------

_SKETCH_PRELUDE = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import importlib
        import numpy as np
        import jax, jax.numpy as jnp
        from repro import sketch as skt
        # the package re-exports the query *function*; the module needs
        # importlib (same trick as tests/test_query_path.py)
        qmod = importlib.import_module("repro.sketch.query")
        from repro.core import LSketchConfig
        from repro.core.gss import gss_config
        from repro.core.types import EdgeBatch

        LS = LSketchConfig(d=64, n_blocks=2, F=512, r=4, s=4, c=4, k=4,
                           window_size=400, pool_capacity=256, pool_probes=8)
        GS = gss_config(d=64, r=4, s=4, pool_capacity=256)

        def batch(a):
            return EdgeBatch(*[jnp.asarray(x, jnp.int32) for x in a])

        def stream(kind, seed=11, n=600, tmax=2400, nv=50):
            rng = np.random.default_rng(seed)
            src = rng.integers(0, nv, n).astype(np.int32)
            dst = rng.integers(0, nv, n).astype(np.int32)
            le = rng.integers(0, 5, n).astype(np.int32)
            w = rng.integers(1, 4, n).astype(np.int32)
            t = np.sort(rng.integers(0, tmax, n)).astype(np.int32)
            if kind == "gss":
                z = np.zeros(n, np.int32)
                return src, dst, z, z, z, w, z
            return src, dst, src % 3, dst % 3, le, w, t

        def mesh_over(ndev):
            return jax.sharding.Mesh(np.array(jax.devices()[:ndev]), ("data",))

        # compact (compile-budget-aware) query suite: every kind and both
        # directions, label-restricted edges, and a time-restricted horizon
        # for windowed sketches. Static-arg combos are kept lean — each
        # distinct (kind, with_le, direction, last) pair compiles its own
        # scan program on this 2-core box.
        def suite(kind, a):
            src, dst, la, lb, le, w, t = a
            lasts = (None,) if kind == "gss" else (None, 1)
            vs = np.arange(40, dtype=np.int32)
            for last in lasts:
                yield skt.QueryBatch.edges(src[:48], la[:48], dst[:48],
                                           lb[:48], last=last)
                yield skt.QueryBatch.edges(src[:48], la[:48], dst[:48], lb[:48],
                                           edge_label=le[:48], last=last)
                yield skt.QueryBatch.vertices(vs, vs % 3, direction="out",
                                              last=last)
                yield skt.QueryBatch.vertices(vs, vs % 3, direction="in",
                                              last=last)
                yield skt.QueryBatch.labels(np.arange(4, dtype=np.int32),
                                            last=last)

        def assert_parity(spec, state, kind, ctx):
            for qb in suite(kind, ARRS):
                a = np.asarray(skt.query(spec, state, qb, path="scan"))
                b = np.asarray(skt.query(spec, state, qb, path="collective"))
                assert np.array_equal(a, b), (ctx, qb.kind, qb.last,
                                              qb.direction, a[:6], b[:6])
"""


def test_collective_query_smoke_and_mesh_residency():
    """Tier-1 smoke: collective == scan on one (kind, shards, mesh) cell;
    ingest keeps the handle mesh-resident (sharded output + MeshContext);
    named_shardings warns once on (and only on) the replicated branch."""
    stdout = _run(_SKETCH_PRELUDE + """
        import warnings
        spec = skt.SketchSpec(kind="lsketch", config=LS, n_shards=4)
        mesh = mesh_over(4)
        ARRS = stream("lsketch")
        st = skt.place(spec, skt.create(spec), mesh)
        st = skt.ingest(spec, st, batch(ARRS))
        assert skt.mesh_context(st) is not None, "MeshContext lost by ingest"
        assert not st.shards.C.sharding.is_fully_replicated, \\
            "ingest gathered the placed state"
        host = skt.ingest(spec, skt.create(spec), batch(ARRS))
        assert all(bool(jnp.array_equal(x, y)) for x, y in zip(
            jax.tree.leaves(st.shards), jax.tree.leaves(host.shards))), \\
            "placed ingest diverged from host ingest"
        # full-horizon half of the suite only — the tier-1 compile budget;
        # the slow sweep covers every horizon x window position
        for qb in [q for q in suite("lsketch", ARRS) if q.last is None]:
            a = np.asarray(skt.query(spec, st, qb, path="scan"))
            b = np.asarray(skt.query(spec, st, qb, path="collective"))
            assert np.array_equal(a, b), (qb.kind, qb.direction, a[:6], b[:6])
        print("PARITY_OK")

        # named_shardings: divisible -> sharded (no warning)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            sh = skt.named_shardings(spec, mesh)
        assert not rec, [str(w.message) for w in rec]
        assert not sh.shards.C.is_fully_replicated
        # non-divisible -> replicated, one warning total
        spec3 = skt.SketchSpec(kind="lsketch", config=LS, n_shards=3)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            sh3 = skt.named_shardings(spec3, mesh)
            skt.named_shardings(spec3, mesh)  # second call: deduped
        assert sh3.shards.C.is_fully_replicated
        assert len(rec) == 1 and "replicated" in str(rec[0].message), \\
            [str(w.message) for w in rec]
        print("BRANCHES_OK")
    """)
    assert "PARITY_OK" in stdout and "BRANCHES_OK" in stdout


def test_collective_analytics_parity():
    """Top-k analytics, path="collective": the shard_map body (local
    decode + flatten, all_gather of (identity, weight) rows, replicated
    epilogue) is bit-identical to the host pallas path on a placed
    4-shard handle — including a restricted horizon."""
    stdout = _run(_SKETCH_PRELUDE + """
        spec = skt.SketchSpec(kind="lsketch", config=LS, n_shards=4)
        ARRS = stream("lsketch")
        st = skt.place(spec, skt.create(spec), mesh_over(4))
        st = skt.ingest(spec, st, batch(ARRS))
        for fn, kw in ((skt.heavy_vertices, {"direction": "out"}),
                       (skt.heavy_vertices, {"direction": "in", "last": 1}),
                       (skt.heavy_edges, {}),
                       (skt.top_labels, {})):
            a = fn(spec, st, 6, path="pallas", **kw)
            b = fn(spec, st, 6, path="collective", **kw)
            for x, y in zip(a, b):
                assert np.array_equal(np.asarray(x), np.asarray(y)), \\
                    (fn.__name__, kw, np.asarray(x), np.asarray(y))
        print("HH_COLLECTIVE_OK")
    """)
    assert "HH_COLLECTIVE_OK" in stdout


def test_collective_query_parity_routed_split_key():
    """Skew-aware routing (DESIGN.md §13) on a placed mesh handle: a spec
    with a split hot key partitions ingest across replica shards, and the
    collective query path stays bit-identical to the host scan — the
    replica fan-out is just the existing probe-every-shard-and-sum, so no
    plane rebuilds or collective changes are needed."""
    stdout = _run(_SKETCH_PRELUDE + """
        HOT = 7
        ARRS = list(stream("lsketch", seed=23))
        n = ARRS[0].shape[0]
        take = np.random.default_rng(5).random(n) < 0.5
        ARRS[0] = np.where(take, HOT, ARRS[0]).astype(np.int32)
        ARRS[2] = (ARRS[0] % 3).astype(np.int32)
        ARRS = tuple(ARRS)

        spec = skt.SketchSpec(kind="lsketch", config=LS, n_shards=4)
        routed = spec.with_splits([(HOT, HOT % 3, 4)])
        assert routed == spec  # routing is host-only: jit caches shared
        st = skt.place(routed, skt.create(routed), mesh_over(4))
        st = skt.ingest(routed, st, batch(ARRS))
        assert skt.mesh_context(st) is not None
        # placed routed ingest must match the host routed ingest bit-for-bit
        host = skt.ingest(routed, skt.create(routed), batch(ARRS))
        assert all(bool(jnp.array_equal(x, y)) for x, y in zip(
            jax.tree.leaves(st.shards), jax.tree.leaves(host.shards))), \\
            "placed routed ingest diverged from host routed ingest"
        # tier-1 compile budget: full-horizon half of the suite, like the
        # unrouted smoke test
        for qb in [q for q in suite("lsketch", ARRS) if q.last is None]:
            a = np.asarray(skt.query(routed, st, qb, path="scan"))
            b = np.asarray(skt.query(routed, st, qb, path="collective"))
            assert np.array_equal(a, b), (qb.kind, qb.direction, a[:6], b[:6])
        print("ROUTED_PARITY_OK")
    """)
    assert "ROUTED_PARITY_OK" in stdout


@pytest.mark.slow
def test_collective_query_parity_sweep_lsketch():
    """The acceptance sweep, LSketch half: path="collective" is
    bit-identical to path="scan" across shards {4, 8} x mesh layouts (1
    and 2 shards per device) x window positions — staged ingest, ring
    wraparound, pool overflow."""
    stdout = _run(_SKETCH_PRELUDE + """
        ARRS = stream("lsketch")
        for ns, ndev in ((4, 4), (8, 8), (8, 4)):
            spec = skt.SketchSpec(kind="lsketch", config=LS, n_shards=ns)
            st = skt.place(spec, skt.create(spec), mesh_over(ndev))
            n = len(ARRS[0]); step = -(-n // 2)
            for stage, a in enumerate(range(0, n, step)):
                st = skt.ingest(spec, st, batch(tuple(
                    x[a:a + step] for x in ARRS)))
                assert_parity(spec, st, "lsketch",
                              f"lsketch x{ns}/{ndev}dev s{stage}")
            print("OK", ns, ndev)

        # ring wrapped far past the stream: planes reduce to the same
        # (mostly expired) window the dense reference masks
        spec = skt.SketchSpec(kind="lsketch", config=LS, n_shards=4)
        ARRS = stream("lsketch", seed=12, n=200, tmax=LS.window_size - 1)
        st = skt.place(spec, skt.create(spec), mesh_over(4))
        st = skt.ingest(spec, st, batch(ARRS))
        late = tuple(np.asarray(x, np.int32) for x in
                     ([9999], [0], [9998], [0], [0], [1],
                      [LS.subwindow_size * 40]))
        st = skt.ingest(spec, st, batch(late))
        assert_parity(spec, st, "lsketch", "wraparound")
        print("OK wraparound")

        # saturated pool (pool_lost > 0) answers identically too
        tiny = LSketchConfig(d=8, n_blocks=2, F=256, r=2, s=2, c=4, k=4,
                             window_size=400, pool_capacity=8,
                             pool_probes=2)
        spec = skt.SketchSpec(kind="lsketch", config=tiny, n_shards=4)
        ARRS = stream("lsketch", seed=13, n=500, tmax=1500, nv=400)
        st = skt.place(spec, skt.create(spec), mesh_over(4))
        st = skt.ingest(spec, st, batch(ARRS))
        assert int(jnp.sum(st.shards.pool_lost)) > 0
        assert_parity(spec, st, "lsketch", "pool-overflow")
        print("OK pool-overflow")
    """, timeout=1200)
    assert stdout.count("OK") == 5


@pytest.mark.slow
def test_collective_query_parity_sweep_gss():
    """The acceptance sweep, GSS half (degenerate normalization: no
    labels, no window) across shards {4, 8} x mesh layouts."""
    stdout = _run(_SKETCH_PRELUDE + """
        ARRS = stream("gss")
        for ns, ndev in ((4, 4), (8, 8), (8, 4)):
            spec = skt.SketchSpec(kind="gss", config=GS, n_shards=ns)
            st = skt.place(spec, skt.create(spec), mesh_over(ndev))
            n = len(ARRS[0]); step = -(-n // 2)
            for stage, a in enumerate(range(0, n, step)):
                st = skt.ingest(spec, st, batch(tuple(
                    x[a:a + step] for x in ARRS)))
                assert_parity(spec, st, "gss",
                              f"gss x{ns}/{ndev}dev s{stage}")
            print("OK", ns, ndev)
    """, timeout=1200)
    assert stdout.count("OK") == 3


@pytest.mark.slow
def test_collective_compile_counts_and_device_plane_cache():
    """One shard_map program per (kind, bucket); one device-resident plane
    build per (handle, horizon) — the handle-identity cache contract,
    unchanged on the mesh."""
    stdout = _run(_SKETCH_PRELUDE + """
        spec = skt.SketchSpec(kind="lsketch", config=LS, n_shards=8)
        ARRS = stream("lsketch", seed=31)
        st = skt.place(spec, skt.create(spec), mesh_over(8))
        st = skt.ingest(spec, st, batch(ARRS))
        src, dst, la, lb = ARRS[0], ARRS[1], ARRS[2], ARRS[3]

        def edge_q(n, last=None):
            return skt.QueryBatch.edges(src[:n], la[:n], dst[:n], lb[:n],
                                        last=last)

        before = dict(qmod.QUERY_TRACE_COUNTS)
        delta = lambda kind: (qmod.QUERY_TRACE_COUNTS.get(
            (kind, "collective"), 0) - before.get((kind, "collective"), 0))
        builds0 = qmod.PLANES_BUILD_COUNTS["build"]
        skt.query(spec, st, edge_q(20), path="collective")   # bucket 32
        skt.query(spec, st, edge_q(27), path="collective")   # same bucket
        assert delta("edge") == 1, "same (kind, bucket) retraced"
        skt.query(spec, st, edge_q(40), path="collective")   # bucket 64
        n2 = delta("edge")
        skt.query(spec, st, edge_q(33), path="collective")
        assert delta("edge") == n2, "repeated bucket retraced"
        vs = np.arange(20, dtype=np.int32)
        skt.query(spec, st, skt.QueryBatch.vertices(vs, vs % 3),
                  path="collective")
        skt.query(spec, st, skt.QueryBatch.labels([0, 1]),
                  path="collective")
        # every query above shares the one full-horizon device plane build
        assert qmod.PLANES_BUILD_COUNTS["build"] - builds0 == 1, \\
            qmod.PLANES_BUILD_COUNTS["build"] - builds0
        # a tighter horizon is a different pure function -> one more build
        skt.query(spec, st, edge_q(20, last=1), path="collective")
        assert qmod.PLANES_BUILD_COUNTS["build"] - builds0 == 2
        # a new handle starts cold (ingest invalidates by construction)
        st2 = skt.ingest(spec, st, batch(tuple(
            x[:64] for x in stream("lsketch", seed=32))))
        skt.query(spec, st2, edge_q(20), path="collective")
        assert qmod.PLANES_BUILD_COUNTS["build"] - builds0 == 3
        # the collective planes live under the state's sharding
        planes = skt.query_planes(spec, st2, collective=True)
        assert not planes.cw.sharding.is_fully_replicated, \\
            "device plane cache is not sharded"
        print("COUNTS_OK")
    """, timeout=1200)
    assert "COUNTS_OK" in stdout


@pytest.mark.slow
def test_collective_planes_delta_across_flushes():
    """DESIGN.md §10 on the mesh: the device-resident plane cache
    survives ingest flushes via the shard_map'd delta apply — no
    device-wide rebuild in steady state, results bit-identical to a cold
    device build, sharding preserved, and collective == scan end-to-end
    on the delta-maintained handle."""
    stdout = _run(_SKETCH_PRELUDE + """
        spec = skt.SketchSpec(kind="lsketch", config=LS, n_shards=8)
        # dense enough that every one of the 8 shards claims the live
        # subwindow — a shard that never saw it resets on the first live
        # flush, which (correctly) invalidates the delta globally
        ARRS = stream("lsketch", seed=71, n=1600)
        st = skt.place(spec, skt.create(spec), mesh_over(8))
        st = skt.ingest(spec, st, batch(ARRS))
        skt.query_planes(spec, st, collective=True)  # warm device cache
        b0 = qmod.PLANES_BUILD_COUNTS["build"]
        d0 = qmod.PLANES_BUILD_COUNTS["delta"]

        def live_batch(seed, tlo=2300, thi=2400, n=64):
            # single live subwindow (t in [2300, 2400), subwindow 100):
            # the delta stays valid across every flush
            rng = np.random.default_rng(seed)
            src = rng.integers(0, 50, n).astype(np.int32)
            dst = rng.integers(0, 50, n).astype(np.int32)
            return batch((src, dst, src % 3, dst % 3,
                          rng.integers(0, 5, n), rng.integers(1, 4, n),
                          np.sort(rng.integers(tlo, thi, n))))

        n_flushes = 4
        for i in range(n_flushes):
            st = skt.ingest(spec, st, live_batch(72 + i))
            pl = skt.query_planes(spec, st, collective=True)
            assert not pl.cw.sharding.is_fully_replicated, \\
                "delta-applied device planes lost their sharding"
            inc = jax.tree.leaves(pl)
            skt.clear_plane_cache(st)
            cold = jax.tree.leaves(skt.query_planes(spec, st,
                                                    collective=True))
            assert all(bool(jnp.array_equal(x, y))
                       for x, y in zip(inc, cold)), f"flush {i} diverged"
        assert qmod.PLANES_BUILD_COUNTS["delta"] - d0 == n_flushes
        # the cold rebuilds forced for the comparison are the ONLY builds
        assert qmod.PLANES_BUILD_COUNTS["build"] - b0 == n_flushes
        # ring movement falls back on the mesh too
        st = skt.ingest(spec, st, live_batch(90, tlo=2400, thi=2500))
        skt.query_planes(spec, st, collective=True)
        assert qmod.PLANES_BUILD_COUNTS["build"] - b0 == n_flushes + 1
        assert qmod.PLANES_BUILD_COUNTS["delta"] - d0 == n_flushes
        # end-to-end answers on a delta-maintained handle
        st = skt.ingest(spec, st, live_batch(99, tlo=2400, thi=2500))
        assert_parity(spec, st, "lsketch", "delta-maintained")
        print("COLLECTIVE_DELTA_OK")
    """, timeout=1200)
    assert "COLLECTIVE_DELTA_OK" in stdout


def test_collective_multi_horizon_parity_and_delta():
    """DESIGN.md §14 on the mesh: ``query(last=[h1, ..., hH])`` under
    path="collective" answers bit-identically to the per-horizon scan
    reference, the device-resident stacked ``MultiPlanes`` entry keeps
    its sharding and folds flush deltas device-locally (no rebuild in
    steady state), and the analytics horizon sweep matches its
    single-horizon collective twin."""
    stdout = _run(_SKETCH_PRELUDE + """
        import dataclasses
        spec = skt.SketchSpec(kind="lsketch", config=LS, n_shards=8)
        # dense enough that every shard claims the live subwindow (same
        # reasoning as the single-horizon delta test above)
        ARRS = stream("lsketch", seed=41, n=1600)
        st = skt.place(spec, skt.create(spec), mesh_over(4))
        st = skt.ingest(spec, st, batch(ARRS))
        src, dst, la, lb = ARRS[0], ARRS[1], ARRS[2], ARRS[3]
        lasts = [3, None, 1, 3, 2]  # dupes + full-window alias in user order

        def check(st, ctx):
            vs = np.arange(24, dtype=np.int32)
            for qb in (skt.QueryBatch.edges(src[:32], la[:32], dst[:32],
                                            lb[:32], last=lasts),
                       skt.QueryBatch.vertices(vs, vs % 3, last=lasts)):
                sweep = np.asarray(skt.query(spec, st, qb,
                                             path="collective"))
                for i, h in enumerate(lasts):
                    ref = np.asarray(skt.query(
                        spec, st, dataclasses.replace(qb, last=h),
                        path="scan"))
                    assert np.array_equal(sweep[i], ref), (ctx, qb.kind, h)

        check(st, "cold")
        mp, uniq = skt.query_planes_multi(spec, st, lasts, collective=True)
        assert uniq == (1, 2, 3, 4)
        assert not mp.cw.sharding.is_fully_replicated, \\
            "stacked device planes lost their sharding"

        # steady state: one live flush folds ONE delta into the stacked
        # entry — bit-identical to a cold rebuild, zero extra builds
        b0 = qmod.PLANES_BUILD_COUNTS["build"]
        d0 = qmod.PLANES_BUILD_COUNTS["delta"]
        rng = np.random.default_rng(42)
        lsrc = rng.integers(0, 50, 64).astype(np.int32)
        ldst = rng.integers(0, 50, 64).astype(np.int32)
        live = batch((lsrc, ldst, lsrc % 3, ldst % 3,
                      rng.integers(0, 5, 64), rng.integers(1, 4, 64),
                      np.sort(rng.integers(2300, 2400, 64))))
        st2 = skt.ingest(spec, st, live)
        mp2, _ = skt.query_planes_multi(spec, st2, lasts, collective=True)
        assert qmod.PLANES_BUILD_COUNTS["build"] == b0, \\
            "live flush must fold into the stacked entry, not rebuild"
        assert qmod.PLANES_BUILD_COUNTS["delta"] > d0
        assert not mp2.cw.sharding.is_fully_replicated
        inc = jax.tree.leaves(mp2)
        skt.clear_plane_cache(st2)
        cold = jax.tree.leaves(skt.query_planes_multi(
            spec, st2, lasts, collective=True)[0])
        assert all(bool(jnp.array_equal(x, y))
                   for x, y in zip(inc, cold)), "delta diverged from cold"
        check(st2, "delta-maintained")

        # analytics sweep rides the same stacked device entry
        hs = [1, 2, 4]
        for fn in (skt.heavy_vertices, skt.top_labels):
            sweep = fn(spec, st2, 5, horizons=hs, path="collective")
            for i, h in enumerate(hs):
                ref = fn(spec, st2, 5, last=h, path="collective")
                a = jax.tree.leaves(jax.tree.map(lambda x: x[i], sweep))
                b = jax.tree.leaves(ref)
                assert all(np.array_equal(np.asarray(x), np.asarray(y))
                           for x, y in zip(a, b)), (fn.__name__, h)
        print("MULTI_COLLECTIVE_OK")
    """, timeout=1200)
    assert "MULTI_COLLECTIVE_OK" in stdout
