"""Compile rehearsal: the serving path's programs for a described v5e chip.

Each test lowers one jitted program of the served path at the widths
``chip_smoke.py`` runs (d=1024, b=512, c=16, k=8: 1.15 GB of state per
shard) and compiles it with the TPU compiler for one chip of a described
``v5e:2x2`` topology. No chip is attached and nothing runs: a pass means
Mosaic accepted the Pallas kernels (block shapes, VMEM budget, SMEM
blocks) and XLA fitted the program into one chip's memory.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU compiler library. Code that
picks its lowering from ``jax.default_backend()`` still sees the CPU
here, so the tests steer ``use_tpu_kernels`` to the TPU branch themselves.
"""

import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import sketch as skt
from repro.core import EdgeBatch, LSketchConfig
from repro.core.queries import build_query_planes
from repro.core.types import init_state

CFG = LSketchConfig(d=1024, n_blocks=2, F=1024, r=8, s=8, c=16, k=8,
                    window_size=1440, pool_capacity=4096, pool_probes=16)
SPEC = skt.SketchSpec(kind="lsketch", config=CFG, n_shards=1)
BATCH = 4096
QUERIES = 4096


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2 host, with the persistent
    compilation cache off (a compile for a described chip cannot be read
    back without one)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def tpu_lowering(monkeypatch):
    """Take the TPU branch of the backend-chosen insert lowering."""
    from repro.kernels.sketch_insert import ops as ins_ops
    monkeypatch.setattr(ins_ops, "use_tpu_kernels", lambda: True)


def _on(chip, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)


def _stacked():
    return jax.eval_shape(
        lambda: jax.tree.map(lambda x: x[None], init_state(CFG)))


def _state(chip):
    return _on(chip, _stacked())


def _planes(chip):
    return _on(chip, jax.eval_shape(
        lambda s: build_query_planes(CFG, s, None), _stacked()))


def _i32(chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)


def _compile(fn, *args, **kw):
    compiled = fn.lower(*args, **kw).compile()
    mem = compiled.memory_analysis()
    # everything the program holds must fit one 16 GB v5e chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    return compiled


def test_ingest_program_compiles_with_walk_kernel(chip, tpu_lowering):
    from repro.sketch.ingest import _ingest_stacked_lsketch
    batch = EdgeBatch(**{f: _i32(chip, 1, BATCH) for f in (
        "src", "dst", "src_label", "dst_label", "edge_label", "weight",
        "time")})
    compiled = _compile(_ingest_stacked_lsketch, CFG, None, _state(chip),
                        batch, _i32(chip, 1), use_pallas=True,
                        emit_delta=True)
    text = compiled.as_text()
    assert "sketch_insert_walk" in text
    # the named scopes reach the compiled program's op_name metadata
    for scope in ("plan", "reclaim", "walk", "scan", "delta"):
        assert f"/ingest.{scope}/" in text, scope


def test_ingest_scopes_add_metadata_only(chip, tpu_lowering):
    """The walk program compiles to the same instructions with its
    ``ingest.*`` scopes and without them (``python -m
    repro.launch.scope_identity`` checks every variant at the served
    cell's 36 ring slots)."""
    from repro.launch import scope_identity as si
    on, off = (si.compiled_text(CFG, chip, True, True, scopes=s)
               for s in (True, False))
    assert si.SCOPE.search(on) and not si.SCOPE.search(off)
    assert si.canonical(on) == si.canonical(off)
    assert si.instructions(si.canonical(on)) > 1000


def test_edge_query_program_compiles(chip):
    from repro.sketch.query import _edge_pallas
    q = [_i32(chip, QUERIES) for _ in range(5)]
    _compile(_edge_pallas, SPEC, _planes(chip), *q, with_le=True)


def test_vertex_scan_program_compiles(chip):
    from repro.sketch.query import _vertex_pallas
    q = [_i32(chip, 512) for _ in range(3)]
    for direction in ("out", "in"):
        _compile(_vertex_pallas, SPEC, _planes(chip), *q, with_le=True,
                 direction=direction)


def test_decode_kernel_compiles(chip):
    """The cell-owner decode of top-k analytics, row band by row band
    (the whole-plane block of d=1024 needed 80 MiB of scoped VMEM). The
    sort-based top-k epilogue around it is plain XLA and compiles for
    tens of seconds, so only the kernel is rehearsed here."""
    from repro.kernels.heavy_hitters.kernel import cell_decode_kernel_sharded
    from repro.kernels.heavy_hitters.ops import _static_blocks
    starts, widths = _static_blocks(CFG)
    compiled = _compile(cell_decode_kernel_sharded, _planes(chip).key,
                        starts=starts, widths=widths, r=CFG.r, F=CFG.F)
    assert "cell_decode" in compiled.as_text()


def test_plane_builds_compile(chip):
    from repro.sketch.query import _build_planes, _build_planes_multi
    _compile(_build_planes, SPEC, _state(chip), horizon=8)
    _compile(_build_planes_multi, SPEC, _state(chip), horizons=(1, 2, 4, 8))
