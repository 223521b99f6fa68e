"""Compile rehearsal: every program a cell warms, at the cell's shapes,
compiled for one chip of a described ``v5e:2x2`` host. Nothing runs; a
pass means the TPU compiler accepted the program (Mosaic kernels
included) and it fits one chip's memory.

The topology is described inside a fixture, never at import."""

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BENCH = Path(__file__).resolve().parents[1]
FIELDS = ("src", "dst", "src_label", "dst_label", "edge_label", "weight",
          "time")


def spec_for(config: str):
    from repro import sketch as skt
    from repro.core import LSketchConfig
    sk = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    sk = sk["sketch"]
    cfg = LSketchConfig(**{k: sk[k] for k in (
        "d", "n_blocks", "F", "r", "s", "c", "k", "window_size",
        "pool_capacity", "pool_probes", "seed")})
    return skt.SketchSpec(kind="lsketch", config=cfg, n_shards=1)


def mix(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def tpu_lowering(monkeypatch):
    from repro.kernels.sketch_insert import ops
    monkeypatch.setattr(ops, "use_tpu_kernels", lambda: True)


def on(chip, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)


def shapes(spec):
    from repro.core.queries import build_query_planes
    from repro.core.types import init_state
    state = jax.eval_shape(
        lambda: jax.tree.map(lambda x: x[None], init_state(spec.config)))
    planes = jax.eval_shape(
        lambda s: build_query_planes(spec.config, s, None), state)
    return state, planes


def i32(chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)


HBM = 15.75 * 2 ** 30  # what the v5e compiler gives a program


def fits(compiled):
    mem = compiled.memory_analysis()
    peak = getattr(mem, "peak_memory_in_bytes", None) or (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < HBM
    return compiled


@pytest.mark.parametrize("config", ["comfs-social"])
def test_ingest_and_plane_programs(chip, tpu_lowering, config):
    from repro.core.types import EdgeBatch
    from repro.sketch.ingest import _ingest_stacked_lsketch
    from repro.sketch.query import _apply_planes_delta, _build_planes
    spec = spec_for(config)
    state, planes = shapes(spec)
    B = mix("closed_ingest")["ingest"]["batch"]
    batch = EdgeBatch(**{f: i32(chip, 1, B) for f in FIELDS})
    for emit in (False, True):
        c = fits(_ingest_stacked_lsketch.lower(
            spec.config, None, on(chip, state), batch, i32(chip, 1),
            use_pallas=True, emit_delta=emit).compile())
        assert "sketch_insert_walk" in c.as_text()
    _, delta = jax.eval_shape(
        lambda s, b, n: _ingest_stacked_lsketch(
            spec.config, None, s, b, n, use_pallas=False, emit_delta=True),
        state, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            batch), jax.ShapeDtypeStruct((1,), jnp.int32))
    k = spec.config.k
    fits(_build_planes.lower(spec, on(chip, state), horizon=k).compile())
    fits(_apply_planes_delta.lower(spec, on(chip, state), on(chip, planes),
                                   on(chip, delta), horizon=k).compile())


def bucket(n: int) -> int:
    """The query frontend's pad bucket for ``n`` rows (floor 32)."""
    b = 32
    while b < n:
        b *= 2
    return b


def query_groups():
    """(traffic, group, pad bucket) for every group a mix can flush: the
    post-window check's groups, and an open loop's groups at its largest
    flush."""
    chk = mix("closed_ingest")["check"]
    out = [("closed_ingest", g, bucket(chk["per_group"]))
           for g in chk["groups"]]
    q = mix("serve_point")["queries"]
    out += [("serve_point", g, bucket(q["max_per_flush"])) for g in q["mix"]]
    return out


@pytest.mark.parametrize("traffic,group,n", query_groups(),
                         ids=lambda x: str(x))
def test_query_programs(chip, traffic, group, n):
    from repro.sketch.query import _edge_pallas, _label_pallas, \
        _vertex_pallas
    spec = spec_for("comfs-social")
    _, planes = shapes(spec)
    le = bool(group.get("le"))
    if group["kind"] == "edge":
        fits(_edge_pallas.lower(spec, on(chip, planes),
                                *[i32(chip, n)] * 5, with_le=le).compile())
    elif group["kind"] == "vertex":
        fits(_vertex_pallas.lower(spec, on(chip, planes),
                                  *[i32(chip, n)] * 3, with_le=le,
                                  direction=group["direction"]).compile())
    else:
        fits(_label_pallas.lower(spec, on(chip, planes),
                                 *[i32(chip, n)] * 2, with_le=le,
                                 direction=group["direction"]).compile())
