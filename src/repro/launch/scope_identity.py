"""Check that the ``ingest.*`` named scopes of the stacked ingest program
add metadata only.

Compiles ``_ingest_stacked_lsketch`` for one chip of a described v5e
(no chip attached, nothing runs) twice per variant: as written, and with
``jax.named_scope`` made a no-op while it traces. The two compiled
programs must be the same instructions once each instruction's
``metadata={...}``, the source-location tables and the locations inside
each Mosaic kernel's payload are dropped.

Usage (the served cell's widths, 36 ring slots by default):
  python -m repro.launch.scope_identity [--k 36]
"""

from __future__ import annotations

import argparse
import base64
import contextlib
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp

VARIANTS = ((True, True), (True, False), (False, True))  # use_pallas, delta
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
SCOPE = re.compile(r"/ingest\.(plan|reclaim|scan|walk|delta)/")


def config(k: int):
    """The served width (d=1024, as ``bench/configs/comfs-social.json``)
    at ``k`` ring slots."""
    from repro.core import LSketchConfig
    return LSketchConfig(d=1024, n_blocks=2, F=1024, r=8, s=8, c=16, k=k,
                         window_size=1440, pool_capacity=4096,
                         pool_probes=16)


def described_chip():
    """One chip of a described ``v5e:2x2`` host, the persistent
    compilation cache off (a compile for a described chip cannot be read
    back without one)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(cfg, chip, use_pallas: bool, emit_delta: bool,
                  scopes: bool = True, batch: int = 4096) -> str:
    """The compiled HLO text of the ingest program (the TPU lowering of
    the insert kernel is selected by the caller)."""
    from repro.core import EdgeBatch
    from repro.core.types import init_state
    from repro.sketch.ingest import _FIELDS, _ingest_stacked_lsketch

    def on(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    state = jax.tree.map(on, jax.eval_shape(
        lambda: jax.tree.map(lambda x: x[None], init_state(cfg))))
    rows = EdgeBatch(**{f: on(jax.ShapeDtypeStruct((1, batch), jnp.int32))
                        for f in _FIELDS})
    n_valid = on(jax.ShapeDtypeStruct((1,), jnp.int32))
    jax.clear_caches()  # trace again: the scopes are seen at trace time
    off = mock.patch.object(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
    with off if not scopes else contextlib.nullcontext():
        return _ingest_stacked_lsketch.lower(
            cfg, None, state, rows, n_valid, use_pallas=use_pallas,
            emit_delta=emit_delta).compile().as_text()


def _kernel_without_locations(m) -> str:
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu  # noqa: F401  (registers the dialect)
    from jaxlib.mlir import ir
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        body = ir.Module.parse(base64.b64decode(m.group(1)))
        asm = body.operation.get_asm(enable_debug_info=False)
    return '"body":"' + asm.replace("\n", " ") + '"'


def canonical(text: str) -> str:
    """``text`` without metadata, location tables or kernel locations."""
    keep, skip = [], False
    for line in text.splitlines():
        if line in _TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            keep.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return re.sub(r'"body":"([A-Za-z0-9+/=]+)"', _kernel_without_locations,
                  "\n".join(keep))


def instructions(text: str) -> int:
    return sum(" = " in line for line in text.splitlines())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--k", type=int, default=36, help="ring slots")
    args = ap.parse_args(argv)
    from repro.kernels.sketch_insert import ops as ins_ops
    ins_ops.use_tpu_kernels = lambda: True  # the chip's insert lowering
    chip, cfg = described_chip(), config(args.k)
    same = True
    for up, ed in VARIANTS:
        on, off = (compiled_text(cfg, chip, up, ed, scopes=s)
                   for s in (True, False))
        a, b = canonical(on), canonical(off)
        # the check means something only if the scopes were there to drop
        scoped = bool(SCOPE.search(on)) and not SCOPE.search(off)
        same &= a == b and scoped
        print(f"k={args.k} use_pallas={up} emit_delta={ed}: "
              f"{'identical' if a == b else 'DIFFERENT'} "
              f"({instructions(a)} instructions; scopes "
              f"{'dropped' if scoped else 'NOT dropped'})", flush=True)
    return 0 if same else 1


if __name__ == "__main__":
    raise SystemExit(main())
