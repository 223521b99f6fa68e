"""Pallas TPU kernels for the perf-critical hot spots.

Kernel packages (``kernel.py`` holds the ``pl.pallas_call``, ``ops.py``
the jitted wrappers):

  sketch_insert   — the first-fit key walk of block-binned LSketch
                    insertion. The paper's Storage Blocks Division is the
                    grid: one program per (shard, mA, mB) storage block
                    walks its bin through the block's VMEM key tile; the
                    C/P counters land in one XLA scatter-add afterwards.
  heavy_hitters   — the reversible cell-owner decode behind top-k
                    analytics, tiled over row bands of the planes.
  flash_attention — blockwise-softmax causal attention for the LM substrate.

``sketch_query`` and ``vertex_scan`` hold no kernel: a query probes a few
random cells of planes far larger than VMEM, which XLA's gathers already
do from HBM, so their compiled XLA programs are the one path on every
backend.

Where the kernels run: ``use_tpu_kernels()`` is the one switch. On TPU
every kernel above is compiled by Mosaic (never interpreted); on any
other backend the kernel's compiled XLA lowering runs instead. CPU tests
run with ``JAX_PLATFORMS=cpu`` and hold each kernel, in Pallas interpret
mode, bit-identical to its XLA lowering; ``tests/test_tpu_compile.py``
compiles the kernels for a described v5e chip at deployment widths. The
chip itself runs ``chip_smoke.py`` at the repository root.
"""

import jax


def use_tpu_kernels() -> bool:
    """True where the Pallas kernels compile (the TPU backend). Elsewhere
    their compiled XLA lowerings run; nothing on the served path runs in
    Pallas interpret mode."""
    return jax.default_backend() == "tpu"
