"""Per-kernel shape/dtype sweeps vs their references."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import EdgeBatch, LSketchConfig, init_state
from repro.core.lsketch import insert_window_batch
from repro.kernels.flash_attention.ops import attention
from repro.kernels.flash_attention.ref import reference_attention
from repro.kernels.sketch_insert.ops import insert_window_batch_pallas


def _mk_batch(rng, n, nv=60, nvl=3, nel=6, t=10):
    return EdgeBatch(
        src=jnp.asarray(rng.integers(0, nv, n), jnp.int32),
        dst=jnp.asarray(rng.integers(0, nv, n), jnp.int32),
        src_label=jnp.asarray(rng.integers(0, nvl, n), jnp.int32),
        dst_label=jnp.asarray(rng.integers(0, nvl, n), jnp.int32),
        edge_label=jnp.asarray(rng.integers(0, nel, n), jnp.int32),
        weight=jnp.asarray(rng.integers(1, 4, n), jnp.int32),
        time=jnp.asarray(np.full(n, t), jnp.int32))


@pytest.mark.parametrize("d,nb,F,r,s,c,k", [
    (32, 2, 256, 2, 2, 2, 1),
    (64, 4, 512, 4, 4, 4, 4),
    (64, 2, 1024, 8, 8, 8, 2),
    (128, 8, 2048, 4, 8, 16, 4),
])
def test_sketch_insert_sweep(d, nb, F, r, s, c, k):
    cfg = LSketchConfig(d=d, n_blocks=nb, F=F, r=r, s=s, c=c, k=k,
                        window_size=0 if k == 1 else 100,
                        pool_capacity=256, pool_probes=8)
    rng = np.random.default_rng(d + r)
    batch = _mk_batch(rng, 200)
    a = insert_window_batch(cfg, init_state(cfg), batch, 0)
    b = insert_window_batch_pallas(cfg, init_state(cfg), batch, 0)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert jnp.array_equal(la, lb)


def test_sketch_insert_sequential_batches_compose():
    cfg = LSketchConfig(d=64, n_blocks=4, F=512, r=4, s=4, c=4, k=4,
                        window_size=100, pool_capacity=256, pool_probes=8)
    rng = np.random.default_rng(0)
    b1 = _mk_batch(rng, 100, t=10)
    b2 = _mk_batch(rng, 100, t=60)
    ref = insert_window_batch(cfg, init_state(cfg), b1, 0)
    ref = insert_window_batch(cfg, ref, b2, 2)
    ker = insert_window_batch_pallas(cfg, init_state(cfg), b1, 0)
    ker = insert_window_batch_pallas(cfg, ker, b2, 2)
    for la, lb in zip(jax.tree.leaves(ref), jax.tree.leaves(ker)):
        assert jnp.array_equal(la, lb)


@pytest.mark.parametrize("B,Hq,Hkv,L,dh,dtype", [
    (1, 2, 2, 128, 32, jnp.float32),
    (2, 4, 2, 256, 64, jnp.float32),
    (1, 8, 1, 128, 64, jnp.float32),   # MQA
    (2, 4, 4, 384, 32, jnp.bfloat16),  # bf16 + non-pow2 length
])
def test_flash_attention_sweep(B, Hq, Hkv, L, dh, dtype):
    ks = jax.random.split(jax.random.PRNGKey(L + dh), 3)
    q = jax.random.normal(ks[0], (B, Hq, L, dh), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, L, dh), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, L, dh), dtype)
    ref = reference_attention(q, k, v, causal=True)
    out = attention(q, k, v, causal=True, impl="pallas_interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    err = jnp.max(jnp.abs(ref.astype(jnp.float32) - out.astype(jnp.float32)))
    assert float(err) < tol, float(err)


def test_flash_attention_matches_model_path():
    """models' XLA attention == pallas kernel on a GQA shape."""
    from repro.models.attention import _masked_attention
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32))  # [B,L,H,dh]
    k = jax.random.normal(ks[1], (2, 128, 2, 32))
    v = jax.random.normal(ks[2], (2, 128, 2, 32))
    xla = _masked_attention(q, k, v, causal=True)
    pal = attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), causal=True,
                    impl="pallas_interpret").transpose(0, 2, 1, 3)
    assert float(jnp.max(jnp.abs(xla - pal))) < 2e-5
