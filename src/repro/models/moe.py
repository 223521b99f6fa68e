"""Mixture-of-Experts layer: shared + routed experts, top-k, capacity-based
dispatch (GShard-style einsum formulation — the TPU-native MoE).

Sharding: experts live on the "model" axis (expert parallelism). With tokens
sharded over the data axes, XLA inserts the canonical all-to-all pair around
the expert computation. The dispatch/combine tensors are the collective-
bound part the §Perf hillclimb attacks.

Telemetry: the router emits a (token-bucket x expert) count matrix — the
heterogeneous graph stream (token-bucket --rank--> expert) LSketch summarizes
(DESIGN.md §4).
"""

from __future__ import annotations

import jax
from jax.ad_checkpoint import checkpoint_name
import jax.numpy as jnp

from repro.distributed.sharding_ctx import constrain

from .config import ModelConfig
from .layers import mlp, mlp_defs
from .params import FSDP, TP, ParamDef

TELEMETRY_BUCKETS = 256  # token-hash buckets for the routing stream



def moe_defs(cfg: ModelConfig):
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    defs = {
        "router": ParamDef((D, E), (FSDP, None), init="scaled"),
        "w_gate": ParamDef((E, D, F), (TP, FSDP, None), init="scaled"),
        "w_up": ParamDef((E, D, F), (TP, FSDP, None), init="scaled"),
        "w_down": ParamDef((E, F, D), (TP, None, FSDP), init="scaled"),
    }
    if cfg.n_shared_experts:
        defs["shared"] = mlp_defs(cfg, cfg.moe_d_ff * cfg.n_shared_experts)
    return defs


def moe(params, x, cfg: ModelConfig, token_ids=None,
        capacity_factor: float | None = None):
    """x: [B, S, D] -> (y, aux) where aux carries load-balance loss terms and
    the telemetry count matrix.

    ``capacity_factor`` overrides cfg (decode passes E/top_k, i.e. capacity
    = N tokens per expert — drop-free serving, matching prefill logits)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    N = B * S
    xf = x.reshape(N, D)
    cf = capacity_factor if capacity_factor is not None else cfg.capacity_factor

    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)  # [N,K]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- locality-aware sort-based dispatch --------------------------------
    # The dispatch is *local to each data shard* (shard_map): each shard
    # sorts its own tokens by expert and packs an (E, cap_local, D) buffer —
    # zero collectives. The only cross-chip traffic is the canonical MoE
    # all-to-all: resharding the packed buffer from (replicated-E,
    # data-sharded-cap) to (EP-sharded-E, data-sharded-cap) for the expert
    # matmuls, and back for the combine. A global scatter formulation makes
    # XLA materialize the full [N*K, D] dispatch tensor replicated
    # (~130 GB/layer for DeepSeek-V2) — measured in EXPERIMENTS.md §Perf.
    from repro.distributed.sharding_ctx import _current
    ctx = _current()
    ndp = ctx.axis_size(ctx.logical["dp"]) if ctx is not None else 1
    if N % ndp:
        ndp = 1
    N_loc = N // ndp
    cap_loc = int(max(1, min(N_loc, cf * N_loc * K / E)))

    def dispatch_local(xf_loc, eid_loc):
        n = xf_loc.shape[0]
        fe = eid_loc.reshape(n * K)
        order = jnp.argsort(fe, stable=True)
        grp_start = jnp.searchsorted(fe[order], jnp.arange(E, dtype=fe.dtype))
        pos_sorted = jnp.arange(n * K, dtype=jnp.int32) - grp_start[fe[order]]
        pos = jnp.zeros((n * K,), jnp.int32).at[order].set(pos_sorted)
        keep = pos < cap_loc
        # destinations are unique -> scatter-SET (stays bf16; scatter-ADD
        # upcasts to f32 for accumulation). Dropped tokens aim out of
        # bounds and mode="drop" discards them.
        pos_c = jnp.where(keep, pos, cap_loc)
        tok_idx = jnp.arange(n * K, dtype=jnp.int32) // K
        xe_loc = jnp.zeros((E, cap_loc, D), xf_loc.dtype).at[fe, pos_c].set(
            xf_loc[tok_idx], mode="drop")
        return xe_loc, fe, jnp.where(keep, pos_c, 0), keep

    def combine_local(ye_loc, fe, pos_c, keep, gv_loc):
        back = ye_loc[fe, pos_c] * keep[:, None].astype(ye_loc.dtype)
        n = gv_loc.shape[0]
        return (back.reshape(n, K, D)
                * gv_loc[..., None].astype(ye_loc.dtype)).sum(axis=1)

    if ctx is not None and ndp > 1:
        from jax.sharding import PartitionSpec as P
        dp = ctx.logical["dp"]
        dspec = dp if len(dp) > 1 else dp[0]
        xe, fe, pos_c, keep = jax.shard_map(
            dispatch_local, mesh=ctx.mesh,
            in_specs=(P(dspec, None), P(dspec, None)),
            out_specs=(P(None, dspec, None), P(dspec), P(dspec), P(dspec)),
            check_vma=False,
        )(xf, expert_ids)
    else:
        xe, fe, pos_c, keep = dispatch_local(xf, expert_ids)
    # MoE all-to-all #1: expert axis gets EP-sharded for the matmuls.
    # checkpoint_name: under the "dots"+names remat policy the resharded
    # buffer is SAVED, so backward never re-runs the reshard collectives
    # (§Perf cell A it7).
    xe = constrain(xe, "ep", "dp", None)
    xe = checkpoint_name(xe, "moe_xe")

    def expert_fn(wg, wu, wd, xe_):
        g = jnp.einsum("cd,df->cf", xe_, wg)
        u = jnp.einsum("cd,df->cf", xe_, wu)
        return jnp.einsum("cf,fd->cd", jax.nn.silu(g) * u, wd)

    ye = jax.vmap(expert_fn)(params["w_gate"], params["w_up"],
                             params["w_down"], xe)  # [E,cap,D]
    # keep the return wire in the compute dtype: the reshard back to
    # (replicated-E, data-sharded-cap) is the biggest collective of an MoE
    # step and must not ride in f32 (§Perf cell A it5)
    ye = ye.astype(xf.dtype)
    # MoE all-to-all #2: back to (replicated-E, data-sharded-cap)
    ye = constrain(ye, None, "dp", None)
    ye = checkpoint_name(ye, "moe_ye")
    if ctx is not None and ndp > 1:
        from jax.sharding import PartitionSpec as P
        dp = ctx.logical["dp"]
        dspec = dp if len(dp) > 1 else dp[0]
        y = jax.shard_map(
            combine_local, mesh=ctx.mesh,
            in_specs=(P(None, dspec, None), P(dspec), P(dspec), P(dspec),
                      P(dspec, None)),
            out_specs=P(dspec, None),
            check_vma=False,
        )(ye, fe, pos_c, keep, gate_vals)
    else:
        y = combine_local(ye, fe, pos_c, keep, gate_vals)
    y = y.reshape(B, S, D)

    if cfg.n_shared_experts:
        y = y + mlp(params["shared"], x)

    # aux losses (Switch/GShard) + router z-loss
    density = jnp.bincount(fe.reshape(-1), length=E).astype(jnp.float32) / N
    router_prob = probs.mean(0)  # [E]
    lb_loss = E * jnp.sum(density * router_prob)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    dropped = 1.0 - (keep.sum() / (N * K))

    # telemetry stream: (token-bucket -> expert) weighted edges
    if token_ids is not None:
        bucket = (token_ids.reshape(N) % TELEMETRY_BUCKETS).astype(jnp.int32)
        tele = jnp.zeros((TELEMETRY_BUCKETS, E), jnp.int32)
        tele = tele.at[bucket[:, None], expert_ids].add(1, mode="drop")
    else:
        tele = jnp.zeros((TELEMETRY_BUCKETS, E), jnp.int32)

    aux = {"lb_loss": lb_loss, "z_loss": z_loss, "dropped": dropped,
           "telemetry": tele}
    return y, aux
