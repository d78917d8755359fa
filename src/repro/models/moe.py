"""Top-k token-choice Mixture-of-Experts FFN (Mixtral / Grok-1 style).

TPU-native dispatch: tokens are routed to per-expert capacity-bounded
buffers via cumulative-sum slotting (no data-dependent shapes), experts
run as one batched einsum over the expert dimension, and results are
combined with routing weights. Capacity factor > 1 keeps drops rare;
dropped tokens pass through the residual stream untouched (standard
practice). Router runs in f32 with an optional z-loss for stability.

Sharding: expert weights (E, d, f) are FSDP-sharded on d and TP-sharded
on f; the expert dim stays local so the dispatch is a gather, not an
all-to-all (at E=8 << chips, expert-dim sharding would idle most chips;
see DESIGN.md). Aux losses (load balance, z-loss) are returned for the
train step.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import dense_init, shard

__all__ = ["init_moe", "moe_ffn"]


def init_moe(key, d_model: int, d_ff: int, num_experts: int, dtype) -> dict:
    kr, kg, ku, kd = jax.random.split(key, 4)
    e = num_experts
    return {
        "router": dense_init(kr, (d_model, e), jnp.float32),
        "w_gate": dense_init(kg, (e, d_model, d_ff), dtype),
        "w_up": dense_init(ku, (e, d_model, d_ff), dtype),
        "w_down": dense_init(kd, (e, d_ff, d_model), dtype),
    }


def moe_ffn(
    params: dict,
    x: jax.Array,
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: float,
) -> Tuple[jax.Array, dict]:
    """x: (B, S, D) -> (out (B,S,D), aux {load_balance_loss, router_z_loss, drop_frac})."""
    b, s, d = x.shape
    t = b * s
    e = num_experts
    xt = x.reshape(t, d)

    # --- router (f32) ---
    logits = jnp.dot(xt.astype(jnp.float32), params["router"])  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)  # (T, K)
    weights = weights / jnp.maximum(jnp.sum(weights, axis=-1, keepdims=True), 1e-9)

    # --- aux losses ---
    # load balance (Switch): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)  # (E,)
    assign1 = jax.nn.one_hot(experts[:, 0], e, dtype=jnp.float32)
    fe = jnp.mean(assign1, axis=0)
    load_balance = e * jnp.sum(fe * me)
    z = jax.nn.logsumexp(logits, axis=-1)
    z_loss = jnp.mean(z * z)

    # --- capacity slotting ---
    capacity = int(max(1, round(t * top_k / e * capacity_factor)))
    # flatten (token, k) pairs, expert-major position via cumsum
    flat_expert = experts.reshape(-1)  # (T*K,)
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)  # (T*K, E)
    pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot  # exclusive cumsum
    pos = jnp.sum(pos_in_expert * onehot, axis=1)  # (T*K,)
    keep = pos < capacity
    slot = flat_expert * capacity + pos  # (T*K,) in [0, E*capacity)
    slot = jnp.where(keep, slot, e * capacity)  # overflow slot dropped below

    token_of_pair = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)
    # buffer of token ids per slot; final extra slot swallows drops
    slot_token = jnp.full((e * capacity + 1,), 0, jnp.int32).at[slot].set(token_of_pair)
    slot_used = jnp.zeros((e * capacity + 1,), bool).at[slot].set(keep)
    slot_token = jnp.where(slot_used, slot_token, 0)

    xe = xt[slot_token[:-1]]  # (E*C, D) gather
    xe = xe * slot_used[:-1, None].astype(xe.dtype)
    xe = xe.reshape(e, capacity, d)
    xe = shard(xe, "expert", None, "embed")

    # --- expert FFN (batched einsum over E) ---
    from repro.models.layers import _out_proj_dtype, boundary_cast

    g = boundary_cast(
        jnp.einsum("ecd,edf->ecf", xe, params["w_gate"], preferred_element_type=jnp.float32),
        x.dtype,
    )
    u = boundary_cast(
        jnp.einsum("ecd,edf->ecf", xe, params["w_up"], preferred_element_type=jnp.float32),
        x.dtype,
    )
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    h = shard(h, "expert", None, "ff")
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"], preferred_element_type=_out_proj_dtype())
    ye = ye.reshape(e * capacity, d)

    # --- combine: scatter-add back with routing weights ---
    pair_w = jnp.where(keep, weights.reshape(-1), 0.0)  # (T*K,)
    # map each kept pair to its slot's output row
    safe_slot = jnp.minimum(slot, e * capacity - 1)
    y_pair = ye[safe_slot] * keep[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[token_of_pair].add(y_pair * pair_w[:, None])

    drop_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))
    aux = {"load_balance_loss": load_balance, "router_z_loss": z_loss, "drop_frac": drop_frac}
    return out.astype(x.dtype).reshape(b, s, d), aux


def moe_ffn_local(
    params: dict,
    x: jax.Array,
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: float,
) -> Tuple[jax.Array, dict]:
    """Shard-local MoE dispatch (§Perf optimization, 'moe_impl=local').

    The baseline `moe_ffn` gathers tokens by data-dependent slot indices;
    under SPMD the partitioner cannot prove the gather is shard-local, so
    it ALL-GATHERS the full (T, D) token buffer per layer per direction
    (measured: ~0.5 GB/layer at mixtral train_4k — the dominant collective
    of every MoE cell). Here the dispatch/combine runs inside shard_map
    over the data axes: every token is slotted into ITS OWN shard's
    capacity buffers, so no token ever crosses the network. Expert weights
    arrive TP-sharded on the ff dim (one FSDP all-gather per matrix, ~58MB
    — 9x less wire than the token gather) and the down-projection's
    contraction over ff is completed with a single psum over "model".

    Trade-off vs the baseline: capacity is enforced per shard (drops
    depend on the local token mix, like per-worker capacity in production
    EP systems); routing weights are identical.
    """
    from jax.sharding import PartitionSpec as P

    from repro.models import layers as L

    mesh = L._ACTIVE_MESH
    if mesh is None:  # no mesh (CPU smoke) -> identical math, one shard
        return moe_ffn(
            params, x,
            num_experts=num_experts, top_k=top_k, capacity_factor=capacity_factor,
        )
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = "model" if "model" in mesh.axis_names else None

    def inner(router, wg, wu, wd, xl):
        out, aux = _moe_core(
            {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd},
            xl,
            num_experts=num_experts,
            top_k=top_k,
            capacity_factor=capacity_factor,
        )
        if tp is not None:
            out = jax.lax.psum(out, tp)  # complete the ff contraction
        if dp:
            aux = {k: jax.lax.pmean(v, dp) for k, v in aux.items()}
        return out, aux

    b = x.shape[0]
    dp_ok = dp and b % int(np.prod([mesh.shape[a] for a in dp])) == 0
    x_spec = P(dp if dp_ok else None, None, None)
    fspec = P(None, None, tp)  # (E, D, F) — ff TP-sharded, D replicated
    dspec = P(None, tp, None)  # (E, F, D)
    out_specs = (x_spec, {k: P() for k in ("load_balance_loss", "router_z_loss", "drop_frac")})
    fn = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(None, None), fspec, fspec, dspec, x_spec),
        out_specs=out_specs,
        check_vma=False,
    )
    return fn(params["router"], params["w_gate"], params["w_up"], params["w_down"], x)


def _moe_core(params, x, *, num_experts, top_k, capacity_factor):
    """The dispatch/compute/combine body shared by local mode.

    Identical math to moe_ffn but with the down-projection left PARTIAL
    over the ff dimension (caller completes it with psum when TP-sharded)
    and sharding constraints disabled (we are inside a manual region).
    """
    from repro.models.layers import manual_mode

    with manual_mode():
        return moe_ffn(
            params, x,
            num_experts=num_experts, top_k=top_k, capacity_factor=capacity_factor,
        )
