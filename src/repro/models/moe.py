"""Mixture-of-Experts FFN: shared experts + routed top-k with capacity.

DeepSeek-MoE / Qwen2-MoE style: ``num_shared`` always-active experts
(fused into one wide FFN) plus ``num_experts`` routed experts with top-k
token-choice routing.

Dispatch is scatter-based (no [T, E, C] one-hot tensor, no global sort):

  1. router logits -> top-k expert ids + softmaxed weights per token;
  2. position-in-expert via a cumsum over the flattened (token, k) choices;
  3. tokens scattered into an [E * C, D] expert buffer (capacity drop);
  4. batched expert FFN as einsum over the [E, C, D] buffer
     (expert dim sharded over the 'model'/'expert' mesh axis = EP);
  5. gather back + weighted combine; dropped tokens contribute zero.

An auxiliary load-balance loss (Switch-style) is returned for training.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig
from repro.models.layers import Params, truncated_normal
from repro.parallel.axes import _ambient_mesh, constrain


def init_moe(key, d: int, f: int, moe: MoEConfig, mlp_type: str) -> Params:
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    E = moe.num_experts
    s_in = d ** -0.5
    s_out = f ** -0.5
    p = {
        "router": truncated_normal(kr, (d, E), s_in),
        "w_gate": truncated_normal(kg, (E, d, f), s_in),
        "w_up": truncated_normal(ku, (E, d, f), s_in),
        "w_down": truncated_normal(kd, (E, f, d), s_out),
    }
    if moe.num_shared:
        from repro.models.layers import init_mlp

        p["shared"] = init_mlp(ks, d, f * moe.num_shared, mlp_type)
    return p


def moe_ffn(
    params: Params,
    x: jnp.ndarray,          # [B, S, D]
    moe: MoEConfig,
    mlp_type: str,
    dropless: bool = False,  # decode: capacity = T (no order-dependent drops)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y [B,S,D], aux_loss scalar)."""
    B, S, D = x.shape
    T = B * S
    E, k = moe.num_experts, moe.top_k
    xt = x.reshape(T, D)

    logits = (xt @ params["router"]).astype(jnp.float32)       # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)            # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )

    # Switch-style aux load-balance loss.
    me = probs.mean(axis=0)                                    # [E]
    ce = jnp.zeros((E,), jnp.float32)
    ce = ce.at[expert_ids.reshape(-1)].add(1.0) / (T * k)
    aux = moe.router_aux_weight * E * jnp.sum(me * ce)

    # Capacity per expert.
    if dropless:
        C = T  # decode-sized batches: never drop
    else:
        C = int(max(1, round(T * k / E * moe.capacity_factor)))

    # Position of each (token, slot) within its expert: cumsum over the
    # flattened choices of per-expert one-hot occupancy.
    flat_ids = expert_ids.reshape(T * k)                       # [T*k]
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)      # [T*k, E]
    onehot = constrain(onehot, "batch", None)                  # rows ~ tokens
    pos_all = jnp.cumsum(onehot, axis=0) - 1                   # exclusive count
    pos = jnp.take_along_axis(pos_all, flat_ids[:, None], axis=1)[:, 0]
    keep = pos < C                                             # capacity drop

    slot = flat_ids * C + jnp.where(keep, pos, 0)              # [T*k]
    token_idx = jnp.repeat(jnp.arange(T), k)

    # Scatter token activations into the expert buffer [E*C, D].
    contrib = jnp.where(keep[:, None], xt[token_idx], 0.0)
    contrib = constrain(contrib, "batch", None)                # [T*k, D]
    buf = jnp.zeros((E * C, D), x.dtype)
    buf = buf.at[jnp.where(keep, slot, E * C)].add(contrib, mode="drop")
    buf = buf.reshape(E, C, D)

    # Shard the dispatch buffer: experts over 'model' (EP) when divisible,
    # capacity over 'data' always -- without this GSPMD replicates the
    # [E, C, D] buffer (90 GiB/device on qwen2-moe prefill_32k; §Perf).
    mesh = _ambient_mesh()
    if mesh is not None:
        e_axis = "model" if ("model" in mesh.axis_names
                             and E % mesh.shape["model"] == 0) else None
        buf = constrain(buf, e_axis, "batch", None)

    # Batched expert FFN (expert axis -> EP sharding).
    act = jax.nn.silu if mlp_type == "swiglu" else jax.nn.gelu
    g = act(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"]))
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    eo = jnp.einsum("ecf,efd->ecd", g * u, params["w_down"])   # [E, C, D]

    # Gather back and combine the k expert outputs per token.
    out_flat = jnp.where(
        keep[:, None], eo.reshape(E * C, D)[slot], 0.0
    )                                                          # [T*k, D]
    out_flat = constrain(out_flat, "batch", None)
    combined = (
        out_flat.reshape(T, k, D) * gate_vals[..., None].astype(x.dtype)
    ).sum(axis=1)

    if "shared" in params:
        from repro.models.layers import mlp

        combined = combined + mlp(params["shared"], xt, mlp_type)
    return combined.reshape(B, S, D), aux


# -- explicit-EP shard_map implementation --------------------------------------
#
# GSPMD's scatter partitioner replicates the [E, C, D] dispatch buffer
# (measured 43 GB f32/device on qwen2 prefill; EXPERIMENTS.md §Perf), so the
# production path dispatches *locally per data shard* under shard_map:
#
#   * routing + scatter run per data shard, replicated over 'model'
#     (identical cheap compute; the scatter is shard-local => no collective);
#   * expert FFN: experts sharded over 'model' when E % |model| == 0
#     (true EP: each rank owns E/|model| experts and masks the rest),
#     otherwise the FFN hidden dim is sharded (F-parallel fallback);
#   * one psum over 'model' combines the partial token outputs.
#
# Collectives per MoE layer: exactly one [T_local, D] all-reduce (+ tiny
# pmeans for the aux loss) -- versus the all-gather storm GSPMD emits.


def _moe_local(
    xt: jnp.ndarray,            # [T_loc, D] this data-shard's tokens
    router: jnp.ndarray,        # [D, E] replicated
    wg: jnp.ndarray,            # [E_loc, D, F] or [E, D, F_loc]
    wu: jnp.ndarray,
    wd: jnp.ndarray,            # [E_loc, F, D] or [E, F_loc, D]
    moe: MoEConfig,
    mlp_type: str,
    ep: bool,                   # True: experts sharded over 'model'
    dropless: bool,
    data_axes: Tuple[str, ...],
):
    T, D = xt.shape
    E, k = moe.num_experts, moe.top_k

    logits = (xt @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    me = jax.lax.pmean(probs.mean(axis=0), data_axes)
    ce_loc = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0) / (T * k)
    ce = jax.lax.pmean(ce_loc, data_axes)
    aux = moe.router_aux_weight * E * jnp.sum(me * ce)

    C = T if dropless else int(max(1, round(T * k / E * moe.capacity_factor)))

    if ep:
        E_loc = wg.shape[0]
        m_idx = jax.lax.axis_index("model")
        local = (expert_ids // E_loc) == m_idx             # my experts only
        eff_ids = jnp.where(local, expert_ids % E_loc, E_loc)  # E_loc = drop
        n_buckets = E_loc
    else:
        local = jnp.ones_like(expert_ids, dtype=bool)
        eff_ids = expert_ids
        n_buckets = E

    flat_ids = eff_ids.reshape(T * k)
    onehot = (flat_ids[:, None] == jnp.arange(n_buckets)[None, :]).astype(jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - 1)
    pos = jnp.take_along_axis(
        pos, jnp.minimum(flat_ids, n_buckets - 1)[:, None], axis=1
    )[:, 0]
    keep = (pos < C) & local.reshape(T * k)

    slot = jnp.where(keep, flat_ids * C + pos, n_buckets * C)
    token_idx = jnp.repeat(jnp.arange(T), k)
    contrib = jnp.where(keep[:, None], xt[token_idx], 0.0)
    buf = jnp.zeros((n_buckets * C, D), xt.dtype)
    buf = buf.at[slot].add(contrib, mode="drop").reshape(n_buckets, C, D)

    act = jax.nn.silu if mlp_type == "swiglu" else jax.nn.gelu
    g = act(jnp.einsum("ecd,edf->ecf", buf, wg))
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    eo = jnp.einsum("ecf,efd->ecd", g * u, wd)              # [buckets, C, D]

    out_flat = jnp.where(keep[:, None], eo.reshape(-1, D)[slot], 0.0)
    combined = (
        out_flat.reshape(T, k, D) * gate_vals[..., None].astype(xt.dtype)
    ).sum(axis=1)
    combined = jax.lax.psum(combined, "model")
    return combined, aux


def moe_ffn_ep(
    params: Params,
    x: jnp.ndarray,
    moe: MoEConfig,
    mlp_type: str,
    dropless: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """shard_map-EP MoE; falls back to `moe_ffn` when no suitable mesh."""
    from jax.sharding import PartitionSpec as P

    mesh = _ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return moe_ffn(params, x, moe, mlp_type, dropless=dropless)
    m = mesh.shape["model"]
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    B, S, D = x.shape
    B_total = int(np.prod([mesh.shape[a] for a in daxes])) if daxes else 1
    if B % B_total != 0:
        return moe_ffn(params, x, moe, mlp_type, dropless=dropless)
    ep = moe.num_experts % m == 0
    F = params["w_gate"].shape[-1]
    if not ep and F % m != 0:
        return moe_ffn(params, x, moe, mlp_type, dropless=dropless)

    batch_spec = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    w_spec = P("model", None, None) if ep else P(None, None, "model")
    wd_spec = P("model", None, None) if ep else P(None, "model", None)

    def per_shard(xb, router, wg, wu, wd):
        T_loc = xb.shape[0] * xb.shape[1]
        y, aux = _moe_local(
            xb.reshape(T_loc, D), router, wg, wu, wd,
            moe, mlp_type, ep, dropless, daxes or ("model",),
        )
        return y.reshape(xb.shape), aux

    smap = functools.partial(jax.shard_map, check_vma=False)
    y, aux = smap(
        per_shard,
        mesh=mesh,
        in_specs=(
            P(batch_spec, None, None),
            P(None, None),
            w_spec, w_spec, wd_spec,
        ),
        out_specs=(P(batch_spec, None, None), P()),
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])

    if "shared" in params:
        from repro.models.layers import mlp

        y = y + mlp(params["shared"], x, mlp_type)
    return y, aux
