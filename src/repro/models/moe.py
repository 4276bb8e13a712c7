"""Token-choice Mixture-of-Experts with capacity-based dispatch.

Design notes (TPU adaptation):
  * All three assigned MoE archs have exactly 16 experts, matching the
    16-way ``model`` mesh axis -> expert parallelism maps 1 expert : 1 model
    group; dispatch becomes an all-to-all under GSPMD.
  * Dispatch avoids the O(T*E*C) one-hot einsum used by older JAX MoE code:
    we argsort token->expert assignments, compute each token's rank within its
    expert, and scatter into an (E, C, d) buffer — memory O(T*topk*d).
  * Tokens over capacity are dropped (standard capacity-factor semantics);
    the router aux loss (load-balance, Switch-style) keeps drop rates low.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import _dense_init, mlp


def moe_apply(params: dict, x: jax.Array, **kw) -> Tuple[jax.Array, jax.Array]:
    """Dispatcher: shard_map expert-parallel path when the launcher installed
    a mesh (production), scatter path otherwise (CPU tests, decode)."""
    from repro.sharding.context import get_moe_specs

    specs = get_moe_specs()
    if specs and specs.get("impl") == "alltoall":
        return moe_ffn_alltoall(params, x, mesh=specs["mesh"],
                                data_axes=specs["data_axes"], **kw)
    if specs and specs.get("impl") == "shardmap":
        return moe_ffn_shardmap(params, x, mesh=specs["mesh"],
                                data_axes=specs["data_axes"], **kw)
    return moe_ffn(params, x, **kw)


def init_moe(key, d_model: int, d_ff: int, num_experts: int, gated: bool,
             shared_expert: bool, dtype) -> dict:
    ks = jax.random.split(key, 5)
    n_mats = 3 if gated else 2
    p = {
        "router": _dense_init(ks[0], (d_model, num_experts), jnp.float32, scale=0.02),
        "w_in": _dense_init(ks[1], (num_experts, d_model, d_ff), dtype),
        "w_out": _dense_init(ks[2], (num_experts, d_ff, d_model), dtype),
    }
    if gated:
        p["w_gate"] = _dense_init(ks[3], (num_experts, d_model, d_ff), dtype)
    if shared_expert:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(ks[4], d_model, d_ff, gated, dtype)
    return p


def _expert_ffn(p: dict, x: jax.Array, act: str, gated: bool) -> jax.Array:
    """x: (E, C, d) -> (E, C, d); batched over experts."""
    h = jnp.einsum("ecd,edf->ecf", x, p["w_in"])
    if gated:
        g = jnp.einsum("ecd,edf->ecf", x, p["w_gate"])
        h = jax.nn.silu(g) * h if act == "silu" else jax.nn.gelu(g) * h
    else:
        h = jnp.square(jax.nn.relu(h)) if act == "relu2" else jax.nn.silu(h)
    return jnp.einsum("ecf,efd->ecd", h, p["w_out"])


def moe_ffn(params: dict, x: jax.Array, *, num_experts: int, top_k: int,
            capacity_factor: float, act: str, gated: bool,
            shared_expert: bool, no_drop: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d). Returns (output, aux_loss).

    ``no_drop=True`` sets per-expert capacity to T so no token can be dropped
    (used at decode time, where T is small and drops would make decode diverge
    from teacher forcing)."""
    from repro.sharding.context import constrain_moe

    B, S, d = x.shape
    T = B * S
    E, K = num_experts, top_k
    xt = constrain_moe("tokens", x.reshape(T, d))

    logits = (xt.astype(jnp.float32)) @ params["router"]          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_i = jax.lax.top_k(probs, K)                      # (T, K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance aux loss (Switch): E * sum_e frac_tokens_e * frac_prob_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[gate_i.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    # ---- capacity + rank-within-expert via sorted assignment
    C = T if no_drop else max(1, int(T * K * capacity_factor / E))
    flat_e = gate_i.reshape(-1)                                   # (T*K,)
    sort_idx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_idx]
    # rank of each sorted element within its expert run
    first_pos = jnp.searchsorted(sorted_e, jnp.arange(E))         # (E,)
    rank_sorted = jnp.arange(T * K) - first_pos[sorted_e]
    rank = jnp.zeros((T * K,), jnp.int32).at[sort_idx].set(rank_sorted.astype(jnp.int32))

    keep = rank < C                                               # (T*K,)
    slot = flat_e * C + jnp.minimum(rank, C - 1)                  # (T*K,)

    token_of = jnp.repeat(jnp.arange(T), K)
    expanded = constrain_moe("expanded", xt[token_of])            # (T*K, d)
    buf = jnp.zeros((E * C, d), x.dtype)
    buf = buf.at[jnp.where(keep, slot, E * C)].add(expanded, mode="drop")
    buf = constrain_moe("buf", buf.reshape(E, C, d))

    out_buf = constrain_moe("buf", _expert_ffn(params, buf, act, gated)).reshape(E * C, d)

    gathered = out_buf[slot] * keep[:, None].astype(x.dtype)      # (T*K, d)
    gathered = constrain_moe("expanded", gathered)
    w = gate_w.reshape(-1)[:, None].astype(x.dtype)
    combined = jnp.zeros((T, d), x.dtype).at[token_of].add(gathered * w)
    combined = constrain_moe("tokens", combined)

    if shared_expert:
        combined = combined + mlp(params["shared"], xt, act=act, gated=gated)
    return combined.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# all-to-all expert-parallel MoE (§Perf B.2: the communication-optimal path).
#
# The shardmap path below replicates every token across the model axis (entry
# all-gather ~ T_loc * d bytes/device).  This path keeps tokens d-SHARDED the
# whole way: routing runs on a psum'd (T,E) logit (tiny), then only the
# *routed* rows travel — two all-to-alls moving ~ T_loc*K*cf*d / n_model
# bytes each, an E/(K*cf) ~ 13x reduction for top-1 routing.
# Requires deterministic routing (identical on every model shard, which holds:
# all shards compute the same psum'd logits).
# ---------------------------------------------------------------------------
def moe_ffn_alltoall(params: dict, x: jax.Array, *, num_experts: int, top_k: int,
                     capacity_factor: float, act: str, gated: bool,
                     shared_expert: bool, mesh, data_axes,
                     model_axis: str = "model") -> Tuple[jax.Array, jax.Array]:
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, K = num_experts, top_k
    n_model = mesh.shape[model_axis]
    assert E % n_model == 0, (E, n_model)
    e_per = E // n_model

    dax = data_axes if isinstance(data_axes, tuple) else (data_axes,)
    dspec = dax if len(dax) > 1 else dax[0]

    def local_fn(xt_sh, router, w_in, w_gate, w_out):
        # xt_sh: (T_loc, dsh) my d-slice of the local tokens
        T_loc, dsh = xt_sh.shape
        C = max(1, int(T_loc * K * capacity_factor / E))
        mid = jax.lax.axis_index(model_axis)

        # ---- routing from sharded activations: psum of partial logits
        router_loc = jax.lax.dynamic_slice_in_dim(router, mid * dsh, dsh, 0)
        logits = jax.lax.psum(
            xt_sh.astype(jnp.float32) @ router_loc, model_axis)   # (T_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_i = jax.lax.top_k(probs, K)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[gate_i.reshape(-1)].add(1.0) / (T_loc * K)
        aux = E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, dspec)

        flat_e = gate_i.reshape(-1)
        sort_idx = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[sort_idx]
        first_pos = jnp.searchsorted(sorted_e, jnp.arange(E))
        rank_sorted = jnp.arange(T_loc * K) - first_pos[sorted_e]
        rank = jnp.zeros((T_loc * K,), jnp.int32).at[sort_idx].set(
            rank_sorted.astype(jnp.int32))
        keep = rank < C
        token_of = jnp.repeat(jnp.arange(T_loc), K)

        # ---- dispatch: my d-slice of every routed row, bucketed by expert
        bufs = []
        for e_id in range(E):
            mine = keep & (flat_e == e_id)
            slot = jnp.where(mine, rank, C)
            buf = jnp.zeros((C + 1, dsh), xt_sh.dtype)
            buf = buf.at[slot].add(jnp.where(mine[:, None], xt_sh[token_of], 0))
            bufs.append(buf[:C])
        send = jnp.stack(bufs).reshape(n_model, e_per * C, dsh)
        recv = jax.lax.all_to_all(send, model_axis, 0, 0, tiled=False)
        # recv[j] = d-slice j of my experts' rows -> assemble full-d rows
        full = recv.transpose(1, 0, 2).reshape(e_per, C, n_model * dsh)

        # ---- expert FFN on my experts (full d)
        h = jnp.einsum("ecd,edf->ecf", full, w_in)
        if gated:
            g = jnp.einsum("ecd,edf->ecf", full, w_gate)
            h = (jax.nn.silu(g) * h) if act == "silu" else (jax.nn.gelu(g) * h)
        else:
            h = jnp.square(jax.nn.relu(h)) if act == "relu2" else jax.nn.silu(h)
        y = jnp.einsum("ecf,efd->ecd", h, w_out)                  # (e_per, C, d)

        # ---- return: ship each source shard its d-slice of the outputs
        yb = y.reshape(e_per * C, n_model, dsh).transpose(1, 0, 2)
        back = jax.lax.all_to_all(yb, model_axis, 0, 0, tiled=False)
        # back[m] = my d-slice of shard m's experts' outputs (e_per*C, dsh)
        back = back.reshape(E, C, dsh)

        combined = jnp.zeros((T_loc, dsh), jnp.float32)
        wk_all = gate_w.reshape(-1)
        for e_id in range(E):
            mine = keep & (flat_e == e_id)
            contrib = back[e_id][jnp.minimum(rank, C - 1)]        # (T_loc*K, dsh)
            wk = (wk_all * mine)[:, None]
            combined = combined.at[token_of].add(contrib.astype(jnp.float32) * wk)
        return combined.astype(xt_sh.dtype), aux

    local = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dspec, model_axis), P(), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None)),
        out_specs=(P(dspec, model_axis), P()),
        check_vma=False,
    )
    xt = x.reshape(B * S, d)
    w_gate = params.get("w_gate", params["w_in"])
    out, aux = local(xt, params["router"], params["w_in"], w_gate, params["w_out"])
    if shared_expert:
        out = out + mlp(params["shared"], xt, act=act, gated=gated)
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# shard_map expert-parallel MoE (production path).
#
# GSPMD cannot partition the scatter/gather dispatch above (arbitrary index
# vectors force replication of the (T*K, d) carriers — measured 100+ GB/chip
# on dbrx train_4k).  Instead we drop to shard_map: tokens stay sharded over
# the data axes and are replicated over 'model' (the entry all-gather is the
# same collective a dense TP FFN needs anyway); each model shard owns
# E / n_model experts, selects + capacity-ranks its own tokens with LOCAL
# gathers (no SPMD partitioning involved), runs its expert FFN, and the
# per-token combine is a psum over 'model'.  Zero all-to-alls, zero
# partitioned scatters.
# ---------------------------------------------------------------------------
def moe_ffn_shardmap(params: dict, x: jax.Array, *, num_experts: int, top_k: int,
                     capacity_factor: float, act: str, gated: bool,
                     shared_expert: bool, mesh, data_axes,
                     model_axis: str = "model") -> Tuple[jax.Array, jax.Array]:
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, K = num_experts, top_k
    n_model = mesh.shape[model_axis]
    assert E % n_model == 0 or n_model % E == 0, (E, n_model)
    e_per = max(1, E // n_model)

    dax = data_axes if isinstance(data_axes, tuple) else (data_axes,)
    dspec = dax if len(dax) > 1 else dax[0]

    def local_fn(xt, router, w_in, w_gate, w_out):
        # xt: model-replicated (T_loc, d)
        T_loc = xt.shape[0]
        C = max(1, int(T_loc * K * capacity_factor / E))
        logits = xt.astype(jnp.float32) @ router               # (T_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_i = jax.lax.top_k(probs, K)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[gate_i.reshape(-1)].add(1.0) / (T_loc * K)
        aux = E * jnp.sum(me * ce)

        flat_e = gate_i.reshape(-1)                            # (T_loc*K,)
        sort_idx = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[sort_idx]
        first_pos = jnp.searchsorted(sorted_e, jnp.arange(E))
        rank_sorted = jnp.arange(T_loc * K) - first_pos[sorted_e]
        rank = jnp.zeros((T_loc * K,), jnp.int32).at[sort_idx].set(
            rank_sorted.astype(jnp.int32))
        keep = rank < C
        token_of = jnp.repeat(jnp.arange(T_loc), K)

        mid = jax.lax.axis_index(model_axis)
        my_first = mid * e_per
        combined = jnp.zeros((T_loc, d), jnp.float32)
        for j in range(e_per):
            e_id = my_first + j
            mine = keep & (flat_e == e_id)                     # (T_loc*K,)
            slot = jnp.where(mine, rank, C)                    # C = trash slot
            buf = jnp.zeros((C + 1, d), xt.dtype)
            buf = buf.at[slot].add(jnp.where(mine[:, None], xt[token_of], 0))
            h = buf[:C] @ w_in[j]
            if gated:
                g = buf[:C] @ w_gate[j]
                h = (jax.nn.silu(g) * h) if act == "silu" else (jax.nn.gelu(g) * h)
            else:
                h = jnp.square(jax.nn.relu(h)) if act == "relu2" else jax.nn.silu(h)
            y = h @ w_out[j]                                   # (C, d)
            wk = (gate_w.reshape(-1) * mine)[:, None]
            contrib = y[jnp.minimum(rank, C - 1)] * wk         # (T_loc*K, d)
            combined = combined.at[token_of].add(contrib.astype(jnp.float32))
        combined = jax.lax.psum(combined, model_axis)
        # aux is identical across model shards (same routing math) but is a
        # LOCAL-token statistic along the data axes — average it
        aux = jax.lax.pmean(aux, dax if len(dax) > 1 else dax[0])
        return combined.astype(x.dtype), aux

    local = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dspec, None), P(), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None)),
        out_specs=(P(dspec, None), P()),
        check_vma=False,
    )
    xt = x.reshape(B * S, d)
    w_gate = params.get("w_gate", params["w_in"])  # placeholder when ungated
    out, aux = local(xt, params["router"], params["w_in"], w_gate, params["w_out"])
    if shared_expert:
        out = out + mlp(params["shared"], xt, act=act, gated=gated)
    return out.reshape(B, S, d), aux
