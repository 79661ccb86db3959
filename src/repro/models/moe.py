"""Mixture-of-Experts: token-choice top-k routing with sort-based dispatch.

Dispatch is the static-shape sort algorithm (no [T, E, C] one-hot blow-up):
flatten (token, expert) assignments, stable-sort by expert, rank within each
expert group via searchsorted, drop tokens beyond capacity, scatter into a
[E, capacity, d] buffer, grouped-matmul all experts at once (E sharded over
the "model" axis = expert parallelism), and combine with router gates.
Capacity = ceil(T * k / E * capacity_factor) — standard token dropping.

Aux load-balance loss (Switch-style) is returned for the train loss.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import act_fn, dense_init
from repro.dist.sharding import shard

__all__ = ["init_moe", "moe_block"]


def init_moe(key, cfg, dtype) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    down_scale = 1.0 / jnp.sqrt(f * 2.0 * max(cfg.n_layers, 1))
    p = {
        "router": {"w": dense_init(ks[0], (d, E), scale=0.02, dtype=jnp.float32)},
        "experts": {
            "w_gate": dense_init(ks[1], (E, d, f), dtype=dtype),
            "w_up": dense_init(ks[2], (E, d, f), dtype=dtype),
            "w_down": dense_init(ks[3], (E, f, d), scale=down_scale, dtype=dtype),
        },
    }
    if cfg.shared_expert:
        from repro.models.mlp import init_mlp

        p["shared"] = init_mlp(ks[4], cfg, dtype)
    return p


def moe_block(p: dict, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Returns (output [B,S,d], aux_loss scalar).

    Under an active mesh with a "model" axis, dispatch/combine run in a
    manual shard_map with explicit `lax.all_to_all` exchanges (the production
    EP pattern — GSPMD cannot turn data-dependent gathers into all-to-alls and
    falls back to full all-gathers, measured 10-60x more collective bytes).
    Otherwise the pure-GSPMD path below runs (single device, smoke tests).
    """
    from repro.dist.sharding import current_mesh, current_rules

    mesh = current_mesh()
    if (mesh is not None and "model" in mesh.axis_names
            and cfg.n_experts % _axis_len(mesh, "model") == 0
            and _axis_len(mesh, "model") > 1
            and x.shape[1] % _axis_len(mesh, "model") == 0):
        return _moe_block_manual(p, x, cfg, mesh)
    return _moe_block_auto(p, x, cfg)


def _axis_len(mesh, name):
    return mesh.devices.shape[mesh.axis_names.index(name)]


def _moe_block_auto(p: dict, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, d)

    logits = (xt.astype(jnp.float32)) @ p["router"]["w"]          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)               # [T, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- flatten assignments and sort by expert --------------------------
    Tk = T * k
    flat_expert = expert_idx.reshape(Tk)
    flat_gate = gate_vals.reshape(Tk)
    flat_token = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]

    capacity = max(8, int(round(T * k * cfg.capacity_factor / E + 0.5)))
    # rank within expert group (first-occurrence trick on the sorted array)
    first = jnp.searchsorted(sorted_expert, sorted_expert, side="left")
    rank = jnp.arange(Tk) - first
    keep = rank < capacity
    dest = jnp.where(keep, sorted_expert * capacity + rank, E * capacity)

    # ---- dispatch (gather-only; §Perf 'moe gather dispatch') ---------------
    # d-wide data moves are expressed exclusively as jnp.take gathers; the
    # only scatters touch int32 slot maps (no trailing d width), which GSPMD
    # SPMD-ifies without materializing [T*k, d]-wide index tensors.
    token_for_slot = jnp.full((E * capacity,), -1, jnp.int32)
    token_for_slot = token_for_slot.at[dest].set(sorted_token.astype(jnp.int32),
                                                 mode="drop")
    slot_valid = token_for_slot >= 0
    hidden_flat = jnp.take(xt, jnp.maximum(token_for_slot, 0), axis=0)
    hidden_flat = jnp.where(slot_valid[:, None], hidden_flat, 0)
    hidden_flat = shard(hidden_flat, ("expert_cap", "embed"))
    hidden_in = hidden_flat.reshape(E, capacity, d)
    hidden_in = shard(hidden_in, ("experts", "batch", "embed"))

    # ---- grouped expert matmuls (E on the "model" axis = EP) --------------
    act = act_fn(cfg.act)
    w = p["experts"]
    h = act(jnp.einsum("ecd,edf->ecf", hidden_in, w["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", hidden_in, w["w_up"])
    h = shard(h, ("experts", "batch", None))   # e on model, capacity on dp
    y = jnp.einsum("ecf,efd->ecd", h, w["w_down"])
    y = shard(y, ("experts", "batch", "embed"))

    # ---- combine back to tokens (gather-only) ------------------------------
    src = shard(y.reshape(E * capacity, d), ("expert_cap", "embed"))
    # slot index for each (token, k) assignment, in token order: invert the
    # sort with a gather (inverse permutation), not a scatter.
    inv_order = jnp.argsort(order, stable=True)
    slot_token_order = jnp.where(keep, dest, E * capacity)[inv_order]   # [Tk]
    took = jnp.take(src, jnp.minimum(slot_token_order, E * capacity - 1), axis=0)
    took = jnp.where((slot_token_order < E * capacity)[:, None], took, 0)
    took = shard(took, ("tokens", "embed"))
    # combine in bf16: the [T*k, d] gathers (and their scatter-add cotangents)
    # are the dominant collective payload — f32 here doubles DCN/ICI bytes.
    contrib = took * flat_gate[:, None].astype(took.dtype)
    out = contrib.reshape(T, k, d).sum(axis=1)
    out = shard(out, ("tokens", "embed"))

    if "shared" in p:
        from repro.models.mlp import mlp_block

        out = out + mlp_block(p["shared"], x, cfg).reshape(T, d).astype(out.dtype)

    # ---- Switch-style load-balance aux loss -------------------------------
    me = probs.mean(axis=0)                                        # [E] router mass
    ce = jnp.zeros((E,), jnp.float32).at[flat_expert].add(1.0) / Tk
    aux = E * jnp.sum(me * ce)

    return out.reshape(B, S, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Manual expert-parallel path: shard_map + lax.all_to_all
# ---------------------------------------------------------------------------

def _moe_block_manual(p: dict, x: jax.Array, cfg, mesh) -> Tuple[jax.Array, jax.Array]:
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    E, k = cfg.n_experts, cfg.experts_per_token
    d = x.shape[-1]
    tp = _axis_len(mesh, "model")
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in dp_axes:
        dp *= _axis_len(mesh, a)
    all_axes = dp_axes + ("model",)
    E_loc = E // tp
    B, S, _ = x.shape

    act = act_fn(cfg.act)

    def local_moe(xb, wr, wg, wu, wd):
        # xb [B_loc, S_loc, d] local; wr [d, E]; wg/wu [E_loc, d, f]; wd [E_loc, f, d]
        Bl, Sl, _ = xb.shape
        Tl = Bl * Sl
        # per-device capacity from the LOCAL token count (shapes are static)
        cap_loc = max(8, -(-Tl * k * int(round(cfg.capacity_factor * 4)) // (4 * E)))
        xt = xb.reshape(Tl, d)
        logits = xt.astype(jnp.float32) @ wr
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
        Tk = Tl * k
        flat_expert = expert_idx.reshape(Tk)
        flat_gate = gate_vals.reshape(Tk)
        flat_token = jnp.repeat(jnp.arange(Tl), k)
        order = jnp.argsort(flat_expert, stable=True)
        sorted_expert = flat_expert[order]
        sorted_token = flat_token[order]
        first = jnp.searchsorted(sorted_expert, sorted_expert, side="left")
        rank = jnp.arange(Tk) - first
        keep = rank < cap_loc
        dest = jnp.where(keep, sorted_expert * cap_loc + rank, E * cap_loc)

        token_for_slot = jnp.full((E * cap_loc,), -1, jnp.int32)
        token_for_slot = token_for_slot.at[dest].set(
            sorted_token.astype(jnp.int32), mode="drop")
        valid = token_for_slot >= 0
        hidden = jnp.take(xt, jnp.maximum(token_for_slot, 0), axis=0)
        hidden = jnp.where(valid[:, None], hidden, 0)

        # exchange: tokens -> expert owners along the model axis
        send = hidden.reshape(tp, E_loc, cap_loc, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=2,
                                  tiled=True)                  # [E_loc, tp*cap_loc, d]? (tiled)
        recv = recv.reshape(E_loc, tp * cap_loc, d)

        h = act(jnp.einsum("ecd,edf->ecf", recv, wg))
        h = h * jnp.einsum("ecd,edf->ecf", recv, wu)
        y = jnp.einsum("ecf,efd->ecd", h, wd)                  # [E_loc, tp*cap_loc, d]

        # reverse exchange: results back to token owners
        yb = y.reshape(E_loc, tp, cap_loc, d)
        back = jax.lax.all_to_all(yb, "model", split_axis=1, concat_axis=0,
                                  tiled=True)                  # [tp*E_loc, cap_loc, d]
        src = back.reshape(E * cap_loc, d)

        inv_order = jnp.argsort(order, stable=True)
        slot_token_order = jnp.where(keep, dest, E * cap_loc)[inv_order]
        took = jnp.take(src, jnp.minimum(slot_token_order, E * cap_loc - 1), axis=0)
        took = jnp.where((slot_token_order < E * cap_loc)[:, None], took, 0)
        contrib = took * flat_gate[:, None].astype(took.dtype)
        out = contrib.reshape(Tl, k, d).sum(axis=1)

        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[flat_expert].add(1.0) / Tk
        aux = E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, all_axes)
        return out.reshape(Bl, Sl, d).astype(xb.dtype), aux

    # tokens split over BOTH dp (batch) and model (sequence) axes — otherwise
    # every model-peer dispatches the same tokens (tp x duplicated compute
    # and exchange traffic; measured 11x compute regression, see §Perf log).
    batch_spec = P(dp_axes if dp > 1 and B % dp == 0 else None, "model", None)
    fn = shard_map(
        local_moe, mesh=mesh,
        in_specs=(batch_spec, P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )
    out, aux = fn(x, p["router"]["w"], p["experts"]["w_gate"],
                  p["experts"]["w_up"], p["experts"]["w_down"])
    if "shared" in p:
        from repro.models.mlp import mlp_block

        out = out + mlp_block(p["shared"], x, cfg).astype(out.dtype)
    return out, aux
