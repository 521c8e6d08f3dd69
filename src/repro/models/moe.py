"""Mixture-of-Experts layer: top-k routing with sort-based capacity dispatch.

Dispatch strategy (the SPMD-friendly production path, not the one-hot
einsum): token->expert assignments are sorted, each token takes a slot
`(expert, position_in_expert)` capped by capacity; slot->token indices feed
a gather, experts run as a single batched einsum over the expert dim (which
is expert-parallel on the `model` mesh axis), and results scatter-add back
weighted by the router gate.  Tokens beyond capacity are dropped (standard
capacity-factor semantics); the router uses an auxiliary load-balancing
loss (Switch-style) to keep drops rare.

Arctic additionally runs a *dense residual* MLP in parallel with the MoE
(its published topology).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import ParamSpec, Schema
from .mlp import mlp_apply, mlp_schema


def moe_schema(cfg, layers: int | None = None) -> Schema:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    L = (layers,) if layers is not None else ()
    A = ("layers",) if layers is not None else ()
    s: Schema = {
        "router": ParamSpec(L + (d, e), A + ("dmodel", "experts"), "fan_in"),
        "we_gate": ParamSpec(L + (e, d, f), A + ("experts", "dmodel", "ff"), "fan_in"),
        "we_up": ParamSpec(L + (e, d, f), A + ("experts", "dmodel", "ff"), "fan_in"),
        "we_down": ParamSpec(L + (e, f, d), A + ("experts", "ff", "dmodel"), "fan_in"),
    }
    if cfg.moe_dense_residual:
        s.update(mlp_schema(cfg, layers, prefix="res_"))
    return s


def _capacity(cfg, n_tokens: int) -> int:
    cap = int(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(cap, cfg.top_k * 4)


def moe_apply(cfg, p, x):
    """x: (B, S, D) -> (y, aux_loss)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    cap = _capacity(cfg, t)
    xf = x.reshape(t, d)

    # --- routing (fp32) -------------------------------------------------
    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)             # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Switch-style aux loss: fraction-of-tokens x mean router prob per expert
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)

    # --- sort-based slotting --------------------------------------------
    flat_expert = expert_idx.reshape(-1)                         # (T*k,)
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_expert)                             # stable
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    # position of each slot within its expert
    counts = jnp.bincount(se, length=e)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(t * k) - starts[se]
    keep = pos_in_e < cap

    # slot table: (E*cap,) -> source token (or T = dummy)
    slot = se * cap + jnp.where(keep, pos_in_e, 0)
    slot_token = jnp.full((e * cap,), t, jnp.int32)
    slot_token = slot_token.at[jnp.where(keep, slot, e * cap - 1)].set(
        jnp.where(keep, st, t).astype(jnp.int32), mode="drop")
    slot_gate = jnp.zeros((e * cap,), jnp.float32).at[
        jnp.where(keep, slot, 0)].set(jnp.where(keep, sg, 0.0), mode="drop")

    # --- gather -> expert GEMMs -> scatter-add ---------------------------
    xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xe = jnp.take(xpad, slot_token, axis=0).reshape(e, cap, d)
    gate_h = jnp.einsum("ecd,edf->ecf", xe, p["we_gate"])
    up_h = jnp.einsum("ecd,edf->ecf", xe, p["we_up"])
    h = jax.nn.silu(gate_h.astype(jnp.float32)).astype(x.dtype) * up_h
    ye = jnp.einsum("ecf,efd->ecd", h, p["we_down"]).reshape(e * cap, d)
    ye = ye * slot_gate[:, None].astype(ye.dtype)

    y = jnp.zeros((t + 1, d), x.dtype).at[slot_token].add(ye)[:t]
    y = y.reshape(b, s, d)

    if cfg.moe_dense_residual:
        y = y + mlp_apply(cfg, p, x, prefix="res_")
    return y, aux


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map): the beyond-baseline §Perf path
# ---------------------------------------------------------------------------

def moe_apply_ep(cfg, p, x):
    """shard_map expert-parallel MoE.

    The baseline `moe_apply` routes over GLOBAL tokens; under GSPMD the
    slot gather materializes an all-gather of the full token activations
    per layer (~tokens x d_model bytes, the dominant collective of the MoE
    train cells).  This path keeps tokens device-local: local top-k ->
    local capacity slots -> ONE all-to-all over the `model` axis moving
    only the dispatched slots (tokens_loc x top_k x d x cf bytes), expert
    GEMMs against the local expert shard, reverse all-to-all, local
    combine.  Capacity is enforced per (device, expert) — the standard EP
    semantics (local drops instead of global).
    """
    from jax.sharding import PartitionSpec as P

    from ..distributed import context as mesh_ctx

    mesh = mesh_ctx.get_mesh()
    sizes = mesh_ctx.axis_sizes()
    e = cfg.n_experts
    ep = sizes.get("model", 1)
    if mesh is None or e % max(ep, 1) or ep <= 1:
        return moe_apply(cfg, p, x)         # no mesh / indivisible: fallback

    dp = mesh_ctx.dp_axes()
    b, s, d = x.shape
    k = cfg.top_k
    dp_size = 1
    for a in dp:
        dp_size *= sizes[a]
    # tokens must partition across BOTH dp (batch) and model (sequence):
    # with x replicated over `model`, every EP rank would redundantly
    # dispatch the same slots (measured: 16x compute, see §Perf).
    if b % max(dp_size, 1) or s % ep:
        return moe_apply(cfg, p, x)

    x_spec = P(dp if dp else None, "model", None)
    router_spec = P(None, None)
    we_spec = P("model", None, None)        # experts sharded over `model`
    wd_spec = P("model", None, None)

    def local(xl, router, wg, wu, wd, res_w=None):
        bl, sl, _ = xl.shape
        t = bl * sl
        cap = max(int(t * k / e * cfg.capacity_factor), 4 * k)
        xf = xl.reshape(t, d)
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e,
                                     dtype=jnp.float32), axis=0)
        aux = e * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, dp) if dp else aux

        flat_expert = expert_idx.reshape(-1)
        flat_gate = gate_vals.reshape(-1)
        flat_token = jnp.repeat(jnp.arange(t), k)
        order = jnp.argsort(flat_expert)
        se, st_, sg = flat_expert[order], flat_token[order], flat_gate[order]
        counts = jnp.bincount(se, length=e)
        starts = jnp.cumsum(counts) - counts
        pos_in_e = jnp.arange(t * k) - starts[se]
        keep = pos_in_e < cap
        slot = se * cap + jnp.where(keep, pos_in_e, 0)
        slot_token = jnp.full((e * cap,), t, jnp.int32).at[
            jnp.where(keep, slot, e * cap - 1)].set(
                jnp.where(keep, st_, t).astype(jnp.int32), mode="drop")
        slot_gate = jnp.zeros((e * cap,), jnp.float32).at[
            jnp.where(keep, slot, 0)].set(jnp.where(keep, sg, 0.0),
                                          mode="drop")

        xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
        xe = jnp.take(xpad, slot_token, axis=0).reshape(e, cap, d)
        # ---- all-to-all: slots travel to their expert's shard ----------
        xe = jax.lax.all_to_all(xe, "model", split_axis=0, concat_axis=1,
                                tiled=True)            # (e/ep, cap*ep, d)
        gate_h = jnp.einsum("ecd,edf->ecf", xe, wg)
        up_h = jnp.einsum("ecd,edf->ecf", xe, wu)
        hh = jax.nn.silu(gate_h.astype(jnp.float32)).astype(xl.dtype) * up_h
        ye = jnp.einsum("ecf,efd->ecd", hh, wd)
        # ---- reverse all-to-all ----------------------------------------
        ye = jax.lax.all_to_all(ye, "model", split_axis=1, concat_axis=0,
                                tiled=True)            # (e, cap, d)
        ye = ye.reshape(e * cap, d) * slot_gate[:, None].astype(ye.dtype)
        y = jnp.zeros((t + 1, d), xl.dtype).at[slot_token].add(ye)[:t]
        y = y.reshape(bl, sl, d)
        if res_w is not None:
            rg, ru, rd = res_w
            g = xl @ rg
            u = xl @ ru
            hres = jax.nn.silu(g.astype(jnp.float32)).astype(xl.dtype) * u
            y = y + hres @ rd
        return y, aux

    args = [x, p["router"], p["we_gate"], p["we_up"], p["we_down"]]
    in_specs = [x_spec, router_spec, we_spec, we_spec, wd_spec]
    if cfg.moe_dense_residual:
        res = (p["res_w_gate"], p["res_w_up"], p["res_w_down"])
        fn = lambda xl, r, wg, wu, wd, rg, ru, rd: local(
            xl, r, wg, wu, wd, (rg, ru, rd))
        args += list(res)
        in_specs += [P(None, "model"), P(None, "model"), P("model", None)]
    else:
        fn = lambda xl, r, wg, wu, wd: local(xl, r, wg, wu, wd)

    mapped = jax.shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=(x_spec, P()), check_vma=False)
    return mapped(*args)
