"""Mixture-of-Experts MLP with expert parallelism over the ``model`` axis.

Routing is computed on model-replicated activations (they are replicated
across the tensor-parallel axis at block boundaries), so dispatch needs NO
all-to-all: each model shard gathers — locally — the tokens routed to ITS
experts, runs the batched expert matmuls, scatters back, and one psum over
``model`` combines expert contributions.  Communication per MoE layer is
exactly one all-reduce of the (N_local, D) output — the same volume as the
dense TP all-reduce it replaces.

Single-device path (CPU tests, pruning engine) is the identical math with
E_local = E and no collectives; capture mode additionally records
per-expert routed activations (x, validity) for the per-expert Hessians
(DESIGN.md §3: experts calibrate on their routed tokens only).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.dist.api import current_ctx
from repro.dist.sharding import moe_dispatch_specs
from repro.models.base import ArchConfig
from repro.models.layers import (Params, _dense_init, mlp_apply,
                                 mlp_init, rmsnorm, rmsnorm_init)


def moe_init(key, cfg: ArchConfig, dtype) -> Params:
    mc = cfg.moe
    d, e, f = cfg.d_model, mc.num_experts, mc.d_ff_expert
    ks = jax.random.split(key, 5)
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(f * 2 * cfg.num_layers)
    p = {
        "ln": rmsnorm_init(d, dtype),
        "router": _dense_init(ks[0], d, e, jnp.float32),  # router stays f32
        "wi": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale_in).astype(dtype),
        "wg": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale_in).astype(dtype),
        "wo": (jax.random.normal(ks[3], (e, f, d), jnp.float32) * scale_out).astype(dtype),
    }
    if mc.num_shared:
        p["shared"] = mlp_init(ks[4], cfg, dtype, d_ff=mc.num_shared * f)
    return p


def _route(x2, router_w, top_k: int):
    """x2: (N, D) → dense renormalized gate matrix (N, E) f32 + aux loss."""
    logits = x2.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)
    topv = topv / jnp.clip(topv.sum(-1, keepdims=True), 1e-9)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x2.shape[0])[:, None], topi
    ].set(topv)
    # GShard load-balance loss: E * Σ_e mean(probs_e) * frac_tokens_e
    e = probs.shape[-1]
    frac = jnp.mean((gates > 0).astype(jnp.float32), axis=0)
    aux = e * jnp.sum(jnp.mean(probs, axis=0) * frac)
    return gates, aux


def _expert_ffn(xg, wi, wg, wo):
    """xg: (E, C, D) routed tokens → (E, C, D) expert outputs (swiglu)."""
    up = jnp.einsum("ecd,edf->ecf", xg, wi.astype(xg.dtype))
    gate = jnp.einsum("ecd,edf->ecf", xg, wg.astype(xg.dtype))
    hid = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", hid, wo.astype(xg.dtype)), hid


def _gather_compute_scatter(x2, gates_loc, wi, wg, wo, capacity, caps, prefix,
                            expert_offset=0):
    """Local dispatch: top-C tokens per (local) expert, FFN, scatter-add."""
    n, d = x2.shape
    c = min(capacity, n)
    gv, gi = jax.lax.top_k(gates_loc.T, c)          # (E_loc, C) gates/indices
    valid = gv > 0.0
    xg = x2[gi]                                      # (E_loc, C, D)
    yo, hid = _expert_ffn(xg, wi, wg, wo)
    if caps is not None:
        e_loc = xg.shape[0]
        for e in range(e_loc):
            caps[f"{prefix}wi.{expert_offset + e}"] = (xg[e], valid[e])
            caps[f"{prefix}wg.{expert_offset + e}"] = (xg[e], valid[e])
            caps[f"{prefix}wo.{expert_offset + e}"] = (hid[e], valid[e])
    yo = yo * jnp.where(valid, gv, 0.0)[..., None].astype(yo.dtype)
    out = jnp.zeros((n, d), yo.dtype).at[gi.reshape(-1)].add(
        yo.reshape(-1, d))
    return out


# §Perf (serving): bypass the shard_map expert-parallel dispatch and let
# GSPMD partition the expert einsums directly — required when expert
# weights are 2-D sharded (experts × model, d_ff × data) so trillion-
# param MoEs fit resident at serve time (kimi: 131GB/chip at EP=16 →
# 8.2GB/chip at 16×16). shard_map's in_specs pin a 1-D expert layout and
# would re-gather 2-D-sharded weights every step.
FORCE_PLAIN_GSPMD = False


def moe_apply(p: Params, h: jax.Array, cfg: ArchConfig, *,
              caps=None, prefix: str = "moe.") -> Tuple[jax.Array, jax.Array]:
    """Returns (h + moe_out, aux_loss)."""
    mc = cfg.moe
    b, t, d = h.shape
    h_in = rmsnorm(p["ln"], h, cfg.norm_eps)
    if caps is not None:
        caps[f"{prefix}router"] = h_in
    x2 = h_in.reshape(-1, d)
    n = x2.shape[0]
    gates, aux = _route(x2, p["router"], mc.top_k)

    ctx = current_ctx()
    use_shard_map = (ctx is not None and ctx.tp > 1
                     and not FORCE_PLAIN_GSPMD
                     and n % ctx.dp == 0          # tokens split over data
                     and mc.num_experts % ctx.tp == 0)
    if use_shard_map:
        tp, tpax = ctx.tp, ctx.tp_axis
        n_loc = n // ctx.dp
        cap = max(1, int(math.ceil(n_loc * mc.top_k / mc.num_experts
                                   * mc.capacity_factor)))
        e_loc = mc.num_experts // tp

        def body(x2s, gs, wi, wg, wo):
            eidx = jax.lax.axis_index(tpax)
            g_loc = jax.lax.dynamic_slice(
                gs, (0, eidx * e_loc), (x2s.shape[0], e_loc))
            out = _gather_compute_scatter(
                x2s, g_loc, wi, wg, wo, cap, None, prefix)
            return jax.lax.psum(out, tpax)

        # specs come from the dist rules layer, built off the context —
        # no ad-hoc PartitionSpec construction here (docs/dist_api.md)
        in_specs, out_specs = moe_dispatch_specs(ctx)
        out2 = shard_map(
            body,
            mesh=ctx.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )(x2, gates, p["wi"], p["wg"], p["wo"])
    else:
        cap = max(1, int(math.ceil(n * mc.top_k / mc.num_experts
                                   * mc.capacity_factor)))
        out2 = _gather_compute_scatter(
            x2, gates, p["wi"], p["wg"], p["wo"], cap, caps, prefix)

    y = out2.reshape(b, t, d).astype(h.dtype)
    if mc.num_shared:
        # shared expert: plain dense MLP on the same normed input; reuse
        # mlp_apply's residual by passing h and letting it add.
        y = y + (mlp_apply(p["shared"], h, cfg, caps=caps,
                           prefix=f"{prefix}shared.") - h)
    return h + y, aux
