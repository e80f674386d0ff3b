"""Token-choice top-k MoE with capacity-based dispatch.

Dispatch is done per example (vmap over batch) with a static capacity
C = ceil(S * top_k * capacity_factor / E), scatter into an (E, C, d)
buffer, batched expert SwiGLU matmuls (EP-sharded over the 'model' axis),
and gather-combine.  Overflow tokens are dropped (standard capacity MoE).
FLOPs scale with E*C ≈ top_k*S*capacity_factor — i.e. with *active*
parameters, which is what the roofline MODEL_FLOPS ratio checks.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.distributed.meshes import constrain
from repro.models.params import P


def moe_specs(cfg):
    e, d = cfg.moe, cfg.d_model
    # expert weight dims use their own logical axes ('expert_embed' stays
    # unsharded; 'expert_mlp' -> data) so dense-FSDP 'embed' rules never
    # shard expert tensors — the EP body keeps weights stationary and
    # moves activations instead (§Perf change 9)
    s = {
        "router": P((d, e.num_experts), ("embed", None), scale=0.02),
        "wi": P((e.num_experts, d, e.d_expert),
                ("experts", "expert_embed", "expert_mlp")),
        "wg": P((e.num_experts, d, e.d_expert),
                ("experts", "expert_embed", "expert_mlp")),
        "wo": P((e.num_experts, e.d_expert, d),
                ("experts", "expert_mlp", "expert_embed")),
    }
    if e.num_shared:
        f = e.num_shared * e.d_expert
        s["shared_wi"] = P((d, f), ("embed", "mlp"))
        s["shared_wg"] = P((d, f), ("embed", "mlp"))
        s["shared_wo"] = P((f, d), ("mlp", "embed"))
    return s


def capacity(S: int, top_k: int, E: int, factor: float) -> int:
    c = math.ceil(S * top_k * factor / E)
    if S >= 8:
        c = max(8, ((c + 7) // 8) * 8)
    return max(1, c)


def _dispatch_one(x, idx, w, keep, pos, E, C):
    """Per-example scatter.  x: (S,d) idx/w/keep/pos: (S*k,)."""
    S, d = x.shape
    k = idx.shape[0] // S
    xr = jnp.repeat(x, k, axis=0)                                # (S*k, d)
    vals = xr * keep[:, None].astype(x.dtype)
    pos_c = jnp.minimum(pos, C - 1)
    buf = jnp.zeros((E, C, d), x.dtype).at[idx, pos_c].add(vals)
    return buf


def _combine_one(out, idx, w, keep, pos, S, k):
    pos_c = jnp.minimum(pos, out.shape[1] - 1)
    y = out[idx, pos_c]                                          # (S*k, d)
    y = y * (w * keep.astype(w.dtype))[:, None]
    return y.reshape(S, k, -1).sum(axis=1)


def moe_apply(p, x, cfg):
    """x: (B, S, d) -> (B, S, d).  Under an active mesh with a 'model'
    axis that divides the expert count, dispatch runs through the
    shard_map expert-parallel path (explicit all_to_all); otherwise the
    pjit einsum path below."""
    from repro.distributed.meshes import active_mesh
    e = cfg.moe
    mesh = active_mesh()
    if mesh is not None and "model" in mesh.shape:
        m = mesh.shape["model"]
        total = m * mesh.shape.get("data", 1)
        full_ep = e.num_experts % total == 0
        tokens = x.shape[0] * x.shape[1]
        # EP always wins for fine-grained MoE (whole experts per device,
        # zero weight movement) and for low-token serving steps; for
        # small-E training the token gather/psum costs more than the
        # einsum dispatch (measured in §Perf change 9), so fall through.
        if e.num_experts % m == 0 and (full_ep or tokens <= 65_536):
            return moe_apply_ep(p, x, cfg, mesh)
    return _moe_apply_einsum(p, x, cfg)


def _moe_apply_einsum(p, x, cfg):
    e = cfg.moe
    B, S, d = x.shape
    E, k = e.num_experts, e.top_k
    C = capacity(S, k, E, e.capacity_factor)

    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)                             # (B,S,k)
    w = (w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)).astype(x.dtype)

    idx_f = idx.reshape(B, S * k)
    w_f = w.reshape(B, S * k)
    oh = jax.nn.one_hot(idx_f, E, dtype=jnp.int32)               # (B,S*k,E)
    pos_e = jnp.cumsum(oh, axis=1) - oh
    pos = (pos_e * oh).sum(-1)                                   # (B,S*k)
    keep = pos < C

    buf = jax.vmap(_dispatch_one, in_axes=(0, 0, 0, 0, 0, None, None))(
        x, idx_f, w_f, keep, pos, E, C)                          # (B,E,C,d)
    buf = constrain(buf, "batch", "experts", None, None)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, p["wi"]))
    h = h * jnp.einsum("becd,edf->becf", buf, p["wg"])
    h = constrain(h, "batch", "experts", None, "expert_mlp")
    out = jnp.einsum("becf,efd->becd", h, p["wo"])
    out = constrain(out, "batch", "experts", None, None)
    y = jax.vmap(_combine_one, in_axes=(0, 0, 0, 0, 0, None, None))(
        out, idx_f, w_f, keep, pos, S, k)

    if e.num_shared:
        hs = jax.nn.silu(x @ p["shared_wi"]) * (x @ p["shared_wg"])
        y = y + hs @ p["shared_wo"]
    # aux metrics for load-balance loss (computed, cheap, used by train loop)
    me = probs.mean(axis=(0, 1))                                 # (E,)
    ce = (oh.sum(axis=1).astype(jnp.float32) / (S * k)).mean(0)  # (E,)
    aux = E * jnp.sum(me * ce)
    return constrain(y, "batch", "seq", None), aux


# ---------------------------------------------------------------------------
# Expert-parallel path: shard_map + explicit all_to_all (§Perf)
# ---------------------------------------------------------------------------
#
# The einsum/scatter dispatch above leaves GSPMD no way to prove that each
# token only visits top_k experts, so it materialises and ALL-REDUCES the
# full (B,E,C,d) dispatch buffer across the model group (28 GiB per MoE
# layer on deepseek-v3 train_4k).  The textbook fix is explicit expert
# parallelism: tokens stay data-sharded, each model shard owns E/m experts,
# and two all_to_alls move only the routed token activations —
# O(tokens*d) wire bytes instead of O(B*E*C*d).

def _ep_specs(mesh, cfg, S: int, B: int):
    from jax.sharding import PartitionSpec as P
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    prod = 1
    got = []
    for ax in batch_axes:                 # drop axes that don't divide B
        if B % (prod * mesh.shape[ax]) == 0:
            got.append(ax)
            prod *= mesh.shape[ax]
    b = tuple(got) if len(got) > 1 else (got[0] if got else None)
    # shard the token (sequence) dim over 'model' so each shard dispatches
    # a distinct token slice — otherwise expert compute is redundant xm
    m = mesh.shape["model"]
    seq_ax = "model" if S % m == 0 else None
    xs = P(b, seq_ax, None)
    E = cfg.moe.num_experts
    data = mesh.shape.get("data", 1)
    from repro.distributed.meshes import current_rules
    rules = current_rules()
    if E % (m * data) == 0:
        # full-mesh EP: each device owns whole experts — zero weight
        # movement; the all_to_all spans (data, model)
        return xs, P(("data", "model"), None, None), ("data", "model"), "none"
    # experts over model, ffn columns over data ('ff'): weights stay put;
    # tokens are co-located across the expert's data group by an
    # all_gather, partial outputs psum'd, own tokens sliced back
    if "data" in mesh.shape and "data" in (rules.get("expert_mlp") or ()):
        return xs, P("model", None, "data"), ("model",), "ff"
    return xs, P("model", None, None), ("model",), "none"


def moe_apply_ep(p, x, cfg, mesh):
    e = cfg.moe
    B, S, d = x.shape
    E, k = e.num_experts, e.top_k
    m = mesh.shape["model"]
    xs, ws, ep_axes, wshard = _ep_specs(mesh, cfg, S, B)
    full_ep = len(ep_axes) > 1
    from jax.sharding import PartitionSpec as P

    # routing outside the shard_map (small, dense)
    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = (w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)).astype(x.dtype)

    def body(x_loc, w_loc, idx_loc, wi_loc, wg_loc, wo_loc):
        Bl, Sl, dl = x_loc.shape
        T = Bl * Sl
        xf = x_loc.reshape(T, dl)
        idx_f = idx_loc.reshape(T * k)
        w_f = w_loc.reshape(T * k)
        C = capacity(T, k, E, e.capacity_factor)
        oh = jax.nn.one_hot(idx_f, E, dtype=jnp.int32)
        pos = ((jnp.cumsum(oh, axis=0) - oh) * oh).sum(-1)
        keep = pos < C
        pos_c = jnp.minimum(pos, C - 1)
        vals = jnp.repeat(xf, k, axis=0) * keep[:, None].astype(xf.dtype)
        buf = jnp.zeros((E, C, dl), xf.dtype).at[idx_f, pos_c].add(vals)
        # dispatch: every shard sends each expert-group to its owner
        a2a_ax = ep_axes if full_ep else "model"
        buf = jax.lax.all_to_all(buf, a2a_ax, split_axis=0, concat_axis=1,
                                 tiled=True)               # (E/g, C*g, d)
        # expert FFN with stationary weights: activations move, weights
        # don't (§Perf change 9 — replaces in-body FSDP weight gathers)
        if wshard == "ff":
            # each expert's ffn columns are spread over the data axis;
            # co-locate the expert's tokens across that group, compute the
            # local f-slice, psum the d-sized partials, take own slice
            Tl = buf.shape[1]
            buf_g = jax.lax.all_gather(buf, "data", axis=1, tiled=True)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf_g, wi_loc))
            h = h * jnp.einsum("ecd,edf->ecf", buf_g, wg_loc)
            out_g = jax.lax.psum(
                jnp.einsum("ecf,efd->ecd", h, wo_loc), "data")
            di = jax.lax.axis_index("data")
            out = jax.lax.dynamic_slice_in_dim(out_g, di * Tl, Tl, axis=1)
        else:
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wi_loc))
            h = h * jnp.einsum("ecd,edf->ecf", buf, wg_loc)
            out = jnp.einsum("ecf,efd->ecd", h, wo_loc)
        # return trip
        out = jax.lax.all_to_all(out, a2a_ax, split_axis=1, concat_axis=0,
                                 tiled=True)                  # (E, C, d)
        y = out[idx_f, pos_c] * (w_f * keep.astype(w_f.dtype))[:, None]
        return y.reshape(Bl, Sl, k, dl).sum(2)

    if full_ep:
        wo_spec = P(ep_axes, None, None)
    elif wshard == "ff":
        wo_spec = P("model", "data", None)      # f rows sharded
    else:
        wo_spec = P("model", None, None)
    y = jax.shard_map(                    # lint: allow=mesh-guard
        body, mesh=mesh,
        in_specs=(xs, P(xs[0], xs[1], None), P(xs[0], xs[1], None),
                  ws, ws, wo_spec),
        out_specs=xs, check_vma=False,
    )(x, w, idx, p["wi"], p["wg"], p["wo"])

    if e.num_shared:
        hs = jax.nn.silu(x @ p["shared_wi"]) * (x @ p["shared_wg"])
        y = y + hs @ p["shared_wo"]
    oh_g = jax.nn.one_hot(idx.reshape(B, S * k), E, dtype=jnp.float32)
    me = probs.mean(axis=(0, 1))
    ce = (oh_g.sum(axis=1) / (S * k)).mean(0)
    aux = E * jnp.sum(me * ce)
    return constrain(y, "batch", "seq", None), aux
