"""Mixture-of-Experts layer: top-k routing, two implementations.

1. ``_moe_dense`` — single-device capacity dispatch (scatter/gather).
   Used by smoke tests and as the semantic oracle.
2. ``_moe_shard_map`` — production expert parallelism: experts live on the
   'model' mesh axis; tokens are bucketed per destination shard locally,
   exchanged with ONE tiled all-to-all, processed by the local experts as
   dense [E_local, tokens, d] einsums (MXU-friendly), and returned with a
   second all-to-all.  No scatter crosses a shard boundary, so SPMD never
   falls back to replication — this is the fix for the 2470× FLOP blow-up
   the naive global-scatter version showed in the dry-run (see
   EXPERIMENTS.md §Perf hillclimb #1).

Position-within-expert uses argsort + searchsorted (O(n log n)) instead of
a one-hot cumsum — XLA lowers big cumsums to O(n²) reduce-windows.

Routed-expert counts that don't divide the EP degree are padded
(``n_experts_padded``) with dead experts; the router never selects them.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ModelConfig, ParamSpec, RunConfig, spec
from .layers import mlp, mlp_specs


def moe_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    e = cfg.n_experts_padded or cfg.n_experts
    s: Dict[str, ParamSpec] = {
        "router": spec((cfg.d_model, e), ("embed", "experts")),
        "w_gate": spec((e, cfg.d_model, cfg.d_ff), ("experts", "embed", "expert_ffn")),
        "w_up": spec((e, cfg.d_model, cfg.d_ff), ("experts", "embed", "expert_ffn")),
        "w_down": spec((e, cfg.d_ff, cfg.d_model), ("experts", "expert_ffn", "embed"),
                       init="scaled"),
    }
    if cfg.shared_ff:
        s["shared"] = mlp_specs(cfg, d_ff=cfg.shared_ff)
        s["shared_gate"] = spec((cfg.d_model, 1), ("embed", None))
    return s


def _router(params, xt: jnp.ndarray, cfg: ModelConfig
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """xt: [T, d] → (top_w [T,k] f32 normalized, top_e [T,k] i32)."""
    e_pad = cfg.n_experts_padded or cfg.n_experts
    logits = (xt @ params["router"].astype(xt.dtype)).astype(jnp.float32)
    if e_pad > cfg.n_experts:
        pad_mask = jnp.arange(e_pad) < cfg.n_experts
        logits = jnp.where(pad_mask[None, :], logits, -1e30)
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(gates, cfg.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return top_w, top_e.astype(jnp.int32)


def _positions_within_expert(flat_e: jnp.ndarray, n_experts: int) -> jnp.ndarray:
    """Rank of each slot within its expert bucket, FIFO by slot order.
    argsort+searchsorted: O(n log n), no O(n²) reduce-window."""
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - first.astype(jnp.int32)
    return jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)


def _expert_ffn(buf: jnp.ndarray, w_gate, w_up, w_down) -> jnp.ndarray:
    """buf: [E, C, d] grouped tokens → [E, C, d] (SwiGLU per expert)."""
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down)


# ---------------------------------------------------------------------------
# Single-shard reference path
# ---------------------------------------------------------------------------


def _moe_dense(params, x: jnp.ndarray, cfg: ModelConfig, run: RunConfig,
               capacity_factor: float) -> jnp.ndarray:
    cdt = run.compute_dtype
    B, S, d = x.shape
    T = B * S
    e_pad = cfg.n_experts_padded or cfg.n_experts
    k = cfg.top_k
    xt = x.reshape(T, d)
    top_w, top_e = _router(params, xt, cfg)

    capacity = max(int(math.ceil(T * k / e_pad * capacity_factor)), 8)
    flat_e = top_e.reshape(-1)
    pos = _positions_within_expert(flat_e, e_pad)
    keep = pos < capacity

    idx = flat_e * capacity + jnp.minimum(pos, capacity - 1)
    src = (jnp.repeat(xt, k, axis=0)
           * keep[:, None].astype(xt.dtype)).astype(cdt)
    buf = jnp.zeros((e_pad * capacity, d), cdt).at[idx].add(src)

    yb = _expert_ffn(buf.reshape(e_pad, capacity, d),
                     params["w_gate"].astype(cdt),
                     params["w_up"].astype(cdt),
                     params["w_down"].astype(cdt)).reshape(e_pad * capacity, d)
    out_k = yb[idx].reshape(T, k, d)
    w = (top_w * keep.reshape(T, k)).astype(cdt)
    return jnp.einsum("tkd,tk->td", out_k, w).reshape(B, S, d)


# ---------------------------------------------------------------------------
# shard_map expert-parallel path
# ---------------------------------------------------------------------------


def _moe_shard_map(params, x: jnp.ndarray, cfg: ModelConfig, run: RunConfig,
                   capacity_factor: float, mesh, batch_axes,
                   seq_axis: Optional[str]) -> jnp.ndarray:
    cdt = run.compute_dtype
    e_pad = cfg.n_experts_padded or cfg.n_experts
    k = cfg.top_k
    tp = mesh.shape["model"]
    e_local = e_pad // tp

    def body(router, w_gate, w_up, w_down, x_loc):
        Bl, Sl, d = x_loc.shape
        Tl = Bl * Sl
        xt = x_loc.reshape(Tl, d)
        top_w, top_e = _router({"router": router}, xt, cfg)

        cap = max(int(math.ceil(Tl * k / e_pad * capacity_factor)), 4)
        flat_e = top_e.reshape(-1)                      # [Tl*k]
        pos = _positions_within_expert(flat_e, e_pad)
        keep = pos < cap
        # destination shard + local expert id
        dst = flat_e // e_local
        loc = flat_e % e_local
        idx = (dst * e_local + loc) * cap + jnp.minimum(pos, cap - 1)

        src = (jnp.repeat(xt, k, axis=0)
               * keep[:, None].astype(xt.dtype)).astype(cdt)
        send = jnp.zeros((tp * e_local * cap, d), cdt).at[idx].add(src)
        send = send.reshape(tp, e_local * cap, d)
        # ONE tiled all-to-all: row j goes to model-shard j.
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=True)
        # my experts' tokens from every source: [tp*cap per expert]
        grouped = recv.reshape(tp, e_local, cap, d).transpose(1, 0, 2, 3)
        grouped = grouped.reshape(e_local, tp * cap, d)
        y = _expert_ffn(grouped, w_gate, w_up, w_down)
        y = y.reshape(e_local, tp, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(y.reshape(tp, e_local * cap, d), "model",
                                  split_axis=0, concat_axis=0, tiled=True)
        out_k = back.reshape(tp * e_local * cap, d)[idx]
        w = (top_w * keep.reshape(Tl, k)).astype(cdt)
        y_tok = jnp.einsum("tkd,tk->td", out_k.reshape(Tl, k, d), w)
        return y_tok.reshape(Bl, Sl, d)

    xspec = P(batch_axes, seq_axis, None)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P("model", None, None), P("model", None, None),
                  P("model", None, None), xspec),
        out_specs=xspec,
        check_vma=False,
    )(params["router"].astype(cdt), params["w_gate"].astype(cdt),
      params["w_up"].astype(cdt), params["w_down"].astype(cdt), x)
    return out


def moe(params: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: ModelConfig,
        run: RunConfig, capacity_factor: Optional[float] = None) -> jnp.ndarray:
    """x: [B, S, d] → [B, S, d].  Dispatches to the shard_map EP path when
    a mesh with a 'model' axis is in scope and shapes divide; otherwise
    the dense single-shard path (same semantics up to capacity grouping).
    """
    from ..parallel import ctx
    if capacity_factor is None:
        capacity_factor = getattr(run, "moe_capacity", 1.25)
    cdt = run.compute_dtype
    B, S, d = x.shape
    scope = ctx.current()
    y = None
    if scope is not None:
        mesh, rules = scope
        e_pad = cfg.n_experts_padded or cfg.n_experts
        tp = mesh.shape.get("model", 1)
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dp = 1
        for a in data_axes:
            dp *= mesh.shape[a]
        if tp > 1 and e_pad % tp == 0 and B % dp == 0:
            seq_axis = "model" if (rules.get("seq_act") == "model"
                                   and S % tp == 0) else None
            y = _moe_shard_map(params, x, cfg, run, capacity_factor,
                               mesh, data_axes, seq_axis)
    if y is None:
        y = _moe_dense(params, x, cfg, run, capacity_factor)

    if cfg.shared_ff:
        xt = x.reshape(B * S, d)
        sg = jax.nn.sigmoid((xt @ params["shared_gate"].astype(cdt))
                            .astype(jnp.float32)).astype(cdt)
        y = y + (mlp(params["shared"], xt, run) * sg).reshape(B, S, d)
    return y


def moe_load_balance_loss(params, x: jnp.ndarray, cfg: ModelConfig,
                          run: RunConfig) -> jnp.ndarray:
    """Auxiliary load-balancing loss (Switch-style fraction·prob)."""
    cdt = run.compute_dtype
    T = x.shape[0] * x.shape[1]
    e_pad = cfg.n_experts_padded or cfg.n_experts
    xt = x.reshape(T, -1)
    logits = (xt @ params["router"].astype(cdt)).astype(jnp.float32)
    if e_pad > cfg.n_experts:
        pad_mask = jnp.arange(e_pad) < cfg.n_experts
        logits = jnp.where(pad_mask[None, :], logits, -1e30)
    gates = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(gates, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, e_pad, dtype=jnp.float32), axis=0)
    prob = jnp.mean(gates, axis=0)
    return cfg.n_experts * jnp.sum(frac * prob)
