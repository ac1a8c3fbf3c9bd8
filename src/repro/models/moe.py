"""Top-k MoE FFN with expert-parallel shard_map dispatch.

Two paths:

``_moe_dense``      mesh-free reference (CPU smoke tests, tiny configs):
                    sort-based capacity dispatch in plain jnp.

``_moe_shard_map``  the production path.  Plain pjit of the dispatch is a
                    data-dependent scatter GSPMD cannot place: the dry-run
                    measured a 454 GB/device temp for olmoe train_4k
                    (EXPERIMENTS.md §Perf).  Instead the token->expert
                    exchange is explicit, the GShard/Switch layout:

      tokens  : sharded over dp = ('pod','data')   — T_loc per device
      experts : sharded over dp                    — E_loc = E/|dp|
      d_ff    : sharded over 'model'               — Megatron within expert

      per device: local router -> local top-k -> local sort -> capacity
      dispatch xe_loc [E, C_loc, d]
      all_to_all(dp)         -> [E_loc, |dp|*C_loc, d]   (the EP exchange)
      gate/up einsum (ff/16 shard) -> silu*up -> down einsum -> psum('model')
      all_to_all(dp) back    -> combine into [T_loc, d]

    This makes the collective term explicit and exactly 2 all-to-alls of
    activation bytes + 1 all-reduce of the down-projection — the numbers
    the §Roofline table reads.  In GraphLab terms the exchange IS the
    ghost synchronization of the bipartite token-expert graph (DESIGN.md
    §4): each expert receives each routed token once.

Capacity: C_loc = ceil(T_loc*k/E * capacity_factor); overflow drops to the
residual path (standard Switch behaviour).  Aux loss: Switch load balance,
pmean'd over dp.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import AxisRules, shard_constraint

Pytree = Any


def moe_ffn(cfg, rules: AxisRules, mesh, x: jnp.ndarray,
            mlp: Pytree) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, d] -> (out [B, S, d], aux loss scalar)."""
    if mesh is None:
        return _moe_dense(cfg, x, mlp)
    return _moe_shard_map(cfg, rules, mesh, x, mlp)


# ---------------------------------------------------------------------------
# local dispatch machinery (shared by both paths)
# ---------------------------------------------------------------------------

def _dispatch(cfg, xt: jnp.ndarray, router_w: jnp.ndarray, capacity: int):
    """Local sort-based capacity dispatch.

    Returns xe [E, C, d], combine info (tok, e, pos, w, keep), aux loss.
    """
    T, d = xt.shape
    E, k = cfg.n_experts, cfg.top_k
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    f = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(f * probs.mean(0))

    flat_e = top_i.reshape(T * k)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    flat_w = top_w.reshape(T * k).astype(cfg.dtype)

    order = jnp.argsort(flat_e, stable=True)
    se, st_tok, sw = flat_e[order], flat_t[order], flat_w[order]
    seg_start = jnp.searchsorted(se, jnp.arange(E, dtype=se.dtype))
    pos = jnp.arange(T * k, dtype=jnp.int32) - seg_start[se].astype(jnp.int32)
    keep = pos < capacity

    e_idx = jnp.where(keep, se, E)  # out-of-capacity -> dropped scatter
    xe = jnp.zeros((E, capacity, d), cfg.dtype)
    xe = xe.at[e_idx, pos].set(xt[st_tok].astype(cfg.dtype), mode="drop")
    return xe, (st_tok, se, pos, sw, keep), aux


def _combine(cfg, ye: jnp.ndarray, info, T: int) -> jnp.ndarray:
    st_tok, se, pos, sw, keep = info
    E, C, d = ye.shape
    y_tok = ye[jnp.minimum(se, E - 1), jnp.minimum(pos, C - 1)]
    y_tok = jnp.where(keep[:, None], y_tok, 0.0)
    return jnp.zeros((T, d), cfg.dtype).at[st_tok].add(sw[:, None] * y_tok)


def _expert_ffn(cfg, xe, w_gate, w_up, w_down):
    g = jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(cfg.dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, w_up.astype(cfg.dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(cfg.dtype))


# ---------------------------------------------------------------------------
# mesh-free reference
# ---------------------------------------------------------------------------

def _moe_dense(cfg, x, mlp):
    B, S, d = x.shape
    T = B * S
    C = int(math.ceil(T * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    xe, info, aux = _dispatch(cfg, x.reshape(T, d), mlp["router"], C)
    ye = _expert_ffn(cfg, xe, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
    return _combine(cfg, ye, info, T).reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# production shard_map path
# ---------------------------------------------------------------------------

def _moe_shard_map(cfg, rules, mesh, x, mlp):
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    has_tp = "model" in mesh.shape and cfg.d_ff % mesh.shape["model"] == 0

    B, S, d = x.shape
    T = B * S
    E = cfg.n_experts
    if T % n_dp != 0 or E % n_dp != 0:
        return _moe_dense(cfg, x, mlp)  # mesh incompatible: reference path
    T_loc = T // n_dp
    C_loc = int(math.ceil(
        T_loc * cfg.top_k / cfg.n_experts * cfg.capacity_factor))

    tp_spec = "model" if has_tp else None
    # token chunks bound the xe blowup (xe is top_k*cf times the tokens)
    n_chunks = cfg.moe_token_chunks if T_loc % max(
        cfg.moe_token_chunks, 1) == 0 else 1
    T_chunk = T_loc // max(n_chunks, 1)
    C_chunk = int(math.ceil(
        T_chunk * cfg.top_k / cfg.n_experts * cfg.capacity_factor))

    def one_chunk(xt_chunk, router_w, w_gate, w_up, w_down):
        xe, info, aux = _dispatch(cfg, xt_chunk, router_w, C_chunk)
        # EP exchange: expert dim scattered over dp, capacity gathered
        xe = jax.lax.all_to_all(xe, dp, split_axis=0, concat_axis=1,
                                tiled=True)        # [E_loc, n_dp*C, d]
        ye = _expert_ffn(cfg, xe, w_gate, w_up, w_down)
        if has_tp:
            # down-projection partial sums over the ff shard
            ye = jax.lax.psum(ye, "model")
        ye = jax.lax.all_to_all(ye, dp, split_axis=1, concat_axis=0,
                                tiled=True)        # [E, C, d]
        return _combine(cfg, ye, info, xt_chunk.shape[0]), aux

    def body(x_loc, router_w, w_gate, w_up, w_down):
        # x_loc [B_loc, S, d] on this device; weights: local expert shard
        # [E_loc, d, ff_loc]
        T_l = x_loc.shape[0] * x_loc.shape[1]
        xt = x_loc.reshape(T_l, d)
        if n_chunks <= 1:
            out, aux = one_chunk(xt, router_w, w_gate, w_up, w_down)
        else:
            def scan_body(_, chunk):
                o, a = one_chunk(chunk, router_w, w_gate, w_up, w_down)
                return (), (o, a)

            _, (out, auxs) = jax.lax.scan(
                scan_body, (), xt.reshape(n_chunks, T_chunk, d))
            out = out.reshape(T_l, d)
            aux = auxs.mean()
        aux = jax.lax.pmean(aux, dp)
        return out.reshape(x_loc.shape), aux

    dspec = P(dp if len(dp) > 1 else dp[0], None, None)
    espec = P(dp if len(dp) > 1 else dp[0], None, tp_spec)
    dnspec = P(dp if len(dp) > 1 else dp[0], tp_spec, None)
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(dspec, P(None, None), espec, espec, dnspec),
        out_specs=(dspec, P()),
        check_vma=False,
    )(x, mlp["router"], mlp["w_gate"], mlp["w_up"], mlp["w_down"])
    return out, aux
