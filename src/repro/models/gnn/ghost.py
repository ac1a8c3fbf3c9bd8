"""Ghost-exchange message passing — the paper's §4.1 on a TPU mesh.

Plain pjit of full-graph GNN training lets GSPMD handle the x[senders]
gather / segment-sum scatter across data shards; on ogb_products it
"involuntarily rematerializes" full node arrays per edge chunk per layer:
the baseline dry-run measured 44.6 TB peak HBM and 17.6 TB of
collective-permutes for equiformer-v2 (EXPERIMENTS.md §Perf A0).

This module is the paper's answer: partition vertices (two-phase atoms),
keep edges with their *receiver's* shard, and exchange only **ghosts** —
the boundary vertices a shard reads but does not own:

  host prep (``partition_for_ghosts``): reorder vertices by shard, localize
  edge endpoints, and build per-peer send tables (which of my rows each
  peer needs), all statically shaped (budgets padded);

  device exchange (``GhostCtx.refresh``): inside shard_map, each shard
  gathers its send rows into a [P, B, feat] buffer and one
  ``all_to_all`` delivers every shard its ghost rows — "each machine
  receives each modified vertex data at most once" (paper Sec. 5.1).

Per layer the models refresh ghosts before gathering, aggregate into owned
rows only, and ghost rows of the state are dead until the next refresh.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

Pytree = Any


# ---------------------------------------------------------------------------
# Host-side preparation (graph ingress — the atom loader's job)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GhostPlan:
    n_shards: int
    n_loc: int                 # owned vertices per shard (padded)
    budget: int                # ghost rows accepted from EACH peer (padded)
    e_loc: int                 # edges per shard (padded)
    # global arrays, shard s owns block s (leading dim = n_shards * per-shard)
    perm: np.ndarray           # [N_pad] new-order -> original vertex id
    senders_local: np.ndarray  # [S*E_loc] ids into [own(n_loc) ; ghosts(P*B)]
    receivers_local: np.ndarray  # [S*E_loc] ids into own rows
    edge_mask: np.ndarray      # [S*E_loc]
    send_idx: np.ndarray       # [S*(P*B)] local row each peer wants (pad 0)
    send_mask: np.ndarray      # [S*(P*B)]
    dropped_edges: int         # over-budget edges (masked; reported, not silent)


def plan_shapes(n_vertices: int, n_edges: int, n_shards: int,
                budget_frac: float = 1.0,
                edge_chunks: int = 1) -> GhostPlan:
    """Dimension-only plan (ShapeDtypeStruct dry-run path — the value
    arrays come from the atom loader in a real run)."""
    n_loc = -(-n_vertices // n_shards)
    budget = int(np.ceil(n_loc * budget_frac / n_shards))
    quantum = 8 * max(edge_chunks, 1)
    e_loc = int(np.ceil(n_edges / n_shards / quantum) * quantum)
    S, B = n_shards, budget
    z = np.zeros(0, np.int32)
    return GhostPlan(
        n_shards=S, n_loc=n_loc, budget=B, e_loc=e_loc,
        perm=z, senders_local=z, receivers_local=z,
        edge_mask=np.zeros(0, bool), send_idx=z,
        send_mask=np.zeros(0, bool), dropped_edges=0)


def partition_for_ghosts(senders: np.ndarray, receivers: np.ndarray,
                         n_vertices: int, n_shards: int,
                         budget_frac: float = 1.0) -> GhostPlan:
    """Contiguous-range vertex partition (callers pre-order vertices with the
    atom partitioner for locality) + localized edges + send tables."""
    n_loc = -(-n_vertices // n_shards)
    n_pad = n_loc * n_shards
    shard_of = np.minimum(np.arange(n_pad) // n_loc, n_shards - 1)

    e_shard = receivers // n_loc                       # receiver-owned edges
    order = np.argsort(e_shard, kind="stable")
    s_sorted, r_sorted = senders[order], receivers[order]
    e_shard = e_shard[order]

    budget = int(np.ceil(n_loc * budget_frac / n_shards))
    e_loc = int(np.ceil(np.bincount(e_shard, minlength=n_shards).max()
                        / 8.0) * 8)

    S, B = n_shards, budget
    senders_local = np.zeros(S * e_loc, np.int32)
    receivers_local = np.zeros(S * e_loc, np.int32)
    edge_mask = np.zeros(S * e_loc, bool)
    send_idx = np.zeros(S * S * B, np.int32)
    send_mask = np.zeros(S * S * B, bool)
    dropped = 0
    all_tables: Dict[int, Dict[int, Dict[int, int]]] = {}

    for s in range(S):
        idx = np.nonzero(e_shard == s)[0]
        ss, rr = s_sorted[idx], r_sorted[idx]
        lo = s * n_loc
        remote = ss // n_loc != s
        # ghost slots per source shard, in order of first appearance
        ghost_slot = np.full(len(ss), -1, np.int64)
        per_peer: Dict[int, Dict[int, int]] = {}
        keep = np.ones(len(ss), bool)
        for i in np.nonzero(remote)[0]:
            src = int(ss[i])
            peer = src // n_loc
            table = per_peer.setdefault(peer, {})
            if src not in table:
                if len(table) >= B:      # over budget: drop edge (masked)
                    keep[i] = False
                    dropped += 1
                    continue
                table[src] = len(table)
            ghost_slot[i] = peer * B + table[src]
        local_sender = np.where(
            remote, n_loc + ghost_slot, ss - lo).astype(np.int32)
        n_e = len(ss)
        senders_local[s * e_loc:s * e_loc + n_e] = np.where(
            keep, local_sender, 0)
        receivers_local[s * e_loc:s * e_loc + n_e] = (rr - lo).astype(
            np.int32)
        edge_mask[s * e_loc:s * e_loc + n_e] = keep
        all_tables[s] = per_peer

    # shard s must SEND to peer p the rows p ghosts from s
    for p in range(S):
        for src_shard, table in all_tables[p].items():
            base = src_shard * (S * B) + p * B
            for global_row, slot in table.items():
                send_idx[base + slot] = global_row - src_shard * n_loc
                send_mask[base + slot] = True

    return GhostPlan(
        n_shards=S, n_loc=n_loc, budget=B, e_loc=e_loc,
        perm=np.arange(n_pad),
        senders_local=senders_local, receivers_local=receivers_local,
        edge_mask=edge_mask, send_idx=send_idx, send_mask=send_mask,
        dropped_edges=dropped)


# ---------------------------------------------------------------------------
# Device-side exchange
# ---------------------------------------------------------------------------

class GhostCtx:
    """Per-shard ghost exchange handle (lives inside the shard_map body)."""

    def __init__(self, send_idx: jnp.ndarray, send_mask: jnp.ndarray,
                 n_loc: int, budget: int, n_shards: int, dp):
        self.send_idx = send_idx        # [P*B] local rows to ship, grouped
        self.send_mask = send_mask      # [P*B]
        self.n_loc = n_loc
        self.budget = budget
        self.n_shards = n_shards
        self.dp = dp

    def refresh(self, x_all: jnp.ndarray) -> jnp.ndarray:
        """x_all [n_loc + P*B, ...]: recompute ghost rows from owners.

        gather own rows for each peer -> [P, B, feat] -> all_to_all over the
        data axes -> ghosts grouped by source shard -> concat after owned.
        """
        own = x_all[:self.n_loc]
        send = own[self.send_idx]                       # [P*B, ...]
        send = send * self.send_mask.reshape(
            (-1,) + (1,) * (send.ndim - 1)).astype(send.dtype)
        send = send.reshape((self.n_shards, self.budget) + send.shape[1:])
        recv = jax.lax.all_to_all(send, self.dp, split_axis=0,
                                  concat_axis=0, tiled=True)
        ghosts = recv.reshape((self.n_shards * self.budget,) + recv.shape[2:])
        return jnp.concatenate([own, ghosts], axis=0)

    def expand_static(self, tree: Pytree) -> Pytree:
        return jax.tree.map(self.refresh, tree)


def ghost_loss_fn(cfg, mod, gnn_loss, mesh, plan: GhostPlan):
    """Builds loss(params, batch) with the whole forward inside shard_map.

    ``batch`` arrays are globally shaped and sharded over dp; per shard the
    body sees its own block.  Node arrays enter at [S*n_loc] and are
    expanded to [n_loc + S*B] locally (static features once, the state x
    per layer via batch['ghost_refresh']).
    """
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp_spec = dp if len(dp) > 1 else dp[0]

    node_keys = ("features", "species", "positions", "labels", "node_mask",
                 "graph_id")
    edge_keys = ("senders", "receivers", "edge_mask")

    def body(params, batch):
        ctx = GhostCtx(batch["send_idx"], batch["send_mask"],
                       plan.n_loc, plan.budget, plan.n_shards, dp)
        local = dict(batch)
        for k in node_keys:
            local[k] = ctx.refresh(batch[k])
        # ghost rows never contribute to the loss
        local["node_mask"] = local["node_mask"].at[plan.n_loc:].set(False)
        local["ghost_refresh"] = ctx.refresh
        out = mod.forward(cfg, params, local)
        loss = gnn_loss(cfg, out, local)
        return jax.lax.pmean(loss, dp)

    in_specs = (
        P(),  # params replicated; grads psum'd by the shard_map transpose
        {
            **{k: P(dp_spec) for k in node_keys},
            **{k: P(dp_spec) for k in edge_keys},
            "send_idx": P(dp_spec), "send_mask": P(dp_spec),
        },
    )
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)
