"""2-D distributed GNN message passing — MGBC's decomposition applied
to GNN training (the paper's technique as a first-class framework
feature, DESIGN.md §5).

GSPMD's automatic partitioning of ``gather + segment_sum`` replicates
node state around the scatter (hundreds of GB/device on ogb_products).
This module instead expresses one message-passing layer with the exact
communication structure of the paper's traversal level:

  expand (vertical):    all_gather(h chunks, axis=row) → h[cols_j]
                        all_gather(h chunks, axis=col) → h[rows_i]
                        (the second gather feeds messages that read the
                        *destination* features — BC's frontier only
                        needed sources)
  local compute:        per-arc message MLP + local segment_sum
  fold (horizontal):    psum_scatter(partials, axis=col) → owner chunks

Per-device memory is O(n/√p · d + arcs/p · d) instead of O(n·d) — the
paper's scalability argument, inherited verbatim.

Node arrays use the BC chunk layout (chunk jR+i on device (i,j), i.e.
``P((col, row))`` on the flat vertex dim); arc arrays come from
graphs/partition.partition_arcs_2d.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import GNNArch

__all__ = ["make_gnn2d_loss_fn", "gnn2d_batch_specs"]

PyTree = Any


def make_gnn2d_loss_fn(
    cfg: GNNArch,
    mesh: Mesh,
    shape_kind: str,
    chunk: int,
    max_arcs: int,
    n_graphs: int = 0,
    row_axis: str = "data",
    col_axis: str = "model",
    gather_dtype=None,
    fold_dtype=None,
):
    """Builds loss_fn(params, batch) as a shard_map program.

    Batch (global shapes; n_pad = R*C*chunk):
      node_feat [n_pad, d_feat]      — P((col, row)) chunk layout
      src_local/dst_local [R, C, max_arcs] — P(row, col)
      edge_feat [R, C, max_arcs, d_feat]   — meshgraphnet only
      target [n_pad, d_out] | labels [n_pad] + label_mask [n_pad]
      graph_ids [n_pad] + labels [n_graphs] (batched_graphs)
    """
    R = mesh.shape[row_axis]
    C = mesh.shape[col_axis]
    grid = (row_axis, col_axis)
    n_acc = C * chunk + 1  # + sentinel row

    def body(params, batch):
        src_l = batch["src_local"][0, 0]
        dst_l = batch["dst_local"][0, 0]
        x = batch["node_feat"]  # [chunk, d_feat] owned
        h = jnp.tanh(x @ params["enc_w"] + params["enc_b"])

        e_loc = None
        if cfg.kind == "meshgraphnet":
            e_loc = jnp.tanh(
                batch["edge_feat"][0, 0] @ params["edge_enc_w"] + params["edge_enc_b"]
            )

        gd = gather_dtype

        def gather(z, axis):
            """Expand collective; optional low-precision payload
            (bf16 halves the gather bytes — §Perf graphcast iteration 2)."""
            if gd is not None and z.dtype != gd:
                return jax.lax.all_gather(z.astype(gd), axis, tiled=True).astype(
                    z.dtype
                )
            return jax.lax.all_gather(z, axis, tiled=True)

        def mp(h, e_loc, lp):
            if cfg.kind == "gat":
                H, dh = cfg.n_heads, cfg.d_hidden
                hw_own = jnp.einsum("nd,dhk->nhk", h, lp["w"])  # [chunk, H, dh]
                hw_col = gather(hw_own, row_axis)
                hw_row = gather(hw_own, col_axis)
                hwc = jnp.concatenate(
                    [hw_col, jnp.zeros((1, H, dh), hw_col.dtype)], axis=0
                )
                hwr = jnp.concatenate(
                    [hw_row, jnp.zeros((1, H, dh), hw_row.dtype)], axis=0
                )
                e_src = (hwc[src_l] * lp["a_src"]).sum(-1)  # [A, H]
                e_dst = (hwr[jnp.minimum(dst_l, C * chunk - 1)] * lp["a_dst"]).sum(-1)
                valid = (dst_l < C * chunk)[:, None]
                logit = jax.nn.leaky_relu(e_src + e_dst, 0.2)
                logit = jnp.where(valid, logit, -jnp.inf)
                # segment softmax: stats psum'd across the row group
                mx_l = jax.ops.segment_max(logit, dst_l, num_segments=n_acc)
                # softmax is shift-invariant: the cross-device max is a
                # constant for AD (pmax has no differentiation rule)
                mx = jax.lax.stop_gradient(
                    jax.lax.pmax(jax.lax.stop_gradient(mx_l), col_axis)
                )
                mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
                ex = jnp.where(valid, jnp.exp(logit - mx[dst_l]), 0.0)
                denom = jax.lax.psum(
                    jax.ops.segment_sum(ex, dst_l, num_segments=n_acc), col_axis
                )
                alpha = ex / jnp.maximum(denom[dst_l], 1e-9)
                msgs = hwc[src_l] * alpha[..., None]  # [A, H, dh]
                partial = jax.ops.segment_sum(msgs, dst_l, num_segments=n_acc)
                folded = jax.lax.psum_scatter(
                    partial[: C * chunk].reshape(C * chunk, H * dh),
                    col_axis,
                    scatter_dimension=0,
                    tiled=True,
                )
                return jax.nn.elu(folded), e_loc

            h_col = gather(h, row_axis)  # [R*chunk, d]
            h_row = (
                gather(h, col_axis)  # [C*chunk, d]
                if cfg.kind in ("graphcast", "meshgraphnet")
                else None
            )
            hc = jnp.concatenate([h_col, jnp.zeros((1,) + h_col.shape[1:], h_col.dtype)], 0)
            hr = (
                jnp.concatenate([h_row, jnp.zeros((1,) + h_row.shape[1:], h_row.dtype)], 0)
                if h_row is not None
                else None
            )
            src_i = src_l
            dst_i = dst_l  # sentinel C*chunk lands in the dropped row
            if cfg.kind == "gin":
                partial, e2 = (
                    jax.ops.segment_sum(hc[src_i], dst_i, num_segments=n_acc),
                    e_loc,
                )
            elif cfg.kind == "meshgraphnet":
                cat = jnp.concatenate(
                    [e_loc, hc[src_i], hr[jnp.minimum(dst_i, C * chunk - 1)]], axis=-1
                )
                upd = jax.nn.relu(cat @ lp["we1"] + lp["be1"]) @ lp["we2"] + lp["be2"]
                e2 = e_loc + upd * (dst_i < C * chunk)[:, None]
                partial = jax.ops.segment_sum(e2, dst_i, num_segments=n_acc)
            else:  # graphcast
                cat = jnp.concatenate(
                    [hc[src_i], hr[jnp.minimum(dst_i, C * chunk - 1)]], axis=-1
                )
                m = jax.nn.relu(cat @ lp["wm1"] + lp["bm1"]) @ lp["wm2"] + lp["bm2"]
                m = m * (dst_i < C * chunk)[:, None]
                partial = jax.ops.segment_sum(m, dst_i, num_segments=n_acc)
                e2 = e_loc
            if fold_dtype is not None:
                partial = partial.astype(fold_dtype)
            agg = jax.lax.psum_scatter(
                partial[: C * chunk], col_axis, scatter_dimension=0, tiled=True
            ).astype(h.dtype)  # [chunk, d]
            if cfg.kind == "gin":
                z = (1.0 + lp["eps"]) * h + agg
                z = jax.nn.relu(z @ lp["w1"] + lp["b1"])
                z = jax.nn.relu(z @ lp["w2"] + lp["b2"])
                return z, e2
            if cfg.kind == "meshgraphnet":
                cat_n = jnp.concatenate([h, agg], axis=-1)
                h2 = jax.nn.relu(cat_n @ lp["wn1"] + lp["bn1"]) @ lp["wn2"] + lp["bn2"]
                return h + h2, e2
            cat_n = jnp.concatenate([h, agg], axis=-1)
            u = jax.nn.relu(cat_n @ lp["wu1"] + lp["bu1"]) @ lp["wu2"] + lp["bu2"]
            return h + u, e2

        def scan_body(carry, lp):
            h, e = carry
            h2, e2 = jax.checkpoint(mp)(h, e, lp)
            return (h2, e2), None

        (h, _), _ = jax.lax.scan(scan_body, (h, e_loc), params["layers"])
        out = h @ params["dec_w"] + params["dec_b"]  # [chunk, d_out]

        # ------------------------------------------------------- losses
        if cfg.kind in ("graphcast", "meshgraphnet"):
            err = (out - batch["target"]).astype(jnp.float32)
            mask = batch["label_mask"][:, None]
            sse = jax.lax.psum(jnp.sum(jnp.square(err) * mask), grid)
            cnt = jax.lax.psum(jnp.sum(mask) * out.shape[1], grid)
            loss = sse / jnp.maximum(cnt, 1.0)
        elif shape_kind == "batched_graphs":
            masked = out * batch["label_mask"][:, None]
            pooled = jax.ops.segment_sum(
                masked, batch["graph_ids"], num_segments=n_graphs
            )
            logits = jax.lax.psum(pooled, grid).astype(jnp.float32)  # [G, d_out]
            labels = batch["labels"]  # replicated [G]
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
            loss = jnp.mean(logz - gold)
        else:  # full_graph / minibatch via label_mask
            logits = out.astype(jnp.float32)
            labels = batch["labels"]
            mask = batch["label_mask"]
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, jnp.maximum(labels, 0)[:, None], axis=-1
            )[:, 0]
            num = jax.lax.psum(jnp.sum((logz - gold) * mask), grid)
            den = jax.lax.psum(jnp.sum(mask), grid)
            loss = num / jnp.maximum(den, 1.0)
        return loss

    # sharding specs for shard_map
    owner = P((col_axis, row_axis))
    batch_specs_in = {
        "node_feat": P((col_axis, row_axis), None),
        "src_local": P(row_axis, col_axis, None),
        "dst_local": P(row_axis, col_axis, None),
    }
    if cfg.kind in ("graphcast", "meshgraphnet"):
        batch_specs_in["target"] = P((col_axis, row_axis), None)
        batch_specs_in["label_mask"] = owner
        if cfg.kind == "meshgraphnet":
            batch_specs_in["edge_feat"] = P(row_axis, col_axis, None, None)
    elif shape_kind == "batched_graphs":
        batch_specs_in["graph_ids"] = owner
        batch_specs_in["labels"] = P()
        batch_specs_in["label_mask"] = owner
    else:
        batch_specs_in["labels"] = owner
        batch_specs_in["label_mask"] = owner

    shmapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), batch_specs_in),  # params replicated
        out_specs=P(),
        check_vma=False,
    )
    return shmapped, batch_specs_in


def gnn2d_batch_specs(cfg: GNNArch, shape_kind, n_pad, R, C, max_arcs, d_feat, d_out, n_graphs=0):
    """ShapeDtypeStruct tree for the 2-D batch."""
    SDS = jax.ShapeDtypeStruct
    specs = {
        "node_feat": SDS((n_pad, d_feat), jnp.float32),
        "src_local": SDS((R, C, max_arcs), jnp.int32),
        "dst_local": SDS((R, C, max_arcs), jnp.int32),
    }
    if cfg.kind in ("graphcast", "meshgraphnet"):
        specs["target"] = SDS((n_pad, d_out), jnp.float32)
        specs["label_mask"] = SDS((n_pad,), jnp.float32)
        if cfg.kind == "meshgraphnet":
            specs["edge_feat"] = SDS((R, C, max_arcs, d_feat), jnp.float32)
    elif shape_kind == "batched_graphs":
        specs["graph_ids"] = SDS((n_pad,), jnp.int32)
        specs["labels"] = SDS((n_graphs,), jnp.int32)
        specs["label_mask"] = SDS((n_pad,), jnp.float32)
    else:
        specs["labels"] = SDS((n_pad,), jnp.int32)
        specs["label_mask"] = SDS((n_pad,), jnp.float32)
    return specs
