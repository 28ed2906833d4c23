"""The operator layer of the traversal stack (paper §3.1–§3.2).

One traversal algorithm — level-synchronous shortest-path counting plus
dependency accumulation — runs everywhere in this codebase; what varies
is *how a level is applied* and *how level-global facts are agreed on*.
:class:`TraversalOperator` is that seam.  The engine layer
(:mod:`repro.core.engine`) owns the level loops; the driver layer
(:mod:`repro.core.driver`) owns the per-round algebra and the host round
loop; operators own everything below a level:

  apply(x)                 A @ x over the rows this operator holds
  forward_level(...)       one forward BFS level (default: masked matmul
                           via ``apply``; Pallas operators fuse it)
  backward_level(...)      one dependency level (same contract)
  reduce_any/max/sum       collective agreement on frontier liveness,
                           max depth, and additive per-column facts
                           (identity on a single device; psum/pmax on a
                           2-D grid)
  row_ids / level_cap      which global vertices the local rows are, and
                           the worst-case level count
  root_omega               look up ω at the round's root vertices
  overlap                  collective-schedule policy (OVERLAP_POLICIES):
                           barrier all_gather/psum_scatter vs ppermute
                           ring steps pipelined with block compute

Implementations:

* :class:`DenseOperator`     — [n, n] 0/1 matmul on the MXU (§3.1).
* :class:`SparseOperator`    — padded arc list + gather/segment-sum, the
                               TPU stand-in for atomic scatter-add (§3.1).
* :class:`PallasDenseOperator` — fused level kernels
                               (kernels/frontier_spmm.py,
                               kernels/dependency_spmm.py): one kernel
                               launch per level, no HBM-materialized
                               frontier/g intermediates.
* :class:`DistributedOperator` — the paper's 2-D decomposition (§3.2):
                               expand (all_gather over grid rows) →
                               block-local compute → fold (psum_scatter
                               over grid columns), with arc-list local
                               compute.
* :class:`DistributedPallasOperator` — same collective skeleton, but the
                               block-local compute is the fused Pallas
                               kernel applied to the device's dense
                               adjacency block — the paper's coarse/fine
                               hybrid (cf. Mishra et al.,
                               arXiv:2008.05718) made reachable from the
                               distributed path.
* :class:`DistributedPallasSparseOperator` — the same fused level
                               structure on a blocked-sparse (BCSR) tile
                               list: only nonzero (bm × bk) tiles of the
                               device block are stored and streamed, so
                               adjacency memory is O(nnz_tiles) — the
                               RMAT-scale engine (kernels/blocked_spmm.py).
* :class:`DistributedPallasHybridOperator` — per-cell mix of the two:
                               each device cell streams whichever
                               representation the roofline's
                               bytes-streamed threshold picked for it
                               (roofline/model.cell_kernel_choice), so
                               near-dense community cells run the dense
                               kernels while hyper-sparse off-diagonal
                               cells run the BCSR kernels — under every
                               overlap policy.

``_forward_level`` / ``_backward_level`` below are the *only*
implementations of the level recurrences in the repository; every
non-fused operator routes through them.

Weighted graphs swap the level recurrences for *bucket* recurrences
(delta-stepping, Fan et al. arXiv:1701.05975): the
:class:`WeightedTraversalOperator` family supplies tentative-distance
relaxation (``relax``, with the light/heavy edge split inside the
operator), the path-count equality step (``sigma_step``) and the
dependency equality step (``delta_step``); the bucket loops live in
:func:`repro.core.engine.forward_buckets` /
:func:`repro.core.engine.backward_buckets`.  The distributed weighted
operators reuse the exact expand/fold collective skeleton (all_gather
over grid rows, segment/pmin fold over grid columns) under every overlap
policy — ring-pipelining the bucketed relaxation is future work, so the
weighted path always runs the barrier schedule internally while keeping
the replica-lockstep contract (``sync_axes``) of the unweighted engine.
"""
from __future__ import annotations

from typing import Callable

import math

import jax
import jax.numpy as jnp

from repro.kernels.ref import F32_EXACT, matmul_f32

__all__ = [
    "TraversalOperator",
    "DenseOperator",
    "SparseOperator",
    "PallasDenseOperator",
    "DistributedOperator",
    "DistributedPallasOperator",
    "DistributedPallasSparseOperator",
    "DistributedPallasHybridOperator",
    "WeightedTraversalOperator",
    "WeightedDenseOperator",
    "WeightedSparseOperator",
    "DistributedWeightedOperator",
    "DistributedWeightedDenseOperator",
    "as_operator",
    "auto_delta",
    "OVERLAP_POLICIES",
    "normalize_overlap",
]

# Collective-schedule policies for the distributed operators (paper §3.3
# Fig. 2 pipelining).  "none" is the barrier schedule — monolithic
# all_gather expand, block compute, psum_scatter fold, every device idle
# through both collectives.  "expand" decomposes the expand into R-1
# ppermute ring steps interleaved with per-chunk block compute
# (collective-matmul style: the next chunk is in flight while the one in
# hand multiplies).  "expand+fold" additionally decomposes the fold into
# a C-1-step reduce ring, so no monolithic collective remains on the
# level's critical path.  Single-device operators have no collectives;
# they accept only "none".
OVERLAP_POLICIES = ("none", "expand", "expand+fold")


def normalize_overlap(policy: str | None) -> str:
    """Validate an overlap policy string (None means "none")."""
    policy = "none" if policy is None else policy
    if policy not in OVERLAP_POLICIES:
        raise ValueError(
            f"unknown overlap policy {policy!r}; expected one of {OVERLAP_POLICIES}"
        )
    return policy


def _ring_perm(axis_size: int) -> list[tuple[int, int]]:
    """ppermute permutation for one ring hop: device s sends to s+1."""
    return [(s, (s + 1) % axis_size) for s in range(axis_size)]


def _forward_level(op: "TraversalOperator", lvl, sigma, depth):
    """One forward BFS level (paper Alg. 2 analogue — the sole copy).

        t = A @ (σ ⊙ [d = lvl-1]);  newly = (t > 0) ∧ (d < 0)
        d := lvl on newly;          σ += t on newly
    """
    frontier = sigma * (depth == lvl - 1)
    contrib = op.apply(frontier)
    newly = (contrib > 0) & (depth < 0)
    depth = jnp.where(newly, lvl, depth)
    sigma = sigma + jnp.where(newly, contrib, 0.0)
    return sigma, depth, newly.any()


def _backward_level(op: "TraversalOperator", lvl, sigma, depth, omega, delta):
    """One dependency level (paper Alg. 4/5 analogue — the sole copy).

        g = (1 + δ + ω) / σ on d = lvl+1;  δ += σ ⊙ (A @ g) on d = lvl

    Checking successors (Madduri et al.) — no predecessor lists.
    """
    omega_col = omega.astype(jnp.float32)[:, None]
    safe_sigma = jnp.where(sigma > 0, sigma, 1.0)
    g = jnp.where(depth == lvl + 1, (1.0 + delta + omega_col) / safe_sigma, 0.0)
    t = op.apply_backward(g)
    return delta + jnp.where(depth == lvl, sigma * t, 0.0)


def _forward_level_checked(op: "TraversalOperator", lvl, sigma, depth):
    """:func:`_forward_level` with a transient ABFT ones-checksum lane.

    The lane is appended to the masked frontier just before the SpMM and
    stripped right after — it never enters the loop carry, so σ/d stay
    [n, s] everywhere and liveness / max-depth are unpolluted.  Returns
    the usual triple plus the relative column-sum residual of this
    level's product (f32 scalar, row-local — no extra collectives).
    """
    from repro.kernels.ops import checksum_append, checksum_residual

    frontier = sigma * (depth == lvl - 1)
    t = op.apply(checksum_append(frontier))
    err = checksum_residual(t)
    contrib = t[:, :-1]
    newly = (contrib > 0) & (depth < 0)
    depth = jnp.where(newly, lvl, depth)
    sigma = sigma + jnp.where(newly, contrib, 0.0)
    return sigma, depth, newly.any(), err


def _backward_level_checked(op: "TraversalOperator", lvl, sigma, depth, omega, delta):
    """:func:`_backward_level` with a transient ABFT ones-checksum lane.

    Same transient-lane contract as :func:`_forward_level_checked`:
    the lane rides only the ``A @ g`` product; δ stays [n, s].
    """
    from repro.kernels.ops import checksum_append, checksum_residual

    omega_col = omega.astype(jnp.float32)[:, None]
    safe_sigma = jnp.where(sigma > 0, sigma, 1.0)
    g = jnp.where(depth == lvl + 1, (1.0 + delta + omega_col) / safe_sigma, 0.0)
    t = op.apply_backward(checksum_append(g))
    err = checksum_residual(t)
    return delta + jnp.where(depth == lvl, sigma * t[:, :-1], 0.0), err


class TraversalOperator:
    """Protocol base: single-device semantics, no collectives."""

    # rows this operator holds (static python int)
    n_rows: int

    # ------------------------------------------------------------- core
    def apply(self, x: jnp.ndarray) -> jnp.ndarray:
        """A @ x for the local rows."""
        raise NotImplementedError

    def apply_backward(self, g: jnp.ndarray) -> jnp.ndarray:
        """A @ g in the dependency sweep (hook for payload-split modes)."""
        return self.apply(g)

    # ------------------------------------------------------ level steps
    def forward_level(self, lvl, sigma, depth):
        """(σ, d) -> (σ', d', local_alive) for one forward level."""
        return _forward_level(self, lvl, sigma, depth)

    def backward_level(self, lvl, sigma, depth, omega, delta):
        """Running δ -> δ' for one dependency level (ω is f32 [n_rows])."""
        return _backward_level(self, lvl, sigma, depth, omega, delta)

    def forward_level_checked(self, lvl, sigma, depth):
        """:meth:`forward_level` + the level's ABFT checksum residual.

        Returns ``(σ', d', local_alive, err)`` where ``err`` is the
        relative column-sum residual of the checksum-extended SpMM (see
        :func:`repro.kernels.ops.checksum_residual`).  The lane is
        transient — state shapes are identical to the unchecked step.
        """
        return _forward_level_checked(self, lvl, sigma, depth)

    def backward_level_checked(self, lvl, sigma, depth, omega, delta):
        """:meth:`backward_level` + the level's ABFT checksum residual."""
        return _backward_level_checked(self, lvl, sigma, depth, omega, delta)

    # ------------------------------------------- collective agreements
    def reduce_any(self, alive: jnp.ndarray) -> jnp.ndarray:
        """Global 'any column discovered a vertex this level'."""
        return alive

    def reduce_max(self, value: jnp.ndarray) -> jnp.ndarray:
        """Global max (depth agreement before the backward sweep)."""
        return value

    def reduce_max_grid(self, value: jnp.ndarray) -> jnp.ndarray:
        """Max over *this traversal's own* devices only.

        Identical to :meth:`reduce_max` except that it never spans
        ``sync_axes``: under a ring overlap policy the loop-bound
        reductions include the sub-cluster replica axis (all replicas run
        max-over-replicas levels so the ppermute rendezvous stays in
        lockstep), but the straggler scheduler
        (:class:`repro.core.driver.BCDriver`) needs each replica's *own*
        data-dependent depth as its per-round cost signal — the quantity
        the synced bound deliberately hides.
        """
        return self.reduce_max(value)

    def reduce_max_sync(self, value: jnp.ndarray) -> jnp.ndarray:
        """Extend an already grid-reduced max over ``sync_axes`` only.

        ``reduce_max == reduce_max_sync ∘ reduce_max_grid``; the driver's
        round body uses the decomposed form so the per-replica depth
        (grid max) and the synced loop bound share one reduction — no
        extra collective when ``sync_axes`` is empty (the common case).
        Identity on single-device operators.
        """
        return value

    def reduce_sum(self, value: jnp.ndarray) -> jnp.ndarray:
        """Global sum of an additive per-column quantity (e.g. n_s)."""
        return value

    # ------------------------------------------------------- geometry
    def row_ids(self) -> jnp.ndarray:
        """Global vertex id of each local row (i32 [n_rows])."""
        return jnp.arange(self.n_rows, dtype=jnp.int32)

    def level_cap(self) -> int:
        """Static upper bound on the number of BFS levels (global n)."""
        return self.n_rows

    def root_omega(self, roots: jnp.ndarray, omega: jnp.ndarray) -> jnp.ndarray:
        """ω at the round's root vertices (f32 [num_roots]; 0 at padding)."""
        safe = jnp.clip(roots, 0, omega.shape[0] - 1)
        return jnp.where(roots >= 0, omega[safe].astype(jnp.float32), 0.0)


class _CallableOperator(TraversalOperator):
    """Adapter: a bare ``A @ x`` closure as a TraversalOperator."""

    def __init__(self, fn: Callable[[jnp.ndarray], jnp.ndarray], n_rows: int | None = None):
        self._fn = fn
        self.n_rows = n_rows if n_rows is not None else -1

    def apply(self, x):
        return self._fn(x)

    def row_ids(self):
        if self.n_rows < 0:
            raise ValueError("callable operator needs n_rows for row_ids()")
        return super().row_ids()


def as_operator(op) -> TraversalOperator:
    """Accept a TraversalOperator or a bare ``A @ x`` callable."""
    if isinstance(op, TraversalOperator):
        return op
    if callable(op):
        return _CallableOperator(op)
    raise TypeError(f"not an operator: {op!r}")


class DenseOperator(TraversalOperator):
    """``A @ x`` with a dense [n, n] 0/1 adjacency (undirected ⇒ symmetric)."""

    def __init__(self, adjacency: jnp.ndarray):
        self.adjacency = adjacency
        self.n_rows = adjacency.shape[0]

    def apply(self, x):
        return matmul_f32(self.adjacency, x)


class SparseOperator(TraversalOperator):
    """``A @ x`` via arc-list gather + segment-sum.

    ``src``/``dst`` are the padded symmetric arc arrays; padding arcs use
    the sentinel vertex ``n`` on both endpoints, which reads from / writes
    to a discarded extra row.  ``out[v] = Σ_{(u,v) arcs} x[u]``.
    """

    def __init__(self, src: jnp.ndarray, dst: jnp.ndarray, n: int):
        self.src = src
        self.dst = dst
        self.n_rows = n

    def apply(self, x):
        n = self.n_rows
        x_pad = jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)], axis=0)
        msgs = x_pad[self.src]
        out = jax.ops.segment_sum(msgs, self.dst, num_segments=n + 1)
        return out[:n]


class PallasDenseOperator(TraversalOperator):
    """Fused level kernels on a dense adjacency (single device).

    Overrides the level steps — not ``apply`` — because the kernels fuse
    the frontier mask / g computation and the state update into the
    matmul (see kernels/frontier_spmm.py).  The adjacency may be bf16
    (0/1 values are exact); the accumulator stays f32.
    """

    def __init__(self, adjacency: jnp.ndarray, interpret: bool | None = None):
        self.adjacency = adjacency
        self.n_rows = adjacency.shape[0]
        self.interpret = interpret

    def apply(self, x):  # reference semantics, used by parity tests
        return matmul_f32(self.adjacency, x)

    def forward_level(self, lvl, sigma, depth):
        from repro.kernels import ops as kops

        sigma2, depth2 = kops.frontier_spmm(
            self.adjacency, sigma, depth, lvl, interpret=self.interpret
        )
        return sigma2, depth2, jnp.any(depth2 != depth)

    def backward_level(self, lvl, sigma, depth, omega, delta):
        from repro.kernels import ops as kops

        return kops.dependency_spmm(
            self.adjacency,
            sigma,
            depth,
            delta,
            omega.astype(jnp.float32),
            lvl,
            interpret=self.interpret,
        )

    # The fused square kernels never expose the raw product t, so the
    # checked steps route through the *partial* kernels instead, with the
    # checksum lane encoded as one extra in-kernel operand column: the
    # kernel recomputes frontier/g from (σ, d, δ, ω), so the lane's
    # operands are chosen to make the recompute land on the column sum —
    # forward σ_c = Σ_j σ_j·[d_j = lvl-1], d_c = lvl-1; backward σ_c = 1,
    # d_c = lvl+1, δ_c = Σ_j g_j - 1 - ω (then g_c = (1+δ_c+ω)/1 = Σ_j g_j).

    def forward_level_checked(self, lvl, sigma, depth):
        from repro.kernels import ops as kops

        fsum = (sigma * (depth == lvl - 1)).sum(axis=1, keepdims=True)
        sg = jnp.concatenate([sigma, fsum], axis=1)
        dp = jnp.concatenate([depth, jnp.full_like(depth[:, :1], lvl - 1)], axis=1)
        t = kops.frontier_spmm_partial(
            self.adjacency, sg, dp, lvl, interpret=self.interpret
        )
        err = kops.checksum_residual(t)
        contrib = t[:, :-1]
        newly = (contrib > 0) & (depth < 0)
        depth2 = jnp.where(newly, lvl, depth)
        sigma2 = sigma + jnp.where(newly, contrib, 0.0)
        return sigma2, depth2, newly.any(), err

    def backward_level_checked(self, lvl, sigma, depth, omega, delta):
        from repro.kernels import ops as kops

        om = omega.astype(jnp.float32)
        safe_sigma = jnp.where(sigma > 0, sigma, 1.0)
        g = jnp.where(depth == lvl + 1, (1.0 + delta + om[:, None]) / safe_sigma, 0.0)
        sg = jnp.concatenate([sigma, jnp.ones_like(sigma[:, :1])], axis=1)
        dp = jnp.concatenate([depth, jnp.full_like(depth[:, :1], lvl + 1)], axis=1)
        dl = jnp.concatenate(
            [delta, g.sum(axis=1, keepdims=True) - 1.0 - om[:, None]], axis=1
        )
        t = kops.dependency_spmm_partial(
            self.adjacency, sg, dp, dl, om, lvl, interpret=self.interpret
        )
        err = kops.checksum_residual(t)
        return delta + jnp.where(depth == lvl, sigma * t[:, :-1], 0.0), err


class DistributedOperator(TraversalOperator):
    """2-D-decomposed operator (paper §3.2) — built *inside* a shard_map
    body, where the mesh axis names are live.

    Per application:
      expand (vertical, Alg. 2 line 15):  all_gather over ``row_axis``
          delivers the frontier slice of grid column j — O(√p) partners.
      local compute (node level):         gather x_col[src_local] +
          segment_sum into dst_local.
      fold (horizontal, Alg. 2 line 19):  psum_scatter over ``col_axis``
          sums the C partials and delivers each device its owned chunk.

    Only frontier-σ / g ever travel; the depth test of the far endpoint
    is folded into the gathered quantity (beyond-paper: one exchange per
    level instead of the paper's σ+d pair).

    ``split_backward`` mimics the paper's unfused σ/d exchange by
    splitting the backward gather into two half-width collectives
    (Fig. 9 benchmark mode).

    ``overlap`` selects the collective schedule (see OVERLAP_POLICIES):
    the ring schedules need the per-row-chunk arc layout
    (:meth:`repro.graphs.partition.TwoDPartition.ring_arcs`) instead of
    the flat ``src_local``/``dst_local`` arrays, because each ring step
    processes only the arcs sourced in the chunk currently in hand.

    ``sync_axes`` lists extra mesh axes whose devices must agree on
    *loop bounds* (liveness / max depth) — the sub-cluster replica axis
    under a ring schedule.  Replicas process different rounds, so their
    level loops have independent data-dependent trip counts; grouped
    collectives (all_gather/psum/psum_scatter) tolerate that, but a
    ``ppermute`` lowers to one collective-permute whose source-target
    pairs span the whole mesh, so every replica must execute the same
    number of ring hops or the runtime deadlocks at the rendezvous.
    Including ``sync_axes`` in ``reduce_any``/``reduce_max`` makes each
    replica run max-over-replicas levels (the extras are masked no-ops);
    per-column *value* reductions (``reduce_sum``) stay grid-local.
    """

    def __init__(
        self,
        src_local: jnp.ndarray | None,  # i32 [max_arcs] — into the gathered column
        dst_local: jnp.ndarray | None,  # i32 [max_arcs] — into the C*chunk partial
        *,
        chunk: int,
        R: int,
        C: int,
        row_axis: str,
        col_axis: str,
        split_backward: bool = False,
        overlap: str = "none",
        ring_src_local: jnp.ndarray | None = None,  # i32 [R, max_ring_arcs]
        ring_dst_local: jnp.ndarray | None = None,  # i32 [R, max_ring_arcs]
        sync_axes: tuple[str, ...] = (),
    ):
        self.src_local = src_local
        self.dst_local = dst_local
        self.ring_src_local = ring_src_local
        self.ring_dst_local = ring_dst_local
        self.chunk = chunk
        self.R = R
        self.C = C
        self.row_axis = row_axis
        self.col_axis = col_axis
        self.grid_axes = (row_axis, col_axis)
        self.sync_axes = tuple(sync_axes)
        self.loop_axes = (row_axis, col_axis) + tuple(sync_axes)
        self.split_backward = split_backward
        self.overlap = normalize_overlap(overlap)
        if self.overlap != "none" and split_backward:
            raise ValueError(
                "split_backward is a barrier-schedule benchmark mode; "
                "it cannot be combined with a ring overlap policy"
            )
        self.n_rows = chunk

    # ---------------------------------------------- collective skeleton
    def _expand(self, x_owned):
        return jax.lax.all_gather(x_owned, self.row_axis, tiled=True)

    def _fold(self, partial):
        return jax.lax.psum_scatter(
            partial, self.col_axis, scatter_dimension=0, tiled=True
        )

    def _local(self, x_col):
        msgs = x_col[self.src_local]  # [max_arcs, s]
        return jax.ops.segment_sum(
            msgs, self.dst_local, num_segments=self.C * self.chunk + 1
        )[: self.C * self.chunk]

    # ------------------------------------------------- ring schedules
    def _fold_partial(self, partial):
        """Fold the [C·chunk, s] partial per the overlap policy."""
        if self.overlap == "expand+fold":
            return self._fold_ring(partial)
        return self._fold(partial)

    def _fold_ring(self, partial):
        """Reduce-ring fold: C-1 ppermute hops over the column axis.

        Block m of ``partial`` (rows [m·chunk, (m+1)·chunk)) is device
        (i, m)'s owned chunk.  The block bound for device j starts at
        device j+1 with that device's local partial and travels the ring
        gathering one add per hop; after C-1 hops device j holds the
        fully summed block j — the exact psum_scatter result, with each
        hop's send overlappable against the neighbouring adds.
        """
        C, chunk = self.C, self.chunk
        if C == 1:
            return partial
        j = jax.lax.axis_index(self.col_axis)
        perm = _ring_perm(C)

        def block(m):  # m is traced: the block this device contributes now
            return jax.lax.dynamic_slice_in_dim(partial, m * chunk, chunk, axis=0)

        acc = block(jnp.mod(j - 1, C))
        for t in range(1, C):
            acc = jax.lax.ppermute(acc, self.col_axis, perm) + block(
                jnp.mod(j - 1 - t, C)
            )
        return acc

    def _ring_partial(self, x_owned):
        """Ring-pipelined expand: R-1 ppermute hops over the row axis.

        The owned chunk rotates around the grid column; at step t the
        chunk of row ``r = (i - t) mod R`` is in hand and exactly its
        arcs (ring slot r) accumulate into the local partial while the
        next chunk is already in flight — the collective-matmul overlap
        of paper Fig. 2, expressed at the arc-list level.
        """
        if self.ring_src_local is None or self.ring_dst_local is None:
            raise ValueError(
                "overlap != 'none' needs the ring arc layout "
                "(TwoDPartition.ring_arcs)"
            )
        R, C, chunk = self.R, self.C, self.chunk
        i = jax.lax.axis_index(self.row_axis)
        perm = _ring_perm(R)
        hand = x_owned
        acc = jnp.zeros((C * chunk + 1,) + x_owned.shape[1:], jnp.float32)
        for t in range(R):
            nxt = jax.lax.ppermute(hand, self.row_axis, perm) if t + 1 < R else None
            r = jnp.mod(i - t, R)
            src_r = jax.lax.dynamic_index_in_dim(self.ring_src_local, r, keepdims=False)
            dst_r = jax.lax.dynamic_index_in_dim(self.ring_dst_local, r, keepdims=False)
            acc = acc + jax.ops.segment_sum(
                hand[src_r], dst_r, num_segments=C * chunk + 1
            )
            if nxt is not None:
                hand = nxt
        return acc[: C * chunk]

    def apply(self, x_owned):
        if self.overlap == "none":
            return self._fold(self._local(self._expand(x_owned)))
        return self._fold_partial(self._ring_partial(x_owned))

    def apply_backward(self, g):
        if not self.split_backward:
            return self.apply(g)
        half = g.shape[1] // 2  # paper-style split payload (benchmark mode)
        return jnp.concatenate([self.apply(g[:, :half]), self.apply(g[:, half:])], axis=1)

    # ------------------------------------------- collective agreements
    def reduce_any(self, alive):
        return jax.lax.psum(alive.astype(jnp.int32), self.loop_axes) > 0

    def reduce_max(self, value):
        return jax.lax.pmax(value, self.loop_axes)

    def reduce_max_grid(self, value):
        # grid-local (never spans sync_axes): the replica's own depth
        return jax.lax.pmax(value, self.grid_axes)

    def reduce_max_sync(self, value):
        # replica-axis extension of a grid max (no-op without sync_axes)
        if not self.sync_axes:
            return value
        return jax.lax.pmax(value, self.sync_axes)

    def reduce_sum(self, value):
        return jax.lax.psum(value, self.grid_axes)

    # ------------------------------------------------------- geometry
    def row_ids(self):
        i = jax.lax.axis_index(self.row_axis)
        j = jax.lax.axis_index(self.col_axis)
        base = (j * self.R + i) * self.chunk  # first owned global vertex id
        return base + jnp.arange(self.chunk, dtype=jnp.int32)

    def level_cap(self):
        return self.chunk * self.R * self.C  # n_pad

    def root_omega(self, roots, omega):
        owned_ids = self.row_ids()
        local = jnp.where(
            roots[None, :] == owned_ids[:, None],
            omega.astype(jnp.float32)[:, None],
            0.0,
        ).sum(axis=0)
        return self.reduce_sum(local)


class DistributedPallasOperator(DistributedOperator):
    """2-D decomposition with fused-Pallas dense-block local compute.

    The device's adjacency block A[rows_i, cols_j] (shape
    [C·chunk, R·chunk]) is dense; block-local compute calls the
    frontier/dependency SpMM kernels in *partial* mode — the operand
    fusion (mask / g recompute in VMEM) is unchanged, the epilogue is
    deferred past the fold because the state update needs the globally
    summed ``t``.  Exchanges therefore carry (σ, d) forward and
    (σ, d, δ, ω) backward — the paper's §3.2 exchange set — instead of
    the pre-masked single tensor of the arc-list operator; the A-stream
    moves to the MXU and may be bf16.

    Under a ring overlap policy the expand rotates the owned operand
    chunks around the row axis with ``ppermute`` and each step multiplies
    the adjacency sub-block ``A[:, r·chunk:(r+1)·chunk]`` against the
    chunk in hand through the partial kernels' chunked-operand mode
    (``acc=`` — the running combine is fused into the kernel's VMEM
    accumulator init), so the next chunk's transfer overlaps the current
    chunk's MXU work.
    """

    def __init__(
        self,
        adjacency_block: jnp.ndarray,  # [C*chunk, R*chunk] dense 0/1 block
        *,
        chunk: int,
        R: int,
        C: int,
        row_axis: str,
        col_axis: str,
        interpret: bool | None = None,
        overlap: str = "none",
        sync_axes: tuple[str, ...] = (),
    ):
        super().__init__(
            src_local=None,
            dst_local=None,
            chunk=chunk,
            R=R,
            C=C,
            row_axis=row_axis,
            col_axis=col_axis,
            overlap=overlap,
            sync_axes=sync_axes,
        )
        self.adjacency_block = adjacency_block
        self.interpret = interpret

    def _local(self, x_col):
        return matmul_f32(self.adjacency_block, x_col)

    # ------------------------------------------------------ block hooks
    # The dense and blocked-sparse fused operators share the entire level
    # structure below; only how the adjacency block is *represented* (one
    # dense array vs a BCSR tile list) and which kernel consumes it
    # differ.  ``_full_block`` / ``_ring_block`` produce the A-operand
    # (whole block, or the slice for ring step r), the ``_partial_*``
    # hooks dispatch it to the matching kernel.

    def _full_block(self):
        """A-operand of the barrier schedule (the whole device block)."""
        return self.adjacency_block

    def _ring_block(self, r):
        """A-operand of ring step r (columns of the chunk in hand)."""
        return jax.lax.dynamic_slice_in_dim(
            self.adjacency_block, r * self.chunk, self.chunk, axis=1
        )

    def _partial_forward(self, block, sigma, depth, lvl, acc=None):
        from repro.kernels import ops as kops

        return kops.frontier_spmm_partial(
            block, sigma, depth, lvl, acc=acc, interpret=self.interpret
        )

    def _partial_backward(self, block, sigma, depth, delta, omega, lvl, acc=None):
        from repro.kernels import ops as kops

        return kops.dependency_spmm_partial(
            block, sigma, depth, delta, omega, lvl, acc=acc, interpret=self.interpret
        )

    def _ring_steps(self, operands, step_fn):
        """Ring-pipelined expand over the row axis (block form).

        ``operands`` is a tuple of owned [chunk, ...] arrays that travel
        together; ``step_fn(block, hand, acc)`` folds one chunk's product
        into the running [C·chunk, s] accumulator, ``block`` being
        ``self._ring_block(r)`` for the chunk in hand.  The ppermute for
        step t+1 is issued before step t's compute so XLA's async
        collective-permute overlaps the transfer with the block compute.
        """
        R, chunk = self.R, self.chunk
        i = jax.lax.axis_index(self.row_axis)
        perm = _ring_perm(R)
        hand = tuple(operands)
        acc = jnp.zeros((self.C * chunk, operands[0].shape[1]), jnp.float32)
        for t in range(R):
            nxt = (
                tuple(jax.lax.ppermute(x, self.row_axis, perm) for x in hand)
                if t + 1 < R
                else None
            )
            r = jnp.mod(i - t, R)
            acc = step_fn(self._ring_block(r), hand, acc)
            if nxt is not None:
                hand = nxt
        return acc

    def _ring_partial(self, x_owned):
        # dense-block counterpart of the arc-list ring (used via apply)
        return self._ring_steps(
            (x_owned,), lambda a_r, hand, acc: acc + matmul_f32(a_r, hand[0])
        )

    def forward_level(self, lvl, sigma, depth):
        if self.overlap == "none":
            sigma_col = self._expand(sigma)  # [R*chunk, s]
            depth_col = self._expand(depth)
            partial = self._partial_forward(
                self._full_block(), sigma_col, depth_col, lvl
            )  # [C*chunk, s]
        else:
            partial = self._ring_steps(
                (sigma, depth),
                lambda blk, hand, acc: self._partial_forward(
                    blk, hand[0], hand[1], lvl, acc=acc
                ),
            )
        t = self._fold_partial(partial)  # [chunk, s]
        newly = (t > 0) & (depth < 0)
        depth = jnp.where(newly, lvl, depth)
        sigma = sigma + jnp.where(newly, t, 0.0)
        return sigma, depth, newly.any()

    def backward_level(self, lvl, sigma, depth, omega, delta):
        omega_f = omega.astype(jnp.float32)
        if self.overlap == "none":
            sigma_col = self._expand(sigma)
            depth_col = self._expand(depth)
            delta_col = self._expand(delta)
            omega_col = self._expand(omega_f)
            partial = self._partial_backward(
                self._full_block(), sigma_col, depth_col, delta_col, omega_col, lvl
            )
        else:
            partial = self._ring_steps(
                (sigma, depth, delta, omega_f),
                lambda blk, hand, acc: self._partial_backward(
                    blk, hand[0], hand[1], hand[2], hand[3], lvl, acc=acc
                ),
            )
        t = self._fold_partial(partial)
        return delta + jnp.where(depth == lvl, sigma * t, 0.0)

    # Checked level steps: same extend-operand trick as the single-device
    # Pallas operator (the kernels recompute frontier/g in VMEM, so the
    # checksum lane is encoded in the operands), threaded through the
    # identical expand/ring + fold structure — the lane column survives
    # all_gather / ppermute / psum_scatter because each is linear per
    # column, so one residual on the folded t audits the whole pipeline.
    # The sparse and hybrid subclasses inherit these via the block hooks.

    def forward_level_checked(self, lvl, sigma, depth):
        from repro.kernels import ops as kops

        fsum = (sigma * (depth == lvl - 1)).sum(axis=1, keepdims=True)
        sg = jnp.concatenate([sigma, fsum], axis=1)
        dp = jnp.concatenate([depth, jnp.full_like(depth[:, :1], lvl - 1)], axis=1)
        if self.overlap == "none":
            partial = self._partial_forward(
                self._full_block(), self._expand(sg), self._expand(dp), lvl
            )
        else:
            partial = self._ring_steps(
                (sg, dp),
                lambda blk, hand, acc: self._partial_forward(
                    blk, hand[0], hand[1], lvl, acc=acc
                ),
            )
        t = self._fold_partial(partial)
        err = kops.checksum_residual(t)
        contrib = t[:, :-1]
        newly = (contrib > 0) & (depth < 0)
        depth2 = jnp.where(newly, lvl, depth)
        sigma2 = sigma + jnp.where(newly, contrib, 0.0)
        return sigma2, depth2, newly.any(), err

    def backward_level_checked(self, lvl, sigma, depth, omega, delta):
        from repro.kernels import ops as kops

        omega_f = omega.astype(jnp.float32)
        safe_sigma = jnp.where(sigma > 0, sigma, 1.0)
        g = jnp.where(
            depth == lvl + 1, (1.0 + delta + omega_f[:, None]) / safe_sigma, 0.0
        )
        sg = jnp.concatenate([sigma, jnp.ones_like(sigma[:, :1])], axis=1)
        dp = jnp.concatenate([depth, jnp.full_like(depth[:, :1], lvl + 1)], axis=1)
        dl = jnp.concatenate(
            [delta, g.sum(axis=1, keepdims=True) - 1.0 - omega_f[:, None]], axis=1
        )
        if self.overlap == "none":
            partial = self._partial_backward(
                self._full_block(),
                self._expand(sg),
                self._expand(dp),
                self._expand(dl),
                self._expand(omega_f),
                lvl,
            )
        else:
            partial = self._ring_steps(
                (sg, dp, dl, omega_f),
                lambda blk, hand, acc: self._partial_backward(
                    blk, hand[0], hand[1], hand[2], hand[3], lvl, acc=acc
                ),
            )
        t = self._fold_partial(partial)
        err = kops.checksum_residual(t)
        return delta + jnp.where(depth == lvl, sigma * t[:, :-1], 0.0), err


class DistributedPallasSparseOperator(DistributedPallasOperator):
    """2-D decomposition with blocked-sparse (BCSR) fused local compute.

    Same level structure as :class:`DistributedPallasOperator`, but the
    device's adjacency block is a tile list — only the nonzero (bm × bk)
    tiles of A[rows_i, cols_j] are stored (``tiles`` [T, bm, bk] +
    per-tile ``tile_rows``/``tile_cols`` index maps, host-built once by
    :meth:`repro.graphs.partition.TwoDPartition.blocked_sparse`) — and
    the local compute runs the scalar-prefetched sparse kernels
    (kernels/blocked_spmm.py), so per-device adjacency memory and
    A-stream HBM traffic are O(nnz_tiles · bm · bk) instead of
    O(n_pad²/p).  This is the engine for the RMAT-scale regime where the
    dense block does not fit.

    Under a ring overlap policy the per-ring-chunk tile slices
    (``ring_*`` [R, Tr, ...]; slot r = the tiles sourced in the chunk of
    grid row r, column ids re-based to the chunk) are selected at each
    hop: the slot's row/col ids by ``dynamic_index_in_dim``, its tiles by
    the kernel's tile index map, which reads them from the resident ring
    tiles (viewed as [R·Tr, bm, bk]) at the scalar-prefetched offset
    r·Tr — a dynamic slice of the tiles would be a copy of the slot in
    front of every call, since the kernel takes whole buffers.  The
    chunked-``acc`` kernel mode carries the running partial between hops.
    A block is (tiles, tile_rows, tile_cols, tile_offset).
    """

    def __init__(
        self,
        tiles: jnp.ndarray | None = None,  # [T, bm, bk] stored tiles
        tile_rows: jnp.ndarray | None = None,  # i32 [T]
        tile_cols: jnp.ndarray | None = None,  # i32 [T]
        *,
        chunk: int,
        R: int,
        C: int,
        row_axis: str,
        col_axis: str,
        interpret: bool | None = None,
        overlap: str = "none",
        sync_axes: tuple[str, ...] = (),
        ring_tiles: jnp.ndarray | None = None,  # [R, Tr, bm, bk]
        ring_tile_rows: jnp.ndarray | None = None,  # i32 [R, Tr]
        ring_tile_cols: jnp.ndarray | None = None,  # i32 [R, Tr]
    ):
        super().__init__(
            None,
            chunk=chunk,
            R=R,
            C=C,
            row_axis=row_axis,
            col_axis=col_axis,
            interpret=interpret,
            overlap=overlap,
            sync_axes=sync_axes,
        )
        if self.overlap == "none" and tiles is None:
            raise ValueError("barrier schedule needs the full tile layout")
        if self.overlap != "none" and ring_tiles is None:
            raise ValueError(
                "overlap != 'none' needs the ring tile layout "
                "(TwoDPartition.blocked_sparse(ring=True))"
            )
        self.tiles = tiles
        self.tile_rows = tile_rows
        self.tile_cols = tile_cols
        self.ring_tiles = ring_tiles
        self.ring_tile_rows = ring_tile_rows
        self.ring_tile_cols = ring_tile_cols

    # ------------------------------------------------------ block hooks
    def _full_block(self):
        return (self.tiles, self.tile_rows, self.tile_cols, 0)

    def _ring_block(self, r):
        pick = lambda a: jax.lax.dynamic_index_in_dim(a, r, keepdims=False)
        R, slot, bm, bk = self.ring_tiles.shape
        return (
            self.ring_tiles.reshape(R * slot, bm, bk),
            pick(self.ring_tile_rows),
            pick(self.ring_tile_cols),
            r * slot,
        )

    def _partial_forward(self, block, sigma, depth, lvl, acc=None):
        from repro.kernels import ops as kops

        tiles, rows, cols, off = block
        return kops.frontier_spmm_sparse(
            tiles, rows, cols, sigma, depth, lvl, m=self.C * self.chunk,
            acc=acc, tile_offset=off, interpret=self.interpret,
        )

    def _partial_backward(self, block, sigma, depth, delta, omega, lvl, acc=None):
        from repro.kernels import ops as kops

        tiles, rows, cols, off = block
        return kops.dependency_spmm_sparse(
            tiles, rows, cols, sigma, depth, delta, omega, lvl, m=self.C * self.chunk,
            acc=acc, tile_offset=off, interpret=self.interpret,
        )

    # --------------------------------------- reference apply() semantics
    def _dense_of(self, block, kdim):
        from repro.kernels.blocked_spmm import tiles_to_dense

        tiles, rows, cols, off = block
        return tiles_to_dense(tiles, rows, cols, self.C * self.chunk, kdim, off)

    def _local(self, x_col):
        # parity/debug path only — the engine runs the fused level hooks
        return matmul_f32(
            self._dense_of(self._full_block(), x_col.shape[0]), x_col
        )

    def _ring_partial(self, x_owned):
        return self._ring_steps(
            (x_owned,),
            lambda blk, hand, acc: acc
            + matmul_f32(self._dense_of(blk, self.chunk), hand[0]),
        )


class DistributedPallasHybridOperator(DistributedPallasSparseOperator):
    """2-D decomposition with a per-cell dense/BCSR kernel choice.

    Each device cell carries BOTH operand sets (shard_map needs uniform
    shapes across the mesh) but only its chosen one holds data: the host
    layout (:meth:`repro.graphs.partition.TwoDPartition.blocked_hybrid`)
    materializes dense block data for the dense-chosen cells and tile
    data for the sparse-chosen cells (the other slot is untouched
    zeros / the minimal filler list).  ``dense_cell`` is this device's
    choice — a *traced* scalar, so one SPMD program serves the whole
    mesh and each cell branches locally with ``lax.cond``; the branch
    contains only block-local kernel work (never a collective), so the
    mixed mesh stays in lockstep through every overlap policy's
    collective schedule, which this class inherits unchanged through the
    ``_full_block`` / ``_ring_block`` / ``_partial_*`` seams.
    """

    def __init__(
        self,
        adjacency_block: jnp.ndarray,  # [C*chunk, R*chunk] dense data (or zeros)
        dense_cell: jnp.ndarray,  # scalar bool: this cell's kernel choice
        **kwargs,
    ):
        super().__init__(**kwargs)
        self.adjacency_block = adjacency_block
        self.dense_cell = dense_cell

    # ------------------------------------------------------ block hooks
    def _full_block(self):
        return (self.adjacency_block,) + super()._full_block()

    def _ring_block(self, r):
        dense_r = jax.lax.dynamic_slice_in_dim(
            self.adjacency_block, r * self.chunk, self.chunk, axis=1
        )
        return (dense_r,) + super()._ring_block(r)

    def _partial_forward(self, block, sigma, depth, lvl, acc=None):
        from repro.kernels import ops as kops

        a_dense, tiles, rows, cols, off = block
        return jax.lax.cond(
            self.dense_cell,
            lambda: kops.frontier_spmm_partial(
                a_dense, sigma, depth, lvl, acc=acc, interpret=self.interpret
            ),
            lambda: kops.frontier_spmm_sparse(
                tiles, rows, cols, sigma, depth, lvl, m=self.C * self.chunk,
                acc=acc, tile_offset=off, interpret=self.interpret,
            ),
        )

    def _partial_backward(self, block, sigma, depth, delta, omega, lvl, acc=None):
        from repro.kernels import ops as kops

        a_dense, tiles, rows, cols, off = block
        return jax.lax.cond(
            self.dense_cell,
            lambda: kops.dependency_spmm_partial(
                a_dense, sigma, depth, delta, omega, lvl,
                acc=acc, interpret=self.interpret,
            ),
            lambda: kops.dependency_spmm_sparse(
                tiles, rows, cols, sigma, depth, delta, omega, lvl, m=self.C * self.chunk,
                acc=acc, tile_offset=off, interpret=self.interpret,
            ),
        )

    # --------------------------------------- reference apply() semantics
    def _mixed_dense(self, block, kdim):
        """Dense view of whichever representation this cell holds data in."""
        a_dense, *tile_block = block
        return jnp.where(
            self.dense_cell,
            a_dense.astype(jnp.float32),
            self._dense_of(tuple(tile_block), kdim),
        )

    def _local(self, x_col):
        # parity/debug path only — the engine runs the fused level hooks
        return matmul_f32(
            self._mixed_dense(self._full_block(), x_col.shape[0]), x_col
        )

    def _ring_partial(self, x_owned):
        return self._ring_steps(
            (x_owned,),
            lambda blk, hand, acc: acc
            + matmul_f32(self._mixed_dense(blk, self.chunk), hand[0]),
        )


# --------------------------------------------------------------------------
# Weighted traversal (delta-stepping buckets, Fan et al. arXiv:1701.05975)
# --------------------------------------------------------------------------
#
# The weighted operators deliberately ship *no* new Pallas kernels: the
# bucket recurrences are equality-masked min-plus / sum-product contractions
# that XLA already fuses well at the block sizes the fake-device CI exercises,
# and on TPU the dense variants still land on the MXU/VPU through the same
# [m, k, s] contraction shapes as the unweighted partial kernels.  Fusing the
# relax/sigma/delta steps into VMEM-resident Pallas kernels (the weighted
# analogue of kernels/frontier_spmm.py) is the follow-up once real-TPU
# profiles exist.  Every engine kind therefore accepts ``weighted=`` today;
# pallas/pallas_bf16/pallas_sparse/pallas_hybrid run their weighted compute
# on float32 operands (weights are never cast to bf16 — distances feed exact
# equality masks).

_BIG_DIST = 1e30  # segment_min identity guard: anything above is "unreached"


def auto_delta(graph) -> float:
    """Derive a bucket width from edge-weight statistics (host-side).

    The classic delta-stepping guidance is Δ ≈ Θ(1 / max-degree) scaled by
    the mean weight — wide enough that a bucket amortizes a relaxation
    sweep, narrow enough that the light-edge fixpoint stays shallow.  We
    clamp below by the minimum weight so a bucket always makes progress.
    Deterministic in the graph (no RNG): the same graph always yields the
    same Δ, which the reproducibility tests rely on.
    """
    w = getattr(graph, "w", None)
    if w is None or w.size == 0:
        raise ValueError("auto_delta needs a weighted graph with at least one edge")
    avg_degree = max(1.0, float(graph.num_arcs) / float(max(1, graph.n)))
    return float(max(float(w.min()), float(w.mean()) / avg_degree))


def _bucket_split(w, delta, heavy: bool):
    """Per-arc weight with non-selected arcs pushed to +inf.

    Arcs with w <= delta are *light* (relaxed to a fixpoint inside the
    bucket), w > delta are *heavy* (relaxed once after the bucket
    settles).  Padding arcs carry w == 0 and are excluded from both.
    """
    if heavy:
        sel = w > delta
    else:
        sel = (w > 0) & (w <= delta)
    return jnp.where(sel, w, jnp.inf)


class WeightedTraversalOperator(TraversalOperator):
    """Single-device weighted operator base: bucket-loop protocol.

    The engine's bucket loops (:func:`repro.core.engine.forward_buckets`,
    :func:`~repro.core.engine.backward_buckets`) drive three data hooks —

      relax(dist, frontier, heavy)  tentative-distance relaxation: the
          min over selected arcs (u, v) with u in the frontier of
          ``dist[u] + w``; +inf where no arc relaxes v.
      sigma_step(sigma_in, dist)    σ'_v = Σ_{u : d_v = d_u + w} σ_in[u]
          (shortest-path predecessor counting via the distance-equality
          mask; overwrite semantics — the engine fixpoints it over the
          within-bucket predecessor DAG).
      delta_step(g, dist)           per-vertex Σ_{v : d_v = d_u + w} g[v]
          (the dependency sum over *successors*; the engine multiplies by
          σ_u and fixpoints within the bucket).

    — plus ``reduce_min`` for the bucket-skip agreement.  All reductions
    are identities on a single device.
    """

    weighted = True

    def __init__(self, delta: float):
        delta = float(delta)
        if not (delta > 0.0) or not math.isfinite(delta):
            raise ValueError(f"bucket width delta must be positive and finite, got {delta}")
        self.delta = delta

    def reduce_min(self, value):
        return value

    def relax(self, dist, frontier, heavy):  # pragma: no cover - interface
        raise NotImplementedError

    def sigma_step(self, sigma_in, dist):  # pragma: no cover - interface
        raise NotImplementedError

    def delta_step(self, g, dist):  # pragma: no cover - interface
        raise NotImplementedError


class WeightedDenseOperator(WeightedTraversalOperator):
    """[n, n] weight-matrix operator (weight 0 encodes "no edge").

    The relax step is a min-plus contraction, sigma/delta are
    equality-masked sum contractions — all [n, n, s] broadcasts, the
    weighted analogue of the dense matmul path (small n only, like
    :class:`DenseOperator`).
    """

    def __init__(self, weights: jnp.ndarray, delta: float):
        super().__init__(delta)
        self.weights = weights.astype(jnp.float32)
        self.n_rows = weights.shape[0]
        self.mask = self.weights > 0
        self.w_light = _bucket_split(self.weights, self.delta, heavy=False)
        self.w_heavy = _bucket_split(self.weights, self.delta, heavy=True)
        self.w_full = jnp.where(self.mask, self.weights, jnp.inf)

    def apply(self, x):
        # unweighted reachability semantics (parity/debug only)
        return matmul_f32(self.mask, x)

    def relax(self, dist, frontier, heavy):
        wsel = self.w_heavy if heavy else self.w_light
        d = jnp.where(frontier, dist, jnp.inf)
        # cand[v, s] = min_u d[u, s] + w[u, v]
        return jnp.min(d[:, None, :] + wsel[:, :, None], axis=0)

    def _eq(self, dist):
        # eq[u, v, s]: arc (u, v) lies on a shortest path into v
        cand = dist[:, None, :] + self.w_full[:, :, None]
        return self.mask[:, :, None] & jnp.isfinite(cand) & (dist[None, :, :] == cand)

    def sigma_step(self, sigma_in, dist):
        # dot_general over u (same contraction the unweighted matmul uses,
        # so unit weights at delta=1 reproduce DenseOperator bitwise)
        eq = self._eq(dist).astype(jnp.float32)
        return jnp.einsum("uvs,us->vs", eq, sigma_in, precision=F32_EXACT)

    def delta_step(self, g, dist):
        eq = self._eq(dist).astype(jnp.float32)
        return jnp.einsum("uvs,vs->us", eq, g, precision=F32_EXACT)


class WeightedSparseOperator(WeightedTraversalOperator):
    """Padded-arc-list weighted operator (gather + segment_min/sum).

    Sentinel arcs point at vertex slot ``n`` with weight 0; every
    accumulation allocates n+1 segments and discards the sentinel row,
    exactly like :class:`SparseOperator`.
    """

    def __init__(self, src, dst, w, n: int, delta: float):
        super().__init__(delta)
        self.src = src
        self.dst = dst
        self.w = w.astype(jnp.float32)
        self.n = n
        self.n_rows = n
        self.w_light = _bucket_split(self.w, self.delta, heavy=False)
        self.w_heavy = _bucket_split(self.w, self.delta, heavy=True)
        self.w_full = jnp.where(self.w > 0, self.w, jnp.inf)

    def apply(self, x):
        x_pad = jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)], axis=0)
        msgs = x_pad[self.src]
        return jax.ops.segment_sum(msgs, self.dst, num_segments=self.n + 1)[: self.n]

    def _pad(self, x, fill):
        return jnp.concatenate([x, jnp.full((1,) + x.shape[1:], fill, x.dtype)], axis=0)

    def relax(self, dist, frontier, heavy):
        wsel = self.w_heavy if heavy else self.w_light
        d_pad = self._pad(jnp.where(frontier, dist, jnp.inf), jnp.inf)
        val = d_pad[self.src] + wsel[:, None]
        cand = jax.ops.segment_min(val, self.dst, num_segments=self.n + 1)[: self.n]
        return jnp.where(cand > _BIG_DIST, jnp.inf, cand)

    def _eq(self, dist):
        d_pad = self._pad(dist, jnp.inf)
        cand = d_pad[self.src] + self.w_full[:, None]
        return jnp.isfinite(cand) & (d_pad[self.dst] == cand), d_pad

    def sigma_step(self, sigma_in, dist):
        eq, _ = self._eq(dist)
        s_pad = self._pad(sigma_in, 0.0)
        contrib = jnp.where(eq, s_pad[self.src], 0.0)
        return jax.ops.segment_sum(contrib, self.dst, num_segments=self.n + 1)[: self.n]

    def delta_step(self, g, dist):
        # successor test from the dst side: the symmetric arc list serves
        # both directions, so accumulate g over arcs (y, x) with
        # d_y = d_x + w into x
        d_pad = self._pad(dist, jnp.inf)
        cand = d_pad[self.dst] + self.w_full[:, None]
        eq = jnp.isfinite(cand) & (d_pad[self.src] == cand)
        g_pad = self._pad(g, 0.0)
        contrib = jnp.where(eq, g_pad[self.src], 0.0)
        return jax.ops.segment_sum(contrib, self.dst, num_segments=self.n + 1)[: self.n]


class DistributedWeightedOperator(DistributedOperator):
    """2-D-decomposed weighted operator, arc-list local compute.

    Collective skeleton per relax: expand the frontier's (masked)
    distances over ``row_axis`` (all_gather), per-arc min-plus into the
    [C·chunk] partial (segment_min), then a *min-fold*: ``pmin`` over
    ``col_axis`` followed by slicing the device's owned chunk — the
    min-plus analogue of the psum_scatter fold.  sigma/delta steps are
    equality-masked segment sums folded with the usual psum_scatter; the
    equality test needs the *output-side* distances, replicated with an
    all_gather over ``col_axis`` (fold-order blocks, matching
    ``dst_local``'s partial indexing).

    Always the barrier schedule internally (ring-pipelining bucketed
    relaxation is future work); ``sync_axes`` still applies so replicas
    stay in loop-bound lockstep on sub-cluster meshes.

    weighted = True
    """

    weighted = True

    def __init__(
        self,
        src_local,
        dst_local,
        w_local,
        *,
        delta: float,
        chunk: int,
        R: int,
        C: int,
        row_axis: str,
        col_axis: str,
        sync_axes: tuple[str, ...] = (),
    ):
        super().__init__(
            src_local,
            dst_local,
            chunk=chunk,
            R=R,
            C=C,
            row_axis=row_axis,
            col_axis=col_axis,
            overlap="none",
            sync_axes=sync_axes,
        )
        if not (delta > 0):
            raise ValueError(f"bucket width delta must be positive, got {delta}")
        self.delta = float(delta)
        self.w_local = w_local.astype(jnp.float32)
        self.w_light = _bucket_split(self.w_local, self.delta, heavy=False)
        self.w_heavy = _bucket_split(self.w_local, self.delta, heavy=True)
        self.w_full = jnp.where(self.w_local > 0, self.w_local, jnp.inf)

    # ------------------------------------------------ collective pieces
    def _expand_out(self, x_owned):
        """Replicate owned chunks along the *fold* dimension: [chunk, s]
        -> [C·chunk, s] with block j holding device (i, j)'s chunk — the
        layout ``dst_local`` indexes (psum_scatter's scatter order)."""
        return jax.lax.all_gather(x_owned, self.col_axis, tiled=True)

    def _min_fold(self, partial):
        """Elementwise-min fold of the [C·chunk, s] partial: pmin over the
        column axis, then slice the owned block."""
        folded = jax.lax.pmin(partial, self.col_axis)
        j = jax.lax.axis_index(self.col_axis)
        return jax.lax.dynamic_slice_in_dim(folded, j * self.chunk, self.chunk, axis=0)

    def reduce_min(self, value):
        return jax.lax.pmin(value, self.loop_axes)

    # ------------------------------------------------------ bucket hooks
    def relax(self, dist, frontier, heavy):
        wsel = self.w_heavy if heavy else self.w_light
        d_col = self._expand(jnp.where(frontier, dist, jnp.inf))  # [R*chunk, s]
        val = d_col[self.src_local] + wsel[:, None]
        partial = jax.ops.segment_min(
            val, self.dst_local, num_segments=self.C * self.chunk + 1
        )[: self.C * self.chunk]
        partial = jnp.where(partial > _BIG_DIST, jnp.inf, partial)
        return self._min_fold(partial)

    def _pad_out(self, x_out, fill):
        return jnp.concatenate(
            [x_out, jnp.full((1,) + x_out.shape[1:], fill, x_out.dtype)], axis=0
        )

    def sigma_step(self, sigma_in, dist):
        s_col = self._expand(sigma_in)
        d_col = self._expand(dist)
        d_out = self._pad_out(self._expand_out(dist), jnp.inf)
        cand = d_col[self.src_local] + self.w_full[:, None]
        eq = jnp.isfinite(cand) & (d_out[self.dst_local] == cand)
        contrib = jnp.where(eq, s_col[self.src_local], 0.0)
        partial = jax.ops.segment_sum(
            contrib, self.dst_local, num_segments=self.C * self.chunk + 1
        )[: self.C * self.chunk]
        return self._fold(partial)

    def delta_step(self, g, dist):
        g_col = self._expand(g)
        d_col = self._expand(dist)
        d_out = self._pad_out(self._expand_out(dist), jnp.inf)
        cand = d_out[self.dst_local] + self.w_full[:, None]
        eq = jnp.isfinite(cand) & (d_col[self.src_local] == cand)
        contrib = jnp.where(eq, g_col[self.src_local], 0.0)
        partial = jax.ops.segment_sum(
            contrib, self.dst_local, num_segments=self.C * self.chunk + 1
        )[: self.C * self.chunk]
        return self._fold(partial)


class DistributedWeightedDenseOperator(DistributedOperator):
    """2-D-decomposed weighted operator on a dense weight block.

    The device holds W[rows_i, cols_j] as [C·chunk, R·chunk] float32
    (weight 0 = no edge) — the weighted analogue of
    :class:`DistributedPallasOperator`'s adjacency block; the engine
    kinds pallas / pallas_bf16 / pallas_sparse / pallas_hybrid all route
    their weighted compute through this operator (BCSR/hybrid layouts
    are densified per device cell inside the shard_map body — see
    ``repro.core.distributed``).  Compute is XLA [m, k, s] contractions;
    fused Pallas bucket kernels are the documented follow-up.

    weighted = True
    """

    weighted = True

    def __init__(
        self,
        weight_block,
        *,
        delta: float,
        chunk: int,
        R: int,
        C: int,
        row_axis: str,
        col_axis: str,
        sync_axes: tuple[str, ...] = (),
    ):
        super().__init__(
            None,
            None,
            chunk=chunk,
            R=R,
            C=C,
            row_axis=row_axis,
            col_axis=col_axis,
            overlap="none",
            sync_axes=sync_axes,
        )
        if not (delta > 0):
            raise ValueError(f"bucket width delta must be positive, got {delta}")
        self.delta = float(delta)
        self.weight_block = weight_block.astype(jnp.float32)  # [C*chunk, R*chunk]
        self.mask = self.weight_block > 0
        self.w_light = _bucket_split(self.weight_block, self.delta, heavy=False)
        self.w_heavy = _bucket_split(self.weight_block, self.delta, heavy=True)
        self.w_full = jnp.where(self.mask, self.weight_block, jnp.inf)

    def _expand_out(self, x_owned):
        return jax.lax.all_gather(x_owned, self.col_axis, tiled=True)

    def _min_fold(self, partial):
        folded = jax.lax.pmin(partial, self.col_axis)
        j = jax.lax.axis_index(self.col_axis)
        return jax.lax.dynamic_slice_in_dim(folded, j * self.chunk, self.chunk, axis=0)

    def reduce_min(self, value):
        return jax.lax.pmin(value, self.loop_axes)

    def _local(self, x_col):
        # unweighted reachability semantics (parity/debug only)
        return matmul_f32(self.mask, x_col)

    def relax(self, dist, frontier, heavy):
        wsel = self.w_heavy if heavy else self.w_light
        d_col = self._expand(jnp.where(frontier, dist, jnp.inf))  # [k, s]
        partial = jnp.min(wsel[:, :, None] + d_col[None, :, :], axis=1)  # [m, s]
        return self._min_fold(partial)

    def sigma_step(self, sigma_in, dist):
        s_col = self._expand(sigma_in)
        d_col = self._expand(dist)
        d_out = self._expand_out(dist)  # [m, s]
        cand = d_col[None, :, :] + self.w_full[:, :, None]  # [m, k, s]
        eq = self.mask[:, :, None] & jnp.isfinite(cand) & (d_out[:, None, :] == cand)
        partial = jnp.sum(jnp.where(eq, s_col[None, :, :], 0.0), axis=1)
        return self._fold(partial)

    def delta_step(self, g, dist):
        g_col = self._expand(g)
        d_col = self._expand(dist)
        d_out = self._expand_out(dist)
        cand = d_out[:, None, :] + self.w_full[:, :, None]
        eq = self.mask[:, :, None] & jnp.isfinite(cand) & (d_col[None, :, :] == cand)
        partial = jnp.sum(jnp.where(eq, g_col[None, :, :], 0.0), axis=1)
        return self._fold(partial)
