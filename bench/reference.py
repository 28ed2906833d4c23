"""Plain reference: Brandes' dependencies of a batch of roots, in jnp.

Level-synchronous BFS and dependency sweep over the benchmark's own CSR
(:func:`bench.graphs.csr`), one column per root, float32, with the
adjacency product written as a gather of neighbour rows and a segment sum
— no kernel, no tiles, no layout of the program's.  It runs on the chip
after the window, once the program's state is freed.

``precision="highest"`` adds the neighbour rows exactly in float32, as
the program's ``Precision.HIGHEST`` contractions promise.  ``"high"`` is
the control: it first rounds the right operand of every product to the
16-bit mantissa that ``Precision.HIGH`` (three bf16 passes) keeps of it,
which is exactly what those passes compute against a 0/1 adjacency.
"""
from __future__ import annotations

import numpy as np

__all__ = ["PRECISIONS", "Reference"]

PRECISIONS = ("highest", "high")


class Reference:
    """Σ over roots of δ_s(v) (δ_s(s) = 0) for batches of ``batch`` roots."""

    def __init__(self, row_ptr: np.ndarray, col: np.ndarray, *, batch: int = 128,
                 precision: str = "highest", device=None):
        import jax
        import jax.numpy as jnp

        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
        n = row_ptr.shape[0] - 1
        self.n = n
        self.batch = int(batch)
        row = np.repeat(np.arange(n, dtype=np.int32), np.diff(row_ptr))
        put = (lambda a: jax.device_put(a, device)) if device is not None else jnp.asarray
        row_d, col_d = put(row), put(np.asarray(col, np.int32))

        def to_bf16(x):
            # round to nearest even at bf16's 8-bit mantissa, in integer
            # arithmetic: XLA may drop an f32 -> bf16 -> f32 convert pair
            u = jax.lax.bitcast_convert_type(x, jnp.uint32)
            u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
            return jax.lax.bitcast_convert_type(u, jnp.float32)

        def operand(x):
            if precision == "highest":
                return x
            hi = to_bf16(x)
            return hi + to_bf16(x - hi)

        def spmm(x):
            # (A @ x)[v] = Σ_{u ∈ N(v)} x[u]
            return jax.ops.segment_sum(
                operand(x)[col_d], row_d, num_segments=n, indices_are_sorted=True
            )

        def dependencies(roots):
            b = roots.shape[0]
            valid = roots >= 0
            cols = jnp.arange(b)
            r = jnp.where(valid, roots, 0)
            depth = jnp.full((n, b), -1, jnp.int32).at[r, cols].max(
                jnp.where(valid, 0, -1))
            sigma = jnp.zeros((n, b), jnp.float32).at[r, cols].max(
                jnp.where(valid, 1.0, 0.0))

            def fwd_body(c):
                lvl, sigma, depth, _ = c
                t = spmm(jnp.where(depth == lvl - 1, sigma, 0.0))
                new = (t > 0) & (depth < 0)
                return (lvl + 1, sigma + jnp.where(new, t, 0.0),
                        jnp.where(new, lvl, depth), new.any())

            lvl, sigma, depth, _ = jax.lax.while_loop(
                lambda c: c[3], fwd_body, (jnp.int32(1), sigma, depth, True))
            deepest = lvl - 2

            def bwd_body(c):
                lvl, delta = c
                safe = jnp.where(sigma > 0, sigma, 1.0)
                g = jnp.where(depth == lvl, (1.0 + delta) / safe, 0.0)
                t = spmm(g)
                return lvl - 1, delta + jnp.where(depth == lvl - 1, sigma * t, 0.0)

            _, delta = jax.lax.while_loop(
                lambda c: c[0] >= 1, bwd_body, (deepest, jnp.zeros_like(sigma)))
            bc = jnp.where(depth > 0, delta, 0.0).sum(axis=1)
            return bc, deepest

        #: jitted (roots i32 [b], -1 = padding) -> (Σ δ_s f32 [n], deepest level)
        self.dependencies = jax.jit(dependencies)

    def contributions(self, roots: np.ndarray) -> tuple[np.ndarray, list[int]]:
        """(Σ_s δ_s as float64 [n], levels of each batch) over ``roots``."""
        roots = np.asarray(roots, np.int32)
        total = np.zeros(self.n, np.float64)
        levels = []
        for start in range(0, roots.size, self.batch):
            chunk = np.full(self.batch, -1, np.int32)
            part = roots[start:start + self.batch]
            chunk[:part.size] = part
            bc, deepest = self.dependencies(chunk)
            total += np.asarray(bc, np.float64)
            levels.append(int(deepest))
        return total, levels
