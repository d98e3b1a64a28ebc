"""Sparse Ising problems: padded neighbor lists + greedy graph coloring.

PASS's fine-grained parallelism comes from *locality* — each p-bit couples
only to its graph neighbors — yet a dense (n, n) coupling matrix makes every
per-event and per-sweep cost O(n). `SparseIsing` stores the same model (the
conventions of `repro.core.ising`: E = sum_{i<j} J_ij s_i s_j + b.s,
p ∝ e^{-E}) as a padded neighbor list:

    nbr_idx: (n, max_deg) int32   — neighbor site indices
    nbr_w:   (n, max_deg) float32 — coupling J_ij to each neighbor
    deg:     (n,) int32           — true degree of each site

Slots k >= deg[i] are PADDING: they point at the site itself (a valid index,
so gathers never go out of bounds) and carry weight 0 (so vectorized
gathers AND duplicate-target scatter-adds are both correct without masking).
Fixed max_deg keeps every array rectangular — vmap/Pallas-friendly, no
ragged CSR offsets to marshal.

Each undirected edge (i, j, w) is stored twice — once in row i and once in
row j — so `local_fields` is one gather and `energy` halves the pair sum,
exactly mirroring the dense symmetric-J convention.

`color_masks` (optional, (n_colors, n) bool) partitions the sites into
independent sets via greedy graph coloring (`color_graph`): same-color
sites share no edge, so their conditionals are independent — the exact
parallel (chromatic) Gibbs structure sparse Ising machines exploit, here
generalized beyond the king's lattice to arbitrary graphs.

Complexities (the point of this module):

    local_fields      O(n * max_deg)   (vs dense O(n^2))
    delta_fields      O(max_deg)       (vs dense O(n) row add)
    energy            O(n * max_deg)

combined with `event_tree.update_many`, a CTMC flip event costs
O(max_deg * log n) instead of the dense O(n) rebuild.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ising import DenseIsing


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("nbr_idx", "nbr_w", "deg", "b", "color_masks"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class SparseIsing:
    """Ising problem over a sparse graph in padded neighbor-list layout.

    Attributes:
      nbr_idx: (n, max_deg) int32 neighbor indices; padded slots = own index.
      nbr_w:   (n, max_deg) float32 couplings; padded slots = 0.
      deg:     (n,) int32 true degrees.
      b:       (n,) float32 biases.
      color_masks: optional (n_colors, n) bool independent-set partition.
    """

    nbr_idx: jax.Array
    nbr_w: jax.Array
    deg: jax.Array
    b: jax.Array
    color_masks: Optional[jax.Array] = None

    @property
    def n(self) -> int:
        """Number of sites."""
        return self.nbr_idx.shape[-2]

    @property
    def max_deg(self) -> int:
        """Padded neighbor-list width."""
        return self.nbr_idx.shape[-1]

    @property
    def n_colors(self) -> int:
        """Number of color classes (0 when uncolored)."""
        if self.color_masks is None:
            raise ValueError("problem has no color_masks (built with color=False)")
        return self.color_masks.shape[0]

    def neighbor_sum(self, s: jax.Array) -> jax.Array:
        """sum_j J_ij s_j via one padded gather. s: (..., n) ±1 -> (..., n).

        Padded slots gather the site's own spin but multiply by weight 0.
        The slots are added in `slot_sum`'s fixed order, the order the
        Pallas sweep kernel adds them in, so ref/kernel paths agree
        bit-for-bit in interpret mode.
        """
        s = s.astype(self.nbr_w.dtype)
        gathered = jnp.take(s, self.nbr_idx, axis=-1)  # (..., n, max_deg)
        return slot_sum(self.nbr_w, gathered)

    def local_fields(self, s: jax.Array) -> jax.Array:
        """h_i = sum_j J_ij s_j + b_i (batched)."""
        return self.neighbor_sum(s) + self.b

    def energy(self, s: jax.Array) -> jax.Array:
        """E(s); each undirected edge is stored twice, so halve the pair sum."""
        s = s.astype(self.nbr_w.dtype)
        pair = 0.5 * jnp.sum(s * self.neighbor_sum(s), axis=-1)
        field = jnp.sum(self.b * s, axis=-1)
        return pair + field

    def delta_fields(self, s: jax.Array, i: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Field updates caused by flipping site i: O(max_deg).

        Returns (idx, dh), both (max_deg,): after s_i -> -s_i, apply
        `h = h.at[idx].add(dh)`. Padded slots contribute dh = 0 at idx = i,
        so the scatter-add needs no degree mask. h_i itself is unchanged
        (no self-coupling).
        """
        return self.nbr_idx[i], self.nbr_w[i] * (-2.0 * s[i])

    def to_dense(self) -> DenseIsing:
        """Materialize the (n, n) symmetric coupling matrix (host-side)."""
        n, md = self.n, self.max_deg
        J = np.zeros((n, n), np.float64)
        rows = np.repeat(np.arange(n), md)
        np.add.at(
            J,
            (rows, np.asarray(self.nbr_idx).reshape(-1)),
            np.asarray(self.nbr_w, np.float64).reshape(-1),
        )  # padded slots add 0 on the diagonal — harmless
        return DenseIsing(J=jnp.asarray(J, jnp.float32), b=jnp.asarray(self.b))

    @classmethod
    def from_dense(
        cls,
        problem: DenseIsing,
        threshold: float = 0.0,
        max_deg: Optional[int] = None,
        color: bool = True,
    ) -> "SparseIsing":
        """Neighbor-list form of a DenseIsing, keeping |J_ij| > threshold.

        max_deg defaults to the largest resulting row degree; passing a
        larger value pads further (useful to align layouts across
        instances). Raises if any row degree exceeds a given max_deg.
        """
        J = np.asarray(problem.J)
        if J.ndim != 2 or J.shape[0] != J.shape[1]:
            raise ValueError(f"J must be square, got shape {J.shape}")
        keep = np.abs(J) > threshold
        np.fill_diagonal(keep, False)
        edges = [
            (int(i), int(j), float(J[i, j]))
            for i, j in zip(*np.nonzero(np.triu(keep, k=1)))
        ]
        return cls.from_edges(
            J.shape[0], edges, b=np.asarray(problem.b), max_deg=max_deg, color=color
        )

    @classmethod
    def from_edges(
        cls,
        n: int,
        edges: Iterable[tuple[int, int, float]],
        b=None,
        max_deg: Optional[int] = None,
        color: bool = True,
        color_masks=None,
    ) -> "SparseIsing":
        """Build from an undirected edge list [(i, j, w), ...], each edge once.

        `color_masks` supplies a known coloring (e.g. the king 4-coloring);
        otherwise `color=True` runs greedy `color_graph` at construction.
        """
        adj: list[list[tuple[int, float]]] = [[] for _ in range(n)]
        for i, j, w in edges:
            i, j = int(i), int(j)
            if i == j:
                raise ValueError(f"self-loop on site {i} (zero-diagonal convention)")
            if not (0 <= i < n and 0 <= j < n):
                raise ValueError(f"edge ({i}, {j}) out of range for n={n}")
            adj[i].append((j, float(w)))
            adj[j].append((i, float(w)))
        deg = np.asarray([len(a) for a in adj], np.int32)
        md = max(1, int(deg.max()) if n else 1)
        if max_deg is not None:
            if max_deg < md:
                raise ValueError(f"max_deg={max_deg} < largest row degree {md}")
            md = max_deg
        # padding convention: own index, zero weight
        nbr_idx = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, md))
        nbr_w = np.zeros((n, md), np.float32)
        for i, a in enumerate(adj):
            for k, (j, w) in enumerate(a):
                nbr_idx[i, k] = j
                nbr_w[i, k] = w
        if color_masks is None and color:
            color_masks = colors_to_masks(color_graph(nbr_idx, deg))
        b = np.zeros((n,), np.float32) if b is None else np.asarray(b, np.float32)
        return cls(
            nbr_idx=jnp.asarray(nbr_idx),
            nbr_w=jnp.asarray(nbr_w),
            deg=jnp.asarray(deg),
            b=jnp.asarray(b),
            color_masks=None if color_masks is None else jnp.asarray(color_masks),
        )

    def validate(self) -> None:
        """Raise ValueError on a malformed instance (host-side, for
        constructors and tests — not jit-traceable)."""
        idx = np.asarray(self.nbr_idx)
        w = np.asarray(self.nbr_w)
        deg = np.asarray(self.deg)
        n, md = idx.shape
        if w.shape != (n, md) or deg.shape != (n,) or np.asarray(self.b).shape != (n,):
            raise ValueError(
                f"inconsistent shapes: nbr_idx {idx.shape}, nbr_w {w.shape}, "
                f"deg {deg.shape}, b {np.asarray(self.b).shape}"
            )
        if idx.min(initial=0) < 0 or idx.max(initial=0) >= n:
            raise ValueError(f"nbr_idx out of range [0, {n})")
        if not np.all(np.isfinite(w)) or not np.all(np.isfinite(np.asarray(self.b))):
            raise ValueError(
                "nbr_w/b must be finite: NaN/Inf couplings would silently "
                "poison every recorded energy and the downstream TTS fits"
            )
        slot = np.arange(md)[None, :]
        pad = slot >= deg[:, None]
        if np.any(w[pad] != 0.0):
            raise ValueError("padded neighbor slots must carry zero weight")
        if np.any(idx[~pad] == np.arange(n)[:, None].repeat(md, 1)[~pad]):
            raise ValueError("self-coupling in a live neighbor slot (zero-diagonal convention)")
        J = np.asarray(self.to_dense().J)
        if not np.allclose(J, J.T, atol=1e-6):
            raise ValueError(
                "couplings are not symmetric: every edge (i, j, w) must be "
                "stored in BOTH row i and row j"
            )
        if self.color_masks is not None:
            masks = np.asarray(self.color_masks)
            if masks.shape[-1] != n:
                raise ValueError(f"color_masks last dim {masks.shape[-1]} != n {n}")
            if not np.all(masks.sum(axis=0) == 1):
                raise ValueError("color_masks must assign each site exactly one color")
            colors = masks.argmax(axis=0)
            live = ~pad
            if np.any(colors[idx][live] == colors[:, None].repeat(md, 1)[live]):
                raise ValueError("color_masks is not a proper coloring (edge within a color)")


def slot_sum(nbr_w: jax.Array, gathered: jax.Array) -> jax.Array:
    """sum_k nbr_w[:, k] * gathered[..., k], added slot by slot from k = 0.

    The order is written out because a vectorized reduce leaves it to XLA,
    which may pick a different one in each program; with it fixed, the ref
    path, the jnp oracle and the Pallas kernels round alike.
    """
    acc = nbr_w[:, 0] * gathered[..., 0]
    for k in range(1, nbr_w.shape[-1]):
        acc = acc + nbr_w[:, k] * gathered[..., k]
    return acc


def color_graph(nbr_idx: np.ndarray, deg: np.ndarray) -> np.ndarray:
    """Greedy graph coloring (first-fit in site order): (n,) int colors.

    Uses at most max_deg + 1 colors; on a 3-regular graph that is <= 4, and
    structured graphs (lattices, rings) typically land on their chromatic
    number. Host-side — runs once at problem construction.
    """
    idx = np.asarray(nbr_idx)
    deg = np.asarray(deg)
    n = idx.shape[0]
    colors = np.full(n, -1, np.int64)
    for i in range(n):
        used = {int(colors[j]) for j in idx[i, : deg[i]] if colors[j] >= 0}
        c = 0
        while c in used:
            c += 1
        colors[i] = c
    return colors


def colors_to_masks(colors: np.ndarray) -> np.ndarray:
    """(n,) int colors -> (n_colors, n) bool independent-set masks."""
    colors = np.asarray(colors)
    n_colors = int(colors.max()) + 1 if colors.size else 1
    return np.stack([colors == c for c in range(n_colors)])
