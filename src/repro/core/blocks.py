"""Block storage data structures (paper §4.3.2) adapted to JAX static shapes.

The paper stores each block as a CSR/COO/CCOO subgraph.  A TPU program
needs *static* shapes, so PGAbB-JAX packs the ground set of blocks into a
small number of flat arrays:

* **Segmented COO** — every edge appears once, sorted by (block id, src,
  dst); ``block_ptr`` delimits each block's contiguous edge segment.  A
  task is a contiguous slice — the direct analog of handing a block-list
  to a kernel.
* **Conformal row slices** — because the partition is conformal (one
  shared cut vector), the portion of vertex ``u``'s adjacency that falls
  in column stripe ``k`` is a *contiguous slice* of the global CSR row.
  ``row_block_ptr[u, k]`` gives its start; this replaces per-block CSR
  materialization and is exactly the "reasoning" benefit the paper claims
  for conformal partitioning (§4.3).
* **Dense bitmap tiles** — blocks selected by the scheduler's density
  cut-off are additionally materialized as 0/1 tiles of a fixed
  ``tile_dim`` so the MXU path (Pallas matmul kernels) can run them.
  This is the K_D representation; its VMEM footprint is bounded the way
  block-lists bound GPU copies in the paper.

All arrays are plain numpy here; ``device_arrays`` converts what an
algorithm needs to jnp once, up front (the engine hands them to jitted
kernels).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import cached_property

import numpy as np

from .graph import Graph
from .partition import Layout, make_layout

__all__ = ["BlockStore", "build_block_store"]


@dataclass
class BlockStore:
    graph: Graph
    layout: Layout

    # --- segmented COO (sorted by block, then src, then dst) ---
    src: np.ndarray          # (m,) int32 global source ids
    dst: np.ndarray          # (m,) int32 global dest ids
    edge_block: np.ndarray   # (m,) int32 block id of each edge
    block_ptr: np.ndarray    # (nb+1,) int64 edge segment offsets per block id

    # --- conformal row slicing over the (degree-ordered) global CSR ---
    indptr: np.ndarray       # (n+1,) int64
    indices: np.ndarray      # (m,) int32 sorted adjacency
    row_block_ptr: np.ndarray  # (n, p+1) int64: indptr[u] + offset of stripe k

    # --- dense bitmap tiles (filled by the scheduler's dense selection) ---
    tile_dim: int = 0
    tile_block_ids: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int32))
    tiles: np.ndarray = field(default_factory=lambda: np.zeros((0, 0, 0), np.float32))
    tile_row_start: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    tile_col_start: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))

    @property
    def n(self) -> int:
        return self.graph.n

    @property
    def m(self) -> int:
        return int(self.src.shape[0])

    @property
    def p(self) -> int:
        return self.layout.p

    @cached_property
    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr).astype(np.int64)

    def block_edges(self, block_id: int) -> tuple[np.ndarray, np.ndarray]:
        s, e = self.block_ptr[block_id], self.block_ptr[block_id + 1]
        return self.src[s:e], self.dst[s:e]

    def block_density(self, block_id: int) -> float:
        i, j = divmod(block_id, self.p)
        r = self.layout.cuts[i + 1] - self.layout.cuts[i]
        c = self.layout.cuts[j + 1] - self.layout.cuts[j]
        e = self.block_ptr[block_id + 1] - self.block_ptr[block_id]
        return float(e) / float(max(r * c, 1))

    def block_range(self, block_id: int) -> tuple[int, int]:
        i, j = divmod(block_id, self.p)
        return (
            int(self.layout.cuts[i + 1] - self.layout.cuts[i]),
            int(self.layout.cuts[j + 1] - self.layout.cuts[j]),
        )

    # ------------------------------------------------------------------
    def materialize_tiles(self, block_ids: np.ndarray, tile_dim: int) -> None:
        """Pack the selected blocks as dense 0/1 tiles of shape (tile_dim²).

        Blocks whose vertex ranges exceed ``tile_dim`` are the caller's
        bug — the scheduler only selects blocks that fit (the analog of
        the paper's "blocks of a single block-list fit device memory").
        """
        block_ids = np.asarray(block_ids, dtype=np.int32)
        nd = block_ids.shape[0]
        tiles = np.zeros((nd, tile_dim, tile_dim), dtype=np.float32)
        row_start = np.zeros(nd, dtype=np.int64)
        col_start = np.zeros(nd, dtype=np.int64)
        for t, b in enumerate(block_ids):
            i, j = divmod(int(b), self.p)
            r0, c0 = self.layout.cuts[i], self.layout.cuts[j]
            rr, cc = self.block_range(int(b))
            if rr > tile_dim or cc > tile_dim:
                raise ValueError(
                    f"block {b} range ({rr},{cc}) exceeds tile_dim {tile_dim}"
                )
            es, ed = self.block_edges(int(b))
            tiles[t, es - r0, ed - c0] = 1.0
            row_start[t], col_start[t] = r0, c0
        self.tile_dim = tile_dim
        self.tile_block_ids = block_ids
        self.tiles = tiles
        self.tile_row_start = row_start
        self.tile_col_start = col_start

    # ------------------------------------------------------------------
    def edge_segments(self, block_ids: np.ndarray) -> list[tuple[int, int]]:
        """Coalesced ``[start, end)`` edge ranges covering ``block_ids``.

        Blocks are contiguous in the segmented COO, so a wave whose
        blocks are consecutive ids collapses to a single slice — the
        "one copy per block-list" staging property of the paper.  Input
        order is ignored; ranges come back sorted and merged.
        """
        ids = np.unique(np.asarray(block_ids, dtype=np.int64))
        out: list[tuple[int, int]] = []
        for b in ids:
            s, e = int(self.block_ptr[b]), int(self.block_ptr[b + 1])
            if s == e:
                continue
            if out and out[-1][1] == s:
                out[-1] = (out[-1][0], e)
            else:
                out.append((s, e))
        return out

    def csr_slices(
        self, block_ids: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, list[tuple[int, int]]]:
        """Conformal CSR row slices covering ``block_ids`` — the per-wave
        CSR staging unit of the streaming executor.

        Because the partition is conformal, the adjacency a block (i, j)
        contributes is, for every row ``u`` in stripe ``i``, the
        contiguous slice ``indices[row_block_ptr[u, j] :
        row_block_ptr[u, j+1]]``.  This method concatenates exactly
        those slices (rows ascending, stripes ascending within a row)
        and returns

        * ``indices_slice`` — the staged adjacency (int32), holding only
          the selected blocks' entries;
        * ``row_block_ptr`` — rebased ``(n, p+1)`` map: for a selected
          ``(u, k)``, ``indices_slice[rbp[u, k] : rbp[u, k+1]]`` equals
          the same slice of the global CSR.  Unselected ``(u, k)``
          entries collapse to zero-length slices;
        * ``indptr`` — rebased ``(n+1,)``: start of each row's *staged*
          adjacency (``diff`` gives staged — not global — degrees);
        * ``segments`` — the coalesced ``[start, end)`` *global* index
          ranges gathered, for staging diagnostics (few segments when
          the selected blocks are contiguous).
        """
        p = self.p
        n = self.n
        rbp = self.row_block_ptr
        ids = np.unique(np.asarray(block_ids, dtype=np.int64))
        touched = np.zeros((p, p), dtype=bool)
        if ids.size:
            gi, gj = np.divmod(ids, p)
            touched[gi, gj] = True
        stripe_of_row = np.repeat(np.arange(p), np.diff(self.layout.cuts))
        touched_row = touched[stripe_of_row]            # (n, p)
        seg_len = rbp[:, 1:] - rbp[:, :-1]              # (n, p)
        lens = np.where(touched_row, seg_len, 0).ravel()
        csum = np.concatenate([[0], np.cumsum(lens)])   # (n*p + 1,)
        new_rbp = np.empty_like(rbp)
        new_rbp[:, :p] = csum[:-1].reshape(n, p)
        new_rbp[:, p] = csum[p::p] if n else 0
        new_indptr = np.concatenate([new_rbp[:, 0], csum[-1:]])
        mask = lens > 0
        starts_g = rbp[:, :-1].ravel()[mask]
        ends_g = starts_g + lens[mask]
        if starts_g.size:
            brk = np.flatnonzero(starts_g[1:] != ends_g[:-1]) + 1
            seg_s = starts_g[np.concatenate([[0], brk])]
            seg_e = ends_g[np.concatenate([brk - 1, [starts_g.size - 1]])]
            idx = np.concatenate(
                [np.arange(s, e, dtype=np.int64) for s, e in zip(seg_s, seg_e)]
            )
            sliced = self.indices[idx]
            segments = list(zip(seg_s.tolist(), seg_e.tolist()))
        else:
            sliced = np.zeros(0, np.int32)
            segments = []
        return sliced.astype(np.int32), new_rbp, new_indptr, segments

    def tile_subset(
        self, block_ids: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(tiles, row_start, col_start) for ``block_ids`` out of the
        materialized tile set — the per-wave dense staging unit.  All
        requested blocks must already be materialized."""
        ids = np.asarray(block_ids, dtype=np.int32)
        pos_of = {int(b): i for i, b in enumerate(self.tile_block_ids)}
        try:
            pos = np.asarray([pos_of[int(b)] for b in ids], dtype=np.int64)
        except KeyError as e:  # pragma: no cover — scheduler bug guard
            raise ValueError(f"block {e} has no materialized tile") from e
        return self.tiles[pos], self.tile_row_start[pos], self.tile_col_start[pos]

    # ------------------------------------------------------------------
    def device_arrays(self, *, with_src: bool = True) -> dict:
        """jnp views of the store for jitted kernels (lazy import keeps the
        host-side path numpy-only); ``with_src=False`` leaves ``src`` on
        the host."""
        import jax.numpy as jnp

        out = dict(
            dst=jnp.asarray(self.dst),
            edge_block=jnp.asarray(self.edge_block),
            indptr=jnp.asarray(self.indptr),
            indices=jnp.asarray(self.indices),
            degrees=jnp.asarray(self.degrees),
            row_block_ptr=jnp.asarray(self.row_block_ptr),
        )
        if with_src:
            out["src"] = jnp.asarray(self.src)
        if self.tile_block_ids.size:
            out.update(
                tiles=jnp.asarray(self.tiles),
                tile_row_start=jnp.asarray(self.tile_row_start),
                tile_col_start=jnp.asarray(self.tile_col_start),
            )
        return out


def build_block_store(g: Graph, p: int, *, order: str = "row_major") -> BlockStore:
    """Partition ``g`` with the symmetric rectilinear partitioner and pack blocks."""
    layout = make_layout(g, p, order=order)
    src, dst = g.coo()
    src = src.astype(np.int64)
    dst64 = dst.astype(np.int64)
    bi = np.searchsorted(layout.cuts, src, side="right") - 1
    bj = np.searchsorted(layout.cuts, dst64, side="right") - 1
    bid = (bi * p + bj).astype(np.int64)
    # sort by (block, src, dst) — cheap radix via linearization
    key = (bid * g.n + src) * g.n + dst64
    order_idx = np.argsort(key, kind="stable")
    src_s = src[order_idx].astype(np.int32)
    dst_s = dst64[order_idx].astype(np.int32)
    bid_s = bid[order_idx].astype(np.int32)
    nb = p * p
    block_ptr = np.zeros(nb + 1, dtype=np.int64)
    np.add.at(block_ptr, bid_s + 1, 1)
    np.cumsum(block_ptr, out=block_ptr)

    # conformal row slicing: offsets of each column stripe inside each CSR row.
    # counts[u, k] = #neighbors of u in column stripe k; prefix over k gives
    # the slice starts.  O(m) vectorized — no per-row searchsorted loop.
    row_block_ptr = np.empty((g.n, p + 1), dtype=np.int64)
    row_block_ptr[:, 0] = g.indptr[:-1]
    if g.m:
        csr_src, _ = g.coo()
        stripe = np.searchsorted(layout.cuts, g.indices.astype(np.int64),
                                 side="right") - 1
        counts = np.zeros((g.n, p), dtype=np.int64)
        np.add.at(counts, (csr_src.astype(np.int64), stripe), 1)
        np.cumsum(counts, axis=1, out=counts)
        row_block_ptr[:, 1:] = g.indptr[:-1, None] + counts
    else:
        row_block_ptr[:, 1:] = g.indptr[:-1, None]

    return BlockStore(
        graph=g,
        layout=layout,
        src=src_s,
        dst=dst_s,
        edge_block=bid_s,
        block_ptr=block_ptr,
        indptr=g.indptr.astype(np.int64),
        indices=g.indices.astype(np.int32),
        row_block_ptr=row_block_ptr,
    )
