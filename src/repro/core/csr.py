"""Sparse CSR graph container — lifting the paper's adjacency-matrix ceiling.

The paper's own §V diagnosis: the dense adjacency matrix burns O(n²) memory
(Table II's 40,000-vertex graph has only 120k edges but needs a 1.6 GB
matrix) and the dense relax sweep does O(n²) work per iteration regardless
of density.  This module stores edges in O(n + m):

* **CSR over incoming edges** (i.e. CSR of the adjacency transpose): every
  relax engine asks "which u reach v?" — ``new[v] = min(dist[v],
  min_{(u,w)->v} dist[u] + w)`` — so row v holds v's *incoming* arcs.
  For undirected graphs both orientations are stored, exactly like the
  symmetric dense matrix.

* **Padded ELL** (``ell()``): the TPU-friendly fixed-width view, rows padded
  to a common width K with (index 0, weight INF) sentinels that can never
  win a min.  This is what the Pallas kernel (kernels/csr_relax) consumes —
  fixed row width means static block shapes, the same trick the paper's
  padding plays for its process count (§III-B.2).

This file is deliberately numpy-only (container layer); device-array
staging lives in core/bellman_csr.py.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.graph import Graph, INF, random_edge_list


def _freeze(*arrays: np.ndarray):
    """Mark arrays read-only (see CsrGraph.__post_init__'s immutability
    contract): memoized views are shared across callers, so the builders
    freeze everything they cache."""
    for a in arrays:
        a.flags.writeable = False
    return arrays if len(arrays) > 1 else arrays[0]


def _row_rank(indptr: np.ndarray) -> np.ndarray:
    """(nnz,) int64 position of each stored arc within its row."""
    deg = np.diff(indptr)
    return np.arange(int(indptr[-1])) - np.repeat(indptr[:-1], deg)


def _build_ell(
    indptr: np.ndarray, ids: np.ndarray, weights: np.ndarray,
    n: int, width_multiple: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Pack one CSR orientation into padded ELL: (n, K) int32 ids and
    (n, K) float32 weights, K = max row degree rounded up to
    ``width_multiple`` (min one lane group even for edgeless graphs).
    Padding slots are (0, INF): an INF candidate can never win a min, the
    same unreachable-padding argument as the paper's padded matrix.
    Shared by the incoming (``ell``) and outgoing (``out_ell``) views so
    the padding rules cannot diverge."""
    deg = np.diff(indptr)
    max_deg = int(deg.max()) if deg.size else 0
    K = -(-max(max_deg, 1) // width_multiple) * width_multiple
    idx = np.zeros((n, K), np.int32)
    w = np.full((n, K), INF, np.float32)
    rows = np.repeat(np.arange(n), deg)
    pos = _row_rank(indptr)
    idx[rows, pos] = ids
    w[rows, pos] = weights
    return _freeze(idx, w)


def _masked_row_counts(mask: np.ndarray, indptr: np.ndarray,
                       n: int) -> np.ndarray:
    """Per-row count of True arcs under a per-arc ``mask``, for rows
    delimited by ``indptr``.  ``np.add.reduceat`` mishandles empty rows
    (it returns the element AT the boundary, and raises outright when a
    trailing empty row's boundary equals len(mask)), so empty rows are
    clipped and zeroed explicitly."""
    deg = np.diff(indptr)
    if mask.size == 0:
        return np.zeros(n, np.int64)
    starts = np.minimum(np.asarray(indptr[:-1], np.int64), mask.size - 1)
    return np.where(deg > 0, np.add.reduceat(mask, starts), 0).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class CsrGraph:
    """Incoming-edge CSR graph.

    indptr:  (n+1,) int64 — row v's incoming arcs live in
             ``[indptr[v], indptr[v+1])``; rows sorted by (dst, src).
    indices: (nnz,) int32 — source vertex u of each stored arc.
    weights: (nnz,) float32.
    n:        vertex count.
    directed: as in Graph; undirected graphs store both orientations, so
              ``num_edges == nnz // 2`` there.
    """

    indptr: np.ndarray
    indices: np.ndarray
    weights: np.ndarray
    n: int
    directed: bool = False

    def __post_init__(self):
        # Immutability contract: every derived view (dst_ids / ell /
        # out_csr / out_ell / partitioned / to_dense) is memoized per
        # instance and SHARED by every later caller — serve/registry.py
        # pins them on long-lived handles and dynamic/overlay.py layers
        # mutable overlays on top of a frozen base.  An in-place write to
        # any field array would silently corrupt whichever memoized views
        # were already built from it, so the arrays are marked read-only
        # here (and the memoized views are frozen by their builders).
        # Mutation goes through dynamic.DynamicGraph, which copies what
        # it needs; numpy raises ValueError on any write attempt below.
        for arr in (self.indptr, self.indices, self.weights):
            arr.flags.writeable = False

    @property
    def nnz(self) -> int:
        """Stored arcs (both orientations for undirected graphs)."""
        return int(self.indices.shape[0])

    @property
    def num_edges(self) -> int:
        # matches Graph.num_edges (which counts finite adj > 0): zero- or
        # INF-weight arcs are stored and relaxed but not counted as edges.
        cnt = int((np.isfinite(self.weights) & (self.weights > 0)).sum())
        return cnt if self.directed else cnt // 2

    @property
    def nbytes(self) -> int:
        return self.indptr.nbytes + self.indices.nbytes + self.weights.nbytes

    def _memo(self, key, build):
        # derived views are memoized per instance; writing through __dict__
        # sidesteps the frozen-dataclass __setattr__ (fields stay immutable,
        # dataclass __eq__ ignores non-field entries).
        if key not in self.__dict__:
            self.__dict__[key] = build()
        return self.__dict__[key]

    def dst_ids(self) -> np.ndarray:
        """(nnz,) int32 destination id of each stored arc (segment ids for
        the segment-min relax sweep); ascending by construction.  Memoized."""
        def build():
            deg = np.diff(self.indptr)
            return _freeze(np.repeat(np.arange(self.n, dtype=np.int32), deg))
        return self._memo("_dst_ids", build)

    def in_runs(self) -> tuple[np.ndarray, np.ndarray, int]:
        """The incoming arcs as one run per destination, for a segmented
        scan: ``(rank, last, steps)``.  ``rank`` (nnz,) int32 is each
        arc's position in its run; ``last`` (n,) int32 the index of each
        vertex's final arc, nnz for a vertex with none; ``steps`` =
        ceil(log2(max in-degree)), the doubling steps that span the
        longest run (0 when no run holds two arcs).  Memoized."""
        def build():
            deg = np.diff(self.indptr)
            rank = _row_rank(self.indptr).astype(np.int32)
            last = np.where(deg > 0, self.indptr[1:] - 1,
                            self.nnz).astype(np.int32)
            steps = max(int(deg.max(initial=0)) - 1, 0).bit_length()
            return (*_freeze(rank, last), steps)
        return self._memo("_in_runs", build)

    def ell(self, width_multiple: int = 8) -> tuple[np.ndarray, np.ndarray]:
        """Padded-ELL view: (n, K) int32 indices and (n, K) float32 weights.

        K = max in-degree rounded up to ``width_multiple`` (min one lane
        group even for edgeless graphs).  Padding slots are (0, INF):
        ``dist[0] + INF == INF`` never beats a real candidate, the same
        unreachable-padding argument as the paper's padded matrix.
        Memoized per width_multiple.

        Note this view is O(n · max_in_degree), not O(n + m): on heavily
        skewed degree distributions (a hub with ~n incoming arcs) it
        re-approaches the dense matrix — the flat CSR arrays (and the
        ``bellman_csr`` engine) stay O(n + m) regardless.
        """
        def build():
            return _build_ell(self.indptr, self.indices, self.weights,
                              self.n, width_multiple)
        return self._memo(("_ell", width_multiple), build)

    def out_csr(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Outgoing-edge CSR view: ``(out_indptr, out_dst, out_w)``.

        Row u holds u's *outgoing* arcs — ``out_dst[out_indptr[u] :
        out_indptr[u+1]]`` are the vertices u reaches — sorted by
        (src, dst).  The stored container is incoming-only (rows = "who
        reaches v?", the pull formulation every whole-graph sweep wants);
        frontier-driven relaxation asks the opposite question ("whom does
        the improved vertex u push to?"), so this is the transpose,
        built once in O(m log m) and memoized like the other views.
        """
        def build():
            src = np.asarray(self.indices, np.int64)
            dst = self.dst_ids().astype(np.int64)
            order = np.lexsort((dst, src))              # by src, then dst
            out_dst = dst[order].astype(np.int32)
            out_w = np.asarray(self.weights)[order]
            counts = np.bincount(src, minlength=self.n)
            indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
            return _freeze(indptr, out_dst, out_w)
        return self._memo("_out_csr", build)

    def out_ell(self, width_multiple: int = 8) -> tuple[np.ndarray, np.ndarray]:
        """Padded-ELL view of :meth:`out_csr`: (n, K) int32 destination ids
        and (n, K) float32 weights, K = max *out*-degree rounded up to
        ``width_multiple``.  Padding slots are (0, INF) — an INF candidate
        scatter-min'd into vertex 0 never wins, the push-side twin of
        ``ell()``'s unreachable-padding argument.  Memoized per width.
        """
        def build():
            indptr, out_dst, out_w = self.out_csr()
            return _build_ell(indptr, out_dst, out_w, self.n, width_multiple)
        return self._memo(("_out_ell", width_multiple), build)

    def light_in_ell(
        self, delta: float, width_multiple: int = 8
    ) -> tuple[np.ndarray, np.ndarray]:
        """Padded-ELL view of the *light* incoming arcs (weight <= Δ):
        (n, K_light) int32 source ids and (n, K_light) float32 weights —
        the Δ-stepping light phase's pull operand (core/delta_stepping.py).

        The split is the classic Δ-stepping light/heavy partition
        (Meyer & Sanders; revisited by arXiv 1604.02113): light arcs can
        re-improve labels inside the current Δ-bucket and are iterated to
        a fixpoint, heavy arcs (weight > Δ) can only reach later buckets
        and are relaxed once per bucket — see ``heavy_out_csr`` for the
        other half.  K_light = max light in-degree rounded up to
        ``width_multiple``; padding slots are the usual (0, INF)
        sentinels.  Memoized per (Δ, width): serving solves on a pinned
        handle pay the O(m) split once.
        """
        def build():
            mask = np.asarray(self.weights) <= np.float32(delta)
            ldeg = _masked_row_counts(mask, self.indptr, self.n)
            lip = np.concatenate([[0], np.cumsum(ldeg)]).astype(np.int64)
            return _build_ell(lip, self.indices[mask], self.weights[mask],
                              self.n, width_multiple)
        return self._memo(("_light_in_ell", float(delta), width_multiple),
                          build)

    def heavy_out_csr(
        self, delta: float
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Outgoing-edge CSR restricted to the *heavy* arcs (weight > Δ):
        ``(indptr, dst, w)`` with the same (src, dst) ordering as
        ``out_csr``.  A heavy arc can never land inside the bucket it
        leaves (its weight alone exceeds the bucket width), so Δ-stepping
        relaxes each settled bucket's heavy out-arcs exactly once — a
        push over this view — instead of re-touching them every inner
        iteration.  Complement of ``light_in_ell`` (disjoint by the same
        weight <= Δ test).  Memoized per Δ.
        """
        def build():
            indptr, out_dst, out_w = self.out_csr()
            mask = out_w > np.float32(delta)
            hdeg = _masked_row_counts(mask, indptr, self.n)
            hip = np.concatenate([[0], np.cumsum(hdeg)]).astype(np.int64)
            return _freeze(hip, out_dst[mask], out_w[mask])
        return self._memo(("_heavy_out_csr", float(delta)), build)

    def partitioned(self, nprocs: int, *, pad_multiple: int = 8) -> "CsrPartition":
        """1-D vertex partition of this graph across ``nprocs`` owners —
        the sparse twin of ``Graph.padded(P)`` + column slicing (the
        paper's §III-B.2 partitioning step, at O(m/P) per owner instead
        of O(n²/P)).

        Vertices are padded to ``n_pad = ceil(n / P) * P`` and owner p
        gets the contiguous block ``[p*loc_n, (p+1)*loc_n)``.  Each owner
        stores exactly the arcs *targeting* its owned vertices (the
        incoming-CSR row block), in two per-owner orientations:

        * ``in_*``: sorted by (local dst, src) — the segment-min sweep
          layout (core/sharded_csr.sssp_bellman_csr_sharded);
        * ``out_*``: the same arcs re-sorted by (global src, local dst)
          behind a per-owner CSR over *all* global sources — the
          frontier-push layout (sssp_frontier_sharded): given a frontier
          vertex u, ``out_indptr[p, u] : out_indptr[p, u+1]`` window the
          arcs u sends into p's owned block.

        Blocks are stacked along a leading owner axis and padded to the
        max block nnz (rounded up to ``pad_multiple``) with inert
        sentinel arcs (w = INF, src 0, dst = last local row) so shard_map
        sees one rectangular array per field.  Memoized per (P, pad).
        """
        def build():
            return _partition_csr(self, nprocs, pad_multiple)
        return self._memo(("_part", nprocs, pad_multiple), build)

    @classmethod
    def from_dense(cls, g: Graph) -> "CsrGraph":
        """Capture every finite off-diagonal entry of ``g.adj`` as an arc.

        Uses the full (possibly padded) matrix dimension as the vertex
        count, matching how the dense engines treat a padded Graph.
        """
        adj = np.asarray(g.adj, np.float32)
        n = adj.shape[0]
        mask = np.isfinite(adj)
        np.fill_diagonal(mask, False)
        u, v = np.nonzero(mask)
        order = np.lexsort((u, v))                       # by dst, then src
        src = u[order].astype(np.int32)
        dst = v[order]
        w = adj[u, v][order].astype(np.float32)
        counts = np.bincount(dst, minlength=n)
        indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        return cls(indptr=indptr, indices=src, weights=w, n=n,
                   directed=g.directed)

    def to_dense(self) -> Graph:
        """Materialize the O(n²) matrix (INF off-edges, 0 diagonal).
        Memoized like the other derived views — repeat dense-engine solves
        of one CsrGraph reuse the matrix instead of refilling n² entries."""
        def build():
            adj = np.full((self.n, self.n), INF, dtype=np.float32)
            np.fill_diagonal(adj, 0.0)
            adj[self.indices, self.dst_ids()] = self.weights
            return Graph(adj=_freeze(adj), n=self.n, directed=self.directed)
        return self._memo("_dense", build)


@dataclasses.dataclass(frozen=True)
class CsrPartition:
    """Per-owner row blocks of a :class:`CsrGraph` (see
    ``CsrGraph.partitioned``).  All arrays are numpy, stacked along a
    leading owner axis of size ``nprocs``; device staging lives in
    core/sharded_csr.py.

    in_src:     (P, nnz_max) int32  global source of each arc.
    in_dst_loc: (P, nnz_max) int32  LOCAL destination row, ascending per
                owner (segment ids for the local segment-min); sentinel
                padding uses the last local row so the ascending order
                survives.
    in_w:       (P, nnz_max) f32    weights, INF on padding.
    out_indptr: (P, n_pad + 2) int32  per-owner CSR over global sources:
                row u of owner p windows the arcs u -> (p's owned block).
                One extra trailing row (always empty) absorbs the
                frontier engines' sentinel id n_pad.
    out_dst_loc, out_w: the in_* arcs re-sorted by (src, local dst).
    """

    nprocs: int
    n: int
    n_pad: int
    loc_n: int
    nnz_max: int
    in_src: np.ndarray
    in_dst_loc: np.ndarray
    in_w: np.ndarray
    out_indptr: np.ndarray
    out_dst_loc: np.ndarray
    out_w: np.ndarray

    @property
    def per_device_edge_bytes(self) -> int:
        """Edge-array bytes held by ONE owner (the O(m/P) payload; the
        out_indptr index is O(n) per owner and reported separately)."""
        per = self.nnz_max * (self.in_src.itemsize + self.in_dst_loc.itemsize
                              + self.in_w.itemsize + self.out_dst_loc.itemsize
                              + self.out_w.itemsize)
        return int(per)

    @property
    def per_device_index_bytes(self) -> int:
        return int((self.n_pad + 2) * self.out_indptr.itemsize)

    @property
    def nbytes(self) -> int:
        """Total host bytes of the partition view across ALL owners — what
        serve/registry.py charges against its byte budget when it stages a
        partition (the staged device arrays mirror these buffers 1:1)."""
        return int(self.in_src.nbytes + self.in_dst_loc.nbytes
                   + self.in_w.nbytes + self.out_indptr.nbytes
                   + self.out_dst_loc.nbytes + self.out_w.nbytes)


def _partition_csr(cg: CsrGraph, nprocs: int, pad_multiple: int) -> CsrPartition:
    if nprocs < 1:
        raise ValueError(f"nprocs must be >= 1, got {nprocs}")
    n = cg.n
    loc_n = -(-n // nprocs)
    n_pad = loc_n * nprocs
    dst = cg.dst_ids()                         # ascending => owner-grouped
    # owner p's arcs are the contiguous indptr range of its row block.
    row_edges = np.minimum(np.arange(nprocs + 1) * loc_n, n)
    bounds = np.asarray(cg.indptr)[row_edges]
    blk_nnz = np.diff(bounds)
    nnz_max = int(-(-max(int(blk_nnz.max()) if nprocs else 1, 1)
                    // pad_multiple) * pad_multiple)

    in_src = np.zeros((nprocs, nnz_max), np.int32)
    in_dst_loc = np.full((nprocs, nnz_max), loc_n - 1, np.int32)
    in_w = np.full((nprocs, nnz_max), INF, np.float32)
    out_indptr = np.zeros((nprocs, n_pad + 2), np.int32)
    out_dst_loc = np.zeros((nprocs, nnz_max), np.int32)
    out_w = np.full((nprocs, nnz_max), INF, np.float32)

    for p in range(nprocs):
        lo, hi = int(bounds[p]), int(bounds[p + 1])
        k = hi - lo
        src = np.asarray(cg.indices[lo:hi], np.int32)
        dloc = (dst[lo:hi] - p * loc_n).astype(np.int32)
        w = np.asarray(cg.weights[lo:hi], np.float32)
        in_src[p, :k] = src
        in_dst_loc[p, :k] = dloc
        in_w[p, :k] = w
        order = np.lexsort((dloc, src))        # by src, then local dst
        out_dst_loc[p, :k] = dloc[order]
        out_w[p, :k] = w[order]
        counts = np.bincount(src, minlength=n_pad)
        ptr = np.concatenate([[0], np.cumsum(counts)])
        out_indptr[p, :n_pad + 1] = ptr
        out_indptr[p, n_pad + 1] = ptr[-1]     # sentinel row: zero degree
    return CsrPartition(
        nprocs=nprocs, n=n, n_pad=n_pad, loc_n=loc_n, nnz_max=nnz_max,
        in_src=in_src, in_dst_loc=in_dst_loc, in_w=in_w,
        out_indptr=out_indptr, out_dst_loc=out_dst_loc, out_w=out_w,
    )


def csr_from_edge_list(
    n: int,
    edges: np.ndarray,
    weights: np.ndarray,
    directed: bool = False,
) -> CsrGraph:
    """Build an incoming-edge CSR from an edge list in O(m log m).

    Same semantics as graph.from_edge_list: undirected edges are mirrored,
    self-loops dropped (the diagonal is implicit), and duplicate arcs keep
    the minimum weight.
    """
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    w = np.asarray(weights, np.float32).reshape(-1)
    if edges.size and (edges.min() < 0 or edges.max() >= n):
        # fail fast like the dense sibling's fancy-indexing would; the
        # (dst, src) -> dst*n+src packing below would otherwise silently
        # alias out-of-range ids onto valid arcs.
        raise IndexError(
            f"edge endpoints must be in [0, {n}); got "
            f"[{edges.min()}, {edges.max()}]"
        )
    u, v = edges[:, 0], edges[:, 1]
    if not directed:
        u, v = np.concatenate([u, v]), np.concatenate([v, u])
        w = np.concatenate([w, w])
    keep = u != v
    u, v, w = u[keep], v[keep], w[keep]
    # dedupe (dst, src) pairs keeping the min weight, sorted by (dst, src).
    key = v * np.int64(n) + u
    uniq, inv = np.unique(key, return_inverse=True)
    wmin = np.full(uniq.shape[0], INF, np.float32)
    np.minimum.at(wmin, inv, w)
    dst = (uniq // n).astype(np.int64)
    src = (uniq % n).astype(np.int32)
    counts = np.bincount(dst, minlength=n)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    return CsrGraph(indptr=indptr, indices=src, weights=wmin, n=n,
                    directed=directed)


def random_csr_graph(
    n: int,
    m: int,
    *,
    seed: int = 0,
    directed: bool = False,
    max_weight: float = 100.0,
    connected: bool = True,
) -> CsrGraph:
    """CSR-native random graph — same RNG stream as graph.random_graph, so
    equal seeds yield the identical graph in either representation, without
    ever allocating the dense matrix."""
    e, w = random_edge_list(
        n, m, seed=seed, max_weight=max_weight, connected=connected
    )
    return csr_from_edge_list(n, e, w, directed=directed)


def sparse_csr_graph(n: int, *, seed: int = 0) -> CsrGraph:
    """Paper Table II corpus shape (m = 3n) in O(n) memory — usable far
    beyond the dense generator's n≈40k ceiling."""
    return random_csr_graph(n, 3 * n, seed=seed)


def road_like_csr_graph(n: int, *, seed: int = 0) -> CsrGraph:
    """Long-diameter grid corpus (graph.road_like_edge_list) as a CSR —
    the Δ-stepping gate's road-network stand-in.  ``n`` rounds down to a
    perfect square; read the actual count back from ``.n``."""
    from repro.core.graph import road_like_edge_list

    nn, e, w = road_like_edge_list(n, seed=seed)
    return csr_from_edge_list(nn, e, w)


def skewed_hub_csr_graph(n: int, *, seed: int = 0) -> CsrGraph:
    """Heavy-tailed hub corpus (graph.skewed_hub_edge_list) as a CSR —
    the Δ-stepping gate's skewed-weight stand-in."""
    from repro.core.graph import skewed_hub_edge_list

    e, w = skewed_hub_edge_list(n, seed=seed)
    return csr_from_edge_list(n, e, w)
