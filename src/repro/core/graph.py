"""ε-graph results: the CSR ``NNGraph`` public result type, normalized
``RunStats`` counters, and the ``EpsGraph`` edge-set oracle representation.

``NNGraph`` is what ``repro.nng.build_nng`` returns: a symmetric CSR
adjacency (``row_ptr`` / ``col_ids``) built from the engines' padded
per-rank ``(ids, nbrs)`` neighbor tables, carrying a ``RunStats`` and a
provenance ``meta`` dict. ``EpsGraph`` remains the canonical (i < j)
edge-set used by the oracles and tests; ``NNGraph.to_eps_graph()`` bridges
the two.

``RunStats`` is the single naming scheme for work/communication counters
across host reference algorithms (``PhaseStats`` subclasses it) and the
device engines — float counters throughout, because the device reports
float32 (int32 wraps at paper scale) and the host must mirror it."""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro.obs import count, span

SENTINEL = 2**31 - 1     # neighbor-table padding id (device.SENTINEL)


@dataclass
class RunStats:
    """Normalized work / communication counters of one graph build.

    The field names are THE names: device engines, host reference
    algorithms and benchmark JSON all report these quantities under these
    keys. Counters are floats end-to-end — the device engines emit float32
    (exact below 2^24, approximate beyond; int32 would wrap at paper
    scale) and the host mirrors the convention.
    """

    tiles_scheduled: float = 0.0   # tile blocks the schedule would evaluate
    tiles_skipped: float = 0.0     # blocks pruned (triangle ineq. / groups)
    dists_evaluated: float = 0.0   # pair distances actually computed
    nodes_pruned: float = 0.0      # tree frontier pairs discarded
    comm_bytes: dict = field(default_factory=dict)  # channel -> bytes
    overflow: bool = False         # final run overflowed (never via drivers)
    replans: int = 0               # overflow -> grow iterations taken
    elapsed_s: float = 0.0         # wall clock of the final (exact) run:
                                   # the nng.rerun span
    build_s: float = 0.0           # forest-construction wall clock, the
                                   # nng.forest span (tree traversal only;
                                   # 0.0 on tile paths — reported
                                   # SEPARATELY from elapsed_s)
    update_s: float = 0.0          # wall clock spent in online updates
                                   # (OnlineNNG insert/delete, cumulative —
                                   # separate from the batch elapsed_s)
    edges_added: float = 0.0       # undirected edges appended by updates
    edges_removed: float = 0.0     # undirected edges dropped by tombstones
    # host side of one build (repro.obs): filled where the work happens
    engine_calls: int = 0          # engine invocations: warm runs (one per
                                   # grow too) plus the steady re-run
    compiles: int = 0              # backend compiles (persistent-cache
    compile_s: float = 0.0         # loads included) and their seconds
    fetch_bytes: int = 0           # bytes of the tables copied to the host
    table_slots: int = 0           # rows x width of the neighbour tables
    pairs_selected: int = 0        # their non-SENTINEL entries of valid
                                   # rows: directed pairs before symmetry
    csr_mirror_added: int = 0      # directed entries the row-table CSR
                                   # path added to make the table's rows
                                   # symmetric (0 on the general path)
    ring_bytes: float = 0.0        # bytes each rank sent by ppermute,
                                   # summed over the point engine's calls
    epilogue_scan_pct: float | None = None  # 100 x (slot, chunk) pairs the
                                   # bits_to_cols kernel scanned / pairs a
                                   # full scan takes, over every epilogue
                                   # call of all ranks in the final engine
                                   # call (the steady re-run repeats the
                                   # warm run); None where not counted
                                   # (tree traversal, spatial partition)
    spans: list = field(default_factory=list)  # (name, parent, start_s,
                                               # end_s), perf_counter clock

    @property
    def total_comm_bytes(self) -> float:
        return float(sum(self.comm_bytes.values()))

    @property
    def tile_skip_rate(self) -> float:
        return self.tiles_skipped / max(self.tiles_scheduled, 1.0)


class NNGraph:
    """Symmetric CSR ε-neighbor graph on ``n`` points.

    ``row_ptr`` (n+1,) int64 and ``col_ids`` (nnz,) int32: row i's
    neighbors are ``col_ids[row_ptr[i]:row_ptr[i+1]]``, sorted ascending.
    The adjacency is symmetric (both directions stored), so
    ``row_ptr[-1] == 2 * num_edges``.

    On top of the base CSR sits an optional **delta log** for online
    maintenance: an append-only list of added undirected edges plus a set
    of tombstoned node ids. All read accessors (``neighbors``,
    ``degrees``, ``edge_key``, ``num_edges``, ``to_eps_graph``, equality)
    present the MERGED view — base + adds − tombstoned — so a graph with
    a pending delta log is indistinguishable from its compacted form.
    ``compact()`` folds the log into a clean base CSR; edge keys are
    int64 throughout (``i * n + j`` overflows int32 from n ≈ 46k).
    """

    def __init__(self, n: int, row_ptr: np.ndarray, col_ids: np.ndarray,
                 stats: RunStats | None = None, meta: dict | None = None):
        self.n = int(n)
        self.row_ptr = np.asarray(row_ptr, np.int64)
        self.col_ids = np.asarray(col_ids, np.int32)
        assert self.row_ptr.shape == (self.n + 1,)
        assert self.row_ptr[-1] == len(self.col_ids)
        self.stats = stats if stats is not None else RunStats()
        self.meta = dict(meta or {})
        # delta log: canonical (lo < hi) added edges, tombstoned node ids
        self._add_lo = np.zeros(0, np.int64)
        self._add_hi = np.zeros(0, np.int64)
        self._dead = np.zeros(0, np.int64)      # sorted tombstoned ids
        self._dead_dirty = False                # base still holds dead edges
        self._tomb_edges = 0                    # edges removed since compact
        self._merged_cache = None

    # -- delta log (online maintenance layer) -------------------------------
    @property
    def has_delta(self) -> bool:
        """True when reads must merge (pending adds or un-folded deletes)."""
        return len(self._add_lo) > 0 or self._dead_dirty

    @property
    def delta_edges(self) -> int:
        return len(self._add_lo)

    def _invalidate(self):
        self._merged_cache = None

    def _merged(self):
        """(row_ptr, col_ids) of the merged view (cached until mutated)."""
        if not self.has_delta:
            return self.row_ptr, self.col_ids
        if self._merged_cache is None:
            rows = np.repeat(np.arange(self.n, dtype=np.int64),
                             np.diff(self.row_ptr))
            cols = self.col_ids.astype(np.int64)
            src = np.concatenate([rows, self._add_lo, self._add_hi])
            dst = np.concatenate([cols, self._add_hi, self._add_lo])
            if self._dead_dirty and len(self._dead):
                live = ~(np.isin(src, self._dead) | np.isin(dst, self._dead))
                src, dst = src[live], dst[live]
            key = np.unique(src * self.n + dst)
            rp = np.zeros(self.n + 1, np.int64)
            np.cumsum(np.bincount(key // self.n, minlength=self.n),
                      out=rp[1:])
            self._merged_cache = (rp, (key % self.n).astype(np.int32))
        return self._merged_cache

    def delta_insert_nodes(self, k: int) -> np.ndarray:
        """Grow the node set by ``k`` isolated nodes; returns their ids.
        Ids are allocated densely at the end and never reused."""
        ids = np.arange(self.n, self.n + int(k), dtype=np.int64)
        self.row_ptr = np.concatenate(
            [self.row_ptr, np.full(int(k), self.row_ptr[-1], np.int64)])
        self.n += int(k)
        self._invalidate()
        return ids

    def delta_add_edges(self, src, dst) -> int:
        """Append undirected edges to the delta log. Drops self loops,
        out-of-range / SENTINEL endpoints (driver padding), edges touching
        tombstoned nodes, and duplicates (within the batch and against the
        current merged view). Returns the count of genuinely new edges."""
        src = np.asarray(src, np.int64).ravel()
        dst = np.asarray(dst, np.int64).ravel()
        lo = np.minimum(src, dst)
        hi = np.maximum(src, dst)
        keep = (lo != hi) & (lo >= 0) & (hi < self.n)
        if len(self._dead):
            keep &= ~(np.isin(lo, self._dead) | np.isin(hi, self._dead))
        key = np.unique(lo[keep] * self.n + hi[keep])
        if len(key):
            rp, cols = self._merged()
            rows = np.repeat(np.arange(self.n, dtype=np.int64), np.diff(rp))
            cols = cols.astype(np.int64)
            upper = rows < cols
            have = rows[upper] * self.n + cols[upper]
            key = np.setdiff1d(key, have, assume_unique=True)
        if not len(key):
            return 0
        self._add_lo = np.concatenate([self._add_lo, key // self.n])
        self._add_hi = np.concatenate([self._add_hi, key % self.n])
        self.stats.edges_added += float(len(key))
        self._invalidate()
        return len(key)

    def delta_delete_nodes(self, ids) -> int:
        """Tombstone nodes: their edges vanish from the merged view and
        future adds touching them are rejected. Returns the number of
        undirected edges removed."""
        ids = np.unique(np.asarray(ids, np.int64).ravel())
        ids = ids[(ids >= 0) & (ids < self.n)]
        ids = np.setdiff1d(ids, self._dead, assume_unique=True)
        if not len(ids):
            return 0
        rp, cols = self._merged()
        rows = np.repeat(np.arange(self.n, dtype=np.int64), np.diff(rp))
        cols = cols.astype(np.int64)
        hit = np.isin(rows, ids) | np.isin(cols, ids)
        removed = int(np.count_nonzero(hit & (rows < cols)))
        self._dead = np.union1d(self._dead, ids)
        self._dead_dirty = True
        # prune the add-log of edges now dead (keeps the log size honest)
        if len(self._add_lo):
            live = ~(np.isin(self._add_lo, ids) | np.isin(self._add_hi, ids))
            self._add_lo, self._add_hi = self._add_lo[live], self._add_hi[live]
        self._tomb_edges += removed
        self.stats.edges_removed += float(removed)
        self._invalidate()
        return removed

    def compact(self) -> "NNGraph":
        """Fold the delta log into a clean base CSR, in place. Idempotent:
        compacting twice (or reading through a pending log) yields the same
        merged view. Tombstoned ids stay recorded so later adds touching
        them are still rejected."""
        if self.has_delta:
            rp, cols = self._merged()
            self.row_ptr = np.asarray(rp, np.int64)
            self.col_ids = np.asarray(cols, np.int32)
            self._add_lo = np.zeros(0, np.int64)
            self._add_hi = np.zeros(0, np.int64)
            self._dead_dirty = False
            self._tomb_edges = 0
            self._invalidate()
            self.meta["compactions"] = int(self.meta.get("compactions", 0)) + 1
        return self

    def maybe_compact(self, ratio: float = 0.5) -> bool:
        """Size-ratio auto-compaction: fold once the pending delta (added
        plus tombstone-removed edges) exceeds ``ratio`` × base edges."""
        base = max(len(self.col_ids) // 2, 1)
        if self.delta_edges + self._tomb_edges > ratio * base:
            self.compact()
            return True
        return False

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_directed_pairs(cls, n: int, src, dst, stats=None, meta=None
                            ) -> "NNGraph":
        """Build from directed (src, dst) hit pairs: drops self loops and
        out-of-range endpoints (driver padding rows), symmetrizes, dedups.
        """
        with span("nng.csr.sort"):
            src = np.asarray(src, np.int64)
            dst = np.asarray(dst, np.int64)
            keep = ((src < n) & (dst < n) & (src >= 0) & (dst >= 0)
                    & (src != dst))
            src, dst = src[keep], dst[keep]
            key = np.unique(np.concatenate([src * n + dst, dst * n + src]))
        with span("nng.csr.rows"):
            row_ptr = np.zeros(n + 1, np.int64)
            np.cumsum(np.bincount(key // n, minlength=n), out=row_ptr[1:])
            cols = (key % n).astype(np.int32)
        return cls(n, row_ptr, cols, stats, meta)

    @classmethod
    def from_neighbor_tables(cls, n: int, tables, stats=None, meta=None
                             ) -> "NNGraph":
        """Build from engine outputs: ``tables`` is an iterable of
        (ids (m,), nbrs (m, k)) SENTINEL-padded per-row neighbor arrays
        (one per engine phase — e.g. owned + ghost for the landmark
        engine). Rows with id >= n (duplicate-padding) are dropped.

        One table whose ids are ``0..m-1`` in order (the point engine's)
        is already a padded CSR: ``_from_row_table`` compresses it and
        proves it symmetric instead of sorting every pair. Any other
        input selects its pairs and sorts them. Both give the same
        bytes."""
        tables = [(np.asarray(ids), np.asarray(nbrs)) for ids, nbrs in tables]
        if len(tables) == 1 and _is_row_table(n, *tables[0]):
            return cls._from_row_table(n, tables[0][1], stats, meta)
        src_all, dst_all = [], []
        with span("nng.csr.select"):
            for ids, nbrs in tables:
                valid = (ids != SENTINEL) & (ids < n)
                ii, kk = np.nonzero((nbrs != SENTINEL) & valid[:, None])
                src_all.append(ids[ii])
                dst_all.append(nbrs[ii, kk])
                count("table_slots", nbrs.size)
                count("pairs_selected", len(ii))
            src = (np.concatenate(src_all) if src_all
                   else np.zeros(0, np.int64))
            dst = (np.concatenate(dst_all) if dst_all
                   else np.zeros(0, np.int64))
        return cls.from_directed_pairs(n, src, dst, stats, meta)

    @classmethod
    def _from_row_table(cls, n: int, nbrs, stats, meta) -> "NNGraph":
        """The CSR of a table whose row ``i`` holds point ``i``'s neighbours.

        Row ``i``'s entries in ``[0, n)`` other than ``i`` are its CSR
        row (``nng.csr.select``). It is final when each row rises strictly
        (sorted, no duplicates) and is symmetric: the keys ``i * n + j``
        of the entries below the diagonal equal the sorted transposed
        keys of those above it (``nng.csr.mirror``). Missing mirror
        entries are added (``_add_mirrors``, counted in
        ``csr_mirror_added``); a row not sorted or holding a duplicate
        sends the pairs to ``from_directed_pairs``."""
        with span("nng.csr.select"):
            count("table_slots", nbrs.size)
            filled = nbrs[:n] != SENTINEL
            cols = nbrs[:n][filled]
            count("pairs_selected", len(cols))
            deg = filled.view(np.uint8).sum(axis=1, dtype=np.int64)
            del filled
            src = np.repeat(np.arange(n, dtype=np.int32), deg)
            low, up = cols < src, cols > src
            if len(cols) and (cols.min() < 0 or cols.max() >= n
                              or np.count_nonzero(low)
                              + np.count_nonzero(up) < len(cols)):
                # duplicate-padding ids, self loops, bad ids
                ok = (cols >= 0) & (cols < n) & (cols != src)
                cols, src, low, up = cols[ok], src[ok], low[ok], up[ok]
                deg = np.bincount(src, minlength=n)
            row_ptr = np.zeros(n + 1, np.int64)
            np.cumsum(deg, out=row_ptr[1:])
        with span("nng.csr.mirror"):
            rising = cols[1:] > cols[:-1]
            starts = row_ptr[1:-1]
            rising[starts[(starts > 0) & (starts < len(cols))] - 1] = True
            canonical = bool(rising.all())
            del rising
            if canonical:
                below = _keys(src[low], cols[low], n)
                above_t = _keys(cols[up], src[up], n)
                above_t.sort()
                added = 0
                if not np.array_equal(below, above_t):
                    row_ptr, cols, added = _add_mirrors(
                        n, src, cols, deg, below, above_t)
                count("csr_mirror_added", added)
        if not canonical:
            return cls.from_directed_pairs(n, src, cols, stats, meta)
        return cls(n, row_ptr, cols, stats, meta)

    # -- accessors ----------------------------------------------------------
    @property
    def num_nodes(self) -> int:
        return self.n

    @property
    def num_edges(self) -> int:
        """Undirected edge count (the symmetric CSR stores 2 per edge)."""
        return int(self._merged()[0][-1]) // 2

    @property
    def avg_degree(self) -> float:
        return float(self._merged()[0][-1]) / max(self.n, 1)

    def degrees(self) -> np.ndarray:
        return np.diff(self._merged()[0])

    def neighbors(self, i: int) -> np.ndarray:
        base = self.col_ids[self.row_ptr[i]:self.row_ptr[i + 1]]
        if not self.has_delta:
            return base
        # cheap per-row merge: no full CSR rebuild for point lookups
        if self._dead_dirty and len(self._dead):
            if np.isin(i, self._dead):
                return np.zeros(0, self.col_ids.dtype)
            base = base[~np.isin(base.astype(np.int64), self._dead)]
        add = np.concatenate([self._add_hi[self._add_lo == i],
                              self._add_lo[self._add_hi == i]])
        if not len(add):
            return np.asarray(base)
        return np.unique(np.concatenate(
            [base.astype(np.int64), add])).astype(self.col_ids.dtype)

    def edge_key(self) -> np.ndarray:
        """Canonical (i < j) edge keys i * n + j, sorted, int64 — the same
        encoding ``EpsGraph.edge_key`` uses, for direct comparison."""
        rp, col = self._merged()
        rows = np.repeat(np.arange(self.n, dtype=np.int64), np.diff(rp))
        cols = col.astype(np.int64)
        upper = rows < cols
        return np.sort(rows[upper] * self.n + cols[upper])

    def to_eps_graph(self) -> "EpsGraph":
        rp, col = self._merged()
        rows = np.repeat(np.arange(self.n, dtype=np.int64), np.diff(rp))
        return EpsGraph(self.n, rows, col.astype(np.int64))

    def to_scipy_csr(self):
        """The adjacency (merged view) as a ``scipy.sparse.csr_array`` of
        uint8 ones. scipy is an optional dependency — imported lazily."""
        try:
            from scipy.sparse import csr_array
        except ImportError as e:
            raise ImportError(
                "NNGraph.to_scipy_csr requires the optional dependency "
                "scipy, which is not installed. The raw CSR arrays are "
                "available without scipy as .row_ptr / .col_ids "
                "(merged view via edge_key() / to_eps_graph())."
            ) from e
        rp, col = self._merged()
        data = np.ones(len(col), np.uint8)
        return csr_array((data, col, rp), shape=(self.n, self.n))

    def __eq__(self, other) -> bool:
        if isinstance(other, NNGraph):
            if self.n != other.n:
                return False
            rp_a, col_a = self._merged()
            rp_b, col_b = other._merged()
            return (np.array_equal(rp_a, rp_b)
                    and np.array_equal(col_a, col_b))
        if isinstance(other, EpsGraph):
            return (self.n == other.n
                    and np.array_equal(self.edge_key(), other.edge_key()))
        return NotImplemented

    def __repr__(self):
        return (f"NNGraph(n={self.n}, edges={self.num_edges}, "
                f"avg_deg={self.avg_degree:.2f})")


def _is_row_table(n: int, ids, nbrs) -> bool:
    """True for one (ids, nbrs) table whose ids are ``0..m-1``, m >= n."""
    return (ids.ndim == 1 and nbrs.ndim == 2 and len(ids) == len(nbrs) >= n
            and np.array_equal(ids, np.arange(len(ids))))


def _keys(rows, cols, n: int) -> np.ndarray:
    """int64 keys ``rows * n + cols``."""
    key = rows.astype(np.int64)
    key *= n
    key += cols
    return key


def _add_mirrors(n: int, src, cols, deg, below, above_t):
    """Make a CSR with strictly rising rows symmetric: ``src`` / ``cols``
    its entries in row order, ``deg`` its row lengths, ``below`` the
    keys of its entries below the diagonal and ``above_t`` the sorted
    transposed keys of those above. Returns (row_ptr, col_ids, added).
    Only the rows whose two lower segments differ are set against each
    other, so the sorting scales with them; the rest are linear passes."""
    below_deg = np.bincount(src[cols < src], minlength=n)
    above_deg = np.bincount(cols[cols > src], minlength=n)
    b_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(below_deg, out=b_ptr[1:])
    a_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(above_deg, out=a_ptr[1:])
    differ = below_deg != above_deg
    # rows of equal length: compare their segments entry by entry
    row = np.repeat(np.arange(n), below_deg)
    same = np.flatnonzero(~differ[row])
    shift = (a_ptr - b_ptr)[row[same]]
    differ[row[same[below[same] != above_t[same + shift]]]] = True
    mine = below[np.repeat(differ, below_deg)]
    theirs = above_t[np.repeat(differ, above_deg)]
    # add the (i, j) whose mirror (j, i) is in the table and they are
    # not, and (j, i) for the (i, j) that are in it alone
    lone = np.setdiff1d(mine, theirs, assume_unique=True)
    add = np.sort(np.concatenate([
        np.setdiff1d(theirs, mine, assume_unique=True),
        (lone % n) * n + lone // n]))
    at = np.searchsorted(_keys(src, cols, n), add)
    cols = np.insert(cols, at, (add % n).astype(cols.dtype))
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg + np.bincount(add // n, minlength=n), out=row_ptr[1:])
    return row_ptr, cols, len(add)


class EpsGraph:
    """An undirected ε-graph on n points, stored as canonical (i < j) edges."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray):
        self.n = int(n)
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        lo = np.minimum(src, dst)
        hi = np.maximum(src, dst)
        keep = lo != hi  # drop self loops
        key = lo[keep] * n + hi[keep]
        key = np.unique(key)
        self.src = (key // n).astype(np.int64)
        self.dst = (key % n).astype(np.int64)

    @property
    def num_edges(self) -> int:
        return len(self.src)

    @property
    def avg_degree(self) -> float:
        return 2.0 * self.num_edges / max(self.n, 1)

    def degree(self) -> np.ndarray:
        deg = np.zeros(self.n, dtype=np.int64)
        np.add.at(deg, self.src, 1)
        np.add.at(deg, self.dst, 1)
        return deg

    def edge_key(self) -> np.ndarray:
        return self.src * self.n + self.dst

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, EpsGraph)
            and self.n == other.n
            and len(self.src) == len(other.src)
            and bool(np.array_equal(self.edge_key(), other.edge_key()))
        )

    def symmetric_difference(self, other: "EpsGraph") -> int:
        # edge_key() is sorted-unique by construction, so the array path
        # applies directly — no Python-set round trip boxing every key
        return int(np.setxor1d(self.edge_key(), other.edge_key(),
                               assume_unique=True).size)

    def __repr__(self):
        return f"EpsGraph(n={self.n}, edges={self.num_edges}, avg_deg={self.avg_degree:.2f})"


def merge_graphs(n: int, graphs) -> EpsGraph:
    src = np.concatenate([g.src for g in graphs]) if graphs else np.zeros(0, np.int64)
    dst = np.concatenate([g.dst for g in graphs]) if graphs else np.zeros(0, np.int64)
    return EpsGraph(n, src, dst)


def edges_from_pairs(n: int, pairs: np.ndarray) -> EpsGraph:
    if len(pairs) == 0:
        return EpsGraph(n, np.zeros(0, np.int64), np.zeros(0, np.int64))
    pairs = np.asarray(pairs)
    return EpsGraph(n, pairs[:, 0], pairs[:, 1])
