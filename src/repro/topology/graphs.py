"""Network topologies for decentralized Byzantine-robust training.

Byrd-SAGA's federation is an implicit STAR: one master aggregates every
worker's message.  This module makes the communication graph explicit so the
same robust-aggregation machinery runs server-free (Peng/Li/Ling 2023,
arXiv:2308.05292): a :class:`Topology` carries

* ``adjacency``     -- (N, N) bool, symmetric, zero diagonal;
* ``mixing``        -- (N, N) float64 Metropolis-Hastings weights
                       ``W_ij = 1 / (1 + max(deg_i, deg_j))`` for edges,
                       ``W_ii = 1 - sum_j W_ij``: symmetric and DOUBLY
                       stochastic by construction, so plain-mean gossip
                       preserves the honest average;
* ``neighbor_mask`` -- (N, N) float32 with self-loops,
                       ``mask[i, j] = 1  iff  j in N(i) or j == i``:
                       the per-node restriction every masked aggregator in
                       :mod:`repro.topology.masked` consumes (mask-select,
                       never slice+concat -- DESIGN.md Sec. 1).

Everything is plain numpy, computed once at trace time: masks and mixing
rows enter jit as compile-time constants.

Constructors (registry-driven like the aggregators/attacks):
``ring``, ``torus2d``, ``complete``, ``erdos_renyi(p, seed)``, and ``star``
for backward compatibility (node 0 is the hub; routing a star topology
through the training entry points reproduces the master path bit-exactly --
DESIGN.md Sec. 6).

The spectral gap ``1 - |lambda_2(mixing)|`` (reported by
:func:`Topology.describe`) governs the consensus rate: complete > torus2d >
ring at equal N.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Topology:
    """An undirected communication graph plus its gossip matrices."""

    name: str
    num_nodes: int
    adjacency: np.ndarray  # (N, N) bool, symmetric, zero diagonal

    def __post_init__(self):
        adj = np.asarray(self.adjacency, bool)
        n = self.num_nodes
        if adj.shape != (n, n):
            raise ValueError(f"adjacency must be ({n}, {n}), got {adj.shape}")
        if adj.diagonal().any():
            raise ValueError("adjacency must have a zero diagonal "
                             "(self-loops live in neighbor_mask)")
        if not (adj == adj.T).all():
            raise ValueError("adjacency must be symmetric (undirected graph)")
        object.__setattr__(self, "adjacency", adj)

    @property
    def degrees(self) -> np.ndarray:
        """(N,) neighbor counts, self excluded."""
        return self.adjacency.sum(axis=1).astype(np.int64)

    @property
    def neighbor_mask(self) -> np.ndarray:
        """(N, N) float32 mask with self-loops: row i selects N(i) + {i}."""
        return (self.adjacency | np.eye(self.num_nodes, dtype=bool)).astype(
            np.float32)

    @property
    def mixing(self) -> np.ndarray:
        """(N, N) float64 Metropolis-Hastings weights (symmetric, doubly
        stochastic): ``1 / (1 + max(deg_i, deg_j))`` on edges, the residual
        mass on the diagonal."""
        n = self.num_nodes
        deg = self.degrees
        w = np.where(self.adjacency,
                     1.0 / (1.0 + np.maximum(deg[:, None], deg[None, :])),
                     0.0)
        w[np.arange(n), np.arange(n)] = 1.0 - w.sum(axis=1)
        return w

    @property
    def min_neighborhood(self) -> int:
        """Smallest neighborhood size INCLUDING self (= min degree + 1):
        the bound per-node trimmed_mean / krum feasibility checks use."""
        return int(self.degrees.min()) + 1

    def is_connected(self) -> bool:
        return _connected(self.adjacency)

    def spectral_gap(self) -> float:
        """``1 - |lambda_2|`` of the mixing matrix (symmetric, so eigvalsh);
        larger gap = faster consensus.  A disconnected graph reports 0."""
        lam = np.linalg.eigvalsh(self.mixing)
        mags = np.sort(np.abs(lam))
        if self.num_nodes == 1:
            return 1.0
        # Clamped: a repeated eigenvalue 1 (disconnected graph) comes back
        # from eigvalsh a few ulps above 1.
        return float(np.clip(1.0 - mags[-2], 0.0, 1.0))

    def describe(self) -> dict:
        """The spectral-gap report (demo / benchmark / log line)."""
        deg = self.degrees
        return {
            "name": self.name,
            "num_nodes": self.num_nodes,
            "num_edges": int(self.adjacency.sum()) // 2,
            "degree_min": int(deg.min()),
            "degree_max": int(deg.max()),
            "degree_mean": float(deg.mean()),
            "connected": self.is_connected(),
            "spectral_gap": self.spectral_gap(),
        }


def _connected(adj: np.ndarray) -> bool:
    n = adj.shape[0]
    seen = np.zeros(n, bool)
    seen[0] = True
    frontier = adj[0].copy()
    while frontier.any():
        seen |= frontier
        frontier = (adj[frontier].any(axis=0)) & ~seen
    return bool(seen.all())


def _check_n(name: str, n: int, minimum: int = 2) -> None:
    if n < minimum:
        raise ValueError(f"{name} topology needs >= {minimum} nodes, got {n}")


# ---------------------------------------------------------------------------
# Constructors
# ---------------------------------------------------------------------------

def ring(num_nodes: int) -> Topology:
    """Cycle graph: node i talks to i +- 1 (mod N)."""
    _check_n("ring", num_nodes)
    adj = np.zeros((num_nodes, num_nodes), bool)
    idx = np.arange(num_nodes)
    adj[idx, (idx + 1) % num_nodes] = True
    adj[(idx + 1) % num_nodes, idx] = True
    np.fill_diagonal(adj, False)  # num_nodes == 2: the two edges coincide
    return Topology("ring", num_nodes, adj)


def torus2d(num_nodes: int, *, rows: Optional[int] = None) -> Topology:
    """2-D torus (wrap-around grid, degree <= 4).  ``rows`` defaults to the
    largest divisor of N at most sqrt(N); a prime N has no non-trivial grid,
    so it is rejected (use ``ring``)."""
    _check_n("torus2d", num_nodes, 4)
    if rows is None:
        rows = max(d for d in range(1, int(math.isqrt(num_nodes)) + 1)
                   if num_nodes % d == 0)
    if num_nodes % rows != 0:
        raise ValueError(f"torus2d: rows={rows} does not divide N={num_nodes}")
    cols = num_nodes // rows
    if rows == 1 or cols == 1:
        raise ValueError(
            f"torus2d: N={num_nodes} only factors as a 1-wide grid "
            "(prime N?); use the ring topology instead")
    adj = np.zeros((num_nodes, num_nodes), bool)
    for r in range(rows):
        for c in range(cols):
            i = r * cols + c
            for j in (((r + 1) % rows) * cols + c,
                      r * cols + (c + 1) % cols):
                if i != j:
                    adj[i, j] = adj[j, i] = True
    return Topology("torus2d", num_nodes, adj)


def complete(num_nodes: int) -> Topology:
    """Fully connected: every node sees every message (the decentralized
    graph closest to the master's view)."""
    _check_n("complete", num_nodes)
    adj = ~np.eye(num_nodes, dtype=bool)
    return Topology("complete", num_nodes, adj)


def erdos_renyi(num_nodes: int, *, p: float = 0.5, seed: int = 0,
                max_tries: int = 64, require_connected: bool = True) -> Topology:
    """G(N, p) with each edge drawn i.i.d. Bernoulli(p) from a seeded numpy
    Generator.  Deterministic in (N, p, seed).  A disconnected draw is
    rejected and redrawn (fresh substream, same seed) up to ``max_tries``
    times; persistent disconnection (tiny p) raises with the fix spelled
    out rather than silently densifying the graph.  With
    ``require_connected=False`` the FIRST draw is returned as-is -- the
    time-varying schedules (``topology/schedule.py``) legitimately use
    disconnected rounds and validate connectivity over a window instead."""
    _check_n("erdos_renyi", num_nodes)
    if not 0.0 <= p <= 1.0:
        raise ValueError(f"erdos_renyi: p must be in [0, 1], got {p}")
    rng = np.random.default_rng(np.random.SeedSequence([num_nodes, seed]))
    for _ in range(max_tries):
        upper = rng.random((num_nodes, num_nodes)) < p
        adj = np.triu(upper, k=1)
        adj = adj | adj.T
        if not require_connected or _connected(adj):
            return Topology("erdos_renyi", num_nodes, adj)
    raise ValueError(
        f"erdos_renyi(N={num_nodes}, p={p}, seed={seed}): no connected draw "
        f"in {max_tries} tries -- raise p (connectivity threshold ~ ln(N)/N) "
        "or pick another seed")


def star(num_nodes: int) -> Topology:
    """Hub-and-spokes, node 0 the hub: the paper's master federation as a
    graph.  Training entry points special-case this name onto the existing
    master path so ``topology='star'`` is bit-exact with the status quo
    (DESIGN.md Sec. 6)."""
    _check_n("star", num_nodes)
    adj = np.zeros((num_nodes, num_nodes), bool)
    adj[0, 1:] = True
    adj[1:, 0] = True
    return Topology("star", num_nodes, adj)


# name -> builder(num_nodes, **opts).  TOPOLOGY_NAMES and the unknown-name
# error derive from this dict (same pattern as the aggregator and attack
# registries): registering here is the ONE place a topology is added.
_TOPOLOGIES: dict[str, Callable[..., Topology]] = {
    "ring": ring,
    "torus2d": torus2d,
    "complete": complete,
    "erdos_renyi": erdos_renyi,
    "star": star,
}

TOPOLOGY_NAMES = tuple(_TOPOLOGIES)


def get_topology(name: str, num_nodes: int, *, seed: int = 0,
                 p: float = 0.5) -> Topology:
    """Build a topology by name.  ``seed``/``p`` only reach the constructors
    that take them (``erdos_renyi``)."""
    try:
        build = _TOPOLOGIES[name]
    except KeyError:
        raise ValueError(
            f"unknown topology {name!r}; known: "
            f"{', '.join(sorted(_TOPOLOGIES))}") from None
    if name == "erdos_renyi":
        return build(num_nodes, p=p, seed=seed)
    return build(num_nodes)
