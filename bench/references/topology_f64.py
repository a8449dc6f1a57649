"""Plain reference for a topology answer, in float64 on the host, with numpy
alone: it imports nothing of the program.

- ``check(n, r, edges, g, W, r_asym)``: the guarantees of one returned
  topology: the support within the budget and connected; W symmetric, rows
  summing to 1 and zero off the support; the reported r_asym equal to
  max |eig(W - 11^T/n)| of W.
- ``best_classic(n, r)``: the independent answer to the same request, the
  best classic topology (ring, 2-D grid, 2-D torus, hypercube, each with
  Metropolis weights) within r edges, and its r_asym.
- ``control(n, r)``: that reference answer computed in float32, the
  precision below the one the configuration states, put in the program's
  place.
"""
from __future__ import annotations

import math

import numpy as np


def r_asym(W: np.ndarray) -> float:
    n = W.shape[0]
    return float(np.max(np.abs(np.linalg.eigvalsh(
        np.asarray(W, np.float64) - 1.0 / n))))


def connected(n: int, edges) -> bool:
    adj = [[] for _ in range(n)]
    for i, j in edges:
        adj[i].append(j)
        adj[j].append(i)
    seen, stack = {0}, [0]
    while stack:
        for j in adj[stack.pop()]:
            if j not in seen:
                seen.add(j)
                stack.append(j)
    return len(seen) == n


def check(n: int, r: int, edges, W, reported: float) -> dict:
    """The numbers one answer is held to (each must be at most its limit)."""
    W = np.asarray(W)
    W64 = W.astype(np.float64)
    sup = np.zeros((n, n), bool)
    for i, j in edges:
        sup[i, j] = sup[j, i] = True
    np.fill_diagonal(sup, True)
    w_dev = max(float(np.max(np.abs(W64 - W64.T))),
                float(np.max(np.abs(W64.sum(axis=1) - 1.0))),
                float(np.max(np.abs(np.where(sup, 0.0, W64)))))
    edge_set = {tuple(sorted(map(int, e))) for e in edges}
    return {
        "over_budget": float(max(len(edge_set) - r, 0)
                             + (len(edge_set) != len(edges))),
        "disconnected": float(not connected(n, edges)),
        "w_dev": w_dev,
        "r_asym_dev": abs(float(reported) - r_asym(W64)),
        "r_asym": r_asym(W64),
    }


# ---------------------------------------------------------------------------
# the classic topologies
# ---------------------------------------------------------------------------

def _grid_shape(n: int) -> tuple[int, int]:
    a = int(math.isqrt(n))
    while n % a:
        a -= 1
    return a, n // a


def classics(n: int) -> dict[str, list[tuple[int, int]]]:
    out = {"ring": sorted({tuple(sorted((i, (i + 1) % n))) for i in range(n)})}
    a, b = _grid_shape(n)
    if a > 1:
        grid, torus = set(), set()
        for x in range(a):
            for y in range(b):
                u = x * b + y
                if y + 1 < b:
                    grid.add((u, u + 1))
                if x + 1 < a:
                    grid.add((u, u + b))
                torus.add(tuple(sorted((u, x * b + (y + 1) % b))))
                torus.add(tuple(sorted((u, ((x + 1) % a) * b + y))))
        out["grid"] = sorted(grid)
        out["torus"] = sorted(e for e in torus if e[0] != e[1])
    if n & (n - 1) == 0:
        out["hypercube"] = sorted({tuple(sorted((i, i ^ (1 << k))))
                                   for i in range(n)
                                   for k in range(n.bit_length() - 1)})
    return out


def metropolis(n: int, edges, dtype=np.float64) -> np.ndarray:
    deg = np.zeros(n, int)
    for i, j in edges:
        deg[i] += 1
        deg[j] += 1
    W = np.zeros((n, n), dtype)
    for i, j in edges:
        W[i, j] = W[j, i] = dtype(1.0) / dtype(1 + max(deg[i], deg[j]))
    W[np.diag_indices(n)] = dtype(1.0) - W.sum(axis=1, dtype=dtype)
    return W


def best_classic(n: int, r: int, dtype=np.float64):
    """``(name, edges, W, r_asym)`` of the best classic within r edges."""
    best = None
    for name, edges in classics(n).items():
        if len(edges) > r:
            continue
        W = metropolis(n, edges, dtype)
        if dtype == np.float64:
            val = r_asym(W)
        else:
            val = float(np.max(np.abs(np.linalg.eigvalsh(
                W - dtype(1.0) / dtype(n)))))
        if best is None or val < best[3]:
            best = (name, edges, W, val)
    if best is None:
        raise ValueError(f"no classic topology of {n} nodes fits {r} edges")
    return best


def control(n: int, r: int):
    """The reference answer in float32: ``(edges, W, reported r_asym)``."""
    _, edges, W, val = best_classic(n, r, np.float32)
    return edges, W, val
