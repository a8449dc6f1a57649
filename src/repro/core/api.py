"""High-level BA-Topo API: one call per paper scenario.

Pipeline (the paper's full recipe, §IV–§VI):
  1. scenario → ConstraintSet (M, e) and candidate-edge admissibility,
  2. Algorithm 1 (node scenarios) → per-node edge capacities maximizing b_unit,
  3. simulated-annealing warm start (low ASPL, feasible) [§VI] — by
     default the *device* SA (``core.warmstart``): all restarts annealed
     in one vmapped, scan-compiled call with matmul-BFS ASPL,
  4. Algorithm 2 ADMM (homogeneous Eq. 20 / heterogeneous Eq. 28) — with
     ``cfg.restarts > 1`` all restarts are solved in one batched,
     vmapped device call (engine ``solve_batched``, DESIGN.md §4),
  5. support extraction + greedy feasibility repair (beyond paper, see
     DESIGN.md §6) + convex weight polish — every candidate of the solve
     (restarts × {admm, warm} × classics) polished in one vmapped,
     scan-compiled call (``weights.polish_weights_batched``),
  6. keep the best of {ADMM, warm start, feasible classics}, each
     evaluated by ONE ``r_asym`` (Lanczos above ``FAST_SPECTRAL_MIN_N``)
     — the ADMM is non-convex (cardinality / binary constraints), so this
     guards against bad local points, mirroring the paper's
     initialization-sensitivity note.

The host warm start / polish survive as ``warmstart="host"`` /
``polish="host"`` — the ``driver="python"``-style fallback and parity
oracle for the device outer pipeline (DESIGN.md §10). Pass ``profile={}``
to ``optimize_topology`` to collect the per-phase wall-time breakdown
(warm start / ADMM / round+repair / polish / eval).

``sweep_topologies`` amortizes step 4 across many (n, r) scenarios: for a
fixed n the whole cardinality sweep runs as one vmapped solve.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field

import numpy as np

from .admm import ADMMConfig, HeterogeneousADMM, HomogeneousADMM
from .anneal import anneal_topology, greedy_degree_graph
from .constraints import ConstraintSet
from .graph import Topology, all_edges, edge_index, is_connected, r_asym, weight_matrix_from_weights
from .weights import metropolis_weights, polish_weights, polish_weights_batched

__all__ = ["BATopoConfig", "optimize_topology", "sweep_topologies",
           "extract_support", "repair_selection", "large_n_admm_config"]


def _pipeline_admm_default() -> ADMMConfig:
    """Pipeline-default ADMM stack (DESIGN.md §10/§13): the PR-2 measured-fast
    solver options (inexact CG tied to the primal residual, fp32 loop with
    fp64 residuals) plus a 600-iteration budget. The pipeline consumes only
    the solver's *support decision* — weights are re-derived by the convex
    polish, and the warm-start/classic candidates compete on equal footing —
    and that decision saturates long before the eps-residual does: measured
    drift vs the exact 1500-iteration solve is 0.0 on every paper scenario
    at n≤32 and ≤7e-4 at n=64/4 restarts (committed bench_pipeline rows).
    ``psd_backend``/``partition`` are the "auto" selectors: on a
    single-device CPU they resolve to the previous eigh/unsharded behavior;
    on multi-device or accelerator backends they engage the measured large-n
    stack (core.shard, engine.NS_MIN_N). Direct ``HomogeneousADMM``/
    ``HeterogeneousADMM`` use keeps the exact paper-faithful
    ``ADMMConfig()`` defaults."""
    return ADMMConfig(max_iters=600, cg_inexact=True, dtype="float32",
                      psd_backend="auto", partition="auto")


def large_n_admm_config(max_iters: int = 600) -> ADMMConfig:
    """The measured large-n solver stack (DESIGN.md §13), as an explicit
    factory for direct solver use and benchmarks: fp32 loop with fp64
    residuals, inexact CG tied to the primal residual, platform/size-resolved
    PSD backend (``engine.resolve_psd_backend``) and device layout
    (``shard.resolve_partition``). The spectral-evaluation side pairs with
    it automatically: ``Topology.r_asym`` routes through the Lanczos
    ``r_asym_fast`` above ``graph.FAST_SPECTRAL_MIN_N`` (= 192, measured in
    PR 3). This equals the pipeline default stack — named so callers and
    benches can request it without relying on the pipeline default staying
    identical."""
    return ADMMConfig(max_iters=max_iters, cg_inexact=True, dtype="float32",
                      psd_backend="auto", partition="auto")


@dataclass
class BATopoConfig:
    admm: ADMMConfig = field(default_factory=_pipeline_admm_default)
    sa_iters: int = 1500
    polish_iters: int = 500
    support_tol: float = 1e-6
    seed: int = 0
    restarts: int = 1
    # -- outer-pipeline performance stack (DESIGN.md §10) -------------------
    warmstart: str = "device"     # device (batched SA) | host (parity oracle)
    polish: str = "device"        # device (vmapped scan) | host
    polish_dtype: str = "float32"  # device polish loop dtype (f64 bookkeeping)
    sa_kernel: bool = False       # route matmul-BFS through the hop_bfs Pallas pair


def _validate_pipeline_cfg(cfg: BATopoConfig) -> None:
    """Reject typo'd backend selectors (a silently-ignored
    ``warmstart="Device"`` would benchmark the wrong pipeline)."""
    if cfg.warmstart not in ("device", "host"):
        raise ValueError(f"unknown warmstart {cfg.warmstart!r}; "
                         "expected 'device' or 'host'")
    if cfg.polish not in ("device", "host"):
        raise ValueError(f"unknown polish {cfg.polish!r}; "
                         "expected 'device' or 'host'")
    if cfg.polish_dtype not in ("float32", "float64"):
        raise ValueError(f"unknown polish_dtype {cfg.polish_dtype!r}; "
                         "expected 'float32' or 'float64'")


def extract_support(
    n: int, g: np.ndarray, r: int, tol: float, z: np.ndarray | None = None,
    edge_ok: np.ndarray | None = None,
) -> np.ndarray:
    """Boolean selection over the full candidate edge list: top-r weights
    (optionally gated by the binary z of the heterogeneous solver)."""
    m = len(g)
    score = np.asarray(g, dtype=np.float64).copy()
    if z is not None:
        score = score + 1e-3 * np.asarray(z)  # prefer z-selected edges on ties
    if edge_ok is not None:
        score[~edge_ok] = -np.inf
    score[score <= tol] = -np.inf
    k = min(r, int(np.isfinite(score).sum()))
    sel = np.zeros(m, dtype=bool)
    if k > 0:
        idx = np.argpartition(-score, k - 1)[:k]
        sel[idx] = True
    return sel


def repair_selection(n: int, sel: np.ndarray, g: np.ndarray, cs: ConstraintSet | None) -> np.ndarray:
    """Greedy feasibility + connectivity repair of a rounded edge selection.

    1. While a capacity row is violated (M z > e), drop the lowest-weight
       selected edge contributing to the most-violated row.
    2. While the graph is disconnected, add the highest-weight admissible
       edge joining two components that does not violate capacities.

    Capacity usage ``M @ sel`` is computed once per phase and updated
    incrementally as edges are dropped/added (it used to be recomputed per
    candidate edge, a quadratic hot spot on dense candidate sets).
    """
    edges_full = all_edges(n)
    sel = sel.copy()
    g = np.asarray(g, dtype=np.float64)
    usage = cs.M @ sel.astype(np.int64) if cs is not None else None

    if cs is not None:
        while True:
            over = usage - cs.e_cap
            if np.all(over <= 0):
                break
            row = int(np.argmax(over))
            members = [l for l in np.nonzero(sel)[0] if cs.M[row, l]]
            drop = min(members, key=lambda l: g[l])
            sel[drop] = False
            usage = usage - cs.M[:, drop]

    def comps(sel_mask):
        parent = list(range(n))

        def find(a):
            while parent[a] != a:
                parent[a] = parent[parent[a]]
                a = parent[a]
            return a

        for l in np.nonzero(sel_mask)[0]:
            i, j = edges_full[l]
            ri, rj = find(i), find(j)
            if ri != rj:
                parent[ri] = rj
        return [find(i) for i in range(n)]

    for _ in range(n):
        c = comps(sel)
        if len(set(c)) == 1:
            break
        cands = []
        for l, (i, j) in enumerate(edges_full):
            if sel[l] or c[i] == c[j]:
                continue
            if cs is not None:
                if not cs.edge_ok[l]:
                    continue
                if np.any(usage + cs.M[:, l] > cs.e_cap):
                    continue
            cands.append(l)
        if not cands:
            break  # cannot connect under capacities — caller handles r_asym=1
        best = max(cands, key=lambda l: g[l])
        sel[best] = True
        if cs is not None:
            usage = usage + cs.M[:, best]
    return sel


def _homo_degree_targets(n: int, r: int) -> np.ndarray:
    """Balanced degree sequence with Σd = 2r (homogeneous Algorithm-1 limit)."""
    base = (2 * r) // n
    extra = (2 * r) % n
    d = np.full(n, base, dtype=np.int64)
    d[:extra] += 1
    return np.minimum(d, n - 1)


def _finalize_batch(n: int, items: list[tuple[np.ndarray, str, dict]],
                    cfg: BATopoConfig, cs: ConstraintSet | None) -> list[Topology]:
    """Connectivity-check + weight-polish a batch of candidate selections.

    Every connected candidate of a solve (restarts × {admm, warm} ×
    classics) is polished in ONE vmapped, scan-compiled device call
    (``cfg.polish="host"`` keeps the serial host loop as parity oracle).
    """
    edges_full = all_edges(n)
    topos: list[Topology | None] = [None] * len(items)
    # identical supports (a warm-started ADMM frequently rounds back to
    # exactly its warm-start support; restarts can coincide too) polish to
    # identical weights — solve each distinct support once
    support_of: dict[bytes, list[int]] = {}
    for k, (sel, name, meta) in enumerate(items):
        edges = [edges_full[l] for l in np.nonzero(sel)[0]]
        if not edges or not is_connected(n, edges):
            g = metropolis_weights(n, edges) if edges else np.zeros(0)
            topos[k] = Topology(n, edges, g, name=name,
                                meta={**meta, "connected": False})
            continue
        support_of.setdefault(np.asarray(sel, dtype=bool).tobytes(),
                              []).append(k)
    if support_of:
        pending = []
        for ks in support_of.values():
            edges = [edges_full[l] for l in np.nonzero(items[ks[0]][0])[0]]
            pending.append((ks, edges, metropolis_weights(n, edges)))
        if cfg.polish == "device":
            gs = polish_weights_batched(
                n, [e for _, e, _ in pending], [g0 for _, _, g0 in pending],
                iters=cfg.polish_iters, dtype=cfg.polish_dtype)
        else:
            gs = [polish_weights(n, e, g0, iters=cfg.polish_iters)
                  for _, e, g0 in pending]
        for (ks, edges, _), g in zip(pending, gs):
            for k in ks:
                _, name, meta = items[k]
                topos[k] = Topology(n, edges, g, name=name,
                                    meta={**meta, "connected": True})
    return topos


def _candidate_items(n: int, r: int, warms, results, cs: ConstraintSet | None,
                     cfg: BATopoConfig, meta: dict, use_z: bool,
                     ) -> tuple[list[tuple[np.ndarray, str, dict]], list[str]]:
    """Phase 3 shared by ``optimize_topology`` / ``sweep_topologies`` /
    ``serve.topo_service``: round every ADMM result (top-r support + greedy
    feasibility repair), and enter the annealed warm starts and the feasible
    classic baselines as competing candidates. Returns the ``(sel, name,
    meta)`` items for ``_finalize_batch`` plus a parallel provenance list."""
    items: list[tuple[np.ndarray, str, dict]] = []
    sources: list[str] = []
    edge_ok = (np.asarray(cs.edge_ok)
               if (use_z and cs is not None) else None)
    for (g0, z0, lam0), res in zip(warms, results):
        score = res.g + res.g_raw
        if use_z:
            sel = extract_support(n, score, r, cfg.support_tol, z=res.z,
                                  edge_ok=edge_ok)
        else:
            sel = extract_support(n, score, r, cfg.support_tol)
        sel = repair_selection(n, sel, score, cs)
        items.append((sel, f"ba-topo(n={n},r={r})", {**meta,
                      "admm_iters": res.iters, "admm_residual": res.residual,
                      "lam_tilde": res.lam_tilde}))
        sources.append("admm")
        items.append((z0.astype(bool), f"ba-topo(n={n},r={r},warm)",
                      dict(meta)))
        sources.append("warm-start")
    for base_name, sel in _classic_candidates(n, r, cs):
        items.append((sel, f"ba-topo(n={n},r={r},{base_name})", dict(meta)))
        sources.append(f"classic:{base_name}")
    return items, sources


def _pick_best(n: int, items, topos, sources,
               ) -> tuple[Topology | None, float, list[str]]:
    """Phase 5 shared by ``optimize_topology`` / ``sweep_topologies`` /
    ``serve.topo_service``: release-validate each connected candidate
    against the ``core.guard`` invariant checklist (finite W, symmetry,
    row-stochasticity, connectivity) and pick the lowest r_asym among the
    survivors, one spectral/invariant evaluation per distinct support.
    Returns ``(best, best_val, failures)`` — ``failures`` names the
    invariant each flunked candidate violated, so callers can raise a
    structured error when nothing survives."""
    from .guard import check_invariants

    best: Topology | None = None
    best_val = np.inf
    val_cache: dict[bytes, float] = {}
    inv_cache: dict[bytes, str | None] = {}
    failures: list[str] = []
    for (sel, _, _), cand, src in zip(items, topos, sources):
        if not cand.meta.get("connected", False):
            continue
        key = np.asarray(sel, dtype=bool).tobytes()
        if key not in inv_cache:
            inv_cache[key] = check_invariants(cand)
        bad = inv_cache[key]
        if bad is not None:
            failures.append(f"{cand.name}: {bad}")
            continue
        if key not in val_cache:
            val_cache[key] = cand.r_asym()
        val = val_cache[key]
        if best is None or val < best_val:
            cand.meta["selected_from"] = src
            best, best_val = cand, val
    return best, best_val, failures


def _init_graph(n: int, r: int, scenario: str, cs: ConstraintSet | None,
                deg_targets, cfg: BATopoConfig, restart: int):
    """Greedy feasible start graph for one restart. Returns (edges0, seed)."""
    seed = cfg.seed + 1000 * restart
    rng = np.random.default_rng(seed)
    if deg_targets is not None:
        warm_cs = cs if scenario == "node" else None
        return greedy_degree_graph(n, deg_targets, rng, warm_cs), seed
    return _greedy_constraint_graph(n, r, cs, rng), seed


def _pack_warm(n: int, edges0: list[tuple[int, int]]):
    """Annealed edge list → (g0, z0, lam0) ADMM warm start."""
    eidx = edge_index(n)
    m = len(all_edges(n))
    z0 = np.zeros(m)
    for e in edges0:
        z0[eidx[e]] = 1.0
    g0 = np.zeros(m)
    gm = metropolis_weights(n, edges0)
    for k, e in enumerate(edges0):
        g0[eidx[e]] = gm[k]
    W0 = weight_matrix_from_weights(n, edges0, gm)
    lam0 = max(1.0 - r_asym(W0, symmetric=True), 0.05)
    return g0, z0, lam0


def _anneal_edges(n: int, inits: list[list[tuple[int, int]]], seeds: list[int],
                  sa_cs: ConstraintSet | None, cfg: BATopoConfig) -> list:
    """Anneal a batch of start graphs. ``cfg.warmstart="device"`` runs one
    vmapped, scan-compiled SA call per distinct edge count (a 2-swap
    preserves the count, so restarts — or sweep instances — with
    equal-size init graphs share a call and a compilation);
    ``"host"`` keeps the seed per-graph Python SA as the parity oracle."""
    if cfg.warmstart == "device":
        from .warmstart import anneal_topology_batched

        groups: dict[int, list[int]] = {}
        for k, e in enumerate(inits):
            groups.setdefault(len(e), []).append(k)
        annealed: list = [None] * len(inits)
        for idxs in groups.values():
            outs = anneal_topology_batched(
                n, [inits[i] for i in idxs], sa_cs, iters=cfg.sa_iters,
                seeds=[seeds[i] for i in idxs], use_kernel=cfg.sa_kernel)
            for i, out in zip(idxs, outs):
                annealed[i] = out
        return annealed
    return [anneal_topology(n, e0, sa_cs, iters=cfg.sa_iters, seed=sd)
            for e0, sd in zip(inits, seeds)]


def _warm_starts(n: int, r: int, scenario: str, cs: ConstraintSet | None,
                 deg_targets, cfg: BATopoConfig, n_restarts: int):
    """Warm starts for every restart: greedy init (host) + simulated
    annealing (batched on device by default). Returns (g0, z0, lam0)s."""
    inits, seeds = [], []
    for k in range(n_restarts):
        edges0, seed = _init_graph(n, r, scenario, cs, deg_targets, cfg, k)
        inits.append(edges0)
        seeds.append(seed)
    sa_cs = cs if scenario != "homo" else None
    annealed = _anneal_edges(n, inits, seeds, sa_cs, cfg)
    return [_pack_warm(n, e) for e in annealed]


def _make_solver(n: int, r: int, scenario: str, cs: ConstraintSet | None,
                 cfg: BATopoConfig):
    if scenario == "homo":
        return HomogeneousADMM(n, r, cfg.admm)
    return HeterogeneousADMM(
        n, r, np.asarray(cs.M, dtype=np.float64), np.asarray(cs.e_cap, dtype=np.float64),
        cfg.admm, equality=cs.equality, edge_ok=np.asarray(cs.edge_ok),
    )


def optimize_topology(
    n: int,
    r: int,
    scenario: str = "homo",
    cs: ConstraintSet | None = None,
    node_bandwidths: np.ndarray | None = None,
    cfg: BATopoConfig | None = None,
    profile: dict | None = None,
) -> Topology:
    """Deprecated signature-compatible wrapper around the unified request
    API (DESIGN.md §17): build a :class:`~repro.core.anytime.TopologyRequest`
    and call :func:`~repro.core.anytime.solve_topology` instead. Behavior
    (including the barrier execution order, profile keys and error
    messages) is unchanged.
    """
    warnings.warn(
        "optimize_topology(n, r, ...) is deprecated; build a "
        "TopologyRequest and call repro.core.anytime.solve_topology(...)",
        DeprecationWarning, stacklevel=2)
    return _optimize_request(n, r, scenario=scenario, cs=cs,
                             node_bandwidths=node_bandwidths, cfg=cfg,
                             profile=profile)


def _optimize_request(
    n: int,
    r: int,
    scenario: str = "homo",
    cs: ConstraintSet | None = None,
    node_bandwidths: np.ndarray | None = None,
    cfg: BATopoConfig | None = None,
    profile: dict | None = None,
) -> Topology:
    """Produce a BA-Topo for the given scenario — the phase-barriered
    pipeline (``solve_topology(engine="barrier")`` and the unbudgeted
    anytime parity oracle).

    scenario ∈ {"homo", "node", "constraint"}:
      - "homo": Eq. (9) with Card(g) ≤ r.
      - "node": §IV-B1 — requires ``node_bandwidths``; Algorithm 1 allocates
        per-node capacities, then the heterogeneous ADMM runs with equality
        degree rows.
      - "constraint": any ConstraintSet (intra-server, BCube, pod-boundary)
        with inequality capacities.

    With ``cfg.restarts > 1`` and a JAX backend, all restarts are solved by
    one batched device call; the best candidate (lowest ``r_asym`` after
    repair + polish) wins. Pass ``profile={}`` to collect the per-phase
    wall-time breakdown (keys ``warm_s/admm_s/round_s/polish_s/eval_s``).
    """
    from ..obs import PhaseProfile
    from .anytime import resolve_scenario

    cfg = cfg or BATopoConfig()
    _validate_pipeline_cfg(cfg)
    prof = PhaseProfile(area="solve")
    cs, deg_targets, meta = resolve_scenario(n, r, scenario, cs,
                                             node_bandwidths, context="api")
    try:
        return _barrier_phases(n, r, scenario, cs, deg_targets, meta, cfg,
                               prof)
    finally:
        if profile is not None:
            prof.add_to(profile)


def _barrier_phases(n, r, scenario, cs, deg_targets, meta, cfg, prof):
    """The five barriered phases of ``_optimize_request``, each timed into
    ``prof`` under its ``repro.solve.<phase>`` span."""
    # ---- phase 1: warm starts (device SA by default) ----------------------
    n_restarts = max(1, cfg.restarts)
    with prof.phase("warm"):
        warms = _warm_starts(n, r, scenario, cs, deg_targets, cfg, n_restarts)

    solver = _make_solver(n, r, scenario, cs, cfg)

    # ---- phase 2: ADMM — batched restarts in one device call (scan driver
    # only; an explicit driver="python" request keeps the per-restart loop)
    with prof.phase("admm"):
        if (n_restarts > 1 and cfg.admm.solver != "kkt_bicgstab_ilu"
                and cfg.admm.driver == "scan"):
            g0s = np.stack([w[0] for w in warms])
            lam0s = np.asarray([w[2] for w in warms])
            if scenario == "homo":
                results = solver.solve_batched(g0s, lam0s)
            else:
                results = solver.solve_batched(
                    g0s, np.stack([w[1] for w in warms]), lam0s)
        elif scenario == "homo":
            results = [solver.solve(g0=g0, lam0=lam0)
                       for g0, _, lam0 in warms]
        else:
            results = [solver.solve(g0=g0, z0=z0, lam0=lam0)
                       for g0, z0, lam0 in warms]

    # ---- phase 3: rounding + greedy feasibility repair --------------------
    with prof.phase("round"):
        items, sources = _candidate_items(n, r, warms, results, cs, cfg, meta,
                                          use_z=(scenario != "homo"))

    # ---- phase 4: weight polish, all candidates in one batched call -------
    with prof.phase("polish"):
        topos = _finalize_batch(n, items, cfg, cs)

    # ---- phase 5: release validation + spectral evaluation (one invariant
    # check and one r_asym per distinct support) ----------------------------
    with prof.phase("eval"):
        best_topo, best_val, failures = _pick_best(n, items, topos, sources)
        if best_topo is None:
            if failures:
                from .guard import TopologyInvariantError

                bad = failures[0].rsplit(": ", 1)[-1]
                raise TopologyInvariantError(
                    f"no candidate topology for n={n}, r={r}, "
                    f"scenario={scenario!r} passed release validation — "
                    f"first failure: {failures[0]!r} (all: {failures})",
                    invariant=bad, failures=failures)
            raise ValueError(
                f"failed to construct any connected topology for n={n}, "
                f"r={r}, scenario={scenario!r} — every candidate (ADMM, warm "
                "starts, classics) was disconnected under the constraints; "
                "raise r or relax the ConstraintSet")
        best_topo.meta["r_asym"] = best_val
    return best_topo


def _classic_candidates(n: int, r: int,
                        cs: ConstraintSet | None) -> list[tuple[str, np.ndarray]]:
    """Classic-topology candidates: the ADMM is non-convex, and on small
    tightly-budgeted instances a known-good structure (ring / torus) that
    happens to be feasible can beat a weak local optimum. Their weights get
    the same convex polish as the ADMM output so the comparison is fair.

    Returns (name, selection) pairs for the feasible classics. Only
    ``ValueError`` — the documented "n not expressible for this family"
    signal (e.g. hypercube needs a power of two) — skips a baseline; any
    other exception is a real construction bug and propagates.
    """
    from .topologies import make_baseline
    eidx = edge_index(n)
    out: list[tuple[str, np.ndarray]] = []
    for kind in ("ring", "torus", "hypercube"):
        try:
            base = make_baseline(kind, n)
        except ValueError:
            continue
        if len(base.edges) > r or base.meta.get("directed"):
            continue
        sel = np.zeros(len(all_edges(n)), dtype=bool)
        for e in base.edges:
            sel[eidx[tuple(sorted(e))]] = True
        if cs is not None and not cs.feasible(sel):
            continue
        out.append((base.name, sel))
    return out


def sweep_topologies(
    ns, rs, cfg: BATopoConfig | None = None,
) -> dict:
    """Deprecated signature-compatible wrapper: build
    :class:`~repro.core.anytime.TopologyRequest` objects and call
    :func:`~repro.core.anytime.solve_topologies` instead (same vmapped
    per-n sweep engine underneath). Returns ``{(n, r): Topology}`` exactly
    as before."""
    warnings.warn(
        "sweep_topologies(ns, rs, ...) is deprecated; build TopologyRequest "
        "objects and call repro.core.anytime.solve_topologies(...)",
        DeprecationWarning, stacklevel=2)
    return _sweep_requests(ns, rs, cfg)


def _sweep_requests(ns, rs, cfg: BATopoConfig | None = None) -> dict:
    """Homogeneous multi-scenario sweep: a BA-Topo for every (n, r) pair.

    For each node count n, the whole cardinality sweep ``rs`` runs as ONE
    vmapped, scan-compiled ADMM call (engine ``solve_sweep_spec`` — the
    budget r is a data leaf of the ProblemSpec, so instances with different
    budgets share a compilation). Warm starts and post-processing (support
    extraction, repair, polish, warm-start and classic-baseline comparison)
    stay per-instance on host. Returns ``{(n, r): Topology}``, keyed by the
    *requested* r (budgets above the candidate-edge count are clamped for
    the solve); a value is ``None`` if no connected candidate was found.
    Unlike the one-shot pipeline, the sweep uses one warm start per (n, r)
    — ``cfg.restarts`` is not consulted — and, like ``solve_batched``, it
    always runs the vmapped scan driver: a ``driver="python"`` preference
    applies only to the one-shot solve.
    """
    cfg = cfg or BATopoConfig()
    if cfg.admm.driver not in ("scan", "python"):
        raise ValueError(
            f"unknown driver {cfg.admm.driver!r}; expected 'scan' or 'python'")
    if cfg.admm.solver == "kkt_bicgstab_ilu":
        raise ValueError(
            "sweep_topologies needs a device backend (schur_cg or "
            "kkt_bicgstab); the scipy-ILU backend is host-side")
    _validate_pipeline_cfg(cfg)
    out: dict = {}
    for n in ns:
        out.update(_sweep_one_n(int(n), [int(r) for r in rs], cfg))
    return out


def _sweep_one_n(n: int, rs_req: list[int], cfg: BATopoConfig) -> dict:
    """One node count of the sweep: all budgets in ``rs_req`` solved as one
    vmapped dispatch. Shared by ``_sweep_requests`` and
    ``anytime.solve_topologies``."""
    import jax
    import jax.numpy as jnp

    from .engine import init_state, make_homo_spec, solve_sweep_spec

    out: dict = {}
    m = len(all_edges(n))
    rs_n = [min(r, m) for r in rs_req]  # solve with the clamped budget
    spec = make_homo_spec(n, max(rs_n), cfg.admm)
    # one warm start per (n, r); sweep instance k plays the role of
    # restart k, and the device SA batches instances whose warm graphs
    # share an edge count into one vmapped call
    inits, seeds = [], []
    for k, r in enumerate(rs_n):
        deg_targets = _homo_degree_targets(n, r)
        edges0, seed = _init_graph(n, r, "homo", None, deg_targets, cfg, k)
        inits.append(edges0)
        seeds.append(seed)
    warms = [_pack_warm(n, e)
             for e in _anneal_edges(n, inits, seeds, None, cfg)]
    states = [init_state(spec, jnp.asarray(g0), lam0) for g0, _, lam0 in warms]
    batched = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    from .shard import (
        resolve_partition, solve_spec_sharded, solve_sweep_spec_sharded)

    part = resolve_partition(cfg.admm.partition, n, batch=len(rs_n))
    if part == "instances":
        results = solve_sweep_spec_sharded(
            spec, np.asarray(rs_n), batched, cfg.admm)
    elif part == "edges":
        results = [solve_spec_sharded(
            spec.replace(r=jnp.asarray(rn, dtype=jnp.int64)),
            jax.tree.map(lambda a, k=k: a[k], batched), cfg.admm,
            r_cap=max(rs_n)) for k, rn in enumerate(rs_n)]
    else:
        results = solve_sweep_spec(spec, np.asarray(rs_n), batched, cfg.admm)
    for (r_req, r, warm, res) in zip(rs_req, rs_n, warms, results):
        meta = {"scenario": "homo", "r": r}
        items, sources = _candidate_items(n, r, [warm], [res], None, cfg,
                                          meta, use_z=False)
        topos = _finalize_batch(n, items, cfg, None)
        best, best_val, failures = _pick_best(n, items, topos, sources)
        if best is None and failures:
            from .guard import TopologyInvariantError

            bad = failures[0].rsplit(": ", 1)[-1]
            raise TopologyInvariantError(
                f"no candidate topology for n={n}, r={r} passed release "
                f"validation — first failure: {failures[0]!r} "
                f"(all: {failures})", invariant=bad, failures=failures)
        if best is not None:
            best.meta["r_asym"] = best_val
        out[(n, r_req)] = best  # keyed by the *requested* budget
    return out


def _greedy_constraint_graph(n: int, r: int, cs: ConstraintSet, rng) -> list[tuple[int, int]]:
    """Random feasible connected graph with ≤ r edges under ``cs`` capacities."""
    edges_full = all_edges(n)
    m = len(edges_full)
    order = [l for l in range(m) if cs.edge_ok[l]]
    for _ in range(256):
        rng.shuffle(order)
        usage = np.zeros(cs.q, dtype=np.int64)
        sel = np.zeros(m, dtype=bool)
        count = 0
        # first pass: spanning-tree bias for connectivity
        comp = list(range(n))

        def find(a):
            while comp[a] != a:
                comp[a] = comp[comp[a]]
                a = comp[a]
            return a

        for phase in (0, 1):
            for l in order:
                if count >= r:
                    break
                if sel[l]:
                    continue
                i, j = edges_full[l]
                if phase == 0 and find(i) == find(j):
                    continue
                col = cs.M[:, l]
                if np.any(usage + col > cs.e_cap):
                    continue
                sel[l] = True
                usage += col
                count += 1
                comp[find(i)] = find(j)
        edges = [edges_full[l] for l in np.nonzero(sel)[0]]
        if is_connected(n, edges):
            return edges
    raise RuntimeError("could not build a feasible connected warm start")
