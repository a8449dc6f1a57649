"""Anytime topology pipeline: request/result API, incumbent semantics,
parity oracle against the phase-barriered pipeline (DESIGN.md §17)."""
import numpy as np
import pytest

from repro.core import BATopoConfig
from repro.core.anytime import (
    AnytimeSolver,
    PhaseProfile,
    TopologyRequest,
    solve_topologies,
    solve_topology,
    validate_request,
)
from repro.core.api import optimize_topology
from repro.core.constraints import bcube_constraints, intra_server_constraints
from repro.core.guard import check_invariants, validate_topology
from repro.core.reopt import reoptimize_topology
from repro.core.warmstart import anneal_topology_batched, anneal_topology_stream

FAST = BATopoConfig(sa_iters=120, polish_iters=100, restarts=2)

NODE_BW_16 = np.array([9.76] * 8 + [3.25] * 8)


def _support(topo):
    return sorted(tuple(sorted(e)) for e in topo.edges)


# =========================================================================
# the parity oracle: budget_ms=None replays the barrier pipeline
# =========================================================================

@pytest.mark.parametrize("kw", [
    dict(n=16, r=32, scenario="homo"),
    dict(n=16, r=32, scenario="node", node_bandwidths=NODE_BW_16),
    dict(n=8, r=12, scenario="constraint", cs=intra_server_constraints(8)),
    dict(n=16, r=48, scenario="constraint", cs=bcube_constraints(p=4, k=2)),
], ids=["homo", "node", "intra", "bcube"])
def test_unbudgeted_parity_with_barrier(kw):
    """Unbudgeted anytime result is support-equal to the pre-refactor
    ``optimize_topology`` on every paper scenario, with r_asym drift ≤ 1e-3
    (the ISSUE-10 acceptance band; in practice the replay is bit-exact)."""
    with pytest.deprecated_call():
        legacy = optimize_topology(kw["n"], kw["r"], kw["scenario"],
                                   cs=kw.get("cs"),
                                   node_bandwidths=kw.get("node_bandwidths"),
                                   cfg=FAST)
    res = solve_topology(TopologyRequest(**kw), cfg=FAST)
    assert res.complete and res.quality_tier == "full"
    assert _support(res.topology) == _support(legacy)
    assert abs(res.r_asym - float(legacy.meta["r_asym"])) <= 1e-3
    assert res.topology.meta.get("selected_from") == \
        legacy.meta.get("selected_from")


def test_barrier_engine_matches_legacy_exactly():
    with pytest.deprecated_call():
        legacy = optimize_topology(12, 24, "homo", cfg=FAST)
    prof: dict = {}
    res = solve_topology(TopologyRequest(n=12, r=24), cfg=FAST,
                         profile=prof, engine="barrier")
    assert _support(res.topology) == _support(legacy)
    assert res.quality_tier == "full" and res.complete
    assert set(prof) >= {"warm_s", "admm_s", "polish_s", "eval_s"}


def test_solve_topologies_matches_sweep_grouping():
    """The batch front end groups sweepable homo requests through the
    legacy sweep engine (same amortized batching, same results) and solves
    hetero requests individually, returning results in input order."""
    from repro.core.api import sweep_topologies

    reqs = [TopologyRequest(n=12, r=24),
            TopologyRequest(n=8, r=12, scenario="constraint",
                            cs=intra_server_constraints(8)),
            TopologyRequest(n=12, r=18)]
    out = solve_topologies(reqs, cfg=FAST)
    assert len(out) == 3
    for req, res in zip(reqs, out):
        assert res.topology is not None and res.topology.n == req.n
        assert res.complete and res.quality_tier == "full"
    with pytest.deprecated_call():
        legacy = sweep_topologies([12], [24, 18], cfg=FAST)
    assert _support(out[0].topology) == _support(legacy[(12, 24)])
    assert _support(out[2].topology) == _support(legacy[(12, 18)])
    single = solve_topology(reqs[1], cfg=FAST)
    assert _support(out[1].topology) == _support(single.topology)


# =========================================================================
# incumbent semantics under a budget
# =========================================================================

def test_incumbent_monotone_and_final_result():
    solver = AnytimeSolver(TopologyRequest(n=16, r=32, deadline_ms=60_000.0),
                           FAST)
    seen = []
    while (inc := solver.next_improvement()) is not None:
        seen.append(inc)
    assert len(seen) >= 2                   # classics then at least one solve
    r_seq = [inc.r_asym for inc in seen]
    assert all(b <= a for a, b in zip(r_seq, r_seq[1:])), \
        "incumbent quality must be monotone non-increasing in r_asym"
    t_seq = [inc.elapsed_ms for inc in seen]
    assert all(b >= a for a, b in zip(t_seq, t_seq[1:]))
    res = solver.result()
    assert res.r_asym == seen[-1].r_asym
    assert res.improvements == len(seen)
    validate_topology(res.topology, context="anytime final")


def test_expired_budget_returns_release_valid_topology():
    res = solve_topology(TopologyRequest(n=16, r=32), cfg=FAST,
                         budget_ms=1e-3)
    assert not res.complete
    assert res.quality_tier == "classic"
    assert res.reason and "budget" in res.reason
    validate_topology(res.topology, context="expired budget")
    assert check_invariants(res.topology) is None


def test_tight_budget_is_valid_and_reports_curtailment():
    res = solve_topology(TopologyRequest(n=16, r=32), cfg=FAST,
                         budget_ms=40.0)
    assert res.topology is not None
    validate_topology(res.topology, context="tight budget")
    if not res.complete:
        assert res.reason                    # says what was skipped/curtailed


# =========================================================================
# one validation path (satellite: dedup + byte-identical messages)
# =========================================================================

@pytest.mark.parametrize("kw,frag", [
    (dict(n=1, r=4), "need n >= 2"),
    (dict(n=8, r=3), "can never connect"),
    (dict(n=8, r=16, scenario="warp"), "unknown scenario"),
    (dict(n=8, r=16, scenario="node"), "requires node_bandwidths"),
    (dict(n=8, r=16, scenario="node",
          node_bandwidths=np.full(8, np.nan)), "finite and positive"),
    (dict(n=8, r=16, scenario="constraint"), "requires a ConstraintSet"),
    (dict(n=8, r=16, deadline_ms=-5.0), "deadline_ms"),
    (dict(n=8, r=16, restarts=0), "restarts"),
])
def test_validate_request_covers_service_admission(kw, frag):
    bad = validate_request(TopologyRequest(**kw))
    assert bad is not None and frag in bad
    with pytest.raises(ValueError):
        AnytimeSolver(TopologyRequest(**kw), FAST)


def test_scenario_error_messages_stay_context_pinned():
    """The pre-refactor entrypoints kept their exact error texts."""
    with pytest.raises(ValueError) as api_err, pytest.deprecated_call():
        optimize_topology(8, 16, "node")
    assert str(api_err.value) == ("scenario='node' requires node_bandwidths "
                                  "(per-node GB/s profile for Algorithm 1)")
    with pytest.raises(ValueError) as reopt_err:
        from repro.core import make_baseline
        reoptimize_topology(make_baseline("ring", 8), scenario="node")
    assert str(reopt_err.value) == ("scenario='node' re-optimization requires "
                                    "the drifted node_bandwidths profile")
    with pytest.raises(ValueError) as cs_err, pytest.deprecated_call():
        optimize_topology(8, 16, "constraint")
    assert str(cs_err.value) == ("scenario='constraint' requires a "
                                 "ConstraintSet (cs=...)")


def test_old_entrypoints_warn_but_work():
    with pytest.deprecated_call():
        topo = optimize_topology(8, 16, "homo", cfg=FAST)
    assert check_invariants(topo) is None


# =========================================================================
# PhaseProfile (satellite: documented schema + merge)
# =========================================================================

def test_phase_profile_merge_and_dict_roundtrip():
    a = PhaseProfile({"warm": 0.5, "admm": 2.0})
    b = PhaseProfile({"admm": 1.0, "eval": 0.25})
    m = a.merge(b)
    assert m.phases == {"warm": 0.5, "admm": 3.0, "eval": 0.25}
    assert a.phases == {"warm": 0.5, "admm": 2.0}   # merge is non-mutating
    d = m.to_dict()
    assert d == {"warm_s": 0.5, "admm_s": 3.0, "eval_s": 0.25}
    assert PhaseProfile.from_dict(d).phases == m.phases
    # legacy key spellings: *_s is seconds, *_ms is milliseconds
    p = PhaseProfile.from_dict({"queue_s": 1.0, "solve_ms": 500.0})
    assert p.phases == {"queue": 1.0, "solve": 0.5}


def test_solve_topology_fills_legacy_profile_dict():
    prof: dict = {}
    solve_topology(TopologyRequest(n=8, r=16), cfg=FAST, profile=prof)
    assert prof and all(k.endswith("_s") for k in prof)


# =========================================================================
# streaming SA (the stage the budgeted path interleaves)
# =========================================================================

def test_anneal_stream_bit_equals_batched():
    n, iters = 12, 60
    rng = np.random.default_rng(0)
    edges0 = []
    for _ in range(2):
        perm = rng.permutation(n)
        edges0.append(sorted(tuple(sorted((int(perm[i]),
                                           int(perm[(i + 1) % n]))))
                             for i in range(n)))
    ref = anneal_topology_batched(n, edges0, iters=iters, seeds=[3, 4])
    last = None
    for best_edges, costs, t_done in anneal_topology_stream(
            n, edges0, iters=iters, seeds=[3, 4], chunk=17):
        last = (best_edges, t_done)
    assert last is not None and last[1] == iters
    assert [sorted(e) for e in last[0]] == [sorted(e) for e in ref]
