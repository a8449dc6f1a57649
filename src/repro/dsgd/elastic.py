"""Elastic gossip training runtime for the real model zoo (DESIGN.md §16).

The chaos tier (§14) made the *simulated* DSGD engines fault-tolerant; this
module does the same for the real-model gossip loop that ``launch/train.py``
drives over ``repro/models``. One ``ElasticRuntime`` wraps a single jitted
train step with every time-varying input passed as DATA, so nothing a fault
or a re-optimization changes ever retraces:

  membership   ``ChaosSpec.alive``/``link_up`` rows feed ``degrade_matrix``
               inside the step: the effective mixing matrix is renormalized
               row-stochastic on the alive subgraph, dead workers freeze
               params AND optimizer state (``where(alive, …)``) and rejoin
               at their frozen state. With the all-clear masks the step is
               an IEEE-exact identity over ``dsgd_train_step`` — the
               fault-free elastic path is bit-exact vs the plain trainer
               (tested).
  watchdog     a per-round deadline derived from the Eq. 34 modeled latency
               (``node_step_latency_ms``, the per-node refinement of
               ``benchmarks.common.chaos_step_times``): nodes whose modeled
               round latency exceeds ``deadline_factor ×`` the fault-free
               round are dropped from the round's exchange only — they keep
               their local update, survivors renormalize, the round clock is
               capped at the deadline instead of waiting out the straggler.
               Round execution itself runs a bounded retry/backoff ladder
               with ``core.guard.run_ladder`` semantics (classified
               ``RungReport`` trail, never raises): a non-finite loss is
               retried ``max_round_retries`` times, then the round is
               skipped with the state frozen.
  re-optimize  a ``core.reopt.DriftDetector`` watches (B(t), alive) each
               round; on a trigger the incumbent is re-solved warm-started
               (``reoptimize_topology``'s warm → cold → keep-incumbent
               ladder) and the winner is adopted a deterministic
               ``activation_lag_steps`` later by hot-swapping the W matrix
               (and the deg-capped padded-neighbor tables of the kernel
               path) — data swaps, no retrace.
  resume       ``ElasticState`` round-trips through the checkpoint extras
               payload (``to_extras``/``from_extras``): incumbent + pending
               topology, detector baselines, PRNG key, data-stream position
               and the membership counters — everything a SIGKILLed run
               needs to reproduce the uninterrupted loss curve bit-exactly.

``make_elastic_sharded_train_step`` applies the same contract to the
production ppermute path: schedule weights and membership masks are data
(``gossip_shard_elastic``), so weight re-polish and churn never retrace;
only a support change rebuilds the schedule.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.bandwidth import PaperConstants, t_iter
from repro.core.graph import Topology, degrees, weight_matrix_from_weights
from repro.core.guard import RungReport
from repro.core.reopt import (
    DriftDetector,
    DriftPolicy,
    ReoptResult,
    reoptimize_topology,
)
from repro import obs
from repro.obs import PhaseProfile
from repro.optim import apply_updates

from .chaos import ChaosSpec, degrade_matrix
from .gossip import (
    elastic_neighbor_tables,
    gather_neighbor_weights,
    gossip_shard_elastic,
    gossip_sim_tree,
    schedule_weight_arrays,
)
from .schedule import GossipSchedule
from .trainer import DSGDState, _loss_fn

__all__ = ["ElasticSpec", "ElasticState", "ElasticHooks", "RoundReport",
           "ElasticRuntime", "make_elastic_train_step",
           "make_elastic_sharded_train_step", "node_step_latency_ms",
           "fault_free_round_ms"]


# ---------------------------------------------------------------------------
# modeled per-node latency (the watchdog's clock)
# ---------------------------------------------------------------------------

def node_step_latency_ms(topo: Topology, chaos: ChaosSpec, t: int,
                         const: PaperConstants = PaperConstants()
                         ) -> np.ndarray:
    """Per-node modeled latency (ms) of round ``t`` — the per-node view of
    ``benchmarks.common.chaos_step_times``.

    Node i's comm time is Eq. 34 at the slowest of its *active* incident
    edges (both endpoints alive; degree-shared ``min(B_i/d_i, B_j/d_j)``
    with static degrees — ports are provisioned for the full graph); its
    round latency is ``(t_comm + t_comp) × straggler_i(t)``. Dead nodes
    report 0 — they are not waited on. Link drops cost accuracy, not time
    (the exchange window elapses either way), matching the chaos clock.
    """
    n = topo.n
    alive = np.asarray(chaos.alive[t]) > 0
    bw = np.asarray(chaos.bandwidth[t], np.float64)
    strag = np.asarray(chaos.straggler[t], np.float64)
    d = np.maximum(degrees(n, topo.edges).astype(np.float64), 1.0)
    comm = np.zeros(n)
    for i, j in topo.edges:
        if alive[i] and alive[j]:
            b_e = min(bw[i] / d[i], bw[j] / d[j])
            t_e = t_iter(b_e, const)
            comm[i] = max(comm[i], t_e)
            comm[j] = max(comm[j], t_e)
    lat = (comm + const.t_comp_ms) * strag
    lat[~alive] = 0.0
    return lat


def fault_free_round_ms(topo: Topology, bandwidth: np.ndarray,
                        const: PaperConstants = PaperConstants()) -> float:
    """The fault-free modeled round time (ms) of ``topo`` under a static
    per-node ``bandwidth`` profile — the watchdog deadline's baseline."""
    n = topo.n
    bw = np.broadcast_to(np.asarray(bandwidth, np.float64), (n,))
    d = np.maximum(degrees(n, topo.edges).astype(np.float64), 1.0)
    comm = 0.0
    for i, j in topo.edges:
        comm = max(comm, t_iter(min(bw[i] / d[i], bw[j] / d[j]), const))
    return comm + const.t_comp_ms


# ---------------------------------------------------------------------------
# spec / state / reports
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ElasticSpec:
    """Static policy of an elastic run (the ChaosSpec carries the faults).

    ``deadline_factor``: round deadline = factor × the incumbent's
    fault-free modeled round time at the initial bandwidth profile.
    ``drop_stragglers``: watchdog authority to drop over-deadline nodes from
    a round's exchange (False = classic BSP: every round waits out the
    slowest straggler). ``max_round_retries``/``retry_backoff``: bounded
    retry ladder for non-finite rounds; retry k is modeled to cost
    ``backoff^k`` extra round times. ``reopt``: close the DriftDetector →
    ``reoptimize_topology`` loop; adopted topologies activate
    ``activation_lag_steps`` rounds after the trigger (deterministic in
    steps, so a resumed run replays the same adoption schedule bit-exactly;
    the measured solve wall time is reported, not modeled).
    ``reopt_budget``: bound the re-solve with the anytime pipeline —
    ``"window"`` budgets it to exactly the adoption window the fleet waits
    out anyway (``activation_lag_steps`` × the incumbent's modeled
    fault-free round time at the drifted profile), a float is an explicit
    ms budget, and None (default) keeps the unbudgeted deterministic
    re-solve: a wall-clock budget makes the adopted support
    timing-dependent, which would break the bit-exact crash/resume replay
    guarantee (DESIGN.md §16) — so budgeting is opt-in.
    """

    chaos: ChaosSpec
    deadline_factor: float = 3.0
    drop_stragglers: bool = True
    max_round_retries: int = 1
    retry_backoff: float = 2.0
    reopt: bool = True
    reopt_scenario: str = "node"
    reopt_r: int | None = None
    reopt_budget: float | str | None = None
    activation_lag_steps: int = 1
    drift: DriftPolicy = field(default_factory=DriftPolicy)
    topo_cfg: Any = None              # BATopoConfig | None (core.api import cycle)
    const: PaperConstants = field(default_factory=PaperConstants)


@dataclass
class ElasticState:
    """Host-side elastic runtime state — everything `--resume` must restore
    beyond the DSGDState pytree (see ``to_extras``/``from_extras``)."""

    topology: Topology
    W: jnp.ndarray                                  # (n, n) f32, data leaf
    nbr: tuple[jnp.ndarray, jnp.ndarray] | None     # deg-capped kernel tables
    detector: DriftDetector
    key: jnp.ndarray                                # PRNG key (folded per round)
    data_step: int = 0                              # batches consumed
    pending: tuple[int, Topology] | None = None     # (activate_step, topology)
    reopts: int = 0                                 # solver runs triggered
    adopted: int = 0                                # topologies hot-swapped
    dropped_rounds: int = 0                         # rounds with ≥1 drop
    drops: int = 0                                  # node-rounds dropped
    events: list[dict] = field(default_factory=list)


@dataclass
class RoundReport:
    """What one elastic round did (the watchdog/membership trail)."""

    step: int
    alive: np.ndarray                 # (n,) bool — chaos membership this round
    dropped: np.ndarray               # (n,) bool — watchdog drops this round
    round_ms: float                   # modeled round time (deadline-capped)
    deadline_ms: float
    attempts: int                     # step executions (1 + retries)
    rungs: list[RungReport]
    reopt: ReoptResult | None = None  # set when the detector fired this round
    reopt_reason: str | None = None
    swapped: bool = False             # a pending topology activated this round
    # host seconds of the round's phases: plan (step read, swap, watchdog,
    # mask uploads), dispatch (the step call), sync (the loss read), reopt
    profile: PhaseProfile = field(
        default_factory=lambda: PhaseProfile(area="round"))


class ElasticHooks:
    """Fault-injection seams (tests/bench only — production uses defaults).

    ``on_attempt(step, attempt, batch) -> batch`` runs before every step
    execution; returning a poisoned batch exercises the retry ladder,
    returning a repaired one exercises recovery."""

    def on_attempt(self, step: int, attempt: int, batch):
        return batch


# ---------------------------------------------------------------------------
# the jitted steps (everything time-varying is data)
# ---------------------------------------------------------------------------

def _bmask(mask: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """(n,) mask broadcast against a stacked (n, ...) leaf, as bool."""
    return (mask > 0).reshape((x.shape[0],) + (1,) * (x.ndim - 1))


def _masked_consensus_error(params, alive: jnp.ndarray,
                            n_alive: jnp.ndarray) -> jnp.ndarray:
    """‖x − x̄‖_F over the ALIVE replicas. With the all-ones mask this is
    bit-equal to ``trainer._consensus_error`` (multiplies by 1.0 are exact,
    the reductions are the same); dead nodes' frozen params are excluded so
    churn does not masquerade as divergence."""
    def leaf_err(x):
        m = _bmask(alive, x).astype(x.dtype)
        mean = (x * m).sum(axis=0, keepdims=True) / n_alive.astype(x.dtype)
        return jnp.sum(jnp.square(((x - mean) * m).astype(jnp.float32)))
    return jnp.sqrt(sum(jax.tree.leaves(jax.tree.map(leaf_err, params))))


def make_elastic_train_step(cfg, opt_update: Callable, *,
                            use_kernel: bool = False):
    """The elastic stacked-worker step — ``dsgd_train_step``'s math with the
    fault tensors as arguments:

      step(state, batch, W, alive, link_up, mix_mask[, nbr_idx, nbr_mask])
        → (state, metrics)

    ``W (n,n)`` the incumbent mixing matrix (hot-swap = new array),
    ``alive (n,)`` chaos membership (dead ⇒ params+optimizer freeze),
    ``mix_mask (n,)`` round participation = alive ∧ ¬watchdog-dropped
    (dropped nodes keep their LOCAL update — they are late, not dead),
    ``link_up (n,n)`` packet-loss mask. Mixing runs over
    ``degrade_matrix(W, mix_mask, link_up)`` — row-stochastic on the
    participating subgraph. All-clear masks make every mask op an IEEE-exact
    identity, so the fault-free elastic step is bit-exact vs
    ``dsgd_train_step`` (tested). The kernel path gathers its per-round
    weights from the degraded matrix on device over deg-capped tables, so
    topology swaps stay retrace-free there too.
    """
    loss_fn = _loss_fn(cfg)

    def _step(state: DSGDState, batch, W, alive, link_up, mix_mask,
              nbr_idx=None, nbr_mask=None):
        losses, grads = jax.vmap(jax.value_and_grad(loss_fn))(state.params, batch)
        with obs.scope("optimizer"):
            updates, opt = jax.vmap(opt_update)(grads, state.opt, state.params)
            local = jax.vmap(apply_updates)(state.params, updates)
        with obs.scope("gossip"):
            W_eff = degrade_matrix(W, mix_mask, link_up)
            if use_kernel:
                from repro.kernels.gossip_mix.ops import gossip_mix_batched

                weights = gather_neighbor_weights(W_eff, nbr_idx, nbr_mask)
                mixed = jax.tree.map(
                    lambda x: gossip_mix_batched(x, nbr_idx, weights), local)
            else:
                mixed = gossip_sim_tree(local, W_eff)
        params = jax.tree.map(
            lambda mx, lc, od: jnp.where(
                _bmask(mix_mask, mx), mx, jnp.where(_bmask(alive, lc), lc, od)),
            mixed, local, state.params)
        opt = jax.tree.map(
            lambda nw, od: jnp.where(_bmask(alive, nw), nw, od),
            opt, state.opt)
        n_alive = alive.sum()
        loss = (losses * alive).sum() / n_alive
        loss_max = jnp.where(alive > 0, losses, -jnp.inf).max()
        metrics = {"loss": loss, "loss_max": loss_max,
                   "consensus_err": _masked_consensus_error(params, alive,
                                                            n_alive),
                   "n_alive": n_alive}
        return DSGDState(params, opt, state.step + 1), metrics

    return jax.jit(_step)


def make_elastic_sharded_train_step(cfg, sched: GossipSchedule,
                                    opt_update: Callable, mesh, *,
                                    gossip_axes=("data",)):
    """Elastic variant of ``make_sharded_train_step`` (the production
    ppermute path): schedule weights and membership are DATA —

      step(state, batch, alive, mix_mask, w_self, w_recv) → (state, metrics)

    ``w_self (n,)`` / ``w_recv (rounds, n)`` from
    ``gossip.schedule_weight_arrays`` (a re-polished weight set hot-swaps
    without retrace; a support change rebuilds the schedule and retraces),
    ``alive``/``mix_mask`` as in the stacked step. Dead workers freeze
    params+optimizer on device; dropped stragglers skip the exchange with
    the row-stochastic renorm done inside ``gossip_shard_elastic``.
    """
    axis = gossip_axes if len(gossip_axes) > 1 else gossip_axes[0]
    loss_fn = _loss_fn(cfg)

    def worker(params, opt, batch, step, alive, mix_mask, w_self, w_recv):
        sq = lambda t: jax.tree.map(lambda x: x[0], t)
        un = lambda t: jax.tree.map(lambda x: x[None], t)
        p1, o1 = sq(params), sq(opt)
        b1 = sq(batch)
        loss, grads = jax.value_and_grad(loss_fn)(p1, b1)
        with obs.scope("optimizer"):
            updates, o2 = opt_update(grads, o1, p1)
            p2 = apply_updates(p1, updates)
        with obs.scope("gossip"):
            pm = gossip_shard_elastic(p2, sched, axis, mix_mask, w_self,
                                      w_recv)
        i = jax.lax.axis_index(axis)
        a_i, m_i = alive[i] > 0, mix_mask[i] > 0
        p_out = jax.tree.map(
            lambda mx, lc, od: jnp.where(m_i, mx, jnp.where(a_i, lc, od)),
            pm, p2, p1)
        o_out = jax.tree.map(lambda nw, od: jnp.where(a_i, nw, od), o2, o1)
        a_f = alive[i].astype(jnp.float32)
        loss = jax.lax.psum(loss * a_f, axis) / jax.lax.psum(a_f, axis)
        return un(p_out), un(o_out), loss

    nspec = P(gossip_axes if len(gossip_axes) > 1 else gossip_axes[0])
    smapped = jax.shard_map(
        worker, mesh=mesh,
        in_specs=(nspec, nspec, nspec, P(), P(), P(), P(), P()),
        out_specs=(nspec, nspec, P()),
        axis_names=set(gossip_axes),
        check_vma=False,  # model scan carries flip axis-invariant → varying
    )

    def train_step(state: DSGDState, batch, alive, mix_mask, w_self, w_recv):
        params, opt, loss = smapped(state.params, state.opt, batch, state.step,
                                    alive, mix_mask, w_self, w_recv)
        return DSGDState(params, opt, state.step + 1), {"loss": loss}

    return train_step


# ---------------------------------------------------------------------------
# the runtime (host-side orchestration around the one jitted step)
# ---------------------------------------------------------------------------

class ElasticRuntime:
    """Watchdog + membership + re-optimization around one jitted step.

    ``round()`` never raises on a classified failure: a poisoned round walks
    the retry ladder and, exhausted, freezes the state for that round — the
    ``RoundReport`` carries the full rung trail (``run_ladder`` semantics).
    """

    def __init__(self, cfg, spec: ElasticSpec, topology: Topology,
                 opt_update: Callable, *, use_kernel: bool = False,
                 deg_cap: int | None = None, step_fn=None,
                 hooks: ElasticHooks | None = None):
        if spec.chaos.n != topology.n:
            raise ValueError(f"ChaosSpec is for n={spec.chaos.n} nodes but "
                             f"the topology has n={topology.n}")
        self.cfg = cfg
        self.spec = spec
        self.n = topology.n
        self.use_kernel = use_kernel
        self.deg_cap = deg_cap if deg_cap is not None else max(self.n - 1, 1)
        self.step_fn = step_fn if step_fn is not None else \
            make_elastic_train_step(cfg, opt_update, use_kernel=use_kernel)
        self.hooks = hooks or ElasticHooks()
        self.deadline_ms = spec.deadline_factor * fault_free_round_ms(
            topology, spec.chaos.bandwidth[0], spec.const)

    # -- state ------------------------------------------------------------

    def make_state(self, topology: Topology, seed: int = 0) -> ElasticState:
        ch = self.spec.chaos
        return ElasticState(
            topology=topology,
            W=self._matrix(topology),
            nbr=self._tables(topology),
            detector=DriftDetector.from_profile(ch.bandwidth[0], ch.alive[0],
                                                self.spec.drift),
            key=jax.random.PRNGKey(seed),
        )

    def _matrix(self, topo: Topology) -> jnp.ndarray:
        return jnp.asarray(
            weight_matrix_from_weights(topo.n, topo.edges, topo.g), jnp.float32)

    def _tables(self, topo: Topology):
        if not self.use_kernel:
            return None
        return elastic_neighbor_tables(np.asarray(self._matrix(topo)),
                                       deg_cap=self.deg_cap)

    def _adopt(self, es: ElasticState, topo: Topology, t: int,
               bw: np.ndarray, alive: np.ndarray) -> None:
        es.topology = topo
        es.W = self._matrix(topo)
        es.nbr = self._tables(topo)
        es.detector.rebase(bw, alive)
        es.pending = None
        es.adopted += 1
        es.events.append({"step": t, "event": "adopt", "name": topo.name})

    # -- one round --------------------------------------------------------

    def round(self, state: DSGDState, es: ElasticState, batch
              ) -> tuple[DSGDState, dict, RoundReport]:
        spec, ch = self.spec, self.spec.chaos
        prof = PhaseProfile(area="round")
        with prof.phase("plan"):
            t = int(state.step)
            ti = min(t, ch.steps - 1)
            alive_np = np.asarray(ch.alive[ti]) > 0
            bw_np = np.asarray(ch.bandwidth[ti], np.float64)

            swapped = False
            if es.pending is not None and t >= es.pending[0]:
                self._adopt(es, es.pending[1], t, bw_np, ch.alive[ti])
                swapped = True

            # watchdog: modeled latencies vs the round deadline
            lat = node_step_latency_ms(es.topology, ch, ti, spec.const)
            dropped = np.zeros(self.n, bool)
            if spec.drop_stragglers:
                dropped = alive_np & (lat > self.deadline_ms)
                if dropped.all() or not (alive_np & ~dropped).any():
                    dropped[:] = False      # the watchdog cannot drop everyone
            mix_np = (alive_np & ~dropped).astype(np.float32)
            participants = lat[alive_np & ~dropped]
            round_ms = float(participants.max()) if participants.size else 0.0
            if dropped.any():
                # the watchdog waits until the deadline to declare the drop
                round_ms = max(round_ms, self.deadline_ms)
                es.dropped_rounds += 1
                es.drops += int(dropped.sum())

            alive_d = jnp.asarray(ch.alive[ti], jnp.float32)
            link_d = jnp.asarray(ch.link_up[ti], jnp.float32)
            mix_d = jnp.asarray(mix_np)

        # bounded retry/backoff ladder (run_ladder semantics: classified
        # rung reports, never raises; terminal rung freezes the round)
        rungs: list[RungReport] = []
        new_state = metrics = None
        attempts = 0
        for k in range(spec.max_round_retries + 1):
            attempts = k + 1
            ab = self.hooks.on_attempt(t, k, batch)
            with prof.phase("dispatch", step=t):
                cand_state, cand_metrics = self._run(state, ab, es, alive_d,
                                                     link_d, mix_d)
            with prof.phase("sync", step=t):
                loss = float(cand_metrics["loss"])
            name = "round" if k == 0 else f"retry{k}"
            if np.isfinite(loss):
                rungs.append(RungReport(name, "ok"))
                new_state, metrics = cand_state, cand_metrics
                break
            rungs.append(RungReport(name, "non_finite", f"loss={loss}"))
            round_ms += round_ms and self.deadline_ms * spec.retry_backoff ** k
        if new_state is None:
            rungs.append(RungReport("freeze", "ok",
                                    "retries exhausted — round skipped, "
                                    "state frozen"))
            new_state = DSGDState(state.params, state.opt, state.step + 1)
            metrics = {"loss": jnp.float32(np.nan),
                       "loss_max": jnp.float32(np.nan),
                       "consensus_err": jnp.float32(np.nan),
                       "n_alive": jnp.float32(alive_np.sum())}

        # drift detection → warm re-optimization → deferred adoption
        reopt_res, reason = None, None
        with prof.phase("reopt", step=t):
            if spec.reopt and es.pending is None:
                reason = es.detector.check(t, bw_np, ch.alive[ti])
                if reason is not None:
                    reopt_res = self._reoptimize(es, t, bw_np, ch.alive[ti],
                                                 reason)

        es.data_step += 1
        es.key = jax.random.fold_in(es.key, t)
        report = RoundReport(step=t, alive=alive_np, dropped=dropped,
                             round_ms=round_ms, deadline_ms=self.deadline_ms,
                             attempts=attempts, rungs=rungs, reopt=reopt_res,
                             reopt_reason=reason, swapped=swapped,
                             profile=prof)
        return new_state, metrics, report

    def _run(self, state, batch, es: ElasticState, alive, link_up, mix):
        if self.use_kernel:
            return self.step_fn(state, batch, es.W, alive, link_up, mix,
                                es.nbr[0], es.nbr[1])
        return self.step_fn(state, batch, es.W, alive, link_up, mix)

    def _reoptimize(self, es: ElasticState, t: int, bw: np.ndarray,
                    alive, reason: str) -> ReoptResult:
        spec = self.spec
        budget_ms = None
        if spec.reopt_budget is not None:
            if spec.reopt_budget == "window":
                budget_ms = (max(spec.activation_lag_steps, 1)
                             * fault_free_round_ms(es.topology, bw, spec.const))
            else:
                budget_ms = float(spec.reopt_budget)
        res = reoptimize_topology(
            es.topology, scenario=spec.reopt_scenario,
            node_bandwidths=bw if spec.reopt_scenario == "node" else None,
            r=spec.reopt_r, alive=np.asarray(alive), cfg=spec.topo_cfg,
            policy=spec.drift, budget_ms=budget_ms)
        es.reopts += 1
        if res.reoptimized:
            es.pending = (t + max(spec.activation_lag_steps, 1), res.topology)
            es.events.append({"step": t, "event": "reopt", "reason": reason,
                              "time_to_reopt_s": res.time_to_reopt_s,
                              "r_asym_after": res.r_asym_after})
        else:
            es.events.append({"step": t, "event": "keep_incumbent",
                              "reason": res.fallback_reason})
        return res

    # -- crash-safe resume (checkpoint extras payload) --------------------

    def to_extras(self, es: ElasticState) -> dict[str, np.ndarray]:
        """ElasticState → named arrays for ``CheckpointManager.save(extra=)``.
        Everything here is exactly what ``from_extras`` needs to continue
        the run bit-exactly: topology support+weights (edge counts change
        across reopts, hence the shape-free extras channel), detector
        baselines, pending adoption, PRNG key, stream position, counters."""
        topo = es.topology
        out = {
            "edges": np.asarray(topo.edges, np.int64).reshape(-1, 2),
            "g": np.asarray(topo.g, np.float64),
            **es.detector.to_state(),
            "key": np.asarray(es.key),
            "data_step": np.asarray(es.data_step, np.int64),
            "counters": np.asarray([es.reopts, es.adopted, es.dropped_rounds,
                                    es.drops], np.int64),
            "pending_step": np.asarray(
                -1 if es.pending is None else es.pending[0], np.int64),
        }
        if es.pending is not None:
            ptopo = es.pending[1]
            out["pending_edges"] = np.asarray(ptopo.edges,
                                              np.int64).reshape(-1, 2)
            out["pending_g"] = np.asarray(ptopo.g, np.float64)
        return out

    def from_extras(self, extras: dict[str, np.ndarray],
                    name: str = "resumed") -> ElasticState:
        """Rebuild the ElasticState a checkpoint carried (inverse of
        ``to_extras``)."""
        edges = [tuple(int(v) for v in e) for e in extras["edges"]]
        topo = Topology(self.n, edges, np.asarray(extras["g"]), name=name)
        det = DriftDetector.from_state(extras, self.spec.drift)
        reopts, adopted, dropped_rounds, drops = (
            int(v) for v in extras["counters"])
        pending = None
        p_step = int(extras["pending_step"])
        if p_step >= 0:
            p_edges = [tuple(int(v) for v in e)
                       for e in extras["pending_edges"]]
            pending = (p_step, Topology(self.n, p_edges,
                                        np.asarray(extras["pending_g"]),
                                        name=name + "-pending"))
        return ElasticState(
            topology=topo, W=self._matrix(topo), nbr=self._tables(topo),
            detector=det, key=jnp.asarray(extras["key"]),
            data_step=int(extras["data_step"]), pending=pending,
            reopts=reopts, adopted=adopted, dropped_rounds=dropped_rounds,
            drops=drops)
