"""Program spans and counters (``repro.obs``): solve phases and ADMM/CG
counters, the elastic round's host spans, and named scopes in the training
step that change HLO metadata only."""
import contextlib
import glob
import os
import re
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro import obs
from repro.configs import get_arch, reduced_for_smoke
from repro.core import (BATopoConfig, TopologyRequest, make_baseline,
                        solve_topology)
from repro.core import api as core_api
from repro.data import DataConfig, synthetic_lm_batch
from repro.dsgd import (ElasticRuntime, ElasticSpec, init_dsgd_state,
                        make_elastic_train_step, no_chaos)
from repro.obs import PhaseProfile
from repro.optim import sgd_momentum

FAST = BATopoConfig(sa_iters=120, polish_iters=100, restarts=2)
SOLVE_PHASES = {"prep", "warm", "admm", "round", "polish", "eval"}
SCOPES = ("attention", "mlp", "logits", "optimizer", "gossip")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def repro_spans(tmp_path):
    """Trace the block on the CPU; afterwards the list holds every
    ``repro.*`` host span as ``(name, args)``."""
    spans: list[tuple[str, dict]] = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield spans
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.append((ev.name, dict(ev.stats)))


# --- PhaseProfile ----------------------------------------------------------

def test_phase_adds_the_block_seconds_only_when_it_ends_normally():
    ticks = iter([10.0, 12.5, 20.0, 21.0, 30.0, 34.0])
    p = PhaseProfile(area="solve", clock=lambda: next(ticks))
    with p.phase("admm", restart=0) as ph:
        pass
    assert ph.seconds == 2.5 and p.phases == {"admm": 2.5}
    with p.phase("admm", restart=1):
        pass
    assert p.phases == {"admm": 3.5}
    with pytest.raises(RuntimeError):
        with p.phase("round"):
            raise RuntimeError("no seconds for a block that fails")
    assert p.phases == {"admm": 3.5}
    p.count("admm_iters", 7)
    p.count("admm_iters", 5)
    assert p.counts == {"admm_iters": 12}
    assert p.to_dict() == {"admm_s": 3.5}
    assert p.add_to({"admm_s": 1.0, "queue_s": 2.0}) == {"admm_s": 4.5,
                                                        "queue_s": 2.0}


def test_merge_sums_phases_and_counters():
    a = PhaseProfile({"admm": 1.0}, area="solve", counts={"admm_iters": 600})
    b = PhaseProfile({"admm": 2.0, "round": 0.5},
                     counts={"admm_iters": 300, "cg_iters": 1500})
    m = a.merge(b)
    assert m.phases == {"admm": 3.0, "round": 0.5}
    assert m.counts == {"admm_iters": 900, "cg_iters": 1500}
    assert m.area == "solve"
    assert a.counts == {"admm_iters": 600} and a.phases == {"admm": 1.0}
    assert a.merge({"warm_s": 0.25}).counts == {"admm_iters": 600}


# --- the topology pipeline -------------------------------------------------

def test_solve_phase_spans_and_admm_cg_counters(tmp_path, monkeypatch):
    """A tiny unbudgeted solve on a CPU trace: one ``repro.solve`` span with
    the request's ids, every phase as a ``repro.solve.<phase>`` span, the
    per-restart ones carrying ``restart=``, and the counters equal to the
    sums of the restarts' ``ADMMResult`` counts."""
    results = []
    make = core_api._make_solver

    def recording_solver(*a, **k):
        solver = make(*a, **k)
        solve = solver.solve

        def recorded(*sa, **sk):
            res = solve(*sa, **sk)
            results.append(res)
            return res
        solver.solve = recorded
        return solver

    monkeypatch.setattr(core_api, "_make_solver", recording_solver)
    with repro_spans(tmp_path) as spans:
        res = solve_topology(TopologyRequest(n=8, r=12, seed=3), cfg=FAST)
    names = [n for n, _ in spans]
    assert [a for n, a in spans if n == "repro.solve"] == [
        {"n": 8, "r": 12, "seed": 3}]
    assert {n.rsplit(".", 1)[1] for n in names if n.count(".") == 2} \
        == SOLVE_PHASES
    for phase in ("warm", "admm", "round"):
        restarts = sorted(a["restart"] for n, a in spans
                          if n == f"repro.solve.{phase}")
        assert restarts == [0, 1], (phase, restarts)
    assert len(results) == 2
    assert res.profile.counts["admm_iters"] == sum(r.iters for r in results)
    assert res.profile.counts["cg_iters"] == sum(r.cg_iters for r in results)
    assert res.profile.counts["admm_iters"] > 0


def test_solve_phase_keys_and_dict_view_are_unchanged(tmp_path):
    """The anytime phases keep their keys and ``<phase>_s`` view and lie
    inside the solve's wall time; the barrier fills the legacy dict with the
    same keys as before, adding to what the dict held, under the same
    ``repro.solve.<phase>`` spans."""
    res = solve_topology(TopologyRequest(n=8, r=12), cfg=FAST)
    assert set(res.profile.phases) == SOLVE_PHASES
    assert set(res.profile.to_dict()) == {f"{k}_s" for k in SOLVE_PHASES}
    assert all(v > 0 for v in res.profile.phases.values())
    assert sum(res.profile.phases.values()) <= res.elapsed_ms / 1e3
    prof = {"warm_s": 1.0, "queue_s": 2.0}
    with repro_spans(tmp_path) as spans:
        bar = solve_topology(TopologyRequest(n=8, r=12), cfg=FAST,
                             profile=prof, engine="barrier")
    assert set(prof) == {"warm_s", "admm_s", "round_s", "polish_s", "eval_s",
                         "queue_s"}
    assert prof["warm_s"] > 1.0 and prof["queue_s"] == 2.0
    assert sum(v for k, v in prof.items() if k != "queue_s") - 1.0 \
        <= bar.elapsed_ms / 1e3
    assert {n for n, _ in spans} == {"repro.solve"} | {
        f"repro.solve.{k}" for k in ("warm", "admm", "round", "polish",
                                     "eval")}


def test_service_profile_carries_the_solve_counters():
    """A deadlined request runs the anytime solver; its response's
    ``profile`` dict reports the ADMM and CG iterations beside the phase
    seconds."""
    from repro.serve.topo_service import TopologyService

    svc = TopologyService(cfg=FAST)
    resp = svc.request(8, 12, deadline_ms=600_000.0)
    assert resp.ok and resp.quality_tier == "full"
    assert resp.profile["admm_iters"] > 0
    assert resp.profile["cg_iters"] >= resp.profile["admm_iters"]
    assert {"queue_s", "admm_s", "solve_s"} <= set(resp.profile)


# --- the elastic round -----------------------------------------------------

def test_elastic_round_spans_and_report_profile(tmp_path):
    cfg = reduced_for_smoke(get_arch("smollm-135m"))
    n = 4
    topo = make_baseline("ring", n)
    opt_init, opt_update = sgd_momentum(0.05)
    state = init_dsgd_state(jax.random.PRNGKey(0), cfg, n, opt_init)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, batch_size=1)
    per = [synthetic_lm_batch(dc, 0, node=i) for i in range(n)]
    batch = {k: jnp.stack([b[k] for b in per]) for k in per[0]}
    rt = ElasticRuntime(cfg, ElasticSpec(chaos=no_chaos(2, n), reopt=True),
                        topo, opt_update)
    es = rt.make_state(topo)
    state, _, _ = rt.round(state, es, batch)          # compiles
    with repro_spans(tmp_path) as spans:
        _, _, rep = rt.round(state, es, batch)
    round_spans = {nm: a for nm, a in spans if nm.startswith("repro.round.")}
    assert set(round_spans) == {f"repro.round.{p}" for p in
                                ("plan", "dispatch", "sync", "reopt")}
    assert round_spans["repro.round.dispatch"] == {"step": 1}
    assert rep.profile.area == "round"
    assert set(rep.profile.phases) == {"plan", "dispatch", "sync", "reopt"}
    assert all(v > 0 for v in rep.profile.phases.values())


def test_train_logs_the_round_split(tmp_path):
    from repro.launch import train

    out = train.main(["--arch", "smollm-135m", "--reduced", "--workers", "4",
                      "--steps", "2", "--batch", "1", "--seq", "16",
                      "--topo", "ring", "--elastic", "--log-every", "1",
                      "--json-out", str(tmp_path / "run.json")])
    for h in out["history"]:
        assert {"step_s", "plan_s", "dispatch_s", "sync_s", "reopt_s"} <= set(h)
        assert h["dispatch_s"] + h["sync_s"] <= h["step_s"]


# --- named scopes in the training step -------------------------------------

_METADATA = re.compile(r",? metadata=\{[^}]*\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_NUMBERED = re.compile(r"[A-Za-z_][\w\-]*(?:\.\d+)+")


def _without_metadata(text: str) -> str:
    """HLO text less each instruction's ``metadata={...}`` and the source
    tables (file, function, location, frame) that metadata points into,
    with numbered names (``broadcast_in_dim.506``) renamed in order of first
    appearance: a scope can shift the counter that makes names unique."""
    out, skip = [], False
    for line in text.splitlines():
        if line in _DEBUG_TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
        if not skip:
            out.append(line)
    names: dict[str, str] = {}
    return _NUMBERED.sub(
        lambda m: names.setdefault(m.group(0), f"v{len(names)}"),
        _METADATA.sub("", "\n".join(out)))


def _stacked_step_text():
    cfg = reduced_for_smoke(get_arch("smollm-135m"))
    n = 4
    opt_init, opt_update = sgd_momentum(0.05)
    state = init_dsgd_state(jax.random.PRNGKey(0), cfg, n, opt_init)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, batch_size=1)
    per = [synthetic_lm_batch(dc, 0, node=i) for i in range(n)]
    batch = {k: jnp.stack([b[k] for b in per]) for k in per[0]}
    ones = jnp.ones((n,), jnp.float32)
    step = make_elastic_train_step(cfg, opt_update)
    return step.lower(state, batch, jnp.eye(n), ones, jnp.ones((n, n)),
                      ones).compile().as_text()


def _assert_metadata_only(scoped: str, plain: str) -> None:
    names = _OP_NAME.findall(scoped)
    for s in SCOPES:
        assert any(f"repro.{s}" in nm for nm in names), s
    assert any("transpose(" in nm and "repro." in nm for nm in names)
    assert not any("repro." in nm for nm in _OP_NAME.findall(plain))
    assert _without_metadata(scoped) == _without_metadata(plain)


@pytest.fixture
def no_compile_cache():
    """JAX's compilation cache keys a program without its metadata, so a
    cached scoped build would answer for the plain one: keep it off."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_scopes_change_only_metadata_in_the_stacked_step(monkeypatch,
                                                         no_compile_cache):
    scoped = _stacked_step_text()
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    _assert_metadata_only(scoped, _stacked_step_text())


SHARDED_SCRIPT = r"""
import contextlib, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
jax.config.update("jax_enable_compilation_cache", False)
from repro import obs
from repro.configs import get_arch, reduced_for_smoke
from repro.core import make_baseline
from repro.data import DataConfig, synthetic_lm_batch
from repro.dsgd import (init_dsgd_state, make_elastic_sharded_train_step,
                        schedule_from_topology, schedule_weight_arrays)
from repro.launch.mesh import make_mesh
from repro.optim import sgd_momentum

n = 4
cfg = reduced_for_smoke(get_arch("smollm-135m"))
mesh = make_mesh((n,), ("data",))
sched = schedule_from_topology(make_baseline("ring", n))
w_self, w_recv = (jnp.asarray(a) for a in schedule_weight_arrays(sched))
opt_init, opt_update = sgd_momentum(0.05)
state = init_dsgd_state(jax.random.PRNGKey(0), cfg, n, opt_init)
dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, batch_size=1)
per = [synthetic_lm_batch(dc, 0, node=i) for i in range(n)]
batch = {k: jnp.stack([b[k] for b in per]) for k in per[0]}
ones = jnp.ones((n,), jnp.float32)

def text():
    step = jax.jit(make_elastic_sharded_train_step(cfg, sched, opt_update,
                                                   mesh))
    with jax.set_mesh(mesh):
        return step.lower(state, batch, ones, ones, w_self,
                          w_recv).compile().as_text()

scoped = text()
obs.scope = lambda name: contextlib.nullcontext()
plain = text()
with open(sys.argv[1], "w") as f:
    f.write(scoped)
with open(sys.argv[2], "w") as f:
    f.write(plain)
print("SHARDED_TEXT_OK")
"""


def test_scopes_change_only_metadata_in_the_sharded_step(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    a, b = tmp_path / "scoped.txt", tmp_path / "plain.txt"
    res = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT, str(a),
                          str(b)], env=env, capture_output=True, text=True,
                         timeout=900, cwd=REPO)
    assert "SHARDED_TEXT_OK" in res.stdout, res.stdout + "\n" + res.stderr
    _assert_metadata_only(a.read_text(), b.read_text())
    assert "collective-permute" in a.read_text()
