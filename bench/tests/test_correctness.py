"""``correct`` at a size a test run can hold, on the CPU: sound runs pass,
the lower-precision control fails, and each fault a cell can have, planted
in the timed path underneath the harness, makes ``correct`` false."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench import calibrate, harness, loader
from bench.tests.conftest import ROOT, TINY_LIMITS

SEED = 2**33 + 12345


def run(root, cell, trace=False, seconds=0.5):
    return harness.execute(cell, SEED, seconds, trace, require_tpu=False,
                           device_kind="TPU v5 lite",
                           base=os.path.join(root, "bench"), root=root,
                           trace_device=("/host:CPU", "tf_XLA"))


def test_sound_training_run_is_correct(tiny_root):
    res = run(tiny_root, "train.tiny.stacked4")
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "round_p90_ms", "setup_s"}
    assert list(res)[-1] == "checks"


def test_traced_training_run_reports_its_layers(tiny_root):
    res = run(tiny_root, "train.tiny.stacked4", trace=True)
    assert res["correct"], res["checks"]
    assert {"train.step_mfu", "device_idle_share.train"} <= set(res["metrics"])
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]


def test_sound_solve_run_is_correct(tiny_root):
    res = run(tiny_root, "solve.homo-n8.full")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"solve_s", "setup_s"}


def test_lower_precision_control_is_not_correct(tiny_root):
    cell = loader.cell("train.tiny.stacked4", os.path.join(tiny_root, "bench"))
    for row in calibrate.train_readings(cell, SEED, "control", tiny_root):
        assert any(row[k] > TINY_LIMITS[k] for k in TINY_LIMITS), row
    cell = loader.cell("solve.homo-n8.full", os.path.join(tiny_root, "bench"))
    for row in calibrate.solve_readings(cell, "control", tiny_root):
        assert any(row[k] > cell["limits"][k]
                   for k in ("w_dev", "r_asym_dev", "r_asym_excess")), row


def _broken_train_step(kind):
    from repro.dsgd import elastic

    orig = elastic.make_elastic_train_step

    def make(cfg, opt_update, **kw):
        step = orig(cfg, opt_update, **kw)

        def broken(state, batch, W, alive, link_up, mix, *rest):
            if kind == "unchanged":
                return state, step(state, batch, W, alive, link_up, mix, *rest)[1]
            if kind == "half_batch":
                lab = batch["labels"]
                lab = lab.at[..., lab.shape[-1] // 2:].set(-100)
                batch = {**batch, "labels": lab}
            if kind == "no_exchange":
                W = jnp.eye(W.shape[0], dtype=W.dtype)
            return step(state, batch, W, alive, link_up, mix, *rest)

        return broken

    return elastic, make


@pytest.mark.parametrize("kind", ["unchanged", "half_batch", "no_exchange"])
def test_training_fault_makes_the_run_not_correct(tiny_root, monkeypatch, kind):
    mod, make = _broken_train_step(kind)
    monkeypatch.setattr(mod, "make_elastic_train_step", make)
    res = run(tiny_root, "train.tiny.stacked4")
    assert not res["correct"], res["checks"]


def test_altered_answer_makes_the_solve_not_correct(tiny_root, monkeypatch):
    import numpy as np

    import repro.core

    orig = repro.core.solve_topology

    def altered(req, **kw):
        res = orig(req, **kw)
        res.topology.g = np.array(res.topology.g, np.float64)
        res.topology.g[0] *= 0.5
        return res

    monkeypatch.setattr(repro.core, "solve_topology", altered)
    res = run(tiny_root, "solve.homo-n8.full")
    assert not res["correct"], res["checks"]
    assert res["checks"]["r_asym_dev"]["value"] > res["checks"]["r_asym_dev"]["limit"]


SHARDED = r"""
import json, os, sys
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import jax.numpy as jnp
from bench import harness
from repro.dsgd import elastic
if {broken!r}:
    orig = elastic.make_elastic_sharded_train_step
    def make(cfg, sched, opt_update, mesh, **kw):
        step = orig(cfg, sched, opt_update, mesh, **kw)
        def broken(state, batch, alive, mix, w_self, w_recv):
            return step(state, batch, alive, mix, jnp.ones_like(w_self),
                        jnp.zeros_like(w_recv))
        return broken
    elastic.make_elastic_sharded_train_step = make
    import repro.dsgd
    repro.dsgd.make_elastic_sharded_train_step = make
res = harness.execute("train.tiny.sharded4", {seed}, 0.5, False,
                      require_tpu=False, device_kind="TPU v5 lite",
                      base={root!r} + "/bench", root={root!r})
print(json.dumps(res))
"""


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "no_exchange"])
def test_sharded_layout_on_four_cpu_devices(tiny_root, broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SHARDED.format(
        root=tiny_root, broken=broken, seed=SEED)], env=env,
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is (not broken), res["checks"]


def test_worse_topology_makes_the_solve_not_correct(tiny_root):
    """Weights left unpolished: every guarantee holds, and only the answer's
    quality against the request's best known r_asym shows the fault (at
    n = 8 the other planted faults reach the same answers as the program)."""
    with calibrate.solve_fault("unpolished"):
        res = run(tiny_root, "solve.homo-n8.full", seconds=2.0)
    assert not res["correct"], res["checks"]
    excess = res["checks"]["r_asym_excess"]
    assert excess["value"] > excess["limit"], res["checks"]
