"""Launch layer: distribution plans, spec assignment, serve/dryrun plumbing."""
import numpy as np

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, INPUT_SHAPES, get_arch, shape_supported
from repro.launch.mesh import MULTIPOD_SHAPE, POD_SHAPE
from repro.launch.sharding import DistPlan, _leaf_spec, params_bytes, plan_for


class FakeMesh:
    """Shape-only stand-in (plan_for/_leaf_spec never touch devices)."""
    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.empty(shape, dtype=object)
        self.shape = dict(zip(names, shape))


SINGLE = FakeMesh(POD_SHAPE, ("data", "model"))
MULTI = FakeMesh(MULTIPOD_SHAPE, ("pod", "data", "model"))


def test_plan_standard_arch_train():
    plan = plan_for(get_arch("smollm-135m"), SINGLE, mode="train")
    assert plan.gossip_axes == ("data",) and plan.n_workers == 16
    plan = plan_for(get_arch("smollm-135m"), MULTI, mode="train")
    assert plan.gossip_axes == ("pod", "data") and plan.n_workers == 32


def test_plan_big_arch_promotes_to_pod_worker():
    plan = plan_for(get_arch("mixtral-8x22b"), SINGLE, mode="train")
    assert plan.gossip_axes == () and plan.tensor_axes == ("data", "model")
    plan = plan_for(get_arch("mixtral-8x22b"), MULTI, mode="train")
    assert plan.gossip_axes == ("pod",) and plan.n_workers == 2


def test_plan_inference_tp_only_auto():
    # 9B fits a 16-chip slice → TP-only; 141B does not → 2-D FSDP
    assert plan_for(get_arch("gemma2-9b"), SINGLE, mode="prefill").tensor_axes == ("model",)
    assert plan_for(get_arch("mixtral-8x22b"), SINGLE,
                    mode="prefill").tensor_axes == ("data", "model")


def test_leaf_spec_megatron_pattern():
    sizes = {"data": 16, "model": 16}
    plan = DistPlan((), ("model",), ("data",), 1)
    # granite regression: d_model(1024) > d_ff(512) must still shard d_ff
    assert _leaf_spec("['layers']['moe']['w_gate']", (24, 32, 1024, 512),
                      plan, sizes) == P(None, None, None, "model")
    assert _leaf_spec("['layers']['moe']['w_down']", (24, 32, 512, 1024),
                      plan, sizes) == P(None, None, "model")
    # attention: heads out (column), wo in (row)
    assert _leaf_spec("['layers']['attn']['wq']", (24, 1024, 2048),
                      plan, sizes) == P(None, None, "model")
    assert _leaf_spec("['layers']['attn']['wo']", (24, 2048, 1024),
                      plan, sizes) == P(None, "model")
    # layer-stacked dim 0 is never sharded
    spec = _leaf_spec("['layers']['mlp']['w_up']", (30, 576, 1536), plan, sizes)
    assert spec[0] is None


def test_leaf_spec_respects_divisibility():
    sizes = {"data": 16, "model": 16}
    plan = DistPlan((), ("model",), ("data",), 1)
    # 9 heads × 64 = 576: divisible; a 7-dim vector is not
    assert _leaf_spec("['final_norm']", (7,), plan, sizes) == P()


def test_params_bytes_orders_of_magnitude():
    assert 0.2e9 < params_bytes(get_arch("smollm-135m")) < 0.8e9   # 135M f32… bf16
    assert 250e9 < params_bytes(get_arch("mixtral-8x22b")) < 350e9


def test_supported_matrix_counts():
    runnable = sum(shape_supported(a, s) for a in ARCHS for s in INPUT_SHAPES)
    assert runnable == 34  # 40 − 6 long_500k policy skips
    assert all(shape_supported(a, "train_4k") for a in ARCHS)


def test_topology_cache_roundtrip(tmp_path, monkeypatch):
    """The BA topology is memoized in-process and never touches the disk: a
    fresh solve from the same seed reproduces it exactly."""
    import repro.launch.steps as steps
    monkeypatch.chdir(tmp_path)
    steps._MEM_CACHE.clear()
    t1 = steps.topology_for(8, kind="ba", r=12)
    assert steps.topology_for(8, kind="ba", r=12) is t1     # memo hit
    steps._MEM_CACHE.clear()
    t2 = steps.topology_for(8, kind="ba", r=12)             # solved again
    assert t2 is not t1
    assert t1.edges == t2.edges
    np.testing.assert_array_equal(t1.g, t2.g)
    assert not list(tmp_path.iterdir())


def test_trivial_topologies():
    from repro.launch.steps import topology_for
    t1 = topology_for(1)
    assert t1.n == 1 and not t1.edges
    t2 = topology_for(2)
    W = np.eye(2) - np.array([[0.5, -0.5], [-0.5, 0.5]])
    from repro.core.graph import weight_matrix_from_weights
    np.testing.assert_allclose(
        weight_matrix_from_weights(2, t2.edges, t2.g), W)


def test_accum_grad_equivalence():
    """Gradient accumulation must equal the full-batch gradient."""
    from repro.dsgd.trainer import _accum_value_and_grad
    from repro.configs import reduced_for_smoke
    from repro.models import transformer
    from repro.data import DataConfig, synthetic_lm_batch

    cfg = reduced_for_smoke(get_arch("qwen1.5-0.5b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, batch_size=4)
    batch = synthetic_lm_batch(dc, 0)

    loss_fn = lambda p, b: transformer.train_loss(p, cfg, b)
    l1, g1 = _accum_value_and_grad(loss_fn, params, batch, 1)
    l2, g2 = _accum_value_and_grad(loss_fn, params, batch, 2)
    # microbatch loss mean == full mean only when valid counts match per
    # microbatch (true here: every row has the same label layout)
    assert abs(float(l1) - float(l2)) < 1e-4
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-4)


def test_compile_cache_dir(monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and the code sets nothing;
    otherwise the cache goes to the fixed ``<repo>/.jax_cache``."""
    import os

    from repro.launch import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.REPO_CACHE_DIR == os.path.join(repo, ".jax_cache")
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert (compile_cache.enable_compile_cache()
                == compile_cache.REPO_CACHE_DIR)
        assert (jax.config.jax_compilation_cache_dir
                == compile_cache.REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
