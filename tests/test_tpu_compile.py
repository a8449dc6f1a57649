"""Ahead-of-time compiles for a described TPU v5e chip — no chip needed.

The TPU compiler is installed with JAX, so the kernels and the step programs
of the main path compile here at real sizes for a v5e that is described,
not attached. That catches what interpret mode cannot (tiling rules, fast
memory, f64 in a model step) before any chip time is spent. Nothing runs:
these tests say nothing about results or speed.

The topology is described only inside the module fixture below (never at
import): one process at a time may load the TPU library, and this file's
tests all run in the process that holds it.
"""
from __future__ import annotations

import os
import re
from dataclasses import replace
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def v5e():
    """A described v5e:2x2 (four chips)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    import repro.core  # noqa: F401 — x64 on, as in every entry point: the
    # kernels must compile under it (Mosaic aborts on a 64-bit value)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _abstract(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding)
        if isinstance(x, (np.ndarray, jax.Array, jax.ShapeDtypeStruct))
        else x, tree)


def _f64_lines(hlo: str) -> list[str]:
    return [ln for ln in hlo.splitlines() if re.search(r"\bf64\[", ln)]


# ---------------------------------------------------------------------------
# Pallas kernels at real sizes, interpret=False
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [256, 1024])
def test_hop_bfs_compiles_for_v5e(one_chip, n):
    from repro.kernels.hop_bfs import ops

    a = _sds(one_chip, (n, n), jnp.bool_)
    f = jax.jit(partial(ops.hop_step, use_kernel=True, interpret=False))
    hlo = f.lower(a, a).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_gossip_mix_batched_compiles_for_v5e(one_chip):
    """Four stacked workers mixing smollm-135m's largest leaf, the tied
    embedding (49 152 × 576, bf16)."""
    from repro.configs import get_arch
    from repro.kernels.gossip_mix import ops

    cfg = get_arch("smollm-135m")
    x = _sds(one_chip, (4, cfg.vocab_size, cfg.d_model), jnp.bfloat16)
    idx = _sds(one_chip, (4, 3), jnp.int32)
    w = _sds(one_chip, (4, 4), jnp.float32)
    f = jax.jit(partial(ops.gossip_mix_batched, use_kernel=True,
                        interpret=False))
    compiled = f.lower(x, idx, w).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**31


@pytest.mark.parametrize("n,dtype,shape", [
    (16, jnp.bfloat16, (576, 1536)),    # smollm-135m MLP leaf, 16 workers
    (16, jnp.float32, (576, 1536)),
    (256, jnp.bfloat16, (576,)),        # a norm leaf over 256 workers
])
def test_gossip_mix_batched_at_full_degree_compiles_for_v5e(one_chip, n,
                                                            dtype, shape):
    """Every worker a neighbor of every other (deg = n − 1, the elastic
    runtime's default degree cap): the row tile shrinks with the degree and
    the element width so that the double-buffered tiles fit VMEM; at 255
    neighbors even the smallest tile needs a raised VMEM limit."""
    from repro.kernels.gossip_mix import ops

    x = _sds(one_chip, (n,) + shape, dtype)
    idx = _sds(one_chip, (n, n - 1), jnp.int32)
    w = _sds(one_chip, (n, n), jnp.float32)
    f = jax.jit(partial(ops.gossip_mix_batched, use_kernel=True,
                        interpret=False))
    assert "tpu_custom_call" in f.lower(x, idx, w).compile().as_text()


@pytest.mark.parametrize("kernel", ["edge_laplacian", "edge_quadform"])
def test_edge_kernels_refuse_the_tpu_with_a_clear_error(one_chip, kernel):
    """Mosaic refuses both kernels' in-kernel gathers; off the interpreter
    they must raise, naming the fix, not run something else."""
    from repro.kernels.edge_laplacian import ops
    from repro.kernels.mode import TPUUnsupportedError

    n = 256
    m = n * (n - 1) // 2
    idx = _sds(one_chip, (m,), jnp.int32)
    if kernel == "edge_laplacian":
        f = jax.jit(partial(ops.edge_laplacian, n=n, use_kernel=True,
                            interpret=False))
        args = (_sds(one_chip, (m,), jnp.float32), idx, idx)
    else:
        f = jax.jit(partial(ops.edge_quadform, use_kernel=True,
                            interpret=False))
        args = (_sds(one_chip, (n, n), jnp.float32), idx, idx)
    with pytest.raises(TPUUnsupportedError, match="edge_kernel=False"):
        f.lower(*args).compile()


# ---------------------------------------------------------------------------
# step programs
# ---------------------------------------------------------------------------

def test_elastic_step_at_published_widths_has_no_f64(one_chip):
    """smollm-135m at its published widths (2 of its 30 layers), four
    stacked workers × batch 2 × seq 512: the stacked elastic step compiles
    for the v5e with no f64 anywhere, although the solver's x64 switch is
    on in this process."""
    from repro.configs import get_arch
    from repro.dsgd import init_dsgd_state
    from repro.dsgd.elastic import make_elastic_train_step
    from repro.optim import make_optimizer, warmup_cosine

    assert jax.config.jax_enable_x64
    cfg = replace(get_arch("smollm-135m"), num_layers=2)
    n, b, seq = 4, 2, 512
    opt_init, opt_update = make_optimizer("sgd", warmup_cosine(0.05, 1, 5))
    state = _abstract(one_chip, jax.eval_shape(
        lambda: init_dsgd_state(jax.random.PRNGKey(0), cfg, n, opt_init)))
    tok = _sds(one_chip, (n, b, seq), jnp.int32)
    f32 = partial(_sds, one_chip, dtype=jnp.float32)  # as ElasticRuntime feeds
    compiled = make_elastic_train_step(cfg, opt_update).lower(
        state, {"tokens": tok, "labels": tok}, f32((n, n)), f32((n,)),
        f32((n, n)), f32((n,))).compile()
    assert _f64_lines(compiled.as_text()) == []


def test_admm_chunk_driver_compiles_for_v5e(one_chip):
    """The pipeline-default ADMM chunk driver at n=64: the TPU compiler
    accepts its f64 residual bookkeeping by splitting it into f32 pairs."""
    from repro.core import BATopoConfig, engine

    cfg = BATopoConfig().admm
    spec = engine.make_homo_spec(64, 128, cfg)
    st = engine.init_state(spec, jnp.zeros(spec.m, jnp.dtype(cfg.dtype)), 0.5)
    max_iters, chunk = engine._chunk_plan(cfg)
    compiled = engine._solve_device.lower(
        _abstract(one_chip, spec), _abstract(one_chip, st),
        max_iters=max_iters, check_every=chunk, eps=cfg.eps,
        backend=cfg.solver, abort_nonfinite=cfg.abort_nonfinite).compile()
    hlo = compiled.as_text()
    assert "while" in hlo
    assert all("X64Combine" in ln or "parameter(" in ln
               for ln in _f64_lines(hlo) if "= f64[" in ln)


def _flash_kernels(hlo: str) -> set[str]:
    """Names of the flash attention Mosaic calls in compiled HLO text."""
    return {m for ln in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in ln
            for m in re.findall(r"%(flash_attention_(?:fwd|bwd))", ln)}


def _score_blocks(hlo: str) -> list[str]:
    """``attend_chunked``'s float32 score blocks at smollm-135m's training
    shape, (..., S = 2048, Hkv = 3, g = 3, chunk = 1024): stacked
    ``f32[4,1,2048,3,3,1024]``, sharded ``f32[1,2048,3,3,1024]``."""
    return re.findall(r"f32\[[\d,]*2048,3,3,1024\]", hlo)


def _smollm_stacked_step(one_chip, n: int, b: int, seq: int):
    from repro.configs import get_arch
    from repro.dsgd import init_dsgd_state
    from repro.dsgd.elastic import make_elastic_train_step
    from repro.optim import make_optimizer, warmup_cosine

    cfg = replace(get_arch("smollm-135m"), num_layers=2)
    opt_init, opt_update = make_optimizer("sgd", warmup_cosine(0.05, 1, 5))
    state = _abstract(one_chip, jax.eval_shape(
        lambda: init_dsgd_state(jax.random.PRNGKey(0), cfg, n, opt_init)))
    tok = _sds(one_chip, (n, b, seq), jnp.int32)
    f32 = partial(_sds, one_chip, dtype=jnp.float32)
    return make_elastic_train_step(cfg, opt_update).lower(
        state, {"tokens": tok, "labels": tok}, f32((n, n)), f32((n,)),
        f32((n, n)), f32((n,))).compile()


def test_stacked_step_at_the_cells_shape_runs_flash_attention(one_chip):
    """The stacked training cell's step (smollm-135m at published widths, 2
    of its 30 layers, 4 workers x 1 x 2048): attention is the Pallas flash
    kernel, forward (twice: the layer remat) and backward, and the float32
    (workers, 1, S, Hkv, g, 1024) score blocks of ``attend_chunked`` are
    gone; still no f64 although the solver's x64 switch is on."""
    hlo = _smollm_stacked_step(one_chip, 4, 1, 2048).as_text()
    assert _flash_kernels(hlo) == {"flash_attention_fwd", "flash_attention_bwd"}
    assert _score_blocks(hlo) == []
    assert _f64_lines(hlo) == []


def test_sharded_step_at_the_cells_shape_runs_flash_attention(v5e):
    """The sharded training cell's step: one smollm-135m worker (2 layers)
    per chip of the described 2x2, 1 x 2048 tokens each, ppermute gossip
    over the ring."""
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_arch
    from repro.core.graph import Topology
    from repro.dsgd import (DSGDState, init_dsgd_state,
                            make_elastic_sharded_train_step, schedule_from_topology)
    from repro.optim import make_optimizer, warmup_cosine

    n, b, seq = 4, 1, 2048
    cfg = replace(get_arch("smollm-135m"), num_layers=2)
    opt_init, opt_update = make_optimizer("sgd", warmup_cosine(0.05, 1, 5))
    mesh = Mesh(np.asarray(v5e.devices[:n]), ("data",), axis_types=(AxisType.Auto,))
    shard, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    sched = schedule_from_topology(
        Topology(n, [(i, (i + 1) % n) for i in range(n)], np.full(n, 1.0 / 3.0)))
    state = jax.eval_shape(
        lambda: init_dsgd_state(jax.random.PRNGKey(0), cfg, n, opt_init))
    state = DSGDState(_abstract(shard, state.params), _abstract(shard, state.opt),
                      _sds(rep, (), jnp.int32))
    tok = _sds(shard, (n, b, seq), jnp.int32)
    v = partial(_sds, rep, dtype=jnp.float32)
    step = jax.jit(make_elastic_sharded_train_step(cfg, sched, opt_update, mesh))
    with jax.set_mesh(mesh):
        hlo = step.lower(state, {"tokens": tok, "labels": tok}, v((n,)), v((n,)),
                         v((n,)), v((sched.rounds, n))).compile().as_text()
    assert _flash_kernels(hlo) == {"flash_attention_fwd", "flash_attention_bwd"}
    assert _score_blocks(hlo) == []
    assert _f64_lines(hlo) == []
