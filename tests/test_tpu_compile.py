"""Compile the main path for a described TPU v5e chip (no chip attached).

Interpret-mode tests run a kernel's semantics but not Mosaic's tiling and
layout rules; these compiles do, at real widths, in a few seconds each. The
topology is described only inside the fixtures: only one process at a time
may load the TPU library, so nothing here touches it at import.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import VectorPoolConfig
from repro.core import continuous_batching as cb
from repro.kernels import distance
from repro.models import model_zoo


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _corpus(n, dim, sharding):
    shape = jax.eval_shape(distance.corpus_layout,
                           jax.ShapeDtypeStruct((n, dim), jnp.float32))
    return _spec(shape, sharding)


@pytest.mark.parametrize("dim", [128, 768])  # SIFT / text-embedding widths
def test_distance_kernel_compiles(one_chip, dim):
    N, R, T = 1_000_000, 64, 2048
    fn = jax.jit(lambda c, q, i, s: distance.distance_tasks(
        c, q, i, s, interpret=False))
    compiled = fn.lower(
        _corpus(N, dim, one_chip),
        jax.ShapeDtypeStruct((R, dim), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((T,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((T,), jnp.int32, sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the corpus is read in place: no relayout copy of it per call
    assert compiled.memory_analysis().temp_size_in_bytes < N * dim


def test_extend_multi_compiles_with_kernel(one_chip, monkeypatch):
    """The fused engine step at the ``VectorPoolConfig`` defaults, on the
    kernel (on this CPU host ``_interpret`` would say True)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = VectorPoolConfig()
    state = _spec(jax.eval_shape(lambda: cb.init_engine_state(cfg)),
                  one_chip)
    graph = jax.ShapeDtypeStruct((cfg.num_vectors, cfg.graph_degree),
                                 jnp.int32, sharding=one_chip)
    lowered = cb.extend_multi.lower(
        state, _corpus(cfg.num_vectors, cfg.dim, one_chip), graph,
        num_steps=cfg.extend_chunk, p=cfg.parents_per_step,
        task_batch=cfg.task_batch, use_pallas=True, metric=cfg.metric,
        distance_mode=cfg.distance_mode)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's instruction keeps its name, and the step's stages their
    # scopes, for a trace to be read by (the padding fuses into the
    # kernel's operands)
    assert re.search(r"%distance_tasks\.\d+ = \S+ custom-call\(", text)
    for scope in ("build_tasks", "distance", "merge_topm", "converge"):
        assert f"/{scope}/" in text, scope


def test_megabatch_extend_compiles_with_kernel(one_chip, monkeypatch):
    """The sharded pool's vmapped step over G=4 stacked lanes."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg, G = VectorPoolConfig(), 4
    state = _spec(jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (G,) + x.shape),
        cb.init_engine_state(cfg))), one_chip)
    corpus = _corpus(cfg.num_vectors, cfg.dim, one_chip)
    dbs = jax.ShapeDtypeStruct((G,) + corpus.shape, corpus.dtype,
                               sharding=one_chip)
    graphs = jax.ShapeDtypeStruct((G, cfg.num_vectors, cfg.graph_degree),
                                  jnp.int32, sharding=one_chip)
    lowered = cb.extend_multi_group.lower(
        state, dbs, graphs,
        jax.ShapeDtypeStruct((G,), jnp.bool_, sharding=one_chip),
        num_steps=cfg.extend_chunk, p=cfg.parents_per_step,
        task_batch=cfg.task_batch, use_pallas=True, metric=cfg.metric,
        distance_mode=cfg.distance_mode)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_internvl2_1b_decode_step_compiles(one_chip):
    """One full-width decode step of the published internvl2-1b."""
    cfg = get_config("internvl2-1b")
    B, max_len = 4, 512
    params = _spec(jax.eval_shape(
        lambda: model_zoo.init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    caches = _spec(jax.eval_shape(
        lambda: model_zoo.init_decode_caches(cfg, B, max_len)), one_chip)
    step = jax.jit(lambda p, t, c, n: model_zoo.decode_fn(cfg, p, t, c, n))
    compiled = step.lower(
        params, jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip),
        caches, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 1e9  # ~1.27 GB of bf16 weights
