"""Reduced-scale dry-run machinery tests (8 host devices via subprocess) +
the HLO cost analyzer's trip-count property."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyze

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_cost_analysis_scales_loop_bodies():
    """rolled scan flops == unrolled flops (XLA's own cost_analysis fails
    this — the reason launch/hlo_cost.py exists)."""

    def body(x, _):
        return x @ x, None

    def rolled(x):
        return jax.lax.scan(body, x, None, length=10)[0]

    def unrolled(x):
        for _ in range(10):
            x = x @ x
        return x

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    a1 = analyze(jax.jit(rolled).lower(x).compile().as_text())
    a2 = analyze(jax.jit(unrolled).lower(x).compile().as_text())
    assert a1["flops"] == a2["flops"] == 10 * 2 * 256**3


def test_collectives_counted():
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import shard_map

    mesh = jax.make_mesh((1,), ("x",))
    fn = jax.jit(shard_map(lambda v: jax.lax.psum(v, "x"), mesh=mesh,
                           in_specs=P("x"), out_specs=P()))
    c = fn.lower(jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile()
    coll = analyze(c.as_text())["collective_bytes"]
    assert coll.get("all-reduce", 0) == 8 * 128 * 4


def test_sharding_rules_divisibility():
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import spec_for_leaf
    from repro.launch.mesh import abstract_mesh

    mesh = abstract_mesh((2, 4), ("data", "model"))
    # divisible dims shard; non-divisible replicate
    assert spec_for_leaf("blocks/l0/attn/wq", (64, 128), mesh) == \
        P("data", "model")
    assert spec_for_leaf("blocks/l0/attn/wq", (63, 127), mesh) == P(None, None)
    # output projections flip: contracting dim on model
    assert spec_for_leaf("blocks/l0/attn/wo", (128, 64), mesh) == \
        P("model", "data")
    # expert stacks: E on model
    assert spec_for_leaf("blocks/l0/mlp/w_gate", (8, 64, 32), mesh) == \
        P("model", "data", None)
    # norms replicate
    assert spec_for_leaf("blocks/l0/ln1", (64,), mesh) == P(None)


@pytest.mark.slow
def test_seqshard_decode_matches_baseline_subprocess():
    """The §Perf shard_map flash-combine decode must be numerically
    identical to the GSPMD baseline (8 host devices, GQA + MLA)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    script = os.path.join(os.path.dirname(__file__), "seqshard_check_script.py")
    res = subprocess.run([sys.executable, script], env=env,
                         capture_output=True, text=True, timeout=540)
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.count("OK") == 2


@pytest.mark.slow
def test_small_scale_dryrun_subprocess(tmp_path):
    """Full lower+compile of a smoke arch on an 8-device host mesh —
    validates the dry-run pipeline end to end without the 512-device cost."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, sys
        import jax
        from repro.configs import get_smoke_config, TRAIN_4K
        import dataclasses
        from repro.distributed import sharding as shard
        from repro.launch import hlo_cost
        from repro.launch.dryrun import build_step
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke_config("deepseek-moe-16b")
        shape = dataclasses.replace(TRAIN_4K, seq_len=64, global_batch=8)
        mesh = make_host_mesh(model_axis=4)  # (2, 4), Auto axes
        fn, args, in_sh = build_step(cfg, shape, mesh)
        with mesh, shard.activation_sharding(mesh):
            compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
        out = hlo_cost.analyze(compiled.as_text())
        mem = compiled.memory_analysis()
        out["temp_bytes"] = mem.temp_size_in_bytes
        print("RESULT " + json.dumps(
            {k: (v if not isinstance(v, dict) else v) for k, v in out.items()}))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=540)
    assert res.returncode == 0, res.stderr[-2000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][0]
    data = json.loads(line[len("RESULT "):])
    assert data["flops"] > 0
    assert data["collective_bytes"]["total"] > 0  # TP/EP collectives present
    assert data["temp_bytes"] > 0
