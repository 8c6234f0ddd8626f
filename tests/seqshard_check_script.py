import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models import model_zoo
from repro.distributed import sharding as shard
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(model_axis=4)  # (2, 4) data x model, Auto axes
for arch in ("phi3-medium-14b", "deepseek-v3-671b"):
    cfg = get_smoke_config(arch)
    params = model_zoo.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 32
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 500, (B, S)), jnp.int32)
    caches = model_zoo.init_decode_caches(cfg, B, S)
    # baseline decode of full prompt
    lg_base = None
    c = caches
    for i in range(S):
        lg_base, c = model_zoo.decode_fn(cfg, params, toks[:, i:i+1], c, jnp.int32(i))
    # seqshard decode under the mesh ctx
    with mesh, shard.activation_sharding(mesh):
        fn = jax.jit(lambda p, t, c, n: model_zoo.decode_fn(cfg, p, t, c, n, seq_axis="model"))
        c2 = caches
        lg_ss = None
        for i in range(S):
            lg_ss, c2 = fn(params, toks[:, i:i+1], c2, jnp.int32(i))
    np.testing.assert_allclose(np.asarray(lg_base, np.float32), np.asarray(lg_ss, np.float32), rtol=2e-3, atol=2e-3)
    print(arch, "seqshard == baseline OK")
