#!/usr/bin/env python3
"""Smoke run of the main serving path on one TPU chip.

  python3 chip_smoke.py

One process, on the chip JAX finds, in four phases:

  device  require a TPU (there is no CPU fallback);
  kernel  the Pallas distance kernel, compiled, against its jnp reference;
  pool    a full-width ``VectorPool`` on the kernel serving mixed prefill and
          decode probes: recall@10 against exact kNN, and top-10 agreement
          with the same requests on the jnp path;
  server  ``RealServer`` with the published internvl2-1b (random weights)
          and that pool: 4 requests, twice, the same tokens both times.

Any failure raises and exits non-zero. Only when every phase passed does the
last line of stdout name the device as one JSON object. Times printed are
host-clock times of this one run, compilation included where said: a smoke
check, not a benchmark.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import continuous_batching as cb  # noqa: E402
from repro.core.scheduler import VectorRequest  # noqa: E402
from repro.core.trinity_pool import VectorPool  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import FULL_POOL, RealServer  # noqa: E402
from repro.vector.dataset import make_dataset  # noqa: E402
from repro.vector.graph import make_cagra_graph  # noqa: E402
from repro.vector.ref import exact_knn, recall_at_k  # noqa: E402

SEED = 0


def log(msg: str):
    print(msg, flush=True)


def check(cond: bool, msg: str):
    if not cond:
        raise AssertionError(msg)


def phase_device():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}")
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())}")
    return dev


def phase_kernel(n: int = 100_000, dim: int = 128, slots: int = 64,
                 tasks: int = 2048):
    """The kernel (compiled on a TPU backend) against its jnp reference on
    the same device."""
    k = jax.random.split(jax.random.PRNGKey(SEED), 4)
    db = jax.random.normal(k[0], (n, dim), jnp.float32)
    queries = jax.random.normal(k[1], (slots, dim), jnp.float32)
    ids = jax.random.randint(k[2], (tasks,), 0, n).at[::7].set(-1)
    slot = jax.random.randint(k[3], (tasks,), 0, slots)
    got = ops.distance_tasks(ops.corpus_layout(db), queries, ids, slot)
    want = ref.distance_tasks_ref(db, queries, ids, slot)
    err = float(jnp.max(jnp.abs(got - want) / jnp.maximum(jnp.abs(want), 1)))
    log(f"[kernel] distance_tasks N={n} d={dim} R={slots} T={tasks} "
        f"interpret={ops._interpret()}: max rel err vs ref {err:.3e}")
    check(err <= 1e-4, f"kernel disagrees with its reference ({err})")


def _requests(queries, seed: int):
    """A mixed retrieval stream: ~30% prefill RAG (tight deadline), the rest
    decode-time probes."""
    rng = np.random.default_rng(seed)
    t, out = 0.0, []
    for i, q in enumerate(queries):
        t += float(rng.exponential(1e-4))
        kind = "prefill" if rng.random() < 0.3 else "decode"
        out.append(VectorRequest(i, kind, q, t,
                                 t + (0.005 if kind == "prefill" else 0.05)))
    return out


def _serve(pool, queries):
    """Submit the stream and run the pool until every request completed.
    Returns {rid: top-k ids}."""
    reqs = _requests(queries, SEED)
    for r in reqs:
        pool.submit(r)
    t = reqs[-1].t_arrival
    while len(pool.metrics.completed) < len(reqs):
        t += 0.5
        check(t < 60.0, "pool did not drain")
        pool.run_until(t)
    return {r.rid: np.asarray(r.result_ids) for r in pool.metrics.completed}


def phase_pool(cfg, num_queries: int = 384):
    db, queries = make_dataset(cfg.num_vectors, cfg.dim,
                               num_queries=num_queries, seed=SEED)
    t0 = time.time()
    graph = make_cagra_graph(db, cfg.graph_degree, seed=SEED)
    log(f"[pool] N={cfg.num_vectors} d={cfg.dim} R={cfg.max_requests} "
        f"T={cfg.task_batch} top_m={cfg.top_m}: graph built in "
        f"{time.time() - t0:.1f} s (host)")

    pool = VectorPool(cfg, db, graph, policy="trinity", seed=SEED)
    eng = pool.replicas[0].engine
    check(eng.use_pallas is True, "the engine did not choose the kernel")
    hlo = cb.extend_multi.lower(
        eng.state, eng.db, eng.graph, num_steps=eng.extend_chunk,
        p=cfg.parents_per_step, task_batch=cfg.task_batch,
        use_pallas=eng.use_pallas, metric=cfg.metric,
        distance_mode=eng.distance_mode).as_text()
    custom = "tpu_custom_call" in hlo
    log(f"[pool] engine use_pallas={eng.use_pallas}; extend program holds "
        f"tpu_custom_call: {custom}")
    check(custom, "the extend program holds no compiled kernel")

    t0 = time.time()
    got = _serve(pool, queries)
    log(f"[pool] kernel path: {len(got)} probes in {time.time() - t0:.1f} s "
        "wall (compilation included)")
    found = np.stack([got[i][:10] for i in range(num_queries)])
    recall = recall_at_k(found, exact_knn(db, queries, 10)[0])
    log(f"[pool] recall@10 vs exact kNN: {recall:.4f}")

    oracle = _serve(VectorPool(cfg, db, graph, policy="trinity",
                               use_pallas=False, seed=SEED), queries)
    agree = np.array([len(set(got[i][:10]) & set(oracle[i][:10])) / 10
                      for i in range(num_queries)])
    log(f"[pool] top-10 agreement kernel vs jnp path: mean {agree.mean():.4f} "
        f"min {agree.min():.2f}")
    check(agree.mean() >= 0.99, f"kernel and jnp paths disagree "
          f"({agree.mean():.4f} < 0.99)")
    return pool


def phase_server(cfg, pool, requests: int = 4, text_len: int = 128,
                 max_new: int = 16):
    """Each prompt is one image (the frontend's patch positions come first)
    followed by ``text_len`` text tokens."""
    prompt_len = cfg.frontend_tokens + text_len
    t0 = time.time()
    server = RealServer(cfg, pool.cfg, pool=pool)
    jax.block_until_ready(server.params)
    log(f"[server] {cfg.name}: d_model={cfg.d_model} layers={cfg.num_layers} "
        f"vocab={cfg.vocab_size}; weights initialised in "
        f"{time.time() - t0:.1f} s; {requests} prompts of {prompt_len} "
        f"tokens ({cfg.frontend_tokens} image + {text_len} text)")
    prompts = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, size=(requests, prompt_len)).astype(np.int32)
    runs = []
    for i in range(2):
        t0 = time.time()
        toks, stats = server.generate(prompts, max_new=max_new)
        log(f"[server] pass {i + 1}: {time.time() - t0:.2f} s wall"
            f"{' (compilation included)' if i == 0 else ''}, "
            f"ttft {stats['ttft_s']:.4f} s, decode {stats['decode_s']:.4f} s "
            f"(host clock), rag probes {stats['rag_probes']}, "
            f"logits finite {stats['logits_finite']}")
        check(stats["logits_finite"], "non-finite logits")
        check(toks.shape == (requests, max_new), f"tokens {toks.shape}")
        check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
              "token outside the vocabulary")
        runs.append(toks)
    log(f"[server] tokens of request 0: {runs[0][0].tolist()}")
    check(np.array_equal(runs[0], runs[1]), "the two passes differ")
    log("[server] both passes gave the same tokens")


def main():
    enable_compile_cache()
    dev = phase_device()
    t0 = time.time()
    phase_kernel()
    pool = phase_pool(FULL_POOL)
    phase_server(get_config("internvl2-1b"), pool)
    stats = dev.memory_stats() or {}
    log(f"[done] {time.time() - t0:.1f} s of phases; peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
