"""Real-compute serving driver: a miniature Trinity deployment on whatever
devices exist — real model prefill/decode (greedy) + real vector search
through the continuous-batching pool, PD-disaggregated at the process level
(prefill engine and decode engine are separate objects exchanging KV
caches, the vector pool serves both through the two-queue scheduler).

``python -m repro.launch.serve --arch internvl2-1b --requests 8`` runs the
small smoke config (CPU-sized); ``--full --prompt-len 384`` serves the
published config at full width with a full-width pool (a chip's job; the
prompt holds one image's 256 patch positions).
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config, list_archs
from repro.configs.base import VectorPoolConfig
from repro.core.scheduler import VectorRequest
from repro.core.trinity_pool import VectorPool
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_zoo
from repro.vector.dataset import make_dataset
from repro.vector.graph import make_cagra_graph


# the full-width pool: ``VectorPoolConfig`` engine widths (R=64 slots,
# task_batch 2048, top_m 32) at SIFT width, over the largest corpus the host
# builds an exact kNN graph for (``vector/graph.py``)
FULL_POOL = VectorPoolConfig(num_vectors=20_000, dim=128)


class RealServer:
    """Prefill pool + decode pool + Trinity vector pool, real compute."""

    def __init__(self, cfg, pool_cfg, *, rag_interval: int = 8, seed: int = 0,
                 pool: Optional[VectorPool] = None):
        """``pool``: serve from an already built pool (its config must be
        ``pool_cfg``); None builds one over a synthetic corpus."""
        self.cfg = cfg
        self.params = model_zoo.init_params(cfg, jax.random.PRNGKey(seed))
        if pool is None:
            db, _ = make_dataset(pool_cfg.num_vectors, pool_cfg.dim,
                                 num_queries=1, seed=seed)
            graph = make_cagra_graph(db, pool_cfg.graph_degree, seed=seed)
            pool = VectorPool(pool_cfg, db, graph, policy="trinity")
        self.pool = pool
        self.rag_interval = rag_interval
        self.pool_cfg = pool_cfg
        self._prefill = jax.jit(
            lambda p, b: model_zoo.prefill_fn(cfg, p, b))
        self._decode = jax.jit(
            lambda p, tok, c, n: model_zoo.decode_fn(cfg, p, tok, c, n))
        # pool sim-time: a pool that already served starts where it stands
        self._clock = max(r.clock for r in self.pool.replicas)
        self._rid = 0

    def _retrieve(self, kind: str, qvec) -> np.ndarray:
        """Submit one retrieval through the scheduler and drain the pool."""
        self._rid += 1
        ddl = self._clock + self.pool_cfg.prefill_deadline_ms / 1e3
        req = VectorRequest(self._rid, kind, qvec, self._clock, ddl)
        self.pool.submit(req)
        # advance pool sim-time until this request completes
        for _ in range(512):
            self._clock += 2e-4
            self.pool.run_until(self._clock)
            if req.t_completed is not None:
                return req.result_ids
        raise RuntimeError("retrieval did not complete")

    def generate(self, prompts: np.ndarray, max_new: int = 16):
        """prompts: (B, S) int32. Greedy decode with periodic RAG probes.
        With a frontend, its embeddings take the first ``frontend_tokens``
        positions, so S must hold them. Returns (tokens (B, max_new), stats)."""
        B, S = prompts.shape
        if not model_zoo.is_encdec(self.cfg) and S < self.cfg.frontend_tokens:
            raise ValueError(f"{self.cfg.name}: a prompt of {S} tokens cannot "
                             f"hold the frontend's {self.cfg.frontend_tokens} "
                             "embedding positions")
        t0 = time.time()
        # prefill-side RAG: one retrieval per request (context injection)
        rng = np.random.default_rng(0)
        for b in range(B):
            self._retrieve("prefill",
                           self.pool.db[rng.integers(len(self.pool.db))])
        batch = {"tokens": jnp.asarray(prompts)}
        if model_zoo.is_encdec(self.cfg):
            batch = {"frames": jnp.ones((B, S, self.cfg.d_model),
                                        jnp.float32) * 0.1,
                     "tokens": jnp.asarray(prompts)}
        elif self.cfg.frontend_tokens > 0:
            batch["frontend"] = jnp.ones(
                (B, self.cfg.frontend_tokens, self.cfg.d_model), jnp.float32)
        logits, _ = self._prefill(self.params, batch)
        logits.block_until_ready()
        ttft = time.time() - t0
        finite = jnp.all(jnp.isfinite(logits))

        # decode pool consumes the transferred caches (fresh max-len caches
        # seeded by re-running prefill into them token-by-token is wasteful;
        # production transfers pages — here we re-prefill into a decode-side
        # cache because the smoke models are tiny)
        max_len = S + max_new
        caches = model_zoo.init_decode_caches(self.cfg, B, max_len)
        tok = jnp.asarray(prompts[:, :1])
        for i in range(S):
            _, caches = self._decode(self.params, jnp.asarray(prompts[:, i:i + 1]),
                                     caches, jnp.int32(i))
        out = []
        tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        stalls = 0
        for step in range(max_new):
            if self.rag_interval and step and step % self.rag_interval == 0:
                # decode-side RAG probe for request 0 (demo)
                self._retrieve("decode", np.asarray(
                    self.pool.db[step % len(self.pool.db)]))
                stalls += 1
            lg, caches = self._decode(self.params, tok, caches,
                                      jnp.int32(S + step))
            finite &= jnp.all(jnp.isfinite(lg))
            tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            out.append(np.asarray(tok)[:, 0])
        toks = np.stack(out, axis=1)
        return toks, {"ttft_s": ttft, "decode_s": time.time() - t0 - ttft,
                      "logits_finite": bool(finite),
                      "rag_probes": len(self.pool.metrics.completed),
                      "rag_p95_ms": self.pool.metrics.p(95) * 1e3,
                      "stalls": stalls}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="internvl2-1b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--full", action="store_true",
                    help="published config and a full-width pool (chip)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.full:
        cfg = get_config(args.arch)
        pool_cfg = FULL_POOL
    else:
        cfg = get_smoke_config(args.arch)
        pool_cfg = VectorPoolConfig(num_vectors=2000, dim=64,
                                    max_requests=16, top_m=16,
                                    task_batch=512, visited_slots=256,
                                    top_k=5)
    server = RealServer(cfg, pool_cfg)
    rng = np.random.default_rng(0)
    prompts = rng.integers(
        0, cfg.vocab_size, size=(args.requests, args.prompt_len)).astype(np.int32)
    toks, stats = server.generate(prompts, max_new=args.max_new)
    print("generated tokens (first request):", toks[0].tolist())
    for k, v in stats.items():
        print(f"  {k}: {v:.4g}" if isinstance(v, float) else f"  {k}: {v}")


if __name__ == "__main__":
    main()
