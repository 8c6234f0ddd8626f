"""The pool's data, made from the seed on the device: a clustered Gaussian
corpus, fresh queries from the same distribution, and a fixed-degree
search graph.

The graph follows the CAGRA-style recipe of the program's own graph
construction (nearest-neighbour lists, the first ``degree - long_edges``
kept, ``long_edges`` random long-range edges per node, every node of
in-degree zero linked from its nearest neighbour). The neighbour lists
come from one jitted call on the device: L2 over the whole corpus in
blocks of rows, products at three bfloat16 passes, and the TPU's
approximate top-k (``approx_min_k``, recall target 0.95; exact on the
CPU). The rest is cheap host work.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

KNN_RECALL = 0.95
KNN_BLOCK_ROWS = 500  # rows of one distance block: a (500, N) f32 array


@functools.partial(jax.jit, static_argnames=("clusters", "dim"))
def _centers(key, *, clusters: int, dim: int):
    return jax.random.normal(key, (clusters, dim), jnp.float32)


@functools.partial(jax.jit, static_argnames=("n",))
def _rows(centers, key, sigma, *, n: int):
    kc, kn = jax.random.split(key)
    c = centers[jax.random.randint(kc, (n,), 0, centers.shape[0])]
    return c + sigma * jax.random.normal(kn, c.shape, jnp.float32)


def _key(seed: int):
    return jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)


class ClusteredData:
    """``clusters`` Gaussian centres N(0, 1) in ``dim`` dimensions; each row
    is a centre plus N(0, sigma) noise."""

    def __init__(self, seed: int, dim: int, clusters: int, sigma: float):
        self.sigma = jnp.float32(sigma)
        self.centers = _centers(_key(seed), clusters=clusters, dim=dim)

    def device_rows(self, n: int, seed: int) -> jax.Array:
        return _rows(self.centers, _key(seed), self.sigma, n=n)

    def rows(self, n: int, seed: int) -> np.ndarray:
        """(n, dim) float32 rows, the same for the same seed."""
        return np.asarray(self.device_rows(n, seed))


@functools.partial(jax.jit, static_argnames=("k", "block"))
def knn_ids(db, *, k: int, block: int):
    """k nearest neighbours of every row (self excluded), L2. db (N, d),
    N % block == 0."""
    n = db.shape[0]
    sq = jnp.sum(db * db, axis=1)

    def one(i):
        rows = jax.lax.dynamic_slice_in_dim(db, i * block, block)
        d = jax.lax.dynamic_slice_in_dim(sq, i * block, block)[:, None]
        d = d - 2.0 * jnp.dot(rows, db.T,
                              precision=jax.lax.Precision.HIGH) + sq[None]
        self_ix = i * block + jnp.arange(block)
        d = d.at[jnp.arange(block), self_ix].set(jnp.inf)
        return jax.lax.approx_min_k(d, k, recall_target=KNN_RECALL)[1]

    return jax.lax.map(one, jnp.arange(n // block)).reshape(n, k)


def search_graph(db: jax.Array, degree: int, long_edges: int, rng,
                 log: Callable[[str], None] = lambda m: None) -> np.ndarray:
    """(N, degree) int32 adjacency over the device array ``db``."""
    n = db.shape[0]
    block = next(b for b in (KNN_BLOCK_ROWS, 400, 250, 200, 100, 50, 10, 1)
                 if n % b == 0)
    short = degree - long_edges
    knn = np.asarray(knn_ids(db, k=short, block=block), np.int32)
    log(f"{short}-NN lists of {n} rows made")
    g = np.empty((n, degree), np.int32)
    g[:, :short] = knn
    g[:, short:] = rng.integers(0, n, size=(n, long_edges))
    indeg = np.bincount(g.reshape(-1), minlength=n)
    for o in np.nonzero(indeg == 0)[0]:
        g[knn[o, 0], short - 1] = o
    return g
