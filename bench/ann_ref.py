"""Plain reference of the pool's search, in NumPy, with nothing taken from
the program: the same graph search on the same data gives the same ids.

The search (CAGRA-style, as the configuration states it): each query's
candidate list of ``top_m`` starts from ``entry_points`` random rows
drawn from a per-request key (JAX's threefry: ``fold_in(PRNGKey(seed),
rid)``, then ``randint``), with their exact distances. One extend picks
the ``p`` best unexpanded candidates (ties to the lower position), reads
their graph neighbours, drops ids already in the query's visited table
(open addressing, ``visited_slots`` slots, 4 probes of a Knuth
multiplicative hash; an id that finds no empty slot stays unrecorded),
computes the survivors' distances and keeps the ``top_m`` best of list
and candidates (an id already listed, or repeated, is dropped; ties to the
lower position, the list first). A query is done when no candidate is
left to expand, and answers its first ``top_k`` ids and distances.

Queries are stepped together, a whole batch per NumPy operation.
"""
from __future__ import annotations

import functools

import jax
import ml_dtypes
import numpy as np

INF = np.float32(1e30)
HASH_MULT = np.uint32(2654435761)


@functools.partial(jax.jit, static_argnames=("num",))
def _entries(key, rids, hi, num: int):
    return jax.vmap(lambda r: jax.random.randint(
        jax.random.fold_in(key, r), (num,), 0, hi))(rids)


def entry_points(seed: int, rids: np.ndarray, rows: int, num: int):
    """(B, num) int32 entry rows of the requests ``rids``."""
    rids = np.asarray(rids, np.int64) & 0x7FFFFFFF
    return np.asarray(_entries(jax.random.PRNGKey(seed),
                               np.asarray(rids, np.uint32), np.int32(rows),
                               num=num), np.int32)


def distances(db, q, ids, precision: str = "float32"):
    """Squared L2 of rows ``ids`` (B, C) to their query q (B, d); INF where
    the id is -1. ``bfloat16`` rounds rows and queries to bfloat16 first
    (the control)."""
    x = db[np.maximum(ids, 0)]
    if precision == "bfloat16":
        x = x.astype(ml_dtypes.bfloat16).astype(np.float32)
        q = q.astype(ml_dtypes.bfloat16).astype(np.float32)
    elif precision != "float32":
        raise ValueError(precision)
    diff = x - q[:, None, :]
    d = np.sum(diff * diff, axis=-1, dtype=np.float32)
    return np.where(ids >= 0, d, INF).astype(np.float32)


def hash_probe(visited, ids, num_probes: int = 4):
    """Look up and insert ``ids`` (B, C) into the tables ``visited`` (B, V).
    Returns (new tables, seen (B, C)). Seen: already in the table, or equal
    to an earlier id of the same row. A new id goes to the first empty slot
    of its probe window; where two want one slot, the earlier id gets it."""
    B, V = visited.shape
    C = ids.shape[1]
    valid = ids >= 0
    probe = np.arange(num_probes, dtype=np.uint32)
    slots = ((ids.astype(np.uint32)[:, :, None] * HASH_MULT + probe)
             % np.uint32(V)).astype(np.int64)
    cur = np.take_along_axis(visited, slots.reshape(B, -1),
                             axis=1).reshape(B, C, num_probes)
    hit = (cur == ids[:, :, None]).any(-1)
    earlier = np.tril(ids[:, None, :] == ids[:, :, None], k=-1).any(-1)
    seen = (hit | earlier) & valid
    empty = cur == -1
    want = valid & ~seen & empty.any(-1)
    slot_of = np.take_along_axis(slots, empty.argmax(-1)[..., None],
                                 axis=-1)[..., 0]
    winner = np.full((B, V), C, np.int64)
    bi, ci = np.nonzero(want)
    np.minimum.at(winner, (bi, slot_of[bi, ci]), ci)
    ins = want & (np.take_along_axis(winner, slot_of, 1) == np.arange(C))
    bi, ci = np.nonzero(ins)
    visited = visited.copy()
    visited[bi, slot_of[bi, ci]] = ids[bi, ci]
    return visited, seen


def search(db, graph, queries, entries, *, top_m: int, p: int,
           visited_slots: int, top_k: int, precision: str = "float32",
           max_extends: int = 100_000):
    """Returns (ids (B, top_k), dists (B, top_k), extends (B,))."""
    B = queries.shape[0]
    M, E = top_m, entries.shape[1]
    D = graph.shape[1]
    ids = np.full((B, M), -1, np.int32)
    ids[:, :E] = entries
    dist = np.full((B, M), INF, np.float32)
    dist[:, :E] = distances(db, queries, entries, precision)
    expanded = np.zeros((B, M), bool)
    visited, _ = hash_probe(np.full((B, visited_slots), -1, np.int32),
                            entries)
    extends = np.zeros(B, np.int64)
    live = np.arange(B)
    for _ in range(max_extends):
        if live.size == 0:
            break
        t_ids, t_d, t_exp = ids[live], dist[live], expanded[live]
        rank = np.where(t_exp | (t_ids < 0), INF, t_d)
        pix = np.argsort(rank, axis=1, kind="stable")[:, :p]
        ok = np.take_along_axis(rank, pix, 1) < INF
        parents = np.where(ok, np.take_along_axis(t_ids, pix, 1), -1)
        rows = np.arange(live.size)[:, None]
        t_exp[rows, pix] |= ok
        nbrs = np.where(parents[:, :, None] >= 0,
                        graph[np.maximum(parents, 0)], -1).reshape(-1, p * D)
        vis, seen = hash_probe(visited[live], nbrs)
        nbrs = np.where(seen, -1, nbrs)
        cd = distances(db, queries[live], nbrs, precision)
        dup_top = (nbrs[:, :, None] == t_ids[:, None, :]).any(-1)
        dup_prev = np.tril(nbrs[:, None, :] == nbrs[:, :, None],
                           k=-1).any(-1)
        keep = (nbrs >= 0) & ~dup_top & ~dup_prev
        all_ids = np.concatenate([t_ids, np.where(keep, nbrs, -1)], 1)
        all_d = np.concatenate([t_d, np.where(keep, cd, INF)], 1)
        all_e = np.concatenate([t_exp, np.zeros(nbrs.shape, bool)], 1)
        order = np.argsort(all_d, axis=1, kind="stable")[:, :M]
        ids[live] = np.take_along_axis(all_ids, order, 1)
        dist[live] = np.take_along_axis(all_d, order, 1)
        expanded[live] = np.take_along_axis(all_e, order, 1)
        visited[live] = vis
        worked = ok.any(1)
        extends[live] += worked
        live = live[worked]
    else:
        raise RuntimeError("reference search did not converge")
    return ids[:, :top_k].copy(), dist[:, :top_k].copy(), extends


def exact_distances(db, queries, ids):
    """float64 squared L2 of rows ``ids`` (B, k) to their queries."""
    x = db[np.maximum(ids, 0)].astype(np.float64)
    diff = x - queries.astype(np.float64)[:, None, :]
    return np.sum(diff * diff, axis=-1)
