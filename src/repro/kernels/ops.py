"""Jit'd public wrappers for the Pallas kernels.

On CPU every kernel runs in ``interpret=True`` mode — the kernel body
executes as pure JAX ops, validating semantics but not Mosaic's tiling
rules (tests/test_tpu_compile.py compiles for a described TPU for that).
On a TPU backend the same call sites compile to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import distance as _dist
from repro.kernels import flash_attention as _fa

_INF = jnp.float32(1e30)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("k",))
def merge_partial_topk(ids, dists, *, k: int):
    """Scatter–gather merge: combine per-shard partial top-k lists into the
    global top-k in ONE jitted fixed-shape dispatch.

    ids (..., S, K) int32 — global row ids, −1 = padding (a shard
    returning fewer than K valid rows pads with −1); dists (..., S, K)
    f32; leading batch dims merge independently. Returns (ids (..., k)
    int32, dists (..., k) f32) ascending by distance, −1/+INF padded when
    fewer than ``k`` valid entries exist in total.

    Shards partition the corpus, so a global id appears in at most one
    shard's list — no cross-shard dedup pass is needed; the merge is one
    ``top_k`` over the flattened S·K pool. Under exhaustive (exact)
    per-shard search this merge is the monolithic exact top-k: every
    global top-k member lives in exactly one shard and must appear in that
    shard's local top-k (pinned by the hypothesis property test in
    tests/test_properties.py). Ties break to the lower flat index (shard
    order), matching jax.lax.top_k semantics.
    """
    pool = ids.shape[-2] * ids.shape[-1]
    assert k <= pool, (k, ids.shape)
    flat_ids = ids.reshape(ids.shape[:-2] + (pool,))
    flat_d = jnp.where(flat_ids >= 0,
                       dists.reshape(flat_ids.shape).astype(jnp.float32),
                       _INF)
    neg, sel = jax.lax.top_k(-flat_d, k)
    out_d = -neg
    out_ids = jnp.where(out_d < _INF,
                        jnp.take_along_axis(flat_ids, sel, axis=-1), -1)
    return out_ids, out_d


@functools.partial(jax.jit, donate_argnums=(0, 1))
def fold_partial_topk(buf_ids, buf_dists, top_ids, top_dists, trans, g_idx,
                      slots, rows, cols):
    """On-device scatter–gather fold (PR 8): a completing per-shard child
    writes its (M,) partial top list straight into its parent's
    preallocated merge-buffer row, with shard-local→global id translation
    folded in as a gather over the partition table — the host never sees
    the S partial lists.

    buf_ids/buf_dists (P, S, M) — per-parent device merge buffers (−1 /
    +INF = empty); top_ids/top_dists (G, R, M) — the grouped engine state
    the children finished in; trans (S, T) int32 — per-shard local row →
    global id (−1 = tombstoned, matching host ``to_global``); g_idx/slots
    (B,) — each child's (lane, slot); rows/cols (B,) — its parent's buffer
    row and its shard column. Batches are power-of-two padded by
    replicating entry 0 (duplicate writes scatter identical values).
    Returns the updated buffers."""
    cid = top_ids[g_idx, slots]  # (B, M) shard-local ids
    cd = top_dists[g_idx, slots]
    safe = jnp.clip(cid, 0, trans.shape[1] - 1)
    gid = jnp.where(cid >= 0, trans[cols[:, None], safe], -1)
    return buf_ids.at[rows, cols].set(gid), buf_dists.at[rows, cols].set(cd)


@functools.partial(jax.jit, static_argnames=("k",), donate_argnums=(0, 1))
def finalize_partial_topk(buf_ids, buf_dists, rows_f, *, k: int):
    """Finish the parents whose merge-buffer rows are complete: ONE
    ``top_k`` per row over the (S, M) partial pool (the device half of
    ``merge_partial_topk`` — identical merge math, so the result matches
    the host path bit-for-bit on tie-free data), then clear the rows for
    reuse. The host syncs only the merged (F, k) ids+dists. ``rows_f`` is
    power-of-two padded by replicating entry 0 (re-merging/re-clearing a
    row is idempotent). Returns (buf_ids, buf_dists, merged_ids,
    merged_dists)."""
    m_ids, m_d = merge_partial_topk(buf_ids[rows_f], buf_dists[rows_f], k=k)
    return (buf_ids.at[rows_f].set(-1), buf_dists.at[rows_f].set(_INF),
            m_ids, m_d)


corpus_layout = _dist.corpus_layout
TASK_BLOCK = _dist.TASK_BLOCK


def distance_tasks(corpus, queries, task_ids, task_slot, metric: str = "l2",
                   task_block: int = TASK_BLOCK):
    """``corpus`` is in the kernel's layout (``corpus_layout``)."""
    return _dist.distance_tasks(corpus, queries, task_ids, task_slot,
                                metric=metric, task_block=task_block,
                                interpret=_interpret())


def flash_attention(q, k, v, causal: bool = True, block_q: int = 256,
                    block_k: int = 256):
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=_interpret())


def decode_attention(q, k, v, cur_len, block_s: int = 512):
    return _dec.decode_attention(q, k, v, cur_len, block_s=block_s,
                                 interpret=_interpret())
