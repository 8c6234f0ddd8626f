"""Pure-jnp oracles for every Pallas kernel (tests assert_allclose these)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

DUMMY_DIST = 1e30


def distance_tasks_ref(db, queries, task_ids, task_slot, metric: str = "l2"):
    """Oracle for the Trinity global distance stage (slot-gather form).

    Gathers the owning query row per task and reduces row-wise — O(T·d)
    work, the same dataflow as the ``slot_gather`` Pallas kernel.

    db:        (N, d)  database vectors
    queries:   (R, d)  per-request-slot query vectors
    task_ids:  (T,)    db row per task; -1 marks a masked dummy
    task_slot: (T,)    owning request slot per task
    Returns (T,) float32 distances; dummies get DUMMY_DIST.
    """
    valid = task_ids >= 0
    ids = jnp.maximum(task_ids, 0)
    x = db[ids].astype(jnp.float32)  # (T, d)
    q = queries[task_slot].astype(jnp.float32)  # (T, d)
    if metric == "l2":
        dist = jnp.sum((x - q) ** 2, axis=-1)
    elif metric == "ip":
        dist = -jnp.sum(x * q, axis=-1)
    else:
        raise ValueError(metric)
    return jnp.where(valid, dist, DUMMY_DIST)


def distance_tasks_onehot_ref(db, queries, task_ids, task_slot,
                              metric: str = "l2"):
    """Oracle for the original matmul+one-hot distance stage.

    Computes the full (T, R) task-by-slot Gram matrix then one-hot-selects
    the owning column — O(T·R·d) work; the engine's ``matmul_onehot`` mode
    runs it on the jnp path (the slot-gather kernel must agree to 1e-4).
    """
    valid = task_ids >= 0
    ids = jnp.maximum(task_ids, 0)
    x = db[ids].astype(jnp.float32)  # (T, d)
    q = queries.astype(jnp.float32)  # (R, d)
    xq = x @ q.T  # (T, R)
    R = q.shape[0]
    onehot = task_slot[:, None] == jnp.arange(R, dtype=task_slot.dtype)[None]
    sel_xq = jnp.sum(jnp.where(onehot, xq, 0.0), axis=1)
    if metric == "l2":
        xnorm = jnp.sum(x * x, axis=1)
        qnorm = jnp.sum(q * q, axis=1)
        sel_qn = jnp.sum(jnp.where(onehot, qnorm[None, :], 0.0), axis=1)
        dist = xnorm - 2.0 * sel_xq + sel_qn
    elif metric == "ip":
        dist = -sel_xq
    else:
        raise ValueError(metric)
    return jnp.where(valid, dist, DUMMY_DIST)


def mha_ref(q, k, v, causal: bool = True):
    """q: (B,Sq,H,hd), k/v: (B,Sk,Hkv,hd) -> (B,Sq,H,hd). GQA broadcast."""
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Sq, Hkv, g, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, kf) / jnp.sqrt(hd)
    if causal:
        Sk = k.shape[1]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def decode_attn_ref(q, k, v, cur_len):
    """q: (B,H,hd) single step; k/v: (B,S,Hkv,hd); positions <= cur_len attend.
    Returns (B,H,hd)."""
    B, H, hd = q.shape
    S = k.shape[1]
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg, k.astype(jnp.float32)) / jnp.sqrt(hd)
    valid = jnp.arange(S) <= cur_len
    scores = jnp.where(valid[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, H, hd).astype(q.dtype)
