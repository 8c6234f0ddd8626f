"""Trinity's fixed-shape global distance stage as a Pallas TPU kernel.

Paper §3.2: all surviving (request, candidate) pairs from one *extend* step
are flattened into a single fixed-shape task array and evaluated by ONE
kernel launch; short batches are padded with masked dummies so the operator
shape never changes (the CUDA-graph analogue on TPU is the fixed jitted
shape → no recompiles).

TPU adaptation (DESIGN.md §3): the GPU warp-gather becomes a *burst DMA
gather* — task db-row ids arrive via scalar prefetch (SMEM), each grid step
issues TASK_BLOCK row copies HBM→VMEM back-to-back on per-row DMA
semaphores, then waits. The owning query row of each task is selected from
the VMEM-resident (R, d) query block by a (R, TB) one-hot matmul on the MXU,
and the distance is a row-wise VPU reduction over the two (TB, d) blocks.

Layout (what Mosaic accepts): a one-row DMA may not split a tiled
dimension, so the corpus lives on the device as ``(N, 1, d_pad)`` — each
row its own (1, 128)-tiled slab — with ``d_pad`` the width rounded up to the
128-lane tile. ``corpus_layout`` builds that array once, where the corpus is
placed on the device; zero lanes leave l2 and ip distances exact (queries
are zero-padded to match per call, an (R, d_pad) copy). Task ids and slots
travel as one (2, T) operand and the output is (1, T), so every blocked
operand keeps XLA's own 2-D tiling.

Arithmetic intensity per task ≈ d MACs / d·4 bytes ⇒ memory-bound, matching
the paper's roofline placement of ANN next to decode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DUMMY_DIST = 1e30
LANES = 128
TASK_BLOCK = 256  # tasks per grid step


def corpus_layout(db):
    """(N, d) corpus → the kernel's (N, 1, d_pad) float32 layout, d_pad the
    next multiple of 128 (zero lanes). Call once per placed corpus: the
    kernel never re-lays-out or copies the corpus itself."""
    db = jnp.asarray(db, jnp.float32)
    pad = -db.shape[1] % LANES
    if pad:
        db = jnp.pad(db, ((0, 0), (0, pad)))
    return db[:, None, :]


def _distance_kernel(task_ids_sref, db_ref, queries_ref, meta_ref, out_ref,
                     xgather, sems, *, task_block: int, metric: str):
    """One grid step = one task block, O(TB·d) VPU work.

    task_ids_sref: (T,) int32 in SMEM (scalar prefetch, DMA addressing)
    db_ref:        (N, 1, d) in ANY (stays in HBM; rows DMA'd on demand)
    queries_ref:   (R, d) VMEM — resident query block
    meta_ref:      (2, task_block) VMEM — row 0 task ids (dummy mask),
                   row 1 owning slot per task
    out_ref:       (1, task_block) VMEM distances
    xgather:       (task_block, 1, d) VMEM scratch
    sems:          (task_block,) DMA semaphores
    """
    base = pl.program_id(0) * task_block

    # ---- burst DMA gather: start all row copies, then wait all ----------
    def start(i, carry):
        row = jnp.maximum(task_ids_sref[base + i], 0)  # dummies fetch row 0
        pltpu.make_async_copy(db_ref.at[pl.ds(row, 1)],
                              xgather.at[pl.ds(i, 1)], sems.at[i]).start()
        return carry

    jax.lax.fori_loop(0, task_block, start, 0)

    def wait(i, carry):
        # a wait needs only the semaphore and the copy's size
        pltpu.make_async_copy(db_ref.at[pl.ds(0, 1)],
                              xgather.at[pl.ds(i, 1)], sems.at[i]).wait()
        return carry

    jax.lax.fori_loop(0, task_block, wait, 0)

    # ---- owning query rows: exact one-hot select on the MXU -------------
    meta = meta_ref[...]
    ids, slots = meta[0:1, :], meta[1:2, :]  # (1, TB) each
    q = queries_ref[...]  # (R, d)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], task_block), 0)
              == slots).astype(jnp.float32)  # (R, TB)
    qsel = jax.lax.dot_general(onehot, q, (((0,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)  # (TB, d)

    # ---- distances: row-wise VPU reduction -------------------------------
    x = xgather[...].reshape(task_block, q.shape[1])  # (TB, d)
    if metric == "l2":
        diff = x - qsel
        dist = jnp.sum(diff * diff, axis=1)
    elif metric == "ip":
        dist = -jnp.sum(x * qsel, axis=1)
    else:
        raise ValueError(metric)

    out_ref[...] = jnp.where(ids >= 0, dist[None, :], DUMMY_DIST)


@functools.partial(jax.jit, static_argnames=("metric", "task_block",
                                             "interpret"))
def distance_tasks(corpus, queries, task_ids, task_slot, *, metric: str = "l2",
                   task_block: int = TASK_BLOCK, interpret: bool = True):
    """Fixed-shape distance stage; oracle is ``ref.distance_tasks_ref``.

    corpus (N, 1, d_pad) from ``corpus_layout`` · queries (R, d), d ≤ d_pad ·
    task_ids/task_slot (T,) int32 (the engine pads with dummies; id −1 =
    dummy). ``task_block`` is capped at T and must divide it.
    Returns (T,) float32 distances (dummies = DUMMY_DIST).
    """
    if (corpus.ndim != 3 or corpus.shape[1] != 1
            or corpus.shape[2] % LANES or corpus.dtype != jnp.float32):
        raise ValueError(
            f"distance_tasks takes the corpus as float32 (N, 1, k*{LANES}) "
            f"(build it once with corpus_layout), got {corpus.dtype}"
            f"{tuple(corpus.shape)}")
    T = task_ids.shape[0]
    task_block = min(task_block, T)
    if T % task_block:
        raise ValueError(f"task count {T} is not a multiple of the task "
                         f"block {task_block}")
    d_pad = corpus.shape[2]
    queries = queries.astype(jnp.float32)
    queries = jnp.pad(queries, ((0, 0), (0, d_pad - queries.shape[1])))
    meta = jnp.stack([task_ids, task_slot]).astype(jnp.int32)  # (2, T)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # task_ids (SMEM, DMA addressing)
        grid=(T // task_block,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # corpus stays in HBM
            pl.BlockSpec(queries.shape, lambda i, *_: (0, 0)),  # resident
            pl.BlockSpec((2, task_block), lambda i, *_: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, task_block), lambda i, *_: (0, i)),
        scratch_shapes=[
            pltpu.VMEM((task_block, 1, d_pad), jnp.float32),
            pltpu.SemaphoreType.DMA((task_block,)),
        ],
    )
    kernel = functools.partial(_distance_kernel, task_block=task_block,
                               metric=metric)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, T), jnp.float32),
        interpret=interpret,
        name="distance_tasks",
    )(task_ids.astype(jnp.int32), corpus, queries, meta)
    return out[0]
