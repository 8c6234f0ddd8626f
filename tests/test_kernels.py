"""Per-kernel shape/dtype sweeps against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype, k):
    return jax.random.normal(jax.random.fold_in(KEY, k), shape, jnp.float32) \
        .astype(dtype)


# ---------------------------------------------------------------------------
# distance kernel (the paper's fixed-shape global distance stage)
# ---------------------------------------------------------------------------


# the kernel's oracle for each engine distance mode: the Pallas kernel is
# the slot-gather form; the matmul+one-hot form survives as a jnp oracle
ORACLES = {"slot_gather": ref.distance_tasks_ref,
           "matmul_onehot": ref.distance_tasks_onehot_ref}


@pytest.mark.parametrize("mode", ["slot_gather", "matmul_onehot"])
@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("N,d,R,T", [
    (500, 128, 8, 256), (1000, 64, 16, 512), (256, 256, 4, 256),
])
def test_distance_tasks_matches_oracle(mode, metric, N, d, R, T):
    db = _rand((N, d), jnp.float32, 1)
    queries = _rand((R, d), jnp.float32, 2)
    task_ids = jax.random.randint(jax.random.fold_in(KEY, 3), (T,), 0, N)
    task_ids = task_ids.at[::5].set(-1)  # masked dummies
    task_slot = jax.random.randint(jax.random.fold_in(KEY, 4), (T,), 0, R)
    out = ops.distance_tasks(ops.corpus_layout(db), queries, task_ids,
                             task_slot, metric=metric)
    want = ORACLES[mode](db, queries, task_ids, task_slot, metric=metric)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_slot_gather_matches_matmul_onehot_oracle(metric):
    """Acceptance: the O(T·d) slot-gather kernel agrees with the O(T·R·d)
    matmul+one-hot oracle to 1e-4 (d=96 also exercises lane padding)."""
    N, d, R, T = 800, 96, 12, 512
    db = _rand((N, d), jnp.float32, 40)
    queries = _rand((R, d), jnp.float32, 41)
    task_ids = jax.random.randint(jax.random.fold_in(KEY, 42), (T,), 0, N)
    task_ids = task_ids.at[::7].set(-1)
    task_slot = jax.random.randint(jax.random.fold_in(KEY, 43), (T,), 0, R)
    gather = ops.distance_tasks(ops.corpus_layout(db), queries, task_ids,
                                task_slot, metric=metric)
    onehot_oracle = ref.distance_tasks_onehot_ref(db, queries, task_ids,
                                                  task_slot, metric=metric)
    np.testing.assert_allclose(np.asarray(gather), np.asarray(onehot_oracle),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", ["slot_gather", "matmul_onehot"])
def test_distance_tasks_dummy_padding_invariant(mode):
    """Appending masked dummies never changes real task results (paper:
    'round up with masked dummies to preserve a stable operator shape') —
    for the kernel and for the one-hot oracle the jnp path runs."""
    db = _rand((300, 64), jnp.float32, 5)
    queries = _rand((8, 64), jnp.float32, 6)
    if mode == "slot_gather":
        corpus = ops.corpus_layout(db)
        fn = lambda i, s: ops.distance_tasks(corpus, queries, i, s)
    else:
        fn = lambda i, s: ref.distance_tasks_onehot_ref(db, queries, i, s)
    ids = jax.random.randint(jax.random.fold_in(KEY, 7), (256,), 0, 300)
    slot = jax.random.randint(jax.random.fold_in(KEY, 8), (256,), 0, 8)
    base = fn(ids, slot)
    padded_ids = jnp.concatenate([ids, jnp.full((256,), -1, jnp.int32)])
    padded_slot = jnp.concatenate([slot, jnp.zeros((256,), jnp.int32)])
    padded = fn(padded_ids, padded_slot)
    np.testing.assert_allclose(np.asarray(base), np.asarray(padded[:256]),
                               rtol=1e-6)


def test_distance_tasks_rejects_unplaced_corpus():
    """The kernel never re-lays-out the corpus itself: a raw (N, d) corpus
    is refused instead of being copied on every call."""
    db = _rand((300, 128), jnp.float32, 9)
    ids = jnp.zeros((256,), jnp.int32)
    with pytest.raises(ValueError, match="corpus_layout"):
        ops.distance_tasks(db, db[:8], ids, ids)


def test_corpus_layout_pads_lanes_with_zeros():
    db = _rand((10, 96), jnp.float32, 11)
    corpus = ops.corpus_layout(db)
    assert corpus.shape == (10, 1, 128) and corpus.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(corpus[:, 0, :96]),
                                  np.asarray(db))
    assert not np.asarray(corpus[:, 0, 96:]).any()


# ---------------------------------------------------------------------------
# flash attention (prefill) / decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Sk,H,Hkv,hd,causal", [
    (2, 128, 128, 4, 2, 64, True),
    (1, 256, 256, 8, 8, 32, True),
    (2, 64, 64, 4, 1, 128, False),
])
def test_flash_attention_matches_oracle(dtype, B, Sq, Sk, H, Hkv, hd, causal):
    q = _rand((B, Sq, H, hd), dtype, 10)
    k = _rand((B, Sk, Hkv, hd), dtype, 11)
    v = _rand((B, Sk, Hkv, hd), dtype, 12)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    want = ref.mha_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("B,S,H,Hkv,hd,cur_len", [
    (2, 256, 4, 2, 64, 100), (1, 512, 8, 1, 128, 511), (3, 128, 4, 4, 32, 0),
])
def test_decode_attention_matches_oracle(B, S, H, Hkv, hd, cur_len):
    q = _rand((B, H, hd), jnp.float32, 20)
    k = _rand((B, S, Hkv, hd), jnp.float32, 21)
    v = _rand((B, S, Hkv, hd), jnp.float32, 22)
    out = ops.decode_attention(q, k, v, cur_len, block_s=64)
    want = ref.decode_attn_ref(q, k, v, cur_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_ignores_future_positions():
    """Garbage beyond cur_len must not affect the result."""
    B, S, H, Hkv, hd = 1, 128, 4, 4, 32
    q = _rand((B, H, hd), jnp.float32, 30)
    k = _rand((B, S, Hkv, hd), jnp.float32, 31)
    v = _rand((B, S, Hkv, hd), jnp.float32, 32)
    cur = 63
    out1 = ops.decode_attention(q, k, v, cur, block_s=64)
    k2 = k.at[:, cur + 1:].set(1e6)
    v2 = v.at[:, cur + 1:].set(-1e6)
    out2 = ops.decode_attention(q, k2, v2, cur, block_s=64)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)
