"""End-to-end behaviour: a real miniature Trinity deployment — real model
compute (prefill + greedy decode) and real vector search through the
continuous-batching pool + two-queue scheduler."""
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import VectorPoolConfig
from repro.launch.serve import RealServer


@pytest.fixture(scope="module")
def server():
    cfg = get_smoke_config("qwen1.5-32b")
    pool_cfg = VectorPoolConfig(num_vectors=1500, dim=64, max_requests=16,
                                top_m=16, task_batch=512, visited_slots=256,
                                top_k=5)
    return RealServer(cfg, pool_cfg, rag_interval=4)


def test_generate_end_to_end(server):
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 500, size=(2, 16)).astype(np.int32)
    toks, stats = server.generate(prompts, max_new=8)
    assert toks.shape == (2, 8)
    assert np.all(toks >= 0) and np.all(toks < 512)
    assert stats["rag_probes"] >= 2  # prefill probes at least
    assert stats["rag_p95_ms"] > 0


def test_generation_is_deterministic(server):
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, 500, size=(1, 12)).astype(np.int32)
    t1, _ = server.generate(prompts, max_new=6)
    t2, _ = server.generate(prompts, max_new=6)
    np.testing.assert_array_equal(t1, t2)


def test_prompt_must_hold_frontend_positions():
    """A frontend's embeddings take the first ``frontend_tokens`` positions;
    a shorter prompt is refused with a clear error."""
    cfg = get_smoke_config("internvl2-1b")
    pool_cfg = VectorPoolConfig(num_vectors=300, dim=64, max_requests=16,
                                top_m=16, task_batch=512, visited_slots=256,
                                top_k=5)
    srv = RealServer(cfg, pool_cfg)
    short = np.zeros((1, cfg.frontend_tokens - 1), np.int32)
    with pytest.raises(ValueError, match="frontend"):
        srv.generate(short, max_new=1)
