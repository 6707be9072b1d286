"""Host-offloaded embedding table (reference strategy: the PS sparse-table
tests — test/legacy_test/test_dist_fleet_ps*.py exercise pull_sparse /
push_sparse against memory/ssd tables; here the host tier is the
`pinned_host` memory kind and pushes are compiled scatter updates)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import HostOffloadedEmbedding


def test_table_lives_in_host_memory():
    tab = HostOffloadedEmbedding(1000, 16, optimizer="sgd")
    assert tab.memory_kind == "pinned_host"


def test_lookup_matches_table_rows():
    tab = HostOffloadedEmbedding(100, 8, optimizer="sgd")
    ids = paddle.to_tensor(np.array([[3, 5], [7, 3]], np.int32))
    out = tab(ids)
    assert out.shape == [2, 2, 8]
    table = np.asarray(tab.weight._value)
    np.testing.assert_allclose(out.numpy()[0, 0], table[3], rtol=1e-6)
    np.testing.assert_allclose(out.numpy()[1, 1], table[3], rtol=1e-6)
    np.testing.assert_allclose(out.numpy()[0, 1], table[5], rtol=1e-6)


def test_sparse_push_updates_only_touched_rows():
    tab = HostOffloadedEmbedding(50, 4, optimizer="sgd", learning_rate=1.0)
    tab.train()
    before = np.asarray(tab.weight._value).copy()
    ids = paddle.to_tensor(np.array([2, 2, 9], np.int32))
    out = tab(ids)
    # loss = sum(out) -> d/drow = 1 per occurrence; row 2 appears twice
    out.sum().backward()
    after = np.asarray(tab.weight._value)
    np.testing.assert_allclose(after[2], before[2] - 2.0, rtol=1e-5)
    np.testing.assert_allclose(after[9], before[9] - 1.0, rtol=1e-5)
    untouched = [i for i in range(50) if i not in (2, 9)]
    np.testing.assert_array_equal(after[untouched], before[untouched])
    # no dense gradient ever materializes for the table
    assert tab.weight.grad is None
    assert tab.memory_kind == "pinned_host"


def test_adagrad_accumulates():
    tab = HostOffloadedEmbedding(20, 4, optimizer="adagrad",
                                 learning_rate=0.5)
    tab.train()
    ids = paddle.to_tensor(np.array([1], np.int32))
    before = np.asarray(tab.weight._value)[1].copy()
    tab(ids).sum().backward()
    step1 = before - np.asarray(tab.weight._value)[1]
    tab(ids).sum().backward()
    step2 = (before - step1) - np.asarray(tab.weight._value)[1]
    # same cotangent twice: adagrad's second step must be smaller
    assert np.all(np.abs(step2) < np.abs(step1))
    assert float(np.asarray(tab._accum)[1]) > 0


def test_larger_than_device_memory_trains():
    # The capacity claim: the table is held ONLY in host memory; device
    # memory sees just the touched rows. 200k x 64 fp32 = 51 MB stands in
    # for a table exceeding HBM — the mechanism (host placement + sparse
    # row pushes, never a dense [N, D] grad) is what scales.
    N, D = 200_000, 64
    tab = HostOffloadedEmbedding(N, D, optimizer="sgd", learning_rate=0.1)
    tab.train()
    assert tab.memory_kind == "pinned_host"
    rng = np.random.RandomState(0)
    ids_np = rng.randint(0, N, size=(64,)).astype(np.int32)
    before = np.asarray(tab.weight._value)[ids_np[0]].copy()
    for _ in range(3):
        out = tab(paddle.to_tensor(ids_np))
        (out * out).sum().backward()
    after = np.asarray(tab.weight._value)[ids_np[0]]
    assert not np.allclose(before, after)


def test_eval_cache_serves_hot_rows():
    tab = HostOffloadedEmbedding(100, 4, cache_size=8, optimizer="sgd")
    tab.eval()
    ids = paddle.to_tensor(np.array([4, 5, 4], np.int32))
    out1 = tab(ids)
    assert set(tab._cache_map) == {4, 5}
    table = np.asarray(tab.weight._value)
    np.testing.assert_allclose(out1.numpy()[0], table[4], rtol=1e-6)
    out2 = tab(ids)  # served from cache
    np.testing.assert_allclose(out2.numpy(), out1.numpy())


def test_cache_invalidated_after_training_push():
    tab = HostOffloadedEmbedding(30, 4, cache_size=4, optimizer="sgd",
                                 learning_rate=1.0)
    tab.eval()
    ids = paddle.to_tensor(np.array([3], np.int32))
    stale = tab(ids).numpy().copy()
    tab.train()
    tab(ids).sum().backward()  # push updates row 3
    tab.eval()
    fresh = tab(ids).numpy()
    assert not np.allclose(stale, fresh)
    np.testing.assert_allclose(fresh[0],
                               np.asarray(tab.weight._value)[3], rtol=1e-6)


def test_lru_eviction():
    tab = HostOffloadedEmbedding(100, 4, cache_size=2, optimizer="sgd")
    tab.eval()
    tab(paddle.to_tensor(np.array([1], np.int32)))
    tab(paddle.to_tensor(np.array([2], np.int32)))
    tab(paddle.to_tensor(np.array([1], np.int32)))  # touch 1
    tab(paddle.to_tensor(np.array([3], np.int32)))  # evicts 2
    assert 2 not in tab._cache_map
    assert {1, 3} <= set(tab._cache_map)


def test_cache_overflow_batch_bypasses_cache():
    # batch working set > cache_size must serve correctly (no KeyError)
    tab = HostOffloadedEmbedding(100, 4, cache_size=4, optimizer="sgd")
    tab.eval()
    ids = np.arange(8, dtype=np.int32)
    out = tab(paddle.to_tensor(ids))
    table = np.asarray(tab.weight._value)
    np.testing.assert_allclose(out.numpy(), table[ids], rtol=1e-6)
    # then a small batch still uses the cache and can't evict its own hits
    tab(paddle.to_tensor(np.array([1, 2, 3, 4], np.int32)))
    out2 = tab(paddle.to_tensor(np.array([1, 5], np.int32)))
    np.testing.assert_allclose(out2.numpy(), table[[1, 5]], rtol=1e-6)


def test_smallest_id_trains_with_nonpow2_unique_count():
    # regression: pad ids duplicated the smallest uid; a duplicate-index
    # scatter-set could drop its real update
    tab = HostOffloadedEmbedding(20, 4, optimizer="sgd", learning_rate=1.0)
    tab.train()
    before = np.asarray(tab.weight._value).copy()
    ids = paddle.to_tensor(np.array([0, 5, 9], np.int32))  # 3 -> pad to 4
    tab(ids).sum().backward()
    after = np.asarray(tab.weight._value)
    np.testing.assert_allclose(after[0], before[0] - 1.0, rtol=1e-5)
    np.testing.assert_allclose(after[5], before[5] - 1.0, rtol=1e-5)
    np.testing.assert_allclose(after[9], before[9] - 1.0, rtol=1e-5)
