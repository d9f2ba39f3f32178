"""Row-sparse gradient tests (SelectedRows analog, VERDICT r1 #4).

Reference: selected_rows.h:41 (rows+values), lookup_table_v2 sparse grad,
lazy sparse optimizer kernels (adam_op.h), sharded embedding split
semantics (distributed/collective.py:811).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.nn import functional as F
from paddle_tpu.sparse_grad import IndexedSlices
from paddle_tpu.tensor import Tensor


VOCAB, DIM = 1000, 16


def _make(seed=0, sparse=True):
    paddle.seed(seed)
    emb = nn.Embedding(VOCAB, DIM, sparse=sparse)
    return emb


class TestIndexedSlices:
    def test_embedding_backward_is_sparse(self):
        emb = _make()
        ids = paddle.to_tensor(np.array([[1, 5, 7], [5, 2, 9]], np.int64))
        out = emb(ids)
        out.sum().backward()
        g = emb.weight._grad
        assert isinstance(g, IndexedSlices)
        assert g.values.shape == (6, DIM)           # one row grad per id
        assert g.dense_shape == (VOCAB, DIM)
        # the dense vocab×dim grad is never formed: nnz rows ≪ vocab
        assert g.rows.shape[0] == 6 < VOCAB

    def test_to_dense_matches_dense_path(self):
        ids_np = np.array([[1, 5, 7], [5, 2, 9]], np.int64)
        emb_s = _make(seed=3, sparse=True)
        emb_d = _make(seed=3, sparse=False)
        np.testing.assert_allclose(np.asarray(emb_s.weight._value),
                                   np.asarray(emb_d.weight._value))
        for emb in (emb_s, emb_d):
            (emb(paddle.to_tensor(ids_np)) ** 2).sum().backward()
        gs, gd = emb_s.weight._grad, emb_d.weight._grad
        np.testing.assert_allclose(np.asarray(gs.to_dense()),
                                   np.asarray(gd._value), rtol=1e-5)

    def test_merged_handles_duplicates(self):
        rows = jnp.asarray([3, 1, 3, 1, 3], jnp.int32)
        vals = jnp.ones((5, 4), jnp.float32)
        m = IndexedSlices(rows, vals, (10, 4)).merged()
        dense = np.asarray(m.to_dense())
        assert dense[3].sum() == 12.0 and dense[1].sum() == 8.0
        assert dense.sum() == 20.0

    def test_accumulation_two_backwards(self):
        emb = _make(seed=1)
        ids1 = paddle.to_tensor(np.array([[0, 1]], np.int64))
        ids2 = paddle.to_tensor(np.array([[1, 2]], np.int64))
        emb(ids1).sum().backward()
        emb(ids2).sum().backward()
        g = emb.weight._grad
        assert isinstance(g, IndexedSlices)
        dense = np.asarray(g.to_dense())
        np.testing.assert_allclose(dense[1], np.full(DIM, 2.0))
        np.testing.assert_allclose(dense[0], np.ones(DIM))


class TestSparseOptimizers:
    @pytest.mark.parametrize("opt_cls,kwargs", [
        (optimizer.SGD, {}),
        (optimizer.Momentum, {"momentum": 0.9}),
        (optimizer.Adam, {}),
    ])
    def test_sparse_step_matches_dense_on_touched_rows(self, opt_cls, kwargs):
        ids_np = np.array([[1, 5, 7, 5]], np.int64)
        results = {}
        for sparse in (True, False):
            emb = _make(seed=7, sparse=sparse)
            opt = opt_cls(learning_rate=0.1, parameters=emb.parameters(),
                          **kwargs)
            (emb(paddle.to_tensor(ids_np)) ** 2).sum().backward()
            opt.step()
            results[sparse] = np.asarray(emb.weight._value)
        touched = [1, 5, 7]
        np.testing.assert_allclose(results[True][touched],
                                   results[False][touched],
                                   rtol=1e-4, atol=1e-6)
        # untouched rows identical to initial (single step from zero state)
        untouched = [0, 2, 3]
        np.testing.assert_allclose(results[True][untouched],
                                   results[False][untouched])

    def test_large_vocab_trains(self):
        """End-to-end: a large-vocab embedding model trains with sparse
        updates, loss decreases."""
        paddle.seed(0)
        emb = nn.Embedding(50_000, 32, sparse=True)
        head = nn.Linear(32, 2)
        opt = optimizer.Adam(
            learning_rate=0.05,
            parameters=list(emb.parameters()) + list(head.parameters()))
        loss_fn = nn.CrossEntropyLoss()
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(rng.randint(0, 50_000, (16, 4)).astype(np.int64))
        y = paddle.to_tensor((rng.randint(0, 2, (16,))).astype(np.int64))
        losses = []
        for _ in range(15):
            logits = head(emb(ids).mean(axis=1))
            loss = loss_fn(logits, y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(np.asarray(loss._value)))
        assert losses[-1] < losses[0] * 0.5


class TestShardedEmbeddingParity:
    def test_vocab_parallel_matches_dense(self):
        """Row-sharded (mp) embedding under shard_map == gather from the
        full table (reference split semantics, collective.py:811 parallel
        embedding: row-split + allreduce)."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed import init_mesh

        mesh = init_mesh({"mp": 8})
        paddle.seed(0)
        emb = dist.VocabParallelEmbedding(64, 16)
        rng = np.random.RandomState(0)
        full_w = rng.randn(64, 16).astype(np.float32)
        ids = np.array([[0, 13, 21, 63]], np.int64)

        def f(idx, w_shard):
            emb.weight._value = w_shard
            return emb(Tensor(idx))._value

        out = shard_map(
            f, mesh=mesh, in_specs=(P(None, None), P("mp", None)),
            out_specs=P(None, None, None),
        )(jnp.asarray(ids, jnp.int32), jnp.asarray(full_w))
        want = full_w[ids.reshape(-1)].reshape(1, 4, 16)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)

    def test_eager_shard_lookup_masked(self):
        """Eager (single-participant) lookup: out-of-shard ids give zeros,
        never NaN."""
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed import init_mesh

        init_mesh({"mp": 8})
        paddle.seed(0)
        emb = dist.VocabParallelEmbedding(64, 16)
        out = emb(paddle.to_tensor(np.array([[0, 7, 8, 63]], np.int64)))
        arr = out.numpy()
        assert np.isfinite(arr).all()
        assert np.abs(arr[0, :2]).sum() > 0          # local rows resolved
        np.testing.assert_allclose(arr[0, 2:], 0.0)  # non-local rows zero
