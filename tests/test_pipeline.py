"""Pipeline parallelism tests (reference analog: SectionWorker microbatch
schedules, section_worker.cc:98 — validated here by equivalence with
sequential execution)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed import init_mesh
from paddle_tpu.distributed.pipeline import (
    pipeline_forward,
    stack_stage_params,
)


def stage_fn(params, x):
    w, b = params["w"], params["b"]
    return jnp.tanh(x @ w + b)


def make_params(n_stages, d, seed=0):
    rng = np.random.RandomState(seed)
    per_stage = [
        {"w": jnp.asarray(rng.randn(d, d).astype(np.float32) * 0.5),
         "b": jnp.asarray(rng.randn(d).astype(np.float32) * 0.1)}
        for _ in range(n_stages)
    ]
    return per_stage


class TestPipeline:
    def test_matches_sequential(self):
        mesh = init_mesh({"pp": 4})
        d = 8
        per_stage = make_params(4, d)
        stacked = stack_stage_params(per_stage)
        x = np.random.RandomState(3).randn(16, d).astype(np.float32)

        out = pipeline_forward(mesh, stage_fn, stacked, jnp.asarray(x),
                               micro_batch_size=4)
        ref = jnp.asarray(x)
        for p in per_stage:
            ref = stage_fn(p, ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_grads_match_sequential(self):
        mesh = init_mesh({"pp": 4})
        d = 8
        per_stage = make_params(4, d, seed=9)
        stacked = stack_stage_params(per_stage)
        x = jnp.asarray(np.random.RandomState(5).randn(8, d).astype(np.float32))

        def loss_pipe(params):
            out = pipeline_forward(mesh, stage_fn, params, x, micro_batch_size=2)
            return jnp.sum(out ** 2)

        def loss_seq(per):
            ref = x
            for p in per:
                ref = stage_fn(p, ref)
            return jnp.sum(ref ** 2)

        g_pipe = jax.grad(loss_pipe)(stacked)
        g_seq = jax.grad(loss_seq)(per_stage)
        g_seq_stacked = stack_stage_params(g_seq)
        np.testing.assert_allclose(np.asarray(g_pipe["w"]),
                                   np.asarray(g_seq_stacked["w"]),
                                   rtol=1e-4, atol=1e-5)

    def test_microbatch_count_independence(self):
        """More microbatches (deeper pipeline fill) must not change results."""
        mesh = init_mesh({"pp": 4})
        d = 4
        stacked = stack_stage_params(make_params(4, d, seed=2))
        x = jnp.asarray(np.random.RandomState(8).randn(16, d).astype(np.float32))
        o2 = pipeline_forward(mesh, stage_fn, stacked, x, micro_batch_size=8)
        o8 = pipeline_forward(mesh, stage_fn, stacked, x, micro_batch_size=2)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(o8), rtol=1e-5)

    def test_pp_times_dp_mesh(self):
        """pipeline inside a 2-axis mesh (pp=4, dp=2): batch sharded over dp."""
        mesh = init_mesh({"pp": 4, "dp": 2})
        d = 4
        per_stage = make_params(4, d, seed=11)
        stacked = stack_stage_params(per_stage)
        x = np.random.RandomState(1).randn(8, d).astype(np.float32)

        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed.pipeline import pipeline_apply

        def inner(params_local, xloc):
            params_local = jax.tree_util.tree_map(
                lambda p: jnp.squeeze(p, axis=0), params_local)
            xm = xloc.reshape(2, 2, d)
            outs = pipeline_apply(stage_fn, params_local, xm, axis_name="pp")
            n = jax.lax.psum(1, "pp")
            idx = jax.lax.axis_index("pp")
            outs = jax.lax.psum(outs * (idx == n - 1).astype(outs.dtype), "pp")
            return outs.reshape(4, d)

        fn = shard_map(inner, mesh=mesh,
                       in_specs=(P("pp"), P("dp")), out_specs=P("dp"))
        out = fn(stacked, jnp.asarray(x))
        ref = jnp.asarray(x)
        for p in per_stage:
            ref = stage_fn(p, ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestPipelineHeadTail:
    """Shape/dtype-changing head (embedding) + tail (classifier) stages
    (VERDICT r2 task 3b) with loss parity vs the non-pipelined model."""

    V, D, K = 32, 8, 4

    def _parts(self, seed=0):
        rng = np.random.RandomState(seed)
        head = {"emb": jnp.asarray(rng.randn(self.V, self.D)
                                   .astype(np.float32) * 0.5)}
        tail = {"w": jnp.asarray(rng.randn(self.D, self.K)
                                 .astype(np.float32) * 0.5)}
        stages = make_params(4, self.D, seed=seed + 1)
        return head, stages, tail

    @staticmethod
    def _head_fn(hp, tok):
        return hp["emb"][tok]            # int32 [mb, T] -> f32 [mb, T, D]

    @staticmethod
    def _tail_fn(tp, h):
        return h.mean(axis=1) @ tp["w"]  # [mb, T, D] -> [mb, K]

    def _stage3(self, p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    def test_head_tail_matches_sequential(self):
        mesh = init_mesh({"pp": 4})
        head, stages, tail = self._parts()
        stacked = stack_stage_params(stages)
        tok = jnp.asarray(np.random.RandomState(2).randint(
            0, self.V, (16, 5)), jnp.int32)
        out = pipeline_forward(
            mesh, self._stage3, stacked, tok, micro_batch_size=4,
            head_fn=self._head_fn, head_params=head,
            tail_fn=self._tail_fn, tail_params=tail)
        ref = self._head_fn(head, tok)
        for p in stages:
            ref = self._stage3(p, ref)
        ref = self._tail_fn(tail, ref)
        assert out.shape == (16, self.K)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_head_tail_grads_and_loss_parity(self):
        """Full loss parity incl. gradients for head/stage/tail params vs
        the non-pipelined computation."""
        mesh = init_mesh({"pp": 4})
        head, stages, tail = self._parts(seed=5)
        stacked = stack_stage_params(stages)
        tok = jnp.asarray(np.random.RandomState(4).randint(
            0, self.V, (8, 5)), jnp.int32)
        y = jnp.asarray(np.random.RandomState(5).randint(0, self.K, (8,)),
                        jnp.int32)

        def pipe_loss(hp, st, tp):
            logits = pipeline_forward(
                mesh, self._stage3, st, tok, micro_batch_size=2,
                head_fn=self._head_fn, head_params=hp,
                tail_fn=self._tail_fn, tail_params=tp)
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            return jnp.mean(lse - ll)

        def ref_loss(hp, per_stage, tp):
            h = self._head_fn(hp, tok)
            for p in per_stage:
                h = self._stage3(p, h)
            logits = self._tail_fn(tp, h)
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            return jnp.mean(lse - ll)

        l1, g1 = jax.value_and_grad(pipe_loss, argnums=(0, 1, 2))(
            head, stacked, tail)
        l2, g2 = jax.value_and_grad(
            lambda hp, st, tp: ref_loss(
                hp, [jax.tree_util.tree_map(lambda v: v[i], st)
                     for i in range(4)], tp),
            argnums=(0, 1, 2))(head, stacked, tail)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g1),
                        jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_schedules_agree(self):
        """'remat' (checkpointed) and 'f-then-b' (full stash) are the
        same math — outputs and grads must agree exactly."""
        mesh = init_mesh({"pp": 4})
        head, stages, tail = self._parts(seed=8)
        stacked = stack_stage_params(stages)
        tok = jnp.asarray(np.random.RandomState(6).randint(
            0, self.V, (8, 5)), jnp.int32)

        def loss(st, schedule):
            out = pipeline_forward(
                mesh, self._stage3, st, tok, micro_batch_size=2,
                head_fn=self._head_fn, head_params=head,
                tail_fn=self._tail_fn, tail_params=tail,
                schedule=schedule)
            return (out.astype(jnp.float32) ** 2).sum()

        l1, g1 = jax.value_and_grad(lambda s: loss(s, "remat"))(stacked)
        l2, g2 = jax.value_and_grad(lambda s: loss(s, "f-then-b"))(stacked)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(g1),
                        jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_shape_preserving_violation_raises(self):
        mesh = init_mesh({"pp": 4})
        _, stages, _ = self._parts()
        stacked = stack_stage_params(stages)
        x = jnp.ones((8, 8), jnp.float32)

        def bad_stage(p, v):
            return (v @ p["w"])[:, :4]  # shrinks the activation

        with pytest.raises(Exception, match="preserve the carried"):
            pipeline_forward(mesh, bad_stage, stacked, x,
                             micro_batch_size=2)


class Test1F1B:
    """True interleaved 1F1B (VERDICT r4 next-round #5): explicit
    warmup/steady/cooldown microbatch schedule with per-microbatch
    jax.vjp backward, p2p via ppermute, stash bounded by n_stages.

    Reference: section_worker.cc:98,115,129 (1F1B issue order),
    fluid/optimizer.py:4324,4351 (program transform)."""

    def test_schedule_tables_are_1f1b(self):
        from paddle_tpu.distributed.pipeline import (
            build_1f1b_schedule, schedule_peak_in_flight)

        M, n = 8, 4
        f, b = build_1f1b_schedule(M, n)
        # every stage forwards and backwards every microbatch exactly
        # once, in order
        for s in range(n):
            fs = [int(x) for x in f[:, s] if x >= 0]
            bs = [int(x) for x in b[:, s] if x >= 0]
            assert fs == list(range(M))
            assert bs == list(range(M))
        # peak live activations: 1F1B bound (<= n stages), not M
        peak = schedule_peak_in_flight(f, b)
        assert peak <= n < M
        # last stage backwards each mb in the same tick as its forward
        for t in range(f.shape[0]):
            if f[t, n - 1] >= 0:
                assert b[t, n - 1] == f[t, n - 1]
        # warmup: stage 0 admits exactly n forwards before its first B
        first_b_tick = min(t for t in range(b.shape[0]) if b[t, 0] >= 0)
        warmup_fwds = sum(1 for t in range(first_b_tick)
                          if f[t, 0] >= 0)
        assert warmup_fwds == n

    def test_schedule_steady_state_interleaves(self):
        from paddle_tpu.distributed.pipeline import build_1f1b_schedule

        M, n = 16, 4
        f, b = build_1f1b_schedule(M, n)
        # in the steady region, stage 0 does one F and one B per tick
        steady = [t for t in range(f.shape[0])
                  if f[t, 0] >= n and b[t, 0] >= 0]
        assert len(steady) > 0
        for t in steady:
            assert f[t, 0] >= 0 and b[t, 0] >= 0  # interleaved, not phased

    def test_train_step_matches_sequential(self):
        from paddle_tpu.distributed.pipeline import pipeline_train_step

        mesh = init_mesh({"pp": 4})
        n, d, B, mbs = 4, 8, 8, 2
        M = B // mbs
        per_stage = make_params(n, d, seed=11)
        stacked = stack_stage_params(per_stage)
        rng = np.random.RandomState(3)
        head = {"w": jnp.asarray(rng.randn(6, d).astype(np.float32) * 0.3)}
        x = jnp.asarray(rng.randn(B, 6).astype(np.float32))
        y = jnp.asarray(rng.randn(B, d).astype(np.float32))

        def head_fn(hp, xb):
            return xb @ hp["w"]

        def loss_fn(out, tgt):
            return ((out - tgt) ** 2).sum()

        loss, g_stage, g_head = pipeline_train_step(
            mesh, stage_fn, stacked, x, y, mbs, loss_fn,
            head_fn=head_fn, head_params=head)

        def seq_loss(hp, st):
            h = head_fn(hp, x)
            for s in range(n):
                p = jax.tree_util.tree_map(lambda a: a[s], st)
                h = stage_fn(p, h)
            return loss_fn(h, y) / M

        ref_loss, (ref_gh, ref_gs) = jax.value_and_grad(
            seq_loss, argnums=(0, 1))(head, stacked)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        for a, r in zip(jax.tree_util.tree_leaves(g_stage),
                        jax.tree_util.tree_leaves(ref_gs)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-4, atol=1e-5)
        for a, r in zip(jax.tree_util.tree_leaves(g_head),
                        jax.tree_util.tree_leaves(ref_gh)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-4, atol=1e-5)

    def test_more_microbatches_than_stages(self):
        from paddle_tpu.distributed.pipeline import pipeline_train_step

        mesh = init_mesh({"pp": 4})
        n, d, B, mbs = 4, 4, 24, 2
        M = B // mbs
        per_stage = make_params(n, d, seed=5)
        stacked = stack_stage_params(per_stage)
        rng = np.random.RandomState(9)
        x = jnp.asarray(rng.randn(B, d).astype(np.float32))
        y = jnp.asarray(rng.randn(B, d).astype(np.float32))

        def loss_fn(out, tgt):
            return ((out - tgt) ** 2).sum()

        loss, g_stage, _ = pipeline_train_step(
            mesh, stage_fn, stacked, x, y, mbs, loss_fn)

        def seq_loss(st):
            h = x
            for s in range(n):
                p = jax.tree_util.tree_map(lambda a: a[s], st)
                h = stage_fn(p, h)
            return loss_fn(h, y) / M

        ref_loss, ref_gs = jax.value_and_grad(seq_loss)(stacked)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        for a, r in zip(jax.tree_util.tree_leaves(g_stage),
                        jax.tree_util.tree_leaves(ref_gs)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-4, atol=1e-5)

    def test_1f1b_alias_removed(self):
        from paddle_tpu.distributed.pipeline import pipeline_apply

        with pytest.raises(ValueError, match="pipeline_train_1f1b"):
            pipeline_apply(stage_fn, {}, jnp.zeros((2, 2, 4)),
                           schedule="1f1b")
