"""Pipeline parallelism over the 'pp' mesh axis.

Reference analog: PipelineOptimizer (fluid/optimizer.py:3718 — program cut by
device_guard into stage sub-programs, send_v2/recv_v2 p2p, micro-batch loop in
SectionWorker, F-then-B and 1F1B schedules; fleet
meta_optimizers/pipeline_optimizer.py:25).

TPU-native design (the "pipelined scan" from the scaling-book playbook):
every device runs the SAME program under shard_map over 'pp'; each holds its
stage's layer parameters; microbatches stream through the ring via
jax.lax.ppermute inside a lax.scan over fill+steady+drain ticks.  The
backward pass is jax.grad of the scan — XLA reverses the schedule (the
F-then-B equivalent), so no hand-written send/recv of gradients is needed.
Activation stash for the backward is handled by autodiff-of-scan; pair with
jax.checkpoint on the stage fn for 1F1B-like memory behavior.
"""
from __future__ import annotations

import functools
from typing import Callable, List

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

NEG = 0.0


def pipeline_apply(stage_fn: Callable, stage_params, x_microbatches,
                   axis_name: str = "pp", remat: bool = None,
                   head_fn: Callable = None, head_params=None,
                   tail_fn: Callable = None, tail_params=None,
                   schedule: str = "remat"):
    """Run microbatches through the pipeline inside shard_map.

    stage_fn(params, x) -> y : one stage's computation (same code every
      stage); must preserve the activation shape (the carried type).
    stage_params: this device's stage parameters (pytree).
    x_microbatches: [M, mb, ...] microbatches, valid data on EVERY device
      (replicated); stage 0 consumes them in order.
    head_fn(head_params, x_mb) -> activation: OPTIONAL shape/dtype-changing
      ingest (e.g. an embedding: int tokens -> hidden states), applied on
      stage 0 as each microbatch enters the pipe (reference: the first
      stage's section program holds the pre-pipeline layers).
    tail_fn(tail_params, activation) -> out: OPTIONAL shape-changing final
      projection applied on the last stage as each microbatch finishes.
    schedule: 'remat' (default) wraps the stage in jax.checkpoint — under
      autodiff-of-scan only the O(M) stage-BOUNDARY activations are stashed
      and per-stage intermediates are recomputed during the reverse sweep.
      'f-then-b' stashes every intermediate (reference F-then-B
      fluid/optimizer.py:4324 — faster backward, more memory).  The TRUE
      interleaved 1F1B issue order (warmup/steady/cooldown, reference
      section_worker.cc:98-129) controls the BACKWARD schedule, which a
      forward-only API cannot express — use pipeline_train_1f1b /
      pipeline_train_step for it.
    Returns [M, mb, ...] outputs (valid on the last stage; replicated out by
    caller via ppermute/psum as needed).
    """
    if schedule == "1f1b":
        raise ValueError(
            "schedule='1f1b' interleaves forward AND backward per "
            "microbatch; a forward-only pipeline cannot express it. Use "
            "pipeline_train_1f1b (inside shard_map) or "
            "pipeline_train_step (whole-array) for the real interleaved "
            "schedule, or schedule='remat' for 1F1B-class memory with "
            "autodiff-of-scan.")
    if schedule not in ("remat", "f-then-b"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    # remat is DERIVED from the schedule ('remat' = remat on, 'f-then-b' =
    # full stash); an explicit contradictory remat is an error, not a
    # silent override
    want_remat = schedule == "remat"
    if remat is None:
        remat = want_remat
    elif remat != want_remat:
        raise ValueError(
            f"remat={remat} contradicts schedule={schedule!r} "
            "(remat = rematerialized, f-then-b = full stash); pass only "
            "schedule=")
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = x_microbatches.shape[0]

    fn = jax.checkpoint(stage_fn) if remat else stage_fn
    hfn = head_fn
    if hfn is not None and remat:
        hfn = jax.checkpoint(head_fn)

    total = M + n - 1
    perm = [(i, (i + 1) % n) for i in range(n)]

    def ingest(t):
        feed = jax.lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
        return hfn(head_params, feed) if hfn is not None else feed

    # derive initial carries from a probe so their shard_map varying-axis
    # types match the loop body's outputs on any mesh (pp alone, pp×dp, …)
    probe_in = ingest(0)
    probe = fn(stage_params, probe_in) * 0
    if probe.shape != probe_in.shape or probe.dtype != probe_in.dtype:
        raise ValueError(
            "pipeline stage_fn must preserve the carried activation type "
            f"(got {probe_in.shape}/{probe_in.dtype} -> "
            f"{probe.shape}/{probe.dtype}); move shape-changing layers into "
            "head_fn / tail_fn")
    buf0 = probe
    out_probe = (tail_fn(tail_params, probe) if tail_fn is not None
                 else probe)
    outs0 = jnp.zeros((M,) + out_probe.shape, out_probe.dtype) + \
        out_probe[None] * 0

    def tick(carry, t):
        cur, outs = carry
        # stage 0 ingests microbatch t (if in range) — other stages use the
        # activation that arrived from the previous stage (where, not cond:
        # the branches differ in shard_map varying-axis type)
        cur = jnp.where(idx == 0, ingest(t), cur)
        y = fn(stage_params, cur)
        # last stage records its finished microbatch (t - (n-1))
        out_t = t - (n - 1)
        record = (idx == n - 1) & (out_t >= 0)
        out_val = tail_fn(tail_params, y) if tail_fn is not None else y
        outs = jax.lax.cond(
            record,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, out_val, jnp.clip(out_t, 0, M - 1), axis=0),
            lambda o: o,
            outs,
        )
        # rotate activations to the next stage
        nxt = jax.lax.ppermute(y, axis_name, perm)
        return (nxt, outs), None

    (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(total))
    return outs


class PipelineStage:
    """Describes the per-stage computation for pipeline_train_step."""

    def __init__(self, stage_fn, params):
        self.stage_fn = stage_fn
        self.params = params


def pipeline_forward(mesh, stage_fn, params_by_stage, x, micro_batch_size,
                     axis_name: str = "pp", remat: bool = None,
                     head_fn=None, head_params=None,
                     tail_fn=None, tail_params=None, schedule: str = "remat"):
    """Whole-array entry: params_by_stage is a pytree whose leaves have a
    leading stage dimension (sharded over 'pp'); x is the global batch
    (replicated); head/tail params are replicated.  Returns final-stage
    outputs for the full batch (head/tail may change shape+dtype)."""
    from jax import shard_map

    B = x.shape[0]
    M = B // micro_batch_size
    xm = x.reshape((M, micro_batch_size) + x.shape[1:])

    def inner(params_local, xm_local, head_p, tail_p):
        params_local = jax.tree_util.tree_map(
            lambda p: jnp.squeeze(p, axis=0), params_local)
        outs = pipeline_apply(stage_fn, params_local, xm_local,
                              axis_name=axis_name, remat=remat,
                              head_fn=head_fn, head_params=head_p,
                              tail_fn=tail_fn, tail_params=tail_p,
                              schedule=schedule)
        # broadcast final-stage outputs to all stages so out_specs can be
        # replicated (last stage holds the real values)
        n = jax.lax.psum(1, axis_name)
        idx = jax.lax.axis_index(axis_name)
        mask = (idx == n - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis_name)

    fn = shard_map(
        inner,
        mesh=mesh,
        in_specs=(PartitionSpec(axis_name), PartitionSpec(),
                  PartitionSpec(), PartitionSpec()),
        out_specs=PartitionSpec(),
    )
    outs = fn(params_by_stage, xm, head_params, tail_params)
    return outs.reshape((B,) + outs.shape[2:])


def stack_stage_params(per_stage_params: List):
    """Stack a list of per-stage parameter pytrees along a new leading axis
    (to be sharded over 'pp')."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


# ---------------------------------------------------------------------------
# True interleaved 1F1B (reference section_worker.cc:98-129 issue order;
# program transform fluid/optimizer.py:4324,4351)
# ---------------------------------------------------------------------------

def build_1f1b_schedule(n_microbatches: int, n_stages: int):
    """Static 1F1B issue tables, built in Python at trace time (the
    reference's SectionWorker also runs a FIXED schedule per config).

    One tick = one forward slot + one backward slot per stage (they are
    different microbatches in steady state).  Constraints:
    - activations travel one stage per tick (ppermute), grads likewise;
    - the last stage runs B(j) in the same tick as F(j);
    - stage s keeps at most (n_stages - s) microbatches in flight — the
      1F1B memory bound (warmup), vs M for full-stash F-then-B.

    Returns (f_tab, b_tab) int32 arrays [T, n_stages]: the microbatch
    forwarded/backwarded by each stage at each tick, -1 = idle slot.
    """
    import numpy as np

    M, n = n_microbatches, n_stages
    next_f = [0] * n
    next_b = [0] * n
    f_time = [[-1] * n for _ in range(M)]
    b_time = [[-1] * n for _ in range(M)]
    f_rows, b_rows = [], []
    t = 0
    while min(next_b) < M:
        ft = [-1] * n
        bt = [-1] * n
        for s in range(n):
            i = next_f[s]
            if i < M:
                avail = s == 0 or (0 <= f_time[i][s - 1] < t)
                in_flight = next_f[s] - next_b[s]
                if avail and in_flight < n - s:
                    ft[s] = i
                    f_time[i][s] = t
                    next_f[s] += 1
        for s in range(n):  # B issues after F within a tick
            j = next_b[s]
            if j < M and j < next_f[s]:
                avail = (f_time[j][s] <= t if s == n - 1
                         else 0 <= b_time[j][s + 1] < t)
                if avail:
                    bt[s] = j
                    b_time[j][s] = t
                    next_b[s] += 1
        f_rows.append(ft)
        b_rows.append(bt)
        t += 1
        if t > 4 * (M + n) + 8:
            raise RuntimeError("1f1b schedule did not converge")
    return (np.asarray(f_rows, np.int32), np.asarray(b_rows, np.int32))


def schedule_peak_in_flight(f_tab, b_tab) -> int:
    """Max microbatches stashed on any stage at any tick — the measured
    peak live-activation count of the schedule (must be <= n_stages; a
    full-stash F-then-B schedule peaks at M)."""
    n = f_tab.shape[1]
    live = [0] * n
    peak = 0
    for ft, bt in zip(f_tab, b_tab):
        for s in range(n):
            if ft[s] >= 0:
                live[s] += 1
        peak = max(peak, max(live))
        for s in range(n):
            if bt[s] >= 0:
                live[s] -= 1
    return peak


def pipeline_train_1f1b(stage_fn, stage_params, x_microbatches,
                        y_microbatches, loss_fn, head_fn=None,
                        head_params=None, axis_name: str = "pp"):
    """One interleaved-1F1B training step, called INSIDE shard_map.

    Explicit warmup/steady/cooldown microbatch loop: every tick each stage
    (maybe) forwards one microbatch and (maybe) backwards another, per the
    static issue tables; activations flow s->s+1 and cotangents s+1->s via
    ppermute.  The backward of a microbatch re-linearizes the stage at its
    stashed INPUT (jax.vjp), so the stash holds at most n_stages
    activations per stage — 1F1B's memory bound — instead of M.

    stage_fn(params, x) -> y        shape/dtype-preserving stage
    head_fn(head_params, x_mb) -> a optional ingest on stage 0
    loss_fn(y, y_mb) -> scalar      final projection + loss on the last
                                    stage (fold tail layers in here)
    Returns (loss_sum, stage_param_grads, head_param_grads); divide by M
    for mean-loss semantics.  Reference: section_worker.cc:98,115,129.
    """
    n_static = int(jax.lax.psum(1, axis_name))  # static under shard_map
    n = n_static
    idx = jax.lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    f_tab_np, b_tab_np = build_1f1b_schedule(M, n_static)
    T = f_tab_np.shape[0]
    f_tab = jnp.asarray(f_tab_np)
    b_tab = jnp.asarray(b_tab_np)
    # arrival tables: what lands on me this tick (sent by my neighbor in
    # the PREVIOUS tick) — static, so no metadata rides the wire
    import numpy as np

    ra_np = np.full_like(f_tab_np, -1)
    ra_np[1:, 1:] = f_tab_np[:-1, :-1]        # act of mb f_tab[t-1, s-1]
    rg_np = np.full_like(b_tab_np, -1)
    rg_np[1:, :-1] = b_tab_np[:-1, 1:]        # grad of mb b_tab[t-1, s+1]
    ra_tab = jnp.asarray(ra_np)
    rg_tab = jnp.asarray(rg_np)

    perm_fwd = [(i, (i + 1) % n_static) for i in range(n_static)]
    perm_bwd = [(i, (i - 1) % n_static) for i in range(n_static)]

    def _to_varying(v):
        """pcast to device-varying over the pipeline axis (no-op if
        already varying)."""
        if axis_name in jax.typeof(v).vma:
            return v
        return jax.lax.pcast(v, (axis_name,), to="varying")

    def ingest(mb):
        feed = jax.lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(mb, 0, M - 1), axis=0, keepdims=False)
        return (head_fn(head_params, feed) if head_fn is not None else feed)

    def target(mb):
        return jax.lax.dynamic_index_in_dim(
            y_microbatches, jnp.clip(mb, 0, M - 1), axis=0, keepdims=False)

    probe_x = ingest(0)
    act_shape, act_dtype = probe_x.shape, probe_x.dtype
    probe_y = stage_fn(stage_params, probe_x)
    if probe_y.shape != act_shape or probe_y.dtype != act_dtype:
        raise ValueError(
            "pipeline stage_fn must preserve the carried activation type "
            f"(got {act_shape}/{act_dtype} -> "
            f"{probe_y.shape}/{probe_y.dtype}); move shape-changing layers "
            "into head_fn / loss_fn")
    zeros_buf = jnp.zeros((n_static,) + act_shape, act_dtype)
    g_stage0 = jax.tree_util.tree_map(jnp.zeros_like, stage_params)
    g_head0 = (jax.tree_util.tree_map(jnp.zeros_like, head_params)
               if head_params is not None else None)

    def slot(mb):
        return jnp.clip(mb, 0, M - 1) % n_static

    def upd(buf, mb, val):
        new = jax.lax.dynamic_update_index_in_dim(buf, val, slot(mb), axis=0)
        return jnp.where(mb >= 0, new, buf)

    def tick(carry, t):
        (act_in, stash, grad_in, act_recv, grad_recv,
         g_stage, g_head, loss_sum) = carry
        f_row = jax.lax.dynamic_index_in_dim(f_tab, t, 0, keepdims=False)
        b_row = jax.lax.dynamic_index_in_dim(b_tab, t, 0, keepdims=False)
        fm = f_row[idx]
        bm = b_row[idx]
        ram = jax.lax.dynamic_index_in_dim(ra_tab, t, 0, keepdims=False)[idx]
        rgm = jax.lax.dynamic_index_in_dim(rg_tab, t, 0, keepdims=False)[idx]

        # integrate last tick's arrivals
        act_in = upd(act_in, ram, act_recv)
        grad_in = upd(grad_in, rgm, grad_recv)

        # ---- forward slot ----
        x_f = jnp.where(idx == 0, ingest(fm),
                        jax.lax.dynamic_index_in_dim(
                            act_in, slot(fm), axis=0, keepdims=False))
        y = stage_fn(stage_params, x_f)
        stash = upd(stash, fm, x_f)

        # ---- backward slot ----
        x_b = jax.lax.dynamic_index_in_dim(stash, slot(bm), axis=0,
                                           keepdims=False)
        y_b, stage_vjp = jax.vjp(stage_fn, stage_params, x_b)
        # cotangent: last stage differentiates the loss of THIS tick's
        # microbatch (B(j) shares the tick with F(j) there); other stages
        # use the grad that arrived from downstream
        loss_j, loss_vjp = jax.vjp(lambda yy: loss_fn(yy, target(bm)), y_b)
        # cotangent derived from loss_j so its shard_map varying-axis
        # type matches the differentiated output
        (g_y_last,) = loss_vjp(loss_j * 0 + 1)
        g_y_mid = jax.lax.dynamic_index_in_dim(grad_in, slot(bm), axis=0,
                                               keepdims=False)
        g_y = jnp.where(idx == n - 1, g_y_last.astype(act_dtype),
                        g_y_mid)
        gp, gx = stage_vjp(g_y)
        do_b = bm >= 0
        g_stage = jax.tree_util.tree_map(
            lambda acc, g: acc + jnp.where(do_b, g, 0), g_stage, gp)
        loss_sum = loss_sum + jnp.where(do_b & (idx == n - 1), loss_j, 0.0)
        if head_fn is not None:
            feed_b = jax.lax.dynamic_index_in_dim(
                x_microbatches, jnp.clip(bm, 0, M - 1), axis=0,
                keepdims=False)
            # pcast primals to device-varying BEFORE the vjp: shard_map AD
            # psums the cotangent of a REPLICATED primal over the axis,
            # which would silently mix other stages' (masked-out) garbage
            # into stage 0's head grads
            hp_v = jax.tree_util.tree_map(_to_varying, head_params)
            _, head_vjp = jax.vjp(head_fn, hp_v, _to_varying(feed_b))
            (gh,) = head_vjp(gx)[:1]
            g_head = jax.tree_util.tree_map(
                lambda acc, g: acc + jnp.where(do_b & (idx == 0), g, 0),
                g_head, gh)

        # ---- p2p for next tick ----
        act_recv = jax.lax.ppermute(y, axis_name, perm_fwd)
        grad_recv = jax.lax.ppermute(gx, axis_name, perm_bwd)
        return (act_in, stash, grad_in, act_recv, grad_recv,
                g_stage, g_head, loss_sum), None

    carry0 = (zeros_buf, zeros_buf, zeros_buf, probe_x * 0, probe_x * 0,
              g_stage0, g_head0, jnp.zeros((), jnp.float32))
    # initial carries derive from replicated inputs; the loop body makes
    # them device-varying (stage-dependent), so align the varying types
    carry0 = jax.tree_util.tree_map(_to_varying, carry0)
    carry, _ = jax.lax.scan(tick, carry0, jnp.arange(T))
    (_, _, _, _, _, g_stage, g_head, loss_sum) = carry
    return loss_sum, g_stage, g_head


def pipeline_train_step(mesh, stage_fn, params_by_stage, x, y,
                        micro_batch_size, loss_fn, head_fn=None,
                        head_params=None, axis_name: str = "pp"):
    """Whole-array interleaved-1F1B step (reference PipelineOptimizer
    minimize + SectionWorker run): shards stage params over `axis_name`,
    runs the 1F1B schedule, and returns (mean_loss, stage_grads_by_stage,
    head_grads) — grads stacked/replicated to match the inputs.
    """
    from jax import shard_map

    B = x.shape[0]
    M = B // micro_batch_size
    xm = x.reshape((M, micro_batch_size) + x.shape[1:])
    ym = y.reshape((M, micro_batch_size) + y.shape[1:])
    n = mesh.shape[axis_name]

    def inner(params_local, xm_, ym_, head_p):
        params_local = jax.tree_util.tree_map(
            lambda p: jnp.squeeze(p, axis=0), params_local)
        loss_sum, g_stage, g_head = pipeline_train_1f1b(
            stage_fn, params_local, xm_, ym_, loss_fn,
            head_fn=head_fn, head_params=head_p, axis_name=axis_name)
        idx = jax.lax.axis_index(axis_name)
        loss = jax.lax.psum(
            jnp.where(idx == n - 1, loss_sum, 0.0), axis_name) / M
        g_stage = jax.tree_util.tree_map(
            lambda g: (g / M)[None], g_stage)
        if g_head is not None:
            g_head = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(
                    jnp.where(idx == 0, g, 0.0), axis_name) / M, g_head)
        return loss, g_stage, g_head

    fn = shard_map(
        inner,
        mesh=mesh,
        in_specs=(PartitionSpec(axis_name), PartitionSpec(),
                  PartitionSpec(), PartitionSpec()),
        out_specs=(PartitionSpec(), PartitionSpec(axis_name),
                   PartitionSpec()),
    )
    return fn(params_by_stage, xm, ym, head_params)
