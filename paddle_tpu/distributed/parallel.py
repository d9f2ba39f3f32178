"""Data parallelism.

Reference analog: paddle.DataParallel (fluid/dygraph/parallel.py:322) backed
by the C++ Reducer (imperative/reducer.cc:587 MarkVarReady, :685
FusedAllReduceSchedule — bucketed fused allreduce overlapped with backward).

TPU-native: gradient bucketing/overlap is subsumed by XLA's async collectives
inside the jitted train step — `make_sharded_train_step` builds that step
(batch sharded over 'dp', params replicated, grads psum'd by XLA).  The
DataParallel wrapper is kept for API parity: eagerly it is transparent
(single process), and its `.sharded_step()` exposes the SPMD path.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..jit.functional import functional_call, get_state
from ..nn.layer import Layer
from ..tensor import Tensor
from .env import get_rank, get_world_size, init_parallel_env  # noqa: F401
from .mesh import get_mesh


class DataParallel(Layer):
    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None):
        super().__init__()
        self._layers = layers
        self.add_sublayer("_layers", layers)
        self.find_unused_parameters = find_unused_parameters

    def forward(self, *inputs, **kwargs):
        return self._layers(*inputs, **kwargs)

    def state_dict(self, *args, **kwargs):
        return self._layers.state_dict(*args, **kwargs)

    def set_state_dict(self, state_dict, *args, **kwargs):
        return self._layers.set_state_dict(state_dict, *args, **kwargs)

    def scale_loss(self, loss):
        return loss

    def apply_collective_grads(self):
        """Reducer analog: in SPMD the psum happens inside the step; eagerly
        single-process this is a no-op."""
        return


def make_localsgd_train_step(layer: Layer, loss_fn: Callable, optimizer,
                             k_steps: int, mesh=None, axis: str = "dp",
                             begin_step: int = 1):
    """LocalSGD SPMD step (reference localsgd_optimizer.py semantics): every
    replica along ``axis`` holds its OWN parameter/optimizer-state copy and
    takes purely local steps (no gradient collective); every ``k_steps``-th
    step past ``begin_step``, parameters (and optimizer state) are pmean'd
    across the axis inside the same compiled program.

    Returns (step_fn, state); step_fn(state, x, y) -> (state, mean_loss).
    x/y are global batches sharded over ``axis``.
    """
    from jax import shard_map

    mesh = mesh or get_mesh()
    n = mesh.shape[axis]
    params0, buffers0 = get_state(layer)
    opt0 = optimizer.init_opt_state(params0)

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda v: jnp.broadcast_to(v[None], (n,) + v.shape), tree)

    state = {"params": stack(params0), "buffers": stack(buffers0),
             "opt": stack(opt0), "step": jnp.zeros((), jnp.int32)}

    from ..framework.random import rng_scope

    def inner(p_st, b_st, o_st, count, x, y, key):
        squeeze = lambda t: jax.tree_util.tree_map(
            lambda v: jnp.squeeze(v, 0), t)
        p, b, o = squeeze(p_st), squeeze(b_st), squeeze(o_st)

        def loss_of(pp, bb):
            with rng_scope(key):
                out, nb = functional_call(layer, pp, bb, (x,), training=True)
            loss = loss_fn(Tensor(out) if isinstance(out, jax.Array) else out,
                           Tensor(y))
            return loss._value.astype(jnp.float32), nb

        (loss, nb), grads = jax.value_and_grad(loss_of, has_aux=True)(p, b)
        count = count + 1
        new_p, new_o = optimizer.fused_step(p, grads, o, count)

        do_avg = (count >= begin_step) & (count % k_steps == 0)
        avg = lambda t: jax.tree_util.tree_map(
            lambda v: jax.lax.pmean(v, axis) if jnp.issubdtype(
                v.dtype, jnp.floating) else v, t)
        new_p, new_o = jax.lax.cond(
            do_avg, lambda a, c: (avg(a), avg(c)), lambda a, c: (a, c),
            new_p, new_o)

        expand = lambda t: jax.tree_util.tree_map(lambda v: v[None], t)
        return (expand(new_p), expand(nb), expand(new_o), count,
                jax.lax.pmean(loss, axis))

    P = PartitionSpec
    step_sm = shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P(axis), P(axis), P()),
        out_specs=(P(axis), P(axis), P(axis), P(), P()),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def jit_step(state, x, y, key):
        p, b, o, c, loss = step_sm(state["params"], state["buffers"],
                                   state["opt"], state["step"], x, y, key)
        return {"params": p, "buffers": b, "opt": o, "step": c}, loss

    def run(state, x, y, key=None):
        from ..framework.random import default_generator

        if key is None:
            key = default_generator.split_key()
        xv = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        yv = y._value if isinstance(y, Tensor) else jnp.asarray(y)
        return jit_step(state, xv, yv, key)

    return run, state


def make_sharded_train_step(layer: Layer, loss_fn: Callable, optimizer,
                            mesh=None, data_axes=("dp",), donate=True):
    """Build a pjit'd SPMD train step: params replicated over 'dp' (sharded
    over 'mp' etc. if parameters carry partition_spec), batch sharded over
    data_axes, gradients reduced by XLA.

    A mesh axis shards a parameter dim only where it divides it; a dim it
    does not divide (GPT-2's published vocabulary, 50257, over mp=2) stays
    whole on every device, with a warning naming the parameter.  The Pallas
    flash kernel, which XLA cannot partition by itself, is split over the
    same mesh — batch over data_axes, heads over 'mp' — under shard_map
    (`flash_attention.partitioned_over`).

    Returns (step_fn, state) where state = {'params','buffers','opt','step'};
    step_fn(state, batch_x, batch_y, key) -> (state, loss).
    """
    from ..ops.pallas_ops.flash_attention import partitioned_over

    mesh = mesh or get_mesh()
    params, buffers = get_state(layer)
    param_objs = dict(layer.named_parameters())

    def param_sharding(name, v):
        spec = getattr(param_objs[name], "partition_spec", None) or ()
        fitted = tuple(
            None if a in mesh.shape and v.shape[i] % mesh.shape[a] else a
            for i, a in enumerate(spec))
        if fitted != tuple(spec):
            warnings.warn(
                f"make_sharded_train_step: {name} {tuple(v.shape)} is not "
                f"divisible by mesh {dict(mesh.shape)} along {tuple(spec)}; "
                f"placed as {fitted}", stacklevel=3)
        return NamedSharding(mesh, PartitionSpec(*fitted))

    replicated = NamedSharding(mesh, PartitionSpec())
    params = {n: jax.device_put(v, param_sharding(n, v)) for n, v in params.items()}
    buffers = {n: jax.device_put(v, replicated) for n, v in buffers.items()}
    opt_state = jax.tree_util.tree_map(
        lambda v: jax.device_put(v, replicated),
        optimizer.init_opt_state(params))

    data_sharding = NamedSharding(mesh, PartitionSpec(data_axes[0] if data_axes else None))

    from ..framework.random import rng_scope

    def loss_of(params_, buffers_, x, y, key):
        with rng_scope(key):
            out, new_bufs = functional_call(layer, params_, buffers_, (x,),
                                            training=True)
        loss = loss_fn(Tensor(out) if isinstance(out, jax.Array) else out,
                       Tensor(y))
        return loss._value.astype(jnp.float32), new_bufs

    def step_fn(state, x, y, key):
        params_, buffers_, opt_, count = (state["params"], state["buffers"],
                                          state["opt"], state["step"])
        with partitioned_over(mesh, data_axes):
            (loss, new_bufs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params_, buffers_, x, y, key)
        new_params, new_opt = optimizer.fused_step(params_, grads, opt_,
                                                   count + 1)
        return ({"params": new_params, "buffers": new_bufs, "opt": new_opt,
                 "step": count + 1}, loss)

    state = {"params": params, "buffers": buffers, "opt": opt_state,
             "step": jax.device_put(jnp.zeros((), jnp.int32), replicated)}
    # the new state keeps the placement of the old: left to the
    # partitioner it may come back laid out otherwise, which un-shards
    # the weights and compiles the step a second time on the next call
    jit_step = jax.jit(
        step_fn, donate_argnums=(0,) if donate else (),
        out_shardings=(jax.tree_util.tree_map(lambda v: v.sharding, state),
                       replicated))

    def run(state, x, y, key=None):
        from ..framework.random import default_generator

        if key is None:
            key = default_generator.split_key()
        xv = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        yv = y._value if isinstance(y, Tensor) else jnp.asarray(y)
        xv = jax.device_put(xv, data_sharding)
        yv = jax.device_put(yv, data_sharding)
        return jit_step(state, xv, yv, key)

    return run, state


def sync_params_buffers(model, comm_group=None, src_rank=0,
                        is_model_parallel=False):
    """Broadcast-parameters analog (parallel.py sync_params_buffers): on TPU,
    replication is a sharding constraint — re-place params replicated."""
    mesh = get_mesh()
    for _, p in model.named_parameters():
        p._value = jax.device_put(p._value, NamedSharding(mesh, PartitionSpec()))
