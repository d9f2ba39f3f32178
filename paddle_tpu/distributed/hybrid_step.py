"""Hybrid dp×pp×mp training step with ZeRO optimizer sharding — the
explicit-collective composition of every parallelism axis in one compiled
program.

Reference analog: the fleet meta-optimizer stack composing sharding + pipeline
+ tensor parallel rewrites over one Program (sharding_optimizer.py:69,
pipeline_optimizer.py:151, collective.py:811 `split`).  TPU-native: one
``shard_map`` over a ('dp','pp','mp') mesh —
  pp: microbatch pipeline scan via ppermute (distributed/pipeline.py)
  mp: Megatron column/row-parallel MLP with in-graph psum; the classifier
      head is column-sharded with an all_gather of logits
  dp: batch sharding; gradients reduce-scattered and optimizer state sharded
      by ZeRO-1/2 (distributed/zero.py), updated params all-gathered

Model (toy but structurally faithful): embedding -> pp pipeline of
[residual MLP stage] -> mean-pool -> column-parallel classifier.

Gradient bookkeeping (why the psums below are correct):
  - the scalar loss is DEFINED as psum(mask_last_stage * local_loss, 'pp'),
    so only the last pp rank's head/loss computation receives cotangents —
    psum'ing param grads over 'pp' cannot double-count;
  - activation cotangents flowing up the network are PARTIAL over 'mp'
    (each mp rank back-propagates through its own head/W1 shard while the
    residual identity path replicates).  Megatron's ``f`` operator
    (``_mp_copy``: identity forward, psum-over-'mp' backward — reference
    collective.py:811 `_c_identity`) sits at the pipeline input, so the
    embedding grad arrives complete on every mp rank (then psum over 'pp'
    only, since it is nonzero only on the ingest stage);
  - W1/b1/W2 grads are exact locally because the in-stage psum's transpose
    re-totals the partial cotangents; b2 (added after the psum) sees the
    partial cotangent directly, so its grad needs an explicit psum('mp');
  - only the 'dp' reduction (inside the ZeRO update) applies beyond that.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .pipeline import pipeline_apply
from .zero import _chunk_len, zero_adam_update


@jax.custom_vjp
def _mp_copy(x):
    """Megatron f-operator: identity forward, psum over 'mp' backward."""
    return x


def _mp_copy_fwd(x):
    return x, None


def _mp_copy_bwd(_, ct):
    return (jax.lax.psum(ct, "mp"),)


_mp_copy.defvjp(_mp_copy_fwd, _mp_copy_bwd)


def make_hybrid_step(mesh, vocab=64, d_model=32, d_ff=64, n_classes=4,
                     seq=8, micro_batch=1, lr=1e-2, seed=0):
    """Returns (step_fn, state); step_fn(state, x, y) -> (state, loss).

    x: [B, seq] int32 tokens (B divisible by dp*micro_batch), y: [B] labels.
    """
    dp = mesh.shape["dp"]
    pp = mesh.shape["pp"]
    mp = mesh.shape["mp"]
    assert d_ff % mp == 0 and n_classes % mp == 0
    rng = np.random.RandomState(seed)

    def init(*shape, scale=0.1):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)

    params = {
        "emb": init(vocab, d_model),
        "w1": init(pp, d_model, d_ff),      # sharded (pp, -, mp)
        "b1": jnp.zeros((pp, d_ff), jnp.float32),
        "w2": init(pp, d_ff, d_model),      # sharded (pp, mp, -)
        "b2": jnp.zeros((pp, d_model), jnp.float32),
        "head": init(d_model, n_classes),   # sharded (-, mp)
    }
    specs = {
        "emb": P(), "w1": P("pp", None, "mp"), "b1": P("pp", "mp"),
        "w2": P("pp", "mp", None), "b2": P("pp", None),
        "head": P(None, "mp"),
    }

    # ZeRO state: chunks sized by the LOCAL shard of each param
    def local_size(name):
        full = params[name].shape
        s = specs[name]
        n = 1
        for dim, ax in zip(full, tuple(s) + (None,) * (len(full) - len(s))):
            n *= dim // (mesh.shape[ax] if ax else 1)
        return n

    zstate = {"m": {}, "v": {}}
    zspecs = {"m": {}, "v": {}}
    for name in params:
        c = _chunk_len(local_size(name), dp)
        lead = tuple(ax for ax in (specs[name] or ()) if ax)
        shape = tuple(mesh.shape[a] for a in lead) + (dp, c)
        z = jnp.zeros(shape, jnp.float32)
        zstate["m"][name] = z
        zstate["v"][name] = z
        zspecs["m"][name] = P(*(lead + ("dp",)))
        zspecs["v"][name] = P(*(lead + ("dp",)))

    mb = micro_batch

    def stage_fn(sp, x):
        w1, b1, w2, b2 = sp
        h = jax.nn.gelu(jnp.einsum("mtd,df->mtf", x, w1) + b1)
        y = jnp.einsum("mtf,fd->mtd", h, w2)
        y = jax.lax.psum(y, "mp") + b2
        return x + y

    def step_inner(p, z, count, x, y):
        # local views: squeeze pp/mp-sharded leading dims
        w1 = jnp.squeeze(p["w1"], 0)
        b1 = jnp.squeeze(p["b1"], 0)
        w2 = jnp.squeeze(p["w2"], 0)
        b2 = jnp.squeeze(p["b2"], 0)
        pp_idx = jax.lax.axis_index("pp")

        Bl = x.shape[0]
        M = Bl // mb

        def loss_of(pt):
            e = _mp_copy(pt["emb"][x])              # [Bl, seq, d]
            xm = e.reshape(M, mb, seq, d_model)
            outs = pipeline_apply(
                stage_fn, (pt["w1"], pt["b1"], pt["w2"], pt["b2"]), xm,
                axis_name="pp", schedule="f-then-b")
            pooled = outs.reshape(Bl, seq, d_model).mean(axis=1)
            logits_l = pooled @ pt["head"]          # [Bl, n_classes/mp]
            logits = jax.lax.all_gather(logits_l, "mp", axis=0, tiled=False)
            logits = jnp.moveaxis(logits, 0, 1).reshape(Bl, n_classes)
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            local = jnp.mean(lse - ll)
            # loss lives on the last pp stage only (see module docstring)
            mask = (pp_idx == pp - 1).astype(local.dtype)
            return jax.lax.psum(local * mask, "pp")

        trainables = {"emb": p["emb"], "w1": w1, "b1": b1, "w2": w2,
                      "b2": b2, "head": p["head"]}
        loss, grads = jax.value_and_grad(loss_of)(trainables)

        # cross-axis grad totals (dp handled inside the ZeRO update); see
        # module docstring for why each psum is exactly right
        grads["emb"] = jax.lax.psum(grads["emb"], "pp")
        grads["head"] = jax.lax.psum(grads["head"], "pp")
        grads["b2"] = jax.lax.psum(grads["b2"], "mp")

        count = count + 1
        zlocal = jax.tree_util.tree_map(
            lambda a: a.reshape(a.shape[-1:]), z)
        new_p, new_z = zero_adam_update(
            trainables, grads, zlocal, count, "dp", dp, lr=lr)
        new_z = jax.tree_util.tree_map(
            lambda a, old: a.reshape(old.shape), new_z, z)

        out_params = {
            "emb": new_p["emb"],
            "w1": new_p["w1"][None], "b1": new_p["b1"][None],
            "w2": new_p["w2"][None], "b2": new_p["b2"][None],
            "head": new_p["head"],
        }
        loss_mean = jax.lax.psum(loss, "dp") / dp
        return out_params, new_z, count, loss_mean

    pspecs = {k: specs[k] for k in params}
    step_sm = shard_map(
        step_inner, mesh=mesh,
        in_specs=(pspecs, zspecs, P(), P("dp"), P("dp")),
        out_specs=(pspecs, zspecs, P(), P()),
        check_vma=False,
    )

    @jax.jit
    def step(state, x, y):
        p, z, count = state
        p2, z2, c2, loss = step_sm(p, z, count, x, y)
        return (p2, z2, c2), loss

    # initial placement
    params = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in params.items()}
    zstate = {kind: {k: jax.device_put(v, NamedSharding(mesh, zspecs[kind][k]))
                     for k, v in d.items()}
              for kind, d in zstate.items()}
    state = (params, zstate, jnp.zeros((), jnp.int32))
    return step, state
