"""One chip's share of a sparse-expert layer (expert parallelism without
its exchange).

The layer is told which experts it holds — `start` and the leading size of
the expert weights, out of the router's published width.  It routes every
token over ALL published experts (sigmoid scores in float32, the largest
`k` of score + correction bias, weights renormalised over the chosen and
scaled), and computes w_e E_e(x) only for the chosen experts it holds.
What the absent experts would have added is left out: nothing stands in
for the other chips or for their all-to-all, and that partial sum is the
op's result.

No token is dropped whatever the imbalance: the (token, slot) assignments
are sorted by held expert — absent ones last — and the held experts'
SwiGLU runs as three grouped matmuls (`lax.ragged_dot`) over the sorted
rows, each expert multiplying only the rows routed to it.  The sort is a
permutation of all T*k assignments, so both directions of the two row
moves are gathers (a gather's transpose here is the gather by the inverse
permutation, never a scatter).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ._helpers import to_tensor_like
from .dispatch import apply

_HI = jax.lax.Precision.HIGHEST


def route(x, router_w, bias, k, scale, renormalize=True):
    """x [T, d] -> (expert ids [T, k] int32, weights [T, k] float32) over
    the router's whole width; float32 whatever x's dtype."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.matmul(x.astype(f32), router_w.astype(f32),
                                  precision=_HI))
    _, idx = jax.lax.top_k(s + bias.astype(f32), k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w * scale


@jax.custom_vjp
def _permute_rows(a, perm, inv):
    """a[perm] for a permutation `perm` whose inverse is `inv`."""
    return a[perm]


_permute_rows.defvjp(
    lambda a, perm, inv: (a[perm], (perm, inv)),
    lambda res, g: (g[res[1]], None, None))


@jax.custom_vjp
def _rows_of_tokens(x, perm, inv):
    """x[perm // k] where `perm` permutes the T*k (token, slot) pairs."""
    return x[perm // (perm.shape[0] // x.shape[0])]


def _rows_fwd(x, perm, inv):
    return _rows_of_tokens(x, perm, inv), (inv, x.shape[0])


def _rows_bwd(res, g):
    inv, T = res
    return (jnp.sum(g[inv].reshape(T, -1, g.shape[-1]).astype(jnp.float32),
                    axis=1).astype(g.dtype), None, None)


_rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


def expert_share(x, router_w, bias, w_gate, w_up, w_down, *, start, k, scale,
                 renormalize=True, compute_dtype=None):
    """x [T, d]; router_w [d, E_published]; bias [E_published]; w_gate,
    w_up [held, d, f]; w_down [held, f, d]: experts start .. start + held
    of the published E live here.

    Returns (y [T, d] — the held experts' part of the routed sum — and
    counts [held + 1] float32: assignments that landed on each held
    expert, then those that went to absent ones)."""
    T, d = x.shape
    held = w_gate.shape[0]
    cd = compute_dtype or x.dtype
    idx, w = route(x, router_w, bias, k, scale, renormalize)
    local = idx - start
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held).reshape(-1)         # [T * k]
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32), unique_indices=True)
    counts = jnp.sum(jax.nn.one_hot(group, held + 1, dtype=jnp.int32),
                     axis=0)
    sizes = counts[:held]
    rows = _rows_of_tokens(x.astype(cd), order, inv)         # [T * k, d]
    # rows past the held groups belong to no expert here.  The grouped
    # matmul leaves them unwritten on the chip, in its result and in the
    # gradient it hands back for its rows alike, so both sides of every
    # call are selected (never multiplied) to zero there.
    routed = (jnp.arange(T * k) < jnp.sum(sizes))[:, None]

    def grouped(lhs, rhs):
        out = jax.lax.ragged_dot(jnp.where(routed, lhs, 0), rhs.astype(cd),
                                 sizes)
        return jnp.where(routed, out, 0)

    gate, up = grouped(rows, w_gate), grouped(rows, w_up)
    out = grouped(jax.nn.silu(gate) * up, w_down)
    per_slot = _permute_rows(out, inv, order).reshape(T, k, d)
    y = jnp.einsum("tkd,tk->td", per_slot.astype(jnp.float32),
                   jnp.where(here, w, 0.0))
    return y.astype(x.dtype), counts.astype(jnp.float32)


def sparse_expert_share(x, router_weight, correction_bias, w_gate, w_up,
                        w_down, start, k, scale, renormalize=True, name=None):
    """Tensor entry for `expert_share`: x [..., d] -> (y [..., d], counts
    [held + 1]).  Under `amp.auto_cast` the expert matmuls take the
    autocast dtype like every matmul; the router stays float32."""
    from .dispatch import _amp_should_cast

    cast = _amp_should_cast("matmul_v2")

    def f(x, rw, b, wg, wu, wd):
        y, counts = expert_share(
            x.reshape(-1, x.shape[-1]), rw, b, wg, wu, wd, start=start, k=k,
            scale=scale, renormalize=renormalize,
            compute_dtype=cast or x.dtype)
        return y.reshape(x.shape), counts

    return apply("sparse_expert_share", f, *(to_tensor_like(a) for a in (
        x, router_weight, correction_bias, w_gate, w_up, w_down)))
