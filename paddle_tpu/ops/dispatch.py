"""Eager op dispatcher.

Reference analog: imperative::Tracer::TraceOp
(/root/reference/paddle/fluid/imperative/tracer.cc:132) + the generated
``core.ops`` fast path (pybind/op_function_generator.cc:529).  On TPU there is
no per-op kernel registry to dispatch into: every op *is* a jax function, and
XLA owns kernel choice.  ``apply`` runs the function eagerly and, when grad is
required, records a GradNode holding the op's ``jax.vjp`` closure
(tracer.cc:205 CreateGradOpNode analog).

FLAGS_check_nan_inf reproduces the reference's per-op NaN/Inf sweep
(details/nan_inf_utils_detail.cc) on eager outputs.
"""
from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..autograd.tape import Edge, GradNode, is_grad_enabled, no_grad
from ..framework import dtype as _dtype_mod
from ..framework.flags import flag_value


def _tensor_cls():
    from ..tensor import Tensor

    return Tensor


def _amp_should_cast(name):
    """AMP autocast hook (tracer.cc:160 AutoCastInputs analog)."""
    try:
        from ..amp.auto_cast import should_cast
    except ImportError:
        return None
    return should_cast(name)


def _recording_program():
    """Static-graph recording hook: the active Program being built, if any
    (static/program.py — TraceOp's OpDesc-append analog, tracer.cc:205)."""
    try:
        from ..static.program import _active_recorder
    except ImportError:
        return None
    return _active_recorder()


def wrap(value, stop_gradient=True, node=None, index=0):
    Tensor = _tensor_cls()
    t = Tensor(value, stop_gradient=stop_gradient)
    if node is not None:
        t._grad_node = node
        t._out_index = index
        t.stop_gradient = False
    return t


def _is_diff_dtype(arr) -> bool:
    return jnp.issubdtype(arr.dtype, jnp.floating) or jnp.issubdtype(
        arr.dtype, jnp.complexfloating
    )


def _check_nan_inf(name, flat_outs):
    for i, o in enumerate(flat_outs):
        if hasattr(o, "dtype") and jnp.issubdtype(o.dtype, jnp.floating):
            if not bool(jnp.all(jnp.isfinite(o))):
                raise FloatingPointError(
                    f"Operator {name} output #{i} contains NaN or Inf "
                    "(FLAGS_check_nan_inf is set)"
                )


# fns that executed fine but failed jax.vjp once — skip re-attempting the
# linearization (and re-warning) on every subsequent call
# Op NAMES that have hit a structural can't-linearize error at least once —
# used ONLY to warn once per name (a name key, because most call sites build
# a fresh closure per call, so identity keys would never memoize and grow
# without bound).  NOT a dispatch cache: linearization failure can be
# context-dependent (e.g. only while a backward is itself being recorded),
# so every call re-attempts jax.vjp rather than permanently cutting
# gradients for the op name.
_non_linearizable: set = set()


def _is_non_linearizable_error(e) -> bool:
    """True only for jax's structural can't-differentiate errors — e.g.
    forward-mode over a custom_vjp (raw Pallas backward being re-recorded
    for double grad / static replay). Shape bugs, dtype errors, or failures
    inside a user VJP must keep raising loudly."""
    msg = str(e)
    if ("does not support reverse-mode autodiff" in msg
            or "Linearization failed" in msg
            or "does not support JVP" in msg
            or "do not support JVP" in msg):
        # jax's structural can't-differentiate errors: linearize over a
        # primitive with no transpose rule (raw Pallas call inside a
        # recorded backward), pure_callback ("Pure callbacks do not support
        # JVP"), pallas_call with a mesh ("does not support JVP")
        return True
    if isinstance(e, NotImplementedError) and "jvp" in msg.lower():
        return True
    return isinstance(e, TypeError) and (
        "custom_vjp" in msg or "custom_gradient" in msg
        or "jvp" in msg.lower())


def apply(name, fn, *args, n_outputs=None, **kwargs):
    """Run ``fn(*arrays, **kwargs)`` eagerly; record vjp if needed.

    ``args`` may mix Tensors and raw values; ``kwargs`` are static attrs.
    Returns Tensor or tuple of Tensors mirroring fn's output structure
    (only flat tuples/lists of arrays or a single array are supported).
    """
    Tensor = _tensor_cls()
    cast_to = _amp_should_cast(name)
    arrays = []
    tracked_idx = []
    for i, a in enumerate(args):
        if isinstance(a, Tensor):
            v = a._value
            if cast_to is not None and jnp.issubdtype(v.dtype, jnp.floating) \
                    and v.dtype != cast_to:
                v = v.astype(cast_to)
            arrays.append(v)
            if a._tracked and _is_diff_dtype(a._value):
                tracked_idx.append(i)
        else:
            arrays.append(a)

    record = is_grad_enabled() and bool(tracked_idx)
    recorder = _recording_program()

    def _finish_nograd(out):
        if flag_value("check_nan_inf"):
            flat, _ = jax.tree_util.tree_flatten(out)
            _check_nan_inf(name, flat)
        wrapped = _wrap_outputs(out, stop_gradient=True)
        if recorder is not None:
            recorder.add_record(name, fn, args, kwargs, wrapped, cast_to)
        return wrapped

    if not record:
        return _finish_nograd(fn(*arrays, **kwargs))

    def closed(*diff_vals):
        call = list(arrays)
        for i, v in zip(tracked_idx, diff_vals):
            call[i] = v
        return fn(*call, **kwargs)

    primals = [arrays[i] for i in tracked_idx]
    try:
        out, vjp_fn = jax.vjp(closed, *primals)
    except Exception as e:
        # Some ops execute fine but cannot be linearized (e.g. a custom op
        # whose BACKWARD rule contains a raw Pallas kernel, reached when the
        # backward itself is being recorded for double grad / static replay).
        # Degrade ONLY for that structural case; anything else (shape bug in
        # a user VJP, dtype mismatch, transient failure) must raise rather
        # than silently cut gradients through part of the model.
        if not _is_non_linearizable_error(e):
            raise RuntimeError(f"[operator < {name} >] {e}") from e
        try:
            out = fn(*arrays, **kwargs)
        except Exception:
            raise RuntimeError(f"[operator < {name} >] {e}") from e
        import warnings

        if name not in _non_linearizable:
            _non_linearizable.add(name)
            warnings.warn(
                f"operator < {name} > executes but cannot be linearized "
                f"({type(e).__name__}); gradients through it are cut. "
                "Register a custom vjp if it must be differentiable here.",
                stacklevel=2)
        return _finish_nograd(out)
    if flag_value("check_nan_inf"):
        flat, _ = jax.tree_util.tree_flatten(out)
        _check_nan_inf(name, flat)

    flat_out, treedef = jax.tree_util.tree_flatten(out)
    out_avals = [(o.shape, o.dtype) for o in flat_out]
    edges = [Edge(args[i]) for i in tracked_idx]
    node = GradNode(name, vjp_fn, edges, out_avals, treedef, fwd_fn=closed,
                    op_fn=fn, op_kwargs=dict(kwargs), op_args=list(args),
                    tracked_idx=list(tracked_idx), cast_to=cast_to)
    wrapped = [wrap(o, node=node, index=i) for i, o in enumerate(flat_out)]
    result = (wrapped[0] if _is_single(out)
              else jax.tree_util.tree_unflatten(treedef, wrapped))
    if recorder is not None:
        recorder.add_record(name, fn, args, kwargs, result, cast_to)
    return result


def _is_single(out):
    return not isinstance(out, (tuple, list))


def _wrap_outputs(out, stop_gradient=True):
    Tensor = _tensor_cls()
    if _is_single(out):
        return Tensor(out, stop_gradient=stop_gradient)
    flat, treedef = jax.tree_util.tree_flatten(out)
    return jax.tree_util.tree_unflatten(
        treedef, [Tensor(o, stop_gradient=stop_gradient) for o in flat]
    )


def apply_vjp(node: GradNode, flat_cts: List, create_graph: bool):
    """Run a node's vjp closure on cotangent Tensors.

    With ``create_graph`` the vjp call itself is dispatched through ``apply``
    so the backward computation is recorded (double grad —
    partial_grad_engine.cc analog); otherwise it runs unrecorded.
    """
    Tensor = _tensor_cls()
    treedef = node.out_treedef
    vjp_fn = node.vjp_fn
    n_in = len(node.edges)

    if create_graph and node.op_fn is not None:
        # re-derive the vjp as a function of ALL tensor inputs (tracked AND
        # non-tracked — a feed placeholder is stop_gradient yet its VALUE is
        # a primal of the vjp) plus the cotangents, so the recorded backward
        # depends on live values, not build-time constants.  Double grad
        # (partial_grad_engine.cc analog) and static-graph replay both need
        # this.
        op_fn, op_kwargs = node.op_fn, node.op_kwargs
        op_args, tracked = node.op_args, node.tracked_idx
        cast_to = node.cast_to
        tensor_pos = [i for i, a in enumerate(op_args)
                      if isinstance(a, Tensor)]

        def h(*vals):
            n_t = len(tensor_pos)
            tensor_vals = vals[:n_t]
            cts = vals[n_t:]
            call = list(op_args)
            for pos, v in zip(tensor_pos, tensor_vals):
                if cast_to is not None and hasattr(v, "dtype") and \
                        jnp.issubdtype(v.dtype, jnp.floating) and \
                        v.dtype != cast_to:
                    v = v.astype(cast_to)
                call[pos] = v

            def fwd_tr(*tr_vals):
                c = list(call)
                for i, v in zip(tracked, tr_vals):
                    c[i] = v
                return op_fn(*c, **op_kwargs)

            _, inner_vjp = jax.vjp(fwd_tr, *[call[i] for i in tracked])
            ct_struct = jax.tree_util.tree_unflatten(treedef, list(cts))
            return tuple(inner_vjp(ct_struct))

        input_tensors = [op_args[i] for i in tensor_pos]
        out = apply(f"grad[{node.name}]", h, *input_tensors, *flat_cts)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        return list(out)

    def run(*ct_arrays):
        ct_struct = jax.tree_util.tree_unflatten(treedef, list(ct_arrays))
        res = vjp_fn(ct_struct)
        return tuple(res)

    from ..sparse_grad import IndexedSlices

    with no_grad():
        ct_arrays = [c._value for c in flat_cts]
        res = run(*ct_arrays)
        return [r if isinstance(r, IndexedSlices)
                else Tensor(r, stop_gradient=True) for r in res]


def accumulate_grad(a, b, create_graph: bool):
    """Gradient accumulation (gradient_accumulator.cc analog).  Handles
    row-sparse IndexedSlices grads: sparse+sparse concatenates (merged
    lazily at update time); sparse+dense densifies."""
    from ..sparse_grad import IndexedSlices

    Tensor = _tensor_cls()
    a_sp = isinstance(a, IndexedSlices)
    b_sp = isinstance(b, IndexedSlices)
    if a_sp or b_sp:
        if a_sp and b_sp:
            return a.add(b)
        dense = a.to_dense() if a_sp else a._value
        other = b.to_dense() if b_sp else b._value
        return Tensor(jnp.add(dense, other), stop_gradient=True)
    if create_graph:
        return apply("grad_accumulate", jnp.add, a, b)
    with no_grad():
        return Tensor(jnp.add(a._value, b._value), stop_gradient=True)
