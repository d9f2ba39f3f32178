"""Per-kernel measurement runners for the sweep harness (ISSUE 14).

A *runner* answers "run THIS contract's kernel at THIS shape bucket
with THAT candidate config and hand me the output": it builds
deterministic representative inputs once, then returns a callable
``run(choice) -> jax array``.  Every candidate executes under a
``profiled_jit`` named ``tune.<kernel>`` so its compile time and XLA
cost analysis land in the process-wide ``cost_registry`` next to the
serving programs' (docs/OBSERVABILITY.md).

Runners exist for the kernels with a runtime-swappable config:
``flash_attention_fwd`` (block_q/block_k through the wrapper),
``flash_attention_bwd_dkv`` / ``..._bwd_dq`` (the grad-path pair:
forward stats are precomputed ONCE at the default blocks, each
candidate re-tiles only the backward kernel under the sweep's parity
gate — ISSUE 18), ``paged_attention_ragged`` / ``..._int8`` (the
query-row padding floor and the pages a grid step covers for the unified
serving dispatch, and the int8 fused-dequant epilogue choice) and
``quantized_matmul`` (block_m/n/k).

Kernel modules are imported lazily inside each runner so this package
never participates in an import cycle with ``ops.pallas_ops``.
"""
from __future__ import annotations

from typing import Callable, Dict, Mapping

import numpy as np

from ..ops.pallas_ops.contracts import KernelContract

__all__ = ["runner_for", "register_runner", "RUNNERS"]

# contract name -> runner factory (contract, bucket, dtype) -> run(choice)
RUNNERS: Dict[str, Callable] = {}


def register_runner(name: str):
    def deco(fn):
        RUNNERS[name] = fn
        return fn
    return deco


def runner_for(name: str):
    try:
        return RUNNERS[name]
    except KeyError:
        raise ValueError(
            f"no sweep runner registered for kernel {name!r} — "
            f"runnable kernels: {sorted(RUNNERS)}") from None


def _profiled(name: str, fn):
    from ..profiler.jit_cost import profiled_jit

    return profiled_jit(f"tune.{name}", fn)


def _per_choice(name: str, build):
    """Memoize ONE ProfiledJit per candidate choice: the first call
    compiles (attributed to ``tune.<kernel>``), the timed min-of-N
    repeats hit the compiled executable — the sweep measures kernel
    time, not retrace time."""
    jits: Dict[tuple, object] = {}

    def get(choice):
        key = tuple(sorted(choice.items()))
        fn = jits.get(key)
        if fn is None:
            fn = jits[key] = _profiled(name, build(dict(choice)))
        return fn

    return get


@register_runner("quantized_matmul")
def _qmm_runner(contract: KernelContract, bucket: Mapping[str, int],
                dtype: str):
    from ..ops.pallas_ops.cases import qmm_inputs
    from ..ops.pallas_ops.quantized_matmul import quantized_matmul_kernel

    x, w_q, w_s = qmm_inputs(bucket["block_m"], bucket["block_k"],
                             bucket["block_n"])

    jit_for = _per_choice(
        contract.name,
        lambda c: lambda a, b, s: quantized_matmul_kernel(
            a, b, s, block_m=c["block_m"], block_n=c["block_n"],
            block_k=c["block_k"]))

    def run(choice):
        return jit_for(choice)(x, w_q, w_s)

    return run


@register_runner("flash_attention_fwd")
def _flash_runner(contract: KernelContract, bucket: Mapping[str, int],
                  dtype: str):
    import jax.numpy as jnp

    from ..ops.pallas_ops.cases import flash_inputs
    from ..ops.pallas_ops.flash_attention import flash_attention_bshd

    # both sweep axes tile the same sequence extent — run at the larger
    S = max(bucket["block_q"], bucket["block_k"])
    (q, k, v, _), _, _ = flash_inputs(1, 2, S, 64)
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))   # wrapper: BSHD

    jit_for = _per_choice(
        contract.name,
        lambda c: lambda a, b, d: flash_attention_bshd(
            a, b, d, causal=True, block_q=c["block_q"],
            block_k=c["block_k"]))

    def run(choice):
        return jit_for(choice)(q, k, v)

    return run


def _flash_bwd_inputs(bucket: Mapping[str, int]):
    """Deterministic (q, k, v, g, lse, delta, mask, seed, scale) for the
    grad-path runners: ONE forward at the contract-default blocks
    yields the global per-row stats every backward candidate consumes —
    the sweep re-tiles only the backward kernel, so parity failures are
    attributable to the candidate blocks alone."""
    import jax.numpy as jnp

    from ..ops.pallas_ops.cases import flash_inputs
    from ..ops.pallas_ops.contracts import FLASH_FWD
    from ..ops.pallas_ops.flash_attention import _flash_fwd_bhsd

    S = max(bucket["block_q"], bucket["block_k"])
    B, H, D = 1, 2, 64
    (q, k, v, g), mask, seed = flash_inputs(B, H, S, D)
    scale = 1.0 / float(np.sqrt(D))
    bq = min(FLASH_FWD.dim("block_q"), S)
    bk = min(FLASH_FWD.dim("block_k"), S)
    out, lse = _flash_fwd_bhsd(q, k, v, mask, seed, scale, True, 0.0,
                               bq, bk)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(B * H, S, 1)
    return q, k, v, g, lse, delta, mask, seed, scale


@register_runner("flash_attention_bwd_dkv")
def _flash_dkv_runner(contract: KernelContract,
                      bucket: Mapping[str, int], dtype: str):
    import jax.numpy as jnp

    from ..ops.pallas_ops.flash_attention import _flash_dkv_bhsd

    q, k, v, g, lse, delta, mask, seed, scale = _flash_bwd_inputs(bucket)

    jit_for = _per_choice(
        contract.name,
        lambda c: lambda *a: jnp.stack(_flash_dkv_bhsd(
            *a, scale=scale, causal=True, dropout_p=0.0,
            block_q=c["block_q"], block_k=c["block_k"])))

    def run(choice):
        return jit_for(choice)(q, k, v, g, lse, delta, mask, seed)

    return run


@register_runner("flash_attention_bwd_dq")
def _flash_dq_runner(contract: KernelContract,
                     bucket: Mapping[str, int], dtype: str):
    from ..ops.pallas_ops.flash_attention import _flash_dq_bhsd

    q, k, v, g, lse, delta, mask, seed, scale = _flash_bwd_inputs(bucket)

    jit_for = _per_choice(
        contract.name,
        lambda c: lambda *a: _flash_dq_bhsd(
            *a, scale=scale, causal=True, dropout_p=0.0,
            block_q=c["block_q"], block_k=c["block_k"]))

    def run(choice):
        return jit_for(choice)(q, k, v, g, lse, delta, mask, seed)

    return run


def _ragged_inputs(bucket: Mapping[str, int], page_size: int,
                   int8: bool):
    """The case table's mixed three-lane batch (decode lane, prefill
    chunk, spec-verify-shaped lane) at the sweep's small extents."""
    from ..ops.pallas_ops.cases import paged_inputs

    q, kp, vp, pt, rl, _, ks, vs = paged_inputs(
        bucket["heads"], bucket["head_dim"], page_size, int8=int8,
        pages=9, rows=5, table=4)
    return q, kp, vp, pt, rl, ks, vs


@register_runner("paged_attention_ragged")
def _ragged_runner(contract: KernelContract, bucket: Mapping[str, int],
                   dtype: str):
    from ..ops.pallas_ops.paged_attention import \
        ragged_paged_attention_kernel

    q, kp, vp, pt, rl, _, _ = _ragged_inputs(
        bucket, contract.dim("page_size"), int8=False)

    jit_for = _per_choice(
        contract.name,
        lambda c: lambda a, b, d, e, f: ragged_paged_attention_kernel(
            a, b, d, e, f, q_align=c["q_align"],
            pages_per_step=c["pages_per_step"]))

    def run(choice):
        return jit_for(choice)(q, kp, vp, pt, rl)

    return run


@register_runner("paged_attention_ragged_int8")
def _ragged_int8_runner(contract: KernelContract,
                        bucket: Mapping[str, int], dtype: str):
    from ..ops.pallas_ops.paged_attention import \
        ragged_paged_attention_kernel

    q, kp, vp, pt, rl, ks, vs = _ragged_inputs(
        bucket, contract.dim("page_size"), int8=True)

    jit_for = _per_choice(
        contract.name,
        lambda c: lambda a, b, d, e, f, g, h: ragged_paged_attention_kernel(
            a, b, d, e, f, g, h, q_align=c["q_align"],
            pages_per_step=c["pages_per_step"],
            fused_dequant=bool(c["fused_dequant"])))

    def run(choice):
        return jit_for(choice)(q, kp, vp, pt, rl, ks, vs)

    return run
