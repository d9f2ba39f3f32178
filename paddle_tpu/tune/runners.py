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
gate — ISSUE 18), ``paged_attention_ragged`` / ``..._int8`` (query-row
and head padding floors for the unified serving dispatch, and the int8
fused-dequant epilogue choice) and ``quantized_matmul`` (block_m/n/k).

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
    import jax.numpy as jnp

    from ..ops.pallas_ops.quantized_matmul import quantized_matmul_kernel

    M, K, N = (bucket["block_m"], bucket["block_k"], bucket["block_n"])
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(M, K).astype(np.float32))
    w_q = jnp.asarray(rng.randint(-127, 128, (K, N)).astype(np.int8))
    w_s = jnp.asarray((rng.rand(N).astype(np.float32) * 0.1 + 1e-3))

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

    from ..ops.pallas_ops.flash_attention import flash_attention_bshd

    # both sweep axes tile the same sequence extent — run at the larger
    S = max(bucket["block_q"], bucket["block_k"])
    B, H, D = 1, 2, 64
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32) * 0.2)
    k = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32) * 0.2)
    v = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32) * 0.2)

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

    from ..ops.pallas_ops.contracts import FLASH_FWD
    from ..ops.pallas_ops.flash_attention import _flash_fwd_bhsd

    S = max(bucket["block_q"], bucket["block_k"])
    B, H, D = 1, 2, 64
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.2)
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.2)
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.2)
    g = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.2)
    mask = jnp.ones((B, 1, S), jnp.float32)
    seed = jnp.zeros((1,), jnp.int32)
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
    """A representative MIXED group batch for the unified-dispatch
    kernel: a steady-decode lane (1 live row), a prefill-chunk lane
    (5 rows at ascending positions) and a spec-verify-shaped lane
    (3 rows) — ragged exactly as the engine dispatches them."""
    import jax.numpy as jnp

    H, D = bucket["heads"], bucket["head_dim"]
    N, G, Qb, M = 9, 3, 5, 4
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(G, Qb, H, D).astype(np.float32) * 0.3)
    kf = rng.randn(N, page_size, H, D).astype(np.float32)
    vf = rng.randn(N, page_size, H, D).astype(np.float32)
    pt = np.zeros((G, M), np.int32)
    pt[0, :3] = [1, 2, 3]
    pt[1, :4] = [4, 5, 6, 7]
    pt[2, :2] = [8, 1]
    rl = np.zeros((G, Qb), np.int32)
    rl[0, 0] = page_size * 2 + 3                    # decode row
    rl[1, :] = np.arange(8, 8 + Qb)                 # prefill chunk
    rl[2, :3] = np.arange(3, 6)                     # spec-verify rows
    rl_j = jnp.asarray(rl)
    pt_j = jnp.asarray(pt)
    if not int8:
        return q, jnp.asarray(kf), jnp.asarray(vf), pt_j, rl_j, None, None
    ks = (np.abs(kf).max(axis=(1, 3)) / 127 + 1e-9).astype(np.float32)
    vs = (np.abs(vf).max(axis=(1, 3)) / 127 + 1e-9).astype(np.float32)
    kq = np.clip(np.round(kf / ks[:, None, :, None]), -127,
                 127).astype(np.int8)
    vq = np.clip(np.round(vf / vs[:, None, :, None]), -127,
                 127).astype(np.int8)
    return (q, jnp.asarray(kq), jnp.asarray(vq), pt_j, rl_j,
            jnp.asarray(ks), jnp.asarray(vs))


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
            a, b, d, e, f, head_align=c["head_align"],
            q_align=c["q_align"]))

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
            a, b, d, e, f, g, h, head_align=c["head_align"],
            q_align=c["q_align"],
            fused_dequant=bool(c["fused_dequant"])))

    def run(choice):
        return jit_for(choice)(q, kp, vp, pt, rl, ks, vs)

    return run
