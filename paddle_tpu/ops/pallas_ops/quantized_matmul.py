"""Weight-only int8 matmul Pallas kernel (TPU) — ``x @ dequant(w)``.

The serving decode loop is bytes-bound: each decode step streams every
weight matrix once for a handful of query rows, so halving weight bytes
is a direct throughput win.  This kernel keeps the weights RESIDENT AS INT8
— [K, N] s8 plus one fp32 dequant scale per output channel — and
dequantizes in-register after the DMA, immediately before the MXU
contraction.  HBM sees 1 byte/weight instead of 2 (bf16) or 4 (f32);
the MXU still computes in f32 (weight-only quantization: activations
stay in their native dtype, so no activation calibration is needed and
accuracy loss is bounded by the weight rounding alone).

Tiling: grid (M/bm, N/bn, K/bk) with K innermost; a VMEM f32 scratch
accumulates partial products across the K loop and the per-channel
scale is applied ONCE in the epilogue (cheaper than scaling every
partial product, and exact — scaling commutes with the K-sum).  Blocks
are padded to the MXU/ dtype tile floor (int8 wants (32, 128)).

Composability (Tensor Processing Primitives style): this is a plain
``[M, K] x [K, N] -> [M, N]`` primitive; the transformer core calls it
once per projection/MLP matmul.  CPU story mirrors flash/paged
attention: interpret mode runs the same kernel under JAX_PLATFORMS=cpu
when forced with ``PADDLE_TPU_FORCE_QMM=1``; the default CPU route is
the exact XLA reference.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .contracts import QUANTIZED_MATMUL, SUBLANE_FLOOR

__all__ = ["quantized_matmul", "quantized_matmul_kernel",
           "quantized_matmul_xla", "QMM_ROUTE_STATS"]

# default tiling from the declared KernelContract (contracts.py) — the
# single source of truth the pallas-contract lint checks and the
# autotuner swaps (paddle_tpu/tune)
_BLOCK_M = QUANTIZED_MATMUL.dim("block_m")
_BLOCK_N = QUANTIZED_MATMUL.dim("block_n")
_BLOCK_K = QUANTIZED_MATMUL.dim("block_k")
_F32_SUBLANE = SUBLANE_FLOOR["float32"]


def _resolved_blocks(M, K, N):
    """Tiling for this call: tuning-table hit (validate()-gated, keyed
    by the (M, K, N) shape bucket) -> contract default.  With no table
    installed this is a single None check — the historical configs run
    unchanged (docs/TUNING.md)."""
    from ...tune.runtime import lookup_dims

    tuned = lookup_dims(QUANTIZED_MATMUL,
                        {"block_m": M, "block_k": K, "block_n": N},
                        dtype="int8_weights")
    if tuned is None:
        return _BLOCK_M, _BLOCK_N, _BLOCK_K
    return (tuned.get("block_m", _BLOCK_M),
            tuned.get("block_n", _BLOCK_N),
            tuned.get("block_k", _BLOCK_K))

# trace-time routing telemetry, mirroring ops/attention.py ROUTE_STATS —
# the engine's stats() exposes this as the weight-quant hit counter
QMM_ROUTE_STATS = {"pallas": 0, "xla": 0}


def _interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_sc, *, k_steps):
    """Grid (M/bm, N/bn, K/bk), K innermost: accumulate s8-dequantized
    partial products in f32 VMEM scratch, apply the per-output-channel
    scale once at the last K step."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)

    acc_sc[:] += jax.lax.dot(
        x_ref[:].astype(jnp.float32), w_ref[:].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _write():
        o_ref[:] = (acc_sc[:] * s_ref[0].astype(jnp.float32)[None, :]
                    ).astype(o_ref.dtype)


def quantized_matmul_kernel(x, w_q, w_scale, *, interpret=None,
                            block_m=None, block_n=None,
                            block_k=None):
    """The Pallas kernel proper (interpret mode off-TPU unless forced).

    x        [M, K]  activations (any float dtype; accumulates in f32)
    w_q      [K, N]  int8 weights
    w_scale  [N]     fp32 per-output-channel dequant scales

    Returns [M, K] @ (w_q * w_scale[None, :]) as x.dtype.

    Block sizes resolve explicit argument > tuning-table hit > contract
    default (``None`` selects the lookup).
    """
    M, K = x.shape
    Kw, N = w_q.shape
    if Kw != K:
        raise ValueError(f"x [{M},{K}] vs w_q [{Kw},{N}]: K mismatch")
    if w_scale.shape != (N,):
        raise ValueError(f"w_scale must be [N={N}], got {w_scale.shape}")
    if block_m is None or block_n is None or block_k is None:
        t_m, t_n, t_k = _resolved_blocks(M, K, N)
        block_m = t_m if block_m is None else block_m
        block_n = t_n if block_n is None else block_n
        block_k = t_k if block_k is None else block_k

    # pad everything to the block grid; int8 tile floor is (32, 128) so
    # the weight blocks stay tileable on real TPU.  Decode/prefill M is
    # small (a lane bucket or a prefill chunk) — one M block suffices.
    bm = min(block_m, max(_F32_SUBLANE,
                          -(-M // _F32_SUBLANE) * _F32_SUBLANE))
    Mp = -(-M // bm) * bm
    Kp = -(-K // block_k) * block_k
    Np = -(-N // block_n) * block_n
    xf = x
    if (Mp, Kp) != (M, K):
        xf = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
    wq = w_q
    if (Kp, Np) != (K, N):
        wq = jnp.pad(w_q, ((0, Kp - K), (0, Np - N)))
    # scales ride as [1, Np] so the block keeps a lane-aligned last dim
    ws = w_scale.astype(jnp.float32)
    if Np != N:
        ws = jnp.pad(ws, (0, Np - N))
    ws = ws[None, :]

    k_steps = Kp // block_k
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, k_steps=k_steps),
        grid=(Mp // bm, Np // block_n, k_steps),
        in_specs=[
            pl.BlockSpec((bm, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, block_n), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret_mode() if interpret is None else interpret,
    )(xf, wq, ws)
    if (Mp, Np) != (M, N):
        out = out[:M, :N]
    return out


def quantized_matmul_xla(x, w_q, w_scale):
    """Exact XLA reference: dequantize then matmul in f32.  Same math
    as the kernel (f32 accumulate, scale folded per output channel) —
    the default CPU route."""
    acc = jax.lax.dot(x.astype(jnp.float32), w_q.astype(jnp.float32),
                      preferred_element_type=jnp.float32)
    return (acc * w_scale.astype(jnp.float32)[None, :]).astype(x.dtype)


def quantized_matmul(x, w_q, w_scale):
    """Routing entry (the serving transformer core calls this): Pallas
    kernel on TPU (or when PADDLE_TPU_FORCE_QMM=1 forces interpret mode
    for tests), exact XLA dequant-matmul reference elsewhere.

    Accepts [..., K] activations — leading dims are flattened around the
    2-D kernel.
    """
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1])) if x.ndim != 2 else x
    forced = os.environ.get("PADDLE_TPU_FORCE_QMM") == "1"
    if forced or jax.default_backend() == "tpu":
        QMM_ROUTE_STATS["pallas"] += 1
        out = quantized_matmul_kernel(x2, w_q, w_scale)
    else:
        QMM_ROUTE_STATS["xla"] += 1
        out = quantized_matmul_xla(x2, w_q, w_scale)
    if x.ndim != 2:
        out = out.reshape(lead + (w_q.shape[1],))
    return out
