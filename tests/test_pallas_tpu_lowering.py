"""Every Pallas kernel in CONTRACTS must get through the TPU compiler —
checked from the CPU, in seconds (ISSUE 21).

The kernels are NOT interpreted.  Where the installed libtpu can
describe a v5e topology without a chip (``jax.experimental.topologies``)
each program is COMPILED ahead of time for it: the Pallas->Mosaic
lowering (BlockSpec tiling rules, dot shapes Mosaic can express — where
all six paged kernels used to fail) and then Mosaic's own passes (vector
layouts, strided loads, relayouts) run exactly as they would on the
chip.  Where libtpu cannot, ``jax.export.export(..., platforms=["tpu"])``
still runs the lowering half on any host.

Numerics are the chip's to judge (chip_smoke.py's kernels phase); this
file says "the compiler accepts it".  A kernel a PR leaves refused would
be an ``xfail`` here naming the engine-construction error that guards
its option — this tree has none.
"""
import functools

import numpy as np
import pytest

import jax
import jax.export
import jax.numpy as jnp

from paddle_tpu.ops.pallas_ops import flash_attention as fa
from paddle_tpu.ops.pallas_ops import paged_attention as pa
from paddle_tpu.ops.pallas_ops import quantized_matmul as qm
from paddle_tpu.ops.pallas_ops.contracts import CONTRACTS

SHAPES = ((12, 64), (16, 128))       # GPT-2-small's, and the R1+ width
PAGE = 16


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _cases(H, D):
    """contract name -> [(label, fn, arg specs)] — every kernel form the
    contract governs, at (heads, head_dim)."""
    N, G, Qb, M = 40, 3, 16, 8
    q4, q3 = _sds((G, Qb, H, D)), _sds((G, H, D))
    pool, pool8 = _sds((N, PAGE, H, D)), _sds((N, PAGE, H, D), jnp.int8)
    sc = _sds((N, H))
    pt, rl, sl = (_sds((G, M), jnp.int32), _sds((G, Qb), jnp.int32),
                  _sds((G,), jnp.int32))
    ragged = functools.partial(pa.ragged_paged_attention_kernel,
                               interpret=False)
    decode = functools.partial(pa.paged_attention_kernel, interpret=False)
    stats = functools.partial(pa.ragged_paged_attention_stats_kernel,
                              interpret=False)

    B, S = 1, 256
    scale = 1.0 / float(np.sqrt(D))
    t = _sds((B, H, S, D))
    stat = _sds((B * H, S, 1))
    mask, seed = _sds((B, 1, S)), _sds((1,), jnp.int32)
    flash_kw = dict(scale=scale, causal=True, dropout_p=0.0, block_q=128,
                    block_k=128)
    K_, N_ = H * D, 3 * H * D
    return {
        "flash_attention_fwd": [
            ("fwd", lambda q, k, v, m, s: fa._flash_fwd_bhsd(
                q, k, v, m, s, **flash_kw), (t, t, t, mask, seed))],
        "flash_attention_bwd_dkv": [
            ("dkv", lambda q, k, v, g, lse, dl, m, s: fa._flash_dkv_bhsd(
                q, k, v, g, lse, dl, m, s, **flash_kw),
             (t, t, t, t, stat, stat, mask, seed))],
        "flash_attention_bwd_dq": [
            ("dq", lambda q, k, v, g, lse, dl, m, s: fa._flash_dq_bhsd(
                q, k, v, g, lse, dl, m, s, **flash_kw),
             (t, t, t, t, stat, stat, mask, seed))],
        "paged_attention_ragged": [
            ("ragged", ragged, (q4, pool, pool, pt, rl)),
            ("decode (Q=1)", decode, (q3, pool, pool, pt, sl))],
        "paged_attention_ragged_int8": [
            ("ragged int8", ragged, (q4, pool8, pool8, pt, rl, sc, sc)),
            ("decode int8 (Q=1)", decode,
             (q3, pool8, pool8, pt, sl, sc, sc))],
        "paged_attention_ragged_stats": [
            ("stats", stats, (q4, pool, pool, pt, rl, pt)),
            ("stats int8", stats, (q4, pool8, pool8, pt, rl, pt, sc, sc))],
        "quantized_matmul": [
            ("qmm", functools.partial(qm.quantized_matmul_kernel,
                                      interpret=False),
             (_sds((64, K_)), _sds((K_, N_), jnp.int8), _sds((N_,))))],
    }


@pytest.fixture(autouse=True)
def _never_interpreted(monkeypatch):
    # the flash wrappers take no interpret= argument: they decide from
    # the backend, which is the CPU here
    monkeypatch.setattr(fa, "_interpret_mode", lambda: False)


def test_every_contract_has_a_case():
    assert set(_cases(*SHAPES[0])) == set(CONTRACTS)


@pytest.fixture(scope="module")
def v5e_device():
    """A v5e device description to compile FOR, without a chip — or None
    where this libtpu cannot give one (the test then stops at lowering)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception:  # noqa: BLE001 — no libtpu / no topology API
        return None
    return topo.devices[0]


@pytest.mark.parametrize("H,D", SHAPES)
@pytest.mark.parametrize("name", sorted(CONTRACTS))
def test_tpu_compiler_accepts(name, H, D, v5e_device):
    from jax.sharding import SingleDeviceSharding

    for label, fn, specs in _cases(H, D)[name]:
        if v5e_device is None:
            exported = jax.export.export(jax.jit(fn),
                                         platforms=["tpu"])(*specs)
            assert "tpu_custom_call" in exported.mlir_module(), label
            continue
        on_chip = SingleDeviceSharding(v5e_device)
        args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on_chip)
                for s in specs]
        # lowering + Mosaic: raises with the compiler's own message
        jax.jit(fn).lower(*args).compile()
