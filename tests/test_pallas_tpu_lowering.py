"""Every Pallas kernel in CONTRACTS must get through the TPU compiler —
checked from the CPU, in seconds (ISSUE 21).

The kernels are NOT interpreted.  The installed libtpu describes a v5e
topology without a chip (``jax.experimental.topologies``), so each
program is COMPILED ahead of time for it: the Pallas->Mosaic lowering
(BlockSpec tiling rules, dot shapes Mosaic can express — where all six
paged kernels used to fail) and then Mosaic's own passes (vector
layouts, strided loads, relayouts) run exactly as they would on the
chip.  ``test_tpu_lowering_accepts`` is the weaker half on its own
(``jax.export`` for the TPU platform: lowering, no Mosaic), under its own
name: a libtpu that loses the topology API fails the Mosaic tests
visibly instead of downgrading them to it.

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
from paddle_tpu.ops.pallas_ops.cases import MIXER_CONTRACTS, kernel_cases
from paddle_tpu.ops.pallas_ops.contracts import CONTRACTS

SHAPES = ((12, 64), (16, 128))       # GPT-2-small's, and the R1+ width


@functools.lru_cache(maxsize=None)
def _cases(H, D):
    """contract name -> [(label, kernel, arg specs)] from the one case
    table (ops/pallas_ops/cases.py — it refuses to build while a
    contract has no case)."""
    by_contract = {}
    for case in kernel_cases(H, D):
        specs = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                      for a in case.args)
        by_contract.setdefault(case.contract, []).append(
            (case.label, case.kernel, specs))
    return by_contract


@pytest.fixture(autouse=True)
def _never_interpreted(monkeypatch):
    # the flash wrappers take no interpret= argument: they decide from
    # the backend, which is the CPU here
    monkeypatch.setattr(fa, "_interpret_mode", lambda: False)


@pytest.fixture(scope="module")
def v5e_topology():
    """A v5e 2x2 host to compile FOR, without a chip.  Not caught: where
    libtpu cannot describe one, the Mosaic tests error with its message."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")


def test_tpu_lowering_accepts():
    """The lowering half alone, on any host: every form lowers to a
    Mosaic custom call for the TPU platform (one shape — the Mosaic
    tests below repeat this half at both)."""
    by_contract = _cases(*SHAPES[0])
    assert set(by_contract) == set(CONTRACTS) - set(MIXER_CONTRACTS)
    for forms in by_contract.values():
        for label, fn, specs in forms:
            exported = jax.export.export(jax.jit(fn),
                                         platforms=["tpu"])(*specs)
            assert "tpu_custom_call" in exported.mlir_module(), label


@pytest.mark.parametrize("H,D", SHAPES)
@pytest.mark.parametrize("name", sorted(set(CONTRACTS)
                                         - set(MIXER_CONTRACTS)))
def test_mosaic_compiles_for_v5e(name, H, D, v5e_topology):
    from jax.sharding import SingleDeviceSharding

    on_chip = SingleDeviceSharding(v5e_topology.devices[0])
    for label, fn, specs in _cases(H, D)[name]:
        args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on_chip)
                for s in specs]
        # lowering + Mosaic: raises with the compiler's own message
        jax.jit(fn).lower(*args).compile()


def test_flash_partitions_over_a_v5e_mesh(v5e_topology):
    """XLA's SPMD partitioner refuses a Mosaic call it is not told how to
    split; under `partitioned_over` (where make_sharded_train_step
    traces) the flash kernels run inside shard_map and the dp2 x mp2
    program — forward and both backward kernels — compiles for the 2x2
    host at the smoke's training shape."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(v5e_topology.devices).reshape(2, 2), ("dp", "mp"))
    x = jax.ShapeDtypeStruct(
        (4, 2048, 12, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", None, "mp", None)))

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention_bshd(q, k, v, causal=True)
                       .astype(jnp.float32))

    grads = jax.value_and_grad(loss, argnums=(0, 1, 2))

    def step(q, k, v):
        with fa.partitioned_over(mesh, ("dp",)):
            return grads(q, k, v)

    compiled = jax.jit(step).lower(x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        jax.jit(grads).lower(x, x, x).compile()


# rows 64: the mixed step, whose kernel branches on a lane's live-row
# extent (rows 0-7 | all 64); rows 8: the program with no second row block
@pytest.mark.parametrize("rows", [64, 8], ids=["rows64", "rows8"])
@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["native", "int8"])
@pytest.mark.parametrize("H,D", SHAPES)
def test_serve_step_never_relayouts_a_pool_on_v5e(H, D, kv_dtype, rows,
                                                  v5e_topology, monkeypatch):
    """The whole unified serve step, compiled for the v5e: the pools are
    stored in the layout the ragged kernel reads (ISSUE 26), so the
    OPTIMISED program holds no pad, copy or transpose of a whole pool,
    no temporary of a pool's size, and updates every pool in place —
    with each pool handed to the kernel once per page of a grid step
    (ISSUE 29: the same buffer eight times, no copy).  At (12, 64) the
    per-head [N, P, 12, 64] layout cost two relayouts and a pad per pool
    per step — 55% of the step on the chip."""
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu
    from paddle_tpu.ops.pallas_ops import paged_attention as pa
    from paddle_tpu.ops.pallas_ops import paged_kv_write as kw
    from paddle_tpu.serving.engine import (aliased_arguments,
                                           whole_pool_relayouts)
    from paddle_tpu.text.generation import make_gpt_paged_ragged_step
    from paddle_tpu.text.models import GPTModel

    # the kernel routes, compiled and not interpreted, as on the chip:
    # the ragged kernel reads the pools, the write kernel (native pools)
    # writes them
    monkeypatch.setenv("PADDLE_TPU_FORCE_PAGED", "1")
    monkeypatch.setattr(pa, "_interpret_mode", lambda: False)
    monkeypatch.setattr(kw, "_interpret_mode", lambda: False)
    paddle_tpu.seed(0)
    model = GPTModel(vocab_size=256, hidden_size=H * D, num_layers=1,
                     num_heads=H, ffn_size=256, max_seq_len=1024,
                     dropout=0.0)
    model.eval()
    pages, lanes, page_size = 769, 8, 16
    fn, init_pages = make_gpt_paged_ragged_step(
        model, page_size, 1024 // page_size, kv_cache_dtype=kv_dtype)
    on_chip = SingleDeviceSharding(v5e_topology.devices[0])

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    kv = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype),
                                jax.eval_shape(lambda: init_pages(pages)))
    pools = jax.tree_util.tree_leaves(kv)
    assert kv["k"][0].shape == (pages, page_size, H * D)
    compiled = jax.jit(fn, donate_argnums=(7,)).lower(
        spec((lanes,)), spec((lanes,)), spec((lanes, 1024 // page_size)),
        spec((lanes, rows)), spec((lanes, rows)), spec((lanes, rows)),
        spec((lanes,)), kv).compile()
    text = compiled.as_text()
    # the layer's ragged kernel, and its write kernel where the pools are
    # native (int8 pools keep the row scatter: their scales grow per page)
    assert text.count('custom_call_target="tpu_custom_call"') \
        == (1 if kv_dtype else 2)
    assert whole_pool_relayouts(text, pages, page_size) == []
    assert aliased_arguments(text) == len(pools)
    pool_bytes = pages * page_size * H * D * pools[0].dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes



def test_the_serve_cells_kernel_call_compiles_for_v5e(v5e_topology):
    """The ragged kernel at the long-prompt serve cell's own shape (48
    lanes x 64 rows over 64-page tables, 12 heads of 64): six grid steps
    of eight pages a lane, the row-block branch in each."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas_ops.cases import serve_cell_case

    on_chip = SingleDeviceSharding(v5e_topology.devices[0])
    case = serve_cell_case(pages=33)
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip)
             for a in case.args]
    text = jax.jit(case.kernel).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text


def test_the_kv_write_kernel_compiles_per_tp_shard_on_a_v5e_mesh(
        v5e_topology):
    """The mesh engine runs the paged KV write per tp shard, under the
    core's ``shard_map``: at the serve cell's shape split over tp
    2 of a 2x2 host, each shard writes its 384-wide half of every row into
    its half of the pools, in place — one Mosaic call, no copy of a pool.
    (No chip has run this route: ``chip_smoke.py``'s four-chip phase.)"""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.pallas_ops import paged_kv_write as kw

    mesh = Mesh(np.array(v5e_topology.devices).reshape(2, 2), ("data", "tp"))
    lanes, rows, width, pages, page_size, table = 48, 64, 768, 3073, 16, 64
    row_spec, pool_spec = P(None, "tp"), P(None, None, "tp")

    def write(*args):
        return kw.paged_kv_write(*args, interpret=False)

    fn = jax.shard_map(write, mesh=mesh,
                       in_specs=(row_spec, row_spec, pool_spec, pool_spec,
                                 P(), P(), P()),
                       out_specs=(pool_spec, pool_spec), check_vma=False)

    def spec(shape, dtype, part):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, part))

    new = spec((lanes * rows, width), jnp.float32, row_spec)
    pool = spec((pages, page_size, width), jnp.float32, pool_spec)
    text = jax.jit(fn, donate_argnums=(2, 3)).lower(
        new, new, pool, pool, spec((lanes, table), jnp.int32, P()),
        spec((lanes,), jnp.int32, P()), spec((lanes,), jnp.int32, P())
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not [line for line in text.splitlines()
                if " copy(" in line and f"[{pages}," in line]


@pytest.mark.parametrize("case", range(4), ids=[
    "flash-fwd-192-128", "flash-bwd-192-128", "delta-rule-fwd",
    "delta-rule-bwd"])
def test_the_hybrid_mixers_compile_for_v5e(case, v5e_topology):
    """PR 28's mixers at their own head sizes, bf16 as the train step
    runs them: flash with q/k 192 and v 128 (q/k padded to 256 lanes, v
    not), and the gated delta rule's two kernels (PR 34: the chunk pair's
    rolls, transposes and fp32-contract products, forward and through the
    `custom_vjp`) — compiled for one v5e chip with no loop left in the
    program (the case table's `mixer_cases`, which chip_smoke.py runs on
    the chip against the XLA twins)."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas_ops.cases import mixer_cases

    on_chip = SingleDeviceSharding(v5e_topology.devices[0])
    contract, label, fn, _, args = mixer_cases()[case]
    assert contract in CONTRACTS
    specs = [jax.ShapeDtypeStruct(a.shape, jnp.bfloat16 if i < 3
                                  else a.dtype, sharding=on_chip)
             for i, a in enumerate(args)]
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text and " while(" not in text, label


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
def test_the_delta_rule_kernels_hold_eight_float32_heads(backward,
                                                         v5e_topology):
    """A block of the delta rule's kernels is a chunk pair of EIGHT heads
    as the array stores them, two heads a grid step: with float32 q, k, v
    the backward's blocks and temporaries pass the 16 MiB a kernel gets by
    default — the kernels ask for what the contract states."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas_ops.delta_rule import gated_delta_rule_kernel

    on_chip = SingleDeviceSharding(v5e_topology.devices[0])
    B, T, H, D = 1, 1024, 8, 128
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                               sharding=on_chip)
    args = [spec(B, T, H, D)] * 4 + [spec(B, T, H)]
    fn = gated_delta_rule_kernel
    if backward:
        fn = jax.grad(lambda *a: jnp.sum(gated_delta_rule_kernel(*a)),
                      argnums=(0, 1, 2, 3, 4))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1 + backward
    assert CONTRACTS["delta_rule_bwd"].dim("vmem_limit_mib") > 16


@pytest.mark.parametrize("case", range(3), ids=[
    "flash-fwd-32-8x64", "flash-dkv-32-8x64", "flash-dq-32-8x64"])
def test_the_grouped_flash_kernels_compile_for_v5e(case, v5e_topology):
    """PR 33's grouped-query forms, bf16 as the train step runs them: 32
    query heads over 8 KV heads of 64 — the forward and dq kernels read
    KV head h // 4 through their index maps, the dk/dv kernel walks the
    four heads of a group inside one grid axis (the case table's
    `grouped_cases`, which chip_smoke.py runs on the chip against the XLA
    twins).  dk and dv come back at the KV heads' count: no 32-head copy
    of K or V is in the program."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas_ops.cases import grouped_cases

    on_chip = SingleDeviceSharding(v5e_topology.devices[0])
    contract, label, fn, _, args = grouped_cases()[case]
    assert contract in CONTRACTS
    specs = [jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=on_chip)
             for a in args]
    assert [s.shape[1] for s in specs] == [32, 8, 8, 32]
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text, label
    out = jax.tree_util.tree_leaves(jax.eval_shape(fn, *specs))
    assert [o.shape[1] for o in out] == ([8, 8] if case == 1 else [32])
    # the kernel's own K and V operands are the 8-head arrays
    calls = [e for e in jax.make_jaxpr(fn)(*specs).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    k_op, v_op = calls[-1].invars[2:4]
    assert k_op.aval.shape == v_op.aval.shape == (8, specs[1].shape[2], 64)
