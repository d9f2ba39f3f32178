"""KernelContract layer (ISSUE 8): the declared contracts validate
clean, their dims pin the historical hand-picked block literals
byte-for-byte, the kernel modules actually READ them (single source of
truth), and the refactored kernels stay numerically identical to the
exact XLA references."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas_ops.contracts import (CONTRACTS, DTYPE_BYTES,
                                                 LANE, SUBLANE_FLOOR,
                                                 VMEM_BUDGET_BYTES,
                                                 BlockDecl,
                                                 KernelContract)


class TestContractRegistry:
    def test_every_registered_contract_validates_clean(self):
        for name, c in CONTRACTS.items():
            assert c.validate() == [], name

    def test_vmem_estimates_fit_the_budget_with_headroom(self):
        for name, c in CONTRACTS.items():
            est = c.vmem_estimate_bytes()
            assert 0 < est <= c.vmem_budget_bytes, (name, est)
        # the biggest kernel (flash bwd dkv, ~6.0MiB) leaves the
        # compiler ~half the 12MiB budget
        assert CONTRACTS["flash_attention_bwd_dkv"].vmem_estimate_bytes() \
            < VMEM_BUDGET_BYTES * 0.55

    def test_dims_pin_the_historical_literals(self):
        """The refactor satellite's byte-identity anchor: the contract
        dims ARE the pre-refactor hand-picked constants, so every
        compiled program is unchanged."""
        assert CONTRACTS["flash_attention_fwd"].dim("block_q") == 512
        assert CONTRACTS["flash_attention_fwd"].dim("block_k") == 1024
        qmm = CONTRACTS["quantized_matmul"]
        assert (qmm.dim("block_m"), qmm.dim("block_n"),
                qmm.dim("block_k")) == (128, 128, 128)
        paged = CONTRACTS["paged_attention_ragged"]
        assert paged.dim("q_align") == 8
        assert paged.dim("lane") == 128
        # the page block is the pool's stored row: heads x head_dim fused
        assert paged.dim("kv_width") \
            == paged.dim("heads") * paged.dim("head_dim")
        # the int8 epilogue axis (ISSUE 14) defaults to the historical
        # fused form — scale multiplies folded AFTER the dots
        assert CONTRACTS["paged_attention_ragged_int8"].dim(
            "fused_dequant") == 1

    def test_sweep_axes_bind_dims_and_default_is_a_member(self):
        """The autotuner's search axes (ISSUE 14): every axis names a
        dim the default config binds, every declared candidate value is
        an int, and the default value appears on its own axis — the
        config being tuned is always a member of the search space."""
        swept = {n for n, c in CONTRACTS.items() if c.sweep}
        # ISSUE 18 closed the two gaps: the flash backward pair
        # (training kernels were the only un-sweepable ones) and the
        # ragged serving pair (swept from day one)
        assert swept == {"flash_attention_fwd",
                         "flash_attention_bwd_dkv",
                         "flash_attention_bwd_dq",
                         "paged_attention_ragged",
                         "paged_attention_ragged_int8",
                         "quantized_matmul"}
        for name, c in CONTRACTS.items():
            for sym, values in c.sweep.items():
                assert sym in c.dims, (name, sym)
                assert all(isinstance(v, int) for v in values)
                assert c.dim(sym) in values, (name, sym)

    def test_kernel_modules_read_the_contract(self):
        from paddle_tpu.ops.pallas_ops import (flash_attention,
                                               paged_attention,
                                               quantized_matmul)

        assert flash_attention.DEFAULT_BLOCK_Q \
            == CONTRACTS["flash_attention_fwd"].dim("block_q")
        assert flash_attention.DEFAULT_BLOCK_K \
            == CONTRACTS["flash_attention_fwd"].dim("block_k")
        assert paged_attention._RAGGED_Q_ALIGN \
            == CONTRACTS["paged_attention_ragged"].dim("q_align")
        assert paged_attention._LANE \
            == CONTRACTS["paged_attention_ragged"].dim("lane")
        assert paged_attention._RAGGED_PAGES_PER_STEP \
            == CONTRACTS["paged_attention_ragged"].dim("pages_per_step")
        assert paged_attention._STATS_PAGES_PER_STEP == CONTRACTS[
            "paged_attention_ragged_stats"].dim("pages_per_step")
        assert quantized_matmul._BLOCK_K \
            == CONTRACTS["quantized_matmul"].dim("block_k")

    def test_paged_contracts_need_no_waiver(self):
        """The paged contracts say what the TPU lowering ENFORCES
        (ISSUE 21): every VMEM block's trailing dims are tile-aligned
        or span the whole array extent — no contract waives a rule the
        compiler does not waive (tests/test_pallas_tpu_lowering.py
        lowers the kernels these blocks describe)."""
        assert [(c.name, b.name) for c in CONTRACTS.values()
                for b in c.blocks if b.waivers] == []
        rl = next(b for b in CONTRACTS["paged_attention_ragged"].blocks
                  if b.name == "row_lens")
        assert rl.shape == (1, "q_align", 1) and rl.lanes_full
        ks = next(b for b in
                  CONTRACTS["paged_attention_ragged_int8"].blocks
                  if b.name == "k_scales")
        assert ks.shape == ("pages_per_step", 1, "heads") \
            and ks.lanes_full and ks.sublane_full
        # the page block IS the stored page (ISSUE 26): [P, H*D], the
        # fused row spanning the pool's whole last dim, in every form —
        # pages_per_step of them a grid step (ISSUE 29), and the lane's
        # live-row extent prefetched beside its longest row
        for name in ("paged_attention_ragged",
                     "paged_attention_ragged_int8",
                     "paged_attention_ragged_stats"):
            c = CONTRACTS[name]
            assert c.grid == ("groups", "page_groups")
            assert c.dim("pages_per_step") * c.dim("page_size") \
                == c.dim("lane")
            for side in ("k_pages", "v_pages"):
                blk = next(b for b in c.blocks if b.name == side)
                assert blk.shape == ("pages_per_step", "page_size",
                                     "kv_width") \
                    and blk.lanes_full, (name, side)
            ext = next(b for b in c.blocks if b.name == "live_rows")
            assert ext.memory == "smem" and ext.shape == ("groups",)


class TestValidateRules:
    """validate() is the autotuner's candidate-config gate — each rule
    must fire on a bad swapped-in config."""

    def _contract(self, **over):
        base = dict(
            name="t", module="m.py", grid=("i",),
            dims={"b": 128, "d": 128},
            blocks=(BlockDecl("x", "in", ("b", "d"), "float32"),),
            shape_buckets={"b": (256,)})
        base.update(over)
        return KernelContract(**base)

    def test_lane_rule(self):
        c = self._contract(dims={"b": 128, "d": 96})
        assert any("lane" in v for v in c.validate())

    def test_sublane_rule_is_dtype_correct(self):
        ok8 = self._contract(
            blocks=(BlockDecl("x", "in", (8, "d"), "float32"),))
        assert ok8.validate() == []
        bad_bf16 = self._contract(
            blocks=(BlockDecl("x", "in", (8, "d"), "bfloat16"),))
        assert any("bfloat16 tile floor 16" in v
                   for v in bad_bf16.validate())
        bad_int8 = self._contract(
            blocks=(BlockDecl("x", "in", (16, "d"), "int8"),))
        assert any("int8 tile floor 32" in v for v in bad_int8.validate())

    def test_divisibility_rule(self):
        c = self._contract(shape_buckets={"b": (192,)})
        assert any("not divisible" in v for v in c.validate())

    def test_vmem_rule_counts_double_buffering(self):
        big = self._contract(
            dims={"b": 1024, "d": 1024},
            blocks=(BlockDecl("x", "in", ("b", "d"), "float32"),
                    BlockDecl("s", "scratch", ("b", "d"), "float32")),
            shape_buckets={})
        # in-block 4MB x2 + scratch 4MB x1 = 12MB == budget: holds
        assert big.vmem_estimate_bytes() == 12 * 1024 * 1024
        assert big.validate() == []
        over = self._contract(
            dims={"b": 1024, "d": 1056},
            blocks=(BlockDecl("x", "in", ("b", "d"), "float32"),
                    BlockDecl("s", "scratch", ("b", "d"), "float32")),
            shape_buckets={})
        assert any("exceeds" in v for v in over.validate())

    def test_waiver_suppresses_only_its_rule(self):
        c = self._contract(
            dims={"b": 12, "d": 96},
            blocks=(BlockDecl("x", "in", ("b", "d"), "float32",
                              waivers=("sublane: test",)),),
            shape_buckets={})
        out = c.validate()
        assert len(out) == 1 and "lane" in out[0]

    def test_tables_are_consistent(self):
        assert set(SUBLANE_FLOOR) == set(DTYPE_BYTES)
        assert LANE == 128

    def test_static_checker_mirrors_the_runtime_tables(self):
        """The analyze suite keeps LOCAL copies of the rule tables (it
        imports nothing from paddle_tpu by design) — this pin is what
        makes a contracts.py table edit that forgets the mirror fail
        tier-1 instead of silently splitting the runtime gate from the
        lint."""
        import os
        import sys

        root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        if root not in sys.path:
            sys.path.insert(0, root)
        from tools.analyze import pallas_contract as pc

        assert pc.LANE == LANE
        assert pc.SUBLANE_FLOOR == SUBLANE_FLOOR
        assert pc.DTYPE_BYTES == DTYPE_BYTES
        assert pc.DEFAULT_VMEM_BUDGET == VMEM_BUDGET_BYTES


class TestKernelParityAfterRefactor:
    """The refactored kernels (constants now read from contracts) stay
    numerically identical to the exact XLA references — the
    'pinned byte-identical' satellite, exercised at the default
    contract config in interpret mode."""

    def test_quantized_matmul_default_blocks(self):
        from paddle_tpu.ops.pallas_ops.quantized_matmul import (
            quantized_matmul_kernel, quantized_matmul_xla)

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(9, 160).astype(np.float32))
        w = jnp.asarray(rng.randint(-127, 128, (160, 72)).astype(np.int8))
        s = jnp.asarray((rng.rand(72) * 0.1).astype(np.float32))
        out = quantized_matmul_kernel(x, w, s, interpret=True)
        ref = quantized_matmul_xla(x, w, s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_paged_attention_padding_from_contract(self):
        from paddle_tpu.ops.pallas_ops.paged_attention import (
            paged_attention_kernel, paged_attention_xla)

        rng = np.random.RandomState(1)
        # H=3, D=20: a fused row (60 lanes) that is no multiple of
        # anything — nothing of the pool is padded, only the one query
        # row (-> q_align)
        q = jnp.asarray(rng.randn(2, 3, 20).astype(np.float32))
        kp = jnp.asarray(rng.randn(6, 4, 3 * 20).astype(np.float32))
        vp = jnp.asarray(rng.randn(6, 4, 3 * 20).astype(np.float32))
        pt = jnp.asarray(np.array([[1, 2, 3], [4, 5, 0]], np.int32))
        sl = jnp.asarray(np.array([11, 6], np.int32))
        out = paged_attention_kernel(q, kp, vp, pt, sl, interpret=True)
        ref = paged_attention_xla(q, kp, vp, pt, sl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("kernel", ["flash_attention_fwd",
                                        "flash_attention_bwd_dkv",
                                        "flash_attention_bwd_dq"])
    def test_grouped_flash_runs_under_the_declared_blocks(self, kernel):
        """Fewer KV heads than query heads (ISSUE 33) changes no block of
        the three flash contracts: at the default block sizes the grouped
        case table's kernel (interpret mode, 8 query heads over 2 KV
        heads of 64 at 2,048 tokens in 512-row blocks) matches its XLA twin, dk/dv at the KV heads' count; the
        dk/dv contract's grid says what its axes count."""
        import jax

        from paddle_tpu.ops.pallas_ops.cases import grouped_cases

        c = CONTRACTS[kernel]
        assert c.validate() == []
        assert CONTRACTS["flash_attention_bwd_dkv"].grid \
            == ("batch_kv_heads", "k_blocks", "group_q_blocks")
        case = next(k for k in grouped_cases(
            heads=8, kv_heads=2, head_dim=64, seq=2 * c.dim("block_k"),
            block=c.dim("block_q")) if k.contract == kernel)
        with jax.default_matmul_precision("highest"):
            want = jax.tree_util.tree_leaves(case.twin(*case.args))
        got = jax.tree_util.tree_leaves(case.kernel(*case.args))
        assert [g.shape for g in got] == [w.shape for w in want]
        if kernel == "flash_attention_bwd_dkv":
            assert [g.shape[1] for g in got] == [2, 2]
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-4)
