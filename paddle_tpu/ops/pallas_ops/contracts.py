"""Declared kernel contracts for the Pallas kernels in this package.

Every hand-picked grid/BlockSpec/scratch literal in ``flash_attention``,
``paged_attention`` and ``quantized_matmul`` used to live inline in the
kernel wrappers — invisible to tooling, and exactly the values the
ROADMAP's Pallas autotuner needs to parameterize.  This module lifts
them into :class:`KernelContract` objects: a machine-readable statement
of each kernel's block shapes, dtype tiling rules, memory spaces, grid
divisibility buckets and static VMEM footprint.  Tensor Processing
Primitives (PAPERS.md) argues for exactly this contract-carrying
primitive layer; CUDA-L2 shows the payoff of making kernel configs
explicit, validated objects before searching over them.

Three consumers, one source of truth:

- the KERNELS read their default block constants from here (e.g.
  ``flash_attention.DEFAULT_BLOCK_Q`` is ``FLASH_FWD.dim("block_q")``),
  so a tuned config swap is one ``dims`` replacement away;
- the STATIC checker (``tools/analyze`` ``pallas-contract``, PC00x)
  re-derives every contract from this file's AST — declarations must
  stay PURE LITERALS (ints, strings, tuples, dicts, BlockDecl calls;
  module-level constants like ``LANE`` are fine) so the stdlib linter
  can evaluate them without importing jax;
- the RUNTIME twin :meth:`KernelContract.validate` applies the same
  rules to any candidate config — the gate the autotuner will run each
  swapped-in ``dims`` through before measuring it.

Intentional rule exceptions are declared in-contract via ``waivers``
(a reasoned string per waived rule), not hidden: a waiver shows up in
``validate()``'s accounting and the lint report alike.

This module is PURE STDLIB (dataclasses only, no jax) — importing it
costs microseconds, so the analyzer CLI and host-only tests stay fast.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Tuple, Union

__all__ = ["BlockDecl", "KernelContract", "CONTRACTS", "LANE",
           "SUBLANE_FLOOR", "DTYPE_BYTES", "VMEM_BUDGET_BYTES"]

# TPU lane width: the last dim of every VMEM block tiles in units of 128
LANE = 128

# minimum sublane (second-to-last dim) tile per dtype — the (8, 128) /
# (16, 128) / (32, 128) floors from the TPU tiling table
SUBLANE_FLOOR = {
    "float32": 8, "int32": 8, "uint32": 8,
    "bfloat16": 16, "float16": 16,
    "int8": 32, "uint8": 32, "float8_e4m3fn": 32, "float8_e5m2": 32,
}

DTYPE_BYTES = {
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2,
    "int8": 1, "uint8": 1, "float8_e4m3fn": 1, "float8_e5m2": 1,
}

# per-platform VMEM budget the static footprint estimate is checked
# against (one TPU core's VMEM; the estimate must leave the compiler
# headroom, hence the 0.75 duty factor folded in below)
VMEM_BYTES = {"tpu": 16 * 1024 * 1024}
VMEM_BUDGET_BYTES = 12 * 1024 * 1024       # 0.75 * VMEM_BYTES["tpu"]

Dim = Union[int, str]


@dataclass(frozen=True)
class BlockDecl:
    """One operand/output/scratch block of a kernel.

    ``shape`` entries are ints or symbol names resolved through the
    owning contract's ``dims``.  ``lanes_full`` / ``sublane_full`` mark
    a trailing dim that spans the WHOLE array extent — the TPU tiling
    rule is "(8k, 128k) OR equal to the array dims", so such dims are
    exempt from the alignment floors.  ``waivers`` carries reasoned
    exemptions, one per waived rule, each starting with the rule key
    (``lane``/``sublane``/``divisibility``/``vmem``).
    """

    name: str
    kind: str                      # "in" | "out" | "scratch"
    shape: Tuple[Dim, ...]
    dtype: str
    memory: str = "vmem"           # "vmem" | "smem" | "hbm"
    lanes_full: bool = False
    sublane_full: bool = False
    waivers: Tuple[str, ...] = ()

    def waived(self, rule: str) -> bool:
        return any(w.split(":", 1)[0].strip() == rule
                   for w in self.waivers)


@dataclass(frozen=True)
class KernelContract:
    """Declared resource contract of one Pallas kernel.

    - ``module``: repo-relative path of the kernel file the contract
      governs (the drift lint cross-checks its literals).
    - ``grid``: symbolic grid axes, outermost first.
    - ``dims``: the DEFAULT config — symbol -> int.  This is the object
      the autotuner swaps: ``replace(contract, dims={...})`` then
      ``validate()`` gates the candidate before it is ever compiled.
    - ``blocks``: every in/out/scratch block (SMEM scalar-prefetch
      operands included for completeness; they are exempt from the VMEM
      rules).
    - ``shape_buckets``: block symbol -> padded array extents the kernel
      is expected to tile at this config; each bucket must divide by the
      symbol's bound value (grid divisibility — a non-dividing bucket
      means a ragged final block the kernel body does not handle).
    - ``double_buffered``: pallas double-buffers grid-streamed in/out
      block DMAs, so their VMEM cost counts twice; scratch is resident
      once.
    - ``sweep``: the AUTOTUNER's declared search axes — dim symbol ->
      candidate values (``paddle_tpu/tune``).  The cartesian product of
      these axes, overlaid on ``dims`` and gated through ``validate()``
      at the target shape bucket, is the candidate set; a kernel with an
      empty sweep has no tunable axis (its config is structural).  Axes
      must name symbols bound in ``dims`` so the default config is
      always a member of its own search space.
    """

    name: str
    module: str
    grid: Tuple[str, ...]
    dims: Mapping[str, int]
    blocks: Tuple[BlockDecl, ...]
    shape_buckets: Mapping[str, Tuple[int, ...]] = field(
        default_factory=dict)
    double_buffered: bool = True
    platform: str = "tpu"
    vmem_budget_bytes: int = VMEM_BUDGET_BYTES
    sweep: Mapping[str, Tuple[int, ...]] = field(default_factory=dict)

    # --- resolution -------------------------------------------------------
    def dim(self, sym: str) -> int:
        return int(self.dims[sym])

    def resolve(self, shape: Tuple[Dim, ...]) -> Tuple[int, ...]:
        return tuple(d if isinstance(d, int) else self.dim(d)
                     for d in shape)

    def block_bytes(self, block: BlockDecl) -> int:
        n = 1
        for d in self.resolve(block.shape):
            n *= d
        return n * DTYPE_BYTES[block.dtype]

    def vmem_estimate_bytes(self) -> int:
        """Static footprint: sum of VMEM block bytes, grid-streamed
        in/out blocks counted twice when double-buffered (the DMA for
        grid cell i+1 overlaps compute on cell i)."""
        total = 0
        for b in self.blocks:
            if b.memory != "vmem":
                continue
            mult = 2 if (self.double_buffered
                         and b.kind in ("in", "out")) else 1
            total += mult * self.block_bytes(b)
        return total

    # --- the rule set (runtime twin of the PC00x lint) --------------------
    def validate(self) -> List[str]:
        """Apply the tiling/divisibility/footprint rules to THIS config;
        returns human-readable violations (waived rules excluded — the
        autotuner gates candidate ``dims`` with this)."""
        out: List[str] = []
        for b in self.blocks:
            if b.memory != "vmem" or len(b.shape) < 2:
                continue
            shape = self.resolve(b.shape)
            lane, sub = shape[-1], shape[-2]
            if lane % LANE and not b.lanes_full and not b.waived("lane"):
                out.append(f"block {b.name!r}: last dim {lane} is not a "
                           f"multiple of the {LANE}-wide lane")
            floor = SUBLANE_FLOOR[b.dtype]
            if sub % floor and not b.sublane_full \
                    and not b.waived("sublane"):
                out.append(f"block {b.name!r}: sublane dim {sub} is not "
                           f"a multiple of the {b.dtype} tile floor "
                           f"{floor}")
        for sym, buckets in self.shape_buckets.items():
            size = self.dim(sym)
            for v in buckets:
                if v % size:
                    out.append(f"bucket {v} along {sym!r} is not "
                               f"divisible by its block size {size}")
        est = self.vmem_estimate_bytes()
        if est > self.vmem_budget_bytes:
            out.append(f"VMEM estimate {est} bytes exceeds the "
                       f"{self.platform} budget "
                       f"{self.vmem_budget_bytes}")
        return out


# ===========================================================================
# flash_attention.py — tiled online-softmax attention, fwd + two bwd
# kernels.  Block defaults tuned on v5e @ S=4096, D=128 (see the module
# docstring); the wrapper's _pick_block halves them to a divisor for
# shorter (always x128-padded) sequences.
#
# Grouped-query attention (ISSUE 33) changes no block: with `group` query
# heads to a KV head, k and v stay [B * kv_heads, S, D] in HBM and the
# forward and dq kernels' k/v index maps read block (b // group, j) —
# "batch_heads" counts QUERY heads there.  The dk/dv kernel's first grid
# axis counts KV heads and its last walks the q blocks of each of the
# group's heads in turn (group * q_blocks steps), so dk/dv are summed
# over the group in the float32 scratch the contract already declares
# and written once.  At group 1 all three are the programs they were.
# ===========================================================================
FLASH_FWD = KernelContract(
    name="flash_attention_fwd",
    module="paddle_tpu/ops/pallas_ops/flash_attention.py",
    grid=("batch_heads", "q_blocks", "k_blocks"),
    dims={"block_q": 512, "block_k": 1024, "head_dim": 128, "lane": 128},
    blocks=(
        BlockDecl("seed", "in", (1,), "int32", memory="smem"),
        BlockDecl("q", "in", (1, "block_q", "head_dim"), "float32"),
        BlockDecl("k", "in", (1, "block_k", "head_dim"), "float32"),
        BlockDecl("v", "in", (1, "block_k", "head_dim"), "float32"),
        BlockDecl("mask", "in", (1, 1, "block_k"), "float32",
                  sublane_full=True),
        BlockDecl("o", "out", (1, "block_q", "head_dim"), "float32"),
        BlockDecl("lse", "out", (1, "block_q", 1), "float32",
                  lanes_full=True),
        BlockDecl("acc", "scratch", ("block_q", "head_dim"), "float32"),
        BlockDecl("m", "scratch", ("block_q", "lane"), "float32"),
        BlockDecl("l", "scratch", ("block_q", "lane"), "float32"),
    ),
    shape_buckets={"block_q": (1024, 2048, 4096, 8192),
                   "block_k": (1024, 2048, 4096, 8192)},
    # block_q partitions independent query rows (exactly
    # parity-preserving); block_k reorders the online-softmax
    # accumulation (winners must still pass the sweep's parity gate)
    sweep={"block_q": (256, 512, 1024),
           "block_k": (512, 1024, 2048)},
)

FLASH_BWD_DKV = KernelContract(
    name="flash_attention_bwd_dkv",
    module="paddle_tpu/ops/pallas_ops/flash_attention.py",
    grid=("batch_kv_heads", "k_blocks", "group_q_blocks"),
    dims={"block_q": 512, "block_k": 1024, "head_dim": 128},
    blocks=(
        BlockDecl("seed", "in", (1,), "int32", memory="smem"),
        BlockDecl("q", "in", (1, "block_q", "head_dim"), "float32"),
        BlockDecl("k", "in", (1, "block_k", "head_dim"), "float32"),
        BlockDecl("v", "in", (1, "block_k", "head_dim"), "float32"),
        BlockDecl("do", "in", (1, "block_q", "head_dim"), "float32"),
        BlockDecl("lse", "in", (1, "block_q", 1), "float32",
                  lanes_full=True),
        BlockDecl("delta", "in", (1, "block_q", 1), "float32",
                  lanes_full=True),
        BlockDecl("mask", "in", (1, 1, "block_k"), "float32",
                  sublane_full=True),
        BlockDecl("dk", "out", (1, "block_k", "head_dim"), "float32"),
        BlockDecl("dv", "out", (1, "block_k", "head_dim"), "float32"),
        BlockDecl("dk_sc", "scratch", ("block_k", "head_dim"), "float32"),
        BlockDecl("dv_sc", "scratch", ("block_k", "head_dim"), "float32"),
    ),
    shape_buckets={"block_q": (1024, 2048, 4096, 8192),
                   "block_k": (1024, 2048, 4096, 8192)},
    # block_k partitions independent kv rows (exactly parity-preserving);
    # block_q reorders the dk/dv accumulation over visiting query sets
    # (winners must pass the sweep's parity gate) — ISSUE 18 grad-path
    # runner (tune/runners.py) drives this sweep
    sweep={"block_q": (256, 512, 1024),
           "block_k": (512, 1024, 2048)},
)

FLASH_BWD_DQ = KernelContract(
    name="flash_attention_bwd_dq",
    module="paddle_tpu/ops/pallas_ops/flash_attention.py",
    grid=("batch_heads", "q_blocks", "k_blocks"),
    dims={"block_q": 512, "block_k": 1024, "head_dim": 128},
    blocks=(
        BlockDecl("seed", "in", (1,), "int32", memory="smem"),
        BlockDecl("q", "in", (1, "block_q", "head_dim"), "float32"),
        BlockDecl("k", "in", (1, "block_k", "head_dim"), "float32"),
        BlockDecl("v", "in", (1, "block_k", "head_dim"), "float32"),
        BlockDecl("do", "in", (1, "block_q", "head_dim"), "float32"),
        BlockDecl("lse", "in", (1, "block_q", 1), "float32",
                  lanes_full=True),
        BlockDecl("delta", "in", (1, "block_q", 1), "float32",
                  lanes_full=True),
        BlockDecl("mask", "in", (1, 1, "block_k"), "float32",
                  sublane_full=True),
        BlockDecl("dq", "out", (1, "block_q", "head_dim"), "float32"),
        BlockDecl("dq_sc", "scratch", ("block_q", "head_dim"), "float32"),
    ),
    shape_buckets={"block_q": (1024, 2048, 4096, 8192),
                   "block_k": (1024, 2048, 4096, 8192)},
    # mirror of the dkv sweep: block_q partitions independent query rows
    # (exactly parity-preserving), block_k reorders the dq accumulation
    # over kv chunks (parity gate applies)
    sweep={"block_q": (256, 512, 1024),
           "block_k": (512, 1024, 2048)},
)

# ===========================================================================
# paged_attention.py — ragged-QUERY paged attention (ISSUE 18): ONE
# kernel body for every paged form.  One grid group = one lane: a block
# of up to ``q_align`` query rows (decode lane = 1 row — the decode
# entry point IS this kernel at Q = 1 — chunked-prefill lane = chunk
# rows, spec-verify lane = K rows) sharing ONE page-table row, so the
# page DMA is paid once per lane instead of once per query row.
#
# One block = one physical KV page AS THE POOL STORES IT (ISSUE 26):
# ``[page_size, kv_width]`` with ``kv_width = heads * head_dim`` — heads
# and head_dim fused in one row, so the stored bytes are the logical
# bytes for every model shape and the wrapper pads, transposes and
# copies nothing of pool size (the only padding left is the query-row
# dim, to ``q_align``).  q and o ride in the same fused-row layout; the
# body walks the row in ``lane``-wide windows (a head narrower than a
# lane tile shares its window with its neighbours).
#
# One grid step = ``pages_per_step`` such pages of the lane (ISSUE 29;
# grid ``(groups, page_groups)``, the table padded with page 0 to whole
# groups): each pool is an operand once per page of the group, every
# one a ``(1, page_size, kv_width)`` block with the index_map of its
# slot — declared below as one ``[pages_per_step, page_size, kv_width]``
# block, which is what VMEM holds.  8 pages of 16 make the score block
# ``[rows, 128]``, a whole lane tile, and the online-softmax bookkeeping
# is paid once per group (v5e, the serve cell's shape: 2.44 ms a call at
# one page a step, 1.28 at 4, 0.85 at 8, 0.84 at 16).  ``live_rows`` is
# the lane's prefetched live-row extent: rows are computed to the first
# of (``q_align``, all) that covers it, the rest written as zeros.
#
# Every VMEM block below obeys the rule the TPU lowering ENFORCES — the
# trailing two block dims are (8k, 128k) or span the whole array extent
# (``lanes_full``/``sublane_full``) — with no waiver: the fused row
# always spans the array's whole last dim (and is whole lane tiles at
# the declared shape); the staged query, the accumulator and the
# online-softmax m/l scratch are per head, [heads, q_align, .]; row_lens
# rides as [G, q_align, 1], lse as
# [G, heads, q_align, 1] and the int8 scale rows as [N, 1, heads].
# ===========================================================================
PAGED_RAGGED = KernelContract(
    name="paged_attention_ragged",
    module="paddle_tpu/ops/pallas_ops/paged_attention.py",
    grid=("groups", "page_groups"),
    dims={"page_size": 16, "heads": 8, "head_dim": 128, "kv_width": 1024,
          "lane": 128, "q_align": 8, "pages_per_step": 8},
    blocks=(
        BlockDecl("page_tables", "in", ("groups", "pages_per_seq"),
                  "int32", memory="smem"),
        BlockDecl("group_lens", "in", ("groups",), "int32",
                  memory="smem"),
        BlockDecl("live_rows", "in", ("groups",), "int32",
                  memory="smem"),
        BlockDecl("row_lens", "in", (1, "q_align", 1), "int32",
                  lanes_full=True),
        BlockDecl("q", "in", (1, "q_align", "kv_width"), "float32",
                  lanes_full=True),
        BlockDecl("k_pages", "in",
                  ("pages_per_step", "page_size", "kv_width"), "float32",
                  lanes_full=True),
        BlockDecl("v_pages", "in",
                  ("pages_per_step", "page_size", "kv_width"), "float32",
                  lanes_full=True),
        BlockDecl("o", "out", (1, "q_align", "kv_width"), "float32",
                  lanes_full=True),
        # per head, one lane window wide (head_dim; the lane width where
        # narrower heads share a window): the query staged once per lane
        # (scaled, the window's other heads' lanes zeroed) and the
        # accumulator
        BlockDecl("q_stage", "scratch", ("heads", "q_align", "head_dim"),
                  "float32"),
        BlockDecl("acc", "scratch", ("heads", "q_align", "head_dim"),
                  "float32"),
        BlockDecl("m", "scratch", ("heads", "q_align", "lane"),
                  "float32"),
        BlockDecl("l", "scratch", ("heads", "q_align", "lane"),
                  "float32"),
    ),
    shape_buckets={"head_dim": (128, 256), "heads": (8, 16, 32)},
    # q_align is the padding floor for the per-lane query-row dim —
    # padded rows carry row_len 0 and are sliced off, so the axis is
    # exactly parity-preserving; pages_per_step regroups the online
    # softmax (the same sums in another order: winners must pass the
    # sweep's parity gate)
    sweep={"q_align": (8, 16), "pages_per_step": (4, 8, 16)},
)

PAGED_RAGGED_INT8 = KernelContract(
    name="paged_attention_ragged_int8",
    module="paddle_tpu/ops/pallas_ops/paged_attention.py",
    grid=("groups", "page_groups"),
    # fused_dequant=1 is the historical epilogue: the per-head scales
    # multiply the LOGITS (K) and the accumulated context (V) after the
    # dots; 0 dequantizes the head's page slab BEFORE the dots.  Both
    # stream 1 byte/element from HBM — the choice moves the multiply
    # between the VPU epilogue and the MXU operand path, which is
    # exactly the kind of platform-dependent tie the sweep measures.
    dims={"page_size": 16, "heads": 8, "head_dim": 128, "kv_width": 1024,
          "lane": 128, "q_align": 8, "pages_per_step": 8,
          "fused_dequant": 1},
    blocks=(
        BlockDecl("page_tables", "in", ("groups", "pages_per_seq"),
                  "int32", memory="smem"),
        BlockDecl("group_lens", "in", ("groups",), "int32",
                  memory="smem"),
        BlockDecl("live_rows", "in", ("groups",), "int32",
                  memory="smem"),
        BlockDecl("row_lens", "in", (1, "q_align", 1), "int32",
                  lanes_full=True),
        BlockDecl("q", "in", (1, "q_align", "kv_width"), "float32",
                  lanes_full=True),
        # int8 pages keep the f32 page layout ([page_size, kv_width]:
        # 16 rows, not the int8 floor 32 — the block is the WHOLE page,
        # which the lowering accepts at any extent); each lane window is
        # converted to f32 in-register as it is loaded
        BlockDecl("k_pages", "in",
                  ("pages_per_step", "page_size", "kv_width"), "int8",
                  lanes_full=True, sublane_full=True),
        BlockDecl("v_pages", "in",
                  ("pages_per_step", "page_size", "kv_width"), "int8",
                  lanes_full=True, sublane_full=True),
        BlockDecl("k_scales", "in", ("pages_per_step", 1, "heads"),
                  "float32", lanes_full=True, sublane_full=True),
        BlockDecl("v_scales", "in", ("pages_per_step", 1, "heads"),
                  "float32", lanes_full=True, sublane_full=True),
        BlockDecl("o", "out", (1, "q_align", "kv_width"), "float32",
                  lanes_full=True),
        # per head, one lane window wide (head_dim; the lane width where
        # narrower heads share a window): the query staged once per lane
        # (scaled, the window's other heads' lanes zeroed) and the
        # accumulator
        BlockDecl("q_stage", "scratch", ("heads", "q_align", "head_dim"),
                  "float32"),
        BlockDecl("acc", "scratch", ("heads", "q_align", "head_dim"),
                  "float32"),
        BlockDecl("m", "scratch", ("heads", "q_align", "lane"),
                  "float32"),
        BlockDecl("l", "scratch", ("heads", "q_align", "lane"),
                  "float32"),
    ),
    shape_buckets={"head_dim": (128, 256), "heads": (8, 16, 32)},
    # fused_dequant moves the scale multiply across the dot — NOT
    # bit-exact (rounding points differ), so the non-default choice only
    # survives a sweep run with an explicit tolerance (docs/TUNING.md);
    # q_align is exactly parity-preserving, pages_per_step regroups the
    # online softmax (parity gate applies)
    sweep={"q_align": (8, 16), "pages_per_step": (4, 8, 16),
           "fused_dequant": (0, 1)},
)

# ===========================================================================
# paged_attention.py — mesh-aware head-shard STATS form (ISSUE 19).
# Same body/grid/scratch as the ragged contract, but the kernel runs
# on ONE mesh shard: its page pool holds the shard's 1/sp of the pages
# (and its H/tp head-shard of each — a contiguous slice of the fused
# row, so the shard's pool is again [pages, page_size, kv_width] at its
# local width), a fourth scalar-prefetch operand masks page-table
# entries by OWNERSHIP, and alongside the locally-normalized context
# the kernel emits the online-softmax running stats as lse = m + log(l)
# — the cross-shard merge (pmax of lse, psum of exp-weighted
# context/denominator) lives in the sharded serving core
# (text/generation.py), mirroring distributed/ring_attention.py.
# ===========================================================================
PAGED_RAGGED_STATS = KernelContract(
    name="paged_attention_ragged_stats",
    module="paddle_tpu/ops/pallas_ops/paged_attention.py",
    grid=("groups", "page_groups"),
    dims={"page_size": 16, "heads": 8, "head_dim": 128, "kv_width": 1024,
          "lane": 128, "q_align": 8, "pages_per_step": 8},
    blocks=(
        BlockDecl("page_tables", "in", ("groups", "pages_per_seq"),
                  "int32", memory="smem"),
        BlockDecl("group_lens", "in", ("groups",), "int32",
                  memory="smem"),
        BlockDecl("live_rows", "in", ("groups",), "int32",
                  memory="smem"),
        BlockDecl("page_ok", "in", ("groups", "pages_per_seq"),
                  "int32", memory="smem"),
        BlockDecl("row_lens", "in", (1, "q_align", 1), "int32",
                  lanes_full=True),
        BlockDecl("q", "in", (1, "q_align", "kv_width"), "float32",
                  lanes_full=True),
        BlockDecl("k_pages", "in",
                  ("pages_per_step", "page_size", "kv_width"), "float32",
                  lanes_full=True),
        BlockDecl("v_pages", "in",
                  ("pages_per_step", "page_size", "kv_width"), "float32",
                  lanes_full=True),
        BlockDecl("o", "out", (1, "q_align", "kv_width"), "float32",
                  lanes_full=True),
        # one lse value per (head, row), carried [.., q_align, 1] like
        # the flash kernels' lse
        BlockDecl("lse", "out", (1, "heads", "q_align", 1), "float32",
                  lanes_full=True),
        # per head, one lane window wide (head_dim; the lane width where
        # narrower heads share a window): the query staged once per lane
        # (scaled, the window's other heads' lanes zeroed) and the
        # accumulator
        BlockDecl("q_stage", "scratch", ("heads", "q_align", "head_dim"),
                  "float32"),
        BlockDecl("acc", "scratch", ("heads", "q_align", "head_dim"),
                  "float32"),
        BlockDecl("m", "scratch", ("heads", "q_align", "lane"),
                  "float32"),
        BlockDecl("l", "scratch", ("heads", "q_align", "lane"),
                  "float32"),
    ),
    shape_buckets={"head_dim": (128, 256), "heads": (8, 16, 32)},
    # no sweep: the stats form's config is structural (it must mirror
    # the unified ragged contract it shards — a divergent padding floor
    # would change nothing but the slice-off)
)

# ===========================================================================
# paged_kv_write.py — a ragged step's new K/V rows into the page pools,
# live rows only, a page the lane covers whole as one copy.  Grid (G,):
# one lane a step, K and V together.  The lane's rows arrive through the
# pipeline — its first `tile` of them where they hold every live row (a
# decode lane), else the whole lane (`rows` rounded up to `tile`) — with
# its first page where the live rows cover it in part; the image the rows
# are rolled into and the last page, where read, are scratch.  The pools
# stay in HBM (aliased to the outputs) and every DMA slice is whole
# tiles, so `page_size` must be a multiple of `tile`.
# ===========================================================================
PAGED_KV_WRITE = KernelContract(
    name="paged_kv_write",
    module="paddle_tpu/ops/pallas_ops/paged_kv_write.py",
    grid=("groups",),
    dims={"page_size": 16, "tile": 8, "rows": 64, "image_rows": 80,
          "kv_width": 768},
    blocks=(
        BlockDecl("page_tables", "in", ("groups", "pages_per_seq"),
                  "int32", memory="smem"),
        BlockDecl("first", "in", ("groups",), "int32", memory="smem"),
        BlockDecl("live", "in", ("groups",), "int32", memory="smem"),
        BlockDecl("tile_lane", "in", ("groups",), "int32",
                  memory="smem"),
        BlockDecl("rows_lane", "in", ("groups",), "int32",
                  memory="smem"),
        BlockDecl("head_page", "in", ("groups",), "int32",
                  memory="smem"),
        BlockDecl("k_tile", "in", (1, "tile", "kv_width"), "float32",
                  lanes_full=True),
        BlockDecl("v_tile", "in", (1, "tile", "kv_width"), "float32",
                  lanes_full=True),
        BlockDecl("k_rows", "in", (1, "rows", "kv_width"), "float32",
                  lanes_full=True),
        BlockDecl("v_rows", "in", (1, "rows", "kv_width"), "float32",
                  lanes_full=True),
        BlockDecl("k_head", "in", (1, "page_size", "kv_width"), "float32",
                  lanes_full=True),
        BlockDecl("v_head", "in", (1, "page_size", "kv_width"), "float32",
                  lanes_full=True),
        BlockDecl("k_pool", "out", ("pages", "page_size", "kv_width"),
                  "float32", memory="hbm"),
        BlockDecl("v_pool", "out", ("pages", "page_size", "kv_width"),
                  "float32", memory="hbm"),
        # the pages a lane's rows can reach: its rows rolled page-aligned
        BlockDecl("k_image", "scratch", ("image_rows", "kv_width"),
                  "float32", lanes_full=True),
        BlockDecl("v_image", "scratch", ("image_rows", "kv_width"),
                  "float32", lanes_full=True),
        BlockDecl("k_tail", "scratch", ("page_size", "kv_width"),
                  "float32", lanes_full=True),
        BlockDecl("v_tail", "scratch", ("page_size", "kv_width"),
                  "float32", lanes_full=True),
    ),
    # no sweep: every dim is the data's (the tile is the dtype's)
)

# ===========================================================================
# quantized_matmul.py — weight-only int8 matmul.  Grid (M/bm, N/bn,
# K/bk), K innermost; int8 weight blocks satisfy the (32, 128) floor at
# the default 128x128x128 tiling.
# ===========================================================================
QUANTIZED_MATMUL = KernelContract(
    name="quantized_matmul",
    module="paddle_tpu/ops/pallas_ops/quantized_matmul.py",
    grid=("m_blocks", "n_blocks", "k_steps"),
    dims={"block_m": 128, "block_n": 128, "block_k": 128},
    blocks=(
        BlockDecl("x", "in", ("block_m", "block_k"), "float32"),
        BlockDecl("w_q", "in", ("block_k", "block_n"), "int8"),
        BlockDecl("w_scale", "in", (1, "block_n"), "float32",
                  sublane_full=True),
        BlockDecl("o", "out", ("block_m", "block_n"), "float32"),
        BlockDecl("acc", "scratch", ("block_m", "block_n"), "float32"),
    ),
    shape_buckets={"block_k": (128, 256, 512, 1024, 2048),
                   "block_n": (128, 256, 512, 1024, 2048),
                   "block_m": (128, 256)},
    # the wrapper pads every extent up to the block grid, so any
    # candidate tiles any array; block_k reorders the K-sum (parity
    # gate applies), block_m/block_n are exactly parity-preserving
    sweep={"block_m": (128, 256),
           "block_n": (128, 256, 512),
           "block_k": (128, 256, 512)},
)

# ===========================================================================
# delta_rule.py — the gated delta rule of ops/linear_attention.py (ISSUE
# 34), forward and backward.  Grid (batch, head_blocks, pairs, heads):
# one step = one PAIR of 64-token chunks of one head — 128 tokens, so a
# step's q, k, g tile is [128, 128], one lane tile square, and the pair's
# A, B and triangular inverse are block-diagonal [128, 128] matrices.
# q, k, v, g are read in place from [B, T, H, head_dim]: a block is the
# pair of ``heads`` = 8 heads (the sublanes of the array's own tiles; all
# H where 8 does not divide it), fetched once and held while the last
# grid axis walks its heads.  The pairs of a head run in sequence (the
# backward's in reverse) with the block's states [heads, head_dim,
# head_dim] float32 in scratch; beta comes twice, as a column and as a
# row of the pair.  Structural, no sweep:
# ``sub`` and the chunk (pair / 2) are the algorithm's, head_dim is one
# lane tile.  Padded: T, to a whole pair, with k = 0, beta = 0, g = 0.
# The declared blocks are not all a step holds: the backward keeps ~60
# live [128, 128] float32 temporaries a head (~4 MiB; the sixteen
# diagonals' exp(G_i - G_j) among them, shared by the recomputation and
# the gradient), ``together`` heads at once, so with float32 operands at
# eight heads a block it passes the default 16 MiB scoped limit and the
# kernels ask for ``vmem_limit_mib`` (the chip has 128).
# ===========================================================================
DELTA_RULE_FWD = KernelContract(
    name="delta_rule_fwd",
    module="paddle_tpu/ops/pallas_ops/delta_rule.py",
    grid=("batch", "head_blocks", "pairs", "heads"),
    dims={"pair": 128, "sub": 16, "heads": 8, "together": 2,
          "head_dim": 128, "lane": 128, "vmem_limit_mib": 48},
    blocks=(
        BlockDecl("q", "in", (1, "pair", "heads", "head_dim"),
                  "float32"),
        BlockDecl("k", "in", (1, "pair", "heads", "head_dim"),
                  "float32"),
        BlockDecl("v", "in", (1, "pair", "heads", "head_dim"),
                  "float32"),
        BlockDecl("g", "in", (1, "pair", "heads", "head_dim"),
                  "float32"),
        BlockDecl("beta_col", "in", (1, "together", "pair", 1), "float32",
                  lanes_full=True),
        BlockDecl("beta_row", "in", (1, "together", 1, "pair"), "float32",
                  sublane_full=True),
        BlockDecl("o", "out", (1, "pair", "heads", "head_dim"),
                  "float32"),
        # the state at the pair's start: the backward's residual
        BlockDecl("state_at", "out",
                  (1, "together", 1, "head_dim", "head_dim"), "float32"),
        BlockDecl("state", "scratch", ("heads", "head_dim", "head_dim"),
                  "float32"),
    ),
    shape_buckets={"pair": (1024, 2048, 4096, 8192)},
)

DELTA_RULE_BWD = KernelContract(
    name="delta_rule_bwd",
    module="paddle_tpu/ops/pallas_ops/delta_rule.py",
    grid=("batch", "head_blocks", "pairs", "heads"),
    dims={"pair": 128, "sub": 16, "heads": 8, "together": 2,
          "head_dim": 128, "lane": 128, "vmem_limit_mib": 48},
    blocks=(
        BlockDecl("q", "in", (1, "pair", "heads", "head_dim"),
                  "float32"),
        BlockDecl("k", "in", (1, "pair", "heads", "head_dim"),
                  "float32"),
        BlockDecl("v", "in", (1, "pair", "heads", "head_dim"),
                  "float32"),
        BlockDecl("g", "in", (1, "pair", "heads", "head_dim"),
                  "float32"),
        BlockDecl("beta_col", "in", (1, "together", "pair", 1), "float32",
                  lanes_full=True),
        BlockDecl("beta_row", "in", (1, "together", 1, "pair"), "float32",
                  sublane_full=True),
        BlockDecl("state_at", "in",
                  (1, "together", 1, "head_dim", "head_dim"), "float32"),
        BlockDecl("do", "in", (1, "pair", "heads", "head_dim"),
                  "float32"),
        BlockDecl("dq", "out", (1, "pair", "heads", "head_dim"),
                  "float32"),
        BlockDecl("dk", "out", (1, "pair", "heads", "head_dim"),
                  "float32"),
        BlockDecl("dv", "out", (1, "pair", "heads", "head_dim"),
                  "float32"),
        BlockDecl("dg", "out", (1, "pair", "heads", "head_dim"),
                  "float32"),
        BlockDecl("dbeta", "out", (1, "together", "pair", 1), "float32",
                  lanes_full=True),
        BlockDecl("dstate", "scratch", ("heads", "head_dim", "head_dim"),
                  "float32"),
    ),
    shape_buckets={"pair": (1024, 2048, 4096, 8192)},
)

# name -> contract, the registry the lint, the tests and (next) the
# autotuner iterate
CONTRACTS: Dict[str, KernelContract] = {
    c.name: c for c in (FLASH_FWD, FLASH_BWD_DKV, FLASH_BWD_DQ,
                        PAGED_RAGGED, PAGED_RAGGED_INT8,
                        PAGED_RAGGED_STATS, PAGED_KV_WRITE,
                        QUANTIZED_MATMUL, DELTA_RULE_FWD, DELTA_RULE_BWD)
}
