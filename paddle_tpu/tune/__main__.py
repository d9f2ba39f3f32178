"""``python -m paddle_tpu.tune`` — sweep / show / verify.

    # measure every runnable kernel at its default bench buckets and
    # commit the winners (atomic; a kill never corrupts a prior table)
    python -m paddle_tpu.tune sweep --table tuning_table.ptt

    # one kernel at an explicit bucket, with a parity tolerance
    python -m paddle_tpu.tune sweep --table t.ptt \\
        --kernel quantized_matmul --extent block_m=128,block_k=512,block_n=512 \\
        --repeats 5 --atol 1e-6

    # audit what a table would make the kernels do
    python -m paddle_tpu.tune show --table tuning_table.ptt

    # strict gate: schema + CRC + validate() + re-measured parity; exit
    # nonzero on ANY problem (CI; `show` never fails, `verify` does)
    python -m paddle_tpu.tune verify --table tuning_table.ptt

Exit codes: 0 ok, 1 verification failure / corrupt table, 2 usage.
"""
from __future__ import annotations

import argparse
import sys
from typing import Dict, List

from ..framework.errors import (TuningTableCorruptError,
                                TuningTableIncompatibleError)
from ..ops.pallas_ops.contracts import CONTRACTS
from .runners import RUNNERS
from .search import (bucket_key, candidate_contract, shape_bucket,
                     sweep_kernel)
from .table import TUNE_SCHEMA_VERSION, TuningTable

# the default per-kernel bench buckets `sweep` measures when no
# --extent is given — small enough for interpret mode on CPU, shaped
# like the serving/bench workloads on TPU
DEFAULT_EXTENTS: Dict[str, List[Dict[str, int]]] = {
    "quantized_matmul": [
        {"block_m": 128, "block_k": 256, "block_n": 256},
    ],
    "flash_attention_fwd": [
        {"block_q": 1024, "block_k": 1024},
    ],
    # grad-path pair (ISSUE 18): one extent covers every sweep
    # candidate (2048 is divisible by all declared block_q/block_k)
    "flash_attention_bwd_dkv": [
        {"block_q": 2048, "block_k": 2048},
    ],
    "flash_attention_bwd_dq": [
        {"block_q": 2048, "block_k": 2048},
    ],
    "paged_attention_ragged": [
        {"heads": 8, "head_dim": 128},
    ],
    "paged_attention_ragged_int8": [
        {"heads": 8, "head_dim": 128},
    ],
}
_KERNEL_DTYPE = {"paged_attention_ragged_int8": "int8",
                 "quantized_matmul": "int8_weights"}


def _parse_extent(text: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for part in text.split(","):
        sym, _, v = part.partition("=")
        if not sym or not v:
            raise SystemExit(2)
        out[sym.strip()] = int(v)
    return out


def _dtype_for(kernel: str) -> str:
    return _KERNEL_DTYPE.get(kernel, "float32")


def _cmd_sweep(args) -> int:
    kernels = args.kernel or sorted(RUNNERS)
    table, reason = TuningTable.load_or_default(args.table)
    if reason not in (None, "missing"):
        print(f"note: existing table unusable ({reason}) — "
              "starting fresh")
        table = TuningTable(args.table)
    for name in kernels:
        if name not in CONTRACTS:
            print(f"unknown kernel {name!r} (contracts: "
                  f"{sorted(CONTRACTS)})")
            return 2
        if name not in RUNNERS:
            print(f"{name}: no sweep runner (axes declared: "
                  f"{dict(CONTRACTS[name].sweep)}) — skipped")
            continue
        extents_list = ([_parse_extent(args.extent)] if args.extent
                        else DEFAULT_EXTENTS.get(name, []))
        for extents in extents_list:
            rep = sweep_kernel(name, extents, dtype=_dtype_for(name),
                               repeats=args.repeats, atol=args.atol,
                               table=table)
            measured = [r for r in rep.results if r.measured]
            pruned = [r for r in rep.results
                      if r.rejected and r.rejected.startswith(
                          "validate")]
            parity = [r for r in rep.results
                      if r.rejected and r.rejected.startswith("parity")]
            print(f"{name} @ {rep.bucket}: {len(rep.results)} "
                  f"candidates ({len(pruned)} pruned, {len(parity)} "
                  f"parity-rejected, {len(measured)} measured) -> "
                  f"winner {rep.winner.choice} "
                  f"{rep.winner.wall_ms:.3f} ms "
                  f"(default {rep.default_ms:.3f} ms, "
                  f"speedup {rep.speedup_x:.2f}x)")
    path = table.save(args.table)
    print(f"committed {len(table)} entr{'y' if len(table) == 1 else 'ies'}"
          f" to {path}")
    return 0


def _cmd_show(args) -> int:
    table, reason = TuningTable.load_or_default(args.table)
    if reason is not None:
        print(f"{args.table}: FALLBACK to contract defaults ({reason})")
        return 0 if reason == "missing" else 1
    print(f"{args.table}: schema <= {TUNE_SCHEMA_VERSION}, "
          f"{len(table)} entries")
    for key, entry in table.entries():
        kernel, bucket, dtype, platform = key.split("|")
        tag = "default" if entry.get("is_default") else "TUNED"
        print(f"  {kernel} @ {bucket} [{dtype}/{platform}] {tag} "
              f"dims={entry['dims']} best={entry.get('best_ms')}ms "
              f"default={entry.get('default_ms')}ms "
              f"speedup={entry.get('speedup_x')}x")
    return 0


def _cmd_verify(args) -> int:
    try:
        table = TuningTable.load(args.table)
    except (TuningTableCorruptError, TuningTableIncompatibleError) as e:
        print(f"FAIL {args.table}: {type(e).__name__}: {e}")
        return 1
    failures = 0
    for key, entry in table.entries():
        kernel, bucket, dtype, platform = key.split("|")
        contract = CONTRACTS.get(kernel)
        if contract is None:
            print(f"FAIL {key}: unknown kernel")
            failures += 1
            continue
        try:
            extents = _parse_extent(bucket)
        except (ValueError, SystemExit):
            # a malformed bucket key is a verification FAILURE, not a
            # usage error — the gate must report it, not die on it
            print(f"FAIL {key}: malformed bucket key {bucket!r}")
            failures += 1
            continue
        try:
            dims = {str(k): int(v)
                    for k, v in dict(entry.get("dims") or {}).items()}
            if not dims:
                raise ValueError("empty")
        except (TypeError, ValueError):
            print(f"FAIL {key}: missing or non-numeric dims")
            failures += 1
            continue
        violations = candidate_contract(
            contract, dims, shape_bucket(contract, extents)).validate()
        if violations:
            print(f"FAIL {key}: validate(): {'; '.join(violations)}")
            failures += 1
            continue
        if bucket_key(contract, extents) != bucket:
            print(f"FAIL {key}: bucket is not canonical")
            failures += 1
            continue
        if not args.no_run and kernel in RUNNERS:
            rep = sweep_kernel(kernel, extents, dtype=dtype,
                               repeats=1, atol=args.atol,
                               platform=platform)
            match = next((r for r in rep.results
                          if r.choice == dims), None)
            if match is None or not match.measured:
                why = match.rejected if match else \
                    "dims not in the declared search space"
                print(f"FAIL {key}: {why}")
                failures += 1
                continue
        print(f"ok   {key}: dims={dims}")
    if failures:
        print(f"{failures} entr{'y' if failures == 1 else 'ies'} failed")
        return 1
    print(f"all {len(table)} entries verified")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.tune",
        description="Pallas kernel autotuner — contract-gated config "
                    "search over a persistent tuning table")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p_sweep = sub.add_parser("sweep", help="measure + commit winners")
    p_sweep.add_argument("--kernel", action="append",
                         help="contract name (repeatable; default: "
                              "every runnable kernel)")
    p_sweep.add_argument("--extent", default=None,
                         help="sym=v,sym=v shape extents (default: the "
                              "kernel's bench buckets)")
    p_sweep.add_argument("--repeats", type=int, default=3)
    p_sweep.add_argument("--atol", type=float, default=0.0,
                         help="parity tolerance vs the default config's "
                              "output (default 0.0 = bit-identical)")
    p_show = sub.add_parser("show", help="print table entries")
    p_verify = sub.add_parser("verify",
                              help="strict integrity + parity gate")
    p_verify.add_argument("--no-run", action="store_true",
                          help="skip the re-measured parity check")
    p_verify.add_argument("--atol", type=float, default=0.0)
    for p in (p_sweep, p_show, p_verify):
        p.add_argument("--table", default="tuning_table.ptt",
                       help="table path (default tuning_table.ptt)")
    args = ap.parse_args(argv)
    return {"sweep": _cmd_sweep, "show": _cmd_show,
            "verify": _cmd_verify}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
