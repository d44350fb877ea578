"""``python -m tools.ckpt`` — the sharded-checkpoint operator CLI.

Three subcommands over one checkpoint directory
(``distributed.checkpoint.sharded`` manifest format):

- **ls**:      one row per tensor — shape, dtype, partition spec, piece
               count, bytes — plus totals and orphan/tmp droppings;
- **verify**:  integrity + completeness pass (manifest parse, per-piece
               byte count and sha256, bounds/overlap/coverage). Exits
               **non-zero on any corrupt, truncated or missing piece**
               — the CI hook: a checkpoint that would refuse to load at
               restore/hot-swap time fails loudly here instead;
- **convert**: rewrite a checkpoint under a new float dtype
               (``--dtype bfloat16``: fp32 training checkpoint → a
               half-size bf16 serving checkpoint), piece by piece at
               O(largest piece) host residency, atomic publish.

``--json`` on every subcommand prints one machine-readable object.
Exit codes: 0 ok, 1 verify found problems (or the path is not a
checkpoint), 2 convert failed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def cmd_ls(ckpt_dir: str, as_json: bool) -> int:
    from paddle_tpu.distributed.checkpoint.sharded import read_manifest
    from paddle_tpu.distributed.checkpoint.sharded.manifest import (
        PIECE_SUFFIX, TMP_PREFIX)

    try:
        man = read_manifest(ckpt_dir)
    except (FileNotFoundError, ValueError) as e:
        print(json.dumps({"dir": ckpt_dir, "error": str(e)})
              if as_json else f"tools.ckpt: {e}")
        return 1
    rows = []
    total_bytes = 0
    total_pieces = 0
    referenced = set()
    for name, entry in man["entries"].items():
        nbytes = sum(int(p["bytes"]) for p in entry["pieces"])
        total_bytes += nbytes
        total_pieces += len(entry["pieces"])
        referenced.update(p["file"] for p in entry["pieces"])
        rows.append({"tensor": name, "shape": entry["shape"],
                     "dtype": entry["dtype"], "spec": entry.get("spec"),
                     "pieces": len(entry["pieces"]), "bytes": nbytes})
    orphans = [f for f in sorted(os.listdir(ckpt_dir))
               if (f.endswith(PIECE_SUFFIX) and f not in referenced)
               or f.startswith(TMP_PREFIX)]
    payload = {"dir": ckpt_dir, "n_tensors": len(rows),
               "n_pieces": total_pieces, "bytes": total_bytes,
               "entries": rows, "orphans": orphans}
    if as_json:
        print(json.dumps(payload, indent=2))
    else:
        print(f"{ckpt_dir}: {len(rows)} tensor(s), {total_pieces} "
              f"piece(s), {total_bytes}B")
        for r in rows:
            spec = f" spec={r['spec']}" if r.get("spec") else ""
            print(f"  {r['tensor']:<40} {str(r['shape']):<16} "
                  f"{r['dtype']:<10} x{r['pieces']:<3} {r['bytes']:>10}B"
                  + spec)
        for o in orphans:
            print(f"  ORPHAN  {o}")
    return 0


def cmd_verify(ckpt_dir: str, as_json: bool, deep: bool = True) -> int:
    """Integrity + completeness pass. Non-zero exit on ANY corrupt,
    truncated or missing piece (the CI hook)."""
    from paddle_tpu.distributed.checkpoint.sharded import verify_dir

    problems = verify_dir(ckpt_dir, deep=deep)
    n_entries = 0
    try:
        from paddle_tpu.distributed.checkpoint.sharded import read_manifest

        n_entries = len(read_manifest(ckpt_dir).get("entries", {}))
    except (FileNotFoundError, ValueError):
        pass
    # orphans are hygiene, not restorability — they warn, never gate
    gating = [p for p in problems if p["kind"] != "orphan"]
    if as_json:
        print(json.dumps({"dir": ckpt_dir, "tensors": n_entries,
                          "problems": problems,
                          "ok": not gating}, indent=2))
    else:
        for p in problems:
            where = " / ".join(str(x) for x in (p.get("tensor"),
                                                p.get("piece")) if x)
            print(f"BAD  [{p['kind']}] {where}: {p['problem']}")
        print(f"tools.ckpt verify: {n_entries} tensor(s), "
              f"{len(problems)} problem(s)"
              + ("" if not problems else
                 f" ({len(gating)} gating, "
                 f"{len(problems) - len(gating)} hygiene)"))
    return 1 if gating else 0


def cmd_convert(src: str, dst: str, dtype: str, as_json: bool,
                overwrite: bool) -> int:
    from paddle_tpu.distributed.checkpoint.sharded import convert_sharded

    try:
        report = convert_sharded(src, dst, dtype=dtype, overwrite=overwrite)
    except Exception as e:
        print(json.dumps({"src": src, "dst": dst, "error": str(e)})
              if as_json else f"tools.ckpt convert FAILED: {e}")
        return 2
    if as_json:
        print(json.dumps(report, indent=2))
    else:
        print(f"tools.ckpt convert: {report['n_tensors']} tensor(s) "
              f"({report['n_cast']} cast to {dtype}), "
              f"{report['bytes_in']}B -> {report['bytes_out']}B")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.ckpt",
        description="operate sharded checkpoints "
                    "(paddle_tpu.distributed.checkpoint.sharded): "
                    "list, verify, convert")
    parser.add_argument("command", choices=("ls", "verify", "convert"))
    parser.add_argument("dir", help="checkpoint directory")
    parser.add_argument("dst", nargs="?", default=None,
                        help="convert: destination directory")
    parser.add_argument("--dtype", default="bfloat16",
                        help="convert: target float dtype "
                             "(default: bfloat16)")
    parser.add_argument("--overwrite", action="store_true",
                        help="convert: replace an existing destination")
    parser.add_argument("--shallow", action="store_true",
                        help="verify: skip the per-piece sha256 pass "
                             "(byte counts and coverage still checked)")
    parser.add_argument("--json", action="store_true", dest="as_json")
    args = parser.parse_args(argv)

    if args.command == "convert":
        if not args.dst:
            parser.error("convert needs a destination directory")
        return cmd_convert(args.dir, args.dst, args.dtype, args.as_json,
                           args.overwrite)
    if not os.path.isdir(args.dir):
        print(json.dumps({"dir": args.dir, "error": "no such directory"})
              if args.as_json else
              f"tools.ckpt: {args.dir}: no such directory")
        return 1
    if args.command == "ls":
        return cmd_ls(args.dir, args.as_json)
    return cmd_verify(args.dir, args.as_json, deep=not args.shallow)


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
