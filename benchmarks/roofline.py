"""Roofline report generator.

Reads dry-run JSONL records and renders the per-(arch x shape x mesh) table
for EXPERIMENTS.md §Roofline:

    PYTHONPATH=src python -m benchmarks.roofline \\
        results/dryrun_baseline.jsonl

Kernel roofline shares on the chip come from the device trace
(``benchmarks/chip``).
"""
from __future__ import annotations

import argparse
import json
import sys


def load(path):
    recs = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                recs.append(json.loads(line))
    # last record per key wins (reruns append)
    dedup = {}
    for r in recs:
        dedup[(r["arch"], r["shape"], r["mesh"], r.get("step"),
               r.get("seq_shard", False), r.get("opt"))] = r
    return list(dedup.values())


def fmt_bytes(n):
    if n is None:
        return "-"
    for unit in ["B", "KB", "MB", "GB", "TB"]:
        if abs(n) < 1024:
            return f"{n:.1f}{unit}"
        n /= 1024
    return f"{n:.1f}PB"


def what_moves(rec):
    d = rec["dominant"]
    if d == "compute":
        return "lower-precision matmuls / fewer remat recomputes"
    if d == "memory":
        if rec["shape"].startswith("decode") or rec["shape"] == "long_500k":
            return "shrink KV-cache reads (quantized cache, MLA/ring buffer)"
        return "fuse elementwise chains; cut remat traffic (seq-sharding)"
    return "overlap collectives with compute; 2D-shard to cut all-gathers"


def table(recs, mesh="pod"):
    rows = [r for r in recs if r["mesh"] == mesh]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    hdr = ("| arch | shape | t_comp (s) | t_mem (s) | t_coll (s) | dominant "
           "| MODEL_FLOPS | useful ratio | peak/dev |")
    sep = "|" + "---|" * 9
    out = [hdr, sep]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute']:.3e} "
            f"| {r['t_memory']:.3e} | {r['t_collective']:.3e} "
            f"| **{r['dominant']}** | {r['model_flops_total']:.2e} "
            f"| {r['useful_flop_ratio']:.2f} "
            f"| {fmt_bytes(r['bytes_per_device']['peak'])} |")
    return "\n".join(out)


def report(path):
    recs = load(path)
    print(table(recs, "pod"))
    print()
    print("### Per-pair bottleneck notes")
    for r in sorted(recs, key=lambda r: (r["arch"], r["shape"])):
        if r["mesh"] != "pod":
            continue
        print(f"- {r['arch']} x {r['shape']}: dominant={r['dominant']}; "
              f"to improve: {what_moves(r)}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("path", nargs="?", default="results/dryrun_baseline.jsonl",
                    help="dry-run JSONL records")
    args = ap.parse_args(argv)
    return report(args.path)


if __name__ == "__main__":
    sys.exit(main())
