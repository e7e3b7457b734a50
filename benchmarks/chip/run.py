"""Chip benchmark of federated training rounds: one run of one cell.

A cell runs the algorithm its traffic mix names (``fedpac_soap``,
``fedavg``, ...), its local optimizer set from the mix's block named after
that optimizer (``soap``, ``sgd``, ...).

    python3 benchmarks/chip/run.py --workload vit_s16.c8_k10_qblock \\
        --seed 1234 --seconds 20 --trace 0

Run from the root of a checkout, on a machine that holds the chips the cell
asks for.  It refuses to run without a TPU (exit 2, no result line) and
never falls back to the CPU.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last the
``checks``: each number the correctness comparison holds to its limit.  The
same checks are the last lines of standard error.

Control runs, which have to print ``correct: false``:

    python3 benchmarks/chip/run.py ... --precision high
    python3 benchmarks/chip/run.py ... --fault half_batch

``--precision`` runs the program's matrix products at another precision than
the configuration states; ``--fault`` plants a named fault
(``chipbench/faults.py``) under the timed path.  Neither is a benchmark run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(REPO, "src"))

from chipbench.faults import FAULTS  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True,
                    help="a cell named in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window and report the per-layer "
                         "metrics")
    ap.add_argument("--precision", choices=("default", "high", "highest"),
                    help="control run: the program's products at this "
                         "precision, not the configuration's")
    ap.add_argument("--fault", choices=sorted(FAULTS),
                    help="control run: plant this fault under the timed "
                         "path")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from chipbench import harness, spec
    cell = spec.load_cell(args.workload, repo_root=REPO)
    harness.enable_cache(os.path.join(REPO, ".jax_cache"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chip benchmark: {args.workload} needs {cell.chips} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s) ({devices[0].device_kind})", file=sys.stderr)
        return 2
    counter = harness.CompileEvents().install()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} cell={cell.name} seed={args.seed} "
          f"precision={args.precision or 'as configured'} "
          f"fault={args.fault}", flush=True)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              counter=counter, precision=args.precision,
                              fault=args.fault)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
