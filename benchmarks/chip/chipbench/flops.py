"""Operations and bytes, computed from shapes, for the MFU and the rooflines.

Model FLOPs per sample come from the configuration's family
(``references/<family>.py: flops_per_sample``, derivation in its docstring);
this module turns them into a round's work and gives the wire kernels'
envelopes.  Kernel envelopes count what the algorithm needs, not what a
kernel happens to move: the unpadded elements, read once and written once.
They replace the coarse envelopes of ``repro.obs.profiling``, which count
four operations per element for ``qblock`` and leave the scales out of
``fused_agg``'s multiply count.

qblock, one leaf of n float32 elements quantized in blocks of ``block``
(nb = ceil(n / block) blocks), per client:
  operations  6 n   (|x|, running max, divide by the scale, round, clip at
                     both ends; the nb scale divisions are not counted)
  bytes       4 n read + n int8 written + 4 nb scales written

fused_agg, one leaf accumulated from S clients' int8 blocks:
  operations  2 S n + S nb   (multiply by the folded scale and add, per
                              element; fold weight into scale, per block)
  bytes       S n int8 + 4 S nb scales read + 4 n float32 written
"""
from __future__ import annotations

import math


def clients_per_round(traffic: dict) -> int:
    """The synchronous runtime's cohort size: round(N * participation)."""
    return max(1, int(round(traffic["n_clients"] * traffic["participation"])))


def round_model_flops(ref, cfg: dict, traffic: dict) -> float:
    """Forward and backward model FLOPs of every local step in a round."""
    per_step = ref.samples_per_step(traffic) * ref.flops_per_sample(
        cfg, traffic)
    return clients_per_round(traffic) * traffic["local_steps"] * per_step


def qblock_work(n: int, block: int, clients: int) -> tuple:
    nb = math.ceil(n / block)
    return 6.0 * n * clients, float(clients * (4 * n + n + 4 * nb))


def fused_agg_work(n: int, block: int, clients: int) -> tuple:
    nb = math.ceil(n / block)
    return (float(2 * clients * n + clients * nb),
            float(clients * n + 4 * clients * nb + 4 * n))


KERNEL_WORK = {"qblock": qblock_work, "fused_agg": fused_agg_work}


def least_seconds(flops: float, nbytes: float, peaks) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    t_compute = flops / peaks.bf16_flops
    t_memory = nbytes / peaks.hbm_bytes_s
    return (t_memory, "memory") if t_memory >= t_compute else (
        t_compute, "compute")


def kernel_least_seconds(kernel: str, leaf_sizes, block: int, clients: int,
                         peaks) -> float:
    """Least time of one call of ``kernel`` on every leaf of a round."""
    work = KERNEL_WORK[kernel]
    return sum(least_seconds(*work(n, block, clients), peaks)[0]
               for n in leaf_sizes)
