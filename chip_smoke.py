"""Smoke run of federated FedPAC-SOAP training on TPU chips.

    python chip_smoke.py             # one chip: dense-Theta and qblock-Theta phases
    python chip_smoke.py --chips 4   # four chips: sharded cohort executors vs vmap

One chip: llama-60m at its published width (d_model 512, 8 layers, vocab
32000, random weights from a seed) trains ``fedpac_soap`` for 3 rounds of 8
clients at participation 0.5 and 5 local steps, first through the training
entry point ``repro.launch.train.main`` with dense Theta uploads, then through
``repro.api.build_experiment(..., theta_codec="qblock")``, whose rounds run
the ``qblock`` and ``fused_agg`` Pallas kernels.  Every round's ``loss`` and
``eval_loss`` must be finite, and the qblock round must hold compiled Pallas
kernels (``tpu_custom_call``).

Four chips: the same model's rounds with a cohort of 4 over the 4-device
``("data",)`` mesh under ``executor="shard_map"`` and ``executor="sharded"``,
held to ``executor="vmap"`` on one device at ``rtol=1e-5``.

The last line of standard output is ``{"ok": true, "device": {...}}``.  The
script refuses any platform but ``tpu`` and catches no phase's exception.
Everything runs in this one process, which holds the chips.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.utils import hw  # noqa: E402

ARCH = "llama-60m"
ROUNDS = 3
MESH_ROUNDS = 2
MESH_EXECUTORS = ("vmap", "shard_map", "sharded")
MESH_RTOL = 1e-5


def train_argv(arch: str, reduced: bool, rounds: int) -> list:
    argv = ["--arch", arch, "--algorithm", "fedpac_soap", "--clients", "8",
            "--participation", "0.5", "--local-steps", "5",
            "--rounds", str(rounds)]
    return argv + ["--reduced"] if reduced else argv


def check_finite(phase: str, hist) -> None:
    for rec in hist:
        for key in ("loss", "eval_loss"):
            if not math.isfinite(rec[key]):
                raise FloatingPointError(
                    f"{phase} round {rec['round']}: {key}={rec[key]}")


def _abstract(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def round_hlo(exp, server, args) -> str:
    """Lowered text of one round of ``exp`` at the shapes of ``server`` and
    the round's other ``args`` (cohort state, slots, batches, key)."""
    def one_round(params, theta, g_global, geom, args):
        srv = dataclasses.replace(server, params=params, theta=theta,
                                  g_global=g_global, geom=geom)
        new, cstate, metrics = exp.round_fn(srv, *args)
        return new.params, cstate, metrics

    shapes = jax.tree.map(_abstract, (server.params, server.theta,
                                      server.g_global, server.geom, args))
    return jax.jit(one_round).lower(*shapes).as_text()


def phase_dense(arch: str = ARCH, reduced: bool = False,
                rounds: int = ROUNDS) -> list:
    """Dense-Theta rounds through ``repro.launch.train.main``."""
    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "history.json")
        rc = train.main(train_argv(arch, reduced, rounds) + ["--out", out])
        if rc != 0:
            raise RuntimeError(f"repro.launch.train.main returned {rc}")
        with open(out) as f:
            hist = json.load(f)
    check_finite("dense", hist)
    return hist


def phase_qblock(arch: str = ARCH, reduced: bool = False,
                 rounds: int = ROUNDS):
    """qblock-Theta rounds through ``build_experiment``; also returns how
    the Pallas kernels ran: interpret mode or not, and the number of
    ``tpu_custom_call`` sites in the lowered round."""
    args = train.parse_args(train_argv(arch, reduced, rounds))
    exp, _ = train.build(args, theta_codec="qblock")
    round_fn, last = exp.round_fn, {}

    def recording(server, *rest):
        last["server"] = server
        last["args"] = jax.tree.map(_abstract, rest)
        return round_fn(server, *rest)

    exp.round_fn = recording
    hist = [train.timed_round(exp) for _ in range(rounds)]
    exp.round_fn = round_fn
    check_finite("qblock", hist)
    hlo = round_hlo(exp, last["server"], last["args"])
    kernels = {"interpret": hw.resolve_interpret(),
               "tpu_custom_call": hlo.count("tpu_custom_call")}
    return hist, kernels


def phase_cohort_mesh(arch: str = ARCH, reduced: bool = False,
                      rounds: int = MESH_ROUNDS) -> dict:
    """The same rounds under each cohort executor: ``shard_map`` and
    ``sharded`` spread the cohort over every device's ``("data",)`` mesh,
    ``vmap`` keeps it on one device."""
    hists = {}
    for executor in MESH_EXECUTORS:
        args = train.parse_args(train_argv(arch, reduced, rounds))
        exp, _ = train.build(args, executor=executor)
        hists[executor] = [train.timed_round(exp) for _ in range(rounds)]
        del exp
        check_finite(executor, hists[executor])
    return hists


def compare_executors(hists: dict, rtol: float = MESH_RTOL) -> None:
    """Every executor's round losses must match ``vmap``'s."""
    for executor in MESH_EXECUTORS[1:]:
        for key in ("loss", "eval_loss"):
            np.testing.assert_allclose(
                [r[key] for r in hists[executor]],
                [r[key] for r in hists["vmap"]], rtol=rtol,
                err_msg=f"{executor} vs vmap: {key}")


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def report(phase: str, hist) -> None:
    for rec in hist:
        print(f"{phase} round {rec['round']}: loss={rec['loss']!r} "
              f"eval_loss={rec['eval_loss']!r} round_s={rec['round_s']!r}")
    later = [rec["round_s"] for rec in hist[1:]]
    print(f"{phase}: first_round_s={hist[0]['round_s']!r} (includes compile) "
          f"later_round_s_median="
          f"{statistics.median(later) if later else None!r} "
          f"peak_bytes_in_use={peak_bytes()!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded cohort executors against "
                         "vmap on a 4-device mesh")
    opts = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
            f"({dev.device_kind}); refusing to run on it")
    if opts.chips == 4 and len(devices) != 4:
        raise SystemExit(
            f"chip_smoke --chips 4: needs 4 devices, found {len(devices)}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={hw.enable_compile_cache()}")
    print(f"model: {ARCH} params={M.num_params(configs.get_config(ARCH))}")

    if opts.chips == 4:
        hists = phase_cohort_mesh()
        for executor, hist in hists.items():
            report(f"mesh[{executor}]", hist)
        compare_executors(hists)
        print(f"mesh: shard_map and sharded agree with vmap at "
              f"rtol={MESH_RTOL} over {MESH_ROUNDS} rounds")
    else:
        report("dense", phase_dense())
        hist, kernels = phase_qblock()
        report("qblock", hist)
        print(f"kernels: interpret={kernels['interpret']} "
              f"tpu_custom_call={kernels['tpu_custom_call']}")
        if kernels["interpret"] or not kernels["tpu_custom_call"]:
            raise SystemExit("chip_smoke: the qblock round holds no "
                             "compiled Pallas kernel")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
