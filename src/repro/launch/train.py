"""Single-process training driver (real execution, host-scale).

Runs FedPAC/FedSOA federated pre-training of a (reduced or paper-scale) model
on synthetic non-IID LM data across whatever devices exist.  The production
mesh path is exercised by dryrun.py; this driver actually executes.

  PYTHONPATH=src python -m repro.launch.train --arch llama-60m --reduced \
      --algorithm fedpac_soap --rounds 20
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import configs
from repro.data import make_lm_corpus
from repro.data.synth import lm_batches
from repro.models import model as M
from repro.utils import hw


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-60m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--algorithm", default="fedpac_soap")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--participation", type=float, default=0.5)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--hetero", type=float, default=0.8)
    ap.add_argument("--beta", type=float, default=0.5)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=5)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="append a structured JSONL round trace (spans, "
                         "metrics, telemetry) to PATH")
    return ap.parse_args(argv)


def build(args, **fed_overrides):
    """(experiment, model config) for parsed ``args``: the synthetic non-IID
    LM task through ``repro.api.build_experiment``; ``fed_overrides`` are
    further ``FedConfig`` fields (``theta_codec=``, ``executor=``, ...)."""
    from repro.api import build_experiment

    cfg = (configs.get_reduced(args.arch)
           if args.reduced else configs.get_config(args.arch))
    cfg = cfg.replace(dtype="float32")
    params = M.init_params(cfg, jax.random.key(args.seed))

    streams = make_lm_corpus(args.clients, 200_000, vocab=cfg.vocab_size,
                             hetero=args.hetero, seed=args.seed)
    eval_stream = np.concatenate([s[:20_000] for s in streams])
    ex, ey = lm_batches(eval_stream, seq_len=args.seq, batch=16, steps=1,
                        seed=123)
    eval_batch = {"tokens": jnp.asarray(ex[0]), "labels": jnp.asarray(ey[0])}

    def loss_fn(p, batch):
        return M.loss_fn(p, batch, cfg)

    eval_loss = jax.jit(lambda p: M.loss_fn(p, eval_batch, cfg))

    def eval_fn(p):
        return {"eval_loss": eval_loss(p)}

    def batch_fn(cid, rng):
        s = streams[cid]
        starts = rng.integers(0, len(s) - args.seq - 1, args.batch)
        idx = starts[:, None] + np.arange(args.seq + 1)
        w = s[idx]
        return {"tokens": jnp.asarray(w[:, :-1]),
                "labels": jnp.asarray(w[:, 1:])}

    fed = dict(n_clients=args.clients, participation=args.participation,
               rounds=args.rounds, local_steps=args.local_steps, lr=args.lr,
               beta=args.beta, seed=args.seed)
    fed.update(fed_overrides)
    exp = build_experiment(args.algorithm, params=params, loss_fn=loss_fn,
                           client_batch_fn=batch_fn, eval_fn=eval_fn, **fed)
    return exp, cfg


def timed_round(exp):
    """One round; ``round_s`` is host time until the new params are ready
    (the first round includes compiling)."""
    t0 = time.perf_counter()
    rec = exp.run_round()
    jax.block_until_ready(exp.server.params)
    rec["round_s"] = time.perf_counter() - t0
    return rec


def main(argv=None):
    args = parse_args(argv)
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={len(jax.devices())}")
    hw.enable_compile_cache()
    exp, cfg = build(args)
    print(f"arch={cfg.name} params={M.num_params(cfg)/1e6:.1f}M "
          f"algorithm={args.algorithm}")
    mgr = None
    if args.checkpoint_dir:
        from repro.checkpoint import CheckpointManager
        mgr = CheckpointManager(args.checkpoint_dir)
    if args.trace:
        from repro.obs import JsonlSink, Tracer
        sink = JsonlSink(args.trace, append=True)
        state = None
        if mgr:
            # resume the persisted trace identity (same run_id, continued
            # seq numbering) so restored runs append to the same trace
            try:
                state = mgr.restore_meta().get("telemetry")
            except FileNotFoundError:
                pass
        exp.tracer = Tracer.from_state(state, sinks=(sink,))
    hist = []
    for r in range(args.rounds):
        rec = timed_round(exp)
        hist.append(rec)
        exp.log_round(rec, r)
        if mgr and (r + 1) % args.checkpoint_every == 0:
            mgr.save(exp.server, telemetry=exp.tracer.state())
    print(f"final: train_loss={hist[-1]['loss']:.4f} "
          f"eval_loss={hist[-1]['eval_loss']:.4f} "
          f"comm={exp.comm_bytes_per_round()/1e6:.1f}MB/round")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(hist, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
