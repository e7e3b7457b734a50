"""Pluggable cohort executors: how one round's S clients map onto devices.

``make_cohort_executor`` returns ``run(one_client, *stacked_args)`` where
``one_client(batch_i, key_i, ...)`` is a single client's round and every
arg carries a leading (S,) client axis.  Three backends:

  vmap       one fused batched program — the default, fastest when the whole
             cohort fits one device's memory;
  shard_map  shards the client axis over the mesh's ("pod","data") axes
             (``sharding.partitioning.client_axis_spec``), realizing the
             paper's linear speedup in S: each device group trains S/n
             clients and the engine's aggregation means lower to
             all-reduces;
  chunked    sequential ``lax.map`` over cohort chunks of ``chunk_size``,
             so cohorts larger than device memory still run (peak memory
             scales with the chunk, wall clock with S/chunk_size);
  sharded    shard_map over the mesh *with the chunked body inside each
             shard*: the population-scale path. A 10k cohort splits S/n
             ways across device groups and each group scans its slice in
             ``chunk_size`` pieces, so peak memory per device is
             chunk-proportional while throughput still scales with the
             mesh.

All backends produce numerically equivalent stacked outputs (tested); pick
by cohort size vs device budget — ``benchmarks/executor_scaling.py`` sweeps
the trade-off.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

BACKENDS = ("vmap", "shard_map", "chunked", "sharded")


@dataclasses.dataclass(frozen=True)
class ExecutorConfig:
    backend: str = "vmap"
    chunk_size: int = 8                  # chunked: clients per scan step
    mesh: Optional[Any] = None           # shard_map: None -> all local devices
    client_axes: tuple = ("pod", "data")  # mesh axes to shard clients over

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown executor backend {self.backend!r} "
                f"(want one of {BACKENDS})")
        if self.chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {self.chunk_size}")


def _leading_dim(args) -> int:
    return jax.tree.leaves(args)[0].shape[0]


@functools.lru_cache(maxsize=None)
def _default_mesh():
    # the local device set is fixed for the process lifetime, so the mesh
    # is too — rebuilding it per executor call only burned host time
    return jax.make_mesh((len(jax.devices()),), ("data",),
                         axis_types=(AxisType.Auto,))


def _chunked_run(one_client, chunk_size: int, *args):
    """Bounded-memory sequential ``lax.map`` over cohort slices.

    A cohort that is not a chunk multiple pads with replicas of its first
    rows (pad < c <= s always holds) and the padded outputs are dropped,
    so every cohort size runs through ONE compiled chunk body — the old
    separate vmap tail compiled a fresh program for every distinct
    remainder shape."""
    s = _leading_dim(args)
    c = min(chunk_size, s)
    n = -(-s // c)
    pad = n * c - s
    if pad:
        args = jax.tree.map(
            lambda x: jnp.concatenate([x, x[:pad]], axis=0), args)
    chunks = jax.tree.map(lambda x: x.reshape(n, c, *x.shape[1:]), args)
    out = jax.lax.map(lambda a: jax.vmap(one_client)(*a), chunks)
    out = jax.tree.map(lambda x: x.reshape(n * c, *x.shape[2:]), out)
    if pad:
        out = jax.tree.map(lambda x: x[:s], out)
    return out


def _make_shard_runner(cfg: ExecutorConfig, shard_body_of):
    """shard_map plumbing shared by the ``shard_map`` and ``sharded``
    backends; ``shard_body_of(one_client)`` is what runs on each device
    group's slice of the client axis."""
    from repro.sharding.partitioning import client_axis_spec

    def run(one_client, *args):
        mesh = cfg.mesh if cfg.mesh is not None else _default_mesh()
        axes, spec = client_axis_spec(mesh, preferred=cfg.client_axes)
        n = math.prod(mesh.shape[a] for a in axes)
        s = _leading_dim(args)
        if s % n != 0:
            raise ValueError(
                f"cohort size {s} not divisible by the client-axis "
                f"extent {n} (mesh axes {axes}) — pad the cohort or "
                f"use the 'chunked' executor")
        return jax.shard_map(shard_body_of(one_client), mesh=mesh,
                             in_specs=(spec,) * len(args), out_specs=spec,
                             check_vma=False)(*args)
    return run


def make_cohort_executor(cfg: Optional[ExecutorConfig] = None):
    cfg = cfg or ExecutorConfig()

    if cfg.backend == "vmap":
        def run(one_client, *args):
            return jax.vmap(one_client)(*args)
        return run

    if cfg.backend == "shard_map":
        return _make_shard_runner(
            cfg, lambda one_client: lambda *a: jax.vmap(one_client)(*a))

    if cfg.backend == "sharded":
        # population-scale path: each device group scans its cohort slice in
        # chunk_size pieces — peak memory ~ chunk, throughput ~ mesh
        return _make_shard_runner(
            cfg, lambda one_client:
            lambda *a: _chunked_run(one_client, cfg.chunk_size, *a))

    # chunked: bounded-memory sequential scan over cohort slices
    def run(one_client, *args):
        return _chunked_run(one_client, cfg.chunk_size, *args)
    return run
