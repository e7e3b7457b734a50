"""Chip benchmark of federated FedPAC-SOAP rounds on a TPU.

The harness is driven by data.  ``BENCHMARK.json`` at the repository root
names each cell (one model configuration under one traffic mix); every
configuration, mix, per-layer metric and per-cell correctness limit lives
in a file of its own under ``benchmarks/chip/``, found by its name:

  configs/<config>.json    published sizes, what was cut, the deployment
  traffic/<mix>.json       algorithm, cohort, local steps, batch, data shape
  metrics/<metric>.py      one per-layer metric reader: ``read(ctx)``
  limits/<cell>.json       the limit of each number ``correct`` compares
  references/<family>.py   plain reference model of a configuration family
"""
