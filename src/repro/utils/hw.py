"""Backend detection shared by every Pallas dispatch site.

One auto rule, defined once: real Pallas kernels on TPU, the interpreter
(or the jnp reference path) everywhere else.  Transport codecs
(``TransportConfig``/``QBlock``) and the algorithm-level transport
factory resolve their ``use_pallas`` / ``interpret`` defaults here, so an
accelerator host never silently runs the reference path just because a
caller left the knobs at their CPU defaults.

It also owns where compiled programs are cached between processes
(``enable_compile_cache``), so every entry point shares one cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: a fixed path, because the cache key includes it
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def default_use_pallas() -> bool:
    """Pallas kernels by default on TPU; jnp reference elsewhere."""
    return on_tpu()


def default_interpret() -> bool:
    """Interpret-mode Pallas off-TPU (CPU validation), compiled on TPU."""
    return not on_tpu()


def resolve_interpret(interpret=None) -> bool:
    """``None`` means auto; explicit booleans pass through."""
    return default_interpret() if interpret is None else bool(interpret)


def resolve_use_pallas(use_pallas=None) -> bool:
    """``None`` means auto; explicit booleans pass through."""
    return default_use_pallas() if use_pallas is None else bool(use_pallas)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache lives
    at ``<checkout>/.jax_cache``.  Entry points call this from ``main()``,
    never at import, so tests and library users keep JAX's own default.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CHECKOUT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
