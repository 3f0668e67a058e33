"""Kernel-backend selection and compile-cache placement, shared by every
subsystem.

Three interchangeable, bit-identical lowerings exist for the PPAC ops:
'pallas' (the real TPU kernels; interpret mode off-TPU), 'ref' (jnp
oracles) and 'mxu' (int8 dot-product lowering — the fast path on CPU).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed directory inside the checkout (git-ignored): a cache whose path
# moves between runs never hits
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def auto_backend() -> str:
    """Native Pallas on TPU, the MXU lowering everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "mxu"


def resolve_backend(backend: str) -> str:
    return auto_backend() if backend == "auto" else backend


def auto_interpret() -> bool:
    """Pallas kernels run in interpret mode off-TPU."""
    return jax.default_backend() != "tpu"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`. Call once, before the first compile.
    """
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
