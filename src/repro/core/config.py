"""Numeric configuration for the PGF engine.

The PGF engine (the paper's contribution) is precision-sensitive: products over
millions of per-tuple factors and 8th-order cumulant sums want float64 on CPU.
The LM stack targets bf16/f32 on TPU and passes dtypes explicitly, so the two
subsystems never fight over a global default.

``default_float()`` returns float64 when the host has x64 enabled (tests and
CPU benchmarks enable it via ``enable_x64()``), else float32 (the TPU target,
where the distributed query step runs with the f32 log-CF kernel).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

#: The checkout root (``src/repro/core/config.py`` is three levels down).
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_x64() -> None:
    """Enable 64-bit mode. Call at entry points that need CPU f64 precision."""
    jax.config.update("jax_enable_x64", True)

def x64_enabled() -> bool:
    return bool(jax.config.jax_enable_x64)

def default_float():
    return jnp.float64 if x64_enabled() else jnp.float32

def default_complex():
    return jnp.complex128 if x64_enabled() else jnp.complex64

def default_int():
    return jnp.int64 if x64_enabled() else jnp.int32


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is overridden.  Otherwise the cache goes to ``.jax_cache/`` at
    the checkout root: a fixed path, because the path is part of what a
    later process must find again (never a temporary name, pid or time).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
