"""Jitted wrapper: flattening + padding around the RMSNorm kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from .kernel import rmsnorm_kernel


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 256, interpret: bool | None = None) -> jax.Array:
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    block = min(block_rows, n)
    pad = (-n) % block
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = rmsnorm_kernel(x2, scale, eps=eps, block_rows=block,
                         interpret=(default_interpret() if interpret is None
                                    else interpret))
    return out[:n].reshape(shape)
