"""Jitted wrapper around the SSD scan kernel: head flattening + padding."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from .kernel import ssd_scan_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 256,
             interpret: bool | None = None) -> jax.Array:
    """x: [B, T, H, P]; dt: [B, T, H]; a: [H]; b, c: [B, T, G, N] → y like x.

    Groups are broadcast to heads; (B, H) flatten into the kernel grid dim.
    Padded timesteps carry dt=0 ⇒ exp(0)=1, zero update (exact)."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g

    pad = (-t) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    tt = t + pad

    xf = x.transpose(0, 2, 1, 3).reshape(bsz * h, tt, p)
    dtf = dt.transpose(0, 2, 1).reshape(bsz * h, tt)
    bb = jnp.repeat(b.transpose(0, 2, 1, 3), rep, axis=1)
    bb = bb.reshape(bsz * h, tt, n)
    cc = jnp.repeat(c.transpose(0, 2, 1, 3), rep, axis=1)
    cc = cc.reshape(bsz * h, tt, n)
    af = jnp.broadcast_to(a[None, :], (bsz, h)).reshape(bsz * h)

    y = ssd_scan_kernel(xf, dtf, af, bb, cc, chunk=min(chunk, tt),
                        interpret=(default_interpret() if interpret is None
                                   else interpret))
    y = y.reshape(bsz, h, tt, p).transpose(0, 2, 1, 3)
    return y[:, :t]
