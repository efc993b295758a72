"""Jitted wrapper: GQA layout handling + padding around the flash kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from .kernel import flash_attention_kernel


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_kv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q: [B, T, H, D]; k, v: [B, S, KV, D] (GQA) → [B, T, H, D].

    Repeats are handled by flattening (B, KV, G) into the kernel's BH dim;
    T/S are zero-padded to block multiples (masked out by causal/window
    logic plus the final unpad slice).
    """
    b, t, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh

    tp = (-t) % block_q
    sp = (-s) % block_kv
    if tp:
        q = jnp.pad(q, ((0, 0), (0, tp), (0, 0), (0, 0)))
    if sp:
        k = jnp.pad(k, ((0, 0), (0, sp), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sp), (0, 0), (0, 0)))
    tt, ss = t + tp, s + sp

    # [B, T, KV, G, D] -> [B·KV·G, T, D]
    qf = q.reshape(b, tt, kvh, g, d).transpose(0, 2, 3, 1, 4)
    qf = qf.reshape(b * kvh * g, tt, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1)    # [B, KV·G, S, D]
    kf = kf.reshape(b * kvh * g, ss, d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1)
    vf = vf.reshape(b * kvh * g, ss, d)

    o = flash_attention_kernel(qf, kf, vf, causal=causal, window=window,
                               block_q=block_q, block_kv=block_kv,
                               kv_len=s,
                               interpret=(default_interpret()
                                          if interpret is None
                                          else interpret))
    o = o.reshape(b, kvh, g, tt, d).transpose(0, 3, 1, 2, 4)
    o = o.reshape(b, tt, h, d)
    return o[:, :t]
