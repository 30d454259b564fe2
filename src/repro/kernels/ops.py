"""Jit'd public wrappers for the Pallas kernels.

Off-TPU the kernels execute in interpret mode; the mode is read from the
backend when a wrapper is traced (:func:`repro.kernels.interpret_mode`).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import interpret_mode
from .decode_attention import flash_decode
from .flash_attention import flash_attention_bhsd
from .rglru_scan import rglru_scan_pallas
from .ssd_scan import ssd_scan_pallas


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 512):
    """q: [B,S,Hq,D]; k,v: [B,S,Hkv,D] (model layout) -> [B,S,Hq,D]."""
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               bq=bq, bk=bk, interpret=interpret_mode())
    return jnp.moveaxis(out, 1, 2)


@partial(jax.jit, static_argnames=("bk",))
def decode_attention(q, k, v, kv_len, *, bk: int = 512):
    """q: [B,1,Hq,D]; k,v caches: [B,Skv,Hkv,D]; kv_len scalar.

    Returns [B,1,Hq,D].
    """
    B, _, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    qg = q[:, 0].reshape(B, Hkv, group, D)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    out = flash_decode(qg, kt, vt, kv_len, bk=bk, interpret=interpret_mode())
    return out.reshape(B, 1, Hq, D)


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 256):
    return ssd_scan_pallas(x, dt, A, B, C, chunk=chunk,
                           interpret=interpret_mode())


@partial(jax.jit, static_argnames=("block_t", "block_d"))
def rglru_scan(b_in, a, *, block_t: int = 256, block_d: int = 512):
    return rglru_scan_pallas(b_in, a, block_t=block_t, block_d=block_d,
                             interpret=interpret_mode())
