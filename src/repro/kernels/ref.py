"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..models.layers import _sdpa
from ..models.mamba2 import ssd_reference
from ..models.rglru import rglru_scan_ref


def attention_ref(q, k, v, *, causal=True, window=0):
    """q: [B,Hq,S,D] (BHSD layout, like the kernel); k,v: [B,Hkv,S,D]."""
    qs = jnp.moveaxis(q, 1, 2)     # -> [B,S,H,D]
    ks = jnp.moveaxis(k, 1, 2)
    vs = jnp.moveaxis(v, 1, 2)
    out = _sdpa(qs, ks, vs, causal=causal, window=window, q_offset=0)
    return jnp.moveaxis(out, 2, 1)


def decode_attention_ref(q, k, v, kv_len):
    """q: [B,Hkv,group,D]; k,v: [B,Hkv,Skv,D]."""
    B, Hkv, group, D = q.shape
    Skv = k.shape[2]
    qf = q.astype(jnp.float32) / math.sqrt(D)
    s = jnp.einsum("bhgd,bhkd->bhgk", qf, k.astype(jnp.float32))
    mask = jnp.arange(Skv)[None, None, None, :] < kv_len
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32)).astype(q.dtype)


def ssd_ref(x, dt, A, B, C, chunk):
    return ssd_reference(x.astype(jnp.float32), dt.astype(jnp.float32), A,
                         B.astype(jnp.float32), C.astype(jnp.float32), chunk)


def rglru_ref(b_in, a):
    return rglru_scan_ref(b_in, a)
