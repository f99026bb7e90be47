"""Pallas TPU flash-attention kernel (identified §Perf next step).

The pure-JAX chunked flash in models/attention.py materializes its f32
score tiles in HBM on a fusing backend's worst day; this kernel keeps
the [bq, bk] tile, the online-softmax running max/denominator and the
output accumulator in VMEM scratch across the kv grid steps — the HBM
traffic drops to reading Q/K/V once and writing O once (the roofline
floor for attention).

Grid: (batch*heads, q_blocks, kv_blocks); the kv dim iterates fastest on
TPU so the VMEM scratch carries across kv steps of one (bh, qi) cell.
Validated against kernels/ref.flash_attention_ref in interpret mode
(tests/test_kernels.py sweeps shapes/dtypes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, bq: int, bk: int,
                  nk: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                  # [bk, hd]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    acc = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(kj == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, block_q: int = 128,
                           block_k: int = 128,
                           interpret: bool | None = None) -> jax.Array:
    """q/k/v: [BH, S, hd] (heads pre-flattened, KV pre-repeated for GQA).

    Returns [BH, S, hd].  Blocks must divide S; hd should be a multiple
    of 128 on real hardware (any size in interpret mode).
    """
    bh, s, hd = q.shape
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    nq, nk = s // block_q, s // block_k
    scale = 1.0 / float(np.sqrt(hd))
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, bq=block_q, bk=block_k,
        nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
