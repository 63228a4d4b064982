"""Pallas TPU kernel: blockwise (flash) attention.

The LM stack's dominant compute hot spot.  Layout: heads are folded into
the batch grid dim; the kv-block dim is innermost (sequential on TPU), so
the online-softmax state (m, l, acc) lives in VMEM scratch across kv steps
and the output block is written once at the last kv step:

    grid = (B*H, nq, nk)                  # nk innermost, sequential
    q block   (1, cq, hd)  indexed (b, i)
    k/v block (1, ck, hd)  indexed (b, j)
    out block (1, cq, hd)  indexed (b, i) — pinned across j

Per (b, i): VMEM holds one q block + one kv block + (cq, ck) scores —
hardware-aligned when cq, ck are multiples of 128 and hd in {64, 128}.
Causal masking is derived from program ids (never materialized in HBM).
Whole-kv-block skipping for causal masks is a TODO noted for the target
(needs pl.when on the block compute; the masked blocks still cost zero
HBM traffic here).

Validated against `ref.flash_attention_ref` (and the model-side jnp flash)
in interpret mode; the model stack switches to this kernel on TPU backends
via ``models.layers.flash_attention`` when ``cfg.use_pallas_attention``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  cq: int, ck: int, nk: int, sq: int, skv: int,
                  causal: bool, scale: float):
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # kv block (sequential innermost)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (cq, hd)
    k = k_ref[0]  # (ck, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (cq, ck)

    q_pos = i * cq + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 0)
    k_pos = j * ck + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 1)
    mask = k_pos >= skv  # kv padding
    if causal:
        mask = mask | (k_pos > q_pos)
    s = jnp.where(mask, NEG_INF, s)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "interpret"))
def flash_attention_kernel(q, k, v, causal: bool = True,
                           block_q: int = 128, block_kv: int = 128,
                           interpret: Optional[bool] = None):
    """q: (B, Sq, H, hd); k, v: (B, Skv, H, hd) (kv repeated to H heads).

    Returns (B, Sq, H, hd).  Blocks should be multiples of 128 on the
    target; any size runs under interpret.
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    cq = min(block_q, Sq)
    ck = min(block_kv, Skv)
    pq, pk = (-Sq) % cq, (-Skv) % ck

    # heads fold into the grid batch dim
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Skv, hd)
    if pq:
        qf = jnp.pad(qf, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kf = jnp.pad(kf, ((0, 0), (0, pk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pk), (0, 0)))
    nq, nk = qf.shape[1] // cq, kf.shape[1] // ck

    out = pl.pallas_call(
        functools.partial(_flash_kernel, cq=cq, ck=ck, nk=nk, sq=Sq,
                          skv=Skv, causal=causal,
                          scale=1.0 / math.sqrt(hd)),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, cq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, ck, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, ck, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, cq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, nq * cq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((cq,), jnp.float32),      # running max
            pltpu.VMEM((cq,), jnp.float32),      # running denom
            pltpu.VMEM((cq, hd), jnp.float32),   # accumulator
        ],
        interpret=default_interpret(interpret),
    )(qf, kf, vf)
    return out[:, :Sq].reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
