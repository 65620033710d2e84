"""Pallas kernel: blocked causal GQA attention (FlashAttention-2 schedule).

LM-substrate hot spot for the assigned transformer architectures. Online
softmax over KV blocks -- the (S, S) score matrix is never materialized:

  for each (batch*q_head, q block):
      m, l, acc = -inf, 0, 0
      for kv block:                            # fori_loop, VMEM-resident KV
          s = q @ k^T * scale  (+ causal mask)
          m' = max(m, rowmax(s)); p = exp(s - m')
          acc = acc * exp(m - m') + p @ v; l = l * exp(m - m') + rowsum(p)
      out = acc / l

GQA: q-head h reads kv-head h // (Hq // Hkv); the kernel receives K/V
already indexed per q-head group so the BlockSpec stays rectangular.

Tiling: grid = (B * Hq, nQ). Per program: Q tile (BQ, Dh), K/V slices
(S, Dh) VMEM-resident (decode/serve shapes shard S across devices first;
for 32k x 128 x 2 x 4B = 32 MB the launcher splits the KV axis, this
kernel sees the local shard). MXU-aligned: BQ, Dh multiples of 128 where
possible.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _make_kernel(block_k: int, causal: bool, scale: float, q_offset: int):
    def _kernel(q_ref, k_ref, v_ref, o_ref):
        q = q_ref[0]                           # (BQ, Dh)
        S = k_ref.shape[1]
        BQ, Dh = q.shape
        q_blk = pl.program_id(1)
        q_off = q_blk * BQ
        nk = S // block_k

        def body(kb, carry):
            acc, m, l = carry
            rows = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
            k = k_ref[0, rows, :]              # (BK, Dh)
            v = v_ref[0, rows, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            kv_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (BQ, block_k), 1)
            if causal:
                # q_offset aligns decode-style queries (Sq < Skv) to the
                # tail of the KV axis, matching the reference.
                q_pos = q_off + q_offset + jax.lax.broadcasted_iota(
                    jnp.int32, (BQ, block_k), 0)
                s = jnp.where(kv_pos <= q_pos, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            acc = acc * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            return acc, m_new, l

        acc0 = jnp.zeros((BQ, Dh), jnp.float32)
        m0 = jnp.full((BQ, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((BQ, 1), jnp.float32)
        acc, m, l = jax.lax.fori_loop(0, nk, body, (acc0, m0, l0))
        out = acc / jnp.maximum(l, 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)
    return _kernel


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           *, causal: bool = True, block_q: int = 128,
                           block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, Dh); k, v: (BH, Skv, Dh) -- kv already per-q-head (GQA
    expansion done by the wrapper). Returns (BH, Sq, Dh).
    """
    BH, Sq, Dh = q.shape
    Skv = k.shape[1]
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    if Skv % bk:
        raise ValueError(f"Skv={Skv} must be a multiple of block_k={bk}")
    scale = 1.0 / (Dh ** 0.5)
    grid = (BH, pl.cdiv(Sq, bq))
    return pl.pallas_call(
        _make_kernel(bk, causal, scale, Skv - Sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Skv, Dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Skv, Dh), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dh), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Dh), q.dtype),
        interpret=interpret,
    )(q, k, v)
