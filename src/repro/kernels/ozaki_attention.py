"""Ozaki-II attention: a FlashAttention-style scan over emulated block GEMMs.

Both routes run one online-softmax scan over kv blocks of ``bkv`` rows, with
the running (max, normaliser, accumulator) state per query row in the working
float.  Each block's QKᵀ and PV are emulated products; the routes differ only
in what computes them:

  * ``attention_ref`` (xla route) — ``ozaki2.emulated_matmul``;
  * ``attention_pallas_gemms`` (pallas route) — the product the dispatch
    layer passes in, its Pallas GEMM/GEMV kernels (Mosaic on a TPU).

Each block's (S, bkv) scores and probabilities go through HBM on both routes.
A single fused kernel that keeps them in VMEM would need its softmax state in
float64, which Mosaic does not have.

Bit-identity contract (the dispatch seam's invariant, verified by
tests/test_attention.py): the block products are bit-identical across routes,
and everything around them is the same code (``_block_scan``), so both routes
perform the same float operations in the same order.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import ozaki2

# Finite stand-in for -inf (matches repro.models.attention.NEG_INF): keeps the
# online-softmax state NaN-free for fully-masked rows on both routes.
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Shared per-block math — textually the same code on both routes
# ---------------------------------------------------------------------------

def _masked_scores(s_prod: jax.Array, mask_blk: jax.Array, softcap: float,
                   inv_sqrt_d: float) -> jax.Array:
    """Scale / softcap / mask one block of raw QKᵀ products.

    Op order matches the models' score path: scores·(1/√D), then the tanh
    softcap (when enabled), then masked positions to NEG_INF.
    """
    s = s_prod * inv_sqrt_d
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    return jnp.where(mask_blk, s, NEG_INF)


def _online_update(s: jax.Array, m: jax.Array, l: jax.Array):
    """One FlashAttention online-softmax step over a (rows, bkv) score block.

    Returns (p, corr, m_new, l_new): the block's unnormalised probabilities,
    the correction factor for the running accumulator, and the updated
    running max / normaliser.
    """
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l * corr + jnp.sum(p, axis=-1)
    return p, corr, m_new, l_new


# ---------------------------------------------------------------------------
# The scan, and the two routes over it
# ---------------------------------------------------------------------------

def _block_scan(q, k, v, mask, plan_qk, plan_pv, softcap, bkv, out_dtype,
                product):
    """The online-softmax scan over kv-blocks of ``bkv`` rows, with each
    block's QKᵀ and PV computed by ``product(a, b, plan)``."""
    S0, D = q.shape
    T = k.shape[0]
    # Query rows padded to a sublane multiple.  Rows are independent (q and p
    # scale per row), so padding changes no real row; it keeps a decode step
    # (S = 1) from carrying its softmax state as one-element float64 arrays,
    # whose exp XLA:TPU computes at float32 precision (see repro.core.backend).
    S = -(-S0 // 8) * 8
    q = jnp.pad(q.astype(out_dtype), ((0, S - S0), (0, 0)))
    tp = -(-T // bkv) * bkv
    kp = jnp.pad(k.astype(out_dtype), ((0, tp - T), (0, 0)))
    vp = jnp.pad(v.astype(out_dtype), ((0, tp - T), (0, 0)))
    mp = jnp.pad(mask.astype(jnp.int8), ((0, S - S0), (0, tp - T)))
    kb = kp.reshape(tp // bkv, bkv, D)
    vb = vp.reshape(tp // bkv, bkv, D)
    mb = jnp.moveaxis(mp.reshape(S, tp // bkv, bkv), 1, 0)
    inv_sqrt_d = 1.0 / math.sqrt(D)

    def step(carry, blk):
        m, l, acc = carry
        k_blk, v_blk, mask_blk = blk
        s_prod = product(q, k_blk.T, plan_qk)
        s = _masked_scores(s_prod, mask_blk != 0, softcap, inv_sqrt_d)
        p, corr, m, l = _online_update(s, m, l)
        pv = product(p, v_blk, plan_pv)
        acc = acc * corr[:, None] + pv
        return (m, l, acc), None

    init = (jnp.full((S,), NEG_INF, out_dtype), jnp.zeros((S,), out_dtype),
            jnp.zeros((S, D), out_dtype))
    (m, l, acc), _ = jax.lax.scan(step, init, (kb, vb, mb))
    return (acc / l[:, None])[:S0]


@functools.partial(jax.jit, static_argnames=("plan_qk", "plan_pv", "softcap",
                                             "bkv", "out_dtype"))
def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
                  plan_qk: ozaki2.Plan, plan_pv: ozaki2.Plan,
                  softcap: float = 0.0, bkv: int = 128,
                  out_dtype=jnp.float64) -> jax.Array:
    """The xla route: the scan with its block products on
    ``ozaki2.emulated_matmul``.

    q: (S, D), k/v: (T, D), mask: (S, T) (int8 or bool; nonzero = attend).
    q/k scale per row over D; p per row and v per column over the block.
    """
    def product(a, b, plan):
        return ozaki2.emulated_matmul(a, b, plan, out_dtype=out_dtype)
    return _block_scan(q, k, v, mask, plan_qk, plan_pv, softcap, bkv,
                       out_dtype, product)


@functools.partial(jax.jit, static_argnames=("plan_qk", "plan_pv", "product",
                                             "softcap", "bkv", "out_dtype"))
def attention_pallas_gemms(q: jax.Array, k: jax.Array, v: jax.Array,
                           mask: jax.Array, plan_qk: ozaki2.Plan,
                           plan_pv: ozaki2.Plan, product,
                           softcap: float = 0.0, bkv: int = 128,
                           out_dtype=jnp.float64) -> jax.Array:
    """The pallas route: the scan with its block products on ``product(a, b,
    plan)``, the dispatch layer's Pallas GEMM/GEMV kernels.

    Same arguments as ``attention_ref``, and bit-identical to it: the kernels
    give ``emulated_matmul``'s bits.
    """
    return _block_scan(q, k, v, mask, plan_qk, plan_pv, softcap, bkv,
                       out_dtype, product)
