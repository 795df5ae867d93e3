"""Chunkwise mLSTM Pallas kernel (beyond-paper; powers the xLSTM arch).

Grid (B, H, n_chunks), sequential over chunks; the (C, n, m) recurrent
state lives in VMEM/SMEM scratch and carries across grid steps.  Per chunk
the kernel computes the intra-chunk quadratic term on the MXU and folds in
the inter-chunk state, exactly mirroring ``ref.mlstm_chunkwise``.

TPU-specific trick: 1-D gate vectors are kept as (1, L) rows; column
versions are produced by an identity matmul (vector transpose on the MXU)
because Mosaic has no cheap (1, L) -> (L, 1) relayout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, ig_ref, fg_ref,
            h_ref, cfin_ref, nfin_ref, mfin_ref,
            c_ref, n_ref, m_ref, *,
            chunk: int, n_chunks: int, dk: int, dv: int, seq_len: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[0, 0] = NEG_INF

    q = q_ref[0, 0].astype(jnp.float32) * dk ** -0.5       # (L, dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    ig = ig_ref[0, 0].astype(jnp.float32)                  # (1, L)
    lf = -jax.nn.softplus(-fg_ref[0, 0].astype(jnp.float32))

    # mask gate positions past the true sequence end
    pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    ig = jnp.where(pos < seq_len, ig, NEG_INF)
    lf = jnp.where(pos < seq_len, lf, 0.0)

    ident = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) ==
             jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
             ).astype(jnp.float32)
    upper_incl = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) <=
                  jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
                  ).astype(jnp.float32)
    tril = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >=
            jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))

    def row2col(x):                                        # (1,L) -> (L,1)
        return jax.lax.dot_general(ident, x, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    bsum = jax.lax.dot_general(lf, upper_incl, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (1,L)
    bsum_col = row2col(bsum)                               # (L,1)
    btot = bsum_col[chunk - 1, 0]
    m_prev = m_ref[0, 0]
    n_prev = n_ref[0:1, :]                                 # (1, dk)
    C_prev = c_ref[...]                                    # (dk, dv)

    # ---- intra-chunk decay matrix ------------------------------------
    D = bsum_col - bsum + ig                               # (L, L)
    D = jnp.where(tril, D, NEG_INF)
    m_intra = jnp.max(D, axis=1, keepdims=True)            # (L,1)
    m_inter = m_prev + bsum_col
    m_row = jnp.maximum(m_intra, m_inter)
    w = jnp.exp(D - m_row)
    w = jnp.where(tril, w, 0.0)

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * w
    num = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    nrow = jax.lax.dot_general(w, k, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    wi = jnp.exp(m_inter - m_row)                          # (L,1)
    num = num + wi * jax.lax.dot_general(
        q, C_prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    nrow = nrow + wi * n_prev
    qn = jnp.sum(q * nrow, axis=1, keepdims=True)          # (L,1)
    den = jnp.maximum(jnp.abs(qn), jnp.exp(-m_row))
    h_ref[0, 0] = (num / den).astype(h_ref.dtype)

    # ---- state update --------------------------------------------------
    m_new = jnp.maximum(m_prev + btot, jnp.max(btot - bsum + ig))
    wC = jnp.exp(m_prev + btot - m_new)
    wk = jnp.exp(btot - bsum + ig - m_new)                 # (1,L)
    kw = k * row2col(wk)                                   # (L, dk)
    c_ref[...] = wC * C_prev + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_new = wC * n_prev + jax.lax.dot_general(
        wk, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # (1, dk)
    n_ref[...] = jnp.broadcast_to(n_new, n_ref.shape)
    m_ref[0, 0] = m_new

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        cfin_ref[0, 0] = c_ref[...]
        nfin_ref[0, 0] = n_ref[...]
        mfin_ref[0, 0] = jnp.full_like(mfin_ref[0, 0], m_ref[0, 0])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunkwise_fwd(q, k, v, i_gate, f_gate, *, chunk: int = 128,
                        interpret: bool = False):
    """q,k: (B,H,S,dk); v: (B,H,S,dv); gates: (B,H,S).

    Returns (h (B,H,S,dv), (C (B,H,dk,dv), n (B,H,dk), m (B,H))).
    """
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, max(s, 8))
    pad = (-s) % chunk
    if pad:
        padw = ((0, 0), (0, 0), (0, pad), (0, 0))
        q = jnp.pad(q, padw)
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
        i_gate = jnp.pad(i_gate, ((0, 0), (0, 0), (0, pad)))
        f_gate = jnp.pad(f_gate, ((0, 0), (0, 0), (0, pad)))
    sp = s + pad
    nc = sp // chunk
    igc = i_gate.reshape(b, h, nc, chunk)
    fgc = f_gate.reshape(b, h, nc, chunk)

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=nc, dk=dk,
                               dv=dv, seq_len=s)
    out_shapes = (
        jax.ShapeDtypeStruct((b, h, sp, dv), q.dtype),
        jax.ShapeDtypeStruct((b, h, dk, dv), jnp.float32),
        jax.ShapeDtypeStruct((b, h, 8, dk), jnp.float32),
        jax.ShapeDtypeStruct((b, h, 8, 128), jnp.float32),
    )
    hs, cfin, nfin, mfin = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dk), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, dv), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, ci, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, chunk, dv), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda bi, hi, ci: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 8, dk), lambda bi, hi, ci: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 8, 128), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ),
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((8, dk), jnp.float32),
            pltpu.SMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, igc, fgc)
    state = (cfin, nfin[:, :, 0], mfin[:, :, 0, 0])
    return hs[:, :, :s], state
