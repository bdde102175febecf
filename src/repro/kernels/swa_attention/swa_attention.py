"""Pallas TPU kernels: blocked causal sliding-window flash attention.

The operator behind every dense-arch ``long_500k`` run (DESIGN.md §6). TPU
adaptation of flash attention with a *static* kv-span: with window W and
tile T (128, MXU-aligned), each q tile only ever touches span = W/T + 1 kv
tiles, so the grid is (B, H, nq, span) and HBM traffic per q tile is
O(W + T) instead of O(S) — the structural win that makes 512k-token decode
feasible. Online softmax in f32 VMEM scratch; -1e30 masking (not -inf) so
fully-masked tiles stay NaN-free.

Forward emits the per-row logsumexp as a [B, H, S, 1] column (a [T] row
block of [B, H, S] is not a legal TPU block: its second-minor dim would be
1 of H); the backward pass (dq via a q-parallel
grid, dk/dv via a kv-parallel grid with an extra GQA group axis) recomputes
tile scores from it, the standard flash-bwd trade of FLOPs for HBM.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _pos(i, T):
    return i * T + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)


def _allowed(qp, kp, W, S_true, valid):
    """[T, T] mask: causal ∧ window ∧ in-bounds ∧ tile-valid."""
    ok = (kp.T <= qp) & (kp.T < S_true) & (qp < S_true)
    if W > 0:
        ok = ok & (kp.T > qp - W)
    return ok & valid


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, span, T, W, S_true, scale, out_dtype):
    i = pl.program_id(2)
    s = pl.program_id(3)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    j_int = i - (span - 1) + s
    valid = j_int >= 0
    j = jnp.maximum(j_int, 0)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # [T, hd]
    k = k_ref[0, 0].astype(jnp.float32)                # [T, hd]
    v = v_ref[0, 0].astype(jnp.float32)                # [T, hd]
    sc = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                   # [T, T]
    qp = _pos(i, T)
    kp = _pos(j, T)
    ok = _allowed(qp, kp, W, S_true, valid)
    sc = jnp.where(ok, sc, NEG)

    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
    p = jnp.exp(sc - m_new)
    p = jnp.where(ok, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(s == span - 1)
    def _done():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(out_dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(jnp.maximum(l, 1e-30))


def _fwd(q, k, v, *, window, T, S_true, interpret):
    """q [B,H,S,hd]; k,v [B,K,S,hd]; S multiple of T. Returns (o, lse)."""
    B, H, S, hd = q.shape
    K = k.shape[1]
    G = H // K
    nq = S // T
    span = (window // T) + 1 if window > 0 else nq
    # NOTE: the 1/sqrt(hd) scale is folded into q by ops.py before padding.

    def q_map(b, h, i, s):
        return (b, h, i, 0)

    def kv_map(b, h, i, s):
        j = jnp.maximum(i - (span - 1) + s, 0) if window > 0 else s
        return (b, h // G, j, 0)

    grid = (B, H, nq, span)
    kernel = functools.partial(
        _fwd_kernel, span=span, T=T, W=window, S_true=S_true,
        scale=1.0, out_dtype=q.dtype,
    )
    if window == 0:
        # full causal: span = nq, j = s, with causal masking skipping j > i
        kernel = functools.partial(
            _full_fwd_wrapper, span=span, T=T, S_true=S_true, out_dtype=q.dtype
        )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, T, hd), q_map),
            pl.BlockSpec((1, 1, T, hd), kv_map),
            pl.BlockSpec((1, 1, T, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, T, hd), q_map),
            pl.BlockSpec((1, 1, T, 1), lambda b, h, i, s: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((T, 128), jnp.float32),
            pltpu.VMEM((T, 128), jnp.float32),
            pltpu.VMEM((T, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _full_fwd_wrapper(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                      acc_scr, *, span, T, S_true, out_dtype):
    """Full-causal variant: kv tile index j == s, mask j > i tiles."""
    i = pl.program_id(2)
    s = pl.program_id(3)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid = s <= i
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    sc = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    qp = _pos(i, T)
    kp = _pos(s, T)
    ok = _allowed(qp, kp, 0, S_true, valid)
    sc = jnp.where(ok, sc, NEG)
    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
    p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(s == span - 1)
    def _done():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(out_dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(jnp.maximum(l, 1e-30))


# --------------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------------- #


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, span, T, W, S_true, full):
    i = pl.program_id(2)
    s = pl.program_id(3)

    @pl.when(s == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    if full:
        j_int = s
        valid = s <= i
    else:
        j_int = i - (span - 1) + s
        valid = j_int >= 0
    j = jnp.maximum(j_int, 0)
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                   # [T, 1]
    delta = delta_ref[0, 0]               # [T, 1]
    sc = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    qp = _pos(i, T)
    kp = _pos(j, T)
    ok = _allowed(qp, kp, W, S_true, valid)
    p = jnp.where(ok, jnp.exp(sc - lse), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(s == span - 1)
    def _done():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, span, T, W, S_true,
                G, nq, full):
    jb = pl.program_id(2)   # kv tile
    g = pl.program_id(3)    # GQA group member
    s = pl.program_id(4)    # q tile offset

    @pl.when((g == 0) & (s == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if full:
        i_int = jb + s
        valid = i_int < nq
    else:
        i_int = jb + s
        valid = i_int < nq
    i = jnp.minimum(i_int, nq - 1)
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    sc = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                      # [Tq, Tk]
    qp = _pos(i, T)
    kp = _pos(jb, T)
    ok = _allowed(qp, kp, W, S_true, valid)
    p = jnp.where(ok, jnp.exp(sc - lse), 0.0)
    dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when((g == G - 1) & (s == span - 1))
    def _done():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, *, window, T, S_true, interpret):
    B, H, S, hd = q.shape
    K = k.shape[1]
    G = H // K
    nq = S // T
    full = window == 0
    span = nq if full else (window // T) + 1

    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1, keepdims=True
    )  # [B, H, S, 1]

    def q_map(b, h, i, s):
        return (b, h, i, 0)

    def kv_map(b, h, i, s):
        if full:
            return (b, h // G, s, 0)
        return (b, h // G, jnp.maximum(i - (span - 1) + s, 0), 0)

    def lse_map(b, h, i, s):
        return (b, h, i, 0)

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, span=span, T=T, W=window, S_true=S_true, full=full
        ),
        grid=(B, H, nq, span),
        in_specs=[
            pl.BlockSpec((1, 1, T, hd), q_map),
            pl.BlockSpec((1, 1, T, hd), kv_map),
            pl.BlockSpec((1, 1, T, hd), kv_map),
            pl.BlockSpec((1, 1, T, hd), q_map),
            pl.BlockSpec((1, 1, T, 1), lse_map),
            pl.BlockSpec((1, 1, T, 1), lse_map),
        ],
        out_specs=pl.BlockSpec((1, 1, T, hd), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((T, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # kv-parallel pass
    def kv_self_map(b, kh, jb, g, s):
        return (b, kh, jb, 0)

    def q_of_kv_map(b, kh, jb, g, s):
        i = jnp.minimum(jb + s, nq - 1)
        return (b, kh * G + g, i, 0)

    def lse_of_kv_map(b, kh, jb, g, s):
        i = jnp.minimum(jb + s, nq - 1)
        return (b, kh * G + g, i, 0)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, span=span, T=T, W=window, S_true=S_true,
            G=G, nq=nq, full=full,
        ),
        grid=(B, K, nq, G, span),
        in_specs=[
            pl.BlockSpec((1, 1, T, hd), q_of_kv_map),
            pl.BlockSpec((1, 1, T, hd), kv_self_map),
            pl.BlockSpec((1, 1, T, hd), kv_self_map),
            pl.BlockSpec((1, 1, T, hd), q_of_kv_map),
            pl.BlockSpec((1, 1, T, 1), lse_of_kv_map),
            pl.BlockSpec((1, 1, T, 1), lse_of_kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, T, hd), kv_self_map),
            pl.BlockSpec((1, 1, T, hd), kv_self_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((T, hd), jnp.float32),
            pltpu.VMEM((T, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary", "arbitrary",
            ),
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
