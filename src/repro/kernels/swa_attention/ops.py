"""jit'd public wrapper for the SWA flash-attention kernels.

``swa_attention(q, k, v, window)`` takes [B, S, H, hd] / [B, S, K, hd]
(GQA), handles layout (head-major for the kernel grid), sequence padding to
the 128 tile, head-dim padding to the 128 lane, the 1/√hd scale fold, and
wires the forward/backward kernels through ``jax.custom_vjp``. Set
``use_pallas=False`` to run the pure-jnp oracle. The kernels compile for the
TPU by default; on a CPU pass ``interpret=True`` to run the kernel body
through the Pallas interpreter.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .ref import swa_attention_ref
from .swa_attention import _bwd, _fwd

T = 128  # MXU-aligned tile


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _swa(q, k, v, window: int, interpret: bool):
    o, _ = _swa_fwd_res(q, k, v, window, interpret)[0], None
    return o


def _prep(q, k, v, window):
    B, S, H, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    qt = _pad_to(_pad_to((q * scale).transpose(0, 2, 1, 3), T, 2), 128, 3)
    kt = _pad_to(_pad_to(k.transpose(0, 2, 1, 3), T, 2), 128, 3)
    vt = _pad_to(_pad_to(v.transpose(0, 2, 1, 3), T, 2), 128, 3)
    w_eff = 0 if (window == 0 or window >= S) else window
    return qt, kt, vt, w_eff, S, hd, scale


def _swa_fwd_res(q, k, v, window, interpret):
    qt, kt, vt, w_eff, S, hd, scale = _prep(q, k, v, window)
    o, lse = _fwd(qt, kt, vt, window=w_eff, T=T, S_true=S, interpret=interpret)
    out = o[:, :, :S, :hd].transpose(0, 2, 1, 3)
    return out, (qt, kt, vt, o, lse, w_eff, S, hd, scale)


def _swa_fwd(q, k, v, window, interpret):
    out, res = _swa_fwd_res(q, k, v, window, interpret)
    return out, res


def _swa_bwd(window, interpret, res, dout):
    qt, kt, vt, o, lse, w_eff, S, hd, scale = res
    dot = _pad_to(_pad_to(dout.transpose(0, 2, 1, 3), T, 2), 128, 3)
    dq, dk, dv = _bwd(
        qt, kt, vt, o, lse, dot, window=w_eff, T=T, S_true=S, interpret=interpret
    )
    dq = dq[:, :, :S, :hd].transpose(0, 2, 1, 3) * scale
    dk = dk[:, :, :S, :hd].transpose(0, 2, 1, 3)
    dv = dv[:, :, :S, :hd].transpose(0, 2, 1, 3)
    return dq.astype(qt.dtype), dk.astype(kt.dtype), dv.astype(vt.dtype)


_swa.defvjp(_swa_fwd, _swa_bwd)


@partial(
    jax.jit, static_argnames=("window", "use_pallas", "interpret")
)
def swa_attention(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, S, K, hd]
    v: jax.Array,  # [B, S, K, hd]
    window: int = 0,
    use_pallas: bool = True,
    interpret: bool = False,
) -> jax.Array:
    if window > 0:
        assert window % T == 0, f"window must be a multiple of {T}"
    if not use_pallas:
        return swa_attention_ref(q, k, v, window)
    return _swa(q, k, v, window, interpret)
