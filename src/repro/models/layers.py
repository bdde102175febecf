"""Neural-net primitives shared by every architecture in the zoo.

Pure functions over parameter dicts (no module framework — the HSFL engine
needs to slice/stack/aggregate raw pytrees). All initializers take an explicit
PRNG key. Shapes follow [batch, seq, ...] row-major conventions.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental.pallas.ops.tpu import splash_attention as splash
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as megablox_gmm, tgmm as megablox_tgmm

from .. import obs
from .spec import ModelSpec, pad_to

Params = Dict[str, Any]

# --------------------------------------------------------------------------- #
# basics
# --------------------------------------------------------------------------- #


def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    s = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape) * s).astype(dtype)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * lax.rsqrt(var + eps)
    return (out * (1.0 + w.astype(jnp.float32))).astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [..., S, H, hd]; positions: [..., S]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., :, None, :]  # [..., S, 1, half]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def cross_entropy(logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token cross-entropy. logits [..., V], labels [...] int."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


# --------------------------------------------------------------------------- #
# attention (GQA + rope + optional qk-norm / bias / sliding window / prefix)
# --------------------------------------------------------------------------- #


def init_attention(key, spec: ModelSpec, cross: bool = False) -> Params:
    d, hd = spec.d_model, spec.hd
    h, k = spec.num_heads, spec.num_kv_heads
    ks = jax.random.split(key, 8)
    p: Params = {
        "wq": _dense_init(ks[0], (d, h * hd), spec.pdtype),
        "wk": _dense_init(ks[1], (d, k * hd), spec.pdtype),
        "wv": _dense_init(ks[2], (d, k * hd), spec.pdtype),
        "wo": _dense_init(ks[3], (h * hd, d), spec.pdtype),
        "norm": jnp.zeros((d,), spec.pdtype),
    }
    if spec.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h * hd,), spec.pdtype)
        p["bk"] = jnp.zeros((k * hd,), spec.pdtype)
        p["bv"] = jnp.zeros((k * hd,), spec.pdtype)
    if spec.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((hd,), spec.pdtype)
        p["k_norm"] = jnp.zeros((hd,), spec.pdtype)
    return p


def _mask_bias(
    q_pos: jax.Array,  # [Sq]
    k_pos: jax.Array,  # [Sk]
    causal: bool,
    window: int,
    prefix_len: int,
    k_valid: Optional[jax.Array] = None,  # [B, Sk] bool (cache validity)
) -> jax.Array:
    """Additive mask [.., Sq, Sk] (broadcastable), 0 allowed / -inf blocked."""
    qp = q_pos[:, None]
    kp = k_pos[None, :]
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok = kp <= qp
        if prefix_len > 0:
            # prefix-LM: everything attends to the full (bidirectional) prefix
            ok = ok | (kp < prefix_len)
    if window > 0:
        ok = ok & (kp > qp - window)
    # negative positions mark padding / unfilled cache slots
    ok = ok & (kp >= 0)
    bias = jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)
    if k_valid is not None:
        bias = bias[None] + jnp.where(k_valid, 0.0, -jnp.inf)[:, None, :]
    return bias


def _sdpa(q, k, v, bias):
    """q [B,Sq,H,hd]; k,v [B,Sk,K,hd]; bias broadcastable to [B,H,Sq,Sk]."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    q = q.reshape(B, Sq, K, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if bias.ndim == 2:  # [Sq, Sk]
        b = bias[None, None, None]
    else:  # [B, Sq, Sk]
        b = bias[:, None, None]
    scores = scores + b
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, Sq, H, hd)


def _blockwise_sdpa(q, k, v, q_pos, k_pos, causal, window, prefix_len,
                    block_q: int = 512, block_k: int = 1024):
    """O(S) memory attention: lax.scan over q blocks, inner scan over kv
    blocks with online softmax. Used when Sq*Sk would be too large.
    For windowed attention, each q block gathers only its kv window slice."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    Sk = k.shape[1]
    orig_Sq = Sq
    pad_q = (-Sq) % block_q
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q), constant_values=-1)
        Sq = q.shape[1]
    nq = Sq // block_q
    qb = q.reshape(B, nq, block_q, H, hd)
    qpb = q_pos.reshape(nq, block_q)

    scale = 1.0 / math.sqrt(hd)

    if window > 0 and prefix_len == 0:
        # windowed path: gather [block_q + window] kv slice per q block
        span = block_q + window
        pad_k = window
        kp = jnp.pad(k, ((0, 0), (pad_k, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (pad_k, 0), (0, 0), (0, 0)))
        kpp = jnp.pad(k_pos, (pad_k, 0), constant_values=-(10**9))

        def per_qblock(i, qi, qpi):
            start = i * block_q  # offset in padded k == qstart - window + pad_k
            ks = lax.dynamic_slice_in_dim(kp, start, span, axis=1)
            vs = lax.dynamic_slice_in_dim(vp, start, span, axis=1)
            kps = lax.dynamic_slice_in_dim(kpp, start, span, axis=0)
            bias = _mask_bias(qpi, kps, causal, window, 0)
            return _sdpa(qi, ks, vs, bias)

        outs = []
        for i in range(nq):
            outs.append(per_qblock(i, qb[:, i], qpb[i]))
        out = jnp.stack(outs, axis=1).reshape(B, Sq, H, hd)
        return out[:, :orig_Sq]

    pad_k2 = (-Sk) % block_k
    if pad_k2:
        k = jnp.pad(k, ((0, 0), (0, pad_k2), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k2), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad_k2), constant_values=-(10**9))
    nk = k.shape[1] // block_k
    kb = k.reshape(B, nk, block_k, K, hd)
    vb = v.reshape(B, nk, block_k, K, hd)
    kpb = k_pos.reshape(nk, block_k)

    def q_step(_, qi_qpi):
        qi, qpi = qi_qpi  # [B, bq, H, hd], [bq]
        qg = qi.reshape(B, block_q, K, G, hd)

        def kv_step(carry, kv):
            m, l, acc = carry
            ki, vi, kpi = kv
            s = jnp.einsum("bqkgh,bskh->bkgqs", qg, ki).astype(jnp.float32) * scale
            bias = _mask_bias(qpi, kpi, causal, window, prefix_len)
            s = s + bias[None, None, None]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(vi.dtype), vi
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, block_q), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, K, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, K, G, block_q, hd), jnp.float32)
        (m, l, acc), _ = lax.scan(
            kv_step, (m0, l0, a0),
            (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4), kpb),
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, block_q, K * G, hd)
        return None, out.astype(qi.dtype)

    _, outs = lax.scan(
        q_step, None, (qb.transpose(1, 0, 2, 3, 4), qpb)
    )  # [nq, B, bq, H, hd]
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)
    return out[:, :orig_Sq]


BLOCKWISE_THRESHOLD = 4096  # Sq*Sk above (threshold)^2 -> O(S)-memory path


def attention(
    params: Params,
    x: jax.Array,  # [B, S, d]
    spec: ModelSpec,
    *,
    positions: Optional[jax.Array] = None,  # [S]
    causal: bool = True,
    prefix_len: int = 0,
    cache: Optional[Params] = None,  # {"k","v": [B,C,K,hd], "index": scalar}
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attn
    use_rope: bool = True,
) -> Tuple[jax.Array, Optional[Params]]:
    """Full GQA attention sub-layer (pre-norm + residual handled by caller)."""
    B, S, d = x.shape
    h, k_heads, hd = spec.num_heads, spec.num_kv_heads, spec.hd
    if positions is None:
        positions = jnp.arange(S)

    q = x @ params["wq"]
    if "bq" in params:
        q = q + params["bq"]
    q = q.reshape(B, S, h, hd)

    if kv_override is not None:
        k, v = kv_override  # precomputed (cross attention / enc out)
        new_cache = cache
        k_pos = jnp.arange(k.shape[1])
        bias = jnp.zeros((S, k.shape[1]), jnp.float32)
        if spec.qk_norm and "q_norm" in params:
            q = rms_norm(q, params["q_norm"], spec.norm_eps)
        out = _sdpa(q, k, v, bias)
        return out.reshape(B, S, h * hd) @ params["wo"], new_cache

    kx = x @ params["wk"]
    vx = x @ params["wv"]
    if "bk" in params:
        kx = kx + params["bk"]
        vx = vx + params["bv"]
    kx = kx.reshape(B, S, k_heads, hd)
    vx = vx.reshape(B, S, k_heads, hd)

    if spec.qk_norm and "q_norm" in params:
        q = rms_norm(q, params["q_norm"], spec.norm_eps)
        kx = rms_norm(kx, params["k_norm"], spec.norm_eps)
    if use_rope:
        q = rope(q, positions, spec.rope_theta)
        kx = rope(kx, positions, spec.rope_theta)

    new_cache = None
    if cache is not None:
        C = cache["k"].shape[1]
        idx = cache["index"]  # scalar int32: absolute position of this token
        if spec.window and spec.window < C:
            slot = idx % spec.window
        else:
            slot = idx
        ck = cache["k"].at[:, slot].set(kx[:, 0])
        cv = cache["v"].at[:, slot].set(vx[:, 0])
        new_cache = {"k": ck, "v": cv, "index": idx + 1}
        cache_pos = cache["positions"].at[slot].set(idx)
        new_cache["positions"] = cache_pos
        k_valid = (cache_pos >= 0)[None, :]
        k_valid = jnp.broadcast_to(k_valid, (B, C))
        bias = _mask_bias(
            positions, cache_pos, causal, spec.window, prefix_len, k_valid
        )
        out = _sdpa(q, ck, cv, bias)
        return out.reshape(B, S, h * hd) @ params["wo"], new_cache

    if S > BLOCKWISE_THRESHOLD:
        out = _blockwise_sdpa(
            q, kx, vx, positions, positions, causal, spec.window, prefix_len
        )
    else:
        bias = _mask_bias(positions, positions, causal, spec.window, prefix_len)
        out = _sdpa(q, kx, vx, bias)
    return out.reshape(B, S, h * hd) @ params["wo"], new_cache


def init_attn_cache(spec: ModelSpec, batch: int, cache_len: int) -> Params:
    C = min(cache_len, spec.window) if spec.window else cache_len
    return {
        "k": jnp.zeros((batch, C, spec.num_kv_heads, spec.hd), spec.cdtype),
        "v": jnp.zeros((batch, C, spec.num_kv_heads, spec.hd), spec.cdtype),
        "positions": jnp.full((C,), -1, jnp.int32),
        "index": jnp.zeros((), jnp.int32),
    }


# --------------------------------------------------------------------------- #
# MLP (SwiGLU) and MoE
# --------------------------------------------------------------------------- #


def init_mlp(key, spec: ModelSpec, d_ff: Optional[int] = None, gelu: bool = False) -> Params:
    d = spec.d_model
    ff = d_ff or spec.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w1": _dense_init(ks[0], (d, ff), spec.pdtype),
        "w2": _dense_init(ks[1], (ff, d), spec.pdtype),
        "norm": jnp.zeros((d,), spec.pdtype),
    }
    if not gelu:
        p["w3"] = _dense_init(ks[2], (d, ff), spec.pdtype)
    return p


def mlp(params: Params, x: jax.Array) -> jax.Array:
    if "w3" in params:
        return (jax.nn.silu(x @ params["w1"]) * (x @ params["w3"])) @ params["w2"]
    return jax.nn.gelu(x @ params["w1"]) @ params["w2"]


def init_moe(key, spec: ModelSpec) -> Params:
    d, ff = spec.d_model, spec.d_ff
    E = spec.moe.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (d, E), spec.pdtype, scale=0.02),
        "w1": _dense_init(ks[1], (E, d, ff), spec.pdtype),
        "w3": _dense_init(ks[2], (E, d, ff), spec.pdtype),
        "w2": _dense_init(ks[3], (E, ff, d), spec.pdtype),
        "norm": jnp.zeros((d,), spec.pdtype),
    }


def moe(params: Params, x: jax.Array, spec: ModelSpec,
        constraint=None, groups: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Scatter-based top-k MoE with capacity (scales to long sequences).

    ``groups`` (perf, EXPERIMENTS.md sect. Perf / granite-prefill): with
    groups=1 the dispatch scatter spans the *global* token range, so GSPMD
    cannot prove it local — it all-gathers every token to every device and
    replicates the dispatch + expert compute. With groups=G the tokens are
    reshaped [G, T/G] with per-group capacity (GShard-style grouping) and
    the scatter becomes a batched scatter whose group dim shards over
    `data`; expert compute then shards over (data, model) with no token
    all-gather. Capacity semantics change from global to per-group — the
    standard GShard trade (slightly more drops under skew).

    ``constraint``: optional hook applied to the dispatch buffer and expert
    outputs to pin the sharding GSPMD should use.

    Returns (output, aux_load_balance_loss)."""
    ms = spec.moe
    B, S, d = x.shape
    T = B * S
    E, K = ms.num_experts, ms.top_k
    G = groups if T % groups == 0 else 1
    Tg = T // G
    xg = x.reshape(G, Tg, d)
    logits = (xg @ params["router"]).astype(jnp.float32)  # [G, Tg, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = lax.top_k(probs, K)  # [G, Tg, K]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    cap = int(max(1, math.ceil(Tg * K / E * ms.capacity_factor)))
    # position of each (token, k) within its expert, via cumsum of one-hots
    oh = jax.nn.one_hot(expert_ids, E, dtype=jnp.int32)  # [G, Tg, K, E]
    oh_flat = oh.reshape(G, Tg * K, E)
    pos = jnp.cumsum(oh_flat, axis=1) - oh_flat  # rank within expert (per group)
    pos = jnp.sum(pos * oh_flat, axis=-1).reshape(G, Tg, K)
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)  # overflow -> spill slot `cap`

    # dispatch: per-group buffer [G, E, cap+1, d]; the scatter batches over
    # the group dim, so it shards over `data` instead of forcing a global
    # token all-gather (see docstring).
    buf = jnp.zeros((G, E, cap + 1, d), x.dtype)
    eid = expert_ids.reshape(G, -1)
    sid = slot.reshape(G, -1)
    xrep = jnp.repeat(xg, K, axis=1)  # [G, Tg*K, d]
    buf = jax.vmap(lambda b, e, s, u: b.at[e, s].set(u, mode="drop"))(
        buf, eid, sid, xrep
    )
    ein = buf[:, :, :cap]  # [G, E, cap, d]
    if constraint is not None:
        ein = constraint(ein)

    h = jnp.einsum("gecd,edf->gecf", ein, params["w1"])
    g = jnp.einsum("gecd,edf->gecf", ein, params["w3"])
    h = jax.nn.silu(h) * g
    eout = jnp.einsum("gecf,efd->gecd", h, params["w2"])  # [G, E, cap, d]
    if constraint is not None:
        eout = constraint(eout)

    if G > 1:
        # combine via scatter-add in *expert space*: each model rank adds its
        # local experts' gate-weighted rows into a per-group [Tg, d] partial,
        # so the cross-rank reduction is over [Tg, d] instead of the 8x
        # larger pre-combine [Tg, K, d] gather output (EXPERIMENTS.md
        # sect. Perf / granite-prefill iteration 2).
        gbuf = jnp.zeros((G, E, cap + 1), jnp.float32)
        gbuf = jax.vmap(lambda b, e, s, u: b.at[e, s].set(u, mode="drop"))(
            gbuf, eid, sid, gate_vals.reshape(G, -1)
        )
        tbuf = jnp.full((G, E, cap + 1), Tg, jnp.int32)  # spill -> drop row
        tok_ids = jnp.broadcast_to(
            jnp.arange(Tg)[:, None], (Tg, K)
        ).reshape(1, -1)
        tbuf = jax.vmap(lambda b, e, s, u: b.at[e, s].set(u, mode="drop"))(
            tbuf, eid, sid, jnp.broadcast_to(tok_ids, (G, Tg * K))
        )
        weighted = eout * gbuf[:, :, :cap, None].astype(eout.dtype)
        out = jnp.zeros((G, Tg + 1, d), x.dtype)
        out = jax.vmap(lambda o, t, w: o.at[t].add(w, mode="drop"))(
            out, tbuf[:, :, :cap].reshape(G, -1),
            weighted.reshape(G, E * cap, d),
        )
        out = out[:, :Tg]
    else:
        # combine: gather each (token, k) slot back, per group
        eout_p = jnp.pad(eout, ((0, 0), (0, 0), (0, 1), (0, 0)))  # spill -> 0
        got = jax.vmap(lambda eo, e, s: eo[e, s])(eout_p, eid, sid)
        got = got.reshape(G, Tg, K, d)
        out = jnp.sum(got * gate_vals[..., None].astype(got.dtype), axis=2)

    # aux loss (Switch-style load balancing), per dispatch group then
    # averaged — with groups == co-located clients this makes the pooled
    # (split-placement) execution equal the per-client one by construction.
    me = jnp.mean(probs, axis=1)  # [G, E]
    ce = jnp.mean(
        jax.nn.one_hot(expert_ids[..., 0], E, dtype=jnp.float32), axis=1
    )
    aux = E * jnp.mean(jnp.sum(me * ce, axis=-1))
    return out.reshape(B, S, d), aux


# --------------------------------------------------------------------------- #
# multi-head latent attention (DeepSeek-V3 MLA, no query latent)
# --------------------------------------------------------------------------- #


def rope_interleaved(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding on adjacent pairs ``(x[2i], x[2i+1])``, DeepSeek's
    pairing.  x: [..., S, H, r]; positions: [S].  The rotated pairs come
    out de-interleaved (first members, then second members), as DeepSeek's
    code leaves them: queries and keys take the same permutation, so their
    dot products are those of the pairs rotated in place."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [S, half]
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def init_mla(key, spec: ModelSpec) -> Params:
    m, d, h = spec.mla, spec.d_model, spec.num_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], (d, h * m.qk_dim), spec.pdtype),
        "wkva": _dense_init(ks[1], (d, m.kv_rank + m.rope_dim), spec.pdtype),
        "kv_norm": jnp.zeros((m.kv_rank,), spec.pdtype),
        "wkvb": _dense_init(ks[2], (m.kv_rank, h * (m.nope_dim + m.v_dim)), spec.pdtype),
        "wo": _dense_init(ks[3], (h * m.v_dim, d), spec.pdtype),
        "norm": jnp.zeros((d,), spec.pdtype),
    }


def mla(params: Params, x: jax.Array, spec: ModelSpec, positions: jax.Array,
        cache: Optional[Params] = None) -> Tuple[jax.Array, Optional[Params]]:
    """Causal MLA sub-layer (pre-norm and residual by the caller).

    x [B, S, d]; positions [S].  The latent ``c`` (after its RMSNorm) and
    the rotated shared key ``k_pe`` are what a position keeps: with a
    ``cache`` ({"c": [B, C, rank], "k_pe": [B, C, rope], "positions": [C],
    "index"}) one token is written at ``index`` and the keys and values of
    every cached position are rebuilt from the latents."""
    m, h = spec.mla, spec.num_heads
    B, S, _ = x.shape
    with obs.scope(obs.MLA):
        q = (x @ params["wq"]).reshape(B, S, h, m.qk_dim)
        q_nope = q[..., : m.nope_dim]
        q_pe = rope_interleaved(q[..., m.nope_dim:], positions, spec.rope_theta)
        kva = x @ params["wkva"]
        c = rms_norm(kva[..., : m.kv_rank], params["kv_norm"], spec.norm_eps)
        k_pe = rope_interleaved(kva[..., None, m.kv_rank:], positions, spec.rope_theta)[:, :, 0]
        k_pos = positions
        new_cache = None
        if cache is not None:
            idx = cache["index"]
            c = cache["c"].at[:, idx].set(c[:, 0])
            k_pe = cache["k_pe"].at[:, idx].set(k_pe[:, 0])
            k_pos = cache["positions"].at[idx].set(idx)
            new_cache = {"c": c, "k_pe": k_pe, "positions": k_pos, "index": idx + 1}
        kv = (c @ params["wkvb"]).reshape(B, c.shape[1], h, m.nope_dim + m.v_dim)
        k_nope, v = kv[..., : m.nope_dim], kv[..., m.nope_dim:]
        with obs.scope(obs.MLA_ATTN):
            if _takes_splash(cache, S):
                o = mla_splash_attention(q_nope, q_pe, k_nope, k_pe, v)
            else:
                o = mla_dense_attention(q_nope, q_pe, k_nope, k_pe, v, positions, k_pos)
        return o @ params["wo"], new_cache


def _takes_splash(cache: Optional[Params], S: int) -> bool:
    """Whether ``mla``'s attention runs in the splash kernel: a whole
    sequence (no cache) on the TPU that the kernel's blocks tile."""
    return cache is None and jax.default_backend() == "tpu" and S % 128 == 0


def mla_dense_attention(q_nope, q_pe, k_nope, k_pe, v, q_pos, k_pos) -> jax.Array:
    """Causal attention of ``mla`` over every (query, key) pair in float32,
    masked by position.  q_nope [B, S, h, nope], q_pe [B, S, h, rope],
    k_nope [B, C, h, nope], k_pe [B, C, rope], v [B, C, h, v];
    q_pos [S], k_pos [C].  Returns [B, S, h * v]."""
    B, S, h, _ = q_nope.shape
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
         + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe)).astype(jnp.float32)
    s = s / math.sqrt(q_nope.shape[-1] + q_pe.shape[-1]) + _mask_bias(q_pos, k_pos, True, 0, 0)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, S, h * v.shape[-1])


def _splash_kernel(S: int, h: int, interpret: bool):
    """The splash kernel of h causal heads over S positions (a multiple of
    128), every block (forward, dq and dkv) of the largest of 512, 256 and
    128 that divides S.  Built anew in each trace: its mask arrays become
    values of the trace that builds it (splash caches the host's mask work
    itself)."""
    b = math.gcd(S, 512)
    blocks = splash.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b,
                               block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
                               block_q_dq=b, block_kv_dq=b)
    mask = splash.MultiHeadMask([splash.CausalMask((S, S))] * h)
    return splash.make_splash_mha(mask, block_sizes=blocks, head_shards=1, q_seq_shards=1,
                                  interpret=interpret)


def mla_splash_attention(q_nope, q_pe, k_nope, k_pe, v, interpret: bool = False) -> jax.Array:
    """``mla_dense_attention`` over a whole sequence in the Pallas splash
    kernel: it skips the (query, key) blocks the causal mask removes and
    keeps scores and probabilities in VMEM, with float32 softmax statistics
    and accumulators.  The heads' query is [q_nope, q_pe] scaled by
    1/sqrt(qk) (the kernel does not scale), their key [k_nope, k_pe] with
    the shared k_pe copied to every head (its gradient sums back over
    them), their value v; operands rounded by ``_operand_dtype``.

    The kernel masks by index: key j is seen by query i iff j <= i.  That
    is the dense path's mask by position when positions are 0..S-1, as
    every whole-sequence caller of ``mla`` gives them.  Shapes as
    ``mla_dense_attention`` with C = S; S a multiple of 128."""
    B, S, h, _ = q_nope.shape
    dt = _operand_dtype(q_nope.dtype)
    scale = 1.0 / math.sqrt(q_nope.shape[-1] + q_pe.shape[-1])
    q = jnp.concatenate([q_nope, q_pe], axis=-1) * scale
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], (B, S, h, k_pe.shape[-1]))], axis=-1)
    heads = lambda a: a.swapaxes(1, 2).astype(dt)  # [B, h, S, d]
    o = jax.vmap(_splash_kernel(S, h, interpret))(heads(q), heads(k), heads(v))
    return o.swapaxes(1, 2).reshape(B, S, h * v.shape[-1]).astype(q_nope.dtype)


def init_mla_cache(spec: ModelSpec, batch: int, cache_len: int) -> Params:
    m = spec.mla
    return {
        "c": jnp.zeros((batch, cache_len, m.kv_rank), spec.cdtype),
        "k_pe": jnp.zeros((batch, cache_len, m.rope_dim), spec.cdtype),
        "positions": jnp.full((cache_len,), -1, jnp.int32),
        "index": jnp.zeros((), jnp.int32),
    }


# --------------------------------------------------------------------------- #
# sigmoid-routed experts beside shared experts (DeepSeek-V3), no dropped tokens
# --------------------------------------------------------------------------- #


# std of the selection bias (e_score_correction_bias), drawn from the seed
# and held fixed: it moves which experts are chosen, never a weight
SELECTION_BIAS_STD = 0.01


def init_routed_moe(key, spec: ModelSpec) -> Params:
    """Router over all ``num_experts``, its fixed selection bias, the held
    experts' SwiGLUs stacked [n_held, ...] and the shared experts.  Expert
    e's weights come from the e-th of ``num_experts`` keys, so every share
    of the experts holds the same weights as the whole layer."""
    r, d, f = spec.routed, spec.d_model, spec.routed.d_expert
    ks = jax.random.split(key, 4)

    def expert(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return (_dense_init(k1, (d, f), spec.pdtype), _dense_init(k3, (d, f), spec.pdtype),
                _dense_init(k2, (f, d), spec.pdtype))

    keys = jax.random.split(ks[1], r.num_experts)[r.first_held: r.first_held + r.n_held]
    w1, w3, w2 = jax.vmap(expert)(keys)
    p = {
        "router": _dense_init(ks[0], (d, r.num_experts), spec.pdtype),
        "bias": (jax.random.normal(ks[3], (r.num_experts,)) * SELECTION_BIAS_STD).astype(spec.pdtype),
        "w1": w1, "w3": w3, "w2": w2,
        "norm": jnp.zeros((d,), spec.pdtype),
    }
    if r.num_shared:
        shared = init_mlp(ks[2], spec, d_ff=r.num_shared * f)
        p["shared"] = {k: shared[k] for k in ("w1", "w2", "w3")}
    return p


def routed_moe(params: Params, x: jax.Array, spec: ModelSpec) -> jax.Array:
    """The expert sub-layer (pre-norm and residual by the caller).

    Scores are ``sigmoid(x W_g)`` over every expert, in float32 at the
    highest matmul precision.  The top ``top_k`` of scores plus the fixed
    bias are chosen (the bias gets no gradient); their unbiased scores,
    normalised to sum 1 (``norm_topk``) and times ``routed_scale``, weight
    the chosen experts' outputs.  Of those, only the held experts' part is
    computed here, for every pair routed to them: no token is dropped.  The
    shared experts run on every token."""
    r = spec.routed
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    with obs.scope(obs.MOE_ROUTE):
        logits = jnp.dot(xt.astype(jnp.float32), params["router"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, idx = lax.top_k(scores + lax.stop_gradient(params["bias"].astype(jnp.float32)),
                           r.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if r.norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        w = w * r.routed_scale
        local = idx - r.first_held
        here = (local >= 0) & (local < r.n_held)
        gid = jnp.where(here, local, r.n_held)
    ye = grouped_experts(xt, gid, params["w1"], params["w3"], params["w2"])
    with obs.scope(obs.MOE_ROUTE):
        out = jnp.einsum("tkd,tk->td", ye, jnp.where(here, w, 0.0).astype(ye.dtype))
    if "shared" in params:
        with obs.scope(obs.MOE_SHARED):
            out = out + mlp(params["shared"], xt)
    return out.reshape(B, S, d)


def _operand_dtype(dtype):
    """On the TPU a float32 matmul at the default precision rounds its
    operands to bfloat16 and accumulates in float32, so the megablox kernels
    are handed them so rounded; elsewhere they are interpreted in the
    operands' own dtype.  That CPU path exists for the tests, which hold
    the program to the float32 reference at tight tolerances; it is not
    the chip's arithmetic, which ``chip_smoke.py``'s ``grouped_experts``
    phase checks against a dense per-expert sum on the TPU."""
    return jnp.bfloat16 if jax.default_backend() == "tpu" else dtype


def _megablox_args(m: int, k: int, n: int):
    """Row padding, tiling and whether to interpret the megablox kernels."""
    tm = min(512, pad_to(m, 128))
    return pad_to(m, tm) - m, (tm, min(512, k), min(512, n)), jax.default_backend() != "tpu"


def _gmm(x: jax.Array, w: jax.Array, sizes: jax.Array, transpose_rhs: bool = False) -> jax.Array:
    """Rows of group g of ``x`` [m, k] times ``w[g]`` ([G, k, n], or [G, n, k]
    with ``transpose_rhs``): the megablox grouped matmul, which visits the
    tiles of the groups alone and leaves the rows past the last group
    unwritten (zeroed here).  Float32 out."""
    m, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    pad, tiling, interpret = _megablox_args(m, k, n)
    out = megablox_gmm(jnp.pad(x, ((0, pad), (0, 0))).astype(_operand_dtype(x.dtype)),
                       w.astype(_operand_dtype(w.dtype)), sizes, preferred_element_type=jnp.float32,
                       tiling=tiling, transpose_rhs=transpose_rhs, interpret=interpret)
    return jnp.where((jnp.arange(m) < jnp.sum(sizes))[:, None], out[:m], 0.0)


def _wgrad(x: jax.Array, dy: jax.Array, sizes: jax.Array) -> jax.Array:
    """Per group g, ``x[rows of g].T @ dy[rows of g]``: [G, in, out], float32."""
    m = x.shape[0]
    pad, tiling, interpret = _megablox_args(m, x.shape[1], dy.shape[1])
    rows = ((0, pad), (0, 0))
    return megablox_tgmm(jnp.pad(x, rows).astype(_operand_dtype(x.dtype)).T,
                         jnp.pad(dy, rows).astype(_operand_dtype(dy.dtype)), sizes,
                         preferred_element_type=jnp.float32, tiling=tiling,
                         interpret=interpret)


def _experts_fwd(xt, gid, w1, w3, w2):
    """xt [T, d]; gid [T, K], each pair's group in [0, G) or G (not here);
    w1, w3 [G, d, f], w2 [G, f, d].  The pairs are sorted by group, so the
    grouped products run over the routed rows alone (the rows of pairs
    held elsewhere sit past the last group).  Returns each pair's output
    [T, K, d] (zero for pairs not here) and what the backward pass keeps."""
    T, K = gid.shape
    G = w1.shape[0]
    with obs.scope(obs.MOE_ROUTE):
        flat = gid.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * K, dtype=order.dtype))
        sizes = jnp.sum(flat[:, None] == jnp.arange(G)[None, :], axis=0, dtype=jnp.int32)
        xs = xt.astype(_operand_dtype(xt.dtype))[order // K]
    with obs.scope(obs.MOE_EXPERTS):
        h1 = _gmm(xs, w1, sizes)
        h3 = _gmm(xs, w3, sizes)
        y = _gmm(jax.nn.silu(h1) * h3, w2, sizes)
    with obs.scope(obs.MOE_ROUTE):
        y = y[inv].astype(xt.dtype)
    return y.reshape(T, K, -1), (xs, h1, h3, order, inv, sizes)


def _experts_bwd(res, w1, w3, w2, dye):
    xs, h1, h3, order, inv, sizes = res
    T, K, d = dye.shape
    with obs.scope(obs.MOE_ROUTE):
        dy = dye.reshape(T * K, d)[order]
    with obs.scope(obs.MOE_EXPERTS):
        sig = jax.nn.sigmoid(h1)
        a = h1 * sig
        dhh = _gmm(dy, w2, sizes, transpose_rhs=True)
        dw2 = _wgrad(a * h3, dy, sizes)
        dh3 = dhh * a
        dh1 = dhh * h3 * sig * (1.0 + h1 * (1.0 - sig))
        dxs = _gmm(dh1, w1, sizes, transpose_rhs=True) + _gmm(dh3, w3, sizes, transpose_rhs=True)
        dw1 = _wgrad(xs, dh1, sizes)
        dw3 = _wgrad(xs, dh3, sizes)
    with obs.scope(obs.MOE_ROUTE):
        dxt = jnp.sum(dxs[inv].reshape(T, K, d), axis=1).astype(dye.dtype)
    return dxt, dw1.astype(w1.dtype), dw3.astype(w3.dtype), dw2.astype(w2.dtype)


def _fold_clients(n, batched, xs):
    """Broadcast the unbatched of ``xs`` to ``n`` and fold the leading axis
    into the next: [n, a, ...] -> [n * a, ...]."""
    out = []
    for x, b in zip(xs, batched):
        x = x if b else jnp.broadcast_to(x[None], (n,) + x.shape)
        out.append(x.reshape((n * x.shape[1],) + x.shape[2:]))
    return out


def _unfold(n, x):
    return x.reshape((n, x.shape[0] // n) + x.shape[1:])


# The grouped products take no batch dimension on the TPU, so a vmap over
# clients (Engine A's) folds the clients into the groups: client i's held
# expert g is group i * G + g, and one grouped product serves every client.
@jax.custom_batching.custom_vmap
def _experts_fwd_call(xt, gid, w1, w3, w2):
    return _experts_fwd(xt, gid, w1, w3, w2)


@_experts_fwd_call.def_vmap
def _experts_fwd_vmap(n, in_batched, xt, gid, w1, w3, w2):
    G = w1.shape[1] if in_batched[2] else w1.shape[0]
    gid = gid if in_batched[1] else jnp.broadcast_to(gid[None], (n,) + gid.shape)
    gid = jnp.where(gid < G, gid + G * jnp.arange(n, dtype=gid.dtype)[:, None, None], n * G)
    args = _fold_clients(n, (in_batched[0], True) + tuple(in_batched[2:]), (xt, gid, w1, w3, w2))
    ye, res = _experts_fwd(*args)
    return (_unfold(n, ye), tuple(_unfold(n, r) for r in res)), (True, (True,) * len(res))


@jax.custom_batching.custom_vmap
def _experts_bwd_call(res, w1, w3, w2, dye):
    return _experts_bwd(res, w1, w3, w2, dye)


@_experts_bwd_call.def_vmap
def _experts_bwd_vmap(n, in_batched, res, w1, w3, w2, dye):
    if not all(in_batched[0]):
        raise NotImplementedError("grouped experts: the backward pass is batched "
                                  "only where the forward pass was")
    res = tuple(r.reshape((-1,) + r.shape[2:]) for r in res)
    w1, w3, w2, dye = _fold_clients(n, in_batched[1:], (w1, w3, w2, dye))
    outs = _experts_bwd(res, w1, w3, w2, dye)
    return tuple(_unfold(n, o) for o in outs), (True,) * 4


@jax.custom_vjp
def grouped_experts(xt, gid, w1, w3, w2):
    """Each (token, choice) pair's output of its expert's SwiGLU, over the
    held experts only (see ``_experts_fwd``)."""
    return _experts_fwd_call(xt, gid, w1, w3, w2)[0]


def _grouped_experts_fwd(xt, gid, w1, w3, w2):
    ye, res = _experts_fwd_call(xt, gid, w1, w3, w2)
    return ye, (res, w1, w3, w2)


def _grouped_experts_bwd(saved, dye):
    res, w1, w3, w2 = saved
    dxt, dw1, dw3, dw2 = _experts_bwd_call(res, w1, w3, w2, dye)
    return dxt, None, dw1, dw3, dw2


grouped_experts.defvjp(_grouped_experts_fwd, _grouped_experts_bwd)


# --------------------------------------------------------------------------- #
# Mamba2 (SSD — state space duality, arXiv:2405.21060)
# --------------------------------------------------------------------------- #


def init_mamba(key, spec: ModelSpec) -> Params:
    ss = spec.ssm
    d = spec.d_model
    di = ss.expand * d
    nh = di // ss.head_dim
    n = ss.state_dim
    ks = jax.random.split(key, 5)
    in_dim = 2 * di + 2 * n + nh  # z, x, B, C, dt
    return {
        "in_proj": _dense_init(ks[0], (d, in_dim), spec.pdtype),
        "conv_w": _dense_init(ks[1], (ss.conv_width, di), spec.pdtype, scale=0.5),
        "A_log": jnp.log(
            jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32)
        ).astype(spec.pdtype),
        "D": jnp.ones((nh,), spec.pdtype),
        "dt_bias": jnp.zeros((nh,), spec.pdtype),
        "gate_norm": jnp.zeros((di,), spec.pdtype),
        "out_proj": _dense_init(ks[2], (di, d), spec.pdtype),
        "norm": jnp.zeros((d,), spec.pdtype),
    }


def _segsum(x: jax.Array) -> jax.Array:
    """x [..., T] -> lower-triangular cumulative segment sums [..., T, T]."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    tril = jnp.tril(jnp.ones((T, T), bool), 0)
    return jnp.where(tril, diff, -jnp.inf)


def ssd_scan(
    x: jax.Array,  # [B, S, H, P] (already dt-discretized input)
    A: jax.Array,  # [B, S, H]    (dt * A, negative)
    Bm: jax.Array,  # [B, S, N]
    Cm: jax.Array,  # [B, S, N]
    chunk: int,
    init_state: Optional[jax.Array] = None,  # [B, H, P, N]
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD (dual form). Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        A = jnp.pad(A, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = x.shape[1]
    nc = Sp // chunk
    xc = x.reshape(B, nc, chunk, H, P)
    Ac = A.reshape(B, nc, chunk, H).transpose(0, 3, 1, 2)  # [B,H,nc,l]
    Bc = Bm.reshape(B, nc, chunk, N)
    Cc = Cm.reshape(B, nc, chunk, N)

    A_cumsum = jnp.cumsum(Ac, axis=-1)  # [B,H,nc,l]
    L = jnp.exp(_segsum(Ac))  # [B,H,nc,l,l]
    # 1. intra-chunk (diagonal block) outputs
    Y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc, L, xc)
    # 2. chunk-final states
    decay_states = jnp.exp(A_cumsum[..., -1:] - A_cumsum)  # [B,H,nc,l]
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay_states, xc)
    # 3. inter-chunk recurrence
    if init_state is None:
        init_state = jnp.zeros((B, H, P, N), states.dtype)
    states = jnp.concatenate([init_state[:, None], states], axis=1)
    chunk_decay = A_cumsum[..., -1]  # [B,H,nc]
    dec_pad = jnp.pad(chunk_decay, ((0, 0), (0, 0), (1, 0)))
    decay_chunk = jnp.exp(_segsum(dec_pad))  # [B,H,nc+1,nc+1]
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states)
    states_in = new_states[:, :-1]  # state entering each chunk
    final_state = new_states[:, -1]
    # 4. state -> output contribution
    state_decay_out = jnp.exp(A_cumsum)  # [B,H,nc,l]
    Y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, states_in, state_decay_out)
    Y = (Y_diag + Y_off).reshape(B, Sp, H, P)
    return Y[:, :S], final_state


def mamba_block(
    params: Params,
    x: jax.Array,  # [B, S, d]
    spec: ModelSpec,
    cache: Optional[Params] = None,  # {"conv": [B,W-1,di], "state": [B,H,P,N]}
) -> Tuple[jax.Array, Optional[Params]]:
    ss = spec.ssm
    d = spec.d_model
    di = ss.expand * d
    nh = di // ss.head_dim
    n = ss.state_dim
    B, S, _ = x.shape

    zxbcdt = x @ params["in_proj"]
    z, xs, Bm, Cm, dt = jnp.split(zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))  # [B,S,H]
    A = -jnp.exp(params["A_log"].astype(jnp.float32))  # [H]

    new_cache = None
    if cache is None:
        # causal depthwise conv over xs
        W = ss.conv_width
        xpad = jnp.pad(xs, ((0, 0), (W - 1, 0), (0, 0)))
        xconv = sum(
            xpad[:, i : i + S] * params["conv_w"][i] for i in range(W)
        )
        xconv = jax.nn.silu(xconv)
        xh = xconv.reshape(B, S, nh, ss.head_dim)
        x_dt = xh * dt[..., None].astype(xh.dtype)
        Adt = dt * A  # [B,S,H]
        y, _ = ssd_scan(x_dt, Adt, Bm, Cm, ss.chunk)
        y = y + xh * params["D"].astype(xh.dtype)[None, None, :, None]
    else:
        W = ss.conv_width
        conv_st = cache["conv"]  # [B, W-1, di]
        xcat = jnp.concatenate([conv_st, xs], axis=1)  # [B, W, di] (S==1)
        xconv = sum(xcat[:, i : i + 1] * params["conv_w"][i] for i in range(W))
        xconv = jax.nn.silu(xconv)
        xh = xconv.reshape(B, 1, nh, ss.head_dim)
        dA = jnp.exp(dt[:, 0] * A)  # [B,H]
        st = cache["state"]  # [B,H,P,N]
        inp = (xh[:, 0] * dt[:, 0, :, None]).astype(jnp.float32)  # [B,H,P]
        st = st * dA[..., None, None] + inp[..., None] * Bm[:, 0, None, None, :].astype(jnp.float32)
        y0 = jnp.einsum("bhpn,bn->bhp", st, Cm[:, 0].astype(jnp.float32))
        y = (y0[:, None] + xh * params["D"][None, None, :, None]).astype(xs.dtype)
        new_cache = {"conv": xcat[:, 1:], "state": st}

    y = y.reshape(B, S, di)
    y = rms_norm(y * jax.nn.silu(z), params["gate_norm"], spec.norm_eps)
    return y @ params["out_proj"], new_cache


def init_mamba_cache(spec: ModelSpec, batch: int) -> Params:
    ss = spec.ssm
    di = ss.expand * spec.d_model
    nh = di // ss.head_dim
    return {
        "conv": jnp.zeros((batch, ss.conv_width - 1, di), spec.cdtype),
        "state": jnp.zeros((batch, nh, ss.head_dim, ss.state_dim), jnp.float32),
    }
