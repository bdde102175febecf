"""Pure-jnp oracle for the fused two-level HSFL aggregation (Eqs. 3–4).

Semantics (one tier's parameter shard, client-stacked):

    x        [N, P]   per-client parameter values
    weights  [N]      fed-server aggregation weights (N_m^j/N expanded to
                      clients; uniform = 1/N), must sum to 1
    do_entity scalar  bool — apply Eq. (3) entity-local mean (every round)
    do_global scalar  bool — apply Eq. (4) fed-server weighted mean (at I_m)

    y1 = do_entity ? mean within each of the J contiguous client groups : x
    y2 = do_global ? Σ_n w_n · y1_n  (broadcast back)                  : y1
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def tiered_aggregate_ref(x, weights, do_entity, do_global, num_entities: int):
    N, P = x.shape
    J = num_entities
    per = N // J
    xf = x.astype(jnp.float32)
    grouped = xf.reshape(J, per, P)
    emean = jnp.broadcast_to(
        jnp.mean(grouped, axis=1, keepdims=True), grouped.shape
    ).reshape(N, P)
    y1 = jnp.where(do_entity, emean, xf)
    w = weights.astype(jnp.float32)[:, None]
    gmean = jnp.sum(y1 * w, axis=0, keepdims=True)
    y2 = jnp.where(do_global, jnp.broadcast_to(gmean, y1.shape), y1)
    return y2.astype(x.dtype)


# The q8 oracles are jitted: interpret mode compiles the kernel body as one
# XLA program, and XLA CPU contracts a multiply feeding an add into an FMA
# only inside such a fusion, so op-by-op dispatch rounds differently.
_jit_oracle = partial(jax.jit, static_argnames=("num_entities", "tile_p"))


def q8_tile(q, s, w, do_entity, do_global, num_entities: int):
    """One [N, TP] tile of the fused q8 path, in ``_q8_kernel``'s op order.

    q [N, TP] int8; s [N, 1] this tile's scales; w [N, 1] f32 weights.
    """
    x = q.astype(jnp.float32) * s
    N, TP = x.shape
    J = num_entities
    grouped = x.reshape(J, N // J, TP)
    emean = jnp.mean(grouped, axis=1, keepdims=True)
    emean = jnp.broadcast_to(emean, grouped.shape).reshape(x.shape)
    y1 = jnp.where(do_entity, emean, x)
    gmean = jnp.sum(y1 * w, axis=0, keepdims=True)
    return jnp.where(do_global, jnp.broadcast_to(gmean, y1.shape), y1)


def ragged_q8_tile(q, s, w, m, do_entity, do_global, num_entities: int):
    """One tile of the ragged q8 path, in ``_ragged_q8_kernel``'s op order.

    m [N, 1] f32 0/1 membership; the rest as ``q8_tile``.
    """
    qf = q.astype(jnp.float32)
    x = qf * s
    xm = qf * (s * m)  # member-masked dequant: s·1 == s exactly
    N, TP = x.shape
    J = num_entities
    per = N // J
    mg = m.reshape(J, per, 1)
    sg = jnp.sum(mg, axis=1, keepdims=True)
    emean = jnp.sum(xm.reshape(J, per, TP), axis=1, keepdims=True) / jnp.maximum(
        sg, 1.0
    )
    emean = jnp.broadcast_to(emean, (J, per, TP)).reshape(x.shape)
    sg_rows = jnp.broadcast_to(sg, (J, per, TP)).reshape(x.shape)
    y1 = jnp.where(do_entity & (m > 0.0) & (sg_rows > 0.0), emean, x)
    wm = w * m
    sw = jnp.sum(wm, axis=0, keepdims=True)
    gmean = jnp.sum(y1 * wm, axis=0, keepdims=True) / jnp.where(
        sw > 0.0, sw, 1.0
    )
    return jnp.where(
        do_global & (m > 0.0) & (sw > 0.0), jnp.broadcast_to(gmean, y1.shape), y1
    )


def _tiles(q, scales, tile_p):
    """(q [N, Pp], scales [N, T]) -> per-tile q [T, N, tile_p], s [T, N, 1]."""
    N, Pp = q.shape
    assert Pp % tile_p == 0, (Pp, tile_p)
    qt = q.reshape(N, Pp // tile_p, tile_p).transpose(1, 0, 2)
    return qt, scales.astype(jnp.float32).T[:, :, None]


def _untile(out):
    T, N, TP = out.shape
    return out.transpose(1, 0, 2).reshape(N, T * TP)


def q8_tiles_apply(tile_fn, q, scales, tile_p, *args):
    """``tile_fn`` vmapped over every tile: the whole-array fallback that
    rounds exactly as the kernel's per-tile programs do."""
    qt, st = _tiles(q, scales, tile_p)
    return _untile(jax.vmap(lambda a, b: tile_fn(a, b, *args))(qt, st))


@_jit_oracle
def quantized_tiered_aggregate_ref(
    q, scales, weights, do_entity, do_global, num_entities: int, tile_p: int
):
    """Oracle for the fused q8 path: ``q8_tile`` on each ``tile_p`` chunk in
    turn, as the kernel's grid walks them, so interpret mode is
    bit-identical.

    q       [N, Pp] int8 wire payload (Pp a multiple of ``tile_p``)
    scales  [N, Pp // tile_p] f32 per-tile scales
    """
    qt, st = _tiles(q, scales, tile_p)
    w = weights.astype(jnp.float32)[:, None]
    return jnp.concatenate(
        [
            q8_tile(qt[t], st[t], w, do_entity, do_global, num_entities)
            for t in range(qt.shape[0])
        ],
        axis=1,
    )


@_jit_oracle
def ragged_quantized_tiered_aggregate_ref(
    q, scales, weights, member, do_entity, do_global,
    num_entities: int, tile_p: int,
):
    """Oracle for the ragged q8 path: ``ragged_q8_tile`` per tile (dequant,
    member-masked entity mean, member-renormalized fed mean, member-gated
    receives), so interpret mode is bit-identical.  ``member`` [N] marks
    clients whose class holds this shard's units in the aggregating tier
    (DESIGN.md §14).
    """
    qt, st = _tiles(q, scales, tile_p)
    w = weights.astype(jnp.float32)[:, None]
    m = member.astype(jnp.float32)[:, None]
    return jnp.concatenate(
        [
            ragged_q8_tile(
                qt[t], st[t], w, m, do_entity, do_global, num_entities
            )
            for t in range(qt.shape[0])
        ],
        axis=1,
    )
