"""Pallas TPU kernels: fused client→entity→global parameter aggregation.

The MA hot-spot of HSFL. The naive schedule reads the [N, P] client-stacked
shard from HBM twice (once for the Eq. 3 entity mean, once for the Eq. 4
fed-server mean); this kernel fuses both reduction levels into a single HBM
pass, tiling P into VMEM-resident [N, TILE_P] blocks (N ≤ 64 clients per
shard in practice, so a tile is ≤ 64·TILE_P·4 B — TILE_P=2048 ⇒ 512 KiB,
comfortably inside the ~16 MiB v5e VMEM with double buffering).

Grid: one program per P tile. The round flags (do_entity / do_global) and
the fed-server weights ride in SMEM via scalar prefetch so one compiled
kernel serves every round of the schedule.

``quantized_tiered_aggregate_pallas`` is the compressed-wire variant
(DESIGN.md §9): clients upload int8 payloads with one f32 scale per
``tile_p`` chunk (the ``compress.quantize`` wire format), and the kernel
fuses dequantize → entity mean → fed-server weighted mean in VMEM, so the
single HBM read is ~4× cheaper than the f32 path.  Each grid step's scale
column is a blocked VMEM input next to its int8 tile (the full scale array
is O(P) — too big for SMEM); ``ref.py`` carries the tile-mirroring oracle
the interpret-mode tests pin bit-for-bit.

Every kernel compiles for TPU v5e at real leaf sizes (N = 20, J = 5,
P in the millions; ``tests/test_tpu_compile.py``).  Only the round flags
ride SMEM scalar prefetch: SMEM serves scalar loads only, so the O(N)
weight and membership vectors are whole-array [N, 1] VMEM blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_P = 2048


def _kernel(flags_ref, w_ref, x_ref, o_ref, *, num_entities: int):
    """flags_ref: SMEM [2] int32; w_ref: VMEM [N, 1] f32; x/o: VMEM [N, TP]."""
    x = x_ref[...].astype(jnp.float32)  # [N, TP]
    N = x.shape[0]
    J = num_entities
    per = N // J
    do_entity = flags_ref[0] > 0
    do_global = flags_ref[1] > 0

    grouped = x.reshape(J, per, x.shape[1])
    emean = jnp.mean(grouped, axis=1, keepdims=True)
    emean = jnp.broadcast_to(emean, grouped.shape).reshape(x.shape)
    y1 = jnp.where(do_entity, emean, x)

    w = w_ref[...]  # [N, 1]
    gmean = jnp.sum(y1 * w, axis=0, keepdims=True)
    y2 = jnp.where(do_global, jnp.broadcast_to(gmean, y1.shape), y1)
    o_ref[...] = y2.astype(o_ref.dtype)


def tiered_aggregate_pallas(
    x: jax.Array,        # [N, P]
    weights: jax.Array,  # [N] f32, sums to 1
    do_entity: jax.Array,  # scalar bool/int
    do_global: jax.Array,  # scalar bool/int
    num_entities: int,
    tile_p: int = TILE_P,
    interpret: bool = False,
) -> jax.Array:
    N, P = x.shape
    assert N % num_entities == 0, (N, num_entities)
    pad = (-P) % tile_p
    xp = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
    Pp = xp.shape[1]
    flags = jnp.stack(
        [do_entity.astype(jnp.int32), do_global.astype(jnp.int32)]
    )

    grid = (Pp // tile_p,)
    out = pl.pallas_call(
        functools.partial(_kernel, num_entities=num_entities),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # flags
            grid=grid,
            in_specs=[
                pl.BlockSpec((N, 1), lambda i, *_: (0, 0)),  # weights
                pl.BlockSpec((N, tile_p), lambda i, *_: (0, i)),
            ],
            out_specs=pl.BlockSpec((N, tile_p), lambda i, *_: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        interpret=interpret,
    )(flags, weights.astype(jnp.float32)[:, None], xp)
    return out[:, :P] if pad else out


def _scale_columns(scales: jax.Array) -> jax.Array:
    """[N, T] per-tile scales -> [T, N, 1]: grid step i reads the whole
    [N, 1] column of tile i as a block (a lone column cut from the lane
    axis is not a legal TPU block)."""
    return scales.astype(jnp.float32).T[:, :, None]


def _q8_kernel(flags_ref, w_ref, q_ref, s_ref, o_ref, *, num_entities: int):
    """flags in SMEM ([2] i32); w [N, 1] f32, q [N, TP] i8 and this tile's
    scale column s [N, 1] f32 in VMEM; o VMEM [N, TP] f32.

    One fused pass per tile: int8 → f32 dequant against the tile's scale
    column, then the same two-level (Eq. 3 + Eq. 4) reduction as
    ``_kernel``.  Scales are a *blocked* input, not scalar prefetch — the
    full [N, P/tile_p] scale array is O(P) and would blow SMEM on real
    leaves.  SMEM only serves scalar loads, so the O(N) weights are a
    whole-array VMEM block.  The op sequence is mirrored verbatim by
    ``ref.q8_tile`` so interpret mode matches the oracle bit-for-bit.
    """
    s = s_ref[...].astype(jnp.float32)            # [N, 1]
    x = q_ref[...].astype(jnp.float32) * s        # dequantized [N, TP]
    N = x.shape[0]
    J = num_entities
    per = N // J
    do_entity = flags_ref[0] > 0
    do_global = flags_ref[1] > 0

    grouped = x.reshape(J, per, x.shape[1])
    emean = jnp.mean(grouped, axis=1, keepdims=True)
    emean = jnp.broadcast_to(emean, grouped.shape).reshape(x.shape)
    y1 = jnp.where(do_entity, emean, x)

    w = w_ref[...]  # [N, 1]
    gmean = jnp.sum(y1 * w, axis=0, keepdims=True)
    y2 = jnp.where(do_global, jnp.broadcast_to(gmean, y1.shape), y1)
    o_ref[...] = y2


def _ragged_q8_kernel(
    flags_ref, w_ref, m_ref, q_ref, s_ref, o_ref, *, num_entities: int
):
    """Ragged (per-class cut) variant of ``_q8_kernel`` (DESIGN.md §14).

    ``m_ref`` (VMEM [N, 1] f32, 0/1) marks the clients whose class holds this
    shard's units in the aggregating tier.  Non-members neither contribute
    to nor receive either reduction level — their replica of these units
    belongs to a different tier and is aggregated by that tier's schedule:

      entity:  em_g = Σ_{i∈g} member_i·x_i / max(Σ_{i∈g} member_i, 1)
               y1_i = (do_entity ∧ member_i ∧ Σ_g > 0) ? em_g : x_i
      global:  sw   = Σ_i w_i·member_i
               gm   = Σ_i y1_i·(w_i·member_i) / (sw > 0 ? sw : 1)
               y2_i = (do_global ∧ member_i ∧ sw > 0) ? gm : y1_i

    With member ≡ 1 and weights already normalized (Σ w = 1, exact for
    uniform 1/N at power-of-two N) every guard divide is by 1.0 or the
    exact group size, so the result is bit-identical to ``_q8_kernel`` —
    the collapse the interpret-mode tests pin.  The member mask is folded
    into the scale (``s·1 == s`` exactly), so the masked entity sum
    dequantizes exactly as ``_q8_kernel``'s does.  Mirrored per tile by
    ``ref.ragged_q8_tile``.
    """
    s = s_ref[...].astype(jnp.float32)            # [N, 1]
    member = m_ref[...]                           # [N, 1]
    qf = q_ref[...].astype(jnp.float32)
    x = qf * s                                    # dequantized [N, TP]
    xm = qf * (s * member)                        # member-masked dequant
    N = x.shape[0]
    J = num_entities
    per = N // J
    do_entity = flags_ref[0] > 0
    do_global = flags_ref[1] > 0
    TP = x.shape[1]

    mg = member.reshape(J, per, 1)
    sg = jnp.sum(mg, axis=1, keepdims=True)            # [J, 1, 1]
    emean = jnp.sum(xm.reshape(J, per, TP), axis=1, keepdims=True) / jnp.maximum(
        sg, 1.0
    )
    emean = jnp.broadcast_to(emean, (J, per, TP)).reshape(x.shape)
    sg_rows = jnp.broadcast_to(sg, (J, per, TP)).reshape(x.shape)
    y1 = jnp.where(do_entity & (member > 0.0) & (sg_rows > 0.0), emean, x)

    wm = w_ref[...] * member                               # [N, 1]
    sw = jnp.sum(wm, axis=0, keepdims=True)                # [1, 1]
    gmean = jnp.sum(y1 * wm, axis=0, keepdims=True) / jnp.where(
        sw > 0.0, sw, 1.0
    )
    y2 = jnp.where(
        do_global & (member > 0.0) & (sw > 0.0),
        jnp.broadcast_to(gmean, y1.shape),
        y1,
    )
    o_ref[...] = y2


def quantized_tiered_aggregate_pallas(
    q: jax.Array,          # [N, Pp] int8, Pp % tile_p == 0 (wire payload)
    scales: jax.Array,     # [N, Pp // tile_p] f32 per-tile scales
    weights: jax.Array,    # [N] f32, sums to 1
    do_entity: jax.Array,  # scalar bool/int
    do_global: jax.Array,  # scalar bool/int
    num_entities: int,
    tile_p: int = TILE_P,
    interpret: bool = False,
) -> jax.Array:
    """Fused dequantize → two-level aggregate over the q8 wire format.

    Returns the aggregated model in f32 [N, Pp]; the padded tail (zeros on
    the wire) is the caller's to slice off.
    """
    N, Pp = q.shape
    assert N % num_entities == 0, (N, num_entities)
    assert Pp % tile_p == 0, (Pp, tile_p)
    assert scales.shape == (N, Pp // tile_p), (scales.shape, q.shape, tile_p)
    flags = jnp.stack(
        [do_entity.astype(jnp.int32), do_global.astype(jnp.int32)]
    )

    grid = (Pp // tile_p,)
    return pl.pallas_call(
        functools.partial(_q8_kernel, num_entities=num_entities),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # flags
            grid=grid,
            in_specs=[
                pl.BlockSpec((N, 1), lambda i, *_: (0, 0)),  # weights
                pl.BlockSpec((N, tile_p), lambda i, *_: (0, i)),
                pl.BlockSpec((None, N, 1), lambda i, *_: (i, 0, 0)),  # scales
            ],
            out_specs=pl.BlockSpec((N, tile_p), lambda i, *_: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((N, Pp), jnp.float32),
        interpret=interpret,
    )(flags, weights.astype(jnp.float32)[:, None], q, _scale_columns(scales))


def ragged_quantized_tiered_aggregate_pallas(
    q: jax.Array,          # [N, Pp] int8, Pp % tile_p == 0 (wire payload)
    scales: jax.Array,     # [N, Pp // tile_p] f32 per-tile scales
    weights: jax.Array,    # [N] f32, sums to 1 over the member set
    member: jax.Array,     # [N] f32/bool, 1 = client's class holds these units
    do_entity: jax.Array,  # scalar bool/int
    do_global: jax.Array,  # scalar bool/int
    num_entities: int,
    tile_p: int = TILE_P,
    interpret: bool = False,
) -> jax.Array:
    """Fused dequantize → member-masked two-level aggregate (q8 wire).

    The per-class-cut sync path (``tiers.ragged_synchronize``) applied to
    one unit-range shard whose tier membership is uniform across columns
    but ragged across clients.  ``member`` rides SMEM scalar prefetch next
    to the flags and weights — it is O(N), like them.  An all-ones member
    is bit-identical to ``quantized_tiered_aggregate_pallas`` (see
    ``_ragged_q8_kernel``).
    """
    N, Pp = q.shape
    assert N % num_entities == 0, (N, num_entities)
    assert Pp % tile_p == 0, (Pp, tile_p)
    assert scales.shape == (N, Pp // tile_p), (scales.shape, q.shape, tile_p)
    flags = jnp.stack(
        [do_entity.astype(jnp.int32), do_global.astype(jnp.int32)]
    )

    grid = (Pp // tile_p,)
    return pl.pallas_call(
        functools.partial(_ragged_q8_kernel, num_entities=num_entities),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # flags
            grid=grid,
            in_specs=[
                pl.BlockSpec((N, 1), lambda i, *_: (0, 0)),  # weights
                pl.BlockSpec((N, 1), lambda i, *_: (0, 0)),  # member
                pl.BlockSpec((N, tile_p), lambda i, *_: (0, i)),
                pl.BlockSpec((None, N, 1), lambda i, *_: (i, 0, 0)),  # scales
            ],
            out_specs=pl.BlockSpec((N, tile_p), lambda i, *_: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((N, Pp), jnp.float32),
        interpret=interpret,
    )(
        flags,
        weights.astype(jnp.float32)[:, None],
        member.astype(jnp.float32)[:, None],
        q,
        _scale_columns(scales),
    )
