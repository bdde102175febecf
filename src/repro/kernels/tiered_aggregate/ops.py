"""jit'd public wrappers: apply the fused aggregation to whole pytrees.

``aggregate_tree`` flattens a client-stacked pytree (leaves [N, ...]) into
one [N, P] buffer view per leaf, runs the kernel, and reassembles —
exactly what ``tiers.synchronize`` does per (tier, level), but in one fused
HBM pass per leaf. The kernels compile for the TPU by default; on a CPU
pass ``interpret=True`` to run the same kernel body through the Pallas
interpreter.

``tiered_aggregate_q8`` is the compressed-wire entry (DESIGN.md §9): it
takes the raw [N, P] shard, produces the int8-plus-per-tile-scale wire
payload via the shared ``compress.quantize`` codec, and runs the fused
dequantize→aggregate kernel over it — the HBM-heavy read is the int8
payload, ~4× less traffic than the f32 path.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ...compress.quantize import q8_quantize
from .ref import q8_tile, q8_tiles_apply, ragged_q8_tile, tiered_aggregate_ref
from .tiered_aggregate import (
    TILE_P,
    quantized_tiered_aggregate_pallas,
    ragged_quantized_tiered_aggregate_pallas,
    tiered_aggregate_pallas,
)


@partial(
    jax.jit, static_argnames=("num_entities", "tile_p", "use_pallas", "interpret")
)
def tiered_aggregate(
    x: jax.Array,
    weights: jax.Array,
    do_entity: jax.Array,
    do_global: jax.Array,
    num_entities: int,
    tile_p: int = TILE_P,
    use_pallas: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """[N, P] fused two-level aggregation (see ref.py for semantics)."""
    do_entity = jnp.asarray(do_entity)
    do_global = jnp.asarray(do_global)
    if use_pallas:
        return tiered_aggregate_pallas(
            x, weights, do_entity, do_global, num_entities,
            tile_p=tile_p, interpret=interpret,
        )
    return tiered_aggregate_ref(x, weights, do_entity, do_global, num_entities)


# The wire payload is a program of its own, as on a real link: the
# aggregation reads the int8 payload from HBM.  Fused into one program with
# the aggregation, XLA would round each branch's dequantize differently.
_q8_wire = jax.jit(q8_quantize, static_argnames=("tile",))
_AGG_STATIC = ("num_entities", "tile_p", "use_pallas", "interpret")


@partial(jax.jit, static_argnames=_AGG_STATIC)
def _aggregate_q8(
    q, scales, weights, do_entity, do_global, *, num_entities, tile_p,
    use_pallas, interpret,
):
    if use_pallas:
        return quantized_tiered_aggregate_pallas(
            q, scales, weights, do_entity, do_global, num_entities,
            tile_p=tile_p, interpret=interpret,
        )
    return q8_tiles_apply(
        q8_tile, q, scales, tile_p, weights.astype(jnp.float32)[:, None],
        do_entity, do_global, num_entities,
    )


@partial(jax.jit, static_argnames=_AGG_STATIC)
def _ragged_aggregate_q8(
    q, scales, weights, member, do_entity, do_global, *, num_entities,
    tile_p, use_pallas, interpret,
):
    if use_pallas:
        return ragged_quantized_tiered_aggregate_pallas(
            q, scales, weights, member, do_entity, do_global, num_entities,
            tile_p=tile_p, interpret=interpret,
        )
    return q8_tiles_apply(
        ragged_q8_tile, q, scales, tile_p,
        weights.astype(jnp.float32)[:, None],
        member.astype(jnp.float32)[:, None],
        do_entity, do_global, num_entities,
    )


def tiered_aggregate_q8(
    x: jax.Array,
    weights: jax.Array,
    do_entity: jax.Array,
    do_global: jax.Array,
    num_entities: int,
    tile_p: int = TILE_P,
    key: Optional[jax.Array] = None,
    use_pallas: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Quantize [N, P] to the q8 wire format, aggregate fused, return f32.

    ``key`` switches the codec to stochastic (unbiased) rounding; without
    it the path is deterministic, which is what the bit-for-bit oracle
    tests and the engine-equality tests pin.

    The ``use_pallas=False`` fallback vmaps the oracle's per-tile
    arithmetic (``ref.q8_tile``) over all tiles at once (the per-tile
    ``ref.py`` loop is the *test oracle* — tracing it inside jit would
    unroll O(P/tile_p) subgraphs).
    """
    q, scales = _q8_wire(x.astype(jnp.float32), tile_p, key=key)
    out = _aggregate_q8(
        q, scales, weights, jnp.asarray(do_entity), jnp.asarray(do_global),
        num_entities=num_entities, tile_p=tile_p, use_pallas=use_pallas,
        interpret=interpret,
    )
    return out[:, : x.shape[1]]


def ragged_tiered_aggregate_q8(
    x: jax.Array,
    weights: jax.Array,
    member: jax.Array,
    do_entity: jax.Array,
    do_global: jax.Array,
    num_entities: int,
    tile_p: int = TILE_P,
    key: Optional[jax.Array] = None,
    use_pallas: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Ragged (per-class cut) q8 aggregation of an [N, P] unit-range shard.

    ``member`` [N] marks the clients whose class holds this shard's units
    in the aggregating tier (``tiers.class_tier_members`` column); they
    alone feed and receive the two reduction levels.  All-ones member with
    normalized weights reproduces ``tiered_aggregate_q8`` bit-for-bit.
    The ``use_pallas=False`` fallback vmaps ``ref.ragged_q8_tile`` over all
    tiles at once (the per-tile ``ref.py`` loop stays the test oracle).
    """
    q, scales = _q8_wire(x.astype(jnp.float32), tile_p, key=key)
    out = _ragged_aggregate_q8(
        q, scales, weights, member, jnp.asarray(do_entity),
        jnp.asarray(do_global), num_entities=num_entities, tile_p=tile_p,
        use_pallas=use_pallas, interpret=interpret,
    )
    return out[:, : x.shape[1]]


def aggregate_tree(
    tree: Any,
    weights: jax.Array,
    do_entity: jax.Array,
    do_global: jax.Array,
    num_entities: int,
    tile_p: int = TILE_P,
    use_pallas: bool = True,
    interpret: bool = False,
    quantized: bool = False,
) -> Any:
    """Apply the fused aggregation leaf-wise to a client-stacked pytree.

    ``quantized=True`` routes every leaf through the q8 wire (the MA
    hot-spot at ~4× lower HBM traffic); outputs are cast back to the leaf
    dtype.  ``tile_p`` is both the kernel tile AND the codec's scale-tile —
    pass the same value the analytic layer priced (``Int8Stochastic.tile``)
    so the executed ω matches the Theorem-1 inflation.
    """

    def f(x):
        n = x.shape[0]
        flat = x.reshape(n, -1)
        if quantized:
            out = tiered_aggregate_q8(
                flat, weights, do_entity, do_global, num_entities,
                tile_p=tile_p, use_pallas=use_pallas, interpret=interpret,
            ).astype(x.dtype)
        else:
            out = tiered_aggregate(
                flat, weights, do_entity, do_global, num_entities,
                tile_p=tile_p, use_pallas=use_pallas, interpret=interpret,
            )
        return out.reshape(x.shape)

    return jax.tree.map(f, tree)
