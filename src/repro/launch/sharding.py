"""PartitionSpec rules for every architecture family and execution path.

Layout contract (DESIGN.md §5):

* Training (Engine A): every parameter leaf is client-stacked on axis 0 —
  sharded over the client mesh axes (``data``, or ``pod+data`` multi-pod).
  Trailing *weight* dimensions get Megatron-style TP over ``model``:
  up-projections shard their output dim, down-projections their input dim,
  embedding/unembedding shard the vocab, MoE experts shard the expert axis
  (expert parallelism), Mamba projections shard the channel dim.
* Serving: one aggregated model copy — same TP rules, no client axis;
  decode batch shards over the client axes; the ``long_500k`` single-request
  shape shards the KV cache on the *sequence* dim over ``data`` (scores are
  combined by a GSPMD-inserted all-reduce) and SSM state on heads over
  ``model``.

Every rule is divisibility-guarded: a dim that does not divide its mesh
axis stays replicated (noted per-arch in the roofline table).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# name -> (axis position from the END of the leaf) to shard over `model`.
_TP_RULES: Dict[str, Optional[int]] = {
    # attention
    "wq": -1, "wk": -1, "wv": -1, "wo": -2,
    "bq": -1, "bk": -1, "bv": -1,
    "q_norm": None, "k_norm": None,
    # mlp
    "w1": -1, "w3": -1, "w2": -2,
    # embeddings
    "embed": -2, "unembed": -1, "proj": -1, "enc_pos": None,
    # moe (expert axis first; see _pspec_for_leaf)
    "router": None,
    # mamba
    "in_proj": -1, "out_proj": -2, "conv_w": -1, "gate_norm": -1,
    "A_log": None, "D": None, "dt_bias": None,
    # norms / vgg
    "norm": None, "w": None, "b": None,
}

_MOE_KEYS = {"w1", "w2", "w3"}


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for pp in path:
        if hasattr(pp, "key"):
            names.append(str(pp.key))
        elif hasattr(pp, "idx"):
            names.append(str(pp.idx))
    return tuple(names)


def _pspec_for_leaf(
    names: Tuple[str, ...],
    shape: Tuple[int, ...],
    tp: int,
    tp_axis: str,
    client_axes: Optional[Tuple[str, ...]],
) -> P:
    rank = len(shape)
    entries: list = [None] * rank
    if client_axes:
        entries[0] = client_axes if len(client_axes) > 1 else client_axes[0]
    leaf = names[-1] if names else ""
    in_moe = "moe" in names
    pos = _TP_RULES.get(leaf, None)
    if in_moe and leaf in _MOE_KEYS:
        # expert parallelism when E divides, else fall back to ff sharding
        e_pos = -3
        if shape[e_pos] % tp == 0:
            pos = e_pos
        else:
            pos = -1 if leaf in ("w1", "w3") else -2
    if pos is not None:
        idx = rank + pos
        clientish = 1 if client_axes else 0
        if idx >= clientish and shape[idx] % tp == 0 and shape[idx] >= tp:
            entries[idx] = tp_axis
    return P(*entries)


def param_pspecs(
    params: Any,
    *,
    tp: int = 16,
    tp_axis: str = "model",
    client_axes: Optional[Tuple[str, ...]] = None,
) -> Any:
    """Pytree of PartitionSpec matching ``params`` (shape tree or arrays)."""

    def f(path, leaf):
        shape = leaf.shape
        return _pspec_for_leaf(_path_names(path), shape, tp, tp_axis, client_axes)

    return jax.tree_util.tree_map_with_path(f, params)


def train_pspecs(
    tree: Any,
    client_axes: Tuple[str, ...],
    num_clients: Optional[int] = None,
) -> Any:
    """Client-axis-only pspecs for the Engine-A *training* step on a
    mesh: shard axis 0 of every client-stacked leaf over
    the client mesh axes, replicate everything else (scalar bookkeeping
    like adam's step counter).

    Deliberately distinct from ``param_pspecs``: Megatron TP over
    ``model`` is a *serving* feature here — the training step keeps
    weights replicated across ``model`` and shards only the client
    axis.  (Calling ``param_pspecs(tp=1, ...)`` would NOT express that:
    every weight dim divides 1, so every ``_TP_RULES`` entry would
    spuriously shard over ``model``.)

    ``num_clients`` restricts the client-stacked test to leaves whose
    leading dim matches (safe over mixed trees like a ``TrainState``);
    ``None`` treats every non-scalar leaf as client-stacked.
    """
    ca = client_axes if len(client_axes) > 1 else client_axes[0]

    def f(leaf):
        shape = getattr(leaf, "shape", ())
        stacked = len(shape) > 0 and (
            num_clients is None or shape[0] == num_clients
        )
        if stacked:
            return P(ca, *([None] * (len(shape) - 1)))
        return P()

    return jax.tree.map(f, tree)


def batch_pspecs(batch: Any, client_axes: Tuple[str, ...]) -> Any:
    """Client-stacked batch leaves [N, b, ...]: shard the client axis."""
    ca = client_axes if len(client_axes) > 1 else client_axes[0]

    def f(leaf):
        return P(ca, *([None] * (len(leaf.shape) - 1)))

    return jax.tree.map(f, batch)


def opt_pspecs(opt_state: Any, pps: Any, opt_name: str) -> Any:
    """Optimizer-state pspecs follow the parameter pspecs leaf-for-leaf."""
    if opt_name == "sgd":
        return ()
    if opt_name == "momentum":
        return pps
    if opt_name == "adam":
        return {"m": pps, "v": pps, "t": P()}
    raise ValueError(opt_name)


def state_pspecs(spec_params: Any, opt_name: str, *, tp: int, client_axes):
    from ..core.engine import TrainState

    pps = param_pspecs(spec_params, tp=tp, client_axes=client_axes)
    return TrainState(
        params=pps, opt_state=opt_pspecs(None, pps, opt_name), step=P()
    )


# --------------------------------------------------------------------------- #
# serving
# --------------------------------------------------------------------------- #

# cache leaf name -> {mode: axis position from END to shard, mesh axes}
_CACHE_RULES = {
    # leaf: (batch_pos, long_pos, long_axis)
    "k": (-4, -3, "data"),
    "v": (-4, -3, "data"),
    "xk": (-4, -3, "data"),
    "xv": (-4, -3, "data"),
    "conv": (-3, -1, "model"),
    "state": (-4, -3, "model"),
    "positions": (None, None, None),
    "index": (None, None, None),
}


def cache_pspecs(
    caches: Any,
    *,
    batch: int,
    client_axes: Tuple[str, ...],
    tp: int = 16,
    long_context: bool = False,
    seq_shard: bool = False,
) -> Any:
    """Decode caches: shard batch when it divides; long_500k shards the
    sequence (attention) / heads (SSM) instead.

    ``seq_shard=True`` (perf, see EXPERIMENTS.md sect. Perf/qwen3-decode):
    additionally shard the attention-cache *sequence* dim over ``model``.
    The baseline keeps the cache replicated across model ranks, which (a)
    multiplies per-chip cache memory by tp and (b) makes GSPMD all-gather
    the full updated cache every token to satisfy the replicated output
    sharding. Seq-sharding stores 1/tp of the cache per chip and reduces
    the per-token collective to a scores-gather (~1000x smaller)."""
    import math

    n_client = math.prod(
        {"data": 16, "pod": 2}.get(a, 1) for a in client_axes
    )
    ca = client_axes if len(client_axes) > 1 else client_axes[0]

    def f(path, leaf):
        names = _path_names(path)
        leafname = names[-1] if names else ""
        rule = _CACHE_RULES.get(leafname)
        rank = len(leaf.shape)
        entries: list = [None] * rank
        if rule is None:
            return P(*entries)
        b_pos, l_pos, l_axis = rule
        if not long_context:
            if b_pos is not None and leaf.shape[rank + b_pos] % n_client == 0 \
               and leaf.shape[rank + b_pos] >= n_client:
                entries[rank + b_pos] = ca
            if seq_shard and l_pos is not None and leafname in ("k", "v") \
               and leaf.shape[rank + l_pos] % tp == 0 \
               and leaf.shape[rank + l_pos] >= tp:
                entries[rank + l_pos] = "model"
        else:
            if l_pos is not None:
                size = {"data": 16, "model": tp}[l_axis]
                if leaf.shape[rank + l_pos] % size == 0 and leaf.shape[rank + l_pos] >= size:
                    entries[rank + l_pos] = l_axis
        return P(*entries)

    return jax.tree_util.tree_map_with_path(f, caches)


def token_pspec(batch: int, client_axes: Tuple[str, ...]) -> P:
    import math

    n_client = math.prod({"data": 16, "pod": 2}.get(a, 1) for a in client_axes)
    ca = client_axes if len(client_axes) > 1 else client_axes[0]
    if batch % n_client == 0 and batch >= n_client:
        return P(ca, None)
    return P(None, None)


def to_shardings(mesh: Mesh, pspecs: Any) -> Any:
    return jax.tree.map(
        lambda ps: NamedSharding(mesh, ps),
        pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )
