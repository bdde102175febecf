"""The comparison that decides ``correct``: the program against the plain
reference (``bench/reference/<family>.py``), which imports nothing of the
program and takes nothing it made but the inputs it was fed.

Training: the reference runs the HSFL algorithm itself, per client and in
float32 at ``highest`` matmul precision: each client takes an SGD step on
its own rows, then every tier's parameters are averaged as the plan says
(tier m over its J_m entities every round, over all clients every I_m
rounds; the top tier over all clients every round).  It follows the same
first rounds as the program, on the same rows, from weights it draws
itself from the seed.  The numbers compared:

* ``loss_gap``: the largest relative gap of a round's loss;
* ``grad_gap``: the first gradient as the optimizer got it, read from the
  parameters after one round (``(p0 - p1) / lr``, after that round's
  syncs), by the worst leaf;
* ``change3_gap``, ``change_gap``: the change of the parameters after
  three rounds, and after the first round whose syncs reach every tier,
  by the worst leaf.

A leaf's gap is the gap between the program's norm and the reference's,
over the larger of the reference's norm of that leaf and of the median
leaf.  Leaves whose reference gradient is under a thousandth of the
median leaf's move by round-off alone and are left out.
"""
from __future__ import annotations

import statistics
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

NEGLIGIBLE = 1e-3  # of the median leaf's first gradient


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: Optional[Sequence[str]] = None) -> float:
    names = list(keep if keep is not None else ref)
    med = statistics.median(ref[n] for n in ref)
    worst = 0.0
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        worst = max(worst, gap)
    return worst


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    return max(abs(a - b) / max(abs(b), 1e-30) for a, b in zip(prog, ref))


def moving_leaves(grad_ref: Dict[str, float]) -> List[str]:
    med = statistics.median(grad_ref.values())
    return [n for n, v in grad_ref.items() if v >= NEGLIGIBLE * med]


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """The compared numbers from two sets of readings (see the module)."""
    keep = moving_leaves(ref["grad"])
    return {
        "loss_gap": loss_gap(prog["losses"], ref["losses"]),
        "grad_gap": leaf_gap(prog["grad"], ref["grad"], keep),
        "change3_gap": leaf_gap(prog["change3"], ref["change3"], keep),
        "change_gap": leaf_gap(prog["change"], ref["change"], keep),
    }


def hsfl_reference(ref, cfg: dict, plan: dict, lr: float, batches: List[dict],
                   seed: int, *, dtype=None, batch_rows: Optional[int] = None) -> dict:
    """Readings of the reference over ``len(batches)`` rounds.

    ``plan`` holds ``clients``, ``edges``, ``cuts`` and ``intervals``.
    ``dtype`` (default float32) is the precision of parameters and
    activations; ``batch_rows`` keeps only that many rows of each client's
    batch.  Both exist for the control and the planted faults."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    N, M = plan["clients"], len(plan["intervals"])
    entities = (N, plan["edges"], 1)
    with jax.default_matmul_precision("highest"):
        p0 = ref.init(cfg, jax.random.PRNGKey(seed), dtype)
        tiers = ref.tiers(cfg, p0, plan["cuts"])
        grad_fn = jax.jit(jax.value_and_grad(lambda p, b: ref.loss(cfg, p, b)))
        step_fn = jax.jit(lambda p, g: jax.tree.map(
            lambda x, y: (x - lr * y).astype(x.dtype), p, g))
        norms = jax.jit(lambda a, b: ref.named_norms(
            jax.tree.map(lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))

        def sync_leaf(leaves, tier, fed):
            x = jnp.stack(leaves).astype(jnp.float32)  # [N, ...]
            t = jnp.asarray(tier)
            t = t.reshape(t.shape + (1,) * (x.ndim - 1 - t.ndim))
            out = x
            for m in range(M):
                y = x
                if m == M - 1:
                    y = jnp.broadcast_to(y.mean(0, keepdims=True), y.shape)
                else:
                    J = entities[m]
                    if J < N:
                        g = y.reshape((J, N // J) + y.shape[1:])
                        y = jnp.broadcast_to(g.mean(1, keepdims=True), g.shape).reshape(y.shape)
                    if fed[m]:
                        y = jnp.broadcast_to(y.mean(0, keepdims=True), y.shape)
                out = jnp.where(t[None] == m, y, out)
            return [o.astype(leaves[0].dtype) for o in out]

        def sync_all(clients, fed):
            per = [jax.tree.leaves(c) for c in clients]
            cols = [sync_leaf([p[j] for p in per], tier_leaves[j], fed)
                    for j in range(len(tier_leaves))]
            return [jax.tree.unflatten(treedef, [c[i] for c in cols])
                    for i in range(N)]

        treedef = jax.tree.structure(p0)
        tier_leaves = jax.tree.leaves(tiers)
        sync_fn = jax.jit(sync_all, static_argnums=(1,), donate_argnums=(0,))
        clients = [p0] * N
        losses, readings = [], {}
        for r, batch in enumerate(batches):
            round_losses = []
            for i in range(N):
                b = {k: jnp.asarray(v[i][:batch_rows]) for k, v in batch.items()}
                l, g = grad_fn(clients[i], b)
                clients[i] = step_fn(clients[i], g)
                round_losses.append(float(l))
            losses.append(float(np.mean(round_losses)))
            fed = tuple((r + 1) % I == 0 for I in plan["intervals"])
            clients = sync_fn(clients, fed)
            if r == 0:
                readings["grad"] = _client_norms(
                    norms, [(p0, c) for c in clients], scale=1.0 / lr)
            if r == 2:
                readings["change3"] = _client_norms(norms, [(c, p0) for c in clients])
        readings["change"] = _client_norms(norms, [(c, p0) for c in clients])
        readings["losses"] = losses
    return readings


def _client_norms(norms: Callable, pairs, scale: float = 1.0) -> Dict[str, float]:
    """Norm per leaf of the client-stacked difference of ``(a_i, b_i)``
    pairs, one per client: the root of the sum of the clients' squares."""
    sq: Dict[str, float] = {}
    for ai, bi in pairs:
        for k, v in norms(ai, bi).items():
            sq[k] = sq.get(k, 0.0) + float(v) ** 2
    return {k: float(np.sqrt(v)) * scale for k, v in sq.items()}
