"""The program's tracing vocabulary: named device phases and one host span.

Device phases are ``jax.named_scope``s with fixed names.  They change the
op metadata of the compiled round and nothing else (no new program, no
restructured loop), so a profiler trace can charge each device op to the
phase in its ``tf_op`` path:

* ``hsfl.grad`` — the per-client forward and backward (the whole
  ``vmap(value_and_grad(loss))`` call, so backward ops inherit it);
* ``hsfl.opt`` — the optimizer update;
* ``hsfl.sync.t{m}.entity`` / ``hsfl.sync.t{m}.fed`` — one aggregation
  level of tier m (counted from 1): its last level is the fed-server level,
  any level before it an entity level.

The ``mla_moe`` family names its sub-layers inside ``hsfl.grad`` (a phase's
time is charged to the innermost name, and ``hsfl.grad`` takes every phase
nested in its name, so it keeps its meaning):

* ``hsfl.grad.mla`` — latent attention;
* ``hsfl.grad.mla.attn`` — its scores, causal softmax and value product
  (the splash kernel on the TPU), forward and backward;
* ``hsfl.grad.moe.route`` — the gate, the selection, dispatch and combine;
* ``hsfl.grad.moe.experts`` — the grouped products over the held experts;
* ``hsfl.grad.moe.shared`` — the shared experts.

The host span ``loader`` times ``FederatedLoader.next_round``.  It is
stamped with ``time.time_ns()`` (``CLOCK_REALTIME``, the clock of the
profiler's host timestamps) into a bounded buffer that ``host_spans()``
drains.  It is always on: two clock reads and an append per round.
"""
from __future__ import annotations

import collections
import time
from contextlib import contextmanager
from typing import Deque, Iterator, List, Tuple

import jax

GRAD = "hsfl.grad"
OPT = "hsfl.opt"
MLA = "hsfl.grad.mla"
MLA_ATTN = "hsfl.grad.mla.attn"
MOE_ROUTE = "hsfl.grad.moe.route"
MOE_EXPERTS = "hsfl.grad.moe.experts"
MOE_SHARED = "hsfl.grad.moe.shared"
LOADER = "loader"
MAX_HOST_SPANS = 4096

Span = Tuple[str, int, int]  # (name, start_ns, end_ns) on time.time_ns()

_spans: Deque[Span] = collections.deque(maxlen=MAX_HOST_SPANS)


def sync_level(m: int, level: int, n_levels: int) -> str:
    """Phase name of aggregation level ``level`` (of ``n_levels``) of the
    0-indexed tier ``m``."""
    kind = "fed" if level == n_levels - 1 else "entity"
    return f"hsfl.sync.t{m + 1}.{kind}"


def scope(name: str):
    """A device phase: ops traced inside carry ``name`` in their metadata."""
    return jax.named_scope(name)


@contextmanager
def host_span(name: str) -> Iterator[None]:
    """Record ``(name, start_ns, end_ns)`` of the block on ``time.time_ns()``."""
    t0 = time.time_ns()
    try:
        yield
    finally:
        _spans.append((name, t0, time.time_ns()))


def host_spans() -> List[Span]:
    """The recorded host spans, oldest first (at most ``MAX_HOST_SPANS``),
    and clear the buffer."""
    out = list(_spans)
    _spans.clear()
    return out
