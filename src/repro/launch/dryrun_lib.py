"""Dry-run engine: lower + compile every (arch × shape × mesh) case and
extract the roofline inputs from the compiled artifact.

No env side effects — ``dryrun.py`` (the CLI) sets
``--xla_force_host_platform_device_count=512`` before importing jax and
calls into here. Tests import this module directly under smaller debug
meshes.

Per case we record:
  * ``cost_analysis()``  : HLO FLOPs + bytes accessed   (compute/memory terms)
  * HLO collective ops   : kind, per-device result bytes, group size
                           (collective term — cost_analysis has no ICI info)
  * ``memory_analysis()``: per-device argument/output/temp bytes (fits-check)
"""
from __future__ import annotations

import json
import os
import re
import time
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import get_spec
from ..configs.shapes import LONG_CONTEXT_WINDOW, SHAPES, InputShape, input_specs, sds
from ..core.engine import TrainState, build_train_step_a, init_state_a
from ..core.tiers import default_plan
from ..models.model import SplittableModel
from ..optim import sgd
from . import sharding as sh
from .mesh import client_axes as mesh_client_axes
from .mesh import make_production_mesh, num_clients

# families whose full attention is quadratic -> long_500k runs the
# sliding-window variant (window = 8192); ssm/hybrid run natively.
QUADRATIC_FAMILIES = {"dense", "moe", "vlm", "audio"}

COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "f64": 8, "s32": 4, "u32": 4,
    "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "s16": 2, "u16": 2,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes of every typed buffer in an HLO result type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> List[Dict[str, Any]]:
    """Extract every collective op with its per-device result bytes."""
    out: List[Dict[str, Any]] = []
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+ = (.*?) (" + "|".join(COLLECTIVE_OPS) + r")[.\d]*\(", ls)
        if not m:
            # also catch "ROOT %x = ..."
            m = re.match(
                r"ROOT %?[\w.\-]+ = (.*?) (" + "|".join(COLLECTIVE_OPS) + r")[.\d]*\(",
                ls,
            )
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        rb = _shape_bytes(type_str)
        g = None
        gm = _GROUPS_RE.search(ls)
        if gm:
            g = int(gm.group(2))  # [groups, participants]
        else:
            gl = _GROUPS_LIST_RE.search(ls)
            if gl:
                g = len(gl.group(1).split(","))
        out.append({"op": op, "result_bytes": rb, "group": g})
    return out


def collective_traffic_bytes(colls: List[Dict[str, Any]]) -> float:
    """Per-device ICI traffic model (ring algorithms):
    all-gather: receive ≈ result; all-reduce: 2×result (RS+AG phases);
    reduce-scatter: receive ≈ result×(g−1); all-to-all: result;
    collective-permute: result."""
    total = 0.0
    for c in colls:
        b, g = c["result_bytes"], c["group"] or 2
        if c["op"] == "all-reduce":
            total += 2.0 * b * (g - 1) / g
        elif c["op"] == "all-gather":
            total += b * (g - 1) / g
        elif c["op"] == "reduce-scatter":
            total += b * (g - 1)
        else:
            total += b
    return total


def blockwise_attn_corr_flops(spec, shape, num_devices: int) -> float:
    """Analytic per-device FLOPs executed inside the *blockwise-attention*
    inner scans (layers._blockwise_sdpa), which stay rolled even in unroll
    mode (fully unrolling nq x nk score blocks would explode compile time)
    and are therefore counted once by cost_analysis.

    Only shapes with Sq*Sk > BLOCKWISE_THRESHOLD^2 take that path — in our
    shape set exactly prefill_32k (train_4k sits at the threshold and uses
    the exact-counted full _sdpa; decode attends a cache with Sq=1). The
    inner scans contain NO collectives, so only the compute (and a minor
    memory) term needs this correction. Score flops: QK^T + PV = 4·B·Sq·
    Sk_eff·(H·hd), causal Sk_eff ≈ Sk/2. Per-device = total/num_devices
    (batch over `data`, heads/blocks over `model`)."""
    from ..models.layers import BLOCKWISE_THRESHOLD

    if shape.kind not in ("train", "prefill"):
        return 0.0
    B, S = shape.global_batch, shape.seq_len
    d_attn = spec.num_heads * spec.hd

    def one(Sq: int, Sk: int, n_layers: int, causal: bool = True) -> float:
        if Sq * Sk <= BLOCKWISE_THRESHOLD**2:
            return 0.0
        eff = Sk / 2.0 if causal else float(Sk)
        return 4.0 * B * Sq * eff * d_attn * n_layers

    if spec.family == "ssm":
        total = 0.0
    elif spec.family == "audio":
        # enc self-attn (1500^2) is below threshold; dec self + cross are not
        total = one(S, S, spec.num_layers, causal=True)
        total += one(S, spec.encoder_len, spec.num_layers, causal=False)
    elif spec.family == "hybrid":
        total = one(S, S, spec.n_units)  # one attn layer per super-block
    else:
        total = one(S, S, spec.num_layers)
    mult = 4.0 if shape.kind == "train" else 1.0  # remat: fwd + refwd + 2x bwd
    return mult * total / num_devices


# --------------------------------------------------------------------------- #
# case construction
# --------------------------------------------------------------------------- #


@dataclass
class DryrunCase:
    arch: str
    shape: str
    multi_pod: bool
    opt_name: str = "sgd"
    remat: bool = True
    dtype: Optional[str] = None       # e.g. "bfloat16" override
    seq_shard: bool = False           # sequence-parallel residual constraint
    tag: str = "baseline"
    # XLA's cost_analysis counts a while-loop body ONCE (verified: a scanned
    # 8-layer stack reports exactly 1/8 of the unrolled FLOPs), and HLO-text
    # collectives inside the body likewise appear once. Unrolling the unit
    # scans makes the roofline terms exact; the multi-pod pass keeps the
    # rolled scan (it only proves the `pod` axis shards, and compiles ~2x
    # faster). None = unroll iff single-pod.
    unroll: Optional[bool] = None
    # round specialization (train shapes): "dynamic" = single step with an
    # in-graph cond (baseline), "local" / "sync" = the specialized round
    # steps (perf optimization; see tiers.synchronize).
    round_kind: str = "dynamic"
    # decode shapes: shard the attention-cache sequence dim over `model`
    # (perf; see sharding.cache_pspecs).
    cache_seq_shard: bool = False
    # decode shapes: donate the cache buffers so the in-place .at[].set
    # update aliases instead of copying the full cache every token (perf).
    donate_cache: bool = False
    # train shapes: remat policy ("full" | "dots"); see ModelSpec.remat_policy.
    remat_policy: str = "full"
    # moe archs: install the expert-parallel sharding constraint (perf).
    moe_shard: bool = False
    # train/prefill: lower BLOCKWISE_THRESHOLD so training attention takes the
    # O(S)-memory blockwise path (the Pallas flash kernel is the TPU
    # deployment analogue). NOTE: the blockwise inner scans are counted once
    # by cost_analysis, so the memory term under this flag is a lower bound
    # (attn_corr_flops keeps the compute term exact).
    flash_train: bool = False

    @property
    def resolved_unroll(self) -> bool:
        return (not self.multi_pod) if self.unroll is None else self.unroll


def _spec_for(case: DryrunCase):
    spec = get_spec(case.arch)
    shape = SHAPES[case.shape]
    if shape.name == "long_500k" and spec.family in QUADRATIC_FAMILIES:
        spec = spec.with_window(LONG_CONTEXT_WINDOW)
    if case.dtype:
        spec = spec.with_dtypes(case.dtype, case.dtype)
    if case.remat and shape.kind == "train":
        import dataclasses

        spec = dataclasses.replace(spec, remat=True,
                                   remat_policy=case.remat_policy)
    return spec, shape


def _abstract(tree):
    return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)


def _carry_constraint(mesh):
    def f(h):
        # sequence-parallel residuals: shard S over `model` between units
        if h.ndim == 3 and h.shape[1] % mesh.shape["model"] == 0:
            return jax.lax.with_sharding_constraint(
                h, NamedSharding(mesh, P(None, "model", None))
            )
        return h

    return f


def lower_case(case: DryrunCase, mesh=None):
    """Build + lower one case. Returns (lowered, meta dict)."""
    if mesh is None:
        mesh = make_production_mesh(multi_pod=case.multi_pod)
    ca = tuple(a for a in mesh.axis_names if a != "model")
    tp = mesh.shape["model"]
    n_client = 1
    for a in ca:
        n_client *= mesh.shape[a]

    spec, shape = _spec_for(case)
    if case.flash_train:
        from ..models import layers as _L

        _L.BLOCKWISE_THRESHOLD = 2048
    model = SplittableModel(spec)
    model.scan_unroll = case.resolved_unroll
    if case.seq_shard:
        model.carry_constraint = _carry_constraint(mesh)
    if case.moe_shard:
        def _moe_constraint(b):
            # [G, E, cap, d]: groups over `data`, experts over `model`
            g, e = b.shape[0], b.shape[1]
            pg = "data" if g % mesh.shape["data"] == 0 else None
            pe = "model" if e % mesh.shape["model"] == 0 else None
            return jax.lax.with_sharding_constraint(
                b, NamedSharding(mesh, P(pg, pe, None, None))
            )
        model.moe_constraint = _moe_constraint
        model.moe_groups = mesh.shape["data"]

    meta: Dict[str, Any] = {
        "arch": case.arch, "shape": case.shape,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "axes": list(mesh.axis_names), "kind": shape.kind, "tag": case.tag,
        "window": spec.window, "dtype": str(spec.param_dtype),
        "num_devices": mesh.size,
    }

    if shape.kind == "train":
        opt = sgd(5e-4)
        plan = default_plan(
            spec.n_units, n_client,
            num_pods=mesh.shape.get("pod", 1),
            pod_interval=16 if case.multi_pod else 0,
        )
        state_abs = jax.eval_shape(
            lambda k: init_state_a(model, plan, opt, k), jax.random.PRNGKey(0)
        )
        b_per = shape.global_batch // n_client
        per_client = input_specs(spec, InputShape(shape.name, shape.seq_len, b_per, "train"))
        batch_abs = jax.tree.map(
            lambda s: sds((n_client,) + s.shape, s.dtype), per_client
        )
        pps = sh.param_pspecs(state_abs.params, tp=tp, client_axes=ca)
        state_ps = TrainState(
            params=pps, opt_state=sh.opt_pspecs(None, pps, case.opt_name), step=P()
        )
        state_sh = sh.to_shardings(mesh, state_ps)
        batch_sh = sh.to_shardings(mesh, sh.batch_pspecs(batch_abs, ca))
        fed_round = {"dynamic": None, "local": False, "sync": True}[case.round_kind]
        step = build_train_step_a(model, plan, opt, fed_round=fed_round)
        meta["round_kind"] = case.round_kind
        jitted = jax.jit(
            step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, NamedSharding(mesh, P())),
        )
        lowered = jitted.lower(state_abs, batch_abs)
        meta["plan"] = {
            "cuts": plan.cuts, "intervals": plan.intervals,
            "entities": plan.entities, "num_clients": n_client,
        }
        meta["global_batch"] = shape.global_batch
        meta["seq_len"] = shape.seq_len
        return lowered, meta

    # serving paths: single aggregated model copy
    params_abs = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    pps = sh.param_pspecs(params_abs, tp=tp, client_axes=None)
    params_sh = sh.to_shardings(mesh, pps)
    meta["global_batch"] = shape.global_batch
    meta["seq_len"] = shape.seq_len

    if shape.kind == "prefill":
        batch_abs = input_specs(spec, shape)
        bsh = {}
        for k, v in batch_abs.items():
            b_ax = ca if shape.global_batch % n_client == 0 else ()
            entries = [None] * len(v.shape)
            if b_ax:
                entries[0] = b_ax if len(b_ax) > 1 else b_ax[0]
            bsh[k] = NamedSharding(mesh, P(*entries))
        fwd = lambda p, b: model.forward(p, b)[0]
        jitted = jax.jit(fwd, in_shardings=(params_sh, bsh))
        lowered = jitted.lower(params_abs, batch_abs)
        return lowered, meta

    # decode: one token against a seq_len cache
    B = shape.global_batch
    caches_abs = jax.eval_shape(lambda: model.init_caches(B, shape.seq_len))
    long_ctx = shape.name == "long_500k"
    cps = sh.cache_pspecs(
        caches_abs, batch=B, client_axes=ca, tp=tp, long_context=long_ctx,
        seq_shard=case.cache_seq_shard,
    )
    caches_sh = sh.to_shardings(mesh, cps)
    tok_abs = sds((B, 1), jnp.int32)
    tok_sh = NamedSharding(mesh, sh.token_pspec(B, ca))
    pos_abs = sds((), jnp.int32)

    def serve_step(p, tok, caches, pos):
        return model.decode_step(p, tok, caches, pos)

    logits_entries = [None, "model"]
    if B % n_client == 0 and B >= n_client:
        logits_entries[0] = ca if len(ca) > 1 else ca[0]
    jitted = jax.jit(
        serve_step,
        in_shardings=(params_sh, tok_sh, caches_sh, NamedSharding(mesh, P())),
        out_shardings=(NamedSharding(mesh, P(*logits_entries)), caches_sh),
        donate_argnums=(2,) if case.donate_cache else (),
    )
    lowered = jitted.lower(params_abs, tok_abs, caches_abs, pos_abs)
    return lowered, meta


def run_case(case: DryrunCase, mesh=None, compile_: bool = True) -> Dict[str, Any]:
    t0 = time.time()
    lowered, meta = lower_case(case, mesh)
    meta["lower_s"] = round(time.time() - t0, 2)
    if not compile_:
        return meta
    t1 = time.time()
    compiled = lowered.compile()
    meta["compile_s"] = round(time.time() - t1, 2)

    ca_ = compiled.cost_analysis() or {}
    meta["flops"] = float(ca_.get("flops", 0.0))
    meta["bytes_accessed"] = float(ca_.get("bytes accessed", 0.0))
    spec, shape = _spec_for(case)
    meta["unrolled"] = case.resolved_unroll
    meta["attn_corr_flops"] = blockwise_attn_corr_flops(
        spec, shape, meta["num_devices"]
    )

    mem = compiled.memory_analysis()
    if mem is not None:
        meta["arg_bytes"] = int(getattr(mem, "argument_size_in_bytes", 0))
        meta["out_bytes"] = int(getattr(mem, "output_size_in_bytes", 0))
        meta["temp_bytes"] = int(getattr(mem, "temp_size_in_bytes", 0))
        meta["alias_bytes"] = int(getattr(mem, "alias_size_in_bytes", 0))

    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    meta["collectives"] = _summarize_collectives(colls)
    meta["collective_bytes"] = collective_traffic_bytes(colls)
    meta["hlo_bytes"] = len(hlo)
    return meta


def _summarize_collectives(colls: List[Dict[str, Any]]) -> Dict[str, Any]:
    summary: Dict[str, Any] = {}
    for c in colls:
        s = summary.setdefault(c["op"], {"count": 0, "result_bytes": 0})
        s["count"] += 1
        s["result_bytes"] += c["result_bytes"]
    return summary


def save_result(meta: Dict[str, Any], out_dir: str) -> str:
    os.makedirs(out_dir, exist_ok=True)
    name = f"{meta['arch']}_{meta['shape']}_{meta['mesh']}_{meta['tag']}.json"
    name = name.replace("/", "-")
    path = os.path.join(out_dir, name)
    with open(path, "w") as f:
        json.dump(meta, f, indent=1, default=str)
    return path
