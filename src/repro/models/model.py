"""SplittableModel: uniform frontend/units/head protocol over the model zoo.

The HSFL engine only relies on:
  * ``init_params(key)``  -> {"frontend": .., "units": <stacked [U, ...]>, "head": ..}
  * ``loss_fn(params, batch)`` / ``forward(params, batch)``
  * unit stacks being stacked on axis 0 so cut ranges are slices.

Families: dense | moe | ssm | hybrid | vlm | audio (enc-dec) | mla_moe
(DeepSeek-V3: leading dense layers in the frontend, then MLA + routed and
shared experts per unit).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax import lax

from . import layers as L
from .spec import ModelSpec

Params = Dict[str, Any]


class SplittableModel:
    def __init__(self, spec: ModelSpec):
        self.spec = spec
        # optional hook: sharding constraint applied to the residual stream
        # after every unit (sequence-parallelism; set by launch/dryrun_lib).
        self.carry_constraint = None
        # optional hooks: sharding constraint applied to the MoE dispatch
        # buffer / expert outputs, and the dispatch group count (expert
        # parallelism; set by launch code — see layers.moe).
        self.moe_constraint = None
        self.moe_groups = 1
        # full scan unroll: XLA's cost_analysis counts a while-loop body
        # ONCE (not x trip count), and collectives inside the body likewise
        # appear once in the HLO text. The dry-run sets this so the roofline
        # terms are exact; the training path keeps the rolled scan.
        self.scan_unroll = False

    @property
    def _unroll(self):
        return True if self.scan_unroll else 1


    @property
    def _remat(self):
        """jax.checkpoint partial with the spec's remat policy."""
        if self.spec.remat_policy == "dots":
            return partial(
                jax.checkpoint,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        if self.spec.remat_policy == "outs":
            # save the post-collective sublayer outputs (attn_out / ffn_out,
            # named below): the backward pass then skips both the re-forward
            # matmuls AND their TP all-reduces, at +2 activations/unit of
            # memory (MaxText-style minimal policy).
            return partial(
                jax.checkpoint,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "attn_out", "ffn_out"
                ),
            )
        return jax.checkpoint

    # ------------------------------------------------------------------ #
    # init
    # ------------------------------------------------------------------ #
    def _init_unit(self, key, kind: str) -> Params:
        spec = self.spec
        ks = jax.random.split(key, 16)
        if kind == "dense":
            return {"attn": L.init_attention(ks[0], spec), "mlp": L.init_mlp(ks[1], spec)}
        if kind == "moe":
            return {"attn": L.init_attention(ks[0], spec), "moe": L.init_moe(ks[1], spec)}
        if kind == "mla_moe":
            return {"attn": L.init_mla(ks[0], spec), "moe": L.init_routed_moe(ks[1], spec)}
        if kind == "ssm":
            return {"mamba": L.init_mamba(ks[0], spec)}
        if kind == "hybrid":
            per = spec.attn_period
            n_m = per - 1
            n_moe = per // spec.moe_period
            n_mlp = per - n_moe
            return {
                "attn": L.init_attention(ks[0], spec),
                "mamba": jax.vmap(lambda k: L.init_mamba(k, spec))(
                    jax.random.split(ks[1], n_m)
                ),
                "moe": jax.vmap(lambda k: L.init_moe(k, spec))(
                    jax.random.split(ks[2], n_moe)
                ),
                "mlp": jax.vmap(lambda k: L.init_mlp(k, spec))(
                    jax.random.split(ks[3], n_mlp)
                ),
            }
        if kind == "enc":
            return {
                "attn": L.init_attention(ks[0], spec),
                "mlp": L.init_mlp(ks[1], spec, gelu=True),
            }
        if kind == "dec":
            return {
                "attn": L.init_attention(ks[0], spec),
                "xattn": L.init_attention(ks[1], spec, cross=True),
                "mlp": L.init_mlp(ks[2], spec, gelu=True),
            }
        raise ValueError(kind)

    def init_params(self, key) -> Params:
        spec = self.spec
        kf, ku, kh = jax.random.split(key, 3)
        V, d = spec.padded_vocab, spec.d_model
        frontend: Params = {
            "embed": (jax.random.normal(kf, (V, d)) * 0.02).astype(spec.pdtype)
        }
        if spec.family == "vlm":
            frontend["proj"] = L._dense_init(
                jax.random.fold_in(kf, 1), (d, d), spec.pdtype
            )
        if spec.family == "audio":
            frontend["proj"] = L._dense_init(
                jax.random.fold_in(kf, 1), (d, d), spec.pdtype
            )
            frontend["enc_pos"] = (
                jax.random.normal(jax.random.fold_in(kf, 2), (spec.encoder_len, d))
                * 0.02
            ).astype(spec.pdtype)
        if spec.first_dense:
            def dense_layer(k):
                ka, km = jax.random.split(k)
                return {"attn": L.init_mla(ka, spec), "mlp": L.init_mlp(km, spec)}

            frontend["dense"] = jax.vmap(dense_layer)(
                jax.random.split(jax.random.fold_in(kf, 1), spec.first_dense)
            )

        if spec.family == "audio":
            ne, nd = spec.encoder_layers, spec.num_layers
            units = {
                "enc": jax.vmap(lambda k: self._init_unit(k, "enc"))(
                    jax.random.split(ku, ne)
                ),
                "dec": jax.vmap(lambda k: self._init_unit(k, "dec"))(
                    jax.random.split(jax.random.fold_in(ku, 1), nd)
                ),
            }
        else:
            kind = {"dense": "dense", "vlm": "dense", "moe": "moe",
                    "ssm": "ssm", "hybrid": "hybrid", "mla_moe": "mla_moe"}[spec.family]
            units = jax.vmap(lambda k: self._init_unit(k, kind))(
                jax.random.split(ku, spec.n_units)
            )

        head: Params = {"norm": jnp.zeros((d,), spec.pdtype)}
        if not spec.tie_embeddings:
            head["unembed"] = L._dense_init(kh, (d, V), spec.pdtype, scale=0.02)
        return {"frontend": frontend, "units": units, "head": head}

    # ------------------------------------------------------------------ #
    # unit application (training / prefill)
    # ------------------------------------------------------------------ #
    def _apply_one_unit(self, up: Params, carry: Params, positions, prefix_len: int) -> Params:
        spec = self.spec
        fam = spec.family
        h = carry["h"]
        aux = carry.get("aux", jnp.zeros((), jnp.float32))
        eps = spec.norm_eps
        if fam in ("dense", "vlm", "moe"):
            a, _ = L.attention(
                up["attn"], L.rms_norm(h, up["attn"]["norm"], eps), spec,
                positions=positions, prefix_len=prefix_len,
            )
            a = jax.ad_checkpoint.checkpoint_name(a, "attn_out")
            h = h + a
            if fam == "moe":
                o, al = L.moe(up["moe"], L.rms_norm(h, up["moe"]["norm"], eps), spec,
                    constraint=self.moe_constraint, groups=self.moe_groups)
                aux = aux + al
            else:
                o = L.mlp(up["mlp"], L.rms_norm(h, up["mlp"]["norm"], eps))
            o = jax.ad_checkpoint.checkpoint_name(o, "ffn_out")
            h = h + o
        elif fam == "ssm":
            o, _ = L.mamba_block(
                up["mamba"], L.rms_norm(h, up["mamba"]["norm"], eps), spec
            )
            h = h + o
        elif fam == "mla_moe":
            h, _ = self._mla_moe_layer(up, h, positions)
        elif fam == "hybrid":
            per = spec.attn_period
            i_m = i_moe = i_mlp = 0
            for j in range(per):
                if j == 0:
                    a, _ = L.attention(
                        up["attn"], L.rms_norm(h, up["attn"]["norm"], eps), spec,
                        positions=positions, prefix_len=prefix_len,
                    )
                    h = h + a
                else:
                    mp = jax.tree.map(lambda x: x[i_m], up["mamba"])
                    o, _ = L.mamba_block(mp, L.rms_norm(h, mp["norm"], eps), spec)
                    h = h + o
                    i_m += 1
                if j % spec.moe_period == 1:  # every 2nd sublayer gets MoE
                    ep = jax.tree.map(lambda x: x[i_moe], up["moe"])
                    o, al = L.moe(ep, L.rms_norm(h, ep["norm"], eps), spec,
                        constraint=self.moe_constraint, groups=self.moe_groups)
                    aux = aux + al
                    i_moe += 1
                else:
                    fp = jax.tree.map(lambda x: x[i_mlp], up["mlp"])
                    o = L.mlp(fp, L.rms_norm(h, fp["norm"], eps))
                    i_mlp += 1
                h = h + o
        else:
            raise ValueError(fam)
        if self.carry_constraint is not None:
            h = self.carry_constraint(h)
        out = dict(carry)
        out["h"] = h
        out["aux"] = aux
        return out

    def _mla_moe_layer(self, up: Params, h, positions, cache=None):
        """One MLA layer with a routed-expert (unit) or dense (``"mlp"``,
        the leading layers) feed-forward; returns (h, new MLA cache)."""
        spec, eps = self.spec, self.spec.norm_eps
        a, nc = L.mla(up["attn"], L.rms_norm(h, up["attn"]["norm"], eps), spec,
                      positions, cache)
        h = h + a
        if "mlp" in up:
            o = L.mlp(up["mlp"], L.rms_norm(h, up["mlp"]["norm"], eps))
        else:
            o = L.routed_moe(up["moe"], L.rms_norm(h, up["moe"]["norm"], eps), spec)
        return h + o, nc

    def _apply_enc_unit(self, up: Params, henc: jax.Array) -> jax.Array:
        spec = self.spec
        eps = spec.norm_eps
        pos = jnp.arange(henc.shape[1])
        a, _ = L.attention(
            up["attn"], L.rms_norm(henc, up["attn"]["norm"], eps), spec,
            positions=pos, causal=False, use_rope=False,
        )
        henc = henc + a
        o = L.mlp(up["mlp"], L.rms_norm(henc, up["mlp"]["norm"], eps))
        return henc + o

    def _apply_dec_unit(self, up: Params, carry: Params, positions) -> Params:
        spec = self.spec
        eps = spec.norm_eps
        h = carry["h"]
        a, _ = L.attention(
            up["attn"], L.rms_norm(h, up["attn"]["norm"], eps), spec,
            positions=positions,
        )
        h = h + a
        enc = carry["enc"]
        kx = (enc @ up["xattn"]["wk"]).reshape(
            enc.shape[0], enc.shape[1], spec.num_kv_heads, spec.hd
        )
        vx = (enc @ up["xattn"]["wv"]).reshape(
            enc.shape[0], enc.shape[1], spec.num_kv_heads, spec.hd
        )
        x, _ = L.attention(
            up["xattn"], L.rms_norm(h, up["xattn"]["norm"], eps), spec,
            positions=positions, kv_override=(kx, vx), use_rope=False,
        )
        h = h + x
        o = L.mlp(up["mlp"], L.rms_norm(h, up["mlp"]["norm"], eps))
        out = dict(carry)
        out["h"] = h + o
        return out

    def apply_units(self, units: Params, carry: Params, lo: int, hi: int,
                    positions=None, prefix_len: int = 0) -> Params:
        """Run units [lo, hi) on the carry. Unit params are stacked on axis 0
        (sliced statically here); the loop is a lax.scan over the slice."""
        spec = self.spec
        if lo >= hi:
            return carry
        if positions is None:
            positions = jnp.arange(carry["h"].shape[1])
        if spec.family == "audio":
            ne = spec.encoder_layers
            e_lo, e_hi = min(lo, ne), min(hi, ne)
            d_lo, d_hi = max(lo, ne) - ne, max(hi, ne) - ne
            if e_hi > e_lo:
                esl = jax.tree.map(lambda x: x[e_lo:e_hi], units["enc"])

                def enc_body(henc, up):
                    return self._apply_enc_unit(up, henc), None

                if spec.remat:
                    enc_body = self._remat(enc_body)
                henc, _ = lax.scan(enc_body, carry["enc"], esl, unroll=self._unroll)
                carry = dict(carry)
                carry["enc"] = henc
            if d_hi > d_lo:
                dsl = jax.tree.map(lambda x: x[d_lo:d_hi], units["dec"])

                def dec_body(c, up):
                    return self._apply_dec_unit(up, c, positions), None

                if spec.remat:
                    dec_body = self._remat(dec_body)
                carry, _ = lax.scan(dec_body, carry, dsl, unroll=self._unroll)
            return carry

        usl = jax.tree.map(lambda x: x[lo:hi], units)

        def body(c, up):
            return self._apply_one_unit(up, c, positions, prefix_len), None

        if spec.remat:
            body = self._remat(body)
        carry, _ = lax.scan(body, carry, usl, unroll=self._unroll)
        return carry

    # ------------------------------------------------------------------ #
    # frontend / head
    # ------------------------------------------------------------------ #
    def frontend_apply(self, frontend: Params, batch: Params) -> Params:
        spec = self.spec
        emb = frontend["embed"]
        if spec.family == "vlm":
            te = emb[batch["tokens"]].astype(spec.cdtype)
            pe = (batch["patch_embeds"].astype(spec.cdtype) @ frontend["proj"])
            h = jnp.concatenate([pe, te], axis=1) * math.sqrt(spec.d_model)
            return {"h": h.astype(spec.cdtype), "aux": jnp.zeros((), jnp.float32)}
        if spec.family == "audio":
            henc = (
                batch["frames"].astype(spec.cdtype) @ frontend["proj"]
                + frontend["enc_pos"][None].astype(spec.cdtype)
            )
            h = emb[batch["tokens"]].astype(spec.cdtype)
            return {"h": h, "enc": henc, "aux": jnp.zeros((), jnp.float32)}
        h = emb[batch["tokens"]].astype(spec.cdtype)
        if spec.first_dense:
            positions = jnp.arange(h.shape[1])

            def layer(h, up):
                return self._mla_moe_layer(up, h, positions)[0]

            if spec.remat:
                layer = self._remat(layer)
            for i in range(spec.first_dense):
                h = layer(h, jax.tree.map(lambda x: x[i], frontend["dense"]))
        return {"h": h, "aux": jnp.zeros((), jnp.float32)}

    def head_apply(self, params: Params, carry: Params) -> jax.Array:
        spec = self.spec
        h = L.rms_norm(carry["h"], params["head"]["norm"], spec.norm_eps)
        if spec.tie_embeddings:
            logits = h @ params["frontend"]["embed"].T.astype(h.dtype)
        else:
            logits = h @ params["head"]["unembed"]
        if spec.padded_vocab != spec.vocab_size:
            pad = spec.padded_vocab - spec.vocab_size
            neg = jnp.full(logits.shape[:-1] + (pad,), -1e30, logits.dtype)
            logits = jnp.concatenate([logits[..., : spec.vocab_size], neg], axis=-1)
        return logits

    # ------------------------------------------------------------------ #
    # end-to-end loss / forward
    # ------------------------------------------------------------------ #
    def forward(self, params: Params, batch: Params) -> Tuple[jax.Array, jax.Array]:
        spec = self.spec
        carry = self.frontend_apply(params["frontend"], batch)
        prefix = spec.prefix_len if spec.family == "vlm" else 0
        carry = self.apply_units(
            params["units"], carry, 0, spec.n_units, prefix_len=prefix
        )
        return self.head_apply(params, carry), carry["aux"]

    def loss_fn(self, params: Params, batch: Params) -> jax.Array:
        spec = self.spec
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        if spec.family == "vlm":
            # loss on text positions only
            logits = logits[:, spec.prefix_len :]
        mask = (labels >= 0).astype(jnp.float32)
        loss = L.cross_entropy(logits, jnp.maximum(labels, 0), mask)
        if spec.moe is not None:
            loss = loss + 0.01 * aux
        return loss

    # ------------------------------------------------------------------ #
    # decode (serve_step)
    # ------------------------------------------------------------------ #
    def init_caches(self, batch: int, cache_len: int) -> Params:
        spec = self.spec

        def one(kind: str) -> Params:
            if kind == "dense":
                return {"attn": L.init_attn_cache(spec, batch, cache_len)}
            if kind == "moe":
                return {"attn": L.init_attn_cache(spec, batch, cache_len)}
            if kind == "mla_moe":
                return {"attn": L.init_mla_cache(spec, batch, cache_len)}
            if kind == "ssm":
                return {"mamba": L.init_mamba_cache(spec, batch)}
            if kind == "hybrid":
                per = spec.attn_period
                return {
                    "attn": L.init_attn_cache(spec, batch, cache_len),
                    "mamba": jax.tree.map(
                        lambda *xs: jnp.stack(xs),
                        *[L.init_mamba_cache(spec, batch) for _ in range(per - 1)],
                    ),
                }
            if kind == "dec":
                return {
                    "attn": L.init_attn_cache(spec, batch, cache_len),
                    "xk": jnp.zeros(
                        (batch, spec.encoder_len, spec.num_kv_heads, spec.hd),
                        spec.cdtype,
                    ),
                    "xv": jnp.zeros(
                        (batch, spec.encoder_len, spec.num_kv_heads, spec.hd),
                        spec.cdtype,
                    ),
                }
            raise ValueError(kind)

        if spec.family == "audio":
            caches = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[one("dec") for _ in range(spec.num_layers)],
            )
            return caches
        stack = lambda n, kind: jax.tree.map(
            lambda *xs: jnp.stack(xs), *[one(kind) for _ in range(n)])
        if spec.family == "mla_moe":
            return {"dense": stack(spec.first_dense, "mla_moe"),
                    "units": stack(spec.n_units, "mla_moe")}
        kind = {"dense": "dense", "vlm": "dense", "moe": "moe",
                "ssm": "ssm", "hybrid": "hybrid"}[spec.family]
        return stack(spec.n_units, kind)

    def _decode_unit(self, up: Params, cache: Params, carry: Params, pos) -> Tuple[Params, Params]:
        spec = self.spec
        eps = spec.norm_eps
        fam = spec.family
        h = carry["h"]
        if fam in ("dense", "vlm", "moe"):
            a, nc = L.attention(
                up["attn"], L.rms_norm(h, up["attn"]["norm"], eps), spec,
                positions=pos, cache=cache["attn"],
            )
            h = h + a
            if fam == "moe":
                o, _ = L.moe(up["moe"], L.rms_norm(h, up["moe"]["norm"], eps), spec,
                    constraint=self.moe_constraint, groups=self.moe_groups)
            else:
                o = L.mlp(up["mlp"], L.rms_norm(h, up["mlp"]["norm"], eps))
            carry = dict(carry); carry["h"] = h + o
            return carry, {"attn": nc}
        if fam == "mla_moe":
            h, nc = self._mla_moe_layer(up, h, pos, cache["attn"])
            carry = dict(carry); carry["h"] = h
            return carry, {"attn": nc}
        if fam == "ssm":
            o, nc = L.mamba_block(
                up["mamba"], L.rms_norm(h, up["mamba"]["norm"], eps), spec,
                cache=cache["mamba"],
            )
            carry = dict(carry); carry["h"] = h + o
            return carry, {"mamba": nc}
        if fam == "hybrid":
            per = spec.attn_period
            new_m = []
            i_m = i_moe = i_mlp = 0
            for j in range(per):
                if j == 0:
                    a, nca = L.attention(
                        up["attn"], L.rms_norm(h, up["attn"]["norm"], eps), spec,
                        positions=pos, cache=cache["attn"],
                    )
                    h = h + a
                else:
                    mp = jax.tree.map(lambda x: x[i_m], up["mamba"])
                    mc = jax.tree.map(lambda x: x[i_m], cache["mamba"])
                    o, ncm = L.mamba_block(
                        mp, L.rms_norm(h, mp["norm"], eps), spec, cache=mc
                    )
                    h = h + o
                    new_m.append(ncm)
                    i_m += 1
                if j % spec.moe_period == 1:
                    ep = jax.tree.map(lambda x: x[i_moe], up["moe"])
                    o, _ = L.moe(ep, L.rms_norm(h, ep["norm"], eps), spec,
                        constraint=self.moe_constraint, groups=self.moe_groups)
                    i_moe += 1
                else:
                    fp = jax.tree.map(lambda x: x[i_mlp], up["mlp"])
                    o = L.mlp(fp, L.rms_norm(h, fp["norm"], eps))
                    i_mlp += 1
                h = h + o
            carry = dict(carry); carry["h"] = h
            nm = jax.tree.map(lambda *xs: jnp.stack(xs), *new_m)
            return carry, {"attn": nca, "mamba": nm}
        if fam == "audio":
            a, nc = L.attention(
                up["attn"], L.rms_norm(h, up["attn"]["norm"], eps), spec,
                positions=pos, cache=cache["attn"],
            )
            h = h + a
            x, _ = L.attention(
                up["xattn"], L.rms_norm(h, up["xattn"]["norm"], eps), spec,
                positions=pos, kv_override=(cache["xk"], cache["xv"]),
                use_rope=False,
            )
            h = h + x
            o = L.mlp(up["mlp"], L.rms_norm(h, up["mlp"]["norm"], eps))
            carry = dict(carry); carry["h"] = h + o
            return carry, {"attn": nc, "xk": cache["xk"], "xv": cache["xv"]}
        raise ValueError(fam)

    def decode_step(self, params: Params, tokens: jax.Array, caches: Params,
                    pos_index: jax.Array) -> Tuple[jax.Array, Params]:
        """One decode step. tokens [B, 1] int32; pos_index scalar int32."""
        spec = self.spec
        emb = params["frontend"]["embed"]
        h = emb[tokens].astype(spec.cdtype)  # [B, 1, d]
        carry = {"h": h, "aux": jnp.zeros((), jnp.float32)}
        pos = pos_index[None]  # [1]
        units = params["units"]["dec"] if spec.family == "audio" else params["units"]
        dense_caches = None
        if spec.family == "mla_moe":
            dense_caches = []
            for i in range(spec.first_dense):
                up, uc = jax.tree.map(
                    lambda x: x[i], (params["frontend"]["dense"], caches["dense"]))
                carry, nc = self._decode_unit(up, uc, carry, pos)
                dense_caches.append(nc)
            caches = caches["units"]

        def body(c, xs):
            up, uc = xs
            c2, nc = self._decode_unit(up, uc, c, pos)
            return c2, nc

        carry, new_caches = lax.scan(body, carry, (units, caches), unroll=self._unroll)
        if dense_caches is not None:
            new_caches = {"dense": jax.tree.map(lambda *xs: jnp.stack(xs), *dense_caches),
                          "units": new_caches}
        logits = self.head_apply(params, carry)
        return logits[:, 0], new_caches
