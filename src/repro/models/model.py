"""Model facade: specs/init + loss / prefill / decode over the segment
schedule, for any registry architecture, on either distribution path.

Everything that must agree between the training step, the serving steps
and the checkpointer (shapes, PartitionSpecs, layer schedule, cache
layout) is derived from this one class.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.comm import cost_scope
from ..parallel import axes as A
from ..parallel.ops import Ops, ParallelConfig, ShardOps, remat_wrap
from . import moe as MOE
from . import transformer as T
from .common import (ModelConfig, ParamSpec, gqa_layout, replicated, stacked,
                     tree_instantiate, tree_pspecs)
from .layers import embed, logits_and_xent, logits_only, rmsnorm, rope_angles
from .ssm import mamba2_cache_specs
from .xlstm import mlstm_cache_specs, slstm_cache_specs


def _strip_axis(specs, axis_name: str):
    def leaf(s: ParamSpec):
        entries = []
        for e in s.pspec:
            if isinstance(e, tuple):
                e = tuple(n for n in e if n != axis_name) or None
                if e is not None and len(e) == 1:
                    e = e[0]
            elif e == axis_name:
                e = None
            entries.append(e)
        return dataclasses.replace(s, pspec=P(*entries))
    return jax.tree.map(leaf, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


class Model:
    def __init__(self, cfg: ModelConfig, axes: A.MeshAxes,
                 pcfg: ParallelConfig):
        self.cfg = cfg.validate()
        self.axes = axes
        self.pcfg = pcfg
        self.layout = gqa_layout(cfg.n_heads, max(cfg.n_kv_heads, 1),
                                 axes.model)
        self.v_pad = A.padded_vocab(cfg.vocab, axes.model)
        self.schedule = T.build_schedule(cfg)
        self.specs = self._build_specs()
        if not pcfg.fsdp:
            # resident-weight layout (serving): strip the FSDP (`data`)
            # axis from every parameter spec -- weights replicate across
            # data rows and are never re-gathered per step.
            self.specs = _strip_axis(self.specs, A.DATA_AXIS)
        self.pspecs = tree_pspecs(self.specs)

    # ------------------------------------------------------------------ specs
    def _build_specs(self):
        cfg, lay = self.cfg, self.layout
        d = cfg.d_model
        blocks = {seg.name: T.segment_specs(cfg, lay, seg)
                  for seg in self.schedule}
        if cfg.kind == "hybrid":   # zamba2 shared attention + MLP block
            blocks["shared"] = {**T.attn_specs(cfg, lay),
                                **T.mlp_specs(cfg)}
        sp: dict[str, Any] = {"blocks": blocks, "final_norm": replicated(d)}
        if cfg.input_mode == "tokens":
            sp["embed"] = ParamSpec((self.v_pad, d),
                                    P(A.MODEL_AXIS, A.DATA_AXIS))
        else:                      # audio frames stub frontend projector
            sp["frontend"] = ParamSpec((d, d), P(A.DATA_AXIS, None))
        if cfg.cross_attn_every:
            sp["img_proj"] = ParamSpec((cfg.vision_d, d),
                                       P(A.DATA_AXIS, None))
            sp["embed"] = ParamSpec((self.v_pad, d),
                                    P(A.MODEL_AXIS, A.DATA_AXIS))
        sp["head"] = ParamSpec((d, self.v_pad), P(A.DATA_AXIS, A.MODEL_AXIS))
        return sp

    def init(self, key, dtype=None):
        return tree_instantiate(self.specs, key, self.cfg.init_std,
                                dtype or self.cfg.dtype)

    # -------------------------------------------------------------- counting
    def n_params(self, active_only: bool = False) -> int:
        """Total (or per-token-active) parameter count, *excluding* head
        padding and KV replication waste (i.e. the 'useful' N in 6ND)."""
        cfg, lay = self.cfg, self.layout
        total = 0
        leaves, _ = jax.tree_util.tree_flatten_with_path(
            self.specs, is_leaf=lambda x: isinstance(x, ParamSpec))
        qfrac = lay.n_q / lay.n_q_pad
        kvfrac = cfg.n_kv_heads / lay.kv_eff if cfg.n_kv_heads else 1.0
        shared_mult = (cfg.n_layers // cfg.attn_every
                       if cfg.kind == "hybrid" else 1)
        for path, spec in leaves:
            keys = [str(getattr(k, "key", k)) for k in path]
            name = keys[-1]
            n = float(np.prod(spec.shape))
            if name in ("wq", "wo"):
                n *= qfrac
            elif name in ("wk", "wv") and "moe" not in keys:
                n *= kvfrac
            if name == "embed":
                n = cfg.vocab * cfg.d_model
                if active_only:
                    n = 0.0        # table gather, not matmul FLOPs
            elif name == "head":
                n = cfg.d_model * cfg.vocab
            if active_only and "moe" in keys and name in ("wg", "wu", "wd"):
                n *= cfg.top_k / cfg.n_experts
            if active_only and "shared" in keys:
                n *= shared_mult   # zamba2 shared block applied per group
            total += n
        return int(total)

    # --------------------------------------------------------------- forward
    def _embed_in(self, ops: Ops, params, batch):
        cfg = self.cfg
        img = None
        if cfg.input_mode == "frames":
            w = ops.weight(params["frontend"], P(A.DATA_AXIS, None))
            x = batch["frames"].astype(cfg.dtype) @ w
            x = ops.seq_slice(x)
        else:
            x = embed(ops, params["embed"], batch["tokens"], self.v_pad,
                      combine="none")
            x = ops.seq_shard(x)
        if cfg.cross_attn_every and "image_emb" in batch:
            wi = ops.weight(params["img_proj"], P(A.DATA_AXIS, None))
            img = batch["image_emb"].astype(cfg.dtype) @ wi
        return x, img

    def _rope(self, positions):
        cfg = self.cfg
        d_rot = (cfg.qk_rope_head_dim if cfg.mla
                 else int(cfg.dh * cfg.rope_pct) // 2 * 2)
        if d_rot == 0:
            return None
        return rope_angles(positions, d_rot, cfg.rope_theta, cfg.rope_yarn)

    def forward(self, ops: Ops, params, x, rope, img, mode: str,
                caches=None, pos=None, s_max: int = 0):
        """Run all segments. Returns (x, aux_sum, new_caches)."""
        x, aux, new_caches, _ = self._forward(ops, params, x, rope, img,
                                              mode, caches, pos, s_max)
        return x, aux, new_caches

    def _forward(self, ops: Ops, params, x, rope, img, mode: str,
                 caches=None, pos=None, s_max: int = 0):
        """``forward``, and the expert layers' counts summed over the
        layers, as {"moe.<name>": int32} for each count ``moe.moe_ffn``
        returns: {} for a model without expert layers."""
        aux_total = jnp.float32(0.0)
        new_caches, counts = {}, {}
        for seg in self.schedule:
            c = None if caches is None else caches[seg.name]
            x, aux, nc = self._run_seg(ops, seg, params, x, rope, img,
                                       mode, c, pos, s_max)
            if isinstance(aux, dict):       # an expert segment's
                for name, v in aux["counts"].items():
                    name = "moe." + name
                    counts[name] = counts.get(name, 0) + v
                aux = aux["aux"]
            aux_total = aux_total + aux
            new_caches[seg.name] = nc
        return x, aux_total, new_caches, counts

    def _run_seg(self, ops: Ops, seg, params, x, rope, img, mode,
                 cache, pos, s_max):
        cfg = self.cfg
        p_seg = params["blocks"][seg.name]

        if seg.kind in ("attn_mlp", "attn_moe"):
            stacks = self._expert_stacks(seg, p_seg, mode)
            if stacks is not None:
                p_seg = {**p_seg, "moe": {k: v for k, v in
                                          p_seg["moe"].items()
                                          if k not in stacks}}

            # an expert segment's per-layer aux is {"aux", "counts"}, which
            # the scans sum over the layers leaf by leaf
            def ffn(xc, p, layer):
                if seg.kind == "attn_moe":
                    if stacks is None:      # the scan sliced the experts
                        layer = None
                    else:
                        p = {**p, "moe": {**p["moe"], **stacks}}
                    xc, aux, counts = T.block_moe(ops, p, xc, cfg,
                                                  layer=layer)
                    return xc, {"aux": aux, "counts": counts}
                return T.block_mlp(ops, p, xc, cfg), jnp.float32(0.0)

            if mode == "decode":
                # the stacked (L, B, S_kv, H, D) cache is read where it
                # lies, layer by layer, and this step's rows are written
                # into it once, after the scan: no per-layer slice is
                # copied out and no second stacked cache is built
                def body(xc, inp):
                    p, layer = inp
                    xc, rows = T.block_attn(ops, p, xc, cfg, rope,
                                            cache=cache, pos=pos, mode=mode,
                                            layer=layer)
                    xc, aux = ffn(xc, p, layer)
                    return xc, (rows, aux)
                with cost_scope(seg.count):
                    x, (rows, auxs) = lax.scan(
                        body, x, (p_seg, jnp.arange(seg.count)))
                return (x, jax.tree.map(jnp.sum, auxs),
                        T.write_rows(cfg, cache, rows, pos))

            def body(xc, inp):
                (p, layer), c = inp
                xc, kvc = T.block_attn(ops, p, xc, cfg, rope, cache=c,
                                       pos=pos, mode=mode, s_max=s_max)
                xc, aux = ffn(xc, p, layer)
                return xc, ((kvc if kvc is not None else {}), aux)
            # the layer index is scanned only where the experts need it
            layers = None if stacks is None else jnp.arange(seg.count)
            return self._scan(body, x, (p_seg, layers), cache, seg.count,
                              mode)

        if seg.kind == "zamba_group":
            shared_p = params["blocks"]["shared"]

            def body(xc, inp):
                p, c = inp
                mc = None if c is None else c["mamba"]

                def inner(xi, iinp):
                    pi, ci = iinp
                    xi, mcache = T.block_mamba(ops, pi, xi, cfg, ci, mode)
                    return xi, (mcache if mcache is not None else {})
                xc, mcaches = self._scan_inner(inner, xc, p, mc, seg.inner,
                                               mode)
                xc, kvc = T.block_attn(ops, shared_p, xc, cfg, rope,
                                       cache=None if c is None
                                       else c["shared"],
                                       pos=pos, mode=mode, s_max=s_max)
                xc = T.block_mlp(ops, shared_p, xc, cfg)
                nc = {"mamba": mcaches,
                      "shared": kvc if kvc is not None else {}}
                return xc, (nc, jnp.float32(0.0))
            return self._scan(body, x, p_seg, cache, seg.count, mode,
                              grouped=True)

        if seg.kind == "vlm_group":
            def body(xc, inp):
                p, c = inp
                sc = None if c is None else c["self"]

                def inner(xi, iinp):
                    pi, ci = iinp
                    xi, kvc = T.block_attn(ops, pi, xi, cfg, rope, cache=ci,
                                           pos=pos, mode=mode, s_max=s_max)
                    xi = T.block_mlp(ops, pi, xi, cfg)
                    return xi, (kvc if kvc is not None else {})
                xc, scaches = self._scan_inner(inner, xc, p["self"], sc,
                                               seg.inner, mode)
                xc, ccache = T.block_cross(ops, p["cross"], xc, cfg, img,
                                           None if c is None else c["cross"],
                                           mode)
                nc = {"self": scaches,
                      "cross": ccache if ccache is not None else {}}
                return xc, (nc, jnp.float32(0.0))
            return self._scan(body, x, p_seg, cache, seg.count, mode,
                              grouped=True)

        if seg.kind in ("mlstm", "slstm"):
            blk = T.block_mlstm if seg.kind == "mlstm" else T.block_slstm

            def body(xc, inp):
                p, c = inp
                xc, sc = blk(ops, p, xc, cfg, c, mode)
                return xc, ((sc if sc is not None else {}), jnp.float32(0.0))
            return self._scan(body, x, p_seg, cache, seg.count, mode)

        raise ValueError(seg.kind)

    def _expert_stacks(self, seg, p_seg, mode):
        """The held experts' stacked weights {wg, wu, wd}, (L, n, ...),
        which an expert segment's serving scans (prefill, decode) close
        over whole, handing each layer its index, so that the grouped
        matmuls read them where they lie (``moe.dropless_experts``).
        None where the scan slices them: training, a segment without
        experts, and weights gathered from FSDP shards (a gather of the
        whole stack in every layer)."""
        if seg.kind != "attn_moe" or mode == "train" or \
                (self.pcfg.fsdp and self.axes.data > 1):
            return None
        return {w: p_seg["moe"][w] for w in MOE.EXPERT_WEIGHTS}

    def _scan(self, body, x, p_seg, cache, count, mode, grouped=False):
        """Outer layer scan: body(x, (p_slice, cache_slice)) ->
        (x, (cache_out, aux))."""
        if mode == "train" and self.pcfg.remat != "none":
            body = remat_wrap(body, self.pcfg.remat)
        if cache is None:
            # feed a dummy None-free structure: replicate body signature
            def wrapped(c, p):
                return body(c, (p, None))
            with cost_scope(count):
                x, (caches, auxs) = lax.scan(wrapped, x, p_seg)
        else:
            with cost_scope(count):
                x, (caches, auxs) = lax.scan(body, x, (p_seg, cache))
        return (x, jax.tree.map(jnp.sum, auxs),
                (caches if mode != "train" else None))

    def _scan_inner(self, inner, x, p_inner, cache_inner, count, mode):
        if mode == "train" and self.pcfg.remat != "none":
            inner = remat_wrap(inner, self.pcfg.remat)
        if cache_inner is None:
            def wrapped(c, p):
                return inner(c, (p, None))
            with cost_scope(count):
                x, caches = lax.scan(wrapped, x, p_inner)
        else:
            with cost_scope(count):
                x, caches = lax.scan(inner, x, (p_inner, cache_inner))
        return x, caches

    # ------------------------------------------------------------------ loss
    def loss(self, ops: Ops, params, batch):
        """Training objective. Returns (scalar_loss, metrics). The scalar is
        the *global-mean* objective from this shard's perspective; gradient
        correctness across shards is completed by ops.sync_grads."""
        cfg = self.cfg
        x, img = self._embed_in(ops, params, batch)
        if cfg.input_mode == "frames":
            S = batch["frames"].shape[1]
        else:
            S = batch["tokens"].shape[1]
        rope = self._rope(jnp.arange(S))
        x, aux, _ = self.forward(ops, params, x, rope, img, "train")
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        xf = ops.seq_unshard(x)                       # (B, S, d)

        if cfg.is_encoder:
            hidden, labels = xf, batch["labels"]
        else:
            hidden = xf[:, :-1]
            labels = batch["tokens"][:, 1:]
        valid = jnp.ones(labels.shape, jnp.float32)
        nll_sum, n_valid = logits_and_xent(ops, params["head"], hidden,
                                           labels, valid, self.v_pad,
                                           cfg.vocab)
        is_shard = isinstance(ops, ShardOps)
        shards = ops.dp * ops.tp if is_shard else 1
        # shard_map reverse-AD seeds every device's loss copy: the
        # differentiated objective is the SUM over all dp*tp program
        # instances (psum transposes to psum). Scaling by 1/(dp*tp) makes
        # that sum the global mean -- verified grad-identical to the
        # gspmd path in tests/_dist_checks.py.
        loss = nll_sum / (n_valid * shards)
        if cfg.kind == "moe":
            loss = loss + cfg.router_aux_coef * aux / shards
        metrics = {"nll_sum": nll_sum, "n_valid": n_valid, "aux": aux}
        return loss, metrics

    # --------------------------------------------------------------- serving
    def prefill(self, ops: Ops, params, batch, s_max: int,
                counts: bool = False):
        """Forward + cache build. Returns (last_token_logits, caches), and
        with ``counts`` the expert layers' counts summed over the layers
        ({"moe.<name>": int32}; {} for a model without expert layers)
        third."""
        cfg = self.cfg
        x, img = self._embed_in(ops, params, batch)
        S = (batch["tokens"] if cfg.input_mode == "tokens"
             else batch["frames"]).shape[1]
        rope = self._rope(jnp.arange(S))
        x, _, caches, n = self._forward(ops, params, x, rope, img, "prefill",
                                        s_max=s_max)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        xf = ops.seq_unshard(x)
        logits = logits_only(ops, params["head"], xf[:, -1:], self.v_pad,
                             cfg.vocab)
        return (logits[:, 0], caches) + ((n,) if counts else ())

    def decode(self, ops: Ops, params, caches, tokens, pos,
               counts: bool = False):
        """One decode step. tokens: (B, 1) int32; pos: (B,) absolute
        positions of these tokens. Returns (logits (B, vocab), caches),
        and the expert layers' counts third with ``counts`` (as
        ``prefill``)."""
        cfg = self.cfg
        x, _ = self._embed_in(ops, params, {"tokens": tokens})
        rope = self._rope(pos[:, None])               # (B,1,d_rot/2)
        x, _, new_caches, n = self._forward(ops, params, x, rope, None,
                                            "decode", caches=caches, pos=pos)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = logits_only(ops, params["head"], x, self.v_pad, cfg.vocab)
        return (logits[:, 0], new_caches) + ((n,) if counts else ())

    # ----------------------------------------------------------- cache specs
    def cache_specs(self, batch: int, s_max: int):
        """ParamSpec pytree describing the decode cache."""
        cfg, lay = self.cfg, self.layout
        dh = cfg.dh
        bsp = self._bspec(batch)
        s_kv = min(cfg.window, s_max) if cfg.window else s_max

        def kv(count):
            if cfg.mla:         # the latent and the shared rotary key
                return {name: ParamSpec((count, batch, s_max, w),
                                        P(None, bsp, None, None),
                                        init="zeros")
                        for name, w in (("c", cfg.kv_lora_rank),
                                        ("kr", cfg.qk_rope_head_dim))}
            shp = (count, batch, s_kv, lay.kv_eff, dh)
            return {"k": ParamSpec(shp, P(None, bsp, None, A.MODEL_AXIS,
                                          None), init="zeros"),
                    "v": ParamSpec(shp, P(None, bsp, None, A.MODEL_AXIS,
                                          None), init="zeros")}

        out = {}
        for seg in self.schedule:
            if seg.kind in ("attn_mlp", "attn_moe"):
                out[seg.name] = kv(seg.count)
            elif seg.kind == "zamba_group":
                mc = mamba2_cache_specs(cfg, batch, self.axes.model,
                                        bspec=bsp)
                mc = {k: stacked(seg.count, stacked(seg.inner, v))
                      for k, v in mc.items()}
                shp = (seg.count, batch, s_max, lay.kv_eff, dh)
                out[seg.name] = {
                    "mamba": mc,
                    "shared": {"k": ParamSpec(shp, P(None, bsp, None,
                                                     A.MODEL_AXIS, None),
                                              init="zeros"),
                               "v": ParamSpec(shp, P(None, bsp, None,
                                                     A.MODEL_AXIS, None),
                                              init="zeros")}}
            elif seg.kind == "vlm_group":
                ishp = (seg.count, batch, cfg.n_image_tokens, lay.kv_eff, dh)
                sshp = (seg.count, seg.inner, batch, s_kv, lay.kv_eff, dh)
                out[seg.name] = {
                    "self": {"k": ParamSpec(sshp, P(None, None, bsp, None,
                                                    A.MODEL_AXIS, None),
                                            init="zeros"),
                             "v": ParamSpec(sshp, P(None, None, bsp, None,
                                                    A.MODEL_AXIS, None),
                                            init="zeros")},
                    "cross": {"ik": ParamSpec(ishp, P(None, bsp, None,
                                                      A.MODEL_AXIS, None),
                                              init="zeros"),
                              "iv": ParamSpec(ishp, P(None, bsp, None,
                                                      A.MODEL_AXIS, None),
                                              init="zeros")}}
            elif seg.kind == "mlstm":
                out[seg.name] = {k: stacked(seg.count, v) for k, v in
                                 mlstm_cache_specs(cfg, batch,
                                                   bspec=bsp).items()}
            elif seg.kind == "slstm":
                out[seg.name] = {k: stacked(seg.count, v) for k, v in
                                 slstm_cache_specs(cfg, batch,
                                                   bspec=bsp).items()}
        return out

    def _bspec(self, batch: int):
        dp = self.axes.dp_total
        if batch % dp == 0 and dp > 1:
            return ((A.POD_AXIS, A.DATA_AXIS) if self.axes.pod > 1
                    else A.DATA_AXIS)
        return None

    # ------------------------------------------------------------ batch spec
    def batch_specs(self, global_batch: int, seq: int):
        """(ShapeDtypeStruct tree, PartitionSpec tree) for a training batch."""
        cfg = self.cfg
        bsp = self._bspec(global_batch)
        tree, specs = {}, {}
        if cfg.input_mode == "frames":
            tree["frames"] = jax.ShapeDtypeStruct(
                (global_batch, seq, cfg.d_model), jnp.bfloat16)
            specs["frames"] = P(bsp, None, None)
            tree["labels"] = jax.ShapeDtypeStruct((global_batch, seq),
                                                  jnp.int32)
            specs["labels"] = P(bsp, None)
        else:
            tree["tokens"] = jax.ShapeDtypeStruct((global_batch, seq),
                                                  jnp.int32)
            specs["tokens"] = P(bsp, None)
        if cfg.cross_attn_every:
            tree["image_emb"] = jax.ShapeDtypeStruct(
                (global_batch, cfg.n_image_tokens, cfg.vision_d),
                jnp.bfloat16)
            specs["image_emb"] = P(bsp, None, None)
        return tree, specs
