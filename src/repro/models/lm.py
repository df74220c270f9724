"""Model assembly for all assigned architecture families.

Families: dense | moe | hybrid (zamba2) | ssm (rwkv6) | audio (encoder) |
vlm (cross-attn). Layer stacks are `lax.scan`-scanned over stacked
parameters (leading "layers" axis) so the HLO stays compact for 54–100
layer configs; hybrid/vlm use a two-level (python-group × inner-scan)
layout around their shared/periodic blocks.

Two parameter modes, same code path:
  fp     — bf16-compute training/serving.
  quant  — COMET W4AxKV4 serving: ``LM.quantize`` structurally replaces
           every block projection's ``{"w": ...}`` with packed W4 payloads
           (``{"w_packed", "w_scale"}``); ``layers.common.linear``
           dispatches on that structure into the W4Ax GEMM, and the KV
           cache becomes the packed int4 cache. Scan-uniform INT4
           fraction comes from ``QuantConfig.int4_fraction``.

API (pure functions; ``LM`` only holds static config):
  lm = LM(cfg, quant=None | QuantConfig(...))
  params, axes = lm.init(key)
  qparams, qaxes = lm.quantize(params, axes)           # offline PTQ
  logits, aux = lm.train_logits(params, tokens, extra)
  logits, cache = lm.prefill(params, tokens, cache, extra)
  logits, cache = lm.decode(params, tokens, cache)
  cache = lm.init_cache(batch, max_len)
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import qlinear as QL
from repro.layers import attention as ATT
from repro.layers import common as C
from repro.layers import mamba2 as M2
from repro.layers import mlp as MLP
from repro.layers import rwkv6 as RW
from repro.layers.common import Annotated

__all__ = ["LM", "QuantConfig"]


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    int4_fraction: float = 0.875     # scan-uniform W4A4 block fraction
    schedule: str = "split"          # split | mixed (paper baseline)
    impl: str = "auto"               # kernel impl: auto | pallas | ref
    kv4: bool = True                 # int4 KV cache vs bf16
    weight_group: int = 128
    weight_only: bool = False        # W4A16 baseline mode


QUANT_KEYS = frozenset({
    "wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down",
    "w_r", "w_k", "w_v", "w_g", "w_o", "in_proj", "out_proj",
})


def _stack_layers(trees):
    """List of Annotated trees → one tree with leading 'layers' axis."""
    def stack(*leaves):
        vals = jnp.stack([l.value for l in leaves])
        return Annotated(vals, ("layers",) + leaves[0].axes)
    return jax.tree.map(stack, *trees, is_leaf=C.is_annotated)


class LM:
    def __init__(self, cfg: ModelConfig, quant: Optional[QuantConfig] = None):
        self.cfg = cfg
        self.quant = quant
        if cfg.family == "hybrid":
            assert cfg.num_layers % cfg.attn_period == 0
            self.n_groups = cfg.num_layers // cfg.attn_period
        elif cfg.family == "vlm":
            assert cfg.num_layers % cfg.cross_attn_period == 0
            self.n_groups = cfg.num_layers // cfg.cross_attn_period
            self.self_per_group = cfg.cross_attn_period - 1
        else:
            self.n_groups = 0

    def _ctx(self):
        if self.quant is None:
            return contextlib.nullcontext()
        return QL.quant_runtime(QL.QuantRuntime(
            int4_fraction=self.quant.int4_fraction,
            schedule=self.quant.schedule,
            impl=self.quant.impl,
            weight_only=self.quant.weight_only,
        ))

    # ------------------------------------------------------------------ init

    def init(self, key):
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        tree: dict = {}
        if cfg.family != "audio":
            tree["embed"] = C.init_embedding(keys[0], cfg.vocab_size, cfg.d_model)
        else:
            # stub frontend: conv positional embedding over frame embeddings
            tree["conv_pos"] = {
                "w": Annotated(
                    0.02 * jax.random.normal(
                        keys[0], (cfg.conv_pos_width, cfg.d_model), jnp.float32),
                    (None, "embed")),
                "b": Annotated(jnp.zeros((cfg.d_model,), jnp.float32), ("embed",)),
            }
        tree["final_norm"] = C.init_norm(cfg.norm, cfg.d_model)
        if not cfg.tie_embeddings:
            tree["lm_head"] = C.init_linear(
                keys[1], cfg.d_model, cfg.vocab_size, ("embed", "vocab"))

        lkeys = jax.random.split(keys[2], max(cfg.num_layers, 1))
        fam = cfg.family
        if fam in ("dense", "audio"):
            tree["blocks"] = _stack_layers(
                [self._init_dense_block(lkeys[i]) for i in range(cfg.num_layers)])
        elif fam == "moe":
            tree["blocks"] = _stack_layers(
                [self._init_moe_block(lkeys[i]) for i in range(cfg.num_layers)])
        elif fam == "ssm":
            tree["blocks"] = _stack_layers(
                [self._init_rwkv_block(lkeys[i]) for i in range(cfg.num_layers)])
        elif fam == "hybrid":
            tree["blocks"] = _stack_layers(
                [self._init_mamba_block(lkeys[i]) for i in range(cfg.num_layers)])
            tree["shared_attn"] = self._init_shared_attn(keys[3])
        elif fam == "vlm":
            n_self = self.n_groups * self.self_per_group
            tree["blocks"] = _stack_layers(
                [self._init_dense_block(lkeys[i]) for i in range(n_self)])
            ckeys = jax.random.split(keys[4], self.n_groups)
            tree["cross_blocks"] = _stack_layers(
                [self._init_cross_block(ckeys[i]) for i in range(self.n_groups)])
        else:
            raise ValueError(fam)
        return C.split_annotations(tree)

    def _init_dense_block(self, key):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "attn_norm": C.init_norm(cfg.norm, cfg.d_model),
            "attn": ATT.init_attention(k1, cfg),
            "mlp_norm": C.init_norm(cfg.norm, cfg.d_model),
            "mlp": MLP.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_act),
        }

    def _init_moe_block(self, key):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "attn_norm": C.init_norm(cfg.norm, cfg.d_model),
            "attn": ATT.init_attention(k1, cfg),
            "mlp_norm": C.init_norm(cfg.norm, cfg.d_model),
            "moe": MLP.init_moe(k2, cfg),
        }

    def _init_rwkv_block(self, key):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "tm_norm": C.init_norm("layernorm", cfg.d_model),
            "tmix": RW.init_rwkv6(k1, cfg),
            "cm_norm": C.init_norm("layernorm", cfg.d_model),
            "cmix": RW.init_rwkv6_cmix(k2, cfg),
        }

    def _init_mamba_block(self, key):
        cfg = self.cfg
        return {
            "norm": C.init_norm(cfg.norm, cfg.d_model),
            "mamba": M2.init_mamba2(key, cfg),
        }

    def _init_shared_attn(self, key):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "attn_norm": C.init_norm(cfg.norm, cfg.d_model),
            "attn": ATT.init_attention(k1, cfg),
            "mlp_norm": C.init_norm(cfg.norm, cfg.d_model),
            "mlp": MLP.init_mlp(k2, cfg.d_model, cfg.d_ff, "swiglu"),
        }

    def _init_cross_block(self, key):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "attn_norm": C.init_norm(cfg.norm, cfg.d_model),
            "attn": ATT.init_attention(k1, cfg, cross=True),
            "mlp_norm": C.init_norm(cfg.norm, cfg.d_model),
            "mlp": MLP.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_act),
            "gate": Annotated(jnp.zeros((), jnp.float32), ()),
        }

    # ------------------------------------------------------- offline PTQ

    def quantize(self, params, axes):
        """fp params → packed W4 params (structural tree transform).

        Embedding table and LM head are stored bf16 for serving (§Perf
        cell A, iteration 4): they stay unquantized per the paper, but f32
        storage would double their decode-step HBM reads for no accuracy
        benefit (compute is bf16 anyway).
        """
        assert self.quant is not None
        qcfg = self.quant
        params = dict(params)
        if "embed" in params:
            params["embed"] = {
                "table": params["embed"]["table"].astype(jnp.bfloat16)}
        if "lm_head" in params:
            lh = dict(params["lm_head"])
            lh["w"] = lh["w"].astype(jnp.bfloat16)
            params["lm_head"] = lh

        return _quantize_tree(params, axes, qcfg)

    def init_quantized(self, key):
        """Seeded random serving params built without the f32 model:
        ``(qparams, qaxes)`` equal to ``quantize(*init(key))`` (packed
        payloads bit-equal, weight scales within one f32 ulp — ``jit``
        may fold the scale division into a reciprocal multiply).

        Dense and MoE stacks are initialised and quantised one layer at
        a time under ``jit`` and written into a preallocated packed
        stack, so the device holds the packed model plus one f32 layer
        at most — a full-width 8B model never exists in f32. Other
        families fall back to ``quantize(*init(key))``."""
        cfg = self.cfg
        assert self.quant is not None
        if cfg.family not in ("dense", "moe"):
            return self.quantize(*self.init(key))
        qcfg = self.quant
        keys = jax.random.split(key, 8)
        lkeys = jax.random.split(keys[2], max(cfg.num_layers, 1))
        init_block = (self._init_dense_block if cfg.family == "dense"
                      else self._init_moe_block)
        block_axes = {}

        def packed_block(k):
            p, a = C.split_annotations(init_block(k))
            qp, block_axes["axes"] = _quantize_tree(p, a, qcfg)
            return qp

        layer = jax.jit(packed_block)
        shapes = jax.eval_shape(packed_block, lkeys[0])
        blocks = jax.tree.map(
            lambda s: jnp.zeros((cfg.num_layers,) + s.shape, s.dtype), shapes)
        donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()
        put = jax.jit(lambda stack, one, i: jax.tree.map(
            lambda a, b: a.at[i].set(b), stack, one), donate_argnums=donate)
        for i in range(cfg.num_layers):
            blocks = put(blocks, layer(lkeys[i]), i)

        def bf16_leaf(init):
            return jax.jit(lambda k: init(k).value.astype(jnp.bfloat16))

        params = {"embed": {"table": bf16_leaf(
            lambda k: C.init_embedding(k, cfg.vocab_size,
                                       cfg.d_model)["table"])(keys[0])}}
        axes = {"embed": {"table": ("vocab", "embed")}}
        params["final_norm"], axes["final_norm"] = C.split_annotations(
            C.init_norm(cfg.norm, cfg.d_model))
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": bf16_leaf(lambda k: C.init_linear(
                k, cfg.d_model, cfg.vocab_size, ("embed", "vocab"))["w"])(
                    keys[1])}
            axes["lm_head"] = {"w": ("embed", "vocab")}
        params["blocks"] = blocks
        axes["blocks"] = jax.tree.map(lambda a: ("layers",) + a,
                                      block_axes["axes"],
                                      is_leaf=lambda a: isinstance(a, tuple))
        return params, axes

    # ------------------------------------------------------- block pieces

    def _attn_mlp_block(self, bp, x, mode, cache, positions=None, aux=0.0):
        cfg = self.cfg
        h = C.apply_norm(bp["attn_norm"], x, cfg.norm, cfg.norm_eps)
        new_cache = None
        if mode == "train":
            a = ATT.attention_train(bp["attn"], cfg, h, positions)
        elif mode == "prefill":
            if "k_packed" in cache:
                a, new_cache = ATT.attention_prefill_q4(
                    bp["attn"], cfg, h, cache, positions)
            else:
                a, new_cache = ATT.attention_prefill(
                    bp["attn"], cfg, h, cache, positions)
        else:
            if "k_packed" in cache:
                a, new_cache = ATT.attention_decode_q4(
                    bp["attn"], cfg, h, cache,
                    impl=self.quant.impl if self.quant else "auto")
            else:
                a, new_cache = ATT.attention_decode_fp(bp["attn"], cfg, h, cache)
        x = x + a
        h = C.apply_norm(bp["mlp_norm"], x, cfg.norm, cfg.norm_eps)
        if "moe" in bp:
            y, l_aux = MLP.moe_apply(bp["moe"], h, cfg)
            aux = aux + l_aux
        else:
            y = MLP.mlp_apply(bp["mlp"], h, cfg.mlp_act)
        x = x + y
        return x, new_cache, aux

    # ------------------------------------------------------- cache init

    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        quantized = self.quant is not None and self.quant.kv4

        def attn_cache():
            if quantized:
                return ATT.init_q4_cache(cfg, batch, max_len)
            return ATT.init_fp_cache(cfg, batch, max_len)

        def stack(n, fn):
            one = fn()
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n, *a.shape)).copy(), one)

        fam = cfg.family
        if fam in ("dense", "moe"):
            return {"attn": stack(cfg.num_layers, attn_cache)}
        if fam == "ssm":
            return {"rwkv": stack(cfg.num_layers,
                                  lambda: RW.init_rwkv6_state(cfg, batch))}
        if fam == "hybrid":
            return {
                "mamba": stack(cfg.num_layers,
                               lambda: M2.init_mamba2_state(cfg, batch)),
                "shared_attn": stack(self.n_groups, attn_cache),
            }
        if fam == "vlm":
            img = cfg.num_image_tokens

            def cross_kv():
                shp = (batch, img, cfg.num_kv_heads, cfg.head_dim)
                return {"k": jnp.zeros(shp, jnp.bfloat16),
                        "v": jnp.zeros(shp, jnp.bfloat16)}

            return {
                "attn": stack(self.n_groups * self.self_per_group, attn_cache),
                "cross_kv": stack(self.n_groups, cross_kv),
            }
        if fam == "audio":
            return {}
        raise ValueError(fam)

    # ------------------------------------------------------- forward passes

    def _embed(self, params, tokens):
        x = jnp.take(params["embed"]["table"], tokens, axis=0)
        return x.astype(jnp.bfloat16)

    def _head(self, params, x):
        if self.cfg.tie_embeddings:
            w = params["embed"]["table"].astype(jnp.bfloat16)
            return (x @ w.T).astype(jnp.float32)
        return C.linear(params["lm_head"], x).astype(jnp.float32)

    def train_logits(self, params, tokens, extra=None):
        """Returns (logits [B, S, V] f32, aux scalar)."""
        with self._ctx():
            hidden, aux = self._train_hidden(params, tokens, extra)
            return self._head(params, hidden), aux

    def train_hidden(self, params, tokens, extra=None):
        """Backbone forward up to (incl.) final norm: (hidden, aux).

        Used by the chunked-CE training loss so the full [B, S, V] logits
        are never materialized.
        """
        with self._ctx():
            return self._train_hidden(params, tokens, extra)

    def _train_hidden(self, params, tokens, extra):
        cfg = self.cfg
        fam = cfg.family
        if fam == "audio":
            x = extra["frames"].astype(jnp.bfloat16)      # [B, T, D]
            x = x + _conv_pos(params["conv_pos"], x)
        else:
            x = self._embed(params, tokens)
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        aux = jnp.zeros((), jnp.float32)

        if fam in ("dense", "moe", "audio"):
            def body(carry, bp):
                h, aux = carry
                h, _, aux = self._attn_mlp_block(bp, h, "train", None,
                                                 positions, aux)
                return (h, aux), None
            (x, aux), _ = jax.lax.scan(
                jax.checkpoint(body), (x, aux), params["blocks"])
        elif fam == "ssm":
            def body(carry, bp):
                h, aux = carry
                return (self._rwkv_block_train(bp, h), aux), None
            (x, aux), _ = jax.lax.scan(
                jax.checkpoint(body), (x, aux), params["blocks"])
        elif fam == "hybrid":
            x = self._hybrid_forward(params, x, positions, "train")
        elif fam == "vlm":
            x, aux = self._vlm_forward(params, x, positions, extra, aux)
        else:
            raise ValueError(fam)

        x = C.apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        return x, aux

    def _rwkv_block_train(self, bp, x):
        cfg = self.cfg
        h = C.apply_norm(bp["tm_norm"], x, "layernorm", cfg.norm_eps)
        y, _ = RW.rwkv6_train(bp["tmix"], cfg, h)
        x = x + y
        h = C.apply_norm(bp["cm_norm"], x, "layernorm", cfg.norm_eps)
        y, _ = RW.rwkv6_cmix(
            bp["cmix"], cfg, h,
            jnp.zeros((x.shape[0], 1, cfg.d_model), x.dtype))
        return x + y

    # hybrid (zamba2): groups of (shared attn block → attn_period mamba layers)
    def _hybrid_forward(self, params, x, positions, mode, cache=None):
        cfg = self.cfg
        per = cfg.attn_period
        blocks = params["blocks"]
        new_mamba, new_attn = [], []
        from repro.parallel.sharding import maybe_shard
        for gi in range(self.n_groups):
            # §Perf cell C iteration 4 (sequence parallelism): the
            # residual stream [B, L, d_model] otherwise replicates over
            # the model axis — at 2.7B×4k×16/dev it dominates train HBM
            # traffic. Shard L over "model" between blocks; XLA gathers
            # at the attention/SSD boundaries that need full sequence.
            # (no-op at decode where L == 1.)
            x = maybe_shard(x, "data", "model", None)
            sl = jax.tree.map(lambda a: a[gi * per:(gi + 1) * per], blocks)
            sp = params["shared_attn"]
            h = C.apply_norm(sp["attn_norm"], x, cfg.norm, cfg.norm_eps)
            if mode == "train":
                a = ATT.attention_train(sp["attn"], cfg, h, positions)
            else:
                c = jax.tree.map(lambda a: a[gi], cache["shared_attn"])
                if mode == "prefill":
                    if "k_packed" in c:
                        a, nc = ATT.attention_prefill_q4(
                            sp["attn"], cfg, h, c, positions)
                    else:
                        a, nc = ATT.attention_prefill(
                            sp["attn"], cfg, h, c, positions)
                else:
                    if "k_packed" in c:
                        a, nc = ATT.attention_decode_q4(
                            sp["attn"], cfg, h, c,
                            impl=self.quant.impl if self.quant else "auto")
                    else:
                        a, nc = ATT.attention_decode_fp(sp["attn"], cfg, h, c)
                new_attn.append(nc)
            x = x + a
            h = C.apply_norm(sp["mlp_norm"], x, cfg.norm, cfg.norm_eps)
            x = x + MLP.mlp_apply(sp["mlp"], h, "swiglu")

            if mode == "train":
                def mbody(h, bp):
                    hn = C.apply_norm(bp["norm"], h, cfg.norm, cfg.norm_eps)
                    return h + M2.mamba2_train(bp["mamba"], cfg, hn), None
                x, _ = jax.lax.scan(jax.checkpoint(mbody), x, sl)
            elif mode == "prefill":
                def pbody(h, bp):
                    hn = C.apply_norm(bp["norm"], h, cfg.norm, cfg.norm_eps)
                    y, st = M2.mamba2_train(bp["mamba"], cfg, hn,
                                            return_state=True)
                    return h + y, st
                x, sts = jax.lax.scan(pbody, x, sl)
                new_mamba.append(sts)
            else:
                mc = jax.tree.map(
                    lambda a: a[gi * per:(gi + 1) * per], cache["mamba"])
                def dbody(h, bp_c):
                    bp, c = bp_c
                    hn = C.apply_norm(bp["norm"], h, cfg.norm, cfg.norm_eps)
                    y, nc = M2.mamba2_decode(bp["mamba"], cfg, hn, c)
                    return h + y, nc
                x, ncs = jax.lax.scan(dbody, x, (sl, mc))
                new_mamba.append(ncs)
        if mode == "train":
            return x
        new_cache = {
            "mamba": jax.tree.map(
                lambda *xs: jnp.concatenate(xs, 0), *new_mamba),
            "shared_attn": jax.tree.map(
                lambda *xs: jnp.stack(xs, 0), *new_attn),
        }
        return x, new_cache

    # vlm: groups of (self_per_group self layers → 1 gated cross layer)
    def _vlm_forward(self, params, x, positions, extra, aux,
                     mode="train", cache=None):
        cfg = self.cfg
        spg = self.self_per_group
        img = (extra["image_embeds"].astype(jnp.bfloat16)
               if extra is not None else None)
        new_self, new_cross = [], []
        for gi in range(self.n_groups):
            sl = jax.tree.map(
                lambda a: a[gi * spg:(gi + 1) * spg], params["blocks"])
            if mode == "train":
                def body(carry, bp):
                    h, aux = carry
                    h, _, aux = self._attn_mlp_block(
                        bp, h, "train", None, positions, aux)
                    return (h, aux), None
                (x, aux), _ = jax.lax.scan(jax.checkpoint(body), (x, aux), sl)
            else:
                cl = jax.tree.map(
                    lambda a: a[gi * spg:(gi + 1) * spg], cache["attn"])
                def body(carry, bp_c):
                    h, aux = carry
                    bp, c = bp_c
                    h, nc, aux = self._attn_mlp_block(
                        bp, h, mode, c, positions, aux)
                    return (h, aux), nc
                (x, aux), ncs = jax.lax.scan(body, (x, aux), (sl, cl))
                new_self.append(ncs)

            cb = jax.tree.map(lambda a: a[gi], params["cross_blocks"])
            h = C.apply_norm(cb["attn_norm"], x, cfg.norm, cfg.norm_eps)
            if mode == "decode":
                ckv = jax.tree.map(lambda a: a[gi], cache["cross_kv"])
                a = _cross_decode(cfg, cb["attn"], h, ckv)
                new_cross.append(ckv)
            else:
                a = ATT.attention_train(cb["attn"], cfg, h, positions,
                                        kv_override=img)
                if mode == "prefill":
                    new_cross.append(_cross_kv(cfg, cb["attn"], img))
            x = x + jnp.tanh(cb["gate"]).astype(x.dtype) * a
            h = C.apply_norm(cb["mlp_norm"], x, cfg.norm, cfg.norm_eps)
            x = x + MLP.mlp_apply(cb["mlp"], h, cfg.mlp_act)
        if mode == "train":
            return x, aux
        new_cache = {
            "attn": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *new_self),
            "cross_kv": jax.tree.map(lambda *xs: jnp.stack(xs, 0), *new_cross),
        }
        return x, aux, new_cache

    # ------------------------------------------------------- prefill / decode

    def prefill(self, params, tokens, cache, extra=None):
        with self._ctx():
            return self._prefill(params, tokens, cache, extra)

    def _prefill(self, params, tokens, cache, extra):
        cfg = self.cfg
        fam = cfg.family
        if fam == "audio":
            raise ValueError("encoder-only model has no prefill/decode")
        x = self._embed(params, tokens)
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        aux = jnp.zeros((), jnp.float32)

        if fam in ("dense", "moe"):
            def body(carry, bp_c):
                h, aux = carry
                bp, c = bp_c
                h, nc, aux = self._attn_mlp_block(
                    bp, h, "prefill", c, positions, aux)
                return (h, aux), nc
            (x, aux), ncs = jax.lax.scan(
                body, (x, aux), (params["blocks"], cache["attn"]))
            new_cache = {"attn": ncs}
        elif fam == "ssm":
            def body(carry, bp_c):
                h, aux = carry
                bp, c = bp_c
                h, nc = self._rwkv_block_prefill(bp, h, c)
                return (h, aux), nc
            (x, aux), ncs = jax.lax.scan(
                body, (x, aux), (params["blocks"], cache["rwkv"]))
            new_cache = {"rwkv": ncs}
        elif fam == "hybrid":
            x, new_cache = self._hybrid_forward(
                params, x, positions, "prefill", cache)
        elif fam == "vlm":
            x, aux, new_cache = self._vlm_forward(
                params, x, positions, extra, aux, "prefill", cache)
        else:
            raise ValueError(fam)

        x = C.apply_norm(params["final_norm"], x[:, -1:], cfg.norm, cfg.norm_eps)
        return self._head(params, x), new_cache

    def _rwkv_block_prefill(self, bp, x, c):
        cfg = self.cfg
        h = C.apply_norm(bp["tm_norm"], x, "layernorm", cfg.norm_eps)
        y, tm = RW.rwkv6_train(bp["tmix"], cfg, h, {"shift_tm": c["shift_tm"]})
        x = x + y
        h = C.apply_norm(bp["cm_norm"], x, "layernorm", cfg.norm_eps)
        y, cm_shift = RW.rwkv6_cmix(bp["cmix"], cfg, h, c["shift_cm"])
        x = x + y
        return x, {"s": tm["s"], "shift_tm": tm["shift_tm"],
                   "shift_cm": cm_shift}

    def decode(self, params, tokens, cache):
        """tokens: [B, 1] int32 → (logits [B, 1, V], new cache)."""
        with self._ctx():
            return self._decode(params, tokens, cache)

    def _decode(self, params, tokens, cache):
        cfg = self.cfg
        fam = cfg.family
        x = self._embed(params, tokens)
        aux = jnp.zeros((), jnp.float32)

        if fam in ("dense", "moe"):
            def body(carry, bp_c):
                h, aux = carry
                bp, c = bp_c
                h, nc, aux = self._attn_mlp_block(bp, h, "decode", c, None, aux)
                return (h, aux), nc
            (x, aux), ncs = jax.lax.scan(
                body, (x, aux), (params["blocks"], cache["attn"]))
            new_cache = {"attn": ncs}
        elif fam == "ssm":
            def body(carry, bp_c):
                h, aux = carry
                bp, c = bp_c
                h, nc = self._rwkv_block_decode(bp, h, c)
                return (h, aux), nc
            (x, aux), ncs = jax.lax.scan(
                body, (x, aux), (params["blocks"], cache["rwkv"]))
            new_cache = {"rwkv": ncs}
        elif fam == "hybrid":
            x, new_cache = self._hybrid_forward(params, x, None, "decode", cache)
        elif fam == "vlm":
            x, aux, new_cache = self._vlm_forward(
                params, x, None, None, aux, "decode", cache)
        else:
            raise ValueError(fam)

        x = C.apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        return self._head(params, x), new_cache

    def _rwkv_block_decode(self, bp, x, c):
        cfg = self.cfg
        h = C.apply_norm(bp["tm_norm"], x, "layernorm", cfg.norm_eps)
        y, tm = RW.rwkv6_decode(bp["tmix"], cfg, h,
                                {"s": c["s"], "shift_tm": c["shift_tm"]})
        x = x + y
        h = C.apply_norm(bp["cm_norm"], x, "layernorm", cfg.norm_eps)
        y, cm_shift = RW.rwkv6_cmix(bp["cmix"], cfg, h, c["shift_cm"])
        x = x + y
        return x, {"s": tm["s"], "shift_tm": tm["shift_tm"],
                   "shift_cm": cm_shift}


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _quantize_tree(p, a, qcfg: QuantConfig):
    """Replace every quantizable projection ``{"w": [..., K, N]}`` in the
    (params, axes) trees by its packed W4 payload."""
    if not isinstance(p, dict):
        return p, a
    out_p, out_a = {}, {}
    for key, val in p.items():
        quantizable = (
            key in QUANT_KEYS and isinstance(val, dict) and "w" in val
            and val["w"].shape[-2] % QL.BLOCK_K == 0
        )
        if quantizable:
            w = val["w"]
            lead = w.shape[:-2]
            k, n = w.shape[-2:]
            w2 = w.reshape(-1, k, n)
            packed, scale = jax.vmap(lambda wi: _quant_one(wi, qcfg))(w2)
            packed = packed.reshape(*lead, k // 2, n)
            scale = scale.reshape(*lead, k // QL.BLOCK_K, n)
            nd = {"w_packed": packed, "w_scale": scale}
            na = {"w_packed": a[key]["w"], "w_scale": a[key]["w"]}
            if "b" in val:
                nd["b"], na["b"] = val["b"], a[key]["b"]
            out_p[key], out_a[key] = nd, na
        elif isinstance(val, dict):
            out_p[key], out_a[key] = _quantize_tree(val, a[key], qcfg)
        else:
            out_p[key], out_a[key] = val, a[key]
    return out_p, out_a


def _quant_one(w, qcfg: QuantConfig):
    qp, _ = QL.quantize_linear_fraction(
        w, int4_fraction=qcfg.int4_fraction,
        schedule=qcfg.schedule, impl=qcfg.impl)
    return qp["w_packed"].value, qp["w_scale"].value


def _conv_pos(params, x):
    """Depthwise conv positional embedding (HuBERT)."""
    w, b = params["w"], params["b"]                     # [K, D], [D]
    k = w.shape[0]
    pad = jnp.pad(x.astype(jnp.float32),
                  ((0, 0), (k // 2, k - 1 - k // 2), (0, 0)))
    out = sum(pad[:, i:i + x.shape[1], :] * w[i][None, None, :]
              for i in range(k))
    return jax.nn.gelu(out + b).astype(x.dtype)


def _cross_kv(cfg: ModelConfig, ap, img):
    """Project image embeddings to cross-attn KV once (prefill)."""
    b = img.shape[0]
    k = C.linear(ap["wk"], img).reshape(b, -1, cfg.num_kv_heads, cfg.head_dim)
    v = C.linear(ap["wv"], img).reshape(b, -1, cfg.num_kv_heads, cfg.head_dim)
    return {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}


def _cross_decode(cfg: ModelConfig, ap, x, ckv):
    """Decode-step cross attention against cached image KV. x: [B, 1, D].

    The cached image KV stays bf16 end-to-end with f32 MXU accumulation
    (``preferred_element_type``) — materializing an f32 upcast of the
    [groups, B, T_img, Hkv, D] cache costs ~100 GB of spurious HBM
    traffic on the 90B decode cell (§Perf cell B, iteration 2).
    """
    b = x.shape[0]
    q = C.linear(ap["wq"], x).reshape(b, cfg.num_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = C.rmsnorm(q, ap["q_norm"]["scale"], cfg.norm_eps)
    g = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(b, cfg.num_kv_heads, g, cfg.head_dim).astype(jnp.bfloat16)
    import math
    sm = jnp.bfloat16(1.0 / math.sqrt(cfg.head_dim))
    sc = jnp.einsum("bhgd,bThd->bhgT", qg * sm, ckv["k"],
                    preferred_element_type=jnp.float32)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhgT,bThd->bhgd", p.astype(jnp.bfloat16), ckv["v"],
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, 1, cfg.q_dim).astype(x.dtype)
    return C.linear(ap["wo"], o)
