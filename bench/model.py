"""The system under test, built from a configuration file.

A configuration (``bench/configs/<name>.json``) holds the published
model config's keys as run, plus ``engine`` (``EngineConfig`` values)
and ``quant`` (``QuantConfig`` values). ``build`` makes the packed
serving model on the device in one jitted call from the seed — the
layer weights come from :mod:`bench.weights` and go through the
program's own offline quantizer — and returns a ready ``Engine``.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import weights as W

HERE = Path(__file__).resolve().parent

__all__ = ["load_config", "model_config", "build_params", "build_engine"]


def load_config(name: str, root: Path = HERE / "configs") -> dict:
    return json.loads((root / f"{name}.json").read_text())


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        qkv_bias=bool(cfg.get("qkv_bias", False)),
        norm_eps=float(cfg["rms_norm_eps"]))


def quant_config(cfg: dict):
    from repro.models.lm import QuantConfig
    return QuantConfig(**cfg["quant"])


def build_params(cfg: dict, seed: int):
    """Packed serving params for ``seed``, in one jitted call: a loop
    over layers draws each layer's f32 weights, quantizes them with the
    program's PTQ and writes them into the packed stack, so at most one
    f32 layer exists at a time."""
    from repro.layers import common as C
    from repro.models import lm as LM

    mcfg, qcfg = model_config(cfg), quant_config(cfg)
    lm = LM.LM(mcfg, quant=qcfg)
    box = {}

    def axes_of(k):
        p, box["axes"] = C.split_annotations(lm._init_dense_block(k))
        return p

    jax.eval_shape(axes_of, jax.random.PRNGKey(0))
    axes = box["axes"]

    def packed_layer(k):
        qp, _ = LM._quantize_tree(W.float_block(k, cfg), axes, qcfg)
        return qp

    n_layers = mcfg.num_layers
    seed_key = seed_key_of(seed)

    def build():
        one = jax.eval_shape(packed_layer, W.layer_key(seed_key, 0))
        stack = jax.tree.map(
            lambda s: jnp.zeros((n_layers,) + s.shape, s.dtype), one)

        def body(i, st):
            layer = packed_layer(W.layer_key(seed_key, i))
            return jax.tree.map(
                lambda a, b: jax.lax.dynamic_update_index_in_dim(a, b, i, 0),
                st, layer)

        blocks = jax.lax.fori_loop(0, n_layers, body, stack)
        return {
            "embed": {"table": W.embed_table(seed_key, cfg)},
            "final_norm": {"scale": jnp.ones((cfg["hidden_size"],),
                                             jnp.float32)},
            "lm_head": {"w": W.head_weight(seed_key, cfg)},
            "blocks": blocks,
        }

    return jax.jit(build)()


def seed_key_of(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31),
                              seed // 2**31)


def build_engine(cfg: dict, params, clock):
    from repro.serving.engine import Engine, EngineConfig
    return Engine(model_config(cfg), params, quant_config(cfg),
                  EngineConfig(**cfg["engine"]), clock=clock)
