"""Seeded random weights for a dense GQA decoder, on the int4 grid.

Every number is an integer code times a power-of-two scale, drawn with
integer draws only (no transcendental function, no rounding anywhere), so
the program's build and the reference draw bit-identical weights on any
backend and inside any fusion. (A Gaussian draw goes through ``erf_inv``,
whose last bits differ between the CPU and the TPU and between fused and
unfused programs; through the int4 grid such differences move the logits
as far as a precision step does.)

Every projection weight is ``code * scale`` with integer codes uniform in
[-7, 7] (each 128-row group holds a code of magnitude 7 in every column,
so the group's absmax is exactly ``7 * scale``) and one scale per
(128-row group, output column): a power of two near ``fan_in ** -0.5 /
std(code)`` times m/8, m uniform in 6..10. Symmetric absmax int4
quantization (the serving format) therefore maps these weights onto
themselves: the float weights the reference multiplies by are exactly the
weights the packed model holds, and the reference needs nothing the
program made.

The rows of a projection's int8 activation blocks (its trailing input
channels, ``1 - int4_fraction`` of them in 128-channel blocks, where the
serving format places them) take ``INT8_ROWS`` times that scale, and the
rows of its int4 blocks ``INT4_ROWS`` times: the int8 channels carry most
of each product, as the outlier channels that FMPQ keeps in int8 do in
the models it serves. With every row alike, int4 rounding in seven
eighths of the channels decides each product, and a rounding-level
difference anywhere (one backend's ``exp`` against another's) flips
enough int4 codes downstream that two sound implementations land as far
apart as the precision step below them (PERF.md, section 2.1).

``float_block`` is the one definition of a layer's weights; the program's
packed model and the reference's layers are both drawn through it from
the same key. Norm scales are ones; QKV biases (where the configuration
has them) are small integer codes times a power of two.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

GROUP = 128
_CODE_STD = (56.0 / 3.0) ** 0.5      # std of codes uniform on [-7, 7]
INT8_ROWS = 2.0                      # row scale of the int8 activation blocks
INT4_ROWS = 0.125                    # row scale of the int4 activation blocks

__all__ = ["GROUP", "INT8_ROWS", "INT4_ROWS", "int4_blocks", "float_block",
           "embed_table", "head_weight", "layer_key", "block_keys"]


def layer_key(seed_key, li):
    return jax.random.fold_in(jax.random.fold_in(seed_key, 1), li)


def _pow2(x: float) -> float:
    """The power of two nearest ``x`` (in log scale)."""
    return float(2.0 ** round(math.log2(x)))


def _codes(key, shape):
    """Integer codes uniform on [-7, 7], as float32."""
    return jax.random.randint(key, shape, -7, 8).astype(jnp.float32)


def int4_blocks(k: int, int4_fraction: float) -> int:
    """The leading 128-channel blocks of a K-channel input that are
    quantized to int4; the rest go to int8."""
    nb = k // GROUP
    return max(0, min(nb, int(round(int4_fraction * nb))))


def _grid_weight(key, k, n, std, int4_fraction):
    kc, ks, ksign = jax.random.split(key, 3)
    codes = _codes(kc, (k, n))
    sign = jnp.where(jax.random.bernoulli(ksign, 0.5, (k // GROUP, n)),
                     7.0, -7.0)
    codes = codes.reshape(k // GROUP, GROUP, n).at[:, 0, :].set(sign)
    m = jax.random.randint(ks, (k // GROUP, 1, n), 6, 11)
    rows = jnp.where(jnp.arange(k // GROUP) < int4_blocks(k, int4_fraction),
                     INT4_ROWS, INT8_ROWS)[:, None, None]
    scale = _pow2(std / _CODE_STD) / 8.0 * m.astype(jnp.float32) * rows
    return (codes * scale).reshape(k, n)


def block_keys():
    """Leaf order of one block: (path, fan_in, fan_out)."""
    return ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def float_block(key, cfg: dict) -> dict:
    """One decoder layer's f32 weights, in the tree layout of the
    program's dense block (``attn``/``mlp`` projections as ``{"w": ...}``
    with optional ``"b"``, norms as ``{"scale": ...}``)."""
    d, hq, hkv, hd, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["head_dim"],
                         cfg["intermediate_size"])
    shapes = {"wq": (d, hq * hd), "wk": (d, hkv * hd), "wv": (d, hkv * hd),
              "wo": (hq * hd, d), "w_gate": (d, f), "w_up": (d, f),
              "w_down": (f, d)}
    keys = dict(zip(block_keys(), jax.random.split(key, 8)))
    frac = cfg["quant"]["int4_fraction"]
    proj = {name: {"w": _grid_weight(keys[name], k, n, k ** -0.5, frac)}
            for name, (k, n) in shapes.items()}
    if cfg.get("qkv_bias"):
        kb = jax.random.split(jax.random.fold_in(key, 99), 3)
        for kk, name in zip(kb, ("wq", "wk", "wv")):
            n = shapes[name][1]
            proj[name]["b"] = _codes(kk, (n,)) * 2.0 ** -5   # std 0.14
    ones = {"scale": jnp.ones((d,), jnp.float32)}
    return {
        "attn_norm": ones,
        "attn": {k: proj[k] for k in ("wq", "wk", "wv", "wo")},
        "mlp_norm": dict(ones),
        "mlp": {k: proj[k] for k in ("w_gate", "w_up", "w_down")},
    }


def embed_table(seed_key, cfg: dict):
    """[vocab, d] bf16, codes on [-7, 7] times 1/4 (std 1.08): the served
    embedding."""
    k = jax.random.fold_in(seed_key, 2)
    return (_codes(k, (cfg["vocab_size"], cfg["hidden_size"]))
            * 0.25).astype(jnp.bfloat16)


def head_weight(seed_key, cfg: dict):
    """[d, vocab] bf16, codes on [-7, 7] times the power of two nearest
    d**-0.5 / std(code): the served output head."""
    k = jax.random.fold_in(seed_key, 3)
    d = cfg["hidden_size"]
    return (_codes(k, (d, cfg["vocab_size"]))
            * _pow2(d ** -0.5 / _CODE_STD)).astype(jnp.bfloat16)
