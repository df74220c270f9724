"""Plain reference of a dense GQA decoder served at W4A4(A8)KV4.

Independent of the program: it imports nothing of ``repro`` and takes
nothing the program made. Weights come from :mod:`bench.weights` (the
same seeded definition the packed model was quantized from; on the int4
grid, so weight quantization is exact). The semantics follow the
configuration as stated:

* activations are bfloat16 between operations; norms, RoPE, softmax and
  every dot run in float32 at ``Precision.HIGHEST``;
* each projection's input is quantized per (token, 128-channel block),
  symmetric absmax: the leading ``round(int4_fraction * K/128)`` blocks
  to int4 ([-8, 7]), the rest to int8 ([-128, 127]); QKV biases are
  added after the product;
* keys (after RoPE) and values are quantized to int4 with the static
  per-channel affine scale ``kv_range / 15`` and zero point 7.5 (the
  paged cache); a prompt position reads the keys and values of its own
  prefill chunk as projected and every earlier one through the cache,
  and a served position reads all of them, its own included, through
  the cache — the chunk spans are the client's own record of the run;
* the head multiplies the final-normed hidden state by the bf16 head.

``logits`` runs a packed batch of sequences (segments) one layer at a
time, so only one layer's f32 weights exist at once; it returns the f32
logits at the requested positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
BLOCK = 128
Q_CHUNK = 512

__all__ = ["logits", "gaps"]


def _rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale).astype(jnp.bfloat16)


def _act_fake_quant(x, int4_fraction):
    """Per-(token, block) symmetric quantize-dequantize of [T, K]."""
    t, k = x.shape
    nb = k // BLOCK
    nb4 = max(0, min(nb, int(round(int4_fraction * nb))))
    xb = x.astype(jnp.float32).reshape(t, nb, BLOCK)
    amax = jnp.maximum(jnp.max(jnp.abs(xb), axis=-1, keepdims=True), 1e-8)
    is4 = (jnp.arange(nb) < nb4)[None, :, None]
    scale = jnp.where(is4, amax / 7.0, amax / 127.0)
    lo = jnp.where(is4, -8.0, -128.0)
    hi = jnp.where(is4, 7.0, 127.0)
    q = jnp.clip(jnp.round(xb / scale), lo, hi)
    return (q * scale).reshape(t, k)


def _proj(p, x, int4_fraction):
    y = jnp.dot(_act_fake_quant(x, int4_fraction), p["w"], precision=HI)
    if "b" in p:
        y = y + p["b"]
    return y.astype(jnp.bfloat16)


def _rope(x, pos, theta):
    """Rotate-half RoPE on [T, H, D] at absolute positions ``pos``."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(jnp.bfloat16)


def _kv_fake_quant(x, kv_range):
    scale = kv_range / 15.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale + 7.5), 0.0, 15.0)
    return (q - 7.5) * scale


def _attention(q, k, v, kq, vq, seg, pos, cs):
    """Causal attention within each segment. q [T, Hq, D] bf16; k/v
    [T, Hkv, D] bf16 as projected, kq/vq the same through the int4 cache
    (f32). A query at position p reads keys and values at positions
    >= cs[p] as projected and earlier ones from the int4 cache
    → [T, Hq*D] bf16."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b, precision=HI)

    qf = q.astype(jnp.float32).reshape(t, hkv, g, d) / np.sqrt(d)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    outs = []
    for c0 in range(0, t, Q_CHUNK):
        qc = qf[c0:c0 + Q_CHUNK]
        fp = (pos[None, :] >= cs[c0:c0 + Q_CHUNK, None])[None, None]
        sc = jnp.where(fp, dot("qhgd,khd->hgqk", qc, kf),
                       dot("qhgd,khd->hgqk", qc, kq))
        ok = ((seg[c0:c0 + Q_CHUNK, None] == seg[None, :])
              & (pos[c0:c0 + Q_CHUNK, None] >= pos[None, :]))
        sc = jnp.where(ok[None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        oc = (dot("hgqk,khd->qhgd", jnp.where(fp, pr, 0.0), vf)
              + dot("hgqk,khd->qhgd", jnp.where(fp, 0.0, pr), vq))
        outs.append(oc.reshape(oc.shape[0], hq * d))
    return jnp.concatenate(outs, 0).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("cfg_items", "int4_fraction"))
def _layer(x, key, seg, pos, cs, cfg_items, int4_fraction):
    cfg = dict(cfg_items)
    cfg["quant"] = dict(cfg["quant"])
    bp = W.float_block(key, cfg)
    t = x.shape[0]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    h = _rmsnorm(x, bp["attn_norm"]["scale"], eps)
    a = bp["attn"]
    q = _rope(_proj(a["wq"], h, int4_fraction).reshape(t, hq, hd), pos,
              cfg["rope_theta"])
    k = _rope(_proj(a["wk"], h, int4_fraction).reshape(t, hkv, hd), pos,
              cfg["rope_theta"])
    v = _proj(a["wv"], h, int4_fraction).reshape(t, hkv, hd)
    kq = _kv_fake_quant(k, cfg["kv_range"])
    vq = _kv_fake_quant(v, cfg["kv_range"])
    o = _attention(q, k, v, kq, vq, seg, pos, cs)
    x = x + _proj(a["wo"], o, int4_fraction)
    h = _rmsnorm(x, bp["mlp_norm"]["scale"], eps)
    m = bp["mlp"]
    gate = _proj(m["w_gate"], h, int4_fraction).astype(jnp.float32)
    up = _proj(m["w_up"], h, int4_fraction).astype(jnp.float32)
    hid = (jax.nn.silu(gate) * up).astype(jnp.bfloat16)
    return x + _proj(m["w_down"], hid, int4_fraction)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, head, rows, eps):
    h = _rmsnorm(x[rows], jnp.ones((x.shape[-1],), jnp.float32), eps)
    return jnp.dot(h, head, preferred_element_type=jnp.float32,
                   precision=HI)


def ref_config(cfg: dict) -> tuple:
    """The hashable slice of a configuration the reference reads."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "rms_norm_eps", "rope_theta",
            "vocab_size")
    out = {k: cfg[k] for k in keys}
    out["qkv_bias"] = bool(cfg.get("qkv_bias", False))
    out["kv_range"] = float(cfg["engine"]["kv_range"])
    # the weights' int4 blocks (bench/weights.py), whatever the fraction
    # a pass quantizes activations at
    out["quant"] = (("int4_fraction", float(cfg["quant"]["int4_fraction"])),)
    return tuple(sorted(out.items()))


def logits(cfg: dict, seed_key, tokens, seg, pos, cs, rows,
           int4_fraction: float):
    """Reference logits [len(rows), vocab] f32 for a packed batch.

    tokens/seg/pos/cs: [T] int32 (segment id, in-segment position and
    first full-precision key position of each packed token); rows: [R]
    packed indices whose logits are wanted."""
    items = ref_config(cfg)
    x = W.embed_table(seed_key, cfg)[tokens]
    for li in range(cfg["num_hidden_layers"]):
        x = _layer(x, W.layer_key(seed_key, li), seg, pos, cs, items,
                   int4_fraction)
    return _head(x, W.head_weight(seed_key, cfg), rows,
                 float(cfg["rms_norm_eps"]))


def gaps(ref_logits, chosen):
    """Per row: how far the chosen token's reference logit lies below
    the reference's best (0 where the choice is the reference's argmax)."""
    ref_logits = jnp.asarray(ref_logits)
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(chosen)[:, None],
                              axis=-1)[:, 0]
    return np.asarray(best - got)
