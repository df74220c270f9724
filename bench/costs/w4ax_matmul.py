"""W4Ax GEMM (``kernels/w4ax_matmul.py``): operations and bytes of one
engine step's calls, from the step's shapes.

Every decoder layer runs seven projections (q, k, v, o, gate, up, down),
each one call over the step's M packed tokens. A call multiplies the
leading ``K4 = round(int4_fraction * K/128) * 128`` input channels as
int4 and the rest as int8, both on the int8 MXU path:

* operations: ``2 * M * K * N`` (int8-equivalent multiply-adds);
* bytes: packed int4 weights ``K*N/2``, f32 weight scales
  ``K/128 * N * 4``, quantized activations ``M * (K4/2 + K8)`` and their
  f32 scales ``M * K/128 * 4``, f32 output ``M * N * 4``.

A call's least time is the larger of operations over the int8 peak and
bytes over HBM bandwidth: at decode (M of a few tens) the bytes bound
it, in a prompt chunk (M in the hundreds) the operations do.
"""

PEAK = "int8_ops_s"


def projections(cfg: dict) -> list:
    d, hq, hkv, hd, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["head_dim"],
                         cfg["intermediate_size"])
    return [(d, hq * hd), (d, hkv * hd), (d, hkv * hd), (hq * hd, d),
            (d, f), (d, f), (f, d)]


def calls(cfg: dict, rows: list) -> list:
    """[(ops, bytes), ...] of one step with forward rows [(ctx, q), ...]."""
    m = sum(q for _, q in rows)
    if m == 0:
        return []
    frac = cfg["quant"]["int4_fraction"]
    out = []
    for k, n in projections(cfg):
        nb = k // 128
        k4 = max(0, min(nb, int(round(frac * nb)))) * 128
        k8 = k - k4
        ops = 2.0 * m * k * n
        nbytes = (k * n / 2 + (k // 128) * n * 4 + m * (k4 / 2 + k8)
                  + m * (k // 128) * 4 + m * n * 4)
        out.append((ops, nbytes))
    return out * cfg["num_hidden_layers"]

# device ops that are this kernel's calls, matched in the op's name or
# HLO text: the Mosaic kernels of the split GEMM's W4A4 and W4A8 halves,
# by kernel name, or as the custom call whose operands are quantized
# activations, their scales and packed int4 weights
MATCH = (r"_w4a4_kernel|_w4a8_kernel|operand_layout_constraints="
         r"\{[us]8\[\d+,\d+\]\{1,0\}, f32\[\d+,\d+\]\{1,0\}, u8\[\d+,\d+\]")
