"""Work-queue paged int4 attention (``kernels/paged_attention.py``,
``paged_kv4_prefill_attention_wq``): operations and bytes of one engine
step's calls, from the step's shapes.

The kernel runs once per layer over every forward row (a decode row is
a chunk of one) when any row has paged history; a step whose rows are
all first chunks takes the XLA flash path instead and has no call here.
For a row with ``c`` tokens of history and a chunk of ``q``:

* operations: ``4 * Hq * D * q * (c + (q + 1) / 2)`` (QK and PV over
  the history plus the causal half of the chunk);
* bytes: the history's int4 K and V pages
  ``2 * ceil(c / page) * page * Hkv * D/2``, the chunk's f32 K and V
  ``2 * q * Hkv * D * 4``, bf16 queries ``q * Hq * D * 2`` and f32
  output ``q * Hq * D * 4``.

The dots take bf16 passes on the MXU, so the bf16 peak bounds the
operations.
"""

PEAK = "bf16_flops_s"


def calls(cfg: dict, rows: list) -> list:
    if not rows or all(c == 0 for c, _ in rows):
        return []
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ps = cfg["engine"]["page_size"]
    ops = nbytes = 0.0
    for c, q in rows:
        ops += 4.0 * hq * d * q * (c + (q + 1) / 2)
        pages = -(-c // ps)
        nbytes += (2 * pages * ps * hkv * d / 2 + 2 * q * hkv * d * 4
                   + q * hq * d * 2 + q * hq * d * 4)
    return [(ops, nbytes)] * cfg["num_hidden_layers"]

# device ops that are this kernel's calls, matched in the op's name or
# HLO text: by kernel name, or as the custom call whose first operand is
# the [W, 4] work-item array
MATCH = (r"_paged_kv4_prefill_wq_kernel|operand_layout_constraints="
         r"\{s32\[\d+,4\]")
