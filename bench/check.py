"""``correct``: the logits the timed path served against the plain
reference.

Before the window opens, :func:`choose` draws from the seed the requests
to compare: the longest one due in the window, then others due there in
an order drawn from the seed, while they fit ``PACK`` packed tokens and
``ROWS`` served tokens. While the window runs, :class:`LogitsRecorder`
keeps the logits row each of their tokens was sampled from, as the
engine handed it to its sampler. Once the window has closed and the
engine is freed, the chosen requests that finished go through
:mod:`bench.reference` once: each prompt followed by its served tokens.
The numbers read; those the configuration's ``correct`` section gives a
limit are compared:

* ``logit_err_median`` and ``logit_err_p90`` — per served token, the
  relative L2 distance ``|program - reference| / |reference|`` between
  the program's logits row and the reference's at the same position; the
  median and the 90th percentile over the sample (upper limits). The
  widest is not read: a few rows where round-off flips an int4 code read
  as far off as the control does;
* ``served_not_argmax`` — served tokens that are not the first choice
  of the row they were sampled from, or that no row was recorded for
  (greedy requests: exact, limit 0);
* ``served_tokens`` — tokens compared (lower limit: some hundreds).
"""

from __future__ import annotations

import numpy as np

PACK = 8192           # packed tokens per reference pass (one shape)
ROWS = 1536           # compared positions per pass (one shape)
MISSING = 1e9         # the error of a served token with no recorded row

__all__ = ["PACK", "ROWS", "choose", "LogitsRecorder", "pack", "finished",
           "served_rows", "rel_l2", "readings", "check"]


def choose(reqs, t_lo: float, t_hi: float, seed: int) -> set:
    """Client request ids to compare: of the requests due in
    ``[t_lo, t_hi)``, the longest (prompt plus output), then others in
    an order drawn from the seed, while they fit ``PACK`` packed tokens
    and ``ROWS`` served tokens."""
    due = [r for r in reqs if t_lo <= r.due_s < t_hi]
    if not due:
        return set()
    due.sort(key=lambda r: (-(len(r.prompt) + r.max_new), r.rid))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0xC4EC])
    order = [due[0]] + [due[i] for i in 1 + rng.permutation(len(due) - 1)]
    out, used, rows = set(), 0, 0
    for r in order:
        n = len(r.prompt) + r.max_new - 1
        if used + n > PACK or rows + r.max_new > ROWS:
            continue
        out.add(r.rid)
        used += n
        rows += r.max_new
    return out


class LogitsRecorder:
    """Keeps, for the chosen requests of one client, the logits row each
    token was sampled from: it wraps the engine's batched sampler, which
    receives every row the step consumes with the sequence position of
    the token it produces. ``rows[(rid, position)]`` is a float32 copy.
    :meth:`remove` restores the engine's own sampler."""

    def __init__(self, eng, client, wanted: set):
        self.eng, self.rows = eng, {}
        self._own = eng._sample_batch
        by_engine_id = client.by_engine_id

        def recording(logits, reqs, positions):
            for row, r, p in zip(logits, reqs, positions):
                rec = by_engine_id.get(r.request_id)
                if rec is not None and rec.rid in wanted:
                    self.rows[(rec.rid, int(p))] = np.array(row, np.float32)
            return self._own(logits, reqs, positions)

        eng._sample_batch = recording

    def remove(self):
        self.eng._sample_batch = self._own


def chunk_starts(r, n: int) -> list:
    """For each of a sequence's ``n`` positions, the first position whose
    keys it saw unquantized: the start of the prompt chunk it was
    forwarded in (a chunk attends to its own keys in full precision and
    to earlier ones through the int4 pages), or itself plus one for
    positions forwarded alone (decode rows read their own key from the
    int4 page too)."""
    cs = list(range(1, n + 1))
    for a, b in r.chunks:
        for p in range(a, min(b, n)):
            cs[p] = a
    return cs


def pack(chosen, prompts: dict):
    """Packed (tokens, seg, pos, cs, rows, served) arrays for the
    reference: each sequence is its prompt and its served tokens but the
    last; row ``j`` of a sequence is the position whose logits chose
    served token ``j``; ``cs`` is :func:`chunk_starts`."""
    toks, seg, pos, cs, rows, served = [], [], [], [], [], []
    for si, r in enumerate(chosen):
        seq = list(prompts[r.rid]) + r.tokens[:-1]
        base = len(toks)
        toks += seq
        seg += [si] * len(seq)
        pos += list(range(len(seq)))
        cs += chunk_starts(r, len(seq))
        p = r.prompt_len
        rows += [base + p - 1 + j for j in range(len(r.tokens))]
        served += r.tokens
    n = len(toks)
    toks += [0] * (PACK - n)
    seg += [-1] * (PACK - n)
    pos += [0] * (PACK - n)
    cs += [1] * (PACK - n)
    m = len(rows)
    rows += [0] * (ROWS - m)
    return (np.asarray(toks, np.int32), np.asarray(seg, np.int32),
            np.asarray(pos, np.int32), np.asarray(cs, np.int32),
            np.asarray(rows, np.int32), np.asarray(served, np.int32), m)


def finished(records) -> list:
    """The records that finished with tokens, by request id."""
    return sorted((r for r in records if r.end_state == "FINISHED"
                   and r.tokens), key=lambda r: r.rid)


def served_rows(chosen, recorded: dict, vocab: int) -> np.ndarray:
    """[served tokens, vocab]: the recorded logits row of each served
    token of ``chosen``, in :func:`pack`'s order (NaN where none)."""
    nan_row = np.full((vocab,), np.nan, np.float32)
    return np.stack([recorded.get((r.rid, r.prompt_len + j), nan_row)
                     for r in chosen for j in range(len(r.tokens))])


def rel_l2(got, ref, block: int = 128) -> np.ndarray:
    """Per row: |got - ref| / |ref|, summed in float64 a block of rows
    at a time."""
    out = np.empty(len(ref))
    for i in range(0, len(ref), block):
        g = np.asarray(got[i:i + block], np.float64)
        r = np.asarray(ref[i:i + block], np.float64)
        out[i:i + block] = (np.linalg.norm(g - r, axis=-1)
                            / np.maximum(np.linalg.norm(r, axis=-1), 1e-30))
    return out


def readings(got, ref, served) -> dict:
    """The compared numbers of logits rows ``got`` (NaN rows: none was
    recorded; zeroed in place) against the reference's ``ref``, for
    tokens ``served``."""
    missing = np.isnan(got).any(axis=-1)
    got[missing] = 0.0
    err = rel_l2(got, ref)
    err[missing] = MISSING
    not_first = missing | (np.argmax(got, axis=-1) != served)
    return {"logit_err_median": float(np.median(err)),
            "logit_err_p90": float(np.percentile(err, 90)),
            "served_not_argmax": int(not_first.sum()),
            "served_tokens": int(len(served))}


def verdict(values: dict, limits: dict) -> dict:
    """→ {"correct": bool, "compared": {name: {"value", "limit"}}}: the
    numbers the configuration gives a limit, each held to it (an upper
    limit; ``served_tokens`` a lower one)."""
    cmp = {k: {"value": values[k], "limit": lim} for k, lim in limits.items()}
    ok = all(v["value"] <= v["limit"] for k, v in cmp.items()
             if k != "served_tokens")
    ok = ok and cmp["served_tokens"]["value"] >= limits["served_tokens"]
    return {"correct": bool(ok), "compared": cmp}


def check(cfg: dict, seed: int, records, prompts: dict, recorded: dict,
          int4_fraction=None) -> dict:
    """→ {"correct": bool, "compared": {name: {"value", "limit"}},
    "values": every number read}.

    ``records`` are the client's records of the chosen requests and
    ``recorded`` the :class:`LogitsRecorder` rows. With ``int4_fraction``
    set, this is the control: the reference at that fraction (a
    precision step below the configuration's) is put in the program's
    place at every compared position, and its own first choice is the
    served token."""
    from bench import model as M
    from bench import reference as R
    limits = cfg["correct"]
    chosen = finished(records)
    if not chosen:
        values = {"logit_err_median": MISSING, "logit_err_p90": MISSING,
                  "served_not_argmax": 0, "served_tokens": 0}
        return {**verdict(values, limits), "values": values}
    toks, seg, pos, cs, rows, served, m = pack(chosen, prompts)
    key = M.seed_key_of(seed)
    frac = cfg["quant"]["int4_fraction"]
    ref = np.asarray(R.logits(cfg, key, toks, seg, pos, cs, rows,
                              frac))[:m]
    if int4_fraction is None:
        got = served_rows(chosen, recorded, ref.shape[-1])
    else:
        got = np.array(R.logits(cfg, key, toks, seg, pos, cs, rows,
                                int4_fraction))[:m]
        served = got.argmax(axis=-1)
    values = readings(got, ref, served)
    return {**verdict(values, limits), "values": values}
