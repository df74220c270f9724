"""The unified forward's layout crosses to the device in ONE transfer:
the 12 int32 layout arrays are packed into one buffer on the host and
sliced back out by a jitted prologue. Every forward issues exactly one
transfer inside ``engine.upload`` (decode, mixed and speculating steps,
both attention schedules); pack then unpack returns each array exactly,
padding fills and dtype included; and the packed path's logits and pools
are bit-identical to the forward body fed the 12 arrays separately."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.models.lm import LM, QuantConfig
from repro.serving import engine as E
from repro.serving import tracing
from repro.serving.engine import Engine, EngineConfig, SamplingParams

REPETITIVE = [[188] * 8, [139, 133, 188, 188] * 2, [188] * 12]


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama3_8b")
    qc = QuantConfig(weight_only=True, kv4=True, impl="ref")
    lm = LM(cfg)
    params, axes = lm.init(jax.random.PRNGKey(0))
    qparams, _ = LM(cfg, quant=qc).quantize(params, axes)
    return cfg, qc, qparams


def make_engine(setup, **kw):
    cfg, qc, qparams = setup
    ecfg = dict(max_batch=6, num_pages=128, page_size=8,
                max_pages_per_seq=32, prefill_chunk_tokens=24,
                kv_range=4.0)
    ecfg.update(kw)
    return Engine(cfg, qparams, qc, EngineConfig(**ecfg))


def submit(eng, prompts, max_new, speculation=0):
    for p in prompts:
        eng.submit(p, SamplingParams(max_new_tokens=max_new, temperature=0.0,
                                     speculation=speculation))


def ragged(lens, vocab, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).tolist() for n in lens]


class PhaseTransfers:
    """Stands in for ``tracing.span`` and wraps ``jax.device_put`` and
    ``jnp.asarray``: counts the transfers made inside each phase."""

    def __init__(self, monkeypatch):
        self.phase = None
        self.counts: dict = {}
        rec = self

        def span(name, on):
            class _Span:
                def __enter__(self):
                    rec.phase = name

                def __exit__(self, *a):
                    rec.phase = None
            return _Span()

        def counted(fn):
            def wrapper(*a, **k):
                rec.counts[rec.phase] = rec.counts.get(rec.phase, 0) + 1
                return fn(*a, **k)
            return wrapper

        monkeypatch.setattr(tracing, "span", span)
        monkeypatch.setattr(jax, "device_put", counted(jax.device_put))
        monkeypatch.setattr(jnp, "asarray", counted(jnp.asarray))


def _mixed(rows):
    """A forward holding a prompt chunk beside a decode row."""
    return (any(q > 1 for _, q in rows)
            and any(c > 0 and q == 1 for c, q in rows))


CASES = {
    # (prompts, max_new, speculation, which forward must occur)
    "decode_only": (((5, 3, 7, 4), 6, 0),
                    lambda eng, rows: all(c > 0 and q == 1
                                          for c, q in rows)),
    "mixed": (((40, 7, 23, 64, 13, 29), 6, 0),
              lambda eng, rows: _mixed(rows)),
    # a speculating row consumes every chunk position: Lb > Nb
    "speculating": ((REPETITIVE, 12, 4),
                    lambda eng, rows: any(b[7] > b[4]
                                          for b in eng._fwd_shapes)),
}


@pytest.mark.parametrize("schedule", ["work_queue", "dense"])
@pytest.mark.parametrize("case", list(CASES))
def test_one_transfer_per_forward(setup, monkeypatch, case, schedule):
    (lens, max_new, k), wanted = CASES[case]
    eng = make_engine(setup, attention_schedule=schedule)
    prompts = (lens if isinstance(lens[0], list)
               else ragged(lens, eng.cfg.vocab_size))
    submit(eng, prompts, max_new, speculation=k)
    eng.trace_spans = True
    rec = PhaseTransfers(monkeypatch)
    seen = False
    while eng.sched.has_work:
        rec.counts.clear()
        fwd0 = eng.forward_calls
        eng.step()
        if eng.forward_calls > fwd0:
            assert rec.counts.get("engine.upload") == 1, rec.counts
            seen = seen or wanted(eng, eng.last_forward_rows)
        else:
            assert "engine.upload" not in rec.counts
    assert seen, f"no {case} forward ran"
    assert eng.internal_errors == 0 and eng.failed_count == 0
    assert eng.host_uploads == eng.forward_calls == eng.steps


def test_host_uploads_counted_with_spans_off(setup):
    eng = make_engine(setup)
    submit(eng, ragged((30, 5, 12), eng.cfg.vocab_size), 4)
    eng.run()
    assert eng.forward_calls > 0
    assert eng.host_uploads == eng.forward_calls


# ------------------------------------------------------ pack / unpack


def _arrays(rng, shapes, lens):
    """Host arrays as ``_guarded_forward`` builds them: the [Tb], [Nb]
    and [Lb] ones shorter than their segment (int64, or bool for the dq
    mask), the tables and descriptors whole int32."""
    out = []
    for i, (shape, n) in enumerate(zip(shapes, lens)):
        if len(shape) == 2:
            out.append(rng.integers(0, 50, shape).astype(np.int32))
        elif i == 6:
            out.append(rng.random(n) < 0.5)
        else:
            out.append(rng.integers(1, 1000, n))
    return out


ROUND_TRIPS = {
    # (tb, nb, npb, lb, wb), real lengths of the 1-D arrays
    "tb8_nb1": ((8, 1, 1, 1, 64), 5, 1, 1),
    "dense_dummy_desc": ((16, 4, 8, 4, 8), 13, 3, 3),
    "spec_lb16_nb8": ((32, 8, 1, 16, 256), 27, 8, 14),
}


@pytest.mark.parametrize("case", list(ROUND_TRIPS))
def test_pack_unpack_round_trip(case):
    (tb, nb, npb, lb, wb), ttot, nseq, nlog = ROUND_TRIPS[case]
    shapes = E._layout_shapes(tb, nb, npb, lb, wb)
    fills = (0, 0, 99, 0, nb) + (0,) * 7
    lens = (ttot,) * 7 + (None, nseq, nseq, nlog, None)
    arrays = _arrays(np.random.default_rng(0), shapes, lens)
    packed = E._pack_layout(arrays, shapes, fills)
    assert packed.dtype == np.int32 and packed.ndim == 1
    out = jax.jit(E._unpack_layout, static_argnames="shapes")(
        jnp.asarray(packed), shapes=shapes)
    assert len(out) == 12
    for a, shape, fill, got in zip(arrays, shapes, fills, out):
        want = (E._pad_to(a, shape[0], fill) if len(shape) == 1
                else np.asarray(a, np.int32))
        assert got.dtype == jnp.int32 and got.shape == shape
        np.testing.assert_array_equal(np.asarray(got), want)


def test_pack_rejects_an_array_longer_than_its_segment():
    shapes = E._layout_shapes(8, 1, 1, 1, 8)
    arrays = [np.arange(9)] + [np.zeros(s, np.int32) for s in shapes[1:]]
    with pytest.raises(ValueError):
        E._pack_layout(arrays, shapes, (0,) * 12)


def test_packed_forward_bit_identical_to_separate_arrays(setup,
                                                         monkeypatch):
    """Each forward of a mixed, speculating run: the arrays the packed
    upload hands the forward equal the 12 separately uploaded arrays of
    the unpacked layout, and its logits and pools equal those of the
    forward body jitted over those 12 arrays."""
    packs, calls = [], []
    real_pack = E._pack_layout

    def record_pack(arrays, shapes, fills):
        packs.append((arrays, shapes, fills))
        return real_pack(arrays, shapes, fills)

    monkeypatch.setattr(E, "_pack_layout", record_pack)
    eng = make_engine(setup)
    fwd = eng._fwd

    def spy(*a):
        out = fwd(*a)
        calls.append((a, out))
        return out

    eng._fwd = spy
    cfg = eng.cfg
    submit(eng, REPETITIVE, 6, speculation=4)
    submit(eng, ragged((30,), cfg.vocab_size), 3)
    eng.run()
    assert eng.internal_errors == 0
    assert len(packs) == len(calls) == eng.forward_calls
    checked = set()
    for (arrays, shapes, fills), (a, out) in zip(packs, calls):
        bucket = a[:3] + (shapes,)
        if bucket in checked:
            continue
        checked.add(bucket)
        separate = [jnp.asarray(E._pad_to(x, s[0], f) if len(s) == 1
                                else np.asarray(x, np.int32))
                    for x, s, f in zip(arrays, shapes, fills)]
        for got, want in zip(a[6:18], separate):
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
        cb, no_history, schedule = a[:3]

        def body(params, k_pool, v_pool, *layout):
            return eng._unified_body(
                cb, no_history, schedule, None, params, k_pool, v_pool,
                *layout, eng.cache.k_scale, eng.cache.k_zero,
                eng.cache.v_scale, eng.cache.v_zero)

        ref = jax.jit(body)(a[3], a[4], a[5], *separate)
        for got, want in zip(out, ref):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert len(checked) >= 3
    assert any(s[10][0] > s[8][0] for _, s, _ in packs)     # Lb > Nb
