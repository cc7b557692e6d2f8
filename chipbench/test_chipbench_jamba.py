"""The Jamba family (``chipbench/families/jamba.py``) at a toy size on the
CPU: its config check, its weights against the program's layout, its
float32 reference against the program's forward, its counts, a whole run
of the served path, and the two readers it brings."""

import copy
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import families, reference, roofline, run, weights
from chipbench.families import jamba
from chipbench.metrics import prefill_pad_share, prefill_roofline
from chipbench.trace_reduce import Interval, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**40 + 3


def load(name: str) -> dict:
    for d in ("configs", "testdata"):
        path = os.path.join(HERE, d, f"{name}.json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise FileNotFoundError(name)


@pytest.fixture(scope="module")
def full():
    return load("jamba2-3b")


@pytest.fixture(scope="module")
def tiny():
    return load("tiny-jamba")


def test_the_family_is_found_and_the_config_checks(full, tiny):
    assert families.of(full) is jamba
    cfg = jamba.arch_config(full)
    assert cfg.block_pattern == ("mamba",) * 7 + ("attn",) + ("mamba",) * 6
    assert (cfg.pattern_repeats, cfg.use_rope, cfg.dt_rank) == (2, False, 160)
    assert [jamba.kind(full, i) for i in range(28)].count("attn") == 2
    assert jamba.kind(full, 7) == jamba.kind(full, 21) == "attn"
    assert jamba.arch_config(tiny).block_pattern == ("mamba", "attn", "mamba")


@pytest.mark.parametrize("key,value", [
    ("attn_layer_offset", 6), ("mamba_dt_rank", 128), ("mamba_d_state", 8),
    ("num_experts", 16), ("mamba_proj_bias", True), ("mamba_conv_bias", False),
    ("num_hidden_layers", 27), ("num_key_value_heads", 2)])
def test_a_file_the_program_does_not_serve_is_refused(full, key, value):
    with pytest.raises(ValueError):
        jamba.arch_config(dict(full, **{key: value}))


def test_served_weights_have_the_programs_layout(full, tiny):
    from repro.models.model import LM
    for c in (full, tiny):
        want, _ = LM(jamba.arch_config(c)).abstract_params()
        got = jax.eval_shape(lambda w: jamba.program_params(c, w),
                             weights.seed_words(SEED))
        assert jax.tree.structure(want) == jax.tree.structure(got)
        assert [(a.shape, a.dtype) for a in jax.tree.leaves(want)] == \
            [(a.shape, a.dtype) for a in jax.tree.leaves(got)]


def test_recurrence_parameters_are_mambas(tiny):
    w = jamba.reference_weights(tiny, jnp.asarray(weights.seed_words(SEED)),
                                0)
    n = tiny["mamba_d_state"]
    np.testing.assert_allclose(np.exp(np.asarray(w["A_log"]))[0],
                               np.arange(1, n + 1), rtol=1e-6)
    assert np.all(np.asarray(w["D"]) == 1.0)
    dt = np.log1p(np.exp(np.asarray(w["dt_bias"])))
    assert dt.min() >= jamba.DT_FLOOR and dt.max() <= jamba.DT_MAX * 1.001
    assert dt.max() / dt.min() > 10              # spread over the range


def program_logits(c, toks, dtype):
    from repro.models.model import LM
    cfg = dataclasses.replace(jamba.arch_config(c), dtype=dtype)
    p = jax.jit(lambda w: jamba.program_params(c, w))(
        weights.seed_words(SEED))
    p = jax.tree.map(lambda a: a.astype(jnp.float32)
                     if dtype == "float32" else a, p)
    with jax.default_matmul_precision("highest"):
        lg = LM(cfg).forward(p, {"tokens": toks[None]}, remat=False)[0][0]
    return np.asarray(lg, np.float32)


def test_reference_gaps_equal_the_program_forward(tiny):
    """The reference's gaps for the tokens the program ranks first are 0,
    and its gaps for any tokens are the program's own gaps, to 1e-4: two
    float32 forwards that differ in summation order (chunked scan and
    flash attention against a sequential scan and plain softmax) agree to
    about 1e-6 on logits of up to about 6.  A bfloat16 program misses
    that by far, and the float8 control departs further still."""
    toks = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    lg = program_logits(tiny, toks, "float32")
    prompt, served = toks[:10], toks[10:]
    gaps, ctrl = reference.served_gaps(tiny, SEED, [(prompt, served)],
                                       control=True)
    rows = np.arange(9, 39)
    want = lg[rows].max(-1) - lg[rows, served]
    np.testing.assert_allclose(gaps[0], want, atol=1e-4)
    lg16 = program_logits(tiny, toks, "bfloat16")
    assert np.abs(lg16[rows].max(-1) - lg16[rows, served] - gaps[0]).max() \
        > 1e-2
    assert ctrl[0].max() > 0.05


def reference_logits(c, toks):
    """The family's plain float32 forward over ``toks``: every row's
    logits."""
    words = jnp.asarray(weights.seed_words(SEED))
    x = reference._embed_rows(words, jnp.asarray(toks), reference.static(c))
    with jax.default_matmul_precision("highest"):
        for layer in range(c["num_hidden_layers"]):
            x = jamba.reference_layer(
                c, layer, x, jamba.reference_weights(c, words, layer), False)
        return np.asarray(reference._head_logits(words, x, c, False))


def served_logits(c, dtype, prompt, new_tokens):
    """Bucketed prefill, then fused decode: the tokens served, and the
    logits behind each (the prefill's, then teacher-forced decode steps
    through the same caches)."""
    from repro.serving.engine import RealEngine
    cfg = dataclasses.replace(jamba.arch_config(c), dtype=dtype)
    p = jax.jit(lambda w: jamba.program_params(c, w))(
        weights.seed_words(SEED))
    if dtype == "float32":
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eng = RealEngine(cfg, params=p, max_len=64, segment_len=4)
    with jax.default_matmul_precision("highest"):
        served = eng.generate(prompt, max_new_tokens=new_tokens)["tokens"]
        logits, caches, _ = eng._run_prefill(prompt)
        rows = [np.asarray(logits, np.float32)[0]]
        for tok in served[:-1]:
            logits, caches = eng._decode(
                p, caches, {"tokens": jnp.full((1, 1), tok, jnp.int32)})
            rows.append(np.asarray(logits, np.float32)[0])
    return served, np.stack(rows)


@pytest.mark.parametrize("plen", [11, 20])
def test_served_logits_match_the_reference_forward(tiny, plen):
    """Prompt lengths between buckets (16, 32): the float32 program's
    bucketed prefill and decode steps give the reference's full-forward
    logits at every served position, to 1e-4 (summation order alone
    differs; logits reach about 6), and the fused loop serves their
    argmax.  The bfloat16 program misses by more than 1e-2."""
    prompt = np.random.default_rng(plen).integers(0, 512, plen)
    served, got = served_logits(tiny, "float32", prompt, 12)
    assert served == [int(r.argmax()) for r in got]
    want = reference_logits(
        tiny, np.concatenate([prompt, served[:-1]]).astype(np.int32))
    np.testing.assert_allclose(got, want[plen - 1:], atol=1e-4)
    served16, got16 = served_logits(tiny, "bfloat16", prompt, 12)
    want16 = reference_logits(
        tiny, np.concatenate([prompt, served16[:-1]]).astype(np.int32))
    assert np.abs(got16 - want16[plen - 1:]).max() > 1e-2


def test_counts_at_published_widths(full):
    from repro.models.model import LM
    from repro.serving.engine import recurrent_state_bytes
    cfg = jamba.arch_config(full)
    assert jamba.param_count(full) == cfg.param_count() == \
        LM(cfg).param_count_actual() == 3_029_337_472
    assert roofline.param_count(full) == jamba.param_count(full)
    # every leaf once in its dtype
    shapes, _ = LM(cfg).abstract_params()
    assert jamba.weight_bytes(full) == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert jamba.state_bytes(full) == recurrent_state_bytes(cfg) == 9_318_400
    flops, nbytes = jamba.decode_step(full, [100])
    assert nbytes == (jamba.weight_bytes(full) + 2560 * 2 + 2 * 9_318_400
                      + 100 * 2 * 2 * 128 * 2)
    assert flops == 2 * (jamba.matmul_params(full) + 2560 * 65536) \
        + jamba.mixer_flops_per_token(full) + 100 * 4 * 2 * 20 * 128
    steps = roofline.decode_steps(full, {"plen": 12, "first_step": 16,
                                         "steps": 3})
    assert [s[1] - steps[0][1] for s in steps] == [0, 1024, 2048]


@pytest.mark.parametrize("tokens,bucket", [(1, 16), (16, 16), (17, 32),
                                            (46, 64), (2047, 2048)])
def test_prefill_is_charged_at_its_bucket(full, tokens, bucket):
    """The roofline charges the bucket the program ran (pads are
    computed); the MFU count charges the prompt alone, as llama's does."""
    from repro.serving.generate import bucket_for, geometric_buckets
    assert bucket_for(tokens, geometric_buckets(full["max_len"])) == bucket
    flops, nbytes = jamba.prefill_cost(full, {"tokens": tokens,
                                              "bucket": bucket})
    assert flops == jamba.prefill_flops(full, bucket)
    assert flops >= bucket * 2 * jamba.matmul_params(full)
    assert nbytes == (jamba.weight_bytes(full) + bucket * 2560 * 2
                      + bucket * 1024 + 9_318_400)
    useful = roofline.prefill(full, {"tokens": tokens})
    assert useful == jamba.prefill_flops(full, tokens)
    assert useful <= flops and (useful < flops) == (tokens < bucket)


def test_a_whole_run_on_the_cpu_is_correct(tiny):
    cell = {"name": "tiny-jamba", "config": "tiny-jamba",
            "traffic": "tiny-mix", "chips": 1}
    metrics = [{"name": "ttft_short_p50_s", "unit": "s"},
               {"name": "setup_s", "unit": "s"}]
    out = run.run_cell(cell, tiny, load("tiny-mix"), metrics, 2**33 + 5,
                       2.5, False, require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 5 and out["failed"] == 0
    assert out["diagnostics"]["compiles_in_window"] == 0


def test_a_run_with_pads_in_the_state_is_not_correct(tiny, monkeypatch):
    """The reference decides ``correct``: with the pad mask taken out of
    the mixer, bucketed prefill folds pads into the state and the served
    tokens leave the reference's."""
    from repro.models import ssm
    mix = ssm.mamba_mix

    def unmasked(cfg, p, x, conv_state=None, ssm_state=None,
                 prompt_len=None):
        return mix(cfg, p, x, conv_state, ssm_state)

    monkeypatch.setattr(ssm, "mamba_mix", unmasked)
    cell = {"name": "tiny-jamba", "config": "tiny-jamba",
            "traffic": "tiny-mix", "chips": 1}
    out = run.run_cell(cell, tiny, load("tiny-mix"),
                       [{"name": "setup_s", "unit": "s"}], 2**33 + 5, 2.5,
                       False, require_tpu=False)
    gap = out["checks"]["logit_gap"]
    assert not out["correct"] and gap["value"] > gap["limit"]


def span(name, tokens, bucket=None):
    from repro.serving.observability import Span
    args = {"tokens": tokens} if bucket is None else \
        {"tokens": tokens, "bucket": bucket}
    return Span(name, 1, 0.0, 1.0, "replica0", args)


def test_pad_share_reads_the_prefill_regions(full):
    data = run.RunData(full, [], {}, [span("prefill", 5, 16),
                                      span("prefill", 20, 32),
                                      span("decode", 3, 99)],
                       None, None, None, None)
    assert prefill_pad_share.read(data) == pytest.approx(
        100.0 * (11 + 12) / 48)
    # a program whose regions carry no bucket gives nothing
    data.spans = [span("prefill", 5)]
    assert prefill_pad_share.read(data) is None


def test_prefill_roofline_reads_marks_at_their_bucket(full):
    peak = roofline.peaks("TPU v5 lite")

    def mark(name, t0, t1, **args):
        return Interval(name, t0, t1, args)

    marks = [mark("chipbench.window", 0, 1e9),
             mark("chipbench.prefill", 1e6, 2e7, tokens=20),
             mark("chipbench.segment", 2e7, 5e7, plen=20, first_step=0,
                  steps=16),
             mark("chipbench.prefill", 6e7, 8e7, tokens=40)]
    mods = [Interval("prefill", 2e6, 1.9e7), Interval("seg", 2.1e7, 4.9e7),
            Interval("prefill", 6.1e7, 7.6e7)]
    tr = Trace({"/device:TPU:0": mods}, {"/device:TPU:0": mods}, marks)
    # the buckets come from the program's own prefill regions
    spans = [span("prefill", 20, 32), span("prefill", 40, 64),
             span("prefill", 20, 32)]
    data = run.RunData(full, [], {}, spans, None, tr, None, peak)
    shares = [100 * roofline.least_time(*jamba.prefill_cost(
        full, {"tokens": t, "bucket": b}), peak) / s
        for t, b, s in ((20, 32, 0.017), (40, 64, 0.015))]
    assert prefill_roofline.read(data) == pytest.approx(
        (shares[0] + shares[1]) / 2)
    assert all(0 < s < 100 for s in shares)
    # a mark whose prompt no region recorded is left out
    data.spans = spans[:1]
    assert prefill_roofline.read(data) == pytest.approx(shares[0])
    # nothing where the regions carry no bucket, as at the parent
    data.spans = [span("prefill", 20), span("prefill", 40)]
    assert prefill_roofline.read(data) is None
    data.spans = spans
    # nothing for a family that does not count its prefill's bytes
    llama = dict(load("smollm-360m"))
    assert prefill_roofline.read(dataclasses.replace(data, config=llama)) \
        is None
    assert prefill_roofline.read(dataclasses.replace(data, trace=None)) \
        is None


def test_the_cells_schedule_is_short_and_bursty():
    from chipbench import traffic_gen as tg
    mix = tg.load_json("traffic", "short-burst-jamba")
    sched = tg.schedule(mix, 50.0)
    assert sched == tg.schedule(copy.deepcopy(mix), 50.0)
    assert {r.klass for r in sched} == {"short"}
    assert all(1 <= r.max_tokens < 200 for r in sched)
    starts = sorted({r.offset_s for r in sched})
    assert len(sched) == 8 * len(starts)
    assert starts[1] - starts[0] == mix["arrivals"]["period_s"]
