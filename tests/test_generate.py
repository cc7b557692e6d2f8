"""Fused on-device generation: bitwise equivalence against the seed
per-token loop, ring-buffer KV cache semantics, bucketed prefill, and
mid-generation cancellation (PR 3 tentpole)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import LM
from repro.serving.engine import RealEngine
from repro.serving.generate import (FusedDecoder, bucket_for,
                                    geometric_buckets)


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("smollm-360m").reduced()
    return RealEngine(cfg, max_len=96, segment_len=8)


# ---------------------------------------------------------------- equivalence

@pytest.mark.parametrize("plen", [1, 3, 8, 17, 33, 64])
def test_fused_matches_oracle_bitwise(engine, plen):
    """Fused scan decode == retained Python-loop oracle, token for token."""
    rng = np.random.default_rng(plen)
    ids = rng.integers(0, engine.cfg.vocab_size, plen)
    fused = engine.generate(ids, max_new_tokens=24)
    seed = engine.generate_reference(ids, max_new_tokens=24)
    assert fused["tokens"] == seed["tokens"]
    assert len(fused["tokens"]) == 24
    assert not fused["cancelled"]


def test_fused_eos_early_exit(engine):
    rng = np.random.default_rng(7)
    ids = rng.integers(0, engine.cfg.vocab_size, 10)
    ref = engine.generate_reference(ids, max_new_tokens=24)
    eos = ref["tokens"][5]            # a token the greedy path will emit
    fused = engine.generate(ids, max_new_tokens=24, eos_id=eos)
    seed = engine.generate_reference(ids, max_new_tokens=24, eos_id=eos)
    assert fused["tokens"] == seed["tokens"]
    assert len(fused["tokens"]) < 24
    assert fused["tokens"][-1] == eos


def test_fused_max_len_truncation(engine):
    """plen + generated never exceeds max_len, exactly like the oracle."""
    rng = np.random.default_rng(11)
    ids = rng.integers(0, engine.cfg.vocab_size, engine.max_len - 6)
    fused = engine.generate(ids, max_new_tokens=32)
    seed = engine.generate_reference(ids, max_new_tokens=32)
    assert fused["tokens"] == seed["tokens"]
    assert len(fused["tokens"]) == 6


def test_fused_single_token_budget(engine):
    rng = np.random.default_rng(13)
    ids = rng.integers(0, engine.cfg.vocab_size, 5)
    fused = engine.generate(ids, max_new_tokens=1)
    seed = engine.generate_reference(ids, max_new_tokens=1)
    assert fused["tokens"] == seed["tokens"] and len(fused["tokens"]) == 1


def test_segment_length_does_not_change_tokens(engine):
    rng = np.random.default_rng(17)
    ids = rng.integers(0, engine.cfg.vocab_size, 12)
    outs = [engine.generate(ids, max_new_tokens=20, segment_len=k)["tokens"]
            for k in (1, 4, 20)]
    assert outs[0] == outs[1] == outs[2]


# ------------------------------------------------------------------ caches

def test_fused_cache_matches_sequential_decode(engine):
    """The fused segment's final ring cache == init-from-prefill + one
    decode_step per token (the seed cache update path)."""
    cfg = engine.cfg
    rng = np.random.default_rng(3)
    ids = rng.integers(0, cfg.vocab_size, 9)
    n_new = 12

    # two prefills: the fused path donates its cache buffers.
    logits_a, caches_a, plen = engine._run_prefill(ids)
    logits_b, caches_b, _ = engine._run_prefill(ids)
    tok = int(np.argmax(np.asarray(logits_a)[0]))

    dec = FusedDecoder(engine.lm, engine.max_len, segment_len=5)
    fused = dec.decode(engine.params, caches_a, tok, plen, n_new)

    seq_tok = tok
    for _ in range(n_new - 1):
        logits_b, caches_b = engine._decode(
            engine.params, caches_b,
            {"tokens": jnp.full((1, 1), seq_tok, jnp.int32)})
        seq_tok = int(np.argmax(np.asarray(logits_b)[0]))

    for got, want in zip(jax.tree.leaves(fused["caches"]),
                         jax.tree.leaves(caches_b)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=1e-6, rtol=1e-6)
    # fill level advanced exactly n_new - 1 decode steps past the prompt
    assert int(np.asarray(fused["caches"][0]["t"])[0]) == plen + n_new - 1


def test_ring_buffer_wraps_onto_oldest_slots():
    """Past capacity S, step t lands at slot t % S and the cache holds
    exactly the S most recent tokens' KV (checked against a large cache —
    layer-1 K/V depend only on (token, position), so they must be equal)."""
    cfg = get_config("smollm-360m").reduced()   # single attn block
    lm = LM(cfg)
    params = lm.init(jax.random.key(0))
    S, T = 8, 13
    ring = lm.init_cache(1, S)
    big = lm.init_cache(1, 32)
    rng = np.random.default_rng(5)
    toks = rng.integers(0, cfg.vocab_size, T)
    step = jax.jit(lm.decode_step)
    for tok in toks:
        batch = {"tokens": jnp.full((1, 1), int(tok), jnp.int32)}
        _, ring = step(params, ring, batch)
        _, big = step(params, big, batch)

    ring_k = np.asarray(ring[0]["k"], np.float32)[0, 0]   # (S, KV, hd)
    big_k = np.asarray(big[0]["k"], np.float32)[0, 0]
    assert int(np.asarray(ring[0]["t"])[0]) == T
    for s in range(S):
        p = s + S if s + S < T else s        # latest write to this slot
        np.testing.assert_array_equal(ring_k[s], big_k[p],
                                      err_msg=f"slot {s} != position {p}")


def test_ring_decode_attends_window_only():
    """Once wrapped, the all-true mask attends exactly the live window."""
    from repro.models.attention import decode_attention
    rng = np.random.default_rng(9)
    B, S, KV, H, hd = 1, 8, 2, 4, 16
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    out_wrapped = decode_attention(q, k, v, jnp.asarray(20, jnp.int32))
    out_full = decode_attention(q, k, v, jnp.asarray(S - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(out_wrapped), np.asarray(out_full),
                               atol=1e-6)


@pytest.mark.parametrize("arch,plen,steps", [
    ("smollm-360m", 1, 9),               # slots 1..7 (S-1), wraps to 0, 1
    ("smollm-360m", 7, 2),               # the last slot S-1, then slot 0
    ("llama4-maverick-400b-a17b", 3, 6),  # two attention positions
    ("jamba-v0.1-52b", 2, 7),            # one attention among mamba blocks
])
def test_carried_ring_decode_matches_per_lane_path(arch, plen, steps):
    """Serial decode carries the stacked ring through the layer scan and
    writes one row per layer; the per-lane path (``t`` (repeats, 1))
    still emits whole rings as scan ys.  From one prefilled cache both
    give bitwise-equal logits and K/V, and the carried write touches
    slot ``t % S`` alone in every layer."""
    S = 8
    base = get_config(arch).reduced()
    cfg = dataclasses.replace(base, num_layers=3 * len(base.block_pattern))
    lm = LM(cfg)
    params = lm.init(jax.random.key(1))
    rng = np.random.default_rng(plen)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, plen), jnp.int32)
    logits, carried = lm.prefill(params, {"tokens": ids[None]}, pad_to=S)
    rings = [i for i, c in enumerate(carried) if "t" in c]
    assert rings and all(carried[i]["t"].shape == (cfg.pattern_repeats,)
                         for i in rings)
    lanes = tuple({**c, "t": c["t"][:, None]} if "t" in c else c
                  for c in carried)
    tok = int(np.argmax(np.asarray(logits)[0]))
    # only the carried path writes into the whole (repeats, B, S, ...) stack
    stack_write = "[{}] = dynamic_update_slice".format(
        ",".join(map(str, carried[rings[0]]["k"].shape)))
    batch = {"tokens": jnp.full((1, 1), tok, jnp.int32)}
    assert stack_write in str(jax.make_jaxpr(lm.decode_step)(
        params, carried, batch))
    assert stack_write not in str(jax.make_jaxpr(lm.decode_step)(
        params, lanes, batch))
    step = jax.jit(lm.decode_step)
    for _ in range(steps):
        t = int(np.asarray(carried[rings[0]]["t"])[0])
        before = [np.asarray(carried[i][kv]) for i in rings for kv in "kv"]
        batch = {"tokens": jnp.full((1, 1), tok, jnp.int32)}
        logits_c, carried = step(params, carried, batch)
        logits_l, lanes = step(params, lanes, batch)
        np.testing.assert_array_equal(np.asarray(logits_c),
                                      np.asarray(logits_l))
        after = [np.asarray(carried[i][kv]) for i in rings for kv in "kv"]
        for i in rings:
            assert carried[i]["t"].shape == (cfg.pattern_repeats,)
            np.testing.assert_array_equal(np.asarray(carried[i]["t"]), t + 1)
            for kv in "kv":
                np.testing.assert_array_equal(np.asarray(carried[i][kv]),
                                              np.asarray(lanes[i][kv]))
        others = np.arange(S) != t % S
        for old, new in zip(before, after):
            assert new.shape == old.shape and new.dtype == old.dtype
            np.testing.assert_array_equal(new[:, :, others], old[:, :, others])
            for layer in range(cfg.pattern_repeats):
                assert not np.array_equal(new[layer, :, t % S],
                                          old[layer, :, t % S])
        tok = int(np.argmax(np.asarray(logits_c)[0]))


# ----------------------------------------------------------------- bucketing

def test_geometric_buckets_cover_max_len():
    assert geometric_buckets(96) == (16, 32, 64, 96)
    assert geometric_buckets(128) == (16, 32, 64, 128)
    assert bucket_for(1, (16, 32)) == 16
    assert bucket_for(17, (16, 32)) == 32
    assert bucket_for(33, (16, 32)) == 33      # beyond last: exact (seed)


def test_bucketed_prefill_matches_exact(engine):
    """Right-padding to a bucket must not change the last-position logits
    or the cache fill level (causal attention; pads are masked dead)."""
    lm, params = engine.lm, engine.params
    rng = np.random.default_rng(21)
    for plen in (3, 17, 30):
        ids = rng.integers(0, engine.cfg.vocab_size, plen)
        exact_logits, exact_caches = lm.prefill(
            params, {"tokens": jnp.asarray(ids, jnp.int32)[None]},
            pad_to=engine.max_len)
        bucket_logits, bucket_caches, got_plen = engine._run_prefill(ids)
        assert got_plen == plen
        np.testing.assert_allclose(np.asarray(bucket_logits),
                                   np.asarray(exact_logits),
                                   atol=1e-4, rtol=1e-4)
        assert (int(np.argmax(np.asarray(bucket_logits)[0]))
                == int(np.argmax(np.asarray(exact_logits)[0])))
        assert int(np.asarray(bucket_caches[0]["t"])[0]) == plen
        assert bucket_caches[0]["k"].shape == exact_caches[0]["k"].shape


def test_bucketing_disabled_for_stateful_stacks():
    """SSM/hybrid stacks must prefill at exact length (pads would corrupt
    the recurrent state)."""
    cfg = get_config("jamba-v0.1-52b").reduced()
    eng = RealEngine(cfg, max_len=64)
    assert not eng._bucketing and eng.buckets == ()
    out = eng.generate(np.arange(7) % cfg.vocab_size, max_new_tokens=4)
    assert len(out["tokens"]) == 4


# -------------------------------------------------------------- cancellation

def test_mid_generation_cancellation(engine):
    """§3.4 drain: the cancel flag stops the fused loop at the next segment
    boundary with the tokens generated so far."""
    rng = np.random.default_rng(23)
    ids = rng.integers(0, engine.cfg.vocab_size, 12)
    calls = {"n": 0}

    def cancel_after_two_segments():
        calls["n"] += 1
        return calls["n"] > 2

    out = engine.generate(ids, max_new_tokens=64,
                          cancel_cb=cancel_after_two_segments)
    assert out["cancelled"]
    # prefill token + exactly two full segments
    assert len(out["tokens"]) == 1 + 2 * engine.segment_len
    assert out["segments"] == 2
    # the engine flag is consumed: the next request decodes normally
    out2 = engine.generate(ids, max_new_tokens=8)
    assert not out2["cancelled"] and len(out2["tokens"]) == 8


def test_request_cancel_flag(engine):
    """A disconnect arriving mid-flight (request_cancel) is observed at the
    next segment boundary."""
    rng = np.random.default_rng(29)
    ids = rng.integers(0, engine.cfg.vocab_size, 6)
    state = {"n": 0}

    def cb():                      # fires while segment 1 is about to launch
        state["n"] += 1
        if state["n"] == 1:
            engine.request_cancel()
        return False

    out = engine.generate(ids, max_new_tokens=64, cancel_cb=cb)
    assert out["cancelled"]
    assert len(out["tokens"]) == 1 + engine.segment_len
    assert out["segments"] == 1
