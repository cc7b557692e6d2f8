"""The published Mamba-1 mixer, bucketed recurrent prefill and attention
without rotary positions, on a tiny float32 Jamba (pattern mamba, attn,
mamba) with seeded random weights.

Tolerances.  Two float32 computations of the same quantity that differ
only in summation order (the chunked associative scan against a
sequential recurrence, a padded bucket against the exact length) agree
to about 1e-6 here, on outputs of up to about 6; ``ATOL`` allows for
that.  Running the mixer in bfloat16 moves its outputs by about 5e-2
(the first test shows that it fails ``ATOL``), so a bfloat16 path fails
each comparison here.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import ssm
from repro.models.attention import _project_qkv
from repro.models.model import LM
from repro.serving.engine import RealEngine, recurrent_state_bytes

ATOL = 1e-4
MAX_LEN = 64              # buckets 16, 32, 64


def tiny(dtype="float32", **kw):
    return dataclasses.replace(
        get_config("jamba2-3b").reduced(), num_layers=6,
        block_pattern=("mamba", "attn", "mamba"), dtype=dtype, **kw)


@pytest.fixture(scope="module")
def cfg():
    return tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return LM(cfg).init(jax.random.key(3))


def mixer_params(cfg, key=0):
    return ssm.init_mamba(cfg, jax.random.key(key))[0]


def sequential_mixer(cfg, p, x):
    """The published mixer as a plain per-token loop in float32."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)[0]
    S, di = x.shape[0], cfg.d_inner
    r, n, w = cfg.dt_rank, cfg.ssm_state_dim, cfg.ssm_conv_width

    def rms(v, g):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True)
                           + cfg.norm_eps) * g

    uz = x @ p["in_proj"]
    u, z = uz[:, :di], uz[:, di:]
    up = np.concatenate([np.zeros((w - 1, di)), u])
    a = -np.exp(p["A_log"])
    s = np.zeros((di, n))
    ys = []
    for t in range(S):
        uc = sum(up[t + i] * p["conv_w"][i] for i in range(w)) + p["conv_b"]
        uc = uc / (1 + np.exp(-uc))
        proj = uc @ p["x_proj"]
        dt = rms(proj[:r], p["dt_norm"]) @ p["dt_proj"] + p["dt_bias"]
        delta = np.log1p(np.exp(dt))
        bm, cm = rms(proj[r:r + n], p["B_norm"]), rms(proj[r + n:],
                                                      p["C_norm"])
        s = np.exp(delta[:, None] * a) * s + (delta * uc)[:, None] * bm
        y = s @ cm + p["D"] * uc
        ys.append(y * z[t] / (1 + np.exp(-z[t])))
    return np.stack(ys)[None], s


def test_mixer_matches_a_sequential_recurrence(cfg, monkeypatch):
    """Several chunks of the associative scan (CHUNK 8 over 21 tokens,
    the last chunk padded) against the per-token loop."""
    monkeypatch.setattr(ssm, "CHUNK", 8)
    p = mixer_params(cfg)
    x = jax.random.normal(jax.random.key(1), (1, 21, cfg.d_model))
    y, (_, state) = ssm.mamba_mix(cfg, p, x)
    want_y, want_s = sequential_mixer(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=ATOL)
    np.testing.assert_allclose(np.asarray(state), want_s[None], atol=ATOL)
    # the published pieces matter: no state carries over without dt_proj
    p_flat = dict(p, dt_proj=jnp.zeros_like(p["dt_proj"]))
    y_flat, _ = ssm.mamba_mix(cfg, p_flat, x)
    assert np.abs(np.asarray(y_flat) - want_y).max() > 100 * ATOL
    # and a bfloat16 mixer does not pass this tolerance
    c16 = dataclasses.replace(cfg, dtype="bfloat16")
    p16 = {k: v if k in ("dt_bias", "A_log", "D") else
           v.astype(jnp.bfloat16) for k, v in p.items()}
    y16, _ = ssm.mamba_mix(c16, p16, x.astype(jnp.bfloat16))
    assert np.abs(np.asarray(y16, np.float32) - want_y).max() > ATOL


def test_decode_steps_continue_the_scan(cfg):
    """A prefill of 9 tokens then 5 single-token steps (the recurrence's
    step, with the state in float32) give the full sequence's outputs."""
    p = mixer_params(cfg, 2)
    x = jax.random.normal(jax.random.key(4), (1, 14, cfg.d_model))
    y_all, (conv_all, s_all) = ssm.mamba_mix(cfg, p, x)
    ys, (conv, s) = ssm.mamba_mix(cfg, p, x[:, :9])
    ys = [ys]
    for t in range(9, 14):
        y, (conv, s) = ssm.mamba_mix(cfg, p, x[:, t:t + 1],
                                     conv_state=conv, ssm_state=s)
        assert s.dtype == jnp.float32
        ys.append(y)
    np.testing.assert_allclose(np.concatenate(ys, 1), np.asarray(y_all),
                               atol=ATOL)
    np.testing.assert_allclose(np.asarray(conv), np.asarray(conv_all))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_all), atol=ATOL)


@pytest.mark.parametrize("plen", [5, 17, 30, 33])
def test_bucketed_prefill_matches_exact_length(cfg, params, plen):
    """Prompt lengths between buckets: the padded prefill's logits and
    every cache (Mamba conv and SSM state, attention K/V rows and fill
    level) equal the exact-length prefill's."""
    eng = RealEngine(cfg, params=params, max_len=MAX_LEN)
    assert eng.buckets == (16, 32, 64)
    ids = np.random.default_rng(plen).integers(0, cfg.vocab_size, plen)
    exact, ecache = eng.lm.prefill(
        params, {"tokens": jnp.asarray(ids, jnp.int32)[None]},
        pad_to=MAX_LEN)
    got, gcache, _ = eng._run_prefill(ids)
    assert eng.prefill_pad_tokens == eng.bucket(plen) - plen
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                               atol=ATOL)
    for kind, g, e in zip(cfg.block_pattern, gcache, ecache):
        if kind == "mamba":
            np.testing.assert_allclose(np.asarray(g["conv"]),
                                       np.asarray(e["conv"]), atol=ATOL)
            np.testing.assert_allclose(np.asarray(g["ssm"]),
                                       np.asarray(e["ssm"]), atol=ATOL)
        else:
            assert np.asarray(g["t"]).tolist() == [plen] * 2
            np.testing.assert_allclose(np.asarray(g["k"])[:, :, :plen],
                                       np.asarray(e["k"])[:, :, :plen],
                                       atol=ATOL)


def test_pads_do_not_reach_the_state(cfg):
    """The recurrent state after a padded prefill does not depend on what
    the pads hold, and equals the exact-length state; the same padded
    input with the pads left in the recurrence (no ``prompt_len``) moves
    the state far past the tolerance, so the masking is what holds it."""
    p = mixer_params(cfg, 5)
    plen, bucket = 11, 16
    x = jax.random.normal(jax.random.key(6), (1, bucket, cfg.d_model))
    other = x.at[:, plen:].set(
        jax.random.normal(jax.random.key(7), (1, bucket - plen, cfg.d_model)))
    _, (conv, s) = ssm.mamba_mix(cfg, p, x[:, :plen])
    for padded in (x, other):
        _, (pconv, ps) = ssm.mamba_mix(cfg, p, padded, prompt_len=plen)
        np.testing.assert_allclose(np.asarray(ps), np.asarray(s), atol=ATOL)
        np.testing.assert_array_equal(np.asarray(pconv), np.asarray(conv))
    _, (_, leaked) = ssm.mamba_mix(cfg, p, other)
    assert np.abs(np.asarray(leaked) - np.asarray(s)).max() > 100 * ATOL
    # per-row prompt lengths (a lane group's prefill)
    rows = jnp.concatenate([x, other])
    _, (rconv, rs) = ssm.mamba_mix(cfg, p, rows,
                                   prompt_len=jnp.array([plen, plen - 3]))
    _, (conv3, s3) = ssm.mamba_mix(cfg, p, other[:, :plen - 3])
    np.testing.assert_allclose(np.asarray(rs), np.concatenate([s, s3]),
                               atol=ATOL)
    np.testing.assert_array_equal(np.asarray(rconv),
                                  np.concatenate([conv, conv3]))


def test_served_tokens_match_teacher_forced_logits(cfg, params):
    """Bucketed prefill then fused decode serves the argmax of the
    model's own full forward at every position."""
    eng = RealEngine(cfg, params=params, max_len=MAX_LEN, segment_len=4)
    ids = np.random.default_rng(8).integers(0, cfg.vocab_size, 13)
    out = eng.generate(ids, max_new_tokens=11)
    seq = np.concatenate([ids, out["tokens"]]).astype(np.int32)
    logits, _ = eng.lm.forward(params, {"tokens": jnp.asarray(seq)[None]},
                               remat=False)
    rows = np.asarray(logits)[0, len(ids) - 1:len(seq) - 1]
    gap = rows.max(-1) - rows[np.arange(len(rows)), out["tokens"]]
    assert gap.max() < ATOL
    assert out["tokens"] == eng.generate_reference(ids, 11)["tokens"]


def test_lane_groups_match_serial_runs(cfg, params):
    """Mixed prompt lengths in one padded lane prefill (per-row
    ``prompt_len``) serve what serial runs serve."""
    from repro.serving.engine import BatchedRealEngine
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (3, 9, 14, 20)]
    serial = RealEngine(cfg, params=params, max_len=MAX_LEN, segment_len=4)
    want = [serial.generate(p, max_new_tokens=6)["tokens"] for p in prompts]
    lanes = BatchedRealEngine(cfg, params=params, max_len=MAX_LEN,
                              segment_len=4, n_lanes=4)
    got = lanes.generate_batch(prompts, max_new_tokens=6)
    assert [g["tokens"] for g in got] == want


def test_engine_counts_pads_and_state(cfg, params):
    from repro.serving.observability import Observability, parse_prometheus
    eng = RealEngine(cfg, params=params, max_len=MAX_LEN)
    di, n, w = cfg.d_inner, cfg.ssm_state_dim, cfg.ssm_conv_width
    per = di * n * 4 + (w - 1) * di * 4             # float32 here
    assert eng.recurrent_state_bytes == 4 * per     # 4 mixers
    full = get_config("jamba2-3b")
    assert recurrent_state_bytes(full) == 26 * 5120 * (16 * 4 + 3 * 2)
    assert recurrent_state_bytes(get_config("smollm-360m")) == 0
    eng.generate(np.arange(5) % cfg.vocab_size, max_new_tokens=2)
    eng.generate(np.arange(20) % cfg.vocab_size, max_new_tokens=2)
    st = eng.engine_stats()
    assert st["prefill_pad_tokens"] == (16 - 5) + (32 - 20)
    obs = Observability.default(tracing=False)
    obs.register_engines([eng])
    got = parse_prometheus(obs.render_metrics())
    assert got["clairvoyant_prefill_pad_tokens_total"][0][2] == 23
    assert got["clairvoyant_recurrent_state_bytes"][0][2] == 4 * per


def test_prefill_region_carries_its_bucket(cfg, params):
    from repro.serving.observability import FlightRecorder
    eng = RealEngine(cfg, params=params, max_len=MAX_LEN)
    eng.recorder = FlightRecorder()
    eng.generate(np.arange(7) % cfg.vocab_size, max_new_tokens=2, req_id=1)
    pre = [s for s in eng.recorder.spans() if s.name == "prefill"]
    assert [s.args for s in pre] == [{"tokens": 7, "bucket": 16}]


def test_recurrent_stacks_still_refused_by_paging_and_speculation(cfg):
    from repro.serving.engine import PagedBatchedEngine
    with pytest.raises(ValueError, match="pure-attention"):
        PagedBatchedEngine(cfg, max_len=MAX_LEN, page_size=16)
    draft = get_config("smollm-360m").reduced()
    with pytest.raises(ValueError, match="pure-attention"):
        RealEngine(cfg, max_len=MAX_LEN, draft_cfg=draft, draft_k=2)
    with pytest.raises(ValueError, match="draft model needs"):
        RealEngine(draft, max_len=MAX_LEN, draft_cfg=cfg, draft_k=2)


def test_attention_without_rotary_positions():
    """``use_rope=False`` leaves q and k as their projections, and a
    pure-attention model without positions gives the last position the
    same logits for any order of the tokens before it."""
    base = get_config("smollm-360m").reduced()
    x = jax.random.normal(jax.random.key(0), (1, 6, base.d_model))
    lm = LM(base)
    p = lm.init(jax.random.key(1))
    blk = jax.tree.map(lambda a: a[0], p["blocks"][0]["attn"])
    pos = jnp.arange(6)
    q0, k0, _ = _project_qkv(dataclasses.replace(base, use_rope=False),
                             blk, x, pos)
    q1, k1, _ = _project_qkv(base, blk, x, pos)
    np.testing.assert_allclose(
        np.asarray(q0).reshape(1, 6, -1), np.asarray(x @ blk["wq"]),
        rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(q1) - np.asarray(q0)).max() > 0.1
    ids = jnp.array([[5, 9, 40, 77, 3, 12]], jnp.int32)
    perm = jnp.array([[40, 5, 77, 9, 3, 12]], jnp.int32)

    def last(c, t):
        return np.asarray(LM(c).forward(p, {"tokens": t}, remat=False)[0])[
            0, -1]

    nope = dataclasses.replace(base, use_rope=False)
    np.testing.assert_allclose(last(nope, ids), last(nope, perm), atol=ATOL)
    assert np.abs(last(base, ids) - last(base, perm)).max() > 100 * ATOL


@pytest.mark.parametrize("name", ["jamba2-3b", "jamba-v0.1-52b"])
def test_param_count_counts_the_published_mixer(name):
    full = get_config(name)
    assert full.param_count() == LM(full).param_count_actual()
    red = full.reduced()
    assert red.param_count() == LM(red).param_count_actual()
    di, r, n = full.d_inner, full.dt_rank, full.ssm_state_dim
    assert r == {"jamba2-3b": 160, "jamba-v0.1-52b": 256}[name]
    shapes, _ = LM(full).abstract_params()
    mix = shapes["blocks"][0]["mamba"]
    assert mix["x_proj"].shape[1:] == (di, r + 2 * n)
    assert mix["dt_proj"].shape[1:] == (r, di)
    assert mix["conv_b"].shape[1:] == (di,)
    if name == "jamba2-3b":
        assert full.param_count() == 3_029_337_472


def test_the_sidecar_builds_the_registered_arch():
    """``launch/sidecar.py --backend real --arch jamba2-3b`` finds the
    config by name; its reduced sibling is built here, with bucketing."""
    from repro.launch.sidecar import build_parser, build_sidecar
    args = build_parser().parse_args(
        ["--backend", "real", "--arch", "jamba2-3b-reduced", "--max-len",
         "64", "--no-predictor", "--policy", "fcfs"])
    sidecar = build_sidecar(args)
    eng = sidecar.server.engines[0].engine
    assert eng.cfg.block_pattern == get_config("jamba2-3b").block_pattern
    assert not eng.cfg.use_rope and eng.buckets == (16, 32, 64)
    assert sidecar.server.engines[0].engine_stats()[
        "recurrent_state_bytes"] == recurrent_state_bytes(eng.cfg) > 0
