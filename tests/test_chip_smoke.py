"""chip_smoke.py off the chip: its phases at reduced width on the CPU, its
token-check rule, and its refusal to run without a TPU."""

import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # dataclasses resolve annotations
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    """CHIP_PLAN's shape at reduced width: same request mix, two prefill
    buckets, a shared prefix of whole pages."""
    return smoke.Plan(
        arch="smollm-360m-reduced", max_len=128, seed=0,
        sidecar=(("short-a", 6, 8, True), ("short-a", 6, 8, False),
                 ("short-b", 10, 8, False), ("short-c", 13, 8, False),
                 ("long-a", 40, 24, True), ("long-a", 40, 24, False),
                 ("long-b", 50, 40, False), ("long-c", 60, 32, False)),
        prefix=32, page_size=8, lanes=4,
        waves=((("prefix-a", 36, 12), ("p-short-a", 7, 8),
                ("p-short-b", 11, 8), ("p-long-a", 50, 24)),
               (("prefix-b", 40, 16), ("p-short-c", 5, 8),
                ("p-long-b", 45, 20), ("p-short-d", 12, 4))))


@pytest.fixture(scope="module")
def sidecar_run(smoke, tiny):
    with smoke.donation_fails():
        return smoke.sidecar_phase(tiny)


def test_sidecar_phase_reduced(sidecar_run, tiny):
    report, engine, predictor = sidecar_run
    assert report["requests"] == len(tiny.sidecar)
    assert engine.cfg.name == "smollm-360m-reduced"
    assert engine.max_len == tiny.max_len
    assert predictor is not None


def test_paged_phase_reduced(smoke, tiny, sidecar_run):
    _, engine, predictor = sidecar_run
    with smoke.donation_fails():
        report = smoke.paged_phase(tiny, engine, predictor)
    assert report["requests"] == sum(len(w) for w in tiny.waves)
    assert report["prefix_hit_pages"] > 0


def test_check_tokens_rule(smoke):
    """Equal tokens pass; a difference passes only where the oracle's
    top-2 gap at the first differing step is below the larger of bf16
    resolution and twice the measured logit noise."""
    ref = {"tokens": [5, 6, 7, 8],
           "top2": [(4.0, 3.0), (4.0, 3.99), (4.0, 3.0), (4.0, 3.0)]}
    assert "bitwise" in smoke.check_tokens("r", [5, 6, 7, 8], ref)
    assert "near-tie" in smoke.check_tokens("r", [5, 9, 9, 9], ref)
    with pytest.raises(AssertionError, match="away from a near-tie"):
        smoke.check_tokens("r", [5, 6, 9, 9], ref)   # tie before, not at
    assert "near-tie" in smoke.check_tokens("r", [5, 6, 9, 9], ref,
                                            noise=0.6)
    with pytest.raises(AssertionError, match="away from a near-tie"):
        smoke.check_tokens("r", [9, 6, 7, 8], ref, noise=0.4)
    with pytest.raises(AssertionError, match="tokens, oracle"):
        smoke.check_tokens("r", [5, 6, 7], ref)
    assert smoke.first_divergence([5, 6], [5, 6]) is None
    assert smoke.first_divergence([5, 9, 7], [5, 6, 7]) == 1
    assert smoke.first_divergence([5, 6], [5, 6, 7]) == 2
    assert smoke.bf16_resolution(4.0) == 2.0 ** -5
    assert smoke.bf16_resolution(-0.75) == 2.0 ** -8


def test_logit_noise_reduced(smoke, tiny, sidecar_run):
    """One-row against four-row programs, teacher-forced: float32 on the
    CPU leaves (almost) nothing between them."""
    import numpy as np
    _, engine, _ = sidecar_run
    ids = np.arange(1, 20, dtype=np.int32)
    ref = engine.generate_reference(ids, max_new_tokens=12)
    noise = smoke.logit_noise(engine, ids, ref["tokens"], tiny.lanes)
    assert 0.0 <= noise < 1e-4


def test_main_refuses_off_chip(smoke, monkeypatch, capsys):
    def built(*_):
        raise AssertionError("a phase ran off the chip")
    monkeypatch.setattr(smoke, "sidecar_phase", built)
    monkeypatch.setattr(smoke, "paged_phase", built)
    assert smoke.main() != 0
    out, err = capsys.readouterr()
    assert '"ok": true' not in out
    assert "not a TPU" in err


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(from_env, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX and nothing
    else is set; otherwise the cache goes to the fixed repo-root path."""
    import jax

    from repro.launch import compile_cache
    assert compile_cache.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    default = tmp_path / "default"
    monkeypatch.setattr(compile_cache, "DEFAULT_CACHE_DIR", default)
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.setup_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    if from_env:
        assert (got, now) == (str(tmp_path), was)
        assert not default.exists()
    else:
        assert got == now == str(default)
        assert default.is_dir()


def test_script_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
