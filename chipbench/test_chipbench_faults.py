"""A run with the chip check skipped, at a toy size on the CPU: sound, it
comes out correct; with the timed path broken underneath, it does not."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import run

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = {"name": "tiny", "config": "tiny-llama", "traffic": "tiny-mix",
        "chips": 1}
METRICS = [{"name": "ttft_short_p50_s", "unit": "s"},
           {"name": "setup_s", "unit": "s"}]


def load(name):
    with open(os.path.join(HERE, "testdata", f"{name}.json")) as f:
        return json.load(f)


def altered_token(engine):
    """The first token of every decode segment is changed as it is made."""
    dec = engine._decoder(engine.segment_len)
    seg = dec._segment
    vocab = engine.cfg.vocab_size

    def broken(*a):
        buf, *rest = seg(*a)
        buf = buf.at[0].set(jnp.where(buf[0] >= 0, (buf[0] + 1) % vocab,
                                      buf[0]))
        return (buf, *rest)

    dec._segment = broken


def state_unchanged(engine):
    """A decode segment hands back the cache it was given."""
    dec = engine._decoder(engine.segment_len)
    seg = dec._segment

    def broken(params, caches, *a):
        buf, tok, produced, _, stopped = seg(
            params, jax.tree.map(jnp.copy, caches), *a)
        return buf, tok, produced, caches, stopped

    dec._segment = broken


def one_run(fault=None, control=False):
    return run.run_cell(CELL, load("tiny-llama"), load("tiny-mix"),
                        METRICS, 2**33 + 5, 2.5, False, require_tpu=False,
                        fault=fault, control=control)


def test_sound_run_is_correct():
    out = one_run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 5 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"ttft_short_p50_s", "setup_s"}


@pytest.mark.parametrize("fault", [altered_token, state_unchanged])
def test_broken_path_is_not_correct(fault):
    out = one_run(fault)
    assert not out["correct"]
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_in_the_programs_place_is_not_correct():
    out = one_run(control=True)
    assert not out["correct"]
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    assert out["diagnostics"]["program_logit_gap"] <= gap["limit"]
