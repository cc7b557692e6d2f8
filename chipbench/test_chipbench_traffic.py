"""Traffic generation: determinism from the seed, and the copy of the
corpus profile against the program's own generator."""

import numpy as np
import pytest

from chipbench import traffic_gen as tg

MIXES = ("oasst1-poisson", "oasst1-burst", "short-poisson-granite",
         "short-poisson-smollm")


@pytest.mark.parametrize("name", MIXES)
def test_schedule_is_a_function_of_the_seed(name):
    # every --seed gets the mix's one schedule; only pool_seed moves it
    mix = tg.load_json("traffic", name)
    a = tg.schedule(mix, 50.0)
    assert a == tg.schedule(mix, 50.0)
    other = dict(mix, pool_seed=mix["pool_seed"] + 1)
    assert a != tg.schedule(other, 50.0)
    assert all(0.0 <= r.offset_s < 50.0 for r in a)
    assert [r.offset_s for r in a] == sorted(r.offset_s for r in a)
    assert all(r.klass in mix["classes"] for r in a)
    assert all(1 <= r.max_tokens <= mix["max_tokens_cap"] for r in a)


def test_poisson_rate_and_burst_shape():
    offs = tg.arrival_offsets({"kind": "poisson", "rate_per_s": 2.0},
                              2000.0, 5)
    assert abs(len(offs) / 2000.0 - 2.0) < 0.15
    assert np.all(np.diff(offs) >= 0)
    burst = tg.arrival_offsets({"kind": "burst", "size": 20,
                                "period_s": 10.0}, 35.0, 0)
    assert list(burst) == [0.0] * 20 + [10.0] * 20 + [20.0] * 20 \
        + [30.0] * 20


def test_class_counts_split_exactly():
    counts = tg.class_counts(101, np.array([0.83, 0.107, 0.063]))
    assert counts.sum() == 101
    assert list(counts) == [84, 11, 6]


def test_pool_matches_the_program_corpus_within_sampling_error():
    from repro.data.corpus import sample_dataset
    mix = {"profile": "oasst1", "classes": list(tg.CLASSES),
           "pool_seed": 9}
    prof = tg.load_json("profiles", "oasst1")
    n = 4000
    ours = tg.pool(mix, n)
    theirs = sample_dataset("oasst1", n, seed=10)
    share = np.array([sum(k == c for _, _, k in ours) for c in tg.CLASSES])
    assert np.allclose(share / n, tg.class_probs(prof, tg.CLASSES),
                       atol=1.0 / n)
    for i, c in enumerate(tg.CLASSES):
        a = np.array([ln for _, ln, k in ours if k == c], float)
        b = theirs.lengths[theirs.classes == i].astype(float)
        se = np.sqrt(a.var() / len(a) + b.var() / len(b))
        assert abs(a.mean() - b.mean()) < 4 * se, (c, a.mean(), b.mean())
        pa = np.array([tg.prompt_tokens(p) for p, _, k in ours if k == c])
        pb = np.array([len(p.split()) for p, k in
                       zip(theirs.prompts, theirs.classes) if k == i])
        se = np.sqrt(pa.var() / len(pa) + pb.var() / len(pb))
        assert abs(pa.mean() - pb.mean()) < 4 * se, (c, pa.mean(),
                                                      pb.mean())


def test_short_class_is_under_200_tokens():
    mix = tg.load_json("traffic", "short-poisson-smollm")
    assert all(r.max_tokens < 200 and r.klass == "short"
               for r in tg.schedule(mix, 50.0))
