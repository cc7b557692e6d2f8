"""The family seam (``chipbench/families/``): llama's readings as recorded
before its code moved behind the seam (``testdata/llama_parity.json``,
made by ``testdata/record_llama_parity.py``), an architecture with no
module, and a second, test-only family through a whole run on the CPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import families, reference, roofline, run, trace_reduce
from chipbench.metrics import decode_step_roofline, step_mfu
from chipbench.testdata import record_llama_parity as rec
from chipbench.testdata import twin_family

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CELL = {"name": "tiny-twin", "config": "tiny-twin", "traffic": "tiny-mix",
        "chips": 1}
METRICS = [{"name": "ttft_short_p50_s", "unit": "s"},
           {"name": "setup_s", "unit": "s"}]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "testdata", "llama_parity.json")) as f:
        return json.load(f)


def test_llama_weights_equal_the_recorded(recorded):
    c = rec.config("tiny-llama")
    assert families.of(c).__name__ == "chipbench.families.llama"
    params = families.of(c).make_params(c, recorded["seed"])
    assert rec.digest(params) == recorded["weights"]


def test_llama_reference_gaps_equal_the_recorded(recorded):
    c = rec.config("tiny-llama")
    gaps, ctrl = reference.served_gaps(c, recorded["seed"], rec.sequences(),
                                       control=True)
    assert [[float(v) for v in g] for g in gaps] == recorded["gaps"]
    assert [[float(v) for v in g] for g in ctrl] == recorded["control_gaps"]


@pytest.mark.parametrize("name", rec.COUNTED)
def test_llama_roofline_counts_equal_the_recorded(recorded, name):
    c, want = rec.config(name), recorded["roofline"][name]
    assert roofline.param_count(c) == want["param_count"]
    assert [[list(s) for s in roofline.decode_steps(c, a)]
            for a in rec.SEGMENTS] == want["segments"]
    assert [roofline.prefill(c, {"tokens": n})
            for n in rec.PREFILLS] == want["prefills"]


@pytest.mark.parametrize("name", rec.COUNTED)
def test_roofline_readers_equal_the_recorded(recorded, name):
    tr = trace_reduce.read(os.path.join(HERE, "testdata",
                                        "cpu_trace.xplane.pb"))
    data = run.RunData(rec.config(name), [], {}, [], None, tr,
                       trace_reduce.reduce(tr),
                       roofline.peaks("TPU v5 lite"))
    assert decode_step_roofline.read(data) == \
        recorded["readers"][name]["decode_step_roofline"]
    assert step_mfu.read(data) == recorded["readers"][name]["step_mfu"]


def test_the_module_is_named_from_the_architecture():
    assert families.module_name(
        {"architectures": ["JambaForCausalLM"]}) == "chipbench.families.jamba"


def test_an_architecture_with_no_module_fails_with_its_name():
    c = dict(rec.config("tiny-llama"), architectures=["NoSuchForCausalLM"])
    with pytest.raises(families.UnknownArchitecture,
                       match="chipbench/families/nosuch.py"):
        families.of(c)
    with pytest.raises(families.UnknownArchitecture,
                       match="chipbench/families/nosuch.py"):
        run.run_cell(CELL, c, rec.config("tiny-mix"), METRICS, 1, 1.0,
                     False, require_tpu=False)


def test_the_command_exits_nonzero_naming_the_missing_module(tmp_path):
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    conf = dict(rec.config("tiny-llama"), name="tiny-nosuch",
                architectures=["NoSuchForCausalLM"])
    with open(tmp_path / "chipbench" / "configs" / "tiny-nosuch.json",
              "w") as f:
        json.dump(conf, f)
    bench["configs"].append({"name": "tiny-nosuch", "source": "test",
                             "file": "chipbench/configs/tiny-nosuch.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-nosuch.poisson",
                               "config": "tiny-nosuch",
                               "traffic": "oasst1-poisson", "chips": 1,
                               "why": "test"})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "tiny-nosuch.poisson", "--seed", "3000000001", "--seconds", "5",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert p.returncode != 0
    assert "chipbench/families/nosuch.py" in p.stderr
    assert p.stdout.strip() == ""


@pytest.fixture
def twin(monkeypatch):
    monkeypatch.setitem(sys.modules, "chipbench.families.twin", twin_family)
    monkeypatch.setattr(twin_family, "CALLS", {})
    return twin_family


def twin_run(broken_layer=None):
    twin_family.BROKEN_LAYER = broken_layer
    try:
        return run.run_cell(CELL, rec.config("tiny-twin"),
                            rec.config("tiny-mix"), METRICS, 2**33 + 5, 2.5,
                            False, require_tpu=False)
    finally:
        twin_family.BROKEN_LAYER = None


def test_a_second_family_runs_the_whole_cpu_path(twin):
    c = rec.config("tiny-twin")
    assert families.of(c) is twin
    out = twin_run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 5 and out["failed"] == 0
    layers = c["num_hidden_layers"]
    assert twin.CALLS["arch_config"] == 1
    assert twin.CALLS["make_params"] == 1
    assert twin.CALLS["reference_weights"] == layers
    assert twin.CALLS["reference_layer"] >= layers
    assert roofline.param_count(c) == roofline.param_count(
        rec.config("tiny-llama"))
    assert twin.CALLS["param_count"] == 1


def test_a_second_familys_reference_decides_correct(twin):
    out = twin_run(broken_layer=1)
    assert not out["correct"]
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
