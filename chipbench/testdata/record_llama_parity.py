"""Record ``llama_parity.json``: what the harness gives for llama configs.

    JAX_PLATFORMS=cpu python3 chipbench/testdata/record_llama_parity.py

The file holds, for the llama family (``chipbench/families/llama.py``):
a digest of every served leaf of ``tiny-llama.json`` at one seed, the
reference's gaps and its float8 control's gaps over two sequences of that
configuration, the roofline counts of ``smollm-360m.json`` and
``granite-8b-s9.json`` for a few marks' args, and what the
``decode_step_roofline`` and ``step_mfu`` readers read from
``cpu_trace.xplane.pb`` for those two.  The values were recorded from the
harness before its llama code moved behind the family seam;
``test_chipbench_families.py`` holds the current code to them, bit for
bit.
"""

import hashlib
import json
import os
import sys

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

SEED = 2**33 + 7
#: args of ``chipbench.segment`` marks: a first segment, one late in a
#: 2048-slot window, an empty one, and a short last one
SEGMENTS = [{"plen": 12, "first_step": 0, "steps": 16},
            {"plen": 40, "first_step": 1008, "steps": 16},
            {"plen": 3, "first_step": 32, "steps": 0},
            {"plen": 68, "first_step": 1904, "steps": 7}]
PREFILLS = [1, 16, 68, 2047]
COUNTED = ("smollm-360m", "granite-8b-s9")


def config(name: str) -> dict:
    for d in (HERE, os.path.join(HERE, "..", "configs")):
        path = os.path.join(d, f"{name}.json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise FileNotFoundError(name)


def sequences() -> list:
    """Two (prompt, served) pairs of tiny-llama ids; the second crosses
    the reference's 128-row padding."""
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 512, 185).astype(np.int32)
    return [(ids[:10], ids[10:40]), (ids[40:45], ids[45:185])]


def digest(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        out[jax.tree_util.keystr(path)] = [
            list(a.shape), str(a.dtype),
            hashlib.sha256(a.tobytes()).hexdigest()]
    return out


def record() -> dict:
    from chipbench import families, reference, roofline, trace_reduce
    from chipbench.metrics import decode_step_roofline, step_mfu
    from chipbench.run import RunData

    tiny = config("tiny-llama")
    out = {"seed": SEED,
           "weights": digest(families.of(tiny).make_params(tiny, SEED))}
    gaps, ctrl = reference.served_gaps(tiny, SEED, sequences(), control=True)
    out["gaps"] = [[float(v) for v in g] for g in gaps]
    out["control_gaps"] = [[float(v) for v in g] for g in ctrl]
    tr = trace_reduce.read(os.path.join(HERE, "cpu_trace.xplane.pb"))
    reduced = trace_reduce.reduce(tr)
    peak = roofline.peaks("TPU v5 lite")
    out["roofline"], out["readers"] = {}, {}
    for name in COUNTED:
        c = config(name)
        out["roofline"][name] = {
            "param_count": roofline.param_count(c),
            "segments": [roofline.decode_steps(c, a) for a in SEGMENTS],
            "prefills": [roofline.prefill(c, {"tokens": n})
                         for n in PREFILLS]}
        run = RunData(c, [], {}, [], None, tr, reduced, peak)
        out["readers"][name] = {"decode_step_roofline":
                                decode_step_roofline.read(run),
                                "step_mfu": step_mfu.read(run)}
    return out


def main() -> None:
    with open(os.path.join(HERE, "llama_parity.json"), "w") as f:
        json.dump(record(), f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(os.path.dirname(HERE))]
    main()
