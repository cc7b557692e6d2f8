"""Compile the served path for a TPU v5e at smollm-360m's published widths.

Nothing here runs on a chip: the TPU compiler targets a described v5e
topology, and each program must compile and fit one chip's 16 GB.  Covers
the three Pallas attention kernels at smollm widths (head_dim 64, GQA
group 3), the bucket-512 prefill, a ``FusedDecoder`` segment and a 4-lane
``PagedLaneDecoder`` segment, all over a 2048-token window in bf16.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models.model import LM

MAX_LEN = 2048
PREFILL_BUCKET = 512
SEGMENT_LEN = 16
LANES = 4
PAGE = 16
HBM_BYTES = 16e9                 # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def lm():
    return LM(get_config("smollm-360m"))


def _spec(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, (total, m)
    return total


def _scalar(dtype, sharding, shape=()):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kernel", ["flash", "decode", "paged_decode"])
def test_attention_kernel_compiles(kernel, one_chip, lm):
    from repro.kernels.decode_attention import (
        decode_attention_kernel, paged_decode_attention_kernel)
    from repro.kernels.flash_attention import flash_attention_kernel
    cfg = lm.cfg
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // KV
    bf16 = jnp.bfloat16

    def arr(*shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if kernel == "flash":
        fn = jax.jit(lambda q, k, v: flash_attention_kernel(q, k, v))
        args = (arr(1, H, PREFILL_BUCKET, hd), arr(1, KV, PREFILL_BUCKET, hd),
                arr(1, KV, PREFILL_BUCKET, hd))
    elif kernel == "decode":
        fn = jax.jit(decode_attention_kernel)
        args = (arr(1, KV, G, hd), arr(1, KV, MAX_LEN, hd),
                arr(1, KV, MAX_LEN, hd), arr(dtype=jnp.int32))
    else:
        n_pages = LANES * MAX_LEN // PAGE + 1
        fn = jax.jit(paged_decode_attention_kernel)
        args = (arr(LANES, KV, G, hd), arr(n_pages, KV, PAGE, hd),
                arr(n_pages, KV, PAGE, hd),
                arr(LANES, MAX_LEN // PAGE, dtype=jnp.int32),
                arr(LANES, dtype=jnp.int32))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_prefill_compiles(one_chip, lm):
    params = _spec(lm.abstract_params()[0], one_chip)
    prefill = jax.jit(lambda p, toks, plen: lm.prefill(
        p, {"tokens": toks}, pad_to=MAX_LEN, prompt_len=plen))
    compiled = prefill.lower(
        params, _scalar(jnp.int32, one_chip, (1, PREFILL_BUCKET)),
        _scalar(jnp.int32, one_chip)).compile()
    _fits(compiled)


@pytest.fixture(scope="module")
def fused_segment(one_chip, lm):
    from repro.serving.generate import FusedDecoder
    params = _spec(lm.abstract_params()[0], one_chip)
    caches = _spec(jax.eval_shape(lambda: lm.init_cache(1, MAX_LEN)),
                   one_chip)
    i32 = _scalar(jnp.int32, one_chip)
    dec = FusedDecoder(lm, MAX_LEN, SEGMENT_LEN)
    return dec._segment.lower(params, caches, i32, i32, i32, i32,
                              i32).compile()


def test_fused_segment_compiles(fused_segment):
    _fits(fused_segment)


_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = \w+\[([\d,]*)\]"
                        r"(?:\{[^}]*\})?\s+([\w\-]+)\(([^)]*)\)")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{\s*$")


def test_fused_segment_writes_one_row_per_layer(fused_segment, lm):
    """Serial decode carries the stacked K/V ring through the layer scan:
    each layer writes its one new row into the stack in place and attends
    its ring straight out of the stack.  No instruction writes a layer's
    whole ring back into the stack, none copies a layer's ring out of it
    or the stack to another layout, and the scratch memory is no larger
    than the whole-ring write-back's (215,345,152 bytes)."""
    cfg = lm.cfg
    stack = (cfg.pattern_repeats, 1, MAX_LEN, cfg.num_kv_heads, cfg.head_dim)
    row = (1, 1, 1, cfg.num_kv_heads, cfg.head_dim)
    shapes, instrs, fused = {}, [], set()
    computation = None
    for line in fused_segment.as_text().splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        if " fusion(" in line:
            fused.update(re.findall(r"calls=%([\w.\-]+)", line))
        m = _HLO_INSTR.match(line)
        if m:
            name, dims, op, args = m.groups()
            shapes[name] = tuple(int(d) for d in dims.split(",") if d)
            instrs.append((computation, name, op,
                           re.findall(r"%([\w.\-]+)", args)))
    row_writes = 0
    for computation, name, op, args in instrs:
        if (computation not in fused and shapes[name] != stack
                and MAX_LEN in shapes[name]):
            assert not any(shapes.get(a) == stack for a in args), (
                f"{name} ({op}) copies a layer's ring out of the stack")
        if shapes[name] != stack:
            continue
        assert op != "copy", f"{name} copies the stacked cache"
        for a in args:
            s = shapes.get(a, ())
            assert MAX_LEN not in s or s == stack, (
                f"{name} ({op}) writes {a} {s} into the stacked cache")
        if op == "dynamic-update-slice":
            assert shapes[args[1]] == row, (name, shapes[args[1]])
            row_writes += 1
    assert row_writes == 2                     # K and V
    assert fused_segment.memory_analysis().temp_size_in_bytes <= 215_345_152


def test_paged_lane_segment_compiles(one_chip, lm):
    from repro.serving.generate import PagedLaneDecoder
    n_pages = LANES * MAX_LEN // PAGE + 1
    params = _spec(lm.abstract_params()[0], one_chip)
    caches = _spec(jax.eval_shape(lambda: lm.init_paged_cache(
        LANES, MAX_LEN, n_pages, PAGE)), one_chip)
    lane = _scalar(jnp.int32, one_chip, (LANES,))
    dec = PagedLaneDecoder(lm, MAX_LEN, LANES, SEGMENT_LEN,
                           n_pages=n_pages, page_size=PAGE)
    compiled = dec._segment.lower(
        params, caches, lane, lane, lane, lane, _scalar(jnp.int32, one_chip),
        _scalar(jnp.bool_, one_chip, (LANES,))).compile()
    _fits(compiled)


@pytest.fixture(scope="module")
def jamba_lm():
    return LM(get_config("jamba2-3b"))


@pytest.mark.parametrize("bucket", [16, 64])
def test_jamba_bucketed_prefill_compiles(one_chip, jamba_lm, bucket):
    """jamba2-3b at its published widths (26 Mamba-1 mixers, 2 attention
    layers, 6.06 GB of weights): the masked recurrent prefill at the
    buckets its short prompts use compiles and fits one chip."""
    params = _spec(jamba_lm.abstract_params()[0], one_chip)
    prefill = jax.jit(lambda p, toks, plen: jamba_lm.prefill(
        p, {"tokens": toks}, pad_to=MAX_LEN, prompt_len=plen))
    compiled = prefill.lower(
        params, _scalar(jnp.int32, one_chip, (1, bucket)),
        _scalar(jnp.int32, one_chip)).compile()
    assert _fits(compiled) < 6.3e9


def test_jamba_fused_segment_compiles(one_chip, jamba_lm):
    from repro.serving.generate import FusedDecoder
    params = _spec(jamba_lm.abstract_params()[0], one_chip)
    caches = _spec(jax.eval_shape(lambda: jamba_lm.init_cache(1, MAX_LEN)),
                   one_chip)
    i32 = _scalar(jnp.int32, one_chip)
    dec = FusedDecoder(jamba_lm, MAX_LEN, SEGMENT_LEN)
    compiled = dec._segment.lower(params, caches, i32, i32, i32, i32,
                                  i32).compile()
    assert _fits(compiled) < 6.3e9
