"""Operations and bytes that a step needs, from shapes, and the chip's peaks.

``c`` is a configuration file's dict (Hugging Face key names).  The counts
are the family's (``chipbench/families/<name>.py``), from the args that
the harness's ``chipbench.segment`` and ``chipbench.prefill`` marks
record.  Weights and the K/V cache are bfloat16 (``BYTES``).  The bytes
are what the algorithm needs, so a path that reads less cannot push a
share of the roofline over 100%.
"""

from __future__ import annotations

import json
import os

from chipbench import families

BYTES = 2          # bfloat16

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """``{"flops": ..., "hbm_bytes_per_s": ...}`` of one chip of this kind.
    An unknown kind is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def decode_steps(c: dict, args: dict) -> list:
    """``(flops, bytes)`` of each decode step of a ``chipbench.segment``
    mark with ``args``."""
    return families.of(c).decode_steps(c, args)


def prefill(c: dict, args: dict) -> int:
    """Operations of the prefill of a ``chipbench.prefill`` mark."""
    return families.of(c).prefill(c, args)


def param_count(c: dict) -> int:
    """Every parameter the served model holds."""
    return families.of(c).param_count(c)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The shortest time the chip could take: compute- or memory-bound."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])
