"""One module per model architecture, found from the configuration file.

A configuration whose ``architectures[0]`` is ``LlamaForCausalLM`` is
served, checked and counted by ``chipbench/families/llama.py``: the name
with ``ForCausalLM`` removed, lower-cased.  A new architecture brings one
new module and needs no registry.  A family module provides:

* ``arch_config(c)``: the program's ``ArchConfig`` for configuration file
  ``c``, checked against the file's published keys;
* ``make_params(c, seed)``: the served parameter tree, on the device, made
  from the seed (leaf keys from ``weights._leaf``, so the reference can
  make each leaf again);
* ``reference_weights(c, words, layer)`` and
  ``reference_layer(c, layer, x, w, quant)``: one layer's float32 weights
  and its forward over one sequence, plain or as the float8 control
  (``reference.py`` runs them layer by layer, so a layer's kind may depend
  on its index);
* ``decode_steps(c, args)``, ``prefill(c, args)`` and ``param_count(c)``:
  the operations and bytes that ``roofline.py`` charges, from the args of
  a ``chipbench.segment`` or ``chipbench.prefill`` mark.
"""

from __future__ import annotations

import importlib


class UnknownArchitecture(LookupError):
    """No family module for a configuration's architecture."""


def module_name(c: dict) -> str:
    """``chipbench.families.<name>`` for configuration file ``c``."""
    arch = c["architectures"][0]
    return "chipbench.families." + arch.removesuffix("ForCausalLM").lower()


def of(c: dict):
    """The family module of configuration file ``c``."""
    name = module_name(c)
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        path = name.replace(".", "/") + ".py"
        raise UnknownArchitecture(
            f"no family module for architecture {c['architectures'][0]!r}: "
            f"looked for {path}") from None
