"""Family ``gpt``: the GPT-2 / GPT-NeoX / OPT / BLOOM dense blocks of
``deepspeed_tpu/models/gpt.py``.

A family is the one place of the harness that imports the program's model
code. It hands the modes, the reference check and ``tools/compile_only.py``
what they need of a model and nothing of how it is built:

- ``config(model)``: the program's configuration object from the ``model``
  group of ``configs/<config>.json``;
- ``module(cfg)``: the ``Module`` that ``deepspeed_tpu.initialize`` trains;
- ``init_params(cfg, key)``: the parameter tree, float32, from a PRNG key;
- ``paged_decode_step(cfg, params, tokens, cache, tables, lengths, impl=...)``
  and ``init_cache(cfg, batch, max_len, dtype)``: the functions the serving
  engine's programs wrap, for the reference check and the compile-only tool;
- ``train_program_report(cfg, **placement)``: the train step compiled for a
  described chip (``tools/compile_only.py``).

A family that trains only leaves the serving functions out, and a cell that
needs one then fails with this module's name. ``ServingEngine``,
``make_scheduler``, ``initialize`` and ``train_batch`` stay the entry points:
a model that needs another engine is not brought in through here.
"""

from __future__ import annotations

from deepspeed_tpu.models import gpt as _gpt
from deepspeed_tpu.models.gpt import (init_cache, init_params,  # noqa: F401
                                      paged_decode_step)


def config(model: dict):
    return _gpt.GPTConfig(**model)


def module(cfg):
    return _gpt.build(cfg)[0]


def train_program_report(cfg, **placement) -> dict:
    """``runtime/aot.train_program_report`` knows a model by its preset
    name."""
    from deepspeed_tpu.runtime import aot

    _gpt.PRESETS["_bench_cell"] = cfg
    return aot.train_program_report("_bench_cell", **placement)
