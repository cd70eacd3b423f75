"""Family ``gpt_moe``: ``deepspeed_tpu/models/gpt_moe.py``, the GPT block with
a GShard expert bank in place of every ``moe_freq``-th MLP. It trains through
``initialize`` -> ``train_batch``; the program has no paged serving path for
it, so this family has no serving functions. See ``families/gpt.py`` for what
a family holds.

The ``model`` group is flat: the keys that are fields of ``GPTMoEConfig``
(``num_experts``, ``moe_freq``, ``k``, ...) go there, the rest to its
``base``, a ``GPTConfig``.
"""

from __future__ import annotations

import dataclasses

from deepspeed_tpu.models import gpt_moe as _moe
from deepspeed_tpu.models.gpt import GPTConfig
from deepspeed_tpu.models.gpt_moe import init_params  # noqa: F401

_OWN = {f.name for f in dataclasses.fields(_moe.GPTMoEConfig)} - {"base"}


def config(model: dict):
    base = {k: v for k, v in model.items() if k not in _OWN}
    own = {k: v for k, v in model.items() if k in _OWN}
    return _moe.GPTMoEConfig(base=GPTConfig(**base), **own)


def module(cfg):
    return _moe.build(cfg)[0]
