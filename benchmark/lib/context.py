"""What a reader is given."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from . import flops, manifest
from .spans import SpanLog
from .trace import Reduced
from .window import Window


@dataclasses.dataclass
class Run:
    """What a mode's ``run`` hands back."""

    window: Window
    attempted: int
    failed: int
    problems: List[str]
    facts: Dict[str, float]
    verdict: "Verdict"             # lib/correct.Verdict: the reference check
    compile_mark: tuple            # the compile log's mark at the window's opening
    requests: List = dataclasses.field(default_factory=list)
    # what has to stay alive until ``run.py`` has read the per-layer metrics:
    # a train engine, whose compiled step ``prog_phase_ms`` asks for its
    # scopes (``program_scopes`` holds a jitted function weakly)
    keep: object = None


@dataclasses.dataclass
class Context:
    cell: dict
    window: Window
    spans: SpanLog
    requests: List                 # serving: requests submitted inside
    facts: Dict[str, float]
    device_kind: str
    chips: int
    setup_s: float
    trace: Optional[Reduced] = None
    traced: Optional[Tuple[float, float]] = None   # traced window, host clock

    @property
    def model(self) -> dict:
        return self.cell["config_file"]["model"]

    def count(self, name: str):
        """The function that counts ``name`` (``train_flops_per_token``,
        ``decode_step_bytes``, ``kv_bytes_per_token``, ``cache_layers``) for
        this cell's configuration: its reference module's where that defines
        one, which knows the architecture's equations, else ``lib/flops``'s
        for the dense GPT block."""
        own = manifest.reference_of(self.cell["config_file"])
        return getattr(own, name, None) or getattr(flops, name)
