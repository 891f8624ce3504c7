"""Train/AIR config dataclasses.

Analog of ``python/ray/air/config.py`` in the reference: ``ScalingConfig``
(:103 — num_workers :155, use_gpu :156 → use_tpu here, resources_per_worker,
placement_strategy), ``RunConfig``, ``FailureConfig``, ``CheckpointConfig``.
TPU-specific: ``chips_per_worker`` + STRICT_SPREAD default for pod slices
(one worker per host, gang-scheduled — the SPMD-vs-actor impedance fix from
SURVEY.md §7 hard parts).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


@dataclass
class ScalingConfig:
    num_workers: int = 1
    use_tpu: bool = False
    chips_per_worker: int = 0  # TPU chips each worker owns; 0 = one
    resources_per_worker: Optional[Dict[str, float]] = None
    placement_strategy: str = "PACK"
    # TPU topology hint, e.g. "v5e-64"; reserved for slice-head scheduling
    topology: Optional[str] = None

    def worker_resources(self) -> Dict[str, float]:
        res = dict(self.resources_per_worker or {})
        res.setdefault("CPU", 1)
        if self.use_tpu:
            res.setdefault("TPU", self.chips_per_worker or 1)
        return res

    def bundles(self) -> List[Dict[str, float]]:
        return [self.worker_resources() for _ in range(self.num_workers)]


@dataclass
class FailureConfig:
    max_failures: int = 0  # retries of the whole worker group; -1 = infinite


@dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"
    checkpoint_frequency: int = 0


@dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = field(default_factory=CheckpointConfig)
    verbose: int = 1
    # tune lifecycle callbacks / per-trial loggers (tune/callbacks.py)
    callbacks: Optional[list] = None

    def resolved_storage_path(self) -> str:
        base = self.storage_path or os.path.expanduser("~/ray_tpu_results")
        name = self.name or "train_run"
        return os.path.join(base, name)
