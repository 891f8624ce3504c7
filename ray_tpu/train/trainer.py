"""JaxTrainer — the DataParallelTrainer/TorchTrainer analog.

Reference: ``train/data_parallel_trainer.py:25`` + ``base_trainer.py:567
fit()``. Differences by design: the backend is JAX/XLA (GSPMD inside the
worker's train loop does the sharding math; the trainer contributes
placement, gang scheduling, checkpoint/report plumbing, and fault-tolerant
restarts), and TPU workers are packed one-per-host over a slice via the
placement group.
"""

from __future__ import annotations

import json
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.train.backend_executor import Backend, BackendExecutor, JaxBackend
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import (
    CheckpointConfig,
    FailureConfig,
    RunConfig,
    ScalingConfig,
)
from ray_tpu.util import flight_recorder as _fr

# ``fit()`` entry to the worker group up (placement group, every train
# worker's process started and answering): one record a ``fit()``, its first
# attempt's (``timeline --attribute``'s set-up block)
_sp_place = _fr.register_span("trainer.place")


@dataclass
class Result:
    metrics: Dict[str, Any]
    checkpoint: Optional[Checkpoint]
    path: str
    error: Optional[str] = None
    metrics_dataframe: Optional[List[Dict[str, Any]]] = None
    best_checkpoints: List = field(default_factory=list)


class _CheckpointManager:
    """Top-K checkpoint retention (reference:
    train/_internal/checkpoint_manager.py)."""

    def __init__(self, cfg: CheckpointConfig, run_dir: str):
        self.cfg = cfg
        self.dir = os.path.join(run_dir, "checkpoints")
        os.makedirs(self.dir, exist_ok=True)
        self.kept: List[tuple] = []  # (score, path, metrics)
        self.counter = 0

    def register(self, worker_path: str, metrics: Dict[str, Any]) -> str:
        self.counter += 1
        dest = os.path.join(self.dir, f"checkpoint_{self.counter:06d}")
        if os.path.abspath(worker_path) != os.path.abspath(dest):
            shutil.copytree(worker_path, dest, dirs_exist_ok=True)
        attr = self.cfg.checkpoint_score_attribute
        score = metrics.get(attr, self.counter) if attr else self.counter
        sign = 1 if self.cfg.checkpoint_score_order == "max" else -1
        self.kept.append((sign * float(score), dest, dict(metrics)))
        self.kept.sort(key=lambda t: t[0], reverse=True)
        if self.cfg.num_to_keep is not None:
            while len(self.kept) > self.cfg.num_to_keep:
                _, path, _ = self.kept.pop()
                shutil.rmtree(path, ignore_errors=True)
        return dest

    def latest(self) -> Optional[str]:
        if not self.kept:
            return None
        return max(self.kept, key=lambda t: int(t[1].rsplit("_", 1)[-1]))[1]

    def best(self) -> Optional[tuple]:
        return self.kept[0] if self.kept else None


class JaxTrainer:
    def __init__(
        self,
        train_loop_per_worker: Optional[Callable] = None,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        backend: Optional[Backend] = None,
        datasets: Optional[Dict[str, Any]] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
    ):
        if train_loop_per_worker is None:
            # default loop: SPMD sharded llama training (train/spmd.py)
            # — the same train_loop_config runs devices=1 and devices=N
            # (mesh from the config's "mesh" key or RAY_TPU_TRAIN_MESH)
            from ray_tpu.train.spmd import spmd_train_loop

            train_loop_per_worker = spmd_train_loop
        self.train_loop = train_loop_per_worker
        self.config = dict(train_loop_config or {})
        self.scaling = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.backend = backend or JaxBackend()
        self.datasets = datasets or {}
        self.resume_from_checkpoint = resume_from_checkpoint

    def _dataset_shards(self) -> Optional[List[Dict[str, Any]]]:
        if not self.datasets:
            return None
        n = self.scaling.num_workers
        shards: List[Dict[str, Any]] = [dict() for _ in range(n)]
        for name, ds in self.datasets.items():
            if hasattr(ds, "streaming_split"):
                for i, piece in enumerate(ds.streaming_split(n)):
                    shards[i][name] = piece
            elif hasattr(ds, "split"):
                for i, piece in enumerate(ds.split(n)):
                    shards[i][name] = piece
            else:
                for i in range(n):
                    shards[i][name] = ds
        return shards

    def fit(self) -> Result:
        _t_place = _fr.now()
        run_dir = self.run_config.resolved_storage_path()
        os.makedirs(run_dir, exist_ok=True)
        ckpt_mgr = _CheckpointManager(self.run_config.checkpoint_config, run_dir)
        if self.resume_from_checkpoint is not None:
            ckpt_mgr.register(self.resume_from_checkpoint.path, {})
        history: List[Dict[str, Any]] = []
        last_metrics: Dict[str, Any] = {}
        max_failures = self.run_config.failure_config.max_failures
        attempt = 0
        error: Optional[str] = None

        from ray_tpu.util import events as events_mod

        run_name = self.run_config.name or "train"

        def on_report(rank: int, metrics: Dict[str, Any],
                      ckpt_path: Optional[str]):
            nonlocal last_metrics
            if ckpt_path:
                dest = ckpt_mgr.register(ckpt_path, metrics)
                events_mod.emit(
                    "INFO", events_mod.SOURCE_TRAIN,
                    f"checkpoint saved by rank {rank} -> {dest}",
                    entity_id=run_name, rank=rank, path=dest)
            if rank == 0:
                row = dict(metrics)
                row["_training_iteration"] = len(history)
                row["_timestamp"] = time.time()
                history.append(row)
                last_metrics = metrics
                with open(os.path.join(run_dir, "progress.jsonl"), "a") as f:
                    f.write(json.dumps(row, default=str) + "\n")

        while True:
            executor = BackendExecutor(self.scaling, self.backend,
                                       self.run_config.name or "train",
                                       run_dir)
            try:
                executor.start(ckpt_mgr.latest(), self._dataset_shards())
                _sp_place.end(_t_place)
                _t_place = 0.0  # a restart's placement is recovery's
                error = executor.run(self.train_loop, self.config, on_report)
            except ray_tpu.RayTpuError as e:
                error = f"worker group failure: {e}"
            finally:
                executor.shutdown()
            if error is None:
                break
            attempt += 1
            if max_failures != -1 and attempt > max_failures:
                events_mod.emit(
                    "ERROR", events_mod.SOURCE_TRAIN,
                    f"run {run_name!r} failed after {attempt} attempt(s): "
                    f"{error.splitlines()[0] if error else ''}",
                    entity_id=run_name, attempts=attempt)
                break
            events_mod.emit(
                "WARNING", events_mod.SOURCE_TRAIN,
                f"run {run_name!r} worker failure (attempt {attempt}); "
                f"restarting worker group from latest checkpoint",
                entity_id=run_name, attempt=attempt)
            error = None  # retrying from latest checkpoint

        latest = ckpt_mgr.latest()
        return Result(
            metrics=last_metrics,
            checkpoint=Checkpoint(latest) if latest else None,
            path=run_dir,
            error=error,
            metrics_dataframe=history,
            best_checkpoints=[(Checkpoint(p), m) for _, p, m in ckpt_mgr.kept],
        )
