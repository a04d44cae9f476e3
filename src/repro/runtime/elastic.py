"""Elastic runtime: OASiS schedules -> per-slot worker counts -> re-meshed
training.

This is the execution-side half of the paper's core idea ("adjusted
numbers of concurrent workers ... dynamically adjusted during the course
of the job").  At each slot boundary the runtime:

  1. reads the slot's worker count W_t from the job's OASiS schedule,
  2. checkpoints (async flush -> sync point),
  3. rebuilds the device mesh with dp width W_t,
  4. restores params/optimizer through the new shardings
     (``ckpt.restore`` is sharding-agnostic),
  5. resumes the data pipeline cursor — chunk assignment is worker-count
     independent, so no sample is replayed or skipped (the asynchronous-
     training property the paper relies on, mapped to sync SPMD).

On one host, "workers" are dp slices of the host mesh; on a real cluster
the same code drives jax.distributed with per-pod process groups.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax

from ..ckpt import checkpoint as ckpt
from ..core.types import Schedule
from ..data.pipeline import DataConfig, DataPipeline
from ..launch.mesh import make_mesh


@dataclasses.dataclass
class SlotPlan:
    slot: int
    n_workers: int


def schedule_to_plan(schedule: Schedule) -> List[SlotPlan]:
    plan = []
    for t in sorted(schedule.workers):
        plan.append(SlotPlan(slot=t, n_workers=int(schedule.workers[t].sum())))
    return plan


def dp_width(n_workers: int, n_devices: int) -> int:
    """Largest power-of-two dp width <= min(workers, devices)."""
    w = max(1, min(n_workers, n_devices))
    return 1 << (w.bit_length() - 1)


class ElasticTrainer:
    """Drives train_step across slots with re-meshing between them."""

    def __init__(self, cfg, opt_cfg, data_cfg: DataConfig, ckpt_dir: str,
                 make_step: Callable, steps_per_slot: int = 50):
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.data_cfg = data_cfg
        self.ckpt_dir = ckpt_dir
        self.make_step = make_step          # (mesh) -> (fn, p_shard, o_shard)
        self.steps_per_slot = steps_per_slot
        self.checkpointer = ckpt.AsyncCheckpointer(ckpt_dir)
        self.metrics_log: List[Dict] = []
        self.mesh_history: List[int] = []

    def run(self, plan: List[SlotPlan], params, opt_state,
            pipeline: Optional[DataPipeline] = None) -> Dict[str, Any]:
        pipeline = pipeline or DataPipeline(self.data_cfg)
        step_no = 0
        for slot in plan:
            width = dp_width(slot.n_workers, len(jax.devices()))
            self.mesh_history.append(width)
            mesh = make_mesh((width, 1), ("data", "model"))
            fn, p_shard, o_shard = self.make_step(mesh)
            params = jax.device_put(params, p_shard)
            opt_state = jax.device_put(opt_state, o_shard)
            for _ in range(self.steps_per_slot):
                batch = pipeline.next_batch()
                params, opt_state, metrics = fn(params, opt_state, batch)
                self.metrics_log.append(
                    {k: float(v) for k, v in metrics.items()})
                step_no += 1
            self.checkpointer.save_async(
                step_no, {"params": params, "opt": opt_state},
                extra={"pipeline": pipeline.state.to_dict(),
                       "slot": slot.slot})
        self.checkpointer.wait()
        return {"params": params, "opt": opt_state, "steps": step_no,
                "pipeline": pipeline}
