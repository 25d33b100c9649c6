"""Fault-tolerant training loop.

Features expected at 1000+ node scale, realized at whatever scale the
current mesh provides:

  * auto-resume: picks up the latest complete checkpoint in ckpt_dir.
  * async checkpointing every `ckpt_every` steps (atomic, keep-N).
  * NaN / loss-spike guard: a non-finite loss (SDC, bad node, data bug)
    triggers rollback to the last checkpoint and resumes from there —
    deterministic data means the stream replays identically.
  * straggler monitor: per-step wall time vs a running median; steps slower
    than `straggler_factor` x median are logged with their step index (on a
    real cluster this feeds the scheduler's node-health signal).
  * stateless-resumable data (see train/data.py): no iterator state in the
    checkpoint, elastic-rescale safe.
"""

from __future__ import annotations

import dataclasses
import statistics
import time

import jax
import numpy as np

from repro.launch.steps import make_train_step, state_specs
from repro.models.module import init_params
from repro.train.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint

__all__ = ["TrainerConfig", "Trainer"]


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    log_every: int = 10
    peak_lr: float = 3e-4
    warmup: int = 10
    straggler_factor: float = 2.0
    seed: int = 0


class Trainer:
    def __init__(self, md, cfg, mesh, data, tcfg: TrainerConfig):
        self.md, self.cfg, self.mesh, self.data, self.tcfg = md, cfg, mesh, data, tcfg
        step_fn, self.opt = make_train_step(
            md, cfg, peak_lr=tcfg.peak_lr, warmup=tcfg.warmup,
            total_steps=tcfg.total_steps)
        self.state_sds, self.state_shard = state_specs(md, cfg, mesh)
        self.step_fn = jax.jit(step_fn,
                               in_shardings=(self.state_shard, None),
                               out_shardings=None,
                               donate_argnums=(0,))
        self.ckpt = AsyncCheckpointer(tcfg.ckpt_dir, keep=tcfg.keep)
        self.metrics_log = []
        self.events = []  # (step, kind, detail) — stragglers, rollbacks, ...

    # ------------------------------------------------------------------
    def init_state(self):
        params = jax.jit(
            lambda key: init_params(self.md.specs(self.cfg), key),
            out_shardings=self.state_shard["params"],
        )(jax.random.PRNGKey(self.tcfg.seed))
        opt_state = jax.jit(
            self.opt.init, out_shardings=self.state_shard["opt"],
        )(params)
        return {"params": params, "opt": opt_state}

    def restore_or_init(self):
        last = latest_step(self.tcfg.ckpt_dir)
        if last is None:
            return self.init_state(), 0
        state, step = restore_checkpoint(
            self.tcfg.ckpt_dir, self.state_sds, shardings=self.state_shard)
        self.events.append((step, "resume", f"restored step_{step}"))
        return state, step

    # ------------------------------------------------------------------
    def run(self):
        # the ambient mesh (constrain()) lasts for the run, not the process
        with jax.set_mesh(self.mesh):
            return self._run()

    def _run(self):
        state, start = self.restore_or_init()
        times = []
        step = start
        with self.mesh:
            while step < self.tcfg.total_steps:
                batch = self.data.batch(step)
                t0 = time.perf_counter()
                state, metrics = self.step_fn(state, batch)
                loss = float(metrics["loss"])  # blocks
                dt = time.perf_counter() - t0

                # --- straggler detection ---
                if len(times) >= 5:
                    med = statistics.median(times[-20:])
                    if dt > self.tcfg.straggler_factor * med:
                        self.events.append(
                            (step, "straggler",
                             f"{dt:.3f}s vs median {med:.3f}s"))
                times.append(dt)

                # --- NaN / spike guard with checkpoint rollback ---
                if not np.isfinite(loss):
                    self.events.append((step, "rollback", f"loss={loss}"))
                    self.ckpt.wait()
                    last = latest_step(self.tcfg.ckpt_dir)
                    if last is None:
                        state, step = self.init_state(), 0
                    else:
                        state, step = restore_checkpoint(
                            self.tcfg.ckpt_dir, self.state_sds,
                            shardings=self.state_shard)
                    continue

                step += 1
                if step % self.tcfg.log_every == 0 or step == self.tcfg.total_steps:
                    self.metrics_log.append(
                        {"step": step, "loss": loss,
                         "lr": float(metrics["lr"]),
                         "grad_norm": float(metrics["grad_norm"]),
                         "step_time_s": dt})
                if step % self.tcfg.ckpt_every == 0:
                    self.ckpt.save(state, step)
        self.ckpt.wait()
        return state
