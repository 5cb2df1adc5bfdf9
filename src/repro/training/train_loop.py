"""Trainer: jitted train step + data + async checkpointing + restart.

Runs on whatever mesh it is given (the CPU tests use a 1x1 local mesh; the
production launcher passes the pod mesh).  Fault tolerance: on start it
resumes from the newest checkpoint if one exists; `simulate_crash` in tests
kills the loop between steps and a fresh Trainer picks up byte-identically
(data pipeline state is checkpointed with the model).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.shapes import ShapeConfig
from repro.launch import steps as steplib
from repro.launch.mesh import make_local_mesh
from repro.models.common import ModelConfig
from . import checkpointing as ckpt
from .data import DataConfig, TokenPipeline
from .optimizer import AdamWConfig, init_opt_state


@dataclasses.dataclass
class TrainConfig:
    n_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    keep_ckpts: int = 3
    log_every: int = 10
    seed: int = 0


class Trainer:
    def __init__(self, model_cfg: ModelConfig, shape: ShapeConfig,
                 train_cfg: TrainConfig,
                 mesh: Optional[Any] = None,
                 ocfg: Optional[AdamWConfig] = None):
        self.model_cfg = model_cfg
        self.shape = shape
        self.tc = train_cfg
        self.mesh = mesh or make_local_mesh()
        self.bundle = steplib.make_train_step(model_cfg, shape, self.mesh,
                                              ocfg=ocfg)
        model = self.bundle.meta["model"]
        with self.mesh:
            self.step_fn = jax.jit(
                self.bundle.fn,
                in_shardings=steplib.to_shardings(
                    self.mesh, self.bundle.in_shardings),
                out_shardings=steplib.to_shardings(
                    self.mesh, self.bundle.out_shardings),
                donate_argnums=self.bundle.donate_argnums)
        params = model.init(jax.random.PRNGKey(train_cfg.seed))
        opt = init_opt_state(params, model_cfg.opt_state_dtype,
                             factored=model_cfg.opt_factored)
        self.state = {"params": params, "opt": opt}
        self.data = TokenPipeline(DataConfig(
            vocab_size=model_cfg.vocab_size, seq_len=shape.seq_len,
            global_batch=shape.global_batch, seed=train_cfg.seed,
            kind="audio" if model_cfg.frontend == "audio" else "lm",
            frontend_dim=model_cfg.frontend_dim))
        self.step = 0
        self.history: List[Dict[str, float]] = []
        self.ckpt = (ckpt.AsyncCheckpointer(train_cfg.ckpt_dir,
                                            keep=train_cfg.keep_ckpts)
                     if train_cfg.ckpt_dir else None)
        self._maybe_restore()

    # -- checkpoint/restore ---------------------------------------------------

    def _maybe_restore(self) -> None:
        if not self.tc.ckpt_dir:
            return
        last = ckpt.latest_step(self.tc.ckpt_dir)
        if last is None:
            return
        tree, manifest = ckpt.restore_checkpoint(self.tc.ckpt_dir,
                                                 self.state, step=last)
        self.state = tree
        self.step = int(manifest["step"])
        self.data.restore(manifest["meta"]["data"])

    def save(self) -> None:
        if self.ckpt is None:
            return
        self.ckpt.save(self.step, self.state,
                       meta={"data": self.data.state(),
                             "arch": self.model_cfg.name})

    # -- loop -------------------------------------------------------------------

    def _device_batch(self, batch: Dict[str, np.ndarray]):
        full = {}
        for k, v in batch.items():
            if self.model_cfg.frontend == "vision" and k == "tokens":
                pass
            full[k] = jnp.asarray(v)
        if self.model_cfg.frontend == "vision":
            B = self.shape.global_batch
            full["patches"] = jnp.zeros(
                (B, self.model_cfg.n_patches, self.model_cfg.frontend_dim),
                jnp.bfloat16)
        return full

    def run(self, n_steps: Optional[int] = None,
            crash_at: Optional[int] = None) -> List[Dict[str, float]]:
        n = n_steps if n_steps is not None else self.tc.n_steps
        target = self.step + n
        while self.step < target:
            batch = self._device_batch(self.data.next_batch())
            t0 = time.perf_counter()
            self.state, metrics = self.step_fn(self.state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            self.step += 1
            rec = {"step": self.step, "loss": loss, "sec": dt,
                   "grad_norm": float(metrics.get("grad_norm", 0.0))}
            self.history.append(rec)
            if self.step % self.tc.log_every == 0:
                print(f"step {self.step:5d} loss {loss:8.4f} "
                      f"gnorm {rec['grad_norm']:8.3f} {dt*1e3:7.1f} ms")
            if self.tc.ckpt_dir and self.step % self.tc.ckpt_every == 0:
                self.save()
            if crash_at is not None and self.step >= crash_at:
                raise RuntimeError("simulated crash")   # fault drill
        if self.ckpt is not None:
            self.save()
            self.ckpt.wait()
        return self.history
