"""Training loop integrating every substrate:

  data -> device_put(batch shardings) -> jitted train_step ->
  ARCAS scheduler (counters + Algorithm 1 + migration) ->
  checkpoint (atomic/async) -> failure injection / straggler detection.

The per-step "remote access" counter (Algorithm 1's cache-fill events) is
fed from the compiled step's HLO collective parse — on relayout the step is
re-jitted on the new mesh and the counter constants refresh.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.checkpoint.manager import CheckpointManager
from repro.compression.grad_compress import (init_compression,
                                             int8_compress_transform)
from repro.core.controller import ControllerConfig
from repro.core.counters import PerfCounters
from repro.core.layout import Layout
from repro.core.scheduler import GlobalScheduler, migrate_pytree
from repro.core.topology import ChipletTopology
from repro.launch import sharding as shlib
from repro.launch import hlo_analysis as ha
from repro.launch.steps import make_train_step
from repro.models.params import abstract_params, init_params
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.runtime.failure import (FailureInjector, SimulatedFailure,
                                   StragglerDetector)


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_every: int = 50               # 0 = never checkpoint
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    microbatches: int = 1
    seed: int = 0
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    compress_cross_pod: bool = False
    arcas: bool = True
    log_every: int = 10
    async_ckpt: bool = False


class Trainer:
    def __init__(self, cfg: ModelConfig, mesh, loader, tcfg: TrainerConfig,
                 *, topology: Optional[ChipletTopology] = None,
                 controller_cfg: Optional[ControllerConfig] = None,
                 failure: Optional[FailureInjector] = None,
                 log: Callable[[str], None] = print):
        self.cfg = cfg
        self.mesh = mesh
        self.loader = loader
        self.tcfg = tcfg
        self.failure = failure
        self.log = log
        self.counters = PerfCounters()
        self.straggler = StragglerDetector()
        self.ckpt = (CheckpointManager(tcfg.ckpt_dir, keep=tcfg.keep)
                     if tcfg.ckpt_every else None)
        self.scheduler = None
        if tcfg.arcas and topology is not None:
            self.scheduler = GlobalScheduler(
                topology, controller_cfg, counters=self.counters)
            self.scheduler.register_relayout(self._on_relayout)
        self.step = 0
        self._build()

    # ------------------------------------------------------------------
    def _build(self, restore: bool = False):
        cfg, mesh = self.cfg, self.mesh
        fsdp = False
        self.pspecs = shlib.param_specs(cfg, mesh, fsdp=fsdp)
        self.psh = shlib.named(mesh, self.pspecs)
        key = jax.random.PRNGKey(self.tcfg.seed)
        # drawn straight into the mesh's shardings: no device ever holds
        # the whole unsharded model
        self.params = jax.jit(init_params, static_argnums=0,
                              out_shardings=self.psh)(cfg, key)
        self.opt_state = init_opt_state(self.params)
        ospecs = shlib.opt_specs(cfg, mesh, self.pspecs)
        self.osh = shlib.named(mesh, ospecs)
        self.opt_state = jax.device_put(self.opt_state, self.osh)

        if self.tcfg.compress_cross_pod and not hasattr(self, "_ef"):
            self._ef = init_compression(self.params)["ef"]
        self._compile_step(mesh)

    def _compile_step(self, mesh):
        """(Re-)jit the train step for ``mesh`` (initial build + relayout).

        With compression on, the error-feedback state threads through the
        jitted step as an explicit carry (in/out), so it actually updates
        every step instead of being baked in as a traced constant.
        """
        compress = self.tcfg.compress_cross_pod
        step_fn = make_train_step(
            self.cfg, self.tcfg.opt,
            ef_transform=int8_compress_transform if compress else None,
            microbatches=self.tcfg.microbatches)
        if compress:
            self._jit_step = jax.jit(
                step_fn, out_shardings=(self.psh, self.osh, None, None),
                donate_argnums=(0, 1, 3))
        else:
            self._jit_step = jax.jit(
                step_fn, out_shardings=(self.psh, self.osh, None),
                donate_argnums=(0, 1))
        self._batch_sharding = shlib.named(
            mesh, shlib.batch_specs(self.cfg, None, mesh))
        self._hlo_bytes = None  # (re-)filled after next compile

    # -- relayout handler: migrate live training state to the new layout ----
    def _on_relayout(self, new_layout: Layout, decision) -> None:
        """Invoked by the GlobalScheduler control loop on a spread change.

        With a full fleet attached this rebuilds the mesh and reshards the
        live params/optimizer pytrees (``migrate_pytree``); on smaller
        hosts the relayout is logical — recorded, counters reset, but state
        stays put.
        """
        self.counters.add("relayouts", 1)
        self.log(f"[trainer] relayout s={decision.old_spread}->"
                 f"{decision.new_spread} ({decision.reason})")
        if len(jax.devices()) < new_layout.topology.total_chips:
            return
        mesh = new_layout.make_mesh()
        self.mesh = mesh
        self.pspecs = shlib.param_specs(self.cfg, mesh, fsdp=False)
        self.psh = shlib.named(mesh, self.pspecs)
        ospecs = shlib.opt_specs(self.cfg, mesh, self.pspecs)
        self.osh = shlib.named(mesh, ospecs)
        self.params = migrate_pytree(self.params, self.psh)
        self.opt_state = migrate_pytree(self.opt_state, self.osh)
        if hasattr(self, "_ef"):
            # error-feedback state mirrors params; the re-jitted step
            # captures it, so it must move to the new mesh too
            self._ef = migrate_pytree(self._ef, self.psh)
        self._compile_step(mesh)

    def _put_batch(self, np_batch: Dict[str, np.ndarray]):
        out = {}
        for k, v in np_batch.items():
            shd = self._batch_sharding.get(k)
            out[k] = jax.device_put(v, shd)
        return out

    # ------------------------------------------------------------------
    def resume_if_possible(self) -> bool:
        latest = self.ckpt.latest() if self.ckpt is not None else None
        if latest is None:
            return False
        state = {"params": self.params, "opt": self.opt_state}
        shardings = {"params": self.psh, "opt": self.osh}
        restored, meta = self.ckpt.restore(state, shardings=shardings)
        self.params = restored["params"]
        self.opt_state = restored["opt"]
        self.step = int(meta["step"])
        if "loader" in meta:
            self.loader.load_state_dict(meta["loader"])
        self.log(f"[trainer] resumed from step {self.step}")
        return True

    def _collective_feed(self, compiled_text: str):
        stats = ha.collective_bytes(compiled_text, multi_pod=False)
        self._hlo_bytes = {
            "remote": stats.remote_bytes,
            "local": stats.per_class_bytes.get("intra_group", 0.0),
        }

    # ------------------------------------------------------------------
    def run(self, steps: Optional[int] = None) -> Dict[str, Any]:
        steps = steps or self.tcfg.steps
        losses = []
        t_train0 = time.monotonic()
        while self.step < steps:
            if self.failure is not None:
                self.failure.check(self.step)
            block = self.loader.next()
            from repro.data.pipeline import make_batch
            batch = self._put_batch(make_batch(self.cfg, block))
            t0 = time.monotonic()
            if self.tcfg.compress_cross_pod:
                self.params, self.opt_state, metrics, self._ef = \
                    self._jit_step(self.params, self.opt_state, batch,
                                   self._ef)
            else:
                self.params, self.opt_state, metrics = self._jit_step(
                    self.params, self.opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.monotonic() - t0
            losses.append(loss)
            self.step += 1

            if self._hlo_bytes is None:
                # pull collective constants from the compiled step once
                args = (self.params, self.opt_state, batch)
                if self.tcfg.compress_cross_pod:
                    args += (self._ef,)
                txt = self._jit_step.lower(*args).compile().as_text()
                self._collective_feed(txt)

            slow = self.straggler.observe(dt)
            self.counters.record_step(
                step_time=dt,
                remote_bytes=self._hlo_bytes["remote"] * (2 if slow else 1),
                local_bytes=self._hlo_bytes["local"])
            if self.scheduler is not None:
                # the unified control loop: advance host-side coroutines one
                # round, evaluate Algorithm 1, fire relayout handlers
                self.scheduler.tick()

            if self.step % self.tcfg.log_every == 0:
                self.log(f"[trainer] step {self.step} loss {loss:.4f} "
                         f"({dt*1e3:.0f} ms)")
            if self.ckpt is not None and (
                    self.step % self.tcfg.ckpt_every == 0
                    or self.step == steps):
                self.ckpt.save(
                    self.step,
                    {"params": self.params, "opt": self.opt_state},
                    metadata={"loader": self.loader.state_dict()},
                    blocking=not self.tcfg.async_ckpt)
        if self.ckpt is not None:
            self.ckpt.wait()
        return {"losses": losses, "steps": self.step,
                "wall": time.monotonic() - t_train0,
                "straggler_events": list(self.straggler.events),
                "counters": self.counters.snapshot()}
