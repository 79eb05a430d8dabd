"""Fault-tolerant training runtime.

Responsibilities
  * jit + shard the train step for the current mesh (donated buffers),
  * checkpoint/restart: async checkpoints every N steps; on a step failure
    the trainer restores the latest complete checkpoint and *replays* —
    the data pipeline is deterministic per step, so recovery is exact,
  * straggler mitigation: per-step wall time vs the perf-model prediction,
  * elastic scaling: ``reshard(new_mesh)`` re-lays-out params + optimizer
    state under a different mesh (grow/shrink) and re-jits — the
    single-process realization of "checkpoint → rescale → resume".

Failure injection for tests: pass ``failure_hook(step) -> bool``; a True
return raises a simulated device failure *after* the step executed, which
exercises the restore path deterministically.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs.base import RunConfig
from repro.data.pipeline import make_batch_iterator, shard_batch
from repro.launch.steps import make_train_step
from repro.models import lm
from repro.optim import adamw
from repro.runtime.straggler import StragglerMonitor
from repro.sharding import tree_shardings, use_mesh


class SimulatedFailure(RuntimeError):
    pass


@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: int = 0


class Trainer:
    def __init__(self, run: RunConfig, mesh=None, *,
                 predicted_step_s: Optional[float] = None,
                 failure_hook: Optional[Callable[[int], bool]] = None):
        self.run = run
        self.cfg = run.model
        self.mesh = mesh
        self.ckpt = CheckpointManager(run.checkpoint_dir,
                                      keep=run.keep_checkpoints)
        self.monitor = StragglerMonitor(
            slack=run.straggler_slack, predicted_step_s=predicted_step_s)
        self.failure_hook = failure_hook
        self.metrics_log: List[Dict[str, float]] = []
        self._build()

    # ------------------------------------------------------------------
    def _build(self):
        with use_mesh(self.mesh):
            self._abs_params = lm.abstract_params(self.cfg)
            if self.mesh is not None:
                self._param_sh = tree_shardings(
                    lm.param_axes(self.cfg), self._abs_params, mesh=self.mesh)
                # the step counter lives replicated, as the step returns it:
                # an uncommitted counter would make step 2 recompile
                self._opt_sh = adamw.opt_state_axes(self._param_sh)._replace(
                    count=NamedSharding(self.mesh, P()))
            else:
                self._param_sh = self._opt_sh = None
            step_fn = make_train_step(self.run)
            donate = (0, 1)
            if self.mesh is not None:
                # the step hands back its state in the layout it takes it
                # in; left to the compiler, an output sharding can differ
                # and the next step's jit refuses its own state
                state_sh = (self._param_sh, self._opt_sh, None)
                self._train_step = jax.jit(
                    step_fn, in_shardings=state_sh, out_shardings=state_sh,
                    donate_argnums=donate)
            else:
                self._train_step = jax.jit(step_fn, donate_argnums=donate)

    # ------------------------------------------------------------------
    def init_state(self, seed: int = 0) -> TrainState:
        with use_mesh(self.mesh):
            params = lm.init(jax.random.PRNGKey(seed), self.cfg)
            if self._param_sh is not None:
                params = jax.tree.map(jax.device_put, params, self._param_sh)
            opt = adamw.init_opt_state(params, self.run.optimizer)
            if self._opt_sh is not None:
                opt = opt._replace(
                    count=jax.device_put(opt.count, self._opt_sh.count))
        return TrainState(params, opt, 0)

    def restore_or_init(self, seed: int = 0) -> TrainState:
        latest = self.ckpt.latest_step()
        if latest is None:
            return self.init_state(seed)
        return self.load(latest)

    # ------------------------------------------------------------------
    def train(self, state: TrainState, num_steps: int,
              *, log_every: int = 10) -> TrainState:
        run = self.run
        it_step = state.step
        batches = make_batch_iterator(self.cfg, run.shape, self.mesh,
                                      seed=run.seed, start_step=it_step)
        retries = 0
        while state.step < num_steps:
            batch = next(batches)
            t0 = time.perf_counter()
            try:
                params, opt, metrics = self._train_step(
                    state.params, state.opt_state, batch)
                jax.block_until_ready(metrics["loss"])
                if self.failure_hook and self.failure_hook(state.step):
                    raise SimulatedFailure(f"injected at step {state.step}")
            except Exception as e:  # noqa: BLE001 — fault-tolerant path
                retries += 1
                if retries > run.max_step_retries:
                    raise
                latest = self.ckpt.latest_step()
                if latest is None:
                    state = self.init_state(run.seed)
                else:
                    state = self.load(latest)
                batches = make_batch_iterator(
                    self.cfg, run.shape, self.mesh, seed=run.seed,
                    start_step=state.step)
                self.metrics_log.append(
                    {"step": state.step, "event": "restored",
                     "error": str(e)[:80]})
                continue
            wall = time.perf_counter() - t0
            state = TrainState(params, opt, state.step + 1)
            self.monitor.observe(state.step, wall)
            row = {"step": state.step, "wall_s": wall,
                   **{k: float(v) for k, v in metrics.items()}}
            self.metrics_log.append(row)
            if log_every and state.step % log_every == 0:
                print(f"[train] step={state.step} "
                      f"loss={row.get('loss', float('nan')):.4f} "
                      f"wall={wall:.3f}s", flush=True)
            if run.checkpoint_every and \
                    state.step % run.checkpoint_every == 0:
                self.save(state)
        return state

    def step_hlo(self, state: TrainState) -> str:
        """Optimized HLO of the compiled train step for ``state``'s next
        batch: where its collectives and kernels can be read."""
        batch = next(make_batch_iterator(self.cfg, self.run.shape, self.mesh,
                                         seed=self.run.seed,
                                         start_step=state.step))
        return self._train_step.lower(
            state.params, state.opt_state, batch).compile().as_text()

    # ------------------------------------------------------------------
    def save(self, state: TrainState, *, blocking: bool = False):
        tree = {"params": state.params, "opt": state.opt_state}
        self.ckpt.save(state.step, tree, extra={"step": state.step},
                       blocking=blocking)

    def load(self, step: int) -> TrainState:
        opt_abs = adamw.abstract_opt_state(self._abs_params,
                                           self.run.optimizer)
        abs_tree = {"params": self._abs_params, "opt": opt_abs}
        sh_tree = {"params": self._param_sh, "opt": self._opt_sh} \
            if self._param_sh is not None else None
        tree = self.ckpt.restore(step, abs_tree, sh_tree)
        return TrainState(tree["params"], tree["opt"], step)

    # ------------------------------------------------------------------
    def reshard(self, state: TrainState, new_mesh) -> TrainState:
        """Elastic scaling: move state onto a different mesh and re-jit."""
        host = jax.tree.map(np.asarray, {"params": state.params,
                                         "opt": state.opt_state})
        self.mesh = new_mesh
        self._build()
        with use_mesh(new_mesh):
            sh_tree = {"params": self._param_sh, "opt": self._opt_sh}
            moved = jax.tree.map(
                lambda x, s: jax.device_put(x, s) if s is not None
                else jax.numpy.asarray(x), host, sh_tree)
        return TrainState(moved["params"], moved["opt"], state.step)
