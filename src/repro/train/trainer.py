"""End-to-end trainer: index-backed data → jitted train_step → catalog
checkpoints, with restart/elastic recovery built in.

This is the driver behind ``examples/train_indexed_lm.py`` and the
fault-tolerance tests.  On the container it runs on the 1-device mesh;
on a mesh (:func:`repro.launch.mesh.make_mesh`) the identical object runs —
the mesh and the dp extent are constructor parameters, everything else
(sampler addressing, checkpoint format, step function) is mesh-agnostic.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ModelConfig
from repro.data.pipeline import BatchLoader, IndexedDataset
from repro.data.sampler import GlobalSampler
from repro.dist.compress import ErrorFeedbackCompressor, make_compressor
from repro.models.registry import ModelApi, build_model
from repro.runtime.fault import Heartbeat
from repro.train.loop import make_train_state, make_train_step
from repro.train.optimizer import AdamWConfig

__all__ = ["TrainerConfig", "Trainer"]


@dataclasses.dataclass
class TrainerConfig:
    seq_len: int = 128
    global_batch: int = 8
    steps: int = 50
    ckpt_every: int = 10
    keep_last: int = 3
    grad_accum: int = 1
    compress_grads: bool = False
    # compression scheme when compress_grads is set — a repro.dist.compress
    # registry name ("int8_ef", "int8_pc_ef", "topk_ef"); topk_frac only
    # applies to topk.
    compressor: str = "int8_ef"
    topk_frac: float = 0.1
    seed: int = 0
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)

    def make_compressor(self) -> Optional[ErrorFeedbackCompressor]:
        """The configured gradient compressor, or None when disabled."""
        if not self.compress_grads:
            return None
        return make_compressor(self.compressor, topk_frac=self.topk_frac)


class Trainer:
    def __init__(
        self,
        model_cfg: ModelConfig,
        tcfg: TrainerConfig,
        dataset: IndexedDataset,
        workdir: Path,
        mesh=None,
        dp_rank: int = 0,
        n_dp: int = 1,
    ):
        self.model_cfg = model_cfg
        self.tcfg = tcfg
        self.dataset = dataset
        self.workdir = Path(workdir)
        self.mesh = mesh
        self.dp_rank = dp_rank
        self.n_dp = n_dp
        self.api = build_model(model_cfg)
        self.sampler = GlobalSampler(
            n_examples=len(dataset),
            global_batch=tcfg.global_batch,
            seed=tcfg.seed,
        )
        self.ckpt = CheckpointManager(self.workdir / "ckpt", keep_last=tcfg.keep_last)
        self.heartbeat = Heartbeat(self.workdir, dp_rank)
        self._compressor = tcfg.make_compressor()
        self._step_fn = jax.jit(
            make_train_step(self.api, tcfg.opt, tcfg.grad_accum, self._compressor),
            donate_argnums=(0,),
        )

    # -- state --------------------------------------------------------------

    def init_state(self) -> Dict[str, Any]:
        state = make_train_state(self.api, jax.random.PRNGKey(self.tcfg.seed), self.tcfg.opt)
        if self._compressor is not None:
            state[self._compressor.state_key] = self._compressor.init(
                state["params"]
            )
        return state

    def maybe_restore(self, state: Dict[str, Any]) -> Tuple[int, Dict[str, Any]]:
        latest = self.ckpt.latest_step()
        if latest is None:
            return 0, state
        step, restored = self.ckpt.restore(state)
        restored = jax.tree_util.tree_map(jnp.asarray, restored)
        return step, restored

    # -- run ----------------------------------------------------------------

    def run(
        self,
        until_step: Optional[int] = None,
        state: Optional[Dict[str, Any]] = None,
        on_step: Optional[Callable[[int, dict], None]] = None,
        die_at_step: Optional[int] = None,
    ) -> Tuple[int, Dict[str, Any], list]:
        """Train from the latest checkpoint (or ``state``) to ``until_step``.

        ``die_at_step`` simulates a node failure: the trainer stops without
        a final checkpoint, exactly like a SIGKILL (recovery must come from
        the last periodic checkpoint).

        Runs inside the trainer's mesh context (when one was given), so
        the step function traces with the logical sharding rules active —
        every ``constrain`` in the model resolves against this mesh.
        """
        ctx = (
            jax.set_mesh(self.mesh) if self.mesh is not None
            else contextlib.nullcontext()
        )
        with ctx:
            return self._run(until_step, state, on_step, die_at_step)

    def _run(self, until_step, state, on_step, die_at_step):
        until = until_step if until_step is not None else self.tcfg.steps
        if state is None:
            start, state = self.maybe_restore(self.init_state())
        else:
            start = int(state["step"])
        history = []
        for step in range(start, until):
            batch_np = self.dataset.batch_for(
                self.sampler, step, self.dp_rank, self.n_dp
            )
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            t0 = time.perf_counter()
            state, metrics = self._step_fn(state, batch)
            loss = float(metrics["loss"])
            rec = {
                "step": step,
                "loss": loss,
                "grad_norm": float(metrics["grad_norm"]),
                "lr": float(metrics["lr"]),
                "dt": time.perf_counter() - t0,
            }
            history.append(rec)
            self.heartbeat.beat(step)
            if on_step:
                on_step(step, rec)
            done = step + 1
            if die_at_step is not None and done >= die_at_step:
                return done, state, history  # crashed: no checkpoint written
            if done % self.tcfg.ckpt_every == 0 or done == until:
                self.ckpt.save(done, state, meta={"loss": loss}, blocking=True)
        return until, state, history
