"""The training loop: RawArray data in, RawArray checkpoints out.

Fault-tolerance contract (DESIGN.md §3):

* periodic async checkpoints (params + optimizer + loader state) via the
  atomic-publish RawArray store;
* SIGTERM/SIGINT → synchronous checkpoint-and-exit (preemption-safe);
* ``train(..., resume=True)`` restores the latest checkpoint INCLUDING the
  data-iterator position (exact-once sample order);
* per-step wall-time EWMA + outlier log = straggler monitor (on a real
  fleet this feeds the scheduler; here it catches host-side data stalls).
"""

from __future__ import annotations

import signal
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ..checkpoint import CheckpointManager, restore_naive, restore_pipelined
from ..data import DataLoader, LoaderState
from ..distributed import optimizer as optim
from ..models.config import ModelConfig


@dataclass
class TrainLoopConfig:
    steps: int = 200
    ckpt_every: int = 50
    ckpt_dir: str = "checkpoints"
    keep: int = 3
    log_every: int = 10
    straggler_factor: float = 2.5  # step slower than factor x EWMA -> flag
    adamw: optim.AdamWConfig = field(default_factory=optim.AdamWConfig)


def train(
    model,
    loader: DataLoader,
    loop_cfg: TrainLoopConfig,
    *,
    step_fn: Optional[Callable] = None,
    resume: bool = True,
    restore_mode: str = "pipelined",
    init_rng: int = 0,
    hooks: Optional[List[Callable[[int, Dict[str, float]], None]]] = None,
) -> Dict[str, Any]:
    """Single-host training driver (the e2e example path). Returns summary."""
    cfg: ModelConfig = model.cfg
    adamw = loop_cfg.adamw

    if step_fn is None:

        def _step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: model.train_loss(p, batch), has_aux=True
            )(params)
            params, opt_state, info = optim.apply_updates(params, grads, opt_state, adamw)
            return params, opt_state, {**metrics, **info}

        step_fn = jax.jit(_step, donate_argnums=(0, 1))

    cm = CheckpointManager(loop_cfg.ckpt_dir, keep=loop_cfg.keep)
    start_step = 0
    s = cm.latest() if resume else None
    if s is None:
        # jitted: eager init dispatches faster than the device runs it, and
        # every queued op's output is allocated at dispatch
        params = jax.jit(model.init)(jax.random.PRNGKey(init_rng))
        opt_state = jax.jit(lambda p: optim.init_state(p, adamw))(params)
    else:
        # restore targets are shapes only: the device holds the state once
        params_like = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(init_rng)))
        opt_like = jax.eval_shape(lambda p: optim.init_state(p, adamw), params_like)
        # overlapped cold-start restore straight to device (DESIGN.md §13);
        # restore_mode="naive" keeps the phase-by-phase baseline reachable
        restore_fn = restore_pipelined if restore_mode == "pipelined" else restore_naive
        params, opt_state, extra = restore_fn(cm.path(s), params_like, opt_like)
        if "loader" in extra:
            loader.restore(LoaderState.from_dict(extra["loader"]))
        start_step = s
        print(f"[train] resumed from step {s}")

    # --- preemption handling -------------------------------------------------
    preempted = {"flag": False}

    def _on_signal(signum, frame):
        preempted["flag"] = True

    old_handlers = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        old_handlers[sig] = signal.signal(sig, _on_signal)

    losses: List[float] = []
    ewma = None
    stragglers = 0
    last_state: Optional[LoaderState] = None
    t_train0 = time.perf_counter()
    step = start_step
    try:
        while step < loop_cfg.steps:
            batch = next(loader)
            last_state = batch.pop("_state")
            jbatch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
            t0 = time.perf_counter()
            params, opt_state, metrics = step_fn(params, opt_state, jbatch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            if ewma is None:
                ewma = dt
            elif dt > loop_cfg.straggler_factor * ewma and step > start_step + 3:
                stragglers += 1
                print(f"[straggler] step {step}: {dt*1e3:.1f}ms vs EWMA {ewma*1e3:.1f}ms")
            ewma = 0.9 * (ewma if ewma else dt) + 0.1 * dt
            losses.append(loss)
            step += 1
            if step % loop_cfg.log_every == 0:
                print(
                    f"[train] step {step} loss={loss:.4f} "
                    f"acc={float(metrics.get('acc', 0)):.3f} {dt*1e3:.0f}ms"
                )
            if hooks:
                for h in hooks:
                    h(step, {k: float(v) for k, v in metrics.items()})
            if step % loop_cfg.ckpt_every == 0 or preempted["flag"]:
                cm.save(
                    step, params, opt_state,
                    extra={"loader": last_state.to_dict(), "loss": loss},
                )
            if preempted["flag"]:
                cm.wait()
                print(f"[train] preempted at step {step}; checkpoint flushed")
                break
    finally:
        for sig, h in old_handlers.items():
            signal.signal(sig, h)
        loader.stop()

    cm.wait()
    wall = time.perf_counter() - t_train0
    if step > start_step and step % loop_cfg.ckpt_every != 0 and not preempted["flag"]:
        cm.save(step, params, opt_state, extra={"loader": last_state.to_dict() if last_state else {}})
        cm.wait()
    return {
        "params": params,
        "opt_state": opt_state,
        "losses": losses,
        "steps": step,
        "wall_s": wall,
        "stragglers": stragglers,
        "loader_stats": loader.stats(),
        "ckpt_save_s": cm.save_s,
        "preempted": preempted["flag"],
    }
