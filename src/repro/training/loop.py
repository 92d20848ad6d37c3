"""Training loop with checkpointing — the train-side e2e driver."""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import timing
from repro.data import SyntheticTokens
from repro.models import transformer as T
from repro.optim import adamw, cosine_schedule
from repro.training.steps import make_train_step


def train(cfg: ArchConfig, *, steps: int, batch: int, seq: int,
          lr: float = 3e-4, seed: int = 0, log_every: int = 10,
          checkpoint_path: Optional[str] = None,
          checkpoint_every: int = 0, remat: bool = True,
          log_fn: Callable[[str], None] = print) -> Dict[str, list]:
    key = jax.random.PRNGKey(seed)
    params = T.init_model(cfg, key)
    opt = adamw(schedule=cosine_schedule(lr, warmup=max(steps // 20, 1),
                                         total=steps))
    step_fn, init_opt = make_train_step(cfg, optimizer=opt, remat=remat)
    opt_state = init_opt(params)
    jstep = jax.jit(step_fn, donate_argnums=(0, 1))
    data = SyntheticTokens(cfg, batch, seq, seed=seed)
    it = iter(data)
    hist = {"loss": [], "step_time": []}
    for i in range(steps):
        b = {k: jnp.asarray(v) for k, v in next(it).items()}
        with timing.measure() as m:
            params, opt_state, metrics = jstep(params, opt_state, b)
            loss = float(metrics["loss"])
        dt = m.wall
        hist["loss"].append(loss)
        hist["step_time"].append(dt)
        if log_every and i % log_every == 0:
            log_fn(f"step {i:5d} loss {loss:.4f} "
                   f"({dt * 1e3:.0f} ms/step)")
        if checkpoint_path and checkpoint_every and \
                (i + 1) % checkpoint_every == 0:
            from repro.checkpoint import save_pytree
            save_pytree(params, checkpoint_path)
    if checkpoint_path:
        from repro.checkpoint import save_pytree
        save_pytree(params, checkpoint_path)
    return hist
