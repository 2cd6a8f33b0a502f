"""Training launcher: fault-tolerant loop with checkpoint/restart, straggler
watchdog, and mesh-aware sharding.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --reduced \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Restart semantics: on start, restore the newest committed checkpoint (params,
optimizer, data-pipeline state) and continue; kill -9 at any point loses at
most `ckpt_every` steps. The watchdog flags steps slower than
``straggler_factor`` x the running median — on a real pod this feeds the
controller that evicts/replaces the slow host; here it logs and counts.
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

import jax

from repro.ckpt.checkpoint import Checkpointer
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.data.pipeline import DataState, Prefetcher, SyntheticTokens
from repro.dist import sharding
from repro.launch.mesh import make_host_mesh
from repro.nn.model import build_model
from repro.train.optimizer import OptConfig
from repro.train.step import init_train_state, make_train_step


class StepWatchdog:
    """Straggler mitigation, single-host flavor: detect slow steps, attribute
    them (data-starved vs compute), and surface counters for the controller."""

    def __init__(self, factor: float = 3.0, window: int = 50):
        self.factor = factor
        self.times: list[float] = []
        self.window = window
        self.stragglers = 0
        self.data_starved = 0

    def observe(self, dt: float, queue_depth: int) -> bool:
        self.times.append(dt)
        self.times = self.times[-self.window:]
        if len(self.times) >= 5:
            med = statistics.median(self.times)
            if dt > self.factor * med:
                self.stragglers += 1
                if queue_depth == 0:
                    self.data_starved += 1
                return True
        return False


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--moment-dtype", default="float32", choices=["float32", "int8"])
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    opt_cfg = OptConfig(lr=args.lr, total_steps=max(args.steps, 10),
                        warmup_steps=max(args.steps // 10, 2),
                        moment_dtype=args.moment_dtype)

    mesh = make_host_mesh(args.dp, args.tp)
    train_step = make_train_step(model, opt_cfg, microbatches=args.microbatches,
                                 compress_grads=args.compress_grads, mesh=mesh)

    key = jax.random.PRNGKey(args.seed)
    with jax.set_mesh(mesh):
        state = init_train_state(model, opt_cfg, key, mesh=mesh)

    data_state = DataState()
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if ckpt is not None:
        restored = ckpt.restore_latest(state)
        if restored is not None:
            start_step, state, extra = restored
            data_state = DataState.from_dict(extra.get("data", {}))
            print(f"[train] restored checkpoint at step {start_step}")

    source = SyntheticTokens(cfg.vocab, args.batch, args.seq, seed=args.seed)
    data_state.step = start_step
    prefetch = Prefetcher(source, data_state, depth=2)
    watchdog = StepWatchdog()

    jstep = jax.jit(train_step, donate_argnums=(0,))
    losses = []
    t_start = time.perf_counter()
    with jax.set_mesh(mesh):
        for step in range(start_step, args.steps):
            t0 = time.perf_counter()
            batch = prefetch.get()
            batch = jax.device_put(batch, sharding.batch_shardings(mesh, batch))
            state, metrics = jstep(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.perf_counter() - t0
            if watchdog.observe(dt, prefetch.depth):
                print(f"[watchdog] step {step}: straggler ({dt:.2f}s, "
                      f"queue={prefetch.depth})")
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step {step} loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} ({dt:.2f}s)")
            if ckpt is not None and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, state,
                          extra={"data": data_state.as_dict()})
    if ckpt is not None:
        ckpt.save(args.steps, state, extra={"data": data_state.as_dict()},
                  async_=False)
        ckpt.wait()
    prefetch.stop()

    wall = time.perf_counter() - t_start
    tokens = (args.steps - start_step) * args.batch * args.seq
    result = {
        "arch": cfg.name,
        "first_loss": losses[0] if losses else None,
        "final_loss": losses[-1] if losses else None,
        "losses": losses,
        "steps": args.steps,
        "tokens_per_s": tokens / wall,
        "stragglers": watchdog.stragglers,
    }
    print("[train] done:", json.dumps(result))
    return result


if __name__ == "__main__":
    main()
