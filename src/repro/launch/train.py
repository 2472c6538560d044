"""End-to-end LM training driver: ``python -m repro.launch.train --arch <id>``.

Production-shaped loop: mesh + logical-rules sharding, grad-accumulation
train step, async prefetching loader, checkpoint/restart (elastic across
mesh changes), preemption hook, straggler mitigation, and optional int8
gradient compression on the pod axis.

Straggler policy: on a real fleet the per-step all-reduce synchronises
everyone, so a straggling host shows up as step-time skew.  The loop tracks
a robust step-time EMA; steps slower than ``straggler_factor`` x EMA are
logged and counted, and after ``max_straggler_steps`` consecutive hits the
driver checkpoints and exits with code 75 (EX_TEMPFAIL) so the scheduler can
reschedule/reshape the job — the standard recover-by-restart path (elastic
restore then continues on whatever mesh the new allocation provides).
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.data.pipeline import PrefetchingLoader, synthetic_lm_batches
from repro.distributed.sharding import ShardingRules, tree_shardings, use_rules
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import transformer as T
from repro.training.checkpoint import CheckpointManager
from repro.training.lm import TrainSettings, make_train_step
from repro.training.optimizer import Adam, cosine_warmup_schedule


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--scale", type=float, default=1.0, help="width multiplier on the smoke config")
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--compress-pod-grads", action="store_true")
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    ap.add_argument("--max-straggler-steps", type=int, default=10)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
        if args.scale != 1.0:
            s = args.scale
            cfg = cfg.replace(
                d_model=int(cfg.d_model * s),
                d_ff=int(cfg.d_ff * s),
                head_dim=int(cfg.head_dim * s),
                vocab=max(cfg.vocab, 1024),
            )
    mesh = (
        make_production_mesh(multi_pod=args.multi_pod)
        if args.production_mesh
        else make_host_mesh()
    )
    rules = ShardingRules(mesh)
    print(f"arch={cfg.name} params~{T.param_count(cfg)/1e6:.1f}M mesh={dict(mesh.shape)}")

    opt = Adam(lr=cosine_warmup_schedule(args.lr, warmup=args.warmup, total=args.steps))
    step_fn = make_train_step(
        cfg, opt, TrainSettings(n_micro=args.n_micro, compress_pod_grads=args.compress_pod_grads)
    )

    with mesh, use_rules(rules):
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        pshard = tree_shardings(rules, T.abstract_params(cfg), T.logical_axes(cfg))
        params = jax.tree_util.tree_map(lambda x, s: jax.device_put(x, s), params, pshard)
        opt_state = opt.init(params)

        ckpt = CheckpointManager(Path(args.ckpt_dir) / cfg.name.replace("/", "_"), save_every=args.ckpt_every)
        start_step, (params, opt_state) = ckpt.maybe_restore((params, opt_state))
        state_ref = {"step": start_step, "params": params, "opt": opt_state}
        ckpt.install_preemption_hook(lambda: (state_ref["step"], (state_ref["params"], state_ref["opt"])))

        bshard = rules.sharding(("batch", "seq"), dims=(args.batch, args.seq))
        loader = PrefetchingLoader(
            synthetic_lm_batches(cfg.vocab, args.batch, args.seq, n_steps=args.steps - start_step),
            sharding=bshard,
        )
        jstep = jax.jit(step_fn, donate_argnums=(0, 1))

        ema = None
        stragglers = 0
        losses = []
        t_start = time.time()
        for i, batch in enumerate(loader):
            step = start_step + i
            t0 = time.time()
            params, opt_state, metrics = jstep(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            state_ref.update(step=step + 1, params=params, opt=opt_state)
            losses.append(loss)
            ema = dt if ema is None else (0.9 * ema + 0.1 * dt) if i > 2 else dt
            if i > 5 and dt > args.straggler_factor * ema:
                stragglers += 1
                print(f"[straggler] step {step}: {dt:.2f}s vs ema {ema:.2f}s ({stragglers})")
                if stragglers >= args.max_straggler_steps:
                    ckpt.save(step + 1, (params, opt_state), extra={"straggler_exit": True})
                    print("[straggler] persistent skew -> checkpoint + EX_TEMPFAIL")
                    raise SystemExit(75)
            else:
                stragglers = 0
            if ckpt.should_save(step + 1):
                ckpt.save(step + 1, (params, opt_state))
            if step % args.log_every == 0:
                print(f"step {step}: loss={loss:.4f} ({dt:.2f}s/step)")
        n = len(losses)
        print(
            f"done: {n} steps in {time.time()-t_start:.1f}s; "
            f"loss {losses[0]:.4f} -> {np.mean(losses[-10:]):.4f}"
        )
        ckpt.save(start_step + n, (params, opt_state))
        loader.close()
    return losses


if __name__ == "__main__":
    main()
