"""End-to-end training driver (example application (b) of the deliverables).

Runs a real training loop on the current host's devices (CPU in this
container, TPU pod in production — same code path: the mesh adapts).
Fault tolerance is live twice over: checkpoints every
``--checkpoint-every`` steps with auto-resume (including the
data-pipeline cursor), and *checkpoint-free* elasticity —
``--kill-device-at K`` simulates losing a device at step K, after which
:func:`remesh_live_state` re-plans the mesh from the survivors
(``dist.fault.elastic_plan``: model axis preserved, data axis shrunk to
a power of two) and ``device_put``s the live param/optimizer trees onto
it, mid-run, without reading a checkpoint back (DESIGN.md §4.4 — the
training-side twin of ``plug.Middleware.migrate``).

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
      --reduced --steps 200 --batch 8 --seq 128 --checkpoint-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config, get_reduced
from repro.dist import fault, sharding as shd
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.model import Model
from repro.train import checkpoint as ckpt
from repro.train.data import SyntheticLM
from repro.train.optimizer import AdamW, AdamWConfig
from repro.train.step import init_wire_state, make_train_step


def remesh_live_state(params, opt_state, axes, opt_axes, survivors):
    """Checkpoint-free migration of live training state onto survivors.

    Plans the survivor mesh with ``dist.fault.elastic_plan`` (the model
    axis of the current mesh is preserved exactly — model parallelism is
    load-bearing — and the data axis shrinks to the largest power of two
    that fits), then ``device_put``s the live parameter and optimizer
    pytrees onto it under the re-derived sharding rules.  Nothing is
    read back from disk: every parameter shard still lives on at least
    one survivor (data-parallel replicas; fully-sharded dims re-gather
    through XLA's resharding transfer), which is exactly the plug
    middleware's migration story applied to training state.

    Args:
      params, opt_state: live (device-resident) pytrees.
      axes, opt_axes: their logical-axis pytrees (``model.init`` /
        ``optimizer.state_axes``).
      survivors: the devices still alive, in a deterministic order.
    Returns:
      ``(mesh, rules, params, opt_state)`` on the survivor mesh.
    """
    model_parallel = 1
    for leaf in jax.tree.leaves(params):
        sh = getattr(leaf, "sharding", None)
        if sh is not None and "model" in getattr(sh.mesh, "axis_names", ()):
            model_parallel = sh.mesh.shape["model"]
            break
    plan = fault.elastic_plan(len(survivors), model_parallel=model_parallel)
    mesh = shd.make_mesh(plan.shape, plan.axis_names,
                         devices=survivors[:plan.size])
    rules = shd.make_rules(mesh)
    params = jax.device_put(params,
                            shd.tree_shardings(params, axes, mesh, rules))
    opt_state = jax.device_put(
        opt_state, shd.tree_shardings(opt_state, opt_axes, mesh, rules))
    return mesh, rules, params, opt_state


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-feasible)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--kill-device-at", type=int, default=None,
                    help="simulate losing one device at this step: elastic "
                         "re-mesh + checkpoint-free migration of the live "
                         "param/optimizer state onto the survivors")
    ap.add_argument("--grad-wire", choices=("none", "int8"), default="none",
                    help="compress the gradient through the int8 "
                         "error-feedback wire round of dist.collectives "
                         "before the optimizer (residuals live with the "
                         "run, not the checkpoint)")
    ap.add_argument("--grad-wire-bits", type=int, default=8)
    args = ap.parse_args(argv)

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = Model(cfg)
    mesh = make_host_mesh()
    rules = shd.make_rules(mesh)

    params, axes = model.init(jax.random.PRNGKey(0))
    p_sh = shd.tree_shardings(params, axes, mesh, rules)
    params = jax.device_put(params, p_sh)

    opt = AdamW(AdamWConfig(peak_lr=args.lr, total_steps=args.steps,
                            warmup_steps=max(args.steps // 20, 1)))
    opt_state = opt.init(params)
    o_sh = shd.tree_shardings(opt_state, opt.state_axes(axes), mesh, rules)
    opt_state = jax.device_put(opt_state, o_sh)

    data = SyntheticLM(cfg.vocab_size, args.seq, args.batch)
    start_step = 0
    manager = None
    if args.checkpoint_dir:
        manager = ckpt.CheckpointManager(args.checkpoint_dir,
                                         every=args.checkpoint_every)
        restored = manager.restore_or_none(
            like_params=params, like_opt=opt_state,
            shardings=p_sh, opt_shardings=o_sh)
        if restored:
            params, opt_state = restored["params"], restored["opt_state"]
            data.load_state_dict(restored["data_state"])
            start_step = restored["step"]
            print(f"resumed from step {start_step}")

    wire = None if args.grad_wire == "none" else args.grad_wire
    step_fn = make_train_step(model, opt, microbatches=args.microbatches,
                              grad_wire=wire,
                              grad_wire_bits=args.grad_wire_bits)
    wire_state = None
    if wire:
        jitted = jax.jit(step_fn, donate_argnums=(0, 1, 2))
        wire_state = jax.device_put(
            init_wire_state(params),
            shd.tree_shardings(init_wire_state(params), axes, mesh, rules))
    else:
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    losses = []
    t0 = time.time()

    def run_steps(lo, hi, mesh, rules, params, opt_state, wire_state):
        with mesh, shd.activation_sharding(mesh, rules):
            for step in range(lo, hi):
                batch = {k: jax.numpy.asarray(v)
                         for k, v in data.next_batch().items()}
                if cfg.family == "encdec":
                    batch["frames"] = 0.02 * jax.random.normal(
                        jax.random.PRNGKey(step),
                        (args.batch, cfg.encoder_seq, cfg.d_model))
                if cfg.family == "vlm":
                    batch["patch_embeds"] = 0.02 * jax.random.normal(
                        jax.random.PRNGKey(step),
                        (args.batch, cfg.num_patches, cfg.d_model))
                if wire_state is None:
                    params, opt_state, metrics = jitted(params, opt_state,
                                                        batch)
                else:
                    params, opt_state, wire_state, metrics = jitted(
                        params, opt_state, wire_state, batch)
                losses.append(float(metrics["loss"]))
                if step % args.log_every == 0 or step == args.steps - 1:
                    dt = time.time() - t0
                    print(f"step {step:5d} loss {losses[-1]:.4f} "
                          f"lr {float(metrics['lr']):.2e} "
                          f"gnorm {float(metrics['grad_norm']):.3f} "
                          f"({dt:.1f}s)", flush=True)
                if manager:
                    manager.maybe_save(step + 1, params=params,
                                       opt_state=opt_state,
                                       data_state=data.state_dict())
        return params, opt_state, wire_state

    kill = args.kill_device_at
    if kill is not None and start_step < kill < args.steps:
        params, opt_state, wire_state = run_steps(
            start_step, kill, mesh, rules, params, opt_state, wire_state)
        devices = list(mesh.devices.flat)
        survivors = devices[:-1]  # lose the mesh's last device
        t_mig = time.time()
        mesh, rules, params, opt_state = remesh_live_state(
            params, opt_state, axes, opt.state_axes(axes), survivors)
        if wire_state is not None:
            # the EF residuals migrate with the params (same axes tree)
            wire_state = jax.device_put(
                wire_state, shd.tree_shardings(wire_state, axes, mesh, rules))
        print(f"step {kill:5d} device lost → survivor mesh "
              f"{dict(mesh.shape)} over {mesh.devices.size}/{len(devices)} "
              f"devices, live state migrated checkpoint-free "
              f"({time.time() - t_mig:.2f}s)", flush=True)
        params, opt_state, wire_state = run_steps(
            kill, args.steps, mesh, rules, params, opt_state, wire_state)
    else:
        params, opt_state, wire_state = run_steps(
            start_step, args.steps, mesh, rules, params, opt_state,
            wire_state)
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    print(f"loss: first5={first:.4f} last5={last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    return losses


if __name__ == "__main__":
    main()
