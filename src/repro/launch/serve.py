"""Serving driver: prefill + batched greedy decode (example application).

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
      --reduced --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config, get_reduced
from repro.dist import sharding as shd
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.model import Model
from repro.train.serve import make_decode_step, make_prefill_step


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args(argv)

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = Model(cfg)
    mesh = make_host_mesh()
    rules = shd.make_rules(mesh)
    params, axes = model.init(jax.random.PRNGKey(0))
    params = jax.device_put(params, shd.tree_shardings(params, axes, mesh, rules))

    cache_len = args.prompt_len + args.gen
    rng = jax.random.PRNGKey(1)
    prompts = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size, jnp.int32)
    batch = {"tokens": prompts}
    if cfg.family == "encdec":
        batch["frames"] = 0.02 * jax.random.normal(
            rng, (args.batch, cfg.encoder_seq, cfg.d_model))
    if cfg.family == "vlm":
        batch["patch_embeds"] = 0.02 * jax.random.normal(
            rng, (args.batch, min(cfg.num_patches, args.prompt_len),
                  cfg.d_model))

    with mesh, shd.activation_sharding(mesh, rules):
        prefill = jax.jit(make_prefill_step(model, cache_len=cache_len))
        decode = jax.jit(make_decode_step(model), donate_argnums=(1,))
        t0 = time.time()
        logits, cache = prefill(params, batch)
        tok = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)[:, None]
        t_prefill = time.time() - t0
        out = [tok]
        t0 = time.time()
        for i in range(args.gen - 1):
            tok, cache, _ = decode(params, cache, tok, args.prompt_len + i)
            out.append(tok)
        t_decode = time.time() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"prefill {args.batch}×{args.prompt_len} in {t_prefill:.2f}s; "
          f"decode {args.gen - 1} steps in {t_decode:.2f}s "
          f"({(args.gen - 1) * args.batch / max(t_decode, 1e-9):.1f} tok/s)")
    print("generated ids (first row):", gen[0].tolist())
    return gen


if __name__ == "__main__":
    main()
