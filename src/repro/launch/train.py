"""Training launcher.

Runs real steps on the available devices (CPU-sized configs) or, with
``--dryrun``, only lowers+compiles for the production mesh. For the
federated MEL path use ``examples/train_mnist_fed.py`` — this launcher is
the *dense-pod* trainer the allocator schedules across pods.

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --steps 20 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_reduced
from repro.checkpoint import checkpoint as ckpt
from repro.data.pipeline import token_batches
from repro.launch.mesh import make_mesh_by_name
from repro.launch.steps import build_train
from repro.models.model import Model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="cpu")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--save", default=None)
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = Model(cfg)
    mesh = make_mesh_by_name(args.mesh)

    step, (pshard, oshard, batch_sh), out_sh, _ = build_train(model, mesh)
    from repro.optim.optimizers import get_optimizer

    opt = get_optimizer(cfg.optimizer, cfg.learning_rate)
    params = model.init(jax.random.key(args.seed))
    opt_state = opt.init(params)

    rng = np.random.default_rng(args.seed)
    gen = token_batches(rng, args.batch, args.seq + 1, cfg.vocab_size)

    def with_extras(b):
        if cfg.family == "vlm":
            b = dict(b)
            b["tokens"] = b["tokens"][:, : args.seq - cfg.num_image_tokens]
            b["labels"] = b["labels"][:, : args.seq - cfg.num_image_tokens]
            b["image_embeds"] = rng.standard_normal(
                (args.batch, cfg.num_image_tokens, cfg.d_model)
            ).astype(np.float32) * 0.02
        if cfg.family == "audio":
            b = dict(b)
            b["encoder_embeds"] = rng.standard_normal(
                (args.batch, cfg.encoder_seq, cfg.d_model)
            ).astype(np.float32) * 0.02
        return b

    jitted = jax.jit(step)
    with jax.set_mesh(mesh):
        for i in range(args.steps):
            t0 = time.time()
            batch = {k: jnp.asarray(v) for k, v in with_extras(next(gen)).items()}
            params, opt_state, metrics = jitted(params, opt_state, batch)
            if i % args.log_every == 0:
                print(
                    f"step {i:4d} loss={float(metrics['loss']):.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f} {time.time()-t0:.2f}s",
                    flush=True,
                )
    if args.save:
        ckpt.save(args.save, params, step=args.steps)
        print(f"saved params -> {args.save}")


if __name__ == "__main__":
    main()
