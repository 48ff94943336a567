"""Serving launcher: batched prefill + decode loop on real devices.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
      --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_reduced
from repro.launch.mesh import make_mesh_by_name
from repro.models.model import Model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default="cpu")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = Model(cfg)
    mesh = make_mesh_by_name(args.mesh)
    params = model.init(jax.random.key(args.seed))

    rng = np.random.default_rng(args.seed)
    b, s = args.batch, args.prompt_len
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)}
    extra = 0
    if cfg.family == "vlm":
        batch["image_embeds"] = jnp.asarray(
            rng.standard_normal((b, cfg.num_image_tokens, cfg.d_model)), jnp.float32
        ) * 0.02
        extra = cfg.num_image_tokens
    if cfg.family == "audio":
        batch["encoder_embeds"] = jnp.asarray(
            rng.standard_normal((b, cfg.encoder_seq, cfg.d_model)), jnp.float32
        ) * 0.02

    max_len = s + extra + args.gen
    prefill = jax.jit(lambda p, bt: model.prefill(p, bt, max_len=max_len))
    decode = jax.jit(model.decode)

    with jax.set_mesh(mesh):
        t0 = time.time()
        logits, cache, _aux = prefill(params, batch)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        print(f"prefill({b}x{s}) {time.time()-t0:.2f}s")
        out_tokens = [tok]
        cache_len = jnp.asarray(s + extra, jnp.int32)
        t0 = time.time()
        for i in range(args.gen - 1):
            logits, cache = decode(params, cache, tok, cache_len + i)
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            out_tokens.append(tok)
        dt = time.time() - t0
        toks = jnp.concatenate(out_tokens, axis=1)
        print(f"decoded {args.gen-1} steps in {dt:.2f}s "
              f"({(args.gen-1)*b/max(dt,1e-9):.1f} tok/s)")
        print("sample:", np.asarray(toks[0])[:16].tolist())


if __name__ == "__main__":
    main()
