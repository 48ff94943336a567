"""Readings that set the limits of a cell's check (not part of a run).

  python3 bench/control.py --workload <cell> --seeds 1 2 3 [--out FILE]

For each seed, one campaign of the program at the cell's own size is
compared with the reference (the lower readings), and so are, in the
program's place:

  control     the reference computed in bfloat16, the precision below
              the configuration's float32
  half_batch  the reference with half of each learner's samples left out
              and the mean taken over the rest
  unchanged   the initial params returned as the trained ones

Each line printed is one JSON object: the seed and, per reading, the
compared numbers of ``bench/check.py``.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def half_batch(train):
    """``train_learners`` with the second half of each shard left out (the
    rows are the mask's last axis, with or without a learner axis)."""
    import jax.numpy as jnp

    def broken(start, x, y, m, tau, lr, *, loss, dtype):
        keep = jnp.cumsum(m, axis=-1) <= jnp.ceil(m.sum(axis=-1, keepdims=True) / 2)
        return train(start, x, y, m * keep, tau, lr, loss=loss, dtype=dtype)

    return broken


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax.numpy as jnp
    from bench import check, reference
    from bench.harness import enable_cache, inputs, reference_of
    from bench.run import load_cell

    _, cfg, traffic, _, _, _ = load_cell(args.workload)
    enable_cache()
    lines = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        cell_in = inputs(cfg, traffic, seed)
        o = cell_in.runner.run(seed, 0)
        rows, p0, rp, racc = reference_of(o, cell_in, cfg, traffic)
        out = {"seed": seed, "program": check.numbers(o, rows, rp, racc, p0)}
        for name, kw in (("control", {"dtype": jnp.bfloat16}),
                         ("half_batch", {"train_override": half_batch(reference.train_learners)})):
            _, _, bp, bacc = reference_of(o, cell_in, cfg, traffic, **kw)
            gap, dev = check.param_numbers(p0, bp, rp)
            out[name] = {"param_gap": gap, "param_dev": dev,
                         "acc_gap": check.acc_gap(bacc, racc)}
        gap, dev = check.param_numbers(p0, p0, rp)
        out["unchanged"] = {"param_gap": gap, "param_dev": dev}
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
        lines.append(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(json.dumps(x) for x in lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
