"""Benchmark of federated campaigns on the chip.

  python3 bench/run.py --workload <config>.<traffic> --seed <n> \
      --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs/``), its traffic mix
(``bench/traffic/``) and its metrics are found by name from
``BENCHMARK.json``; the limits of its check from ``bench/checks/``.
The last line of standard output is one JSON object; with ``--trace 0``
its metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics. Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def load_cell(name: str) -> tuple:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"unknown workload {name!r}")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((ROOT / "bench" / "checks" / f"{name}.json").read_text())
    has = lambda m: name in m.get("workloads", [name])
    e2e = [m for m in spec["end_to_end"] if has(m)]
    per_layer = [m for m in spec["per_layer"] if has(m)]
    return cell, cfg, traffic, limits, e2e, per_layer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, cfg, traffic, limits, e2e, per_layer = load_cell(args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    from bench import harness

    result = harness.run(cfg, traffic, limits, seed=args.seed,
                         seconds=args.seconds, trace=bool(args.trace),
                         t_start=T_START, e2e=e2e, per_layer=per_layer)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
