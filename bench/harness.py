"""One benchmark run of one cell: set-up, the timed window, the check.

``run`` is the whole run after the command line and the chip check, so
the tests can drive it on the CPU at a tiny size. The end-to-end metrics
come from the host clock around campaigns that end in
``block_until_ready``; the per-layer metrics from a profiler trace of
the same window in a run of its own (``trace=True``).
"""

from __future__ import annotations

import importlib
import shutil
import statistics
import sys
import time
import types
from pathlib import Path

import numpy as np

from bench import check, models, reference, trace_reduce, work
from bench.campaign import Runner
from bench.fleet import build_fleet, sub_seed

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str, *, out=sys.stderr) -> None:
    print(msg, file=out, flush=True)


class CompileCounter:
    """Counts programs lowered (each jit cache miss) and backend compiles."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.lowered = self.compiled = 0
        self._events = (dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
                        dispatch.BACKEND_COMPILE_EVENT)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self._events[0]:
            self.lowered += 1
        elif event == self._events[1]:
            self.compiled += 1


def enable_cache() -> str:
    """The program's persistent compilation cache, with every program
    kept, so that only a checkout's first run of a cell compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def inputs(cfg: dict, traffic: dict, seed: int) -> types.SimpleNamespace:
    """The cell's learner model family, fleet, data and weights generator,
    and the runner that runs its campaigns on the program."""
    import jax.numpy as jnp
    from repro.data.pipeline import Dataset

    model = models.load(cfg)
    fleet = build_fleet(cfg)
    xtr, ytr, xte, yte = model.data(cfg, sub_seed(seed, 0))
    train = Dataset(xtr, ytr)
    eval_dev = (jnp.asarray(xte), jnp.asarray(yte))
    init = model.init(cfg)
    return types.SimpleNamespace(
        model=model, fleet=fleet, train=train, eval=eval_dev, init=init,
        runner=Runner(cfg, traffic, fleet, train, eval_dev, init))


def reference_of(o, cell_in, cfg: dict, traffic: dict, **kw):
    """The reference's schedule, init, params and accuracies for the
    campaign ``o`` ran (``kw`` goes to ``reference.run_campaign``)."""
    import jax

    rows = cell_in.runner.kind.schedule(cell_in.fleet, traffic, o.engine_seed,
                                        cell_in.train.size)
    p0 = jax.tree_util.tree_map(np.asarray, cell_in.init(o.init_seed))
    rp, racc = reference.run_campaign(
        rows, p0, cell_in.train.x, cell_in.train.y, *cell_in.eval,
        model=cell_in.model, lr=cfg["train"]["lr"],
        gamma=cfg["train"]["staleness_gamma"], **kw)
    return rows, p0, rp, racc


def _offload(outs: list, moving: list | None) -> list:
    """Moves finished campaigns' params off the device, one campaign
    behind: the params whose host copy started at the previous call land
    as NumPy, which frees their device buffers, and the copy of those
    still on the device starts (at the first call every finished
    campaign's, then the newest's). Returns the campaigns being copied."""
    import jax

    for o in moving or ():
        o.params = jax.tree_util.tree_map(np.asarray, o.params)
    moving = outs[-1:] if moving is not None else list(outs)
    for leaf in jax.tree_util.tree_leaves([o.params for o in moving]):
        leaf.copy_to_host_async()
    return moving


def run(cfg: dict, traffic: dict, limits: dict, *, seed: int,
        seconds: float, trace: bool, t_start: float, e2e: list, per_layer: list) -> dict:
    import jax

    counter = CompileCounter()
    enable_cache()
    from repro.fed.async_engine import staging_cache_stats

    dev = jax.devices()[0]
    peak = work.peaks(dev.device_kind)
    cell_in = inputs(cfg, traffic, seed)
    runner = cell_in.runner

    warm = runner.run(seed, -1)
    jax.block_until_ready(warm.params)
    del warm
    setup_s = time.perf_counter() - t_start
    lowered0, compiled0 = counter.lowered, counter.compiled
    hits0 = staging_cache_stats()["hits"]
    log(f"setup: {setup_s:.3f} s ({counter.lowered} programs lowered, "
        f"{counter.compiled} compiled)")

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    outs, attempted, failed = [], 0, 0
    budget = work.memory_budget()
    held, moving = 0, None
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            attempted += 1
            t = time.perf_counter()
            try:
                o = runner.run(seed, attempted - 1)
            except ValueError as e:             # e.g. an infeasible campaign
                failed += 1
                log(f"campaign {attempted - 1} failed: {e}")
            else:
                o.seconds = time.perf_counter() - t
                outs.append(o)
                held += work.tree_bytes(o.params)
                if moving is not None or held > budget:
                    moving = _offload(outs, moving)
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    in_window = (counter.lowered - lowered0, counter.compiled - compiled0)
    hits = staging_cache_stats()["hits"] - hits0
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in jax.devices())

    secs = [o.seconds for o in outs]
    useful = sum(o.useful for o in outs)
    log(f"window: {window_s:.3f} s, {len(outs)} campaigns completed of "
        f"{attempted} ({failed} failed); per campaign median "
        f"{statistics.median(secs) if secs else float('nan'):.4f} s, max "
        f"{max(secs, default=float('nan')):.4f} s; useful sample-steps {useful}",
        out=sys.stdout)
    log(f"compiles in window: {in_window[0]} lowered, {in_window[1]} compiled; "
        f"staging-cache hits in window: {hits}", out=sys.stdout)
    log(f"memory budget: {budget / 1e9:.4g} GB; finished campaigns' params "
        f"{held / 1e9:.4g} GB, {'moved to the host past it' if moving else 'kept on the device'}")

    # the check: free the program's state, then the reference
    rng = np.random.default_rng(sub_seed(seed, 7))
    n_check = min(int(traffic["checked_campaigns"]), len(outs))
    picked = sorted(rng.choice(len(outs), n_check, replace=False).tolist()) if outs else []
    checked = []
    for j in picked:
        o = outs[j]
        o.params = jax.tree_util.tree_map(np.asarray, o.params)
        checked.append(o)
    for o in outs:
        if o not in checked:
            o.params = None
    t_ref = time.perf_counter()
    readings = []
    for o in checked:
        rows, p0, rp, racc = reference_of(o, cell_in, cfg, traffic)
        readings.append(check.numbers(o, rows, rp, racc, p0))
        log(f"checked campaign {o.index}: " + ", ".join(
            f"{k} {v:.6g}" for k, v in readings[-1].items()))
    log(f"reference: {len(checked)} campaigns in {time.perf_counter() - t_ref:.3f} s "
        f"(memory budget {work.memory_budget() / 1e9:.4g} GB)")
    # the window's own invariants: every campaign completes, none replays a
    # staged schedule, nothing is lowered or compiled
    window_numbers = {"failed_campaigns": failed, "staging_hits": hits,
                      "window_compiles": in_window[0] + in_window[1]}
    values = check.worst(readings) if readings else dict.fromkeys(limits)
    correct, shown = check.verdict({**values, **window_numbers},
                                   {**limits, **dict.fromkeys(window_numbers, 0)})

    result = {"correct": correct, "attempted": attempted, "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    if not trace:
        rate = useful / window_s
        values = {"sample_steps_per_s": rate,
                  "mfu": (100.0 * rate * cell_in.model.flops_per_sample_step(cfg)
                          / peak["flops_per_s"]),
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in e2e}
    else:
        t_tr = time.perf_counter()
        tr = trace_reduce.load(trace_reduce.find_xplane(str(TRACE_DIR)))
        lo, hi = trace_reduce.window(tr)
        device.update(busy_s=trace_reduce.busy(tr, lo, hi) * 1e-9,
                      window_s=(hi - lo) * 1e-9)
        ctx = types.SimpleNamespace(trace=tr, program=traffic["program"],
                                    campaigns=outs, model=cell_in.model, cfg=cfg,
                                    peak=peak)
        metrics = {}
        for m in per_layer:
            value = importlib.import_module(f"bench.metrics.{m['name']}").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = trace_reduce.breakdown(tr)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t_tr:.3f} s")
    result["device"] = device
    result["checks"] = shown
    for name, v in shown.items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    return result
