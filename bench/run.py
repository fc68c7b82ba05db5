"""One run of one benchmark cell of the served P²M path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the deployment's weights (``bench/references``) and a pool
of event streams (``bench/traffic/generator.py``) from the seed, builds
the serving engine on the cell's chips and warms it with one short
cohort that fills every lane. The window then drives
``StreamEngine.serve`` for about ``--seconds`` with the cell's traffic,
replayed from the pool. After the window the plain reference runs over
the pool and every served stream is compared with it
(``bench/compare.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` the same traffic runs with the profiler on over a
few seconds of windows, and the result carries the per-layer metrics
(``bench/metrics``) and the device's busy and window seconds. The last
line of standard output is the result as one JSON object; the numbers
compared, each with its limit, are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits non-zero.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / "artifacts" / "bench_trace"
TRACE_SECONDS = 1.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (all its bits count)."""
    import jax

    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


class _Window:
    """The ``serve(on_window=...)`` hook: stamps the window's start,
    counts compilations inside it, and brackets the traced windows."""

    def __init__(self, *, trace_windows: tuple[int, int] | None,
                 trace_dir: Path | None):
        self.t_first = None
        self.compiles = 0
        self.trace_windows = trace_windows
        self.trace_dir = trace_dir
        self._ann = None
        self._tracing = False

    def on_compile(self, name, _secs, **_kw):
        if name == COMPILE_EVENT and self.t_first is not None:
            self.compiles += 1

    def __call__(self, window: int) -> None:
        if window == 0:
            self.t_first = time.perf_counter()
        if self.trace_windows is None:
            return
        import jax

        if window == self.trace_windows[0]:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._tracing = True
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        elif window == self.trace_windows[1]:
            self.stop()

    def stop(self) -> None:
        if self._tracing:
            import jax

            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._tracing = False


def _lane_windows(results, lo: int, hi: int) -> int:
    """Occupied lane-windows of ``[lo, hi)``: a stream holds its lane
    from the window it was admitted to the window it finished."""
    return sum(max(0, min(r.finished_window, hi) - max(r.admitted_window, lo))
               for r in results)


def make_inputs(cfg: dict, mix: dict, seed: int, log=None):
    """Everything a run makes from its seed: the reference module, the
    deployment's weights (one jitted call on the device) and the stream
    pool, cut in the engine's replay chunks (one per sub-slot)."""
    import jax

    from bench import catalog
    from bench.traffic import generator

    t0 = time.perf_counter()
    key = seed_key(seed)
    ref_mod = catalog.reference(cfg["reference"])
    weights, bn_state = ref_mod.make_init(cfg["model"])(
        jax.random.fold_in(key, 0))
    jax.block_until_ready(weights)
    t1 = time.perf_counter()
    p2m = cfg["model"]["p2m"]
    slot_us = round(p2m["t_intg_ms"] * 1000 / p2m["n_sub"])
    pool = generator.make_pool(jax.random.fold_in(key, 1),
                               generator.StreamSpec.from_config(cfg),
                               int(mix["pool"]), slot_us=slot_us,
                               chunk_us=slot_us, log=log)
    if log is not None:
        log(f"set-up: weights {t1 - t0:.3f} s, pool "
            f"{time.perf_counter() - t1:.3f} s")
    return ref_mod, weights, bn_state, pool


def make_deployment(cfg: dict, weights, bn_state):
    """The program's servable deployment of a configuration, holding the
    benchmark's weights."""
    from repro.stream.deploy import Deployment, model_config_from_dict

    model_cfg = model_config_from_dict(cfg["model"])
    return Deployment(model_cfg=model_cfg, params=weights, bn_state=bn_state,
                      record={"label": cfg["name"],
                              "t_intg_ms": model_cfg.p2m.t_intg_ms,
                              "n_sub": model_cfg.p2m.n_sub})


def lane_placement(engine) -> list[tuple[int, int, str]]:
    """``(first lane, end lane, device)`` of each shard of the charge
    state as the engine's fold leaves it."""
    import jax.numpy as jnp

    h, w = engine.fns.in_hw
    cap = engine.padded_capacity
    x = engine.fns.fold(engine.fns.init_state(),
                        jnp.zeros((cap, engine.chunk_slots, h, w, 2)),
                        jnp.zeros((cap,), bool))["x"]
    return sorted((s.index[0].start or 0, s.index[0].stop or cap,
                   str(s.device)) for s in x.addressable_shards)


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             devices, engine_hook=None, log=print) -> dict:
    """Set up, measure and check one run of ``cell``
    (:func:`bench.catalog.cell`) on ``devices``; returns the result
    object. ``engine_hook(engine)`` may replace the engine's steps
    before the warm-up (the fault tests break the timed path so)."""
    import jax
    import numpy as np

    from bench import compare, catalog, trace as trace_mod, work
    from bench.traffic import generator
    from repro.stream.engine import StreamEngine
    from repro.stream.shard import make_lane_executor

    cfg, mix, params = cell["config"], cell["traffic"], cell["params"]
    chips = int(cell["entry"]["chips"])
    model = cfg["model"]
    capacity = int(params["capacity"])

    t0 = time.perf_counter()
    ref_mod, weights, bn_state, pool = make_inputs(cfg, mix, seed, log)
    dep = make_deployment(cfg, weights, bn_state)
    model_cfg = dep.model_cfg
    engine = StreamEngine(
        dep, capacity=capacity,
        executor=make_lane_executor(chips) if chips > 1 else None,
        bin_workers=params.get("bin_workers"))
    if engine_hook is not None:
        engine_hook(engine)
    placement = lane_placement(engine) if chips > 1 else None
    spec = pool.spec
    log(f"set-up: weights and {pool.size}-stream pool "
        f"({int(pool.n_events.sum())} events) in "
        f"{time.perf_counter() - t0:.3f} s")

    # warm-up: one cohort of one-coarse-window streams fills every lane,
    # so every fold/readout shape, reset_lane and the finalisation reads
    # are compiled before the clock starts; one stream more is admitted
    # once a lane frees, so reset_lane also meets the state as the folds
    # leave it (on a lane mesh: sharded)
    t_intg_s = model_cfg.p2m.t_intg_ms * 1e-3
    stamps: list[float] = []
    warm = generator.ReplaySource(pool,
                                  duration_ms=model_cfg.coarse_window_ms)
    t0 = time.perf_counter()
    warm_compiles = []
    count = lambda name, _s, **_k: warm_compiles.append(name)  # noqa: E731
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        engine.serve(warm, capacity + 1,
                     on_window=lambda w: stamps.append(time.perf_counter()))
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    cohort = stamps[:len(stamps) // 2]
    steps = np.diff(cohort[len(cohort) // 4:]) if len(cohort) > 4 else [0.0]
    window_s = float(np.median(steps))
    n_windows = int(round(spec.duration_ms / model_cfg.p2m.t_intg_ms))
    log(f"warm-up: {capacity} lanes x {len(stamps)} windows in "
        f"{time.perf_counter() - t0:.3f} s, median window "
        f"{window_s * 1e3:.3f} ms, "
        f"{warm_compiles.count(COMPILE_EVENT)} compiles")

    plan = generator.plan(mix, capacity=capacity,
                          duration_s=spec.duration_ms * 1e-3,
                          seconds=seconds, cohort_s=window_s * n_windows)
    per_window = t_intg_s if plan["paced"] else window_s
    trace_windows = None
    if trace:
        total = seconds / max(per_window, 1e-9)
        w0 = max(1, int(0.25 * total))
        trace_windows = (w0, w0 + max(1, int(
            min(TRACE_SECONDS, 0.5 * seconds) / max(per_window, 1e-9))))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    hook = _Window(trace_windows=trace_windows, trace_dir=TRACE_DIR)
    source = generator.ReplaySource(pool)
    gc.collect()
    gc.freeze()
    jax.monitoring.register_event_duration_secs_listener(hook.on_compile)
    try:
        report = engine.serve(source, plan["n_streams"], paced=plan["paced"],
                              offered_rate=plan["offered_rate"],
                              max_pending=plan["max_pending"],
                              on_window=hook)
    finally:
        hook.stop()
        jax.monitoring.unregister_event_duration_listener(hook.on_compile)
        gc.unfreeze()
    setup_s = hook.t_first - T_PROCESS
    log(f"window: {plan['n_streams']} streams on {capacity} lanes "
        f"({'paced' if plan['paced'] else 'unpaced'}), "
        f"{report.wall_s:.3f} s, {report.total_events} events, "
        f"{report.total_readouts} readouts")
    log(f"compiles_in_window={hook.compiles}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}

    n_lost = report.n_shed + report.n_rejected
    attempted = report.total_readouts + n_lost * n_windows
    failed = report.n_misses + n_lost * n_windows
    metrics: dict = {}
    breakdown = None
    if not trace:
        values = {"setup_s": setup_s,
                  "events_per_s": report.total_events / report.wall_s}
        if report.miss_margin_ms:
            lat = np.asarray(report.miss_margin_ms) + t_intg_s * 1e3
            # the 90th percentile: at the knee, stalls of a few hundred ms
            # put 4-8% of a run's readouts past the deadline in some runs
            # and none in others, so the 95th falls on either side of
            # them from run to run (PERF.md §2); misses count in `failed`
            values["readout_latency_p90_ms"] = float(np.percentile(lat, 90))
            log("latency: " + ", ".join(
                f"p{q} {np.percentile(lat, q):.3f} ms" for q in (50, 90, 95,
                                                               99))
                + f", {report.n_misses} of {lat.size} readouts missed")
        for m in cell["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        trace_data = summary = None
        try:
            trace_data = trace_mod.load(trace_mod.find(TRACE_DIR))
            summary = trace_mod.summarize(trace_data, n_devices=chips)
        except FileNotFoundError as e:
            log(f"trace: {e}")
        lo, hi = trace_windows
        hi = min(hi, len(report.readout_s))
        # host spans of the windows the profiler did not slow
        cpw = engine.chunks_per_window
        ctx = {"fold_s": [t for j, t in enumerate(report.fold_s)
                          if not lo <= j // cpw < hi],
               "readout_s": [t for j, t in enumerate(report.readout_s)
                             if not lo <= j < hi],
               "model": model, "chips": chips,
               "trace": summary, "trace_data": trace_data,
               "traced_windows": max(0, hi - lo),
               "lane_windows": _lane_windows(report.results, lo, hi),
               "peak": None}
        if summary is not None:
            ctx["peak"] = work.peak(kind)
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = summary["breakdown"]
        for m in cell["per_layer"]:
            v = catalog.metric(m["name"]).reduce(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the reference runs once the window has closed and the peak is read
    results = report.results
    del engine, report, dep
    gc.collect()
    t0 = time.perf_counter()
    fwd = compare.reference_forward(ref_mod, cfg)
    ref = jax.device_get(fwd(weights, bn_state, pool.counts))
    log(f"reference: {pool.size} streams in {time.perf_counter() - t0:.3f} s")
    verdict = compare.judge(results, pool, ref, params["limits"],
                            n_windows=n_windows)
    if placement is not None:
        log(f"lanes: {placement}")
        if len({dev for *_, dev in placement}) != chips:
            verdict["faults"].append(
                f"the lane state of {chips} chips lies on "
                f"{len({dev for *_, dev in placement})} device(s)")
            verdict["correct"] = False
    out = {"correct": verdict["correct"], "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    log(f"compared: {verdict['n_compared']} served streams against the "
        f"reference of their pool entries; widest stream logit error "
        f"{verdict['numbers'].get('logit_err_widest')}")
    out["checks"] = {k: {"value": verdict["numbers"].get(k),
                         "limit": params["limits"][k]}
                     for k in compare.NUMBERS}
    out["faults"] = verdict["faults"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro" / "stream" / "engine.py").is_file():
        print(f"bench: the program under test is not at {SRC}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(SRC)]
    from bench import catalog

    cell = catalog.cell(args.workload)
    # the TPU runtime logs under /tmp unless told otherwise; a run writes
    # only inside its checkout
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "artifacts" / "tpu_logs"))
    import jax

    devices = jax.devices()
    chips = int(cell["entry"]["chips"])
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices[:chips])
    faults = out.pop("faults")
    checks = out.pop("checks")
    out["checks"] = checks   # the numbers compared come last
    for f in faults:
        print(f"fault: {f}", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
