"""Knee sweep: the most sensors a chip serves in real time.

    python3 bench/sweep_knee.py --config <config> --seed <n> --seconds <s>

Runs the ``paced`` mix at full load (evenly spaced connections, each
sensor streaming for its whole duration, so every lane is busy;
``serve(paced=True)``) at capacities 1, 2, 4, 8,
12, 16, 24, 32, 48, 64, 96, 128, … for ``--seconds`` each, and stops two
steps past the first capacity at which more than 1% of lane-readouts
miss their T_INTG deadline. The knee is the largest capacity at which at
least 99% meet it. The saturated cells take twice the knee, rounded up
to a multiple of 4 and at least 4; the paced cell takes the knee's
capacity at four fifths of its load. It prints one line per capacity
and, last, one JSON object with the ladder and the knee. Not a cell: it is run once, on the
chip, to fix the cells' capacities. Without a TPU it exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
LADDER = (1, 2, 4, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512,
          768, 1024)
MISS_LIMIT = 0.01


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np

    from bench import catalog, run
    from bench.traffic import generator
    from repro.stream.engine import StreamEngine

    if jax.devices()[0].platform != "tpu":
        print("sweep_knee: needs a TPU", file=sys.stderr)
        return 1
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = catalog.config(args.config)
    mix = catalog.traffic("paced")
    _, weights, bn_state, pool = run.make_inputs(cfg, mix, args.seed)
    dep = run.make_deployment(cfg, weights, bn_state)
    t_intg_ms = dep.model_cfg.p2m.t_intg_ms
    rows, first_miss = [], None
    for i, cap in enumerate(LADDER):
        engine = StreamEngine(dep, capacity=cap)
        engine.serve(generator.ReplaySource(
            pool, duration_ms=dep.model_cfg.coarse_window_ms), cap)
        plan = generator.plan({**mix, "load": 1.0}, capacity=cap,
                              duration_s=pool.spec.duration_ms * 1e-3,
                              seconds=args.seconds, cohort_s=0.0)
        rep = engine.serve(generator.ReplaySource(pool), plan["n_streams"],
                           paced=True, offered_rate=plan["offered_rate"],
                           max_pending=plan["max_pending"])
        lat = np.asarray(rep.miss_margin_ms) + t_intg_ms
        n = len(lat) + (rep.n_shed + rep.n_rejected) * pool.counts.shape[1] \
            // dep.model_cfg.p2m.n_sub
        miss = (rep.n_misses + n - len(lat)) / max(n, 1)
        row = {"capacity": cap, "readouts": int(n), "miss_share": miss,
               "shed": rep.n_shed,
               "latency_p50_ms": float(np.percentile(lat, 50)),
               "latency_p95_ms": float(np.percentile(lat, 95)),
               "events_per_s": rep.total_events / rep.wall_s}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if miss > MISS_LIMIT and first_miss is None:
            first_miss = i
        if first_miss is not None and i >= first_miss + 2:
            break
        del engine
    ok = [r["capacity"] for r in rows if r["miss_share"] <= MISS_LIMIT]
    knee = max(ok) if ok else 0
    print(json.dumps({"config": args.config, "seed": args.seed,
                      "seconds": args.seconds, "knee": knee,
                      "saturated_capacity": max(4, -(-2 * knee // 4) * 4),
                      "ladder": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
