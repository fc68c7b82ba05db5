"""Sweep launcher.

Default mode — the batched CO-DESIGN sweep (paper Fig 2/4 + Table 1): one
in-process, vmap-batched run over the circuit-VARIANT grid × T_INTG via
repro.core.sweep, emitting ONE structured JSON artifact (schema
"p2m-codesign-sweep/v3", see docs/sweep.md). The variant axes come from
the registry in repro.core.variant_grid: ``--axes`` activates any of
``mismatch`` / ``v-threshold`` / ``sigma`` / ``n-sub`` with its default
value grid, and each axis also has an explicit value flag. ``--devices n``
shards the stacked variant axis over a 1-D device mesh (on CPU force host
devices with XLA_FLAGS=--xla_force_host_platform_device_count=n);
sharded and single-device runs emit identical records. --protocol picks
the phase-2 finetune protocol(s): "frozen" (paper §3 — layer 1 fixed),
"unfrozen" (each circuit variant learns its own layer-1 weights), or
"both" (default: one shared pretrain, records for both protocols in one
artifact so the co-design optimum can be compared). ``--dataset`` picks
the event source (repro.data.sources): the synthetic generators by
default, or the file-backed DVS128-Gesture / N-MNIST loaders with
``--data-root`` pointing at the dataset directory (docs/datasets.md):

  PYTHONPATH=src python -m repro.launch.sweep --grid paper
  PYTHONPATH=src python -m repro.launch.sweep --grid fast --protocol frozen
  PYTHONPATH=src python -m repro.launch.sweep --grid paper \\
      --circuits a c --t-intg 1 10 100 1000 --mismatch 0.02 0.06
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python -m repro.launch.sweep --grid fast \\
      --axes v-threshold sigma --devices 8
  PYTHONPATH=src python -m repro.launch.sweep --grid fast \\
      --dataset dvs128 --data-root /data/DvsGesture

Legacy mode — the dry-run cell sweep (one subprocess per arch × shape ×
pods cell so XLA state never accumulates across the 60+ compiles;
resumable — cells with an existing 'ok'/'skipped' JSON are not re-run
unless --force):

  PYTHONPATH=src python -m repro.launch.sweep --dryrun-cells --pods 1 2
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

# Make the CLI runnable from any cwd: resolve the package root relative to
# THIS file instead of assuming the repo root is the working directory.
# (When repro is pip-installed this resolves inside site-packages, which is
# already importable — the insert is then a harmless no-op entry.)
_SRC = str(Path(__file__).resolve().parents[2])
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)


# ---------------------------------------------------------------------------
# co-design grid sweep (default) — built on repro.core.sweep
# ---------------------------------------------------------------------------

def run_codesign_grid(args) -> int:
    from dataclasses import replace

    from repro.core import sweep as engine
    from repro.core import variant_grid
    from repro.core.leakage import CircuitConfig
    from repro.core.sweep_exec import make_executor

    fast = args.grid == "fast"
    try:
        data, model, sweep_cfg, grid = engine.paper_setup(
            fast=fast, hw=args.hw, dataset=args.dataset,
            data_root=args.data_root)
    except (ValueError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    # file-backed datasets: eval on the held-out split so record
    # accuracies are out-of-sample (synthetic streams have no split)
    from repro.data import sources as sources_mod
    eval_data, eval_split = sources_mod.resolve_eval_dataset(
        args.dataset, hw=args.hw, data_root=args.data_root)
    if eval_split == "train":
        print("note: val split of the dataset is empty — evaluating on "
              "the training split", file=sys.stderr)
    if args.circuits:
        grid = replace(grid, circuits=tuple(
            CircuitConfig(c) for c in args.circuits))
    if args.t_intg:
        grid = replace(grid, t_intg_grid_ms=tuple(sorted(args.t_intg)))

    # variant axes: an explicit value flag wins; --axes <name> activates the
    # axis with its registry default grid. null_mismatch keeps its preset
    # default (0.06) when untouched — the PR-1 grid.
    explicit = {"null_mismatch": args.mismatch,
                "v_threshold": args.v_threshold,
                "sigma": args.sigma,
                "n_sub": args.n_sub}
    active = {variant_grid.axis("null-mismatch" if n == "mismatch" else n
                                ).name for n in (args.axes or [])}
    overrides = {}
    for name, vals in explicit.items():
        if vals is None and name in active:
            vals = variant_grid.axis(name).cli_defaults
        if vals is not None:
            try:
                overrides[name] = variant_grid.check_values(name, vals)
            except ValueError as e:
                print(f"error: {e}", file=sys.stderr)
                return 2
    grid = replace(grid, **overrides)
    mismatch_requested = args.mismatch is not None or \
        "null_mismatch" in active
    if mismatch_requested and CircuitConfig.NULLIFIED not in grid.circuits:
        print("note: the mismatch axis only affects circuit (c), which is "
              "not in this grid — values ignored", file=sys.stderr)

    for t in grid.t_intg_grid_ms:
        g = model.coarse_window_ms / t
        if abs(g - round(g)) > 1e-6:
            print(f"error: --t-intg {t:g} must divide the backbone coarse "
                  f"window ({model.coarse_window_ms:g} ms)", file=sys.stderr)
            return 2

    protocols = engine.resolve_protocols(args.protocol)
    try:
        executor = make_executor(args.devices)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    t0 = time.time()
    results = engine.run_protocols(data, model, sweep_cfg, grid,
                                   protocols=protocols, executor=executor,
                                   eval_data=eval_data)
    wall_s = time.time() - t0

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"codesign_grid_{args.grid}.json"
    artifact = engine.protocols_artifact(results, extra_meta={
        "wall_s": wall_s,
        "devices": executor.devices,
        "data": {"name": data.name, "dataset": args.dataset,
                 "data_root": args.data_root, "hw": data.height,
                 "n_classes": data.n_classes,
                 "duration_ms": data.duration_ms,
                 "eval_split": eval_split},
        "sweep": {"batch_size": sweep_cfg.batch_size,
                  "pretrain_steps": sweep_cfg.pretrain_steps,
                  "finetune_steps": sweep_cfg.finetune_steps,
                  "eval_batches": sweep_cfg.eval_batches},
    })
    path.write_text(json.dumps(artifact, indent=2, default=float))

    first = next(iter(results.values()))
    print(f"\n=== co-design grid sweep ({len(first.labels)} circuit cfgs "
          f"× {len(grid.t_intg_grid_ms)} T_INTG × "
          f"{'/'.join(protocols)}, {wall_s:.0f}s) ===")
    print(f"{'protocol':>9} {'config':>10} {'T_INTG':>8} {'acc':>6} "
          f"{'bw':>7} {'energy':>8} {'ret_mV':>8}")
    for proto, result in results.items():
        for r in result.records:
            print(f"{proto:>9} {r['label']:>10} {r['t_intg_ms']:6.0f}ms "
                  f"{r['accuracy']:6.3f} {r['bandwidth_norm']:6.2f}x "
                  f"{r['energy_improvement']:7.2f}x "
                  f"{r['retention_err_v'] * 1e3:8.2f}")
    print(f"artifact: {path}")
    return 0


# ---------------------------------------------------------------------------
# legacy dry-run cell sweep (subprocess per cell)
# ---------------------------------------------------------------------------

def run_dryrun_cells(args) -> int:
    from repro.configs import SHAPES, list_archs

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    archs = args.archs or list_archs()
    shapes = args.shapes or list(SHAPES)

    cells = [(a, s, p) for a in archs for s in shapes for p in args.pods]
    t0 = time.time()
    n_err = 0
    for i, (arch, shape, pods) in enumerate(cells):
        path = out / f"{arch}__{shape}__{pods}pod.json"
        if path.exists() and not args.force:
            try:
                rec = json.loads(path.read_text())
                if rec.get("status") in ("ok", "skipped"):
                    print(f"[{i+1}/{len(cells)}] {arch}×{shape}×{pods}pod cached "
                          f"({rec['status']})", flush=True)
                    continue
            except json.JSONDecodeError:
                pass
        inherited = os.environ.get("PYTHONPATH")
        env = dict(os.environ,
                   PYTHONPATH=(_SRC + os.pathsep + inherited
                               if inherited else _SRC),
                   REPRO_ARTIFACTS=str(out))
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--pods", str(pods),
               "--out", str(out)]
        t1 = time.time()
        try:
            proc = subprocess.run(cmd, env=env, timeout=args.timeout,
                                  capture_output=True, text=True)
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            rc = -9
            path.write_text(json.dumps({
                "arch": arch, "shape": shape, "pods": pods,
                "status": "error", "error": f"timeout {args.timeout}s"}))
        status = "?"
        if path.exists():
            try:
                status = json.loads(path.read_text()).get("status", "?")
            except json.JSONDecodeError:
                status = "corrupt"
        if status == "error" or rc != 0:
            n_err += 1
        print(f"[{i+1}/{len(cells)}] {arch}×{shape}×{pods}pod {status} "
              f"rc={rc} {time.time()-t1:.0f}s (total {time.time()-t0:.0f}s)",
              flush=True)
    print(f"sweep done: {n_err} errors, {time.time()-t0:.0f}s", flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dryrun-cells", action="store_true",
                    help="legacy arch×shape×pods dry-run sweep "
                         "(subprocess per cell)")
    # co-design grid options
    ap.add_argument("--grid", type=str, default="paper",
                    choices=["paper", "fast"],
                    help="co-design grid preset (default: paper = 3 "
                         "circuits × 4 T_INTG)")
    ap.add_argument("--circuits", type=str, nargs="+", default=None,
                    choices=["a", "b", "c"], help="override circuit configs")
    ap.add_argument("--t-intg", type=float, nargs="+", default=None,
                    help="override T_INTG grid (ms)")
    ap.add_argument("--axes", type=str, nargs="+", default=None,
                    choices=["mismatch", "null-mismatch", "v-threshold",
                             "sigma", "n-sub"],
                    help="activate variant axes with their registry default "
                         "value grids (core/variant_grid.py); explicit "
                         "value flags below override")
    ap.add_argument("--mismatch", type=float, nargs="+", default=None,
                    dest="mismatch",
                    help="nullifier mismatch values for circuit (c)")
    ap.add_argument("--v-threshold", type=float, nargs="+", default=None,
                    help="comparator threshold values (V) — expands every "
                         "circuit")
    ap.add_argument("--sigma", type=float, nargs="+", default=None,
                    help="process-variation sigma values on the leak taus")
    ap.add_argument("--n-sub", type=int, nargs="+", default=None,
                    help="event sub-slots per window (shape-changing: "
                         "outer loop with T_INTG)")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the stacked variant axis over this many "
                         "devices (1-D cfg mesh via shard_map); on CPU "
                         "force host devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--protocol", type=str, default="both",
                    choices=["frozen", "unfrozen", "both"],
                    help="phase-2 finetune protocol(s): frozen layer 1 "
                         "(paper §3), unfrozen joint layer-1+backbone "
                         "training, or both off one shared pretrain")
    ap.add_argument("--dataset", type=str, default="synthetic-gesture",
                    choices=["synthetic-gesture", "synthetic-nmnist",
                             "dvs128", "nmnist"],
                    help="event source (repro.data.sources): synthetic-* "
                         "need no files; dvs128 (AEDAT 3.1) and nmnist "
                         "(.bin) read --data-root (docs/datasets.md)")
    ap.add_argument("--data-root", type=str, default=None,
                    help="dataset directory for the file-backed datasets "
                         "(binned frames are cached under "
                         "<root>/.p2m-frame-cache)")
    ap.add_argument("--hw", type=int, default=16,
                    help="event-frame resolution (synthetic grid size / "
                         "file-backed downscale target)")
    # legacy dry-run options
    ap.add_argument("--pods", type=int, nargs="+", default=None)
    ap.add_argument("--archs", type=str, nargs="+", default=None)
    ap.add_argument("--shapes", type=str, nargs="+", default=None)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=2400)
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()
    from repro.utils import init_compile_cache
    init_compile_cache()

    if args.dryrun_cells:
        args.pods = args.pods or [1, 2]
        args.out = args.out or "artifacts/dryrun"
        return run_dryrun_cells(args)
    if args.pods or args.archs or args.shapes or args.force:
        print("error: --pods/--archs/--shapes/--force belong to the legacy "
              "cell sweep — pass --dryrun-cells to run it", file=sys.stderr)
        return 2
    args.out = args.out or "artifacts/sweep"
    return run_codesign_grid(args)


if __name__ == "__main__":
    sys.exit(main())
