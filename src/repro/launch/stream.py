"""Online streaming inference launcher: serve live event streams through
a deployed P²M variant with continuous batching (repro.stream).

Deployment handshake (docs/streaming.md): a sweep artifact is the menu,
a serving checkpoint (written by ``repro.stream.deploy``) is the weights.
Three ways in:

  * ``--checkpoint DIR`` serves an existing deployment;
    ``--artifact PATH`` optionally cross-checks it against the sweep
    artifact it was deployed from;
  * no checkpoint: a fast co-design sweep runs in-process
    (``keep_params=True``), deploys the best record for ``--protocol``
    (``--deploy-t-intg`` pins the integration time), and serves it;
  * ``--smoke``: fully self-contained CI path — if the dataset is
    file-backed and no ``--data-root`` is given, a miniature fixture
    dataset is generated first (repro.data.fixtures), then the tiny
    train → deploy → serve pipeline runs end-to-end on CPU;
  * ``--registry CKPT [CKPT ...]`` serves a DEPLOYMENT REGISTRY
    (repro.stream.registry) of several compat-equal checkpoints from one
    engine — entry names are the checkpoint dir basenames, the FIRST
    one is the default entry. ``--variants SPEC [...]`` assigns each
    stream a variant request, cycled round-robin: a SPEC is an entry
    name (``ckpt_frozen``) or a ``k=v[,k=v...]`` metadata matcher
    (``protocol=frozen``), resolved at admission; unresolvable requests
    are rejected and counted.

``--adapt`` turns on per-lane ONLINE ADAPTATION (repro.stream.adapt):
each serving lane learns a private delta on the deployed layer-1
weights/threshold from its own stream's labels at every coarse-window
readout (``--adapt-rule`` picks surrogate-gradient or reward-modulated
three-factor; ``--adapt-lr``/``--adapt-lr-theta`` scale the steps).
``--adapt-export DIR`` harvests every adapted lane into a validated
delta checkpoint (``deploy.save_adapt_delta``) that re-registers beside
its base — the close of the adapt → harvest → re-serve loop.
Incompatible with ``--use-kernel`` (the fused fold has no VJP).

Emits one serving-stats JSON artifact (schema ``p2m-stream-serving/v5``):
per-stream predictions (with their registry-entry binding), p50/p99
readout latency, events/s (total and per-device), the mesh ``sharding``
block, the ``registry`` per-entry breakdown, the ``adaptation`` block,
admission (shed/rejected/deferred) counters and — under ``--paced`` —
deadline-miss accounting (docs/streaming.md).

``--devices N`` shards the lane axis over a 1-D device mesh
(repro.stream.shard) — the predictions of ``--devices 1``; ``--bin-workers``
sizes the host binning pool (defaults to the device count). On CPU boxes,
force host devices first: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

  PYTHONPATH=src python -m repro.launch.stream --smoke --streams 8
  PYTHONPATH=src python -m repro.launch.stream --dataset dvs128 \\
      --data-root /data/DvsGesture --checkpoint artifacts/stream/ckpt_frozen \\
      --streams 64 --capacity 16 --devices 4 --bin-workers 4 \\
      --paced --offered-rate 32 --max-pending 128
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

# runnable from any cwd (same pattern as launch/sweep.py)
_SRC = str(Path(__file__).resolve().parents[2])
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

FILE_BACKED = ("dvs128", "nmnist")


def _parse_variant_spec(spec: str):
    """CLI variant request → registry request: a bare entry name, or a
    ``k=v[,k=v...]`` metadata matcher (values parsed as JSON scalars
    when possible, e.g. ``t_intg_ms=100.0``)."""
    if "=" not in spec:
        return spec
    matcher = {}
    for kv in spec.split(","):
        k, _, v = kv.partition("=")
        try:
            matcher[k] = json.loads(v)
        except json.JSONDecodeError:
            matcher[k] = v
    return matcher


def _make_fixture(dataset: str, root: Path) -> None:
    from repro.data import fixtures

    if dataset == "dvs128":
        fixtures.make_dvs128_fixture(root, n_recordings=2,
                                     trials_per_recording=6)
    else:
        fixtures.make_nmnist_fixture(root)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dataset", type=str, default=None,
                    choices=["synthetic-gesture", "synthetic-nmnist",
                             "dvs128", "nmnist"],
                    help="event source to stream (default: dvs128 under "
                         "--smoke — served from a generated fixture — "
                         "else synthetic-gesture)")
    ap.add_argument("--data-root", type=str, default=None,
                    help="dataset directory for file-backed datasets")
    ap.add_argument("--artifact", type=str, default=None,
                    help="sweep artifact JSON to cross-check the "
                         "checkpoint against (deployment handshake)")
    ap.add_argument("--checkpoint", type=str, default=None,
                    help="serving checkpoint dir (repro.stream.deploy); "
                         "omitted: a fast sweep trains and deploys one "
                         "in-process")
    ap.add_argument("--registry", type=str, nargs="+", default=None,
                    metavar="CKPT",
                    help="serve a deployment registry built from these "
                         "checkpoint dirs (entry name = dir basename; "
                         "first entry is the default); mutually exclusive "
                         "with --checkpoint")
    ap.add_argument("--variants", type=str, nargs="+", default=None,
                    metavar="SPEC",
                    help="per-stream variant requests, cycled round-robin "
                         "over the streams: an entry name or a k=v[,k=v] "
                         "metadata matcher (requires --registry)")
    ap.add_argument("--max-entries", type=int, default=None,
                    help="registry engine param-table size (max variants "
                         "co-resident on the lanes; default: entries + 1)")
    ap.add_argument("--streams", type=int, default=8,
                    help="number of event streams to serve")
    ap.add_argument("--capacity", type=int, default=4,
                    help="concurrent serving lanes (the jitted batch)")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the lane axis over this many devices on a "
                         "1-D mesh (capacity is padded up to a multiple; "
                         "the predictions of --devices 1). Default: "
                         "unsharded")
    ap.add_argument("--bin-workers", type=int, default=None,
                    help="host binning worker threads, each owning a "
                         "contiguous lane slice (default: one per device)")
    ap.add_argument("--paced", action="store_true",
                    help="real-time replay: hold each T_INTG window to "
                         "its wall-clock boundary and record deadline "
                         "misses (readouts landing after t_admit + "
                         "k*t_intg); predictions stay bit-identical to "
                         "unpaced replay")
    ap.add_argument("--offered-rate", type=float, default=None,
                    help="offered load, streams/s on the replay clock "
                         "(default: offer all streams up front)")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="bound on the pending admission queue; offers "
                         "beyond capacity + max-pending are shed "
                         "(default: unbounded, no shedding)")
    ap.add_argument("--chunks-per-window", type=int, default=None,
                    help="replay chunks per T_INTG window (must divide "
                         "n_sub; default: one chunk per fine sub-slot)")
    ap.add_argument("--adapt", action="store_true",
                    help="per-lane online adaptation: learn a private "
                         "layer-1 weight/threshold delta on each lane "
                         "from its stream's labels at every coarse "
                         "readout (repro.stream.adapt); frozen serving "
                         "is untouched without this flag")
    ap.add_argument("--adapt-rule", type=str, default="surrogate",
                    choices=["surrogate", "reward"],
                    help="local update rule: surrogate-gradient descent "
                         "on the window readout, or reward-modulated "
                         "three-factor (eligibility traces)")
    ap.add_argument("--adapt-lr", type=float, default=5e-3,
                    help="weight-delta learning rate")
    ap.add_argument("--adapt-lr-theta", type=float, default=0.0,
                    help="comparator-threshold learning rate (default 0: "
                         "thresholds stay deployed)")
    ap.add_argument("--adapt-export", type=str, default=None,
                    metavar="DIR",
                    help="harvest every adapted lane into a validated "
                         "delta checkpoint under DIR/lane<N> "
                         "(deploy.save_adapt_delta) for re-registration")
    ap.add_argument("--use-kernel", action="store_true",
                    help="fold sub-slots through the fused Pallas "
                         "stream_fold kernel instead of the XLA scan "
                         "(same spike maps; compiled on TPU, "
                         "interpreted on CPU — see docs/kernels.md)")
    ap.add_argument("--protocol", type=str, default="frozen",
                    choices=["frozen", "unfrozen"],
                    help="which phase-2 protocol to train+deploy when no "
                         "--checkpoint is given")
    ap.add_argument("--deploy-t-intg", type=float, default=None,
                    help="pin the deployed record's T_INTG (ms); default: "
                         "best accuracy on the trained grid")
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: tiny train steps; generates a fixture "
                         "dataset when file-backed data has no --data-root")
    ap.add_argument("--hw", type=int, default=16,
                    help="event-frame resolution")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=str, default="artifacts/stream")
    args = ap.parse_args()
    from repro.utils import init_compile_cache
    init_compile_cache()

    from repro.data import sources as sources_mod
    from repro.stream import deploy as deploy_mod
    from repro.stream.adapt import AdaptConfig
    from repro.stream.engine import StreamEngine
    from repro.stream.registry import Registry
    from repro.stream.shard import make_lane_executor

    if args.registry is not None and args.checkpoint is not None:
        print("error: --registry and --checkpoint are mutually exclusive",
              file=sys.stderr)
        return 2
    if args.variants is not None and args.registry is None:
        print("error: --variants requires --registry", file=sys.stderr)
        return 2
    if args.adapt_export is not None and not args.adapt:
        print("error: --adapt-export requires --adapt", file=sys.stderr)
        return 2

    dataset = args.dataset or ("dvs128" if args.smoke
                               else "synthetic-gesture")
    data_root = args.data_root
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    fixture_tmp = None
    if dataset in FILE_BACKED and data_root is None:
        if not args.smoke:
            print(f"error: dataset {dataset!r} is file-backed: pass "
                  f"--data-root (or --smoke to generate a fixture)",
                  file=sys.stderr)
            return 2
        fixture_tmp = tempfile.mkdtemp(prefix=f"p2m-{dataset}-fixture-")
        data_root = fixture_tmp
        print(f"[stream] generating {dataset} fixture under {data_root}")
        _make_fixture(dataset, Path(data_root))

    try:
        default_entry = None
        if args.registry is not None:
            # entry names = checkpoint dir basenames; first = default
            reg = Registry()
            for d in args.registry:
                entry = reg.register_checkpoint(Path(d).name, d,
                                                artifact=args.artifact)
                print(f"[registry] {entry.name}#{entry.uid} "
                      f"({entry.meta.get('label')}/"
                      f"{entry.meta.get('protocol')} "
                      f"T={entry.meta.get('t_intg_ms'):g}ms, compat "
                      f"{entry.compat_digest})")
            target = reg
            default_entry = reg.names()[0]
        elif args.checkpoint is not None:
            target = deploy_mod.load_deployment(args.checkpoint,
                                                args.artifact)
        else:
            # no weights on disk: train + deploy in-process (fast grid)
            smoke_t = (100.0, 1000.0) if args.smoke else None
            bundle = deploy_mod.train_and_deploy(
                out / "deploy", dataset=dataset, data_root=data_root,
                hw=args.hw, protocols=(args.protocol,), smoke=args.smoke,
                t_intg_grid_ms=smoke_t,
                deploy_t_intg_ms=(args.deploy_t_intg if args.deploy_t_intg
                                  is not None else
                                  (100.0 if args.smoke else None)))
            target = deploy_mod.load_deployment(
                bundle["checkpoints"][args.protocol], bundle["artifact"])
        source = sources_mod.resolve_dataset(dataset, hw=args.hw,
                                             data_root=data_root,
                                             split="all")
        adapt = (AdaptConfig(rule=args.adapt_rule, lr_w=args.adapt_lr,
                             lr_theta=args.adapt_lr_theta)
                 if args.adapt else None)
        engine = StreamEngine(target, capacity=args.capacity,
                              chunks_per_window=args.chunks_per_window,
                              use_kernel=args.use_kernel,
                              executor=make_lane_executor(args.devices),
                              bin_workers=args.bin_workers,
                              max_entries=args.max_entries,
                              default_entry=default_entry,
                              adapt=adapt)
        variants = None
        if args.variants is not None:
            reqs = [_parse_variant_spec(s) for s in args.variants]
            variants = lambda sid: reqs[sid % len(reqs)]  # noqa: E731
        report = engine.serve(source, args.streams, seed=args.seed,
                              paced=args.paced,
                              offered_rate=args.offered_rate,
                              max_pending=args.max_pending,
                              variants=variants, log=print)
    except (ValueError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    finally:
        if fixture_tmp is not None:
            shutil.rmtree(fixture_tmp, ignore_errors=True)

    art = report.to_artifact()
    art["data"] = {"dataset": dataset, "data_root": data_root,
                   "hw": args.hw, "n_classes": source.n_classes,
                   "duration_ms": source.duration_ms}
    path = out / f"stream_serving_{dataset}.json"
    path.write_text(json.dumps(art, indent=2, default=float))

    lat, thr = art["latency_ms"], art["throughput"]
    adm, ddl = art["admission"], art["deadlines"]
    print(f"\n=== stream serving ({art['n_streams']} streams, "
          f"{report.capacity} lanes, T_INTG={art['t_intg_ms']:g}ms, "
          f"variant {art['deployed']['label']}/{art['deployed']['protocol']}"
          f"{', paced' if art['paced'] else ''}) ===")
    print(f"accuracy       {art['accuracy']:.3f}")
    print(f"readout p50    {lat['readout_p50']:.2f} ms   "
          f"p99 {lat['readout_p99']:.2f} ms")
    print(f"throughput     {thr['events_per_s']:.0f} events/s   "
          f"{thr['readouts_per_s']:.1f} readouts/s   "
          f"{thr['streams_per_s']:.2f} streams/s")
    sh = art["sharding"]
    print(f"sharding       {sh['devices']} device(s) x "
          f"{sh['lanes_per_shard']} lanes  (padded capacity "
          f"{sh['padded_capacity']}, {sh['bin_workers']} bin worker(s))   "
          f"{thr['events_per_s_per_device']:.0f} events/s/device")
    print(f"admission      offered {adm['n_offered']}  admitted "
          f"{adm['n_admitted']}  shed {adm['n_shed']}  rejected "
          f"{adm['n_rejected']}  deferred {adm['n_deferred']}  max open "
          f"{adm['max_open_streams']}")
    if args.registry is not None:
        for row in art["registry"]["entries"]:
            print(f"variant        {row['name']}#{row['uid']}  admitted "
                  f"{row['n_admitted']}  finished {row['n_finished']}  "
                  f"acc {row['accuracy']:.3f}  misses {row['n_misses']}  "
                  f"{row['events_per_s']:.0f} events/s")
    if art["paced"]:
        mg = ddl["margin_ms"]
        print(f"deadlines      {ddl['n_misses']}/{ddl['n_deadlines']} "
              f"missed ({ddl['miss_rate']:.2%})   margin p50 "
              f"{mg['p50']:.2f} ms  p99 {mg['p99']:.2f} ms  max "
              f"{mg['max']:.2f} ms")
    ad = art["adaptation"]
    if ad["enabled"]:
        fmt = lambda a: "-" if a is None else f"{a:.3f}"  # noqa: E731
        print(f"adaptation     {ad['rule']}  lr_w {ad['lr_w']:g}  "
              f"{ad['n_updates']} updates on {len(ad['lanes'])} lane(s)   "
              f"acc pre {fmt(ad['accuracy_pre'])} -> "
              f"post {fmt(ad['accuracy_post'])}")
        if args.adapt_export is not None:
            exp = Path(args.adapt_export)
            for row in ad["lanes"]:
                h = engine.harvest(row["lane"])
                d = exp / f"lane{row['lane']}"
                deploy_mod.save_adapt_delta(
                    d, h["base"], dw=h["dw"], dtheta=h["dtheta"],
                    base_name=h["base_name"], base_uid=h["base_uid"],
                    lane=h["lane"], n_updates=h["n_updates"],
                    rule=args.adapt_rule, meta={"dataset": dataset})
                print(f"[adapt] lane {row['lane']}: {h['n_updates']} "
                      f"updates on base {h['base_name']}#{h['base_uid']} "
                      f"-> {d}")
    print(f"artifact: {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
