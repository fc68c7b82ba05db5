"""Chip smoke test: serve the paper-width P²M network on a TPU, end to end.

Drives the online serving path the way a user does, in one process:

  1. deploy — a fresh (seeded, untrained) deployment of the paper config
     (``repro.configs.p2m_dvs.CONFIG``: 128×128 sensor, 16 in-pixel
     filters, 16/32/64/64 backbone, FC512, 11 classes, T_INTG 10 ms × 4
     sub-slots, 1 s coarse window) is saved as a serving checkpoint and
     loaded back — the ``repro.launch.stream --checkpoint`` handshake;
  2. serve — 32 synthetic-gesture streams of two coarse windows each on
     16 lanes (so lanes recycle) through ``StreamEngine.serve``, once with
     the XLA fold and once with the compiled ``stream_fold`` kernel, each
     checked against ``deploy.offline_forward`` on the same streams:
     identical predictions, per-stream layer-1 spike counts equal but for
     at most ``SPIKE_FLIP_RTOL`` of comparator decisions, and logits
     within ``LOGIT_ATOL`` wherever the spike counts are equal.

``--chips 4`` runs only the lane-mesh phase instead: the same streams
served on a 4-device lane mesh and on one device, compared the same way.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips, lane mesh vs one device

The last line of standard output is ``{"ok": true, "device": {...}}`` and
is printed only when every phase passed. Without a TPU, or without the
repository's ``src/`` beside it, the script exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from dataclasses import replace
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
ARTIFACTS = ROOT / "artifacts" / "chip_smoke"

SEED = 0
N_STREAMS = 32
CAPACITY = 16              # a multiple of 4: 4 lanes per chip on the mesh
DURATION_MS = 2000.0       # two 1 s coarse windows per stream
# largest |Δlogit| admitted between two serving paths that must agree
# (fold kernel vs XLA fold vs offline forward, lane mesh vs one device),
# on streams whose layer-1 spike counts agree
LOGIT_ATOL = 1e-5
# share of layer-1 comparator decisions allowed to differ: the offline
# forward weights a window's sub-slots in one einsum, the serving fold in
# a recurrence, so a charge within float rounding of the threshold may
# compare either way
SPIKE_FLIP_RTOL = 1e-6


class SmokeFailure(AssertionError):
    """A phase produced a wrong answer."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def phase_deploy(model_cfg, *, seed: int, directory: Path):
    """Fresh seeded deployment → serving checkpoint → loaded back."""
    from repro.stream import deploy

    dep = deploy.fresh_deployment(model_cfg, seed=seed)
    if directory.exists():
        shutil.rmtree(directory)
    deploy.save_deployment(directory, dep)
    return deploy.load_deployment(directory)


def _warm_up(engine):
    """Compile the engine's fold, on the window shape it dispatches, and
    its readout on a zero state (what ``serve`` does before its clock
    starts); returns (seconds, folded state)."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    h, w = engine.fns.in_hw
    cap = engine.padded_capacity
    mask = jnp.zeros((cap,), bool)
    state = engine.fns.fold(engine.fns.init_state(),
                            jnp.zeros((cap, engine.n_sub, h, w, 2)),
                            mask)
    state, _ = engine.fns.readout(state, mask, mask)
    jax.block_until_ready(state)
    return time.perf_counter() - t0, state


def fold_lowering_has_kernel(engine) -> bool:
    """Whether the engine's jitted fold step, on the window shape it
    dispatches, lowers to a Mosaic kernel (``tpu_custom_call``) rather
    than XLA ops or the interpreter."""
    import jax.numpy as jnp

    h, w = engine.fns.in_hw
    cap = engine.padded_capacity
    lowered = engine.fns.fold.lower(
        engine.fns.init_state(),
        jnp.zeros((cap, engine.n_sub, h, w, 2)),
        jnp.zeros((cap,), bool))
    return "tpu_custom_call" in lowered.as_text()


def offline_reference(dep, source, engine, *, n_streams: int, seed: int
                      ) -> dict:
    """``deploy.offline_forward`` over the streams ``engine.serve`` will
    replay (same per-stream keys), binned on the engine's sub-slot grid
    as tests/test_streaming.py bins them. One stream per call."""
    import jax

    from repro.data.binning import bin_chunks
    from repro.stream import deploy

    cfg = dep.model_cfg.p2m
    n_slots = source.n_slots(cfg.t_intg_ms)
    h, w = source.height, source.width
    fwd = jax.jit(lambda params, bn, ev: deploy.offline_forward(
        replace(dep, params=params, bn_state=bn), ev))
    key = jax.random.PRNGKey(seed)
    labels, logits, spikes = [], [], []
    for sid in range(n_streams):
        label, chunks = source.iter_event_chunks(
            jax.random.fold_in(key, sid), chunk_us=engine.chunk_us,
            slot_us=engine.slot_us)
        frames = bin_chunks(chunks, n_total=n_slots * cfg.n_sub,
                            slot_us=engine.slot_us,
                            sensor_hw=source.sensor_hw, out_hw=(h, w))
        out = fwd(dep.params, dep.bn_state,
                  frames.reshape(1, n_slots, cfg.n_sub, h, w, 2))
        labels.append(label)
        logits.append(np.asarray(out["logits"][0]))
        spikes.append(float(np.asarray(out["pooled"]).sum()))
    return {"labels": labels, "logits": np.stack(logits),
            "layer1_spikes": np.asarray(spikes)}


def as_reference(report) -> dict:
    """A serving report in the shape :func:`offline_reference` returns."""
    rs = sorted(report.results, key=lambda r: r.stream_id)
    return {"labels": [r.label for r in rs],
            "logits": np.stack([np.asarray(r.logits) for r in rs]),
            "layer1_spikes": np.asarray([r.n_layer1_spikes for r in rs])}


def compare(report, ref: dict, *, atol: float) -> dict:
    """Hold a serving report to a reference (:func:`offline_reference`):
    same streams and labels, identical predictions, layer-1 spike counts
    equal up to ``SPIKE_FLIP_RTOL`` flipped comparator decisions, and
    |Δlogit| ≤ ``atol`` on every stream whose spike count is equal."""
    n = len(ref["labels"])
    by_id = {r.stream_id: r for r in report.results}
    _check(sorted(by_id) == list(range(n)),
           f"served streams {sorted(by_id)} != 0..{n - 1}")
    rs = [by_id[i] for i in range(n)]
    _check([r.label for r in rs] == list(ref["labels"]),
           "stream labels differ from the reference's")
    got = np.stack([np.asarray(r.logits) for r in rs])
    _check(np.isfinite(got).all(), "non-finite served logits")
    dlogit = np.abs(got - ref["logits"]).max(axis=1)
    dspikes = (np.asarray([r.n_layer1_spikes for r in rs])
               - ref["layer1_spikes"])
    same = dspikes == 0
    ref_preds = np.argmax(ref["logits"], axis=1)
    res = {"n_streams": n,
           "preds_agree": int(sum(r.prediction == p
                                  for r, p in zip(rs, ref_preds))),
           "layer1_spikes": report.total_layer1_spikes,
           "ref_layer1_spikes": float(ref["layer1_spikes"].sum()),
           "spike_flips": float(np.abs(dspikes).sum()),
           "streams_flipped": int((~same).sum()),
           "max_dlogit": float(dlogit[same].max()) if same.any() else 0.0,
           "max_dlogit_flipped": (float(dlogit[~same].max())
                                  if (~same).any() else 0.0)}
    _check(res["layer1_spikes"] > 0,
           "no layer-1 spike fired: the comparison covers no comparator "
           "decision")
    _check(res["spike_flips"] <= SPIKE_FLIP_RTOL * res["ref_layer1_spikes"],
           f"{res['spike_flips']:.0f} of {res['ref_layer1_spikes']:.0f} "
           f"layer-1 spikes differ from the reference")
    _check(res["preds_agree"] == n,
           f"predictions differ on {n - res['preds_agree']} of {n} streams")
    _check(res["max_dlogit"] <= atol,
           f"max |Δlogit| {res['max_dlogit']:.3e} > tolerance {atol:.1e}")
    return res


def _compare_line(cmp: dict) -> str:
    return (f"layer1_spikes={cmp['layer1_spikes']:.0f} "
            f"(reference {cmp['ref_layer1_spikes']:.0f}, "
            f"{cmp['spike_flips']:.0f} flipped in "
            f"{cmp['streams_flipped']} stream(s)) "
            f"max_dlogit={cmp['max_dlogit']:.3e} tol={LOGIT_ATOL:.1e} "
            f"(streams with a flip: {cmp['max_dlogit_flipped']:.3e}) "
            f"preds_agree={cmp['preds_agree']}/{cmp['n_streams']}")


def phase_serve(dep, source, *, n_streams: int, capacity: int, seed: int,
                require_kernel: bool, log=print) -> dict:
    """Serve with the XLA fold, then the fused kernel fold; hold both to
    the offline forward. ``require_kernel`` asserts the kernel engine's
    fold lowers to a compiled Mosaic kernel (true on a TPU only)."""
    import jax
    import jax.numpy as jnp

    from repro.stream.engine import StreamEngine

    engines = {"xla": StreamEngine(dep, capacity=capacity),
               "kernel": StreamEngine(dep, capacity=capacity,
                                      use_kernel=True)}
    if require_kernel:
        _check(fold_lowering_has_kernel(engines["kernel"]),
               "use_kernel=True fold has no tpu_custom_call")
        log("[serve:kernel] fold lowers to tpu_custom_call")
    t0 = time.perf_counter()
    ref = offline_reference(dep, source, engines["xla"],
                            n_streams=n_streams, seed=seed)
    log(f"[reference] offline_forward over {n_streams} streams: "
        f"wall_s={time.perf_counter() - t0:.2f} "
        f"layer1_spikes={ref['layer1_spikes'].sum():.0f}")
    out: dict = {"reference": ref}
    for tag, engine in engines.items():
        compile_s, _ = _warm_up(engine)
        t0 = time.perf_counter()
        report = engine.serve(source, n_streams, seed=seed)
        wall_s = time.perf_counter() - t0
        cmp = compare(report, ref, atol=LOGIT_ATOL)
        log(f"[serve:{tag}] compile_s={compile_s:.2f} wall_s={wall_s:.2f} "
            f"events={report.total_events} "
            f"readouts={report.total_readouts} " + _compare_line(cmp))
        out[tag] = {"report": report, **cmp}
    # one window through both folds from the same non-zero charge: how far
    # the kernel's arithmetic sits from the XLA fold's on this backend
    xla, kern = engines["xla"].fns, engines["kernel"].fns
    key = jax.random.PRNGKey(seed)
    h, w = xla.in_hw
    cap = engines["xla"].padded_capacity
    state = xla.init_state()
    state["x"] = jax.random.normal(key, state["x"].shape) * 0.05
    frames = jax.random.poisson(
        jax.random.fold_in(key, 1), 0.3,
        (cap, engines["xla"].n_sub, h, w, 2)).astype(jnp.float32)
    active = jnp.ones((cap,), bool)
    xa = np.asarray(xla.fold(dict(state), frames, active)["x"])
    xk = np.asarray(kern.fold(dict(state), frames, active)["x"])
    out["fold_max_dx"] = float(np.abs(xa - xk).max())
    log(f"[fold] kernel vs XLA fold, one window: max|dx|="
        f"{out['fold_max_dx']:.3e} (|x| max {float(np.abs(xa).max()):.3e})")
    return out


def phase_mesh(dep, source, *, n_streams: int, capacity: int, devices: int,
               seed: int, log=print) -> dict:
    """Serve on a ``devices``-wide lane mesh, then on one device; the two
    must agree stream by stream."""
    from repro.stream.engine import StreamEngine
    from repro.stream.shard import make_lane_executor

    reports = {}
    for n in (devices, 1):
        engine = StreamEngine(dep, capacity=capacity,
                              executor=make_lane_executor(n))
        compile_s, state = _warm_up(engine)
        placement = sorted(
            (s.index[0].start or 0, s.index[0].stop or capacity, str(s.device))
            for s in state["x"].addressable_shards)
        for lo, hi, dev in placement:
            log(f"[mesh:{n}] lanes [{lo}, {hi}) on {dev}")
        _check(len({dev for *_, dev in placement}) == n,
               f"{n}-device lane mesh placed its shards on "
               f"{len({dev for *_, dev in placement})} device(s)")
        t0 = time.perf_counter()
        report = engine.serve(source, n_streams, seed=seed)
        wall_s = time.perf_counter() - t0
        log(f"[mesh:{n}] compile_s={compile_s:.2f} wall_s={wall_s:.2f} "
            f"events={report.total_events} "
            f"readouts={report.total_readouts} "
            f"layer1_spikes={report.total_layer1_spikes:.0f}")
        reports[n] = report
    cmp = compare(reports[devices], as_reference(reports[1]),
                  atol=LOGIT_ATOL)
    log(f"[mesh] {devices} devices vs 1: " + _compare_line(cmp))
    return {"reports": reports, **cmp}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serve phase on one chip; 4: only the lane-mesh "
                         "phase, 4 chips against 1")
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args(argv)

    if not (SRC / "repro" / "stream" / "engine.py").is_file():
        print(f"chip_smoke: no repository sources under {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.configs import p2m_dvs
    from repro.data import sources
    from repro.utils import init_compile_cache

    cache = init_compile_cache()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; jax {jax.__version__}; "
          f"compile cache {cache}")
    t0 = time.perf_counter()
    dep = phase_deploy(p2m_dvs.CONFIG, seed=args.seed,
                       directory=ARTIFACTS / "deploy")
    cfg = dep.model_cfg
    print(f"[deploy] fresh seed={args.seed} saved+loaded in "
          f"{time.perf_counter() - t0:.2f}s: {cfg.backbone.input_hw} sensor, "
          f"F={cfg.p2m.out_channels}, backbone {cfg.backbone.channels} "
          f"FC{cfg.backbone.fc_hidden}, {cfg.backbone.n_classes} classes, "
          f"T_INTG {cfg.p2m.t_intg_ms:g} ms x {cfg.p2m.n_sub}, "
          f"coarse {cfg.coarse_window_ms:g} ms")
    source = sources.resolve_dataset(
        "synthetic-gesture", hw=cfg.backbone.input_hw[0],
        duration_ms=DURATION_MS)
    print(f"[source] synthetic-gesture {N_STREAMS} streams x "
          f"{DURATION_MS:g} ms on {CAPACITY} lanes")
    if args.chips == 1:
        phase_serve(dep, source, n_streams=N_STREAMS, capacity=CAPACITY,
                    seed=args.seed, require_kernel=True)
    else:
        phase_mesh(dep, source, n_streams=N_STREAMS, capacity=CAPACITY,
                   devices=args.chips, seed=args.seed)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
