import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("REPRO_DRYRUN_DEVICES", "512")).strip()

"""Pipeline-parallel dry-run: lower + compile the PP train step on a
(pipe=4, data=16, model=8) = 512-chip mesh — the beyond-spec growth mode
(DESIGN.md §4). Subprocess-only, like dryrun.py.

  PYTHONPATH=src python -m repro.launch.dryrun_pp [--arch internlm2-1.8b]
"""
import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.roofline.hlo import analyze_hlo
from repro.roofline.model import roofline_terms


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="internlm2-1.8b")
    ap.add_argument("--pipe", type=int, default=4)
    ap.add_argument("--data", type=int, default=16)
    ap.add_argument("--model", type=int, default=8)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--out", type=str, default="artifacts/dryrun")
    args = ap.parse_args()

    from repro.train import pipeline as pp

    cfg = get_config(args.arch)
    assert cfg.family == "dense", "PP dry-run covers the dense family"
    assert cfg.n_layers % args.pipe == 0
    mesh = make_mesh((args.pipe, args.data, args.model),
                     ("pipe", "data", "model"))
    t0 = time.perf_counter()
    with mesh:
        shapes = jax.eval_shape(
            lambda k: pp.stage_params(k, cfg, args.pipe), jax.random.PRNGKey(0))
        pspecs = pp.stage_pspecs(shapes, cfg, mesh)
        p_sds = jax.tree.map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
            shapes, pspecs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        tok = jax.ShapeDtypeStruct(
            (args.batch, args.seq), jnp.int32,
            sharding=NamedSharding(mesh, P("data", None)))
        step = pp.build_pp_train_step(cfg, mesh,
                                      n_microbatches=args.microbatches)
        lowered = step.lower(p_sds, tok, tok)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    parsed = analyze_hlo(compiled.as_text(), pod_stride=256)
    chips = mesh.devices.size
    terms = roofline_terms(parsed.flops * chips, parsed.bytes * chips,
                           parsed.collective_bytes * chips, chips)
    rec = {
        "arch": args.arch, "mode": "pipeline",
        "mesh": {"pipe": args.pipe, "data": args.data, "model": args.model},
        "status": "ok", "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "collectives": parsed.as_dict(), "roofline": terms,
    }
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.arch}__pp_train__{chips}c.json").write_text(
        json.dumps(rec, indent=2))
    cp = parsed.coll_count.get("collective-permute", 0)
    print(f"[dryrun-pp] {args.arch} pipe={args.pipe} ok "
          f"compile={rec['compile_s']}s dominant={terms['dominant']} "
          f"bound={terms['roofline_bound_s']:.3f}s "
          f"collective-permutes={cp:.0f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
