import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("REPRO_DRYRUN_DEVICES", "512")).strip()

"""Compressed cross-pod gradient-reduce dry-run: proves the int8+error-
feedback all-reduce (distributed/compression.py) lowers and compiles on the
2-pod 512-chip mesh, and reports the cross-pod byte cut vs fp32.

  PYTHONPATH=src python -m repro.launch.dryrun_compression
"""
import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.compression import compressed_allreduce
from repro.launch.mesh import make_production_mesh
from repro.roofline.hlo import analyze_hlo


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--grad-mb", type=int, default=64,
                    help="per-device gradient MiB to reduce cross-pod")
    ap.add_argument("--out", type=str, default="artifacts/dryrun")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=True)      # (2, 16, 16)
    n = args.grad_mb * 2**20 // 4

    def reduce_compressed(g, ef):
        out, new_ef = compressed_allreduce(g, ef, "pod")
        return out, new_ef

    def reduce_fp32(g):
        return jax.lax.pmean(g, "pod")

    g_sds = jax.ShapeDtypeStruct(
        (2 * n,), jnp.float32,
        sharding=NamedSharding(mesh, P("pod")))      # per-pod shard = n

    t0 = time.perf_counter()
    with mesh:
        fc = jax.jit(jax.shard_map(reduce_compressed, mesh=mesh,
                                   in_specs=(P("pod"), P("pod")),
                                   out_specs=(P("pod"), P("pod")),
                                   check_vma=False))
        cc = fc.lower(g_sds, g_sds).compile()
        ff = jax.jit(jax.shard_map(reduce_fp32, mesh=mesh,
                                   in_specs=P("pod"), out_specs=P("pod")))
        cf = ff.lower(g_sds).compile()
    comp = analyze_hlo(cc.as_text(), pod_stride=256)
    base = analyze_hlo(cf.as_text(), pod_stride=256)
    rec = {
        "status": "ok", "mode": "compressed_crosspod_allreduce",
        "mesh": {"pod": 2, "data": 16, "model": 16},
        "compile_s": round(time.perf_counter() - t0, 2),
        "payload_bytes_fp32": float(base.collective_bytes),
        "payload_bytes_int8ef": float(comp.collective_bytes),
        "cut": float(base.collective_bytes /
                     max(comp.collective_bytes, 1.0)),
    }
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "compression__crosspod__512c.json").write_text(
        json.dumps(rec, indent=2))
    print(f"[dryrun-compression] ok fp32={rec['payload_bytes_fp32']:.3g}B "
          f"int8+ef={rec['payload_bytes_int8ef']:.3g}B "
          f"cut={rec['cut']:.2f}x compile={rec['compile_s']}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
