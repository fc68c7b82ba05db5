"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds <n> <n> <n> ...

The configuration states float32 (``precision`` in its file). The
control is the plain reference put in the program's place and computed
one precision below, in bfloat16 throughout, on the very pool and
weights a run of the cell makes from each seed. Its answers are held to
the float32 reference with the numbers ``bench/compare.py`` computes,
and the readings are printed, one JSON line per seed: the smallest of
them over the seeds is each number's upper reading. The benchmark's own
runs do not run it. Without a TPU it exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell: dict, seed: int) -> dict:
    """The compared numbers of the bfloat16 control on one seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import compare, run

    cfg = cell["config"]
    ref_mod, weights, bn_state, pool = run.make_inputs(
        cfg, cell["traffic"], seed)
    out = {}
    for name, dtype in (("reference", jnp.float32), ("control", jnp.bfloat16)):
        fwd = compare.reference_forward(ref_mod, cfg, dtype)
        out[name] = jax.device_get(fwd(weights, bn_state, pool.counts))
    ref, ctl = out["reference"], out["control"]
    nums = compare.numbers(ctl["layer1_spikes"], ctl["logits"],
                           np.argmax(ctl["logits"], axis=1),
                           np.arange(pool.size), ref)
    return {"seed": seed, **nums,
            "ref_layer1_spikes": float(np.sum(ref["layer1_spikes"]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import catalog, run

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = catalog.cell(args.workload)
    rows = []
    for seed in args.seeds:
        rows.append(readings(cell, seed))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": args.workload, "upper": {
        k: min(r[k] for r in rows) for k in rows[0] if k != "seed"}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
