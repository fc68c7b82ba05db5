"""Deployment handshake: sweep artifact + checkpoint → a servable model.

The offline sweep engine (repro.core.sweep) measures every circuit
variant; serving deploys ONE of them. The handshake has two halves:

  * the **sweep artifact** (``p2m-codesign-sweep/v3`` JSON) is the menu —
    :func:`select_record` picks the record (circuit, v_threshold, sigma,
    T_INTG, n_sub, protocol) to deploy, by accuracy or explicitly;
  * the **checkpoint** (repro.checkpoint.store layout) is the weights —
    :func:`deploy_from_sweep` slices the chosen variant's trained
    layer-1 + backbone (+ BN state) out of a ``keep_params=True`` grid
    run and writes one committed checkpoint whose ``extra`` block embeds
    the record and the full model config, so :func:`load_deployment`
    rebuilds the servable :class:`Deployment` from the checkpoint alone.

``offline_forward`` is the deployment-level batched reference forward —
the oracle the streaming engine (repro.stream.engine) is tested against,
and the precise statement of what "serving this record" computes.
"""
from __future__ import annotations

import hashlib
import json
from dataclasses import asdict, dataclass, field, replace
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.core import backbone, leakage, p2m_layer, snn
from repro.core.analog import AnalogConfig
from repro.core.codesign import P2MModelConfig
from repro.core.leakage import CircuitConfig, LeakageConfig
from repro.core.p2m_layer import P2MConfig

DEPLOY_SCHEMA = "p2m-stream-deploy/v1"


# ---------------------------------------------------------------------------
# model-config (de)serialization — the checkpoint must be self-describing
# ---------------------------------------------------------------------------

def model_config_to_dict(cfg: P2MModelConfig) -> dict:
    """JSON-safe dict of the full model config (enums → values)."""
    d = asdict(cfg)
    d["p2m"]["leak"]["circuit"] = cfg.p2m.leak.circuit.value
    return d


def model_config_from_dict(d: dict) -> P2MModelConfig:
    """Inverse of :func:`model_config_to_dict` (JSON round-trip safe:
    lists are coerced back to the config tuples). The backbone's ``kind``
    picks its config class; a backbone without one is the spiking CNN
    (:func:`repro.core.backbone.config_from_dict`)."""
    p2m = dict(d["p2m"])
    leak = dict(p2m.pop("leak"))
    leak["circuit"] = CircuitConfig(leak["circuit"])
    analog_cfg = AnalogConfig(**p2m.pop("analog"))
    return P2MModelConfig(
        p2m=P2MConfig(**p2m, analog=analog_cfg,
                      leak=LeakageConfig(**leak)),
        backbone=backbone.config_from_dict(d["backbone"]),
        coarse_window_ms=d["coarse_window_ms"])


def leak_config_from_variant(variant: dict, base: LeakageConfig
                             ) -> LeakageConfig:
    """A record's ``"variant"`` dict (core/variant_grid.variant_dict) →
    the LeakageConfig the serving path runs. The record carries the
    RESOLVED comparator threshold, so it is pinned as the per-variant
    override (no model-default fallback ambiguity at load time)."""
    return replace(base,
                   circuit=CircuitConfig(variant["circuit"]),
                   null_mismatch=float(variant["null_mismatch"]),
                   v_threshold=float(variant["v_threshold"]),
                   sigma=float(variant.get("sigma") or 0.0))


# ---------------------------------------------------------------------------
# the servable bundle
# ---------------------------------------------------------------------------

@dataclass
class Deployment:
    """One servable variant: model config pinned to the deployed cell
    (``p2m.t_intg_ms``/``n_sub``/``leak`` = the record's), its trained
    params + BN state, and the sweep record it came from.

    ``meta`` is the registry-facing metadata the checkpoint carries
    beyond the record itself (``dataset``, ``sensor_hw``, ...) — what
    :func:`repro.stream.registry.entry_meta` folds into the catalog row
    so a fleet registry can match streams to variants without reopening
    the training data."""
    model_cfg: P2MModelConfig
    params: dict                 # {"p2m": {...}, "backbone": {...}}
    bn_state: dict
    record: dict
    protocol: str = "frozen"
    meta: dict = field(default_factory=dict)

    @property
    def coeffs(self) -> leakage.LeakCoeffs:
        """Branch-free numerics of the deployed variant — exactly what
        the offline engine's jitted steps ran with."""
        return leakage.leak_coeffs(self.model_cfg.p2m.leak,
                                   self.model_cfg.p2m.v_threshold)

    @property
    def t_intg_ms(self) -> float:
        return self.model_cfg.p2m.t_intg_ms

    def deployed_meta(self) -> dict:
        """The ``"deployed"`` block of the serving-stats artifact."""
        return {"label": self.record.get("label"),
                "protocol": self.protocol,
                "t_intg_ms": self.t_intg_ms,
                "n_sub": self.model_cfg.p2m.n_sub,
                "variant": self.record.get("variant"),
                "accuracy_offline": self.record.get("accuracy")}


def offline_forward(dep: Deployment, events: jax.Array) -> dict:
    """The deployment's offline batched forward — the reference the
    online engine must match (tests/test_streaming.py).

    ``events``: [B, T, n_sub, H, W, 2] binned frames over the full
    stream. Returns the intermediate tensors of the serving contract:
    layer-1 ``spikes`` [B, T, H, W, C] and ``v_pre``, the 2x-``pooled``
    spike maps, the backbone-grid ``coarse`` counts, and the rate-decoded
    ``logits`` [B, n_classes].
    """
    cfg = dep.model_cfg
    spikes, v_pre = p2m_layer.p2m_forward_curvefit_coeffs(
        dep.params["p2m"], events, cfg.p2m, dep.coeffs)
    B, T = spikes.shape[:2]
    tb = snn.max_pool(spikes.reshape((B * T,) + spikes.shape[2:]))
    pooled = tb.reshape((B, T) + tb.shape[1:])
    coarse = p2m_layer.coarsen_spikes(pooled, cfg.coarsen_group())
    logits, _, _ = backbone.apply(dep.params["backbone"], dep.bn_state,
                                  coarse, cfg.backbone, train=False)
    return {"spikes": spikes, "v_pre": v_pre, "pooled": pooled,
            "coarse": coarse, "logits": logits}


def fresh_deployment(model_cfg: P2MModelConfig, *, seed: int = 0,
                     protocol: str = "frozen") -> Deployment:
    """An UNTRAINED deployment (fresh init) — serving-path benchmarks
    measure latency/throughput, which do not need trained weights."""
    from repro.core import codesign, variant_grid

    params, state = codesign.model_init(jax.random.PRNGKey(seed), model_cfg)
    lc = model_cfg.p2m.leak
    record = {
        "label": variant_grid.variant_label(lc),
        "t_intg_ms": model_cfg.p2m.t_intg_ms,
        "n_sub": model_cfg.p2m.n_sub,
        "variant": variant_grid.variant_dict(
            lc, v_threshold_default=model_cfg.p2m.v_threshold,
            n_sub=model_cfg.p2m.n_sub),
        "accuracy": None,
        "untrained": True,
    }
    return Deployment(model_cfg=model_cfg, params=params, bn_state=state,
                      record=record, protocol=protocol)


# ---------------------------------------------------------------------------
# record selection
# ---------------------------------------------------------------------------

def _record_sort_key(r: dict) -> tuple:
    """Total deterministic order over sweep records: best accuracy first,
    ties broken by shortest T_INTG, label, protocol, n_sub, and finally
    the canonical (key-sorted) variant dict. Every component is an
    intrinsic record field — NEVER the position in the records list — so
    selection is reproducible across dict/JSON orderings, which is what
    keeps registry compat keys and deployed checkpoints stable across
    re-serializations of the same artifact."""
    variant = r.get("variant") or {}
    return (-(r.get("accuracy") or 0.0), r["t_intg_ms"],
            str(r.get("label")), str(r.get("protocol")),
            r.get("n_sub") or 0,
            json.dumps(variant, sort_keys=True, default=float))


def select_record(records: list[dict], *, protocol: str | None = None,
                  t_intg_ms: float | None = None,
                  label: str | None = None) -> dict:
    """Pick the record to deploy: filter by protocol / T_INTG / variant
    label, then take the best accuracy. Tie-breaking is TOTAL
    (:func:`_record_sort_key`): equal-accuracy records resolve by
    intrinsic fields, never by input order, so the same artifact always
    deploys the same record however its JSON was (re)serialized."""
    pool = [r for r in records
            if (protocol is None or r.get("protocol") == protocol)
            and (t_intg_ms is None or r["t_intg_ms"] == t_intg_ms)
            and (label is None or r["label"] == label)]
    if not pool:
        raise ValueError(
            f"no sweep record matches protocol={protocol!r} "
            f"t_intg_ms={t_intg_ms!r} label={label!r} "
            f"({len(records)} records total)")
    return min(pool, key=_record_sort_key)


def select_from_artifact(artifact: dict | str | Path, **kwargs) -> dict:
    """``select_record`` over a sweep-artifact dict or JSON path."""
    if isinstance(artifact, (str, Path)):
        artifact = json.loads(Path(artifact).read_text())
    schema = artifact.get("schema", "")
    if not str(schema).startswith("p2m-codesign-sweep/"):
        raise ValueError(f"not a co-design sweep artifact "
                         f"(schema={schema!r})")
    return select_record(artifact["records"], **kwargs)


# ---------------------------------------------------------------------------
# checkpoint save / load
# ---------------------------------------------------------------------------

def save_deployment(directory: str | Path, dep: Deployment) -> Path:
    """Write one committed, self-describing serving checkpoint. The
    ``extra`` block embeds the record, the full model config, and the
    registry metadata (``dep.meta`` — dataset, sensor_hw, ...) so
    :func:`load_deployment` can feed
    :meth:`repro.stream.registry.Registry.register` directly."""
    tree = {"params": dep.params, "bn_state": dep.bn_state}
    extra = {
        "deploy_schema": DEPLOY_SCHEMA,
        "protocol": dep.protocol,
        "record": dep.record,
        "model_config": model_config_to_dict(dep.model_cfg),
        "registry_meta": dict(dep.meta),
    }
    return store.save_checkpoint(directory, 0, tree, extra)


def load_deployment(directory: str | Path,
                    artifact: dict | str | Path | None = None) -> Deployment:
    """Rebuild a :class:`Deployment` from a serving checkpoint.

    ``artifact`` optionally cross-checks the checkpoint against the sweep
    artifact it was deployed from: the embedded record must appear there
    (same label / protocol / T_INTG) — the handshake guard against
    serving weights whose menu entry was regenerated.

    Corrupt or internally inconsistent extras raise ``ValueError``
    instead of mis-deploying: a checkpoint whose embedded record
    disagrees with its embedded model config (t_intg_ms / n_sub / leak
    variant) would serve weights under the WRONG circuit numerics.
    """
    tree, extra = store.load_checkpoint(directory)
    if extra.get("deploy_schema") != DEPLOY_SCHEMA:
        raise ValueError(
            f"{directory} is not a streaming deployment checkpoint "
            f"(extra.deploy_schema={extra.get('deploy_schema')!r}; "
            f"expected {DEPLOY_SCHEMA!r})")
    missing = [k for k in ("record", "model_config", "protocol")
               if k not in extra]
    if missing:
        raise ValueError(
            f"{directory} deployment checkpoint extras are corrupt: "
            f"missing {missing} — re-run deploy_from_sweep")
    try:
        model_cfg = model_config_from_dict(extra["model_config"])
    except (KeyError, TypeError, ValueError) as e:
        raise ValueError(
            f"{directory} deployment checkpoint embeds a malformed "
            f"model_config ({e!r}) — re-run deploy_from_sweep") from e
    record = extra["record"]
    for fld in ("t_intg_ms", "n_sub"):
        if fld in record and record[fld] != getattr(model_cfg.p2m, fld):
            raise ValueError(
                f"{directory} checkpoint record/model_config mismatch: "
                f"record.{fld}={record[fld]!r} but model_config pins "
                f"{getattr(model_cfg.p2m, fld)!r} — the extras were "
                f"tampered with or mixed from different deployments")
    variant = record.get("variant") or {}
    if ("circuit" in variant
            and variant["circuit"] != model_cfg.p2m.leak.circuit.value):
        raise ValueError(
            f"{directory} checkpoint record/model_config mismatch: "
            f"record.variant.circuit={variant['circuit']!r} but "
            f"model_config pins {model_cfg.p2m.leak.circuit.value!r} — "
            f"serving would run the wrong leak numerics")
    tree = jax.tree.map(jnp.asarray, tree)
    dep = Deployment(
        model_cfg=model_cfg,
        params=tree["params"], bn_state=tree["bn_state"],
        record=record, protocol=extra["protocol"],
        meta=dict(extra.get("registry_meta") or {}))
    if artifact is not None:
        _check_against_artifact(dep, artifact)
    return dep


def _check_against_artifact(dep: Deployment,
                            artifact: dict | str | Path) -> None:
    if isinstance(artifact, (str, Path)):
        artifact = json.loads(Path(artifact).read_text())
    key = ("label", "protocol", "t_intg_ms", "n_sub")
    want = tuple(dep.record.get(k) for k in key)
    for r in artifact.get("records", []):
        if tuple(r.get(k) for k in key) == want:
            return
    raise ValueError(
        f"checkpoint record {dict(zip(key, want))} not found in the sweep "
        f"artifact — the artifact and checkpoint are from different runs")


def deploy_from_sweep(result: Any, model_cfg: P2MModelConfig, record: dict,
                      directory: str | Path,
                      meta: dict | None = None) -> Path:
    """Slice ``record``'s variant out of a ``keep_params=True``
    :class:`~repro.core.sweep.GridResult` and write its serving
    checkpoint. Frozen cells share one layer-1; unfrozen cells carry a
    per-variant stacked layer-1 that is sliced like the backbone.
    ``meta`` (dataset, sensor_hw, ...) is persisted as the checkpoint's
    registry metadata (see repro.stream.registry)."""
    cell = (record["t_intg_ms"], record["n_sub"])
    if cell not in result.final_params:
        raise ValueError(
            f"grid result holds no final params for cell {cell} — run the "
            f"sweep with keep_params=True (cells kept: "
            f"{sorted(result.final_params)})")
    g = list(result.labels).index(record["label"])
    fp = result.final_params[cell]
    take = lambda tree: jax.tree.map(lambda v: v[g], tree)  # noqa: E731
    p2m_params = (take(fp["p2m"]) if result.protocol == "unfrozen"
                  else fp["p2m"])
    leak = leak_config_from_variant(record["variant"], model_cfg.p2m.leak)
    cfg_cell = replace(model_cfg, p2m=replace(
        model_cfg.p2m, t_intg_ms=record["t_intg_ms"],
        n_sub=record["n_sub"], mode="curvefit", leak=leak))
    dep = Deployment(model_cfg=cfg_cell,
                     params={"p2m": p2m_params,
                             "backbone": take(fp["backbone"])},
                     bn_state=take(fp["state"]),
                     record=record, protocol=result.protocol,
                     meta=dict(meta or {}))
    return save_deployment(directory, dep)


# ---------------------------------------------------------------------------
# adaptation delta checkpoints (repro.stream.adapt → new registry entries)
# ---------------------------------------------------------------------------

ADAPT_DELTA_SCHEMA = "p2m-stream-adapt-delta/v1"


def deployment_digest(dep: Deployment) -> str:
    """Content digest of a deployment as an ADAPTATION BASE: the full
    model config plus the exact quantized layer-1 weights and comparator
    threshold the per-lane deltas are relative to. A delta checkpoint is
    only meaningful against the base it was learned on —
    :func:`load_adapt_delta` refuses to apply one whose stamped digest
    does not match the offered base."""
    w_q = p2m_layer.effective_weights(dep.params["p2m"], dep.model_cfg.p2m)
    h = hashlib.sha256()
    h.update(json.dumps(model_config_to_dict(dep.model_cfg),
                        sort_keys=True, default=float).encode())
    h.update(np.asarray(w_q, np.float32).tobytes())
    h.update(np.float32(dep.coeffs.v_threshold).tobytes())
    return h.hexdigest()[:16]


def save_adapt_delta(directory: str | Path, base: Deployment, *,
                     dw, dtheta: float, base_name: str = "default",
                     base_uid: int = 0, lane: int = 0, n_updates: int = 0,
                     rule: str = "surrogate",
                     meta: dict | None = None) -> Path:
    """Write one adapted lane's deltas as a committed delta checkpoint.

    ``dw``/``dtheta`` are relative to ``base``'s QUANTIZED layer-1
    weights and deployed threshold (the convention of
    :meth:`repro.stream.engine.StreamEngine.harvest` — the lane served
    ``quantize(w_q_base + dw)`` at ``theta_base + dtheta``). The extras
    stamp the base's registry identity (``base_name``/``base_uid``) and
    its content digest, so a later :func:`load_adapt_delta` can validate
    the delta is being applied to the exact base it was learned on."""
    dw = np.asarray(dw, np.float32)
    w_q = p2m_layer.effective_weights(base.params["p2m"],
                                      base.model_cfg.p2m)
    if dw.shape != w_q.shape:
        raise ValueError(
            f"dw shape {dw.shape} does not match the base's layer-1 "
            f"weights {tuple(w_q.shape)}")
    tree = {"dw": dw, "dtheta": np.float32(dtheta)}
    extra = {
        "delta_schema": ADAPT_DELTA_SCHEMA,
        "base": {"name": base_name, "uid": int(base_uid),
                 "digest": deployment_digest(base)},
        "lane": int(lane),
        "n_updates": int(n_updates),
        "rule": rule,
        "meta": dict(meta or {}),
    }
    return store.save_checkpoint(directory, 0, tree, extra)


def load_adapt_delta(directory: str | Path, base: Deployment, *,
                     expect_uid: int | None = None) -> dict:
    """Load a delta checkpoint and validate it against ``base``.

    Raises ``ValueError`` when the checkpoint is not a delta, the stamped
    base digest does not match ``base`` (tampered extras, or a delta
    learned against different weights/config), the delta shape is wrong,
    or ``expect_uid`` (e.g. the uid of the CURRENT registration of the
    base name) disagrees with the stamped uid — the stale-base guard
    against applying deltas across a hot-swap."""
    tree, extra = store.load_checkpoint(directory)
    if extra.get("delta_schema") != ADAPT_DELTA_SCHEMA:
        raise ValueError(
            f"{directory} is not an adaptation delta checkpoint "
            f"(extra.delta_schema={extra.get('delta_schema')!r}; "
            f"expected {ADAPT_DELTA_SCHEMA!r})")
    stamped = extra.get("base") or {}
    missing = [k for k in ("name", "uid", "digest") if k not in stamped]
    if missing:
        raise ValueError(f"{directory} delta checkpoint base stamp is "
                         f"corrupt: missing {missing}")
    digest = deployment_digest(base)
    if stamped["digest"] != digest:
        raise ValueError(
            f"{directory} delta was learned against base digest "
            f"{stamped['digest']} but the offered deployment digests to "
            f"{digest} — applying it would adapt the wrong weights")
    if expect_uid is not None and int(stamped["uid"]) != int(expect_uid):
        raise ValueError(
            f"{directory} delta is stamped for base uid {stamped['uid']} "
            f"but the live registration is uid {expect_uid} — the base "
            f"entry was hot-swapped since this delta was harvested")
    dw = np.asarray(tree["dw"], np.float32)
    w_q = p2m_layer.effective_weights(base.params["p2m"],
                                      base.model_cfg.p2m)
    if dw.shape != tuple(w_q.shape):
        raise ValueError(
            f"{directory} delta dw shape {dw.shape} does not match the "
            f"base's layer-1 weights {tuple(w_q.shape)}")
    return {"dw": dw, "dtheta": float(tree["dtheta"]),
            "base_name": stamped["name"], "base_uid": int(stamped["uid"]),
            "lane": int(extra.get("lane", 0)),
            "n_updates": int(extra.get("n_updates", 0)),
            "rule": extra.get("rule"), "meta": dict(extra.get("meta") or {})}


def apply_adapt_delta(base: Deployment, delta: dict, *,
                      label_suffix: str = "+adapt") -> Deployment:
    """Fold a (validated) delta into ``base`` → a new servable
    :class:`Deployment` that computes exactly what the adapted lane was
    serving: raw layer-1 weights ``w_q_base + dw`` (whose quantization
    reproduces the lane's effective weights — the quantizer is
    idempotent on grid points and ``dw`` is clipped well inside the clip
    range) and comparator threshold ``theta_base + dtheta`` pinned as
    the leak-config override. The compat key is unchanged (leak and
    threshold are excluded from it), so the result registers beside its
    base in the same registry and re-serves from the same engine."""
    cfg = base.model_cfg
    w_q = p2m_layer.effective_weights(base.params["p2m"], cfg.p2m)
    new_theta = float(base.coeffs.v_threshold) + float(delta["dtheta"])
    model_cfg = replace(cfg, p2m=replace(
        cfg.p2m, leak=replace(cfg.p2m.leak, v_threshold=new_theta)))
    variant = dict(base.record.get("variant") or {})
    if "v_threshold" in variant:
        variant["v_threshold"] = new_theta
    record = {
        **base.record,
        "label": f"{base.record.get('label')}{label_suffix}",
        "variant": variant,
        "adapted": {"base_name": delta.get("base_name", "default"),
                    "base_uid": int(delta.get("base_uid", 0)),
                    "lane": int(delta.get("lane", 0)),
                    "n_updates": int(delta.get("n_updates", 0)),
                    "rule": delta.get("rule"),
                    "dw_norm": float(np.linalg.norm(delta["dw"]))},
    }
    params = {"p2m": {**base.params["p2m"],
                      "w": jnp.asarray(w_q) + jnp.asarray(delta["dw"])},
              "backbone": base.params["backbone"]}
    return Deployment(model_cfg=model_cfg, params=params,
                      bn_state=base.bn_state, record=record,
                      protocol=base.protocol, meta=dict(base.meta))


# ---------------------------------------------------------------------------
# one-call train → artifact + checkpoints (smoke CLI / tests)
# ---------------------------------------------------------------------------

def train_and_deploy(out_dir: str | Path, *, dataset: str = "synthetic-gesture",
                     data_root: str | None = None, hw: int = 16,
                     protocols: tuple[str, ...] = ("frozen",),
                     t_intg_grid_ms: tuple[float, ...] | None = None,
                     circuits: tuple[CircuitConfig, ...] | None = None,
                     smoke: bool = False,
                     deploy_t_intg_ms: float | None = None,
                     log: Any = print) -> dict:
    """Run a (fast-grid) co-design sweep with ``keep_params=True``, write
    the sweep artifact, and deploy the best record per protocol as a
    serving checkpoint. Returns ``{"artifact": path, "checkpoints":
    {protocol: ckpt dir}, "records": {protocol: record}, "results":
    {protocol: GridResult}, "source": train EventSource}``.

    ``smoke`` shrinks the step counts to CI scale;
    ``deploy_t_intg_ms`` pins the deployed record's integration time
    (default: best accuracy anywhere on the grid).
    """
    from repro.core import sweep as engine
    from repro.data import sources as sources_mod

    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    data, model, sweep_cfg, grid = engine.paper_setup(
        fast=True, hw=hw, dataset=dataset, data_root=data_root)
    if smoke:
        sweep_cfg = replace(sweep_cfg, batch_size=2, pretrain_steps=2,
                            finetune_steps=1, eval_batches=1)
    if t_intg_grid_ms is not None:
        ok = set(engine.fit_t_grid(t_intg_grid_ms, data.duration_ms,
                                   model.coarse_window_ms))
        bad = [t for t in t_intg_grid_ms if t not in ok]
        if bad:
            raise ValueError(
                f"T_INTG values {bad} do not divide the coarse window "
                f"({model.coarse_window_ms:g} ms) and stream duration "
                f"({data.duration_ms:g} ms)")
        grid = replace(grid, t_intg_grid_ms=tuple(t_intg_grid_ms))
    if circuits is not None:
        grid = replace(grid, circuits=tuple(circuits))
    eval_data, eval_split = sources_mod.resolve_eval_dataset(
        dataset, hw=hw, data_root=data_root)
    results = engine.run_protocols(data, model, sweep_cfg, grid,
                                   protocols=protocols, log=log,
                                   eval_data=eval_data, keep_params=True)
    artifact = engine.protocols_artifact(results, extra_meta={
        "data": {"name": data.name, "dataset": dataset,
                 "data_root": data_root, "hw": data.height,
                 "n_classes": data.n_classes,
                 "duration_ms": data.duration_ms,
                 "eval_split": eval_split}})
    artifact_path = out / "codesign_grid_deploy.json"
    artifact_path.write_text(json.dumps(artifact, indent=2, default=float))
    checkpoints: dict[str, Path] = {}
    chosen: dict[str, dict] = {}
    for proto, result in results.items():
        rec = select_record(result.records, t_intg_ms=deploy_t_intg_ms)
        ckpt_dir = out / f"ckpt_{proto}"
        deploy_from_sweep(result, model, rec, ckpt_dir,
                          meta={"dataset": dataset,
                                "sensor_hw": list(data.sensor_hw)})
        checkpoints[proto] = ckpt_dir
        chosen[proto] = rec
        log(f"[deploy] {proto}: {rec['label']} @ T={rec['t_intg_ms']:g}ms "
            f"acc={rec['accuracy']:.3f} -> {ckpt_dir}")
    return {"artifact": artifact_path, "checkpoints": checkpoints,
            "records": chosen, "results": results, "source": data}
