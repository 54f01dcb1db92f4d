import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

The two lines above MUST stay first — jax locks the device count on first
backend initialization, and the production meshes need 512 host-platform
stand-in devices.

For each combination this script jits the right step function with explicit
in/out shardings, ``.lower().compile()``s it, and records:

  * ``memory_analysis()``  — proves the layout fits (bytes per device),
  * ``cost_analysis()``    — HLO FLOPs / bytes for the roofline terms,
  * collective bytes parsed from the compiled HLO (all-gather, all-reduce,
    reduce-scatter, all-to-all, collective-permute),
  * derived roofline terms (seconds) against TPU v5e constants.

Results are persisted incrementally to ``benchmarks/results/dryrun/`` so the
run is resumable; ``--all`` sweeps the full matrix.

Usage::

    python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, set_mesh

from repro.configs import ARCH_IDS, get_config
from repro.launch.hlo_analysis import (
    COLLECTIVE_KINDS,
    RooflineTerms,
    analyze_hlo,
    cost_summary,
    memory_summary,
    model_flops_estimate,
)
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    INPUT_SHAPES,
    batch_shardings,
    batch_specs,
    cache_shardings,
    cache_specs,
    config_for_shape,
    decode_token_specs,
    params_shardings,
    params_specs,
)
from repro.launch.steps import (
    make_fed_round_step,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.models.zoo import Model
from repro.optim.adamw import AdamW, AdamWState

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

# --- §Perf hillclimb variants ------------------------------------------------
# Each entry tweaks one knob relative to the baseline lowering.  Variants are
# lowered with ``--variant <name>`` and recorded as separate result files so
# before/after roofline terms are directly comparable.
VARIANTS: dict[str, dict] = {
    "baseline": {},
    "moe_tp": {"moe_sharding": "tp"},          # expert-TP instead of expert-parallel
    "moe_local": {"moe_sharding": "ep_local"},  # shard-local dispatch (see moe.py)
    "noremat": {"remat": False},               # trade HBM for recompute FLOPs
    "losschunk128": {"loss_chunk": 128},
    "losschunk4096": {"loss_chunk": 4096},
    "kvchunk4096": {"kv_chunk": 4096},
    "fed_k1": {"fed_local_steps": 1},          # FedAvg round, 1 local step
    "fed_k4": {"fed_local_steps": 4},
    "fed_k16": {"fed_local_steps": 16},
    "capacity1": {"capacity_factor": 1.0},
    "capacity2": {"capacity_factor": 2.0},
    "cache_batch": {"cache_mode": "batch"},    # decode cache: batch-only sharding
}


def _opt_state_specs(optimizer: AdamW, p_specs):
    return jax.eval_shape(optimizer.init, p_specs)


def _opt_state_shardings(p_shardings, mesh):
    return AdamWState(
        step=NamedSharding(mesh, P()),
        mu=p_shardings,
        nu=p_shardings,
    )


def _apply_variant_cfg(cfg, spec: dict):
    import dataclasses as _dc

    if cfg.moe is not None:
        moe = cfg.moe
        if "moe_sharding" in spec:
            moe = _dc.replace(moe, expert_sharding=spec["moe_sharding"])
        if "capacity_factor" in spec:
            moe = _dc.replace(moe, capacity_factor=spec["capacity_factor"])
        if moe is not cfg.moe:
            cfg = _dc.replace(cfg, moe=moe)
    return cfg


def lower_combo(
    arch: str,
    shape_name: str,
    mesh_kind: str,
    *,
    variant: str = "baseline",
    extra_tags: dict | None = None,
):
    """Lower + compile one combination; returns the result record."""
    spec_v = VARIANTS[variant]
    shape = INPUT_SHAPES[shape_name]
    cfg = _apply_variant_cfg(config_for_shape(get_config(arch), shape), spec_v)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    model = Model(
        cfg,
        remat=spec_v.get("remat", True),
        loss_chunk=spec_v.get("loss_chunk", 512),
    )
    optimizer = AdamW(learning_rate=1e-4, weight_decay=0.01)

    if "fed_local_steps" in spec_v:
        return _lower_fed_round(
            arch, shape_name, mesh_kind, cfg, mesh, model, optimizer,
            local_steps=spec_v["fed_local_steps"], extra_tags=extra_tags,
        )

    p_specs = params_specs(model)
    with set_mesh(mesh):
        p_shardings = params_shardings(p_specs, cfg, mesh)

        t0 = time.perf_counter()
        if shape.kind == "train":
            step = make_train_step(model, optimizer)
            o_specs = _opt_state_specs(optimizer, p_specs)
            o_shardings = _opt_state_shardings(p_shardings, mesh)
            b_specs = batch_specs(cfg, shape)
            b_shardings = batch_shardings(b_specs, mesh)
            metrics_sharding = NamedSharding(mesh, P())
            jitted = jax.jit(
                step,
                in_shardings=(p_shardings, o_shardings, b_shardings),
                out_shardings=(p_shardings, o_shardings, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(p_specs, o_specs, b_specs)
        elif shape.kind == "prefill":
            step = make_prefill_step(model)
            b_specs = batch_specs(cfg, shape)
            b_shardings = batch_shardings(b_specs, mesh)
            jitted = jax.jit(step, in_shardings=(p_shardings, b_shardings))
            lowered = jitted.lower(p_specs, b_specs)
        else:  # decode
            step = make_serve_step(model)
            c_specs = cache_specs(model, shape)
            c_shardings = cache_shardings(
                c_specs, cfg, mesh, mode=spec_v.get("cache_mode", "heads")
            )
            tok = decode_token_specs(cfg, shape)
            tok_sharding = batch_shardings({"tokens": tok["tokens"]}, mesh)["tokens"]
            jitted = jax.jit(
                step,
                in_shardings=(p_shardings, tok_sharding, c_shardings, NamedSharding(mesh, P())),
                out_shardings=(None, c_shardings),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(p_specs, tok["tokens"], c_specs, tok["pos"])

        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    return _finalize_record(
        compiled, arch, shape_name, mesh_kind, cfg, shape, mesh,
        t_lower, t_compile, extra_tags,
    )


def _finalize_record(
    compiled, arch, shape_name, mesh_kind, cfg, shape, mesh, t_lower, t_compile, extra_tags
):
    cost_raw = cost_summary(compiled)          # per-device, scan-body-once
    mem = memory_summary(compiled)
    analysis = analyze_hlo(compiled.as_text())  # trip-count-aware, per-device
    chips = mesh.devices.size
    coll_per_dev = sum(analysis.get(k, 0.0) for k in COLLECTIVE_KINDS)
    terms = RooflineTerms(
        hlo_flops=analysis["flops"] * chips,
        hlo_bytes=analysis["bytes"] * chips,
        coll_bytes=coll_per_dev * chips,
        chips=chips,
        model_flops=model_flops_estimate(cfg, shape, shape.kind),
    )
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "chips": chips,
        "kind": shape.kind,
        "lower_s": t_lower,
        "compile_s": t_compile,
        "cost_raw": cost_raw,
        "memory": mem,
        "hlo_analysis": analysis,
        "roofline": terms.as_dict(),
        "tags": extra_tags or {},
    }
    return record


def _lower_fed_round(
    arch, shape_name, mesh_kind, cfg, mesh, model, optimizer, *, local_steps, extra_tags
):
    """Lower the FedAvg round step: client-replica axis over (pod, data)."""
    import jax.numpy as jnp

    shape = INPUT_SHAPES[shape_name]
    assert shape.kind == "train", "fed variants apply to train shapes"
    from repro.launch.mesh import data_axes

    daxes = data_axes(mesh)
    n_clients = 1
    for a in daxes:
        n_clients *= mesh.shape[a]
    local_batch = max(shape.global_batch // n_clients, 1)
    client_spec = daxes if len(daxes) > 1 else daxes[0]

    p_specs = params_specs(model)
    with set_mesh(mesh):
        base_shardings = params_shardings(p_specs, cfg, mesh)

        def stack_spec(l):
            return jax.ShapeDtypeStruct((n_clients, *l.shape), l.dtype)

        def stack_shard(s):
            return NamedSharding(mesh, P(client_spec, *s.spec))

        pc_specs = jax.tree.map(stack_spec, p_specs)
        pc_shardings = jax.tree.map(
            stack_shard, base_shardings, is_leaf=lambda x: isinstance(x, NamedSharding)
        )
        oc_specs = jax.tree.map(stack_spec, _opt_state_specs(optimizer, p_specs))
        oc_shardings = AdamWState(
            step=NamedSharding(mesh, P(client_spec)),
            mu=pc_shardings,
            nu=pc_shardings,
        )

        b_one = batch_specs(cfg, shape)
        b_specs = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                (n_clients, local_steps, local_batch, *l.shape[1:]), l.dtype
            ),
            b_one,
        )
        b_shardings = jax.tree.map(
            lambda l: NamedSharding(mesh, P(client_spec, *([None] * (len(l.shape) - 1)))),
            b_specs,
        )
        w_specs = jax.ShapeDtypeStruct((n_clients,), jnp.float32)
        w_sharding = NamedSharding(mesh, P(client_spec))

        step = make_fed_round_step(model, optimizer)
        t0 = time.perf_counter()
        jitted = jax.jit(
            step,
            in_shardings=(pc_shardings, oc_shardings, b_shardings, w_sharding),
            out_shardings=(pc_shardings, oc_shardings, None),
            donate_argnums=(0, 1),
        )
        lowered = jitted.lower(pc_specs, oc_specs, b_specs, w_specs)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    tags = dict(extra_tags or {})
    tags.update({"fed_local_steps": local_steps, "clients": n_clients, "local_batch": local_batch})
    record = _finalize_record(
        compiled, arch, shape_name, mesh_kind, cfg, shape, mesh, t_lower, t_compile, tags
    )
    # normalize: model_flops for ONE local step x clients x local_steps
    record["roofline"]["model_flops"] = (
        record["roofline"]["model_flops"] / shape.global_batch * local_batch * n_clients * local_steps
    )
    return record


def result_path(arch: str, shape: str, mesh_kind: str, variant: str = "baseline") -> Path:
    return RESULTS_DIR / f"{arch}__{shape}__{mesh_kind}__{variant}.json"


def run_combo(arch: str, shape: str, mesh_kind: str, force: bool = False, variant: str = "baseline"):
    out = result_path(arch, shape, mesh_kind, variant)
    if out.exists() and not force:
        print(f"[skip] {arch} x {shape} x {mesh_kind} (cached)")
        return json.loads(out.read_text())
    print(f"[run ] {arch} x {shape} x {mesh_kind} ({variant}) ...", flush=True)
    t0 = time.perf_counter()
    try:
        record = lower_combo(arch, shape, mesh_kind, variant=variant)
        record["variant"] = variant
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(record, indent=1))
        r = record["roofline"]
        print(
            f"[ ok ] {arch} x {shape} x {mesh_kind}: "
            f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
            f"collective={r['collective_s']:.3e}s dominant={r['dominant']} "
            f"(lower+compile {time.perf_counter()-t0:.1f}s)",
            flush=True,
        )
        return record
    except Exception as exc:  # record failures — they are bugs to fix
        err = {
            "arch": arch, "shape": shape, "mesh": mesh_kind, "variant": variant,
            "error": f"{type(exc).__name__}: {exc}",
            "traceback": traceback.format_exc()[-4000:],
        }
        out.parent.mkdir(parents=True, exist_ok=True)
        out.with_suffix(".error.json").write_text(json.dumps(err, indent=1))
        print(f"[FAIL] {arch} x {shape} x {mesh_kind}: {exc}", flush=True)
        return err


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_IDS), default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--variant", choices=list(VARIANTS), default="baseline")
    ap.add_argument("--all", action="store_true", help="sweep all archs x shapes")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = list(ARCH_IDS) if args.all or args.arch is None else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or args.shape is None else [args.shape]

    failures = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                rec = run_combo(arch, shape, mesh_kind, force=args.force, variant=args.variant)
                if "error" in rec:
                    failures += 1
    if failures:
        raise SystemExit(f"{failures} combination(s) failed")
    print("all requested combinations lowered + compiled OK")


if __name__ == "__main__":
    main()
