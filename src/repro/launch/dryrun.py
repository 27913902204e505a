import os
# A fake-512-device CPU tool: pin this process and the per-cell children it
# starts (which inherit os.environ) to the CPU, so none of them reaches for
# an attached accelerator.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds the real step function (train / prefill /
decode), resolves parameter/cache/batch shardings through the logical rules,
then ``jit(...).lower(...).compile()`` against ShapeDtypeStructs — nothing is
allocated.  It records ``memory_analysis()`` (fits-in-HBM proof),
``cost_analysis()`` (FLOPs/bytes for the roofline), and the collective
schedule parsed from the compiled HLO, as one JSON artifact per cell under
``--out`` (default benchmarks/artifacts/dryrun).

Run one cell:   python -m repro.launch.dryrun --arch mamba2-2.7b \
                    --shape train_4k --mesh single
Run the matrix: python -m repro.launch.dryrun --all --jobs 3
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# Also holds where jax was imported before this module (python -m imports
# the parent packages first): the backend has not been chosen yet.
jax.config.update("jax_platforms", "cpu")

from repro.configs import get_config, shapes as shp
from repro.configs.registry import ASSIGNED, list_archs
from repro.distributed import api as dist_api
from repro.distributed.sharding import make_shardings, resolve_spec
from repro.launch import flops as flops_mod, hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.nn.params import ParamSpec, abstract_params, count_params
from repro.train import TrainConfig, abstract_state, make_train_step

DEFAULT_OUT = Path(__file__).resolve().parents[3] / \
    "benchmarks" / "artifacts" / "dryrun"


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------

def batch_axes_for(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def shard_batch(tree, mesh, batch_axes, seq_axes=None):
    bsize = 1
    for a in batch_axes:
        bsize *= mesh.shape[a]
    ssize = mesh.shape.get(seq_axes, 1) if isinstance(seq_axes, str) else 1

    def one(x):
        spec = [None] * x.ndim
        if x.ndim and x.shape[0] % bsize == 0 and x.shape[0] >= bsize:
            spec[0] = batch_axes
        if seq_axes and x.ndim > 1 and x.shape[1] % ssize == 0 and \
                x.shape[1] >= ssize:
            spec[1] = seq_axes
        return NamedSharding(mesh, P(*spec))
    return jax.tree.map(one, tree)


def shard_cache(tree, mesh, cfg, batch: int):
    """Heuristic cache layout: batch dim over (pod,data); the last
    model-axis-divisible feature dim over 'model' (so 32k KV caches fit)."""
    baxes = batch_axes_for(mesh)
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]
    msize = mesh.shape.get("model", 1)

    # Leading stack axes that must stay unsharded: scan-stacked layers and
    # recurrentgemma's group-stacked serving caches.
    stack_sizes = {cfg.n_layers}
    if cfg.block_pattern:
        stack_sizes.add(cfg.n_layers // len(cfg.block_pattern))

    def one(x):
        spec = [None] * x.ndim
        used_b = False
        for d, size in enumerate(x.shape):
            if d == 0 and size in stack_sizes and cfg.scan_layers:
                continue
            if not used_b and size == batch and size % bsize == 0:
                spec[d] = baxes
                used_b = True
        for d in range(x.ndim - 1, -1, -1):
            if spec[d] is None and d > 0 and x.shape[d] % msize == 0 and \
                    x.shape[d] >= msize:
                spec[d] = "model"
                break
        return NamedSharding(mesh, P(*spec))
    return jax.tree.map(one, tree)


def state_shardings(model, train_cfg, mesh, extra_rules=()):
    specs = model.param_specs()
    param_sh, report = make_shardings(specs, mesh, extra_rules)
    return {
        "params": param_sh,
        "opt": {
            "step": NamedSharding(mesh, P()),
            "m": param_sh,
            "v": param_sh,
        },
    }, report


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------

def _lower_cell(cfg, shape, mesh, model, train_cfg):
    """Build jit(step) + abstract args for a cell; returns (jitted, args,
    report)."""
    baxes = batch_axes_for(mesh)
    if shape.kind == "train":
        step = make_train_step(model, train_cfg, mesh)
        state_abs = abstract_state(model, train_cfg)
        state_sh, report = state_shardings(model, train_cfg, mesh)
        batch_abs = shp.batch_inputs(cfg, shape)
        seq_axes = dist_api.current_layout()["seq"]
        batch_sh = shard_batch(batch_abs, mesh, baxes, seq_axes)
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None))
        return jitted, (state_abs, batch_abs), report

    params_abs = abstract_params(model.param_specs(), cfg.dtype)
    param_sh, report = make_shardings(model.param_specs(), mesh)
    cache_abs = shp.abstract_cache(model, cfg, shape, cfg.dtype)
    cache_sh = shard_cache(cache_abs, mesh, cfg, shape.global_batch)
    if shape.kind == "prefill":
        def step(params, batch, cache):
            return model.prefill(params, batch, cache)
        batch_abs = shp.prefill_inputs(cfg, shape)
        seq_axes = dist_api.current_layout()["seq"]
        batch_sh = shard_batch(batch_abs, mesh, baxes, seq_axes)
        jitted = jax.jit(step, in_shardings=(param_sh, batch_sh, cache_sh),
                         out_shardings=(None, cache_sh))
        return jitted, (params_abs, batch_abs, cache_abs), report

    def step(params, token, cache, index):
        return model.decode_step(params, token, cache, index)
    tok_abs = shp.decode_inputs(cfg, shape)["token"]
    tok_sh = shard_batch(tok_abs, mesh, baxes)
    idx_abs = jax.ShapeDtypeStruct((), jnp.int32)
    jitted = jax.jit(step, in_shardings=(param_sh, tok_sh, cache_sh,
                                         NamedSharding(mesh, P())),
                     out_shardings=(None, cache_sh))
    return jitted, (params_abs, tok_abs, cache_abs, idx_abs), report


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             xamba_override=None, overrides=None) -> dict:
    from repro.core import accounting

    t0 = time.time()
    cfg = get_config(arch)
    if xamba_override is not None:
        cfg = cfg.replace(xamba=xamba_override)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = shp.SHAPES[shape_name]
    skip = shp.applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "kind": shape.kind, "ok": False}
    if skip:
        rec.update(ok=True, skipped=True, skip_reason=skip)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    baxes = batch_axes_for(mesh)
    # Optimizer dtype policy: >64B-param archs (grok-1) hold Adam moments in
    # bf16 so state fits v5e HBM (params bf16 + m/v bf16 = 6 bytes/param).
    from repro.optim import AdamWConfig
    probe = build_model(cfg)
    big = count_params(probe.param_specs()) > 64e9
    opt_cfg = AdamWConfig(m_dtype="bfloat16" if big else "float32",
                          v_dtype="bfloat16" if big else "float32")
    train_cfg = TrainConfig(optimizer=opt_cfg)
    # Megatron-style sequence parallelism: between TP regions the residual
    # stream is sharded over "model" along the sequence dim (SP), so
    # per-device activations scale 1/(data*model) instead of 1/data.
    # Exception: recurrentgemma's RG-LRU associative scan over a model-
    # sharded sequence axis sends the SPMD partitioner into pathological
    # compile times (>25 min); its activations are small enough (d=2560)
    # that data-parallel-only sharding fits comfortably.
    seq_axes = "model" if shape.kind in ("train", "prefill") and \
        cfg.family != "recurrentgemma" else None

    # --- pass 1: production (rolled-scan) module -> memory analysis -------
    # Scanned layer stacks force per-layer sequential scheduling, so the
    # temp-buffer peak reflects real execution; the unrolled module's peak
    # is a scheduler artifact on the CPU backend (see DESIGN.md §7).
    # Train cells that miss the 16 GB budget retry with more microbatches
    # (gradient accumulation halves live activations each doubling).
    model = build_model(cfg)
    total_params = count_params(model.param_specs())
    rec["params"] = total_params
    mb_candidates = (1, 2, 4, 8) if shape.kind == "train" else (1,)
    for mb in mb_candidates:
        train_cfg = TrainConfig(optimizer=opt_cfg, microbatches=mb)
        with mesh, dist_api.activation_layout(batch_axes=baxes,
                                              seq_axes=seq_axes):
            jitted, args, report = _lower_cell(cfg, shape, mesh, model,
                                               train_cfg)
            rec["sharding_fallbacks"] = report.fallbacks
            t1 = time.time()
            compiled_mem = jitted.lower(*args).compile()
            rec["compile_mem_s"] = round(time.time() - t1, 2)
        ma = compiled_mem.memory_analysis()
        mem = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        mem["total_bytes"] = mem["argument_bytes"] + mem["temp_bytes"]
        mem["total_gb"] = round(mem["total_bytes"] / 2**30, 3)
        mem["fits_16gb_hbm"] = mem["total_bytes"] <= 16 * 2**30
        mem["microbatches"] = mb
        rec["memory"] = mem
        print(f"memory_analysis (rolled, mb={mb}):", ma)
        del compiled_mem
        if mem["fits_16gb_hbm"]:
            break

    # --- pass 2: unrolled accounting -> cost analysis + collectives -------
    # cost_analysis counts while-loop bodies once, so the layer stack and
    # inner scans (attention kv blocks, SSD chunks) must be unrolled for
    # exact totals.  Fully-unrolled deep stacks are slow to compile on this
    # 1-core box, so we measure f(base) and f(base+period) unrolled and
    # extrapolate linearly — exact for homogeneous stacks (validated against
    # a full unroll; see EXPERIMENTS.md §Dry-run).
    def measure(n_layers_override):
        kw = {"scan_layers": False, "n_layers": n_layers_override}
        if cfg.family == "whisper":
            kw["encoder_layers"] = n_layers_override
        cfg_a = cfg.replace(**kw)
        model_a = build_model(cfg_a)
        with mesh, dist_api.activation_layout(batch_axes=baxes,
                                              seq_axes=seq_axes), \
                accounting.unroll_inner_scans():
            jitted, args, _ = _lower_cell(cfg_a, shape, mesh, model_a,
                                          train_cfg)
            compiled = jitted.lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        coll = hlo_analysis.parse_collectives(compiled.as_text())
        return {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll_operand": float(coll.total_operand_bytes),
            "coll_wire": float(coll.total_wire_bytes),
            "coll_counts": coll.counts,
            "coll_operand_by_op": coll.operand_bytes,
        }

    if cfg.family == "recurrentgemma":
        base_l, period = 2, len(cfg.block_pattern or ("r", "r", "a"))
    else:
        base_l, period = 1, 1
    t1 = time.time()
    if cfg.n_layers <= base_l + period:
        m_hi = measure(cfg.n_layers)
        m_lo = None
        n_periods = 0
    else:
        m_lo = measure(base_l)
        m_hi = measure(base_l + period)
        n_periods = (cfg.n_layers - base_l) // period
    rec["compile_acct_s"] = round(time.time() - t1, 2)

    def extrap(key):
        if m_lo is None:
            return m_hi[key]
        return m_lo[key] + n_periods * (m_hi[key] - m_lo[key])

    flops_dev = extrap("flops")
    bytes_dev = extrap("bytes")
    rec["cost"] = {"flops_per_device": flops_dev,
                   "bytes_per_device": bytes_dev,
                   "acct_mode": "marginal" if m_lo else "full",
                   "acct_layers": [base_l, base_l + period],
                   "n_periods": n_periods}
    print("cost_analysis: flops=%.3e bytes=%.3e (per device, extrapolated)"
          % (flops_dev, bytes_dev))

    counts = dict(m_hi["coll_counts"])
    if m_lo is not None:
        for op in set(counts) | set(m_lo["coll_counts"]):
            hi = m_hi["coll_counts"].get(op, 0)
            lo = m_lo["coll_counts"].get(op, 0)
            counts[op] = lo + n_periods * (hi - lo)
    coll_operand = extrap("coll_operand")
    coll_wire = extrap("coll_wire")
    rec["collectives"] = {
        "counts": counts,
        "total_operand_bytes": coll_operand,
        "total_wire_bytes": coll_wire,
    }

    class _Coll:  # adapter for roofline_terms below
        total_operand_bytes = coll_operand
        total_wire_bytes = coll_wire
    coll = _Coll()

    chips = mesh.devices.size
    mf = flops_mod.model_flops(cfg, shape, total_params)
    terms = hlo_analysis.roofline_terms(
        flops_dev, bytes_dev, coll.total_operand_bytes,
        coll.total_wire_bytes)
    terms["model_flops"] = mf
    terms["hlo_flops_global"] = flops_dev * chips
    terms["useful_ratio"] = mf / (flops_dev * chips) if flops_dev else 0.0
    rec["roofline"] = terms
    rec["chips"] = chips
    rec["ok"] = True
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


# ---------------------------------------------------------------------------
# Orchestrator
# ---------------------------------------------------------------------------

def cell_path(out_dir: Path, arch, shape, mesh_kind, tag="") -> Path:
    suffix = f"-{tag}" if tag else ""
    return out_dir / f"{arch}__{shape}__{mesh_kind}{suffix}.json"


def run_matrix(archs, shape_names, mesh_kinds, out_dir: Path, jobs: int,
               skip_existing: bool, tag: str = "", extra_args=()):
    cells = []
    for arch in archs:
        cfg = get_config(arch)
        for sname in shape_names:
            if shp.applicable(cfg, shp.SHAPES[sname]):
                # record the skip without a subprocess
                rec = {"arch": arch, "shape": sname, "kind":
                       shp.SHAPES[sname].kind, "ok": True, "skipped": True,
                       "skip_reason": shp.applicable(cfg, shp.SHAPES[sname])}
                for mk in mesh_kinds:
                    rec2 = dict(rec, mesh=mk)
                    p = cell_path(out_dir, arch, sname, mk, tag)
                    p.parent.mkdir(parents=True, exist_ok=True)
                    p.write_text(json.dumps(rec2, indent=1))
                continue
            for mk in mesh_kinds:
                p = cell_path(out_dir, arch, sname, mk, tag)
                if skip_existing and p.exists():
                    try:
                        if json.loads(p.read_text()).get("ok"):
                            continue
                    except json.JSONDecodeError:
                        pass
                cells.append((arch, sname, mk, p))

    print(f"[dryrun] {len(cells)} cells to run, jobs={jobs}")
    running = []
    idx = 0
    failures = 0
    while idx < len(cells) or running:
        while idx < len(cells) and len(running) < jobs:
            arch, sname, mk, p = cells[idx]
            idx += 1
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", sname, "--mesh", mk,
                   "--out", str(out_dir)]
            if tag:
                cmd += ["--tag", tag]
            cmd += list(extra_args)
            log = p.with_suffix(".log").open("w")
            proc = subprocess.Popen(cmd, stdout=log, stderr=log)
            running.append((proc, arch, sname, mk, p, time.time()))
            print(f"[dryrun] start {arch} {sname} {mk}")
        time.sleep(3)
        still = []
        for proc, arch, sname, mk, p, ts in running:
            if proc.poll() is None:
                still.append((proc, arch, sname, mk, p, ts))
                continue
            ok = p.exists() and json.loads(p.read_text()).get("ok", False) \
                if p.exists() else False
            status = "OK" if ok else f"FAIL(rc={proc.returncode})"
            if not ok:
                failures += 1
            print(f"[dryrun] done  {arch} {sname} {mk}: {status} "
                  f"({time.time() - ts:.0f}s)")
        running = still
    print(f"[dryrun] matrix complete, {failures} failure(s)")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(shp.SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--assigned-only", action="store_true",
                    help="only the 10 assigned archs (skip 130m cells)")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    ap.add_argument("--no-skip-existing", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="ModelConfig override key=value (perf variants)")
    args = ap.parse_args()

    def _parse_override(kv):
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                return k, cast(v)
            except ValueError:
                pass
        if v in ("true", "false"):
            return k, v == "true"
        return k, v

    overrides = dict(_parse_override(kv) for kv in args.override) or None
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all or args.arch is None:
        archs = ASSIGNED if args.assigned_only or args.all else list_archs()
        if args.arch:
            archs = [args.arch]
        rc = run_matrix(archs, list(shp.SHAPES), ["single", "multi"],
                        out_dir, args.jobs, not args.no_skip_existing,
                        args.tag)
        sys.exit(1 if rc else 0)

    rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
           "ok": False}
    try:
        rec = run_cell(args.arch, args.shape, args.mesh, out_dir,
                       overrides=overrides)
        rec["overrides"] = overrides
    except Exception as e:  # noqa: BLE001 — recorded per-cell
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()
        print(rec["traceback"])
    path = cell_path(out_dir, args.arch, args.shape, args.mesh, args.tag)
    path.write_text(json.dumps(rec, indent=1))
    print(f"[dryrun] wrote {path} ok={rec['ok']}")
    sys.exit(0 if rec["ok"] else 1)


if __name__ == "__main__":
    main()
