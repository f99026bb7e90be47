import os
# a CPU emulation of 512 devices by design: never take a TPU
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step, in_shardings=..., out_shardings=...).lower(
**input_specs(arch)).compile()`` must succeed on the 16x16 single-pod mesh
(256 chips) AND the 2x16x16 multi-pod mesh (512 chips) for every cell, and
for the ScalaBFS engine itself (push + pull step programs at Q=256/512
graph shards).  The compiled artifact feeds §Roofline:

  * ``compiled.memory_analysis()``  -> bytes-per-device (proves it fits)
  * ``compiled.cost_analysis()``    -> XLA's own FLOPs/bytes (loop bodies
    counted ONCE - recorded for reference)
  * ``launch.hlo_analysis``         -> loop-aware FLOPs / HBM bytes /
    collective bytes parsed from the optimized HLO (what the roofline uses)

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --bfs rmat22-16 [--multi-pod] \
      [--dispatch bitmap|queue] [--crossbar staged|flat]
  python -m repro.launch.dryrun --all        # fan out every cell (resumable)

``--all`` runs each cell in a fresh subprocess (bounded memory, resumable:
cells with an existing JSON under --out are skipped).
"""
import argparse
import json
import subprocess
import sys
import time
import traceback


def _mesh_tag(multi_pod: bool) -> str:
    return "2x16x16" if multi_pod else "16x16"


def _memory_summary(compiled) -> dict:
    try:
        m = compiled.memory_analysis()
    except Exception as e:                                  # pragma: no cover
        return {"error": repr(e)}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(m, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out["repr"] = str(m)
    return out


def _cost_summary(compiled) -> dict:
    try:
        c = compiled.cost_analysis()
    except Exception as e:                                  # pragma: no cover
        return {"error": repr(e)}
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    keep = {}
    for k, v in dict(c).items():
        if k in ("flops", "bytes accessed", "transcendentals",
                 "optimal_seconds") or k.startswith("bytes accessed"):
            keep[k] = float(v)
    return keep


def lower_lm_cell(arch: str, shape_name: str, multi_pod: bool,
                  keep_hlo: bool = False, microbatches: int = 8,
                  overrides: dict | None = None) -> dict:
    """Lower + compile one LM cell; returns the §Dry-run/§Roofline record."""
    import dataclasses

    import jax  # noqa: F401  (device count locked by XLA_FLAGS above)

    from repro.configs import get_config
    from repro.launch import hlo_analysis, roofline
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES, cell_is_applicable, input_specs
    from repro.models.transformer import abstract_params
    from repro.train.step import (TrainConfig, abstract_train_state,
                                  build_prefill_step, build_serve_step,
                                  build_train_step)

    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    cell = SHAPES[shape_name]
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": _mesh_tag(multi_pod),
        "kind": cell.kind, "overrides": overrides or {},
    }
    ok, why = cell_is_applicable(cfg, cell)
    if not ok:
        rec["skipped"] = why
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    specs = input_specs(cfg, shape_name)

    t0 = time.time()
    if cell.kind == "train":
        st = abstract_train_state(cfg)
        tcfg = TrainConfig(microbatches=microbatches)
        rec["microbatches"] = microbatches
        fn, _, _ = build_train_step(cfg, mesh, tcfg=tcfg, abstract_state=st,
                                    abstract_batch=specs["batch"])
        lowered = fn.lower(st, specs["batch"])
        tokens = cell.global_batch * cell.seq_len
    elif cell.kind == "prefill":
        ap = abstract_params(cfg)
        fn, _, _ = build_prefill_step(cfg, mesh, abstract_params=ap,
                                      abstract_batch=specs["batch"])
        lowered = fn.lower(ap, specs["batch"])
        tokens = cell.global_batch * cell.seq_len
    else:  # decode
        ap = abstract_params(cfg)
        fn, _, _ = build_serve_step(cfg, mesh, abstract_params=ap,
                                    abstract_caches=specs["caches"],
                                    abstract_tokens=specs["tokens"])
        lowered = fn.lower(ap, specs["caches"], specs["tokens"],
                           specs["pos"])
        tokens = cell.global_batch
    rec["lower_s"] = round(time.time() - t0, 2)

    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)

    rec["memory_analysis"] = _memory_summary(compiled)
    rec["cost_analysis"] = _cost_summary(compiled)

    hlo = compiled.as_text()
    rec["hlo_lines"] = hlo.count("\n")
    per_dev = hlo_analysis.analyze_hlo_text(hlo)
    rec["per_device"] = per_dev
    rec["roofline"] = roofline.analyze_cell(
        per_dev, cell.kind, float(cfg.active_param_count()), float(tokens),
        n_dev)
    rec["n_devices"] = n_dev
    if keep_hlo:
        rec["hlo"] = hlo
    return rec


def lower_bfs_cell(graph_name: str, multi_pod: bool, dispatch: str,
                   crossbar: str, keep_hlo: bool = False) -> dict:
    """Lower + compile the BFS push and pull step programs."""
    import jax  # noqa: F401

    from repro.core.bfs_distributed import DistConfig, DistributedBFS
    from repro.graph.datasets import DATASETS
    from repro.launch import hlo_analysis, roofline
    from repro.launch.mesh import make_production_mesh

    meta = DATASETS[graph_name]
    n = 1 << meta.scale
    # symmetrization of undirected inputs doubles directed-edge count
    avg_deg = meta.edge_factor * (1 if meta.directed else 2)
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = DistConfig(dispatch=dispatch, crossbar=crossbar)
    eng = DistributedBFS.abstract(mesh, n, cfg=cfg)
    sds = eng.abstract_inputs(avg_degree=avg_deg)
    budget = sds["indices"].shape[1]

    rec: dict = {
        "arch": f"scalabfs-{dispatch}-{crossbar}", "shape": graph_name,
        "mesh": _mesh_tag(multi_pod), "kind": "bfs",
        "num_vertices": n, "verts_per_shard": eng.vl, "shards": eng.q,
        "edge_budget": budget,
    }
    for phase, fn_name, args in (
        ("push", "push", (sds["frontier"], sds["visited"], sds["level"],
                          sds["lvl"], sds["indptr"], sds["indices"])),
        ("pull", "pull", (sds["frontier"], sds["visited"], sds["level"],
                          sds["lvl"], sds["indptr"], sds["indices"])),
    ):
        t0 = time.time()
        step = eng._get(fn_name, budget)
        lowered = step.lower(*args)
        compiled = lowered.compile()
        hlo = compiled.as_text()
        per_dev = hlo_analysis.analyze_hlo_text(hlo)
        rec[phase] = {
            "compile_s": round(time.time() - t0, 2),
            "memory_analysis": _memory_summary(compiled),
            "cost_analysis": _cost_summary(compiled),
            "per_device": per_dev,
            "roofline": roofline.roofline_terms(per_dev),
            "hlo_lines": hlo.count("\n"),
        }
        if keep_hlo:
            rec[phase]["hlo"] = hlo
    return rec


# ---------------------------------------------------------------------------
# Fan-out driver (resumable; one subprocess per cell)
# ---------------------------------------------------------------------------

BFS_CELLS = [
    # (graph, dispatch, crossbar) - default engine on both meshes, plus the
    # dispatcher design space on the single pod for §Perf.
    ("rmat22-16", "bitmap", "staged"),
    ("rmat22-16", "bitmap", "flat"),
    ("rmat22-16", "queue", "staged"),
    ("rmat23-64", "bitmap", "staged"),
    ("lj-like", "bitmap", "staged"),
]


def all_cells(out_dir: str):
    from repro.configs import ARCH_NAMES
    from repro.launch.shapes import SHAPES
    cells = []
    for multi_pod in (False, True):
        tag = _mesh_tag(multi_pod)
        for arch in ARCH_NAMES:
            for shape in SHAPES:
                path = os.path.join(out_dir, f"{arch}__{shape}__{tag}.json")
                args = ["--arch", arch, "--shape", shape]
                cells.append((path, args + (["--multi-pod"] if multi_pod
                                            else [])))
        for graph, dispatch, crossbar in BFS_CELLS:
            if multi_pod and (dispatch, crossbar) != ("bitmap", "staged"):
                continue  # design-space sweep is single-pod only
            name = f"bfs-{graph}-{dispatch}-{crossbar}"
            path = os.path.join(out_dir, f"{name}__{tag}.json")
            args = ["--bfs", graph, "--dispatch", dispatch,
                    "--crossbar", crossbar]
            cells.append((path, args + (["--multi-pod"] if multi_pod
                                        else [])))
    return cells


def run_all(out_dir: str, timeout: float = 3000.0) -> int:
    os.makedirs(out_dir, exist_ok=True)
    cells = all_cells(out_dir)
    failures = 0
    for i, (path, args) in enumerate(cells):
        if os.path.exists(path):
            print(f"[{i+1}/{len(cells)}] SKIP (done) {os.path.basename(path)}",
                  flush=True)
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               *args, "--json-out", path]
        t0 = time.time()
        try:
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=timeout,
                               env=dict(os.environ, JAX_PLATFORMS="cpu"))
        except subprocess.TimeoutExpired:
            print(f"[{i+1}/{len(cells)}] TIMEOUT {os.path.basename(path)}",
                  flush=True)
            failures += 1
            continue
        dt = time.time() - t0
        if p.returncode != 0:
            failures += 1
            tail = (p.stderr or p.stdout).strip().splitlines()[-12:]
            print(f"[{i+1}/{len(cells)}] FAIL ({dt:.0f}s) "
                  f"{os.path.basename(path)}\n  " + "\n  ".join(tail),
                  flush=True)
        else:
            print(f"[{i+1}/{len(cells)}] ok ({dt:.0f}s) "
                  f"{os.path.basename(path)}", flush=True)
    print(f"done: {len(cells)} cells, {failures} failures", flush=True)
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--bfs", metavar="GRAPH")
    ap.add_argument("--dispatch", default="bitmap",
                    choices=["bitmap", "queue"])
    ap.add_argument("--crossbar", default="staged",
                    choices=["staged", "flat"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--json-out")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--override", action="append", default=[],
                    help="ArchConfig field override, e.g. moe_dispatch=onehot")
    args = ap.parse_args()
    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        overrides[k] = int(v) if v.isdigit() else v

    if args.all:
        return 1 if run_all(args.out) else 0

    try:
        if args.bfs:
            rec = lower_bfs_cell(args.bfs, args.multi_pod, args.dispatch,
                                 args.crossbar, keep_hlo=args.keep_hlo)
        else:
            assert args.arch and args.shape, "--arch and --shape required"
            rec = lower_lm_cell(args.arch, args.shape, args.multi_pod,
                                keep_hlo=args.keep_hlo,
                                microbatches=args.microbatches,
                                overrides=overrides or None)
    except Exception:
        traceback.print_exc()
        return 1

    print(json.dumps(rec, indent=2, default=str))
    if args.json_out:
        os.makedirs(os.path.dirname(args.json_out) or ".", exist_ok=True)
        with open(args.json_out, "w") as f:
            json.dump(rec, f, indent=2, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
