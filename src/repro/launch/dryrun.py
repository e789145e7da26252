import os
# a compile-only sweep on 512 host devices: it and the children it spawns
# stay off any accelerator, and it keeps whatever XLA flags the caller set
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=512")))

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, dump memory/cost/collective analysis to JSON.

Must be run as a script/subprocess (it pins JAX to the CPU and forces 512
host devices before any jax import).  ``--all`` orchestrates one
subprocess per cell so a pathological compile can't take the whole sweep
down, and cells run in parallel.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--jobs 6]
"""
import argparse          # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402

import jax               # noqa: E402


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_path: str,
             quant: str = "none", rule_overrides: dict | None = None,
             cfg_overrides: dict | None = None) -> dict:
    from repro import configs
    from repro.dist.sharding import use_rules
    from repro.launch.mesh import make_production_mesh, rules_for
    from repro.launch.specs import build_cell
    from repro.roofline import analysis

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = configs.get_config(arch, quant=quant)
    shape = configs.SHAPES[shape_name]
    rules = rules_for(cfg, shape.kind, shape_name, multi_pod=multi_pod,
                      overrides=rule_overrides)
    record = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "mesh": list(mesh.devices.shape), "quant": quant,
        "n_devices": mesh.devices.size,
        "rule_overrides": rule_overrides or {},
        "cfg_overrides": cfg_overrides or {},
    }
    def _mem_record(compiled):
        mem = compiled.memory_analysis()
        if mem is None:
            return None
        return {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "total_per_device_bytes": (mem.argument_size_in_bytes
                                       + mem.output_size_in_bytes
                                       + mem.temp_size_in_bytes
                                       - mem.alias_size_in_bytes),
        }

    def _compile(cell):
        # donate the train state / decode cache: in-place update halves the
        # in+out residency (the output aliases the input buffers)
        donate = ()
        if cell["kind"] == "train":
            donate = (0,)
        elif cell["kind"] == "decode":
            donate = (2,)
        jf = jax.jit(cell["fn"], in_shardings=cell["in_shardings"],
                     out_shardings=cell["out_shardings"],
                     donate_argnums=donate)
        lowered = jf.lower(*cell["args_sds"])
        return lowered.compile()

    with mesh, use_rules(rules, mesh):
        shape = configs.SHAPES[shape_name]
        is_train = shape.kind == "train"
        from repro.train.step import TrainConfig

        # ---- exec variant: the FULL production program (scan over groups,
        # microbatched train step). This is the required .lower().compile()
        # proof and the real per-device memory footprint.
        cell = build_cell(arch, shape_name, mesh, rules, quant=quant,
                          unroll=False, cfg_overrides=cfg_overrides)
        if "skip" in cell:
            record["status"] = "skipped"
            record["reason"] = cell["skip"]
            _dump(out_path, record)
            return record
        record["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = _compile(cell)
        record["compile_s"] = round(time.time() - t1, 1)
        record["memory"] = _mem_record(compiled)
        if is_train:
            from repro.launch.specs import TRAIN_MICROBATCHES
            record["exec_microbatches"] = TRAIN_MICROBATCHES.get(arch, 4)

        # ---- cost variants: cost_analysis counts scan bodies ONCE, so we
        # compile 1-group and 2-group UNROLLED programs; the (2g - 1g) delta
        # is the exact per-group cost and extrapolates linearly to G groups
        # (embed/head/loss terms cancel in the delta). Train cost variants
        # drop the microbatch loop for the same reason.
        full_cfg = cell["cfg"]
        P = len(getattr(full_cfg, "pattern", (None,)))
        G = getattr(full_cfg, "n_groups", full_cfg.n_layers)
        t2 = time.time()

        def _cost_terms(n_groups: int):
            over = dict(cfg_overrides or {})
            over["n_layers"] = n_groups * P
            if getattr(full_cfg, "enc_dec", False):
                over["n_enc_layers"] = n_groups
            c = build_cell(arch, shape_name, mesh, rules, quant=quant,
                           unroll=True, cfg_overrides=over,
                           train_cfg=TrainConfig(n_microbatches=1)
                           if is_train else None)
            comp = _compile(c)
            return analysis.roofline_terms(comp.cost_analysis() or {},
                                           comp.as_text())

        t1g = _cost_terms(1)
        t2g = _cost_terms(2)
        record["cost_compile_s"] = round(time.time() - t2, 1)
        terms = analysis.extrapolate_terms(t1g, t2g, G)
        record["roofline"] = terms
        record["roofline_1g"] = {k: v for k, v in t1g.items()
                                 if not isinstance(v, (dict, list))}
        record["top_collectives_2g"] = t2g.get("top_collectives", [])

        # MODEL_FLOPS bookkeeping
        moe = getattr(cell["cfg"], "moe", None)
        counts = analysis.count_params(
            cell["args_sds"][0]["params"] if cell["kind"] == "train"
            else cell["args_sds"][0],
            moe_top_k=(moe.top_k if moe else None),
            n_experts=(moe.n_experts if moe else None))
        sh = configs.SHAPES[shape_name]
        mf = analysis.model_flops(cell["kind"], counts["active"],
                                  sh.global_batch, sh.seq_len)
        hlo_total = terms["hlo_flops_per_device"] * mesh.devices.size
        record["params"] = counts
        record["model_flops_global"] = mf
        record["model_vs_hlo_flops"] = (mf / hlo_total) if hlo_total else None
        record["status"] = "ok"
    _dump(out_path, record)
    return record


def _dump(path: str, record: dict):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=str)


def _cell_list():
    from repro import configs
    return [(a, s) for a in configs.ALIASES if a != "mobilenetv2"
            for s in configs.SHAPES]


def orchestrate(args) -> int:
    cells = _cell_list()
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
    jobs: list[tuple[subprocess.Popen, str, str]] = []
    failures = []
    pending = list(cells)
    out_dir = args.out_dir
    while pending or jobs:
        while pending and len(jobs) < args.jobs:
            arch, shape = pending.pop(0)
            tag = f"{arch}__{shape}__{'mp' if args.multi_pod else 'sp'}"
            out = os.path.join(out_dir, tag + ".json")
            if args.skip_existing and os.path.exists(out):
                print(f"[skip existing] {tag}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", out,
                   "--quant", args.quant]
            if args.multi_pod:
                cmd.append("--multi-pod")
            log = open(os.path.join(out_dir, tag + ".log"), "w")
            jobs.append((subprocess.Popen(
                cmd, stdout=log, stderr=log,
                env={**os.environ, "JAX_PLATFORMS": "cpu"}), tag, out))
            print(f"[launch] {tag}")
        still = []
        for proc, tag, out in jobs:
            rc = proc.poll()
            if rc is None:
                still.append((proc, tag, out))
            elif rc != 0:
                failures.append(tag)
                print(f"[FAIL rc={rc}] {tag}")
            else:
                print(f"[done] {tag}")
        jobs = still
        time.sleep(2)
    print(f"finished; {len(failures)} failures: {failures}")
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=6)
    ap.add_argument("--quant", default="none")
    ap.add_argument("--out", default=None)
    ap.add_argument("--out-dir", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (hillclimbing)")
    ap.add_argument("--rule", action="append", default=[],
                    help="sharding-rule override key=value|none")
    args = ap.parse_args()
    if args.all:
        sys.exit(orchestrate(args))

    def _parse_kv(items):
        out = {}
        for it in items:
            k, v = it.split("=", 1)
            if v.lower() in ("none", "null"):
                out[k] = None
            elif v.lower() in ("true", "false"):
                out[k] = v.lower() == "true"
            else:
                try:
                    out[k] = int(v)
                except ValueError:
                    try:
                        out[k] = float(v)
                    except ValueError:
                        out[k] = tuple(v.split("+")) if "+" in v else v
        return out

    out = args.out or os.path.join(
        args.out_dir,
        f"{args.arch}__{args.shape}__{'mp' if args.multi_pod else 'sp'}.json")
    rec = run_cell(args.arch, args.shape, args.multi_pod, out,
                   quant=args.quant, rule_overrides=_parse_kv(args.rule),
                   cfg_overrides=_parse_kv(args.set))
    status = rec.get("status")
    print(json.dumps(rec, indent=1, default=str)[:2000])
    if status not in ("ok", "skipped"):
        sys.exit(1)


if __name__ == "__main__":
    main()
