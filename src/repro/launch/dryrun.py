import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# the dry-run compiles on forged host devices, and its per-cell children
# inherit this: none of them may take an accelerator from the process
# that owns it
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory / cost / collective analyses.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all          # orchestrates
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

Single-cell mode runs in-process; --all spawns one subprocess per cell (XLA
CPU compilation of 100B-scale SPMD modules is memory-hungry — isolation keeps
the 35 GB container alive) and aggregates JSON into benchmarks/results/.
"""
import argparse
import json
import subprocess
import sys
import traceback

from repro.obs import trace

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../../../benchmarks/results")


def run_cell(arch_id: str, shape_name: str, multi_pod: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.distributed import sharding as sh
    from repro.launch import steps
    from repro.launch.hlo_analysis import collective_summary, module_costs
    from repro.launch.mesh import make_production_mesh

    with trace.timed("dryrun/lower", arch=arch_id, shape=shape_name) as tl:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_dev = mesh.devices.size
        arch = configs.get(arch_id)
        bound = steps.bind(arch, shape_name, reduced=False, mesh=mesh)

        state_specs = bound.abstract_state()
        from jax.sharding import NamedSharding, PartitionSpec as P
        repl = NamedSharding(mesh, P())
        in_shardings = (
            sh.tree_shardings(mesh, bound.state_axes) if bound.state_axes else
            jax.tree.map(lambda _: repl, state_specs),
            sh.tree_shardings(mesh, bound.batch_axes),
        )

        # out_shardings: pin the train-state output to the input (fsdp)
        # sharding so grad reductions lower to reduce-scatter instead of
        # all-reduce+slice
        out_shardings = in_shardings[0] if bound.kind == "train" else None
        if out_shardings is not None:
            out_shardings = (out_shardings, None)   # (state, metrics)
        jitted = jax.jit(bound.step_fn, in_shardings=in_shardings,
                         out_shardings=out_shardings)
        lowered = jitted.lower(state_specs, bound.input_specs)
    t_lower = tl.seconds
    with trace.timed("dryrun/compile", arch=arch_id, shape=shape_name) as tc:
        compiled = lowered.compile()
    t_compile = tc.seconds

    mem = compiled.memory_analysis()
    mem_info = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
    }
    try:
        cost = compiled.cost_analysis()
        cost_info = {k: float(v) for k, v in cost.items()
                     if isinstance(v, (int, float)) and k in
                     ("flops", "bytes accessed", "transcendentals",
                      "bytes accessed0{}", "bytes accessed1{}", "bytes accessedout{}")}
        cost_info["flops"] = float(cost.get("flops", 0.0))
        cost_info["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
    except Exception as e:  # pragma: no cover
        cost_info = {"error": str(e)}

    hlo = compiled.as_text()
    coll = collective_summary(hlo, n_dev)
    costs = module_costs(hlo, n_dev)   # loop-scaled (cost_analysis counts
    cost_info.update(costs)            # while bodies once — see hlo_analysis)

    return {
        "arch": arch_id,
        "shape": shape_name,
        "kind": bound.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(n_dev),
        "ok": True,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem_info,
        "cost": cost_info,
        "collectives": coll,
        "hlo_bytes": len(hlo),
    }


def orchestrate(cells, multi_pod: bool, timeout_s: int = 2400) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    suffix = "multipod" if multi_pod else "singlepod"
    out_path = os.path.join(RESULTS_DIR, f"dryrun_{suffix}.json")
    results = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    for arch_id, shape in cells:
        key = f"{arch_id}/{shape}"
        if key in results and results[key].get("ok"):
            print(f"[skip] {key} (cached)")
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch_id, "--shape", shape, "--json"]
        if multi_pod:
            cmd.append("--multi-pod")
        print(f"[run ] {key} ({suffix}) ...", flush=True)
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=timeout_s,
                env={**os.environ, "PYTHONPATH": "src"},
                cwd=os.path.join(os.path.dirname(__file__), "../../.."))
            tail = proc.stdout.strip().splitlines()
            payload = json.loads(tail[-1]) if tail else {"ok": False, "error": "no output"}
            if not payload.get("ok"):
                payload.setdefault("error", proc.stderr[-2000:])
        except subprocess.TimeoutExpired:
            payload = {"arch": arch_id, "shape": shape, "ok": False,
                       "error": f"timeout {timeout_s}s"}
        except Exception as e:
            payload = {"arch": arch_id, "shape": shape, "ok": False, "error": str(e)}
        results[key] = payload
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
        status = "OK" if payload.get("ok") else "FAIL"
        print(f"[{status:4}] {key}: compile={payload.get('compile_s', '?')}s "
              f"coll={payload.get('collectives', {}).get('total_bytes_per_device', '?')}B")
    n_ok = sum(1 for v in results.values() if v.get("ok"))
    print(f"== {n_ok}/{len(results)} cells green -> {out_path}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--include-ann", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--json", action="store_true", help="emit one-line JSON")
    args = ap.parse_args()

    if args.all:
        from repro import configs
        orchestrate(configs.all_cells(include_ann=args.include_ann), args.multi_pod)
        return

    try:
        res = run_cell(args.arch, args.shape, args.multi_pod)
    except Exception as e:
        res = {"arch": args.arch, "shape": args.shape, "ok": False,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-3000:]}
    if args.json:
        print(json.dumps(res))
    else:
        print(json.dumps(res, indent=2))
    if not res.get("ok"):
        sys.exit(1)


if __name__ == "__main__":
    main()
