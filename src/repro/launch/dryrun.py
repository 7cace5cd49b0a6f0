import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input-shape) cell, on BOTH production meshes
(16x16 single-pod and 2x16x16 multi-pod), this driver:

    lowered  = jax.jit(step_fn).lower(*input_specs)   # SDS, no arrays
    compiled = lowered.compile()
    print(compiled.memory_analysis())                 # proves it fits
    print(compiled.cost_analysis())                   # -> §Roofline

and writes one JSON artifact per cell under results/dryrun/.  Failures
(sharding mismatch, OOM at compile, unsupported collective) are bugs.

Usage:
    python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
    python -m repro.launch.dryrun --all --jobs 4      # process pool
"""

import argparse
import json
import sys
import time
import traceback

# The chip the production meshes describe (TPU v5e pods); the dry-run
# compiles on forced host devices, so the roofline names its target.
TARGET_DEVICE_KIND = "TPU v5 lite"


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             settings_override: dict = None, tag: str = "") -> dict:
    import jax

    from ..configs import get_config, get_shape
    from ..launch.cells import CellSettings, build_cell, cell_settings
    from ..launch.mesh import activate_mesh, describe, make_production_mesh
    from ..roofline import analyze_compiled, chip_peaks

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    with activate_mesh(mesh):
        st = cell_settings(arch, shape)
        if settings_override:
            import dataclasses
            st = dataclasses.replace(st, **settings_override)
        fn, inputs, desc = build_cell(arch, shape, mesh, settings=st)
        desc["mesh"] = describe(mesh)
        desc["multi_pod"] = multi_pod

        donate = getattr(fn, "donate_argnums", ())
        lowered = jax.jit(fn, donate_argnums=donate).lower(*inputs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    n_chips = int(mesh.devices.size)
    hlo_text = compiled.as_text()
    result = analyze_compiled(compiled, desc, n_chips,
                              chip_peaks(TARGET_DEVICE_KIND),
                              hlo_text=hlo_text)
    result["timing"] = {"lower_s": round(t_lower, 1),
                        "compile_s": round(t_compile, 1)}

    mesh_tag = "pod2" if multi_pod else "pod1"
    name = f"{arch}__{shape}__{mesh_tag}{tag}.json"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name)
    with open(path + ".tmp", "w") as fh:
        json.dump(result, fh, indent=1, default=str)
    os.replace(path + ".tmp", path)
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--settings", default="",
                    help='JSON overrides, e.g. {"microbatches":8}')
    args = ap.parse_args()

    from ..configs import cells as all_cells

    if args.all:
        targets = all_cells()
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required without --all")
        targets = [(args.arch, args.shape)]

    meshes = []
    if not args.multi_pod_only:
        meshes.append(False)
    if not args.single_pod_only:
        meshes.append(True)
    if args.multi_pod:
        meshes = [True]

    overrides = json.loads(args.settings) if args.settings else None
    failures = []
    for arch, shape in targets:
        for mp in meshes:
            tag = "pod2" if mp else "pod1"
            out = os.path.join(args.out,
                               f"{arch}__{shape}__{tag}.json")
            if args.skip_existing and os.path.exists(out):
                print(f"[skip] {arch} x {shape} x {tag}")
                continue
            print(f"[dryrun] {arch} x {shape} x {tag} ...", flush=True)
            try:
                r = run_cell(arch, shape, mp, args.out,
                             settings_override=overrides)
                t = r["roofline"]
                print(f"  ok ({r['timing']['compile_s']}s compile) "
                      f"compute={t['compute_s']:.4f}s "
                      f"memory={t['memory_s']:.4f}s "
                      f"collective={t['collective_s']:.4f}s "
                      f"dominant={t['dominant']}", flush=True)
                ma = r.get("memory_analysis", {})
                if "temp_size_in_bytes" in ma:
                    per = (ma.get("argument_size_in_bytes", 0)
                           + ma.get("temp_size_in_bytes", 0))
                    print(f"  memory/device: args+temp = {per/2**30:.2f} GiB")
            except Exception as e:
                failures.append((arch, shape, tag, repr(e)))
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        return 1
    print("\nall dry-run cells compiled")
    return 0


if __name__ == "__main__":
    sys.exit(main())
