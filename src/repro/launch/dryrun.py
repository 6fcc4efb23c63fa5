import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this script:
  1. builds the abstract params / batch / cache (ShapeDtypeStructs — no
     allocation),
  2. jits the right step function (train / prefill / decode) with explicit
     in/out shardings on the production mesh,
  3. `.lower(...)` then `.compile()` — any sharding mismatch, unsupported
     collective, or compile-time OOM fails the cell,
  4. records `memory_analysis()` (proves it fits), `cost_analysis()`
     (FLOPs/bytes for §Roofline) and the per-collective byte counts parsed
     from the optimized HLO text (for the collective roofline term),
  5. writes one JSON per cell to artifacts/dryrun/.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-mini-3.8b
    PYTHONPATH=src python -m repro.launch.dryrun --shape train_4k --multi-pod both
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCH_IDS, get_config
from repro.distributed import shardings as SH
from repro.distributed.context import mesh_context
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import CellPlan, input_specs, plan_for
from repro.models.config import applicable_shapes, skipped_shapes
from repro.models.transformer import build_model
from repro.optim import adamw
from repro.train.step import TrainConfig, make_train_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
          "collective-permute")


def _split_computations(hlo: str) -> dict[str, list[str]]:
    """Map computation name → body lines of the optimized HLO module."""
    comps: dict[str, list[str]] = {}
    current = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        s = line.strip()
        # computation headers are unindented `[ENTRY] %name (args...) -> T {`
        # (args may contain nested tuple parens, so match only the prefix)
        if line and not line.startswith(" ") and s.endswith("{") and \
                "->" in s and "=" not in s.split("(")[0]:
            name = s.split("(")[0].replace("ENTRY", "").strip()
            name = name.lstrip("%").strip()
            if name:
                current = name
                comps[current] = []
                continue
        if s == "}":
            continue
        if current is not None:
            comps[current].append(s)
    return comps


def _type_bytes(type_str: str) -> int:
    nbytes = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for dim in dims.split(","):
                if dim:
                    n *= int(dim)
        nbytes += n * _DTYPE_BYTES[dt]
    return nbytes


def _trip_count(cond_lines: list[str]) -> int:
    """Trip count of a scan-generated while loop = the comparison constant
    in its condition computation (max int constant as a safe fallback)."""
    best = 1
    for line in cond_lines:
        m = re.search(r"constant\((\d+)\)", line)
        if m:
            best = max(best, int(m.group(1)))
    return best


def collective_bytes_from_hlo(hlo: str) -> dict:
    """Per-kind collective payload bytes, with while-loop bodies multiplied
    by their trip counts (lax.scan over layers/microbatches lowers to while,
    whose body executes trip-count times but appears once in the text)."""
    comps = _split_computations(hlo)
    entry = None
    for name in comps:
        if name.startswith("main") or ".main" in name:
            entry = name
    if entry is None and comps:
        entry = list(comps)[-1]

    totals: dict[str, float] = {}
    counts: dict[str, float] = {}

    def walk(comp: str, mult: float, depth: int = 0):
        if comp not in comps or depth > 12:
            return
        for line in comps[comp]:
            m = re.search(
                r"=\s*(.+?)\s+(" + "|".join(_KINDS) + r")(-start)?\(", line)
            if m and "-done(" not in line:
                kind = m.group(2)
                nbytes = _type_bytes(m.group(1))
                totals[kind] = totals.get(kind, 0) + nbytes * mult
                counts[kind] = counts.get(kind, 0) + mult
            w = re.search(r"while\(.*?\),\s*condition=%?([\w\.\-]+),\s*"
                          r"body=%?([\w\.\-]+)", line)
            if w:
                cond, body = w.group(1), w.group(2)
                trips = _trip_count(comps.get(cond, []))
                walk(body, mult * trips, depth + 1)
            c = re.search(r"(?:calls|branch_computations)=.?\{?%?([\w\.\-]+)",
                          line)
            if c and "while(" not in line:
                walk(c.group(1), mult, depth + 1)
    walk(entry, 1.0)
    return {"bytes_by_kind": {k: int(v) for k, v in totals.items()},
            "count_by_kind": {k: int(v) for k, v in counts.items()},
            "total_bytes": int(sum(totals.values()))}


def _memory_stats(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_size_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_size_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_size_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "generated_code_size_bytes":
                int(getattr(ma, "generated_code_size_in_bytes", 0)),
            "alias_size_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        }
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)}


def _cost_stats(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float))}
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)}


def quantized_param_shardings(mesh, aparams, arch):
    """Shardings for the packed-int4 serving param tree: packed weights
    shard like their bf16 counterparts (column-parallel on N for in-projs,
    row-parallel on K/2 for out-projs — nibble pairs stay on one shard
    because K is even per shard)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def spec(path, leaf):
        parts = path.split("/")
        name = parts[-2] if parts[-1] in ("packed", "scale") else parts[-1]
        mdl = "model" if "model" in mesh.axis_names else None
        if parts[-1] == "packed":
            if name in ("wq", "wk", "wv", "w_gate", "w_up"):
                return SH._fit(P(None, None, mdl), leaf.shape, mesh)
            return SH._fit(P(None, mdl, None), leaf.shape, mesh)
        if parts[-1] == "scale" and name in ("wq", "wk", "wv", "w_gate",
                                             "w_up"):
            return SH._fit(P(None, mdl), leaf.shape, mesh)
        if name == "embed":
            return SH._fit(P(mdl, None), leaf.shape, mesh)
        if name == "lm_head":
            return SH._fit(P(None, mdl), leaf.shape, mesh)
        return P(*([None] * len(leaf.shape)))

    paths, leaves, treedef = SH._tree_paths(aparams)
    return jax.tree_util.tree_unflatten(
        treedef, [NamedSharding(mesh, spec(p, l))
                  for p, l in zip(paths, leaves)])


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               save_hlo: bool = False, serve_layout: bool = False,
               remat_policy: str = "nothing",
               microbatches: int | None = None,
               moment_dtype: str = "float32",
               quantized_serve: bool = False) -> dict:
    cfg = get_config(arch)
    cells = {c.name: c for c in applicable_shapes(cfg)}
    if shape_name not in cells:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": skipped_shapes(cfg).get(shape_name, "n/a")}
    plan = plan_for(cfg, cells[shape_name])
    if microbatches is not None and plan.kind == "train":
        import dataclasses as _dc
        plan = _dc.replace(plan, num_microbatches=microbatches)
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg, remat_policy=remat_policy)

    # serving layout (§Perf): replicated batch + 2D weights + fully-sharded
    # cache, so decode never all-gathers ZeRO-3 weights
    serve = serve_layout and plan.kind == "decode"
    rules = SH.SERVE_RULES if serve else None

    t0 = time.perf_counter()
    with mesh_context(mesh, rules=rules):
        aparams = model.init_abstract()
        pshard = SH.param_shardings(mesh, aparams, arch)
        specs = input_specs(model, plan)

        if plan.kind == "train":
            opt_cfg = adamw.AdamWConfig(moment_dtype=moment_dtype)
            aopt = jax.eval_shape(
                lambda p: adamw.init_state(opt_cfg, p), aparams)
            oshard = SH.opt_state_shardings(mesh, aopt, aparams, arch)
            bshard = SH.batch_shardings(mesh, specs["batch"])
            step = make_train_step(
                model, opt_cfg,
                TrainConfig(num_microbatches=plan.num_microbatches,
                            remat=True),
                param_shardings=pshard)
            jitted = jax.jit(
                step,
                in_shardings=(pshard, oshard, bshard),
                out_shardings=(pshard, oshard, SH.replicated(mesh)),
                donate_argnums=(0, 1))
            lowered = jitted.lower(aparams, aopt, specs["batch"])
        elif plan.kind == "prefill":
            bshard = SH.batch_shardings(mesh, specs["batch"])
            if "cache" in specs:
                cshard = SH.cache_shardings(mesh, specs["cache"])

                def prefill(p, b, c):
                    return model.prefill(p, b, c)

                jitted = jax.jit(prefill,
                                 in_shardings=(pshard, bshard, cshard),
                                 out_shardings=(SH.replicated(mesh), cshard),
                                 donate_argnums=(2,))
                lowered = jitted.lower(aparams, specs["batch"],
                                       specs["cache"])
            else:
                def encode(p, b):
                    return model.forward(p, b)

                jitted = jax.jit(encode, in_shardings=(pshard, bshard))
                lowered = jitted.lower(aparams, specs["batch"])
        elif plan.kind == "decode" and quantized_serve:
            from repro.kernels import ops as kops
            from repro.serve.quantized import QuantizedDenseLM, \
                pack_dense_params
            qlm = QuantizedDenseLM(cfg, block_size=32)
            aq = jax.eval_shape(lambda p: pack_dense_params(p, cfg), aparams)
            qshard = quantized_param_shardings(mesh, aq, arch)
            cspec = jax.eval_shape(
                lambda: qlm.init_cache(plan.cell.global_batch,
                                       plan.cell.seq_len))
            cshard = SH.cache_shardings(mesh, cspec)
            tshard = SH.batch_shardings(mesh, {"t": specs["tokens"]})["t"]

            def qdecode(p, t, c, i):
                # force the jnp reference path: the roofline reads op-level
                # FLOP/byte counts from the XLA graph, which interpret-mode
                # Pallas calls would obscure
                with kops.use_kernels(False):
                    return qlm.decode_step(p, t, c, i)

            jitted = jax.jit(qdecode,
                             in_shardings=(qshard, tshard, cshard,
                                           SH.replicated(mesh)),
                             out_shardings=(None, cshard),
                             donate_argnums=(2,))
            lowered = jitted.lower(aq, specs["tokens"], cspec,
                                   jax.ShapeDtypeStruct((), jnp.int32))
        else:  # decode
            if serve:
                cshard = SH.serve_cache_shardings(mesh, specs["cache"])
                tshard = SH.replicated(mesh)
            else:
                cshard = SH.cache_shardings(mesh, specs["cache"])
                tshard = SH.batch_shardings(mesh, {"t": specs["tokens"]})["t"]

            def decode(p, t, c, i):
                return model.decode_step(p, t, c, i)

            jitted = jax.jit(decode,
                             in_shardings=(pshard, tshard, cshard,
                                           SH.replicated(mesh)),
                             out_shardings=(None, cshard),
                             donate_argnums=(2,))
            lowered = jitted.lower(aparams, specs["tokens"], specs["cache"],
                                   jax.ShapeDtypeStruct((), jnp.int32))

        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

        hlo = compiled.as_text()
        coll = collective_bytes_from_hlo(hlo)
        from repro.launch.hlo_analysis import analyze_hlo
        try:
            costs = analyze_hlo(hlo)
            hlo_costs = {
                "flops_per_device": costs.flops,
                "bytes_per_device": costs.bytes_accessed,
                "collective_bytes_by_kind": costs.collective_bytes,
                "collective_counts": costs.collective_counts,
                "top_dots": costs.dot_details[:12],
            }
        except Exception as e:  # noqa: BLE001
            hlo_costs = {"error": str(e)}
        out = {
            "arch": arch,
            "shape": shape_name,
            "kind": plan.kind,
            "multi_pod": multi_pod,
            "mesh": {"shape": list(mesh.devices.shape),
                     "axes": list(mesh.axis_names)},
            # the device the program was compiled for; roofline.py finds
            # its peaks by this kind and refuses any it does not know
            "device_kind": mesh.devices.flat[0].device_kind,
            "num_microbatches": plan.num_microbatches,
            "remat_policy": remat_policy,
            "moment_dtype": moment_dtype,
            "serve_layout": serve,
            "quantized_serve": quantized_serve,
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": _memory_stats(compiled),
            "cost": _cost_stats(compiled),
            "collectives": coll,
            "hlo_costs": hlo_costs,
        }
        if save_hlo:
            out["hlo_path"] = _save_hlo(arch, shape_name, multi_pod, hlo)
        return out


def _save_hlo(arch, shape, multi_pod, hlo: str) -> str:
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    path = os.path.join(
        ARTIFACT_DIR, f"{arch}__{shape}__{'mp' if multi_pod else 'sp'}.hlo")
    with open(path, "w") as f:
        f.write(hlo)
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="single arch id (default all)")
    ap.add_argument("--shape", default=None, help="single shape name")
    ap.add_argument("--multi-pod", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--serve-layout", action="store_true",
                    help="replicated-batch serving layout for decode cells")
    ap.add_argument("--remat-policy", default="nothing",
                    choices=["nothing", "dots"])
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--moment-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    ap.add_argument("--quantized-serve", action="store_true",
                    help="lower the packed-int4 integer decode path")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else \
        ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
    pods = {"single": [False], "multi": [True],
            "both": [False, True]}[args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tag = f"{arch}/{shape}/{'2pod' if mp else '1pod'}"
                try:
                    rec = lower_cell(arch, shape, multi_pod=mp,
                                     save_hlo=args.save_hlo,
                                     serve_layout=args.serve_layout,
                                     remat_policy=args.remat_policy,
                                     microbatches=args.microbatches,
                                     moment_dtype=args.moment_dtype,
                                     quantized_serve=args.quantized_serve)
                except Exception:
                    rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "fail",
                           "error": traceback.format_exc(limit=20)}
                if rec["status"] == "ok":
                    n_ok += 1
                    mem = rec["memory"]
                    per_dev = (mem.get("argument_size_bytes", 0)
                               + mem.get("temp_size_bytes", 0)) / 2 ** 30
                    print(f"[OK]   {tag:60s} lower {rec['lower_s']:6.1f}s "
                          f"compile {rec['compile_s']:6.1f}s "
                          f"arg+temp/dev {per_dev:7.2f} GiB "
                          f"coll {rec['collectives']['total_bytes']/2**30:8.3f} GiB")
                elif rec["status"] == "skipped":
                    n_skip += 1
                    print(f"[SKIP] {tag:60s} {rec['reason']}")
                else:
                    n_fail += 1
                    print(f"[FAIL] {tag}")
                    print(rec["error"])
                fname = f"{arch}__{shape}__{'mp' if mp else 'sp'}" + \
                    ("__serve" if args.serve_layout and
                     rec.get("kind") == "decode" else "") + \
                    (f"__{args.tag}" if args.tag else "") + ".json"
                with open(os.path.join(args.out, fname), "w") as f:
                    json.dump(rec, f, indent=1)
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
