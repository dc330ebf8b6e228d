import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, prove it fits, and extract the roofline terms.

MUST be run as a module with nothing else having initialised jax first
(the two lines above lock the device count before any other import):

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b --distill

Outputs one JSON per pair under experiments/dryrun/.
"""
import argparse
import dataclasses
import json
import re
import sys
import time
import traceback

import jax

from repro import configs
from repro.launch import mesh as mesh_mod
from repro.launch import steps as steps_mod

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)"
                       r"\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the (SPMD-partitioned)
    HLO.  Shapes in the partitioned module are PER-DEVICE; we report
    per-device bytes moved, keyed by op kind.  ``-done`` halves of async
    pairs are skipped (the ``-start`` already carries the payload shape)."""
    out = {k: 0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (.*)$", ls)
        if not m:
            continue
        rest = m.group(1)
        for kind in COLLECTIVES:
            # match the op name, not substrings of other ops; skip -done
            if re.search(rf"\b{kind}-done\(", rest):
                break
            if re.search(rf"\b{kind}(?:-start)?\(", rest):
                # result type(s) appear before the op name
                pre = rest.split(kind)[0]
                out[kind] += _shape_bytes(pre)
                counts[kind] += 1
                break
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_CALL_RE = re.compile(r"(?:to_apply|calls|body|condition)=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_WHILE_BODY_RE = re.compile(r"\bbody=%?([\w.\-]+)")


def _split_computations(hlo_text: str) -> dict:
    """name -> list[str] of body lines, by brace tracking (metadata={...}
    braces are balanced within a line, so net depth is reliable)."""
    comps: dict = {}
    name, depth, buf = None, 0, []
    for line in hlo_text.splitlines():
        if name is None:
            m = _COMP_HDR_RE.match(line)
            if m:
                name, depth, buf = m.group(1), 1, []
            continue
        depth += line.count("{") - line.count("}")
        if depth <= 0:
            comps[name] = buf
            name = None
        else:
            buf.append(line)
    return comps


def collective_bytes_scanned(hlo_text: str, trip_count: float) -> dict:
    """Collective bytes of the PRODUCTION (scan-over-layers) program.

    XLA prints a while-loop body once; its collectives run ``trip_count``
    times.  We attribute each collective to its physical computation, take
    the transitive closure of computations reachable from any while body,
    and weight those by trip_count.  This replaces the depth-1/depth-2
    probe extrapolation for collectives — the SPMD partitioner picks
    *different* collective strategies at different depths (measured:
    qwen3-8b prefill lowers to 6.3 GB of all-gathers at depth 1 but 5.4 GB
    of all-reduces at depth 2), so cross-depth extrapolation is unsound
    for communication, while measuring the real scanned program is exact
    up to the (known) trip count."""
    comps = _split_computations(hlo_text)
    bodies = set()
    for lines in comps.values():
        for line in lines:
            bodies.update(_WHILE_BODY_RE.findall(line))

    def callees(cname: str) -> set:
        out: set = set()
        for line in comps.get(cname, ()):
            out.update(_CALL_RE.findall(line))
            bm = _BRANCH_RE.search(line)
            if bm:
                out.update(x.strip().lstrip("%")
                           for x in bm.group(1).split(","))
        return out

    in_loop: set = set()
    stack = list(bodies)
    while stack:
        n = stack.pop()
        if n in in_loop:
            continue
        in_loop.add(n)
        stack.extend(callees(n))

    by_kind = {k: 0.0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    in_loop_bytes = 0.0
    for cname, lines in comps.items():
        cb = collective_bytes("\n".join(lines))
        mult = trip_count if cname in in_loop else 1.0
        for k in COLLECTIVES:
            by_kind[k] += mult * cb["bytes"][k]
            counts[k] += cb["counts"][k]
        if cname in in_loop:
            in_loop_bytes += cb["total_bytes"]
    return {"bytes": by_kind, "counts": counts,
            "total_bytes": sum(by_kind.values()),
            "in_loop_bytes_once": in_loop_bytes,
            "trip_count": trip_count}


def roofline(cfg, shape, mesh, cost, coll_total_per_dev) -> dict:
    """cost_analysis values come from the SPMD-partitioned module, i.e. they
    are PER-DEVICE (verified: qwen3-8b train flops == 6ND/chips).  The spec
    formulas term = GLOBAL / (chips * rate) reduce to per_device / rate."""
    chips = mesh.devices.size
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    peaks = mesh_mod.device_peaks(mesh_mod.TARGET_DEVICE_KIND)
    compute_t = flops_dev / peaks.flops_bf16
    memory_t = bytes_dev / peaks.hbm_bw
    collective_t = coll_total_per_dev / peaks.ici_bw
    terms = {"compute_s": compute_t, "memory_s": memory_t,
             "collective_s": collective_t}
    dominant = max(terms, key=terms.get)

    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    d_tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                     else 1)
    if shape.kind == "distill":
        # FedDF AVGLOGITS step: K teacher forwards (2ND each) + one student
        # forward+backward (6ND); K=4 teachers in the dry-run bundle.
        mult = 2 * 4 + 6
    else:
        mult = 6 if shape.kind == "train" else 2
    model_flops = mult * n_active * d_tokens
    hlo_flops_global = flops_dev * chips
    return {
        **terms,
        "dominant": dominant,
        "hlo_flops_global": hlo_flops_global,
        "hlo_flops_per_device": flops_dev,
        "hlo_bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_total_per_dev,
        "model_flops": model_flops,
        "useful_flops_ratio": (model_flops / hlo_flops_global
                               if hlo_flops_global else None),
        "params": n_params,
        "active_params": n_active,
    }


def _compile_and_measure(bundle, mesh) -> dict:
    lowered = bundle.lower(mesh)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    cost = dict(cost) if cost else {}
    coll = collective_bytes(compiled.as_text())
    return {
        "memory": _mem_dict(compiled.memory_analysis()),
        "cost": {k: float(v) for k, v in cost.items()
                 if isinstance(v, (int, float))},
        "collectives": coll,
        "compiled": compiled,
    }


def depth_corrected_cost(cfg, make_bundle, mesh, full: dict) -> dict:
    """XLA cost_analysis counts a while-loop (lax.scan) body ONCE, not
    trip-count times.  Correct by linear depth extrapolation: compile a
    1-repeat scanned variant (m1 — exact at depth 1) and a 2-repeat
    *unrolled* variant (m2 — exact at depth 2); every repeat costs the same,
    so  cost(n_layers) = m1 + (n_layers/P - 1) * (m2 - m1).
    Returns corrected {flops, bytes, collective_bytes} plus the raws."""
    p = len(cfg.pattern)
    n_eff = cfg.n_layers / p
    cfg1 = dataclasses.replace(cfg, n_layers=p, name=cfg.name + "@d1u")
    cfg2 = dataclasses.replace(cfg, n_layers=2 * p, name=cfg.name + "@d2u")
    # both probes UNROLLED and WITHOUT remat: while-loop bodies are counted
    # once by cost_analysis, and remat recompute inside a scan body distorts
    # the per-repeat delta (XLA CSEs it away when unrolled).  The production
    # config (full compile above) keeps scan+remat; remat adds ~1 extra
    # forward per layer, i.e. x4/3 on the layer compute term — noted in
    # EXPERIMENTS.md instead of double-counted here.
    m1 = _compile_and_measure(make_bundle(cfg1, True), mesh)
    m2 = _compile_and_measure(make_bundle(cfg2, True), mesh)

    def extrap(v1, v2):
        return v1 + (n_eff - 1.0) * (v2 - v1)

    out = {
        "n_effective_repeats": n_eff,
        "flops": extrap(m1["cost"].get("flops", 0.0),
                        m2["cost"].get("flops", 0.0)),
        "bytes": extrap(m1["cost"].get("bytes accessed", 0.0),
                        m2["cost"].get("bytes accessed", 0.0)),
        "collective_bytes": extrap(m1["collectives"]["total_bytes"],
                                   m2["collectives"]["total_bytes"]),
        "collective_bytes_by_kind": {
            k: extrap(m1["collectives"]["bytes"][k],
                      m2["collectives"]["bytes"][k]) for k in COLLECTIVES},
        "m1_flops": m1["cost"].get("flops", 0.0),
        "m2_flops": m2["cost"].get("flops", 0.0),
        "m1_collective_bytes": m1["collectives"]["total_bytes"],
        "m2_collective_bytes": m2["collectives"]["total_bytes"],
        "full_raw_flops": full["cost"].get("flops", 0.0),
        "full_raw_collective_bytes": full["collectives"]["total_bytes"],
    }
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool, *, fsdp=True,
            remat=True, distill=False, out_dir="experiments/dryrun",
            variant="baseline", skip_depth_extrap=False,
            step_kw=None, cfg_overrides=None) -> dict:
    cfg = configs.get(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "variant": variant, "ok": False}
    t0 = time.time()
    try:
        if distill:
            # pseudo-shape for the roofline terms: the fusion batch is what
            # the server streams per AVGLOGITS step (4 teachers fwd +
            # 1 student fwd/bwd counted via kind="train" multiplier is wrong
            # — use kind="distill" handled in roofline()).
            dk = dict(n_teachers=4, batch_size=128, seq_len=512)
            dk.update({k: v for k, v in (step_kw or {}).items()
                       if k in ("n_teachers", "batch_size", "seq_len")})
            shape = configs.InputShape("distill_fusion", dk["seq_len"],
                                       dk["batch_size"], "distill")

            def make_bundle(c, unroll):
                return steps_mod.make_distill_step(
                    c, mesh, fsdp=fsdp, unroll=unroll, remat=remat, **dk,
                    **{k: v for k, v in (step_kw or {}).items()
                       if k not in ("n_teachers", "batch_size", "seq_len",
                                    "microbatch", "naive_xent", "layout")})
            bundle = make_bundle(cfg, False)
            rec["shape"] = shape_name = "distill_fusion"
            rec["distill_kw"] = dk
        else:
            shape = configs.get_shape(shape_name)
            ok, reason = configs.applicable(cfg, shape)
            if not ok:
                rec["skipped"] = reason
                rec["ok"] = True
                return _finish(rec, out_dir, t0)

            def make_bundle(c, unroll):
                return steps_mod.make_step(c, shape, mesh, fsdp=fsdp,
                                           remat=remat and not unroll,
                                           unroll=unroll, **(step_kw or {}))
            bundle = make_bundle(cfg, False)

        full = _compile_and_measure(bundle, mesh)
        rec["lower_compile_s"] = time.time() - t0
        rec["memory_analysis"] = full["memory"]
        rec["cost_analysis_raw"] = full["cost"]
        rec["collectives_raw"] = full["collectives"]
        print(full["memory"])

        # collectives: measure the production scanned program directly —
        # while-body collectives x trip count (see collective_bytes_scanned)
        n_eff = cfg.n_layers / len(cfg.pattern)
        scanned = collective_bytes_scanned(full["compiled"].as_text(), n_eff)
        rec["collectives_scanned"] = scanned
        coll_total = scanned["total_bytes"]

        if not skip_depth_extrap:
            corr = depth_corrected_cost(cfg, make_bundle, mesh, full)
            rec["depth_corrected"] = corr
            cost = {"flops": corr["flops"], "bytes accessed": corr["bytes"]}
        else:
            cost = full["cost"]

        if shape is not None:
            rec["roofline"] = roofline(cfg, shape, mesh, cost, coll_total)
            print({k: rec["roofline"][k] for k in
                   ("compute_s", "memory_s", "collective_s", "dominant")})
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return _finish(rec, out_dir, t0)


def update_collectives(arch: str, shape_name: str, multi_pod: bool, *,
                       fsdp=True, remat=True,
                       out_dir="experiments/dryrun") -> dict:
    """Recompute ONLY the scanned-collective bytes (and the roofline) for an
    existing baseline JSON: one production compile, no depth probes — the
    saved depth_corrected flops/bytes remain valid."""
    mesh_name = "2x16x16" if multi_pod else "16x16"
    fname = os.path.join(out_dir,
                         f"{arch}__{shape_name}__{mesh_name}__baseline.json")
    rec = json.load(open(fname))
    if "skipped" in rec or not rec.get("ok"):
        print(f"[coll-update] {arch} x {shape_name} @ {mesh_name} -> "
              f"{'SKIP' if 'skipped' in rec else 'was-FAIL'}")
        return rec
    cfg = configs.get(arch)
    shape = configs.get_shape(shape_name)
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    bundle = steps_mod.make_step(cfg, shape, mesh, fsdp=fsdp, remat=remat)
    compiled = bundle.lower(mesh).compile()
    n_eff = cfg.n_layers / len(cfg.pattern)
    scanned = collective_bytes_scanned(compiled.as_text(), n_eff)
    rec["collectives_scanned"] = scanned
    corr = rec.get("depth_corrected")
    cost = ({"flops": corr["flops"], "bytes accessed": corr["bytes"]}
            if corr else rec["cost_analysis_raw"])
    rec["roofline"] = roofline(cfg, shape, mesh, cost,
                               scanned["total_bytes"])
    with open(fname, "w") as f:
        json.dump(rec, f, indent=2)
    print(f"[coll-update] {arch} x {shape_name} @ {mesh_name} -> "
          f"coll={scanned['total_bytes']/1e9:.2f}GB/dev "
          f"({time.time()-t0:.0f}s)")
    return rec


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    out = {}
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        if hasattr(mem, attr):
            out[attr] = int(getattr(mem, attr))
    return out


def _finish(rec: dict, out_dir: str, t0: float) -> dict:
    rec["total_s"] = time.time() - t0
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}__{rec['variant']}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(rec, f, indent=2)
    status = ("SKIP: " + rec.get("skipped", "") if "skipped" in rec
              else "OK" if rec["ok"] else "FAIL: " + rec.get("error", "?"))
    print(f"[dryrun] {rec['arch']} x {rec['shape']} @ {rec['mesh']} "
          f"({rec['variant']}) -> {status} ({rec['total_s']:.1f}s)")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--distill", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--naive-xent", action="store_true",
                    help="v0 loss for the §Perf record")
    ap.add_argument("--layout", default="tp", choices=["tp", "dp_heavy", "dp_heavy_z3"],
                    help="sharding layout preset (see common/sharding.py)")
    ap.add_argument("--attn", default="naive", choices=["naive", "chunked"],
                    help="attention impl (chunked = flash-pattern scan)")
    ap.add_argument("--attn-chunk", type=int, default=1024)
    ap.add_argument("--constrain-acts", action="store_true",
                    help="assert batch-sharded activations at every block "
                         "boundary (§Perf variant)")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="gradient-accumulation microbatches (train only)")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--update-collectives", action="store_true",
                    help="recompute scanned collectives + roofline in "
                         "existing baseline JSONs (one compile per pair)")
    args = ap.parse_args(argv)

    if args.update_collectives:
        for arch in configs.ASSIGNED:
            for shape in configs.SHAPES:
                try:
                    update_collectives(arch, shape, args.multi_pod,
                                       out_dir=args.out_dir)
                except Exception as e:  # noqa: BLE001
                    print(f"[coll-update] {arch} x {shape} FAILED: {e}")
        sys.exit(0)

    kw = dict(fsdp=not args.no_fsdp, remat=not args.no_remat,
              out_dir=args.out_dir, variant=args.variant,
              step_kw={**({"naive_xent": True} if args.naive_xent else {}),
                       **({"constrain_acts": True}
                          if args.constrain_acts else {}),
                       **({"microbatch": args.microbatch}
                          if args.microbatch > 1 else {}),
                       **({"layout": args.layout}
                          if args.layout != "tp" else {})} or None,
              cfg_overrides=({"attn_impl": args.attn,
                              "attn_chunk": args.attn_chunk}
                             if args.attn != "naive" else None))
    failures = 0
    if args.all:
        for arch in configs.ASSIGNED:
            for shape in configs.SHAPES:
                rec = run_one(arch, shape, args.multi_pod, **kw)
                failures += 0 if rec["ok"] else 1
    else:
        assert args.arch, "--arch required unless --all"
        if args.distill:
            rec = run_one(args.arch, "distill_fusion", args.multi_pod,
                          distill=True, **kw)
        else:
            assert args.shape, "--shape required"
            rec = run_one(args.arch, args.shape, args.multi_pod, **kw)
        failures += 0 if rec["ok"] else 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
