#!/usr/bin/env python3
"""Build a cell's engine on the host at its full size and print the band
plan of the target-allows contraction (`kernel.band_rows`, laid by
`api._band_tensors`) for each tensor set the engine holds, with the
engine's AOT plan and, with `--hlo`, a digest of the StableHLO of
`tiled._precompute` lowered over each set (every program that traces the
contraction traces it through there), in each of its five forms (the
counts tiles bnq / bqn packed and not, the class tile's qtn).  Nothing
runs on a device: the lowering takes shapes.

    JAX_PLATFORMS=cpu python3 hack/band_plans.py mesh-100k-10k.port-sweep [...] [--hlo]

Run from two checkouts (a parent unpacked by `git archive` and this one),
the lines of a cell whose sets carry no band_rows must agree letter for
letter: the same plan, shapes and StableHLO, so the same (AOT key,
StableHLO) pairs.  A checkout without the band prints `band: -` and the
form its plan names.  Per direction it prints T' (bucketed targets), P
(bucketed peer rows), the most rows a target owns, whether the rows lie
in target order, and where a band is laid its [tiles, PSPAN] and its share
of the dense one-hot's MXU work, tiles x 128 x PSPAN / (T' x P).
"""

import argparse
import hashlib
import importlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def build(cell: str):
    """(engine, q) for `cell` of BENCHMARK.json at its configuration's
    full sizes and `structure_seed`, at the program's defaults."""
    from benchmarks import generators, program

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wl = next(w for w in bench["workloads"] if w["name"] == cell)
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    with open(os.path.join(REPO, conf["file"])) as f:
        cfg = json.load(f)
    sizes = cfg["sizes"]
    seed = 1
    gen = cfg["generator"]
    if "module" in gen:
        pods, namespaces, policies = importlib.import_module(
            "benchmarks." + gen["module"]
        ).build(sizes, gen, seed)
    else:
        pods, namespaces, policies = generators.build_synthetic(sizes, gen, seed)
    tiers = None
    if "tiers" in cfg:
        from benchmarks import generators_tiers
        from benchmarks.kinds.sweep_tiered import parse_tiers

        tiers = parse_tiers(*generators_tiers.build(sizes, cfg["tiers"], seed))
    from cyclonus_tpu.engine.api import TpuPolicyEngine

    policy = program.build_policy(program.parse_policies(policies))
    return TpuPolicyEngine(policy, pods, namespaces, tiers=tiers)


def direction_line(enc) -> str:
    import numpy as np

    t = int(enc["target_ns"].shape[0])
    p = int(enc["peer_target"].shape[0])
    pt = np.asarray(enc["peer_target"])
    real = pt[pt >= 0]
    most = int(np.bincount(real).max()) if real.size else 0
    line = (f"T' {t}, P {p}, rows a target <= {most}, in target order "
            f"{bool((np.diff(real) >= 0).all())}")
    band = enc.get("band_rows")
    if band is None:
        return line + ", band: -"
    tiles, span = band.shape
    return line + (f", band [{tiles}, {span}], share "
                   f"{tiles * 128 * span / (t * p):.4f}")


def hlo_digests(tensors, q: int):
    """{form: sha256[:16] of the StableHLO of tiled._precompute over the
    shapes of `tensors` and q port cases}."""
    import jax
    import numpy as np

    from cyclonus_tpu.engine import tiled

    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype), tensors
    )
    for k in ("q_port", "q_name", "q_proto"):
        shapes[k] = jax.ShapeDtypeStruct((q,), np.int32)
    out = {}
    for tile, pack in (("bnq", False), ("bnq", True), ("bqn", False),
                       ("bqn", True), ("qtn", False)):
        fn = jax.jit(tiled._precompute, static_argnames=("pack", "tile"))
        text = fn.lower(shapes, pack=pack, tile=tile).as_text()
        out[f"{tile}.{'packed' if pack else 'bool'}"] = hashlib.sha256(
            text.encode()
        ).hexdigest()[:16]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--hlo", action="store_true")
    args = ap.parse_args()
    for cell in args.cells:
        engine = build(cell)
        print(f"{cell}: plan {engine._aot_plan()}", flush=True)
        sets = [("main", engine._tensors)]
        if engine._class_state is not None:
            sets.append(("class", engine._class_state["ctensors"]))
        for name, tensors in sets:
            for d in ("ingress", "egress"):
                print(f"  {name} {d}: {direction_line(tensors[d])}", flush=True)
            if args.hlo:
                print(f"  {name} StableHLO: {json.dumps(hlo_digests(tensors, 2))}",
                      flush=True)
        del engine
    return 0


if __name__ == "__main__":
    sys.exit(main())
