"""Time the case-dependent half of the counts precompute
(`tiled._precompute_cases`, whose contraction is `kernel.target_allows_q`)
on ONE chip: the dense one-hot over every peer row against the banded
form, each tile of targets against its own peer rows, at tiles of 64, 128
and 256 targets.  Run by no cell.

    python3 hack/precompute_cases_probe.py [--reps 10] [--shapes a,b]   # one TPU chip
    JAX_PLATFORMS=cpu python3 hack/precompute_cases_probe.py --rehearse
    JAX_PLATFORMS=cpu python3 hack/precompute_cases_probe.py --compile

The shapes, Q = 2, each a direction's (peer rows, targets, most rows a
target) as the cell's tensors have them (bucketed), and the order its
peer rows lie in:

  envelope        k8s-envelope-150k's class request (`counts.classes`,
                  the MXU form: tile qtn, unpacked): 18,048 + 14,848 peer
                  rows, 16,511 targets a direction, at most 2 / 4 rows a
                  target, 17,408 class rows, rows NOT in target order.
                  The class program traces both halves, so its band's
                  gather is the request's too
  cidr-10k-5k     the cell's request (`counts.cases`, tile bnq, packed):
                  13,056 + 11,520 peer rows, 895 targets, up to 57 / 56
                  rows a target, 10,240 pods, rows in target order; the
                  static (and its band's gather) is resident, built once
  cidr-100k-x4    one chip's share of `cidr-100k-10k-x4`'s ring request
                  (tile bqn, packed): 27,264 + 22,400 peer rows, 4,735 +
                  4,223 targets, up to 32 / 28 rows a target, the 25,600
                  pods of a shard, rows NOT in target order; its static is
                  held too
  mesh-100k-10k   the class request of `mesh-100k-10k` and `anp-100k-10k`
                  (tile bnq, packed): 512 + 256 peer rows, 383 + 255
                  targets, up to 7 / 4 rows a target, 4,224 class rows,
                  rows in target order: a band of 0.50 / 1.00 of the dense
                  work, where the rule declines
  tables-10k-1k   the tables cells' NetworkPolicies: 64 + 32 peer rows, 31 +
                  15 targets, 10,240 pods, in target order (a band larger
                  than the dense one-hot)
  skew-<f>        the crossover: 1,024 targets, 8,192 peer rows a
                  direction, 8,192 pods (tile bnq, packed), one tile of
                  128 targets owning the share f of the rows and the rest
                  spread over the others, shuffled, so that the band is
                  about f of the dense work: skew-0.125 (rows even),
                  skew-0.25, skew-0.5, skew-1.0

The forms over the same static:

  qonehot     the dense form: the port-folded one-hot [Q * T, P] against
              peer_match [P, N] in one matmul
  band<TB>    kernel.TARGET_BAND = TB: band_rows laid on the host
              (kernel.band_rows), the peer rows gathered to them
              (kernel.band_peer_match), and one batched matmul over the
              tiles; timed as `cases` (from the banded static, the
              resident routes' request) and `gather` (the gather and the
              cases in one program, the class route's request).  The
              probe lays the band whether or not kernel.band_engages
              would

The static is made from the seed: a peer row matches a pod one time in
five; its port spec allows everything one row in four and else one port
and protocol of two.  Each target owns 1 - `most` rows (Poisson about P /
T), laid in the cell's order (sorted by target, or shuffled), -1 pads
past them.  Every time is the median of `--reps` runs after two warm-ups,
`block_until_ready` on the tree; ms a REQUEST.  Every form must hand back
qonehot's leaves, bit for bit: the probe says so and exits 1 if one does
not; an error in a shape is printed with the shape's name and exits 2
after the shapes that follow.  `--compile` compiles every program at the
real shapes for a described v5e and prints its compile seconds,
`memory_analysis()` and the compiler's flops and bytes accessed
(`cost_analysis()`; nothing runs).  The last stdout line is a summary in
JSON; `chiprun_out/precompute_cases_probe.json` holds every reading,
written again after each shape.
"""

import argparse
import json
import os
import statistics
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: name -> ({direction: (peer rows, targets, most rows a target)}, pods,
#: tile form, pack, peer rows in target order)
SHAPES = {
    "envelope": (
        {"ingress": (18_048, 16_511, 2), "egress": (14_848, 16_511, 4)},
        17_408, "qtn", False, False,
    ),
    "cidr-10k-5k": (
        {"ingress": (13_056, 895, 57), "egress": (11_520, 895, 56)},
        10_240, "bnq", True, True,
    ),
    "cidr-100k-x4": (
        {"ingress": (27_264, 4_735, 32), "egress": (22_400, 4_223, 28)},
        25_600, "bqn", True, False,
    ),
    "mesh-100k-10k": (
        {"ingress": (512, 383, 7), "egress": (256, 255, 4)},
        4_224, "bnq", True, True,
    ),
    "tables-10k-1k": (
        {"ingress": (64, 31, 3), "egress": (32, 15, 3)},
        10_240, "bnq", True, True,
    ),
}
#: skew-<f>: (targets, peer rows, pods) of a direction; the first tile of
#: 128 targets owns the share f of the rows (draw_skewed)
SKEW = (1_024, 8_192, 8_192)
SKEWS = ("0.125", "0.25", "0.5", "1.0")
for _f in SKEWS:
    SHAPES[f"skew-{_f}"] = (
        {d: (SKEW[1], SKEW[0], float(_f)) for d in ("ingress", "egress")},
        SKEW[2], "bnq", True, False,
    )
REHEARSAL = {
    "envelope": (
        {"ingress": (640, 511, 2), "egress": (512, 511, 4)}, 256, "qtn", False,
        False,
    ),
    "cidr-10k-5k": (
        {"ingress": (1_152, 95, 57), "egress": (1_024, 95, 56)}, 256, "bnq",
        True, True,
    ),
    "cidr-100k-x4": (
        {"ingress": (768, 300, 4), "egress": (640, 257, 4)}, 384, "bqn", True,
        False,
    ),
    "mesh-100k-10k": SHAPES["mesh-100k-10k"][:1] + (256, "bnq", True, True),
    "tables-10k-1k": SHAPES["tables-10k-1k"][:1] + (256, "bnq", True, True),
    "skew-0.5": (
        {d: (1_024, 512, 0.5) for d in ("ingress", "egress")}, 256, "bnq",
        True, False,
    ),
}
Q = 2
BANDS = (64, 128, 256)


def draw_targets(rng, p: int, t: int, most, in_order: bool = True):
    """peer_target [p] int32: 1 - `most` rows a target (Poisson about p /
    t), -1 past them; an int `most` is a row ceiling, a float the share of
    the rows the first tile of 128 targets owns (draw_skewed).  Sorted by
    target where `in_order`, else the real rows shuffled among themselves
    (the pads stay last, as the bucketed axis has them)."""
    import numpy as np

    if isinstance(most, float):
        rows = draw_skewed(rng, p, t, most)
    else:
        counts = np.clip(rng.poisson(p / t, t), 1, most)
        rows = np.repeat(np.arange(t, dtype=np.int32), counts)[:p]
    if not in_order:
        rows = rng.permutation(rows)
    pt = np.full(p, -1, np.int32)
    pt[: rows.size] = rows
    return pt


def draw_skewed(rng, p: int, t: int, share: float):
    """Sorted target ids of the p rows: targets [0, 128) own the share
    `share` of them, the other targets the rest, each row's target drawn
    evenly within its part (a share of 128 / t spreads the rows evenly)."""
    import numpy as np

    hot = int(round(p * share))
    rows = np.concatenate([
        rng.integers(0, 128, hot), rng.integers(128, t, p - hot),
    ]).astype(np.int32)
    return np.sort(rows)


def static_of(
    seed: int, dirs, n: int, pack: bool, in_order: bool, shapes_only: bool = False
):
    """_precompute_static's dense result at the shapes, made on the device
    (`shapes_only`: jax.ShapeDtypeStruct leaves), and each direction's
    peer_target on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cyclonus_tpu.engine.encoding import PORT_INT, packed_words
    from cyclonus_tpu.engine.kernel import pack_bool_words_jnp

    rng = np.random.default_rng(seed % 2**32)
    out, targets = {}, {}
    key = jax.random.PRNGKey(seed % 2**31)
    for direction, (p, t, most) in dirs.items():
        pt = draw_targets(rng, p, t, most, in_order)
        targets[direction] = pt
        if shapes_only:
            sds = jax.ShapeDtypeStruct
            i32 = jnp.int32
            out[direction] = {
                "peer_match": sds((p, n), jnp.bool_),
                "tmatch": sds((t, n), jnp.bool_),
                "has_target": sds((n,), jnp.bool_),
                "peer_target": sds((p,), i32),
                "target_ns": sds((t,), i32),
                "port_spec": {
                    k: sds((p,) if k == "spec_all" else (p, 1),
                           jnp.bool_ if k == "spec_all" else i32)
                    for k in ("spec_all", "item_kind", "item_port", "item_name",
                              "item_proto", "rng_from", "rng_to", "rng_proto")
                },
            }
            if pack:
                out[direction]["tmatch_pk"] = sds((packed_words(t), n), i32)
            continue
        key, *k = jax.random.split(key, 8)
        tmatch = jax.random.uniform(k[0], (t, n)) < 0.01
        out[direction] = {
            "peer_match": jax.random.uniform(k[1], (p, n)) < 0.2,
            "tmatch": tmatch,
            "has_target": jnp.any(tmatch, axis=0),
            "peer_target": jnp.asarray(pt),
            "target_ns": jnp.zeros((t,), jnp.int32),
            "port_spec": {
                "spec_all": jax.random.uniform(k[3], (p,)) < 0.25,
                "item_kind": jnp.full((p, 1), PORT_INT, jnp.int32),
                "item_port": jax.random.randint(k[4], (p, 1), 80, 82, jnp.int32),
                "item_name": jnp.full((p, 1), -1, jnp.int32),
                "item_proto": jax.random.randint(k[5], (p, 1), 0, 2, jnp.int32),
                "rng_from": jnp.zeros((p, 1), jnp.int32),
                "rng_to": jnp.full((p, 1), -1, jnp.int32),
                "rng_proto": jnp.zeros((p, 1), jnp.int32),
            },
        }
        if pack:
            out[direction]["tmatch_pk"] = pack_bool_words_jnp(tmatch)
    return out, targets


def band_plans(targets, dirs):
    """{direction: band_rows} at the kernel's current TARGET_BAND."""
    from cyclonus_tpu.engine import kernel

    return {d: kernel.band_rows(targets[d], dirs[d][1]) for d in dirs}


def with_band(static, bands):
    """The banded static: band_rows beside the rows gathered to them, as
    _precompute_static lays it."""
    from cyclonus_tpu.engine import kernel

    out = dict(static)
    for d, band in bands.items():
        st = dict(static[d])
        st["band_rows"] = band
        st["peer_match"] = kernel.band_peer_match(band, st["peer_match"])
        out[d] = st
    return out


def programs(dirs, targets, tile: str, pack: bool, tb_list):
    """[(name, jitted fn(static, cases), takes_banded_static)] : qonehot,
    then for each TB its `cases` and `gather` programs.  TARGET_BAND is
    read when a program traces, so each is traced under its own TB
    (first_call)."""
    import jax
    import jax.numpy as jnp

    from cyclonus_tpu.engine import kernel, tiled

    def cases_of(s, c):
        return tiled._precompute_cases(s, c[0], c[1], c[2], pack, tile)

    out = [("qonehot", jax.jit(cases_of), None)]
    for tb in tb_list:
        kernel.TARGET_BAND = tb
        bands = {d: jnp.asarray(b) for d, b in band_plans(targets, dirs).items()}
        out.append((f"band{tb}.cases", jax.jit(cases_of), (tb, bands)))
        out.append((
            f"band{tb}.gather",
            jax.jit(lambda s, c, b=bands: cases_of(with_band(s, b), c)),
            (tb, None),
        ))
    kernel.TARGET_BAND = 128
    return out


def compile_all(args, shapes):
    """Compile every program at the shapes for a described v5e."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from cyclonus_tpu.engine import kernel

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree
        )

    out = {}
    for shape, (dirs, n, tile, pack, in_order) in shapes.items():
        static, targets = static_of(
            args.seed, dirs, n, pack, in_order, shapes_only=True
        )
        cases = jax.ShapeDtypeStruct((3, Q), jnp.int32, sharding=one)
        got = {}
        for name, fn, band in programs(dirs, targets, tile, pack, BANDS):
            tb, bands = band if band else (128, None)
            kernel.TARGET_BAND = tb
            arg = static
            if bands is not None:
                arg = jax.eval_shape(lambda s: with_band(s, bands), static)
            t0 = time.perf_counter()
            compiled = fn.lower(placed(arg), cases).compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis() or {}
            if isinstance(cost, list):
                cost = cost[0] if cost else {}
            got[name] = {
                "compile_s": time.perf_counter() - t0,
                "temp_bytes": int(mem.temp_size_in_bytes),
                "argument_bytes": int(mem.argument_size_in_bytes),
                "flops": float(cost.get("flops", -1)),
                "bytes_accessed": float(cost.get("bytes accessed", -1)),
            }
            kernel.TARGET_BAND = 128
            print(shape, name, json.dumps(got[name]), flush=True)
        out[shape] = got
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=2718281828)
    ap.add_argument("--bands", default=",".join(map(str, BANDS)),
                    help="the tiles of targets to time, comma-separated")
    ap.add_argument("--shapes", default=None,
                    help="comma-separated; every shape of the table by default")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument(
        "--rehearse", action="store_true",
        help="tiny shapes, for the CPU: the mechanics and the agreement only",
    )
    mode.add_argument(
        "--compile", action="store_true",
        help="compile at the real shapes for a described v5e, run nothing",
    )
    args = ap.parse_args()

    import jax

    table = REHEARSAL if args.rehearse else SHAPES
    shapes = {k: table[k] for k in (args.shapes or ",".join(table)).split(",")}
    tb_list = [int(x) for x in args.bands.split(",") if x]
    if args.compile:
        out = {"compile": compile_all(args, shapes)}
        print(json.dumps(out))
        return 0

    out = {"device": str(jax.devices()[0].device_kind), "reps": args.reps, "q": Q,
           "shapes": {}, "errors": {}}
    for shape, spec in shapes.items():
        args.shape_name = shape
        try:
            out["shapes"][shape] = run_shape(args, spec, tb_list)
        except Exception as exc:  # noqa: BLE001 - reported, and the exit says so
            traceback.print_exc()
            print(shape, "ERROR", repr(exc), flush=True)
            out["errors"][shape] = repr(exc)
        save(out)
    agree = all(g["leaves_equal_all"] for g in out["shapes"].values())
    out["leaves_equal"] = agree
    save(out)
    print(json.dumps({
        "errors": out["errors"],
        **{shape: {"ms": {k: round(v["request_ms"], 3)
                          for k, v in g["programs"].items()},
                   "spans": g["spans"], "leaves_equal": g["leaves_equal_all"]}
           for shape, g in out["shapes"].items()},
    }))
    return 2 if out["errors"] else 0 if agree else 1


def save(out) -> None:
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "precompute_cases_probe.json"), "w") as f:
        json.dump(out, f, indent=1)


def run_shape(args, spec, tb_list):
    """Every program of one shape, timed and held to qonehot's leaves."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cyclonus_tpu.engine import kernel

    dirs, n, tile, pack, in_order = spec
    static, targets = static_of(args.seed, dirs, n, pack, in_order)
    cases = jnp.array([[80, 81], [-1, -1], [0, 1]], jnp.int32)
    got = {"dirs": {d: list(v) for d, v in dirs.items()}, "pods": n,
           "tile": tile, "pack": pack, "in_order": in_order, "programs": {},
           "spans": {}, "band_share": {}, "leaves_equal": {}}
    for name, fn, band in programs(dirs, targets, tile, pack, tb_list):
        tb, bands = band if band else (128, None)
        kernel.TARGET_BAND = tb
        arg = static
        if bands is not None:
            arg = jax.block_until_ready(jax.jit(with_band)(static, bands))
            got["spans"][f"band{tb}"] = {d: list(b.shape) for d, b in bands.items()}
            # the band's share of the dense one-hot: tiles x TB x PSPAN / (T x P)
            got["band_share"][f"band{tb}"] = {
                d: b.shape[0] * tb * b.shape[1] / (dirs[d][1] * dirs[d][0])
                for d, b in bands.items()
            }
        t0 = time.perf_counter()
        res = jax.block_until_ready(fn(arg, cases))
        first = time.perf_counter() - t0
        jax.block_until_ready(fn(arg, cases))
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(arg, cases))
            times.append((time.perf_counter() - t0) * 1e3)
        kernel.TARGET_BAND = 128
        got["programs"][name] = {
            "request_ms": statistics.median(times),
            "request_ms_runs": times,
            "first_call_s": first,
        }
        have = jax.tree_util.tree_leaves_with_path(
            jax.tree_util.tree_map(np.asarray, res)
        )
        if name == "qonehot":
            want = have
        got["leaves_equal"][name] = len(have) == len(want) and all(
            pa == pb and x.dtype == y.dtype and np.array_equal(x, y)
            for (pa, x), (pb, y) in zip(have, want)
        )
        del arg, res, have
        print(args.shape_name, name, json.dumps(got["programs"][name]),
              "equal", got["leaves_equal"][name], flush=True)
    dense = got["programs"]["qonehot"]["request_ms"]
    got["dense_over"] = {k: dense / v["request_ms"] for k, v in got["programs"].items()}
    got["leaves_equal_all"] = all(got["leaves_equal"].values())
    return got


if __name__ == "__main__":
    sys.exit(main())
