"""Time the class counts' XLA tile loop on ONE chip in the three forms of
its target contraction, at two cells' shapes.  Run by no cell.

    python3 hack/class_tile_probe.py [--reps 3]       (on one TPU chip)
    JAX_PLATFORMS=cpu python3 hack/class_tile_probe.py --rehearse

Shapes (classes bucketed, tiles of 1,024 class rows, Q = 2):

  envelope  k8s-envelope-150k: 17,408 classes, 516 + 516 target words
            (16,512 target rows a direction), 17 tiles
  anp       anp-100k-10k's NetworkPolicy set (its twin mesh-100k-10k's):
            5,120 classes, 12 ingress + 8 egress words, 5 tiles

Forms, each a `lax.fori_loop` over the tiles as
`tiled._class_rowsums_kernel` runs them, on views laid as
`tiled._split_pre` lays them:

  packed  int32 words, `kernel.packed_any` on the VPU: the XLA tile's
          form over a tiered set (the fused kernel's, unrolled)
  bf16    the unpacked target axis on the MXU, TILE_QTN views in bf16
          summed in bf16 (the probe's own tile, `bf16_tile_rowsums`: the
          program runs int8 alone)
  int8    the program's MXU form (`tiled._tile_verdicts_mxu`), int8
          summed in int32

Every view is made on the device from ONE draw of sparse random bits, so
the three forms must give the same row sums: the probe says so and exits
1 if they do not.  Every time is the median of `--reps` runs after a
warm-up, `block_until_ready` on the loop's row sums: ms a request (one
loop is one request's tiles), and target-cells a second, 2 directions x
Q x classes^2 x target rows over it.  The last stdout line is the JSON;
`chiprun_out/class_tile_probe.json` holds it too.
"""

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

Q, BLOCK = 2, 1_024
SHAPES = {
    # name: (classes, ingress words, egress words)
    "envelope": (17_408, 516, 516),
    "anp": (5_120, 12, 8),
}
REHEARSAL = {"envelope": (512, 5, 4), "anp": (256, 2, 1)}
FORMS = ("packed", "bf16", "int8")


def draw(seed: int, cb: int, w_i: int, w_e: int):
    """The bool operands of one request, made on the device: tmatch [T, Cb]
    a direction (a class is a target of few policies: 1 in 512), tallow
    [Q, T, Cb] (1 in 64), has_target [Cb], and the class weights."""
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(jax.random.PRNGKey(seed % 2**31), 16))

    def bits(shape, p):
        return jax.random.uniform(next(keys), shape) < p

    t_i, t_e = 32 * w_i, 32 * w_e
    return {
        "tmatch_e": bits((t_e, cb), 1 / 512),
        "tmatch_i": bits((t_i, cb), 1 / 512),
        "tallow_e": bits((Q, t_e, cb), 1 / 64),
        "tallow_i": bits((Q, t_i, cb), 1 / 64),
        "has_e": bits((cb,), 0.8),
        "has_i": bits((cb,), 0.7),
        "w": jnp.floor(jax.random.uniform(next(keys), (cb,)) * 40).astype(jnp.float32),
    }


def views(form: str, b: dict):
    """(src, dst) of `form` from the bool draw, as tiled._split_pre lays
    them: packed words [W, Cb] and [W, Cb, Q]; the MXU form's [Q, T, Cb]
    and its dst tmatch_i in the operand dtype."""
    import jax.numpy as jnp

    from cyclonus_tpu.engine.kernel import pack_bool_words_jnp

    if form == "packed":
        def tallow(a):
            return jnp.moveaxis(pack_bool_words_jnp(a, axis=1), 0, 2)

        return (
            {"tmatch_e": pack_bool_words_jnp(b["tmatch_e"]), "has_e": b["has_e"],
             "tallow_i": tallow(b["tallow_i"])},
            {"tallow_e": tallow(b["tallow_e"]),
             "tmatch_i": pack_bool_words_jnp(b["tmatch_i"]), "has_i": b["has_i"]},
        )
    dt = {"bf16": jnp.bfloat16, "int8": jnp.int8}[form]
    return (
        {"tmatch_e": b["tmatch_e"], "has_e": b["has_e"],
         "tallow_i": b["tallow_i"].astype(dt)},
        {"tallow_e": b["tallow_e"].astype(dt), "tmatch_i": b["tmatch_i"].astype(dt),
         "has_i": b["has_i"]},
    )


def bf16_tile_rowsums(src, dst, w, start):
    """[BLOCK, Q, 3] row sums of one tile in the MXU form with bf16
    operands: tiled._tile_verdicts_mxu with a bf16 dot, exact as
    tiled._any_bf16 (a sum of 0/1 products is positive from its first
    hit), then the program's row sums."""
    import jax
    import jax.numpy as jnp

    def any_(a, b):
        dims = (((0,), (0,)), ((), ()))
        return jax.lax.dot_general(a.astype(jnp.bfloat16), b, dims) > 0

    def rs(a):
        return jnp.einsum(
            "bd,d->b", a.astype(jnp.float32), w,
            precision=jax.lax.Precision.HIGHEST,
        )

    t_i, t_e = src["tallow_i"].shape[1], src["tmatch_e"].shape[0]
    tme = jax.lax.dynamic_slice(src["tmatch_e"], (0, start), (t_e, BLOCK))
    no_e = ~jax.lax.dynamic_slice(src["has_e"], (start,), (BLOCK,))[:, None]
    no_i = ~dst["has_i"][None, :]
    out = []
    for k in range(Q):
        tli = jax.lax.dynamic_slice(
            src["tallow_i"], (k, 0, start), (1, t_i, BLOCK)
        )[0]
        egress = no_e | any_(tme, dst["tallow_e"][k])
        ingress = no_i | any_(tli, dst["tmatch_i"])
        out.append(jnp.stack([rs(ingress), rs(egress), rs(egress & ingress)], -1))
    return jnp.stack(out, axis=1)


def loop(form: str, cb: int):
    import jax
    import jax.numpy as jnp

    from cyclonus_tpu.engine import tiled

    def run(src, dst, w):
        def body(i, out):
            if form == "bf16":
                rs = bf16_tile_rowsums(src, dst, w, i * BLOCK)
            else:
                rs = tiled._class_tile_rowsums(src, dst, w, i * BLOCK, BLOCK)
            return jax.lax.dynamic_update_slice(out, rs, (i * BLOCK, 0, 0))

        return jax.lax.fori_loop(
            0, cb // BLOCK, body, jnp.zeros((cb, Q, 3), jnp.float32)
        )

    return jax.jit(run)


def time_form(form, cb, b, reps):
    """(row sums, first-call s, run times ms) of one form's loop."""
    import numpy as np

    src, dst = views(form, b)
    fn = loop(form, cb)
    t0 = time.perf_counter()
    out = fn(src, dst, b["w"])
    out.block_until_ready()
    first = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(src, dst, b["w"]).block_until_ready()
        times.append((time.perf_counter() - t0) * 1e3)
    return np.asarray(out), first, times


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=2718281828)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="tiny shapes, for the CPU: the mechanics and the agreement only",
    )
    args = ap.parse_args()

    import jax
    import numpy as np

    shapes = SHAPES
    if args.rehearse:
        global BLOCK
        shapes, BLOCK = REHEARSAL, 128
    out = {
        "device": str(jax.devices()[0].device_kind),
        "q": Q, "block": BLOCK, "reps": args.reps, "shapes": {},
    }
    agree = True
    for name, (cb, w_i, w_e) in shapes.items():
        b = draw(args.seed, cb, w_i, w_e)
        cells = Q * cb * cb * 32 * (w_i + w_e)
        res, sums = {}, {}
        for form in FORMS:
            rs, first, times = time_form(form, cb, b, args.reps)
            med = statistics.median(times)
            res[form] = {
                "request_ms": med,
                "request_ms_runs": times,
                "target_cells_per_s": cells / (med / 1e3),
                "first_call_s": first,
            }
            sums[form] = rs
            print(name, form, json.dumps(res[form]), flush=True)
        same = all(np.array_equal(sums["packed"], sums[f]) for f in FORMS)
        agree = agree and same
        out["shapes"][name] = {
            "classes": cb, "words_i": w_i, "words_e": w_e, "tiles": cb // BLOCK,
            "target_cells": cells, "forms": res, "rowsums_agree": same,
            "packed_over": {f: res["packed"]["request_ms"] / res[f]["request_ms"]
                            for f in FORMS[1:]},
        }
        del b
    out["rowsums_agree"] = agree
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "class_tile_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if agree else 1


if __name__ == "__main__":
    sys.exit(main())
