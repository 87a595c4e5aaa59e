"""Time the XLA counts tile body on ONE chip at `cidr-100k-10k-x4`'s ring
step: 25 tiles of 1,024 source rows against a 25,600-pod dst shard, 136
egress + 152 ingress target words, Q = 2.  Run by no cell.

    chiprun --chips 1 -- python3 hack/counts_tile_probe.py [--reps 5]
    JAX_PLATFORMS=cpu python3 hack/counts_tile_probe.py --rehearse

Three forms of the tile, each a `lax.fori_loop` over the step's 25 tiles
as `tiled._ring_counts` runs them (one step, no hop):

  bnq          the parent's body: `_tile_verdicts_split` ([B, Nd, Q], the
               ingress [Nd, B*Q] contraction swapped) and its three sums
  bqn          `tiled._tile_counts_split` on a precompute laid for it: a
               [B, Nd] slab a case, both contractions rows-major, pods on
               the lanes
  bqn-reshape  ISSUE 39's literal form, here only: one [B*Q, Nd] ingress
               and one [B, Q*Nd] egress contraction, each reshaped to
               [B, Q, Nd]

and each direction's contraction alone in the bnq and bqn orientations
(its output held by `optimization_barrier`, then summed, so that it is
materialized as in the tile).  Every time is the median of `--reps` runs
after two warm-ups, `block_until_ready` on the step's result; ms a STEP
and ms a word (the step over the direction's words).  A request of the
cell is four steps.  The three tile forms must count the same: the
probe says so and exits 1 if they do not.  The last stdout line is the
JSON; `chiprun_out/counts_tile_probe.json` holds it too.
"""

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

W_E, W_I, SHARD, BLOCK, Q = 136, 152, 25_600, 1_024, 2
TILES = SHARD // BLOCK


def operands(seed: int):
    """Sparse random words (six words AND-ed: a bit is set 1 time in 64,
    so about half of a cell's contractions find a hit) and a pad tail,
    made on the device."""
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def words(shape):
        out = None
        for _ in range(6):
            w = jax.lax.bitcast_convert_type(
                jax.random.bits(next(keys), shape, dtype=jnp.uint32),
                jnp.int32,
            )
            out = w if out is None else out & w
        return out

    def flags(p):
        return jax.random.uniform(next(keys), (SHARD,)) < p

    valid = jnp.arange(SHARD) < SHARD - 100
    src = {
        "tmatch_e": words((W_E, SHARD)),
        "has_e": flags(0.8),
        "tallow_i": words((W_I, SHARD, Q)),
    }
    bnq = {
        "tallow_e": words((W_E, SHARD, Q)),
        "tmatch_i": words((W_I, SHARD)),
        "has_i": flags(0.7),
    }
    bqn = dict(bnq, tallow_e=jnp.swapaxes(bnq["tallow_e"], 1, 2))
    return src, bnq, bqn, valid


def programs():
    import jax
    import jax.numpy as jnp

    from cyclonus_tpu.engine import tiled
    from cyclonus_tpu.engine.kernel import packed_any

    def step(tile_counts):
        def run(src, dst, valid):
            def body(i, counts):
                return counts.at[i].set(
                    tile_counts(src, dst, valid, i * BLOCK)
                )

            return jax.lax.fori_loop(
                0, TILES, body, jnp.zeros((TILES, 3), jnp.int32)
            )

        return jax.jit(run)

    def sums(verdicts, mask):
        return jnp.stack([jnp.sum(v & mask, dtype=jnp.int32) for v in verdicts])

    def bnq(src, dst, valid, start):
        v = tiled._tile_verdicts_split(src, dst, start, BLOCK)
        sv = jax.lax.dynamic_slice(valid, (start,), (BLOCK,))
        return sums(v, sv[:, None, None] & valid[None, :, None])

    def bqn(src, dst, valid, start):
        return tiled._tile_counts_split(src, dst, valid, valid, start, BLOCK)

    def bqn_reshape(src, dst, valid, start):
        tme = jax.lax.dynamic_slice(src["tmatch_e"], (0, start), (W_E, BLOCK))
        hte = jax.lax.dynamic_slice(src["has_e"], (start,), (BLOCK,))
        tli = jax.lax.dynamic_slice(
            src["tallow_i"], (0, start, 0), (W_I, BLOCK, Q)
        ).reshape(W_I, BLOCK * Q)
        e = packed_any(tme, dst["tallow_e"].reshape(W_E, Q * SHARD))
        i = packed_any(tli, dst["tmatch_i"])
        e = (~hte[:, None, None]) | e.reshape(BLOCK, Q, SHARD)
        i = (~dst["has_i"][None, None, :]) | i.reshape(BLOCK, Q, SHARD)
        sv = jax.lax.dynamic_slice(valid, (start,), (BLOCK,))
        return sums((i, e, e & i), sv[:, None, None] & valid[None, None, :])

    def held(x):
        return jnp.sum(jax.lax.optimization_barrier(x), dtype=jnp.int32)

    def ingress_bnq(src, dst, valid, start):
        tli = jax.lax.dynamic_slice(
            src["tallow_i"], (0, start, 0), (W_I, BLOCK, Q)
        )
        any_i = packed_any(dst["tmatch_i"], tli.reshape(W_I, BLOCK * Q))
        return jnp.stack([held(any_i), 0, 0])

    def ingress_bqn(src, dst, valid, start):
        tli = jax.lax.dynamic_slice(
            src["tallow_i"], (0, start, 0), (W_I, BLOCK, Q)
        )
        return jnp.stack(
            [sum(held(packed_any(tli[:, :, k], dst["tmatch_i"]))
                 for k in range(Q)), 0, 0]
        )

    def egress_bnq(src, dst, valid, start):
        tme = jax.lax.dynamic_slice(src["tmatch_e"], (0, start), (W_E, BLOCK))
        any_e = packed_any(tme, dst["tallow_e"].reshape(W_E, SHARD * Q))
        return jnp.stack([held(any_e), 0, 0])

    def egress_bqn(src, dst, valid, start):
        tme = jax.lax.dynamic_slice(src["tmatch_e"], (0, start), (W_E, BLOCK))
        return jnp.stack(
            [sum(held(packed_any(tme, dst["tallow_e"][:, k]))
                 for k in range(Q)), 0, 0]
        )

    # (name, program, layout of the dst tallow_e it reads, words)
    return [
        ("tile.bnq", step(bnq), "bnq", W_E + W_I),
        ("tile.bqn", step(bqn), "bqn", W_E + W_I),
        ("tile.bqn-reshape", step(bqn_reshape), "bqn", W_E + W_I),
        ("ingress.bnq", step(ingress_bnq), "bnq", W_I),
        ("ingress.bqn", step(ingress_bqn), "bqn", W_I),
        ("egress.bnq", step(egress_bnq), "bnq", W_E),
        ("egress.bqn", step(egress_bqn), "bqn", W_E),
    ]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=2718281828)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="tiny shapes, for the CPU: the mechanics and the agreement only",
    )
    args = ap.parse_args()
    if args.rehearse:
        global W_E, W_I, SHARD, BLOCK, TILES
        W_E, W_I, SHARD, BLOCK = 5, 7, 1_024, 128
        TILES = SHARD // BLOCK

    import jax
    import numpy as np

    src, bnq, bqn, valid = operands(args.seed)
    dst = {"bnq": bnq, "bqn": bqn}
    out = {
        "device": str(jax.devices()[0].device_kind),
        "shapes": {"words_e": W_E, "words_i": W_I, "shard": SHARD,
                   "block": BLOCK, "q": Q, "tiles": TILES},
        "reps": args.reps,
        "programs": {},
    }
    counts = {}
    for name, fn, layout, words in programs():
        t0 = time.perf_counter()
        res = fn(src, dst[layout], valid)
        res.block_until_ready()
        first = time.perf_counter() - t0
        fn(src, dst[layout], valid).block_until_ready()
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            fn(src, dst[layout], valid).block_until_ready()
            times.append((time.perf_counter() - t0) * 1e3)
        med = statistics.median(times)
        out["programs"][name] = {
            "step_ms": med,
            "step_ms_runs": times,
            "ms_a_word": med / words,
            "request_ms": 4 * med,
            "first_call_s": first,
        }
        if name.startswith("tile."):
            counts[name] = np.asarray(res).sum(axis=0).tolist()
        print(name, json.dumps(out["programs"][name]), flush=True)
    out["counts"] = counts
    out["counts_agree"] = len({tuple(c) for c in counts.values()}) == 1
    p = out["programs"]
    out["ingress_over_egress_a_word"] = {
        form: p[f"ingress.{form}"]["ms_a_word"] / p[f"egress.{form}"]["ms_a_word"]
        for form in ("bnq", "bqn")
    }
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "counts_tile_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["counts_agree"] else 1


if __name__ == "__main__":
    sys.exit(main())
