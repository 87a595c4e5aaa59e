"""Time the case-dependent half of the dense counts precompute
(`tiled._precompute_cases`) on ONE chip, in the form before the port cases
moved onto the one-hot and in the program's.  Run by no cell.

    python3 hack/precompute_cases_probe.py [--reps 10]      # on one TPU chip
    JAX_PLATFORMS=cpu python3 hack/precompute_cases_probe.py --rehearse

Two shapes, pack on, Q = 2:

  cidr-10k-5k     the cell's request (`counts.cases`, tile bnq): 13,056 +
                  11,520 peer rows, 895 targets a direction, 10,240 pods
  cidr-100k-x4    one chip's share of `cidr-100k-10k-x4`'s ring request
                  (tile bqn): 27,264 + 22,400 peer rows, 4,691 + 4,207
                  targets, the 25,600 pods of a shard

and two forms of the function over the same resident static:

  peer_allow  the form before: peer_allow = peer_match & pport as
              [P, N * Q] (or [P, Q * N] for the bqn egress), one
              [T, P] x [P, N * Q] matmul, its result relaid and packed
  qonehot     the program's: kernel.target_allows_q, the port cases on
              the one-hot's rows, one [Q * T, P] x [P, N] matmul

The static is made on the device from the seed: a peer row matches a pod
one time in five, its target is drawn over the targets and the -1 pad, its
port spec allows everything one row in four and else one port and protocol
of two.  Every time is the median of `--reps` runs after two warm-ups,
`block_until_ready` on the tree; ms a REQUEST.  Both forms must hand back
the same leaves, bit for bit: the probe says so and exits 1 if they do
not.  The last stdout line is the JSON;
`chiprun_out/precompute_cases_probe.json` holds it too.
"""

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: name -> (peer rows, targets) a direction, pods, tile form
SHAPES = {
    "cidr-10k-5k": ({"ingress": (13_056, 895), "egress": (11_520, 895)}, 10_240, "bnq"),
    "cidr-100k-x4": (
        {"ingress": (27_264, 4_691), "egress": (22_400, 4_207)}, 25_600, "bqn",
    ),
}
REHEARSAL = {
    "cidr-10k-5k": ({"ingress": (96, 37), "egress": (80, 37)}, 256, "bnq"),
    "cidr-100k-x4": ({"ingress": (128, 70), "egress": (64, 45)}, 384, "bqn"),
}
Q = 2


def static_of(seed: int, dirs, n: int):
    """_precompute_static's result (pack on) at the shapes, made on the
    device."""
    import jax
    import jax.numpy as jnp

    from cyclonus_tpu.engine.encoding import PORT_INT
    from cyclonus_tpu.engine.kernel import pack_bool_words_jnp

    out = {}
    key = jax.random.PRNGKey(seed)
    for direction, (p, t) in dirs.items():
        key, *k = jax.random.split(key, 8)
        tmatch = jax.random.uniform(k[0], (t, n)) < 0.01
        out[direction] = {
            "peer_match": jax.random.uniform(k[1], (p, n)) < 0.2,
            "tmatch": tmatch,
            "has_target": jnp.any(tmatch, axis=0),
            "peer_target": jax.random.randint(k[2], (p,), -1, t, jnp.int32),
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
            "tmatch_pk": pack_bool_words_jnp(tmatch),
        }
    return out


def peer_allow_cases(static, q_port, q_name, q_proto, pack, tile):
    """`tiled._precompute_cases` as it stood before the port cases moved
    onto the one-hot (its body is kept here): peer_allow [P, N * Q], one
    matmul, the result relaid."""
    import jax.numpy as jnp

    from cyclonus_tpu.engine import tiled
    from cyclonus_tpu.engine.kernel import (
        m_tp_onehot,
        pack_bool_words_jnp,
        port_spec_allows,
    )

    out = {}
    q = q_port.shape[0]
    qn = tile == tiled.TILE_BQN and tiled.counts_tile_form(static) == tiled.TILE_BQN
    for direction in ("ingress", "egress"):
        st = static[direction]
        pport = port_spec_allows(st["port_spec"], q_port, q_name, q_proto)
        n_p, n = st["peer_match"].shape
        if qn and direction == "egress":
            peer_allow = (
                st["peer_match"][:, None, :] & pport[:, :, None]
            ).reshape(n_p, q * n)
        else:
            peer_allow = (
                st["peer_match"][:, :, None] & pport[:, None, :]
            ).reshape(n_p, n * q)
        tallow = jnp.matmul(
            m_tp_onehot(st).astype(jnp.bfloat16),
            peer_allow.astype(jnp.bfloat16),
            preferred_element_type=jnp.bfloat16,
        )
        t = tallow.shape[0]
        lay = (t, q, n) if qn and direction == "egress" else (t, n, q)
        out[direction] = {"tmatch": st["tmatch"], "has_target": st["has_target"]}
        out[direction]["tallow_pk"] = pack_bool_words_jnp((tallow > 0).reshape(lay))
        out[direction]["tmatch_pk"] = st["tmatch_pk"]
    return out


def programs(tile: str):
    import jax

    from cyclonus_tpu.engine import tiled

    def jit(f):
        return jax.jit(lambda s, c: f(s, c[0], c[1], c[2], True, tile))

    return [
        ("peer_allow", jit(peer_allow_cases)),
        ("qonehot", jit(tiled._precompute_cases)),
    ]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=2718281828)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="tiny shapes, for the CPU: the mechanics and the agreement only",
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    out = {"device": str(jax.devices()[0].device_kind), "reps": args.reps, "q": Q,
           "shapes": {}}
    agree = True
    for shape, (dirs, n, tile) in (REHEARSAL if args.rehearse else SHAPES).items():
        static = static_of(args.seed, dirs, n)
        cases = jnp.array([[80, 81], [-1, -1], [0, 1]], jnp.int32)
        got = {"dirs": {d: list(v) for d, v in dirs.items()}, "pods": n,
               "tile": tile, "programs": {}}
        trees = {}
        for name, fn in programs(tile):
            t0 = time.perf_counter()
            res = jax.block_until_ready(fn(static, cases))
            first = time.perf_counter() - t0
            jax.block_until_ready(fn(static, cases))
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(static, cases))
                times.append((time.perf_counter() - t0) * 1e3)
            got["programs"][name] = {
                "request_ms": statistics.median(times),
                "request_ms_runs": times,
                "first_call_s": first,
            }
            trees[name] = jax.tree_util.tree_map(np.asarray, res)
            print(shape, name, json.dumps(got["programs"][name]), flush=True)
        a, b = (
            jax.tree_util.tree_leaves_with_path(trees[k])
            for k in ("peer_allow", "qonehot")
        )
        got["leaves_equal"] = len(a) == len(b) and all(
            pa == pb and x.dtype == y.dtype and np.array_equal(x, y)
            for (pa, x), (pb, y) in zip(a, b)
        )
        got["allowed_bits"] = int(sum(
            np.unpackbits(
                np.ascontiguousarray(trees["qonehot"][d]["tallow_pk"]).view(np.uint8)
            ).sum()
            for d in dirs
        ))
        p = got["programs"]
        got["peer_allow_over_qonehot"] = (
            p["peer_allow"]["request_ms"] / p["qonehot"]["request_ms"]
        )
        agree = agree and got["leaves_equal"]
        out["shapes"][shape] = got
    out["leaves_equal"] = agree
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "precompute_cases_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if agree else 1


if __name__ == "__main__":
    sys.exit(main())
