"""Time the precedence-tier first-match on ONE chip at `anp-100k-10k`'s class
tiles: 1,536 bucketed rule rows a direction (48 packed words), 5 tiles of
1,024 class rows against the 5,120-class axis, Q = 2.  Run by no cell.

    python3 hack/tier_first_match_probe.py [--reps 5]      # on one TPU chip
    JAX_PLATFORMS=cpu python3 hack/tier_first_match_probe.py --rehearse

Two forms of `kernel.tier_first_match_keys`, each inside a `lax.fori_loop`
over the request's 5 tiles, both directions a tile as
`tiled._tile_verdicts_split` calls them (egress: the tile's rows are the
subjects, the whole class axis the peers; ingress: the other way round,
handed back [Nd, B, Q]), each direction's keys folded by
`resolve_tier_lattice` and counted, so that nothing is left unread:

  scan   the 8-row `lax.scan` with two int32 [A, B, Q] keys as its carry
         (the form before the word reduction; its body is kept here)
  words  the program's: one min-reduction a tier over packed rule words

and each direction alone.  The rule rows are laid as the encoding lays them:
ANP rows with nondecreasing ranks (equal ranks one rule's peer rows, one
action), then the BANP's, then pad rows.  Every time is the median of
`--reps` runs after two warm-ups, `block_until_ready` on the counts; ms a
REQUEST.  Both forms must count the same: the probe says so and exits 1 if
they do not.  The last stdout line is the JSON;
`chiprun_out/tier_first_match_probe.json` holds it too.
"""

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

G, ANP_ROWS, BANP_ROWS, CLASSES, BLOCK, Q = 1_536, 1_503, 2, 5_120, 1_024, 2
TILES = CLASSES // BLOCK


def keys_of(seed: int):
    """([G] anp, [G] banp) int32 keys in the encoding's order: about 1.5 peer
    rows a rule, actions drawn a rule, pad rows last."""
    import numpy as np

    from cyclonus_tpu.engine.encoding import TIER_KEY_NONE

    rng = np.random.default_rng(seed)
    anp = np.full(G, TIER_KEY_NONE, np.int32)
    banp = np.full(G, TIER_KEY_NONE, np.int32)
    for lo, n, out in ((0, ANP_ROWS, anp), (ANP_ROWS, BANP_ROWS, banp)):
        rank = np.sort(rng.integers(0, max(1, (2 * n) // 3), n))
        act = rng.integers(1, 4, n + 1)[rank]
        out[lo:lo + n] = rank * 4 + act
    return anp, banp


def operands(seed: int):
    """Scope bits made on the device: a subject matches 1 class in 8, a peer
    case 1 in 4."""
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    anp, banp = keys_of(seed)
    return {
        "subj_e": jax.random.uniform(k[0], (G, CLASSES)) < 0.125,
        "peerq_e": jax.random.uniform(k[1], (G, CLASSES, Q)) < 0.25,
        "subj_i": jax.random.uniform(k[2], (G, CLASSES)) < 0.125,
        "peerq_i": jax.random.uniform(k[3], (G, CLASSES, Q)) < 0.25,
        "has": jnp.arange(CLASSES) % 3 == 0,
        "keys": jnp.stack([jnp.asarray(anp), jnp.asarray(banp)]),
    }


def scan_first_match_keys(subj, peerq, anp_key, banp_key, chunk=8):
    """The 8-row scan: two int32 [A, B, Q] min keys carried through HBM
    once a chunk of rows."""
    import jax
    import jax.numpy as jnp

    from cyclonus_tpu.engine.encoding import TIER_KEY_NONE

    g, a = subj.shape
    b, q = peerq.shape[1], peerq.shape[2]
    c = min(chunk, g)
    none = jnp.int32(TIER_KEY_NONE)
    init = (jnp.full((a, b, q), none), jnp.full((a, b, q), none))

    def body(carry, xs):
        s, pq, ka, kb = xs
        m = s[:, :, None, None] & pq[:, None, :, :]
        a_min = jnp.min(jnp.where(m, ka[:, None, None, None], none), axis=0)
        b_min = jnp.min(jnp.where(m, kb[:, None, None, None], none), axis=0)
        return (jnp.minimum(carry[0], a_min), jnp.minimum(carry[1], b_min)), None

    (anp_min, banp_min), _ = jax.lax.scan(
        body,
        init,
        (
            subj.reshape(g // c, c, a),
            peerq.reshape(g // c, c, b, q),
            anp_key.reshape(g // c, c),
            banp_key.reshape(g // c, c),
        ),
    )
    return anp_min, banp_min


def programs():
    import jax
    import jax.numpy as jnp

    from cyclonus_tpu.engine.kernel import resolve_tier_lattice, tier_first_match_keys

    def request(first, egress=True, ingress=True):
        def run(op):
            def body(t, counts):
                start = t * BLOCK
                out = []
                if egress:
                    subj = jax.lax.dynamic_slice(op["subj_e"], (0, start), (G, BLOCK))
                    has = jax.lax.dynamic_slice(op["has"], (start,), (BLOCK,))
                    a, b = first(subj, op["peerq_e"], op["keys"][0], op["keys"][1])
                    v = resolve_tier_lattice(~has[:, None, None], has[:, None, None], a, b)
                    out.append(jnp.sum(v, dtype=jnp.int32))
                if ingress:
                    peerq = jax.lax.dynamic_slice(
                        op["peerq_i"], (0, start, 0), (G, BLOCK, Q)
                    )
                    a, b = first(op["subj_i"], peerq, op["keys"][0], op["keys"][1])
                    has = op["has"][:, None, None]
                    v = resolve_tier_lattice(~has, has, a, b)  # [Nd, B, Q]
                    out.append(jnp.sum(v, dtype=jnp.int32))
                return counts.at[t].set(sum(out))

            return jax.lax.fori_loop(0, TILES, body, jnp.zeros((TILES,), jnp.int32))

        return jax.jit(run)

    return [
        ("request.scan", request(scan_first_match_keys)),
        ("request.words", request(tier_first_match_keys)),
        ("egress.scan", request(scan_first_match_keys, ingress=False)),
        ("egress.words", request(tier_first_match_keys, ingress=False)),
        ("ingress.scan", request(scan_first_match_keys, egress=False)),
        ("ingress.words", request(tier_first_match_keys, egress=False)),
    ]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=3141592653)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="tiny shapes, for the CPU: the mechanics and the agreement only",
    )
    args = ap.parse_args()
    if args.rehearse:
        global G, ANP_ROWS, BANP_ROWS, CLASSES, BLOCK, TILES
        G, ANP_ROWS, BANP_ROWS, CLASSES, BLOCK = 96, 70, 9, 256, 64
        TILES = CLASSES // BLOCK

    import jax
    import numpy as np

    op = operands(args.seed)
    out = {
        "device": str(jax.devices()[0].device_kind),
        "shapes": {"rows": G, "anp_rows": ANP_ROWS, "banp_rows": BANP_ROWS,
                   "classes": CLASSES, "block": BLOCK, "q": Q, "tiles": TILES},
        "reps": args.reps,
        "programs": {},
    }
    counts = {}
    for name, fn in programs():
        t0 = time.perf_counter()
        res = fn(op)
        res.block_until_ready()
        first = time.perf_counter() - t0
        fn(op).block_until_ready()
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            fn(op).block_until_ready()
            times.append((time.perf_counter() - t0) * 1e3)
        out["programs"][name] = {
            "request_ms": statistics.median(times),
            "request_ms_runs": times,
            "first_call_s": first,
        }
        counts[name] = int(np.asarray(res).sum())
        print(name, json.dumps(out["programs"][name]), flush=True)
    out["counts"] = counts
    out["counts_agree"] = all(
        counts[f"{part}.scan"] == counts[f"{part}.words"]
        for part in ("request", "egress", "ingress")
    )
    p = out["programs"]
    out["scan_over_words"] = p["request.scan"]["request_ms"] / p["request.words"]["request_ms"]
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "tier_first_match_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["counts_agree"] else 1


if __name__ == "__main__":
    sys.exit(main())
