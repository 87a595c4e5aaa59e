"""The precedence-tier first-match in its packed-word form.

`kernel.tier_first_match_keys` finds each tier's first matching rule as the
lowest set bit of the first non-zero packed word, and keys it by WORD.  Held
here:

  * against a plain row-by-row numpy oracle of first-match-by-priority (the
    min of rank * 4 + action over the matching rows of a tier): the same
    `key < TIER_KEY_NONE` and `key % 4`, and the same `resolve_tier_lattice`
    verdicts, over rule depths across and on word edges, the sign bit, a
    rule's peer rows, Pass above Deny, one tier alone, no match, all pads,
    and the odd sizes of the other axes;
  * the encoding's precondition the form rests on, for `synthetic.anp_tenancy`
    and for fuzzed tier sets: within a tier the ranks never fall, all ANP rows
    come before all BANP rows, and every pad row after both;
  * end to end, `synthetic.anp_tenancy` at a few hundred pods on
    `counts.classes` and `counts.xla` against `benchmarks/reference_tiers.py`.
"""

import importlib
import os
import random
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, planspec, tiled
from cyclonus_tpu.engine.encoding import (
    TIER_ACT_ALLOW,
    TIER_ACT_DENY,
    TIER_ACT_NONE,
    TIER_ACT_PASS,
    TIER_ANP,
    TIER_BANP,
    TIER_KEY_NONE,
)
from cyclonus_tpu.engine.kernel import resolve_tier_lattice, tier_first_match_keys
from cyclonus_tpu.kube.yaml_io import policy_to_dict
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.synthetic import ANP_TENANCY, anp_tenancy_objects, build_synthetic
from cyclonus_tpu.tiers.fuzz import build_fuzz_case
from cyclonus_tpu.tiers.model import TierSet, parse_tier_object

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NONE = TIER_KEY_NONE


def ordered_keys(rng, g, n_anp, n_banp, actions=None):
    """([G] anp, [G] banp) int32 keys laid as the encoding lays them: ANP
    rows, then BANP rows, ranks nondecreasing in each (about 1.5 peer rows a
    rule, one action a rule), then pad rows."""
    anp = np.full(g, NONE, np.int32)
    banp = np.full(g, NONE, np.int32)
    for lo, n, out in ((0, n_anp, anp), (n_anp, n_banp, banp)):
        rank = np.sort(rng.integers(0, max(1, (2 * n) // 3), n))
        act = rng.integers(1, 4, n + 1)[rank] if actions is None else actions[:n]
        out[lo:lo + n] = rank * 4 + act
    return anp, banp


def row_oracle(subj, peerq, anp, banp):
    """First-match-by-priority, a rule row at a time: each tier's min key
    over the rows whose subject and peer both match the cell."""
    shape = (subj.shape[1],) + peerq.shape[1:]
    out = [np.full(shape, NONE, np.int64), np.full(shape, NONE, np.int64)]
    for r in range(subj.shape[0]):
        hit = subj[r][:, None, None] & peerq[r][None, :, :]
        for key, keys in zip((anp, banp), out):
            if key[r] < NONE:
                keys[hit] = np.minimum(keys[hit], key[r])
    return out


def case(name, seed=0, g=64, a=3, b=4, q=2, n_anp=None, n_banp=None, p=0.3):
    """One parity case: (subj [G, A], peerq [G, B, Q], anp [G], banp [G])."""
    rng = np.random.default_rng(seed)
    n_anp = (2 * g) // 3 if n_anp is None else n_anp
    n_banp = (g - n_anp) // 2 if n_banp is None else n_banp
    anp, banp = ordered_keys(rng, g, n_anp, n_banp)
    subj = rng.random((g, a)) < p
    peerq = rng.random((g, b, q)) < p
    if name == "sign-bit":
        # every cell matches rows 31 (ANP) and 63 (BANP) and nothing else:
        # the lowest set bit of each word is bit 31, the int32 sign
        anp, banp = ordered_keys(rng, g, 40, 24)
        subj[:] = peerq[:] = False
        subj[[31, 63]] = peerq[[31, 63]] = True
    elif name == "peer-rows":
        # one rule's three peer rows (rank 0, Deny) and a later Allow rule:
        # only the rule's LAST peer row matches some cells
        anp = np.full(g, NONE, np.int32)
        anp[:4] = [0 * 4 + TIER_ACT_DENY] * 3 + [1 * 4 + TIER_ACT_ALLOW]
        banp = np.full(g, NONE, np.int32)
        subj[:] = True
        peerq[:] = False
        peerq[2, 0] = peerq[3, 0] = peerq[3, 1] = True
    elif name == "pass-over-deny":
        # an ANP Pass (rank 0) above a Deny (rank 1) matching the same cells,
        # and a BANP Allow below: the Pass falls through to the BANP
        anp = np.full(g, NONE, np.int32)
        anp[:2] = [TIER_ACT_PASS, 4 + TIER_ACT_DENY]
        banp = np.full(g, NONE, np.int32)
        banp[2:4] = [TIER_ACT_ALLOW, 4 + TIER_ACT_DENY]
        subj[:4] = True
        peerq[:4] = True
    elif name == "banp-only":
        anp, banp = ordered_keys(rng, g, 0, g - 5)
    elif name == "no-match":
        subj[:] = False
    elif name == "all-pad":
        anp, banp = ordered_keys(rng, g, 0, 0)
        subj[:] = peerq[:] = True
    return subj, peerq, anp, banp


CASES = {
    **{f"g{g}": dict(g=g, seed=g) for g in (1, 31, 32, 33, 64, 1536)},
    "sign-bit": dict(g=64),
    "peer-rows": dict(g=8),
    "pass-over-deny": dict(g=8),
    "banp-only": dict(g=40),
    "no-match": dict(g=40),
    "all-pad": dict(g=32),
    "q1": dict(g=70, q=1, seed=1),
    "q3": dict(g=70, q=3, seed=3),
    "a1": dict(g=45, a=1, seed=4),
    "b1": dict(g=45, b=1, seed=5),
}


@pytest.mark.parametrize("name", list(CASES))
def test_the_word_first_match_equals_the_row_oracle(name):
    subj, peerq, anp, banp = case(name, **CASES[name])
    want = row_oracle(subj, peerq, anp, banp)
    got = [
        np.asarray(k)
        for k in tier_first_match_keys(
            jnp.asarray(subj), jnp.asarray(peerq), jnp.asarray(anp), jnp.asarray(banp)
        )
    ]
    for g_key, w_key in zip(got, want):
        assert g_key.shape == w_key.shape and g_key.dtype == np.int32
        assert np.array_equal(g_key < NONE, w_key < NONE)
        assert np.array_equal(np.where(g_key < NONE, g_key % 4, -1),
                              np.where(w_key < NONE, w_key % 4, -1))
        assert np.all((g_key == NONE) | (g_key < NONE))
    rng = np.random.default_rng(17)
    np_allowed = rng.random(want[0].shape) < 0.5
    has = rng.random(want[0].shape[:1]) < 0.5
    lattice = [
        np.asarray(resolve_tier_lattice(
            jnp.asarray(np_allowed), jnp.asarray(has)[:, None, None],
            jnp.asarray(k[0]), jnp.asarray(k[1]),
        ))
        for k in (got, [w.astype(np.int32) for w in want])
    ]
    assert np.array_equal(*lattice)
    if name == "sign-bit":
        assert (got[0] % 4 == anp[31] % 4).all() and (got[1] % 4 == banp[63] % 4).all()
    if name == "peer-rows":
        assert (got[0][:, 0, :] % 4 == TIER_ACT_DENY).all()  # the rule, via its 3rd row
        assert (got[0][:, 1, :] % 4 == TIER_ACT_ALLOW).all()
    if name == "pass-over-deny":
        assert (got[0][:4] % 4 == TIER_ACT_PASS).all()
        assert (got[1][:4] % 4 == TIER_ACT_ALLOW).all()
    if name in ("no-match", "all-pad"):
        assert (got[0] == NONE).all() and (got[1] == NONE).all()
    if name == "banp-only":
        assert (got[0] == NONE).all() and (got[1] < NONE).any()


def _fuzzed_tier_sets():
    found = []
    for seed in range(40):
        fc = build_fuzz_case(seed)
        if fc.tiers and sum(fc.tiers.rule_count().values()) >= 4:
            found.append(fc)
        if len(found) == 3:
            return found
    raise AssertionError("the fuzz draws no tier set of four rules in 40 seeds")


def _anp_tenancy_engine(n_pods=96, n_ns=6, seed=3):
    pods, namespaces, policies = build_synthetic(
        n_pods, 16, random.Random(seed), n_ns=n_ns
    )
    anps, banp = anp_tenancy_objects(n_ns, dict(ANP_TENANCY, apps=2, tier_seed=seed))
    tiers = TierSet(
        anps=[parse_tier_object(d) for d in anps], banp=parse_tier_object(banp)
    )
    return TpuPolicyEngine(
        build_network_policies(True, policies), pods, namespaces, tiers=tiers
    )


@pytest.mark.parametrize("source", ["anp_tenancy", "fuzz0", "fuzz1", "fuzz2"])
def test_the_encodings_tier_rows_are_in_first_match_order(source):
    """TierDirectionEncoding's precondition, as encoded and as bucketed."""
    if source == "anp_tenancy":
        eng = _anp_tenancy_engine()
    else:
        fc = _fuzzed_tier_sets()[int(source[-1])]
        eng = TpuPolicyEngine(
            build_network_policies(fc.simplify, fc.netpols), fc.pods,
            fc.namespaces, tiers=fc.tiers,
        )
    slabs = list(eng.encoding.tiers) + [
        {k: np.asarray(v) for k, v in eng._tensors["tiers"][d].items() if k != "port_spec"}
        for d in ("ingress", "egress")
    ]
    assert len(slabs) == 4
    for slab in slabs:
        get = slab.get if isinstance(slab, dict) else lambda k, s=slab: getattr(s, k)
        action = np.asarray(get("action")).astype(int)
        tier = np.asarray(get("tier")).astype(int)
        rank = np.asarray(get("rank")).astype(int)
        real = action != TIER_ACT_NONE
        order = np.where(real, np.where(tier == TIER_ANP, 0, 1), 2)
        assert set(tier[real]) <= {TIER_ANP, TIER_BANP}
        # ANP rows, then BANP rows, then pads
        assert np.all(np.diff(order) >= 0)
        for t in (TIER_ANP, TIER_BANP):
            ranks = rank[real & (tier == t)]
            assert np.all(np.diff(ranks) >= 0)
            # equal ranks (a rule's peer rows) share one action
            acts = action[real & (tier == t)]
            for r in np.unique(ranks):
                assert len(set(acts[ranks == r])) == 1
    assert real.any()


# benchmarks/traffic/port-sweep-tiers.json's first and third pairs
PAIRS = [
    [PortCase(80, "serve-80-tcp", "TCP"), PortCase(81, "serve-81-udp", "UDP")],
    [PortCase(80, "serve-80-sctp", "SCTP"), PortCase(81, "serve-81-tcp", "TCP")],
]


@pytest.mark.parametrize("route", ["counts.classes", "counts.xla"])
def test_anp_tenancy_counts_at_a_few_hundred_pods(route, monkeypatch):
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    reference_tiers = importlib.import_module("benchmarks.reference_tiers")
    n_pods, n_ns, seed = 320, 8, 11
    pods, namespaces, policies = build_synthetic(
        n_pods, 24, random.Random(seed), n_ns=n_ns
    )
    anps, banp = anp_tenancy_objects(n_ns, dict(ANP_TENANCY, apps=3, tier_seed=seed))
    tiers = TierSet(
        anps=[parse_tier_object(d) for d in anps], banp=parse_tier_object(banp)
    )
    eng = TpuPolicyEngine(
        build_network_policies(True, policies), pods, namespaces,
        class_compress="1" if route == "counts.classes" else "0", tiers=tiers,
    )
    assert tiled.tier_attrs(eng._tensors)["tier_words"] > 2
    reference = reference_tiers.TieredGridReference(
        pods, namespaces, [policy_to_dict(p) for p in policies], anps, banp
    )
    monkeypatch.setattr(planspec, "ACTIVE", True)
    for pair in PAIRS:
        planspec.drain()
        got = eng.evaluate_grid_counts(pair)
        assert planspec.drain() == [route]
        assert got == reference.counts([(c.port, c.port_name, c.protocol) for c in pair])
        assert 0 < got["combined"] < got["cells"]


def test_the_first_match_is_one_min_reduction_a_tier_and_no_loop():
    """The fused form (kernel.TIER_FIRST_MATCH): ONE reduce_min a tier over
    the packed words and no scan, while or cond: a loop over rule rows would
    carry the two [A, B, Q] keys through HBM again (the 8-row scan before
    this form did so 192 times a direction of a class tile)."""
    import jax

    from cyclonus_tpu.engine.kernel import TIER_FIRST_MATCH

    assert TIER_FIRST_MATCH == "first=words"
    jaxpr = jax.make_jaxpr(tier_first_match_keys)(
        jax.ShapeDtypeStruct((1536, 64), jnp.bool_),
        jax.ShapeDtypeStruct((1536, 48, 2), jnp.bool_),
        jax.ShapeDtypeStruct((1536,), jnp.int32),
        jax.ShapeDtypeStruct((1536,), jnp.int32),
    )

    def primitives(j):
        for eqn in j.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from primitives(sub)

    names = list(primitives(jaxpr.jaxpr))
    assert not {"scan", "while", "cond"} & set(names), names
    assert names.count("reduce_min") == 2, names
