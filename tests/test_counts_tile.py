"""The XLA counts tile body in its own layout (docs/DESIGN.md "Bit-packed
kernel", the counts tile): `tiled._tile_counts_split` / `_tile_counts`
compute ingress, egress and combined a port case at a time, each a
[block, pods] slab with the pods on the minor axis, from a precompute
whose egress tallow is laid [W, Q, N] (tile=TILE_BQN), and must count
exactly what the tables body `_tile_verdicts_split` counts, masked as
it was, on the [block, pods, Q] layout.

ONE parametrised test, each case its own count: seeded random packed and
unpacked views at Q = 1, 2, 3 with target counts that are not a multiple
of 32, pad rows on both sides (src_valid / dst_valid false), rows with
no target (has_target false) and a dst view of another length than the
src (a ring step); then the precompute of a fuzzed engine on both dtype
plans, and a TIERED one, which must keep the parent's layout and body
(counts_tile_form)."""

import jax.numpy as jnp
import numpy as np
import pytest

from cyclonus_tpu.engine import TpuPolicyEngine
from cyclonus_tpu.engine import tiled
from cyclonus_tpu.engine.encoding import pack_bool_words
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.tiers.fuzz import build_fuzz_case

from test_engine_tiled import CASES, fuzz_problem

T_E, T_I = 45, 70  # target rows: neither a multiple of 32
N_SRC, N_DST, BLOCK = 48, 40, 16

PARAMS = (
    [("random", pack, q) for pack in (False, True) for q in (1, 2, 3)]
    + [("engine", pack, 2) for pack in (False, True)]
    + [("tiers", pack, 3) for pack in (False, True)]
)


def _masked_sums(verdicts, sv, dv, pods_axis):
    """The three int counts of (ingress, egress, combined) under the
    validity mask, on either layout; numpy, so no code of the body."""
    shape = [1, 1, 1]
    shape[pods_axis] = dv.size
    mask = sv[:, None, None] & dv.reshape(shape)
    return [int((np.asarray(v) & mask).sum()) for v in verdicts]


def _random_views(pack, q, seed):
    """(src, dst as the tables body reads it, dst as the counts body
    does, src_valid, dst_valid): the same random bits in both layouts."""
    rng = np.random.default_rng(seed)
    tmatch_e = rng.random((T_E, N_SRC)) < 0.08
    tallow_i = rng.random((T_I, N_SRC, q)) < 0.05
    tallow_e = rng.random((T_E, N_DST, q)) < 0.06
    tmatch_i = rng.random((T_I, N_DST)) < 0.07
    has_e = rng.random(N_SRC) < 0.7
    has_i = rng.random(N_DST) < 0.6
    src_valid = np.arange(N_SRC) < N_SRC - 5
    dst_valid = np.arange(N_DST) < N_DST - 7
    if pack:
        src = {
            "tmatch_e": pack_bool_words(tmatch_e),
            "tallow_i": pack_bool_words(tallow_i),
        }
        bnq = pack_bool_words(tallow_e)
        bqn = pack_bool_words(np.swapaxes(tallow_e, 1, 2))
        match_i = pack_bool_words(tmatch_i)
    else:
        src = {
            "tmatch_e": tmatch_e,
            "tallow_i": jnp.asarray(tallow_i, dtype=jnp.bfloat16),
        }
        bnq = jnp.asarray(tallow_e, dtype=jnp.bfloat16)
        bqn = jnp.asarray(np.swapaxes(tallow_e, 1, 2), dtype=jnp.bfloat16)
        match_i = tmatch_i
    src["has_e"] = has_e
    src = {k: jnp.asarray(v) for k, v in src.items()}
    dst = {"tmatch_i": jnp.asarray(match_i), "has_i": jnp.asarray(has_i)}
    return (
        src,
        dict(dst, tallow_e=jnp.asarray(bnq)),
        dict(dst, tallow_e=jnp.asarray(bqn)),
        src_valid,
        dst_valid,
    )


def _engine_views(kind, pack, monkeypatch):
    """(pre as the tables body reads it, pre as the counts body does,
    valid) of a fuzzed engine's tensor set, padded to whole tiles."""
    monkeypatch.setenv("CYCLONUS_PACK", "1" if pack else "0")
    if kind == "tiers":
        fc = next(
            c for c in map(build_fuzz_case, range(64)) if c.tiers is not None
        )
        policy = build_network_policies(fc.simplify, fc.netpols)
        engine = TpuPolicyEngine(
            policy, fc.pods, fc.namespaces, tiers=fc.tiers
        )
        cases = fc.cases
    else:
        policy, pods, namespaces = fuzz_problem(5, n_extra_pods=21)
        engine = TpuPolicyEngine(policy, pods, namespaces)
        cases = CASES
    n = engine.encoding.cluster.n_pods
    tensors, _ = tiled._pad_pod_axis(
        engine._tensors_with_cases(cases), n, BLOCK
    )
    valid = np.arange(tensors["pod_ns_id"].shape[0]) < n
    bnq = tiled._precompute(tensors, pack)
    bqn = tiled._precompute(tensors, pack, tiled.TILE_BQN)
    assert tiled.counts_tile_form(tensors) == (
        tiled.TILE_BNQ if kind == "tiers" else tiled.TILE_BQN
    )
    return bnq, bqn, valid


@pytest.mark.parametrize(
    "kind,pack,q", PARAMS, ids=[f"{k}-pack{int(p)}-q{q}" for k, p, q in PARAMS]
)
def test_the_counts_tile_counts_what_the_tables_body_counts(
    kind, pack, q, monkeypatch
):
    if kind == "random":
        src, dst_bnq, dst_bqn, sv, dv = _random_views(pack, q, seed=17 * q)
        starts = range(0, N_SRC, BLOCK)
        for s in starts:
            # the same verdicts: a [B, Nd] slab a case against [B, Nd, Q]
            cases = tiled._tile_verdicts_bqn(src, dst_bqn, s, BLOCK)
            old = tiled._tile_verdicts_split(src, dst_bnq, s, BLOCK)
            assert len(cases) == q
            for j, want in enumerate(old):
                got = np.stack([np.asarray(c[j]) for c in cases], axis=-1)
                assert np.array_equal(got, want)
            assert 0 < int(np.asarray(old[2]).sum()) < old[2].size
        # a precompute laid for the tables body is refused, not misread
        with pytest.raises(ValueError, match="tile='bqn'"):
            tiled._tile_counts_split(src, dst_bnq, sv, dv, 0, BLOCK)
    else:
        pre_bnq, pre_bqn, sv = _engine_views(kind, pack, monkeypatch)
        src, dst_bnq = tiled._split_pre(pre_bnq)
        _, dst_bqn = tiled._split_pre(pre_bqn)
        dv = sv
        w_e, n, q_ = dst_bnq["tallow_e"].shape
        if kind == "tiers":
            # the tier epilogue is written for [B, Nd, Q]: nothing moves
            assert dst_bqn["tallow_e"].shape == (w_e, n, q_)
            assert "tier_subj_e" in src
        else:
            assert dst_bqn["tallow_e"].shape == (w_e, q_, n)
        starts = range(0, n, BLOCK)
    for s in starts:
        old = _masked_sums(
            tiled._tile_verdicts_split(src, dst_bnq, s, BLOCK),
            sv[s:s + BLOCK], dv, pods_axis=1,
        )
        got = tiled._tile_counts_split(
            src, dst_bqn, jnp.asarray(sv), jnp.asarray(dv), s, BLOCK
        )
        assert np.asarray(got).tolist() == old
        if kind != "random":
            # the single-array-set form reads the same precompute
            same = tiled._tile_counts(
                pre_bqn, jnp.asarray(sv), s, BLOCK
            )
            assert np.asarray(same).tolist() == old
