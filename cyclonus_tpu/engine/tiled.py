"""Tiled/streaming verdict evaluation for grids too large to materialize.

The single-device kernel (kernel.py) holds three [Q, N, N] bool tables plus
an [N, N*Q] matmul intermediate in HBM at once — at 100k pods that is tens
of GB, far past a single chip.  This module evaluates the grid in
fixed-size SOURCE-ROW BLOCKS instead, in three modes:

  * counts  — the whole block loop runs DEVICE-SIDE inside one jit
              (lax.fori_loop), producing per-tile allow counts; one
              dispatch + one small readback total — a Python-loop
              design would pay a host<->device round trip per tile
              (its cost is not measured on the current machine).
  * blocks  — a Python generator yielding [B, N, Q] verdict blocks for
              streaming consumers (writers, row aggregations); one
              dispatch per tile, transfers dominated by the block fetch.
  * pairs   — point evaluation of arbitrary (src, dst) index pairs
              (evaluate_pairs_kernel); no N x N grid anywhere, so it
              scales to any cluster size — powers the large-scale parity
              spot checks (analysis/oracle.py spot_check_pairs).

Decision procedure identical to kernel.py (reference policy.go:138-174);
parity is enforced by tests/test_engine_tiled.py against both the
single-device kernel and the scalar oracle.

Memory note: the target-allows tensors are precomputed once per direction
and stored as bf16 (ready for the MXU).  Matmul outputs use bf16
accumulation: inputs are 0/1, so every partial sum is a sum of nonnegative
values >= 1 at the first hit — rounding can never drive a positive count
to zero, so the `> 0` threshold stays exact.

Threading note (lock discipline, docs/DESIGN.md): everything here is
pure functions of explicit operands — no module-level mutable state, no
locks — by design.  All caching of these programs' operands (the pinned
precompute, its resident case-independent half, the gathered slab
operands) lives in api.TpuPolicyEngine,
where it is guarded by _slab_lock and checked by tools/locklint.py;
keep it that way rather than adding module-level caches here (a second
cache layer would need its own lock AND a consistent order against
_slab_lock to stay off the LK002 cycle graph).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Iterator, Tuple

from . import first_import

first_import()  # ahead of `import jax`: this may be the process's first
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..telemetry import instruments as ti
from ..utils.tracing import detail, phase
from .encoding import PACK_BITS, TIER_KEY_NONE, pack_enabled, packed_words
from .kernel import (
    band_peer_match,
    direction_precompute,
    m_tp_onehot,
    pack_bool_words_jnp,
    packed_any,
    port_spec_allows,
    resolve_tier_lattice,
    selector_match,
    tallow_attrs,
    target_allows_q,
    tier_direction_arrays,
    tier_first_match_keys,
    tier_rows,
    tier_words,
)


def _apply_host_ip(enc: Dict, pre: Dict) -> Dict:
    if "host_ip_match" in enc:
        pre = dict(pre)
        pre["peer_match"] = jnp.where(
            enc["host_ip_mask"][:, None], enc["host_ip_match"], pre["peer_match"]
        )
    return pre


def _precompute_static(
    tensors: Dict, pack: bool = False, selpod: jnp.ndarray = None
) -> Dict:
    """The half of _precompute that the port cases do not touch: a
    function of the cluster and the policy set alone, so a holder
    (api.TpuPolicyEngine's dense counts route) computes it once per
    engine state and keeps it on the device.  Per direction:

      peer_match [P, N] bool — peer row p matches pod n (host-IP rows
                               applied); where the direction carries
                               band_rows [tiles, PSPAN], its rows as
                               they lie there, [tiles, PSPAN, N]
                               (kernel.band_peer_match)
      tmatch     [T, N] bool, has_target [N] bool, and with pack=True
      tmatch_pk  [W, N] int32

    and the leaves _precompute_cases still reads from the encoding:
    peer_target [P], target_ns [T] (for its length), port_spec and
    band_rows where it rides.  A
    tiered tensor set carries its tier encodings and the two selector
    matches under "tiers": tier_direction_arrays takes the cases, so
    it stays whole in the second half.  `selpod`, where the caller holds
    it (the class counts route past its device-pass ceiling:
    api._CLASS_SELPOD_DEVICE_MAX_BYTES), is kernel.selector_match over
    the pods as given, and the pass is not run again here."""
    if selpod is None:
        selpod = selector_match(
            tensors["sel_req_kv"],
            tensors["sel_exp_op"],
            tensors["sel_exp_key"],
            tensors["sel_exp_vals"],
            tensors["pod_kv"],
            tensors["pod_key"],
        )
    selns = selector_match(
        tensors["sel_req_kv"],
        tensors["sel_exp_op"],
        tensors["sel_exp_key"],
        tensors["sel_exp_vals"],
        tensors["ns_kv"],
        tensors["ns_key"],
    )
    out = {}
    for direction in ("ingress", "egress"):
        enc = tensors[direction]
        pre = direction_precompute(
            enc,
            selpod,
            selns,
            tensors["pod_ns_id"],
            tensors["pod_ip"],
            tensors["pod_ip_valid"],
        )
        pre = _apply_host_ip(enc, pre)
        out[direction] = {
            "peer_match": pre["peer_match"],
            "tmatch": pre["tmatch"],
            "has_target": pre["has_target"],
            "peer_target": enc["peer_target"],
            "target_ns": enc["target_ns"],
            "port_spec": enc["port_spec"],
        }
        if "band_rows" in enc:
            out[direction]["band_rows"] = enc["band_rows"]
            out[direction]["peer_match"] = band_peer_match(
                enc["band_rows"], pre["peer_match"]
            )  # shape: (tiles, PSPAN, N) bool
        if pack:
            out[direction]["tmatch_pk"] = pack_bool_words_jnp(
                pre["tmatch"]
            )  # shape: (W, N) int32
    if "tiers" in tensors:
        out["tiers"] = {
            "enc": tensors["tiers"],
            "selpod": selpod,
            "selns": selns,
            "pod_ns_id": tensors["pod_ns_id"],
        }
    return out


#: the forms of a tile's verdicts: [B, Nd, Q], what every tables path
#: reads (and the tier epilogue is written for), or the counts tile
#: body's [B, Q, Nd], a [B, Nd] slab a port case with the pods on the
#: minor (lane) axis
TILE_BNQ = "bnq"
TILE_BQN = "bqn"
#: the class tile's MXU form (class_tile_form): the target axis unpacked,
#: each direction's tallow [Q, T, N] as target_allows_q leaves it, a
#: [T, N] slab a port case, in MXU_DTYPE
TILE_QTN = "qtn"

#: the MXU form's operand dtype: 0/1 products summed in int32 are exact,
#: and an int8 operand holds half the bytes of a bf16 one
MXU_DTYPE = jnp.int8


def counts_tile_form(tensors: Dict) -> str:
    """The layout the XLA counts tile body runs in over `tensors` (a
    tensor set or _precompute_static's result): TILE_BQN, except where
    tier arrays ride, whose epilogue keeps the parent's TILE_BNQ body.
    The one rule _precompute_cases and _tile_counts_split follow."""
    return TILE_BNQ if "tiers" in tensors else TILE_BQN


#: the precedence-tier epilogue an evaluation runs: the min-key loop inside
#: the fused packed Pallas kernel, or the XLA min-reduction over packed
#: rule words (kernel.tier_first_match_keys) that every other route runs
TIER_FUSED = "fused"
TIER_XLA = "xla"


def tier_attrs(tensors: Dict, kernel: str = TIER_XLA) -> Dict:
    """What an evaluation over the tensor set `tensors` says of its tiers in
    its flight entry and on its `engine.eval` span: tiered=1, tier_rows
    (the bucketed rule rows, ingress + egress) and tier_kernel, and on
    the XLA epilogue tier_words (the packed rule words its first-match
    reduces over, ingress + egress); nothing on an untiered set."""
    if "tiers" not in tensors:
        return {}
    out = {"tiered": 1, "tier_rows": tier_rows(tensors), "tier_kernel": kernel}
    if kernel == TIER_XLA:
        out["tier_words"] = tier_words(tensors)
    return out


def target_words(tensors: Dict) -> int:
    """The packed target words of a tensor set, ingress + egress: the depth
    of every packed contraction over it (encoding.packed_words of each
    direction's bucketed target axis)."""
    return sum(
        packed_words(int(tensors[d]["target_ns"].shape[0]))
        for d in ("ingress", "egress")
    )


def _precompute_cases(
    static: Dict,
    q_port: jnp.ndarray,
    q_name: jnp.ndarray,
    q_proto: jnp.ndarray,
    pack: bool = False,
    tile: str = TILE_BNQ,
) -> Dict[str, Dict[str, jnp.ndarray]]:
    """The half of _precompute that starts where the port cases enter:
    from _precompute_static's result and the three [Q] case arrays, the
    `pre` dict of _precompute, leaf for leaf.  `tile` TILE_BQN lays the
    EGRESS tallow [T, Q, N] (W for T packed) for the counts tile body
    (_tile_counts_split), the same bytes in another order; a tiered
    static keeps [T, N, Q] (counts_tile_form).  The contraction is
    kernel.target_allows_q, [Q, T, N], the port cases on the one-hot's
    rows against the resident peer_match (where the static carries
    band_rows, each tile of kernel.TARGET_BAND targets against its own
    banded rows): no [P, N, Q] operand exists,
    and T is packed where it lies (the one-hot padded to whole words)
    before the small words take their order.  `tile` TILE_QTN (unpacked
    alone) keeps each direction's tallow [Q, T, N] in MXU_DTYPE, cast in
    the compare that ends the contraction, for the class tile's MXU form,
    with T in whole words as packing pads it and tmatch padded to match
    (an odd T took the TPU compiler 383 s over the envelope's one-hot,
    whole words 2 s, compiled for a v5e: PERF.md section 6)."""
    out = {}
    qn = tile == TILE_BQN and counts_tile_form(static) == TILE_BQN
    for direction in ("ingress", "egress"):
        st = static[direction]
        pport = port_spec_allows(st["port_spec"], q_port, q_name, q_proto)
        t = st["target_ns"].shape[0]
        rows = packed_words(t) * PACK_BITS if pack or tile == TILE_QTN else t
        tallow = target_allows_q(
            st, st["peer_match"], pport, rows
        )  # shape: (Q, T, N) | (Q, 32W, N)
        q_axis = 1 if qn and direction == "egress" else 2
        out[direction] = {
            "tmatch": st["tmatch"],
            "has_target": st["has_target"],
        }
        if pack:
            words = pack_bool_words_jnp(tallow, axis=1)  # shape: (Q, W, N)
            out[direction]["tallow_pk"] = jnp.moveaxis(
                words, 0, q_axis
            )  # shape: (W, N, Q) | (W, Q, N) int32
            out[direction]["tmatch_pk"] = st["tmatch_pk"]
        elif tile == TILE_QTN:
            out[direction]["tallow_q"] = tallow.astype(MXU_DTYPE)  # shape: (Q, 32W, N)
            out[direction]["tmatch"] = jnp.pad(
                st["tmatch"], ((0, rows - t), (0, 0))
            )  # shape: (32W, N) bool
        else:
            out[direction]["tallow_bf"] = jnp.moveaxis(
                tallow.astype(jnp.bfloat16), 0, q_axis
            )  # shape: (T, N, Q) | (T, Q, N)
        if "tiers" in static:
            # precedence-tier precompute (docs/DESIGN.md "Precedence
            # tiers"): subj/peerq/keys ride next to tallow so every tile
            # body can run the first-match resolution epilogue
            tiers = static["tiers"]
            out[direction]["tier"] = tier_direction_arrays(
                tiers["enc"][direction],
                tiers["selpod"],
                tiers["selns"],
                tiers["pod_ns_id"],
                q_port,
                q_name,
                q_proto,
            )
    return out


def _precompute(
    tensors: Dict, pack: bool = False, tile: str = TILE_BNQ,
    selpod: jnp.ndarray = None,
) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Per-direction, port-resolved precompute shared by every tile:

      tallow_bf [T, N, Q] bf16 — target t allows traffic with pod n on the
                                 PEER side for port case q
                                 (kernel.target_allows_q)
      tmatch    [T, N] bool    — target t applies to pod n (target side)
      has_target[N] bool

    With pack=True (static; docs/DESIGN.md "Bit-packed kernel") the
    target-axis operands ship 32-per-word instead: tallow_pk [W, N, Q]
    int32 and tmatch_pk [W, N] int32 REPLACE tallow_bf (W =
    encoding.packed_words(T)) — 16x fewer peer-bundle bytes on the ring
    and a 32x shallower contraction in every tile body.  The bool
    tmatch/has_target stay (they are small and the count masks and slab
    plan read them).

    The composition of its two halves, cut where q_port first appears:
    every caller but the dense counts route's resident request traces
    both in one program, as before the cut.  `tile`: _precompute_cases;
    `selpod`: _precompute_static."""
    return _precompute_cases(
        _precompute_static(tensors, pack, selpod),
        tensors["q_port"],
        tensors["q_name"],
        tensors["q_proto"],
        pack,
        tile,
    )


#: the dst-side bundle keys — the arrays the ring paths rotate with
#: ppermute.  Tier arrays indexed by the DST pod axis (egress peer side,
#: ingress target side) must ride the bundle or a rotated step would
#: resolve tiers against the wrong shard.
_DST_VIEW_KEYS = ("tallow_e", "tmatch_i", "has_i")
_DST_TIER_KEYS = ("tier_peerq_e", "tier_subj_i")


def _dst_bundle_keys(ring: Dict) -> Tuple[str, ...]:
    keys = _DST_VIEW_KEYS
    if "tier_peerq_e" in ring:
        keys = keys + _DST_TIER_KEYS
    return keys


def _ring_sweep(n_dev: int, ring: Dict, init, body):
    """THE double-buffered ring loop both 1-D ring paths share — the
    ring counts (_ring_counts) and the sharded grid ring
    (sharded._ring_grid_eval) — so the schedule can never diverge
    between them.  One ppermute hop per step, ISSUED BEFORE the step's
    compute: the transfer and the compute both only read the current
    bundle, so the hop flies on ICI while the MXU contracts (one
    resident bundle + one in-flight).  `body(step, ring, acc) -> acc`
    consumes the bundle currently held.  All n_dev hops run — the final
    rotation returns every bundle to its origin; it is kept rather than
    guarded out because collectives under lax.cond don't lower
    reliably and it is one ICI transfer.  Returns (acc, ring-at-origin)."""
    perm = [(d, (d + 1) % n_dev) for d in range(n_dev)]

    def ring_step(step, carry):
        acc, ring = carry
        nxt = jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, "x", perm), ring
        )
        acc = body(step, ring, acc)
        return acc, nxt

    return jax.lax.fori_loop(0, n_dev, ring_step, (init, ring))


def _split_pre(pre: Dict) -> Tuple[Dict, Dict]:
    """Split the per-direction precompute into the SRC-side view (the
    tile's source rows: egress target side + ingress peer side) and the
    DST-side view (egress peer side + ingress target side).  On a single
    device both views slice the same arrays; in the ring path the dst
    view is the rotating remote shard.  Tier arrays split the same way:
    subjects sit on the direction's target side, peerq on its peer side;
    the [G] key vectors are pod-independent and stay in the src view.

    The canonical view KEYS are representation-independent: with the
    packed precompute (tallow_pk/tmatch_pk present) the same names carry
    the int32 packed words — the bundle specs and ring schedules are
    shape-pattern-identical, and _tile_verdicts_split picks the
    contraction by dtype.  The packed bundle is what rides the ppermute
    ring: ~16x fewer peer bytes per hop than the bf16 tallow.  The MXU
    form's (tallow_q, TILE_QTN) dst tmatch_i is cast to MXU_DTYPE here,
    once a request, outside any tile loop; the src tmatch_e stays bool,
    since a tile converts only its own slice."""
    if "tallow_q" in pre["egress"]:
        src = {
            "tmatch_e": pre["egress"]["tmatch"],
            "has_e": pre["egress"]["has_target"],
            "tallow_i": pre["ingress"]["tallow_q"],
        }
        dst = {
            "tallow_e": pre["egress"]["tallow_q"],
            "tmatch_i": pre["ingress"]["tmatch"].astype(MXU_DTYPE),
            "has_i": pre["ingress"]["has_target"],
        }
    elif "tallow_pk" in pre["egress"]:
        src = {
            "tmatch_e": pre["egress"]["tmatch_pk"],
            "has_e": pre["egress"]["has_target"],
            "tallow_i": pre["ingress"]["tallow_pk"],
        }
        dst = {
            "tallow_e": pre["egress"]["tallow_pk"],
            "tmatch_i": pre["ingress"]["tmatch_pk"],
            "has_i": pre["ingress"]["has_target"],
        }
    else:
        src = {
            "tmatch_e": pre["egress"]["tmatch"],
            "has_e": pre["egress"]["has_target"],
            "tallow_i": pre["ingress"]["tallow_bf"],
        }
        dst = {
            "tallow_e": pre["egress"]["tallow_bf"],
            "tmatch_i": pre["ingress"]["tmatch"],
            "has_i": pre["ingress"]["has_target"],
        }
    if "tier" in pre["egress"]:
        te, ti_ = pre["egress"]["tier"], pre["ingress"]["tier"]
        src["tier_subj_e"] = te["subj"]
        src["tier_peerq_i"] = ti_["peerq"]
        src["tier_keys_e"] = jnp.stack([te["anp_key"], te["banp_key"]])
        src["tier_keys_i"] = jnp.stack([ti_["anp_key"], ti_["banp_key"]])
        dst["tier_peerq_e"] = te["peerq"]
        dst["tier_subj_i"] = ti_["subj"]
    return src, dst


def _any_bf16(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """[A, B] bool: OR_t (a[t, a] AND b[t, b]) over 0/1 [T, A] and
    [T, B] — packed_any's unpacked twin, on the MXU: a bf16 sum of 0/1
    products is positive at its first hit and never rounds to zero."""
    return (
        jnp.matmul(
            a.T.astype(jnp.bfloat16),
            b.astype(jnp.bfloat16),
            preferred_element_type=jnp.bfloat16,
        )
        > 0
    )


def _any_mxu(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """[A, B] bool: OR_t (a[t, a] AND b[t, b]) over 0/1 [T, A] and [T, B],
    b in MXU_DTYPE and `a` in it or bool: one int8 dot over T as both
    lie, with no transpose of either, summed exactly in int32, then
    `> 0`."""
    return (
        jax.lax.dot_general(
            a.astype(MXU_DTYPE), b, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        > 0
    )


def _tile_verdicts_mxu(src: Dict, dst: Dict, start, block: int) -> list:
    """The class tile's verdicts in the MXU form (TILE_QTN views,
    _split_pre): for each port case q, (ingress, egress, combined) [B, Nd]
    bool of the tile's source rows against the dst pods.  Both directions
    contract the unpacked target axis on the MXU (_any_mxu): egress the
    tile's tmatch_e slice against the dst tallow_e[q], ingress the tile's
    tallow_i[q] slice against the dst tmatch_i.  A [T, N] slab is a major
    slice of [Q, T, N], so no operand is copied inside the loop, and both
    outputs put the dst pods on the lanes, so nothing is swapped."""
    q, t_i, _ = src["tallow_i"].shape
    t_e = src["tmatch_e"].shape[0]
    tme = jax.lax.dynamic_slice(src["tmatch_e"], (0, start), (t_e, block))
    no_e = ~jax.lax.dynamic_slice(src["has_e"], (start,), (block,))[:, None]
    no_i = ~dst["has_i"][None, :]
    out = []
    for k in range(q):
        # one slice of the 3-D operand: a [k] first would lay both [T, N]
        # slabs into HBM again every tile
        tli = jax.lax.dynamic_slice(
            src["tallow_i"], (k, 0, start), (1, t_i, block)
        )[0]
        egress = no_e | _any_mxu(tme, dst["tallow_e"][k])  # [B, Nd]
        ingress = no_i | _any_mxu(tli, dst["tmatch_i"])  # [B, Nd]
        out.append((ingress, egress, egress & ingress))
    return out


def _tile_verdicts_split(
    src: Dict, dst: Dict, start: jnp.ndarray, block: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Verdict blocks for source rows [start, start+block) of the src
    view against ALL dst-view pods: (ingress_rows, egress, combined),
    each [B, Nd, Q] bool; ingress_rows[b, d, q] = ingress verdict for
    dst d <- src (start+b).  THE per-tile verdict body of every path
    that hands verdicts on (tables, class row sums, blocks), and of a
    tiered counts tile; the counts tile has its own layout
    (_tile_verdicts_bqn).  The contraction is picked by the view
    REPRESENTATION (_split_pre): int32 views are 32-per-word packed
    bitmaps contracted with packed_any; bool/bf16 views keep the bf16
    matmul.  Both forms are exact on 0/1 values, pinned bit-identical
    by the packed parity suite."""
    t_e, nd, q = dst["tallow_e"].shape
    t_i = dst["tmatch_i"].shape[0]
    packed = src["tmatch_e"].dtype == jnp.int32

    # egress: the source block is the TARGET side; peer side = dst pods
    tme = jax.lax.dynamic_slice(src["tmatch_e"], (0, start), (t_e, block))
    hte = jax.lax.dynamic_slice(src["has_e"], (start,), (block,))  # [B]
    if packed:
        any_e = packed_any(tme, dst["tallow_e"].reshape(t_e, nd * q))
    else:
        any_e = (
            jnp.matmul(
                tme.T.astype(jnp.bfloat16),
                dst["tallow_e"].reshape(t_e, nd * q),
                preferred_element_type=jnp.bfloat16,
            )
            > 0
        )
    any_e = any_e.reshape(block, nd, q)
    egress = (~hte[:, None, None]) | any_e  # [B, Nd, Q]

    # ingress: the source block is the PEER side; target side = dst pods
    tli = jax.lax.dynamic_slice(
        src["tallow_i"], (0, start, 0), (t_i, block, q)
    )  # [T, B, Q]
    if packed:
        any_i = packed_any(dst["tmatch_i"], tli.reshape(t_i, block * q))
    else:
        any_i = (
            jnp.matmul(
                dst["tmatch_i"].T.astype(jnp.bfloat16),
                tli.reshape(t_i, block * q),
                preferred_element_type=jnp.bfloat16,
            )
            > 0
        )
    any_i = any_i.reshape(nd, block, q)
    ingress_t = (~dst["has_i"][:, None, None]) | any_i  # [Nd, B, Q]

    if "tier_subj_e" in src:
        # precedence-tier resolution epilogue, per tile (docs/DESIGN.md
        # "Precedence tiers"): egress subjects are the source block,
        # ingress subjects the dst view — same first-match fold as the
        # full-grid kernel, over this tile's slices
        g_e = src["tier_subj_e"].shape[0]
        subj_e = jax.lax.dynamic_slice(
            src["tier_subj_e"], (0, start), (g_e, block)
        )  # [G, B]
        anp_e, banp_e = tier_first_match_keys(
            subj_e, dst["tier_peerq_e"], src["tier_keys_e"][0],
            src["tier_keys_e"][1],
        )  # [B, Nd, Q]
        egress = resolve_tier_lattice(
            egress, hte[:, None, None], anp_e, banp_e
        )
        g_i = src["tier_peerq_i"].shape[0]
        peerq_i = jax.lax.dynamic_slice(
            src["tier_peerq_i"], (0, start, 0), (g_i, block, q)
        )  # [G, B, Q]
        anp_i, banp_i = tier_first_match_keys(
            dst["tier_subj_i"], peerq_i, src["tier_keys_i"][0],
            src["tier_keys_i"][1],
        )  # [Nd, B, Q]
        ingress_t = resolve_tier_lattice(
            ingress_t, dst["has_i"][:, None, None], anp_i, banp_i
        )

    ingress_rows = jnp.swapaxes(ingress_t, 0, 1)  # [B, Nd, Q]
    combined = egress & ingress_rows
    return ingress_rows, egress, combined


def _tile_verdicts(
    pre: Dict, start: jnp.ndarray, block: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-array-set form of _tile_verdicts_split (src == dst)."""
    src, dst = _split_pre(pre)
    return _tile_verdicts_split(src, dst, start, block)


def _pad_pod_axis(tensors: Dict, n_pods: int, block: int) -> Tuple[Dict, int]:
    """Pad the pod axis to a multiple of `block` with inert rows (same
    scheme as sharded._pad_pod_arrays; padded rows match no target and no
    peer, so their verdicts are all-allow rows that get masked/stripped)."""
    from .sharded import _pad_pod_arrays

    # n_tiles comes from the FINAL padded length: the arrays may arrive
    # longer than n_pods from build-time shape bucketing
    tensors, padded = _pad_pod_arrays(tensors, n_pods, block)
    return tensors, padded // block


def _tile_verdicts_bqn(src: Dict, dst: Dict, start, block: int) -> list:
    """The counts tile's verdicts, case by case: for each port case q,
    (ingress, egress, combined) [B, Nd] bool of the tile's source rows
    against the dst pods.  Both contractions put the tile's rows on the
    major axis and the dst pods on the minor (lane) one, so nothing is
    transposed, and Q never sits between them (a [B, Q, Nd] array made
    by a reshape is a relayout on the TPU: PERF.md section 6, PR 39).
    Ingress contracts the tile's tallow_i[:, :, q] against the dst
    tmatch_i, egress the tile's tmatch_e against the dst tallow_e[:, q],
    which _precompute_cases lays [W, Q, Nd] for it (tile=TILE_BQN).  A
    count never leaves the chip as verdicts, so their layout is free;
    the tables paths read _tile_verdicts_split."""
    w_i, _, q = src["tallow_i"].shape
    w_e = src["tmatch_e"].shape[0]
    nd = dst["tmatch_i"].shape[1]
    if dst["tallow_e"].shape != (w_e, q, nd):
        raise ValueError(
            f"the counts tile body wants the egress tallow as (W, Q, N) = "
            f"{(w_e, q, nd)}, got {dst['tallow_e'].shape}: build the "
            f"precompute with tile={TILE_BQN!r}"
        )
    any_ = packed_any if src["tmatch_e"].dtype == jnp.int32 else _any_bf16
    tme = jax.lax.dynamic_slice(src["tmatch_e"], (0, start), (w_e, block))
    no_e = ~jax.lax.dynamic_slice(src["has_e"], (start,), (block,))[:, None]
    no_i = ~dst["has_i"][None, :]
    tli = jax.lax.dynamic_slice(
        src["tallow_i"], (0, start, 0), (w_i, block, q)
    )  # [W, B, Q]
    out = []
    for k in range(q):
        egress = no_e | any_(tme, dst["tallow_e"][:, k])  # [B, Nd]
        ingress = no_i | any_(tli[:, :, k], dst["tmatch_i"])  # [B, Nd]
        out.append((ingress, egress, egress & ingress))
    return out


def _tile_counts_split(
    src: Dict,
    dst: Dict,
    src_valid: jnp.ndarray,
    dst_valid: jnp.ndarray,
    start,
    block: int,
) -> jnp.ndarray:
    """[3] int32 validity-masked allow counts for src-view rows
    [start, start+block) against all dst-view pods — THE per-tile count
    body, shared by the single-device, mesh-parallel, and ring paths so
    the masking/count semantics cannot diverge.  It runs on the counts
    tile's [B, Nd] slabs a case (_tile_verdicts_bqn, a precompute built
    with tile=TILE_BQN), and where tier arrays ride on the parent's
    [B, Nd, Q] verdicts with the tier epilogue (_tile_verdicts_split):
    counts_tile_form.  Safe in int32 for any block*Nd*Q that fits in
    HBM."""
    if "tier_subj_e" in src:
        verdicts = [_tile_verdicts_split(src, dst, start, block)]
        sv = jax.lax.dynamic_slice(src_valid, (start,), (block,))
        mask = sv[:, None, None] & dst_valid[None, :, None]
    else:
        verdicts = _tile_verdicts_bqn(src, dst, start, block)
        sv = jax.lax.dynamic_slice(src_valid, (start,), (block,))
        mask = sv[:, None] & dst_valid[None, :]
    counts = [
        jnp.stack([jnp.sum(v & mask, dtype=jnp.int32) for v in case])
        for case in verdicts
    ]
    for more in counts[1:]:
        counts[0] = counts[0] + more
    return counts[0]


def _tile_counts(pre: Dict, valid: jnp.ndarray, start, block: int) -> jnp.ndarray:
    """Single-array-set form of _tile_counts_split (src == dst)."""
    src, dst = _split_pre(pre)
    return _tile_counts_split(src, dst, valid, valid, start, block)


def _int32_safe_block(block: int, n_pods: int, q: int) -> int:
    """Halve the tile height until per-tile counts stay below 2^31."""
    while block > 1 and block * n_pods * q >= 2**31:
        block //= 2
    return block


@partial(jax.jit, static_argnames=("block", "n_tiles", "n_pods", "pack"))
def _counts_kernel(
    tensors: Dict, block: int, n_tiles: int, n_pods: int, pack: bool = False
) -> jnp.ndarray:
    """[n_tiles, 3] int32 allow counts (ingress, egress, combined) over the
    full grid, computed with one device execution; the host sums tiles in
    int64."""
    pre = _precompute(tensors, pack, TILE_BQN)
    n_padded = tensors["pod_ns_id"].shape[0]
    valid = jnp.arange(n_padded) < n_pods  # [N] pod-validity mask

    def body(i, counts):
        return counts.at[i].set(_tile_counts(pre, valid, i * block, block))

    counts = jnp.zeros((n_tiles, 3), dtype=jnp.int32)
    return jax.lax.fori_loop(0, n_tiles, body, counts)


def evaluate_grid_counts(
    tensors: Dict, n_pods: int, block: int = 1024, pack: bool = None
) -> Dict[str, int]:
    """Allow counts over the full N x N x Q grid without materializing it.
    One jit dispatch, one [n_tiles, 3] readback.  `pack` routes the tile
    bodies through the 32-per-word packed operands (None: resolve
    CYCLONUS_PACK eagerly here, outside the jit)."""
    if pack is None:
        pack = pack_enabled()
    q = int(tensors["q_port"].shape[0])
    # per-tile counts are int32: keep block * N * Q below 2^31 (the
    # equivalent global-accumulator overflow bit the pallas backend at
    # 100k pods before partials were introduced)
    block = _int32_safe_block(min(block, max(n_pods, 1)), n_pods, q)
    with ti.eval_flight(
        "counts.xla", n_pods, q, block=block, tile=counts_tile_form(tensors),
        **tier_attrs(tensors), **tallow_attrs(tensors),
    ) as fl:
        tensors, n_tiles = _pad_pod_axis(tensors, n_pods, block)
        with phase("engine.dispatch"):
            out = _counts_kernel(tensors, block, n_tiles, n_pods, pack)
        # the readback is the execution barrier (dispatch is async)
        with phase("engine.execute"):
            counts = np.asarray(out, dtype=np.int64).sum(axis=0)
        total = q * n_pods * n_pods
        fl.set(cells=total)
    return {
        "ingress": int(counts[0]),
        "egress": int(counts[1]),
        "combined": int(counts[2]),
        "cells": total,
    }


# --- equivalence-class (compressed-grid) counts ---------------------------
#
# The compressed counts contract: with pods bucketed into C equivalence
# classes (encoding.compute_pod_classes), every full-grid count is the
# class-grid count weighted by class sizes:
#
#     count[q] = sum_{c1, c2} verdict[q, c1, c2] * size[c1] * size[c2]
#
# Exactness without float64 (disabled by default in JAX) is a two-stage
# split: the DEVICE computes per-src-class weighted row sums
# rs[c, q, k] = sum_dst verdict * w[dst] — every partial sum is an
# integer <= N, exact in f32 while N < 2^24 (api gates the path on that
# bound) — and the HOST finishes sum_c w[c] * rs[c] in int64, where the
# ~1e12-scale products live.  The [Q, C, C] verdict grid never
# materializes: the same _tile_verdicts_split body every dense tiled
# path uses runs per class tile, with the count epilogue swapped for
# the weighted row-sum einsum.


def _class_tile_rowsums(
    src: Dict, dst: Dict, w_dst: jnp.ndarray, start, block: int
) -> jnp.ndarray:
    """[block, Q, 3] f32 dst-weighted verdict row sums for src-view rows
    [start, start+block): rs[b, q, k] = sum_dst grid_k * w_dst.  Pad
    classes carry weight 0 on the dst side and are zeroed by the host
    weighting on the src side, so no validity mask is needed.  The views'
    form picks the body: the MXU form's [B, Nd] slabs a case
    (_tile_verdicts_mxu), else the tables' [B, Nd, Q] verdicts."""

    def rs(a: jnp.ndarray) -> jnp.ndarray:
        # HIGHEST precision is load-bearing: TPU's default f32 matmul
        # runs bf16 multiplies, which round class-size weights > 256
        # (e.g. a 1955-pod class -> 1952) and would silently break the
        # exact-integer contract class_counts_finish rounds on.  CPU
        # (where the parity suites run) is exact either way — only the
        # TPU mega shapes would see the corruption.
        return jnp.einsum(
            "bd...,d->b...",
            a.astype(jnp.float32),
            w_dst,
            precision=jax.lax.Precision.HIGHEST,
        )

    if dst["tmatch_i"].dtype == MXU_DTYPE:
        return jnp.stack(
            [
                jnp.stack([rs(v) for v in case], axis=-1)
                for case in _tile_verdicts_mxu(src, dst, start, block)
            ],
            axis=1,
        )  # [B, Q, 3]
    ingress_rows, egress, combined = _tile_verdicts_split(src, dst, start, block)
    return jnp.stack([rs(ingress_rows), rs(egress), rs(combined)], axis=-1)


def _with_case_rows(tensors: Dict, cases: jnp.ndarray) -> Dict:
    """`tensors` (the engine's case-free class tensor set) with the
    port-case leaves set from the rows of `cases`, int32 [3, Q] =
    (q_port, q_name, q_proto): inside a jit, three slices of ONE
    operand, so a request sends one array for its cases and not three."""
    return dict(tensors, q_port=cases[0], q_name=cases[1], q_proto=cases[2])


@partial(jax.jit, static_argnames=("block", "n_tiles", "pack"))
def _class_rowsums_kernel(
    tensors: Dict,
    w: jnp.ndarray,
    cases: jnp.ndarray,
    block: int,
    n_tiles: int,
    pack: bool = False,
    selpod: jnp.ndarray = None,
) -> jnp.ndarray:
    """[n_tiles * block, Q, 3] f32 weighted row sums over the class grid,
    one device execution (fori_loop over class tiles).  `tensors` carries
    no port cases: they come as `cases` (_with_case_rows); `selpod`, the
    resident selector matches of the classes where the engine holds them
    (_precompute_static).  `pack`: class_counts_pack's, and with it the
    tile's layout (class_tile_form)."""
    pre = _precompute(
        _with_case_rows(tensors, cases), pack, class_tile_form(tensors, pack),
        selpod=selpod,
    )
    src, dst = _split_pre(pre)
    q = cases.shape[1]

    def body(i, out):
        rs = _class_tile_rowsums(src, dst, w, i * block, block)
        return jax.lax.dynamic_update_slice(out, rs, (i * block, 0, 0))

    out = jnp.zeros((n_tiles * block, q, 3), dtype=jnp.float32)
    return jax.lax.fori_loop(0, n_tiles, body, out)


def _class_tiling(cb: int, block: int) -> Tuple[int, int]:
    """(block, n_tiles) of the XLA tile loop over a class axis of `cb`.
    Bucketed axes (api._bucket_pods) are powers of two or multiples of
    1024, so min(block, 1024, cb) always divides cb; the fallback to the
    whole axis covers hand-built tensor dicts only."""
    block = max(1, min(block, 1024, cb))
    if cb % block:
        block = cb
    return block, cb // block


def class_weights(cb: int, n_classes: int, class_size: np.ndarray) -> np.ndarray:
    """The dst-side weights of the row sums: the class sizes as float32
    on the padded class axis [cb], pad classes 0.  A function of the
    class state and of nothing a call brings: the engine keeps it on the
    device (api._class_counts_operands)."""
    w = np.zeros((cb,), dtype=np.float32)
    w[:n_classes] = np.asarray(class_size, dtype=np.float32)
    return w


def class_rowsums_plan(
    tensors: Dict, n_classes: int, class_size: np.ndarray, block: int = 1024
):
    """(w, block, n_tiles) for the class row-sum kernel over `tensors`
    whose pod axis is the (bucketing-padded) class axis."""
    cb = int(tensors["pod_ns_id"].shape[0])
    block, n_tiles = _class_tiling(cb, block)
    return class_weights(cb, n_classes, class_size), block, n_tiles


def class_counts_finish(
    rowsums: np.ndarray,
    class_size: np.ndarray,
    n_classes: int,
    q: int,
    n_pods: int,
) -> Dict[str, int]:
    """Exact int64 host finish of the device row sums: the src-side
    class weighting.  Row-sum entries are integers <= N held exactly in
    f32 (N < 2^24 gated by the caller); the products reach ~N^2 and live
    in int64 only."""
    rs = np.rint(np.asarray(rowsums)[:n_classes]).astype(np.int64)  # [C, Q, 3]
    w = np.asarray(class_size, dtype=np.int64)
    totals = (w[:, None, None] * rs).sum(axis=(0, 1))  # [3]
    return {
        "ingress": int(totals[0]),
        "egress": int(totals[1]),
        "combined": int(totals[2]),
        "cells": q * n_pods * n_pods,
    }


@partial(jax.jit, static_argnames=("interpret",))
def _class_rowsums_fused_kernel(
    tensors: Dict, w: jnp.ndarray, cases: jnp.ndarray, interpret: bool = False,
    selpod: jnp.ndarray = None,
) -> jnp.ndarray:
    """Fused-epilogue twin of _class_rowsums_kernel: packed precompute +
    the packed Pallas kernel whose EPILOGUE computes the dst-weighted
    row sums in VMEM (the class-compression gather's weighting never
    round-trips a verdict block through HBM).  One jit: precompute +
    kernel are one device execution.  Returns [Cb, Q, 3] f32 —
    bit-identical to the split kernel by the fused-vs-split parity
    test."""
    from .pallas_kernel import verdict_counts_pallas_packed

    pre = _precompute(_with_case_rows(tensors, cases), True, selpod=selpod)
    tier = {
        d: pre[d]["tier"] for d in ("ingress", "egress")
    } if "tier" in pre["egress"] else None
    cb = int(tensors["pod_ns_id"].shape[0])
    rs = verdict_counts_pallas_packed(
        pre["egress"]["tmatch_pk"],
        pre["egress"]["has_target"],
        pre["egress"]["tallow_pk"],
        pre["ingress"]["tmatch_pk"],
        pre["ingress"]["has_target"],
        pre["ingress"]["tallow_pk"],
        n_pods=cb,  # every class row is live; pad weights are zero
        tier=tier,
        w_dst=w,
        interpret=interpret,
    )  # [Q, Cb', 3] f32
    return jnp.moveaxis(rs[:, :cb, :], 0, 1)  # [Cb, Q, 3]


def _class_counts_kernel_choice(tensors: Dict, pack: bool, kernel: str) -> str:
    """Which class row-sum program runs: "pallas" (the TPU default when
    `pack` is on) is the FUSED packed kernel — contraction + tier lattice
    + the dst-weighted gather epilogue in one Pallas program; "xla" keeps
    the fori_loop tile body.  Identical row sums by construction (the
    fused-vs-split parity test pins them).  The fused kernel unrolls over
    the tier rule rows and the packed target words: past either ceiling
    (packed_tier_eligible, packed_words_eligible) the XLA tile runs."""
    from .pallas_kernel import packed_tier_eligible, packed_words_eligible

    if kernel is None:
        # the tier rule ceiling the dense counts route enforces too
        # (api._packed_tier_ok), and the target word ceiling: past
        # either, the class counts run the XLA tile loop
        kernel = (
            "pallas"
            if pack
            and jax.default_backend() == "tpu"
            and packed_tier_eligible(tensors)
            and packed_words_eligible(tensors)
            else "xla"
        )
    if kernel not in ("pallas", "xla"):
        raise ValueError(
            f"unknown class counts kernel {kernel!r} (want 'pallas' or 'xla')"
        )
    if kernel == "pallas" and not packed_tier_eligible(tensors):
        raise ValueError(
            "class counts kernel 'pallas' cannot fuse a tier rule axis "
            "past the static-unroll ceiling; use kernel='xla' or "
            "kernel=None (auto)"
        )
    if kernel == "pallas" and not packed_words_eligible(tensors):
        raise ValueError(
            "class counts kernel 'pallas' cannot unroll a target word axis "
            "past pallas_kernel.PACKED_MAX_WORDS; use kernel='xla' or "
            "kernel=None (auto)"
        )
    return kernel


#: the forms of the class tile's contraction (`class_contract` on the
#: evaluation): the packed target words, on the VPU (kernel.packed_any, or
#: the fused Pallas kernel), or the unpacked target axis on the MXU
CONTRACT_PACKED = "packed"
CONTRACT_MXU = "mxu"


def class_counts_pack(tensors: Dict, pack: bool, kernel: str) -> bool:
    """Whether the class row-sum program over `tensors` packs its target
    axis.  The fused Pallas kernel (`kernel` "pallas") always does.  On
    the XLA tile a tiered set keeps `pack` (its tier epilogue reads the
    [B, Nd, Q] verdicts), and an untiered set never packs: its two
    contractions run on the MXU (TILE_QTN, _tile_verdicts_mxu), where
    packing would only move them to the VPU.  Decided from the shapes
    and the kernel, outside any jit; the class counts routes and their
    HBM estimate (api._class_counts_bytes) all ask here."""
    return kernel == "pallas" or (bool(pack) and "tiers" in tensors)


def class_tile_form(tensors: Dict, pack: bool) -> str:
    """The layout of the class row-sum tile over `tensors`: TILE_BNQ for
    packed words and for a tiered set (the tier epilogue is written for
    [B, Nd, Q] verdicts, as counts_tile_form), else the MXU form,
    TILE_QTN."""
    return TILE_BNQ if pack or "tiers" in tensors else TILE_QTN


def evaluate_grid_counts_classes(
    fl,
    tensors: Dict,
    w: jnp.ndarray,
    cases: np.ndarray,
    n_classes: int,
    class_size: np.ndarray,
    n_pods: int,
    block: int = 1024,
    pack: bool = None,
    kernel: str = None,
    selpod=None,
) -> Tuple[Dict[str, int], float]:
    """Allow counts over the FULL N x N x Q grid, evaluated on the
    compressed C x C class grid and weighted back exactly, inside the
    caller's eval_flight `fl` (the engine opens it before it builds the
    operands, so that the flight's `engine.eval` span covers the whole
    request): plan, dispatch, the readback barrier, the exact host
    finish.  The operands are split into what the engine owns and what
    the call brings: `tensors` (the class tensor set, WITHOUT port
    cases) and `w` (class_weights) are the engine's and stay on its
    device; `cases`, int32 [3, Q] on the host (_with_case_rows), is the
    call's and is the one array sent, by one explicit device_put.  The
    `engine.dispatch` span says what really crossed: `host_operands`
    and `host_bytes` count the host arrays among all three.  Returns
    (counts, gather_s) where gather_s is the finish (the host weighting)
    — the cheap gather the compression trades the dense grid for.
    `kernel`: see _class_counts_kernel_choice.  `selpod`: the engine's
    resident selector matches of the classes, [S, Cb] on its device, or
    None, where the program computes them (_precompute_static); the
    flight says which (`class_sel` host / device), and which form the
    contraction took (`class_contract`, class_counts_pack)."""
    import time as _time

    if pack is None:
        pack = pack_enabled()
    with detail("engine.plan"):
        kernel = _class_counts_kernel_choice(tensors, pack, kernel)
        pack = class_counts_pack(tensors, pack, kernel)
        block, n_tiles = _class_tiling(
            int(tensors["pod_ns_id"].shape[0]), block
        )
    q = int(cases.shape[1])
    fl.set(
        block=block,
        class_kernel=kernel,
        class_sel="device" if selpod is None else "host",
        class_contract=CONTRACT_PACKED if pack else CONTRACT_MXU,
        target_words=target_words(tensors),
        **tier_attrs(tensors, TIER_FUSED if kernel == "pallas" else TIER_XLA),
        **tallow_attrs(tensors),
    )
    with phase("engine.dispatch") as sp:
        sent = jax.device_put(cases)
        if kernel == "pallas":
            from .pallas_kernel import _should_interpret

            out = _class_rowsums_fused_kernel(
                tensors, w, sent, interpret=_should_interpret(), selpod=selpod
            )
        else:
            out = _class_rowsums_kernel(
                tensors, w, sent, block, n_tiles, pack, selpod=selpod
            )
        # counted once the program is enqueued: the device is running,
        # so walking the operands costs the request nothing
        host = [
            a
            for a in jax.tree_util.tree_leaves((tensors, w, cases, selpod))
            if isinstance(a, np.ndarray)
        ]
        sp.set(
            host_operands=len(host), host_bytes=sum(a.nbytes for a in host)
        )
    # the readback is the execution barrier (dispatch is async)
    with phase("engine.execute"):
        rs = np.asarray(out)
    t0 = _time.perf_counter()
    with detail("engine.finish"):
        counts = class_counts_finish(rs, class_size, n_classes, q, n_pods)
    gather_s = _time.perf_counter() - t0
    fl.set(cells=counts["cells"])
    return counts, gather_s


def evaluate_grid_counts_classes_sharded(
    tensors: Dict,
    n_classes: int,
    class_size: np.ndarray,
    n_pods: int,
    block: int = 1024,
    mesh=None,
) -> Tuple[Dict[str, int], float]:
    """Mesh-parallel compressed counts: the CLASS axis (already tiny
    next to the pod axis) splits over the mesh, each device computes the
    weighted row sums for its class shard against the replicated dst
    view, and one all-gather hands the [C, Q, 3] row sums to the same
    exact host finish as the single-device path."""
    import time as _time

    from jax.sharding import PartitionSpec as P

    from .sharded import mesh_device_context, shard_map_no_check

    mesh, n_dev, q, block, tensors, n_padded = _mesh_counts_setup(
        tensors, n_classes, block, mesh
    )
    pack = class_counts_pack(tensors, pack_enabled(), "xla")
    tile = class_tile_form(tensors, pack)
    shard = n_padded // n_dev
    tiles_per_shard = shard // block
    t = dict(tensors)
    t["class_w"] = class_weights(n_padded, n_classes, class_size)

    def per_device(td):
        w_all = td["class_w"]
        pre = _precompute(
            {k: v for k, v in td.items() if k != "class_w"}, pack, tile
        )
        src, dst = _split_pre(pre)
        dev = jax.lax.axis_index("x")
        row0 = dev * shard

        def body(i, out):
            rs = _class_tile_rowsums(src, dst, w_all, row0 + i * block, block)
            return jax.lax.dynamic_update_slice(out, rs, (i * block, 0, 0))

        out = jax.lax.fori_loop(
            0,
            tiles_per_shard,
            body,
            jnp.zeros((shard, q, 3), dtype=jnp.float32),
        )
        return jax.lax.all_gather(out, "x", axis=0, tiled=True)

    in_specs = jax.tree_util.tree_map(lambda _: P(), t)
    fn = jax.jit(
        shard_map_no_check(
            per_device, mesh=mesh, in_specs=(in_specs,), out_specs=P()
        )
    )
    with ti.eval_flight(
        "counts.classes.sharded",
        n_pods,
        q,
        classes=n_classes,
        devices=int(n_dev),
        class_contract=CONTRACT_PACKED if pack else CONTRACT_MXU,
        **tier_attrs(tensors),
        **tallow_attrs(tensors),
    ) as fl:
        with mesh_device_context(mesh):
            rs = np.asarray(fn(t))
        t0 = _time.perf_counter()
        counts = class_counts_finish(rs, class_size, n_classes, q, n_pods)
        gather_s = _time.perf_counter() - t0
        fl.set(cells=counts["cells"])
    return counts, gather_s


@partial(jax.jit, static_argnames=("block",))
def _block_kernel(pre: Dict, start: jnp.ndarray, block: int):
    return _tile_verdicts(pre, start, block)


def iter_grid_blocks(
    tensors: Dict, n_pods: int, block: int = 1024, pack: bool = None
) -> Iterator[Tuple[int, np.ndarray, np.ndarray, np.ndarray]]:
    """Stream verdict blocks to the host: yields
    (start, ingress_rows, egress, combined) with arrays [b, N, Q] bool,
    pad rows/columns already stripped.  ingress_rows[b, d, q] is the
    ingress verdict for dst d <- src (start+b) — i.e. full-grid
    ingress[q, d, start+b]."""
    if pack is None:
        pack = pack_enabled()
    block = min(block, max(n_pods, 1))
    tensors, n_tiles = _pad_pod_axis(tensors, n_pods, block)
    pre = _precompute_jit(tensors, pack)
    # the pod axis may carry MORE pad rows than one block's worth (shape
    # bucketing pads before this function): iterate only the tiles with
    # real rows and clamp the final tile's height to the real pod count
    n_tiles = min(n_tiles, -(-n_pods // block))
    for i in range(n_tiles):
        start = i * block
        ingress_rows, egress, combined = _block_kernel(
            pre, jnp.int32(start), block
        )
        b = min(block, n_pods - start)
        yield (
            start,
            np.asarray(ingress_rows)[:b, :n_pods],
            np.asarray(egress)[:b, :n_pods],
            np.asarray(combined)[:b, :n_pods],
        )


_precompute_jit = partial(jax.jit, static_argnames=("pack",))(_precompute)


def _mesh_counts_setup(tensors: Dict, n_pods: int, block: int, mesh):
    """Shared mesh/count-path setup: resolve the mesh, bound the tile
    height for int32 partials, and pad the pod axis so every device gets
    a whole number of tiles."""
    from .sharded import _pad_pod_arrays, default_mesh

    mesh = mesh or default_mesh()
    n_dev = mesh.devices.size
    q = int(tensors["q_port"].shape[0])
    block = _int32_safe_block(min(block, max(n_pods // n_dev, 1)), n_pods, q)
    tensors, n_padded = _pad_pod_arrays(tensors, n_pods, n_dev * block)
    return mesh, n_dev, q, block, tensors, n_padded


def mesh_counts_kernel(kernel: str = None) -> str:
    """The per-device kernel of the replicated source-row route: the
    rectangular Pallas kernel on a TPU, the XLA tile loop elsewhere
    (where Pallas would run in slow interpret mode), unless named."""
    if kernel is None:
        kernel = "pallas" if jax.default_backend() == "tpu" else "xla"
    if kernel not in ("pallas", "xla"):
        raise ValueError(
            f"unknown sharded counts kernel {kernel!r} (want 'pallas' or 'xla')"
        )
    return kernel


def _ring_counts(pre: Dict, n_pods, n_dev: int, shard: int, block: int):
    """The pod-sharded ring's per-device body from the shard's `pre` on:
    the src view stays local, the dst view (and its validity mask)
    rotates, one _tile_counts_split a tile a step, then the ONE gather
    of the [n_dev * tiles, 3] int32 partials.  `pre` is built with
    tile=TILE_BQN.  With packing on the rotating bundle carries the
    packed words (~16x fewer bytes a hop).
    `n_pods` may be traced.  The ring route's body in the held program
    (mesh_counts_programs)."""
    tiles_per_shard = shard // block
    row0 = jax.lax.axis_index("x") * shard
    valid_local = (jnp.arange(shard) + row0) < n_pods  # [shard]
    src, dst0 = _split_pre(pre)
    ring = dict(dst0, valid=valid_local)

    def body(step, ring, counts):
        dst = {k: ring[k] for k in _dst_bundle_keys(ring)}

        def tile(i, counts):
            row = _tile_counts_split(
                src, dst, valid_local, ring["valid"], i * block, block
            )
            return counts.at[step * tiles_per_shard + i].set(row)

        return jax.lax.fori_loop(0, tiles_per_shard, tile, counts)

    counts = jnp.zeros((n_dev * tiles_per_shard, 3), dtype=jnp.int32)
    counts, _ = _ring_sweep(n_dev, ring, counts, body)
    return jax.lax.all_gather(counts, "x", axis=0, tiled=True)


def _row_counts(
    pre: Dict, n_pods, n_dev: int, n_padded: int, block: int, kernel: str,
    pack: bool,
):
    """The replicated source-row route's per-device body from the WHOLE
    (replicated) `pre` on: this device's rows against every pod, by the
    rectangular Pallas kernel (kernel="pallas") or the XLA tile loop,
    then the ONE gather of the int32 partials.  `n_pods` may be traced.
    `pre` is built with tile=TILE_BQN for the tile loop and at the
    default for the Pallas kernel.  The rows route's body in the held
    program (mesh_counts_programs)."""
    shard = n_padded // n_dev
    tiles_per_dev = shard // block
    row0 = jax.lax.axis_index("x") * shard
    valid = jnp.arange(n_padded) < n_pods

    if kernel == "pallas":
        from .pallas_kernel import (
            _should_interpret,
            verdict_counts_pallas_packed,
            verdict_counts_pallas_rect,
        )

        e, ig = pre["egress"], pre["ingress"]
        sl = partial(jax.lax.dynamic_slice_in_dim, start_index=row0)
        # rect form: src = this device's row shard, dst = the full axis;
        # the packed words slice on the pod axis like the dense operands
        fn = verdict_counts_pallas_packed if pack else verdict_counts_pallas_rect
        allow, match = (
            ("tallow_pk", "tmatch_pk") if pack else ("tallow_bf", "tmatch")
        )
        partials = fn(
            sl(e[match], slice_size=shard, axis=1),
            sl(e["has_target"], slice_size=shard, axis=0),
            e[allow],
            ig[match],
            ig["has_target"],
            sl(ig[allow], slice_size=shard, axis=1),
            valid_src=sl(valid, slice_size=shard, axis=0),
            valid_dst=valid,
            interpret=_should_interpret(),
        )  # [Q, n_src_tiles_local, 3]
        return jax.lax.all_gather(
            partials.reshape(-1, 3), "x", axis=0, tiled=True
        )

    def body(i, counts):
        return counts.at[i].set(
            _tile_counts(pre, valid, row0 + i * block, block)
        )

    counts = jax.lax.fori_loop(
        0,
        tiles_per_dev,
        body,
        jnp.zeros((tiles_per_dev, 3), dtype=jnp.int32),
    )
    # one collective: gather every device's per-tile partials so the
    # host can sum them in int64 (device int32 would overflow first)
    return jax.lax.all_gather(counts, "x", axis=0, tiled=True)


# --- the HELD mesh counts program ------------------------------------------
#
# What the one-chip dense counts route does since PR 29 / 31 / 33, on the
# mesh: the program pair is built once per engine state (AotProgram, the
# plan in its key), `static` runs once and its result - the half of the
# precompute the port cases do not touch, per shard on the ring route,
# whole on every device on the rows route - stays on the chips, and a
# request runs `cases` from there: its port cases (int32 [3, Q]) are the
# one array it sends.  api.TpuPolicyEngine holds the pair
# (_mesh_counts_jits) and the static (_mesh_static): the Threading note
# above, no cache lives here.

#: names the held pair in the persistent key of both programs (the arg
#: shapes cannot see that a mesh counts program starts from a resident
#: static), and is what a caller of the mesh counts entry can ask a
#: program for before it builds a cluster whose per-call precompute no
#: chip holds (benchmarks/kinds/sweep_mesh_counts_generated.py)
MESH_COUNTS_HELD = "mesh-counts=held"


def _mesh_static_specs(tensors: Dict, pack: bool, ring: bool) -> Dict:
    """shard_map specs of _precompute_static's result: on the ring route
    every leaf with a pod axis is sharded over it (a banded peer_match on
    its last), on the rows route all of it is replicated."""

    def pod(*lead):
        return P(*lead, "x") if ring else P()

    out = {}
    for direction in ("ingress", "egress"):
        spec = {
            "peer_match": pod(None),
            "tmatch": pod(None),
            "has_target": pod(),
            "peer_target": P(),
            "target_ns": P(),
            "port_spec": {k: P() for k in tensors[direction]["port_spec"]},
        }
        if "band_rows" in tensors[direction]:
            spec["band_rows"] = P()
            spec["peer_match"] = pod(None, None)
        if pack:
            spec["tmatch_pk"] = pod(None)
        out[direction] = spec
    if "tiers" in tensors:
        out["tiers"] = {
            "enc": jax.tree_util.tree_map(lambda _: P(), tensors["tiers"]),
            "selpod": pod(None),
            "selns": P(),
            "pod_ns_id": pod(),
        }
    return out


def mesh_counts_programs(
    mesh, tensors: Dict, block: int, route: str, kernel: str, pack: bool,
    plan: str,
):
    """(static, cases): the held pair over `mesh` for the case-free
    `tensors`, padded to whole tiles a device.  `route` "ring"
    keeps both pod axes sharded (_ring_counts), "rows" replicates the
    precompute and splits the source rows (_row_counts, under `kernel`).

      static(tensors)               -> _precompute_static a device
      cases(static, cases, n_pods)  -> [*, 3] int32 partials, gathered

    Both are AotPrograms; `plan` (the engine's dtype plan) is completed
    here with everything else the arg shapes cannot see."""
    from . import aot_cache
    from .sharded import pod_sharded_in_specs, shard_map_no_check

    ring = route == "ring"
    xla_tile = ring or kernel == "xla"
    n_dev = int(mesh.devices.size)
    n_padded = int(tensors["pod_ns_id"].shape[0])
    shard = n_padded // n_dev
    in_specs = (
        pod_sharded_in_specs(tensors)
        if ring
        else jax.tree_util.tree_map(lambda _: P(), tensors)
    )
    static_specs = _mesh_static_specs(tensors, pack, ring)

    def static_device(t):
        return _precompute_static(t, pack)

    def cases_device(static, cases, n_pods):
        pre = _precompute_cases(
            static, cases[0], cases[1], cases[2], pack,
            TILE_BQN if xla_tile else TILE_BNQ,
        )
        if ring:
            return _ring_counts(pre, n_pods, n_dev, shard, block)
        return _row_counts(pre, n_pods, n_dev, n_padded, block, kernel, pack)

    leaves, treedef = jax.tree_util.tree_flatten(in_specs)
    # the tile body's form: the key cannot see code, and the parent's
    # programs of the same shapes hold the [B, Nd, Q] body
    plan = (
        f"{plan};{MESH_COUNTS_HELD};route={route};"
        + ("" if ring else f"kernel={kernel};")
        + (f"tile={counts_tile_form(tensors)};" if xla_tile else "")
        + f"shard={shard};block={block};pack={pack};"
        f"mesh={','.join(mesh.axis_names)}x{n_dev};"
        + aot_cache.digest((str(treedef), [str(x) for x in leaves]))
    )
    # static_specs is a function of in_specs' tree, pack and the route,
    # all three in the plan
    static_fn = aot_cache.AotProgram(
        "counts.mesh.static",
        jax.jit(
            shard_map_no_check(
                static_device, mesh=mesh, in_specs=(in_specs,),
                out_specs=static_specs,
            )
        ),
        schedule=route,
        plan=plan,  # cache-key: static_specs
    )
    cases_fn = aot_cache.AotProgram(
        "counts.mesh.cases",
        jax.jit(
            shard_map_no_check(
                cases_device, mesh=mesh, in_specs=(static_specs, P(), P()),
                out_specs=P(),
            )
        ),
        schedule=route,
        plan=plan,  # cache-key: static_specs
    )
    return static_fn, cases_fn


@jax.jit
def evaluate_pairs_kernel(
    tensors: Dict, s_idx: jnp.ndarray, d_idx: jnp.ndarray
) -> Dict[str, jnp.ndarray]:
    """Point verdicts for K (src, dst) index pairs: returns
    {ingress, egress, combined}, each [K, Q] bool.  O((S+T+P) * K) — no
    N x N grid anywhere; the scale-parity spot check rides this."""
    pod_kv = tensors["pod_kv"]
    pod_key = tensors["pod_key"]

    def sub(idx):
        return {
            "pod_kv": jnp.take(pod_kv, idx, axis=0),
            "pod_key": jnp.take(pod_key, idx, axis=0),
            "pod_ns_id": jnp.take(tensors["pod_ns_id"], idx, axis=0),
            "pod_ip": jnp.take(tensors["pod_ip"], idx, axis=0),
            "pod_ip_valid": jnp.take(tensors["pod_ip_valid"], idx, axis=0),
        }

    selns = selector_match(
        tensors["sel_req_kv"],
        tensors["sel_exp_op"],
        tensors["sel_exp_key"],
        tensors["sel_exp_vals"],
        tensors["ns_kv"],
        tensors["ns_key"],
    )

    def direction_pair(direction, t_idx, p_idx):
        """Verdict [K, Q] for (target-side pods t_idx, peer-side pods
        p_idx) in the given direction."""
        enc = tensors[direction]
        t_sub, p_sub = sub(t_idx), sub(p_idx)
        sel_t = selector_match(
            tensors["sel_req_kv"],
            tensors["sel_exp_op"],
            tensors["sel_exp_key"],
            tensors["sel_exp_vals"],
            t_sub["pod_kv"],
            t_sub["pod_key"],
        )
        sel_p = selector_match(
            tensors["sel_req_kv"],
            tensors["sel_exp_op"],
            tensors["sel_exp_key"],
            tensors["sel_exp_vals"],
            p_sub["pod_kv"],
            p_sub["pod_key"],
        )
        pre_t = direction_precompute(
            enc, sel_t, selns, t_sub["pod_ns_id"], t_sub["pod_ip"],
            t_sub["pod_ip_valid"],
        )
        pre_p = direction_precompute(
            enc, sel_p, selns, p_sub["pod_ns_id"], p_sub["pod_ip"],
            p_sub["pod_ip_valid"],
        )
        # host-evaluated ip-peer rows are indexed by ORIGINAL pod row
        if "host_ip_match" in enc:
            patch = jnp.take(enc["host_ip_match"], p_idx, axis=1)
            pre_p["peer_match"] = jnp.where(
                enc["host_ip_mask"][:, None], patch, pre_p["peer_match"]
            )
        pport = port_spec_allows(
            enc["port_spec"],
            tensors["q_port"],
            tensors["q_name"],
            tensors["q_proto"],
        )
        peer_allow = pre_p["peer_match"][:, :, None] & pport[:, None, :]  # [P,K,Q]
        # tallow[t, k, q] = any peer of target t allows peer-side pod k
        tallow = (
            jnp.einsum(
                "tp,pkq->tkq",
                m_tp_onehot(enc).astype(jnp.bfloat16),
                peer_allow.astype(jnp.bfloat16),
            )
            > 0
        )
        any_allow = jnp.einsum(
            "tk,tkq->kq",
            pre_t["tmatch"].astype(jnp.bfloat16),
            tallow.astype(jnp.bfloat16),
        ) > 0
        allowed = (~pre_t["has_target"][:, None]) | any_allow
        if "tiers" in tensors:
            # precedence-tier epilogue for point pairs: subject over the
            # target-side pods, peer over the peer-side pods, aligned
            # per pair k — [G, K] masks, no grid anywhere
            from .kernel import tier_keys, tier_scope_match

            tenc = tensors["tiers"][direction]
            subj = tier_scope_match(
                tenc["subj_ns_sel"], tenc["subj_pod_kind"],
                tenc["subj_pod_sel"], sel_t, selns, t_sub["pod_ns_id"],
            )  # [G, K]
            peer = tier_scope_match(
                tenc["peer_ns_sel"], tenc["peer_pod_kind"],
                tenc["peer_pod_sel"], sel_p, selns, p_sub["pod_ns_id"],
            )  # [G, K]
            pport_t = port_spec_allows(
                tenc["port_spec"],
                tensors["q_port"],
                tensors["q_name"],
                tensors["q_proto"],
            )  # [G, Q]
            match = (subj & peer)[:, :, None] & pport_t[:, None, :]  # [G,K,Q]
            anp_key, banp_key = tier_keys(tenc)
            none = jnp.int32(TIER_KEY_NONE)
            anp_min = jnp.min(
                jnp.where(match, anp_key[:, None, None], none), axis=0
            )
            banp_min = jnp.min(
                jnp.where(match, banp_key[:, None, None], none), axis=0
            )
            allowed = resolve_tier_lattice(
                allowed, pre_t["has_target"][:, None], anp_min, banp_min
            )
        return allowed

    egress = direction_pair("egress", s_idx, d_idx)  # src is target side
    ingress = direction_pair("ingress", d_idx, s_idx)  # dst is target side
    return {"ingress": ingress, "egress": egress, "combined": ingress & egress}
