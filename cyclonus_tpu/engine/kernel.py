"""JAX verdict kernels (single-device path; sharded.py wraps these with
shard_map over a Mesh).

The decision procedure mirrors matcher/core.py (and thus the reference's
policy.go:138-174), restructured for the MXU:

  per direction d in {ingress, egress}:
    selpod[S, N]      selector s matches pod n's labels        (int compares)
    tmatch[T, N]      target t applies to pod n                (ns eq AND sel)
    peer_match[P, N]  peer p matches pod n (ports aside)       (kind switch)
    pport[P, Q]       peer p's port spec allows port case q    (int compares)
    tallow[T, N, Q]   = one_hot(peer->target) @ (peer_match & pport)
                        <- MXU matmul; the counts precompute takes
                        (one_hot & pport[:, q]) @ peer_match, the
                        cases stacked on the one-hot's rows
                        (target_allows_q), the tables kernel the
                        [P, N*Q] operand (direction_allowed)
    any_allow[n,m,Q]  = tmatch^T @ tallow                      <- MXU matmul
    allowed[n, m, q]  = NOT has_target[n] OR any_allow > 0

  combined[s, d, q] = egress_allowed[s, d, q] AND ingress_allowed[d, s, q]

All tensors are boolean/integer; matmuls run in bfloat16 with float32
accumulation, so the >0 threshold is exact (counts are small positive
integers, never rounded to zero).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

from . import first_import

first_import()  # ahead of `import jax`: this may be the process's first
import jax
import jax.numpy as jnp
import numpy as np

from ..utils import contracts
from .encoding import (
    EXP_DOES_NOT_EXIST,
    EXP_EXISTS,
    EXP_IN,
    EXP_NONE,
    EXP_NOT_IN,
    PACK_BITS,
    packed_words,
    NS_ALL,
    NS_EXACT,
    NS_SELECTOR,
    PEER_ALL,
    PEER_ALL_PORTS,
    PEER_IP,
    PEER_POD,
    POD_SELECTOR,
    PORT_INT,
    PORT_NAMED,
    PORT_NIL,
    TIER_ACT_ALLOW,
    TIER_ACT_NONE,
    TIER_ACT_PASS,
    TIER_ANP,
    TIER_BANP,
    TIER_KEY_NONE,
)


@contracts.args(
    sel_req_kv="(S, R) int32",
    sel_exp_op="(S, E) int32",
    sel_exp_key="(S, E) int32",
    sel_exp_vals="(S, E, V) int32",
    kv="(N, L) int32",
    key="(N, L) int32",
)
def selector_match(
    sel_req_kv: jnp.ndarray,  # [S, R]
    sel_exp_op: jnp.ndarray,  # [S, E]
    sel_exp_key: jnp.ndarray,  # [S, E]
    sel_exp_vals: jnp.ndarray,  # [S, E, V]
    kv: jnp.ndarray,  # [N, L]
    key: jnp.ndarray,  # [N, L]
) -> jnp.ndarray:
    """[S, N] bool: selector s matches label-set n.
    Mirrors kube/labels.py is_labels_match_label_selector."""
    # matchLabels: every non-pad required kv id must be present
    # present[S, N, R] = any_L(kv[n, l] == req[s, r])
    present = jnp.any(
        kv[None, :, None, :] == sel_req_kv[:, None, :, None], axis=-1
    )
    req_ok = jnp.all((sel_req_kv[:, None, :] == -1) | present, axis=-1)  # [S, N]

    # matchExpressions
    has_key = jnp.any(
        key[None, :, None, :] == sel_exp_key[:, None, :, None], axis=-1
    )  # [S, N, E]
    val_hit = jnp.any(
        (sel_exp_vals[:, None, :, :, None] != -1)
        & (kv[None, :, None, None, :] == sel_exp_vals[:, None, :, :, None]),
        axis=(-1, -2),
    )  # [S, N, E]
    op = sel_exp_op[:, None, :]  # [S, 1, E]
    exp_ok = jnp.where(
        op == EXP_NONE,
        True,
        jnp.where(
            op == EXP_IN,
            has_key & val_hit,
            jnp.where(
                op == EXP_NOT_IN,
                has_key & ~val_hit,
                jnp.where(op == EXP_EXISTS, has_key, ~has_key),
            ),
        ),
    )  # [S, N, E]
    return req_ok & jnp.all(exp_ok, axis=-1)


@contracts.args(
    selpod="(S, N) bool",
    selns="(S, M) bool",
    pod_ns_id="(N,) int32",
    pod_ip="(N,) uint32",
    pod_ip_valid="(N,) bool",
)
def direction_precompute(
    enc: Dict[str, jnp.ndarray],
    selpod: jnp.ndarray,  # [S, N] selector-vs-pod-labels
    selns: jnp.ndarray,  # [S, M] selector-vs-namespace-labels
    pod_ns_id: jnp.ndarray,  # [N]
    pod_ip: jnp.ndarray,  # [N] uint32
    pod_ip_valid: jnp.ndarray,  # [N] bool
) -> Dict[str, jnp.ndarray]:
    """Per-direction pod-resolution: tmatch[T, N], has_target[N],
    peer_match[P, N]."""
    # targets: namespace name equality + pod selector
    tmatch = (enc["target_ns"][:, None] == pod_ns_id[None, :]) & jnp.take(
        selpod, enc["target_sel"], axis=0
    )  # [T, N]
    has_target = jnp.any(tmatch, axis=0)  # [N]

    # pod-peer namespace matching
    ns_sel_match = jnp.take(
        selns, jnp.maximum(enc["peer_ns_sel"], 0), axis=0
    )  # [P, M] (garbage rows masked by kind below)
    ns_match_by_pod = jnp.take(ns_sel_match, pod_ns_id, axis=1)  # [P, N]
    ns_kind = enc["peer_ns_kind"][:, None]
    ns_ok = jnp.where(
        ns_kind == NS_EXACT,
        enc["peer_ns_id"][:, None] == pod_ns_id[None, :],
        jnp.where(ns_kind == NS_SELECTOR, ns_match_by_pod, True),
    )  # [P, N]

    # pod-peer pod matching
    pod_sel_match = jnp.take(
        selpod, jnp.maximum(enc["peer_pod_sel"], 0), axis=0
    )  # [P, N]
    pod_ok = jnp.where(
        enc["peer_pod_kind"][:, None] == POD_SELECTOR, pod_sel_match, True
    )

    # ip peers (IPv4 kernel; v6 rows are patched host-side)
    in_cidr = (
        enc["ip_is_v4"][:, None]
        & pod_ip_valid[None, :]
        & ((pod_ip[None, :] & enc["ip_mask"][:, None]) == enc["ip_base"][:, None])
    )  # [P, N]
    # pod_ip's 0-sentinel is a real address (0.0.0.0): an invalid pod
    # must never register as inside an except block, so the validity
    # mask guards this comparison too — today in_cidr already zeroes
    # those columns, but the except term must hold the contract on its
    # own (shapelint SC003 on the pod_ip/pod_ip_valid declaration)
    in_except = jnp.any(
        enc["ex_valid"][:, :, None]
        & pod_ip_valid[None, None, :]
        & (
            (pod_ip[None, None, :] & enc["ex_mask"][:, :, None])
            == enc["ex_base"][:, :, None]
        ),
        axis=1,
    )  # [P, N]
    ip_ok = in_cidr & ~in_except

    kind = enc["peer_kind"][:, None]
    peer_match = jnp.where(
        (kind == PEER_ALL) | (kind == PEER_ALL_PORTS),
        True,
        jnp.where(kind == PEER_IP, ip_ok, ns_ok & pod_ok),
    )  # [P, N]

    return {"tmatch": tmatch, "has_target": has_target, "peer_match": peer_match}


@contracts.args(
    q_port="(Q,) int32", q_name="(Q,) int32", q_proto="(Q,) int32"
)
def port_spec_allows(
    spec: Dict[str, jnp.ndarray],
    q_port: jnp.ndarray,  # [Q] int32
    q_name: jnp.ndarray,  # [Q] int32 (-1: unnamed)
    q_proto: jnp.ndarray,  # [Q] int32
) -> jnp.ndarray:
    """[P, Q] bool: peer p's port matcher allows port case q.
    Mirrors matcher/core.py SpecificPortMatcher.allows / AllPortMatcher."""
    kind = spec["item_kind"][:, :, None]  # [P, I, 1]
    proto_ok = spec["item_proto"][:, :, None] == q_proto[None, None, :]
    item_ok = jnp.where(
        kind == PORT_NIL,
        proto_ok,
        jnp.where(
            kind == PORT_INT,
            (spec["item_port"][:, :, None] == q_port[None, None, :]) & proto_ok,
            jnp.where(
                kind == PORT_NAMED,
                (spec["item_name"][:, :, None] == q_name[None, None, :]) & proto_ok,
                False,  # pad
            ),
        ),
    )  # [P, I, Q]
    rng_ok = (
        (spec["rng_from"][:, :, None] <= q_port[None, None, :])
        & (q_port[None, None, :] <= spec["rng_to"][:, :, None])
        & (spec["rng_proto"][:, :, None] == q_proto[None, None, :])
    )  # [P, R, Q]
    any_ok = jnp.any(item_ok, axis=1) | jnp.any(rng_ok, axis=1)
    return spec["spec_all"][:, None] | any_ok  # [P, Q]


def _bool_matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(a @ b) > 0 computed on the MXU: bf16 inputs, f32 accumulation."""
    return (
        jnp.matmul(
            a.astype(jnp.bfloat16),
            b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        > 0.0
    )


# --- bit-packed contraction (docs/DESIGN.md "Bit-packed kernel") ----------


def pack_bool_words_jnp(a: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """Device twin of encoding.pack_bool_words: pack a bool array
    32-per-int32-word along `axis`.  Bit values are summed as disjoint
    shifted powers of two — exactly the bitwise OR (no carries, bit 31
    rides the int32 sign) — so the twins are bit-identical by
    construction (pinned by tests/test_engine_packed.py)."""
    a = jnp.moveaxis(a, axis, 0)
    t = a.shape[0]
    w = packed_words(t)
    total = w * PACK_BITS  # tile: 32 — the 32-per-word round-up, SC004-proved
    pad = total - t
    if pad:
        a = jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], dtype=a.dtype)], axis=0
        )
    bits = a.reshape((w, PACK_BITS) + a.shape[1:]).astype(jnp.int32)
    shifts = jax.lax.shift_left(
        jnp.int32(1), jnp.arange(PACK_BITS, dtype=jnp.int32)
    ).reshape((1, PACK_BITS) + (1,) * (a.ndim - 1))
    words = jnp.sum(bits * shifts, axis=1, dtype=jnp.int32)
    return jnp.moveaxis(words, 0, axis)


#: the form of packed_any's contraction, in the AOT plan of every program
#: that can trace it (TpuPolicyEngine._aot_plan with pack on, sharded.grid
#: with pack=True): aot_cache.make_key sees nothing of a program's code,
#: and an executable built from another form (the lax.scan of PR 34 and
#: before) computes the same tables six times slower under the same key
PACKED_CONTRACTION = "any=reduce"


def packed_any(a_pk: jnp.ndarray, b_pk: jnp.ndarray) -> jnp.ndarray:
    """[A, B] bool: OR_w (a_pk[w, a] AND b_pk[w, b]) != 0 — the packed
    twin of `_bool_matmul(a.T, b) over a [T, A] x [T, B] contraction`,
    with the target axis pre-packed 32-per-word (a_pk [W, A], b_pk
    [W, B] int32); W is ceil(T/32), which is what cuts the contraction
    depth 32x vs the elementwise bool form.  ONE reduction over the word
    axis of the broadcast AND: the compiler fuses the broadcast into the
    reduce, so no [W, A, B] intermediate ever materializes and an output
    tile's running OR stays in registers (a lax.scan's [A, B] int32
    carry went through HBM once a word: 129.6 ms against 9.5 at
    [100, 10240] x [100, 10240] on a v5e chip, PERF.md section 6).  Bit
    31 rides the int32 sign and an AND never carries, so `!= 0` per word
    is exact.  That nothing materializes rests on the compiler's fusion:
    tests/test_tpu_compile.py holds the TPU compiler to it."""
    return jnp.any((a_pk[:, :, None] & b_pk[:, None, :]) != 0, axis=0)


@contracts.args(
    pod_ip="(N,) uint32",
    pod_ip_valid="(N,) bool",
    pmask="(K,) uint32",
    pbases="(K, B) uint32",
    pindex="(K, B) int32",
)
def lpm_partition_signature(
    pod_ip: jnp.ndarray,  # [N] uint32
    pod_ip_valid: jnp.ndarray,  # [N] bool
    pmask: jnp.ndarray,  # [K] uint32 partition masks (LPM order)
    pbases: jnp.ndarray,  # [K, B] uint32 sorted bases, 0xFFFFFFFF pad
    pindex: jnp.ndarray,  # [K, B] int32 global atom ids, -1 pad
) -> jnp.ndarray:
    """[K, N] int32 TSS/LPM partition signature (docs/DESIGN.md "CIDR
    tuple-space pre-classification"): the global atom index pod n's IP
    matches within partition k, or -1 (no base equals pod_ip & pmask[k],
    or the IP is invalid).  Within a partition at most one base can
    match — pod_ip & mask is one value — so the leftmost binary search
    over the sorted bases is the whole trie walk.  Bit-identical to the
    numpy twin cidrspace.CidrSpace.signature_host (pinned by
    tests/test_engine_cidr.py); pad slots are rejected by their -1
    pindex, never by the pad base value, so a real 255.255.255.255 base
    (which ties the pad and wins the leftmost search) still resolves."""
    key = pod_ip[None, :] & pmask[:, None]  # [K, N] uint32
    pos = jax.vmap(partial(jnp.searchsorted, side="left"))(pbases, key)
    pos = jnp.minimum(pos, pbases.shape[1] - 1)  # [K, N]
    hit = jnp.take_along_axis(pbases, pos, axis=1) == key
    idx = jnp.take_along_axis(pindex, pos, axis=1)
    return jnp.where(
        hit & (idx >= 0) & pod_ip_valid[None, :], idx, jnp.int32(-1)
    ).astype(jnp.int32)


def m_tp_onehot(enc: Dict, rows: Optional[int] = None) -> jnp.ndarray:
    """[T, P] bool peer->target one-hot, built ON DEVICE from the [P]
    peer_target index vector.  The dense matrix reaches ~70 MB at the
    10k-policy bench scale — shipping the index vector instead keeps it
    out of the host->device transfer (the saving is not measured on the
    current machine; the one-hot compare is free next to the verdict
    matmuls).  `rows` past T adds rows that match no peer (peer_target
    is a target index or the -1 pad)."""
    t = enc["target_ns"].shape[0] if rows is None else rows
    pt = enc["peer_target"]
    return pt[None, :] == jnp.arange(t, dtype=pt.dtype)[:, None]


#: the form of the target-allows contraction (target_allows_q: the port
#: cases on the one-hot's side), in the AOT plan of every program that
#: can trace tiled._precompute_cases (TpuPolicyEngine._aot_plan, the
#: sharded ring's plan): an executable of the earlier form, which built
#: peer_allow [P, N, Q] and relaid it, lies under another key
TARGET_ALLOWS = "tallow=qonehot"

#: targets a tile of the banded contraction (band_rows, target_allows_q):
#: a multiple of 32, so that every tile boundary is a packed word's, and
#: Q * TARGET_BAND = 256 one-hot rows a tile at Q = 2
TARGET_BAND = 128

#: the plan's name of the banded form, in place of TARGET_ALLOWS where a
#: program's tensors carry band_rows (target_allows_form): a program whose
#: tensors carry none keeps the dense form's key and executable
TARGET_ALLOWS_BAND = f"tallow=band{TARGET_BAND}"

#: the granule of a band's span (its peer rows a tile): the peer axis's
#: bucket granule past 128 (api._bucket_dim), so that a delta that moves a
#: span by a few rows keeps its shapes
BAND_SPAN_GRANULE = 128


def band_rows(peer_target: np.ndarray, t: int, headroom: int = 0) -> np.ndarray:
    """[ceil(t / TARGET_BAND), PSPAN] int32, on the host: tile k's peer
    rows (those whose peer_target lies in [k * TARGET_BAND, (k + 1) *
    TARGET_BAND)), in target order, stable, -1 past them.  PSPAN is the
    largest tile's rows rounded up to BAND_SPAN_GRANULE, `headroom`
    granules more (the serve slab reservation, as api._bucket_up steps
    the peer axis).  Pad rows (peer_target -1) lie in no tile."""
    pt = np.asarray(peer_target).reshape(-1)
    tiles = max(-(-t // TARGET_BAND), 1)
    real = np.flatnonzero((pt >= 0) & (pt < t))
    order = real[np.argsort(pt[real], kind="stable")]
    tile = pt[order] // TARGET_BAND
    count = np.bincount(tile, minlength=tiles)
    span = -(-max(int(count.max()), 1) // BAND_SPAN_GRANULE) * BAND_SPAN_GRANULE
    span += max(0, headroom) * BAND_SPAN_GRANULE
    out = np.full((tiles, span), -1, np.int32)
    start = np.concatenate([[0], np.cumsum(count)[:-1]])
    out[tile, np.arange(order.size) - start[tile]] = order
    return out


def band_engages(band: np.ndarray, t: int, p: int) -> bool:
    """The rule that decides the banded form, from the shapes: the band's
    one-hot, tiles x TARGET_BAND x PSPAN, is at most a quarter of the
    dense one's T x P (the MXU work of either contraction is that times
    Q x N)."""
    tiles, span = band.shape
    return 4 * tiles * TARGET_BAND * span <= t * p


def target_allows_form(*tensor_sets: Dict) -> str:
    """TARGET_ALLOWS_BAND where a direction of one of `tensor_sets` (tensor
    sets, or the shard_map specs of one) carries band_rows, else
    TARGET_ALLOWS."""
    banded = any(
        "band_rows" in ts.get(d, {})
        for ts in tensor_sets
        for d in ("ingress", "egress")
    )
    return TARGET_ALLOWS_BAND if banded else TARGET_ALLOWS


def tallow_attrs(tensors: Dict) -> Dict:
    """What an evaluation whose program traces _precompute_cases over the
    tensor set `tensors` says of its target-allows contraction
    (kernel.target_allows_q) in its flight entry and on its `engine.eval`
    span: tallow, the form a direction, ingress first ("band": the
    direction carries band_rows; "dense"), and tallow_span, the peer rows
    a target reads in the contraction, ingress + egress (a band's PSPAN;
    a dense direction's every row, P)."""
    forms, span = [], 0
    for direction in ("ingress", "egress"):
        enc = tensors[direction]
        banded = "band_rows" in enc
        forms.append("band" if banded else "dense")
        span += int(
            enc["band_rows"].shape[1] if banded else enc["peer_target"].shape[0]
        )
    return {"tallow": ",".join(forms), "tallow_span": span}


def band_peer_match(band: jnp.ndarray, peer_match: jnp.ndarray) -> jnp.ndarray:
    """[tiles, PSPAN, N] bool: the rows of peer_match [P, N] as `band`
    (band_rows) lays them, its -1 pads false rows."""
    real = band >= 0
    return jnp.take(peer_match, jnp.maximum(band, 0), axis=0) & real[:, :, None]


def _target_allows_band(
    enc: Dict, peer_band: jnp.ndarray, pport: jnp.ndarray, rows: int
) -> jnp.ndarray:
    """target_allows_q over a banded direction: each tile of TARGET_BAND
    targets against its own peer rows only.  The tile's one-hot, its
    peer rows' targets against the tile's target ids masked by each
    case's column of their `pport`, [tiles, Q * TB, PSPAN], runs against
    peer_band [tiles, PSPAN, N] in ONE batched MXU matmul; the result is
    laid [Q, tiles * TB, N] and cut or padded to `rows`."""
    band = enc["band_rows"]
    tiles, span = band.shape
    tb = TARGET_BAND
    q, n = pport.shape[1], peer_band.shape[2]
    idx = jnp.maximum(band, 0)
    target = jnp.where(band >= 0, jnp.take(enc["peer_target"], idx), -1)
    local = target - jnp.arange(tiles, dtype=target.dtype)[:, None] * tb
    hot = local[:, None, :] == jnp.arange(tb, dtype=target.dtype)[None, :, None]
    ports = jnp.moveaxis(jnp.take(pport, idx, axis=0), 2, 1)  # [tiles, Q, S]
    folded = (ports[:, :, None, :] & hot[:, None, :, :]).reshape(
        tiles, q * tb, span
    )
    tallow = jax.lax.dot_general(
        folded.astype(jnp.bfloat16),
        peer_band.astype(jnp.bfloat16),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.bfloat16,
    )  # [tiles, Q * TB, N]
    out = jnp.moveaxis((tallow > 0).reshape(tiles, q, tb, n), 1, 0).reshape(
        q, tiles * tb, n
    )
    if rows <= tiles * tb:
        return out[:, :rows]
    return jnp.pad(out, ((0, 0), (0, rows - tiles * tb), (0, 0)))


def target_allows_q(
    enc: Dict,
    peer_match: jnp.ndarray,
    pport: jnp.ndarray,
    rows: Optional[int] = None,
) -> jnp.ndarray:
    """[Q, T, N] bool: target t allows pod n on the PEER side for port
    case q, OR_p (peer_target[p] == t) & pport[p, q] & peer_match[p, n].
    The port cases sit on the SMALL side of the contraction: the one-hot
    masked by each case's column of `pport`, [Q * T, P], against
    peer_match [P, N] as it is, in one MXU matmul with the flops of
    `m_tp @ (peer_match & pport)` and no [P, N, Q] operand or relayout
    of one.  `rows` (m_tp_onehot's) pads T with false rows, so that a
    packer of T finds nothing to pad.  bf16 accumulation of 0/1 terms:
    `> 0` is exact (tiled.py's memory note).

    Where `enc` carries band_rows (api._band_tensors laid them), every
    peer row belongs to one target, so the one-hot's ones lie in a band:
    `peer_match` is then band_peer_match's [tiles, PSPAN, N] and each
    tile of TARGET_BAND targets contracts its own rows alone
    (_target_allows_band), the same OR over the same rows."""
    if "band_rows" in enc:
        t = enc["target_ns"].shape[0] if rows is None else rows
        return _target_allows_band(enc, peer_match, pport, t)
    m_tp = m_tp_onehot(enc, rows)  # [T, P]
    q, t = pport.shape[1], m_tp.shape[0]
    folded = (pport.T[:, None, :] & m_tp[None, :, :]).reshape(q * t, -1)
    tallow = jnp.matmul(
        folded.astype(jnp.bfloat16),
        peer_match.astype(jnp.bfloat16),
        preferred_element_type=jnp.bfloat16,
    )  # [Q * T, N]
    return (tallow > 0).reshape(q, t, peer_match.shape[1])


def direction_allowed(
    tmatch_target: jnp.ndarray,  # [T, Nt] target-side pods
    has_target: jnp.ndarray,  # [Nt]
    m_tp: jnp.ndarray,  # [T, P] peer->target one-hot
    peer_match: jnp.ndarray,  # [P, Np] peer-side pods
    pport: jnp.ndarray,  # [P, Q]
    pack: bool = False,
) -> jnp.ndarray:
    """[Nt, Np, Q] bool: direction verdict for (target-side pod, peer-side
    pod, port case).  With pack=True the dominant target-axis contraction
    runs over 32-per-word packed bitmaps (packed_any) instead of the
    bf16 matmul — bit-identical by construction, gated differentially by
    the fuzz and packed parity suites."""
    n_p, n_np = peer_match.shape
    q = pport.shape[1]
    # peer_allow[P, Np*Q]
    peer_allow = (peer_match[:, :, None] & pport[:, None, :]).reshape(n_p, n_np * q)
    tallow = _bool_matmul(m_tp, peer_allow)  # [T, Np*Q]
    if pack:
        any_allow = packed_any(
            pack_bool_words_jnp(tmatch_target),  # [W, Nt]
            pack_bool_words_jnp(tallow),  # [W, Np*Q]
        )
    else:
        any_allow = _bool_matmul(tmatch_target.T, tallow)  # [Nt, Np*Q]
    allowed = (~has_target[:, None]) | any_allow
    return allowed.reshape(-1, n_np, q)


# --- precedence-tier resolution epilogue ----------------------------------
#
# The ANP/BANP lattice (docs/DESIGN.md "Precedence tiers") replaces the
# bool-OR assumption with FIRST-MATCH-BY-PRIORITY: tier rows carry an
# int8 action and an int32 rank (encoding.TierDirectionEncoding), and the
# first matching rule of a tier is the min over matching rows of the
# combined key rank * 4 + action (actions are 1..3, so key % 4 recovers
# the winning action and min-of-keys == first-match because ranks are the
# resolution order).  Rows of one rule share its rank, which makes the
# within-rule peer OR exact under the min.  TIER_KEY_NONE (2^30) is the
# no-match identity.  All of it composes with the class-compressed grid
# unchanged: tier rules observe pods only through (ns id, shared-table
# selector matches), both part of the class signature.


def tier_scope_match(
    ns_sel: jnp.ndarray,  # [G] selector ids (namespace labels)
    pod_kind: jnp.ndarray,  # [G] POD_ALL | POD_SELECTOR
    pod_sel: jnp.ndarray,  # [G] selector ids (pod labels; -1 when ALL)
    selpod: jnp.ndarray,  # [S, N]
    selns: jnp.ndarray,  # [S, M]
    pod_ns_id: jnp.ndarray,  # [N]
) -> jnp.ndarray:
    """[G, N] bool: tier scope g (a subject or peer) matches pod n —
    namespace labels via selns, pod labels via selpod (the shared
    selector table; mirrors tiers.model.scope_matches)."""
    ns_by_pod = jnp.take(
        jnp.take(selns, ns_sel, axis=0), pod_ns_id, axis=1
    )  # [G, N]
    pod_m = jnp.take(selpod, jnp.maximum(pod_sel, 0), axis=0)  # [G, N]
    pod_ok = jnp.where(pod_kind[:, None] == POD_SELECTOR, pod_m, True)
    return ns_by_pod & pod_ok


def tier_rows(tensors: Dict) -> int:
    """The bucketed tier rule rows of a tensor set, ingress + egress; 0 on
    an untiered set."""
    if "tiers" not in tensors:
        return 0
    return sum(
        int(tensors["tiers"][d]["action"].shape[0]) for d in ("ingress", "egress")
    )


def tier_words(tensors: Dict) -> int:
    """The packed rule words tier_first_match_keys reduces over, ingress +
    egress; 0 on an untiered set."""
    if "tiers" not in tensors:
        return 0
    return sum(
        packed_words(int(tensors["tiers"][d]["action"].shape[0]))
        for d in ("ingress", "egress")
    )


def tier_keys(tenc: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(anp_key [G], banp_key [G]) int32 priority keys: rank * 4 + action
    for real rows of each tier, TIER_KEY_NONE elsewhere (pad rows carry
    action 0 and are inert in both)."""
    act = tenc["action"].astype(jnp.int32)  # int8 verdict slab -> key arith
    key = tenc["rank"] * 4 + act
    tier = tenc["tier"].astype(jnp.int32)
    valid = act > TIER_ACT_NONE
    none = jnp.int32(TIER_KEY_NONE)
    anp = jnp.where(valid & (tier == TIER_ANP), key, none)
    banp = jnp.where(valid & (tier == TIER_BANP), key, none)
    return anp, banp


#: the form of tier_first_match_keys, in the AOT plan of every TIERED
#: program (TpuPolicyEngine._aot_plan, sharded.grid); untiered plans stay
#: as they were.  The key sees no code, and an executable of the 8-row
#: scan this form replaced gives the same tables under the same key,
#: carrying two keys through HBM once a chunk
TIER_FIRST_MATCH = "first=words"


def tier_first_match_keys(
    subj: jnp.ndarray,  # [G, A] bool — subject side (target pods)
    peerq: jnp.ndarray,  # [G, B, Q] bool — peer side x port cases
    anp_key: jnp.ndarray,  # [G] int32
    banp_key: jnp.ndarray,  # [G] int32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """([A, B, Q], [A, B, Q]) int32: each tier's first matching rule a
    cell, keyed w * 4 + action (w the packed rule word that holds the
    rule's row), TIER_KEY_NONE where no row of the tier matches.  The two
    things resolve_tier_lattice reads of a key, `< TIER_KEY_NONE` and
    `% 4`, are those of the min of rank * 4 + action over the matching
    rows; the value itself is ordered by word, not by rank.

    PRECONDITION (encoding.TierDirectionEncoding; tests/
    test_tier_first_match.py): within a tier, row order is key order, and
    pad rows (TIER_KEY_NONE in both keys) come last.  Rows of equal key
    are one rule's peer rows and share its action.  The lowest matching
    row of a tier then holds the tier's first match.  With the rule axis
    packed 32 rows a word (LSB = lowest row, pack_bool_words_jnp), that
    row is the lowest set bit `m & -m` of the first non-zero word of
    m = subj_pk & peerq_pk over the tier's rows, and its action is read
    from two per-word masks of the tier's key bits (action = key % 4 is
    1 - 3, so a matching row sets at least one).  ONE min-reduction over
    the word axis a tier: the compiler fuses the broadcast into the
    reduce as it does packed_any's, so no [W, A, B, Q] match is
    materialized and no key is carried through HBM (tests/
    test_tpu_compile.py holds the TPU compiler to it).  Bit 31 rides the
    int32 sign: `m & -m` of INT_MIN is INT_MIN, and every test of a word
    is `!= 0`.  The word depth follows G; G is any size (padded to whole
    words with rows of no tier)."""
    none = jnp.int32(TIER_KEY_NONE)
    peer_pk = pack_bool_words_jnp(peerq)  # [W, B, Q]
    base = 4 * jnp.arange(peer_pk.shape[0], dtype=jnp.int32)[:, None, None, None]

    def first(key):  # key [G] -> [A, B, Q]
        mine = key < TIER_KEY_NONE  # the tier's real rows
        s_pk = pack_bool_words_jnp(subj & mine[:, None])  # [W, A]
        bit0 = pack_bool_words_jnp(mine & ((key & 1) != 0))[:, None, None, None]
        bit1 = pack_bool_words_jnp(mine & ((key & 2) != 0))[:, None, None, None]
        m = s_pk[:, :, None, None] & peer_pk[:, None, :, :]  # [W, A, B, Q]
        low = m & -m
        c0 = (low & bit0) != 0
        c1 = (low & bit1) != 0
        v = jnp.where(
            c1,
            jnp.where(c0, base + 3, base + 2),
            jnp.where(c0, base + 1, none),
        )
        return jnp.min(v, axis=0)

    return first(anp_key), first(banp_key)


def resolve_tier_lattice(
    np_allowed: jnp.ndarray,  # NetworkPolicy-tier verdict (any shape)
    has_target_b: jnp.ndarray,  # bool, broadcastable to np_allowed
    anp_min: jnp.ndarray,  # int32 min ANP key, same shape as np_allowed
    banp_min: jnp.ndarray,
) -> jnp.ndarray:
    """The lattice fold: ANP first-match (Allow/Deny final, Pass falls
    through), then the NetworkPolicy tier WHERE a target selects the pod
    (final), then BANP first-match, then default-allow.  np_allowed is
    the existing direction verdict (~has_target | any_allow): where
    has_target holds it equals the NP-tier verdict, and elsewhere it is
    bypassed, so the epilogue composes with every evaluator's existing
    output unchanged."""
    anp_act = jnp.where(anp_min < TIER_KEY_NONE, anp_min % 4, TIER_ACT_NONE)
    banp_act = jnp.where(banp_min < TIER_KEY_NONE, banp_min % 4, TIER_ACT_NONE)
    below = jnp.where(
        has_target_b,
        np_allowed,
        jnp.where(
            banp_act == TIER_ACT_NONE, True, banp_act == TIER_ACT_ALLOW
        ),
    )
    return jnp.where(
        (anp_act == TIER_ACT_NONE) | (anp_act == TIER_ACT_PASS),
        below,
        anp_act == TIER_ACT_ALLOW,
    )


def tier_direction_arrays(
    tenc: Dict[str, jnp.ndarray],
    selpod: jnp.ndarray,
    selns: jnp.ndarray,
    pod_ns_id: jnp.ndarray,
    q_port: jnp.ndarray,
    q_name: jnp.ndarray,
    q_proto: jnp.ndarray,
) -> Dict[str, jnp.ndarray]:
    """Per-direction tier precompute over ONE pod set (grid kernels use
    the same set for both sides): subj [G, N], peerq [G, N, Q], and the
    two [G] key vectors."""
    subj = tier_scope_match(
        tenc["subj_ns_sel"], tenc["subj_pod_kind"], tenc["subj_pod_sel"],
        selpod, selns, pod_ns_id,
    )
    peer = tier_scope_match(
        tenc["peer_ns_sel"], tenc["peer_pod_kind"], tenc["peer_pod_sel"],
        selpod, selns, pod_ns_id,
    )
    pport = port_spec_allows(tenc["port_spec"], q_port, q_name, q_proto)
    anp_key, banp_key = tier_keys(tenc)
    return {
        "subj": subj,
        "peerq": peer[:, :, None] & pport[:, None, :],
        "anp_key": anp_key,
        "banp_key": banp_key,
    }


@partial(jax.jit, static_argnames=("pack",))
def evaluate_grid_kernel(tensors: Dict, pack: bool = False) -> Dict[str, jnp.ndarray]:
    """Full-grid verdict on one device.

    tensors: pytree with keys
      sel_*: selector tables; pod_*: cluster pod arrays; ns_kv/ns_key;
      ingress/egress: per-direction encodings (dicts incl. peer_target);
      q_port/q_name/q_proto: [Q] port cases.
    `pack` (static; resolved by the caller via encoding.pack_enabled)
    routes the target-axis contraction through the 32-per-word packed
    bitmaps.  Returns ingress[q, d, s], egress[q, s, d],
    combined[q, s, d].
    """
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
        peer_match = pre["peer_match"]
        if "host_ip_match" in enc:
            # patch host-evaluated ip-peer rows (IPv6 fallback)
            peer_match = jnp.where(
                enc["host_ip_mask"][:, None], enc["host_ip_match"], peer_match
            )
        pport = port_spec_allows(
            enc["port_spec"],
            tensors["q_port"],
            tensors["q_name"],
            tensors["q_proto"],
        )
        out[direction] = direction_allowed(
            pre["tmatch"], pre["has_target"], m_tp_onehot(enc), peer_match,
            pport, pack=pack,
        )
        if "tiers" in tensors:
            # precedence-tier resolution epilogue: same trace, one
            # device execution still (docs/DESIGN.md "Precedence tiers")
            ta = tier_direction_arrays(
                tensors["tiers"][direction],
                selpod,
                selns,
                tensors["pod_ns_id"],
                tensors["q_port"],
                tensors["q_name"],
                tensors["q_proto"],
            )
            anp_min, banp_min = tier_first_match_keys(
                ta["subj"], ta["peerq"], ta["anp_key"], ta["banp_key"]
            )
            out[direction] = resolve_tier_lattice(
                out[direction],
                pre["has_target"][:, None, None],
                anp_min,
                banp_min,
            )

    # ingress is indexed [dst, src, q]; egress [src, dst, q]
    combined = out["egress"] & jnp.swapaxes(out["ingress"], 0, 1)
    # [q, ., .] layout for the GridVerdict API; transposing here keeps the
    # whole evaluation a single device execution (each extra dispatch costs
    # a host<->device round trip).
    return {
        "ingress": jnp.moveaxis(out["ingress"], -1, 0),
        "egress": jnp.moveaxis(out["egress"], -1, 0),
        "combined": jnp.moveaxis(combined, -1, 0),
    }


#: cells a result word holds, one byte each
WORD_CELLS = 4
#: a table of words is padded to whole 32-bit device tiles, rows to 8 and
#: words to 128: the runtime then keeps the row-major layout (for a shape
#: that would pad its tiles it picks the axis order that pads least, and
#: uint32[2, 10000, 2500] came to the host with a strided last axis, which
#: no boolean view can be laid over: my chip run, PR 27)
WORD_TILE = (8, 128)
#: the format's name in the AOT plan of the programs that emit it ("grid",
#: "grid.classes"): aot_cache.make_key sees nothing of a program's code or
#: result, so an executable that returns its tables in another form (the
#: boolean era's, or other tiles) must not be found under the same key
WORD_FORMAT = f"out=w{8 * WORD_CELLS}.{WORD_TILE[0]}x{WORD_TILE[1]}"


def cell_words(lanes) -> jnp.ndarray:
    """THE result format of the single-device grid programs: the four
    `lanes` (bool [..., W]; lane k holds cells k, 4+k, 8+k, ... of the
    last axis) as uint32 [..., W up to a multiple of 128], cell 4w+k in
    bits 8k..8k+7 of word w, each byte exactly 0 or 1, the pad words 0.
    On a little-endian host the words' bytes ARE the boolean table
    (GridVerdict views them, no copy); the runtime un-tiles every
    fetched buffer on the host, and a 32-bit element four times faster
    than a one-byte one (PERF.md section 6, PR 27)."""
    return pad_words(lane_words(lanes))


def lane_words(lanes) -> jnp.ndarray:
    """cell_words before its pad: uint32 [..., W], exactly as wide as
    the lanes.  A mesh program packs a device's piece of a row with
    this, lays the pieces side by side and pads the whole row once."""
    words = lanes[0].astype(jnp.uint32)
    for k in range(1, WORD_CELLS):
        words = words | (lanes[k].astype(jnp.uint32) << (8 * k))
    return words


def pad_words(words: jnp.ndarray) -> jnp.ndarray:
    """Zero words up to a multiple of WORD_TILE's 128 on the last axis."""
    pad = [(0, 0)] * (words.ndim - 1) + [(0, -words.shape[-1] % WORD_TILE[1])]
    return jnp.pad(words, pad)


def host_cells(words: np.ndarray, n: int) -> np.ndarray:
    """The host's side of cell_words: bool [Q, n, n] over the fetched
    words of a table, a view and not a copy (a buffer whose last axis is
    strided, which WORD_TILE rules out, raises here).  "<u4" is this
    host's own order wherever JAX runs (a no-op), and still right where
    it is not."""
    return words.astype("<u4", copy=False).view(np.bool_)[:, :n, :n]


def _real_bytes(n: int, width: int) -> jnp.ndarray:
    """uint32 [width]: 0x01 in every byte that holds one of the `n` real
    cells of a row of words, 0 in its pad bytes."""
    real = np.zeros(width * WORD_CELLS, dtype=np.uint8)
    real[:n] = 1
    return jnp.asarray(real.view("<u4").astype(np.uint32))


@partial(jax.jit, static_argnames=("pack",))
def evaluate_grid_words(tensors: Dict, pack: bool = False) -> Dict[str, jnp.ndarray]:
    """evaluate_grid_kernel with each table as cell_words over the
    bucketed pod axis (a multiple of eight, so there is no pad byte
    short of the pad words): uint32 [Q, N, >= N/4].  Kernel and words
    trace into one program: one device execution."""
    return {
        k: cell_words([v[..., i::WORD_CELLS] for i in range(WORD_CELLS)])
        for k, v in evaluate_grid_kernel(tensors, pack=pack).items()
    }


@contracts.args(class_of="(N,) int32")
def gather_class_words(
    out: Dict[str, jnp.ndarray],
    class_of: jnp.ndarray,
    rows: Optional[jnp.ndarray] = None,
) -> Dict[str, jnp.ndarray]:
    """Broadcast class-grid verdicts back to the full pod x pod grid, as
    cell_words: uint32 [Q, N up to a multiple of 8, ceil(N/4) up to a
    multiple of 128], every pad byte 0.  With `rows` (int32 [R], the
    class of each row to write, -1 for a pad row) only those rows are
    written, [Q, R, W]: a device of a mesh gathers its own rows of a
    table and never holds the others (sharded._class_words).

    out: {ingress, egress, combined} [Q, C*, C*] bool over the (possibly
    bucketing-padded) class axes; class_of: [N] int32 pod -> class map
    (values < the real class count, so pad rows are never gathered).
    Cell (q, i, j) copies class cell (q, class_of[i], class_of[j]),
    which is exact by the class signature's completeness
    (encoding.compute_pod_classes).  The COLUMNS go first, four int32
    gathers (one a lane) on the small class grid, which build the words
    of every class row; one row gather then writes the result once, in
    its final layout (rows first, and four gathers on the full width,
    took 10.0 ms against 7.7: my chip run, PR 27).  Designed to trace
    INSIDE the caller's jit so grid + gather stay one device
    execution."""
    n = class_of.shape[0]

    def g(a: jnp.ndarray) -> jnp.ndarray:
        # the pad cells and the pad rows gather zeros: a column and a row
        # of them, appended behind the classes
        zeros = a.shape[1]
        a = jnp.pad(a, ((0, 0), (0, 1), (0, 1)))
        cols = jnp.pad(class_of, (0, -n % WORD_CELLS), constant_values=zeros)
        if rows is None:
            own = jnp.pad(
                class_of, (0, -n % WORD_TILE[0]), constant_values=zeros
            )
        else:
            own = jnp.where(rows < 0, zeros, rows)
        words = cell_words(
            [jnp.take(a, cols[k::WORD_CELLS], axis=2) for k in range(WORD_CELLS)]
        )
        return jnp.take(words, own, axis=1)

    return {k: g(v) for k, v in out.items()}


@jax.jit
def rule_firing_kernel(shared: Dict, enc: Dict) -> Dict[str, jnp.ndarray]:
    """Per-RULE firing-mask components for one direction — the batched
    variant of the verdict path that the analysis layer
    (cyclonus_tpu.analysis) audits on.

    The firing mask of flat peer rule p over (target-side pod n,
    peer-side pod m, port case q) is the rank-1 product

        fire[p, n, m, q] = rule_tmatch[p, n] & peer_match[p, m] & pport[p, q]

    so returning the three factors is the whole mask without ever
    materializing [P, N, N, Q].  rule_tmatch gathers each rule's
    target row (a rule fires only where its OWN target applies), with
    pad rules (peer_target -1) masked to all-False."""
    selpod = selector_match(
        shared["sel_req_kv"],
        shared["sel_exp_op"],
        shared["sel_exp_key"],
        shared["sel_exp_vals"],
        shared["pod_kv"],
        shared["pod_key"],
    )
    selns = selector_match(
        shared["sel_req_kv"],
        shared["sel_exp_op"],
        shared["sel_exp_key"],
        shared["sel_exp_vals"],
        shared["ns_kv"],
        shared["ns_key"],
    )
    pre = direction_precompute(
        enc,
        selpod,
        selns,
        shared["pod_ns_id"],
        shared["pod_ip"],
        shared["pod_ip_valid"],
    )
    peer_match = pre["peer_match"]
    if "host_ip_match" in enc:
        peer_match = jnp.where(
            enc["host_ip_mask"][:, None], enc["host_ip_match"], peer_match
        )
    pport = port_spec_allows(
        enc["port_spec"],
        shared["q_port"],
        shared["q_name"],
        shared["q_proto"],
    )
    pt = enc["peer_target"]
    rule_tmatch = jnp.take(pre["tmatch"], jnp.maximum(pt, 0), axis=0) & (
        pt >= 0
    )[:, None]
    return {
        "rule_tmatch": rule_tmatch,  # [P, N] bool
        "peer_match": peer_match,  # [P, N] bool
        "pport": pport,  # [P, Q] bool
        "has_target": pre["has_target"],  # [N] bool
    }


@partial(jax.jit, static_argnames=("n",))
def grid_row_counts_kernel(ingress, egress, combined, n: int) -> jnp.ndarray:
    """int32 [3, Q, n]: the allowed cells of every real row of the three
    tables, whichever form they are in (bool [Q, >=n, >=n] or cell_words
    of it) - one execution and one small transfer.  Rows, not totals: a
    row's count fits int32 at any size, the host adds them in int64."""

    def rows(a: jnp.ndarray) -> jnp.ndarray:
        if a.dtype == jnp.uint32:
            # a byte is 0 or 1, so a word's set bits are its allowed cells
            width = -(-n // WORD_CELLS)
            a = a[:, :n, :width] & _real_bytes(n, width)
            return jnp.sum(jax.lax.population_count(a), axis=2, dtype=jnp.int32)
        return jnp.sum(a[:, :n, :n], axis=2, dtype=jnp.int32)

    return jnp.stack([rows(ingress), rows(egress), rows(combined)])


@jax.jit
def grid_cells_kernel(ingress, egress, combined, q, s, d) -> jnp.ndarray:
    """bool [K, 3]: (ingress, egress, combined) of K (q, src, dst) cells
    in either form - one device gather, one tiny transfer."""

    def cells(a: jnp.ndarray, r, c) -> jnp.ndarray:
        if a.dtype == jnp.uint32:
            word = a[q, r, c // WORD_CELLS]
            return ((word >> (8 * (c % WORD_CELLS)).astype(jnp.uint32)) & 1) != 0
        return a[q, r, c]

    return jnp.stack(
        [cells(ingress, d, s), cells(egress, s, d), cells(combined, s, d)],
        axis=1,
    )
