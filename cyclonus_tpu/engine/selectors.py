"""The host selector pass: which selector of the engine's one selector table
matches which pod, computed over the cluster's DISTINCT pod label rows.

A selector reads a pod's labels and nothing else, and every replica of a
workload carries the same labels, so the pass evaluates the U distinct
(pod_kv, pod_key) rows and a pod reads the column of its row: `SelectorRows`
holds match [S, U] and row_of [N], never an [S, N] matrix.  Its two consumers,
dead-target compaction (api._compact_dead_targets) and the class signature
(encoding.compute_pod_classes), read it as it is; `pods()` spreads it over the
pods for the few that want [S, N] (the slab plan, the tests).

The pass is an inverted index from each (key, value) id and each key id to the
label rows that hold it, as bitsets (one Python int a posting, one bit a row):
a selector is the AND of its terms, an In term the OR of its values' postings,
NotIn the key's posting less that OR; one operation a term over ceil(U / 64)
words.  Its cost follows the selectors' terms, not S x U x V as the op-for-op
twin of kernel.selector_match does (`selector_match_np`, kept as the serve
path's pass over pods and the reference the tests hold the index to): on one
host core 0.007 s against the twin's 1.48 s over k8s-envelope-150k's
rehearsal (820 selectors x 330 rows), 0.048 s against 40.8 s over five of its
namespaces (4,117 x 1,642), and no slower over the cyclic generator's 100 rows.
It runs on the host, inside the constructor, before any device program exists.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np

from .encoding import EXP_EXISTS, EXP_IN, EXP_NONE, EXP_NOT_IN

#: word operations of the pass beyond which it does not run at all (the
#: constructor then skips compaction, and auto class compression with it)
INDEX_OPS = 1 << 32


def selector_match_np(
    sel_req_kv: np.ndarray,  # [S, R]
    sel_exp_op: np.ndarray,  # [S, E]
    sel_exp_key: np.ndarray,  # [S, E]
    sel_exp_vals: np.ndarray,  # [S, E, V]
    kv: np.ndarray,  # [N, L]
    key: np.ndarray,  # [N, L]
) -> np.ndarray:
    """[S, N] bool — numpy twin of kernel.selector_match, op for op.

    Pure numpy on purpose: the device twin would be routed to CPU with
    jax.devices("cpu"), and that call initialises the backend — encode
    must stay host-only.  Twin equality is pinned by
    tests/test_engine_pallas.py::test_selector_match_np_twin."""
    present = np.any(
        kv[None, :, None, :] == sel_req_kv[:, None, :, None], axis=-1
    )
    req_ok = np.all((sel_req_kv[:, None, :] == -1) | present, axis=-1)  # [S, N]

    has_key = np.any(
        key[None, :, None, :] == sel_exp_key[:, None, :, None], axis=-1
    )  # [S, N, E]
    val_hit = np.any(
        (sel_exp_vals[:, None, :, :, None] != -1)
        & (kv[None, :, None, None, :] == sel_exp_vals[:, None, :, :, None]),
        axis=(-1, -2),
    )  # [S, N, E]
    op = sel_exp_op[:, None, :]  # [S, 1, E]
    exp_ok = np.where(
        op == EXP_NONE,
        True,
        np.where(
            op == EXP_IN,
            has_key & val_hit,
            np.where(
                op == EXP_NOT_IN,
                has_key & ~val_hit,
                np.where(op == EXP_EXISTS, has_key, ~has_key),
            ),
        ),
    )  # [S, N, E]
    return req_ok & np.all(exp_ok, axis=-1)


def match_rows_dense(tensors: Dict, kv: np.ndarray, key: np.ndarray, chunk: int = 0):
    """[S, len(kv)] bool: selector_match_np over the rows (kv, key) in row
    chunks (the serve path's pass over pods, api._selector_pod_matches_host).  The chunk scales inversely with the selector count so the
    [S, chunk, ...] broadcast intermediates stay bounded in BOTH axes — a
    fixed chunk would let a large selector table OOM the encode."""
    n = kv.shape[0]
    s = tensors["sel_req_kv"].shape[0]
    if not chunk:
        # budget the [S, chunk, R, L] and [S, chunk, E, V, L] broadcast
        # intermediates, not just S * chunk: a label-heavy cluster (large
        # R/E/V/L) scales the temporaries by the trailing dims too
        chunk = max(64, (1 << 24) // (max(s, 1) * _trailing(tensors, kv)))
    outs = [
        selector_match_np(
            tensors["sel_req_kv"],
            tensors["sel_exp_op"],
            tensors["sel_exp_key"],
            tensors["sel_exp_vals"],
            kv[lo : lo + chunk],
            key[lo : lo + chunk],
        )
        for lo in range(0, n, chunk)
    ]
    if not outs:
        return np.zeros((s, 0), dtype=bool)
    return np.concatenate(outs, axis=1)


def _trailing(tensors: Dict, kv: np.ndarray) -> int:
    """max(R*L, E*V*L, 1): selector_match_np's element operations a
    (selector, row) pair."""
    r = int(tensors["sel_req_kv"].shape[1])
    e, v = (int(x) for x in tensors["sel_exp_vals"].shape[1:3])
    l = int(kv.shape[1])
    return max(r * l, e * v * l, 1)


def match_rows_index(tensors: Dict, kv: np.ndarray, key: np.ndarray) -> np.ndarray:
    """[S, len(kv)] bool, the bits selector_match_np gives over the rows
    (kv, key), by the inverted index (module docstring)."""
    u = int(kv.shape[0])
    req = tensors["sel_req_kv"].tolist()
    ops = tensors["sel_exp_op"].tolist()
    keys = tensors["sel_exp_key"].tolist()
    vals = tensors["sel_exp_vals"].tolist()
    s = len(req)
    kv_rows: Dict[int, int] = {}
    key_rows: Dict[int, int] = {}
    for col in range(kv.shape[1]):
        for r, (a, b) in enumerate(zip(kv[:, col].tolist(), key[:, col].tolist())):
            bit = 1 << r
            if a != -1:
                kv_rows[a] = kv_rows.get(a, 0) | bit
            if b != -1:
                key_rows[b] = key_rows.get(b, 0) | bit
    every = (1 << u) - 1
    nbytes = (u + 7) // 8
    out = []
    for i in range(s):
        m = every
        for a in req[i]:
            if a != -1:
                m &= kv_rows.get(a, 0)
        for op, k, vs in zip(ops[i], keys[i], vals[i]):
            if op == EXP_NONE or not m:
                continue
            has = key_rows.get(k, 0)
            if op == EXP_IN or op == EXP_NOT_IN:
                hit = 0
                for v in vs:
                    if v != -1:
                        hit |= kv_rows.get(v, 0)
                m &= has & hit if op == EXP_IN else has & ~hit
            elif op == EXP_EXISTS:
                m &= has
            else:  # EXP_DOES_NOT_EXIST
                m &= ~has
        out.append(m.to_bytes(nbytes, "little"))
    if not s or not u:
        return np.zeros((s, u), dtype=bool)
    packed = np.frombuffer(b"".join(out), dtype=np.uint8).reshape(s, nbytes)
    return np.unpackbits(packed, axis=1, count=u, bitorder="little").astype(bool)


def label_rows(tensors: Dict) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(kv [U, L], key [U, L], row_of [N] int32): the distinct (pod_kv,
    pod_key) rows of the pods and each pod's row."""
    kv, key = tensors["pod_kv"], tensors["pod_key"]
    n = int(kv.shape[0])
    if n == 0:
        return kv[:0], key[:0], np.zeros((0,), dtype=np.int32)
    both = np.ascontiguousarray(np.concatenate([kv, key], axis=1).astype(np.int32))
    rows = both.view(np.dtype((np.void, both.shape[1] * 4))).reshape(n)
    _, first, inv = np.unique(rows, return_index=True, return_inverse=True)
    return kv[first], key[first], inv.astype(np.int32).reshape(n)


@dataclass
class Plan:
    """What the pass will cost, from the shapes: its word operations and the
    label rows it runs over."""

    ops: int
    kv: np.ndarray
    key: np.ndarray
    row_of: np.ndarray

    @property
    def fits(self) -> bool:
        return self.ops <= INDEX_OPS


def plan(tensors: Dict) -> Plan:
    kv, key, row_of = label_rows(tensors)
    terms = (
        int(np.count_nonzero(tensors["sel_req_kv"] != -1))
        + int(np.count_nonzero(tensors["sel_exp_op"] != EXP_NONE))
        + int(np.count_nonzero(tensors["sel_exp_vals"] != -1))
    )
    s = int(tensors["sel_req_kv"].shape[0])
    return Plan((terms + s) * -(-int(kv.shape[0]) // 64), kv, key, row_of)


@dataclass
class SelectorRows:
    """The pass's result: selector s matches pod n iff match[s, row_of[n]]."""

    match: np.ndarray  # [S, U] bool
    row_of: np.ndarray  # [N] int32

    @property
    def n_rows(self) -> int:
        return int(self.match.shape[1])

    def pods(self) -> np.ndarray:
        """[S, N] bool: the pass spread over the pods."""
        return self.match[:, self.row_of]

    def pattern_rank(self) -> np.ndarray:
        """[U] int32: the rank of each row's column of match, packed along S
        as encoding.pod_signatures packs a pod's, in the lexicographic order
        of those bytes; equal columns share a rank."""
        packed = np.ascontiguousarray(np.packbits(self.match, axis=0).T)
        u = packed.shape[0]
        rows = packed.view(np.dtype((np.void, packed.shape[1]))).reshape(u)
        _, inv = np.unique(rows, return_inverse=True)
        return inv.astype(np.int32).reshape(u)

    def live_targets(
        self, pod_ns_id: np.ndarray, t_ns: np.ndarray, t_sel: np.ndarray
    ) -> np.ndarray:
        """[T] bool: target t's selector matches some pod of its namespace
        (a target of namespace -1 matches none)."""
        u = max(self.n_rows, 1)
        pairs = np.unique(pod_ns_id.astype(np.int64) * u + self.row_of)
        pair_ns, pair_row = pairs // u, pairs % u
        live = np.zeros(t_ns.shape[0], dtype=bool)
        for ns in np.unique(t_ns[t_ns >= 0]):
            lo, hi = np.searchsorted(pair_ns, [ns, ns + 1])
            if lo == hi:
                continue
            idx = np.flatnonzero(t_ns == ns)
            live[idx] = self.match[np.ix_(t_sel[idx], pair_row[lo:hi])].any(axis=1)
        return live


def run(tensors: Dict, p: Optional[Plan] = None) -> SelectorRows:
    """The pass over `tensors`' label rows (`p`: the Plan, where the caller
    has it)."""
    p = p or plan(tensors)
    return SelectorRows(match_rows_index(tensors, p.kv, p.key), p.row_of)


def class_matches(rows: SelectorRows, ct: Dict, class_rep: np.ndarray) -> np.ndarray:
    """[S_b, Cb] bool: kernel.selector_match over the bucketed class tensor
    set `ct` (selectors S_b, class rows Cb), from the pass.  Column c of a
    class is its representative's row's column; a pad class row (every
    label -1) and a pad selector row (no requirement: it matches every
    row) hold what the device gives them, so the class programs read the
    same matrix either way."""
    s = int(rows.match.shape[0])
    s_b, cb = int(ct["sel_req_kv"].shape[0]), int(ct["pod_ns_id"].shape[0])
    c = int(class_rep.shape[0])
    out = np.ones((s_b, cb), dtype=bool)
    out[:s, :c] = rows.match[:, rows.row_of[class_rep]]
    if cb > c:
        out[:s, c:] = selector_match_np(
            ct["sel_req_kv"][:s], ct["sel_exp_op"][:s], ct["sel_exp_key"][:s],
            ct["sel_exp_vals"][:s], ct["pod_kv"][c : c + 1], ct["pod_key"][c : c + 1],
        )
    return out
