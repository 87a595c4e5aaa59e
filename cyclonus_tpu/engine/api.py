"""TpuPolicyEngine: the user-facing facade over the tensor compiler and
verdict kernels.

Replaces the reference's sequential simulated hot loop
(pkg/connectivity/probe/jobrunner.go:68-94): one engine evaluation computes
the whole pod x pod x port-case verdict grid on device.
"""

from __future__ import annotations

import ipaddress
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..kube.ipaddr import is_ip_address_match_for_ip_block
from ..matcher.core import Policy
from ..telemetry import instruments as ti
from ..telemetry.spans import evaluation
from ..utils import guards
from ..utils.tracing import detail, phase
from . import aot_cache, planspec
from .encoding import (
    PEER_IP,
    PolicyEncoding,
    _DirectionEncoding,
    compress_rule_axes,
    compute_pod_classes,
    encode_policy,
    gather_class_pod_rows,
    pack_enabled,
    packed_words,
)


@dataclass(frozen=True)
class PortCase:
    """One distinct (resolved port, resolved port name, protocol) tuple."""

    port: int
    port_name: str
    protocol: str


class GridVerdict:
    """Verdict grids.  The underlying arrays stay DEVICE-RESIDENT (host
    transfer of an N x N x Q grid dominates wall-clock at scale); numpy
    views materialize lazily on first access,
    and `gather` fetches individual cells with one device-side take.

    A table arrives in one of two forms, told apart by its dtype: bool
    [Q, N, N] (the native evaluator, the empty case), or uint32
    `kernel.cell_words` [Q, >= N, >= ceil(N/4)] (every grid program of
    the device, single-device and sharded): cell c of a row is byte
    c % 4 of word c // 4, little-endian, each byte 0 or 1.  A one-byte element leaves
    the device four times slower than a 32-bit one (the runtime un-tiles
    either on the host), and on the host the words' bytes ARE the
    boolean table, so `ingress` / `egress` / `combined` are bool
    [Q, N, N] in either form, with no host copy.  A row-sharded table's
    host memory is lent by `_host_buffers` and goes back for the next
    table when its last view is gone, the GridVerdict's or a caller's
    (PR 37): a table a caller holds is never written again."""

    def __init__(
        self, pod_keys, port_cases, ingress_dev, egress_dev, combined_dev,
        eval_id: Optional[int] = None,
    ):
        self.pod_keys: List[str] = pod_keys
        self.port_cases: List[PortCase] = port_cases
        # the evaluation's number (instruments.eval_flight): the fetch
        # spans run after evaluate_grid has returned and carry it too
        self.eval_id = eval_id
        # device arrays: ingress [Q, N_dst, N_src]; egress/combined
        # [Q, N_src, N_dst] (or the words of those, see above)
        self.ingress_dev = ingress_dev
        self.egress_dev = egress_dev
        self.combined_dev = combined_dev
        self._np: Dict[str, np.ndarray] = {}

    def block_until_ready(self) -> "GridVerdict":
        with evaluation(self.eval_id), phase("grid.wait"):
            for a in (self.ingress_dev, self.egress_dev, self.combined_dev):
                if hasattr(a, "block_until_ready"):
                    a.block_until_ready()
        return self

    def _materialize(self, name: str) -> np.ndarray:
        if name not in self._np:
            dev = getattr(self, name + "_dev")
            words = dev.dtype == np.uint32
            with evaluation(self.eval_id), phase(
                "grid.fetch", table=name, form="words" if words else "bools"
            ):
                # JAX dispatch is async: grid.wait is what the device
                # still had to run, grid.copy the transfer and the
                # host's own work on the buffer, timed apart (np.asarray
                # alone would wait first and copy then, all the same)
                with phase("grid.wait"):
                    if hasattr(dev, "block_until_ready"):
                        dev.block_until_ready()
                with phase("grid.copy") as sp:
                    shards = _shards_of(dev)
                    if shards:
                        out, recycled = _copy_shards(dev, shards)
                    else:
                        out, recycled = np.asarray(dev), False
                    sp.set(
                        bytes=out.nbytes, dtype=str(out.dtype),
                        shards=len(shards) or 1, recycled=int(recycled),
                    )
                if words:
                    from .kernel import host_cells

                    out = host_cells(out, len(self.pod_keys))
                self._np[name] = out
        return self._np[name]

    @property
    def ingress(self) -> np.ndarray:
        return self._materialize("ingress")

    @property
    def egress(self) -> np.ndarray:
        return self._materialize("egress")

    @property
    def combined(self) -> np.ndarray:
        return self._materialize("combined")

    def job_verdict(self, q_idx: int, src_idx: int, dst_idx: int):
        return (
            bool(self.ingress[q_idx, dst_idx, src_idx]),
            bool(self.egress[q_idx, src_idx, dst_idx]),
            bool(self.combined[q_idx, src_idx, dst_idx]),
        )

    def gather(self, triples: Sequence[Tuple[int, int, int]]) -> np.ndarray:
        """Fetch (ingress, egress, combined) for a batch of (q, src, dst)
        triples with one device gather + one tiny transfer — no full-grid
        materialization."""
        idx = np.array(triples, dtype=np.int32).reshape(-1, 3)
        if idx.shape[0] == 0:
            return np.zeros((0, 3), dtype=bool)
        q, s, d = idx[:, 0], idx[:, 1], idx[:, 2]
        if isinstance(self.ingress_dev, np.ndarray):  # host tables already
            return np.stack(
                [
                    self.ingress_dev[q, d, s],
                    self.egress_dev[q, s, d],
                    self.combined_dev[q, s, d],
                ],
                axis=1,
            )
        from .kernel import grid_cells_kernel

        return np.asarray(
            grid_cells_kernel(
                self.ingress_dev, self.egress_dev, self.combined_dev, q, s, d
            )
        )

    def allow_counts(self) -> Tuple[int, int, int]:
        """Allowed cells of (ingress, egress, combined), exact, counted
        on the device in either form: one fused execution and one small
        transfer (a row's count each) — separate readbacks each pay a
        full device->host round trip."""
        if self.ingress_dev.shape[0] == 0:
            return (0, 0, 0)
        from .kernel import grid_row_counts_kernel

        rows = np.asarray(
            grid_row_counts_kernel(
                self.ingress_dev, self.egress_dev, self.combined_dev,
                n=len(self.pod_keys),
            )
        )
        return tuple(int(t) for t in rows.sum(axis=(1, 2), dtype=np.int64))

    def allow_stats(self) -> Dict[str, float]:
        """Mean allow rate per grid (allow_counts over the cells)."""
        cells = len(self.port_cases) * len(self.pod_keys) ** 2
        return {
            name: count / cells if cells else 0.0
            for name, count in zip(
                ("ingress", "egress", "combined"), self.allow_counts()
            )
        }


def _shards_of(dev) -> Sequence:
    """The addressable shards of a table that lies on more than one
    device (the mesh routes' row-sharded words); () for a table on one
    device or on the host, which takes the plain copy."""
    sharding = getattr(dev, "sharding", None)
    if sharding is None or len(sharding.device_set) <= 1:
        return ()
    return dev.addressable_shards


#: a shard is laid into its table's host buffer by up to this many
#: threads, a piece of about _LAY_BYTES each.  History (my chip run, PR 28):
#: the buffer's pages were fresh then, and first touching 9.7 GB of them
#: from one thread took 10 s of a 13 s request on the four-chip host, four
#: threads 4.3; since PR 37 only a process's first tables meet fresh pages
#: (_HostBuffers), and the threads share out a plain copy
_LAY_THREADS = 16
_LAY_BYTES = 32 << 20
_lay_pool = None


def _lay(out: np.ndarray, index, piece: np.ndarray) -> None:
    """out[index] = piece; a large piece in row blocks on the lay threads
    (numpy copies with the GIL released).  `out` is a buffer of
    _host_buffers: its pages are mapped already when it is a recycled one."""
    global _lay_pool
    dst = out[index]
    if piece.nbytes <= _LAY_BYTES:
        dst[...] = piece
        return
    if _lay_pool is None:
        import os
        from concurrent.futures import ThreadPoolExecutor

        _lay_pool = ThreadPoolExecutor(
            max_workers=min(_LAY_THREADS, os.cpu_count() or 1),
            thread_name_prefix="grid-lay",
        )
    rows = max(1, _LAY_BYTES // piece[0, 0].nbytes)
    jobs = [
        _lay_pool.submit(np.copyto, dst[q, r : r + rows], piece[q, r : r + rows])
        for q in range(piece.shape[0])
        for r in range(0, piece.shape[1], rows)
    ]
    for job in jobs:
        job.result()


#: free host buffers _HostBuffers keeps for the next sharded table: one
#: verdict's worth (ingress, egress, combined), all of the shape asked for
#: last.  THE COST: a process that has fetched sharded tables keeps up to
#: 3 x a table's bytes mapped after its last verdict is dropped (4.85 GB
#: at 40,000 pods and one port case), until it exits or fetches a table of
#: another shape
_FREE_BUFFERS = 3


class _TableMemory:
    """The owner of one sharded table's host memory while anything views
    it.  The table `_HostBuffers.take` hands out is `np.asarray` of this
    object, so the `base` chain of every view of the table (the boolean
    view `kernel.host_cells` makes, a caller's slice, a memoryview) ends
    here, and this object dies with the last of them: only then does its
    `weakref.finalize` hand the memory back."""

    __slots__ = ("__array_interface__", "__weakref__")

    def __init__(self, raw: np.ndarray):
        self.__array_interface__ = raw.__array_interface__


class _HostBuffers:
    """The host buffers sharded tables are laid into (`_copy_shards`),
    recycled: `np.empty` of a 1.62 GB table is a fresh mmap whose every
    page the lay threads fault in and the drop unmaps again, the larger
    half of a four-chip request's copy (PERF.md section 6, PR 37).  A
    buffer comes back when NOTHING can read it any more (_TableMemory), is
    handed out again with the last table's bytes in it (the shards cover
    every byte), and at most _FREE_BUFFERS free ones are kept, of the
    shape asked for last alone.  One lock, re-entrant because a finalizer
    may run wherever the collector does, inside `take` too."""

    def __init__(self):
        self._lock = threading.RLock()
        self._key = None
        self._free: List[np.ndarray] = []

    def take(self, shape, dtype) -> Tuple[np.ndarray, bool]:
        """(a writable buffer of that shape, whether it is a recycled
        one); its bytes are whatever the memory held."""
        key = (tuple(shape), np.dtype(dtype))
        with self._lock:
            if key != self._key:
                # the key first: a buffer of the old shape that comes back
                # from here on is dropped, not kept under the new one
                self._key = key
                self._free.clear()
            raw = self._free.pop() if self._free else None
        recycled = raw is not None
        if not recycled:
            raw = np.empty(shape, dtype)
        ti.GRID_HOST_BUFFER.inc(outcome="recycled" if recycled else "fresh")
        owner = _TableMemory(raw)
        # the finalizer's argument is what keeps the memory alive
        weakref.finalize(owner, self._give_back, key, raw).atexit = False
        return np.asarray(owner), recycled

    def _give_back(self, key, raw: np.ndarray) -> None:
        with self._lock:
            if key == self._key and len(self._free) < _FREE_BUFFERS:
                self._free.append(raw)

    def free_bytes(self) -> int:
        """Bytes of the free buffers held now."""
        with self._lock:
            return sum(raw.nbytes for raw in self._free)


_host_buffers = _HostBuffers()


def _copy_shards(dev, shards) -> Tuple[np.ndarray, bool]:
    """A sharded table on the host: ONE buffer of the final shape, each
    shard laid into its place (span `grid.shard_copy`, one a shard: the
    wait for the shard's own transfer and un-tiling, then _lay, whose
    part of the span is `lay_ms`); returns the table and whether its
    buffer was a recycled one.  The shards' transfers are all started
    first, so the runtime brings the later ones over while the earlier
    ones are laid in place.  JAX hands a shard over in a buffer of the
    runtime's, so every byte is written twice on the host; there is no
    call that names a destination (and JAX keeps each shard's host copy
    with the shard, so the host holds the table twice until the
    GridVerdict is dropped).  The buffer is _host_buffers': the memory of
    a table nobody holds any more where there is one, fresh pages
    otherwise."""
    for sh in shards:
        sh.data.copy_to_host_async()
    out, recycled = _host_buffers.take(dev.shape, dev.dtype)
    for sh in shards:
        with detail("grid.shard_copy", device=sh.device.id) as sp:
            piece = np.asarray(sh.data)
            t0 = time.perf_counter()
            _lay(out, sh.index, piece)
            sp.set(
                bytes=piece.nbytes, dtype=str(piece.dtype),
                lay_ms=(time.perf_counter() - t0) * 1e3,
            )
    return out, recycled


def _direction_tensors(enc: _DirectionEncoding) -> Dict:
    # peer->target mapping ships as a [P] index vector; kernels build the
    # dense one-hot on device (kernel.m_tp_onehot) — the materialized
    # [T, P] matrix is ~70 MB at bench scale, dominating device_put time
    peer_target = np.asarray(enc.peer_target, dtype=np.int32).reshape(-1)
    d = {
        "target_ns": enc.target_ns,
        "target_sel": enc.target_sel,
        "peer_kind": enc.peer_kind,
        "peer_ns_kind": enc.peer_ns_kind,
        "peer_ns_id": enc.peer_ns_id,
        "peer_ns_sel": enc.peer_ns_sel,
        "peer_pod_kind": enc.peer_pod_kind,
        "peer_pod_sel": enc.peer_pod_sel,
        "ip_base": enc.ip_base,
        "ip_mask": enc.ip_mask,
        "ip_is_v4": enc.ip_is_v4,
        "ex_base": enc.ex_base,
        "ex_mask": enc.ex_mask,
        "ex_valid": enc.ex_valid,
        "peer_target": peer_target,
        "port_spec": dict(enc.port_spec),
    }
    return d


def _tier_tensors(tenc) -> Dict:
    """Tensor-dict view of one direction's TierDirectionEncoding
    (encoding.py): the int8 verdict + int32 rank slabs, the shared-table
    selector ids, and the per-row port spec."""
    return {
        "subj_ns_sel": tenc.subj_ns_sel,
        "subj_pod_kind": tenc.subj_pod_kind,
        "subj_pod_sel": tenc.subj_pod_sel,
        "peer_ns_sel": tenc.peer_ns_sel,
        "peer_pod_kind": tenc.peer_pod_kind,
        "peer_pod_sel": tenc.peer_pod_sel,
        "action": tenc.action,  # shape: (G,) int8; sentinel: 0=pad
        "tier": tenc.tier,
        "rank": tenc.rank,
        "port_spec": dict(tenc.port_spec),
    }


def _selector_match_np(
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
    from .encoding import EXP_EXISTS, EXP_IN, EXP_NONE, EXP_NOT_IN

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


def _selector_pod_matches_host(tensors: Dict, chunk: int = 0) -> np.ndarray:
    """[S, N] bool selector-vs-pod matches, evaluated host-side in pod
    chunks so the result is available at encode time without touching any
    device.  The chunk scales inversely with the selector count so the
    [S, chunk, ...] broadcast intermediates stay bounded in BOTH axes —
    a fixed pod chunk would let a large selector table OOM the encode."""
    n = tensors["pod_kv"].shape[0]
    s = tensors["sel_req_kv"].shape[0]
    if not chunk:
        # budget the [S, chunk, R, L] and [S, chunk, E, V, L] broadcast
        # intermediates of _selector_match_np, not just S * chunk: a
        # label-heavy cluster (large R/E/V/L) scales the temporaries by
        # the trailing dims too
        r = tensors["sel_req_kv"].shape[1]
        e, v = tensors["sel_exp_vals"].shape[1:3]
        l = tensors["pod_kv"].shape[1]
        per_pod = max(s, 1) * max(r * l, e * v * l, 1)
        chunk = max(64, (1 << 24) // per_pod)
    outs = []
    for lo in range(0, n, chunk):
        outs.append(
            _selector_match_np(
                tensors["sel_req_kv"],
                tensors["sel_exp_op"],
                tensors["sel_exp_key"],
                tensors["sel_exp_vals"],
                tensors["pod_kv"][lo : lo + chunk],
                tensors["pod_key"][lo : lo + chunk],
            )
        )
    if not outs:
        return np.zeros((s, 0), dtype=bool)
    return np.concatenate(outs, axis=1)


# port_spec arrays are [P, ...]-shaped like the flat peer arrays
_PEER_KEYS = (
    "peer_kind",
    "peer_ns_kind",
    "peer_ns_id",
    "peer_ns_sel",
    "peer_pod_kind",
    "peer_pod_sel",
    "ip_base",
    "ip_mask",
    "ip_is_v4",
    "ex_base",
    "ex_mask",
    "ex_valid",
    "host_ip_mask",
)


def _compact_dead_targets(tensors: Dict, selpod: Optional[np.ndarray] = None) -> Dict:
    """Drop targets that match no pod of this cluster (and their peers).

    Verdicts are exactly invariant: a dead target's tmatch row is all
    False (kernel.direction_precompute), so it contributes nothing to
    has_target and nothing to any_allow.  But the target axis T is the
    flops multiplier of every grid kernel — and in namespace-local policy
    sets most compiled targets are dead ((ns, selector) combos with no
    matching pods), so compaction shrinks the dominant matmuls by the
    dead fraction.  Deadness is decided with the real selector kernel
    (no heuristics), evaluated once on CPU at encode time: O(S * N),
    noise next to the O(N^2 * T) evaluation it shrinks."""
    pod_ns_id = tensors["pod_ns_id"]
    if selpod is None:
        selpod = _selector_pod_matches_host(tensors)
    s = selpod.shape[0]
    # rows: any ns id referenced by pods or targets (vocab ns ids can
    # exceed the cluster's ns table when policies name pod-less namespaces)
    n_rows = int(tensors["ns_kv"].shape[0])
    for direction in ("ingress", "egress"):
        t_ns = tensors[direction]["target_ns"]
        if t_ns.size:
            n_rows = max(n_rows, int(t_ns.max()) + 1)
    if pod_ns_id.size:
        n_rows = max(n_rows, int(pod_ns_id.max()) + 1)
    # live_by_sel_ns[s, ns] = selector s matches >= 1 pod in namespace ns
    live_by_sel_ns = np.zeros((s, max(n_rows, 1)), dtype=bool)
    for si in range(s):
        ids = pod_ns_id[selpod[si]]
        if ids.size:
            live_by_sel_ns[si, ids[ids >= 0]] = True

    out = dict(tensors)
    for direction in ("ingress", "egress"):
        d = tensors[direction]
        t_ns, t_sel = d["target_ns"], d["target_sel"]
        t = t_ns.shape[0]
        if t == 0:
            continue
        live = (t_ns >= 0) & live_by_sel_ns[t_sel, np.maximum(t_ns, 0)]
        keep = np.flatnonzero(live)
        if keep.size == t:
            continue
        remap = np.full(t, -1, dtype=np.int32)
        remap[keep] = np.arange(keep.size, dtype=np.int32)
        pt = d["peer_target"]
        pkeep = (pt >= 0) & live[np.clip(pt, 0, t - 1)]
        nd = dict(d)
        nd["target_ns"] = np.ascontiguousarray(t_ns[keep])
        nd["target_sel"] = np.ascontiguousarray(t_sel[keep])
        nd["peer_target"] = np.ascontiguousarray(remap[pt[pkeep]])
        for k in _PEER_KEYS:
            if k in nd:
                nd[k] = np.ascontiguousarray(nd[k][pkeep])
        if "host_ip_match" in nd:
            nd["host_ip_match"] = np.ascontiguousarray(nd["host_ip_match"][pkeep])
        nd["port_spec"] = {
            k: np.ascontiguousarray(v[pkeep]) for k, v in d["port_spec"].items()
        }
        out[direction] = nd
    return out


def _sort_targets_by_ns(tensors: Dict) -> Dict:
    """Permute each direction's targets into namespace order (stable).

    Target order is semantically irrelevant — every kernel reduces over
    the target axis — but with targets ns-sorted (and pods ns-sorted at
    counts time) the tmatch matrices become near block diagonal, which
    is what lets the pallas counts kernel skip empty (pod-tile, T-chunk)
    blocks.  Sorting once in the base tensors means no per-path copy of
    the target/peer arrays is ever needed."""
    out = dict(tensors)
    for direction in ("ingress", "egress"):
        d = tensors[direction]
        t_ns = d["target_ns"]
        if t_ns.size == 0:
            continue
        tperm = np.argsort(t_ns, kind="stable")
        if np.array_equal(tperm, np.arange(tperm.size)):
            continue
        inv = np.empty_like(tperm)
        inv[tperm] = np.arange(tperm.size)
        nd = dict(d)
        nd["target_ns"] = np.ascontiguousarray(t_ns[tperm])
        nd["target_sel"] = np.ascontiguousarray(d["target_sel"][tperm])
        if d["peer_target"].size:
            nd["peer_target"] = np.ascontiguousarray(
                inv[d["peer_target"]].astype(np.int32)
            )
        out[direction] = nd
    return out


def _bucket_dim(n: int, lo: int = 4) -> int:
    """Shape bucket: next power of two up to 128, then multiples of 128
    (pod axis uses _bucket_pods).  Every distinct tensor shape costs a
    fresh XLA compile; the 216 conformance clusters differ by a few
    selectors/targets each, so exact sizing recompiled the engine per
    test case — bucketing collapses them onto a handful of programs.
    Above 128 the granule stays at 128 (the kernels' lane alignment):
    pow2 there would pad the target axis far past the pallas kernel's
    own chunk rounding and measurably deepen the contraction."""
    n = max(n, lo)
    if n <= 128:
        return 1 << (n - 1).bit_length()
    return -(-n // 128) * 128


def _bucket_up(n: int, steps: int) -> int:
    """`n` (already a _bucket_dim bucket) stepped UP `steps` buckets —
    the slab-headroom pre-reservation (serve engines reserve one extra
    bucket so bucket-crossing policy churn stays on the incremental
    patch path instead of forcing a full rebuild)."""
    for _ in range(max(0, steps)):
        n = _bucket_dim(n + 1)
    return n


def _bucket_down(n: int, steps: int) -> int:
    """Inverse of _bucket_up on the bucket ladder (4..128 pow2, then
    multiples of 128), floored at the smallest bucket.  Used to recover
    a slab's ZERO-HEADROOM bucket from its allocated (headroom-stepped)
    size when counting headroom saves."""
    for _ in range(max(0, steps)):
        if n > 256:
            n -= 128
        elif n == 256:
            n = 128
        else:
            n = max(4, n // 2)
    return n


def _bucket_pods(n: int) -> int:
    """Pod-axis bucket: pow2 up to 1024, then multiples of 1024 (matches
    the tile block, and keeps large-N padding waste under ~0.1%)."""
    n = max(n, 8)
    if n <= 1024:
        return 1 << (n - 1).bit_length()
    return -(-n // 1024) * 1024


def _pad_axis(a: np.ndarray, axis: int, size: int, fill) -> np.ndarray:
    """Pad `axis` up to `size` with `fill` (no-op when already there)."""
    cur = a.shape[axis]
    if cur >= size:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, size - cur)
    return np.pad(a, widths, constant_values=fill)


# (array key, per-axis fill values) — the inert pad conventions from
# encoding.py's padding-neutrality invariants: padded selectors are
# unreferenced, padded targets match no pod (ns -1), padded peers belong
# to target -1 (zero one-hot row), padded port items/ranges match nothing
_SEL_PADS = {
    "sel_req_kv": -1,
    "sel_exp_op": 0,
    "sel_exp_key": -1,
    "sel_exp_vals": -1,
}
_DIRECTION_PADS = {
    "target_ns": -1,
    "target_sel": 0,
    "peer_target": -1,
    "peer_kind": 0,
    "peer_ns_kind": 0,
    "peer_ns_id": -1,
    "peer_ns_sel": 0,
    "peer_pod_kind": 0,
    "peer_pod_sel": 0,
    "ip_base": 0,
    "ip_mask": 0,
    "ip_is_v4": False,
    "ex_base": 0,
    "ex_mask": 0,
    "ex_valid": False,
    "host_ip_mask": False,
    "host_ip_match": False,
}
_PORT_SPEC_PADS = {
    "item_kind": -1,
    "item_port": 0,
    "item_name": -2,
    "item_proto": -2,
    "rng_from": 0,
    "rng_to": -1,
    "rng_proto": -2,
    "spec_all": False,
}
# tier-slab pads: action 0 = TIER_ACT_NONE — a padded rule row matches
# nothing (every kernel masks on action > 0), so selector/rank fills
# are inert by construction
_TIER_PADS = {
    "subj_ns_sel": 0,
    "subj_pod_kind": 0,
    "subj_pod_sel": -1,
    "peer_ns_sel": 0,
    "peer_pod_kind": 0,
    "peer_pod_sel": -1,
    "action": 0,
    "tier": 0,
    "rank": 0,
}


def _bucket_tensors(tensors: Dict, headroom: int = 0) -> Dict:
    """Pad every tensor dimension up to its shape bucket with the inert
    fill for that array, so near-identical problems share compiled
    programs.  Semantics are unchanged by construction: each pad value is
    the same inert encoding the encoder itself uses for ragged padding
    (verified by the parity suites, which run everything bucketed).

    `headroom` steps the RULE-SLAB row buckets (selector table, target/
    peer axes, tier rule rows) up that many extra buckets — the serve
    path's slab pre-reservation (CYCLONUS_SERVE_HEADROOM): the reserved
    rows are the same inert pads, so verdicts are unchanged, and a
    later policy patch that crosses the natural bucket boundary can pad
    into the reservation instead of changing compiled shapes."""
    from .sharded import _pad_pod_arrays

    t = dict(tensors)
    # selector tables: rows are unreferenced when padded (fills from
    # _SEL_PADS — the one table this and the serve patch path share)
    s = _bucket_up(_bucket_dim(t["sel_req_kv"].shape[0]), headroom)
    for k in ("sel_req_kv", "sel_exp_op", "sel_exp_key"):
        fill = _SEL_PADS[k]
        t[k] = _pad_axis(
            _pad_axis(t[k], 1, _bucket_dim(t[k].shape[1]), fill), 0, s, fill
        )
    ev = t["sel_exp_vals"]
    fill = _SEL_PADS["sel_exp_vals"]
    t["sel_exp_vals"] = _pad_axis(
        _pad_axis(
            _pad_axis(ev, 2, _bucket_dim(ev.shape[2]), fill),
            1, _bucket_dim(ev.shape[1]), fill,
        ),
        0, s, fill,
    )
    # namespace tables: padded rows are unreferenced (ns ids are real)
    m = _bucket_dim(t["ns_kv"].shape[0])
    for k in ("ns_kv", "ns_key"):
        t[k] = _pad_axis(
            _pad_axis(t[k], 1, _bucket_dim(t[k].shape[1]), -1), 0, m, -1
        )
    # pod label columns
    for k in ("pod_kv", "pod_key"):
        t[k] = _pad_axis(t[k], 1, _bucket_dim(t[k].shape[1]), -1)
    # per-direction policy tensors
    for direction in ("ingress", "egress"):
        d = dict(t[direction])
        # the pallas counts path appends ONE pseudo-target row
        # (pallas_kernel._augment): bucket to boundary - 1 so the
        # augmented axis lands exactly on the 128 chunk boundary instead
        # of spilling a whole extra chunk into the contraction
        nt = _bucket_up(_bucket_dim(d["target_ns"].shape[0] + 1), headroom) - 1
        np_ = _bucket_up(_bucket_dim(d["peer_kind"].shape[0]), headroom)
        for k, fill in _DIRECTION_PADS.items():
            if k not in d:
                continue
            size = nt if k.startswith("target_") else np_
            d[k] = _pad_axis(d[k], 0, size, fill)
            if k in ("ex_base", "ex_mask", "ex_valid"):
                d[k] = _pad_axis(d[k], 1, _bucket_dim(d[k].shape[1]), fill)
        spec = {}
        for k, fill in _PORT_SPEC_PADS.items():
            a = _pad_axis(d["port_spec"][k], 0, np_, fill)
            if a.ndim == 2:
                a = _pad_axis(a, 1, _bucket_dim(a.shape[1]), fill)
            spec[k] = a
        d["port_spec"] = spec
        t[direction] = d
    # precedence-tier slabs: the rule axis buckets like the peer axis,
    # padded with inert (action 0) rows
    if "tiers" in t:
        tiers = {}
        for direction in ("ingress", "egress"):
            d = dict(t["tiers"][direction])
            g = _bucket_up(_bucket_dim(d["action"].shape[0]), headroom)
            for k, fill in _TIER_PADS.items():
                d[k] = _pad_axis(d[k], 0, g, fill)
            spec = {}
            for k, fill in _PORT_SPEC_PADS.items():
                a = _pad_axis(d["port_spec"][k], 0, g, fill)
                if a.ndim == 2:
                    a = _pad_axis(a, 1, _bucket_dim(a.shape[1]), fill)
                spec[k] = a
            d["port_spec"] = spec
            tiers[direction] = d
        t["tiers"] = tiers
    # pod axis last: the inert-row scheme lives in _pad_pod_arrays
    n = t["pod_ns_id"].shape[0]
    t, _ = _pad_pod_arrays(t, n, _bucket_pods(n))
    return t


# device-resident precompute cache ceiling (the tallow tensors are
# [T, N, Q] bf16 — ~260 MB at the 100k x 10k bench, but multi-GB at
# multi-million-pod scale, where recomputing beats pinning HBM)
_PRE_CACHE_MAX_BYTES = 2 << 30

#: the mesh counts entry's route decision (_mesh_counts_route): the dense
#: precompute may be REPLICATED on every chip (the source-row route) while
#: one chip's copy of it, from the shapes, stays under this many bytes and
#: under half of what the device reports as its memory; past it both pod
#: axes stay sharded (the ring).  Half of a v5e chip's 16 GB: the program's
#: temporaries (peer_allow as bf16 beside the boolean) take the other half
_MESH_REPLICATED_MAX_BYTES = 8 << 30


def _tree_nbytes(tree) -> int:
    """Bytes of a pytree's array leaves (.nbytes is a host-side
    attribute: no device sync)."""
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def _pre_cache_enabled() -> bool:
    """Repeat evaluations of one case set keep the precompute on device
    (CYCLONUS_PRE_CACHE=0 opts out)."""
    import os

    return os.environ.get("CYCLONUS_PRE_CACHE", "1") != "0"


def _compaction_enabled(tensors: Dict) -> bool:
    """Compaction is on by default (CYCLONUS_COMPACT=0 opts out), guarded
    by a host-work budget: the CPU selector pass is O(S * N) with small
    per-element constants — cap S * N so a pathological selector count
    can't stall encode."""
    import os

    setting = os.environ.get("CYCLONUS_COMPACT", "")
    if setting == "0":
        return False
    if setting == "1":
        return True  # explicit opt-in overrides the work budget
    s = int(tensors["sel_req_kv"].shape[0])
    n = int(tensors["pod_ns_id"].shape[0])
    r = int(tensors["sel_req_kv"].shape[1])
    e, v = (int(x) for x in tensors["sel_exp_vals"].shape[1:3])
    l = int(tensors["pod_kv"].shape[1])
    # budget ELEMENT OPS of the host selector pass (S * N * the trailing
    # broadcast dims of _selector_match_np), not just S * N: 2^32 ops is
    # ~seconds-to-a-minute of single-threaded numpy.  The old flat S * N
    # cap bounded memory but let a label-heavy cluster stall encode for
    # minutes — past this budget the compaction win is dwarfed by its
    # own cost, so skip it (CYCLONUS_COMPACT=1 forces it back on).
    ops = s * n * max(r * l, e * v * l, 1)
    if ops > 1 << 32:
        import logging

        logging.getLogger(__name__).info(
            "skipping dead-target compaction: host selector pass would "
            "cost ~%.1e element ops (budget 2^32); set CYCLONUS_COMPACT=1 "
            "to force it",
            float(ops),
        )
        return False
    return True


#: below this pod count the auto mode leaves the legacy paths untouched:
#: the compressed path's win is quadratic in cluster size, and tiny
#: clusters are where the per-engine second tensor set costs most
#: relative to the work saved (CYCLONUS_CLASS_MIN_PODS overrides)
_CLASS_AUTO_MIN_PODS = 2048
#: the weighted-count split keeps every device-side partial an exact f32
#: integer only while row sums stay below 2^24 (tiled.py class counts
#: design note) — larger clusters bypass compression entirely
_CLASS_MAX_PODS_EXACT = 1 << 24


def _class_compress_mode() -> str:
    """CYCLONUS_CLASS_COMPRESS: "auto" (default — engage above the pod
    floor when the class reduction is real), "1" (force, any size),
    "0" (off, incl. the rule-axis partition compression)."""
    import os

    return os.environ.get("CYCLONUS_CLASS_COMPRESS", "auto").lower()


def _class_auto_min_pods() -> int:
    import os

    try:
        return int(
            os.environ.get("CYCLONUS_CLASS_MIN_PODS", str(_CLASS_AUTO_MIN_PODS))
        )
    except ValueError:
        return _CLASS_AUTO_MIN_PODS


def _np_leaves(tree):
    """Flat iterator over the numpy leaves of a nested tensor dict."""
    if isinstance(tree, dict):
        for v in tree.values():
            yield from _np_leaves(v)
    elif isinstance(tree, np.ndarray):
        yield tree


def _pack_tensors(tree, name: str = "unpack"):
    """Pack a numpy pytree into one int32 buffer + an unpack function
    called `name` (a jit of it reads `jit_<name>` in a profile).

    Every device_put pays a fixed per-buffer overhead (not measured on
    the current machine), and the tensor dict has ~57 leaves of a few MB
    in total.  Packing every leaf into a single int32 buffer makes it
    one transfer; `unpack` rebuilds the
    pytree from the buffer with static slices + bitcasts and is designed
    to be traced INSIDE a consumer jit (so the unpack adds no extra
    dispatch or executable of its own).

    Returns (packed_int32_np, unpack) where unpack(buf_jnp) -> pytree.
    The per-leaf layout rides along as `unpack.metas_by_path`
    ({("ingress", "ip_base"): (dtype, shape, word_offset, n_words), ...})
    — the delta path (cyclonus_tpu/serve) uses it to scatter-patch
    touched rows of the device buffer without re-transferring anything
    else.  Every leaf starts on a fresh int32 word (tail bytes are
    zero-padded), so row patches never cross leaf boundaries."""
    from jax import tree_util as jtu

    path_leaves, treedef = jtu.tree_flatten_with_path(tree)
    leaves = [leaf for _path, leaf in path_leaves]
    paths = [
        tuple(getattr(k, "key", str(k)) for k in path)
        for path, _leaf in path_leaves
    ]
    metas = []  # (dtype, shape, word_offset, n_words)
    chunks = []
    off = 0
    for leaf in leaves:
        a = np.ascontiguousarray(leaf)
        if a.dtype not in (
            np.dtype(np.int32),
            np.dtype(np.uint32),
            np.dtype(bool),
            np.dtype(np.int8),
        ):
            # unpack below BITCASTS from int32 words; any other dtype
            # would be silently reinterpreted — fail loudly instead
            raise TypeError(f"_pack_tensors: unsupported leaf dtype {a.dtype}")
        raw = a.tobytes()
        pad = (-len(raw)) % 4
        if pad:
            raw += b"\0" * pad
        words = np.frombuffer(raw, dtype=np.int32)
        metas.append((a.dtype, a.shape, off, words.size))
        chunks.append(words)
        off += words.size
    packed = np.concatenate(chunks) if chunks else np.zeros(0, np.int32)

    def unpack(buf):
        import jax
        import jax.numpy as jnp
        from jax import tree_util as jtu2

        outs = []
        for dtype, shape, o, nw in metas:
            n = int(np.prod(shape))
            if n == 0:
                outs.append(jnp.zeros(shape, dtype=dtype))
                continue
            words = buf[o : o + nw]
            if dtype == np.bool_:
                flat = jax.lax.bitcast_convert_type(words, jnp.uint8)
                arr = flat.reshape(-1)[:n].astype(jnp.bool_)
            elif dtype == np.int8:
                # the tier action slab: 4 int8 lanes per packed word
                flat = jax.lax.bitcast_convert_type(words, jnp.int8)
                arr = flat.reshape(-1)[:n]
            elif dtype == np.uint32:
                arr = jax.lax.bitcast_convert_type(words, jnp.uint32)
            else:  # int32 (the only other dtype _pack_tensors accepts)
                arr = words
            outs.append(arr.reshape(shape))
        return jtu2.tree_unflatten(treedef, outs)

    unpack.metas_by_path = dict(zip(paths, metas))
    unpack.__name__ = unpack.__qualname__ = name
    return packed, unpack




@guards.checked
class TpuPolicyEngine:
    """Compile once per (policy set, cluster state); evaluate many port
    cases.  Pods are (namespace, name, labels, ip) tuples.

    Threading model (docs/DESIGN.md "Lock discipline"): evaluations are
    issued from one thread at a time, but the autotune's abandoned
    candidate thread (run_bounded timeout) can outlive its call and race
    the issuing thread inside _slab_ops_for.  Everything that pair of
    threads shares for WRITING — the slab choice and the cached
    gathered operands — is guarded by _slab_lock; _pre_cache is written
    only by the issuing thread, and the one place the orphan reads it
    (_slab_ops_for's operand build) snapshots it once and treats a
    concurrent eviction as a contained candidate failure.  The rest of
    the per-engine caches stay single-threaded by contract, _static_pre
    (the case-independent half of the dense precompute) among them: no
    autotune candidate reads it.
    """

    # the guarded-by contract (tools/locklint.py LK001 statically; under
    # CYCLONUS_GUARD_CHECK=1 these become asserting descriptors)
    _slab_choice = guards.Guarded("_slab_lock")
    _slab_ops_cache = guards.Guarded("_slab_lock")
    _kernel_choice = guards.Guarded("_slab_lock")

    def __init__(
        self,
        policy: Policy,
        pods: Sequence[Tuple[str, str, Dict[str, str], str]],
        namespaces: Dict[str, Dict[str, str]],
        *,
        compact: Optional[bool] = None,
        class_compress: Optional[str] = None,
        cidr_tss: Optional[str] = None,
        tiers=None,
        slab_headroom: int = 0,
    ):
        with phase(
            "engine.new", pods=len(pods),
            targets=len(policy.ingress) + len(policy.egress),
        ):
            # compact/class_compress override the CYCLONUS_COMPACT /
            # CYCLONUS_CLASS_COMPRESS env defaults per engine (None = env).
            # The serve layer builds its engines with compact=False — dead-
            # target compaction bakes "no pod matches this target" into the
            # tensors, and a pod delta can make a dead target live, so a
            # delta-oriented engine must keep every target resident.
            # tiers: an optional tiers.model.TierSet — AdminNetworkPolicy/
            # BANP precedence tiers layered over the NetworkPolicy verdict
            # (docs/DESIGN.md "Precedence tiers").  With it absent or empty,
            # the tensor set — and therefore every compiled program — is
            # byte-identical to the networkingv1-only engine.
            # every evaluation path below is jax-backed: first-touch setup of
            # the persistent compile cache happens here, not at import time
            from . import ensure_persistent_compile_cache, start_backend

            ensure_persistent_compile_cache()
            # the process's first engine starts the backend here, in a span
            # of its own (startup.backend), and not inside whatever first
            # touches the device: engine.cidrspace, a device_put, or no span
            start_backend()
            self._opt_compact = compact
            self._opt_class_compress = class_compress
            # cidr_tss overrides CYCLONUS_CIDR_TSS for the TSS/LPM CIDR
            # pre-classification stage (engine/cidrspace.py; docs/DESIGN.md
            # "CIDR tuple-space pre-classification") — None = env
            self._opt_cidr_tss = cidr_tss
            # rule-slab headroom (extra _bucket_dim steps pre-reserved on
            # the selector/target/peer/tier row buckets).  0 for batch
            # engines; the serve path passes CYCLONUS_SERVE_HEADROOM so
            # bucket-crossing policy churn patches into the reservation
            # (serve/incremental.py patch_policy) instead of rebuilding.
            self._slab_headroom = max(0, int(slab_headroom or 0))
            self.tiers = tiers if tiers else None
            if self.tiers is not None:
                self.tiers.validate()
            with phase("engine.encode"):
                with phase("engine.encode_policy", pods=len(pods)):
                    self.encoding: PolicyEncoding = encode_policy(
                        policy, pods, namespaces, tiers=self.tiers
                    )
                with phase("engine.build_tensors"):
                    self._tensors = self._build_tensors()
                # one O(S*N) host selector pass serves both consumers: dead-
                # target compaction here and the slab-window plan later
                # (selector and pod axes are unchanged by compaction, only
                # padded by bucketing)
                self._selpod_prebucket = None
                compact_on = (
                    _compaction_enabled(self._tensors)
                    if compact is None
                    else bool(compact)
                )
                if compact_on:
                    with phase("engine.compact"):
                        self._selpod_prebucket = _selector_pod_matches_host(
                            self._tensors
                        )
                        self._tensors = _compact_dead_targets(
                            self._tensors, selpod=self._selpod_prebucket
                        )
                # equivalence-class grid compression (docs/DESIGN.md "Grid
                # compression"): tuple-space partition compression of the
                # rule axes is exact and cheap, so it applies whenever
                # compression isn't disabled outright; the pod-class state
                # additionally needs the host selector pass and a real
                # reduction (auto mode) before paying for a second tensor set
                self._partition_stats = None
                self._class_state = None
                mode = (
                    _class_compress_mode()
                    if class_compress is None
                    else str(class_compress).lower()
                )
                class_route = "off"
                if mode != "0":
                    with phase("engine.partition"):
                        pstats = {}
                        for direction in ("ingress", "egress"):
                            nd, pstats[direction] = compress_rule_axes(
                                self._tensors[direction]
                            )
                            self._tensors[direction] = nd
                        self._partition_stats = pstats
                    class_route = self._maybe_build_class_state(mode)
                # which side of the class-route hand-off this engine is on
                ti.CLASS_ROUTE.inc(outcome=class_route)
                with phase("engine.class_tensors"):
                    self._tensors = _bucket_tensors(
                        _sort_targets_by_ns(self._tensors),
                        headroom=self._slab_headroom,
                    )
                    if self._class_state is not None:
                        self._class_state["ctensors"] = _bucket_tensors(
                            _sort_targets_by_ns(
                                self._class_state.pop("ctensors_raw")
                            ),
                            headroom=self._slab_headroom,
                        )
                if self._class_state is not None:
                    st = self._class_state
                    # the gather/index tensors the compressed path pins on
                    # device: class map + weights + the compressed tensor
                    # buffer — counted against CYCLONUS_SLAB_MAX_BYTES by
                    # the slab plan and the compressed-counts eligibility
                    cb = int(st["ctensors"]["pod_ns_id"].shape[0])
                    # the TSS partition tensors (trie map) charge the same
                    # budget: the LPM stage must never over-commit the HBM
                    # the compression exists to save
                    cidr_bytes = (
                        st["cidr"].nbytes() if st.get("cidr") is not None else 0
                    )
                    st["aux_bytes"] = int(
                        self.encoding.cluster.n_pods * 4
                        + cb * 4
                        + sum(a.nbytes for a in _np_leaves(st["ctensors"]))
                        + cidr_bytes
                    )
                    ti.CLASS_AUX_BYTES.set(st["aux_bytes"])
            # wall-clock of the last tiered grid evaluation's dispatch
            # (tier_stats()["resolve_s"]; None until a tiered eval ran)
            self._tier_resolve_s = None
            # The trailing `# derived-from:` declarations below are the
            # cache-coherence contract tools/cachelint.py CC002 enforces:
            # a VALUE token means invalidate_after_patch must reset the
            # attribute after an in-place buffer patch; `shapes` marks a
            # compiled-program cache (shape-keyed, survives value patches);
            # `patched` marks state the serve patch path maintains itself.
            self._device_tensors = None  # derived-from: buffer (unpacked views)
            self._packed_buf = None  # derived-from: patched (scatter writes back)
            self._unpack = None  # derived-from: patched (layout fixed at build)
            # jit wrappers over the unpack closures, cached so the serve
            # layer's patch/invalidate cycle re-unpacks through the SAME
            # compiled program instead of retracing per patch
            self._unpack_jit = None  # derived-from: shapes
            self._class_unpack_jit = None  # derived-from: shapes
            # compressed-path device state (all lazy; None when no class
            # state): packed class-representative buffer + unpacked pytree,
            # the pod->class gather map, and the fused grid+gather program
            self._class_packed_buf = None  # derived-from: patched
            self._class_unpack = None  # derived-from: patched
            self._class_device_tensors = None  # derived-from: buffer
            self._class_of_dev = None  # derived-from: classes
            # the counts route's dst-side class weights (tiled.class_weights):
            # the serve layer mutates class_size in place, so a kept copy is
            # a wrong count unless every reset of _class_of_dev resets it too
            self._class_w_dev = None  # derived-from: classes
            self._class_grid_jit = None  # derived-from: shapes
            self._pod_perm_dev = None  # derived-from: pod-rows (ns-order perm)
            self._pod_perm_host = None  # derived-from: pod-rows
            self._slab_plan_state = "unset"  # derived-from: buffer (window proof)
            # None = not yet tuned (auto mode times both at the first
            # steady-state call); True/False = slab kernel chosen/rejected
            self._slab_choice = None  # derived-from: buffer (re-timed)
            self._slab_autotune = None  # {"default_s", "slab_s"} once timed
            # the bit-packed dtype plan (docs/DESIGN.md "Bit-packed
            # kernel"): resolved ONCE per engine from CYCLONUS_PACK — the
            # compiled program set is a function of it, like the operand
            # dtype — and passed static everywhere
            self._pack = pack_enabled()
            # persistent AOT executable adapters (engine/aot_cache.py):
            # built lazily per program family; with CYCLONUS_AOT_CACHE off
            # they pass straight through to the plain jits
            self._grid_aot = None  # derived-from: shapes
            self._pairs_aot = None  # derived-from: shapes
            # the tuned counts configuration: None until the autotune (or a
            # persisted-cache adoption) picks one; then {"kernel":
            # "default"|"slab"|"packed", optional "bs"/"bd"}.  Shares
            # _slab_lock with _slab_choice so the pair can never be read
            # half-updated against the autotune's abandoned thread.
            self._kernel_choice = None  # derived-from: buffer (re-tuned)
            # autotune forensics for pack_stats(): {"source":
            # search|cache|single, "search_s", "candidates": [...],
            # "noise_floor"} once the first steady-state call resolves it
            self._autotune_stats = None
            # slab HBM cost scales with the port-case count, but the plan and
            # choice persist for the engine's life; dispatch re-checks the
            # budget against the ACTUAL q (plan time budgets q=2)
            self._slab_bytes_per_case = None
            self._slab_budget = None
            # set after an autotune TIMEOUT: {"event": Event, "waited": bool}
            # — the abandoned candidate thread's completion marker; dispatches
            # gate on it (_drain_autotune_orphan)
            self._autotune_orphan = None
            # guards the (_slab_choice, _slab_ops_cache) pair: the autotune's
            # rejection writes and the ops-cache fill can race an abandoned
            # candidate thread still inside _slab_ops_for
            self._slab_lock = guards.lock()
            self._counts_packed_jit = None  # derived-from: shapes
            # steady-state counts: cache the device-resident precompute per
            # port-case set so repeat evaluations run only the pallas kernel
            self._pre_jit = None  # derived-from: shapes
            self._counts_from_pre_jit = None  # derived-from: shapes
            self._counts_from_pre_packed_jit = None  # derived-from: shapes
            self._pre_cache = None  # derived-from: buffer (cases key + pre pytree)
            # the half of the precompute the port cases do not touch
            # (tiled._precompute_static), built at the first counts.pallas
            # call and kept: a request whose case set is not pinned runs
            # `counts.cases`, the program that starts where its cases enter.
            # Written by the issuing thread alone, like _pre_cache, and
            # never read by the autotune's orphan
            self._static_jit = None  # derived-from: shapes
            self._counts_cases_jit = None  # derived-from: shapes
            self._static_pre = None  # derived-from: buffer (static pytree)
            # the mesh counts entry's held pair (tiled.mesh_counts_programs),
            # one a (mesh, route, kernel, block), and the static half of the
            # precompute its `static` program left on the chips: (key of the
            # pair, static pytree, the pod count as a device scalar)
            self._mesh_counts_jits = {}  # derived-from: shapes
            self._mesh_static = None  # derived-from: buffer (key + static pytree)
            # gathered slab operands, cached next to the pre: building them
            # per dispatch cost more than the slab's depth cut saved
            self._slab_ops_jit = None  # derived-from: shapes
            self._counts_from_slab_ops_jit = None  # derived-from: shapes
            self._slab_ops_cache = None  # derived-from: buffer (gathered ops)
            self._pre_cache_misses = 0  # derived-from: buffer
            self._pre_cache_declined = None  # derived-from: buffer (declined key)
            self._last_counts_key = None  # derived-from: buffer
            self._has_ip_peers = (
                bool(np.any(self.encoding.ingress.peer_kind == PEER_IP))
                or bool(np.any(self.encoding.egress.peer_kind == PEER_IP))
            )
            # pod_ip_valid=True already proves parseability (the encoder's
            # IPv4 fast path), so only the residue — IPv6 pods and garbage —
            # pays ipaddress.ip_address; at 100k all-IPv4 pods this pass was
            # ~0.5 s of redundant parsing
            self._unparseable_ips = [
                ip
                for ip, v4 in zip(
                    self.encoding.cluster.pod_ips,
                    self.encoding.cluster.pod_ip_valid,
                )
                if not v4 and not _parseable_ip(ip)
            ]

    @property
    def pod_keys(self) -> List[str]:
        return self.encoding.cluster.pod_keys

    def pod_index(self) -> Dict[str, int]:
        return {k: i for i, k in enumerate(self.pod_keys)}

    def invalidate_after_patch(self) -> None:
        """Reset every VALUE-derived device cache after the serve layer
        (cyclonus_tpu/serve) patches the packed buffer in place.  Shapes
        are unchanged by contract, so the compiled programs — unpack,
        grid/counts kernels, pairs — all stay valid and are reused; the
        precompute / slab-operand pins and the device tensor views are
        stale data and must rebuild from the patched buffer (device-side
        work only: no host re-encode, no re-device_put of the buffer).
        The slab plan's per-tile window proof is churn-stale too, so the
        slab path stays disabled until the next full rebuild."""
        self._device_tensors = None
        self._class_device_tensors = None
        self._class_of_dev = None
        self._class_w_dev = None
        self._pre_cache = None
        self._pre_cache_misses = 0
        self._pre_cache_declined = None
        self._last_counts_key = None
        ti.PRE_CACHE_BYTES.set(0)
        self._static_pre = None
        self._mesh_static = None
        ti.STATIC_PRE_BYTES.set(0)
        with self._slab_lock:
            self._slab_choice = None
            self._slab_ops_cache = None
            # a tuned PACKED tile stays valid (it is a function of the
            # unchanged shapes); any DENSE-plan choice dies with the
            # slab plan — keeping a tuned "default" while _slab_choice
            # resets would leave the pair incoherent and suppress the
            # re-tune the fresh plan deserves
            if self._kernel_choice is not None and (
                self._kernel_choice.get("kernel") != "packed"
            ):
                self._kernel_choice = None
        self._slab_plan_state = None
        self._selpod_prebucket = None
        # ns-sort permutation: pod ns ids may have changed; [N] int32 is
        # re-uploaded lazily (a touched index vector, not a slab)
        self._pod_perm_dev = None
        self._pod_perm_host = None

    def _aot_plan(self, extra: str = "") -> str:
        """The dtype-plan half of the persistent AOT executable key
        (engine/aot_cache.py): packed32, with the form of the packed
        contraction (kernel.PACKED_CONTRACTION: every packed program can
        trace kernel.packed_any, whose code the key cannot see), vs the
        dense operand dtype, plus the tier flag.  Programs whose trace
        bakes per-engine constants (the unpack closures' leaf layout)
        append a metas digest via `extra` — two engines with equal
        buffer lengths but different leaf layouts must never share an
        executable."""
        from .kernel import PACKED_CONTRACTION
        from .pallas_kernel import _resolve_operand_dtype

        dtype = (
            f"packed32;{PACKED_CONTRACTION}"
            if self._pack
            else _resolve_operand_dtype(None)
        )
        plan = f"{dtype};tiered={self.tiers is not None}"
        return plan + (";" + extra if extra else "")

    @staticmethod
    def _metas_digest(unpack) -> str:
        """Stable digest of a _pack_tensors unpack closure's baked leaf
        layout ((dtype, shape, word offset) per path) — the part of an
        unpack-consuming program's identity the arg shapes alone can't
        see."""
        return aot_cache.digest(sorted(unpack.metas_by_path.items()))

    def aot_stats(self) -> Dict:
        """The per-process AOT executable-cache forensics (serve's
        prewarm report carries them)."""
        return aot_cache.counters()

    def _build_tensors(self) -> Dict:
        enc = self.encoding
        c = enc.cluster
        tensors = {
            "sel_req_kv": enc.sel_req_kv,
            "sel_exp_op": enc.sel_exp_op,
            "sel_exp_key": enc.sel_exp_key,
            "sel_exp_vals": enc.sel_exp_vals,
            "pod_ns_id": c.pod_ns_id,
            "pod_kv": c.pod_kv,
            "pod_key": c.pod_key,
            "pod_ip": c.pod_ip,
            "pod_ip_valid": c.pod_ip_valid,
            "ns_kv": c.ns_kv,
            "ns_key": c.ns_key,
            "ingress": _direction_tensors(enc.ingress),
            "egress": _direction_tensors(enc.egress),
        }
        if enc.tiers is not None:
            tensors["tiers"] = {
                "ingress": _tier_tensors(enc.tiers[0]),
                "egress": _tier_tensors(enc.tiers[1]),
            }
        for direction, denc in (("ingress", enc.ingress), ("egress", enc.egress)):
            if denc.host_ip_rows:
                # IPv6 / mixed-family IPBlocks: evaluate via the oracle's IP
                # matcher on host, inject as precomputed rows.
                n = c.n_pods
                mask = np.zeros((denc.n_peers,), dtype=bool)
                match = np.zeros((denc.n_peers, n), dtype=bool)
                for row, peer in denc.host_ip_rows:
                    mask[row] = True
                    for i, ip in enumerate(c.pod_ips):
                        match[row, i] = is_ip_address_match_for_ip_block(
                            ip, peer.ip_block
                        )
                tensors[direction]["host_ip_mask"] = mask
                tensors[direction]["host_ip_match"] = match
        return tensors

    # --- equivalence-class grid compression ------------------------------

    def _maybe_build_class_state(self, mode: str) -> str:
        """Bucket pods into label-equivalence classes and keep the
        compressed tensor set when compression is forced (mode "1") or
        worth it (auto: above the pod floor with a real reduction).
        Reuses the SAME host selector pass dead-target compaction paid
        for; when compaction's work budget skipped that pass, auto mode
        skips classes too (forcing recomputes it).  Returns the decision
        as cyclonus_tpu_class_route_total{outcome} names it: "kept", or
        why no class state was kept ("off", "below_floor",
        "no_selector_pass", "no_reduction")."""
        n = self.encoding.cluster.n_pods
        if n >= _CLASS_MAX_PODS_EXACT:
            return "off"
        if n == 0 or (mode != "1" and n < _class_auto_min_pods()):
            return "below_floor"
        selpod = self._selpod_prebucket
        if selpod is None:
            if mode != "1":
                return "no_selector_pass"
            selpod = self._selpod_prebucket = _selector_pod_matches_host(
                self._tensors
            )
        # TSS/LPM CIDR pre-classification (engine/cidrspace.py): when the
        # stage resolves (CYCLONUS_CIDR_TSS gate + distinct-spec floor +
        # HBM budget), the class signature's CIDR dimension rides the
        # [K] int32 partition signature instead of per-spec bits — the
        # O(specs)->O(partitions) cut that keeps classification feasible
        # on CIDR-heavy sets.  None = the dense bit path, byte-identical
        # to the pre-TSS signature.
        from . import cidrspace

        with phase("engine.cidrspace") as sp:
            space = cidrspace.resolve(
                self._tensors, mode=self._opt_cidr_tss, n_pods=n
            )
            # what cidr_stats() knows, here too: a refused class state
            # takes the space with it
            if space is None:
                sp.set(active=False)
            else:
                sp.set(
                    active=True,
                    specs=space.n_specs,
                    atoms=space.n_atoms,
                    partitions=space.n_partitions,
                    device=space.lpm_on_device(n),
                )
        with phase("engine.classify") as sp:
            pc = compute_pod_classes(self._tensors, selpod, cidr=space)
            kept = mode == "1" or pc.n_classes <= int(0.9 * n)
            sp.set(classes=pc.n_classes, pods=n, kept=kept)
        if not kept:
            # no real reduction: the second tensor set isn't worth it
            return "no_reduction"
        # engine.class_tensors: the second tensor set (one row a class),
        # then in __init__ the padding of both sets to their shape buckets
        with phase("engine.class_tensors"):
            ctensors_raw = gather_class_pod_rows(self._tensors, pc.class_rep)
        self._class_state = {
            "classes": pc,
            "ratio": n / max(pc.n_classes, 1),
            "ctensors_raw": ctensors_raw,
            "aux_bytes": 0,  # finalized after bucketing (engine __init__)
            "last_gather_s": None,
            "cidr": space,
        }
        ti.CLASS_PODS.set(n)
        ti.CLASS_COUNT.set(pc.n_classes)
        ti.CLASS_RATIO.set(self._class_state["ratio"])
        return "kept"

    def pod_classes(self):
        """The PodClasses of the active compression state, or None when
        compression is off / bypassed for this engine (analysis's
        audit_class_reduction consumes this)."""
        st = self._class_state
        return st["classes"] if st is not None else None

    def _class_aux_bytes(self) -> int:
        """Device bytes of the compression's gather/index tensors —
        charged against CYCLONUS_SLAB_MAX_BYTES wherever that budget is
        gated, so the compressed path can never over-commit the HBM it
        exists to save."""
        st = self._class_state
        return int(st["aux_bytes"]) if st is not None else 0

    def class_compression_stats(self) -> Dict:
        """The grid-compression summary (serve's /state carries it):
        pods, classes, ratio, the last broadcast-back epilogue seconds,
        and the rule-axis partition stats."""
        n = self.encoding.cluster.n_pods
        st = self._class_state
        if st is None:
            return {
                "active": False,
                "pods": n,
                "classes": None,
                "ratio": None,
                "gather_s": None,
                "partitions": self._partition_stats,
            }
        pc = st["classes"]
        return {
            "active": True,
            "pods": n,
            "classes": pc.n_classes,
            "ratio": round(st["ratio"], 4),
            "gather_s": st["last_gather_s"],
            "signature_bytes": pc.signature_bytes,
            "aux_bytes": st["aux_bytes"],
            "partitions": self._partition_stats,
        }

    def cidr_stats(self) -> Dict:
        """The TSS/LPM CIDR pre-classification summary (chip_smoke.py
        prints it): whether the stage is active, the distinct
        spec/atom/partition counts, the last LPM stage wall-clock and
        whether it ran on device, and the partition-tensor bytes charged
        to the HBM budget."""
        st = self._class_state
        space = st.get("cidr") if st is not None else None
        if space is None:
            return {
                "active": False,
                "distinct_cidrs": None,
                "atoms": None,
                "partitions": None,
                "lpm_s": None,
                "device": None,
                "bytes": 0,
            }
        return {
            "active": True,
            "distinct_cidrs": space.n_specs,
            "atoms": space.n_atoms,
            "partitions": space.n_partitions,
            "max_bucket": space.max_bucket,
            "host_rows": space.n_host_rows,
            "lpm_s": space.last_lpm_s,
            "device": space.last_device,
            "bytes": space.nbytes(),
        }

    def tier_stats(self) -> Dict:
        """The precedence-tier summary (serve's /state carries it):
        whether the lattice is active, the ANP object /
        flat rule-row counts, and the wall-clock of the last tiered grid
        evaluation (resolve_s; None until one ran)."""
        if self.tiers is None:
            return {
                "active": False,
                "anp_count": 0,
                "rule_rows": 0,
                "banp": False,
                "resolve_s": None,
            }
        enc_t = self.encoding.tiers
        rows = sum(t.n_rows for t in enc_t) if enc_t is not None else 0
        return {
            "active": True,
            "anp_count": len(self.tiers.anps),
            "rule_rows": rows,
            "banp": self.tiers.banp is not None,
            "resolve_s": self._tier_resolve_s,
        }

    def _class_resident_tensors(self) -> Dict:
        """The class-representative tensor set on the device, WITHOUT
        port cases: sent once through its own single-buffer transfer and
        kept until a patch or a class rebuild drops it.  Callers must
        not write into the dict."""
        import jax

        if self._class_device_tensors is None:
            buf = self._packed_transfer(
                "_class_packed_buf", "_class_unpack",
                self._class_state["ctensors"], name="unpack_classes",
            )
            if self._class_unpack_jit is None:
                self._class_unpack_jit = aot_cache.AotProgram(
                    "unpack.classes",
                    jax.jit(self._class_unpack),
                    plan=self._aot_plan(
                        self._metas_digest(self._class_unpack)
                    ),
                )
                self._class_unpack_jit.resolve(buf)
            with detail("engine.unpack"):
                self._class_device_tensors = self._class_unpack_jit(buf)
        return self._class_device_tensors

    def _ctensors_with_cases(
        self, cases: Sequence[PortCase], device: bool = False
    ) -> Dict:
        """Compressed-tensor twin of _tensors_with_cases: the class-
        representative tensor set + port-case arrays, optionally through
        its own single-buffer device transfer.  The grid and mesh routes'
        operand form (their AOT plans are keyed on the three case
        leaves); the counts route has its own (_class_counts_operands)."""
        st = self._class_state
        with detail("engine.case_tensors", device=device):
            q_port, q_name, q_proto = self._port_case_arrays(cases)
            if device:
                tensors = dict(self._class_resident_tensors())
            else:
                tensors = dict(st["ctensors"])
            tensors["q_port"] = q_port
            tensors["q_name"] = q_name
            tensors["q_proto"] = q_proto
        return tensors

    def _class_counts_operands(self, cases: Sequence[PortCase]):
        """(tensors, w, cases) for tiled.evaluate_grid_counts_classes:
        what the engine owns — the resident class tensors as they are,
        with no per-call copy, and the class weights, sent once — and
        what the call brings, its port cases as ONE int32 [3, Q] host
        array.  No table by case set: a user who sweeps distinct ports
        would never hit it."""
        import jax

        from .tiled import class_weights

        st = self._class_state
        with detail("engine.case_tensors", device=True):
            tensors = self._class_resident_tensors()
            if self._class_w_dev is None:
                pc = st["classes"]
                w = class_weights(
                    int(st["ctensors"]["pod_ns_id"].shape[0]),
                    pc.n_classes,
                    pc.class_size,
                )
                with phase("engine.device_put", bytes=w.nbytes):
                    self._class_w_dev = jax.device_put(w)
            q_cases = np.stack(self._port_case_arrays(cases))
        return tensors, self._class_w_dev, q_cases

    def _class_counts_eligible(self, q: int) -> bool:
        """The compressed counts route must itself fit the HBM budget it
        protects: aux/index tensors + the class precompute + row sums,
        all estimated host-side before any dispatch."""
        st = self._class_state
        if st is None:
            return False
        from ..utils import envflags

        budget = envflags.get_int("CYCLONUS_SLAB_MAX_BYTES")
        ct = st["ctensors"]
        cb = int(ct["pod_ns_id"].shape[0])
        t = sum(
            int(ct[d]["target_ns"].shape[0]) for d in ("ingress", "egress")
        )
        if self._pack:
            # packed plan: tallow_pk int32 [W, Cb, Q] + tmatch_pk
            # [W, Cb] + the bool tmatch — ~16x below the bf16 estimate
            # (the _pre_bytes_estimate twin; overstating it here would
            # silently decline the compressed route at exactly the
            # watch-scale sizes it exists for)
            w = sum(
                packed_words(int(ct[d]["target_ns"].shape[0]))
                for d in ("ingress", "egress")
            )
            est = st["aux_bytes"] + cb * (4 * w * (q + 1) + t) + cb * q * 12
        else:
            # tallow bf16 [T, Cb, Q] per direction + tmatch + f32 row sums
            est = st["aux_bytes"] + t * cb * (2 * q + 1) + cb * q * 12
        return est <= budget

    def _counts_classes(
        self,
        cases: Sequence[PortCase],
        n: int,
        *,
        sharded: bool = False,
        block: int = 1024,
        mesh=None,
    ) -> Dict[str, int]:
        """Compressed counts: class-grid weighted row sums on device
        (single-device, or class-axis-sharded over `mesh`), exact int64
        class-size weighting on host (tiled.py).  One epilogue for both
        routes so the stats/telemetry can never diverge."""
        st = self._class_state
        pc = st["classes"]
        if sharded:
            planspec.record("counts.sharded.classes")
            from .tiled import evaluate_grid_counts_classes_sharded

            counts, gather_s = evaluate_grid_counts_classes_sharded(
                self._ctensors_with_cases(cases),
                pc.n_classes,
                pc.class_size,
                n,
                block=block,
                mesh=mesh,
            )
        else:
            planspec.record("counts.classes")
            from .tiled import evaluate_grid_counts_classes

            with ti.eval_flight(
                "counts.classes", n, len(cases), classes=pc.n_classes
            ) as fl:
                counts, gather_s = evaluate_grid_counts_classes(
                    fl,
                    *self._class_counts_operands(cases),
                    pc.n_classes,
                    pc.class_size,
                    n,
                    pack=self._pack,
                )
        st["last_gather_s"] = gather_s
        ti.CLASS_EVALS.inc(path="sharded" if sharded else "counts")
        return counts

    def _evaluate_grid_classes(self, cases: Sequence[PortCase]) -> GridVerdict:
        """Compressed grid path: evaluate the C x C x Q class grid and
        broadcast back to pod axes with the int32 gather epilogue, which
        emits the tables as 32-bit words (kernel.cell_words) —
        kernel + gather trace into ONE jit, so the path keeps the dense
        path's single-execution property."""
        import jax

        from .kernel import WORD_FORMAT, evaluate_grid_kernel, gather_class_words

        planspec.record("grid.classes")
        st = self._class_state
        n = self.encoding.cluster.n_pods
        with ti.eval_flight(
            "grid.classes",
            n,
            len(cases),
            classes=st["classes"].n_classes,
            dispatch_only=True,
        ) as fl:
            tensors = self._ctensors_with_cases(cases, device=True)
            if self._class_of_dev is None:
                with phase("engine.device_put"):
                    self._class_of_dev = jax.device_put(
                        st["classes"].class_of_pod
                    )
            if self._class_grid_jit is None:
                pack = self._pack

                def grid_classes(t, co):
                    return gather_class_words(
                        evaluate_grid_kernel(t, pack=pack), co
                    )

                self._class_grid_jit = aot_cache.AotProgram(
                    "grid.classes",
                    jax.jit(grid_classes),
                    plan=self._aot_plan(WORD_FORMAT),
                )
                # the executable is obtained here, so that engine.dispatch
                # below is the call that enqueues and nothing else
                self._class_grid_jit.resolve(tensors, self._class_of_dev)
            t0 = time.perf_counter()
            with phase("engine.dispatch"):
                out = self._class_grid_jit(tensors, self._class_of_dev)
            if self.tiers is not None:
                self._tier_resolve_s = time.perf_counter() - t0
            ti.CLASS_EVALS.inc(path="grid")
        return GridVerdict(
            self.pod_keys,
            list(cases),
            out["ingress"],
            out["egress"],
            out["combined"],
            eval_id=fl.eval_id,
        )

    def _evaluate_grid_sharded_classes(
        self, cases: Sequence[PortCase], mesh, schedule=None
    ) -> GridVerdict:
        """Compressed mesh path: the shard_map program runs over the
        class axis — with the ring schedule, a C x C ring over class
        representatives — and broadcasts back to pod rows inside the
        same program, each device its own rows, as words
        (sharded.evaluate_grid_sharded with `class_of`)."""
        from .sharded import evaluate_grid_sharded

        planspec.record("grid.sharded.classes")
        pc = self._class_state["classes"]
        tables, eval_id = evaluate_grid_sharded(
            self._ctensors_with_cases(cases), pc.n_classes, mesh=mesh,
            schedule=schedule, class_of=pc.class_of_pod,
        )
        ti.CLASS_EVALS.inc(path="sharded")
        return GridVerdict(self.pod_keys, list(cases), *tables, eval_id=eval_id)

    def _pipelined_classes(self, cases: Sequence[PortCase], reps: int):
        """Compressed twin of the pipelined steady-state measurement:
        `reps` async dispatches of the class row-sum program, one
        readback, the same exact host finish."""
        import time as _time

        from .tiled import (
            _class_rowsums_kernel,
            class_counts_finish,
            class_rowsums_plan,
        )

        st = self._class_state
        pc = st["classes"]
        n = self.encoding.cluster.n_pods
        tensors = self._class_resident_tensors()
        q_cases = np.stack(self._port_case_arrays(cases))
        w, block, n_tiles = class_rowsums_plan(
            tensors, pc.n_classes, pc.class_size
        )
        args = (tensors, w, q_cases, block, n_tiles, self._pack)
        out = _class_rowsums_kernel(*args)
        np.asarray(out)  # warm barrier
        t0 = _time.perf_counter()
        outs = [_class_rowsums_kernel(*args) for _ in range(reps)]
        rs = np.asarray(outs[-1])  # in-order stream: one barrier
        dt = (_time.perf_counter() - t0) / reps
        counts = class_counts_finish(
            rs, pc.class_size, pc.n_classes, len(cases), n
        )
        if dt > 0:
            ti.EVAL_PIPELINED_CELLS_PER_SEC.set(counts["cells"] / dt)
        return dt, counts

    def _port_case_arrays(self, cases: Sequence[PortCase]):
        vocab = self.encoding.cluster.vocab
        q_port = np.array([c.port for c in cases], dtype=np.int32)  # shape: (Q,) int32
        q_name = np.array(
            [vocab.port_name.get(c.port_name, -1) for c in cases], dtype=np.int32
        )  # shape: (Q,) int32; sentinel: -1=unnamed
        # protocols unseen at compile time can match no spec: id -1 (pads
        # are -2, real ids >= 0)
        q_proto = np.array(
            [vocab.proto.get(c.protocol, -1) for c in cases], dtype=np.int32
        )
        return q_port, q_name, q_proto

    def _check_ips(self) -> None:
        if self._has_ip_peers and self._unparseable_ips:
            # The oracle raises when an IP peer matcher meets an unparseable
            # pod IP (kube/ipaddr.py); a grid evaluation hits every pair, so
            # raise with the same class of error.
            raise ValueError(
                f"unable to parse IP(s) {self._unparseable_ips[:3]!r} "
                f"while IPBlock peers are present"
            )

    def evaluate_grid(self, cases: Sequence[PortCase]) -> GridVerdict:
        """Single-device evaluation of the full N x N x Q verdict grid.
        Results stay on device (see GridVerdict)."""
        from .kernel import WORD_FORMAT, evaluate_grid_words

        self._check_ips()
        if not cases:
            n = self.encoding.cluster.n_pods
            empty = np.zeros((0, n, n), dtype=bool)
            return GridVerdict(self.pod_keys, [], empty, empty.copy(), empty.copy())
        if self._class_state is not None:
            return self._evaluate_grid_classes(cases)
        planspec.record("grid.dense")
        n = self.encoding.cluster.n_pods
        with ti.eval_flight("grid", n, len(cases), dispatch_only=True) as fl:
            tensors = self._tensors_with_cases(cases, device=True)
            if self._grid_aot is None:
                self._grid_aot = aot_cache.AotProgram(
                    "grid",
                    evaluate_grid_words,
                    plan=self._aot_plan(WORD_FORMAT),
                    static_argnames=("pack",),
                )
                self._grid_aot.resolve(tensors, pack=self._pack)
            # dispatch-only timing: jit calls return once enqueued (async);
            # device execution time lands in grid.wait / allow_stats
            t0 = time.perf_counter()
            with phase("engine.dispatch"):
                out = self._grid_aot(tensors, pack=self._pack)
            if self.tiers is not None:
                self._tier_resolve_s = time.perf_counter() - t0
        # kernel emits the words in [q, ...] layout directly: one device
        # execution total.  Bucketing pads the pod axis; GridVerdict
        # leaves the pad rows and cells out of every host view and count.
        return GridVerdict(
            self.pod_keys,
            list(cases),
            out["ingress"],
            out["egress"],
            out["combined"],
            eval_id=fl.eval_id,
        )

    def _packed_transfer(
        self, buf_attr: str, unpack_attr: str, tensors: Dict, name: str
    ):
        """Single-buffer device copy with per-engine caching (one
        transfer instead of one per leaf — see _pack_tensors)."""
        if getattr(self, buf_attr) is None:
            import jax

            with phase("engine.device_put") as sp:
                packed, unpack = _pack_tensors(tensors, name=name)
                setattr(self, buf_attr, jax.device_put(packed))
                setattr(self, unpack_attr, unpack)
                sp.set(bytes=packed.nbytes)
        return getattr(self, buf_attr)

    def _ensure_packed(self):
        """Packed device buffer of the caller-order tensors (grid paths)."""
        return self._packed_transfer(
            "_packed_buf", "_unpack", self._tensors, name="unpack_tensors"
        )

    def _tensors_with_cases(
        self, cases: Sequence[PortCase], device: bool = False
    ) -> Dict:
        """Tensors + port-case arrays.  device=True reuses the packed
        device buffer (paths that don't re-pad the pod axis host-side)."""
        with detail("engine.case_tensors", device=device):
            q_port, q_name, q_proto = self._port_case_arrays(cases)
            if device:
                import jax

                if self._device_tensors is None:
                    buf = self._ensure_packed()
                    if self._unpack_jit is None:
                        self._unpack_jit = aot_cache.AotProgram(
                            "unpack",
                            jax.jit(self._unpack),
                            plan=self._aot_plan(
                                self._metas_digest(self._unpack)
                            ),
                        )
                        self._unpack_jit.resolve(buf)
                    with detail("engine.unpack"):
                        self._device_tensors = self._unpack_jit(buf)
                tensors = dict(self._device_tensors)
            else:
                tensors = dict(self._tensors)
            tensors["q_port"] = q_port
            tensors["q_name"] = q_name
            tensors["q_proto"] = q_proto
        return tensors

    def evaluate_grid_counts(
        self,
        cases: Sequence[PortCase],
        block: int = 1024,
        backend: Optional[str] = None,
    ) -> Dict[str, int]:
        """Tiled full-grid allow counts for grids too large to materialize
        (one device execution, one small readback).  The default picks
        per platform: "pallas" — the fused verdict+count kernel
        (engine/pallas_kernel.py; adaptive tile sizes, `block` ignored),
        the fastest path at every measured scale — on TPU, where it
        compiles via Mosaic; "xla" — the lax.fori_loop tile loop
        (engine/tiled.py) — elsewhere, where pallas would fall back to
        slow interpret mode.  Identical results by construction; pass
        backend explicitly to force either."""
        explicit = backend is not None
        if backend is None:
            import jax

            backend = "pallas" if jax.default_backend() == "tpu" else "xla"
        if backend not in ("xla", "pallas"):
            raise ValueError(
                f"unknown counts backend {backend!r} (want 'xla' or "
                f"'pallas'; mesh-parallel = evaluate_grid_counts_sharded)"
            )
        # tiers x pallas: the decision (legal under the packed fused
        # tier epilogue; else fallback on auto, loud failure on an
        # explicit request — silently rewriting it would let a benchmark
        # publish the XLA rate under the pallas label) is a declared
        # cell of the planspec compatibility matrix, resolved there so
        # the declaration and the dispatch cannot drift
        backend = planspec.resolve_counts_backend(
            backend=backend,
            explicit=explicit,
            tiers=self.tiers is not None,
            pack=self._pack,
            packed_tier_ok=self._packed_tier_ok,
        )
        self._check_ips()
        n = self.encoding.cluster.n_pods
        if not cases or n == 0:
            return {"ingress": 0, "egress": 0, "combined": 0, "cells": 0}
        if self._class_state is not None and self._class_counts_eligible(
            len(cases)
        ):
            # compressed route (either backend: identical by construction;
            # the class grid is small enough that the XLA tile loop is
            # already device-bound) — bypassed when the estimate would
            # blow the HBM budget, falling back to the dense kernels
            return self._counts_classes(cases, n)
        if backend == "pallas":
            return self._counts_pallas_packed(cases, n)
        planspec.record("counts.xla")
        from .tiled import evaluate_grid_counts

        # the xla path pads the pod axis with numpy before dispatch
        return evaluate_grid_counts(
            self._tensors_with_cases(cases), n, block=block, pack=self._pack
        )

    def _packed_tier_ok(self) -> bool:
        """The fused tier epilogue unrolls statically over the bucketed
        rule rows (pallas_kernel.PACKED_TIER_MAX_ROWS); past the
        ceiling tiered counts fall back to the XLA tile loop.  Shared
        implementation with the fused class-counts route
        (pallas_kernel.packed_tier_eligible) so the two gates cannot
        drift."""
        from .pallas_kernel import packed_tier_eligible

        return packed_tier_eligible(self._tensors)

    def _pre_bytes_estimate(self, q: int) -> int:
        """Host-side size estimate of the precompute pytree (dominated by
        the per-direction [T, N, Q] tallow tensors): deciding the cache
        cap BEFORE dispatching the split path matters at multi-million-pod
        scale, where compiling the split programs just to find the result
        uncacheable wastes the whole compile (not measured on the current
        machine)."""
        n = int(self._tensors["pod_ns_id"].shape[0])
        t = sum(
            int(self._tensors[d]["target_ns"].shape[0])
            for d in ("ingress", "egress")
        )
        if self._pack:
            # packed plan: tallow_pk int32 [W, N, Q] + tmatch_pk [W, N]
            # + the bool tmatch [T, N] — ~16x below the bf16 estimate
            w = sum(
                packed_words(int(self._tensors[d]["target_ns"].shape[0]))
                for d in ("ingress", "egress")
            )
            return n * (4 * w * (q + 1) + t)
        # tallow bf16 [T, N, Q] per direction + tmatch bool [T, N] + small
        return t * n * (2 * q + 1)

    def _slab_plan(self, perm: np.ndarray):
        """Per-tile target-slab windows for the pallas slab kernel, or
        None when it doesn't apply.

        Host-side eligibility with the SAME reduction the kernel's
        safety rests on: per direction, every pod tile's matching
        targets (on the ns-sorted axis = perm order) must fit one
        SLAB_W window (pallas_kernel.slab_windows).  CYCLONUS_PALLAS_SLAB
        modes: "auto" (default) plans on TPU and lets the first
        steady-state call TIME both programs and keep the winner
        (_autotune_slab) — the depth-cut win only exists on hardware and
        interpret-mode timing is meaningless, so auto never engages off
        TPU; "1" forces the slab kernel (how CPU tests and chip_smoke.py
        exercise it); "0" disables.  Also requires the
        cluster to span at least two src tiles (below that the
        single-chunk kernel is already minimal) and the materialized
        slabs to fit the byte budget.  The numpy tmatch twin here is the
        same formula as kernel.direction_precompute, O(T*N) once per
        engine."""
        import os

        from .pallas_kernel import (
            SLAB_BD,
            SLAB_BS,
            SLAB_W,
            _resolve_operand_dtype,
            slab_w_aug,
            slab_windows,
        )

        if self._pack:
            # the packed kernel contracts over ceil(T/32) words — a far
            # deeper depth cut than the slab window, from the SAME
            # precompute with no gathered-operand HBM pin — so the slab
            # path (and its multi-second host window pass) is retired
            # under the packed dtype plan; CYCLONUS_PACK=0 restores it
            return None
        mode = os.environ.get("CYCLONUS_PALLAS_SLAB", "auto").lower()
        if mode == "auto":
            import jax

            if jax.default_backend() != "tpu":
                return None
            if not _pre_cache_enabled():
                # the autotune point IS the first steady-state (pinned
                # precompute) call; with the pre-cache off it would
                # never fire, so don't pay the plan for a dead path
                return None
        elif mode != "1":
            return None
        n_b = int(self._tensors["pod_ns_id"].shape[0])
        if n_b < 2 * SLAB_BS:
            return None
        # upper gate: the slabs are materialized [q, n_tiles, w, N] HBM
        # copies (see verdict_counts_pallas_slab's design note); past
        # ~150k pods their bytes explode quadratically-in-tiles and the
        # chunked kernels win.  Budget both directions at 2 port cases
        # (at the widest ladder rung; a narrower chosen w only shrinks).
        n_tiles = -(-n_b // SLAB_BS) + -(-n_b // SLAB_BD)
        # slab_w_aug: the kernel augments each window with the OR-term
        # row and pads to the dtype sublane tile.  The slabs materialize
        # in the OPERAND dtype, so the budget is elements * itemsize —
        # counting elements as bytes let bf16 slabs blow 2x past
        # CYCLONUS_SLAB_MAX_BYTES
        itemsize = 2 if _resolve_operand_dtype(None) == "bf16" else 1
        bytes_per_case = n_tiles * slab_w_aug() * n_b * itemsize
        from ..utils import envflags

        budget = envflags.get_int("CYCLONUS_SLAB_MAX_BYTES")
        # the class-compression gather/index tensors share the budget:
        # without counting them here the slab + aux could jointly
        # over-commit HBM exactly when compression is supposed to save it
        aux = self._class_aux_bytes()
        # watermark gauges: planned slab HBM (q=2 budget point) vs the
        # budget — set before the gate so a rejected plan is visible too
        ti.SLAB_HBM_BYTES.set(2 * bytes_per_case + aux)
        ti.SLAB_HBM_BUDGET_BYTES.set(budget)
        if 2 * bytes_per_case + aux > budget:
            return None
        self._slab_bytes_per_case = bytes_per_case
        self._slab_budget = budget
        import jax

        n = self.encoding.cluster.n_pods
        if self._selpod_prebucket is not None:
            # pad the compaction-time pass to the bucketed axes: pad
            # selector rows match nothing; pad pod columns diverge from
            # the device (empty selectors match pads there) but every
            # pad column is force-masked below, so False is safe
            pre = self._selpod_prebucket
            selpod = np.zeros(
                (self._tensors["sel_req_kv"].shape[0], n_b), dtype=bool
            )
            selpod[: pre.shape[0], : pre.shape[1]] = pre
        else:
            selpod = _selector_pod_matches_host(self._tensors)
        pod_ns = self._tensors["pod_ns_id"]
        # adaptive window width: the slab kernel's cost follows its
        # contraction depth, so contract over the NARROWEST ladder rung
        # whose windows cover every tile's band in both directions —
        # target bands at the bench shape are ~5-10 rows, far below the
        # conservative SLAB_W.  Wider-w correctness is monotone (rows
        # outside a tile's band are zero for its columns), so one shared
        # w = the max of the two directions' smallest fits.
        # rungs never exceed the configured SLAB_W ceiling (tests set it
        # low to drive the gate-rejection path)
        ladder = sorted({max(1, SLAB_W // 4), max(1, SLAB_W // 2), SLAB_W})
        plan = {}
        w_need = ladder[0]
        for direction, tile in (("egress", SLAB_BS), ("ingress", SLAB_BD)):
            d = self._tensors[direction]
            tm = d["target_ns"][:, None] == pod_ns[None, :]
            if selpod.size and d["target_sel"].size:
                t_sel = np.clip(d["target_sel"], 0, selpod.shape[0] - 1)
                tm &= selpod[t_sel]
            tm = tm[:, perm]
            tm[:, n:] = False  # pads sort last; mirrors the kernel's mask
            t0 = ok = None
            for w_try in ladder:
                t0, ok = slab_windows(tm, tile, w_try)
                if ok:
                    w_need = max(w_need, w_try)
                    break
            if not ok:
                return None
            plan[direction] = jax.device_put(t0)
        plan["w"] = w_need
        if mode == "1":
            # forced mode skips the autotune; set the choice only now
            # that the plan is actually accepted (a stale True with no
            # plan would break the invariant autotune readers rely on)
            with self._slab_lock:
                self._slab_choice = True
                self._kernel_choice = {"kernel": "slab"}
        return plan

    def _drain_autotune_orphan(self) -> None:
        """After an autotune timeout the abandoned daemon thread can
        still hold one in-flight compile+execution on the same backend.
        Before the next dispatch, wait briefly for it to finish (first
        call only; waiting forever would turn the contained candidate
        failure into the very stall it guards against).  Every dispatch
        that proceeds while the orphan is still live is counted in the
        autotune telemetry, so a polluted timing is recognizable."""
        orphan = self._autotune_orphan
        if orphan is None:
            return
        import os

        timeout = (
            0.0
            if orphan["waited"]
            else float(os.environ.get("CYCLONUS_AUTOTUNE_DRAIN_S", "5"))
        )
        orphan["waited"] = True
        if orphan["event"].wait(timeout):
            self._autotune_orphan = None
            return
        if self._slab_autotune is not None:
            self._slab_autotune["orphan_overlap_dispatches"] = (
                self._slab_autotune.get("orphan_overlap_dispatches", 0) + 1
            )

    def _autotune_enabled(self) -> bool:
        """CYCLONUS_AUTOTUNE: "auto" (default — tune on TPU, where the
        timings mean something), "1" (force: how CPU tests exercise the
        search/persistence machinery in interpret mode), "0" (off)."""
        import os

        mode = os.environ.get("CYCLONUS_AUTOTUNE", "auto").lower()
        if mode == "0":
            return False
        if mode == "1":
            return True
        import jax

        return jax.default_backend() == "tpu"

    def _autotune_key(self, q: int) -> str:
        """Persisted-cache key: (shape bucket, mesh, dtype plan) — see
        engine/autotune.py for why exactly these dimensions make a
        winner transferable across processes."""
        import jax

        from . import autotune as at
        from .pallas_kernel import _resolve_operand_dtype

        t = self._tensors
        shape = {
            "n": int(t["pod_ns_id"].shape[0]),
            "te": int(t["egress"]["target_ns"].shape[0]),
            "ti": int(t["ingress"]["target_ns"].shape[0]),
            "q": int(q),
            "tiered": self.tiers is not None,
            "classes": self._class_state is not None,
        }
        devs = jax.devices()
        mesh = (
            f"{jax.default_backend()}:{devs[0].device_kind}:{len(devs)}"
        )
        dtype = "packed32" if self._pack else _resolve_operand_dtype(None)
        return at.make_key(shape, mesh, dtype)

    def _timed_rounds(self, dispatch, cancelled=None):
        """(best_s, round_times, out): min-of-N pipelined timing.  Each
        round issues CYCLONUS_AUTOTUNE_REPS async dispatches with ONE
        value readback as the barrier; the candidate keeps the MIN over
        CYCLONUS_AUTOTUNE_ROUNDS rounds — the same min-of-N discipline
        the overhead tests use, because a single-shot comparison under
        host timing jitter can pick the loser."""
        import os
        import time as _time

        out = dispatch()
        np.asarray(out)  # compile + first execution outside the timing
        reps = max(1, int(os.environ.get("CYCLONUS_AUTOTUNE_REPS", "4")))
        rounds = max(1, int(os.environ.get("CYCLONUS_AUTOTUNE_ROUNDS", "3")))
        times = []
        for _ in range(rounds):
            t0 = _time.perf_counter()
            outs = []
            for _ in range(reps):
                if cancelled is not None and cancelled["v"]:
                    raise RuntimeError("autotune candidate cancelled")
                outs.append(dispatch())
            np.asarray(outs[-1])  # in-order stream: one barrier covers all
            times.append((_time.perf_counter() - t0) / reps)
        return min(times), times, out

    @staticmethod
    def _noise_floor(baseline_rounds) -> float:
        """The margin a challenger must beat the incumbent by: at least
        10%, widened to the incumbent's own observed round-to-round
        spread (capped at 50%) — if the baseline wobbles 30% between
        rounds, a 12% 'win' is noise, not signal."""
        lo = min(baseline_rounds)
        hi = max(baseline_rounds)
        spread = (hi - lo) / max(lo, 1e-9)
        return max(0.10, min(0.5, spread))

    def _autotune_slab(self, n32, key):
        """Steady-state kernel autotune for the DENSE (CYCLONUS_PACK=0)
        dtype plan: time the default and the slab counts programs from
        the SAME pinned precompute and keep the winner for the rest of
        the engine's life — min-of-N rounds per leg (_timed_rounds)
        with a noise-floor margin (_noise_floor), the winner persisted
        via engine/autotune.py and ADOPTED search-free by the next
        process with the same (shape bucket, mesh, dtype plan).  The
        candidate is the slab kernel dispatched FROM CACHED OPERANDS
        (_slab_ops_for): the one-time gather build happens inside the
        bounded candidate leg but outside its timed loop, so the
        comparison is steady state vs steady state.  Returns the
        winner's partials for the call that paid for the tuning."""
        import logging
        import time as _time

        from . import autotune as at

        q = len(key[0]) // 4  # key[0] is q_port.tobytes() (int32)
        akey = self._autotune_key(q)
        persisted = at.load_winner(akey)
        if persisted is not None and persisted.get("kernel") in (
            "slab",
            "default",
        ):
            chose_slab = persisted["kernel"] == "slab"
            with self._slab_lock:
                self._slab_choice = chose_slab
                self._kernel_choice = {"kernel": persisted["kernel"]}
                if not chose_slab:
                    self._slab_ops_cache = None
            ti.AUTOTUNE_CACHE.inc(outcome="hit")
            self._autotune_stats = {
                "source": "cache",
                "winner": dict(persisted),
                "search_s": 0.0,
                "candidates": [],
            }
            if chose_slab:
                return self._counts_from_slab_ops_jit(self._slab_ops_for(key))
            return self._counts_from_pre_jit(
                self._pre_cache[1], n32, None, None
            )
        if at.cache_path() is not None:
            ti.AUTOTUNE_CACHE.inc(outcome="miss")
        ti.AUTOTUNE_SEARCHES.inc()
        t_search0 = _time.perf_counter()

        pre = self._pre_cache[1]
        cancelled = {"v": False}

        t_default, rounds_default, out_default = self._timed_rounds(
            lambda: self._counts_from_pre_jit(pre, n32, None, None),
            cancelled,
        )
        # the candidate leg is BOUNDED as well as caught: its first call
        # compiles a brand-new program, and a wedged compile must
        # reject the candidate, not stall the caller into a watchdog
        # kill.  On
        # timeout the abandoned daemon thread finishes its in-flight
        # compile+execution plus up to reps-1 already-queued pipelined
        # executions (~0.1 s each; the async dispatches enqueue within
        # milliseconds, so the cancel flag rarely interrupts the loop) —
        # the orphan gate (_drain_autotune_orphan) bounds and counts any
        # overlap with the caller's subsequent default-path work.
        import threading

        from ..utils import envflags
        from ..utils.bounded import run_bounded

        timeout_s = envflags.get_float("CYCLONUS_AUTOTUNE_TIMEOUT_S")
        candidate_done = threading.Event()

        def candidate():
            try:
                # the one-time gather build (a fresh program of its own)
                # is bounded here but excluded from the timed loop
                ops = self._slab_ops_for(key)
                return self._timed_rounds(
                    lambda: self._counts_from_slab_ops_jit(ops), cancelled
                )
            finally:
                candidate_done.set()

        status, value = run_bounded(candidate, timeout_s)
        if status != "ok":
            cancelled["v"] = True
            # compile/run failure or timeout: the candidate rejects
            # itself — it must never take down the proven default path
            # (this autotune is the only place the slab program runs
            # unforced, so the failure is contained here).  Rejection and
            # cache clear happen atomically under _slab_lock: the
            # abandoned thread may still be inside _slab_ops_for, and an
            # unguarded clear here could be overwritten by its cache
            # fill, re-pinning slab HBM for a rejected kernel
            with self._slab_lock:
                self._slab_choice = False
                self._kernel_choice = {"kernel": "default"}
                self._slab_ops_cache = None
            # the rejection is telemetry too: _slab_autotune must show WHY
            # there are no timed legs, and whether the abandoned thread's
            # in-flight work later raced a real dispatch
            self._slab_autotune = {
                "default_s": round(t_default, 4),
                "candidate": status,
                "candidate_error": None if status == "timeout" else repr(value),
                "orphan_overlap_dispatches": 0,
            }
            self._autotune_stats = {
                "source": "search",
                "winner": {"kernel": "default"},
                "search_s": round(_time.perf_counter() - t_search0, 4),
                "candidates": [
                    {"kernel": "default", "s": round(t_default, 4)},
                    {"kernel": "slab", "status": status},
                ],
            }
            ti.AUTOTUNE_OUTCOMES.inc(outcome=status)
            if status == "timeout":
                # the abandoned daemon thread may still hold one in-flight
                # compile+execution; gate the NEXT dispatch on it so a
                # spurious slab execution cannot silently pollute the
                # default path's first timed leg (_drain_autotune_orphan)
                self._autotune_orphan = {
                    "event": candidate_done, "waited": False
                }
            logging.getLogger(__name__).warning(
                "slab autotune: candidate %s (%s) -> default",
                "timed out" if status == "timeout" else "failed",
                f"{timeout_s:g}s" if status == "timeout" else repr(value),
            )
            return out_default
        t_slab, rounds_slab, out_slab = value
        # min-of-N verdict with a noise floor: the slab must beat the
        # default by MORE than the default's own observed jitter (at
        # least the historical 10% margin) — a single-shot comparison
        # could pick the loser under timing noise
        floor = self._noise_floor(rounds_default)
        chose_slab = bool(t_slab < (1.0 - floor) * t_default)
        with self._slab_lock:
            self._slab_choice = chose_slab
            self._kernel_choice = {
                "kernel": "slab" if chose_slab else "default"
            }
            if not chose_slab:
                # a timing-rejected slab never dispatches again: its
                # cached operands (up to the slab byte budget of HBM)
                # must not stay pinned next to the precompute
                self._slab_ops_cache = None
        search_s = _time.perf_counter() - t_search0
        self._slab_autotune = {
            "default_s": round(t_default, 4),
            "slab_s": round(t_slab, 4),
            "noise_floor": round(floor, 4),
        }
        winner = {"kernel": "slab" if chose_slab else "default"}
        self._autotune_stats = {
            "source": "search",
            "winner": winner,
            "search_s": round(search_s, 4),
            "noise_floor": round(floor, 4),
            "candidates": [
                {"kernel": "default", "s": round(t_default, 4)},
                {"kernel": "slab", "s": round(t_slab, 4)},
            ],
        }
        if at.store_winner(
            akey,
            winner,
            {"default_s": t_default, "slab_s": t_slab},
        ):
            ti.AUTOTUNE_CACHE.inc(outcome="store")
        ti.AUTOTUNE_OUTCOMES.inc(
            outcome="slab" if chose_slab else "default"
        )
        logging.getLogger(__name__).info(
            "slab autotune: default %.4fs, slab %.4fs (floor %.0f%%) -> %s",
            t_default,
            t_slab,
            floor * 100,
            "slab" if chose_slab else "default",
        )
        return out_slab if chose_slab else out_default

    def _autotune_packed(self, n32, key, q: int):
        """Steady-state tile autotune for the PACKED dtype plan: the
        candidates are the packed kernel at every eligible (bs, bd) of
        pallas_kernel.PACKED_TILE_CANDIDATES, enumerated per shape
        bucket, timed min-of-N from the SAME pinned precompute, the
        winner adopted for the engine's life AND persisted keyed by
        (shape bucket, mesh, dtype plan) — a restarted process adopts
        it with zero candidate search (the AUTOTUNE_SEARCHES counter
        stays flat; asserted by tests/test_engine_packed.py).  Returns
        the winner's partials for the call that paid for the tuning."""
        import logging
        import os
        import time as _time

        from ..utils.bounded import run_bounded
        from . import autotune as at
        from .pallas_kernel import PACKED_TILE_CANDIDATES

        n_b = int(self._tensors["pod_ns_id"].shape[0])
        cands = [PACKED_TILE_CANDIDATES[0]]
        for bs, bd in PACKED_TILE_CANDIDATES[1:]:
            # a tile taller than the problem only adds padding; the
            # int32 partial-count bound re-checks like _tiles_for
            if n_b > bs and bs * max(n_b, bd) < 2**31:
                cands.append((bs, bd))

        def adopt(bs, bd):
            choice = {"kernel": "packed", "bs": int(bs), "bd": int(bd)}
            with self._slab_lock:
                self._kernel_choice = choice
                self._slab_choice = False
            return choice

        akey = self._autotune_key(q)
        pre = self._pre_cache[1]
        persisted = at.load_winner(akey)
        if (
            persisted is not None
            and persisted.get("kernel") == "packed"
            and (persisted.get("bs"), persisted.get("bd")) in cands
        ):
            choice = adopt(persisted["bs"], persisted["bd"])
            ti.AUTOTUNE_CACHE.inc(outcome="hit")
            self._autotune_stats = {
                "source": "cache",
                "winner": choice,
                "search_s": 0.0,
                "candidates": [],
            }
            return self._counts_from_pre_packed_jit(
                pre, n32, bs=choice["bs"], bd=choice["bd"]
            )
        if at.cache_path() is not None:
            ti.AUTOTUNE_CACHE.inc(outcome="miss")
        if len(cands) == 1:
            # one eligible tile: nothing to search, nothing to persist
            choice = adopt(*cands[0])
            self._autotune_stats = {
                "source": "single",
                "winner": choice,
                "search_s": 0.0,
                "candidates": [
                    {"kernel": "packed", "bs": cands[0][0], "bd": cands[0][1]}
                ],
            }
            return self._counts_from_pre_packed_jit(
                pre, n32, bs=cands[0][0], bd=cands[0][1]
            )

        ti.AUTOTUNE_SEARCHES.inc()
        t_search0 = _time.perf_counter()
        from ..utils import envflags

        timeout_s = envflags.get_float("CYCLONUS_AUTOTUNE_TIMEOUT_S")
        results = []  # (bs, bd, best_s, rounds, out) for candidates that ran
        stats = []
        base_rounds = None
        for idx, (bs, bd) in enumerate(cands):
            def leg(_bs=bs, _bd=bd):
                return self._timed_rounds(
                    lambda: self._counts_from_pre_packed_jit(
                        pre, n32, bs=_bs, bd=_bd
                    )
                )

            if idx == 0:
                # the default tile is the proven configuration: timed
                # unbounded (it is also the fallback on any failure)
                best, rounds, out = leg()
                base_rounds = rounds
                results.append((bs, bd, best, out))
                stats.append(
                    {"kernel": "packed", "bs": bs, "bd": bd,
                     "s": round(best, 4)}
                )
                continue
            # every challenger compiles a fresh program: bounded so a
            # wedged compile rejects the CANDIDATE, not the run
            status, value = run_bounded(leg, timeout_s)
            if status == "ok":
                best, rounds, out = value
                results.append((bs, bd, best, out))
                stats.append(
                    {"kernel": "packed", "bs": bs, "bd": bd,
                     "s": round(best, 4)}
                )
            else:
                # the rejection keeps its reason: a tile the compiler
                # refuses must be repaired or taken out of
                # PACKED_TILE_CANDIDATES, not re-rejected unseen
                reason = (
                    f"{type(value).__name__}: {value}"
                    if status == "error"
                    else f"no result within {timeout_s:g}s"
                )
                stats.append(
                    {"kernel": "packed", "bs": bs, "bd": bd,
                     "status": status, "error": reason}
                )
                logging.getLogger(__name__).warning(
                    "packed autotune: tile (%d, %d) rejected (%s): %s",
                    bs, bd, status, reason,
                )
                ti.AUTOTUNE_OUTCOMES.inc(outcome=status)

        # min-of-N winner, noise-floored against the default tile: a
        # challenger must beat it by more than its own observed jitter
        floor = self._noise_floor(base_rounds)
        d_bs, d_bd, t_default, out_default = results[0]
        winner = (d_bs, d_bd, t_default, out_default)
        for bs, bd, best, out in results[1:]:
            if best < (1.0 - floor) * winner[2]:
                winner = (bs, bd, best, out)
        choice = adopt(winner[0], winner[1])
        search_s = _time.perf_counter() - t_search0
        self._autotune_stats = {
            "source": "search",
            "winner": choice,
            "search_s": round(search_s, 4),
            "noise_floor": round(floor, 4),
            "candidates": stats,
        }
        if at.store_winner(
            akey, choice, {c.get("bs", 0): c.get("s") for c in stats}
        ):
            ti.AUTOTUNE_CACHE.inc(outcome="store")
        ti.AUTOTUNE_OUTCOMES.inc(outcome="packed")
        logging.getLogger(__name__).info(
            "packed autotune: %d candidates in %.2fs -> tile (%d, %d)",
            len(cands),
            search_s,
            winner[0],
            winner[1],
        )
        return winner[3]

    def pack_stats(self) -> Dict:
        """The bit-packed-plan summary (chip_smoke.py prints it):
        whether the packed dtype plan is active, the
        packed word depths (kt twin), the tuned winner, and the
        autotune forensics (search time, candidates tried, cache
        source)."""
        from . import autotune as at
        from .pallas_kernel import _resolve_operand_dtype

        with self._slab_lock:
            choice = self._kernel_choice
        t = self._tensors
        return {
            "active": self._pack,
            "dtype": "packed32" if self._pack else _resolve_operand_dtype(None),
            "words": [
                packed_words(int(t["egress"]["target_ns"].shape[0])),
                packed_words(int(t["ingress"]["target_ns"].shape[0])),
            ],
            "winner": dict(choice) if choice else None,
            "autotune": self._autotune_stats,
            "cache_path": at.cache_path(),
        }

    def _build_counts_jits(self) -> None:
        """Build the counts programs once per engine: the fused jit
        (unpack + sort + precompute + pallas in one program), the
        resident pair (_static_jit / _counts_cases_jit: the precompute
        cut where the port cases enter, its first half kept on the
        device), and the split pair (_pre_jit / _counts_from_pre_jit)
        the repeat path uses to keep the WHOLE precompute of one case
        set device-resident."""
        import jax

        from .pallas_kernel import (
            _should_interpret,
            slab_operands,
            verdict_counts_pallas,
            verdict_counts_pallas_packed,
            verdict_counts_pallas_slab,
            verdict_counts_pallas_slab_from_ops,
        )
        from .sharded import _POD_KEYS
        from .tiled import _precompute, _precompute_cases, _precompute_static

        unpack = self._unpack
        interpret = _should_interpret()
        pack = self._pack

        def sorted_tensors(buf, perm):
            import jax.numpy as jnp

            tensors = dict(unpack(buf))
            for k in _POD_KEYS:
                tensors[k] = jnp.take(tensors[k], perm, axis=0)
            for direction in ("ingress", "egress"):
                if "host_ip_match" in tensors[direction]:
                    d = dict(tensors[direction])
                    d["host_ip_match"] = jnp.take(
                        d["host_ip_match"], perm, axis=1
                    )
                    tensors[direction] = d
            return tensors

        def prepared_tensors(buf, perm, q_port, q_name, q_proto):
            tensors = sorted_tensors(buf, perm)
            tensors["q_port"] = q_port
            tensors["q_name"] = q_name
            tensors["q_proto"] = q_proto
            return tensors

        def packed_tier(pre):
            e, ig = pre["egress"], pre["ingress"]
            if "tier" not in e:
                return None
            return {"egress": e["tier"], "ingress": ig["tier"]}

        def counts_from_pre_packed(pre, n_pods, bs, bd):
            e, ig = pre["egress"], pre["ingress"]
            return verdict_counts_pallas_packed(
                e["tmatch_pk"], e["has_target"], e["tallow_pk"],
                ig["tmatch_pk"], ig["has_target"], ig["tallow_pk"],
                n_pods=n_pods, tier=packed_tier(pre),
                bs=bs, bd=bd, interpret=interpret,
            )

        def counts_from_pre(pre, n_pods, t0_e=None, t0_i=None):
            e, ig = pre["egress"], pre["ingress"]
            if "tallow_pk" in e:
                # packed dtype plan: the packed kernel at the DEFAULT
                # tile (the tuned-tile steady state dispatches through
                # _counts_from_pre_packed_jit instead); the fused tier
                # epilogue rides when the engine is tiered
                from .pallas_kernel import PACKED_BD, PACKED_BS

                return counts_from_pre_packed(
                    pre, n_pods, PACKED_BS, PACKED_BD
                )
            if t0_e is not None:
                # per-tile slab fast path (host-verified eligibility)
                return verdict_counts_pallas_slab(
                    e["tmatch"], e["has_target"], e["tallow_bf"],
                    ig["tmatch"], ig["has_target"], ig["tallow_bf"],
                    t0_e, t0_i, n_pods, interpret=interpret,
                )
            return verdict_counts_pallas(
                e["tmatch"],
                e["has_target"],
                e["tallow_bf"],
                ig["tmatch"],
                ig["has_target"],
                ig["tallow_bf"],
                n_pods=n_pods,
                interpret=interpret,
            )

        @jax.jit
        def counts_packed(buf, perm, q_port, q_name, q_proto, n_pods, t0_e=None, t0_i=None):
            pre = _precompute(
                prepared_tensors(buf, perm, q_port, q_name, q_proto), pack
            )
            return counts_from_pre(pre, n_pods, t0_e, t0_i)

        # every program below rides the persistent AOT executable cache
        # (engine/aot_cache.py): a restarted process adopts serialized
        # executables — zero trace, zero compile — and any program the
        # runtime can't serialize falls back to the plain jit.  The
        # fused/pre programs bake the unpack closure's leaf layout into
        # their trace, so their cache key carries the metas digest.
        unpack_plan = self._aot_plan(self._metas_digest(unpack))
        self._counts_packed_jit = aot_cache.AotProgram(
            "counts.fused", counts_packed, plan=unpack_plan
        )
        self._pre_jit = aot_cache.AotProgram(
            "counts.pre",
            jax.jit(
                lambda buf, perm, qp, qn, qr: _precompute(
                    prepared_tensors(buf, perm, qp, qn, qr), pack
                )
            ),
            plan=unpack_plan,
        )
        self._counts_from_pre_jit = aot_cache.AotProgram(
            "counts.from_pre", jax.jit(counts_from_pre), plan=self._aot_plan()
        )
        # the resident pair: `counts.static` is the precompute up to
        # where the port cases enter, run once per engine state and
        # kept (_static_pre_resident); `counts.cases` is the request's
        # program from there on, its cases ONE int32 [3, Q] operand
        # (the class route's form, tiled._with_case_rows)
        self._static_jit = aot_cache.AotProgram(
            "counts.static",
            jax.jit(
                lambda buf, perm: _precompute_static(
                    sorted_tensors(buf, perm), pack
                )
            ),
            plan=unpack_plan,
        )

        def counts_cases(static, cases, n_pods, t0_e=None, t0_i=None):
            pre = _precompute_cases(static, cases[0], cases[1], cases[2], pack)
            return counts_from_pre(pre, n_pods, t0_e, t0_i)

        self._counts_cases_jit = aot_cache.AotProgram(
            "counts.cases", jax.jit(counts_cases), plan=self._aot_plan()
        )
        self._counts_from_pre_packed_jit = aot_cache.AotProgram(
            "counts.from_pre_packed",
            jax.jit(counts_from_pre_packed, static_argnames=("bs", "bd")),
            plan=self._aot_plan(),
            static_argnames=("bs", "bd"),
        )

        def slab_ops(pre, n_pods, t0_e, t0_i, w=None):
            e, ig = pre["egress"], pre["ingress"]
            return slab_operands(
                e["tmatch"], e["has_target"], e["tallow_bf"],
                ig["tmatch"], ig["has_target"], ig["tallow_bf"],
                t0_e, t0_i, n_pods, w=w,
            )

        self._slab_ops_jit = jax.jit(slab_ops, static_argnames=("w",))
        self._counts_from_slab_ops_jit = jax.jit(
            lambda ops: verdict_counts_pallas_slab_from_ops(
                ops, interpret=interpret
            )
        )

    def _counts_pallas_packed(self, cases: Sequence[PortCase], n: int) -> Dict[str, int]:
        """Telemetry shell around the pallas counts path: one flight-
        recorder entry + latency/throughput instruments per evaluation
        (host-side only — the timed body below never syncs for it)."""
        with ti.eval_flight("counts.pallas", n, len(cases)) as fl:
            counts = self._counts_pallas_dispatch(cases, n, fl)
            fl.set(cells=counts["cells"])
            return counts

    def _counts_pallas_dispatch(
        self, cases: Sequence[PortCase], n: int, fl
    ) -> Dict[str, int]:
        """The pallas counts path over the SINGLE-BUFFER tensor
        transfer (shared with the grid/pairs paths).  Which program a
        call runs is its `mode`: `steady` (the case set's precompute is
        pinned: the counts kernel alone), `split` (its second call in a
        row: pin it), else `resident` (the half of the precompute the
        cases do not touch is built once per engine state and kept, the
        request runs `counts.cases` from there) or, where that half
        does not fit the pins' ceiling or CYCLONUS_PRE_CACHE=0, `fused`
        (unpack + pod-axis ns-sort + precompute + pallas counts in one
        jit, everything computed again).  Records as planspec path
        "counts.pallas"; the steady-state kernel choice within it
        records its own counts.steady.* leaf.

        Why the sort: a target applies to pods of exactly one namespace,
        so with pods ns-sorted (on device, via the permutation gather
        below) and targets ns-sorted (in the base tensors —
        _sort_targets_by_ns) the tmatch matrices become near block
        diagonal and most (pod-tile, target-chunk) blocks are ALL ZERO;
        the pallas kernel skips their matmuls (scalar-prefetch nz maps),
        dropping the dominant flops term from O(N^2 T) dense to the
        occupied blocks only.  Counts are invariant under both
        permutations, so only this path sorts; grid paths keep caller
        order."""
        import jax

        from .sharded import _POD_KEYS

        planspec.record("counts.pallas")
        buf = self._ensure_packed()
        if self._pod_perm_dev is None:
            # bucketing pads carry ns id -1: keep them LAST (the kernel's
            # validity mask assumes real pods occupy the first n rows)
            ns = self._tensors["pod_ns_id"]
            key = np.where(ns < 0, np.iinfo(np.int32).max, ns)
            perm = np.argsort(key, kind="stable").astype(np.int32)
            self._pod_perm_host = perm
            with phase("engine.device_put"):
                self._pod_perm_dev = jax.device_put(perm)
        if self._slab_plan_state == "unset":
            with phase("engine.slab_plan"):
                self._slab_plan_state = self._slab_plan(self._pod_perm_host)
        slab = self._slab_plan_state
        if self._counts_packed_jit is None:
            self._build_counts_jits()
        self._drain_autotune_orphan()
        from .pallas_kernel import sum_partials

        key, slab_ok, slab_args, (q_port, q_name, q_proto), choice = (
            self._steady_state_args(cases)
        )
        if self._pre_cache is not None and self._pre_cache[0] == key:
            # steady state: only the pallas counts kernel runs
            self._pre_cache_misses = 0
            ti.PRE_CACHE_HITS.inc()
            fl.set(mode="steady", slab=slab_args[0] is not None)
            # CYCLONUS_AUTOTUNE gates BOTH plans (the dense slab search
            # costs the same timed rounds and cache writes the packed
            # search does); the dense plan additionally needs an
            # eligible slab plan to have anything to race
            tune_pending = (
                choice is None
                and self._autotune_enabled()
                and (self._pack or slab_ok)
            )
            if tune_pending:
                # autotune at the first steady-state call: every
                # candidate runs from the SAME pinned precompute, so
                # this times exactly what every later call will execute
                # (or adopts the persisted winner with no search at all)
                with phase("engine.autotune"):
                    if self._pack:
                        partials = self._autotune_packed(
                            np.int32(n), key, len(cases)
                        )
                    else:
                        partials = self._autotune_slab(np.int32(n), key)
            else:
                with phase("engine.dispatch"):
                    partials = self._dispatch_steady(key, slab_args, choice)
        elif (
            self._last_counts_key == key
            and key != self._pre_cache_declined
            and _pre_cache_enabled()
            and self._pre_bytes_estimate(len(cases)) <= _PRE_CACHE_MAX_BYTES
        ):
            # second consecutive evaluation of the same case set: switch
            # to the split path and keep the precompute device-resident.
            # The split programs compile once (persistently cached), and
            # only for a caller that repeats a case set.
            ti.PRE_CACHE_MISSES.inc()
            ti.PRE_CACHE_BUDGET_BYTES.set(_PRE_CACHE_MAX_BYTES)
            fl.set(mode="split")
            with phase("engine.dispatch"):
                pre = self._pre_jit(
                    buf, self._pod_perm_dev, q_port, q_name, q_proto
                )
                nbytes = _tree_nbytes(pre)
                if nbytes <= _PRE_CACHE_MAX_BYTES:
                    self._pre_cache = (key, pre)  # evicts any other set
                    with self._slab_lock:
                        self._slab_ops_cache = None  # stale for new set
                    self._pre_cache_misses = 0
                    ti.PRE_CACHE_BYTES.set(nbytes)
                else:
                    # too big to pin: remember, so repeats go back to the
                    # single fused dispatch instead of this split path
                    self._pre_cache_declined = key
                # always the DEFAULT program here: with the slab chosen,
                # the steady state dispatches from cached operands
                # (_dispatch_steady), so a split-path slab trace would be
                # a heavy one-off compile used exactly once
                partials = self._counts_from_pre_jit(
                    pre, np.int32(n), None, None
                )
        else:
            self._last_counts_key = key
            ti.PRE_CACHE_MISSES.inc()
            resident = self._static_pre_admitted(len(cases))
            fl.set(mode="resident" if resident else "fused")
            if self._pre_cache is not None:
                # release the cached set's HBM only after two consecutive
                # other-set evaluations: a single interleaved call (the
                # A, B, A, B probe pattern) must not thrash the cache
                self._pre_cache_misses += 1
                if self._pre_cache_misses >= 2:
                    self._pre_cache = None
                    with self._slab_lock:
                        self._slab_ops_cache = None  # HBM goes with the pre
                    ti.PRE_CACHE_BYTES.set(0)
            with phase("engine.dispatch"):
                if resident:
                    # the first call enqueues both programs back to
                    # back: nothing here waits for the static
                    partials = self._counts_cases_jit(
                        self._static_pre_resident(buf),
                        jax.device_put(np.stack((q_port, q_name, q_proto))),
                        np.int32(n), *slab_args,
                    )
                else:
                    partials = self._counts_packed_jit(
                        buf, self._pod_perm_dev, q_port, q_name, q_proto,
                        np.int32(n), *slab_args,
                    )
        # the [Q, n_tiles, 3] readback is the execution barrier: device
        # run time lands here, not in the async dispatch above (nor in
        # engine.autotune, whose candidates run synchronously, timed)
        with phase("engine.execute"):
            partials = np.asarray(partials)
        return sum_partials(partials, len(cases), n)

    def _static_pre_bytes(self) -> int:
        """The bytes tiled._precompute_static's result holds, from the
        shapes alone, before any program exists: per direction the
        [P, N] peer matches, the [T, N] target matches (and their packed
        words), has_target, and the encoding leaves that ride along; a
        tiered set also keeps both selector matches."""
        t = self._tensors
        n = int(t["pod_ns_id"].shape[0])
        total = 0
        for d in ("ingress", "egress"):
            enc = t[d]
            n_t = int(enc["target_ns"].shape[0])
            total += n * (int(enc["peer_target"].shape[0]) + n_t + 1)
            if self._pack:
                total += 4 * n * packed_words(n_t)
            total += enc["peer_target"].nbytes + enc["target_ns"].nbytes
            total += sum(x.nbytes for x in enc["port_spec"].values())
        if "tiers" in t:
            s = int(t["sel_req_kv"].shape[0])
            total += s * (n + int(t["ns_kv"].shape[0])) + 4 * n
            total += _tree_nbytes(t["tiers"])
        return total

    def _static_pre_admitted(self, q: int) -> bool:
        """Whether a request of `q` cases that no pin serves runs from
        the resident static (mode `resident`) or the fused program: the
        static and the precompute a repeat would pin beside it have to
        fit the pins' ceiling together, and CYCLONUS_PRE_CACHE=0 keeps
        nothing on the device.  Decided from shapes, so nothing
        compiles for a static that would not be kept."""
        if not (
            _pre_cache_enabled()
            and self._static_pre_bytes() + self._pre_bytes_estimate(q)
            <= _PRE_CACHE_MAX_BYTES
        ):
            ti.STATIC_PRE.inc(outcome="declined")
            return False
        if self._static_pre is not None:
            ti.STATIC_PRE.inc(outcome="hit")
        return True

    def _static_pre_resident(self, buf):
        """The static half of the precompute on the device, built from
        the packed buffer where this engine state has none yet (span
        engine.static_pre; the build is enqueued, not waited for)."""
        if self._static_pre is None:
            with phase("engine.static_pre") as sp:
                self._static_pre = self._static_jit(buf, self._pod_perm_dev)
                nbytes = _tree_nbytes(self._static_pre)
                sp.set(bytes=nbytes)
            ti.STATIC_PRE.inc(outcome="built")
            ti.STATIC_PRE_BYTES.set(nbytes)
        return self._static_pre

    def _steady_state_args(self, cases: Sequence[PortCase]):
        """(key, slab_ok, slab_args, (q_port, q_name, q_proto), choice)
        for the pinned-precompute steady state — THE single definition
        of which program a steady-state dispatch runs, shared by
        evaluate_grid_counts and counts_pipelined_eval_s so the two can
        never measure different programs.  `choice` is the tuned
        _kernel_choice dict (None until the autotune or a persisted
        adoption resolves it), read ONCE under _slab_lock so callers
        branch on one coherent value instead of re-reading an attribute
        the autotune's abandoned candidate thread may be racing.
        slab_args engages only when a plan exists, the autotune chose
        the slab kernel, AND the slab's materialized HBM bytes fit the
        budget at THIS case count (plan time budgets q=2 — a larger
        case list must fall back to the default kernel, not OOM the
        device)."""
        q_port, q_name, q_proto = self._port_case_arrays(cases)
        n = self.encoding.cluster.n_pods
        key = (q_port.tobytes(), q_name.tobytes(), q_proto.tobytes(), n)
        slab = self._slab_plan_state
        slab_ok = isinstance(slab, dict) and (
            self._slab_bytes_per_case is None
            or len(cases) * self._slab_bytes_per_case
            + self._class_aux_bytes()
            <= self._slab_budget
        )
        with self._slab_lock:
            choice = self._kernel_choice
        slab_args = (
            (slab["egress"], slab["ingress"])
            if slab_ok and choice is not None and choice.get("kernel") == "slab"
            else (None, None)
        )
        return key, slab_ok, slab_args, (q_port, q_name, q_proto), choice

    def _slab_ops_for(self, key):
        """Device-resident gathered slab operands for the pinned case
        set, built ONCE per (case set, plan) and cached next to the
        pre-cache (evicted together).  The HBM held is bounded by the
        same CYCLONUS_SLAB_MAX_BYTES budget that gates the slab path —
        pinning holds the SAME bytes a per-dispatch rebuild would
        transiently allocate, trading that rebuild for residency."""
        # one locked read of the (key, ops) tuple: the old
        # `self._slab_ops_cache is not None and self._slab_ops_cache[0]`
        # double read could interleave with the autotune rejection's
        # clear and crash on None[0] (found by tools/locklint.py LK001;
        # the schedule is fuzzed by tests/raceharness.py)
        with self._slab_lock:
            cached = self._slab_ops_cache
        if cached is not None and cached[0] == key:
            ti.SLAB_OPS_CACHE_HITS.inc()
            return cached[1]
        ti.SLAB_OPS_CACHE_MISSES.inc()
        slab = self._slab_plan_state
        n32 = np.int32(self.encoding.cluster.n_pods)
        # snapshot _pre_cache ONCE: the issuing thread guarantees it is
        # pinned before calling here, but the abandoned autotune thread
        # has no such guarantee — the issuing thread's 2-miss eviction
        # can null it mid-build, and a direct self._pre_cache[1] read
        # would crash on None[1].  The raise is a contained candidate
        # failure (run_bounded catches it and the autotune rejects).
        pre_cache = self._pre_cache
        if pre_cache is None:
            raise RuntimeError(
                "slab operand build raced pre-cache eviction "
                "(abandoned autotune candidate; contained)"
            )
        ops = self._slab_ops_jit(
            pre_cache[1], n32, slab["egress"], slab["ingress"],
            w=slab.get("w"),
        )
        # the ACTUAL pinned bytes supersede the plan-time q=2 estimate
        ti.SLAB_HBM_BYTES.set(_tree_nbytes(ops))
        # check-and-fill under the SAME lock as the autotune's rejection
        # writes: without it an abandoned candidate thread can pass the
        # choice check, lose the CPU to the main thread's rejection +
        # cache clear, then re-pin slab HBM for the rejected kernel
        with self._slab_lock:
            if self._slab_choice is False:
                return ops
            self._slab_ops_cache = (key, ops)
        return ops

    def _dispatch_steady(self, key, slab_args, choice=None):
        """One steady-state dispatch of the CHOSEN program: the slab
        kernel from the cached gathered operands, the packed kernel at
        the tuned tile, or the default program from the pinned
        precompute (which under the packed plan is the packed kernel at
        the default tile).  Returns the async partials array."""
        if slab_args[0] is not None:
            planspec.record("counts.steady.slab")
            return self._counts_from_slab_ops_jit(self._slab_ops_for(key))
        n32 = np.int32(self.encoding.cluster.n_pods)
        if (
            choice is not None
            and choice.get("kernel") == "packed"
            and "bs" in choice
        ):
            planspec.record("counts.steady.packed_tuned")
            return self._counts_from_pre_packed_jit(
                self._pre_cache[1], n32, bs=choice["bs"], bd=choice["bd"]
            )
        planspec.record("counts.steady.default")
        return self._counts_from_pre_jit(self._pre_cache[1], n32, None, None)

    def counts_pipelined_eval_s(
        self, cases: Sequence[PortCase], reps: int = 10
    ):
        """Steady-state DEVICE-side seconds per counts evaluation:
        dispatch `reps` identical programs back-to-back from the pinned
        precompute and read back only the last, so the device queue
        pipelines and the per-eval cost excludes the per-dispatch
        host->device->host round trip a sync eval pays (not measured on
        the current machine).  Runs exactly the program the steady state runs
        (_steady_state_args).  Returns (seconds_per_eval, counts) or
        None when the engine is not at the pinned-precompute steady
        state for this case set — or when a cancelled autotune
        candidate's execution is still in flight (it shares the device
        queue and would pollute a number recorded as stable)."""
        import time as _time

        if self._class_state is not None and self._class_counts_eligible(
            len(cases)
        ):
            # the orphan gate applies here too: a cancelled autotune
            # candidate (possible when an earlier INELIGIBLE case set
            # ran the dense pallas path) shares the device queue and
            # would pollute the compressed timing just the same
            self._drain_autotune_orphan()
            if self._autotune_orphan is not None:
                return None
            return self._pipelined_classes(cases, reps)
        key, _slab_ok, slab_args, _qs, choice = self._steady_state_args(cases)
        if self._pre_cache is None or self._pre_cache[0] != key:
            return None
        self._drain_autotune_orphan()
        if self._autotune_orphan is not None:
            return None
        n = self.encoding.cluster.n_pods
        out = self._dispatch_steady(key, slab_args, choice)
        np.asarray(out)  # warm barrier
        t0 = _time.perf_counter()
        outs = [
            self._dispatch_steady(key, slab_args, choice) for _ in range(reps)
        ]
        partials = np.asarray(outs[-1])  # in-order stream: one barrier
        dt = (_time.perf_counter() - t0) / reps
        from .pallas_kernel import sum_partials

        counts = sum_partials(partials, len(cases), n)
        # the pipelined rate as a REAL gauge: what a batched caller
        # sustains, vs the sync eval's per-dispatch-round-trip number
        if dt > 0:
            ti.EVAL_PIPELINED_CELLS_PER_SEC.set(counts["cells"] / dt)
        return dt, counts

    def evaluate_grid_counts_sharded(
        self,
        cases: Sequence[PortCase],
        block: int = 1024,
        mesh=None,
        kernel: str = None,
    ) -> Dict[str, int]:
        """THE mesh counts entry: allow counts of the full grid over all
        the devices of `mesh` (default: sharded.default_mesh), exact
        int64 sums of int32 partials, one gather of partials a request.

        It picks its route from what it can see.  A class state takes
        the compressed route (counts.sharded.classes).  The dense route
        REPLICATES the precompute and splits the source rows
        (counts.sharded.pallas, the TPU default, or .xla under `kernel`)
        where one chip holds the replicated precompute, else keeps both
        pod axes sharded and rotates the dst-side bundle round the ring
        (counts.ring): _mesh_counts_route, from the shapes, before
        anything compiles.  Either way the dense program is HELD
        (_counts_mesh): built once per engine state, the static half of
        the precompute placed once, a request sends its port cases."""
        self._check_ips()
        n = self.encoding.cluster.n_pods
        if not cases or n == 0:
            return {"ingress": 0, "egress": 0, "combined": 0, "cells": 0}
        if self._class_state is not None and self._class_counts_eligible(
            len(cases)
        ):
            return self._counts_classes(
                cases, n, sharded=True, block=block, mesh=mesh
            )
        # tiers x per-device pallas: same matrix cell discipline as
        # evaluate_grid_counts — auto routes to the XLA tile body (it
        # carries the tier resolution epilogue), an explicit pallas
        # request fails loudly with the declared message
        kernel = planspec.resolve_sharded_counts_kernel(
            kernel=kernel, tiers=self.tiers is not None
        )
        return self._counts_mesh(cases, n, block, mesh, kernel)

    def _mesh_replicated_bytes(self, q: int, n_padded: int) -> int:
        """One chip's bytes of the REPLICATED dense precompute at `q`
        cases, from the shapes alone: the static half
        (_static_pre_bytes), what a request keeps of the other
        (_pre_bytes_estimate), and the largest temporary of
        tiled._precompute_cases, a direction's `peer_allow` [P, N * Q]
        as booleans and as bf16 for the one-hot matmul beside its
        product [T, N * Q] bf16."""
        t = self._tensors
        temp = max(
            3 * int(t[d]["peer_target"].shape[0])
            + 2 * int(t[d]["target_ns"].shape[0])
            for d in ("ingress", "egress")
        )
        return (
            self._static_pre_bytes()
            + self._pre_bytes_estimate(q)
            + temp * n_padded * q
        )

    def _mesh_counts_route(self, q: int, n_padded: int, mesh) -> Tuple[str, int, int]:
        """(route, bytes, ceiling): "rows" (replicated precompute,
        source rows split) where one chip's replicated bytes pass under
        the ceiling - _MESH_REPLICATED_MAX_BYTES, and half the device's
        memory where the backend reports it - else "ring"."""
        need = self._mesh_replicated_bytes(q, n_padded)
        ceiling = _MESH_REPLICATED_MAX_BYTES
        stats = mesh.devices.flat[0].memory_stats() or {}
        if stats.get("bytes_limit"):
            ceiling = min(ceiling, int(stats["bytes_limit"]) // 2)
        return ("rows" if need <= ceiling else "ring"), need, ceiling

    def _counts_mesh(
        self, cases: Sequence[PortCase], n: int, block: int, mesh, kernel
    ) -> Dict[str, int]:
        """One dense mesh counts request on the held pair: route from
        the shapes, the pair and the static from the engine's state
        (built where it has none: spans engine.program,
        engine.static_pre), the port cases sent (engine.dispatch_sharded,
        host_operands 1, host_bytes 12 x Q: the one host array, which
        the runtime lays on every chip), the readback barrier
        (engine.execute), the int64 host sum."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from .sharded import _pad_pod_arrays, default_mesh, mesh_device_context
        from .tiled import (
            _int32_safe_block,
            mesh_counts_kernel,
            mesh_counts_programs,
        )

        mesh = mesh or default_mesh()
        n_dev = int(mesh.devices.size)
        q = len(cases)
        block = _int32_safe_block(min(block, max(n // n_dev, 1)), n, q)
        kernel = mesh_counts_kernel(kernel)
        step = n_dev * block
        n_padded = -(-int(self._tensors["pod_ns_id"].shape[0]) // step) * step
        route, need, ceiling = self._mesh_counts_route(q, n_padded, mesh)
        if route == "ring":
            path = "counts.ring"
            planspec.record("counts.ring")
        elif kernel == "pallas":
            path = "counts.sharded.pallas"
            planspec.record("counts.sharded.pallas")
        else:
            path = "counts.sharded.xla"
            planspec.record("counts.sharded.xla")
        key = (
            tuple(mesh.devices.flat), tuple(mesh.axis_names), route,
            kernel if route == "rows" else None, block,
        )
        replicated = NamedSharding(mesh, PartitionSpec())
        with ti.eval_flight(
            path, n, q, devices=n_dev, mode="held",
            replicated_bytes=need, ceiling_bytes=ceiling,
        ) as fl, mesh_device_context(mesh):
            held = self._mesh_static
            if key not in self._mesh_counts_jits or held is None or held[0] != key:
                # the case-free tensors, padded to whole tiles a device:
                # what the pair is traced over and what `static` is sent
                tensors, _ = _pad_pod_arrays(self._tensors, n, step)
            if key not in self._mesh_counts_jits:
                self._mesh_counts_jits[key] = mesh_counts_programs(
                    mesh, tensors, block, route, kernel, self._pack,
                    self._aot_plan(),
                )
            static_fn, cases_fn = self._mesh_counts_jits[key]
            if held is None or held[0] != key:
                with phase("engine.static_pre", devices=n_dev) as sp:
                    static = static_fn(tensors)
                    # a chip's share: the ring's static is sharded
                    nbytes = _tree_nbytes(static) // (
                        n_dev if route == "ring" else 1
                    )
                    sp.set(bytes=nbytes)
                self._mesh_static = (
                    key, static, jax.device_put(np.int32(n), replicated)
                )
                ti.STATIC_PRE.inc(outcome="built")
                ti.STATIC_PRE_BYTES.set(nbytes)
            else:
                ti.STATIC_PRE.inc(outcome="hit")
            _, static, n32 = self._mesh_static
            q_port, q_name, q_proto = self._port_case_arrays(cases)
            sent = np.stack((q_port, q_name, q_proto))
            shard = n_padded // n_dev
            cases_fn.resolve(static, sent, n32)
            with phase(
                "engine.dispatch_sharded", route=route, devices=n_dev,
                shard=shard,
            ) as sp:
                out = cases_fn(static, jax.device_put(sent, replicated), n32)
                sp.set(host_operands=1, host_bytes=sent.nbytes)
            ti.MESH_DISPATCH_BYTES.inc(sent.nbytes, route=route)
            # the [tiles, 3] readback is the execution barrier: the
            # chips' whole run lands here, not in the dispatch above
            with phase("engine.execute"):
                counts = np.asarray(out, dtype=np.int64).sum(axis=0)
            fl.set(cells=q * n * n)
        return {
            "ingress": int(counts[0]),
            "egress": int(counts[1]),
            "combined": int(counts[2]),
            "cells": q * n * n,
        }

    def evaluate_grid_counts_ring(
        self, cases: Sequence[PortCase], block: int = 1024, mesh=None
    ) -> Dict[str, int]:
        """Ring-rotation counts: both pod axes stay sharded and the
        dst-side precompute rotates around the mesh with ppermute —
        per-device memory O(N / mesh size), the path for clusters whose
        precompute exceeds one device (engine/tiled.py)."""
        self._check_ips()
        n = self.encoding.cluster.n_pods
        if not cases or n == 0:
            return {"ingress": 0, "egress": 0, "combined": 0, "cells": 0}
        planspec.record("counts.ring")
        from .tiled import evaluate_grid_counts_ring

        return evaluate_grid_counts_ring(
            self._tensors_with_cases(cases), n, block=block, mesh=mesh
        )

    def mesh_counts_pipelined_eval_s(
        self,
        cases: Sequence[PortCase],
        reps: int = 10,
        block: int = 1024,
        mesh=None,
    ):
        """Steady-state DEVICE-side seconds per MESH counts evaluation —
        counts_pipelined_eval_s's twin for the overlapped ring path:
        one seed dispatch pins the sharded tensors + per-shard
        precompute on the mesh, then `reps` ring sweeps run back to
        back with the rotating peer bundle DONATED and fed forward
        (engine/tiled.py ring_counts_pipeline), one readback at the
        end.  Returns (seconds_per_eval, counts), or None for an empty
        problem."""
        self._check_ips()
        n = self.encoding.cluster.n_pods
        if not cases or n == 0:
            return None
        planspec.record("counts.ring.pipelined")
        from .tiled import evaluate_grid_counts_ring_pipelined

        return evaluate_grid_counts_ring_pipelined(
            self._tensors_with_cases(cases), n, reps=reps, block=block,
            mesh=mesh,
        )

    def evaluate_grid_counts_ring2d(
        self, cases: Sequence[PortCase], block: int = 1024, mesh=None
    ) -> Dict[str, int]:
        """Hierarchical multi-host ring counts over a ("dcn", "ici") mesh:
        ring hops ride the intra-host ICI ring and cross the DCN host
        boundary once per round (engine/tiled.py ring2d).  The multi-host
        scale-out path."""
        self._check_ips()
        n = self.encoding.cluster.n_pods
        if not cases or n == 0:
            return {"ingress": 0, "egress": 0, "combined": 0, "cells": 0}
        planspec.record("counts.ring2d")
        from .tiled import evaluate_grid_counts_ring2d

        return evaluate_grid_counts_ring2d(
            self._tensors_with_cases(cases), n, block=block, mesh=mesh
        )

    def iter_grid_blocks(self, cases: Sequence[PortCase], block: int = 1024):
        """Stream verdict blocks of source rows to the host:
        yields (start, ingress_rows, egress, combined), arrays [b, N, Q]
        bool.  For consumers that scan grids bigger than host/device
        memory."""
        from .tiled import iter_grid_blocks

        self._check_ips()
        n = self.encoding.cluster.n_pods
        if not cases or n == 0:
            return iter(())
        planspec.record("grid.blocks")
        return iter_grid_blocks(self._tensors_with_cases(cases), n, block=block)

    def evaluate_pairs(
        self, cases: Sequence[PortCase], pairs: Sequence[Tuple[int, int]]
    ) -> np.ndarray:
        """Point verdicts for (src_idx, dst_idx) pod pairs: [K, Q, 3] bool
        (ingress, egress, combined) — no N x N grid anywhere, so it scales
        to arbitrary cluster sizes (powers the large-scale parity spot
        checks, analysis/oracle.py spot_check_pairs)."""
        from .tiled import evaluate_pairs_kernel

        self._check_ips()
        if not cases or len(pairs) == 0:
            return np.zeros((len(pairs), len(cases), 3), dtype=bool)
        planspec.record("pairs.aot")
        idx = np.asarray(pairs, dtype=np.int32).reshape(-1, 2)
        if self._pairs_aot is None:
            # the serve query path's program: a restarted serve replica
            # adopts it from the AOT cache before its first verdict
            self._pairs_aot = aot_cache.AotProgram(
                "pairs", evaluate_pairs_kernel, plan=self._aot_plan()
            )
        with ti.eval_flight(
            "pairs", self.encoding.cluster.n_pods, len(cases), k=len(pairs)
        ):
            out = self._pairs_aot(
                self._tensors_with_cases(cases, device=True), idx[:, 0], idx[:, 1]
            )
        return np.stack(
            [
                np.asarray(out["ingress"]),
                np.asarray(out["egress"]),
                np.asarray(out["combined"]),
            ],
            axis=2,
        )

    def firing_components(
        self, cases: Sequence[PortCase]
    ) -> Dict[str, Dict[str, np.ndarray]]:
        """Per-direction RULE firing-mask components on the RAW encoding
        (no dead-target compaction, no shape bucketing), so flat peer row
        p maps 1:1 to resolved rule (peer_target[p], peer_rule_idx[p]) of
        the policy's sorted_targets() order — the contract the analysis
        subsystem (cyclonus_tpu.analysis) audits on.

        Returns {direction: {rule_tmatch [P, N], peer_match [P, N],
        pport [P, Q], has_target [N]}} numpy bool arrays; rule p's firing
        mask over (target-side pod n, peer-side pod m, case q) is
        rule_tmatch[p, n] & peer_match[p, m] & pport[p, q]."""
        from .kernel import rule_firing_kernel

        self._check_ips()
        planspec.record("firing.raw")
        raw = self._build_tensors()
        q_port, q_name, q_proto = self._port_case_arrays(cases)
        # "tiers" excluded on purpose: firing masks are a NetworkPolicy-
        # TIER concept (rule = one peer matcher of one target).  The
        # audit built on them stays sound under the lattice — see
        # analysis/audit.py's tier-composition note — because removing a
        # shadowed NP rule changes neither has_target nor any any_allow
        # cell, and the lattice reads the NP tier only through those two.
        shared = {
            k: v
            for k, v in raw.items()
            if k not in ("ingress", "egress", "tiers")
        }
        shared["q_port"] = q_port
        shared["q_name"] = q_name
        shared["q_proto"] = q_proto
        out = {}
        for direction in ("ingress", "egress"):
            comp = rule_firing_kernel(shared, raw[direction])
            out[direction] = {k: np.asarray(v) for k, v in comp.items()}
        return out

    def evaluate_grid_sharded(
        self, cases: Sequence[PortCase], mesh=None, schedule=None
    ) -> GridVerdict:
        """Mesh-sharded evaluation: the shard_map program runs over `mesh`
        (default: all devices of the default backend —
        sharded.default_mesh).  `schedule` picks the peer exchange:
        "ring" (overlapped ppermute streaming, the default) or
        "allgather" (the replicated reference) — bit-identical grids
        either way.  A 1-device mesh still runs the sharded program;
        use evaluate_grid for the plain single-device kernel."""
        from .sharded import evaluate_grid_sharded, mesh_schedule

        self._check_ips()
        if not cases:
            return self.evaluate_grid(cases)
        if self._class_state is not None:
            return self._evaluate_grid_sharded_classes(
                cases, mesh, schedule=schedule
            )
        # record at the dispatch leaf, not inside the shared shard_map
        # primitive (the compressed route reuses it over the class axis)
        if mesh_schedule(schedule) == "ring":
            planspec.record("grid.sharded.ring")
        else:
            planspec.record("grid.sharded.allgather")
        tables, eval_id = evaluate_grid_sharded(
            self._tensors_with_cases(cases), self.encoding.cluster.n_pods,
            mesh=mesh, schedule=schedule,
        )
        return GridVerdict(self.pod_keys, list(cases), *tables, eval_id=eval_id)


def _parseable_ip(ip: str) -> bool:
    try:
        ipaddress.ip_address(ip)
        return True
    except ValueError:
        return False
