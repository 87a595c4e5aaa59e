"""Incremental (delta-oriented) engine maintenance for the verdict
service.

The batch engine compiles (policy set, cluster) into one packed int32
device buffer (engine/api.py _pack_tensors) and device_puts it whole,
after a full host re-encode.  A watch-scale controller cannot pay that
per pod event, so this module patches the LIVE buffer instead:

  * pod deltas (add / remove / label change / ip change) re-encode ONLY
    the touched pod rows against the engine's existing vocabulary
    (encoding.encode_pod_rows: the vocab grows monotonically, so a
    fresh label pair gets an id no selector references — exactly the
    fresh-rebuild semantics) and scatter-patch the touched int32 words
    of the device buffer (one tiny transfer + one device-side scatter;
    untouched slabs are never re-uploaded);
  * namespace-label deltas patch the one namespace row (both the main
    and, when present, the class-representative buffer);
  * policy deltas re-encode the RULE SLABS (directions + selector
    table) against the same vocabulary, run them through the engine's
    own partition-compression / ns-sort / bucketing pipeline, and patch
    them wholesale IF every slab keeps its bucketed shape — compiled
    executables key on shapes, so a shape-preserving patch reuses every
    program;
  * anything that cannot patch exactly — label rows wider than the
    encoded width, a namespace beyond the bucketed table, IPv6
    host-evaluated IP blocks, rule slabs that change bucket — raises
    Ineligible, and the service falls back to a full rebuild from its
    authoritative cluster state.

Class-compression state (encoding.PodClasses) is patched too: a pod
delta recomputes that pod's observability signature (the same bytes
compute_pod_classes buckets on) and moves it between EXISTING classes
in place; a brand-new signature, a departing class representative with
survivors, or any policy/add/remove churn rebuilds the class state
alone (host classify + class-buffer re-upload — the main buffer stays
untouched).  Empty classes keep their rows: the gathered representative
values were copied at class-build time, so they remain a faithful
stand-in for their signature, and unreferenced class cells are never
gathered back.

After any patch, TpuPolicyEngine.invalidate_after_patch() drops every
VALUE-derived device cache (precompute pins, unpacked views, slab
operands) while keeping all compiled programs — shapes are unchanged by
construction.

Correctness is pinned by the differential gate (tests/test_serve.py and
VerdictService.verify_parity): after any delta sequence the patched
engine's truth tables must be bit-identical to an engine freshly built
from the post-delta cluster state, with the scalar oracle spot-checking
both.
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..engine import api as engine_api
from ..engine.api import TpuPolicyEngine
from ..engine.encoding import (
    classes_from_signatures,
    compress_rule_axes,
    encode_directions,
    encode_ns_row,
    encode_pod_rows,
    gather_class_pod_rows,
    pod_signatures,
)
from ..matcher.core import Policy
from ..telemetry import instruments as ti

logger = logging.getLogger("cyclonus.serve")

PodTuple = Tuple[str, str, Dict[str, str], str]

#: the five per-pod leaves every pod-row patch touches
_POD_LEAVES = ("pod_ns_id", "pod_kv", "pod_key", "pod_ip", "pod_ip_valid")

#: rule-slab leaves outside the per-direction dicts
_SEL_LEAVES = ("sel_req_kv", "sel_exp_op", "sel_exp_key", "sel_exp_vals")


class Ineligible(Exception):
    """This delta batch cannot patch the live engine exactly; the caller
    must fall back to a full rebuild from authoritative state."""


def serve_headroom() -> int:  # never-raises
    """CYCLONUS_SERVE_HEADROOM: extra rule-slab bucket steps the serve
    path pre-reserves at engine build (default 1 — one bucket of
    headroom absorbs most bucket-crossing policy churn, keeping it on
    the incremental path; 0 restores exact-fit buckets).  A malformed
    value degrades to the default with a debug log (the cachelint CC005
    evidence discipline), never an error at engine build."""
    import os

    try:
        return max(0, int(os.environ.get("CYCLONUS_SERVE_HEADROOM", "1")))
    except Exception as e:
        logger.debug("malformed CYCLONUS_SERVE_HEADROOM: %s", e)
        return 1


def pow2_pad(n: int) -> int:
    """Min-8 power-of-two round-up: the one compiled-shape policy both
    padded surfaces share (scatter idx/vals in _PatchSet.flush, pair
    batches in VerdictService.query) — jit keys executables on shapes,
    so bounding the shape set bounds the program set."""
    return 1 << max(3, int(n - 1).bit_length())


def patch_byte_budget() -> int:  # never-raises
    """CYCLONUS_SLAB_MAX_BYTES as the staged-patch ceiling (default
    6 GiB) — parsed through the utils/envflags registry, the one parse
    every consumer (pod/ns rows in service.py, rule slabs in
    patch_policy, engine counts slabs, CIDR staging) now shares, so a
    malformed value degrades to the default everywhere instead of
    raising on one path only."""
    from ..utils import envflags

    return envflags.get_int("CYCLONUS_SLAB_MAX_BYTES")


def _scatter_words(buf, idx: np.ndarray, vals: np.ndarray):
    """One device-side scatter of the touched int32 words: the only
    host->device traffic of a patch is idx+vals (a few words per touched
    row).  jit caches one executable per (buffer, idx) shape; idx is
    padded to a power of two by the caller so the shape set stays tiny."""
    import jax

    global _SCATTER_JIT
    if _SCATTER_JIT is None:
        _SCATTER_JIT = jax.jit(lambda b, i, v: b.at[i].set(v))
    return _SCATTER_JIT(buf, idx, vals)


_SCATTER_JIT = None  # cache-key: shapes (one executable per (buffer, idx) shape)


class _PatchSet:
    """Staged word updates against one packed device buffer.

    Rows are staged AFTER the host arrays are updated: word values are
    re-read from the host array itself, so boundary bytes of sub-word
    leaves (bools) come out right without keeping a packed host mirror."""

    def __init__(self, metas_by_path: Dict[tuple, tuple]):
        self._metas = metas_by_path
        self._idx: List[np.ndarray] = []
        self._vals: List[np.ndarray] = []

    def stage_rows(
        self, path: tuple, host: np.ndarray, row_lo: int, row_hi: int
    ) -> None:
        """Stage rows [row_lo, row_hi) of the leaf at `path` (axis 0)."""
        if row_hi <= row_lo:
            return
        meta = self._metas.get(path)
        if meta is None:
            raise Ineligible(f"no packed leaf at {path!r}")
        dtype, shape, off, n_words = meta
        if tuple(shape) != tuple(host.shape) or np.dtype(dtype) != host.dtype:
            raise Ineligible(
                f"leaf {path!r} drifted from packed layout: "
                f"{host.dtype}{host.shape} vs {np.dtype(dtype)}{tuple(shape)}"
            )
        row_bytes = host.dtype.itemsize * int(np.prod(shape[1:], dtype=np.int64))
        byte_lo, byte_hi = row_lo * row_bytes, row_hi * row_bytes
        w0, w1 = byte_lo // 4, min(-(-byte_hi // 4), n_words)
        flat = np.ascontiguousarray(host).view(np.uint8).reshape(-1)
        seg = flat[w0 * 4 : min(w1 * 4, flat.size)]
        if seg.size < (w1 - w0) * 4:  # zero tail pad, mirroring _pack_tensors
            seg = np.concatenate(
                [seg, np.zeros((w1 - w0) * 4 - seg.size, np.uint8)]
            )
        self._idx.append(np.arange(off + w0, off + w1, dtype=np.int32))
        self._vals.append(np.ascontiguousarray(seg).view(np.int32))

    def stage_leaf(self, path: tuple, host: np.ndarray) -> None:
        self.stage_rows(path, host, 0, int(host.shape[0]))

    @property
    def staged_bytes(self) -> int:
        return 4 * sum(int(i.size) for i in self._idx)

    def flush(self, dev_buf):
        """Apply the staged words; returns (new_buffer, bytes_patched).
        Duplicate indices are benign (both stages read the same final
        host value).  idx/vals pad to a power of two (rewriting the last
        word with its own value) so the scatter program set stays small."""
        if not self._idx:
            return dev_buf, 0
        idx = np.concatenate(self._idx)
        vals = np.concatenate(self._vals)
        nbytes = 4 * int(idx.size)
        cap = pow2_pad(int(idx.size))
        if cap > idx.size:
            idx = np.concatenate(
                [idx, np.full(cap - idx.size, idx[-1], np.int32)]
            )
            vals = np.concatenate(
                [vals, np.full(cap - vals.size, vals[-1], np.int32)]
            )
        return _scatter_words(dev_buf, idx, vals), nbytes


def _pad_row(row: np.ndarray, width: int, fill) -> np.ndarray:
    if row.shape[-1] >= width:
        return row
    out = np.full(row.shape[:-1] + (width,), fill, dtype=row.dtype)
    out[..., : row.shape[-1]] = row
    return out


class IncrementalEngine:
    """A TpuPolicyEngine plus the state needed to patch it in place.

    Single-writer by contract: the owning VerdictService serializes
    every apply and query under its own lock, so nothing here locks.
    The underlying engine's own `_slab_lock` discipline still applies to
    the caches invalidate_after_patch clears."""

    def __init__(
        self,
        policy: Policy,
        pods: Sequence[PodTuple],
        namespaces: Dict[str, Dict[str, str]],
        *,
        class_compress: Optional[str] = None,
        tiers=None,
    ):
        # compact=False: dead-target compaction bakes pod state into the
        # RULE tensors, which a pod delta can invalidate — a
        # delta-oriented engine keeps every target resident
        self.engine = TpuPolicyEngine(
            policy,
            pods,
            namespaces,
            compact=False,
            class_compress=class_compress,
            tiers=tiers,
            # slab headroom pre-reservation (ROADMAP 1b): one extra
            # bucket on the rule-slab axes so bucket-crossing policy
            # churn pads into the reservation (patch_policy) instead
            # of forcing a full rebuild
            slab_headroom=serve_headroom(),
        )
        self._class_compress = class_compress
        # the counterfactual ZERO-HEADROOM engine's rule-slab buckets
        # (what a headroom-0 build would currently have allocated, had
        # it rebuilt on every bucket change) — the baseline headroom
        # SAVES are counted against, so a grown slab is counted once,
        # not on every subsequent same-size patch.  Lazily derived from
        # the allocations on the first policy patch (patch_policy).
        self._natural_buckets: Optional[Dict[tuple, int]] = None
        # class-patch support: the per-pod signature matrix and the
        # signature -> class id index (see _class_update_row)
        self._sigs: Optional[np.ndarray] = None
        self._selpod: Optional[np.ndarray] = None
        self._class_sig_of: Dict[bytes, int] = {}
        # (staged, space) handoff from patch_policy's structure pin to
        # the rebuild it ends with — consumed (and reset) by
        # rebuild_class_state, staged only after every Ineligible
        self._resolved_cidr = (False, None)
        if self.engine._class_state is not None:
            self._init_class_support()

    # --- construction-time views ----------------------------------------

    def _raw_selector_view(self) -> Dict:
        """Raw (pre-bucket) selector + pod arrays for host selector-match
        passes (the class signature's selpod block must keep the raw
        selector count, which the bucketed tables pad)."""
        enc = self.engine.encoding
        c = enc.cluster
        return {
            "sel_req_kv": enc.sel_req_kv,
            "sel_exp_op": enc.sel_exp_op,
            "sel_exp_key": enc.sel_exp_key,
            "sel_exp_vals": enc.sel_exp_vals,
            "pod_kv": c.pod_kv,
            "pod_key": c.pod_key,
            "pod_ns_id": c.pod_ns_id,
        }

    def _sig_view(self, rows) -> Dict:
        """A row-sliced view of the engine tensors for pod_signatures:
        per-pod arrays at `rows`, direction dicts shared (the ip-peer
        spec set is row-independent)."""
        t = self.engine._tensors
        v = {
            k: np.ascontiguousarray(t[k][rows])
            for k in ("pod_ns_id", "pod_ip", "pod_ip_valid")
        }
        v["ingress"] = t["ingress"]
        v["egress"] = t["egress"]
        return v

    def _init_class_support(self) -> None:
        eng = self.engine
        n = eng.encoding.cluster.n_pods
        self._selpod = engine_api._selector_pod_matches_host(
            self._raw_selector_view()
        )
        # the engine's resolved CidrSpace (or None = dense bits) rides
        # every signature computation: build and serve must read the
        # SAME partition map or row widths/values would diverge
        self._sigs = pod_signatures(
            self._sig_view(np.arange(n)),
            self._selpod,
            cidr=eng._class_state.get("cidr"),
        )
        pc = eng._class_state["classes"]
        self._class_sig_of = {
            self._sigs[rep].tobytes(): cid
            for cid, rep in enumerate(np.asarray(pc.class_rep))
        }

    # --- eligibility -----------------------------------------------------

    def check_patchable(self) -> None:
        """Engine-level preconditions every incremental path shares."""
        enc = self.engine.encoding
        if enc.ingress.host_ip_rows or enc.egress.host_ip_rows:
            raise Ineligible(
                "host-evaluated (IPv6/mixed) IPBlock rows present: their "
                "per-pod match columns are rebuilt host-side only"
            )

    def pod_capacity(self) -> int:
        """Spare bucketed pod rows available for in-place adds."""
        return int(
            self.engine._tensors["pod_ns_id"].shape[0]
            - self.engine.encoding.cluster.n_pods
        )

    # --- pod row patches -------------------------------------------------

    def _ensure_namespace(self, ns: str) -> int:
        """Vocab id for `ns`, claiming a padded namespace row when the
        namespace is new (Ineligible when the bucketed table is full).
        Stages NOTHING: a fresh namespace starts label-less and its
        bucketed row is already the all-pad row."""
        eng = self.engine
        vocab = eng.encoding.cluster.vocab
        nid = vocab.ns.get(ns)
        if nid is not None:
            return nid
        t = eng._tensors
        nid = len(vocab.ns)
        if nid >= int(t["ns_kv"].shape[0]):
            raise Ineligible(
                f"namespace table full ({nid} ids, "
                f"{int(t['ns_kv'].shape[0])} bucketed rows)"
            )
        vocab.ns_id(ns)
        # a fresh namespace starts label-less; its bucketed row is
        # already the all-pad row, so only the RAW table needs the append
        c = eng.encoding.cluster
        if nid >= int(c.ns_kv.shape[0]):
            pad = np.full((1, c.ns_kv.shape[1]), -1, dtype=np.int32)
            c.ns_kv = np.concatenate([c.ns_kv, pad])
            c.ns_key = np.concatenate([c.ns_key, pad.copy()])
        return nid

    def set_namespace_labels(
        self,
        ns: str,
        labels: Dict[str, str],
        patch: _PatchSet,
        class_patch: Optional[_PatchSet],
    ) -> None:
        eng = self.engine
        c = eng.encoding.cluster
        t = eng._tensors
        nid = self._ensure_namespace(ns)
        try:
            kv, key = encode_ns_row(labels, c.vocab, int(c.ns_kv.shape[1]))
        except ValueError as e:
            raise Ineligible(str(e)) from None
        c.ns_kv[nid] = kv
        c.ns_key[nid] = key
        bw = int(t["ns_kv"].shape[1])
        t["ns_kv"][nid] = _pad_row(kv, bw, -1)
        t["ns_key"][nid] = _pad_row(key, bw, -1)
        patch.stage_rows(("ns_kv",), t["ns_kv"], nid, nid + 1)
        patch.stage_rows(("ns_key",), t["ns_key"], nid, nid + 1)
        st = eng._class_state
        if st is not None:
            ct = st["ctensors"]
            # the class buffer shares the namespace tables; its copies
            # may or may not alias the main ones — write + stage both
            ct["ns_kv"][nid] = t["ns_kv"][nid]
            ct["ns_key"][nid] = t["ns_key"][nid]
            if class_patch is not None:
                class_patch.stage_rows(("ns_kv",), ct["ns_kv"], nid, nid + 1)
                class_patch.stage_rows(("ns_key",), ct["ns_key"], nid, nid + 1)

    def _write_pod_row(
        self, i: int, pod: PodTuple, patch: _PatchSet, *, append: bool
    ) -> None:
        """Encode `pod` against the live vocab and write row i of the raw
        + bucketed pod arrays, staging the bucketed words."""
        eng = self.engine
        c = eng.encoding.cluster
        t = eng._tensors
        try:
            ns_id, kv, key, ip, ip_valid = encode_pod_rows(
                [pod], c.vocab, int(c.pod_kv.shape[1])
            )
        except ValueError as e:
            raise Ineligible(str(e)) from None
        if append:
            c.pod_ns_id = np.concatenate([c.pod_ns_id, ns_id])
            c.pod_kv = np.concatenate([c.pod_kv, kv])
            c.pod_key = np.concatenate([c.pod_key, key])
            c.pod_ip = np.concatenate([c.pod_ip, ip])
            c.pod_ip_valid = np.concatenate([c.pod_ip_valid, ip_valid])
            c.pod_keys.append(f"{pod[0]}/{pod[1]}")
            c.pod_ips.append(pod[3])
        else:
            c.pod_ns_id[i] = ns_id[0]
            c.pod_kv[i] = kv[0]
            c.pod_key[i] = key[0]
            c.pod_ip[i] = ip[0]
            c.pod_ip_valid[i] = ip_valid[0]
            c.pod_keys[i] = f"{pod[0]}/{pod[1]}"
            c.pod_ips[i] = pod[3]
        bw = int(t["pod_kv"].shape[1])
        t["pod_ns_id"][i] = ns_id[0]
        t["pod_kv"][i] = _pad_row(kv[0], bw, -1)
        t["pod_key"][i] = _pad_row(key[0], bw, -1)
        t["pod_ip"][i] = ip[0]
        t["pod_ip_valid"][i] = ip_valid[0]
        self._stage_pod_row(i, patch)

    def _stage_pod_row(self, i: int, patch: _PatchSet) -> None:
        t = self.engine._tensors
        for k in _POD_LEAVES:
            patch.stage_rows((k,), t[k], i, i + 1)

    def _clear_pod_row(self, i: int, patch: _PatchSet) -> None:
        """Reset bucketed row i to the inert pad scheme (ns -1, labels
        -1, invalid ip) — the exact fill _pad_pod_arrays uses."""
        t = self.engine._tensors
        t["pod_ns_id"][i] = -1
        t["pod_kv"][i] = -1
        t["pod_key"][i] = -1
        t["pod_ip"][i] = 0
        t["pod_ip_valid"][i] = False
        self._stage_pod_row(i, patch)

    def update_pod(self, i: int, pod: PodTuple, patch: _PatchSet) -> None:
        """Label/ip/namespace change of an existing pod row."""
        self._ensure_namespace(pod[0])
        self._write_pod_row(i, pod, patch, append=False)

    def add_pod(self, pod: PodTuple, patch: _PatchSet) -> int:
        """Claim the first padded row for a new pod; returns its index."""
        if self.pod_capacity() < 1:
            raise Ineligible("bucketed pod axis is full")
        self._ensure_namespace(pod[0])
        i = self.engine.encoding.cluster.n_pods
        self._write_pod_row(i, pod, patch, append=True)
        return i

    def remove_pod(self, i: int, patch: _PatchSet) -> Optional[int]:
        """Swap-remove pod row i (the last real row moves into the hole);
        returns the moved row's OLD index (None when i was last)."""
        eng = self.engine
        c = eng.encoding.cluster
        t = eng._tensors
        last = c.n_pods - 1
        moved = None
        if i != last:
            moved = last
            # copy VALUES first (reads before any write, alias-safe)
            row = tuple(np.copy(t[k][last]) for k in _POD_LEAVES)
            for k, v in zip(_POD_LEAVES, row):
                t[k][i] = v
            self._stage_pod_row(i, patch)
            c.pod_ns_id[i] = c.pod_ns_id[last]
            c.pod_kv[i] = c.pod_kv[last]
            c.pod_key[i] = c.pod_key[last]
            c.pod_ip[i] = c.pod_ip[last]
            c.pod_ip_valid[i] = c.pod_ip_valid[last]
            c.pod_keys[i] = c.pod_keys[last]
            c.pod_ips[i] = c.pod_ips[last]
        self._clear_pod_row(last, patch)
        c.pod_ns_id = c.pod_ns_id[:last].copy()
        c.pod_kv = c.pod_kv[:last].copy()
        c.pod_key = c.pod_key[:last].copy()
        c.pod_ip = c.pod_ip[:last].copy()
        c.pod_ip_valid = c.pod_ip_valid[:last].copy()
        c.pod_keys.pop()
        c.pod_ips.pop()
        return moved

    # --- class-state maintenance ----------------------------------------

    def class_mode(self) -> Optional[str]:
        return (
            None if self.engine._class_state is None else "active"
        )

    def update_pod_signature(self, i: int) -> str:
        """Recompute pod i's signature after a same-row update; move it
        between existing classes in place when possible.  Returns the
        action taken: 'none' (no class state), 'noop', 'moved', or
        'rebuild' (class state rebuilt)."""
        eng = self.engine
        if eng._class_state is None:
            return "none"
        enc = eng.encoding
        c = enc.cluster
        # refresh the selpod column from the RAW row (raw widths)
        col = engine_api._selector_match_np(
            enc.sel_req_kv,
            enc.sel_exp_op,
            enc.sel_exp_key,
            enc.sel_exp_vals,
            c.pod_kv[i : i + 1],
            c.pod_key[i : i + 1],
        )[:, 0]
        self._selpod[:, i] = col
        sig = pod_signatures(
            self._sig_view(np.array([i])),
            self._selpod[:, i : i + 1],
            cidr=eng._class_state.get("cidr"),
        )[0]
        if sig.shape[0] != self._sigs.shape[1]:
            self.rebuild_class_state()
            return "rebuild"
        if sig.tobytes() == self._sigs[i].tobytes():
            return "noop"
        pc = eng._class_state["classes"]
        cid_old = int(pc.class_of_pod[i])
        cid_new = self._class_sig_of.get(sig.tobytes())
        self._sigs[i] = sig
        if cid_new is None or (
            int(pc.class_rep[cid_old]) == i and int(pc.class_size[cid_old]) > 1
        ):
            # a brand-new signature needs a new class row (shape change),
            # and a departing representative leaves survivors pointing at
            # values that no longer exist — both rebuild the class state
            self.rebuild_class_state()
            return "rebuild"
        pc.class_of_pod[i] = cid_new
        pc.class_size[cid_old] -= 1
        pc.class_size[cid_new] += 1
        return "moved"

    def resize_signatures(self) -> None:
        """After add/remove churn the signature matrix is row-stale;
        the class state rebuilds wholesale (class axes may change)."""
        if self.engine._class_state is not None:
            self.rebuild_class_state()

    def rebuild_class_state(self) -> None:
        """Recompute classes + the class-representative tensor set from
        the CURRENT (already patched) engine tensors and re-upload only
        the class buffer; the main packed buffer is untouched."""
        eng = self.engine
        st = eng._class_state
        if st is None:
            return
        n = eng.encoding.cluster.n_pods
        self._selpod = engine_api._selector_pod_matches_host(
            self._raw_selector_view()
        )
        # re-resolve the TSS partition map from the CURRENT tensors: a
        # same-structure policy patch may have changed atom membership
        # within existing masks (patch_policy pins the MASK structure
        # itself — a new mask structure went Ineligible before any
        # mutation), and the stale map would compute stale signatures.
        # A patch_policy call stashes the space it already resolved for
        # the structure pin (same spec set — see _resolved_cidr) so the
        # policy-delta hot path derives it once, not twice.
        stashed, space = getattr(self, "_resolved_cidr", (False, None))
        self._resolved_cidr = (False, None)
        if not stashed:
            from ..engine import cidrspace

            space = cidrspace.resolve(
                eng._tensors, mode=eng._opt_cidr_tss, n_pods=n
            )
        st["cidr"] = space
        self._sigs = pod_signatures(
            self._sig_view(np.arange(n)),
            self._selpod,
            cidr=st["cidr"],
        )
        pc = classes_from_signatures(self._sigs)
        self._class_sig_of = {
            self._sigs[rep].tobytes(): cid
            for cid, rep in enumerate(np.asarray(pc.class_rep))
        }
        real = {
            k: np.ascontiguousarray(eng._tensors[k][:n])
            for k in _POD_LEAVES
        }
        base = dict(eng._tensors)
        base.update(real)
        ct = gather_class_pod_rows(base, pc.class_rep)
        ct = engine_api._bucket_tensors(
            engine_api._sort_targets_by_ns(ct),
            headroom=eng._slab_headroom,
        )
        st["classes"] = pc
        st["ratio"] = n / max(pc.n_classes, 1)
        st["ctensors"] = ct
        cb = int(ct["pod_ns_id"].shape[0])
        st["aux_bytes"] = int(
            n * 4 + cb * 4
            + sum(a.nbytes for a in engine_api._np_leaves(ct))
            + (st["cidr"].nbytes() if st["cidr"] is not None else 0)
        )
        st["last_gather_s"] = None
        # class buffer device state rebuilds lazily from the new host set
        eng._class_packed_buf = None
        eng._class_unpack = None
        eng._class_unpack_jit = None
        eng._class_device_tensors = None
        eng._class_of_dev = None
        eng._class_w_dev = None
        # the resident class selector matches were made for the old class
        # set: the program's own pass takes their place
        st["selpod"] = None
        eng._class_selpod_dev = None
        ti.CLASS_PODS.set(n)
        ti.CLASS_COUNT.set(pc.n_classes)
        ti.CLASS_RATIO.set(st["ratio"])
        ti.CLASS_AUX_BYTES.set(st["aux_bytes"])

    # --- rule-slab patches ----------------------------------------------

    def patch_policy(self, policy: Policy, tiers=None) -> None:
        """Re-encode the rule slabs for a changed policy/tier set and
        patch them into the live buffer; Ineligible when any slab changes
        its bucketed shape.

        `tiers` must be the service's CURRENT TierSet whenever the live
        engine carries tier slabs — even for a pure NetworkPolicy delta.
        The tier rows index the SHARED selector table this re-encode
        rebuilds, so re-encoding the NP directions alone would leave the
        tier slabs pointing at selector ids of the OLD table: a latent
        verdict≡allow-only assumption the lattice exposed (every
        pre-tier caller could drop the table because bool-OR rules were
        all re-encoded together).  The bucketed-shape comparison below
        covers the tier slabs exactly like the NP slabs."""
        eng = self.engine
        enc = eng.encoding
        vocab = enc.cluster.vocab
        had_tiers = "tiers" in eng._tensors
        if had_tiers and not tiers:
            raise Ineligible(
                "live engine carries tier slabs but the patch has no "
                "TierSet (tensor structure change)"
            )
        if tiers and not had_tiers:
            raise Ineligible(
                "tier slabs appear on a tier-less engine (tensor "
                "structure change)"
            )
        ingress, egress, sel_arrays, n_sel, tier_enc = encode_directions(
            policy, vocab, tiers=tiers if had_tiers else None
        )
        if ingress.host_ip_rows or egress.host_ip_rows:
            raise Ineligible(
                "changed policy set introduces host-evaluated (IPv6) "
                "IPBlock rows"
            )
        # TSS partition-map pin (docs/DESIGN.md "CIDR tuple-space
        # pre-classification"): when the live class state rides the LPM
        # stage, a policy delta that changes the MASK structure — a new
        # prefix length appearing, one disappearing, or the stage
        # flipping active/inactive — changes the very shape of every pod
        # signature.  That must be a full rebuild, checked BEFORE any
        # state mutates: patching first and reclassifying after would
        # leave a window where the engine's cached partition map
        # disagrees with its rule slabs.  Atom churn WITHIN existing
        # masks stays patchable (rebuild_class_state re-resolves the
        # map after the slabs land).  Checked below once the new
        # direction tensor dicts exist.
        new: Dict = {
            "sel_req_kv": sel_arrays[0],
            "sel_exp_op": sel_arrays[1],
            "sel_exp_key": sel_arrays[2],
            "sel_exp_vals": sel_arrays[3],
            "ingress": engine_api._direction_tensors(ingress),
            "egress": engine_api._direction_tensors(egress),
        }
        if tier_enc is not None:
            new["tiers"] = {
                "ingress": engine_api._tier_tensors(tier_enc[0]),
                "egress": engine_api._tier_tensors(tier_enc[1]),
            }
        if eng._class_state is not None:
            from ..engine import cidrspace

            new_space = cidrspace.resolve(
                {"ingress": new["ingress"], "egress": new["egress"]},
                mode=eng._opt_cidr_tss,
                n_pods=eng.encoding.cluster.n_pods,
            )
            if cidrspace.mask_structure(
                eng._class_state.get("cidr")
            ) != cidrspace.mask_structure(new_space):
                raise Ineligible(
                    "CIDR TSS partition structure changed (new mask "
                    "structure): the class signature layout must rebuild"
                )
        pstats = None
        if eng._partition_stats is not None:
            pstats = {}
            for direction in ("ingress", "egress"):
                new[direction], pstats[direction] = compress_rule_axes(
                    new[direction]
                )
        merged = dict(eng._tensors)
        merged.update(new)
        merged = engine_api._bucket_tensors(
            engine_api._sort_targets_by_ns(merged)
        )
        # fit the re-encoded slabs into the engine's ALLOCATED buckets
        # (compiled programs key on shapes): each leaf pads up to its
        # existing shape with the inert fill — into the headroom
        # reservation (slab_headroom) when the natural bucket grew —
        # and any axis past the allocation is a bucket overflow only a
        # full rebuild can absorb.  All checked before anything mutates.
        old = eng._tensors
        headroom = eng._slab_headroom

        def _fit(label: str, arr: np.ndarray, old_arr: np.ndarray, fill):
            if arr.shape == old_arr.shape:
                return arr
            if any(a > b for a, b in zip(arr.shape, old_arr.shape)):
                raise Ineligible(
                    f"{label} outgrows its allocated bucket "
                    f"{old_arr.shape} -> {arr.shape}"
                )
            for ax, size in enumerate(old_arr.shape):
                arr = engine_api._pad_axis(arr, ax, size, fill)
            return arr

        def _fit_slab_dict(label: str, od: Dict, nd: Dict, pads: Dict) -> Dict:
            if set(od) != set(nd):
                raise Ineligible(f"{label} slab key set changed")
            out = {}
            for k in od:
                if k == "port_spec":
                    if set(od[k]) != set(nd[k]):
                        raise Ineligible(f"{label} port_spec key set changed")
                    out[k] = {
                        s: _fit(
                            f"{label}.port_spec.{s}",
                            nd[k][s],
                            od[k][s],
                            engine_api._PORT_SPEC_PADS[s],
                        )
                        for s in od[k]
                    }
                else:
                    out[k] = _fit(f"{label}.{k}", nd[k], od[k], pads[k])
            return out

        # a headroom SAVE = THIS patch grew some rule-slab row axis past
        # the counterfactual zero-headroom engine's CURRENT bucket but
        # still fit the reservation — one full rebuild avoided
        # (cyclonus_tpu_serve_headroom_saves_total).  The baseline is
        # what a headroom-0 build would have allocated right now: it
        # starts at the build-time natural buckets and follows each
        # applied patch (a zero-headroom engine rebuilds on any bucket
        # change, ending up at exactly the patch's natural buckets), so
        # follow-up patches at an already-grown size count nothing.
        # Needed sizes are read BEFORE the fit pads them to allocation;
        # target axes are tracked in full-bucket units (allocated as
        # bucket - 1).
        needed_buckets: Dict[tuple, int] = {
            ("sel",): int(merged["sel_req_kv"].shape[0]),
        }
        for direction in ("ingress", "egress"):
            nd = merged[direction]
            needed_buckets[(direction, "target")] = (
                int(nd["target_ns"].shape[0]) + 1
            )
            needed_buckets[(direction, "peer")] = int(
                nd["peer_kind"].shape[0]
            )
            if had_tiers:
                needed_buckets[(direction, "tier")] = int(
                    merged["tiers"][direction]["action"].shape[0]
                )
        if self._natural_buckets is None:
            base: Dict[tuple, int] = {
                ("sel",): engine_api._bucket_down(
                    int(old["sel_req_kv"].shape[0]), headroom
                ),
            }
            for direction in ("ingress", "egress"):
                od = old[direction]
                base[(direction, "target")] = engine_api._bucket_down(
                    int(od["target_ns"].shape[0]) + 1, headroom
                )
                base[(direction, "peer")] = engine_api._bucket_down(
                    int(od["peer_kind"].shape[0]), headroom
                )
                if had_tiers:
                    base[(direction, "tier")] = engine_api._bucket_down(
                        int(old["tiers"][direction]["action"].shape[0]),
                        headroom,
                    )
            self._natural_buckets = base
        saved = headroom > 0 and any(
            needed > self._natural_buckets.get(key, needed)
            for key, needed in needed_buckets.items()
        )
        for k in _SEL_LEAVES:
            merged[k] = _fit(k, merged[k], old[k], engine_api._SEL_PADS[k])
        def no_band(d: Dict) -> Dict:
            # the band plan follows the FITTED shapes: laid anew below
            return {k: v for k, v in d.items() if k != "band_rows"}

        for direction in ("ingress", "egress"):
            merged[direction] = _fit_slab_dict(
                direction,
                no_band(old[direction]),
                no_band(merged[direction]),
                engine_api._DIRECTION_PADS,
            )
            if had_tiers:
                merged["tiers"] = dict(merged.get("tiers", {}))
                merged["tiers"][direction] = _fit_slab_dict(
                    f"tiers.{direction}",
                    old["tiers"][direction],
                    merged["tiers"][direction],
                    engine_api._TIER_PADS,
                )
        # the target-allows band plan of the fitted slabs
        # (api._band_tensors): it rides where the live one rides, its
        # span padded up to the allocated one, or the patch is a rebuild
        merged = engine_api._band_tensors(merged, headroom)
        for direction in ("ingress", "egress"):
            od, nd = old[direction], merged[direction]
            if ("band_rows" in od) != ("band_rows" in nd):
                raise Ineligible(
                    f"{direction} target-allows band plan appears or goes "
                    "(tensor structure change)"
                )
            if "band_rows" in nd:
                nd["band_rows"] = _fit(
                    f"{direction}.band_rows", nd["band_rows"],
                    od["band_rows"], -1,
                )
        patch = self.main_patchset()

        def _stage_slab_dict(prefix: tuple, d: Dict) -> None:
            for k, v in d.items():
                if k == "port_spec":
                    for s, arr in v.items():
                        patch.stage_leaf(prefix + ("port_spec", s), arr)
                else:
                    patch.stage_leaf(prefix + (k,), v)

        for k in _SEL_LEAVES:
            patch.stage_leaf((k,), merged[k])
        for direction in ("ingress", "egress"):
            _stage_slab_dict((direction,), merged[direction])
            if had_tiers:
                _stage_slab_dict(
                    ("tiers", direction), merged["tiers"][direction]
                )
        # the same CYCLONUS_SLAB_MAX_BYTES rule the pod/ns path obeys:
        # a slab patch stages idx+vals comparable to the slab size, and
        # past the budget the full rebuild (one packed transfer, no
        # scatter doubling) is the cheaper, bounded path.  Checked
        # BEFORE any host slab is replaced, so Ineligible leaves the
        # engine untouched.
        if patch.staged_bytes > patch_byte_budget():
            raise Ineligible(
                f"rule-slab patch bytes {patch.staged_bytes} exceed the "
                "CYCLONUS_SLAB_MAX_BYTES budget"
            )
        for k in _SEL_LEAVES:
            old[k] = merged[k]
        for direction in ("ingress", "egress"):
            old[direction] = merged[direction]
        if had_tiers:
            old["tiers"] = merged["tiers"]
        self.flush_main(patch)
        # the counterfactual zero-headroom engine has now rebuilt onto
        # exactly this patch's natural buckets
        self._natural_buckets = needed_buckets
        if saved:
            ti.SERVE_HEADROOM_SAVES.inc()
        # raw encoding follows (firing_components and the analysis layer
        # read it) + the derived host state
        enc.ingress = ingress
        enc.egress = egress
        enc.sel_req_kv, enc.sel_exp_op, enc.sel_exp_key, enc.sel_exp_vals = (
            sel_arrays
        )
        enc.n_selectors = n_sel
        if had_tiers:
            enc.tiers = tier_enc
            eng.tiers = tiers
        if pstats is not None:
            eng._partition_stats = pstats
        from ..engine.encoding import PEER_IP

        eng._has_ip_peers = bool(
            np.any(ingress.peer_kind == PEER_IP)
        ) or bool(np.any(egress.peer_kind == PEER_IP))
        if eng._class_state is not None:
            # the selector table changed: every signature's selpod block
            # is differently shaped — classes rebuild from scratch.  The
            # space resolved for the structure pin above is handed over
            # (a deterministic function of the spec set, which compress/
            # bucketing leave unchanged) so the policy-delta hot path
            # derives the partition map once, not twice; staged HERE —
            # past every Ineligible — so an aborted patch can never
            # leave a stale stash for a later rebuild to consume.
            self._resolved_cidr = (True, new_space)
            self.rebuild_class_state()

    # --- buffer application ----------------------------------------------

    def main_patchset(self) -> _PatchSet:
        eng = self.engine
        eng._ensure_packed()  # the buffer (and its layout) must exist
        return _PatchSet(eng._unpack.metas_by_path)

    def class_patchset(self) -> Optional[_PatchSet]:
        eng = self.engine
        if eng._class_state is None or eng._class_packed_buf is None:
            return None  # next transfer packs the (updated) host set
        return _PatchSet(eng._class_unpack.metas_by_path)

    def flush_main(self, patch: _PatchSet) -> int:
        eng = self.engine
        new_buf, nbytes = patch.flush(eng._packed_buf)
        eng._packed_buf = new_buf
        if nbytes:
            ti.SERVE_PATCH_BYTES.inc(nbytes)
        return nbytes

    def flush_class(self, patch: Optional[_PatchSet]) -> int:
        eng = self.engine
        if patch is None or eng._class_packed_buf is None:
            return 0
        new_buf, nbytes = patch.flush(eng._class_packed_buf)
        eng._class_packed_buf = new_buf
        if nbytes:
            ti.SERVE_PATCH_BYTES.inc(nbytes)
        return nbytes

    def finish(self) -> None:
        """Invalidate value-derived caches and refresh derived host
        state after a flushed patch."""
        eng = self.engine
        c = eng.encoding.cluster
        eng._unparseable_ips = [
            ip
            for ip, v4 in zip(c.pod_ips, c.pod_ip_valid)
            if not v4 and not engine_api._parseable_ip(ip)
        ]
        eng.invalidate_after_patch()
