"""Mesh-sharded verdict evaluation: SPMD over the pod axis with shard_map.

Sharding layout (see SURVEY.md section 2.7 / 5):
  * every per-pod tensor (labels, ns ids, IPs) is sharded over the 1D mesh
    axis 'x'; policy tensors (selectors, targets, peers, port specs) are
    replicated — they are small.
  * each device computes verdict ROWS for its source-pod block.
  * the three tables leave the program as kernel.cell_words
    [Q, N_pad, W] in their final [q, row, word] order, the ROW axis
    sharded over 'x': each device packs (the class route: gathers and
    packs) only its own rows, no device ever holds a whole table, and
    GridVerdict copies them to the host shard by shard.

Two schedules produce bit-identical grids (docs/DESIGN.md "Multi-chip
scale-out"):

  ring (default) — the OVERLAPPED path: each device keeps only its own
      pod shard's peer-side precompute and streams peer pod-blocks
      around the mesh with jax.lax.ppermute, one hop per step, computing
      the verdict block it already holds while the next block is in
      flight (the ppermute is issued BEFORE the step's matmuls, so the
      ICI transfer hides behind the MXU work).  Per-device peer-side
      working set: O(N / n_dev) resident + one in-flight block, vs the
      all-gather schedule's O(N) replicated copy.

  allgather — the reference schedule the ring is differentially pinned
      against: the peer-side target_allows[T, N, Q] (egress) and
      tmatch[T, N] + has_target[N] (ingress) are ALL-GATHERed once per
      eval and every device contracts against the full replicated copy.

The collectives ride ICI on a real TPU slice; on CPU the same programs
run over the virtual 8-device mesh the tests and dryrun_multichip pass
in explicitly.  Compiled programs are cached per (mesh, schedule,
shard) so repeat evaluations — and same-bucket cluster resizes — reuse
the trace (the zero-recompile elastic-resize contract).
"""

from __future__ import annotations

import math
import os
from typing import Dict, Optional, Tuple

from . import first_import

first_import()  # ahead of `import jax`: this may be the process's first
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..telemetry import instruments as ti
from ..utils import cachekeys
from ..utils.tracing import phase

from .kernel import (
    PACKED_CONTRACTION,
    TIER_FIRST_MATCH,
    WORD_CELLS,
    WORD_FORMAT,
    WORD_TILE,
    _bool_matmul,
    cell_words,
    direction_precompute,
    gather_class_words,
    lane_words,
    m_tp_onehot,
    pad_words,
    port_spec_allows,
    resolve_tier_lattice,
    selector_match,
    tallow_attrs,
    target_allows_form,
    tier_direction_arrays,
    tier_first_match_keys,
)

def shard_map_no_check(fn, mesh, in_specs, out_specs):
    """jax.shard_map with the replication (varying-manual-axes) check
    disabled."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


# pod-axis-sharded tensor keys
_POD_KEYS = ("pod_ns_id", "pod_kv", "pod_key", "pod_ip", "pod_ip_valid")


def pod_sharded_in_specs(tensors: Dict) -> Dict:
    """shard_map in_specs for an engine tensor dict: per-pod arrays (and
    host-evaluated ip-match rows) sharded over mesh axis 'x', policy
    tensors replicated.  Shared by every pod-axis-sharded program
    (full-grid sharded, ring counts) so a new tensor key cannot end up
    sharded in one and replicated in the other."""
    in_specs: Dict = {}
    for k, v in tensors.items():
        if k in _POD_KEYS:
            in_specs[k] = (
                P("x") if np.ndim(v) == 1 else P("x", *([None] * (np.ndim(v) - 1)))
            )
        elif k == "tiers":
            # tier slabs are rule-axis arrays: replicated, leaf by leaf
            in_specs[k] = jax.tree_util.tree_map(lambda _: P(), v)
        elif k in ("ingress", "egress"):
            sub = {}
            for kk, vv in v.items():
                if kk == "host_ip_match":
                    sub[kk] = P(None, "x")
                elif kk == "port_spec":
                    sub[kk] = {k3: P() for k3 in vv}
                else:
                    sub[kk] = P()
            in_specs[k] = sub
        else:
            in_specs[k] = P()
    return in_specs


def mesh_device_context(mesh: Mesh):
    """Context manager for dispatching onto `mesh`.  A CPU mesh (the
    virtual multi-device mesh a caller passed in explicitly) pins every
    dispatch in the scope to CPU so no unsharded op lands on the default
    device: a CPU-mesh evaluation must never touch — or require — an
    accelerator.  Decided from the mesh platform alone; when CPU already
    IS the default backend the pin is a no-op."""
    import contextlib

    dev = mesh.devices.flat[0]
    if dev.platform == "cpu":
        return jax.default_device(dev)
    return contextlib.nullcontext()


def default_mesh() -> Mesh:
    """All devices of the default backend and nothing else: a one-chip
    TPU gets a one-device mesh, never a virtual CPU mesh that would
    compute `--engine tpu-sharded` on the host.  Callers that want a
    virtual CPU mesh (the tests, dryrun_multichip) build and pass it."""
    from . import devices

    return Mesh(np.array(devices()), ("x",))


def _pad_pod_arrays(tensors: Dict, n_pods: int, n_dev: int) -> Tuple[Dict, int]:
    """Pad the pod axis to a multiple of the device count with inert rows
    (ns id -1, labels -1, invalid ip): they match no target and no peer.
    The arrays may already be LONGER than n_pods (shape bucketing pads
    them with the same inert rows at build time) — the current length,
    not n_pods, is what gets rounded up."""
    cur = int(tensors["pod_ns_id"].shape[0])
    padded = math.ceil(max(cur, n_pods, 1) / n_dev) * n_dev
    if padded == cur:
        return tensors, cur
    pad = padded - cur
    t = dict(tensors)
    t["pod_ns_id"] = np.concatenate(
        [tensors["pod_ns_id"], np.full((pad,), -1, np.int32)]
    )
    t["pod_kv"] = np.concatenate(
        [tensors["pod_kv"], np.full((pad, tensors["pod_kv"].shape[1]), -1, np.int32)]
    )
    t["pod_key"] = np.concatenate(
        [tensors["pod_key"], np.full((pad, tensors["pod_key"].shape[1]), -1, np.int32)]
    )
    t["pod_ip"] = np.concatenate(
        [tensors["pod_ip"], np.zeros((pad,), np.uint32)]
    )  # shape: (N,) uint32; sentinel: 0=invalid; mask: pod_ip_valid
    t["pod_ip_valid"] = np.concatenate(
        [tensors["pod_ip_valid"], np.zeros((pad,), bool)]
    )  # shape: (N,) bool
    for direction in ("ingress", "egress"):
        d = t[direction]
        if "host_ip_match" in d:
            d = dict(d)
            d["host_ip_match"] = np.concatenate(
                [
                    d["host_ip_match"],
                    np.zeros((d["host_ip_match"].shape[0], pad), bool),
                ],
                axis=1,
            )
            t[direction] = d
    return t, padded


def _sharded_eval(tensors: Dict) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The per-device ALL-GATHER reference program (schedule="allgather").
    Local pod block = this device's source rows (and, symmetrically, its
    slice of every per-pod precompute); the peer side is gathered whole.
    Kept as the differential twin the overlapped ring schedule is pinned
    bit-identical against."""
    selpod = selector_match(
        tensors["sel_req_kv"],
        tensors["sel_exp_op"],
        tensors["sel_exp_key"],
        tensors["sel_exp_vals"],
        tensors["pod_kv"],
        tensors["pod_key"],
    )  # [S, Nb]
    selns = selector_match(
        tensors["sel_req_kv"],
        tensors["sel_exp_op"],
        tensors["sel_exp_key"],
        tensors["sel_exp_vals"],
        tensors["ns_kv"],
        tensors["ns_key"],
    )  # [S, M] replicated

    pre = {}
    pport = {}
    for direction in ("ingress", "egress"):
        enc = tensors[direction]
        p = direction_precompute(
            enc,
            selpod,
            selns,
            tensors["pod_ns_id"],
            tensors["pod_ip"],
            tensors["pod_ip_valid"],
        )
        if "host_ip_match" in enc:
            p["peer_match"] = jnp.where(
                enc["host_ip_mask"][:, None], enc["host_ip_match"], p["peer_match"]
            )
        pre[direction] = p
        pport[direction] = port_spec_allows(
            enc["port_spec"],
            tensors["q_port"],
            tensors["q_name"],
            tensors["q_proto"],
        )

    q = tensors["q_port"].shape[0]

    # precedence-tier precompute over the LOCAL pod block; the remote
    # side of each direction is all-gathered below exactly like the
    # NetworkPolicy arrays (docs/DESIGN.md "Precedence tiers")
    tier = None
    if "tiers" in tensors:
        tier = {
            d: tier_direction_arrays(
                tensors["tiers"][d],
                selpod,
                selns,
                tensors["pod_ns_id"],
                tensors["q_port"],
                tensors["q_name"],
                tensors["q_proto"],
            )
            for d in ("ingress", "egress")
        }

    # --- egress: local source block is the target side ---
    enc_e, pre_e = tensors["egress"], pre["egress"]
    n_b = pre_e["peer_match"].shape[1]
    peer_allow_e = (
        pre_e["peer_match"][:, :, None] & pport["egress"][:, None, :]
    ).reshape(pre_e["peer_match"].shape[0], n_b * q)
    tallow_e_local = _bool_matmul(m_tp_onehot(enc_e), peer_allow_e)  # [T, Nb*Q]
    t_e = tallow_e_local.shape[0]
    # one collective per eval: gather destination-side target_allows
    g_tallow_e = jax.lax.all_gather(
        tallow_e_local.reshape(t_e, n_b, q), "x", axis=1, tiled=True
    )  # [T, N, Q]
    n_total = g_tallow_e.shape[1]
    any_allow_e = _bool_matmul(
        pre_e["tmatch"].T, g_tallow_e.reshape(t_e, n_total * q)
    ).reshape(n_b, n_total, q)
    egress = (~pre_e["has_target"][:, None, None]) | any_allow_e  # [Sb, N, Q]
    if tier is not None:
        te = tier["egress"]
        # subject = local source block; peer side gathers like tallow
        g_peerq_e = jax.lax.all_gather(
            te["peerq"], "x", axis=1, tiled=True
        )  # [G, N, Q]
        anp_e, banp_e = tier_first_match_keys(
            te["subj"], g_peerq_e, te["anp_key"], te["banp_key"]
        )  # [Sb, N, Q]
        egress = resolve_tier_lattice(
            egress, pre_e["has_target"][:, None, None], anp_e, banp_e
        )

    # --- ingress: local source block is the peer side ---
    enc_i, pre_i = tensors["ingress"], pre["ingress"]
    peer_allow_i = (
        pre_i["peer_match"][:, :, None] & pport["ingress"][:, None, :]
    ).reshape(pre_i["peer_match"].shape[0], n_b * q)
    tallow_i_local = _bool_matmul(m_tp_onehot(enc_i), peer_allow_i)  # [T, Nb*Q]
    t_i = tallow_i_local.shape[0]
    # port-independent collectives: gather target-side matches
    g_tmatch_i = jax.lax.all_gather(pre_i["tmatch"], "x", axis=1, tiled=True)  # [T, N]
    g_has_t_i = jax.lax.all_gather(pre_i["has_target"], "x", axis=0, tiled=True)  # [N]
    any_allow_i = _bool_matmul(
        g_tmatch_i.T, tallow_i_local
    )  # [N, Sb*Q]
    ingress_t = (
        (~g_has_t_i[:, None, None]) | any_allow_i.reshape(n_total, n_b, q)
    )  # [N_dst, Sb, Q]
    if tier is not None:
        ti_ = tier["ingress"]
        # target side gathers (like tmatch); peer = local source block
        g_subj_i = jax.lax.all_gather(
            ti_["subj"], "x", axis=1, tiled=True
        )  # [G, N]
        anp_i, banp_i = tier_first_match_keys(
            g_subj_i, ti_["peerq"], ti_["anp_key"], ti_["banp_key"]
        )  # [N_dst, Sb, Q]
        ingress_t = resolve_tier_lattice(
            ingress_t, g_has_t_i[:, None, None], anp_i, banp_i
        )
    ingress_rows = jnp.swapaxes(ingress_t, 0, 1)  # [Sb, N_dst, Q]

    combined = egress & ingress_rows
    return ingress_rows, egress, combined


def _ring_grid_eval(tensors: Dict, n_dev: int, shard: int, pack: bool = False):
    """The per-device OVERLAPPED ring program: local peer-side bundle
    only, one ppermute hop per step, verdict blocks written column-wise.

    Reuses the tiled path's precompute/split/verdict bodies
    (tiled._precompute / _split_pre / _tile_verdicts_split) so the ring
    step's semantics — including the precedence-tier epilogue, whose
    min-key resolution runs INSIDE each ring step against the rotated
    subject/peer blocks — can never diverge from the single-device and
    ring-counts paths.  With `pack` the rotating bundle carries the
    32-per-word packed match slabs (tiled._split_pre), so each ppermute
    hop moves ~16x fewer peer bytes; the allgather schedule stays the
    dense reference twin the ring is pinned bit-identical against."""
    from .tiled import (
        _dst_bundle_keys,
        _precompute,
        _ring_sweep,
        _split_pre,
        _tile_verdicts_split,
    )

    pre = _precompute(tensors, pack)
    src, dst0 = _split_pre(pre)
    dev = jax.lax.axis_index("x")
    n_total = n_dev * shard
    q = tensors["q_port"].shape[0]
    init = tuple(
        jnp.zeros((shard, n_total, q), dtype=bool) for _ in range(3)
    )

    def body(step, ring, grids):
        ing, eg, comb = grids
        dst = {k: ring[k] for k in _dst_bundle_keys(ring)}
        i_blk, e_blk, c_blk = _tile_verdicts_split(src, dst, 0, shard)
        # after `step` hops we hold the bundle that originated at device
        # (dev - step) mod n_dev: its verdicts land in those columns
        col0 = ((dev - step) % n_dev) * shard
        ing = jax.lax.dynamic_update_slice(ing, i_blk, (0, col0, 0))
        eg = jax.lax.dynamic_update_slice(eg, e_blk, (0, col0, 0))
        comb = jax.lax.dynamic_update_slice(comb, c_blk, (0, col0, 0))
        return (ing, eg, comb)

    (ing, eg, comb), _ = _ring_sweep(n_dev, dst0, init, body)
    return ing, eg, comb


def mesh_schedule(schedule: Optional[str] = None) -> str:
    """Resolve the mesh exchange schedule: explicit arg, else
    CYCLONUS_MESH_SCHEDULE, else "ring" (the overlapped default;
    "allgather" keeps the replicated reference schedule)."""
    s = (schedule or os.environ.get("CYCLONUS_MESH_SCHEDULE", "ring")).lower()
    if s not in ("ring", "allgather"):
        raise ValueError(
            f"unknown mesh schedule {s!r} (want 'ring' or 'allgather')"
        )
    return s


def peer_buffer_bytes(
    tensors: Dict, n_dev: int, schedule: str, pack: bool = False
) -> int:
    """Host-side estimate of the PER-DEVICE peer-side working set of one
    sharded grid eval — the number the HBM watermark gauge records and
    the scale-out acceptance asserts on (ring < allgather at 8 devices).

    allgather: the gathered bool arrays every device holds replicated —
    egress tallow [T_e, N, Q] + ingress tmatch [T_i, N] + has [N]
    (+ the gathered tier scope blocks).  ring: TWO copies (resident +
    in-flight ppermute target) of the rotating bundle over one shard —
    tallow_bf is bf16 (2 bytes), the rest bool; with `pack` the
    tallow/tmatch legs ship as 32-per-word int32 packed slabs
    (encoding.packed_words(T) words of 4 bytes each)."""
    from .encoding import packed_words

    n = int(tensors["pod_ns_id"].shape[0])
    q = int(tensors["q_port"].shape[0])
    t_e = int(tensors["egress"]["target_ns"].shape[0])
    t_i = int(tensors["ingress"]["target_ns"].shape[0])
    g_e = g_i = 0
    if "tiers" in tensors:
        g_e = int(tensors["tiers"]["egress"]["action"].shape[0])
        g_i = int(tensors["tiers"]["ingress"]["action"].shape[0])
    if schedule == "allgather":
        return t_e * n * q + t_i * n + n + g_e * n * q + g_i * n
    shard = n // max(n_dev, 1)
    if pack:
        bundle = (
            4 * packed_words(t_e) * shard * q  # tallow_pk: int32 words
            + 4 * packed_words(t_i) * shard  # tmatch_pk
            + shard  # has_i
            + g_e * shard * q
            + g_i * shard
        )
    else:
        bundle = (
            2 * t_e * shard * q  # tallow_bf: bf16
            + t_i * shard
            + shard  # has_i
            + g_e * shard * q
            + g_i * shard
        )
    return 2 * bundle


def _block_words(block: jnp.ndarray) -> jnp.ndarray:
    """A device's rows of a table, bool [R, N, Q], as cell_words
    uint32 [Q, R, W]: the final order, packed where the rows are."""
    a = jnp.moveaxis(block, -1, 0)
    return cell_words([a[..., k::WORD_CELLS] for k in range(WORD_CELLS)])


def _dense_words(ingress_rows, egress, combined):
    """The dense routes' epilogue, inside the sharded program: each
    device's [Sb, N, Q] blocks as words of its own rows.  Ingress is
    indexed [dst, src] and a word packs along src, so a device's source
    block is whole WORDS of every destination's row: it packs them where
    they are ([Q, N, Sb / 4] uint32, its columns of the table) and the
    pieces change hands in ONE explicit all_to_all over a LEADING axis
    of device-sized chunks (device j gets the words of its destinations
    from every source block and lays them side by side), N * N * Q /
    n_dev bytes a device as before.  The exchange used to carry the
    booleans, split along their second axis: at 40,960 pods on four
    v5e chips that one operation took the TPU compiler nine minutes and
    327 MB of code (ISSUE 34; my compile for a described v5e:2x2)."""
    shard, n_total, q = ingress_rows.shape
    n_dev = n_total // shard
    a = jnp.transpose(ingress_rows, (2, 1, 0))  # [Q, N_dst, Sb]
    cols = lane_words([a[..., k::WORD_CELLS] for k in range(WORD_CELLS)])
    width = cols.shape[-1]
    got = jax.lax.all_to_all(
        jnp.moveaxis(cols.reshape(q, n_dev, shard, width), 1, 0),
        "x",
        split_axis=0,
        concat_axis=0,
        tiled=True,
    )  # [n_dev (source block), Q, Db, Sb / 4]
    ingress = pad_words(
        jnp.moveaxis(got, 0, 2).reshape(q, shard, n_dev * width)
    )
    return ingress, _block_words(egress), _block_words(combined)


def _class_words(ingress_rows, egress, combined, rows, cols):
    """The class route's epilogue, inside the sharded program: the
    C x C x Q class grids are all-gathered (they are small), ingress
    takes its [dst, src] orientation THERE, and every device gathers
    and packs the words of its own pod rows (`rows`: its slice of the
    pod -> class map, -1 on pad rows; `cols`: the whole map).  No
    [N, N, Q] value exists anywhere."""

    def whole(a, order):
        return jnp.transpose(
            jax.lax.all_gather(a, "x", axis=0, tiled=True), order
        )

    out = gather_class_words(
        {
            "ingress": whole(ingress_rows, (2, 1, 0)),
            "egress": whole(egress, (2, 0, 1)),
            "combined": whole(combined, (2, 0, 1)),
        },
        cols,
        rows=rows,
    )
    return out["ingress"], out["egress"], out["combined"]


#: compiled sharded-grid programs, keyed by (mesh devices, schedule,
#: shard, pack, classes, in_specs structure).  One entry per (mesh,
#: schedule, shape family) — re-jitting per eval cost a full retrace
#: every call, and a same-bucket cluster resize must hit this cache
#: (zero-recompile contract, pinned by tests/test_engine_sharded.py)
_SHARDED_PROGRAMS: Dict = {}  # cache-key: mesh, schedule, shard, pack, classes, specs
_SHARDED_PROGRAMS_MAX = 64

#: the tables' sharding as they leave the program: [q, row, word], rows over x
_WORDS_SPEC = P(None, "x", None)

#: how the dense routes' epilogue hands ingress over (_dense_words): part
#: of the persistent key, since the arg shapes and the result's form
#: cannot see it and an executable that exchanges booleans computes the
#: same tables
DENSE_EXCHANGE = "xchg=words"


def _sharded_program(
    mesh: Mesh,
    schedule: str,
    shard: int,
    in_specs: Dict,
    pack: bool = False,
    classes: bool = False,
):
    """The jitted shard_map program of one (mesh, schedule, shape
    family): the schedule's verdict blocks, then the word epilogue of
    the dense routes (fn(tensors)) or of the class route
    (fn(tensors, rows, cols))."""
    n_dev = int(mesh.devices.size)
    leaves, treedef = jax.tree_util.tree_flatten(in_specs)
    key = (
        tuple(mesh.devices.flat),
        tuple(mesh.axis_names),
        schedule,
        shard,
        pack,
        classes,
        treedef,
        tuple(leaves),
    )
    fn = _SHARDED_PROGRAMS.get(key)
    if fn is None:
        if schedule == "ring":
            def blocks(t):
                return _ring_grid_eval(t, n_dev, shard, pack)
        else:
            blocks = _sharded_eval
        if classes:
            def body(t, rows, cols):
                return _class_words(*blocks(t), rows, cols)

            specs = (in_specs, P("x"), P())
        else:
            def body(t):
                return _dense_words(*blocks(t))

            specs = (in_specs,)
        fn = jax.jit(
            shard_map_no_check(
                body, mesh=mesh, in_specs=specs, out_specs=(_WORDS_SPEC,) * 3
            )
        )
        # the persistent AOT executable cache covers the cached sharded
        # programs too (engine/aot_cache.py): a restarted process
        # adopts the ring/allgather executables for its mesh without a
        # retrace.  The partition-spec structure, the shard/pack
        # statics, the form of the packed contraction, that of the tier
        # first-match (a tiered set alone: an untiered plan stays as it
        # was), that of the target-allows contraction (the ring alone
        # traces tiled._precompute; banded where the specs carry
        # band_rows), the epilogue and the result's form
        # are program identity the arg shapes can't see, so they ride in
        # the plan.
        from . import aot_cache

        spec_digest = aot_cache.digest(
            (str(treedef), [str(x) for x in leaves])
        )
        fn = aot_cache.AotProgram(
            "sharded.grid",
            fn,
            schedule=schedule,
            plan=(
                (f"{target_allows_form(in_specs)};" if schedule == "ring" else "")
                + f"shard={shard};pack={pack};"
                + (f"{PACKED_CONTRACTION};" if pack else "")
                + (f"{TIER_FIRST_MATCH};" if "tiers" in in_specs else "")
                + f"classes={classes};"
                f"mesh={','.join(mesh.axis_names)}x{n_dev};{spec_digest};"
                + ("" if classes else f"{DENSE_EXCHANGE};")
                + WORD_FORMAT
            ),
        )
        if cachekeys.ACTIVE:
            cachekeys.register(
                "sharded.programs",
                kind="program",
                components=cachekeys.program(
                    "mesh", "schedule", "shard", "pack", "classes", "specs"
                ),
            )
        if len(_SHARDED_PROGRAMS) >= _SHARDED_PROGRAMS_MAX:
            _SHARDED_PROGRAMS.clear()  # crude bound; programs re-jit
        _SHARDED_PROGRAMS[key] = fn
    return fn


def evaluate_grid_sharded(
    tensors: Dict,
    n_pods: int,
    mesh: Optional[Mesh] = None,
    schedule: Optional[str] = None,
    class_of: Optional[np.ndarray] = None,
) -> Tuple[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray], Optional[int]]:
    """((ingress, egress, combined), eval_id): the three tables as
    DEVICE-RESIDENT kernel.cell_words uint32 [Q, N_pad, W] (ingress
    [q, dst, src], egress and combined [q, src, dst]), the row axis
    sharded over the mesh's 'x' and padded to whole WORD_TILE rows on
    every device (GridVerdict leaves pad rows and cells out of every
    view), and the evaluation's number for the fetch spans.

    `schedule` picks the peer exchange: "ring" (overlapped, default) or
    "allgather" (replicated reference); both are bit-identical by
    construction and pinned so by tests/test_engine_sharded.py.

    With `class_of` (int32 [N], pod -> class) `tensors` carries class-
    representative rows on the pod axis (encoding.gather_class_pod_rows)
    and `n_pods` is the class count: the schedule runs over the class
    axis - with the ring, a C x C ring over class representatives - and
    the same program broadcasts back to pod rows (_class_words)."""
    from .encoding import pack_enabled
    from .tiled import tier_attrs

    mesh = mesh or default_mesh()
    schedule = mesh_schedule(schedule)
    pack = pack_enabled()
    n_dev = int(mesh.devices.size)
    classes = class_of is not None
    # a device's rows of a table are whole word tiles: on the class
    # route the rows are pods and the evaluated axis only has to divide
    step = n_dev * WORD_TILE[0]
    tensors, padded_n = _pad_pod_arrays(
        tensors, n_pods, n_dev if classes else step
    )
    shard = padded_n // n_dev

    in_specs = pod_sharded_in_specs(tensors)
    fn = _sharded_program(
        mesh, schedule, shard, in_specs, pack=pack, classes=classes
    )
    args = (tensors,)
    if classes:
        class_of = np.asarray(class_of, dtype=np.int32)
        n_pods = class_of.shape[0]
        rows = np.full(-(-n_pods // step) * step, -1, np.int32)
        rows[:n_pods] = class_of
        args = (tensors, rows, class_of)
    peer_bytes = peer_buffer_bytes(tensors, n_dev, schedule, pack=pack)
    ti.MESH_PEER_BYTES.set(peer_bytes, schedule=schedule)
    route = "classes" if classes else schedule
    with ti.eval_flight(
        "grid.sharded", n_pods, int(tensors["q_port"].shape[0]),
        devices=n_dev, schedule=schedule, classes=classes,
        dispatch_only=True, **tier_attrs(tensors),
        # the ring alone traces tiled._precompute
        **(tallow_attrs(tensors) if schedule == "ring" else {}),
    ) as fl:
        with mesh_device_context(mesh):
            fn.resolve(*args)
            with phase(
                "engine.dispatch_sharded",
                route=route,
                devices=n_dev,
                schedule=schedule,
                shard=shard,
                peer_bytes=peer_bytes,
            ) as sp:
                out = fn(*args)
                # counted once the program is enqueued: the chips are
                # running, so walking the operands costs the request nothing
                host_operands, host_bytes = _host_traffic(
                    args, (in_specs, P("x"), P()), n_dev
                )
                sp.set(host_operands=host_operands, host_bytes=host_bytes)
            ti.MESH_DISPATCH_BYTES.inc(host_bytes, route=route)
    return out, fl.eval_id


def _host_traffic(args, specs, n_dev: int) -> Tuple[int, int]:
    """(host arrays among a sharded program's operands, the bytes they
    cost the call): an array sharded over 'x' goes out once, a piece to
    each chip; a replicated one goes whole to every chip.  `specs` is
    the operands' spec tree (or a longer one: the dense routes have no
    row map)."""
    host = [
        (a, spec)
        for a, spec in zip(
            jax.tree_util.tree_leaves(args),
            jax.tree_util.tree_leaves(specs[: len(args)]),
        )
        if isinstance(a, np.ndarray)
    ]
    return len(host), sum(
        a.nbytes * (1 if "x" in spec else n_dev) for a, spec in host
    )
