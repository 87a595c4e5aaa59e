"""Pallas TPU kernel: fused verdict-tile + count reduction.

The XLA tiled counts path (tiled.py) materializes per-tile boolean verdict
blocks and f32 matmul outputs in HBM before reducing them.  This kernel
fuses the whole per-tile epilogue —

    egress   = (tmatch_e_blk'^T @ tallow_e') > 0
    ingress  = (tallow_i_blk'^T @ tmatch_i') > 0
    combined = egress AND ingress
    counts  += [sum ingress, sum egress, sum combined]

— into VMEM: a blocked matmul over grid (q, src-tile, dst-tile, T-chunk)
with two f32 accumulators in scratch and a count epilogue on the last
T-chunk.  The three N x N x Q verdict tensors never exist anywhere.
The primed operands carry one extra PSEUDO-TARGET row per direction that
encodes both the allow-if-no-matching-target rule and the pod-validity
mask (verdict_counts_pallas docstring), so the epilogue needs no
correction terms.

Decision procedure mirrors tiled._tile_verdicts / kernel.py (reference
policy.go:138-174); parity vs the XLA paths is enforced by
tests/test_engine_pallas.py (interpret mode on CPU, compiled on TPU).

Layout notes:
  * all matmul operands are pre-cast to bf16; accumulation is f32 on the
    MXU, so the > 0 threshold is exact (0/1 inputs).
  * the pod axis is padded to the lane-aligned tile BD and the target
    axis to the chunk KT with zeros: padded targets match nothing and
    allow nothing; padded pods fail the pseudo-target's validity gate,
    so their rows and columns count as zero with no explicit mask.
  * counts accumulate into a per-(port case, src-tile) int32 output block
    (the standard reduction-output pattern); lanes 0-2 hold ingress/
    egress/combined.  Per-block partials are bounded by bs * N with bs
    chosen by _tiles_for (512 or 1024), which checks exactly this bound
    before doubling; the host sums them in int64 (a single global int32
    accumulator overflowed at 100k pods).
"""

from __future__ import annotations

import math
import os
from functools import partial
from typing import Dict, Tuple

from . import first_import

first_import()  # ahead of the imports below: these may be the process's first
first_import("jax.experimental.pallas")
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry import instruments as ti

# base tile sizes: BS/BD are the src/dst tile heights (MXU-aligned), KT
# the MAX target-axis chunk.  The actual per-call sizes come from
# _kt_for (shrinks KT to the live target count) and _tiles_for (doubles
# the src tile to 1024 when the smaller chunks leave VMEM room) — the
# VMEM/overflow budgets live in those two functions.
BS = 512
BD = 512
KT = 1024


def _kt_for(n_targets: int) -> int:
    """Per-direction target-axis chunk: lane-aligned (128) and no larger
    than needed.  Target counts after dead-target compaction are often
    far below the max chunk (e.g. ~300 at the 10k-policy bench config);
    padding them to a fixed 1024 would multiply both the contraction
    depth (matmul flops) and the [Q, KT, N] operand's HBM footprint —
    the single-chip memory ceiling at multi-million-pod scale."""
    return max(128, min(KT, lane_round_up(n_targets)))


def lane_round_up(n: int) -> int:
    """Smallest multiple of the 128-lane tile >= n (>= 128) — THE
    ceil-div round-up shapelint SC004 discharges for the target chunks
    (_kt_for above), factored out so lane alignment has one formula,
    not several hand-rolled copies."""
    return -(-max(int(n), 1) // 128) * 128


def _tiles_for(
    kt_e: int,
    kt_i: int,
    n: int,
    single_chunk_int8: bool = False,
    n_dst: int = None,
) -> Tuple[int, int]:
    """Src/dst tile heights.  From the default (512, 512), double the src
    tile when (a) the T-chunks leave VMEM room for the bigger blocks +
    scratch and (b) per-(q, src-tile) int32 count partials stay below
    2^31 — fewer grid steps amortize the per-step epilogue/DMA overhead
    (bench-measured 56 -> 68 e9 cells/s at the 100k x 10k config).  On
    the scratch-free single-chunk int8 path the blocks are half the
    bytes and there are no accumulator tiles, so (2048, 1024) fits and
    measures fastest (0.27 -> 0.19 s at the bench config).  The count
    bound is per (src tile x FULL dst axis), so rectangular callers pass
    n_dst (defaults to n for the square case).  A non-default BS/BD
    (tests sweep them) is honored as-is."""
    if n_dst is None:
        n_dst = n
    bs, bd = BS, BD
    if (bs, bd) != (512, 512):
        return bs, bd
    if single_chunk_int8:
        # VMEM gate on the actual chunk sizes, not just the int32 count
        # bound: the (2048, 1024) tile's double-buffered int8 input
        # blocks are 2 * (kt_e + kt_i) * (2048 + 1024) bytes, and the
        # two [2048, 1024] int32 matmul intermediates add ~16 MiB more
        # against the ~16 MiB/core VMEM budget.  The bench regime
        # (kt_e + kt_i ~ 640 after compaction) fits with room; with both
        # directions near the 1024 chunk max (~12 MiB of blocks alone)
        # Mosaic compilation would fail at runtime — cap the blocks at
        # 6 MiB (kt_e + kt_i <= 1024) and fall through to the 512-tile
        # path, whose own budget accounts for kt, when it doesn't fit.
        blocks_1chunk = 2 * (kt_e + kt_i) * (2048 + 1024)  # int8, dbuf
        if (
            n > 2 * bs
            and 2048 * (n_dst + 4096) < 2**31
            and blocks_1chunk <= 6 * 2**20
        ):
            return 2048, 1024
        # fall through to the doubled-bs check for mid-size clusters
    blocks = 4 * (kt_e + kt_i) * (2 * bs + bd)  # bf16, double-buffered
    scratch = 2 * 4 * (2 * bs) * bd  # two f32 accumulators
    if (
        n > bs  # a single default tile already holds the whole problem
        and blocks + scratch <= 12 * 2**20
        and 2 * bs * (n_dst + 2048) < 2**31
    ):
        bs *= 2
    return bs, bd


def _make_verdict_counts_kernel(n_k_e: int, n_k_i: int):
    """Kernel body specialized on the per-direction T-chunk counts: the
    two directions usually pad to different target-axis lengths (egress
    targets are a subset of policies), and multiplying the shorter
    direction's zero chunks would waste up to ~⅓ of the MXU work.

    Content skip: the nz_e/nz_i scalar-prefetch maps mark which
    (pod-tile, T-chunk) tmatch blocks contain any nonzero.  With pods
    and targets namespace-sorted (api._counts_pallas_packed) tmatch is
    near block diagonal, so most blocks are empty and their matmuls are
    skipped entirely — this is where the 10k-policy regime's T-axis
    flops go."""
    ti.KERNEL_TRACES.inc(kernel="counts_chunked")

    def _verdict_counts_kernel(
        nz_e_ref,  # [n_i * n_k_e] int32 scalar-prefetch: tmatch_e block nonzero
        nz_i_ref,  # [n_k_i * n_j] int32 scalar-prefetch: tmatch_i block nonzero
        redir_e_ref,  # [n_i * n_k_e] int32: last nonzero chunk <= k (DMA reuse)
        redir_i_ref,  # [n_k_i * n_j] int32: last nonzero chunk <= k (DMA reuse)
        a_e_ref,  # [BS, KT] bf16   tmatch_e^T src block, T-chunk k
        b_e_ref,  # [1, KT, BD] bf16  tallow_e (q, T-chunk k, dst block j)
        b_i_ref,  # [1, KT, BS] bf16  tallow_i (q, T-chunk k, src block i)
        a_i_ref,  # [KT, BD] bf16   tmatch_i (T-chunk k, dst block j)
        counts_ref,  # [1, n_i, 128] int32: per-q count plane, row per src-tile
        acc_e_ref,  # [BS, BD] f32 scratch
        acc_i_ref,  # [BS, BD] f32 scratch
        cnt_ref,  # [1, 128] int32 scratch: running counts for this (q, i)
    ):
        i = pl.program_id(1)
        j = pl.program_id(2)
        k = pl.program_id(3)
        n_j = pl.num_programs(2)
        n_k = pl.num_programs(3)

        # counts accumulate into a per-(q, src-tile) ROW of the per-q count
        # plane: a single global accumulator overflows int32 once allowed
        # cells exceed 2^31 (seen at 100k pods); per-row partials are bounded
        # by the _tiles_for-checked bs * N < 2^31.  (The plane is the output block — a (1, 1, 128)
        # block would violate the Mosaic (8, 128) tiling rule for n_i > 1.)
        @pl.when((i == 0) & (j == 0) & (k == 0))
        def _init_counts():
            counts_ref[:] = jnp.zeros_like(counts_ref)

        @pl.when(k == 0)
        def _init_acc():
            acc_e_ref[:] = jnp.zeros_like(acc_e_ref)
            acc_i_ref[:] = jnp.zeros_like(acc_i_ref)

        @pl.when((j == 0) & (k == 0))
        def _init_cnt():
            cnt_ref[:] = jnp.zeros_like(cnt_ref)

        # egress[b, d] += sum_t tmatch_e[t, src b] * tallow_e[t, dst d].
        # Guarded per direction: for k >= n_k_dir the clamped index maps
        # REFETCH the direction's last real chunk (not zeros), so the
        # accumulate must be skipped, not relied on to be a no-op; and an
        # all-zero tmatch block contributes nothing, so its matmul is
        # skipped by content (nz map).
        acc_dt = acc_e_ref.dtype  # int32 for int8 operands, f32 for bf16

        @pl.when((k < n_k_e) & (nz_e_ref[i * n_k_e + jnp.minimum(k, n_k_e - 1)] > 0))
        def _acc_egress():
            acc_e_ref[:] += jnp.dot(
                a_e_ref[:], b_e_ref[0], preferred_element_type=acc_dt
            )

        # ingress[b, d] += sum_t tallow_i[t, src b] * tmatch_i[t, dst d]
        @pl.when((k < n_k_i) & (nz_i_ref[jnp.minimum(k, n_k_i - 1) * n_j + j] > 0))
        def _acc_ingress():
            acc_i_ref[:] += jax.lax.dot_general(
                b_i_ref[0],
                a_i_ref[:],
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=acc_dt,
            )

        @pl.when(k == n_k - 1)
        def _epilogue():
            # The no-matching-target => allow rule and the pod validity
            # mask are FOLDED INTO THE MATMUL as one pseudo-target row per
            # direction (see verdict_counts_pallas): acc > 0 IS the final
            # verdict, and invalid (padded) pods produce all-False rows/
            # columns, so the counts need no masking.  This epilogue runs
            # for every (src, dst) tile pair — at multi-million-pod scale
            # its per-cell VPU work, not the MXU matmuls, is the kernel
            # floor, so every fused op here was measured to matter.  (A
            # variant that rode the count reductions on the MXU as thin
            # ones-vector f32 contractions measured ~10% SLOWER at the
            # 100k bench — thin f32 matmuls underutilize the systolic
            # array more than the VPU tree-reduce costs.)
            zero = jnp.array(0, acc_dt)
            egress = acc_e_ref[:] > zero
            ingress = acc_i_ref[:] > zero
            combined = egress & ingress
            c_in = jnp.sum(ingress.astype(jnp.int32))
            c_eg = jnp.sum(egress.astype(jnp.int32))
            c_co = jnp.sum(combined.astype(jnp.int32))
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
            cnt_ref[:] += (
                jnp.where(lane == 0, c_in, 0)
                + jnp.where(lane == 1, c_eg, 0)
                + jnp.where(lane == 2, c_co, 0)
            )
            # flush to this (q, i)'s row of the count plane once per src-tile
            # (the dynamic-row store is the expensive part)
            @pl.when(j == n_j - 1)
            def _flush():
                counts_ref[:, pl.ds(i, 1), :] = cnt_ref[:].reshape(1, 1, 128)

    return _verdict_counts_kernel


def _make_verdict_counts_kernel_1chunk():
    """Kernel body for the SINGLE T-chunk case (n_k_e == n_k_i == 1),
    which is the common regime after dead-target compaction: both
    directions' live targets fit one lane-aligned chunk (<= 1024), so
    there is nothing to accumulate across k.  The general kernel pays,
    per grid step: two scratch zero-inits, two matmul accumulations into
    VMEM scratch, and an epilogue that re-reads both scratch tiles —
    ~8 MB of VMEM round-trips per step that this body skips entirely by
    keeping the matmul results in registers straight into the count
    epilogue.  The nz/redir skip machinery is also dropped: the
    pseudo-target row lives in the (only) chunk, so no block is ever
    all-zero."""
    ti.KERNEL_TRACES.inc(kernel="counts_1chunk")

    def _verdict_counts_kernel_1chunk(
        a_e_ref,  # [BS, KT] bf16   tmatch_e^T src block
        b_e_ref,  # [1, KT, BD] bf16  tallow_e (q, dst block j)
        b_i_ref,  # [1, KT, BS] bf16  tallow_i (q, src block i)
        a_i_ref,  # [KT, BD] bf16   tmatch_i (dst block j)
        counts_ref,  # [1, n_i, 128] int32 per-q count plane
        cnt_ref,  # [1, 128] int32 scratch: running counts for this (q, i)
    ):
        i = pl.program_id(1)
        j = pl.program_id(2)
        n_j = pl.num_programs(2)

        @pl.when((i == 0) & (j == 0))
        def _init_counts():
            counts_ref[:] = jnp.zeros_like(counts_ref)

        @pl.when(j == 0)
        def _init_cnt():
            cnt_ref[:] = jnp.zeros_like(cnt_ref)

        acc_dt = jnp.int32 if a_e_ref.dtype == jnp.int8 else jnp.float32
        acc_e = jnp.dot(
            a_e_ref[:], b_e_ref[0], preferred_element_type=acc_dt
        )
        acc_i = jax.lax.dot_general(
            b_i_ref[0],
            a_i_ref[:],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=acc_dt,
        )
        zero = jnp.array(0, acc_dt)
        egress = acc_e > zero
        ingress = acc_i > zero
        combined = egress & ingress
        c_in = jnp.sum(ingress.astype(jnp.int32))
        c_eg = jnp.sum(egress.astype(jnp.int32))
        c_co = jnp.sum(combined.astype(jnp.int32))
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        cnt_ref[:] += (
            jnp.where(lane == 0, c_in, 0)
            + jnp.where(lane == 1, c_eg, 0)
            + jnp.where(lane == 2, c_co, 0)
        )

        @pl.when(j == n_j - 1)
        def _flush():
            counts_ref[:, pl.ds(i, 1), :] = cnt_ref[:].reshape(1, 1, 128)

    return _verdict_counts_kernel_1chunk


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    """Zero-pad `axis` up to a multiple of `mult` — at least one full
    chunk, so a zero-size axis (e.g. a direction with no targets) still
    yields a valid block (all-zero = matches nothing, allows nothing)."""
    n = x.shape[axis]
    pad = mult if n == 0 else (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _resolve_operand_dtype(operand_dtype: str | None) -> str:
    """CYCLONUS_PALLAS_DTYPE, resolved OUTSIDE the jitted kernels and
    passed in as a static argument: the module-level jit caches are
    keyed on shapes plus statics, so for DIRECT calls to the public
    wrappers an env flip after a shape has been traced triggers a
    retrace instead of being silently ignored (previously the env var
    was read at trace time inside the jit).  Scope: the engine-level
    programs (api._build_counts_jits, tiled's shard_map bodies) wrap
    these calls in their own outer jits and therefore still bake the
    dtype in at THEIR trace time — an engine keeps the operand dtype it
    was built with."""
    if operand_dtype is None:
        operand_dtype = os.environ.get("CYCLONUS_PALLAS_DTYPE", "int8")
    if operand_dtype not in ("int8", "bf16"):
        raise ValueError(
            f"CYCLONUS_PALLAS_DTYPE must be int8 or bf16, got {operand_dtype!r}"
        )
    return operand_dtype


def verdict_counts_pallas(
    tmatch_e: jnp.ndarray,  # [T_e, N] bool
    has_e: jnp.ndarray,  # [N] bool
    tallow_e: jnp.ndarray,  # [T_e, N, Q] bf16 (0/1)
    tmatch_i: jnp.ndarray,  # [T_i, N] bool
    has_i: jnp.ndarray,  # [N] bool
    tallow_i: jnp.ndarray,  # [T_i, N, Q] bf16 (0/1)
    n_pods: int | jnp.ndarray = None,
    interpret: bool = False,
    operand_dtype: str = None,
) -> jnp.ndarray:
    """Square (src pods == dst pods) form of verdict_counts_pallas_rect:
    the single-chip counts path.  See the rect docstring for the kernel
    contract."""
    return _verdict_counts_pallas_square(
        tmatch_e, has_e, tallow_e, tmatch_i, has_i, tallow_i,
        n_pods=n_pods if n_pods is not None else tmatch_e.shape[1],
        interpret=interpret,
        operand_dtype=_resolve_operand_dtype(operand_dtype),
    )


@partial(jax.jit, static_argnames=("interpret", "operand_dtype"))
def _verdict_counts_pallas_square(
    tmatch_e, has_e, tallow_e, tmatch_i, has_i, tallow_i,
    n_pods, interpret, operand_dtype,
):
    n = tmatch_e.shape[1]
    valid = jnp.arange(n) < n_pods  # [N] bool
    return _verdict_counts_pallas_rect(
        tmatch_e, has_e, tallow_e, tmatch_i, has_i, tallow_i,
        valid_src=valid, valid_dst=valid, interpret=interpret,
        operand_dtype=operand_dtype,
    )


def verdict_counts_pallas_rect(
    tmatch_e: jnp.ndarray,
    has_e: jnp.ndarray,
    tallow_e: jnp.ndarray,
    tmatch_i: jnp.ndarray,
    has_i: jnp.ndarray,
    tallow_i: jnp.ndarray,
    valid_src: jnp.ndarray = None,
    valid_dst: jnp.ndarray = None,
    interpret: bool = False,
    operand_dtype: str = None,
) -> jnp.ndarray:
    """Public rect entry: resolves the operand dtype eagerly (env or
    argument) and dispatches to the jitted implementation with it as a
    static argument.  See _verdict_counts_pallas_rect for the contract."""
    return _verdict_counts_pallas_rect(
        tmatch_e, has_e, tallow_e, tmatch_i, has_i, tallow_i,
        valid_src=valid_src, valid_dst=valid_dst, interpret=interpret,
        operand_dtype=_resolve_operand_dtype(operand_dtype),
    )


@partial(jax.jit, static_argnames=("interpret", "operand_dtype"))
def _verdict_counts_pallas_rect(
    tmatch_e: jnp.ndarray,  # [T_e, Ns] bool — egress targets vs SRC pods
    has_e: jnp.ndarray,  # [Ns] bool — src pod has an egress target
    tallow_e: jnp.ndarray,  # [T_e, Nd, Q] bf16 (0/1) — egress allows DST
    tmatch_i: jnp.ndarray,  # [T_i, Nd] bool — ingress targets vs DST pods
    has_i: jnp.ndarray,  # [Nd] bool — dst pod has an ingress target
    tallow_i: jnp.ndarray,  # [T_i, Ns, Q] bf16 (0/1) — ingress allows SRC
    valid_src: jnp.ndarray = None,  # [Ns] bool
    valid_dst: jnp.ndarray = None,  # [Nd] bool
    interpret: bool = False,
    operand_dtype: str = "int8",
) -> jnp.ndarray:
    """[Q, n_src_tiles, 3] int32 partial allow counts (ingress, egress,
    combined) over the Ns x Nd x Q grid, without materializing any
    verdict tensor.  Partials are per (port case, src tile) so each stays
    below 2^31; sum them in int64 on the host.

    RECTANGULAR: the src and dst pod axes are independent, which is what
    lets the mesh paths run this kernel per device (src = the device's
    row shard, dst = the full axis or the rotating ring shard).  Validity
    comes in as per-side masks because a shard's rows are a window of the
    global pod axis, not a prefix.

    The allow-if-no-matching-target rule (reference policy.go:158-160)
    and the pod-validity mask are folded into the contraction as ONE
    PSEUDO-TARGET ROW per direction: the pseudo target "matches" exactly
    the valid pods with no real target and "allows" exactly the valid
    pods, so `acc > 0` is the complete verdict and invalid pods come out
    all-False with no per-cell mask arithmetic.  That keeps the per-tile
    epilogue — the VPU-bound floor of this kernel at large N — to two
    compares, one AND, and three reductions.

    Operands ride the MXU as INT8 with int32 accumulation by default:
    exact for 0/1 values, double the bf16 MACs/s on v5e, and half the
    HBM/VMEM per block (bench: 0.27 -> 0.19 s at 100k x 10k, verified
    bit-identical vs bf16 and numpy).  CYCLONUS_PALLAS_DTYPE=bf16
    (resolved by the public wrappers, static here) restores the float
    path."""
    # trace-time side effect on purpose: each increment is one program
    # trace = one compile-cache miss at the jit level (the persistent
    # XLA cache may still serve the binary); dispatches - traces = hits
    ti.KERNEL_TRACES.inc(kernel="counts_rect")
    od = jnp.bfloat16 if operand_dtype == "bf16" else jnp.int8
    ns = tmatch_e.shape[1]
    nd = tmatch_i.shape[1]
    q = tallow_e.shape[2]
    if valid_src is None:
        valid_src = jnp.ones(ns, dtype=bool)
    if valid_dst is None:
        valid_dst = jnp.ones(nd, dtype=bool)

    def _augment(tmatch, has, tallow_qtn, valid_match, valid_allow):
        """Append the pseudo-target row (matches valid no-target pods on
        the MATCH side, allows valid pods on the ALLOW side) and zero the
        invalid-pod columns of BOTH operands: kind-ALL / 0.0.0.0-0 peers
        match EVERY pod including the inert pads the pod axis arrives
        with (shape bucketing pads before the precompute), and an
        unmasked pad column would count as allowed.  tmatch needs the
        mask too — pads match no target, but an arbitrary validity mask
        (the rect contract) may invalidate a REAL pod that a real target
        matches, and that pod's rows must come out all-False, not just
        its columns."""
        va = valid_allow.astype(od)
        vm = valid_match.astype(od)
        pseudo_match = ((~has) & valid_match).astype(od)[None, :]
        tmatch = jnp.concatenate(
            [tmatch.astype(od) * vm[None, :], pseudo_match], axis=0
        )
        tallow_qtn = tallow_qtn * va[None, None, :]
        valid_q = jnp.broadcast_to(va[None, None, :], (q, 1, va.shape[0]))
        tallow_qtn = jnp.concatenate([tallow_qtn, valid_q], axis=1)
        return tmatch, tallow_qtn

    tm_e, tl_e = _augment(
        tmatch_e, has_e, jnp.moveaxis(tallow_e, 2, 0).astype(od),
        valid_src, valid_dst,
    )
    tm_i, tl_i = _augment(
        tmatch_i, has_i, jnp.moveaxis(tallow_i, 2, 0).astype(od),
        valid_dst, valid_src,
    )
    kt_e = _kt_for(tm_e.shape[0])  # tile: 128
    kt_i = _kt_for(tm_i.shape[0])  # tile: 128
    single_chunk = kt_e >= tm_e.shape[0] and kt_i >= tm_i.shape[0]
    bs, bd = _tiles_for(
        kt_e, kt_i, ns,
        single_chunk_int8=single_chunk and od == jnp.int8,
        n_dst=nd,
    )
    # each axis pads to ITS tile size; the per-axis operand PAIRS pad
    # identically (a_e + tl_i share the src axis, b_e + a_i the dst
    # axis), so no view can drop trailing rows of the other
    a_e = _pad_to(_pad_to(tm_e, 0, kt_e), 1, bs).T  # [Ns', T_e']
    a_i = _pad_to(_pad_to(tm_i, 0, kt_i), 1, bd)  # [T_i', Nd']
    b_e = _pad_to(_pad_to(tl_e, 1, kt_e), 2, bd)  # [Q, T_e', Nd']
    b_i = _pad_to(_pad_to(tl_i, 1, kt_i), 2, bs)  # [Q, T_i', Ns']

    ns_pad = a_e.shape[0]
    nd_pad = a_i.shape[1]
    # the k grid dimension is shared, but each direction only has its OWN
    # padded T-chunk count of real work: the kernel skips the other
    # direction's matmul past its n_k (saving the MXU time), and the
    # clamped index maps below keep the block fetch in bounds without
    # padding the shorter direction up (saving the HBM space + DMA)
    n_k_e = b_e.shape[1] // kt_e
    n_k_i = b_i.shape[1] // kt_i

    n_i = ns_pad // bs
    # per-(q, src-tile) partial counts stay within int32: bs * nd_pad
    # allowed cells max per block (raise, not assert — this runtime size
    # guard must survive python -O)
    if bs * nd_pad >= 2**31:
        raise ValueError(
            f"dst axis {nd_pad} too large for int32 tile counts at bs={bs}"
        )
    n_j = nd_pad // bd
    if n_k_e == 1 and n_k_i == 1:
        # single-T-chunk fast path: no cross-k accumulation, so skip the
        # scratch accumulators and the nz/redir skip machinery entirely
        counts = pl.pallas_call(
            _make_verdict_counts_kernel_1chunk(),
            grid=(q, n_i, n_j),
            in_specs=[
                pl.BlockSpec((bs, kt_e), lambda q, i, j: (i, 0)),
                pl.BlockSpec((1, kt_e, bd), lambda q, i, j: (q, 0, j)),
                pl.BlockSpec((1, kt_i, bs), lambda q, i, j: (q, 0, i)),
                pl.BlockSpec((kt_i, bd), lambda q, i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((1, n_i, 128), lambda q, i, j: (q, 0, 0)),
            scratch_shapes=[pltpu.VMEM((1, 128), jnp.int32)],
            out_shape=jax.ShapeDtypeStruct((q, n_i, 128), jnp.int32),
            cost_estimate=pl.CostEstimate(
                flops=2 * q * ns_pad * nd_pad * (kt_e + kt_i),
                bytes_accessed=2 * q * n_i * nd_pad * (kt_e + kt_i),
                transcendentals=0,
            ),
            interpret=interpret,
        )(a_e, b_e, b_i, a_i)
        return counts[:, :, :3]
    grid = (q, n_i, n_j, max(n_k_e, n_k_i))
    # content maps for the scalar-prefetch skip: which (pod-tile, T-chunk)
    # tmatch blocks hold any nonzero.  O(N*T) device reduction — noise
    # next to the O(N^2 T) matmuls it lets the kernel skip.
    nz_e_mat = (a_e.reshape(n_i, bs, n_k_e, kt_e) != 0).any(axis=(1, 3))  # [n_i, n_k_e]
    nz_i_mat = (a_i.reshape(n_k_i, kt_i, n_j, bd) != 0).any(axis=(1, 3))  # [n_k_i, n_j]

    # DMA-reuse redirects: for a skipped chunk, point every operand's
    # index map at the last USED chunk, so the pallas pipeline sees an
    # unchanged index and fetches nothing (the data is never read — the
    # matmul for that step is skipped by the nz guard).  Without this
    # the skip saves MXU time but the kernel stays HBM-bound fetching
    # blocks it will ignore.
    def _redir(nz, axis):
        n = nz.shape[axis]
        ar = jnp.arange(n, dtype=jnp.int32)
        idx = jnp.where(nz, ar[:, None] if axis == 0 else ar[None, :], -1)
        return jnp.maximum(jax.lax.cummax(idx, axis=axis), 0)

    redir_e = _redir(nz_e_mat, axis=1)  # [n_i, n_k_e]
    redir_i = _redir(nz_i_mat, axis=0)  # [n_k_i, n_j]

    nz_e = nz_e_mat.reshape(-1).astype(jnp.int32)
    nz_i = nz_i_mat.reshape(-1).astype(jnp.int32)
    redir_e = redir_e.reshape(-1)
    redir_i = redir_i.reshape(-1)

    acc_dt = jnp.int32 if od == jnp.int8 else jnp.float32
    clamp_e = lambda k: jnp.minimum(k, n_k_e - 1)
    clamp_i = lambda k: jnp.minimum(k, n_k_i - 1)
    re_ = lambda i, k, redir_e_ref: redir_e_ref[i * n_k_e + clamp_e(k)]
    ri_ = lambda j, k, redir_i_ref: redir_i_ref[clamp_i(k) * n_j + j]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (bs, kt_e), lambda q, i, j, k, ne, ni, re, ri: (i, re_(i, k, re))
            ),
            pl.BlockSpec(
                (1, kt_e, bd),
                lambda q, i, j, k, ne, ni, re, ri: (q, re_(i, k, re), j),
            ),
            pl.BlockSpec(
                (1, kt_i, bs),
                lambda q, i, j, k, ne, ni, re, ri: (q, ri_(j, k, ri), i),
            ),
            pl.BlockSpec(
                (kt_i, bd), lambda q, i, j, k, ne, ni, re, ri: (ri_(j, k, ri), j)
            ),
        ],
        out_specs=pl.BlockSpec((1, n_i, 128), lambda q, i, j, k, *_: (q, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bs, bd), acc_dt),
            pltpu.VMEM((bs, bd), acc_dt),
            pltpu.VMEM((1, 128), jnp.int32),
        ],
    )
    counts = pl.pallas_call(
        _make_verdict_counts_kernel(n_k_e, n_k_i),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q, n_i, 128), jnp.int32),
        # deliberate WORST-CASE (dense) cost: the nz-skip fraction is
        # runtime data, and CostEstimate must be static — an upper bound
        # keeps the scheduler conservative rather than starving the
        # pipeline on the dense-tmatch (unsorted/adversarial) case
        cost_estimate=pl.CostEstimate(
            flops=2 * q * ns_pad * nd_pad * (n_k_e * kt_e + n_k_i * kt_i),
            bytes_accessed=2
            * q
            * n_i
            * nd_pad
            * (n_k_e * kt_e + n_k_i * kt_i),
            transcendentals=0,
        ),
        interpret=interpret,
    )(nz_e, nz_i, redir_e, redir_i, a_e, b_e, b_i, a_i)
    # [Q, n_i, 3] int32 partials; the caller sums them in numpy int64
    # (jnp int64 silently truncates to int32 without jax_enable_x64)
    return counts[:, :, :3]


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


# --- bit-packed kernel (docs/DESIGN.md "Bit-packed kernel") ---------------
#
# The verdict contraction is pure boolean, so the target axis packs
# 32-per-int32-word (encoding.pack_bool_words): any_allow becomes an OR
# over ceil(T/32) word AND steps instead of a depth-T matmul — a 32x cut
# of the contraction depth and a 16x cut of the dominant operand bytes
# vs bf16.  The whole packed depth fits ONE block at any realistic
# target count (W <= 33 words for T <= 1024), so the kernel is always
# single-chunk: word steps unroll statically and the matmul results
# never leave registers before the epilogue.
#
# The contraction here is the popcount-style word form on the VPU — the
# ISSUE's int8 MXU alternative is the existing dense int8 kernel, which
# stays available as the CYCLONUS_PACK=0 dtype plan; the persisted
# autotuner (engine/autotune.py) picks per shape bucket.
#
# FUSED EPILOGUES: the same body optionally resolves the precedence-
# tier lattice (min-key first-match over scalar-prefetched rule keys —
# previously only the XLA tile loop could evaluate tiered counts, with
# the [c, A, B, Q] tier intermediates round-tripping HBM) and/or the
# class-compression gather's dst-weighted row sums (previously a
# separate einsum over materialized verdict blocks).  Everything stays
# in VMEM between the contraction and the reduction.
#
# Layout rule of thumb: SRC-side per-pod operands put pods on the
# SUBLANE axis and the packed-word/rule axis on the LANE axis
# (128-rounded via lane_round_up, shapelint SC004); DST-side operands
# put pods on the LANE axis.  Both slice [.., w:w+1] / [w:w+1, ..]
# with STATIC w, so no dynamic relayouts reach Mosaic.  Per-side has/
# valid flags ride ONE extra int32 word appended past the packed depth
# (bit 0 = has_target, bit 1 = valid); the matching position of the
# OTHER operand is structural zero padding, so the contraction loop —
# which unrolls only the real words — never sees them.

#: packed-kernel default tile heights (src x dst); the persisted
#: autotuner searches over _PACKED_TILE_CANDIDATES per shape bucket
PACKED_BS = 512
PACKED_BD = 512

#: the packed tile search space (engine/autotune.py candidates): every
#: entry is bounded by the int32 partial-count rule bs * Nd' < 2^31,
#: re-checked at call time
PACKED_TILE_CANDIDATES = ((512, 512), (1024, 512), (2048, 1024))

#: fused-tier rule-row ceiling: the min-key loop is a rolled fori_loop
#: (one [BS, BD] body whatever the row count), so the ceiling bounds
#: only the per-step loop trips; past it tiered counts route to the XLA
#: tile loop
PACKED_TIER_MAX_ROWS = 1024


#: the packed kernel's word loops unroll statically, one [BS, BD] AND/OR
#: a packed target word a direction, and the TPU compiler's time grows
#: with them (a described v5e, the weighted class kernel over 17,408
#: rows: 6.3 s at 24 words a direction, 26.3 s at 64; k8s-envelope-150k's
#: 516 had not finished after six minutes); past this many words in
#: either direction the class counts route runs the XLA tile loop
PACKED_MAX_WORDS = 64


def _sub8(n: int) -> int:
    """Round up to the int32/f32 sublane tile (8)."""
    return -(-max(int(n), 1) // 8) * 8


#: Mosaic's default scoped-VMEM limit on the v5e; a kernel whose own
#: arithmetic needs more asks for it (CompilerParams.vmem_limit_bytes)
_SCOPED_VMEM_DEFAULT = 16 * 2**20


def _packed_compiler_params(blocks, bs: int, bd: int, tiered: bool):
    """CompilerParams raising the scoped-VMEM limit to what the packed
    kernel at this tile needs, or None when the default holds it.  The
    need is the double-buffered input blocks plus the live [BS, BD]
    int32 planes: two OR-accumulators and two epilogue temporaries,
    and with tiers two carried key planes and two loop temporaries
    more.  The (2048, 1024) tile needs 25.08 MiB by the compiler's own
    count (this bound says 36 MiB); the smaller tiles stay under the
    default and pass no params."""
    planes = 8 if tiered else 4
    need = 2 * sum(4 * math.prod(shape) for shape, _ in blocks) + (
        planes * 4 * bs * bd
    )
    if need <= _SCOPED_VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need)


def _tier_min_keys(src_words, dst_row, anp_ref, banp_ref, rows: int, shape):
    """([BS, BD], [BS, BD]) int32 min ANP / BANP key over the `rows`
    tier rule rows matching each (src, dst) cell — the kernel-local
    twin of kernel.tier_first_match_keys, still a loop over single rule
    rows with rank keys: that one finds the first match a packed word at
    a time and keys it by word, and the lattice reads the same of both
    (`< TIER_KEY_NONE`, `% 4`).  The word form has not been carried in
    here because no measured cell runs this kernel (at most
    PACKED_TIER_MAX_ROWS rows).  The rule axis arrives
    bit-packed 32-per-word on BOTH sides: `src_words` [BS, Wl] holds
    the src pods' scope bits with words on the lane axis; `dst_row(w)`
    loads word w of the dst pods' bits as a [1, BD] row.  One rolled
    loop: a static unroll gives every rule row its own [BS, BD]
    temporaries on Mosaic's scoped-VMEM stack (45 MiB at 64 rows
    against the 16 MiB limit) and compile time grows with it."""
    from .encoding import PACK_BITS, TIER_KEY_NONE

    none = jnp.int32(TIER_KEY_NONE)
    lane = jax.lax.broadcasted_iota(jnp.int32, src_words.shape, 1)

    def body(g, keys):
        anp, banp = keys
        w = g // PACK_BITS
        b = g % PACK_BITS
        # word w of every src pod: a one-hot lane reduce, because a
        # dynamic LANE slice does not lower (the dst side indexes the
        # sublane axis, which does)
        col = jnp.sum(
            jnp.where(lane == w, src_words, 0), axis=1, keepdims=True
        )  # [BS, 1]
        m = (((col >> b) & 1) & ((dst_row(w) >> b) & 1)) != 0  # [BS, BD]
        anp = jnp.minimum(anp, jnp.where(m, anp_ref[g], none))
        banp = jnp.minimum(banp, jnp.where(m, banp_ref[g], none))
        return anp, banp

    init = jnp.full(shape, none, dtype=jnp.int32)
    return jax.lax.fori_loop(0, rows, body, (init, init))


def _make_packed_kernel(
    n_w_e: int, n_w_i: int, g_e: int, g_i: int, tiered: bool, weighted: bool
):
    """Packed single-chunk kernel body, specialized on the per-direction
    word depths, the tier rule-row counts, and the epilogue variant.
    The word loops unroll statically (n_w <= ~33); the tier rule loop
    is rolled (_tier_min_keys)."""
    ti.KERNEL_TRACES.inc(
        kernel="counts_packed"
        + ("_tiered" if tiered else "")
        + ("_weighted" if weighted else "")
    )

    def _kernel(*refs):
        idx = 0
        if tiered:
            anp_e_ref, banp_e_ref, anp_i_ref, banp_i_ref = refs[:4]
            idx = 4
        a_e_ref = refs[idx]  # [BS, We_l] i32 — tmatch_e^T words + flags col
        b_e_ref = refs[idx + 1]  # [1, We_s, BD] i32 — tallow_e words
        b_i_ref = refs[idx + 2]  # [1, BS, Wi_l] i32 — tallow_i^T words
        a_i_ref = refs[idx + 3]  # [Wi_s, BD] i32 — tmatch_i words + flags row
        idx += 4
        if tiered:
            # tier scope bits, rule axis packed 32-per-word
            subj_e_ref = refs[idx]  # [BS, Ge_l] i32
            peerq_e_ref = refs[idx + 1]  # [1, Ge_s, BD] i32
            subj_i_ref = refs[idx + 2]  # [Gi_s, BD] i32
            peerq_i_ref = refs[idx + 3]  # [1, BS, Gi_l] i32
            idx += 4
        if weighted:
            w_ref = refs[idx]  # [8, BD] f32 (row 0 real)
            idx += 1
        out_ref = refs[idx]
        acc_ref = refs[idx + 1]  # weighted: [BS, 128] f32; counts: [1, 128] i32

        i = pl.program_id(1)
        j = pl.program_id(2)
        n_j = pl.num_programs(2)

        if not weighted:
            @pl.when((i == 0) & (j == 0))
            def _init_out():
                out_ref[:] = jnp.zeros_like(out_ref)

        @pl.when(j == 0)
        def _init_acc():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        # word-packed contraction, fully unrolled: the OR-accumulators
        # live in registers straight into the epilogue
        acc_e = a_e_ref[:, 0:1] & b_e_ref[0, 0:1, :]  # [BS, BD] i32
        for w in range(1, n_w_e):
            acc_e = acc_e | (a_e_ref[:, w : w + 1] & b_e_ref[0, w : w + 1, :])
        acc_i = b_i_ref[0, :, 0:1] & a_i_ref[0:1, :]
        for w in range(1, n_w_i):
            acc_i = acc_i | (b_i_ref[0, :, w : w + 1] & a_i_ref[w : w + 1, :])

        # per-side flags ride one extra word past the packed depth
        flags_s = a_e_ref[:, n_w_e : n_w_e + 1]  # [BS, 1] i32
        flags_d = a_i_ref[n_w_i : n_w_i + 1, :]  # [1, BD] i32
        has_s = (flags_s & 1) != 0
        valid_s = (flags_s & 2) != 0
        has_d = (flags_d & 1) != 0
        valid_d = (flags_d & 2) != 0

        egress = (~has_s) | (acc_e != 0)  # [BS, BD]
        ingress = (~has_d) | (acc_i != 0)

        if tiered:
            # fused tier min-key first-match epilogue: the same fold as
            # kernel.tier_first_match_keys, with rule keys read from
            # scalar prefetch and the [g, BS, BD] intermediates never
            # leaving VMEM (the HBM round trip this fusion kills)
            anp_e, banp_e = _tier_min_keys(
                subj_e_ref[:],
                lambda w: peerq_e_ref[0, pl.ds(w, 1), :],
                anp_e_ref, banp_e_ref, g_e, egress.shape,
            )
            egress = resolve_tier_lattice_packed(egress, has_s, anp_e, banp_e)
            # ingress subjects are the DST pods, peers the SRC pods
            anp_i, banp_i = _tier_min_keys(
                peerq_i_ref[0],
                lambda w: subj_i_ref[pl.ds(w, 1), :],
                anp_i_ref, banp_i_ref, g_i, ingress.shape,
            )
            ingress = resolve_tier_lattice_packed(ingress, has_d, anp_i, banp_i)

        combined = egress & ingress

        if weighted:
            # fused class-compression gather epilogue: dst-weighted row
            # sums (tiled._class_tile_rowsums' einsum) computed in VMEM.
            # Full-f32 VPU multiply-accumulate — exact for integer row
            # sums < 2^24, the same bound the split path's HIGHEST-
            # precision einsum holds (pad classes carry weight 0).
            wrow = w_ref[0:1, :]  # [1, BD] f32
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
            rs = (
                jnp.where(
                    lane == 0,
                    jnp.sum(ingress.astype(jnp.float32) * wrow, axis=1,
                            keepdims=True),
                    0.0,
                )
                + jnp.where(
                    lane == 1,
                    jnp.sum(egress.astype(jnp.float32) * wrow, axis=1,
                            keepdims=True),
                    0.0,
                )
                + jnp.where(
                    lane == 2,
                    jnp.sum(combined.astype(jnp.float32) * wrow, axis=1,
                            keepdims=True),
                    0.0,
                )
            )  # [BS, 128]
            acc_ref[:] += rs

            @pl.when(j == n_j - 1)
            def _flush_rs():
                out_ref[:] = acc_ref[:].reshape(1, *acc_ref.shape)
        else:
            mask = valid_s & valid_d
            c_in = jnp.sum((ingress & mask).astype(jnp.int32))
            c_eg = jnp.sum((egress & mask).astype(jnp.int32))
            c_co = jnp.sum((combined & mask).astype(jnp.int32))
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
            acc_ref[:] += (
                jnp.where(lane == 0, c_in, 0)
                + jnp.where(lane == 1, c_eg, 0)
                + jnp.where(lane == 2, c_co, 0)
            )

            @pl.when(j == n_j - 1)
            def _flush():
                out_ref[:, pl.ds(i, 1), :] = acc_ref[:].reshape(1, 1, 128)

    return _kernel


def resolve_tier_lattice_packed(np_allowed, has_b, anp_min, banp_min):
    """The tier lattice fold, kernel-local twin of
    kernel.resolve_tier_lattice (pure jnp, safe inside a Pallas body;
    re-implemented here to keep this module import-light and the
    constants explicit).  Bit-identity with the XLA fold is pinned by
    the fused-vs-split parity tests."""
    from .encoding import (
        TIER_ACT_ALLOW,
        TIER_ACT_NONE,
        TIER_ACT_PASS,
        TIER_KEY_NONE,
    )

    anp_act = jnp.where(anp_min < TIER_KEY_NONE, anp_min % 4, TIER_ACT_NONE)
    banp_act = jnp.where(banp_min < TIER_KEY_NONE, banp_min % 4, TIER_ACT_NONE)
    # boolean algebra, not jnp.where: Mosaic has no select over i1
    # VALUES (it widens them to i8 and cannot truncate back)
    below = (has_b & np_allowed) | (
        ~has_b & ((banp_act == TIER_ACT_NONE) | (banp_act == TIER_ACT_ALLOW))
    )
    defer = (anp_act == TIER_ACT_NONE) | (anp_act == TIER_ACT_PASS)
    return (defer & below) | (~defer & (anp_act == TIER_ACT_ALLOW))


def packed_tier_eligible(tensors: Dict) -> bool:
    """THE host-side gate for the fused tier epilogue — the min-key
    loop unrolls statically over the bucketed rule rows, so an
    adversarial rule count must route to the XLA tile loop instead.
    One implementation on purpose: both the dense counts route
    (api._packed_tier_ok) and the fused class-counts route
    (tiled.evaluate_grid_counts_classes) consult it, so the ceiling
    cannot drift between them.  `tensors` is an engine tensor dict
    (the bucketed tier action slabs carry the row counts)."""
    from .kernel import tier_rows

    return tier_rows(tensors) <= PACKED_TIER_MAX_ROWS


def packed_words_eligible(tensors: Dict) -> bool:
    """The host-side gate on the fused kernel's target words: both
    directions' packed words (encoding.packed_words of the bucketed
    target axis) within PACKED_MAX_WORDS.  The class counts route
    (tiled._class_counts_kernel_choice) consults it beside
    packed_tier_eligible."""
    from .encoding import packed_words

    return all(
        packed_words(int(tensors[d]["target_ns"].shape[0])) <= PACKED_MAX_WORDS
        for d in ("ingress", "egress")
    )


def verdict_counts_pallas_packed(
    tmatch_e_pk: jnp.ndarray,  # [We, Ns] int32 — packed egress tmatch
    has_e: jnp.ndarray,  # [Ns] bool
    tallow_e_pk: jnp.ndarray,  # [We, Nd, Q] int32 — packed egress tallow
    tmatch_i_pk: jnp.ndarray,  # [Wi, Nd] int32
    has_i: jnp.ndarray,  # [Nd] bool
    tallow_i_pk: jnp.ndarray,  # [Wi, Ns, Q] int32
    n_pods: int | jnp.ndarray = None,
    valid_src: jnp.ndarray = None,
    valid_dst: jnp.ndarray = None,
    tier: Dict = None,
    w_dst: jnp.ndarray = None,
    bs: int = None,
    bd: int = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """The packed verdict kernel over pre-packed operands
    (tiled._precompute(pack=True)).

    Returns [Q, n_src_tiles, 3] int32 partial counts, or — with `w_dst`
    (the class-size weights of the fused class-compression gather) —
    [Q, Ns_pad, 3] f32 dst-weighted row sums.  `tier` fuses the
    precedence-tier min-key epilogue ({direction: {subj, peerq,
    anp_key, banp_key}} from the tiled precompute).  RECTANGULAR like
    verdict_counts_pallas_rect: per-side validity masks, so the mesh
    paths run it per device shard.  Semantics mirror the XLA tile body
    exactly (explicit ~has OR and validity-masked counts — no
    pseudo-target row), which is what the packed parity suite pins."""
    ns = tmatch_e_pk.shape[1]
    nd = tmatch_i_pk.shape[1]
    if valid_src is None:
        n32 = ns if n_pods is None else n_pods
        valid_src = jnp.arange(ns) < n32
    if valid_dst is None:
        n32 = nd if n_pods is None else n_pods
        valid_dst = jnp.arange(nd) < n32
    return _verdict_counts_pallas_packed(
        tmatch_e_pk, has_e, tallow_e_pk, tmatch_i_pk, has_i, tallow_i_pk,
        valid_src, valid_dst, tier, w_dst,
        bs=bs if bs is not None else PACKED_BS,
        bd=bd if bd is not None else PACKED_BD,
        interpret=interpret,
    )


@partial(jax.jit, static_argnames=("bs", "bd", "interpret"))
def _verdict_counts_pallas_packed(
    tmatch_e_pk, has_e, tallow_e_pk, tmatch_i_pk, has_i, tallow_i_pk,
    valid_src, valid_dst, tier, w_dst, bs, bd, interpret,
):
    we = tmatch_e_pk.shape[0]
    wi = tmatch_i_pk.shape[0]
    q = tallow_e_pk.shape[2]

    # mask invalid pod columns out of every packed operand (an arbitrary
    # rect validity mask may invalidate REAL pods, and a pad column must
    # contribute nothing to either contraction)
    vs = valid_src[None, :]
    vd = valid_dst[None, :]
    tm_e = jnp.where(vs, tmatch_e_pk, 0)
    tm_i = jnp.where(vd, tmatch_i_pk, 0)
    tl_e = jnp.where(vd[:, :, None], tallow_e_pk, 0)
    tl_i = jnp.where(vs[:, :, None], tallow_i_pk, 0)

    # per-side flags words (bit 0 = has_target, bit 1 = valid)
    flags_s = has_e.astype(jnp.int32) + 2 * valid_src.astype(jnp.int32)
    flags_d = has_i.astype(jnp.int32) + 2 * valid_dst.astype(jnp.int32)

    we_l = lane_round_up(we + 1)  # tile: 128 — flags col at index we
    wi_l = lane_round_up(wi)  # tile: 128
    we_s = _sub8(we)
    wi_s = _sub8(wi + 1)  # flags row at index wi

    a_e = jnp.concatenate([tm_e.T, flags_s[:, None]], axis=1)  # [Ns, We+1]
    a_e = _pad_to(_pad_to(a_e, 1, we_l), 0, bs)  # [Ns', We_l]
    b_e = _pad_to(
        _pad_to(jnp.moveaxis(tl_e, 2, 0), 1, we_s), 2, bd
    )  # [Q, We_s, Nd']
    b_i = _pad_to(
        _pad_to(jnp.transpose(tl_i, (2, 1, 0)), 1, bs), 2, wi_l
    )  # [Q, Ns', Wi_l]
    a_i = jnp.concatenate([tm_i, flags_d[None, :]], axis=0)  # [Wi+1, Nd]
    a_i = _pad_to(_pad_to(a_i, 0, wi_s), 1, bd)  # [Wi_s, Nd']

    ns_pad = a_e.shape[0]
    nd_pad = a_i.shape[1]
    n_i = ns_pad // bs
    n_j = nd_pad // bd
    if bs * nd_pad >= 2**31:
        raise ValueError(
            f"dst axis {nd_pad} too large for int32 tile counts at bs={bs}"
        )

    # structure, not value: jit retraces per pytree structure, so the
    # None checks are static at trace time
    tiered = tier is not None  # jaxlint: ignore[JX002]
    weighted = w_dst is not None  # jaxlint: ignore[JX002]
    g_e = int(tier["egress"]["subj"].shape[0]) if tiered else 0  # jaxlint: ignore[JX002]
    g_i = int(tier["ingress"]["subj"].shape[0]) if tiered else 0  # jaxlint: ignore[JX002]

    # (block shape, plain (q, i, j) index map) pairs; materialized as
    # BlockSpecs per grid-spec flavor below (the prefetch flavor's maps
    # take trailing scalar refs the packed maps ignore)
    operands = [a_e, b_e, b_i, a_i]
    blocks = [
        ((bs, we_l), lambda q, i, j: (i, 0)),
        ((1, we_s, bd), lambda q, i, j: (q, 0, j)),
        ((1, bs, wi_l), lambda q, i, j: (q, i, 0)),
        ((wi_s, bd), lambda q, i, j: (0, j)),
    ]
    prefetch = []
    if tiered:  # jaxlint: ignore[JX002] — static structure branch
        from .encoding import packed_words
        from .kernel import pack_bool_words_jnp

        te, ti_ = tier["egress"], tier["ingress"]
        # the rule axis packs 32-per-word like the target axis: SRC-side
        # bits put the words on the lane axis, DST-side on the sublanes
        ge_l = lane_round_up(packed_words(g_e))  # tile: 128
        ge_s = _sub8(packed_words(g_e))
        gi_l = lane_round_up(packed_words(g_i))  # tile: 128
        gi_s = _sub8(packed_words(g_i))
        subj_e = _pad_to(
            _pad_to(pack_bool_words_jnp(te["subj"] & vs).T, 1, ge_l), 0, bs
        )  # [Ns', Ge_l]
        peerq_e = _pad_to(
            _pad_to(
                jnp.moveaxis(
                    pack_bool_words_jnp(te["peerq"] & vd[:, :, None]), 2, 0
                ),
                1,
                ge_s,
            ),
            2,
            bd,
        )  # [Q, Ge_s, Nd']
        subj_i = _pad_to(
            _pad_to(pack_bool_words_jnp(ti_["subj"] & vd), 0, gi_s), 1, bd
        )  # [Gi_s, Nd']
        peerq_i = _pad_to(
            _pad_to(
                jnp.transpose(
                    pack_bool_words_jnp(ti_["peerq"] & vs[:, :, None]),
                    (2, 1, 0),
                ),
                1,
                bs,
            ),
            2,
            gi_l,
        )  # [Q, Ns', Gi_l]
        operands += [subj_e, peerq_e, subj_i, peerq_i]
        blocks += [
            ((bs, ge_l), lambda q, i, j: (i, 0)),
            ((1, ge_s, bd), lambda q, i, j: (q, 0, j)),
            ((gi_s, bd), lambda q, i, j: (0, j)),
            ((1, bs, gi_l), lambda q, i, j: (q, i, 0)),
        ]
        prefetch = [
            te["anp_key"].astype(jnp.int32),
            te["banp_key"].astype(jnp.int32),
            ti_["anp_key"].astype(jnp.int32),
            ti_["banp_key"].astype(jnp.int32),
        ]
    if weighted:  # jaxlint: ignore[JX002] — static structure branch
        w8 = jnp.zeros((8, nd_pad), dtype=jnp.float32)
        w8 = w8.at[0, : w_dst.shape[0]].set(w_dst.astype(jnp.float32))
        operands.append(w8)
        blocks.append(((8, bd), lambda q, i, j: (0, j)))

    kernel = _make_packed_kernel(we, wi, g_e, g_i, tiered, weighted)
    if weighted:  # jaxlint: ignore[JX002] — static structure branch
        out_block = ((1, bs, 128), lambda q, i, j: (q, i, 0))
        out_shape = jax.ShapeDtypeStruct((q, ns_pad, 128), jnp.float32)
        scratch = [pltpu.VMEM((bs, 128), jnp.float32)]
    else:
        out_block = ((1, n_i, 128), lambda q, i, j: (q, 0, 0))
        out_shape = jax.ShapeDtypeStruct((q, n_i, 128), jnp.int32)
        scratch = [pltpu.VMEM((1, 128), jnp.int32)]
    cost = pl.CostEstimate(
        flops=2 * q * ns_pad * nd_pad * (we + wi + g_e + g_i + 3),
        bytes_accessed=4 * q * n_i * (bs * we_l + nd_pad * (we_s + wi_s))
        + 4 * q * n_i * bs * wi_l,
        transcendentals=0,
    )
    params = _packed_compiler_params(blocks, bs, bd, tiered)
    if tiered:  # jaxlint: ignore[JX002] — static structure branch

        def _with_prefetch(m):
            # grid indices first, then one ref per scalar-prefetch
            # operand (ignored by the packed maps)
            return lambda q, i, j, *refs, _m=m: _m(q, i, j)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(q, n_i, n_j),
            in_specs=[
                pl.BlockSpec(shape, _with_prefetch(m)) for shape, m in blocks
            ],
            out_specs=pl.BlockSpec(out_block[0], _with_prefetch(out_block[1])),
            scratch_shapes=scratch,
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            cost_estimate=cost,
            compiler_params=params,
            interpret=interpret,
        )(*prefetch, *operands)
    else:
        out = pl.pallas_call(
            kernel,
            grid=(q, n_i, n_j),
            in_specs=[pl.BlockSpec(shape, m) for shape, m in blocks],
            out_specs=pl.BlockSpec(*out_block),
            scratch_shapes=scratch,
            out_shape=out_shape,
            cost_estimate=cost,
            compiler_params=params,
            interpret=interpret,
        )(*operands)
    return out[:, :, :3]


# --- per-tile target slabs -------------------------------------------------
#
# The single-chunk kernel contracts EVERY tile pair over the full live
# target depth (kt_e + kt_i, ~640 at the 100k x 10k bench), but with
# pods and targets namespace-sorted a 2048-pod src tile only ever
# matches a narrow contiguous band of targets (~5-10 rows at the bench
# shape: a target applies to pods of exactly one namespace,
# kernel.direction_precompute).  The slab path gathers, per pod tile,
# one fixed-width window (SLAB_W rows) of the target axis covering that
# band — for BOTH directions — so the per-step contraction depth drops
# from kt_e + kt_i to 2 * SLAB_W regardless of the policy count.  The
# no-matching-target rule and the validity mask cannot ride the matmul
# anymore (the pseudo row lives outside most windows), so they move to
# the epilogue as two VPU OR-terms per direction, fed by four small
# per-tile vectors.
#
# Eligibility is decided HOST-side (slab_windows on a numpy tmatch
# twin): every tile's nonzero target rows must fit one SLAB_W window.
# Ns-sorted clusters qualify overwhelmingly; anything else falls back
# to the single/multi-chunk kernels.  r3 measured a 256-aligned
# windowing of the INGRESS direction only at ~10-15% — consistent with
# depth 640 -> 512; this path targets depth -> 256.

SLAB_W = 128
SLAB_BS = 2048
SLAB_BD = 1024


def slab_w_aug(operand_dtype: str = None, w: int = None) -> int:
    """Augmented window depth the slab kernel actually materializes:
    the w-row window + the pseudo/validity OR-term row, ROUNDED UP to
    the operand dtype's native sublane tile (int8: 32, bf16: 16).  The
    ceil keeps the alignment property for ARBITRARY w overrides (the
    old `w + tile` form only aligned when w itself was tile-aligned);
    for tile-aligned w the two forms agree, so the default layout is
    unchanged.  The ONE source of truth — the engine's HBM budget
    (api._slab_plan) must use this, not re-derive it."""
    if w is None:
        w = SLAB_W
    od = _resolve_operand_dtype(operand_dtype)
    tile = 32 if od == "int8" else 16
    return -(-(w + 1) // tile) * tile


def slab_windows(tmatch: "np.ndarray", tile: int, w: int = SLAB_W):
    """Per-tile target-window starts from a HOST (numpy, valid-masked)
    tmatch [T, N]: returns (t0 [n_tiles] int32, ok).  ok is False when
    any tile's nonzero rows span more than w — the caller must then use
    the non-slab kernels.  Empty tiles get t0 = 0 (their tmatch slab is
    all zero, so the window content is irrelevant)."""
    import numpy as np

    t, n = tmatch.shape
    n_tiles = -(-n // tile) if n else 0
    if n_tiles == 0 or t == 0:
        return np.zeros(max(n_tiles, 1), dtype=np.int32), True
    pad = n_tiles * tile - n
    if pad:
        tmatch = np.pad(tmatch, ((0, 0), (0, pad)))
    nz = tmatch.reshape(t, n_tiles, tile).any(axis=2)  # [T, n_tiles]
    any_t = nz.any(axis=0)
    first = np.where(any_t, nz.argmax(axis=0), 0).astype(np.int32)
    last = np.where(any_t, t - 1 - nz[::-1].argmax(axis=0), -1)
    ok = bool(((last - first) < w).all())
    return first, ok


def _make_verdict_counts_kernel_slab():
    """Kernel body for the slab path: one matmul per direction over the
    tile's augmented target window (values straight into the count
    epilogue, exactly like the 1chunk kernel).  The pseudo/validity
    OR-terms ride INSIDE the window as one augmented row per direction
    (appended at gather time by _verdict_counts_pallas_slab), so
    `acc > 0` is the complete verdict.  An epilogue formulation was
    tried and does not survive Mosaic: i1 minor-dim inserts
    (`pe[:, None]`) are unsupported, 1-D int32 relayouts crash layout
    inference, and rank-1 dot_general OR-terms blow the 16 MB scoped
    VMEM stack at the (2048, 1024) tile."""
    ti.KERNEL_TRACES.inc(kernel="counts_slab")

    def _kernel(
        a_e_ref,  # [1, Wa, BS] od — tmatch_e window+pseudo row, src tile i
        b_e_ref,  # [1, 1, Wa, BD] od — tallow_e window+valid row (q, i, j)
        b_i_ref,  # [1, 1, Wa, BS] od — tallow_i window+valid row (q, j, i)
        a_i_ref,  # [1, Wa, BD] od — tmatch_i window+pseudo row, dst tile j
        counts_ref,  # [1, n_i, 128] int32 per-q count plane
        cnt_ref,  # [1, 128] int32 scratch
    ):
        i = pl.program_id(1)
        j = pl.program_id(2)
        n_j = pl.num_programs(2)

        @pl.when((i == 0) & (j == 0))
        def _init_counts():
            counts_ref[:] = jnp.zeros_like(counts_ref)

        @pl.when(j == 0)
        def _init_cnt():
            cnt_ref[:] = jnp.zeros_like(cnt_ref)

        acc_dt = jnp.int32 if a_e_ref.dtype == jnp.int8 else jnp.float32
        # egress[s, d] = sum_w tmatch_e[w, s] * tallow_e[w, d]
        acc_e = jax.lax.dot_general(
            a_e_ref[0],
            b_e_ref[0, 0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=acc_dt,
        )
        # ingress[s, d] = sum_w tallow_i[w, s] * tmatch_i[w, d]
        acc_i = jax.lax.dot_general(
            b_i_ref[0, 0],
            a_i_ref[0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=acc_dt,
        )
        zero = jnp.array(0, acc_dt)
        egress = acc_e > zero
        ingress = acc_i > zero
        combined = egress & ingress
        c_in = jnp.sum(ingress.astype(jnp.int32))
        c_eg = jnp.sum(egress.astype(jnp.int32))
        c_co = jnp.sum(combined.astype(jnp.int32))
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        cnt_ref[:] += (
            jnp.where(lane == 0, c_in, 0)
            + jnp.where(lane == 1, c_eg, 0)
            + jnp.where(lane == 2, c_co, 0)
        )

        @pl.when(j == n_j - 1)
        def _flush():
            counts_ref[:, pl.ds(i, 1), :] = cnt_ref[:].reshape(1, 1, 128)

    return _kernel


def verdict_counts_pallas_slab(
    tmatch_e: jnp.ndarray,  # [T_e, N] bool
    has_e: jnp.ndarray,  # [N] bool
    tallow_e: jnp.ndarray,  # [T_e, N, Q] bf16 (0/1)
    tmatch_i: jnp.ndarray,  # [T_i, N] bool
    has_i: jnp.ndarray,  # [N] bool
    tallow_i: jnp.ndarray,  # [T_i, N, Q] bf16 (0/1)
    t0_e: jnp.ndarray,  # [n_i] int32 window starts (host: slab_windows)
    t0_i: jnp.ndarray,  # [n_j] int32
    n_pods: int | jnp.ndarray,
    interpret: bool = False,
    operand_dtype: str = None,
    bs: int = None,
    bd: int = None,
    w: int = None,
) -> jnp.ndarray:
    """[Q, n_i, 3] int32 partial counts via per-tile target slabs.  The
    caller guarantees (via slab_windows on the SAME valid-masked tmatch,
    with the SAME w) that every tile's nonzero target rows fit its w
    window; violations silently undercount, which is why eligibility is
    checked host-side with the identical reduction.  All three layout
    defaults resolve from the module globals at CALL time so a runtime
    override (tests monkeypatch them) can never desynchronize the host
    check from the kernel's actual window.

    Design note: the slabs are MATERIALIZED per-tile gathers — [q,
    n_tiles, w_aug, N] in HBM — which caps this path at ~150k pods (the
    caller gates on the byte estimate).  This composed form rebuilds
    them per dispatch; steady-state callers should build them once with
    slab_operands and dispatch verdict_counts_pallas_slab_from_ops
    (rebuild cost vs the depth cut's savings: not measured on the
    current machine).
    The alternative (scalar-prefetch block maps into the original
    arrays, like the general kernel's nz redirects) avoids the copies
    and the cap, but block index maps are w-ALIGNED, so covering an
    arbitrary <=w/2-wide span needs a 2-block window — doubling the
    contraction depth and giving back most of the win at the 100k bench
    shape."""
    return _verdict_counts_pallas_slab(
        tmatch_e, has_e, tallow_e, tmatch_i, has_i, tallow_i,
        t0_e, t0_i, n_pods,
        interpret=interpret,
        operand_dtype=_resolve_operand_dtype(operand_dtype),
        bs=bs if bs is not None else SLAB_BS,
        bd=bd if bd is not None else SLAB_BD,
        w=w if w is not None else SLAB_W,
    )


def slab_operands(
    tmatch_e, has_e, tallow_e, tmatch_i, has_i, tallow_i,
    t0_e, t0_i, n_pods, operand_dtype=None, bs=None, bd=None, w=None,
):
    """The slab path's gathered operands — {a_e, b_e, b_i, a_i} — as a
    SEPARATE traceable stage: they depend only on the precompute and the
    (fixed) window starts, so a steady-state caller can materialize them
    ONCE and cache them device-resident next to the precompute instead
    of rebuilding them per dispatch (the original fused form)."""
    return _slab_operands(
        tmatch_e, has_e, tallow_e, tmatch_i, has_i, tallow_i,
        t0_e, t0_i, n_pods,
        operand_dtype=_resolve_operand_dtype(operand_dtype),
        bs=bs if bs is not None else SLAB_BS,
        bd=bd if bd is not None else SLAB_BD,
        w=w if w is not None else SLAB_W,
    )


@partial(
    jax.jit, static_argnames=("operand_dtype", "bs", "bd", "w")
)
def _slab_operands(
    tmatch_e, has_e, tallow_e, tmatch_i, has_i, tallow_i,
    t0_e, t0_i, n_pods, operand_dtype, bs, bd, w,
):
    od = jnp.bfloat16 if operand_dtype == "bf16" else jnp.int8
    n = tmatch_e.shape[1]
    q = tallow_e.shape[2]
    valid = (jnp.arange(n) < n_pods).astype(od)  # [N]

    ns_pad = -(-max(n, 1) // bs) * bs
    nd_pad = -(-max(n, 1) // bd) * bd
    n_i, n_j = ns_pad // bs, nd_pad // bd
    if bs * nd_pad >= 2**31:
        raise ValueError(
            f"dst axis {nd_pad} too large for int32 tile counts at bs={bs}"
        )

    def prep(tmatch, tallow, valid_match, valid_allow, n_pad_match, n_pad_allow):
        """Valid-masked, od-cast, pod-padded operands plus a w-padded
        target axis so every dynamic window slice is in bounds."""
        tm = tmatch.astype(od) * valid_match[None, :]
        tl = jnp.moveaxis(tallow, 2, 0).astype(od) * valid_allow[None, None, :]
        tm = _pad_to(_pad_to(tm, 0, 1), 1, n_pad_match)  # pod pad
        tl = _pad_to(tl, 2, n_pad_allow)
        # target-axis guard: append w zero rows (zero targets match and
        # allow nothing, so an empty tile's window reads only zeros)
        tm = jnp.pad(tm, ((0, w), (0, 0)))
        tl = jnp.pad(tl, ((0, 0), (0, w), (0, 0)))
        return tm, tl

    tm_e, tl_e = prep(tmatch_e, tallow_e, valid, valid, bs, bd)
    tm_i, tl_i = prep(tmatch_i, tallow_i, valid, valid, bd, bs)
    t_e_pad = tm_e.shape[0]
    t_i_pad = tm_i.shape[0]
    t0_e = jnp.clip(t0_e.astype(jnp.int32), 0, t_e_pad - w)
    t0_i = jnp.clip(t0_i.astype(jnp.int32), 0, t_i_pad - w)

    # Augmented window depth: one extra row carries the pseudo/validity
    # OR-term per direction (the kernel is then pure matmul + compare,
    # mirroring the proven 1chunk body), padded to the dtype's native
    # sublane tile so every block fetch stays aligned.
    w_aug = slab_w_aug(operand_dtype, w)

    # slab gathers (per-eval; cacheable with the precompute when the
    # engine's device-resident pre-cache holds)
    def gather_tm(tm, t0, tile, count, pseudo):
        """[count, w_aug, tile]: the w-row window, then the pseudo row
        for this tile's pod columns, then alignment zeros."""

        def one(i, t0i):
            return jax.lax.dynamic_slice(tm, (t0i, i * tile), (w, tile))

        win = jax.vmap(one)(jnp.arange(count), t0)  # [count, w, tile]
        aug = pseudo.reshape(count, 1, tile)
        pad = jnp.zeros((count, w_aug - w - 1, tile), dtype=win.dtype)
        return jnp.concatenate([win, aug, pad], axis=1)

    def gather_tl(tl, t0, vrow_other):
        """[count, q, w_aug, n_other]: window + the valid row (the
        OR-term's allow side) + alignment zeros."""

        def one(t0i):
            return jax.lax.dynamic_slice(
                tl, (0, t0i, 0), (q, w, tl.shape[2])
            )

        win = jax.vmap(one)(t0)  # [count, q, w, n_other]
        count = win.shape[0]
        n_other = win.shape[3]
        aug = jnp.broadcast_to(
            vrow_other[None, None, None, :], (count, q, 1, n_other)
        ).astype(win.dtype)
        pad = jnp.zeros((count, q, w_aug - w - 1, n_other), dtype=win.dtype)
        return jnp.concatenate([win, aug, pad], axis=2)

    pe = ((~has_e) & (jnp.arange(n) < n_pods)).astype(od)  # [N]
    pi = ((~has_i) & (jnp.arange(n) < n_pods)).astype(od)
    pe_s = _pad_to(pe[None, :], 1, bs)[0]  # [ns_pad]
    pi_d = _pad_to(pi[None, :], 1, bd)[0]  # [nd_pad]
    vs = _pad_to(valid[None, :], 1, bs)[0]  # [ns_pad]
    vd = _pad_to(valid[None, :], 1, bd)[0]  # [nd_pad]

    # egress: acc[s, d] += pe[s] * vd[d]; ingress: acc[s, d] += vs[s] * pi[d]
    a_e = gather_tm(tm_e, t0_e, bs, n_i, pe_s)  # [n_i, w_aug, bs]
    a_i = gather_tm(tm_i, t0_i, bd, n_j, pi_d)  # [n_j, w_aug, bd]
    b_e = jnp.moveaxis(gather_tl(tl_e, t0_e, vd), 1, 0)  # [q, n_i, w_aug, nd_pad]
    b_i = jnp.moveaxis(gather_tl(tl_i, t0_i, vs), 1, 0)  # [q, n_j, w_aug, ns_pad]
    return {"a_e": a_e, "b_e": b_e, "b_i": b_i, "a_i": a_i}


def verdict_counts_pallas_slab_from_ops(ops, interpret: bool = False):
    """[Q, n_i, 3] int32 partials from pre-gathered slab operands
    (slab_operands).  Every layout parameter is derived from the operand
    shapes, so cached operands can never desynchronize from the kernel's
    block specs."""
    a_e, b_e, b_i, a_i = ops["a_e"], ops["b_e"], ops["b_i"], ops["a_i"]
    n_i, w_aug, bs = a_e.shape
    n_j, _, bd = a_i.shape
    q = b_e.shape[0]
    ns_pad, nd_pad = n_i * bs, n_j * bd
    counts = pl.pallas_call(
        _make_verdict_counts_kernel_slab(),
        grid=(q, n_i, n_j),
        in_specs=[
            pl.BlockSpec((1, w_aug, bs), lambda q, i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, w_aug, bd), lambda q, i, j: (q, i, 0, j)),
            pl.BlockSpec((1, 1, w_aug, bs), lambda q, i, j: (q, j, 0, i)),
            pl.BlockSpec((1, w_aug, bd), lambda q, i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_i, 128), lambda q, i, j: (q, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, 128), jnp.int32)],
        out_shape=jax.ShapeDtypeStruct((q, n_i, 128), jnp.int32),
        cost_estimate=pl.CostEstimate(
            flops=2 * q * ns_pad * nd_pad * 2 * w_aug,
            bytes_accessed=q * n_i * n_j * w_aug * (bs + bd),
            transcendentals=0,
        ),
        interpret=interpret,
    )(a_e, b_e, b_i, a_i)
    return counts[:, :, :3]


@partial(
    jax.jit, static_argnames=("interpret", "operand_dtype", "bs", "bd", "w")
)
def _verdict_counts_pallas_slab(
    tmatch_e, has_e, tallow_e, tmatch_i, has_i, tallow_i,
    t0_e, t0_i, n_pods, interpret, operand_dtype, bs, bd, w,
):
    ops = _slab_operands(
        tmatch_e, has_e, tallow_e, tmatch_i, has_i, tallow_i,
        t0_e, t0_i, n_pods,
        operand_dtype=operand_dtype, bs=bs, bd=bd, w=w,
    )
    return verdict_counts_pallas_slab_from_ops(ops, interpret=interpret)


def sum_partials(partials, q: int, n_pods: int) -> Dict[str, int]:
    """Host-side int64 reduction of [Q, n_tiles, 3] partials into the
    counts dict — the ONE place that knows the lane order (ingress,
    egress, combined).  jnp int64 silently truncates without
    jax_enable_x64, hence numpy."""
    import numpy as np

    c = np.asarray(partials, dtype=np.int64).sum(axis=(0, 1))
    return {
        "ingress": int(c[0]),
        "egress": int(c[1]),
        "combined": int(c[2]),
        "cells": q * n_pods * n_pods,
    }


def evaluate_grid_counts_pallas(tensors: Dict, n_pods: int) -> Dict[str, int]:
    """Drop-in alternative to tiled.evaluate_grid_counts riding the fused
    Pallas kernel.  Per-(port case, src-tile) partials are int32-bounded
    (bs * N < 2^31, checked in _tiles_for and again at call time); totals
    are summed host-side in int64."""
    from .tiled import _precompute_jit

    pre = _precompute_jit(tensors)
    partials = verdict_counts_pallas(
        pre["egress"]["tmatch"],
        pre["egress"]["has_target"],
        pre["egress"]["tallow_bf"],
        pre["ingress"]["tmatch"],
        pre["ingress"]["has_target"],
        pre["ingress"]["tallow_bf"],
        n_pods=n_pods,
        interpret=_should_interpret(),
    )
    return sum_partials(partials, int(tensors["q_port"].shape[0]), n_pods)
