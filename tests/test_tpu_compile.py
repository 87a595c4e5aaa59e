"""What only the TPU's compiler can say, asked without a chip: programs
of the main path compiled at real shapes for a DESCRIBED v5e (libtpu is
installed here; nothing runs, so nothing here is a chip reading).

The one file for such compiles: the process that describes a topology
loads the TPU's library and keeps it, so the call is made inside a
fixture of this file, never while a module is imported (xdist hands a
file to one worker).  Where no topology can be described the tests
skip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "w,a,b",
    [
        (100, 10240, 10240),  # a step of cidr-40k-20k-x4's ring, one direction
        (32, 512, 1024),  # a class grid
        (1, 8, 40960),
    ],
)
def test_packed_any_materializes_nothing(one_chip, w, a, b):
    """kernel.packed_any's promise "no [W, A, B] intermediate" rests on
    the compiler fusing the broadcast AND into the reduce.  On the TPU it
    does: ONE fusion, no loop, no temporary at all (the CPU backend does
    not fuse it and is no deployment target).  42 GB at the ring's shape
    if it ever stops."""
    from cyclonus_tpu.engine.kernel import packed_any

    compiled = (
        jax.jit(packed_any)
        .lower(
            jax.ShapeDtypeStruct((w, a), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((w, b), jnp.int32, sharding=one_chip),
        )
        .compile()
    )
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= a * b
    # a materialized intermediate is at least one byte a cell of [W, A, B]
    assert mem.temp_size_in_bytes < max(a * b, w * a * b // 64)
    text = compiled.as_text()
    assert " while(" not in text
    assert text.count(" fusion(") == 1


#: `cidr-100k-10k-x4`'s ring step: egress / ingress target words, the
#: tile's source rows, the dst shard's pods, the port cases
RING_STEP = dict(w_e=136, w_i=152, block=1024, shard=25_600, q=2)
#: the parent's tile body (PR 38) compiled at RING_STEP for a described
#: v5e, read once (PR 39): memory_analysis().temp_size_in_bytes
PARENT_TILE_TEMP_BYTES = 210_231_296


def _ring_step_tile(one_chip, form):
    """One ring step's counts tile at RING_STEP, compiled: the parent's
    body (`_tile_verdicts_split` and its sums over [B, Nd, Q]) for
    "bnq", `tiled._tile_counts_split` on a dst view laid for it for
    "bqn"; the tile's start is traced, as in the ring's loop."""
    from cyclonus_tpu.engine import tiled

    w_e, w_i, b, n, q = (RING_STEP[k] for k in ("w_e", "w_i", "block", "shard", "q"))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    src = {
        "tmatch_e": sds((w_e, n), jnp.int32),
        "has_e": sds((n,), jnp.bool_),
        "tallow_i": sds((w_i, n, q), jnp.int32),
    }
    dst = {
        "tallow_e": sds((w_e, q, n) if form == "bqn" else (w_e, n, q), jnp.int32),
        "tmatch_i": sds((w_i, n), jnp.int32),
        "has_i": sds((n,), jnp.bool_),
    }

    def parent(src, dst, sv, dv, start):
        verdicts = tiled._tile_verdicts_split(src, dst, start, b)
        s = jax.lax.dynamic_slice(sv, (start,), (b,))
        mask = s[:, None, None] & dv[None, :, None]
        return jnp.stack([jnp.sum(v & mask, dtype=jnp.int32) for v in verdicts])

    def bqn(src, dst, sv, dv, start):
        return tiled._tile_counts_split(src, dst, sv, dv, start, b)

    fn = bqn if form == "bqn" else parent
    return (
        jax.jit(fn)
        .lower(src, dst, sds((n,), jnp.bool_), sds((n,), jnp.bool_),
               sds((), jnp.int32))
        .compile()
    )


def test_the_counts_tile_moves_no_verdicts_between_layouts(one_chip):
    """The counts tile body (PR 39) at the counts ring's step: no copy or
    transpose of a block x shard (x Q) verdict array, and no more
    temporary memory than the parent's body, which moves its egress
    verdicts into [B, Nd, Q] (a copy of pred[1024,25600,2])."""
    import re

    b, n, q = RING_STEP["block"], RING_STEP["shard"], RING_STEP["q"]
    moved = re.compile(r"= pred\[([\d,]+)\]\{[^}]*\} (?:copy|transpose)\(")

    def big_moves(compiled):
        return [
            m.group(1) for m in moved.finditer(compiled.as_text())
            if np.prod([int(d) for d in m.group(1).split(",")]) >= b * n
        ]

    parent = _ring_step_tile(one_chip, "bnq")
    new = _ring_step_tile(one_chip, "bqn")
    # the parent's, as the issue read it from the ledger's breakdown
    assert f"{b},{n},{q}" in big_moves(parent)
    assert big_moves(new) == []
    temp = new.memory_analysis().temp_size_in_bytes
    assert temp <= parent.memory_analysis().temp_size_in_bytes
    assert temp <= PARENT_TILE_TEMP_BYTES


def test_the_resident_counts_program_ignores_the_tile_argument(
    one_chip, monkeypatch
):
    """The one-chip dense route's request program (`counts.cases`, the
    packed Pallas kernel, Mosaic as on the chip) traces
    `_precompute_cases` at the layout argument's default: passing the
    default by name lowers the same program, text for text."""
    from cyclonus_tpu.engine import TpuPolicyEngine, pallas_kernel, tiled

    from test_engine_tiled import fuzz_problem

    monkeypatch.setenv("CYCLONUS_PACK", "1")
    monkeypatch.setattr(pallas_kernel, "_should_interpret", lambda: False)
    policy, pods, namespaces = fuzz_problem(3, n_extra_pods=40)
    eng = TpuPolicyEngine(policy, pods, namespaces)
    eng._ensure_packed()
    perm = np.zeros((eng._tensors["pod_ns_id"].shape[0],), np.int32)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    def lowered():
        eng._build_counts_jits()
        static = on_chip(
            jax.eval_shape(eng._static_jit._jitted, eng._packed_buf, perm)
        )
        cases = jax.ShapeDtypeStruct((3, 2), jnp.int32, sharding=one_chip)
        n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        return eng._counts_cases_jit._jitted.lower(static, cases, n).as_text()

    default = lowered()
    assert "tpu_custom_call" in default  # the Pallas kernel, not interpret
    real = tiled._precompute_cases
    monkeypatch.setattr(
        tiled,
        "_precompute_cases",
        lambda *a, **k: real(*a, **k, tile=tiled.TILE_BNQ),
    )
    assert lowered() == default
