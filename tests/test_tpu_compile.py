"""What only the TPU's compiler can say, asked without a chip: programs
of the main path compiled at real shapes for a DESCRIBED v5e (libtpu is
installed here; nothing runs, so nothing here is a chip reading).

The one file for such compiles: the process that describes a topology
loads the TPU's library and keeps it, so the call is made inside a
fixture of this file, never while a module is imported (xdist hands a
file to one worker).  Where no topology can be described the tests
skip."""

import re

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


#: `anp-100k-10k`'s class tile (PR 40): a direction's bucketed tier rule
#: rows, the tile's class rows, the padded class axis, the port cases
TIER_TILE = dict(g=1536, a=1024, b=5120, q=2)


def test_the_tier_scan_keeps_two_keys_and_no_match(one_chip):
    """kernel.tier_first_match_keys at that tile (TIER_FIRST_MATCH): no
    loop over rule rows or words, ONE reduce a tier over the 48 packed
    words into its int32 [A, B, Q] key, and the two keys are all it
    hands back.  Neither a [W, A, B, Q] match nor a carried key stays in
    HBM: its temporaries are under a byte a cell of one key (the packed
    operands, some 2 MB).  The 8-row scan it replaced wrote and read both
    keys once a chunk, 192 chunks a direction of a tile."""
    import re

    from cyclonus_tpu.engine.kernel import tier_first_match_keys

    g, a, b, q = (TIER_TILE[k] for k in ("g", "a", "b", "q"))
    compiled = (
        jax.jit(tier_first_match_keys)
        .lower(
            jax.ShapeDtypeStruct((g, a), jnp.bool_, sharding=one_chip),
            jax.ShapeDtypeStruct((g, b, q), jnp.bool_, sharding=one_chip),
            jax.ShapeDtypeStruct((g,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((g,), jnp.int32, sharding=one_chip),
        )
        .compile()
    )
    mem = compiled.memory_analysis()
    # the two keys (and the tuple that holds them)
    assert 0 <= mem.output_size_in_bytes - 2 * 4 * a * b * q < 4096
    assert mem.temp_size_in_bytes < a * b * q  # under a byte a cell of one key
    text = compiled.as_text()
    assert " while(" not in text
    key_reduce = re.compile(rf"= s32\[{a},{b},{q}\]\{{[^}}]*\}} reduce\(")
    assert len(key_reduce.findall(text)) == 2


#: `cidr-10k-5k`'s request (`counts.cases`): each direction's peer rows,
#: the targets, the padded pods, the port cases, a port spec's items and
#: ranges
CASES_CELL = dict(p={"ingress": 13_056, "egress": 11_520}, t=895, n=10_240, q=2,
                  items=2, ranges=1)
#: the parent's `_precompute_cases` (peer_allow [P, N * Q] and its
#: relayout) compiled at CASES_CELL with pack on for a described v5e,
#: read once: memory_analysis().temp_size_in_bytes, by tile form
PARENT_CASES_TEMP_BYTES = {"bnq": 803_450_880, "bqn": 803_354_112}


@pytest.mark.parametrize("tile", ["bnq", "bqn"])
def test_the_case_precompute_builds_no_peer_allow(one_chip, tile):
    """tiled._precompute_cases at that request (kernel.TARGET_ALLOWS):
    the port cases ride the one-hot, so no boolean array of P x N x Q
    elements exists, the resident peer_match is read where it lies (no
    copy of a pred[P, N] table into another layout), and the program's
    temporaries are no more than the parent's."""
    import re

    from cyclonus_tpu.engine import tiled
    from cyclonus_tpu.engine.encoding import packed_words

    t, n, q = CASES_CELL["t"], CASES_CELL["n"], CASES_CELL["q"]
    i, r = CASES_CELL["items"], CASES_CELL["ranges"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def direction(p):
        spec = {k: sds((p, i), jnp.int32)
                for k in ("item_kind", "item_port", "item_name", "item_proto")}
        spec.update({k: sds((p, r), jnp.int32)
                     for k in ("rng_from", "rng_to", "rng_proto")})
        spec["spec_all"] = sds((p,), jnp.bool_)
        return {
            "peer_match": sds((p, n), jnp.bool_),
            "tmatch": sds((t, n), jnp.bool_),
            "has_target": sds((n,), jnp.bool_),
            "peer_target": sds((p,), jnp.int32),
            "target_ns": sds((t,), jnp.int32),
            "port_spec": spec,
            "tmatch_pk": sds((packed_words(t), n), jnp.int32),
        }

    static = {d: direction(p) for d, p in CASES_CELL["p"].items()}
    case = sds((q,), jnp.int32)
    compiled = (
        jax.jit(lambda s, a, b, c: tiled._precompute_cases(s, a, b, c, True, tile))
        .lower(static, case, case, case)
        .compile()
    )
    text = compiled.as_text()
    op = re.compile(r"= pred\[([\d,]+)\]\{[^}]*\} ([\w-]+)\(")
    ops = [
        (tuple(int(d) for d in m.group(1).split(",")), m.group(2))
        for m in op.finditer(text)
    ]
    least = min(CASES_CELL["p"].values()) * n * q
    assert [o for o in ops if np.prod(o[0]) >= least] == []
    tables = {(p, n) for p in CASES_CELL["p"].values()}
    assert [o for o in ops if o[0] in tables and o[1] == "copy"] == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= PARENT_CASES_TEMP_BYTES[tile]


#: k8s-envelope-150k's class set at its full size (PERF.md section 4, the
#: draw on a TPU host): pods, classes and selectors bucketed,
#: and a direction's (peer rows bucketed, target rows bucketed)
ENVELOPE_FULL = dict(
    pods=150_004, cb=17_408, s=41_472,
    ingress=(18_048, 16_511), egress=(14_848, 16_511),
)


def _envelope_class_set(one_chip, full=False):
    """(ct, w, cases, selpod, eng) with the first four as ShapeDtypeStructs:
    the class tensor set of k8s-envelope-150k's rehearsal (one load
    namespace, 330 classes), built on the host, and its resident selector
    matches [S, Cb]; `full` lays the same leaves at the full deployment's
    axes (ENVELOPE_FULL), and `eng`, the rehearsal's engine, then holds
    them as its class state, for its estimate alone."""
    from cyclonus_tpu.engine import TpuPolicyEngine
    from cyclonus_tpu.kube.yaml_io import parse_policy_dict
    from cyclonus_tpu.matcher import build_network_policies
    from cyclonus_tpu.synthetic import k8s_envelope_objects

    pods, namespaces, policies = k8s_envelope_objects(1, 100)
    policy = build_network_policies(True, [parse_policy_dict(d) for d in policies])
    eng = TpuPolicyEngine(policy, pods, namespaces, class_compress="1")
    ct = eng._class_state["ctensors"]
    cb, s = ct["pod_ns_id"].shape[0], ct["sel_req_kv"].shape[0]
    if full:
        cb, s = ENVELOPE_FULL["cb"], ENVELOPE_FULL["s"]

    def sds(path, a):
        shape = list(a.shape)
        key = [k.key for k in path]
        if full and key[0] in ("ingress", "egress"):
            peers, targets = ENVELOPE_FULL[key[0]]
            shape[0] = targets if key[-1] in ("target_ns", "target_sel") else peers
        elif full and key[0].startswith("pod_"):
            shape[0] = cb
        elif full and key[0].startswith("sel_"):
            shape[0] = s
        return jax.ShapeDtypeStruct(tuple(shape), a.dtype, sharding=one_chip)

    ct = jax.tree_util.tree_map_with_path(sds, ct)
    selpod = jax.ShapeDtypeStruct((s, cb), jnp.bool_, sharding=one_chip)
    if full:
        leaves = jax.tree_util.tree_leaves(ct) + [selpod]
        eng._class_state = dict(
            eng._class_state,
            ctensors=ct,
            selpod=selpod,
            aux_bytes=ENVELOPE_FULL["pods"] * 4 + cb * 4 + sum(
                int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves
            ),
        )
    return (
        ct,
        jax.ShapeDtypeStruct((cb,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((3, 2), jnp.int32, sharding=one_chip),
        selpod,
        eng,
    )


def _while_body_ops(text):
    """What a compiled module's while loops do a trip: ([(opcode, largest
    array it writes, in elements)] of each body's own instructions, the
    buffers a trip materializes; [(opcode, element type, elements)] of
    every instruction of the bodies' fusions and the computations they
    call)."""
    import math
    import re

    comps = {}
    for block in re.split(r"\n(?=%|ENTRY )", text):
        m = re.match(r"(?:ENTRY )?%(\S+) ", block)
        if m:
            comps[m.group(1)] = block
    bodies = re.findall(r"\bwhile\(.*?body=%([\w.\-]+)", text)
    written, todo = [], []
    for body in bodies:
        for line in comps[body].split("\n")[1:]:
            m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([\w\-]+)\(", line)
            if m and m.group(2) not in ("parameter", "get-tuple-element", "tuple"):
                sizes = [
                    math.prod(int(d) for d in dims.split(",") if d)
                    for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))
                ]
                written.append((m.group(2), max(sizes, default=0)))
            todo += re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", line)
    seen, inner = set(), []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name].split("\n")[1:]:
            m = re.match(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(", line)
            if m:
                dims = [int(d) for d in m.group(2).split(",") if d]
                inner.append((m.group(3), m.group(1), math.prod(dims)))
            todo += re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", line)
    return written, inner


def test_the_class_program_handed_its_matches_runs_no_selector_pass(one_chip):
    """tiled._class_rowsums_kernel (the XLA class tile, what
    k8s-envelope-150k runs past PACKED_MAX_WORDS): handed the resident
    selector matches it builds no [S, Cb, E] plane of the selector pass
    (the 5.7 GB of two such planes at the envelope's full shapes), and
    holds no more temporaries than the same program without them."""
    import re

    from cyclonus_tpu.engine import tiled

    ct, w, cases, selpod, _ = _envelope_class_set(one_chip)
    s, e = ct["sel_exp_op"].shape
    cb = ct["pod_ns_id"].shape[0]
    block, n_tiles = tiled._class_tiling(cb, 1024)
    plane = re.compile(rf"pred\[{s},{cb},{e}\]|pred\[{s},{e},{cb}\]")
    temps = {}
    for held in (False, True):
        compiled = tiled._class_rowsums_kernel.lower(
            ct, w, cases, block, n_tiles, True, selpod=selpod if held else None
        ).compile()
        assert bool(plane.search(compiled.as_text())) is not held
        temps[held] = compiled.memory_analysis().temp_size_in_bytes
    assert temps[True] <= temps[False]


@pytest.mark.parametrize("full", [False, True], ids=["rehearsal", "envelope"])
def test_the_untiered_class_program_contracts_on_the_mxu(one_chip, full):
    """The XLA class program over an untiered set (k8s-envelope-150k's past
    PACKED_MAX_WORDS, 516 words a direction at full size) runs
    the MXU form: its tile loop holds one dot a direction and port case
    over the unpacked target axis, in tiled.MXU_DTYPE, and writes,
    converts, copies or transposes no [T, Cb]-sized array a trip (the dst
    ingress tmatch is cast once, before the loop, and a tile's [T, block]
    slab is sliced where it lies); the class route's estimate holds its
    temporaries and arguments, and at full size by at most 15 % more."""
    from cyclonus_tpu.engine import tiled

    ct, w, cases, selpod, eng = _envelope_class_set(one_chip, full)
    assert not tiled.class_counts_pack(ct, True, "xla")
    cb = ct["pod_ns_id"].shape[0]
    t = min(ct[d]["target_ns"].shape[0] for d in ("ingress", "egress"))
    # two tiles or more, so that the loop is a loop
    block, n_tiles = tiled._class_tiling(cb, 1024 if full else 128)
    compiled = tiled._class_rowsums_kernel.lower(
        ct, w, cases, block, n_tiles, False, selpod=selpod
    ).compile()
    written, inner = _while_body_ops(compiled.as_text())
    assert [o for o in inner if o[0] == "convolution"] == [("convolution", "s32", block * cb)] * 4
    assert [o for o in written if o[1] >= t * cb] == []
    assert [o for o in inner if o[0] in ("convert", "copy", "transpose") and o[2] >= t * cb] == []
    mem = compiled.memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert used <= eng._class_counts_bytes(2)
    if full:
        assert eng._class_counts_bytes(2) <= 1.15 * used


def test_the_banded_class_program_contracts_target_tiles(one_chip):
    """The class program at k8s-envelope-150k's full shapes with the band
    plan its constructor lays (api._band_tensors: 129 tiles of 128
    targets, PSPAN 256 a direction): the target-allows contraction is ONE
    batched matmul a direction, [129, 256, 256] x [129, 256, Cb], no
    one-hot over every peer row ([Q·T', P]) exists, the tile loop is the
    MXU form's, and the class route's estimate holds the program's
    temporaries and arguments by at most 15 % more."""
    from cyclonus_tpu.engine import kernel, tiled

    ct, w, cases, selpod, eng = _envelope_class_set(one_chip, True)
    band = jax.ShapeDtypeStruct((129, 256), jnp.int32, sharding=one_chip)
    for d in ("ingress", "egress"):
        ct[d] = dict(ct[d], band_rows=band)
    eng._class_state = dict(eng._class_state, ctensors=ct)
    assert kernel.target_allows_form(ct) == kernel.TARGET_ALLOWS_BAND
    cb = ct["pod_ns_id"].shape[0]
    block, n_tiles = tiled._class_tiling(cb, 1024)
    compiled = tiled._class_rowsums_kernel.lower(
        ct, w, cases, block, n_tiles, False, selpod=selpod
    ).compile()
    text = compiled.as_text()
    dots = re.findall(r"= (\w+\[[\d,]+\])\{[^}]*\} convolution\(", text)
    assert sorted(dots) == sorted(
        [f"bf16[129,{2 * kernel.TARGET_BAND},{cb}]"] * 2 + [f"s32[{block},{cb}]"] * 4
    )
    t = ENVELOPE_FULL["ingress"][1] + 1  # whole words
    for p in (ENVELOPE_FULL["ingress"][0], ENVELOPE_FULL["egress"][0]):
        assert f"[{2 * t},{p}]" not in text
    written, inner = _while_body_ops(text)
    assert [o for o in inner if o[0] == "convolution"] == [("convolution", "s32", block * cb)] * 4
    mem = compiled.memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert used <= eng._class_counts_bytes(2) <= 1.15 * used
