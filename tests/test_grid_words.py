"""The finished tables' device format (docs/DESIGN.md "GridVerdict"): the
single-device grid programs and, since PR 28, the mesh routes hand
`GridVerdict` 32-bit words, four cells a word, and the host lays a boolean
view over them (the mesh routes' words are row-sharded over the mesh and come
to the host shard by shard); the native evaluator and the empty case keep
handing it boolean tables.  Either way the caller sees bool [Q, N, N], held
here to the scalar oracle."""

import functools
import gc
import random
import threading

import numpy as np
import pytest

from cyclonus_tpu.analysis.oracle import traffic_for_cell
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
from cyclonus_tpu.engine.api import GridVerdict, _bucket_pods
from cyclonus_tpu.engine.kernel import WORD_CELLS, WORD_TILE
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.matcher.tiered import tiered_oracle_verdicts
from cyclonus_tpu.synthetic import build_synthetic, tiers_lattice
from cyclonus_tpu.telemetry import instruments as ti

from test_engine_sharded import cpu_mesh

CASES = [
    PortCase(80, "serve-80-tcp", "TCP"),
    PortCase(81, "serve-81-udp", "UDP"),
    PortCase(80, "", "UDP"),
]
TABLES = ("ingress", "egress", "combined")
#: PathSpec route -> the class_compress that takes it
ROUTES = {"grid.dense": "0", "grid.classes": "1"}


@functools.lru_cache(maxsize=None)
def problem(n: int, tiered: bool):
    """A seeded cluster and the scalar oracle's three tables over CASES:
    ingress [q, dst, src], egress and combined [q, src, dst]."""
    pods, namespaces, policies = build_synthetic(n, 12, random.Random(27 + n))
    policy = build_network_policies(True, policies)
    tiers = tiers_lattice() if tiered else None
    want = {name: np.zeros((len(CASES), n, n), dtype=bool) for name in TABLES}
    for q, case in enumerate(CASES):
        for s in range(n):
            for d in range(n):
                i, e, c = tiered_oracle_verdicts(
                    policy, tiers, traffic_for_cell(pods, namespaces, case, s, d)
                )
                want["ingress"][q, d, s] = i
                want["egress"][q, s, d] = e
                want["combined"][q, s, d] = c
    return policy, pods, namespaces, tiers, want


def triples_of(n: int, q: int):
    """Every cell of a small grid, a seeded thousand of a larger one."""
    if n <= 16:
        return [(k, s, d) for k in range(q) for s in range(n) for d in range(n)]
    rng = random.Random(n)
    return [
        (rng.randrange(q), rng.randrange(n), rng.randrange(n))
        for _ in range(1000)
    ]


def cells_of(tables, triples) -> np.ndarray:
    """What GridVerdict.gather has to return, from the host tables."""
    ingress, egress, combined = tables
    return np.array(
        [[ingress[q, d, s], egress[q, s, d], combined[q, s, d]] for q, s, d in triples]
    )


def assert_same_answers(grid, tables):
    """gather, allow_counts and allow_stats read the device form, whichever
    it is, and agree with the host tables."""
    q, n = tables[0].shape[0], tables[0].shape[1]
    triples = triples_of(n, q)
    got = grid.gather(triples)
    assert got.dtype == np.bool_ and got.shape == (len(triples), 3)
    assert np.array_equal(got, cells_of(tables, triples))
    counts = tuple(int(t.sum()) for t in tables)
    assert grid.allow_counts() == counts
    stats = grid.allow_stats()
    for name, count in zip(TABLES, counts):
        assert stats[name] == pytest.approx(count / (q * n * n), abs=1e-12)


@pytest.mark.parametrize("tiered", [False, True], ids=["untiered", "tiered"])
@pytest.mark.parametrize("q", [1, 3])
@pytest.mark.parametrize("n", [9, 13, 130])
@pytest.mark.parametrize("route", list(ROUTES))
def test_word_tables_equal_the_oracle(route, n, q, tiered):
    policy, pods, namespaces, tiers, want = problem(n, tiered)
    engine = TpuPolicyEngine(
        policy, pods, namespaces, tiers=tiers, class_compress=ROUTES[route]
    )
    assert (engine.pod_classes() is not None) == (route == "grid.classes")
    grid = engine.evaluate_grid(CASES[:q])
    tables = []
    for name in TABLES:
        dev = getattr(grid, name + "_dev")
        # the device format: whole (8, 128) tiles of words over the pod
        # axis as the route pads it
        rows = _bucket_pods(n) if route == "grid.dense" else n
        assert dev.dtype == np.uint32
        assert dev.shape[:2] == (q, -(-rows // WORD_TILE[0]) * WORD_TILE[0])
        assert dev.shape[2] % WORD_TILE[1] == 0
        assert dev.shape[2] >= -(-rows // WORD_CELLS)
        table = getattr(grid, name)
        assert table.dtype == np.bool_ and table.shape == (q, n, n)
        assert np.array_equal(table, want[name][:q]), name
        # a view of the fetched words, not a copy of them
        words = np.asarray(dev)
        assert np.shares_memory(table, words)
        assert getattr(grid, name) is table
        cells = words.view(np.uint8)
        assert cells.max() <= 1
        # the pad bytes: everything past the axis the route evaluated
        assert not cells[:, :, rows:].any()
        if route == "grid.classes":
            assert not cells[:, rows:, :].any()
        tables.append(table)
    assert_same_answers(grid, tables)
    assert grid.job_verdict(q - 1, 2, 5) == tuple(
        bool(x) for x in cells_of(tables, [(q - 1, 2, 5)])[0]
    )


#: mesh route -> (the class_compress, the schedule) that take it
MESH_ROUTES = {
    "classes": ("1", None), "ring": ("0", "ring"), "allgather": ("0", "allgather"),
}


@functools.lru_cache(maxsize=None)
def cluster(n: int):
    """A seeded cluster too large for the scalar oracle, and the tables of
    the single-device dense route over CASES."""
    pods, namespaces, policies = build_synthetic(n, 12, random.Random(27 + n))
    policy = build_network_policies(True, policies)
    ref = TpuPolicyEngine(policy, pods, namespaces, class_compress="0")
    grid = ref.evaluate_grid(CASES)
    return policy, pods, namespaces, {name: getattr(grid, name) for name in TABLES}


@pytest.mark.parametrize("n", [9, 130, 1027])
@pytest.mark.parametrize("n_dev", [2, 4, 8])
@pytest.mark.parametrize("route", list(MESH_ROUTES))
def test_mesh_word_tables_are_row_sharded_and_exact(route, n_dev, n):
    """Every mesh route hands over cell_words [Q, N_pad, W] whose ROW axis
    is split over the mesh: no device holds more than its share of a table,
    and the host's tables equal evaluate_grid's bit for bit (and the
    oracle's, where the oracle is affordable)."""
    policy, pods, namespaces, single = cluster(n)
    class_compress, schedule = MESH_ROUTES[route]
    engine = TpuPolicyEngine(
        policy, pods, namespaces, class_compress=class_compress
    )
    grid = engine.evaluate_grid_sharded(
        CASES, mesh=cpu_mesh(n_dev), schedule=schedule
    )
    tables = []
    for name in TABLES:
        dev = getattr(grid, name + "_dev")
        assert dev.dtype == np.uint32 and dev.shape[0] == len(CASES)
        rows = dev.shape[1]
        assert rows >= n and rows % (n_dev * WORD_TILE[0]) == 0
        assert dev.shape[2] % WORD_TILE[1] == 0
        assert dev.shape[2] >= -(-n // WORD_CELLS)
        assert tuple(dev.sharding.spec)[:2] == (None, "x")
        shards = dev.addressable_shards
        assert len(shards) == n_dev
        held = sorted(sh.index[1].indices(rows)[:2] for sh in shards)
        share = rows // n_dev
        assert held == [(k * share, (k + 1) * share) for k in range(n_dev)]
        for sh in shards:
            assert sh.data.shape == (len(CASES), share, dev.shape[2])
        table = getattr(grid, name)
        assert table.dtype == np.bool_ and table.shape == (len(CASES), n, n)
        assert np.array_equal(table, single[name]), name
        if n <= 130:
            assert np.array_equal(table, problem(n, False)[4][name]), name
        assert getattr(grid, name) is table
        tables.append(table)
    assert_same_answers(grid, tables)


def test_a_large_shard_is_laid_into_the_table_in_row_blocks(monkeypatch):
    """Above _LAY_BYTES a shard goes into the table's host buffer in row
    blocks on the lay threads; the table is the same, bit for bit."""
    from cyclonus_tpu.engine import api

    policy, pods, namespaces, single = cluster(130)
    engine = TpuPolicyEngine(policy, pods, namespaces, class_compress="1")
    blocks = []
    real = np.copyto
    monkeypatch.setattr(api, "_LAY_BYTES", 4 << 10)
    monkeypatch.setattr(
        api.np, "copyto", lambda dst, src: (blocks.append(dst.shape), real(dst, src))
    )
    grid = engine.evaluate_grid_sharded(CASES, mesh=cpu_mesh(4))
    for name in TABLES:
        assert np.array_equal(getattr(grid, name), single[name]), name
    # 3 tables x 4 shards x 3 port cases, a shard's 40 rows of 128 words in
    # blocks of 8 rows (4 KiB)
    assert blocks == [(8, 128)] * (3 * 4 * 3 * 5)


@pytest.fixture
def holder(monkeypatch):
    """A holder of host buffers of the test's own (the module's one is
    shared by every sharded fetch of the process)."""
    from cyclonus_tpu.engine import api

    fresh = api._HostBuffers()
    monkeypatch.setattr(api, "_host_buffers", fresh)
    return fresh


def handed_out() -> dict:
    return {
        o: ti.GRID_HOST_BUFFER.value(outcome=o) for o in ("recycled", "fresh")
    }


def address(table: np.ndarray) -> int:
    return table.__array_interface__["data"][0]


def sharded_engine(n: int, class_compress: str = "1"):
    policy, pods, namespaces, single = cluster(n)
    engine = TpuPolicyEngine(
        policy, pods, namespaces, class_compress=class_compress
    )
    return engine, single


def test_a_dropped_verdicts_memory_serves_the_next_one(holder):
    """Once a verdict AND its tables are gone, the next sharded tables of
    that shape are laid into the same memory: no fresh pages."""
    engine, single = sharded_engine(130)
    mesh = cpu_mesh(4)
    grid = engine.evaluate_grid_sharded(CASES, mesh=mesh)
    tables = [getattr(grid, name) for name in TABLES]
    first = sorted(address(t) for t in tables)
    nbytes = tables[0].base.nbytes
    assert len(set(first)) == 3 and holder.free_bytes() == 0
    before = handed_out()
    del grid
    assert holder.free_bytes() == 0  # the tables outlive the verdict
    del tables
    gc.collect()
    assert holder.free_bytes() == 3 * nbytes
    grid = engine.evaluate_grid_sharded(CASES, mesh=mesh)
    again = sorted(address(getattr(grid, name)) for name in TABLES)
    assert again == first
    assert handed_out() == {
        "recycled": before["recycled"] + 3, "fresh": before["fresh"],
    }
    assert holder.free_bytes() == 0
    for name in TABLES:
        assert np.array_equal(getattr(grid, name), single[name]), name


#: what a caller may keep of a table after the GridVerdict is gone
VIEWS = {
    "table": lambda t: t,
    "slice": lambda t: t[0, 3:7],
    "memoryview": memoryview,
}


@pytest.mark.parametrize("kept", list(VIEWS))
def test_a_table_somebody_still_views_is_never_written_again(holder, kept):
    """While any view of a table lives, a later evaluation of the same
    shape takes other memory: the view's bytes stay the oracle's."""
    n = 13
    policy, pods, namespaces, _, want = problem(n, False)
    engine = TpuPolicyEngine(policy, pods, namespaces, class_compress="1")
    mesh = cpu_mesh(4)
    grid = engine.evaluate_grid_sharded(CASES[:1], mesh=mesh)
    held = [VIEWS[kept](getattr(grid, name)) for name in TABLES]
    expected = [VIEWS[kept](want[name][:1]) for name in TABLES]
    taken = {address(getattr(grid, name)) for name in TABLES}
    del grid
    gc.collect()
    assert holder.free_bytes() == 0
    before = handed_out()
    # another case of the same shape: its tables differ from the first's
    later = engine.evaluate_grid_sharded(CASES[1:2], mesh=mesh)
    for name in TABLES:
        table = getattr(later, name)
        assert address(table) not in taken
        assert np.array_equal(table, want[name][1:2]), name
    assert handed_out() == {
        "recycled": before["recycled"], "fresh": before["fresh"] + 3,
    }
    for view, oracle in zip(held, expected):
        assert np.array_equal(np.asarray(view), np.asarray(oracle))
    assert any(
        not np.array_equal(want[name][:1], want[name][1:2]) for name in TABLES
    )


@pytest.mark.parametrize("route", list(MESH_ROUTES))
def test_a_recycled_buffer_holds_the_new_request_alone(holder, route):
    """A recycled buffer comes with the last table's bytes in it and the
    shards cover every one: the words on the host, pad words included, are
    the device's, and the table is evaluate_grid's, case set after case
    set."""
    class_compress, schedule = MESH_ROUTES[route]
    engine, single = sharded_engine(130, class_compress)
    mesh = cpu_mesh(4)
    for turn, k in enumerate([0, 1, 2, 0]):
        before = handed_out()
        grid = engine.evaluate_grid_sharded(
            CASES[k : k + 1], mesh=mesh, schedule=schedule
        )
        for name in TABLES:
            table = getattr(grid, name)
            assert np.array_equal(table, single[name][k : k + 1]), (name, k)
            words = table.base
            assert words.dtype == np.uint32
            # JAX's own assembly of the shards, into memory of its own
            assert np.array_equal(
                words, np.asarray(getattr(grid, name + "_dev"))
            ), (name, k)
        outcome = "recycled" if turn else "fresh"
        assert handed_out()[outcome] == before[outcome] + 3
        del grid, table, words
        gc.collect()
    assert any(
        not np.array_equal(single[name][0], single[name][1]) for name in TABLES
    )


def test_the_holder_keeps_three_free_buffers_of_the_last_shape():
    from cyclonus_tpu.engine import api

    assert api._FREE_BUFFERS == 3
    holder = api._HostBuffers()
    shape, other = (1, 32, 128), (2, 32, 128)
    one = int(np.prod(shape)) * 4
    taken = [holder.take(shape, np.uint32) for _ in range(4)]
    assert [recycled for _, recycled in taken] == [False] * 4
    assert all(
        buf.shape == shape and buf.dtype == np.uint32 and buf.flags.writeable
        for buf, _ in taken
    )
    assert holder.free_bytes() == 0
    for k in range(4):
        taken.pop()
        # the fourth free buffer is released at once
        assert holder.free_bytes() == min(k + 1, 3) * one
    buf, recycled = holder.take(shape, np.uint32)
    assert recycled and holder.free_bytes() == 2 * one
    # another shape (or dtype) evicts every free buffer ...
    wide, recycled = holder.take(other, np.uint32)
    assert not recycled and wide.shape == other and holder.free_bytes() == 0
    # ... and a buffer of the old shape that comes back now is not kept
    del buf
    assert holder.free_bytes() == 0
    del wide
    assert holder.free_bytes() == 2 * one
    same_bytes, recycled = holder.take(other, np.int32)
    assert not recycled and same_bytes.dtype == np.int32
    assert holder.free_bytes() == 0


def test_two_threads_fetching_two_verdicts_get_disjoint_buffers(holder):
    engine, single = sharded_engine(130)
    mesh = cpu_mesh(4)
    # three free buffers to contend for
    warm = engine.evaluate_grid_sharded(CASES, mesh=mesh)
    nbytes = warm.combined.base.nbytes
    _ = warm.ingress, warm.egress
    del warm, _
    gc.collect()
    assert holder.free_bytes() == 3 * nbytes
    grids = [engine.evaluate_grid_sharded(CASES, mesh=mesh) for _ in range(2)]
    before = handed_out()
    start = threading.Barrier(2)
    failed = []

    def fetch(grid):
        try:
            start.wait()
            for name in TABLES:
                getattr(grid, name)
        except Exception as e:  # surfaced below
            failed.append(e)

    threads = [threading.Thread(target=fetch, args=(g,)) for g in grids]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not failed
    tables = [getattr(g, name) for g in grids for name in TABLES]
    assert len({address(t) for t in tables}) == 6
    for a in range(6):
        for b in range(a + 1, 6):
            assert not np.shares_memory(tables[a], tables[b])
    assert handed_out() == {
        "recycled": before["recycled"] + 3, "fresh": before["fresh"] + 3,
    }
    for g in grids:
        for name in TABLES:
            assert np.array_equal(getattr(g, name), single[name]), name


@pytest.mark.parametrize("entry", ["evaluate_grid", "one_device_mesh"])
def test_a_one_device_table_takes_no_buffer_from_the_holder(holder, entry):
    engine, single = sharded_engine(130)
    before = handed_out()
    if entry == "evaluate_grid":
        grid = engine.evaluate_grid(CASES)
    else:
        grid = engine.evaluate_grid_sharded(CASES, mesh=cpu_mesh(1))
    for name in TABLES:
        assert np.array_equal(getattr(grid, name), single[name]), name
    del grid
    gc.collect()
    assert handed_out() == before and holder.free_bytes() == 0


def boolean_grid(form: str):
    """A GridVerdict as each route that does NOT emit words builds it, and
    the tables it must return."""
    n = 13
    policy, pods, namespaces, _, want = problem(n, False)
    keys = [f"{p[0]}/{p[1]}" for p in pods]
    tables = [want[name] for name in TABLES]
    if form == "native":  # native.evaluate_grid_native: numpy tables
        return GridVerdict(keys, list(CASES), *tables), tables
    if form == "device":  # boolean device arrays
        import jax.numpy as jnp

        return GridVerdict(keys, list(CASES), *map(jnp.asarray, tables)), tables
    assert form == "empty"
    engine = TpuPolicyEngine(policy, pods, namespaces, class_compress="0")
    return engine.evaluate_grid([]), [t[:0] for t in tables]


@pytest.mark.parametrize("schedule", ["ring", "allgather"])
@pytest.mark.parametrize("class_compress", ["0", "1"])
def test_a_one_device_mesh_hands_over_words_on_one_device(class_compress, schedule):
    """A table on one device takes the single-device copy (no shard copy)."""
    policy, pods, namespaces, _, want = problem(13, False)
    engine = TpuPolicyEngine(
        policy, pods, namespaces, class_compress=class_compress
    )
    grid = engine.evaluate_grid_sharded(CASES, mesh=cpu_mesh(1), schedule=schedule)
    tables = [getattr(grid, name) for name in TABLES]
    for name, table in zip(TABLES, tables):
        dev = getattr(grid, name + "_dev")
        assert dev.dtype == np.uint32 and len(dev.sharding.device_set) == 1
        assert np.array_equal(table, want[name])
        assert np.shares_memory(table, np.asarray(dev))  # a view, no assembly
    assert_same_answers(grid, tables)


@pytest.mark.parametrize("form", ["native", "device", "empty"])
def test_boolean_tables_behave_as_before(form):
    grid, want = boolean_grid(form)
    assert grid.ingress_dev.dtype == np.bool_
    tables = [getattr(grid, name) for name in TABLES]
    for table, expected in zip(tables, want):
        assert table.dtype == np.bool_ and table.shape == expected.shape
        assert np.array_equal(table, expected)
    if form == "native":
        assert tables[0] is grid.ingress_dev  # no copy of host tables
    if form == "empty":
        assert grid.allow_counts() == (0, 0, 0)
        assert grid.allow_stats() == dict.fromkeys(TABLES, 0.0)
        assert grid.gather([]).shape == (0, 3)
    else:
        assert_same_answers(grid, tables)
