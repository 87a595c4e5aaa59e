"""The finished tables' device format (docs/DESIGN.md "GridVerdict"): the
single-device grid programs hand `GridVerdict` 32-bit words, four cells a
word, and the host lays a boolean view over them; every other route keeps
handing it boolean tables.  Either way the caller sees bool [Q, N, N], held
here to the scalar oracle."""

import functools
import random

import numpy as np
import pytest

from bench import build_synthetic, tiers_lattice
from cyclonus_tpu.analysis.oracle import traffic_for_cell
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine
from cyclonus_tpu.engine.api import GridVerdict, _bucket_pods
from cyclonus_tpu.engine.kernel import WORD_CELLS, WORD_TILE
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.matcher.tiered import tiered_oracle_verdicts

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
    engine = TpuPolicyEngine(
        policy, pods, namespaces,
        class_compress="1" if form == "sharded.classes" else "0",
    )
    if form == "empty":
        return engine.evaluate_grid([]), [t[:0] for t in tables]
    schedule = None if form == "sharded.classes" else form.split(".")[1]
    return (
        engine.evaluate_grid_sharded(CASES, mesh=cpu_mesh(8), schedule=schedule),
        tables,
    )


@pytest.mark.parametrize(
    "form",
    ["native", "device", "empty", "sharded.ring", "sharded.allgather",
     "sharded.classes"],
)
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
