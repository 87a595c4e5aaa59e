"""The ipBlock-heavy cluster on the DENSE mesh route: what the four-chip
cell `cidr-40k-20k-x4.port-sweep` runs, held here on virtual CPU devices
at a few hundred pods.

  * PARITY: `synthetic.cidr_allowlists` through `evaluate_grid_sharded`
    on 2, 4 and 8 devices under both schedules, on the cell's six single
    port cases, bit for bit against the scalar oracle, `evaluate_grid`
    and the benchmark's plain reference; the pod count is no multiple
    of devices x 8, so every device pads;
  * ROUTE: above the pod floor `auto` refuses class compression, the
    recorded route is `grid.sharded.ring` (or `.allgather`) and nothing
    else, and the evaluation's span says which leaf ran;
  * the sharded words serve `gather` and `allow_counts` without a whole
    table on the host;
  * the dense epilogue exchanges ingress as WORDS over a leading axis
    (the exchange of booleans is what the TPU compiler took nine minutes
    over at 40,960 pods on four chips: PERF.md, PR 34), and the program's
    persistent key names the exchange;
  * `engine.dispatch_sharded` says what the launch sent.
"""

import importlib.util
import os
import re

import numpy as np
import pytest

from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, kernel, planspec, tiled
from cyclonus_tpu.engine import sharded as sharded_mod
from cyclonus_tpu.kube.yaml_io import policy_to_dict
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.synthetic import CIDR_ALLOWLISTS, cidr_allowlists
from cyclonus_tpu.telemetry import instruments as ti
from cyclonus_tpu.telemetry import spans
from cyclonus_tpu.tiers.fuzz import _oracle_table, _table_from_grid

import test_engine_sharded
from test_engine_sharded import cpu_mesh

# benchmarks/traffic/port-sweep-mesh-cidr.json: six single cases, Q = 1
SIX = [
    PortCase(port, f"serve-{port}-{proto.lower()}", proto)
    for port, proto in ((80, "TCP"), (81, "UDP"), (80, "UDP"),
                        (81, "SCTP"), (80, "SCTP"), (81, "TCP"))
]
N_PODS = 150  # no multiple of 16, 32 or 64: every mesh pads its last device
# ten nodes of 16 pods, so that /24s, /26s and /28s all cut the cluster
SMALL = dict(CIDR_ALLOWLISTS, pods_per_node=16)
SCHEDULES = ("ring", "allgather")


@pytest.fixture(scope="module")
def cluster():
    pods, namespaces, policies = cidr_allowlists(N_PODS, 90, 3, SMALL)
    policy = build_network_policies(True, policies)
    want = _oracle_table(policy, None, pods, namespaces, SIX)  # [6, N, N, 3]
    return policy, pods, namespaces, policies, want


@pytest.fixture(scope="module")
def engine(cluster):
    """The program's defaults, with the pod floor lowered so that `auto`
    decides as it does at 40,000 pods."""
    policy, pods, namespaces, _, _ = cluster
    floor = os.environ.get("CYCLONUS_CLASS_MIN_PODS")
    os.environ["CYCLONUS_CLASS_MIN_PODS"] = "32"
    try:
        before = ti.CLASS_ROUTE.value(outcome="no_reduction")
        eng = TpuPolicyEngine(policy, pods, namespaces)
        assert ti.CLASS_ROUTE.value(outcome="no_reduction") == before + 1
    finally:
        if floor is None:
            del os.environ["CYCLONUS_CLASS_MIN_PODS"]
        else:
            os.environ["CYCLONUS_CLASS_MIN_PODS"] = floor
    assert eng.pod_classes() is None
    return eng


def test_the_six_cases_decide_cells_and_differ(cluster):
    *_, want = cluster
    shares = [want[q, :, :, 2].mean() for q in range(len(SIX))]
    assert 0.02 < shares[0] < 0.9          # 80/TCP: the allowlists decide
    assert shares[1] != shares[2]          # the named UDP port decides too
    assert len({w.tobytes() for w in want}) >= 3


@pytest.mark.parametrize("case", range(len(SIX)), ids=[f"{c.port}-{c.protocol}" for c in SIX])
@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_the_dense_mesh_route_against_the_scalar_oracle(
    cluster, engine, n_dev, schedule, case
):
    *_, want = cluster
    assert N_PODS % (n_dev * 8)
    grid = engine.evaluate_grid_sharded(
        [SIX[case]], mesh=cpu_mesh(n_dev), schedule=schedule
    )
    assert str(grid.ingress_dev.dtype) == "uint32"
    assert grid.ingress_dev.shape[1] % (n_dev * 8) == 0
    assert np.array_equal(_table_from_grid(grid), want[case:case + 1])


@pytest.mark.parametrize("n_dev", [2, 4])
def test_the_ring_over_the_band_against_the_scalar_oracle(cluster, n_dev, monkeypatch):
    """The ring (`grid.sharded.ring`, which traces tiled._precompute a
    device) over the cluster with the target-allows band laid (forced: at
    this size the rule declines; tiles of 8 targets): every table of
    every case is the oracle's, and the evaluation says band."""
    from cyclonus_tpu.telemetry import recorder

    policy, pods, namespaces, _, want = cluster
    monkeypatch.setattr(kernel, "band_engages", lambda *a: True)
    monkeypatch.setattr(kernel, "TARGET_BAND", 8)
    monkeypatch.setenv("CYCLONUS_CLASS_MIN_PODS", "32")
    eng = TpuPolicyEngine(policy, pods, namespaces)
    assert eng._tensors["ingress"]["band_rows"].shape[0] > 1
    grid = eng.evaluate_grid_sharded(SIX, mesh=cpu_mesh(n_dev), schedule="ring")
    assert np.array_equal(_table_from_grid(grid), want)
    assert recorder.entries()[-1]["tallow"] == "band,band"


@pytest.mark.parametrize("case", range(len(SIX)), ids=[f"{c.port}-{c.protocol}" for c in SIX])
def test_one_chip_and_the_benchmarks_reference_say_the_same(cluster, engine, case):
    """`evaluate_grid` on one device, and `benchmarks/reference.py` (read
    only here: what decides the cell's `correct`), in the tables' own
    orientation: ingress [q, dst, src]."""
    _, pods, namespaces, policies, want = cluster
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference", os.path.join(repo, "benchmarks", "reference.py")
    )
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    c = SIX[case]
    assert np.array_equal(
        _table_from_grid(engine.evaluate_grid([c])), want[case:case + 1]
    )
    ref = reference.GridReference(
        pods, namespaces, [policy_to_dict(p) for p in policies], ""
    )
    said = ref.tables([(c.port, c.port_name, c.protocol)])
    grid = engine.evaluate_grid_sharded([c], mesh=cpu_mesh(4))
    for got, ref_table in zip((grid.ingress, grid.egress, grid.combined), said):
        assert np.array_equal(np.asarray(got), ref_table)


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_the_recorded_route_is_the_dense_leaf_and_nothing_else(
    engine, monkeypatch, schedule
):
    monkeypatch.setattr(planspec, "ACTIVE", True)  # arm the recorder
    planspec.drain()
    spans.REGISTRY.reset()
    engine.evaluate_grid_sharded(
        SIX[:1], mesh=cpu_mesh(4), schedule=schedule
    ).block_until_ready()
    assert planspec.drain() == [f"grid.sharded.{schedule}"]
    (root,) = [
        rec["attrs"] for path, rec in spans.REGISTRY.tree().items()
        if path == "engine.eval"
    ]
    # the ring traces the target-allows contraction: its form a direction
    tallow = kernel.tallow_attrs(engine._tensors) if schedule == "ring" else {}
    assert root == {
        "route": "grid.sharded", "schedule": schedule, "classes": False, **tallow,
    }


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_gather_and_allow_counts_read_the_sharded_words(cluster, engine, n_dev):
    *_, want = cluster
    grid = engine.evaluate_grid_sharded(SIX[:1], mesh=cpu_mesh(n_dev))
    rng = np.random.default_rng(n_dev)
    triples = [
        (0, int(s), int(d))
        for s, d in zip(rng.integers(0, N_PODS, 40), rng.integers(0, N_PODS, 40))
    ] + [(0, N_PODS - 1, N_PODS - 1), (0, 0, N_PODS - 1)]
    got = grid.gather(triples)
    assert np.array_equal(got, np.stack([want[q, s, d] for q, s, d in triples]))
    assert grid.allow_counts() == tuple(
        int(want[0, :, :, k].sum()) for k in range(3)
    )
    assert grid._np == {}  # no table came to the host for either


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_ingress_changes_hands_as_words_over_a_leading_axis(
    engine, monkeypatch, schedule
):
    """One all_to_all, of uint32 words, split and joined along a leading
    axis of one chunk a device: no boolean is exchanged, and no boolean
    table over all rows exists."""
    n_dev = 4
    text, grid = test_engine_sharded.TestMeshWordPrograms._lowered(
        monkeypatch, engine, cpu_mesh(n_dev), schedule=schedule, cases=SIX[:1]
    )
    exchanges = re.findall(r'"?stablehlo\.all_to_all"?.*', text)
    assert len(exchanges) == 1
    (exchange,) = exchanges
    n_pad = grid.ingress_dev.shape[1]
    shard = n_pad // n_dev
    assert f"tensor<{n_dev}x1x{shard}x{shard // 4}xui32>" in exchange
    assert "xi1>" not in exchange
    assert "split_dimension = 0" in exchange and "concat_dimension = 0" in exchange
    # a device's block of a table is there; a table over all rows is not
    assert f"tensor<{shard}x{n_pad}x1xi1>" in text
    for whole in (f"{n_pad}x{n_pad}x1", f"1x{n_pad}x{n_pad}"):
        assert f"tensor<{whole}xi1>" not in text


def test_the_persistent_key_names_the_exchange(engine):
    sharded_mod._SHARDED_PROGRAMS.clear()
    engine.evaluate_grid_sharded(SIX[:1], mesh=cpu_mesh(2))
    (fn,) = sharded_mod._SHARDED_PROGRAMS.values()
    assert sharded_mod.DENSE_EXCHANGE == "xchg=words"
    assert f";{sharded_mod.DENSE_EXCHANGE};" in fn._plan and "classes=False" in fn._plan
    sharded_mod._SHARDED_PROGRAMS.clear()


@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_the_dispatch_span_says_what_the_launch_sent(engine, n_dev, schedule):
    """Replicated host arrays (the policy tensors, the port cases) count
    once a chip, the per-pod arrays once: on this route the peer and
    port-spec arrays are most of a launch."""
    import jax

    mesh = cpu_mesh(n_dev)
    engine.evaluate_grid_sharded(SIX[:1], mesh=mesh, schedule=schedule)  # warm
    before = ti.MESH_DISPATCH_BYTES.value(route=schedule)
    spans.REGISTRY.reset()
    engine.evaluate_grid_sharded(SIX[:1], mesh=mesh, schedule=schedule)
    (attrs,) = [
        rec["attrs"] for path, rec in spans.REGISTRY.tree().items()
        if path.rsplit("/", 1)[-1] == "engine.dispatch_sharded"
    ]
    tensors, padded = sharded_mod._pad_pod_arrays(
        engine._tensors_with_cases(SIX[:1]), N_PODS, n_dev * 8
    )
    leaves = jax.tree_util.tree_flatten_with_path(tensors)[0]
    per_pod = sum(
        np.asarray(a).nbytes for path, a in leaves
        if path[0].key in sharded_mod._POD_KEYS
    )
    replicated = sum(np.asarray(a).nbytes for _, a in leaves) - per_pod
    assert attrs["route"] == attrs["schedule"] == schedule
    assert attrs["devices"] == n_dev and attrs["shard"] == padded // n_dev
    assert attrs["host_operands"] == len(leaves)
    assert attrs["host_bytes"] == per_pod + n_dev * replicated
    assert replicated > 4 * per_pod
    assert attrs["peer_bytes"] == ti.MESH_PEER_BYTES.value(schedule=schedule) > 0
    assert ti.MESH_DISPATCH_BYTES.value(route=schedule) - before == attrs["host_bytes"]
