"""PR 40's deployment `anp-100k-10k` on the CPU, at a small size.

`synthetic.anp_tenancy` scaled down in its number of guardrail ANPs alone:
four at the CRD's 100 rules a direction still cross the fused kernel's row
ceiling (`pallas_kernel.PACKED_TIER_MAX_ROWS`), so every route runs the XLA
tier epilogue.  On seeded draws of the cluster and of the tier set:

  * the engine's counts on `counts.classes` (the XLA reroute) and on the dense
    `counts.xla`, and its `evaluate_grid` tables, equal
    `benchmarks/reference_tiers.py` (what decides the cell's `correct`), and
    sampled cells equal the scalar oracle `matcher/tiered.py`;
  * the reference's control `drop_priority` changes the answer;
  * every tiered evaluation's `engine.eval` carries `tiered`, `tier_rows` and
    `tier_kernel`, counted by `cyclonus_tpu_tier_evals_total{kernel}`, and the
    constructor's tier encoding is a span `engine.tier_encode` of its own;
  * the class route's HBM estimate counts the tier arrays.
"""

import importlib
import os
import random
import sys

import numpy as np
import pytest

import cyclonus_tpu.engine.api as api
from cyclonus_tpu.analysis.oracle import traffic_for_cell
from cyclonus_tpu.engine import PortCase, TpuPolicyEngine, planspec, tiled
from cyclonus_tpu.engine.pallas_kernel import PACKED_TIER_MAX_ROWS, packed_tier_eligible
from cyclonus_tpu.kube.yaml_io import policy_to_dict
from cyclonus_tpu.matcher import build_network_policies
from cyclonus_tpu.matcher.tiered import TieredPolicy
from cyclonus_tpu.synthetic import ANP_TENANCY, anp_tenancy_objects, build_synthetic
from cyclonus_tpu.telemetry import instruments as ti
from cyclonus_tpu.telemetry import recorder, spans
from cyclonus_tpu.tiers.model import TierSet, parse_tier_object

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# benchmarks/traffic/port-sweep-tiers.json: three pairs, Q = 2
PAIRS = [
    [PortCase(p, f"serve-{p}-{proto.lower()}", proto) for p, proto in pair]
    for pair in (
        ((80, "TCP"), (81, "UDP")),
        ((80, "UDP"), (81, "SCTP")),
        ((80, "SCTP"), (81, "TCP")),
    )
]
IDS = ["A", "B", "C"]
N_PODS, N_POLICIES, N_NS = 96, 16, 6
DRAWS = [3, 20261015]  # the cluster's seed and the tier set's tier_seed


def tiers_gen(seed: int) -> dict:
    return dict(ANP_TENANCY, apps=4, tier_seed=seed)


def cases_of(pair):
    return [(c.port, c.port_name, c.protocol) for c in pair]


def spans_named(name):
    return [
        rec["attrs"] for path, rec in spans.REGISTRY.tree().items()
        if path.rsplit("/", 1)[-1] == name
    ]


@pytest.fixture(scope="module")
def reference_tiers():
    """`benchmarks/reference_tiers.py`, which imports the benchmark's
    reference.py as `benchmarks.reference`."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    return importlib.import_module("benchmarks.reference_tiers")


@pytest.fixture(scope="module", params=DRAWS, ids=[f"draw{s}" for s in DRAWS])
def draw(request):
    seed = request.param
    pods, namespaces, policies = build_synthetic(
        N_PODS, N_POLICIES, random.Random(seed), n_ns=N_NS
    )
    anps, banp = anp_tenancy_objects(N_NS, tiers_gen(seed))
    # the listing order moves no verdict: the lattice orders by priority
    random.Random(seed).shuffle(anps)
    return pods, namespaces, policies, anps, banp


def parse(draw):
    """The draw's tier dicts through the program's parser, as the cell's kind
    hands them over."""
    _, _, _, anps, banp = draw
    return TierSet(anps=[parse_tier_object(d) for d in anps], banp=parse_tier_object(banp))


def new_engine(draw, class_compress):
    pods, namespaces, policies, _, _ = draw
    return TpuPolicyEngine(
        build_network_policies(True, policies), pods, namespaces,
        class_compress=class_compress, tiers=parse(draw),
    )


@pytest.fixture(scope="module")
def engines(draw):
    """{route: engine}: the class route forced, and the dense one."""
    return {
        "counts.classes": new_engine(draw, "1"),
        "counts.xla": new_engine(draw, "0"),
    }


@pytest.fixture(scope="module")
def reference(draw, reference_tiers):
    pods, namespaces, policies, anps, banp = draw
    return reference_tiers.TieredGridReference(
        pods, namespaces, [policy_to_dict(p) for p in policies], anps, banp
    )


def test_the_tier_set_is_past_the_fused_kernels_ceiling(engines):
    for eng in engines.values():
        rows = tiled.tier_rows(eng._tensors)
        assert rows > PACKED_TIER_MAX_ROWS and not packed_tier_eligible(eng._tensors)
        with pytest.raises(planspec.PlanError, match="precedence-tier"):
            eng.evaluate_grid_counts(PAIRS[0], backend="pallas")
    ct = engines["counts.classes"]._class_state["ctensors"]
    assert tiled._class_counts_kernel_choice(ct, True, None) == "xla"


@pytest.mark.parametrize("route", ["counts.classes", "counts.xla"])
@pytest.mark.parametrize("pair", range(3), ids=IDS)
def test_counts_equal_the_reference(engines, reference, route, pair, monkeypatch):
    monkeypatch.setattr(planspec, "ACTIVE", True)  # arm the recorder
    planspec.drain()
    got = engines[route].evaluate_grid_counts(PAIRS[pair])
    assert planspec.drain() == [route]
    assert got == reference.counts(cases_of(PAIRS[pair]))
    assert 0 < got["combined"] < got["cells"]


@pytest.mark.parametrize("route", ["counts.classes", "counts.xla"])
def test_tables_equal_the_reference(engines, reference, route):
    pair = PAIRS[0]
    grid = engines[route].evaluate_grid(pair)
    for got, want in zip(
        (grid.ingress, grid.egress, grid.combined), reference.tables(cases_of(pair))
    ):
        assert np.array_equal(np.asarray(got), want)


def test_sampled_cells_equal_the_scalar_oracle(draw, engines, reference):
    pods, namespaces, policies, _, _ = draw
    oracle = TieredPolicy(build_network_policies(True, policies), parse(draw))
    pair = PAIRS[1]
    tables = reference.tables(cases_of(pair))
    rng = random.Random(7)
    for _ in range(120):
        q, s, d = rng.randrange(len(pair)), rng.randrange(N_PODS), rng.randrange(N_PODS)
        said = oracle.is_traffic_allowed(traffic_for_cell(pods, namespaces, pair[q], s, d))
        # ingress is indexed [q, dst, src], egress and combined [q, src, dst]
        assert said == (tables[0][q, d, s], tables[1][q, s, d], tables[2][q, s, d])


def test_the_control_drop_priority_changes_the_answer(draw, reference, reference_tiers):
    pods, namespaces, policies, anps, banp = draw
    broken = reference_tiers.TieredGridReference(
        pods, namespaces, [policy_to_dict(p) for p in policies], anps, banp,
        "drop_priority",
    )
    assert any(
        broken.counts(cases_of(p)) != reference.counts(cases_of(p)) for p in PAIRS
    )


@pytest.mark.parametrize("route", ["counts.classes", "counts.xla"])
def test_a_tiered_evaluation_says_so_and_is_counted(engines, route):
    eng = engines[route]
    spans.REGISTRY.reset()
    before = ti.TIER_EVALS.value(kernel=tiled.TIER_XLA)
    eng.evaluate_grid_counts(PAIRS[2])
    (root,) = spans_named("engine.eval")
    assert root["route"] == route
    assert (root["tiered"], root["tier_rows"], root["tier_kernel"]) == (
        1, tiled.tier_rows(eng._tensors), tiled.TIER_XLA
    )
    # the packed rule words the first-match reduces over, ingress + egress
    words = sum(-(-int(eng._tensors["tiers"][d]["action"].shape[0]) // 32)
                for d in ("ingress", "egress"))
    assert root["tier_words"] == words > 0
    entry = recorder.entries()[-1]
    assert (entry["tier_kernel"], entry["tier_words"]) == (tiled.TIER_XLA, words)
    assert ti.TIER_EVALS.value(kernel=tiled.TIER_XLA) == before + 1
    spans.REGISTRY.reset()
    eng.evaluate_grid(PAIRS[2])
    (root,) = spans_named("engine.eval")
    assert root["tier_kernel"] == tiled.TIER_XLA
    assert ti.TIER_EVALS.value(kernel=tiled.TIER_XLA) == before + 2


def test_an_untiered_evaluation_says_nothing_of_tiers(draw):
    pods, namespaces, policies, _, _ = draw
    eng = TpuPolicyEngine(build_network_policies(True, policies), pods, namespaces)
    spans.REGISTRY.reset()
    before = ti.TIER_EVALS.value(kernel=tiled.TIER_XLA)
    eng.evaluate_grid_counts(PAIRS[0])
    (root,) = spans_named("engine.eval")
    assert not {"tiered", "tier_rows", "tier_kernel", "tier_words"} & set(root)
    assert ti.TIER_EVALS.value(kernel=tiled.TIER_XLA) == before
    assert tiled.tier_attrs(eng._tensors) == {}


def test_the_tier_encoding_is_a_span_of_the_constructor(draw):
    spans.REGISTRY.reset()
    tiers = parse(draw)
    eng = new_engine(draw, "0")
    (enc,) = spans_named("engine.tier_encode")
    assert enc == {
        "anps": len(tiers.anps),
        "rules": sum(tiers.rule_count().values()),
        "rows": sum(t.n_rows for t in eng.encoding.tiers),
    }
    assert enc["rows"] <= tiled.tier_rows(eng._tensors)  # before the buckets


def test_the_class_routes_estimate_counts_the_tier_arrays(engines, reference, monkeypatch):
    eng = engines["counts.classes"]
    ct = eng._class_state["ctensors"]
    cb = int(ct["pod_ns_id"].shape[0])
    block, _ = tiled._class_tiling(cb, 1024)
    tier = api._tier_pre_bytes(ct, cb, 2) + api._tier_first_match_bytes(ct, block, cb, 2)
    # the precompute, then a tile's two int32 keys a direction and its
    # packed operands: peer words [W, *, Q] and a subject [W, *] a tier
    w_e, w_i = (-(-int(ct["tiers"][d]["action"].shape[0]) // 32) for d in ("egress", "ingress"))
    packed = 4 * w_e * (2 * block + cb * 2) + 4 * w_i * (2 * cb + block * 2)
    assert tier == tiled.tier_rows(ct) * cb * 3 + 2 * 2 * 4 * block * cb * 2 + packed
    est = eng._class_counts_bytes(2)
    assert est > tier > 0
    monkeypatch.setenv("CYCLONUS_SLAB_MAX_BYTES", str(est))
    assert eng._class_counts_eligible(2)
    # a budget the route's NetworkPolicy half fits and its tiers do not
    monkeypatch.setenv("CYCLONUS_SLAB_MAX_BYTES", str(est - tier))
    assert not eng._class_counts_eligible(2)
    monkeypatch.setattr(planspec, "ACTIVE", True)
    planspec.drain()
    got = eng.evaluate_grid_counts(PAIRS[0])
    assert planspec.drain() == ["counts.xla"]
    assert got == reference.counts(cases_of(PAIRS[0]))
    dense = engines["counts.xla"]
    n = int(dense._tensors["pod_ns_id"].shape[0])
    assert dense._pre_bytes_estimate(2) > api._tier_pre_bytes(dense._tensors, n, 2) > 0
