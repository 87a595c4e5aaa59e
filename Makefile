# Mirrors the reference's Makefile targets (test/fmt/vet/build) in Python
# form (reference Makefile:1-12).

test:
	python -m pytest tests/ -q

# static lint: ruff (when installed; pinned by [tool.ruff] in
# pyproject.toml so the installed branch is deterministic) + the JAX
# hot-path lint (tools/jaxlint.py — device-sync / traced-branch /
# recompile-risk / host-callback checks) over every package that stages
# jit code: engine, telemetry, worker, analysis, probe — so
# instrumentation and audit passes can never smuggle a device sync into
# a hot path (tests/test_telemetry.py asserts the same) + the
# lock-discipline lint (tools/locklint.py — guarded-by, lock-order
# cycles, leaked guards; see docs/DESIGN.md "Lock discipline") over the
# whole package + the tensor-contract lint (tools/shapelint.py —
# shape/dtype/sentinel/tile-alignment contracts of the encoding->kernel
# pipeline; see docs/DESIGN.md "Tensor contracts") over the engine, the
# analysis layer, and the worker wire model.
# The fourth leg, the cache-coherence lint (tools/cachelint.py —
# cache-key completeness of every compiled/persisted program,
# derived-cache invalidation, env-on-cached-path, persisted write
# discipline, never-raise degradation contracts; docs/DESIGN.md "Cache
# discipline"), runs over the cache-bearing packages.
# The fifth leg, the dispatch-surface lint (tools/planlint.py —
# route-recorder literals vs the PathSpec registry, differential-gate
# existence, compatibility-matrix completeness, determinism hazards,
# dead declarations; docs/DESIGN.md "Plan surface"), cross-checks
# engine/planspec.py against the dispatch graph and emits the plan
# manifest artifact.
# The sixth leg, the authoritative-state lint (tools/statelint.py —
# guarded-commit-path mutation discipline, rollback-snapshot and
# digest/note_epoch/state() coverage, epoch-bump discipline, delta-kind
# lifecycle rows; docs/DESIGN.md "State discipline"), cross-checks
# serve/stateregistry.py against the service, the wire model, and the
# audit canonicalization.
# The seventh leg, the wire-protocol compatibility lint
# (tools/wirelint.py — undeclared/misguarded key emits, unguarded
# optional reads, schema-evolution drift against the frozen
# worker/wire_schema.json golden, reply-epoch discipline, value
# portability; docs/DESIGN.md "Wire discipline"), cross-checks every
# emit and parse site in worker/ + serve/ against the versioned
# message registry (worker/wireregistry.py).
# tests/test_cachelint.py pins the seven legs under a combined
# one-minute wall-clock budget so the gate stays cheap enough to run.
lint: shapelint cachelint planlint statelint wirelint
	@if python -m ruff --version >/dev/null 2>&1; then \
	  python -m ruff check cyclonus_tpu tools; \
	else echo "ruff not installed; skipping"; fi
	python tools/jaxlint.py cyclonus_tpu/engine cyclonus_tpu/telemetry \
	  cyclonus_tpu/worker cyclonus_tpu/analysis cyclonus_tpu/probe \
	  cyclonus_tpu/serve cyclonus_tpu/tiers cyclonus_tpu/chaos \
	  cyclonus_tpu/linter cyclonus_tpu/recipes cyclonus_tpu/slo \
	  cyclonus_tpu/audit
	python tools/locklint.py cyclonus_tpu

shapelint:
	python tools/shapelint.py cyclonus_tpu/engine cyclonus_tpu/analysis \
	  cyclonus_tpu/worker/model.py cyclonus_tpu/serve cyclonus_tpu/tiers \
	  cyclonus_tpu/chaos cyclonus_tpu/linter cyclonus_tpu/recipes \
	  cyclonus_tpu/slo cyclonus_tpu/audit

cachelint:
	python tools/cachelint.py cyclonus_tpu/engine cyclonus_tpu/serve \
	  cyclonus_tpu/chaos cyclonus_tpu/audit

planlint:
	python tools/planlint.py --manifest artifacts/plan_manifest.json \
	  cyclonus_tpu/engine cyclonus_tpu/serve cyclonus_tpu/tiers \
	  cyclonus_tpu/slo cyclonus_tpu/audit

statelint:
	python tools/statelint.py cyclonus_tpu/serve cyclonus_tpu/audit

wirelint:
	python tools/wirelint.py cyclonus_tpu/worker cyclonus_tpu/serve

# git-diff-scoped lint: run only the legs whose scanned paths contain a
# file changed vs the merge base (falls back to HEAD for a clean tree).
# Registry-level legs (planlint) always run in full — their findings
# are cross-file by construction.
lint-changed:
	python tools/lint_changed.py

# the key-mutation harness (tests/keyharness.py; docs/DESIGN.md "Cache
# discipline"): for every registered cache family, perturb each key
# component one at a time and assert a miss/retrace, then revert and
# assert a hit — including the subprocess restart leg (a warm AOT
# cache adopts with ZERO compiles; a mutated dtype-plan component
# misses every entry with bit-identical verdicts).  The quick slice
# runs in tier-1 via tests/test_cachelint.py; this is the full sweep.
keyharness:
	JAX_PLATFORMS=cpu python -m tests.keyharness --full --verbose

# the dispatch-route harness (tests/planharness.py; docs/DESIGN.md
# "Plan surface"): arm the route recorder (CYCLONUS_PLANHARNESS=1),
# sweep the governing flag/argument matrix through the real public
# entry points, and assert the recorded routes equal what the PathSpec
# registry predicts — including the compatibility matrix's exact raise
# messages.  The quick slice runs in tier-1 via tests/test_planlint.py;
# this is the full sweep (adds the slow ring-pipeline leg).
planharness:
	JAX_PLATFORMS=cpu python -m tests.planharness --full --verbose

# the state-surface harness (tests/stateharness.py; docs/DESIGN.md
# "State discipline"): arm the registry call recorder
# (CYCLONUS_STATEHARNESS=1), drive every registered field's delta kinds
# through a live VerdictService, and assert the epoch digest changes,
# a chaos-injected mid-apply failure rolls the digest back through the
# registry snapshot/restore pair, the epoch advances exactly once per
# batch, and every declared kind round-trips the wire Delta — plus the
# forgotten-field legs proving the strict registry surfaces fail
# loudly.  The quick slice runs in tier-1 via tests/test_statelint.py;
# this is the full sweep (adds the scaled parity leg).
stateharness:
	JAX_PLATFORMS=cpu python -m tests.stateharness --full --verbose

# the peer version-skew harness (tests/skewharness.py; docs/DESIGN.md
# "Wire discipline"): arm the skew-view recorder (CYCLONUS_SKEWHARNESS=1),
# synthesize older-peer legacy views and newer-peer unknown-key payloads
# for EVERY registered wire message straight from the registry, push
# them through the real codecs and the real in-process serve loop, and
# assert verdict/apply parity against an un-skewed twin — plus the
# coverage census (no registered optional key unexercised in either
# skew direction) and the static-vs-runtime manifest byte-identity.
# The quick slice runs in tier-1 via tests/test_wirelint.py; this is
# the full sweep (adds the scaled mixed-version stream leg).
skewharness:
	JAX_PLATFORMS=cpu python -m tests.skewharness --full --verbose

# the compressed-path parity gate: the equivalence-class grid
# compression forced on AND the runtime tensor contracts live
# (CYCLONUS_SHAPE_CHECK=1), through the full parity + class suites —
# compressed vs dense vs scalar oracle stays bit-identical with every
# class tensor validated at construction (docs/DESIGN.md "Grid
# compression")
parity-compressed:
	CYCLONUS_SHAPE_CHECK=1 CYCLONUS_CLASS_COMPRESS=1 JAX_PLATFORMS=cpu \
	  python -m pytest tests/test_engine_parity.py \
	  tests/test_engine_classes.py -q

# the TSS/LPM CIDR pre-classification parity gate (docs/DESIGN.md
# "CIDR tuple-space pre-classification"): the trie stage FORCED on
# (CYCLONUS_CIDR_TSS=1) under class compression with the runtime tensor
# contracts live, through the full parity suite + the dedicated CIDR
# suite, plus the adversarial CIDR fuzz family (dense == compressed ==
# TSS == oracle, mesh leg included)
parity-cidr:
	CYCLONUS_SHAPE_CHECK=1 CYCLONUS_CIDR_TSS=1 CYCLONUS_CLASS_COMPRESS=1 \
	  JAX_PLATFORMS=cpu python -m pytest tests/test_engine_parity.py \
	  tests/test_engine_cidr.py -q
	JAX_PLATFORMS=cpu python -m cyclonus_tpu fuzz --seeds 0 --cidr-seeds 4

# verdict-service smoke (docs/DESIGN.md "Verdict service"): start a real
# `cyclonus-tpu serve` subprocess, apply a delta batch over the wire
# (asserting the single-pod delta takes the INCREMENTAL path), query,
# assert every verdict against the scalar oracle, clean shutdown
serve-smoke:
	JAX_PLATFORMS=cpu python tools/serve_smoke.py

# multichip smoke (docs/DESIGN.md "Multi-chip scale-out"): one
# 8-virtual-device OVERLAPPED ring run — ring grid bit-identical to the
# all-gather schedule and the single-device kernel, every collective
# counts path verified, and the per-chip row emitted as one JSON line
multichip-smoke:
	JAX_PLATFORMS=cpu python -c \
	  "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)"

# the seeded fault-injection suite (docs/DESIGN.md "Cold start &
# chaos"): kill/restart serve mid-churn with a bounded time-to-first-
# verdict, poison/truncate the AOT + autotune caches, flake backend
# init, kill the worker wire, drop a delta batch mid-apply — every
# fault must degrade as designed (retry / rollback / fresh compile)
# with oracle parity preserved.  Bounded and seeded so it rides inside
# `make check`.
chaos:
	JAX_PLATFORMS=cpu python -m cyclonus_tpu chaos --seed 0

# the SLO gate (docs/DESIGN.md "SLO engine"): the unit legs — burn-rate
# math against synthetic histogram streams with pinned exhaustion
# instants, hysteresis entry/exit, the /slo payload + gauge-name pins,
# shed/admission enforcement with the differential gate — then the
# enforcement drill (tools/slo_drill.py): REAL overload until the
# query_p99 budget exhausts and queries shed (every non-shed answer
# bit-identical to an unenforced twin), then budget recovery back to
# live.  Seconds-bounded via shrunk windows, so it rides inside
# `make check`.
slo:
	JAX_PLATFORMS=cpu python -m pytest tests/test_slo.py -q
	JAX_PLATFORMS=cpu python tools/slo_drill.py

# the audit gate (docs/DESIGN.md "Audit plane"): the unit legs —
# seeded-sampler determinism, epoch-digest bit-stability across engine
# routes and across a subprocess restart, divergence capture with
# bundle pins, queue-overflow drop accounting, the disabled-path
# overhead differential — then the drill (tools/audit_drill.py): a REAL
# serve with the shadow-oracle sampler armed at rate 1.0, /audit and
# /metrics agreeing, replica-vs-replica digest equality at the same
# epoch, and an armed verdict_corrupt detected within the check budget.
audit:
	JAX_PLATFORMS=cpu python -m pytest tests/test_audit.py -q
	JAX_PLATFORMS=cpu python tools/audit_drill.py

# the one-command CI gate (mirrors reference go.yml build/fmt/vet/test):
# syntax-compile everything, lint the hot paths,
# smoke the verdict service and the 8-device overlapped mesh path, run
# the seeded tier fuzz gate (mesh leg included), run the chaos suite,
# then run the suite on a CPU 8-device mesh
check: vet lint parity-compressed parity-cidr serve-smoke multichip-smoke slo audit fuzz chaos
	JAX_PLATFORMS=cpu python -m pytest tests/ -q

# opt-in: the full 216-case conformance suite with a journal artifact
conformance:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m conformance

# the precedence-tier differential fuzz gate (docs/DESIGN.md
# "Precedence tiers"): seeded adversarial ANP/BANP policy sets —
# overlapping priorities, Pass-chains, overlapping CIDRs, empty
# selectors, sentinel-adjacent ports, endPort ranges, SCTP — checked
# kernel-vs-scalar-lattice-oracle, dense AND class-compressed, every
# engine's truth table ALSO routed through the overlapped ring mesh
# path (the mesh leg; --no-mesh skips), plus the
# generator's ANP/BANP conformance family.  Seeded and bounded (8
# seeds) so it rides inside `make check`; a failure names the seed for
# `cyclonus-tpu fuzz --seed N --seeds 1` reproduction.
fuzz:
	JAX_PLATFORMS=cpu python -m cyclonus_tpu fuzz --seeds 8 --conformance

# opt-in: the tier gate above plus 100 extra randomized parity seeds
# through the grid kernel and the xla/pallas counts engines
fuzz-full: fuzz
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m fuzz

# opt-in: the extended schedule-fuzzing race sweep (tests/raceharness.py
# at 16 threads x 200 seeded schedules, runtime lock guards asserting;
# the 8-thread/50-schedule gate already runs in tier-1 via
# tests/test_locklint.py)
race:
	CYCLONUS_GUARD_CHECK=1 JAX_PLATFORMS=cpu python -m tests.raceharness \
	  --schedules 200 --threads 16 --seed 99 --verbose

fmt:
	python -m black cyclonus_tpu tests 2>/dev/null || \
	  echo "black not installed; skipping"

vet:
	python -m compileall -q cyclonus_tpu tests __graft_entry__.py

cyclonus:
	pip install -e .

docker:
	docker build -t cyclonus-tpu:latest .

.PHONY: test check conformance fuzz fuzz-full race chaos slo audit fmt vet lint lint-changed shapelint cachelint planlint statelint wirelint keyharness planharness stateharness skewharness parity-compressed parity-cidr serve-smoke multichip-smoke cyclonus docker
