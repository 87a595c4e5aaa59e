"""Git-diff-scoped lint driver (`make lint-changed`): run only the
linter legs whose scanned paths intersect the files changed against
HEAD (working tree + index; falls back to the last commit's diff when
the tree is clean, so it is useful right after a commit too).

Leg selection, not path narrowing: a leg whose scope is touched runs
over its FULL path set, because every leg's findings can be cross-file
(a cache-key declared in one module and baked in another, a PathSpec
recorded three files away).  planlint additionally runs whenever the
registry, the Makefile, or a tests/ gate file changes — its PL002/PL005
checks read those directly.  Changing a tools/ file reruns every leg.

Exit 1 if any selected leg fails; prints the legs it skipped.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (leg name, argv after the script, scanned path prefixes)
LEGS: Tuple[Tuple[str, List[str], List[str]], ...] = (
    (
        "jaxlint",
        ["cyclonus_tpu/engine", "cyclonus_tpu/telemetry",
         "cyclonus_tpu/worker", "cyclonus_tpu/analysis",
         "cyclonus_tpu/probe",
         "cyclonus_tpu/serve", "cyclonus_tpu/tiers", "cyclonus_tpu/chaos",
         "cyclonus_tpu/linter", "cyclonus_tpu/recipes", "cyclonus_tpu/slo",
         "cyclonus_tpu/audit"],
        ["cyclonus_tpu/"],
    ),
    ("locklint", ["cyclonus_tpu"], ["cyclonus_tpu/"]),
    (
        "shapelint",
        ["cyclonus_tpu/engine", "cyclonus_tpu/analysis",
         "cyclonus_tpu/worker/model.py",
         "cyclonus_tpu/serve", "cyclonus_tpu/tiers", "cyclonus_tpu/chaos",
         "cyclonus_tpu/linter", "cyclonus_tpu/recipes", "cyclonus_tpu/slo",
         "cyclonus_tpu/audit"],
        ["cyclonus_tpu/engine", "cyclonus_tpu/analysis",
         "cyclonus_tpu/worker/model.py",
         "cyclonus_tpu/serve", "cyclonus_tpu/tiers", "cyclonus_tpu/chaos",
         "cyclonus_tpu/linter", "cyclonus_tpu/recipes", "cyclonus_tpu/slo",
         "cyclonus_tpu/audit"],
    ),
    (
        "cachelint",
        ["cyclonus_tpu/engine", "cyclonus_tpu/serve",
         "cyclonus_tpu/chaos", "cyclonus_tpu/audit"],
        ["cyclonus_tpu/engine", "cyclonus_tpu/serve",
         "cyclonus_tpu/chaos", "cyclonus_tpu/audit"],
    ),
    (
        "planlint",
        ["--manifest", "artifacts/plan_manifest.json",
         "cyclonus_tpu/engine", "cyclonus_tpu/serve", "cyclonus_tpu/tiers",
         "cyclonus_tpu/slo", "cyclonus_tpu/audit"],
        ["cyclonus_tpu/engine", "cyclonus_tpu/serve", "cyclonus_tpu/tiers",
         "cyclonus_tpu/slo", "cyclonus_tpu/audit", "Makefile", "tests/"],
    ),
    (
        # registry-level leg like planlint: the ST003/ST005 checks read
        # the wire model, the Makefile, and tests/ gate files directly
        "statelint",
        ["cyclonus_tpu/serve", "cyclonus_tpu/audit"],
        ["cyclonus_tpu/serve", "cyclonus_tpu/audit",
         "cyclonus_tpu/worker/model.py", "Makefile", "tests/"],
    ),
    (
        # registry-level leg: WR003 reads the frozen wire_schema.json
        # golden, and the harness gate files live under tests/
        "wirelint",
        ["cyclonus_tpu/worker", "cyclonus_tpu/serve"],
        ["cyclonus_tpu/worker", "cyclonus_tpu/serve", "Makefile",
         "tests/"],
    ),
)


def changed_files() -> List[str]:
    def _git(*args: str) -> List[str]:
        out = subprocess.run(
            ["git", *args], capture_output=True, text=True, cwd=REPO,
        )
        if out.returncode != 0:
            return []
        return [ln.strip() for ln in out.stdout.splitlines() if ln.strip()]

    files = _git("diff", "--name-only", "HEAD")
    files += _git("ls-files", "--others", "--exclude-standard")
    if not files:
        files = _git("diff", "--name-only", "HEAD~1", "HEAD")
    return sorted(set(files))


def legs_for(files: List[str]) -> List[str]:
    if any(f.startswith("tools/") for f in files):
        return [name for name, _a, _p in LEGS]
    selected = []
    for name, _argv, prefixes in LEGS:
        if any(f.startswith(p) for f in files for p in prefixes):
            selected.append(name)
    return selected


def main(argv=None) -> int:
    files = changed_files()
    if not files:
        print("lint-changed: no changed files, nothing to lint",
              file=sys.stderr)
        return 0
    selected = legs_for(files)
    skipped = [n for n, _a, _p in LEGS if n not in selected]
    print(
        f"lint-changed: {len(files)} changed file(s) -> "
        f"leg(s) {selected or ['-']}"
        + (f", skipping {skipped}" if skipped else ""),
        file=sys.stderr,
    )
    rc = 0
    for name, leg_argv, _prefixes in LEGS:
        if name not in selected:
            continue
        proc = subprocess.run(
            [sys.executable, os.path.join("tools", f"{name}.py"), *leg_argv],
            cwd=REPO,
        )
        rc = rc or proc.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
