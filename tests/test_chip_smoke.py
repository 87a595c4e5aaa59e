"""chip_smoke.py on the CPU: the rehearsal runs every phase and assertion
of the chip check at tiny sizes; without a TPU and without the rehearsal
switch the script fails and prints no result; a disagreeing count fails the
phase that found it.  Plus the compile-cache root every artefact lives under
(engine.cache_root): placed from outside by JAX_COMPILATION_CACHE_DIR, else
one fixed path inside the checkout."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
#: the suite pins these off (tests/conftest.py); the smoke and the cache
#: tests need the defaults back
CACHE_FLAGS = (
    "CYCLONUS_AOT_CACHE",
    "CYCLONUS_AUTOTUNE_CACHE",
    "CYCLONUS_JAX_CACHE",
    "JAX_COMPILATION_CACHE_DIR",
)


def clean_env(**extra):
    env = {k: v for k, v in os.environ.items() if k not in CACHE_FLAGS}
    env.update(extra)
    return env


def run(argv, env, cwd=REPO, timeout=600):
    return subprocess.run(
        [sys.executable] + argv,
        capture_output=True, text=True, timeout=timeout, cwd=cwd, env=env,
    )


def files_under(path):
    return sorted(
        os.path.relpath(os.path.join(root, f), path)
        for root, _dirs, files in os.walk(path)
        for f in files
    )


class TestChipSmoke:
    def test_rehearsal_runs_every_phase_and_keeps_caches_where_placed(
        self, tmp_path
    ):
        placed = tmp_path / "cache"
        checkout_cache = os.path.join(REPO, ".cache")
        before = files_under(checkout_cache)
        proc = run(
            [SMOKE],
            clean_env(
                CHIP_SMOKE_REHEARSE="1",
                CHIP_SMOKE_OUT=str(tmp_path / "out"),
                JAX_COMPILATION_CACHE_DIR=str(placed),
            ),
        )
        assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
        out = proc.stdout
        result = json.loads(out.strip().splitlines()[-1])
        assert result["ok"] is True and result["rehearsal"] is True
        assert result["device"]["platform"] == "cpu"
        assert result["device"]["count"] == 4
        for phase in (
            "== device", "== batch counts", "== packed tile candidates",
            "== oracle pairs", "== tables", "== variant: tiered",
            "== variant: dense int8/bf16", "== variant: CIDR TSS",
            "== cli --engine tpu", "== mesh over 4 devices",
            "== serve (cold start)", "== serve (warm start",
            "== compile caches",
        ):
            assert phase in out, f"phase {phase!r} missing:\n{out[-3000:]}"
        # the routes that ran, not the ones intended
        assert "default route ['counts.classes']" in out
        assert "dense cold fused ['counts.pallas']" in out
        assert "counts.steady.packed_tuned" in out
        assert "serve.query.live=128 serve.query.degraded=0" in out
        assert out.count("'incremental'") >= 8
        # every compile artefact under the placed root, none in the checkout
        entries = files_under(str(placed))
        assert any(e.endswith("-cache") for e in entries), entries[:5]
        assert any(e.startswith("aot" + os.sep) for e in entries)
        assert files_under(checkout_cache) == before

    def test_without_tpu_it_fails_and_prints_no_result(self, tmp_path):
        proc = run(
            [SMOKE], clean_env(CHIP_SMOKE_OUT=str(tmp_path / "out")),
            timeout=300,
        )
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        # JAX's own message names the platform it could not find
        assert "SMOKE_FAIL phase='device'" in proc.stdout
        assert "backend 'tpu'" in proc.stdout
        assert "chip_smoke: FAILED" in proc.stdout

    def test_a_disagreeing_count_fails_its_phase(self, tmp_path):
        """One count off by one on the XLA route: the batch child names
        the phase and exits non-zero."""
        code = (
            "import sys; sys.argv = ['chip_smoke.py', 'batch']\n"
            "import chip_smoke\n"
            "from cyclonus_tpu.engine import TpuPolicyEngine as E\n"
            "real = E.evaluate_grid_counts\n"
            "def off_by_one(self, cases, block=1024, backend=None):\n"
            "    out = real(self, cases, block=block, backend=backend)\n"
            "    if backend == 'xla':\n"
            "        out = dict(out, combined=out['combined'] + 1)\n"
            "    return out\n"
            "E.evaluate_grid_counts = off_by_one\n"
            "sys.exit(chip_smoke.batch_main())\n"
        )
        proc = run(
            ["-c", code],
            clean_env(
                CHIP_SMOKE_REHEARSE="1",
                CHIP_SMOKE_OUT=str(tmp_path / "out"),
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
                JAX_PLATFORMS="cpu",
                CYCLONUS_PLANHARNESS="1",
            ),
        )
        assert proc.returncode != 0
        assert "SMOKE_FAIL phase='batch counts" in proc.stdout
        assert "counts differ" in proc.stdout
        assert "SMOKE_BATCH_OK" not in proc.stdout


class TestCacheRoot:
    def test_env_var_wins_for_all_three_caches(self, monkeypatch, tmp_path):
        from cyclonus_tpu import engine
        from cyclonus_tpu.engine import aot_cache, autotune

        for k in CACHE_FLAGS:
            monkeypatch.delenv(k, raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert engine.cache_root() == str(tmp_path)
        assert aot_cache.cache_dir() == str(tmp_path / "aot")
        assert autotune.cache_path() == str(tmp_path / "autotune.json")

    def test_default_is_one_fixed_path_inside_the_checkout(self, monkeypatch):
        import tempfile

        from cyclonus_tpu import engine
        from cyclonus_tpu.engine import aot_cache, autotune

        for k in CACHE_FLAGS:
            monkeypatch.delenv(k, raising=False)
        root = engine.cache_root()
        assert root == os.path.join(REPO, ".cache", "jax")
        assert "~" not in root and str(os.getpid()) not in root
        assert not root.startswith(tempfile.gettempdir() + os.sep)
        assert aot_cache.cache_dir() == os.path.join(root, "aot")
        assert autotune.cache_path() == os.path.join(root, "autotune.json")
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".cache/" in f.read().split()

    def test_zero_still_means_off(self, monkeypatch):
        from cyclonus_tpu.engine import aot_cache, autotune

        monkeypatch.setenv("CYCLONUS_AOT_CACHE", "0")
        monkeypatch.setenv("CYCLONUS_AUTOTUNE_CACHE", "0")
        assert aot_cache.cache_dir() is None
        assert autotune.cache_path() is None

    def test_no_expanduser_under_engine(self):
        engine_dir = os.path.join(REPO, "cyclonus_tpu", "engine")
        for name in os.listdir(engine_dir):
            if name.endswith(".py"):
                with open(os.path.join(engine_dir, name)) as f:
                    assert "expanduser" not in f.read(), name

    CONFIG = (
        "import jax\n"
        "from cyclonus_tpu.engine import ensure_persistent_compile_cache\n"
        "ensure_persistent_compile_cache()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n"
        "print(jax.config.jax_include_full_tracebacks_in_locations)\n"
    )

    def test_placed_from_outside_the_code_sets_no_directory(self, tmp_path):
        proc = run(
            ["-c", self.CONFIG],
            clean_env(PYTHONPATH=REPO, JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
        )
        assert proc.returncode == 0, proc.stderr[-1000:]
        # the threshold and the key hygiene apply wherever the cache lives
        assert proc.stdout.split() == [str(tmp_path), "0.1", "False"]

    def test_same_fixed_path_from_two_processes_and_working_directories(
        self, tmp_path
    ):
        want = [os.path.join(REPO, ".cache", "jax"), "0.1", "False"]
        for cwd in (REPO, str(tmp_path)):
            proc = run(["-c", self.CONFIG], clean_env(PYTHONPATH=REPO), cwd=cwd)
            assert proc.returncode == 0, proc.stderr[-1000:]
            assert proc.stdout.split() == want, cwd

    def test_a_cache_that_cannot_be_configured_warns(self, tmp_path):
        blocker = tmp_path / "file"
        blocker.write_text("")
        proc = run(
            ["-c", self.CONFIG],
            clean_env(
                PYTHONPATH=REPO, CYCLONUS_JAX_CACHE=str(blocker / "sub")
            ),
        )
        assert proc.returncode == 0, proc.stderr[-1000:]
        assert "persistent compile cache disabled" in proc.stderr
        assert proc.stdout.split()[0] == "None"
