"""How the program starts on a chip, held on the CPU (ISSUE 21).

- compile-cache placement: one rule, identical from `caffe`, the serving
  engine and the tools (utils/compile_cache.py);
- chip_smoke.py and benchmarks/run.py refuse a non-TPU platform by
  name; chip_smoke.py's parent — like every launcher of device
  children — never imports jax;
- an unknown accelerator has no peak and no MFU: `peak_flops` raises.

(That interpret mode is the `cpu` platform's alone is held next to the
Mosaic compiles, in tests/test_tpu_aot_compile.py.)
"""

import json
import os
import subprocess
import sys
import time
import types

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code: str, **env) -> subprocess.CompletedProcess:
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT, **env)
    return subprocess.run([sys.executable, "-c", code], env=full, cwd=_ROOT,
                          capture_output=True, text=True, timeout=120)


# the spellings that used to disagree: cli.main's device commands and
# ServingEngine.__init__ (home directory) against the tools
# (<repo>/.jax_cache, passed as an argument — held statically below)
_CACHE_PROBE = """
import json, sys
import jax
from caffe_mpi_tpu.utils import compile_cache as cc
seen = {}
seen["function"] = cc.enable_compile_cache()
seen["after_function"] = jax.config.jax_compilation_cache_dir
jax.config.update("jax_compilation_cache_dir", None)

from caffe_mpi_tpu.serving import ServingEngine
ServingEngine(start=False).close()
seen["engine"] = jax.config.jax_compilation_cache_dir
jax.config.update("jax_compilation_cache_dir", None)

from caffe_mpi_tpu.tools import cli
try:
    cli.main(["test", "-model", "/nonexistent.prototxt"])
except OSError:
    pass
seen["cli"] = jax.config.jax_compilation_cache_dir
jax.config.update("jax_compilation_cache_dir", None)
seen["checkout"] = cc.CHECKOUT_CACHE_DIR
print(json.dumps(seen))
"""


class TestCompileCachePlacement:
    def test_unset_means_checkout_dir_from_every_entry_point(self):
        r = _python(_CACHE_PROBE)
        assert r.returncode == 0, r.stderr[-2000:]
        seen = json.loads(r.stdout.strip().splitlines()[-1])
        want = os.path.join(_ROOT, ".jax_cache")
        assert seen["checkout"] == want
        assert seen["function"] == seen["after_function"] == want
        assert seen["engine"] == want
        assert seen["cli"] == want

    def test_env_set_means_no_directory_set_in_code(self, tmp_path):
        placed = str(tmp_path / "placed")
        # jax itself reads the variable into its config at import; the
        # program must leave it exactly there — reset-to-None after each
        # entry point would show any code path that sets a directory
        r = _python(_CACHE_PROBE, JAX_COMPILATION_CACHE_DIR=placed)
        assert r.returncode == 0, r.stderr[-2000:]
        seen = json.loads(r.stdout.strip().splitlines()[-1])
        assert seen["function"] == placed
        assert seen["after_function"] == placed   # jax's own reading
        assert seen["engine"] is None
        assert seen["cli"] is None

    def test_every_caller_uses_the_one_spelling(self):
        """One function, no argument: the smoke, the benchmark, the
        tools and the package cannot place the cache anywhere of their
        own."""
        import re
        callers = []
        for top in ("chip_smoke.py", "benchmarks", "tools", "caffe_mpi_tpu"):
            path = os.path.join(_ROOT, top)
            files = [path] if path.endswith(".py") else [
                os.path.join(d, f) for d, _, fs in os.walk(path)
                for f in fs if f.endswith(".py")]
            for f in files:
                text = open(f).read()
                for m in re.finditer(r"enable_compile_cache\(([^)]*)\)",
                                     text):
                    if not text[:m.start()].endswith("def "):
                        callers.append((os.path.relpath(f, _ROOT),
                                        m.group(1).strip()))
        assert {f for f, _ in callers} >= {
            "chip_smoke.py", "benchmarks/run.py",
            "caffe_mpi_tpu/tools/cli.py",
            "caffe_mpi_tpu/serving/engine.py"}
        assert all(arg == "" for _, arg in callers), callers


class TestChipSmokeOffChip:
    def test_fails_fast_naming_cpu_and_prints_no_result(self):
        t0 = time.monotonic()
        r = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=_ROOT,
            capture_output=True, text=True, timeout=120)
        took = time.monotonic() - t0
        assert r.returncode != 0
        assert "'cpu'" in r.stderr
        assert '"ok": true' not in r.stdout
        assert took < 60, f"took {took:.0f}s to notice there is no chip"

    def test_parent_never_imports_jax(self):
        code = (
            "import sys\n"
            "import chip_smoke\n"
            "from caffe_mpi_tpu.utils import subproc\n"
            "calls = []\n"
            "def fake(cmd, timeout, **kw):\n"
            "    calls.append(cmd[-1])\n"
            "    return 3, '', \"needs platform 'tpu'; jax found 'cpu'\"\n"
            "subproc.run_contained = fake\n"
            "chip_smoke.build_native = lambda: None\n"
            "rc = chip_smoke.run_all()\n"
            "assert rc == 1 and calls == ['train-f32'], (rc, calls)\n"
            "assert 'jax' not in sys.modules, 'parent imported jax'\n")
        r = _python(code)
        assert r.returncode == 0, r.stderr[-2000:]

    def test_a_dp4_leg_asked_for_by_name_names_the_device_count(self):
        code = (
            "import jax, chip_smoke\n"
            "try:\n"
            "    chip_smoke.leg_train(gpu_all=True, on_chip=False)\n"
            "except chip_smoke.SmokeFailure as e:\n"
            "    print('MSG', e)\n")
        r = _python(code, XLA_FLAGS="")
        assert "needs >= 4 devices, jax found 1" in r.stdout, \
            r.stdout + r.stderr[-1500:]


class TestLaunchersStayOffJax:
    """One process per chip: a parent that only launches children must
    not import jax (a parent that has touched the backend holds the chip
    its child needs)."""

    def test_supervised_train_parent(self):
        code = (
            "import sys\n"
            "from caffe_mpi_tpu.tools import cli\n"
            "from caffe_mpi_tpu.utils import resilience\n"
            "resilience.supervise = lambda *a, **k: 0\n"
            "rc = cli.main(['train', '-solver',\n"
            "               'models/lenet/lenet_solver.prototxt',\n"
            "               '-max_restarts', '2'])\n"
            "assert rc == 0\n"
            "assert 'jax' not in sys.modules, 'supervisor imported jax'\n")
        r = _python(code)
        assert r.returncode == 0, r.stderr[-2000:]

    def test_the_benchmark_exits_nonzero_without_a_tpu(self):
        """The one benchmark command runs in the process that holds the
        chip (it imports jax itself: there is no parent to keep off it),
        and off a TPU it names the platform and prints no result."""
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("CAFFE_")}   # run.py refuses those first
        r = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "benchmarks", "run.py"),
             "--workload", "alexnet_f32", "--seed", "0", "--seconds", "1",
             "--trace", "0"],
            env=dict(env, JAX_PLATFORMS="cpu"), cwd=_ROOT,
            capture_output=True, text=True, timeout=300)
        assert r.returncode != 0, "no TPU must not be reported as success"
        assert "'cpu'" in r.stderr and "No result" in r.stderr
        assert '"correct"' not in r.stdout and '"metrics"' not in r.stdout


class TestPeakFlops:
    def test_known_kind(self):
        from caffe_mpi_tpu.utils.flops import peak_flops
        dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        assert peak_flops(dev) == 197e12

    def test_cpu_has_no_peak(self):
        import jax
        from caffe_mpi_tpu.utils.flops import peak_flops
        assert peak_flops(jax.devices()[0]) is None

    def test_unknown_accelerator_raises(self):
        from caffe_mpi_tpu.utils.flops import peak_flops
        dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v9z")
        with pytest.raises(ValueError, match="TPU v9z"):
            peak_flops(dev)
        gpu = types.SimpleNamespace(platform="gpu", device_kind="H100")
        with pytest.raises(ValueError, match="H100"):
            peak_flops(gpu)
