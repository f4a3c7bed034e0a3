"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Must run before any jax import — pytest imports conftest first.  Benchmarks
(bench.py) do NOT go through here and use the real TPU.
"""

import faulthandler
import os
import signal
import subprocess
import sys

# Hang forensics (ISSUE 2, grounded in the seed suite's historical hang in
# this container): any crash dumps tracebacks, and a driver's timeout
# SIGTERM dumps EVERY thread's stack — a hung suite fails with stack traces
# instead of silently eating the time budget.  The handler then restores
# the default disposition and re-raises, so SIGTERM stays FATAL (a bare
# faulthandler.register would swallow it, turning a hung-but-killable
# suite into an unkillable one under `timeout` without --kill-after).
# Per-test stall dumps ride pytest's faulthandler_timeout (pyproject.toml).
faulthandler.enable()


def _dump_stacks_and_die(signum, frame):
    faulthandler.dump_traceback(all_threads=True)
    signal.signal(signum, signal.SIG_DFL)
    os.kill(os.getpid(), signum)


signal.signal(signal.SIGTERM, _dump_stacks_and_die)

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite runs on the host CPU
# entry points the tests call in-process (cli.main, mkscenario) place the
# persistent compile cache (utils/compile_cache.py); the suite's tiny CPU
# compiles stay out of it, and so do test_chip_compile.py's v5e programs
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402  (after the platform pinning above)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="session")
def native_bin(tmp_path_factory):
    """Build the shim and the dual-execution test binary — shared by the
    native-plugin suite and the supervision fault-injection tests."""
    subprocess.run(["make", "-C", os.path.join(_REPO, "native")], check=True,
                   capture_output=True)
    out = tmp_path_factory.mktemp("nativebin") / "testapp"
    subprocess.run(["gcc", "-O1", "-o", str(out),
                    os.path.join(_REPO, "tests", "native_src", "testapp.c"),
                    "-lpthread"],
                   check=True, capture_output=True)
    return str(out)
