"""Smoke tests: every example must run end to end.

Examples run CPU-pinned for determinism; the `slow` device test re-runs
the movie-ratings example with no platform pin where JAX finds an
accelerator.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXAMPLES = [
    ["examples/movie_view_ratings/run_local.py", "--rows", "5000"],
    [
        "examples/movie_view_ratings/run_without_frameworks.py",
        "--generate_rows", "5000", "--local"
    ],
    [
        "examples/movie_view_ratings/run_without_frameworks.py",
        "--generate_rows", "5000", "--pld_accounting", "--local"
    ],
    [
        "examples/movie_view_ratings/run_without_frameworks.py",
        "--generate_rows", "5000", "--streaming"
    ],
    ["examples/restaurant_visits/run_private_api.py", "--rows", "1000"],
    ["examples/restaurant_visits/run_parameter_tuning.py", "--rows", "1000"],
    ["examples/codelab/codelab.py"],
    [
        "examples/movie_view_ratings/run_multihost_ingest.py",
        "--generate_rows", "5000", "--hosts", "3"
    ],
    ["examples/experimental/custom_combiners.py", "--generate_rows", "5000"],
    ["examples/quickstart.py", "--rows", "2000"],
    ["examples/service_demo.py", "--rows", "1000"],
]


@pytest.mark.parametrize("cmd", EXAMPLES,
                         ids=lambda c: " ".join([c[0]] + c[3:]))
def test_example_runs(cmd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable] + cmd, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip(), "example produced no output"


FRAMEWORK_EXAMPLES = [
    ["examples/movie_view_ratings/run_on_beam.py", "--generate_rows", "5000"],
    [
        "examples/movie_view_ratings/run_on_spark.py", "--generate_rows",
        "5000"
    ],
    ["examples/experimental/beam_combine_fn.py", "--generate_rows", "5000"],
]

# Success marker each framework script prints (default: the shared
# count+sum line of the movie_view_ratings scripts).
FRAMEWORK_MARKERS = {
    "examples/experimental/beam_combine_fn.py": "movies; first 3:",
}


@pytest.mark.parametrize("cmd", FRAMEWORK_EXAMPLES, ids=lambda c: c[0])
def test_framework_example_runs(cmd):
    """Beam/Spark example scripts over the in-memory fake runners."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(REPO, "tests", "fake_runners")
    proc = subprocess.run([sys.executable] + cmd, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    marker = FRAMEWORK_MARKERS.get(cmd[0], "computed DP count+sum")
    assert marker in proc.stdout


@pytest.mark.slow
def test_movie_example_on_device():
    """The real-file-format example on the actual device path.

    One child, no probe: this process is pinned to CPU by conftest and
    never holds the chip, so the example is the one process that may
    take it (a second, probing child would only race it). Where JAX
    finds no accelerator the child fails at backend start-up and the
    test skips. chip_smoke.py's dense_file phase is the chip gate for
    the same parser path; this stays for the example script itself.
    """
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable,
         "examples/movie_view_ratings/run_without_frameworks.py",
         "--generate_rows", "20000"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1500)
    if proc.returncode != 0 and "Unable to initialize backend" in proc.stderr:
        pytest.skip("JAX found no accelerator")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "computed DP metrics" in proc.stdout
