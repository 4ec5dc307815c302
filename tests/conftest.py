"""Test configuration: force an 8-device virtual CPU platform.

Mirrors the reference's test strategy of running distributed logic on a CPU
fallback backend (SURVEY.md §4: gloo in CI; here a virtual CPU mesh), so all
sharding/collective paths execute without TPU hardware.
"""

import os

# The tests run on the CPU with eight virtual devices wherever they are
# started, a machine with a chip included: set before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
# Tier-1's time is XLA compiling for the CPU (of a reference file's two
# minutes, all but a few seconds are tracing, lowering and compiling): the
# tests certify results, control flow and counts, never a time, so LLVM
# optimises nothing here (a third less CPU time a file, every case passing
# at the tolerance it had).  A flag named outside stands.
flags = os.environ.get("XLA_FLAGS", "")
for name, value in (
    ("xla_force_host_platform_device_count", 8),
    ("xla_backend_optimization_level", 0),
):
    if name not in flags:
        flags += f" --{name}={value}"
os.environ["XLA_FLAGS"] = flags.strip()

import contextlib  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


# ``--dist loadfile`` hands a worker the next FILE in collection order, so a
# file of minutes that sorts late (``test_step_scopes.py`` is the 106th of
# 116) starts when the others end and is the run's tail: the run then takes
# its start plus its length, whatever the total.  The three files that take
# five minutes and more of a worker (junit case-seconds of a whole run,
# ROADMAP D12) start first; the rest keep their order (sixteen files first,
# longest first, read the same wall: 1,116 s beside 1,119).  A name that
# is gone only costs order, never a case.
STARTS_FIRST = (
    "test_benchmark_program_spans.py", "test_benchmark_rehearsal.py",
    "test_step_scopes.py",
)


def pytest_collection_modifyitems(items):
    place = {name: at for at, name in enumerate(STARTS_FIRST)}
    # (stable: a file's cases keep their order)
    items.sort(key=lambda item: place.get(item.path.name, len(place)))


def _cpu_child_env(base=None):
    """Subprocess env on the CPU backend (a child must never take a chip
    away from, or wait for one held by, the process that runs the tests)."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.fixture
def cpu_child_env():
    """Fixture, not a cross-module import: pytest loads conftest.py as the
    top-level ``conftest`` module, so ``from tests.conftest import ...``
    would re-execute it as a duplicate namespace-package module."""
    return _cpu_child_env()


@pytest.fixture(autouse=True, scope="module")
def compiled_programs_end_with_their_file():
    """A worker runs some fifty files in one process, and every program
    XLA compiles for the CPU stays mapped for as long as a jit cache holds
    it: about eight memory maps a program (11,101 maps after
    ``test_olmoe_reference.py`` alone, 711 once the caches are dropped), and
    the kernel refuses a process its 65,531st (``vm.max_map_count``): the
    compile that asks for it segfaults, the worker is gone and the run hangs
    on what it held (seen once in five whole runs).  No file reads what
    another compiled, so the caches are dropped when a file ends."""
    yield
    import gc

    import jax

    jax.clear_caches()
    gc.collect()


CASE_LIMIT_S = 300


@contextlib.contextmanager
def limited(name):
    """Raises ``TimeoutError`` naming ``name`` in the main thread once
    ``CASE_LIMIT_S`` seconds have passed inside; on the way out the alarm is
    off and the handler the one it found.  Off the main thread nothing is
    armed."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    seconds = CASE_LIMIT_S

    def late(signum, frame):
        raise TimeoutError(f"{name} waited past {seconds} s")

    before = signal.signal(signal.SIGALRM, late)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture(autouse=True)
def a_case_that_waits_fails_alone(request):
    """A case that waits past ``CASE_LIMIT_S`` (the longest takes 97 s under
    load) fails by name and the run goes on: without it a hung case holds
    its worker until the run's own limit cuts every case after it.  (xdist
    runs a case on its worker's main thread.)"""
    with limited(request.node.nodeid):
        yield


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def tap():
    """Everything the process-wide recorder records during the test."""
    from dlrover_tpu.common import telemetry

    recorder = telemetry.recorder()
    was_enabled = recorder.enabled
    recorder.configure(enabled=True)
    with recorder.open_tap() as held:
        yield held
    recorder.configure(enabled=was_enabled)


@pytest.fixture(scope="module")
def one_step_program():
    """For cases of one module that run the same step program under
    different trainers: it is traced and compiled by the first of them and
    the build cache hands it to the rest, so ``trace_count("train_step")``
    reads 1 after each."""
    from dlrover_tpu.trainer import train_lib

    train_lib.reset_build_cache()
    train_lib.reset_trace_counts()


@pytest.fixture
def small_pieces(monkeypatch):
    """The staged save path's sizes, cut so that test-size leaves are
    staged: 4 KiB pieces, 8 KiB in flight, from 1 KiB a block."""
    from dlrover_tpu.checkpoint import shm_handler

    monkeypatch.setattr(shm_handler, "_STAGED_MIN_BYTES", 1024)
    monkeypatch.setattr(shm_handler, "_PIECE_BYTES", 4096)
    monkeypatch.setattr(shm_handler, "_IN_FLIGHT_BYTES", 8192)
    return shm_handler
