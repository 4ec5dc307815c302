"""Unit tests for the tracelint static-analysis framework.

Every rule gets a positive fixture (a distilled version of the incident
that motivated it) and a negative fixture (the sanctioned spelling of the
same pattern), plus round-trips for inline suppressions, the baseline
file, and the CLI exit-code contract.  Fixtures are analyzed in-process
via ``run_paths`` — no subprocess per case — so the whole module stays
fast; the CLI itself is exercised once at the end and by
``tests/test_lint_gate.py``.
"""

import json
import os
import subprocess
import sys

import pytest

from dlrover_tpu.analysis import (
    all_rules,
    load_baseline,
    run_paths,
    write_baseline,
)
from dlrover_tpu.analysis.engine import (
    EXIT_CLEAN,
    EXIT_ERROR,
    EXIT_FINDINGS,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_RULE_IDS = {
    "TRC001", "TRC002", "TRC003", "CMP001", "THR001", "LOG001", "RTY001",
    "DON001", "DON002", "SHD001", "SHD002", "SEAM001",
    "CKY001", "TEL001", "LCK001",
}


def lint(tmp_path, name, source, select=None, baseline=None):
    """Write ``source`` under ``tmp_path`` and analyze just that file."""
    path = tmp_path / name
    path.write_text(source)
    return run_paths(
        [str(path)], select=select, baseline=baseline, root=str(tmp_path)
    )


def lint_files(tmp_path, files, select=None, baseline=None):
    """Write a whole fixture tree and analyze it — the project-scope
    rules (CKY001/TEL001) need several modules linked by imports."""
    for name, source in files.items():
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(source)
    return run_paths(
        [str(tmp_path)], select=select, baseline=baseline,
        root=str(tmp_path),
    )


def rule_ids(report):
    return sorted({f.rule for f in report.findings})


def test_all_rules_registered():
    assert {r.id for r in all_rules()} == ALL_RULE_IDS


# -- TRC001: flax module construction inside a scan-like body --------------

TRC001_BAD = """\
import flax.linen as nn
import jax

def outer(params, xs):
    def body(carry, x):
        proj = nn.Dense(4)
        return carry, proj(x)
    return jax.lax.scan(body, params, xs)
"""

# Construction under jit (outside scan bodies) is the standard linen
# idiom — module __init__ is metadata-only there.
TRC001_OK = """\
import flax.linen as nn
import jax

@jax.jit
def apply(params, x):
    model = nn.Dense(4)
    return model.apply(params, x)
"""


def test_trc001_fires_on_module_in_scan_body(tmp_path):
    report = lint(tmp_path, "m.py", TRC001_BAD)
    assert rule_ids(report) == ["TRC001"]
    assert "nn.Dense" in report.findings[0].message


def test_trc001_allows_module_under_jit(tmp_path):
    report = lint(tmp_path, "m.py", TRC001_OK, select=["TRC001"])
    assert report.findings == []


# -- TRC002: host sync on the hot step path --------------------------------

TRC002_BAD = """\
import jax

class Trainer:
    def fit(self, batches):
        for batch in batches:
            out = self.step(batch)
            loss = float(out)
            host = jax.device_get(out)
        return loss
"""

TRC002_OK = """\
import jax

class Trainer:
    def fit(self, batches):
        for batch in batches:
            out = self.step(batch)
        with pipeline_counters().host_block("metrics_flush"):
            host = jax.device_get(out)
        return host
"""


def test_trc002_fires_in_hot_file(tmp_path):
    report = lint(tmp_path, "elastic_trainer.py", TRC002_BAD)
    assert rule_ids(report) == ["TRC002"]
    assert len(report.findings) == 2  # float(out) + device_get


def test_trc002_sanctioned_host_block(tmp_path):
    report = lint(tmp_path, "elastic_trainer.py", TRC002_OK)
    assert report.findings == []


def test_trc002_ignores_cold_files(tmp_path):
    report = lint(tmp_path, "not_hot.py", TRC002_BAD)
    assert report.findings == []


# -- TRC003: host impurity inside traced code ------------------------------

TRC003_BAD = """\
import time
import jax

@jax.jit
def step(x):
    return x * time.time()
"""

TRC003_OK = """\
import time

def wall_clock():
    return time.time()
"""


def test_trc003_fires_inside_traced_fn(tmp_path):
    report = lint(tmp_path, "m.py", TRC003_BAD)
    assert rule_ids(report) == ["TRC003"]
    assert "time.time" in report.findings[0].message


def test_trc003_allows_host_side_clock(tmp_path):
    report = lint(tmp_path, "m.py", TRC003_OK, select=["TRC003"])
    assert report.findings == []


# -- CMP001: version-gated APIs without the compat shim --------------------

CMP001_BAD = """\
import tomllib
import jax

def activate(mesh):
    jax.set_mesh(mesh)
"""

CMP001_OK = """\
try:
    import tomllib
except ImportError:
    tomllib = None
import jax

def activate(mesh):
    if hasattr(jax, "set_mesh"):
        jax.set_mesh(mesh)
"""


def test_cmp001_fires_on_ungated_uses(tmp_path):
    report = lint(tmp_path, "m.py", CMP001_BAD)
    assert rule_ids(report) == ["CMP001"]
    symbols = {f.symbol for f in report.findings}
    assert symbols == {"import:tomllib", "jax.set_mesh"}


def test_cmp001_allows_probed_uses(tmp_path):
    report = lint(tmp_path, "m.py", CMP001_OK, select=["CMP001"])
    assert report.findings == []


def test_cmp001_exempts_the_shim_module(tmp_path):
    report = lint(tmp_path, "mesh.py", CMP001_BAD, select=["CMP001"])
    # The shim file may touch gated JAX names; the tomllib import gate
    # still applies everywhere.
    assert {f.symbol for f in report.findings} == {"import:tomllib"}


# -- THR001: cross-thread attribute without a lock -------------------------

THR001_BAD = """\
import threading

class Pump:
    def __init__(self):
        self.count = 0
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while True:
            self.count += 1

    def snapshot(self):
        return self.count
"""

THR001_OK = """\
import threading

class Pump:
    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while True:
            with self._lock:
                self.count += 1

    def snapshot(self):
        return self.count
"""

THR001_QUEUE_OK = """\
import multiprocessing as mp
import threading

class Feeder:
    def _start(self):
        ctx = mp.get_context("spawn")
        self._task_queue = ctx.Queue(maxsize=4)
        threading.Thread(target=self._feed, daemon=True).start()

    def _feed(self):
        while True:
            self._task_queue.put(1)
"""


def test_thr001_fires_on_unlocked_cross_thread_write(tmp_path):
    report = lint(tmp_path, "m.py", THR001_BAD)
    assert rule_ids(report) == ["THR001"]
    assert report.findings[0].symbol == "Pump.count"


def test_thr001_locked_write_is_clean(tmp_path):
    report = lint(tmp_path, "m.py", THR001_OK, select=["THR001"])
    assert report.findings == []


def test_thr001_mp_queue_attr_is_threadsafe(tmp_path):
    report = lint(tmp_path, "m.py", THR001_QUEUE_OK, select=["THR001"])
    assert report.findings == []


# -- LOG001: eagerly formatted logging -------------------------------------

LOG001_BAD = """\
import logging

logger = logging.getLogger(__name__)

def report(step, loss):
    logger.info(f"step {step}")
    logger.warning("loss %s" % loss)
    logger.error("msg: {}".format(step))
"""

LOG001_OK = """\
import logging

logger = logging.getLogger(__name__)

def report(step, loss):
    logger.info("step %d loss %.3f", step, loss)
"""


def test_log001_fires_on_eager_formats(tmp_path):
    report = lint(tmp_path, "m.py", LOG001_BAD)
    assert rule_ids(report) == ["LOG001"]
    assert len(report.findings) == 3


def test_log001_lazy_template_is_clean(tmp_path):
    report = lint(tmp_path, "m.py", LOG001_OK, select=["LOG001"])
    assert report.findings == []


# -- RTY001: hand-rolled retry loops + silent swallows ---------------------

RTY001_RETRY_LOOP = """\
import time

def fetch(url):
    for attempt in range(5):
        try:
            return do_fetch(url)
        except ConnectionError:
            time.sleep(2 ** attempt)
    raise RuntimeError("gave up")
"""

# The sanctioned spelling: no sleep in the handler, the policy owns it.
RTY001_OK_POLICY = """\
from dlrover_tpu.common.retry import RetryPolicy

def fetch(url):
    return RetryPolicy(max_attempts=5).call(do_fetch, url)
"""

# A poll loop that sleeps OUTSIDE the except handler is not a retry loop.
RTY001_OK_POLL = """\
import time

def watch(poll):
    while True:
        try:
            poll()
        except StopIteration:
            break
        time.sleep(1.0)
"""

RTY001_SWALLOW = """\
def shutdown(client):
    try:
        client.close()
    except Exception:
        pass
"""


def test_rty001_fires_on_catch_sleep_retry_loop(tmp_path):
    report = lint(tmp_path, "m.py", RTY001_RETRY_LOOP, select=["RTY001"])
    assert rule_ids(report) == ["RTY001"]
    assert "RetryPolicy" in report.findings[0].message


def test_rty001_policy_call_and_poll_loop_are_clean(tmp_path):
    for src in (RTY001_OK_POLICY, RTY001_OK_POLL):
        report = lint(tmp_path, "m.py", src, select=["RTY001"])
        assert report.findings == []


def test_rty001_retry_home_module_is_exempt(tmp_path):
    (tmp_path / "common").mkdir()
    report = lint(
        tmp_path, os.path.join("common", "retry.py"),
        RTY001_RETRY_LOOP, select=["RTY001"],
    )
    assert report.findings == []


def test_rty001_swallow_fires_only_in_failure_tiers(tmp_path):
    (tmp_path / "agent").mkdir()
    report = lint(
        tmp_path, os.path.join("agent", "m.py"),
        RTY001_SWALLOW, select=["RTY001"],
    )
    assert rule_ids(report) == ["RTY001"]
    # The same code outside agent/master/checkpoint is tolerated.
    report = lint(tmp_path, "util.py", RTY001_SWALLOW, select=["RTY001"])
    assert report.findings == []


# -- DON001: use-after-donate ----------------------------------------------

DON001_BAD = """\
import jax

step = jax.jit(train_step, donate_argnums=(0,))

def fit(state, batches):
    for batch in batches:
        out = step(state, batch)
    return state.params
"""

# The serving donated-pool idiom: the KV pool is donated to insert and
# the result is rebound over the operand in the same statement — the
# stale binding dies with the statement, so the pattern is clean.
DON001_OK_POOL = """\
import jax

class Engine:
    def __init__(self, fn):
        self._insert = jax.jit(fn, donate_argnums=(0,))

    def admit(self, pool, rows):
        for row, slot in rows:
            pool = self._insert(pool, row, slot)
        return pool

    def admit_cached(self, row, slot):
        self.cache = self._insert(self.cache, row, slot)
        return self.cache
"""

# AOT lowering reads shapes only; .lower on the jitted callable does not
# consume the buffer.
DON001_OK_AOT = """\
import jax

class Engine:
    def __init__(self, fn):
        self._insert = jax.jit(fn, donate_argnums=(0,))

    def warm(self, pool, row, slot):
        lowered = self._insert.lower(pool, row, slot)
        return lowered.compile(), pool
"""


def test_don001_fires_on_read_after_donate(tmp_path):
    report = lint(tmp_path, "m.py", DON001_BAD, select=["DON001"])
    assert rule_ids(report) == ["DON001"]
    finding = report.findings[0]
    assert "'state'" in finding.message
    assert finding.symbol == "fit:state"


def test_don001_branch_read_fires(tmp_path):
    src = """\
import jax

step = jax.jit(f, donate_argnums=(0,))

def g(state, flag):
    out = step(state, 1)
    if flag:
        return state
    return out
"""
    report = lint(tmp_path, "m.py", src, select=["DON001"])
    assert rule_ids(report) == ["DON001"]


def test_don001_donate_argnames_fires(tmp_path):
    src = """\
import jax

step = jax.jit(f, donate_argnames=("state",))

def g(s):
    out = step(state=s)
    return s
"""
    report = lint(tmp_path, "m.py", src, select=["DON001"])
    assert rule_ids(report) == ["DON001"]


def test_don001_serving_pool_idiom_is_clean(tmp_path):
    for src in (DON001_OK_POOL, DON001_OK_AOT):
        report = lint(tmp_path, "m.py", src, select=["DON001"])
        assert report.findings == []


def test_don001_conditional_donation_fires(tmp_path):
    # train_lib's "(0,) if donate_state else ()" spelling still donates
    # on some configuration — lint treats it as donating.
    src = """\
import jax

step = jax.jit(f, donate_argnums=(0,) if DONATE else ())

def g(state):
    out = step(state, 1)
    return state.params, out
"""
    report = lint(tmp_path, "m.py", src, select=["DON001"])
    assert rule_ids(report) == ["DON001"]


# -- DON002: donated binding captured by a closure -------------------------

DON002_BAD = """\
import jax

step = jax.jit(f, donate_argnums=(0,))

def outer(state):
    def peek():
        return state.params
    out = step(state, 1)
    return out, peek
"""

DON002_OK_REBOUND = """\
import jax

step = jax.jit(f, donate_argnums=(0,))

def outer(state):
    def peek():
        return state.params
    state = step(state, 1)
    return state, peek
"""


def test_don002_fires_on_closure_capture(tmp_path):
    report = lint(tmp_path, "m.py", DON002_BAD, select=["DON002"])
    assert rule_ids(report) == ["DON002"]
    assert "closure" in report.findings[0].message


def test_don002_rebound_operand_is_clean(tmp_path):
    report = lint(tmp_path, "m.py", DON002_OK_REBOUND, select=["DON002"])
    assert report.findings == []


# -- SHD001: PartitionSpec axis drift --------------------------------------

SHD001_BAD = """\
from jax.sharding import PartitionSpec as P

SPEC = P("dp", None)
"""

SHD001_OK_CANONICAL = """\
from jax.sharding import PartitionSpec as P

SPEC = P(("data", "fsdp"), None)
"""

SHD001_OK_LOCAL_MESH = """\
from jax.sharding import Mesh, PartitionSpec as P

mesh = Mesh(devices, ("rows", "cols"))
SPEC = P("rows")
"""


def test_shd001_fires_on_unknown_axis(tmp_path):
    report = lint(tmp_path, "m.py", SHD001_BAD, select=["SHD001"])
    assert rule_ids(report) == ["SHD001"]
    assert report.findings[0].symbol == "axis:dp"


def test_shd001_canonical_and_local_mesh_axes_are_clean(tmp_path):
    for src in (SHD001_OK_CANONICAL, SHD001_OK_LOCAL_MESH):
        report = lint(tmp_path, "m.py", src, select=["SHD001"])
        assert report.findings == []


def test_shd001_resolves_module_constants(tmp_path):
    src = """\
from jax.sharding import PartitionSpec as P

ROW_AXIS = "tesnor"
SPEC = P(ROW_AXIS)
"""
    report = lint(tmp_path, "m.py", src, select=["SHD001"])
    assert rule_ids(report) == ["SHD001"]
    assert report.findings[0].symbol == "axis:tesnor"


# -- SHD002: spec rank exceeds the array's known rank ----------------------

SHD002_BAD = """\
import jax.numpy as jnp
from jax.lax import with_sharding_constraint
from jax.sharding import PartitionSpec as P

def f():
    x = jnp.zeros((4, 8))
    x = with_sharding_constraint(x, P("data", "fsdp", "tensor"))
    return x
"""

SHD002_OK = """\
import jax.numpy as jnp
from jax.lax import with_sharding_constraint
from jax.sharding import PartitionSpec as P

def f():
    x = jnp.zeros((4, 8))
    x = with_sharding_constraint(x, P("data", "fsdp"))
    return x
"""


def test_shd002_fires_on_rank_overflow(tmp_path):
    report = lint(tmp_path, "m.py", SHD002_BAD, select=["SHD002"])
    assert rule_ids(report) == ["SHD002"]
    assert "rank 2" in report.findings[0].message


def test_shd002_matching_rank_and_unknown_rank_are_clean(tmp_path):
    report = lint(tmp_path, "m.py", SHD002_OK, select=["SHD002"])
    assert report.findings == []
    # Rank not statically derivable (function argument): stay silent.
    unknown = SHD002_OK.replace("x = jnp.zeros((4, 8))", "x = get()")
    report = lint(tmp_path, "m.py", unknown, select=["SHD002"])
    assert report.findings == []


# -- SEAM001: raw I/O outside Faultline ------------------------------------

SEAM001_BAD = """\
import os

def persist(path, blob):
    tmp = path + ".tmp"
    with open(tmp, "wb") as fh:
        fh.write(blob)
    os.replace(tmp, path)
"""

SEAM001_OK = """\
import os
from dlrover_tpu.common import faults

def persist(path, blob):
    faults.fire("storage.write", path=path)
    tmp = path + ".tmp"
    with open(tmp, "wb") as fh:
        fh.write(blob)
    os.replace(tmp, path)
"""

SEAM001_UNREGISTERED = """\
import os
from dlrover_tpu.common import faults

def persist(path, blob):
    faults.fire("made.up.seam")
    os.replace(path + ".tmp", path)
"""


def test_seam001_fires_in_fault_tiers(tmp_path):
    (tmp_path / "agent").mkdir()
    report = lint(
        tmp_path, os.path.join("agent", "m.py"),
        SEAM001_BAD, select=["SEAM001"],
    )
    assert rule_ids(report) == ["SEAM001"]
    kinds = {f.symbol for f in report.findings}
    assert kinds == {"persist:open-for-write", "persist:os.replace"}


def test_seam001_registered_seam_covers_the_function(tmp_path):
    (tmp_path / "checkpoint").mkdir()
    report = lint(
        tmp_path, os.path.join("checkpoint", "m.py"),
        SEAM001_OK, select=["SEAM001"],
    )
    assert report.findings == []


def test_seam001_unregistered_seam_does_not_count(tmp_path):
    (tmp_path / "master").mkdir()
    report = lint(
        tmp_path, os.path.join("master", "m.py"),
        SEAM001_UNREGISTERED, select=["SEAM001"],
    )
    assert rule_ids(report) == ["SEAM001"]


def test_seam001_ignores_cold_tiers(tmp_path):
    report = lint(tmp_path, "serving.py", SEAM001_BAD, select=["SEAM001"])
    assert report.findings == []


def test_seam001_covers_the_embedding_tier(tmp_path):
    """embedding/ is a fault tier: its spill logs and table exports are
    remote-storage-shaped I/O, so raw open/replace without a registered
    seam in scope is a finding — and the embed seams count as coverage."""
    (tmp_path / "embedding").mkdir()
    report = lint(
        tmp_path, os.path.join("embedding", "m.py"),
        SEAM001_BAD, select=["SEAM001"],
    )
    assert rule_ids(report) == ["SEAM001"]
    covered = SEAM001_BAD.replace(
        "def persist(path, blob):",
        "from dlrover_tpu.common import faults\n"
        "def persist(path, blob):\n"
        '    faults.fire("embed.reshard", src=2, dst=4)',
    )
    report = lint(
        tmp_path, os.path.join("embedding", "m2.py"),
        covered, select=["SEAM001"],
    )
    assert report.findings == []


SEAM001_READ_BAD = """\
def load(path):
    with open(path) as fh:
        return fh.read()
"""

SEAM001_READ_OK = """\
from dlrover_tpu.common import faults

def load(path):
    faults.fire("storage.read", path=path)
    with open(path) as fh:
        return fh.read()
"""


def test_seam001_flags_uncovered_reads_in_fault_tiers(tmp_path):
    """A read that silently swallows I/O errors is exactly the path a
    storage drill needs to reach — uncovered ``open``-for-read in a fault
    tier fires, and a ``storage.read`` seam covers it."""
    (tmp_path / "data").mkdir()
    report = lint(
        tmp_path, os.path.join("data", "m.py"),
        SEAM001_READ_BAD, select=["SEAM001"],
    )
    assert rule_ids(report) == ["SEAM001"]
    assert {f.symbol for f in report.findings} == {"load:open-for-read"}
    report = lint(
        tmp_path, os.path.join("data", "ok.py"),
        SEAM001_READ_OK, select=["SEAM001"],
    )
    assert report.findings == []


def test_seam001_proc_reads_are_exempt(tmp_path):
    """/proc pseudo-files are kernel state, not storage: no seam owed."""
    (tmp_path / "agent").mkdir()
    proc_only = """\
def cpu_times():
    with open("/proc/stat") as fh:
        return fh.read()
"""
    report = lint(
        tmp_path, os.path.join("agent", "m.py"),
        proc_only, select=["SEAM001"],
    )
    assert report.findings == []


# -- suppressions ----------------------------------------------------------

def test_inline_suppression_silences_one_rule(tmp_path):
    src = TRC003_BAD.replace(
        "return x * time.time()",
        "return x * time.time()  # tracelint: disable=TRC003",
    )
    report = lint(tmp_path, "m.py", src)
    assert report.findings == []
    assert report.suppressed == 1


def test_inline_suppression_disable_all(tmp_path):
    src = CMP001_BAD.replace(
        "jax.set_mesh(mesh)",
        "jax.set_mesh(mesh)  # tracelint: disable=all",
    )
    report = lint(tmp_path, "m.py", src, select=["CMP001"])
    assert {f.symbol for f in report.findings} == {"import:tomllib"}
    assert report.suppressed == 1


def test_suppression_for_other_rule_does_not_silence(tmp_path):
    src = TRC003_BAD.replace(
        "return x * time.time()",
        "return x * time.time()  # tracelint: disable=LOG001",
    )
    report = lint(tmp_path, "m.py", src)
    assert rule_ids(report) == ["TRC003"]
    assert report.suppressed == 0


# -- baseline --------------------------------------------------------------

def test_baseline_round_trip(tmp_path):
    report = lint(tmp_path, "m.py", CMP001_BAD)
    assert len(report.findings) == 2

    baseline_path = tmp_path / "baseline.json"
    write_baseline(str(baseline_path), report.findings)
    baseline = load_baseline(str(baseline_path))
    assert len(baseline) == 2

    again = lint(tmp_path, "m.py", CMP001_BAD, baseline=baseline)
    assert again.findings == []
    assert again.baselined == 2


def test_baseline_survives_line_drift(tmp_path):
    report = lint(tmp_path, "m.py", CMP001_BAD)
    baseline_path = tmp_path / "baseline.json"
    write_baseline(str(baseline_path), report.findings)
    baseline = load_baseline(str(baseline_path))

    drifted = "'''module docstring'''\n\n\n" + CMP001_BAD
    again = lint(tmp_path, "m.py", drifted, baseline=baseline)
    assert again.findings == []
    assert again.baselined == 2


# -- engine edge cases -----------------------------------------------------

def test_syntax_error_is_a_finding_not_a_crash(tmp_path):
    report = lint(tmp_path, "m.py", "def broken(:\n")
    assert rule_ids(report) == ["ENGINE"]
    assert report.exit_code == EXIT_FINDINGS


def test_unknown_rule_select_raises(tmp_path):
    (tmp_path / "m.py").write_text("x = 1\n")
    with pytest.raises(KeyError):
        run_paths([str(tmp_path)], select=["NOPE99"])


def test_findings_sorted_and_keyed(tmp_path):
    report = lint(tmp_path, "m.py", CMP001_BAD + "\n" + LOG001_BAD)
    keys = [(f.path, f.line, f.col, f.rule) for f in report.findings]
    assert keys == sorted(keys)
    for finding in report.findings:
        assert finding.baseline_key.startswith(f"{finding.rule}::m.py::")


# -- CLI exit codes --------------------------------------------------------

def _run_cli(args, env):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tracelint.py"),
         *args],
        capture_output=True, text=True, timeout=120, env=env,
    )


def test_cli_exit_codes_and_json(tmp_path, cpu_child_env):
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "m.py").write_text(TRC003_BAD)
    good = tmp_path / "good"
    good.mkdir()
    (good / "m.py").write_text(TRC003_OK)

    dirty = _run_cli(
        [str(bad), "--root", str(bad), "--no-baseline", "--json"],
        cpu_child_env,
    )
    assert dirty.returncode == EXIT_FINDINGS, dirty.stderr
    payload = json.loads(dirty.stdout)
    assert payload["counts"] == {"TRC003": 1}
    assert payload["findings"][0]["rule"] == "TRC003"

    clean = _run_cli(
        [str(good), "--root", str(good), "--no-baseline"], cpu_child_env
    )
    assert clean.returncode == EXIT_CLEAN, clean.stderr

    usage = _run_cli(
        [str(good), "--select", "NOPE99", "--no-baseline"], cpu_child_env
    )
    assert usage.returncode == EXIT_ERROR


def test_cli_write_baseline_round_trip(tmp_path, cpu_child_env):
    (tmp_path / "m.py").write_text(CMP001_BAD)
    baseline = tmp_path / "base.json"

    wrote = _run_cli(
        [str(tmp_path), "--root", str(tmp_path), "--baseline",
         str(baseline), "--write-baseline"],
        cpu_child_env,
    )
    assert wrote.returncode == 0, wrote.stderr
    assert baseline.exists()

    clean = _run_cli(
        [str(tmp_path), "--root", str(tmp_path), "--baseline",
         str(baseline)],
        cpu_child_env,
    )
    assert clean.returncode == EXIT_CLEAN, clean.stdout


# -- CKY001: cache-key coverage (project scope) ----------------------------

CKY_KEYS = """\
def train_cache_key(model_config, mesh_shape, *, global_batch_size,
                    seq_len, zero1=False):
    fields = tuple(sorted(
        (k, repr(v)) for k, v in vars(model_config).items()
    ))
    return repr((fields, tuple(mesh_shape), global_batch_size, seq_len,
                 zero1))
"""

CKY_BUILD_OK = """\
from pkg.keys import train_cache_key

def build_sharded_train(model, mesh, *, global_batch_size, seq_len,
                        zero1=False, cache_key=None):
    key = cache_key or train_cache_key(
        model.config, mesh.shape, global_batch_size=global_batch_size,
        seq_len=seq_len, zero1=zero1,
    )
    return key
"""

# ``overlap`` shapes the program (a build-entry parameter) but is absent
# from train_cache_key's signature — the PR-19 aliasing shape.
CKY_BUILD_PARITY_BAD = """\
from pkg.keys import train_cache_key

def build_sharded_train(model, mesh, *, global_batch_size, seq_len,
                        zero1=False, overlap=False, cache_key=None):
    key = cache_key or train_cache_key(
        model.config, mesh.shape, global_batch_size=global_batch_size,
        seq_len=seq_len, zero1=zero1,
    )
    return key, overlap
"""

# A build-path function reads config.overlap — a knob the build entry
# names but the key does not — outside any key-call argument.
CKY_READ_BAD = """\
from pkg.build import build_sharded_train

def make_programs(config, model, mesh):
    overlap = config.overlap
    return build_sharded_train(
        model, mesh, global_batch_size=8, seq_len=16,
    ), overlap
"""

CKY_READ_SUPPRESSED = """\
from pkg.build import build_sharded_train

def make_programs(config, model, mesh):
    overlap = config.overlap  # tracelint: disable=CKY001
    return build_sharded_train(
        model, mesh, global_batch_size=8, seq_len=16,
    ), overlap
"""

# Sanctioned spellings: the read rides a key call's arguments, or the
# carrier goes into the key-reaching call whole.
CKY_READ_OK = """\
from pkg.keys import train_cache_key

def name_program(config, model_config, mesh):
    return train_cache_key(
        model_config, mesh.shape, global_batch_size=8, seq_len=16,
        zero1=config.zero1,
    )

def wrap_key(model_config, mesh):
    return train_cache_key(
        model_config, mesh.shape, global_batch_size=8, seq_len=16,
    )

def fold_whole(model_config, mesh):
    hidden = model_config.seq_len
    return wrap_key(model_config, mesh), hidden
"""

CKY_KEYS_NO_VARS = """\
def train_cache_key(model_config, mesh_shape, *, global_batch_size):
    return repr((model_config.vocab_size, tuple(mesh_shape),
                 global_batch_size))
"""


def test_cky001_signature_parity_fires(tmp_path):
    report = lint_files(tmp_path, {
        "pkg/keys.py": CKY_KEYS,
        "pkg/build.py": CKY_BUILD_PARITY_BAD,
    }, select=["CKY001"])
    symbols = {f.symbol for f in report.findings}
    assert "build_sharded_train::overlap" in symbols


def test_cky001_uncovered_knob_read_fires(tmp_path):
    report = lint_files(tmp_path, {
        "pkg/keys.py": CKY_KEYS,
        "pkg/build.py": CKY_BUILD_PARITY_BAD,
        "pkg/caller.py": CKY_READ_BAD,
    }, select=["CKY001"])
    symbols = {f.symbol for f in report.findings}
    assert "make_programs::config.overlap" in symbols


def test_cky001_covered_spellings_are_clean(tmp_path):
    report = lint_files(tmp_path, {
        "pkg/keys.py": CKY_KEYS,
        "pkg/build.py": CKY_BUILD_OK,
        "pkg/caller.py": CKY_READ_OK,
    }, select=["CKY001"])
    assert report.findings == []


def test_cky001_missing_vars_fold_fires(tmp_path):
    report = lint_files(tmp_path, {
        "pkg/keys.py": CKY_KEYS_NO_VARS,
    }, select=["CKY001"])
    symbols = {f.symbol for f in report.findings}
    assert "train_cache_key::vars" in symbols


def test_cky001_inline_suppression(tmp_path):
    report = lint_files(tmp_path, {
        "pkg/keys.py": CKY_KEYS,
        "pkg/build.py": CKY_BUILD_PARITY_BAD,
        "pkg/caller.py": CKY_READ_SUPPRESSED,
    }, select=["CKY001"])
    assert "make_programs::config.overlap" not in {
        f.symbol for f in report.findings
    }
    assert report.suppressed >= 1


def test_cky001_silent_without_key_functions(tmp_path):
    """Trees that define no cache key (fixtures, partial lints) must not
    drown in findings — the rule guards a contract, not a style."""
    report = lint_files(tmp_path, {
        "pkg/app.py": "def run(config):\n    return config.zero1\n",
    }, select=["CKY001"])
    assert report.findings == []


# -- TEL001: telemetry emit -> route -> render contract --------------------

TEL_TELEMETRY = """\
def event(name, /, duration_s=0.0, t_mono=None, **attrs):
    return (name, duration_s, attrs)

def span(name, /, **attrs):
    return name
"""

TEL_MASTER = """\
class SpeedMonitor:
    def record_fault(self, seam, kind, seconds):
        pass

class Servicer:
    def _report_telemetry(self, events):
        for name, duration_s, attrs in events:
            if name == "fault":
                self.speed_monitor.record_fault(
                    attrs.get("seam"), attrs.get("kind"), duration_s
                )
"""

TEL_WORKER_ROUTED = """\
from pkg import telemetry

def report(seam):
    telemetry.event("fault", seam=seam)
"""

TEL_WORKER_UNROUTED = """\
from pkg import telemetry

def report():
    telemetry.event("retry")
"""

TEL_WORKER_TIMED = """\
from pkg import telemetry

def report(dt):
    telemetry.event("compile", duration_s=dt)
"""

TEL_WORKER_SUPPRESSED = """\
from pkg import telemetry

def report():
    telemetry.event("retry")  # tracelint: disable=TEL001
"""

TEL_MASTER_DEAD_ROUTE = """\
class Servicer:
    def _report_telemetry(self, events):
        for name, duration_s, attrs in events:
            if name == "ghost":
                self.count += 1
"""

TEL_RENDER = """\
class Timeline:
    def bump(self, name, n=1):
        self._counters[name] = self._counters.get(name, 0) + n

    def note(self):
        self.bump("orphan")

    def render_metrics(self):
        lines = []

        def gauge(name, value, help_text="", labels=""):
            if help_text:
                lines.append(f"# HELP {name} {help_text}")
                lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name}{labels} {value}")

        gauge("dlrover_good_total", 1, "a documented counter")
        gauge("dlrover_bare_total", 2)
        return lines
"""

TEL_SPEED_MONITOR_DRIFT = """\
class SpeedMonitor:
    def record_used(self, node):
        pass

    def record_orphan(self, node):
        pass

class Servicer:
    def _report_telemetry(self, events):
        for name, duration_s, attrs in events:
            if name == "used":
                self.speed_monitor.record_used(attrs["node"])
            elif name == "gone":
                self.speed_monitor.record_gone(attrs["node"])
"""


def test_tel001_unrouted_instant_event_fires(tmp_path):
    report = lint_files(tmp_path, {
        "pkg/telemetry.py": TEL_TELEMETRY,
        "pkg/master.py": TEL_MASTER,
        "pkg/worker.py": TEL_WORKER_ROUTED,
        "pkg/flaky.py": TEL_WORKER_UNROUTED,
    }, select=["TEL001"])
    symbols = {f.symbol for f in report.findings}
    assert "event::retry" in symbols
    assert "event::fault" not in symbols


def test_tel001_timed_events_are_exempt(tmp_path):
    report = lint_files(tmp_path, {
        "pkg/telemetry.py": TEL_TELEMETRY,
        "pkg/master.py": TEL_MASTER,
        "pkg/worker.py": TEL_WORKER_ROUTED,
        "pkg/timed.py": TEL_WORKER_TIMED,
    }, select=["TEL001"])
    assert report.findings == []


def test_tel001_dead_route_fires(tmp_path):
    report = lint_files(tmp_path, {
        "pkg/telemetry.py": TEL_TELEMETRY,
        "pkg/master.py": TEL_MASTER_DEAD_ROUTE,
    }, select=["TEL001"])
    symbols = {f.symbol for f in report.findings}
    assert "route::ghost" in symbols


TEL_FAMILY_TABLE = """\
HEALTH_KINDS = {"ssm": {"gauges": ()}, "ghost": {"gauges": ()}}
"""

TEL_FAMILY_MASTER = """\
from pkg.table import HEALTH_KINDS

class SpeedMonitor:
    def record_health(self, kind, node, **attrs):
        pass

class Servicer:
    def _report_telemetry(self, events):
        for name, duration_s, attrs in events:
            if name in HEALTH_KINDS:
                self.speed_monitor.record_health(name, 0, **attrs)
"""

TEL_FAMILY_MODELS = """\
from pkg import telemetry

class Family:
    def __init__(self, event, read):
        self.event, self.read = event, read

FAMILY = Family(event="ssm", read=dict)
UNROUTED = Family(event="conv", read=dict)

def report(family, vec):
    telemetry.event(family.event, **family.read(vec))
"""


@pytest.mark.parametrize("symbol,fires", [
    # a kind routed by a table the routing module imports, and emitted by
    # a family's declaration, is neither dead nor unrouted ...
    ("route::ssm", False), ("event::ssm", False),
    # ... a row no family declares is a dead route, a family no row routes
    # an unrouted event
    ("route::ghost", True), ("event::conv", True),
])
def test_tel001_reads_the_family_table_and_declarations(
    tmp_path, symbol, fires
):
    report = lint_files(tmp_path, {
        "pkg/telemetry.py": TEL_TELEMETRY,
        "pkg/table.py": TEL_FAMILY_TABLE,
        "pkg/master.py": TEL_FAMILY_MASTER,
        "pkg/models.py": TEL_FAMILY_MODELS,
    }, select=["TEL001"])
    assert (symbol in {f.symbol for f in report.findings}) == fires


def test_tel001_silent_without_routing_functions(tmp_path):
    """Single-file fixtures with no master in sight emit freely."""
    report = lint_files(tmp_path, {
        "pkg/telemetry.py": TEL_TELEMETRY,
        "pkg/worker.py": TEL_WORKER_UNROUTED,
    }, select=["TEL001"])
    assert report.findings == []


def test_tel001_gauge_help_and_orphan_counter(tmp_path):
    report = lint_files(tmp_path, {
        "pkg/timeline.py": TEL_RENDER,
    }, select=["TEL001"])
    symbols = {f.symbol for f in report.findings}
    assert "gauge::dlrover_bare_total" in symbols
    assert "gauge::dlrover_good_total" not in symbols
    assert "counter::orphan" in symbols


def test_tel001_speed_monitor_surface_drift(tmp_path):
    report = lint_files(tmp_path, {
        "pkg/telemetry.py": TEL_TELEMETRY,
        "pkg/master.py": TEL_SPEED_MONITOR_DRIFT,
        "pkg/worker.py": (
            "from pkg import telemetry\n\n"
            "def a():\n    telemetry.event(\"used\")\n\n"
            "def b():\n    telemetry.event(\"gone\")\n"
        ),
    }, select=["TEL001"])
    symbols = {f.symbol for f in report.findings}
    assert "speed_monitor::record_gone" in symbols
    assert "speed_monitor::orphan::record_orphan" in symbols
    assert "speed_monitor::orphan::record_used" not in symbols


def test_tel001_inline_suppression(tmp_path):
    report = lint_files(tmp_path, {
        "pkg/telemetry.py": TEL_TELEMETRY,
        "pkg/master.py": TEL_MASTER,
        "pkg/flaky.py": TEL_WORKER_SUPPRESSED,
    }, select=["TEL001"])
    assert "event::retry" not in {f.symbol for f in report.findings}
    assert report.suppressed >= 1


# -- LCK001: lockset races (CFG must-hold analysis) ------------------------

LCK_INCONSISTENT = """\
import threading

class Pump:
    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0
        self._thread = threading.Thread(target=self._run)

    def _run(self):
        while True:
            with self._lock:
                self._value += 1

    def reset(self):
        self._value = 0
"""

LCK_DISJOINT = """\
import threading

class Pump:
    def __init__(self):
        self._a_lock = threading.Lock()
        self._b_lock = threading.Lock()
        self._value = 0
        self._thread = threading.Thread(target=self._run)

    def _run(self):
        while True:
            with self._a_lock:
                self._value += 1

    def snapshot(self):
        with self._b_lock:
            return self._value
"""

# acquire()/try/finally/release() is a held lock — the lexical heuristic
# (THR001) cannot see it, the must-hold dataflow can.
LCK_TRY_FINALLY_OK = """\
import threading

class Pump:
    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0
        self._thread = threading.Thread(target=self._run)

    def _run(self):
        while True:
            self._lock.acquire()
            try:
                self._value += 1
            finally:
                self._lock.release()

    def snapshot(self):
        with self._lock:
            return self._value
"""

LCK_CONSISTENT = """\
import threading

class Pump:
    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0
        self._thread = threading.Thread(target=self._run)

    def _run(self):
        while True:
            with self._lock:
                self._value += 1

    def snapshot(self):
        with self._lock:
            return self._value
"""

LCK_SUPPRESSED = """\
import threading

class Pump:
    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0
        self._thread = threading.Thread(target=self._run)

    def _run(self):
        while True:
            with self._lock:
                self._value += 1

    def reset(self):
        self._value = 0  # tracelint: disable=LCK001
"""


def test_lck001_inconsistent_guard_fires(tmp_path):
    report = lint(tmp_path, "m.py", LCK_INCONSISTENT, select=["LCK001"])
    assert rule_ids(report) == ["LCK001"]
    assert report.findings[0].symbol == "Pump._value"
    assert "empty lockset" in report.findings[0].message


def test_lck001_disjoint_locksets_fire(tmp_path):
    report = lint(tmp_path, "m.py", LCK_DISJOINT, select=["LCK001"])
    assert rule_ids(report) == ["LCK001"]
    assert "disjoint" in report.findings[0].message
    # The lexical heuristic calls both sides "locked" and stays silent —
    # this race shape is exactly what the lockset analysis adds.
    assert rule_ids(
        lint(tmp_path, "m2.py", LCK_DISJOINT, select=["THR001"])
    ) == []


def test_lck001_try_finally_acquire_is_held(tmp_path):
    report = lint(tmp_path, "m.py", LCK_TRY_FINALLY_OK, select=["LCK001"])
    assert report.findings == []
    # ...while the lexical heuristic false-positives on the same code:
    # the motivating THR001 -> LCK001 precision delta.
    assert rule_ids(
        lint(tmp_path, "m2.py", LCK_TRY_FINALLY_OK, select=["THR001"])
    ) == ["THR001"]


def test_lck001_consistent_locking_is_clean(tmp_path):
    report = lint(tmp_path, "m.py", LCK_CONSISTENT, select=["LCK001"])
    assert report.findings == []


def test_lck001_fully_unguarded_attr_is_thr001_territory(tmp_path):
    source = LCK_INCONSISTENT.replace(
        "            with self._lock:\n                self._value += 1",
        "            self._value += 1",
    )
    report = lint(tmp_path, "m.py", source, select=["LCK001"])
    assert report.findings == []
    assert rule_ids(
        lint(tmp_path, "m2.py", source, select=["THR001"])
    ) == ["THR001"]


def test_lck001_inline_suppression(tmp_path):
    report = lint(tmp_path, "m.py", LCK_SUPPRESSED, select=["LCK001"])
    assert report.findings == []
    assert report.suppressed == 1


# -- SARIF: new rules advertised with stable indices -----------------------

def test_sarif_rule_indices_cover_new_rules(tmp_path):
    report = lint(tmp_path, "m.py", LCK_INCONSISTENT, select=None)
    sarif = json.loads(report.render_sarif())
    driver_rules = sarif["runs"][0]["tool"]["driver"]["rules"]
    ids = [r["id"] for r in driver_rules]
    assert ids == sorted(ids), "ruleIndex must follow sorted rule ids"
    for rule_id in ("CKY001", "TEL001", "LCK001"):
        assert rule_id in ids
    for result in sarif["runs"][0]["results"]:
        assert ids[result["ruleIndex"]] == result["ruleId"]
