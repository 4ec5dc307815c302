"""Pre-flight node health probes: chip matmul TFLOPs + collective bandwidth.

Capability ref: ``dlrover/trainer/torch/node_check/nvidia_gpu.py:24`` +
``utils.py:58-196`` (``matmul`` stress + ``bm_allgather`` timed) and the
agent driver ``training.py:828-977`` (``NodeCheckElasticAgent``).

TPU redesign: one probe process drives all local chips (no
fork-per-device), measuring (a) bf16 matmul sustained TFLOPs on every local
chip — catches degraded/thermally-limited chips, and (b) psum all-reduce
bandwidth across local chips over ICI — catches bad ICI links.  Elapsed time
is reported to the master's NetworkCheckRendezvousManager, which runs the
pairwise bisection (SURVEY.md §3.5).

A chip belongs to one process at a time, and the agent goes on to spawn the
trainer: the probes therefore run in a short-lived child
(``python -m dlrover_tpu.agent.node_check``) that has exited, and released
the chip, before the trainer starts.  The agent itself never initialises a
JAX backend.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional, Tuple

from dlrover_tpu.common.log import default_logger as logger


def matmul_probe(
    matrix_dim: int = 4096, iters: int = 8, device=None
) -> float:
    """Sustained bf16 matmul TFLOPs on one device."""
    import jax
    import jax.numpy as jnp

    device = device or jax.devices()[0]
    key = jax.random.PRNGKey(0)
    x = jax.device_put(
        jax.random.normal(key, (matrix_dim, matrix_dim), jnp.bfloat16), device
    )

    @jax.jit
    def chain(x):
        for _ in range(iters):
            x = x @ x
            # Renormalize so the chain is numerically tame (jit-fused, cheap).
            x = x * jax.lax.rsqrt(jnp.float32(matrix_dim)).astype(x.dtype)
        return x

    chain(x).block_until_ready()  # compile
    t0 = time.monotonic()
    chain(x).block_until_ready()
    dt = time.monotonic() - t0
    flops = 2 * matrix_dim**3 * iters
    return flops / dt / 1e12


def allreduce_probe(size_mb: int = 64) -> Tuple[float, float]:
    """(elapsed_s, algo_bw_GBps) of a psum across all local devices over ICI."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    devices = jax.local_devices()
    n = len(devices)
    nelem = size_mb * (1 << 20) // 4
    if n < 2:
        return 0.0, 0.0
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(devices), ("d",))
    x = jax.device_put(
        jnp.ones((n, nelem), jnp.float32),
        NamedSharding(mesh, PartitionSpec("d")),
    )

    @jax.jit
    def reduce(x):
        return x.sum(axis=0)  # all-reduce over the sharded dim

    reduce(x).block_until_ready()
    t0 = time.monotonic()
    reduce(x).block_until_ready()
    dt = time.monotonic() - t0
    gb = nelem * 4 / 1e9
    return dt, gb / dt if dt > 0 else 0.0


def probe_result_digest(matrix_dim: int = 512, iters: int = 4) -> str:
    """Deterministic digest of a seeded matmul chain's exact result bits.

    The input is seeded (``PRNGKey(0)``) and the chain runs on local
    device 0, so on healthy hardware the result is bit-identical run to
    run — the node's *golden value*.  A re-join whose digest differs means
    this chip now computes differently than it did at job start: the
    suspicion-driven silent-data-corruption confirm probe (the agent-side
    counterpart of the trainer's cross-replica state digest vote).
    """
    import zlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    x = jax.random.normal(
        jax.random.PRNGKey(0), (matrix_dim, matrix_dim), jnp.bfloat16
    )

    @jax.jit
    def chain(x):
        for _ in range(iters):
            x = x @ x
            x = x * jax.lax.rsqrt(jnp.float32(matrix_dim)).astype(x.dtype)
        return x

    out = np.asarray(jax.device_get(chain(x)))
    return f"{zlib.crc32(out.tobytes()) & 0xFFFFFFFF:08x}"


def golden_replay_check(client, node_rank: int, digest: str) -> bool:
    """Record the golden probe digest at first join; compare on re-join.

    ``digest`` is this host's :func:`probe_result_digest`.  The golden
    value lives in the master's kv store (it survives master restarts
    through the state store), keyed by node rank.  A mismatch is
    reported like a failed bisection round — the master's verdict then
    excludes this host the same way a bad ICI link would be.
    """
    key = f"node_check_golden/{node_rank}"
    golden = client.kv_get(key)
    if not golden:
        client.kv_put(key, digest.encode())
        logger.info(
            "node check: golden digest %s recorded for rank %d",
            digest, node_rank,
        )
        return True
    golden = golden.decode() if isinstance(golden, bytes) else str(golden)
    if golden != digest:
        logger.error(
            "node check: golden digest mismatch on rank %d (recorded %s, "
            "replayed %s) — hardware computes differently than at job "
            "start (SDC suspect)", node_rank, golden, digest,
        )
        return False
    return True


def run_probe_payload(matrix_dim: int = 4096) -> Tuple[bool, float]:
    """The full per-host probe: returns (healthy, elapsed_seconds)."""
    import jax

    from dlrover_tpu.common import faults

    t0 = time.monotonic()
    try:
        # Seam: the TPU runtime failing at init is this probe failing.
        faults.fire("backend.init")
        tflops = []
        for device in jax.local_devices():
            tflops.append(matmul_probe(matrix_dim, device=device))
        dt, bw = allreduce_probe()
        elapsed = time.monotonic() - t0
        logger.info(
            "node check: matmul %s TFLOPs, allreduce %.1f GB/s, %.2fs",
            [f"{t:.1f}" for t in tflops], bw, elapsed,
        )
        return True, elapsed
    except Exception as e:
        logger.error("node check probe failed: %s", e)
        return False, time.monotonic() - t0


def probe_in_child(
    with_digest: bool = False, timeout: float = 600.0
) -> Tuple[bool, float, Optional[str]]:
    """Run the probe payload in a child process that holds the chip alone
    and has exited when this returns: ``(healthy, elapsed_s, digest)``.

    ``digest`` is the golden-replay digest when asked for and the child
    got that far, else None.  A child that crashes, hangs past ``timeout``
    or prints no verdict is an unhealthy host, not an agent failure.
    """
    cmd = [sys.executable, "-m", "dlrover_tpu.agent.node_check"]
    if with_digest:
        cmd.append("--digest")
    package_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (package_root, env.get("PYTHONPATH", "")) if p
    )
    t0 = time.monotonic()
    try:
        out = subprocess.run(
            cmd, env=env, capture_output=True, text=True, timeout=timeout
        )
        verdict = json.loads(out.stdout.strip().splitlines()[-1])
        return (
            bool(verdict["healthy"]), float(verdict["elapsed"]),
            verdict.get("digest"),
        )
    except subprocess.TimeoutExpired:
        logger.error("node check child exceeded %.0fs", timeout)
    except (IndexError, KeyError, ValueError):
        logger.error(
            "node check child gave no verdict (rc=%d): %s",
            out.returncode, out.stderr.strip()[-2000:],
        )
    return False, time.monotonic() - t0, None


def _child_main(argv=None) -> int:
    """The probe child: one JSON verdict as the last line of stdout."""
    want_digest = "--digest" in (sys.argv[1:] if argv is None else argv)
    healthy, elapsed = run_probe_payload()
    verdict = {"healthy": healthy, "elapsed": elapsed}
    if want_digest:
        verdict["digest"] = probe_result_digest()
    print(json.dumps(verdict), flush=True)
    return 0


def run_network_check(
    client, node_rank: int, rounds: int = 2, timeout: float = 300.0
) -> bool:
    """Drive the check rounds against the master; returns node health.

    ref ``training.py:1054-1118``: each round joins the network-check
    rendezvous, runs the probe, reports status+elapsed; after the final
    round the *master's* pairwise-bisection verdict decides health.  A node
    whose own probe failed still joins every round — dropping out would
    stall the remaining nodes' rendezvous and starve the bisection of the
    suspect it needs to re-pair.
    """
    from dlrover_tpu.master.rdzv_manager import RendezvousName

    local_healthy = True
    for check_round in range(rounds):
        client.join_rendezvous(node_rank, 1, RendezvousName.NETWORK_CHECK)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            state = client.get_comm_world(
                node_rank, RendezvousName.NETWORK_CHECK
            )
            if state.world:
                break
            time.sleep(0.5)
        # Golden-batch replay rides the first round only: one seeded
        # matmul digest compared against the value recorded at the job's
        # first join.  A mismatch fails this round exactly like a failed
        # probe, feeding the master's bisection the suspect.
        healthy, elapsed, digest = probe_in_child(
            with_digest=check_round == 0
        )
        if digest is not None:
            try:
                healthy = (
                    golden_replay_check(client, node_rank, digest)
                    and healthy
                )
            except Exception as e:  # noqa: BLE001 - probe is best-effort
                logger.warning("golden replay check skipped: %s", e)
        local_healthy = local_healthy and healthy
        client.report_network_status(node_rank, healthy, elapsed)

    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        result = client.get_network_check_result()
        if result.reason == "done":
            if result.stragglers:
                logger.warning("straggler nodes: %s", result.stragglers)
            return node_rank not in result.fault_nodes
        time.sleep(1.0)
    logger.warning("network-check verdict timed out; using local result")
    return local_healthy


if __name__ == "__main__":
    sys.exit(_child_main())
