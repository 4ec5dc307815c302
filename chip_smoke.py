"""chip_smoke.py: does the system still start on the chip?

    python chip_smoke.py             # one TPU chip: device, kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: the sharded step and its
                                     # one-device reference, nothing else

The quickest proof that the elastic train path and the serving path run on
a real TPU at GPT-2 1.5B's full width (48 layers, d_model 1600, 25 heads,
vocab 50304, seq 1024, bf16 parameters; random weights from a seed):

* device   jax sees a TPU; versions printed.
* kernels  every Pallas kernel the tree can route to on a TPU, compiled
           (not interpreted), at real widths, against its plain reference.
* train    the README's own command: launcher -> master -> agent -> trainer
           on the chip, Flash Checkpoint every 2 steps, a crash at step 4,
           restart-in-place, a second trainer that takes the chip over,
           restores, hits the persistent compile cache and finishes.
* serve    examples/serve_lm.py: AOT warm-up, 8 mixed requests; prefill
           logits against a plain full forward, greedy tokens against the
           scan decode of rl/generation.
* sharded  (--chips 4 only) three steps on data=2 x fsdp=2 with ZeRO-1
           against the same three steps on one device.

This process never initialises a JAX backend: a chip belongs to one
process at a time, so each phase is one child that holds it alone, run in
turn.  It stops at the first phase that fails, with a non-zero exit and no
result line; that includes a machine without a TPU and a directory that
holds nothing of the repo but this file.  On success the last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``;
everything else (seconds, losses, cache hits, peak HBM) is on earlier
lines.  The compile cache, logs and whatever is built land in paths git
ignores.
"""

from __future__ import annotations

import argparse
import ast
import json
import math
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# GPT-2 1.5B as bench.py runs it: the one configuration that has fitted a
# 16 GB v5e (bf16 parameters, adafactor, flash attention, the backward
# re-running everything but the flash kernel, batch 16 x seq 1024).
SIZE = "1.5b"
SEQ_LEN = 1024
BATCH = 16
SEED = 0

# What the kernels phase runs, at the widths of the models that would use
# them: [batch, seq, heads, head_dim] for attention (1.5B's, and a head_dim
# 128 / two-kv-block shape that takes the split backward), [batch, seq, d]
# for the norms, (N, K, M, group sizes) for the MoE grouped matmul, one
# 1.5B MLP kernel for the quantized optimizers, (rows, dim, slots) for the
# embedding hot-row cache.
ATTN_FUSED = (BATCH, SEQ_LEN, 25, 64)
ATTN_SPLIT = (4, 2048, 32, 128)
FLASH_BLOCK = 1024
LAYERNORM = (BATCH, SEQ_LEN, 1600)
RMSNORM = (4, 2048, 4096)
GROUPED = (40960, 1600, 3200, (8192, 4096, 0, 10240, 2048, 6144, 5120, 5120))
ROW_SUM = (17408, 2048, 8, 2048)       # rows, tokens, rows a token, width
LEAF = (1600, 6400)
EMBED_CACHE = (65536, 128, 4096)

# Two bf16 computations of one quantity, reduced in different orders,
# agree to a few units in the last place of their largest element: errors
# are judged as max|a - b| / max|b| against this.
BF16_TOL = 2e-2
# A prefill's logits may lie this many times as far from the f32 forward as
# the plain bf16 forward does (both are 48 layers of bf16 rounding, summed
# in other orders; a wrong mask, position or cache row is O(1) off).
PREFILL_SLACK = 2.0
# Loss of the same three steps on one device and on four (bf16 parameters,
# f32 loss; the reductions and the optimizer run in other orders).
LOSS_RTOL = 2e-2
# Spread allowed between the four chips' bytes_in_use (nothing piled on
# device 0): the largest at most this multiple of the smallest.
BYTES_BAND = 1.25


def say(line: str = "") -> None:
    print(line, flush=True)


# ---------------------------------------------------------------------------
# parent: runs the phases, one child at a time
# ---------------------------------------------------------------------------


def _child_env(workdir: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO, env.get("PYTHONPATH", "")) if p
    )
    env["PYTHONUNBUFFERED"] = "1"
    # Deployment settings of the elastic runtime: where its sockets and
    # logs live, and the tag that names its shared-memory arena (unique,
    # so a stale arena of another run is never restored from).
    env["DLROVER_TPU_SOCKET_DIR"] = os.path.join(workdir, "sockets")
    env["DLROVER_TPU_JOB"] = f"smoke{os.getpid()}"
    return env


# The whole script answers within 1200 s: every phase gets what is left of
# this budget (its start is taken when the module is loaded).
BUDGET_S = 1150.0
_T0 = time.monotonic()


def _run_logged(cmd, env, log_path: str):
    """Run ``cmd``; its stdout and stderr go to ``log_path``.  Returns
    ``(rc, lines)``.  The child is killed with its whole process group at
    the time limit, so nothing it started outlives this script."""
    timeout = max(1.0, BUDGET_S - (time.monotonic() - _T0))
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            rc = 124
        finally:
            try:
                os.killpg(proc.pid, 9)  # stragglers of the group, if any
            except ProcessLookupError:
                pass
            proc.wait()
    with open(log_path, errors="replace") as log:
        return rc, log.read().splitlines()


def _fail(phase: str, why: str, lines) -> int:
    say(f"[{phase}] FAILED: {why}")
    for line in lines[-40:]:
        say(f"[{phase}] | {line[:300]}")
    return 1


def _run_jax_phase(phase, args, env, log_dir):
    """A phase that is one JAX process (this file with ``--phase``).  Its
    lines that start with ``SMOKE `` are the phase's report."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--chips", str(args.chips)]
    rc, lines = _run_logged(
        cmd, env, os.path.join(log_dir, f"{phase}.log")
    )
    report = [line[6:] for line in lines if line.startswith("SMOKE ")]
    for line in report:
        say(f"[{phase}] {line}")
    if rc != 0:
        why = "time limit" if rc == 124 else f"exit code {rc}"
        return _fail(phase, why, lines), report
    return 0, report


_STEP_LINE = re.compile(
    r"^\[(\d+-\d+-\d+ \d+:\d+:\d+),(\d+)\].*\] step (\d+) loss (\S+) lr"
)
_SAVE_LINE = re.compile(
    r"step (\d+): saved to shm in ([\d.]+)s \((\w+), ([\d.]+) GB/s"
)


def _run_train_phase(args, env, workdir, log_dir) -> int:
    """The README quick start at full width, with a crash in the middle."""
    phase = "train"
    ckpt = os.path.join(workdir, "ckpt")
    cmd = [
        sys.executable, "-m", "dlrover_tpu.run", "--standalone",
        "--checkpoint-dir", ckpt, "--monitor-interval", "1", "--",
        sys.executable, os.path.join(REPO, "examples", "train_lm.py"),
        "--size", SIZE, "--seq-len", str(SEQ_LEN),
        "--batch-size", str(BATCH), "--param-dtype", "bfloat16",
        "--optimizer", "adafactor", "--attention-impl", "flash",
        "--remat", "flash_only", "--checkpoint-dir", ckpt,
        "--steps", "6", "--ckpt-every", "2", "--fail-at-step", "4",
        "--report-every", "1", "--warmup-compile",
    ]
    say(f"[{phase}] $ " + " ".join(
        "<dir>" if c == ckpt else os.path.relpath(c, REPO)
        if c.startswith(REPO + os.sep) else os.path.basename(c)
        if c == sys.executable else c for c in cmd
    ))
    rc, lines = _run_logged(cmd, env, os.path.join(log_dir, f"{phase}.log"))

    # One list of (step, loss, t) per trainer process, in start order.
    runs, warmups, resumed, saves = [], [], [], []
    for line in lines:
        m = _SAVE_LINE.search(line)
        if m:
            saves.append((int(m.group(1)), float(m.group(2)), m.group(3),
                          float(m.group(4))))
        if "starting trainer (round" in line:
            runs.append([])
        m = _STEP_LINE.match(line)
        if m and runs:
            t = time.mktime(
                time.strptime(m.group(1), "%Y-%m-%d %H:%M:%S")
            ) + int(m.group(2)) / 1000.0
            runs[-1].append((int(m.group(3)), float(m.group(4)), t))
        if "compile warmup: " in line:
            warmups.append(
                ast.literal_eval(line.split("compile warmup: ", 1)[1])
            )
        m = re.search(r"resumed from checkpoint at step (\d+)", line)
        if m:
            resumed.append(int(m.group(1)))
    for i, run in enumerate(runs):
        say(f"[{phase}] trainer {i + 1}: " + ", ".join(
            f"step {s} loss {loss:.4f}" for s, loss, _ in run
        ))
        gaps = [b[2] - a[2] for a, b in zip(run, run[1:])]
        if gaps:
            say(f"[{phase}] trainer {i + 1}: seconds between step reports "
                f"(checkpoint saves included) "
                + " ".join(f"{g:.2f}" for g in gaps))
    for step, seconds, path, gb_s in saves:
        say(f"[{phase}] save of step {step}: {seconds:.2f}s in the arena, "
            f"device-to-host path {path} at {gb_s:.2f} GB/s")
    for i, w in enumerate(warmups):
        say(f"[{phase}] trainer {i + 1}: step program compiled in "
            f"{w['seconds']:.1f}s, persistent cache hits "
            f"{w['persistent_hits']} misses {w['persistent_misses']}, "
            f"{w['kernel_calls']} compiled Pallas kernels in it")
    try:
        with open(os.path.join(
            env["DLROVER_TPU_SOCKET_DIR"], "metrics_n0.json"
        )) as f:
            peak_gb = json.load(f)["device_peak_gb"]
        say(f"[{phase}] peak HBM (memory_stats, second trainer): "
            f"{peak_gb:.2f} GiB")
    except (OSError, KeyError, ValueError) as e:
        return _fail(phase, f"no device metrics from the trainer: {e}", lines)

    def problem():
        """The first check that does not hold, or None."""
        if rc != 0:
            return f"launcher exit code {rc}"
        if not any("trainer exited with code 17" in line for line in lines):
            return "the first trainer did not crash at step 4 (exit 17)"
        if len(runs) != 2 or len(warmups) != 2:
            return f"{len(runs)} trainer processes, expected 2"
        first, second = runs
        if [s for s, _, _ in first] != [1, 2, 3]:
            return "the first trainer should report steps 1-3, then crash"
        if resumed != [2] or [s for s, _, _ in second] != [3, 4, 5, 6]:
            return f"resumed from {resumed}: expected step 2, then 3-6"
        if not all(math.isfinite(loss) for _, loss, _ in first + second):
            return "a loss is not finite"
        # The master re-queues the dead trainer's shard, so the second
        # trainer's first batch is the first trainer's first batch: a
        # start from the seeded initial weights would print the first
        # trainer's step-1 loss again, digit for digit.  From the restored
        # step-2 state it prints another, near where the first left off.
        fresh, last, again = first[0][1], first[-1][1], second[0][1]
        if again == fresh or abs(again - last) > 0.1 * abs(last):
            return (f"the resumed loss {again} does not continue from "
                    f"{last} (a fresh start gives {fresh})")
        if warmups[1]["persistent_misses"] or not warmups[1]["persistent_hits"]:
            return "the second trainer compiled its step program again"
        if any(w["kernel_calls"] < 2 for w in warmups):
            return "the compiled step holds no flash kernel"
        # A runtime on which the staged device-to-host path is refused
        # saves at a tenth of the rate: seen here, not in a benchmark cell.
        if not saves or any(path != "staged" for _, _, path, _ in saves):
            return (f"saves took the paths {[s[2] for s in saves]}: "
                    "expected every one staged")
        return None

    why = problem()
    if why is not None:
        return _fail(phase, why, lines)
    say(f"[{phase}] crash at step 4, restart in place, resumed from step 2 "
        f"at loss {runs[1][0][1]:.4f} (a fresh start would repeat "
        f"{runs[0][0][1]:.4f}), step program compiled in "
        f"{warmups[1]['seconds']:.1f}s from the cache against "
        f"{warmups[0]['seconds']:.1f}s before: ok")
    return 0


def run_phases(args) -> int:
    if not os.path.isdir(os.path.join(REPO, "dlrover_tpu")):
        say("chip_smoke.py runs from the root of a checkout of the repo; "
            f"there is no dlrover_tpu/ beside it in {REPO}")
        return 2
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    log_dir = args.log_dir or workdir
    os.makedirs(log_dir, exist_ok=True)
    env = _child_env(workdir)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache"
    )
    say(f"chip_smoke: {args.chips} chip(s); compile cache {cache}; "
        f"logs {log_dir}")
    # Four chips: the sharded step and its reference in one process, which
    # also answers for the device.
    phases = (
        ["sharded"] if args.chips == 4
        else ["device", "kernels", "train", "serve"]
    )
    device = None
    try:
        for phase in phases:
            t0 = time.monotonic()
            if phase == "train":
                rc = _run_train_phase(args, env, workdir, log_dir)
            else:
                rc, report = _run_jax_phase(phase, args, env, log_dir)
                for line in report:
                    if line.startswith('{"platform"'):
                        device = json.loads(line)
            say(f"[{phase}] {time.monotonic() - t0:.1f}s")
            if rc != 0:
                return rc
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    say(json.dumps({"ok": True, "device": device}))
    return 0


# ---------------------------------------------------------------------------
# children: each is one process that holds the chip alone
# ---------------------------------------------------------------------------


def report(line: str) -> None:
    print(f"SMOKE {line}", flush=True)


def phase_device(args) -> int:
    import importlib.metadata as metadata

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        report(f"platform is {devices[0].platform!r}, not 'tpu': "
               "chip_smoke.py needs a TPU and measures nothing without one")
        return 3
    if len(devices) < args.chips:
        report(f"{len(devices)} device(s), --chips {args.chips} needs more")
        return 3
    report(f"jax {jax.__version__} jaxlib {metadata.version('jaxlib')} "
           f"libtpu {metadata.version('libtpu')} "
           f"flax {metadata.version('flax')} "
           f"python {sys.version.split()[0]}")
    report(json.dumps({
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }))
    return 0


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


class _KernelCases:
    """The kernels phase: ``check(name, kernel, reference, args)`` runs both
    jitted, demands ``tpu_custom_call`` in the kernel's lowered text and
    agreement of every output within ``tol``."""

    def __init__(self):
        self.failed = []

    def check(self, name, kernel_fn, ref_fn, args, tol, want_calls=1):
        import jax

        t0 = time.monotonic()
        try:
            lowered = jax.jit(kernel_fn).lower(*args)
            calls = lowered.as_text().count("tpu_custom_call")
            got = jax.tree.leaves(lowered.compile()(*args))
            want = jax.tree.leaves(jax.jit(ref_fn)(*args))
            errs = [_rel_err(g, w) for g, w in zip(got, want)]
        except Exception as e:  # noqa: BLE001 - reported, then the phase fails
            first = str(e).strip().splitlines()[0][:400]
            report(f"{name}: does not compile or run: "
                   f"{type(e).__name__}: {first}")
            self.failed.append(name)
            return
        ok = (
            calls >= want_calls and len(got) == len(want)
            and all(e <= tol for e in errs)
        )
        report(
            f"{name}: {'ok' if ok else 'MISMATCH'} "
            f"(tpu_custom_call x{calls}, max rel err "
            f"{max(errs):.2e} <= {tol:.1e} over {len(errs)} outputs, "
            f"{time.monotonic() - t0:.1f}s with compile)"
        )
        if not ok:
            self.failed.append(name)


def phase_kernels(args) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.embedding import kernels as embed_kernels
    from dlrover_tpu.models import layers
    from dlrover_tpu.models.attention import xla_attention
    from dlrover_tpu.ops import backend
    from dlrover_tpu.ops import flash_attention as fa
    from dlrover_tpu.ops import quantization as qz
    from dlrover_tpu.ops import row_gather_sum
    from dlrover_tpu.ops.grouped_matmul import (
        grouped_matmul,
        grouped_matmul_ref,
    )
    from dlrover_tpu.runtime import compile_cache

    compile_cache.maybe_enable()
    if backend.interpret():
        report("the kernels would run in interpret mode on this backend")
        return 1
    cases = _KernelCases()
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 64))
    f32 = jnp.float32

    def normal(shape, dtype=jnp.bfloat16, scale=1.0):
        return (jax.random.normal(next(keys), shape, f32) * scale).astype(
            dtype
        )

    def out_and_grads(fn):
        """``run(ct, *args)``: ``fn(*args)`` and the gradients of
        ``sum(out * ct)`` for every argument.  (``ct`` is an argument, not
        a captured constant: tens of MB of literal slow the compile.)"""
        def loss(args, ct):
            out = fn(*args)
            return (out.astype(f32) * ct.astype(f32)).sum(), out

        def run(ct, *args):
            (_, out), grads = jax.value_and_grad(loss, has_aux=True)(args, ct)
            return out, grads

        return run

    # -- flash attention: forward + fused backward, and the split backward --
    for name, shape, want_calls in (
        ("flash fwd + fused bwd", ATTN_FUSED, 2),
        ("flash fwd + split bwd", ATTN_SPLIT, 3),
    ):
        q, k, v, ct = (normal(shape) for _ in range(4))
        cases.check(
            f"{name} {list(shape)} bf16",
            out_and_grads(lambda q, k, v: fa.mha(
                q, k, v, causal=True,
                block_q=FLASH_BLOCK, block_kv=FLASH_BLOCK,
            )),
            out_and_grads(
                lambda q, k, v: xla_attention(q, k, v, causal=True)
            ),
            (ct, q, k, v), BF16_TOL, want_calls,
        )

    # -- fused norms: the modules with and without the Pallas backward ------
    for name, module_cls, shape in (
        ("fused_layernorm", layers.LayerNorm, LAYERNORM),
        ("fused_rmsnorm", layers.RMSNorm, RMSNORM),
    ):
        x, ct = normal(shape), normal(shape)
        fused = module_cls(fused_backward=True)
        plain = module_cls(fused_backward=False)
        params = jax.tree.map(
            lambda p: p + normal(p.shape, p.dtype, 0.1),
            plain.init(next(keys), x),
        )
        cases.check(
            f"{name} fwd+bwd {list(shape)} bf16",
            out_and_grads(fused.apply), out_and_grads(plain.apply),
            (ct, params, x), BF16_TOL,
        )

    # -- grouped matmul: ragged groups, one of them empty --------------------
    n, k, m, group_sizes = GROUPED
    sizes = jnp.asarray(group_sizes, jnp.int32)
    gx, gct = normal((n, k)), normal((n, m))
    gw = normal((len(group_sizes), k, m), scale=k ** -0.5)
    cases.check(
        f"grouped_matmul fwd+bwd N={n} K={k} M={m} "
        f"E={len(group_sizes)} bf16",
        out_and_grads(lambda x, w: grouped_matmul(x, w, sizes, 128)),
        out_and_grads(lambda x, w: grouped_matmul_ref(x, w, sizes)),
        (gct, gx, gw), BF16_TOL, 3,
    )

    # -- a token's k rows fetched and summed (the dropless combine), from
    # rows the grouped GEMM hands out row-tiled ------------------------------
    r, t, picks, width = ROW_SUM
    even = jnp.full((8,), r // 8, jnp.int32)
    rx, rw = normal((r, 512)), normal((8, 512, width), scale=512 ** -0.5)
    picked = jax.random.randint(next(keys), (t, picks), 0, r)
    gates = jax.random.uniform(next(keys), (t, picks), f32)
    cases.check(
        f"grouped_matmul row-tiled out + row_gather_sum R={r} T={t} "
        f"k={picks} D={width} bf16",
        lambda x, w, idx, g: row_gather_sum.gather_sum(
            grouped_matmul(x, w, even, 128, True), idx, g
        ),
        lambda x, w, idx, g: (
            grouped_matmul_ref(x, w, even)[idx].astype(f32) * g[..., None]
        ).sum(1),
        (rx, rw, picked, gates), BF16_TOL, 2,
    )

    # -- block quantization and the quantized Adam updates -------------------
    leaf = normal(LEAF, f32)
    n_blocks = -(-leaf.size // qz.BLOCK)

    def blocks(x):
        """[R, BLOCK] view of a flattened, zero-padded leaf."""
        return jnp.pad(
            x.reshape(-1), (0, n_blocks * qz.BLOCK - x.size)
        ).reshape(n_blocks, qz.BLOCK)

    def unblock(x):
        """A leaf back out of its (row-padded) [R, BLOCK] view."""
        return x[:n_blocks].reshape(-1)[: leaf.size].reshape(leaf.shape)

    def quant_roundtrip_ref(x):
        b = blocks(x)
        absmax = jnp.abs(b).max(axis=1, keepdims=True)
        scale = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
        return unblock(jnp.clip(jnp.round(b / scale), -127, 127) * scale)

    cases.check(
        f"quantize + dequantize {list(LEAF)} f32",
        lambda x: qz.dequantize(*qz.quantize(x), x.shape),
        quant_roundtrip_ref, (leaf,), 1e-6, 2,
    )

    lr, b1, b2, eps = 1e-3, 0.9, 0.999, 1e-8
    g1, g2 = normal(LEAF, f32, 0.01), normal(LEAF, f32, 0.01)

    def adam_from(m, v, g, t):
        """Plain f32 Adam: update and moments after a step from (m, v)."""
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        scale = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        return -lr * m * scale / (jnp.sqrt(v) + eps), m, v

    def nibbles(packed, signed):
        """[R, BLOCK/2] bytes -> [R, BLOCK]: low nibbles, then high ones."""
        p = packed.astype(jnp.int32)
        lo, hi = (p << 28) >> 28, (p << 24) >> 28
        if not signed:
            lo, hi = lo & 0xF, hi & 0xF
        return jnp.concatenate([lo, hi], axis=1).astype(f32)

    # How each optimizer holds (m, v): the root of the domain a moment is
    # quantized in, its number of levels, and a plain jnp decoder to f32
    # (which also pins the packed layout).
    def scale(mo):
        return mo.scales[:, :1]

    def q4_m(mo):
        level = nibbles(mo.q, True) / 7.0
        return jnp.sign(level) * level * level * scale(mo)

    stores = {
        "q8_adam": (qz.q8_adam, (
            (1, 127, lambda mo: mo.q.astype(f32) * scale(mo)),
            (4, 127, lambda mo: (mo.q.astype(f32) / 127.0) ** 4 * scale(mo)),
        )),
        "q4_adam": (qz.q4_adam, (
            (2, 7, q4_m),
            (4, 15, lambda mo: (nibbles(mo.q, False) / 15.0) ** 4 * scale(mo)),
        )),
    }
    for name, (make_opt, moment_stores) in stores.items():
        opt = make_opt(learning_rate=lr, b1=b1, b2=b2, eps=eps)

        def held(p, g):
            """(m, v) the optimizer holds after one update, decoded."""
            _, state = opt.update({"w": g}, opt.init({"w": p}), {"w": p})
            return [
                unblock(decode(moment)) for moment, (_, _, decode) in zip(
                    (state.m["w"], state.v["w"]), moment_stores
                )
            ]

        def two_updates(p, g1, g2):
            u1, state = opt.update({"w": g1}, opt.init({"w": p}), {"w": p})
            u2, _ = opt.update({"w": g2}, state, {"w": p})
            return u1["w"], u2["w"]

        def two_updates_ref(p, g1, g2):
            # Each update is computed in f32 from the moments held, so it
            # equals plain Adam run from those moments.
            m, v = held(p, g1)
            return adam_from(0.0, 0.0, g1, 1.0)[0], adam_from(m, v, g2, 2.0)[0]

        cases.check(f"{name} two updates {list(LEAF)} f32",
                    two_updates, two_updates_ref, (leaf, g1, g2), 1e-4)

        # The moments held sit within half a quantization step of the f32
        # moments, in the domain they are quantized in (each block's root
        # scaled to an absmax of 1, where a step is 1 / levels).
        for i, (what, (root, levels, _)) in enumerate(
            zip("mv", moment_stores)
        ):
            def in_steps(x, g, i=i, root=root):
                want = adam_from(0.0, 0.0, g, 1.0)[1 + i]
                rooted = blocks(jnp.abs(want)) ** (1.0 / root)
                top = jnp.maximum(rooted.max(axis=1, keepdims=True), 1e-30)
                return blocks(jnp.abs(x)) ** (1.0 / root) / top

            cases.check(
                f"{name} stored {what} (1/{root} power, {levels} levels)",
                lambda p, g, i=i, f=in_steps: f(held(p, g)[i], g),
                lambda p, g, i=i, f=in_steps: f(
                    adam_from(0.0, 0.0, g, 1.0)[1 + i], g
                ),
                (leaf, g1), 0.51 / levels,
            )

    # -- embedding hot-row cache: gather and scatter -------------------------
    mode = embed_kernels.kernel_mode()
    report(f"embedding kernel_mode() -> {mode!r}")
    if mode != "pallas":
        cases.failed.append("embedding kernel_mode")
    rng = np.random.default_rng(SEED)
    n_rows, dim, n_slots = EMBED_CACHE
    cache = normal((n_rows, dim), f32)
    rows = normal((n_slots, dim), f32)
    # Live slots and a padded tail on the scratch slot 0, whose row no
    # lookup reads: it is left out of the comparison.
    live = n_slots - n_slots // 40
    slots = np.zeros(n_slots, np.int32)
    slots[:live] = rng.permutation(np.arange(1, n_rows))[:live]
    slots = jnp.asarray(slots)
    # Under the jit of check() the scatter's donation of the cache is
    # ignored, so the reference still has its operand.
    cases.check(
        f"embedding gather_rows [{n_rows},{dim}] f32, {n_slots} slots",
        embed_kernels.gather_rows,
        lambda c, s: jnp.take(c, s, axis=0), (cache, slots), 0.0,
    )
    cases.check(
        f"embedding scatter_rows [{n_rows},{dim}] f32, {n_slots} slots",
        lambda c, s, r: embed_kernels.scatter_rows(c, s, r)[1:],
        lambda c, s, r: c.at[s].set(r)[1:], (cache, slots, rows), 0.0,
    )

    if cases.failed:
        report("kernels that failed: " + "; ".join(cases.failed))
        return 1
    return 0


def phase_serve(args) -> int:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "examples"))
    import serve_lm

    from dlrover_tpu.models.transformer import TransformerLM
    from dlrover_tpu.rl.generation import GenerationBackend, SamplingParams
    from dlrover_tpu.serving.bucketing import pad_to_bucket

    t0 = time.monotonic()
    served = serve_lm.run(serve_lm.parse_args([
        "--size", SIZE, "--param-dtype", "bfloat16",
        "--attention-impl", "flash", "--slots", "8", "--requests", "8",
        "--seed", str(SEED),
    ]))
    config, params, engine = (
        served["config"], served["params"], served["engine"]
    )
    requests, results = served["requests"], served["results"]
    report(f"serve_lm.py: {SIZE} bf16, 8 slots, buckets {engine.buckets}: "
           f"AOT warm-up {served['aot_s']:.1f}s, 8 requests answered, "
           f"{time.monotonic() - t0:.1f}s in all")
    stats = engine.stats()
    report(f"engine stats: qps {stats['qps']:.2f} p50 "
           f"{stats['p50_s'] * 1e3:.0f} ms p95 {stats['p95_s'] * 1e3:.0f} ms")
    failed = []
    for req in requests:
        tokens = results[req.uid].tokens
        if len(tokens) != req.sampling.max_new_tokens or not all(
            0 <= int(t) < config.vocab_size for t in tokens
        ):
            failed.append(f"{req.uid}: wrong completion {tokens}")

    # Prefill logits, as the prefill program computes them (decode-mode
    # model, prompt padded to its bucket, flash kernel for the chunk),
    # against a plain full forward (XLA attention, no cache, no padding).
    # How far two right bf16 evaluations of a 48-layer model may lie apart
    # is measured, not guessed: the same plain forward with f32
    # activations is the yardstick, and the prefill path may be at most
    # PREFILL_SLACK times as far from it as the plain bf16 forward is.
    programs = engine.programs
    plain_cfg = dataclasses.replace(config, attention_impl="xla")
    plain = TransformerLM(plain_cfg)
    exact = TransformerLM(dataclasses.replace(plain_cfg, dtype=jnp.float32))

    @jax.jit
    def prefill_logits(params, padded, true_len):
        (logits, _), _ = programs.model.apply(
            {"params": params}, padded,
            positions=jnp.arange(padded.shape[1])[None, :],
            mutable=["cache"],
        )
        return jax.lax.dynamic_slice_in_dim(logits, true_len - 1, 1, 1)[0, 0]

    def last_logits(model):
        return jax.jit(
            lambda params, prompt: model.apply({"params": params}, prompt)[0][
                0, -1
            ]
        )

    plain_logits, exact_logits = last_logits(plain), last_logits(exact)
    worst_serve = worst_plain = 0.0
    for req in requests:
        padded, true_len = pad_to_bucket(req.prompt, engine.buckets)
        prompt = jnp.asarray(req.prompt)[None, :]
        got = prefill_logits(
            params, jnp.asarray(padded)[None, :], jnp.int32(true_len)
        )
        want = exact_logits(params, prompt)
        e_serve = _rel_err(got, want)
        e_plain = _rel_err(plain_logits(params, prompt), want)
        worst_serve, worst_plain = (
            max(worst_serve, e_serve), max(worst_plain, e_plain)
        )
        if not np.isfinite(np.asarray(got, np.float32)).all():
            failed.append(f"{req.uid}: prefill logits not finite")
        if e_serve > max(PREFILL_SLACK * e_plain, BF16_TOL):
            failed.append(
                f"{req.uid}: prefill logits {e_serve:.2e} from the f32 "
                f"forward, the plain bf16 forward {e_plain:.2e}"
            )
    report(f"prefill logits, 8 prompts, max rel err against the plain "
           f"forward in f32: {worst_serve:.2e}; the plain bf16 forward "
           f"itself: {worst_plain:.2e} (allowed: {PREFILL_SLACK} x that)")

    # Greedy requests against the repo's scan decode (one program for all:
    # 8 new tokens, prompts padded to the engine's first bucket).
    scan = GenerationBackend(
        config, SamplingParams(temperature=0.0, max_new_tokens=8),
        prompt_buckets=engine.buckets[:1],
    )
    for req in requests:
        if req.sampling.temperature != 0.0:
            continue
        tokens, _ = scan.generate(
            params, jnp.asarray(req.prompt)[None, :],
            jax.random.PRNGKey(SEED),
        )
        want = np.asarray(tokens)[0, engine.buckets[0]:]
        got = np.asarray(results[req.uid].tokens)[:8]
        differ = np.nonzero(got != want[: len(got)])[0]
        where = f"from token {differ[0] + 1}" if differ.size else "nowhere"
        report(f"{req.uid} greedy: first {len(got)} tokens {got.tolist()} "
               f"differ from the scan decode {where}")
        if differ.size:
            failed.append(f"{req.uid}: greedy tokens differ {where}")
    peak = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0
    )
    report(f"peak HBM (memory_stats): {peak / 2**30:.2f} GiB")
    if failed:
        report("serve checks that failed: " + "; ".join(failed))
        return 1
    return 0


def phase_sharded(args) -> int:
    """Three steps of the 1.5B model on data=2 x fsdp=2 with ZeRO-1, and the
    same three steps on one device: one process drives all four chips."""
    import collections
    import logging

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.gpt2 import gpt2_config
    from dlrover_tpu.models.transformer import TransformerLM
    from dlrover_tpu.parallel import rules as lr
    from dlrover_tpu.runtime import compile_cache
    from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
    from dlrover_tpu.trainer import train_lib

    rc = phase_device(args)
    if rc != 0:
        return rc
    compile_cache.maybe_enable()
    devices = jax.devices()[:4]
    config = gpt2_config(
        SIZE, max_seq_len=SEQ_LEN, param_dtype=jnp.bfloat16,
        remat="flash_only", attention_impl="flash",
    )
    seq_len = config.max_seq_len
    rng = np.random.default_rng(SEED)
    tokens = rng.integers(
        0, config.vocab_size, size=(BATCH, seq_len + 1), dtype=np.int32
    )
    batch = {"inputs": tokens[:, :-1].copy(), "targets": tokens[:, 1:].copy()}
    failed = []

    class MeshPath(logging.Handler):
        path = "mesh_utils.create_device_mesh"

        def emit(self, record):
            if "create_device_mesh failed" in record.getMessage():
                self.path = "a reshape of jax.devices() (see the warning)"

    def three_steps(name, parallel, mesh_devices, zero1):
        handler = MeshPath()
        logger = logging.getLogger("dlrover_tpu")
        logger.addHandler(handler)
        try:
            mesh = build_mesh(parallel, devices=mesh_devices)
        finally:
            logger.removeHandler(handler)
        axes = {a: n for a, n in mesh.shape.items() if n > 1}
        report(f"{name}: mesh {axes or 'of one device'} built by "
               f"{handler.path}")
        train = train_lib.build_sharded_train(
            TransformerLM(config),
            train_lib.make_optimizer("adafactor", learning_rate=1e-3),
            mesh, lr.DEFAULT_RULES, global_batch_size=BATCH,
            seq_len=seq_len, zero1=zero1,
        )
        compile_s = train.aot_compile()
        text = train._aot_step.as_text()
        state = train.init(jax.random.PRNGKey(SEED))
        placed = train_lib.shard_batch(batch, train)
        losses, t0 = [], time.monotonic()
        for _ in range(3):
            state, metrics = train.step(state, placed)
            losses.append(float(metrics["loss"]))
        report(f"{name}: compiled in {compile_s:.1f}s, 3 steps in "
               f"{time.monotonic() - t0:.2f}s, losses "
               + " ".join(f"{loss:.4f}" for loss in losses))
        return train, state, text, losses

    # -- four chips ----------------------------------------------------------
    train, state, text, losses4 = three_steps(
        "four chips", ParallelConfig(data=2, fsdp=2), devices, zero1=True
    )
    if not train.zero1:
        failed.append("ZeRO-1 is not live on the data=2 mesh")
    kernels = text.count('custom_call_target="tpu_custom_call"')
    report(f"four chips: {kernels} compiled Pallas kernels (flash forward "
           "and backward) in the step program")
    if kernels < 2:
        failed.append("no flash tpu_custom_call in the sharded step")
    by_kind = collections.Counter()
    groups = collections.defaultdict(collections.Counter)
    for kind, rest in re.findall(
        r"= \S+ (all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\((.*)", text
    ):
        by_kind[kind] += 1
        m = re.search(
            r"replica_groups=(\{\{.*?\}\}"
            r"|\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)", rest
        )
        groups[kind][m.group(1) if m else "?"] += 1
    report("four chips: collectives in the step program by kind: "
           + ", ".join(f"{k} x{c}" for k, c in sorted(by_kind.items())))
    for kind in sorted(groups):
        report(f"four chips:   {kind} replica groups: "
               + ", ".join(f"{g} x{c}" for g, c in groups[kind].most_common()))
    # On a 2 x 2 mesh the groups of two along data and those along fsdp
    # are two different partitions of the four devices.
    pairs = {
        g for kind in groups for g in groups[kind]
        if g.startswith("[2,2]") or g.count("{") == 3
    }
    if not by_kind["all-gather"] or not by_kind["all-reduce"]:
        failed.append("the step holds no all-gather or no all-reduce")
    if len(pairs) < 2:
        failed.append(f"collectives pair the devices one way only "
                      f"({sorted(pairs)}): not both mesh axes")
    sharded = whole = 0
    for leaf in jax.tree.leaves((state.params, state.opt_state)):
        if leaf.sharding.is_fully_replicated:
            whole += 1
            continue
        sharded += 1
        on = {s.device.id for s in leaf.addressable_shards}
        if len(on) != 4:
            failed.append(f"a sharded leaf {leaf.shape} lives on {on}")
    report(f"four chips: {sharded} sharded parameter and optimizer leaves, "
           f"each with shards on 4 distinct devices; {whole} replicated")
    if not sharded:
        failed.append("no sharded leaf at all")
    stats = [d.memory_stats() or {} for d in devices]
    in_use = [s.get("bytes_in_use", 0) for s in stats]
    peaks = [s.get("peak_bytes_in_use", 0) for s in stats]
    report("four chips: bytes_in_use per device "
           + " ".join(f"{b / 2**30:.2f}" for b in in_use)
           + " GiB; peak " + " ".join(f"{b / 2**30:.2f}" for b in peaks)
           + f" GiB (band: max <= {BYTES_BAND} x min)")
    if not min(in_use) or max(in_use) > BYTES_BAND * min(in_use):
        failed.append("bytes_in_use is not even over the four chips")
    del train, state, text

    # -- the same three steps on one device ----------------------------------
    _, _, _, losses1 = three_steps(
        "one device", ParallelConfig(data=1), devices[:1], zero1=False
    )
    worst = max(
        abs(a - b) / abs(b) for a, b in zip(losses4, losses1)
    )
    report(f"losses, four chips against one device: max relative "
           f"difference {worst:.2e} <= {LOSS_RTOL:.0e}")
    if not worst <= LOSS_RTOL:
        failed.append("the sharded losses are not the one-device losses")
    if failed:
        report("sharded checks that failed: " + "; ".join(failed))
        return 1
    return 0


PHASES = {
    "device": phase_device,
    "kernels": phase_kernels,
    "serve": phase_serve,
    "sharded": phase_sharded,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded step and its one-device "
                         "reference, on four chips")
    ap.add_argument("--log-dir", default="",
                    help="keep each phase's full output here (default: a "
                         "temporary directory, removed at the end)")
    ap.add_argument("--phase", choices=sorted(PHASES), default="",
                    help=argparse.SUPPRESS)  # a child of this script
    args = ap.parse_args(argv)
    if args.phase:
        return PHASES[args.phase](args)
    return run_phases(args)


if __name__ == "__main__":
    sys.exit(main())
