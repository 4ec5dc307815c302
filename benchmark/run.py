"""One cell, once: ``python -m benchmark.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>``, from the root of a checkout.

Everything that belongs to one cell is data: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``benchmark/configs/<config>.json``
and ``benchmark/traffic/<traffic>.json`` hold them, the traffic file names
its scenario (``benchmark/scenarios/<scenario>.py``), and each per-layer
metric is ``benchmark/layer_metrics/<metric>.json`` with a reader under
``benchmark/readers/``.  This file looks nothing up by a cell's name.

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``).  Every earlier line that starts with
``{`` is a note of the run (its readings, its reference check, its
spans).  Without a TPU, or with fewer chips than the cell asks for, the
exit code is not 0 and no result is printed.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict  # noqa: E402

from benchmark import build, layers  # noqa: E402

REPO = os.path.dirname(build.ROOT)
RUNS_DIR = os.path.join(REPO, ".bench_runs")
REHEARSAL_CONFIGS = os.path.join(
    REPO, "tests", "benchmark_suite", "presets"
)


@dataclasses.dataclass
class Context:
    manifest: Dict[str, Any]
    cell: str
    config: Dict[str, Any]
    config_file: str
    traffic: Dict[str, Any]
    traffic_file: str
    chips: int
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    t0: float
    run_dir: str

    def say(self, note: Dict[str, Any]):
        print(json.dumps(note), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearsal", action="store_true",
        help="run the scenario on whatever backend jax has, at the tiny "
             "preset under tests/benchmark_suite/presets, and print no "
             "number: every metric's value is null",
    )
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    manifest = build.manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no workload {args.workload!r} in BENCHMARK.json; "
              f"there are {sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config_file = os.path.join(REPO, configs[cell["config"]]["file"])
    if args.rehearsal:
        config_file = os.path.join(
            REHEARSAL_CONFIGS, os.path.basename(config_file)
        )
    traffic_file = os.path.join(
        build.ROOT, "traffic", f"{cell['traffic']}.json"
    )
    traffic = build.load_json(traffic_file)
    if args.rehearsal:
        traffic.update(traffic.get("rehearsal", {}))
    # The program (dlrover_tpu) is imported from here on; a directory that
    # holds only the benchmark fails at this import, with no result line.
    scenario = importlib.import_module(
        f"benchmark.scenarios.{traffic['scenario']}"
    )
    run_dir = os.path.join(RUNS_DIR, f"run{os.getpid()}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    ctx = Context(
        manifest=manifest, cell=cell["name"],
        config=build.load_json(config_file), config_file=config_file,
        traffic=traffic, traffic_file=traffic_file,
        chips=int(cell["chips"]), seed=args.seed,
        seconds=float(
            manifest["run_seconds"] if args.seconds is None else args.seconds
        ),
        trace=bool(args.trace), rehearsal=args.rehearsal, t0=T0,
        run_dir=run_dir,
    )
    try:
        result = scenario.run(ctx)
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    group = "per_layer" if ctx.trace else "end_to_end"
    metrics = {}
    for entry in layers.cell_entries(manifest, ctx.cell, group):
        value = result[group].get(entry["name"])
        if isinstance(value, dict):
            value = value["value"]
        if value is None:
            continue
        metrics[entry["name"]] = {
            "value": None if args.rehearsal else value,
            "unit": entry["unit"],
        }
    line = {
        "correct": result["correct"],
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": metrics,
        "device": result["device"],
    }
    if ctx.trace and result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    if args.rehearsal:
        line["rehearsal"] = True
        line.pop("breakdown", None)
        for key in ("busy_s", "window_s"):
            line["device"].pop(key, None)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
