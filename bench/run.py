"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, the ranker, warming every program the
cell's traffic reaches) is timed as ``setup_s``; the window then offers the
cell's traffic open-loop for ``--seconds``; the plain reference checks what
was served. The last line of standard output is one JSON object; the checks
that decide ``correct`` are also the last lines of standard error. With
``--trace 1`` the run records a profiler window and reports the per-layer
metrics instead of the end-to-end ones.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    chips = cells[args.workload]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3

    from bench import harness
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    for line in harness.check_lines(result):
        harness.log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
