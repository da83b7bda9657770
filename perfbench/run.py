"""perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell on the chips this machine holds. Refuses anything
but a TPU that ``peaks.json`` knows (no result line, exit code 2). The
cell's files are found by its name (``perfbench/cells.py``); its traffic
module makes the inputs from the seed, warms up, measures and compares.
The last line of standard output is the result; the numbers compared,
each beside its limit, are also the last lines of standard error.
"""

from __future__ import annotations

import time

T0 = time.time()                     # set-up is counted from here

import argparse                      # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import cells          # noqa: E402


def find_device(chips: int, need_tpu: bool = True) -> tuple:
    """``(device, peaks)`` of this machine, or ``BenchError``."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if need_tpu and device["platform"] != "tpu":
        raise cells.BenchError(f"no TPU: JAX found {device}")
    if device["count"] < chips:
        raise cells.BenchError(f"the cell needs {chips} chips: {device}")
    return device, cells.device_peaks(device["kind"])


def main(argv=None, root: str = ROOT, device=None) -> int:
    """``device`` is for the tests only: a ``(device, peaks)`` pair that
    stands in for the look for a chip."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cells.load_cell(args.workload, root)
        dev, peaks = device or find_device(cell["chips"])
        traffic = cells.traffic_module(cell["traffic"]["kind"])
        env = {"t0": T0 if argv is None else time.time(), "device": dev,
               "peaks": peaks}
        result = traffic.run(cell, args.seed, args.seconds, bool(args.trace),
                             env)
    except cells.BenchError as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(cells.last_line(
        result["correct"], result["attempted"], result["failed"],
        result["metrics"], result["device"], result["checks"],
        result["breakdown"], result.get("observed"))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
