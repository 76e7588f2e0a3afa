"""The benchmark's one command.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s>
                            --trace <0|1>

finds the workload in ``BENCHMARK.json``, loads its configuration and its
traffic file, hands both to ``benchmark/runners/<kind>.py`` (the ``kind`` the
configuration file names), which sets up, warms up, measures for
``--seconds`` and checks the outputs, and prints as the LAST line of its
output one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics), ``device`` and, traced, ``breakdown``. It runs on a TPU or exits
non-zero with no result line. See ``benchmark/README.md``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()      # set-up is counted from here

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness   # noqa: E402


def main(argv=None, t_start=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(
        harness.REPO, "BENCHMARK.json"),
        help="another manifest (the tests' tiny one); never a cell")
    ap.add_argument("--keep", default=None,
                    help="keep the profiler's trace in this directory")
    args = ap.parse_args(argv)
    t_start = T_START if t_start is None else t_start

    cell = harness.Cell(args.manifest, args.workload)
    runner = harness.load_part("runners", cell.config["kind"])
    res = runner.run(cell, args, t_start)
    obs = res["obs"]
    if args.trace:
        if obs["trace"] is None:
            raise harness.BenchmarkError(
                "the traced run found no device operation in its trace")
        metrics = harness.read_layer_metrics(cell, obs)
    else:
        metrics = harness.pick_end_to_end(cell, res["values"])
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics,
            "device": harness.device_record(res["devs"], obs["trace"])}
    if args.trace:
        line["breakdown"] = {"device_ops": obs["trace"]["device_ops"],
                             "idle_gaps": obs["trace"]["idle_gaps"]}
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
    # every process the run started has been waited for; what is left are
    # the program's daemon threads (gateway handlers, the replica's driver),
    # which must not hold the interpreter's shutdown
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)
