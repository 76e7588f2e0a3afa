#!/usr/bin/env python
"""Offline cross-rank flight-dump diagnosis (runnable standalone AND
importable — the test suite calls ``main()`` in-process).

Given a directory of ``flightdump.<rank>.<generation>.json`` files (the
gang supervisor points ``PADDLE_FLIGHT_DUMP_DIR`` at its log dir, so
after a wedge the dumps sit next to the workerlogs), print the SAME
cross-rank diagnosis the supervisor's failure report emits —
``flight_recorder.diagnose_dir`` is the single shared implementation,
so this output reproduces the supervisor's byte-for-byte.

Usage:
    python tools/flight_report.py <dump_dir> [--generation N]
                                  [--world W] [--json]

Exit codes: 0 = diagnosis printed, 2 = no dumps found in the dir.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    from paddle_tpu.distributed.resilience import flight_recorder

    parser = argparse.ArgumentParser("tools/flight_report.py")
    parser.add_argument("dump_dir",
                        help="directory holding flightdump.*.json "
                             "(the supervisor's log dir)")
    parser.add_argument("--generation", type=int, default=None,
                        help="restart generation to diagnose "
                             "(default: newest present)")
    parser.add_argument("--world", type=int, default=None,
                        help="gang size, to name ranks with missing "
                             "dumps (default: from the dump headers)")
    parser.add_argument("--json", action="store_true",
                        help="print the structured verdict instead of "
                             "the human text")
    args = parser.parse_args(argv)

    text, diag = flight_recorder.diagnose_dir(
        args.dump_dir, world=args.world, generation=args.generation)
    if not diag["ranks_with_dump"] and not diag["missing_dump_errors"]:
        print(f"flight_report: no flight dumps in {args.dump_dir!r} "
              "(recorder disabled, or the gang never wedged?)",
              file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(diag, indent=2, default=str))
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, REPO_ROOT)
    # a structure check, not a measurement: standalone runs pin the CPU
    # (same lever as tests/conftest.py: the config override wins over env)
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.exit(main())
