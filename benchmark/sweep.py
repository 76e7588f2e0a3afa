"""Find the knee of a serving cell ONCE, by a sweep on the chip: one process,
one set-up, one window per offered rate.

    python benchmark/sweep.py --workload gpt2_large.chat_steady --seed 1
        --seconds 20 [--rates 0.5,1,2,4] [--out chiprun_out/sweep.json]

Without ``--rates`` it doubles from 0.5 requests/s until a rate is not
sustained, then bisects twice between the last sustained rate and the first
that was not. A rate is sustained when at least 95% of the requests due in
the window finish within ``drain_s`` after it AND the backlog (requests
queued plus slots in use, sampled through the window) is no higher over the
window's last fifth than over its middle fifth (+1 for noise). The rates
that go into the traffic files are then written by hand: 0.8 x the knee
below it, 1.3 x above. This file measures nothing that a check reads.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import tempfile                 # noqa: E402
from statistics import mean     # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, latency          # noqa: E402
from benchmark.runners import serve             # noqa: E402


def one_rate(sess, traffic, rate, seconds, tmp):
    t = dict(traffic, rate_per_s=rate, count="due_in_window")
    t["drain_s"] = max(float(traffic["drain_s"]), 15.0)
    obs = sess.window(t, seconds, 0, tmp)
    s = latency.due_in_window(obs["records"], obs["w0"], obs["w1"])
    load = [(x["t"], x["queue_depth"] + x["occupancy"] * sess.eng.num_slots)
            for x in obs["samples"]]
    span = obs["w1"] - obs["w0"]
    mid = [v for t_, v in load
           if 0.4 * span <= t_ - obs["w0"] < 0.6 * span]
    end = [v for t_, v in load if t_ - obs["w0"] >= 0.8 * span]
    done_share = 1.0 - s["failed"] / max(s["attempted"], 1)
    row = {"rate_per_s": rate, "attempted": s["attempted"],
           "finished_share": done_share, "backlog_mid": mean(mid),
           "backlog_end": mean(end),
           "sustained": bool(done_share >= 0.95
                             and mean(end) <= mean(mid) + 1.0),
           "serve_tok_s": s["tokens_in_window"] / seconds,
           "retraces": obs["retraces"]}
    for k in ("ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms",
              "lateness_p95_ms"):
        row[k] = s[k]
    harness.say("sweep row: " + json.dumps(row))
    # let the engine work off what this rate left behind
    t0 = time.monotonic()
    while sess.eng.has_work and time.monotonic() - t0 < 120:
        time.sleep(0.2)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--manifest", default=os.path.join(harness.REPO,
                                                       "BENCHMARK.json"))
    a = ap.parse_args(argv)
    cell = harness.Cell(a.manifest, a.workload)
    tmp = tempfile.mkdtemp(prefix="bench_")
    sess = serve.Session(cell.config, a.seed, cell.chips)
    rows = []
    try:
        sess.warm_up_and_check(cell.traffic)
        harness.say(f"set-up {time.monotonic() - T_START:.1f} s")
        if a.rates:
            for r in a.rates.split(","):
                rows.append(one_rate(sess, cell.traffic, float(r),
                                     a.seconds, tmp))
        else:
            rate, ok, bad = 0.5, None, None
            while bad is None and rate <= 64:
                row = one_rate(sess, cell.traffic, rate, a.seconds, tmp)
                rows.append(row)
                if row["sustained"]:
                    ok, rate = rate, rate * 2
                else:
                    bad = rate
            for _ in range(2):
                if ok is None or bad is None:
                    break
                row = one_rate(sess, cell.traffic, (ok + bad) / 2,
                               a.seconds, tmp)
                rows.append(row)
                if row["sustained"]:
                    ok = row["rate_per_s"]
                else:
                    bad = row["rate_per_s"]
            harness.say(f"knee: highest sustained rate {ok} req/s, lowest "
                        f"not sustained {bad} req/s")
    finally:
        sess.close()
        shutil.rmtree(tmp, ignore_errors=True)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"workload": a.workload, "seconds": a.seconds,
                       "device": harness.device_record(sess.devs),
                       "rows": rows}, f, indent=1)
    print(json.dumps(rows), flush=True)


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)
