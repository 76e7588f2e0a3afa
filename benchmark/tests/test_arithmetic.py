"""The trace reduction and the latency arithmetic on hand-made inputs, and
the schedule's promise that every seed gets the same work."""
import json
import os

import pytest

from benchmark import latency, schedule, trace_reduce

MS = 1_000_000          # ns


def _trace():
    ops = [("%while.1 = (s32[], bf16[8,16]{1,0}) while(%t), body=%b",
            0, 10 * MS),                                # covers its body
           ("%fusion.1 = bf16[8,16]{1,0:T(8,128)} fusion(%p), kind=kLoop",
            1 * MS, 3 * MS),
           ('%closed_call.7 = bf16[8,20,16,64]{3,2,1,0} custom-call(%q), '
            'custom_call_target="tpu_custom_call"', 5 * MS, 4 * MS),
           ("%fusion.1 = bf16[8,16]{1,0:T(8,128)} fusion(%p), kind=kLoop",
            20 * MS, 5 * MS),
           ("%copy.3 = bf16[36,2,128]{2,1,0} copy(%x)", 40 * MS, 10 * MS)]
    host = [("train_step", 24 * MS, 20 * MS), ("feed_batch", 26 * MS, 2 * MS),
            ("other", 0, 100 * MS)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [("jit_step", 0, 50 * MS)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}


def test_busy_is_a_union_not_a_sum():
    r = trace_reduce.reduce(_trace(), 0.1, ("train_step", "feed_batch"))
    assert r["busy_s"] == pytest.approx(0.025)        # 10 + 5 + 10 ms
    assert sum(d for _, _, d in _trace()["planes"][0]["lines"][0]
               ["events"]) == 32 * MS                  # the sum says 32
    assert r["window_s"] == 0.1


def test_self_time_takes_children_out_of_their_parent():
    r = trace_reduce.reduce(_trace(), 0.1)
    ops = dict(r["device_ops"])
    assert ops["fusion.1 bf16[8,16]"] == pytest.approx(0.008)   # twice
    assert ops["while.1"] == pytest.approx(0.003)      # 10 - 3 - 4 ms
    assert ops["closed_call.7 bf16[8,20,16,64]"] == pytest.approx(0.004)
    assert r["pallas_s"] == pytest.approx(0.004)
    assert list(ops)[0] == "copy.3 bf16[36,2,128]"     # most time first


def test_idle_gaps_carry_the_host_span_that_covers_them():
    r = trace_reduce.reduce(_trace(), 0.1, ("train_step", "feed_batch"))
    assert r["idle_gaps"] == [["train_step", pytest.approx(0.015)],
                              ["unattributed", pytest.approx(0.010)]]


def test_no_device_plane_gives_nothing():
    t = _trace()
    t["planes"] = t["planes"][1:]
    assert trace_reduce.reduce(t, 0.1) is None


def _rec(due, send, events, max_tokens, done=True, status=200, end=None):
    return {"id": "r", "due": due, "send": send, "status": status,
            "max_tokens": max_tokens, "events": events, "done": done,
            "end": end if end is not None else (events[-1][0] if events
                                                else send), "error": None}


def test_ttft_counts_from_when_the_request_was_due():
    r = _rec(10.0, 10.2, [[11.0, 1], [11.4, 4], [12.0, 3]], 8)
    assert latency.ttft_s(r) == pytest.approx(1.0)
    assert latency.lateness_s(r) == pytest.approx(0.2)
    # 1.0 s for the 7 tokens after the first event
    assert latency.tpot_s(r) == pytest.approx(1.0 / 7)
    assert not latency.failed(r)


def test_tpot_is_robust_to_the_first_chunk_size():
    r = _rec(0.0, 0.0, [[1.0, 4], [2.0, 4]], 8)
    assert latency.tpot_s(r) == pytest.approx(0.25)
    assert latency.tpot_s(_rec(0.0, 0.0, [[1.0, 8]], 8)) is None


def test_failed_requests():
    assert latency.failed(_rec(0, 0, [[1.0, 4]], 8))             # short
    assert latency.failed(_rec(0, 0, [[1.0, 8]], 8, done=False))  # cut
    assert latency.failed(_rec(0, 0, [], 8, status=429))


def test_windows_count_differently_below_and_above_the_knee():
    recs = [_rec(1.0, 1.0, [[2.0, 4], [3.0, 4]], 8),
            _rec(5.0, 5.0, [[9.0, 4], [12.0, 4]], 8),       # ends after w1
            _rec(9.0, 9.0, [[11.0, 2]], 8, done=False, end=None)]
    recs[2]["end"] = None
    below = latency.due_in_window(recs, 0.0, 10.0)
    assert (below["attempted"], below["failed"]) == (3, 1)
    assert below["tokens_in_window"] == 12
    above = latency.finished_in_window(recs, 0.0, 10.0)
    assert (above["attempted"], above["failed"]) == (1, 0)
    assert above["tokens_in_window"] == 12


def test_percentile_interpolates():
    assert latency.percentile([1, 2, 3, 4, 5], 50) == 3
    assert latency.percentile([0, 10], 95) == pytest.approx(9.5)
    assert latency.percentile([], 95) is None


def test_every_seed_gets_the_same_work_in_another_order():
    t = json.load(open(os.path.join(os.path.dirname(__file__), "..",
                                    "traffic", "chat_steady.json")))
    a = schedule.build(t, 1, 200, 50257)
    b = schedule.build(t, 2 ** 31 + 77, 200, 50257)
    k = t["block"]
    for s in (a, b):
        sizes = sorted(len(r["prompt"]) for r in s[:k])
        assert sizes == sorted(len(r["prompt"]) for r in s[k:2 * k])
        assert s[k - 1]["due_s"] == pytest.approx(k / t["rate_per_s"])
    assert sorted(len(r["prompt"]) for r in a[:k]) == \
        sorted(len(r["prompt"]) for r in b[:k])
    assert sorted(r["max_tokens"] for r in a[:k]) == \
        sorted(r["max_tokens"] for r in b[:k])
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    lo, hi = t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]
    assert all(lo <= len(r["prompt"]) <= hi for r in a)
    assert all(len(r["prompt"]) + r["max_tokens"] < 1024 for r in a)
    assert schedule.build(t, 1, 200, 50257) == a        # same seed, same


def _traffic(**kw):
    t = json.load(open(os.path.join(os.path.dirname(__file__), "..",
                                    "traffic", "chat_steady.json")))
    t.update(kw)
    return t


def test_bursts_keep_the_rate_and_arrive_together():
    s = schedule.build(_traffic(arrivals="bursts", burst=4, rate_per_s=2.0),
                       5, 40, 50257)
    assert s[23]["due_s"] == pytest.approx(24 / 2.0)
    gaps = [b["due_s"] - a["due_s"] for a, b in zip(s, s[1:24])]
    assert sum(g == pytest.approx(0.01) for g in gaps) == 18    # 6 x 3
    assert all(g == pytest.approx(0.01) for g in gaps[:3])


def test_sessions_share_their_own_prefix():
    s = schedule.build(_traffic(shared_prefix_tokens=32, session_turns=3),
                       5, 40, 50257)
    assert s[0]["prompt"][:32] == s[1]["prompt"][:32] == s[2]["prompt"][:32]
    assert s[3]["prompt"][:32] != s[2]["prompt"][:32]
    assert s[3]["prompt"][:32] == s[5]["prompt"][:32]
    one = schedule.build(_traffic(shared_prefix_tokens=32), 5, 40, 50257)
    assert one[0]["prompt"][:32] == one[30]["prompt"][:32]
