"""Percentile, TPOT and attainment arithmetic; failures count as misses."""

import bench_paths  # noqa: F401
import pytest
from lib.arith import attainment_pct, percentile, tpot_ms


@pytest.mark.parametrize("values,q,want", [
    ([], 95, None),
    ([7.0], 95, 7.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4, 5], 100, 5.0),
    ([1, 2, 3, 4, 5], 0, 1.0),
    ([10, 20], 95, 19.5),
    (list(range(1, 101)), 95, 95.05),
])
def test_percentile_is_numpys_linear_interpolation(values, q, want):
    import numpy as np

    got = percentile(values, q)
    assert got == (want if want is None else pytest.approx(want))
    if values:
        assert got == pytest.approx(float(np.percentile(values, q)))


def test_tpot_is_time_after_first_token_per_later_token():
    assert tpot_ms(1.0, 1.9, 10) == pytest.approx(100.0)
    assert tpot_ms(1.0, 1.0, 1) is None
    assert tpot_ms(0.5, 0.7, 2) == pytest.approx(200.0)


def rec(ok=True, ttft=100.0, tpot=20.0):
    return {"ok": ok, "ttft_ms": ttft if ok else None,
            "tpot_ms": tpot if ok else None}


@pytest.mark.parametrize("records,want", [
    ([], None),
    ([rec()], 100.0),
    ([rec(), rec(ok=False)], 50.0),                 # a failure is a miss
    ([rec(), rec(ttft=600.0)], 50.0),               # TTFT over its limit
    ([rec(), rec(tpot=51.0)], 50.0),                # TPOT over its limit
    ([rec(tpot=None), rec(ok=False), rec(ok=False), rec(ok=False)], 25.0),
])
def test_attainment_counts_requests_sent_and_failures_as_misses(
        records, want):
    got = attainment_pct(records, 500.0, 50.0)
    assert got == (want if want is None else pytest.approx(want))


def finished(req, t_first, t_last, n, status=200, done=True, error=None,
             token=1, reserved=()):
    from lib.client import finish_record

    rec = {"status": status, "t_sent": req.due_s + 0.001,
           "t_first": t_first, "t_last": t_last, "done": done,
           "error": error, "tokens": [[1] * (n - 1) + [token]] if n else None}
    return finish_record(req, rec, 512, 40.0, reserved)


@pytest.mark.parametrize("token, reserved, ok", [
    (500, (), True), (500, (500,), False), (499, (500,), True),
    (511, (3, 511), False), (512, (), False), (-1, (), False),
], ids=["nothing-reserved", "the-mask-id-streamed", "its-neighbour",
        "the-last-id-reserved", "out-of-range", "negative"])
def test_an_answer_that_holds_a_reserved_id_is_a_failed_request(
        token, reserved, ok):
    """The exact check every answer gets, limit 0: a finished stream of
    the right length whose last id is one the configuration reserves is
    not ok, and is charged at the window's end like any failure."""
    from lib.traffic import Request

    rec = finished(Request(0, 10.0, 64, 20, True), t_first=10.2,
                   t_last=10.58, n=20, token=token, reserved=reserved)
    assert rec["ok"] is ok and rec["status"] == 200 and rec["error"] is None
    assert (rec["tpot_ms"] == pytest.approx(20.0)) is ok


@pytest.mark.parametrize("case,ttft,tpot", [
    # finished: first chunk 0.2 s after due, 19 more tokens over 0.38 s
    (dict(t_first=10.2, t_last=10.58, n=20), 200.0, 20.0),
    # cut by the window's end after 11 of 20 tokens: charged to the end
    (dict(t_first=10.2, t_last=12.0, n=11, done=False,
          error="TimeoutError: "), 200.0, 2980.0),
    # refused: no token at all, both charged at the window's end
    (dict(t_first=None, t_last=None, n=0, status=503, done=False,
          error="busy"), 30000.0, 30000.0),
])
def test_a_failed_request_is_charged_at_the_windows_end(case, ttft, tpot):
    from lib.traffic import Request

    rec = finished(Request(0, 10.0, 64, 20, True), **case)
    assert rec["ok"] == (case.get("error") is None)
    assert rec["ttft_ms"] == pytest.approx(ttft)
    assert rec["tpot_ms"] == pytest.approx(tpot)


def test_a_tail_over_requests_sent_gets_worse_when_requests_fail():
    import run as bench_run
    from lib.traffic import Request

    good = [finished(Request(i, 1.0 + i, 64, 20, True),
                     t_first=1.2 + i, t_last=1.58 + i, n=20)
            for i in range(20)]
    base = bench_run.tails(good)
    assert base["ttft_p90_ms"] == pytest.approx(200.0)
    assert base["tpot_p90_ms"] == pytest.approx(20.0)
    # the three slowest-to-be requests time out instead of finishing
    # late: a tail over the finished ones alone would not move
    for i in (17, 18, 19):
        good[i] = finished(Request(i, 1.0 + i, 64, 20, True),
                           t_first=1.2 + i, t_last=5.0 + i, n=3,
                           done=False, error="TimeoutError: ")
    worse = bench_run.tails(good)
    assert worse["tpot_p90_ms"] > 100 * base["tpot_p90_ms"]
    assert worse["ttft_p90_ms"] == pytest.approx(200.0)
    assert attainment_pct(good, 1000.0, 30.0) == pytest.approx(85.0)
