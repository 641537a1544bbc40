"""The end-to-end arithmetic on hand-made timestamps: time to first token
over every request due in the window (unanswered ones count their wait),
and every gap between tokens."""
import json

import pytest

import harness
import run

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())


def _run(served, close=10.0):
    rec = harness.Record(seconds=close)
    rec.close = close
    for s in served:
        rec.served[s.rid] = s
    cell = run.Cell(SPEC, "internlm2-1.8b.conversation")
    return run.Run(cell, rec, 1.0, {}, None)


def _served(rid, due, times, admitted=None):
    return harness.Served(rid=rid, due=due, admitted=admitted,
                          token_times=list(times))


def test_ttft_counts_unanswered_requests_with_their_wait():
    served = [_served(i, float(i), [i + 0.1]) for i in range(9)]
    served.append(_served(9, 4.0, []))            # never answered: 6 s
    r = _run(served)
    assert run.load_reader("ttft_p90_ms")(r) == pytest.approx(100.0)
    served.append(_served(10, 2.0, []))           # a second: 8 s
    r = _run(served)
    assert run.load_reader("ttft_p90_ms")(r) == pytest.approx(6000.0)


def test_ttft_ignores_tokens_after_the_close_and_requests_due_after():
    served = [_served(0, 1.0, [12.0]), _served(1, 11.0, [11.5])]
    r = _run(served, close=10.0)
    assert run.load_reader("ttft_p90_ms")(r) == pytest.approx(9000.0)


def test_itl_takes_every_gap_inside_the_window():
    served = [_served(0, 0.0, [1.0, 1.1, 1.2, 1.5]),
              _served(1, 0.0, [2.0, 2.05, 9.0, 11.0])]
    r = _run(served, close=10.0)
    # gaps: 0.1 0.1 0.3 0.05 6.95 (the one past the close is left out)
    assert run.load_reader("itl_p95_ms")(r) == pytest.approx(6950.0)


def test_queue_wait_counts_requests_never_admitted():
    served = [_served(i, float(i), [], admitted=i + 0.2) for i in range(9)]
    served.append(_served(9, 5.0, []))
    r = _run(served, close=10.0)
    assert run.load_reader("queue_wait_p90_ms")(r) == pytest.approx(200.0)
