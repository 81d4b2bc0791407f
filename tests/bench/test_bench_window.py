"""Window arithmetic: a stall enters the tails, rates count partial
work."""
import pytest

from bench import window
from bench.window import Rec


def test_a_stalled_request_enters_the_ttft_tail_with_its_wait():
    recs = [Rec(0, 1.0, 10, 4, tok=[1.2, 1.3]),
            Rec(1, 2.0, 10, 4, tok=[]),           # never got a token
            Rec(2, 3.0, 10, 4, tok=[12.0]),       # first token after close
            Rec(3, -1.0, 10, 4, tok=[0.5]),       # due before the window
            Rec(4, None, 10, 4, tok=[0.5])]       # backlog
    got = window.ttft_ms(recs, close=10.0)
    assert got == pytest.approx([200.0, 8000.0, 7000.0])
    # sorted [200, 7000, 8000]: the 95th percentile lies 0.9 of the way
    # from 7000 to 8000, so the stalled request sets the tail
    assert window.p95(got) == pytest.approx(7900.0)


def test_queue_wait_counts_unadmitted_requests_to_the_close():
    recs = [Rec(0, 1.0, 10, 4, admit=1.5), Rec(1, 2.0, 10, 4, admit=None)]
    assert window.queue_wait_ms(recs, 5.0) == pytest.approx([500.0, 3000.0])


def test_itl_takes_gaps_inside_the_window_only():
    recs = [Rec(0, 0.0, 1, 5, tok=[-0.5, 0.1, 0.3, 0.6, 10.5])]
    assert window.itl_ms(recs, close=10.0) == pytest.approx([200.0, 300.0])


def test_rates_count_partial_work_inside_the_window():
    recs = [Rec(0, None, 10, 4, tok=[-1.0, 0.5, 9.9, 10.1])]
    assert window.tokens_in(recs, 10.0) == 2
    # a prompt half prefilled before the window, a quarter inside it
    prefill = [(-0.1, 0, 0, 64), (2.0, 0, 64, 96), (11.0, 0, 96, 128)]
    assert window.prompt_tokens_in(prefill, 10.0) == 32


def test_inflight_intervals_merge_and_clip():
    recs = [Rec(0, None, 1, 1, submit=-5.0, done=2.0),
            Rec(1, 1.0, 1, 1, submit=1.0, done=3.0),
            Rec(2, 6.0, 1, 1, submit=6.0, done=None),
            Rec(3, 9.0, 1, 1, submit=None)]
    assert window.inflight(recs, 8.0) == [(0.0, 3.0), (6.0, 8.0)]
