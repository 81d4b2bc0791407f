"""The reduction from a profiler trace to intervals on the window's clock,
on a small synthetic event list."""
import pytest

from bench import trace as tr

NS = 1_000_000  # events below are in milliseconds


def _planes():
    t0 = 1000 * NS  # the anchor opens here, at window time 0.5 s
    host = {"python": [("bench.window", t0, t0 + 2000 * NS),
                       ("bench.step", t0 + 0, t0 + 300 * NS),
                       ("bench.wait", t0 + 300 * NS, t0 + 700 * NS),
                       ("bench.step", t0 + 700 * NS, t0 + 1600 * NS),
                       ("other", t0, t0 + 5 * NS)]}
    dev = {
        "XLA Modules": [("jit_fused_step(7)", t0 + 10 * NS, t0 + 110 * NS),
                        ("jit_fused_step(7)", t0 + 800 * NS,
                         t0 + 900 * NS),
                        ("jit_prefill_chunk(3)", t0 + 900 * NS,
                         t0 + 1300 * NS)],
        "XLA Ops": [("fusion.1", t0 + 10 * NS, t0 + 60 * NS),
                    ("fusion.1", t0 + 50 * NS, t0 + 110 * NS),
                    ("paged_decode_kernel", t0 + 800 * NS, t0 + 900 * NS),
                    ("dot.2", t0 + 900 * NS, t0 + 1300 * NS),
                    ("late", t0 + 1900 * NS, t0 + 2100 * NS)],
    }
    return [("/host:CPU", host), ("/device:TPU:0", dev),
            ("/host:metadata", {})]


def test_reduce_moves_events_onto_the_window_clock():
    t = tr.reduce(_planes(), close=2.0, anchor=0.5)
    (d,) = t.devices
    assert d.modules[0] == ("jit_fused_step(7)", pytest.approx(0.51),
                            pytest.approx(0.61))
    assert [s[0] for s in t.spans] == ["bench.step", "bench.wait",
                                       "bench.step"]


def test_busy_is_the_union_of_ops_inside_the_window():
    t = tr.reduce(_planes(), close=2.0, anchor=0.0)
    # ops: [0.01, 0.11] (two overlapping), [0.8, 0.9], [0.9, 1.3],
    # [1.9, 2.1] clipped to 2.0
    assert t.busy_s() == pytest.approx(0.1 + 0.5 + 0.1)
    assert t.busy_s([(0.0, 0.5)]) == pytest.approx(0.1)


def test_module_and_op_time():
    t = tr.reduce(_planes(), close=2.0, anchor=0.0)
    secs, n = t.time_of("modules", lambda s: "fused_step" in s)
    assert (secs, n) == (pytest.approx(0.2), 2)
    secs, n = t.time_of("ops", lambda s: "paged_decode" in s)
    assert (secs, n) == (pytest.approx(0.1), 1)
    assert t.top_ops(2)[0] == ["dot.2", pytest.approx(0.4)]


def test_idle_gaps_go_to_the_host_span_around_them():
    t = tr.reduce(_planes(), close=2.0, anchor=0.0)
    gaps = dict((n, s) for n, s in t.idle_gaps())
    # gaps: [0, .01] step, [.11, .8] mid .455 wait, [1.3, 1.9] mid 1.6
    # step (its end), no span after
    assert gaps["bench.wait"] == pytest.approx(0.69)
    assert gaps["bench.step"] == pytest.approx(0.01 + 0.6)


def test_a_trace_without_the_anchor_is_refused():
    planes = [(n, {k: [e for e in v if e[0] != tr.ANCHOR]
                   for k, v in lines.items()}) for n, lines in _planes()]
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce(planes, close=2.0)
