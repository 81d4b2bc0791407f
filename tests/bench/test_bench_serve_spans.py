"""The serving engine writes spans of its own (``serve.*``) into the trace
beside the benchmark's ``bench.*`` spans. The benchmark's reduction reads a
trace that holds them exactly as it reads one without them, so no accepted
metric or breakdown moves because the program is instrumented."""
import pytest
from benchutil import run_cell
from test_bench_trace import NS, _planes

from bench import trace as tr


def _with_serve_spans():
    """The fixture, with the engine's spans nested inside each
    ``bench.step`` (one of them inside the device's idle gap there)."""
    planes = _planes()
    host = planes[0][1]["python"]
    t0 = next(s for n, s, _ in host if n == tr.ANCHOR)
    ms = [(0, 300, "serve.step"), (0, 8, "serve.admit"),
          (8, 290, "serve.decode"), (20, 110, "serve.sync"),
          (700, 1600, "serve.step"), (1250, 1550, "serve.flush"),
          (1250, 1300, "serve.sync")]
    host.extend((n, t0 + a * NS, t0 + b * NS) for a, b, n in ms)
    return planes


@pytest.mark.parametrize("anchor", [0.0, 0.5])
def test_serve_spans_leave_the_reduction_as_it_was(anchor):
    plain = tr.reduce(_planes(), close=2.0, anchor=anchor)
    spanned = tr.reduce(_with_serve_spans(), close=2.0, anchor=anchor)
    assert spanned.spans == plain.spans
    assert spanned.devices == plain.devices
    assert spanned.busy_s() == plain.busy_s()
    assert spanned.time_of("modules", lambda n: "fused_step" in n) \
        == plain.time_of("modules", lambda n: "fused_step" in n)
    assert spanned.top_ops() == plain.top_ops()
    assert spanned.idle_gaps() == plain.idle_gaps()


def test_a_traced_run_of_the_instrumented_engine(tiny_root):
    rc, out = run_cell(tiny_root, trace=1)
    assert rc == 0
    assert out["correct"], out["checks"]
    assert all(n == "other" or n.startswith("bench.")
               for n, _ in out["breakdown"]["idle_gaps"])
    assert "queue_wait_p95_ms" in out["metrics"]
