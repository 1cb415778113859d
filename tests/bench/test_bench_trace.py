"""The reduction from a profiler trace to the per-layer metrics, on a small
trace recorded on a TPU v5e (three blocks of a jitted matmul and one Pallas
``sumsq`` kernel, each followed by a 10 ms host edge) and on hand-made op
lists."""
from pathlib import Path

import pytest

import benchtiny  # noqa: F401  (puts the repository on the path)
from bench import harness, trace

TINY = Path(__file__).resolve().parent / "data" / "tiny_tpu.xplane.pb"


@pytest.fixture(scope="module")
def tiny():
    return trace.load(str(TINY))


def test_loads_device_ops_and_harness_spans(tiny):
    assert list(tiny.devices) == ["/device:TPU:0"]
    ops = tiny.devices["/device:TPU:0"]
    assert len(ops) == 18
    assert [s[0] for s in tiny.spans] == ["bench.block", "bench.edge"] * 3
    assert trace.opcode(ops[2][3]) == "custom-call"
    assert ops[2][0] == "sumsq.1"


def test_busy_union_and_idle_share(tiny):
    ops = tiny.devices["/device:TPU:0"]
    lo, hi = tiny.spans[0][1], tiny.spans[-1][2]
    busy = trace.busy_ns(ops, lo, hi)
    # the ops of two of the three blocks fall inside the host spans (the
    # device clock reads about a millisecond earlier than the host's)
    assert busy == pytest.approx(46725.0)
    idle = 1 - busy / (hi - lo)
    assert 0.99 < idle < 1.0
    gaps = trace.idle_gaps(ops, lo, hi)
    assert sum(e - s for s, e in gaps) == pytest.approx(hi - lo - busy)


def test_kernel_time_by_name(tiny):
    ops = tiny.devices["/device:TPU:0"]
    ns, n = trace.matching_ns(ops, ["sumsq"], float("-inf"), float("inf"),
                              "tpu_custom_call")
    assert (ns, n) == (pytest.approx(4230.0), 3)
    assert trace.matching_ns(ops, ["sumsq"], float("-inf"), float("inf"),
                             "AllocateBuffer") == (0.0, 0)


def test_gaps_are_named_by_the_open_span(tiny):
    ops = tiny.devices["/device:TPU:0"]
    lo, hi = tiny.spans[0][1], tiny.spans[-1][2]
    gaps = trace.attribute_gaps(ops, tiny.spans, lo, hi)
    assert gaps[0][0] == "bench.edge" and gaps[0][1] > 0.01
    assert trace.span_at(tiny.spans, lo - 1) == "outside"


def _op(name, s, e, code="fusion"):
    return (name, float(s), float(e), f"%{name} = f32[8]{{0}} {code}(f32[8] %x)")


def test_collective_time():
    ops = [_op("a", 0, 10), _op("cp", 10, 30, "collective-permute-start"),
           _op("cpd", 30, 35, "collective-permute-done"),
           _op("ar", 40, 50, "all-reduce"), _op("b", 50, 60)]
    assert trace.collective_ns(ops, 0, 100) == 25.0
    assert trace.collective_ns(ops, 0, 100, ("all-reduce",)) == 10.0
    assert trace.collective_ns(ops, 12, 100) == 5.0


def test_self_time_takes_nested_ops_out():
    ops = [_op("while", 0, 100, "while"), _op("f1", 10, 30), _op("f2", 40, 90),
           _op("g", 60, 70), _op("after", 120, 130)]
    st = trace.self_times(ops)
    assert st["while"] == pytest.approx(30e-9)
    assert st["f2"] == pytest.approx(40e-9)
    assert st["g"] == pytest.approx(10e-9)
    assert sum(st.values()) == pytest.approx(
        trace.busy_ns(ops, 0, 200) / 1e9)


def test_readers_on_the_recorded_trace(tiny):
    lo, hi = tiny.spans[0][1], tiny.spans[-1][2]
    rec = harness.RunRecord(
        chips=1, peaks={"bf16_flops_per_s": 197e12},
        round_flops=2 * 1024 ** 3, rounds=3, trace=tiny, lo=lo, hi=hi,
        run_ns=sum(e - s for n, s, e in tiny.spans if n == "bench.block"))
    read = lambda m: harness.load_reader(  # noqa: E731
        benchtiny.ROOT / "bench", m)(rec)
    assert read("device_idle_frac") == pytest.approx(
        100 * (1 - 46725.0 / (hi - lo)))
    assert read("host_edge_ms") == pytest.approx(
        (hi - lo - rec.run_ns) / 1e6 / 3)
    assert 0 < read("round_mfu") < 100
