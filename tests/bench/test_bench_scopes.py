"""The by-scope reduction (``bench/scopes.py``): the wire-format read of
each op's scope path, on traces recorded on a TPU v5e and on hand-made
protobuf bytes; scope matching through transform wrappers; self time under
a scope; device idle time inside host spans; and the per-round, per-chip
readings."""
from pathlib import Path

import pytest

import benchtiny
from bench import harness, scopes, trace

DATA = Path(__file__).resolve().parent / "data"
TINY = DATA / "tiny_tpu.xplane.pb"
#: three calls of a jitted, vmapped gradient under ``fl.private`` with its
#: loss under ``fl.loss``, each inside an ``fl.dispatch`` host annotation
#: (``tests/bench/record_scoped_trace.py``, on a TPU v5e)
SCOPED = DATA / "scoped_tpu.xplane.pb"


def _record(tiny):
    lo, hi = tiny.spans[0][1], tiny.spans[-1][2]
    return harness.RunRecord(
        chips=1, peaks={"bf16_flops_per_s": 197e12},
        round_flops=2 * 1024 ** 3, rounds=3, trace=tiny, lo=lo, hi=hi,
        run_ns=sum(e - s for n, s, e in tiny.spans if n == "bench.block"))


def test_decoder_reads_the_pallas_kernels_path():
    paths = scopes.op_paths(str(TINY))
    assert list(paths) == ["/device:TPU:0"]
    (text, path), = [(t, p) for t, p in paths["/device:TPU:0"].items()
                     if t.startswith("%sumsq.1 = ")]
    assert path == "jit(<lambda>)/jit(sumsq)/pallas_call:"
    assert scopes.ambiguous(paths["/device:TPU:0"]) == 0


def test_loaded_trace_keeps_what_the_readers_read():
    """The existing fields and the three existing readers read the same
    values from the extended trace as from ``bench.trace.load``."""
    base, ext = trace.load(str(TINY)), scopes.load(str(TINY))
    assert ext.devices == base.devices and ext.spans == base.spans
    assert ext.program_spans == []
    for metric in ("device_idle_frac", "host_edge_ms", "round_mfu"):
        read = harness.load_reader(benchtiny.ROOT / "bench", metric)
        assert read(_record(ext)) == read(_record(base)), metric
    rec = _record(ext)
    assert harness.load_reader(benchtiny.ROOT / "bench", "device_idle_frac")(
        rec) == pytest.approx(100 * (1 - 46725.0 / (rec.hi - rec.lo)))
    assert harness.breakdown(rec) == harness.breakdown(_record(base))


def test_scopes_found_through_vmap_and_jvp_in_a_tpu_trace():
    t = scopes.load(str(SCOPED))
    paths = t.op_paths["/device:TPU:0"]
    assert scopes.ambiguous(paths) == 0
    # the whole gradient is one fusion, its loss scope inside three wrappers
    assert any("fl.private/vmap(transpose(jvp(fl.loss)))/" in p
               for p in paths.values())
    for scope in ("fl.private", "fl.loss"):
        assert any(scopes.in_scope(p, scope) for p in paths.values()), scope
    assert [n for n, _, _ in t.program_spans] == ["fl.dispatch"] * 3
    lo, hi = t.program_spans[0][1], t.program_spans[-1][2] + 10e6
    ops = t.devices["/device:TPU:0"]
    st = scopes.self_ns(ops, lo, hi)
    private = scopes.scope_ns(st, paths, "fl.private")
    loss = scopes.scope_ns(st, paths, "fl.loss")
    assert 0 < loss <= private <= trace.busy_ns(ops, lo, hi)


@pytest.mark.parametrize("path,inside,outside", [
    ("jit(block_fn)/while/body/closed_call/vmap(fl.adam)/sub:",
     ["fl.adam"], ["fl.local"]),
    ("jit(block_fn)/while/body/fl.local/vmap(fl.private)/"
     "transpose(jvp(fl.loss))/jit(log_softmax)/div:",
     ["fl.local", "fl.private", "fl.loss"], ["fl.proxy", "log"]),
    ("jit(block_fn)/fl.exchange/shard_map/ppermute",
     ["fl.exchange"], ["fl.exchange/shard_map"]),
    ("jit(loss)/fl.eval/fl.attention/exp:", ["fl.eval", "fl.attention"],
     ["fl.eva", "fl.attention/exp"]),
])
def test_scope_is_a_whole_path_component(path, inside, outside):
    for s in inside:
        assert scopes.in_scope(path, s), s
    for s in outside:
        assert not scopes.in_scope(path, s), s
    assert not scopes.in_scope(None, "fl.local")


def _op(name, s, e):
    return (name, float(s), float(e), f"%{name} = f32[8]{{0}} fusion()")


#: a while (the rounds' scan) around the local phase, and an eval op
OPS = [_op("while", 0, 100), _op("priv", 10, 30), _op("adam", 40, 90),
       _op("loss", 60, 70), _op("eval", 120, 130)]
PATHS = {
    OPS[0][3]: "jit(b)/while:",
    OPS[1][3]: "jit(b)/while/body/fl.local/vmap(fl.private)/dot:",
    OPS[2][3]: "jit(b)/while/body/fl.local/vmap(fl.adam)/sub:",
    OPS[3][3]: "jit(b)/while/body/fl.local/fl.adam/transpose(jvp(fl.loss))/"
               "exp:",
    OPS[4][3]: "jit(l)/fl.eval/dot:",
}


def test_self_time_under_a_scope_takes_nested_ops_out():
    st = scopes.self_ns(OPS, 0, 200)
    assert st[OPS[0][3]] == pytest.approx(30)   # the while's own gaps
    assert scopes.scope_ns(st, PATHS, "fl.local") == pytest.approx(70)
    # the nested loss counts under Adam too
    assert scopes.scope_ns(st, PATHS, "fl.adam") == pytest.approx(50)
    assert scopes.scope_ns(st, PATHS, "fl.loss") == pytest.approx(10)
    assert scopes.scope_ns(st, PATHS, "fl.eval") == pytest.approx(10)
    # ops that start outside the window are left out
    st = scopes.self_ns(OPS, 0, 110)
    assert scopes.scope_ns(st, PATHS, "fl.eval") == 0.0


def test_idle_time_inside_spans():
    spans = [("fl.dispatch", 95.0, 115.0), ("fl.edge.eval", 118.0, 140.0),
             ("bench.edge", 100.0, 150.0)]
    ops = OPS
    # idle gaps in [0, 150]: (100, 120) and (130, 150)
    assert scopes.idle_in_spans_ns(ops, spans, ("fl.dispatch",), 0, 150) \
        == 15.0
    assert scopes.idle_in_spans_ns(ops, spans, ("fl.edge.eval",), 0, 150) \
        == 2.0 + 10.0
    # overlapping spans of one set count their union once
    assert scopes.idle_in_spans_ns(
        ops, spans, ("fl.dispatch", "fl.edge.eval"), 0, 150) == 15.0 + 12.0
    assert scopes.idle_in_spans_ns(ops, spans, ("fl.none",), 0, 150) == 0.0


def test_readings_are_ms_a_round_and_a_mean_over_chips():
    dev1 = [(n, s * 1e6, e * 1e6, t) for n, s, e, t in OPS]   # ms -> ns
    dev2 = [(n, s * 2e6, e * 2e6, t) for n, s, e, t in OPS]
    t = scopes.ScopedTrace(
        devices={"/device:TPU:0": dev1, "/device:TPU:1": dev2},
        spans=[("bench.run", 90e6, 125e6), ("bench.edge", 125e6, 300e6)],
        op_paths={"/device:TPU:0": PATHS, "/device:TPU:1": PATHS},
        program_spans=[("fl.dispatch", 95e6, 115e6)])
    got = scopes.metrics(t, 0, 300e6, rounds=2)
    # chip 1 reads 50 ms of Adam, chip 2 100 ms: 75 ms a chip, 2 rounds
    assert got["adam_ms"] == pytest.approx(75 / 2)
    assert got["private_grad_ms"] == pytest.approx(30 / 2)
    assert got["eval_ms"] == pytest.approx(15 / 2)
    # chip 1 is idle for 15 ms of the span, chip 2 (busy 0-200 ms) for none
    assert got["dispatch_idle_ms"] == pytest.approx(7.5 / 2)
    for missing in ("proxy_dp_ms", "exchange_ms", "attention_ms",
                    "edge_idle_ms"):
        assert got[missing] is None, missing
    assert set(got) == set(scopes.SCOPE_METRICS) | set(scopes.IDLE_METRICS)
    table = scopes.by_scope(t, 0, 300e6, rounds=2)
    assert table["scopes_ms"]["fl.local"] == pytest.approx(105 / 2)
    assert table["ambiguous"] == 0
    # the while's own 45 ms a chip are the one unscoped op
    assert table["top_unscoped"] == [["while", pytest.approx(45 / 2),
                                      "jit(b)/while:"]]
    # busy: 110 ms on chip 1, 220 ms on chip 2
    assert table["busy_ms"] == pytest.approx(165 / 2)
    assert table["partition_share"] == pytest.approx(1 - 45 / 165)
    # gaps are named by the innermost span open at their midpoint
    assert table["idle_gaps_ms"] == [
        ["bench.edge", pytest.approx(170)], ["bench.edge", pytest.approx(40)],
        ["bench.edge", pytest.approx(40)], ["fl.dispatch", pytest.approx(20)]]


# -- hand-made protobuf bytes -------------------------------------------------


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _len(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field, n):
    return _varint(field << 3) + _varint(n)


def _plane(name, events, stat_names):
    """An ``XPlane``: ``events`` of (id, text, stat value, by reference)."""
    body = _len(2, name)
    for sid, sname in stat_names.items():
        body += _len(5, _int(1, sid) + _len(2, _int(1, sid) + _len(2, sname)))
    for eid, text, value, by_ref in events:
        stat = _int(1, 1) + (_int(7, value) if by_ref else _len(5, value))
        meta = _int(1, eid) + _len(2, text) + _len(5, stat)
        body += _len(4, _int(1, eid) + _len(2, meta))
    return _len(1, body)


def test_decoder_reads_string_and_reference_stats_and_flags_ambiguity(
        tmp_path):
    stats = {1: "tf_op", 2: "jit(f)/fl.loss/exp:"}
    space = _plane("/device:TPU:0", [
        (10, "%a = f32[] exp()", "jit(f)/fl.adam/sub:", False),
        (11, "%b = f32[] exp()", 2, True),
        (12, "%c = f32[] add()", "jit(f)/fl.local/add:", False),
        (13, "%c = f32[] add()", "jit(g)/fl.eval/add:", False),
        (14, "%d = f32[] add()", "jit(f)/add:", False),
        (15, "%d = f32[] add()", "jit(f)/add:", False),
    ], stats) + _plane("/host:CPU", [(1, "x", "y", False)], stats)
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(space)
    paths = scopes.op_paths(str(pb))
    assert paths == {"/device:TPU:0": {
        "%a = f32[] exp()": "jit(f)/fl.adam/sub:",
        "%b = f32[] exp()": "jit(f)/fl.loss/exp:",
        "%c = f32[] add()": None,
        "%d = f32[] add()": "jit(f)/add:"}}
    assert scopes.ambiguous(paths["/device:TPU:0"]) == 1


def test_traced_run_adds_the_readings_and_restores_the_harness(tmp_path):
    """A ``--trace 1`` CPU run of the tiny cell: the nine readings and the
    table come back beside the harness's own result (the CPU trace has no
    device plane, so the device readings are empty)."""
    root, spec = benchtiny.make_root(tmp_path)
    record = harness.traced_record
    out = scopes.traced(spec, root, "tiny.lm", 7, 0.2, allow_cpu=True,
                        log=lambda msg: None)
    assert harness.traced_record is record
    assert set(out["scopes"]) == set(scopes.SCOPE_METRICS) | set(
        scopes.IDLE_METRICS)
    assert out["by_scope"]["rounds"] > 0 and out["traced_round_s"] > 0
    assert {"host_edge_ms"} <= set(out["metrics"])
