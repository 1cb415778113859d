"""The program's own scopes and spans in a profiler trace, reduced to
milliseconds a round per scope.

The program names its device work with ``jax.named_scope`` (``fl.local``,
``fl.private``, ``fl.proxy``, ``fl.adam``, ``fl.exchange``, ``fl.eval``,
``fl.attention``, ``fl.loss``) and its host work with profiler annotations
(``fl.dispatch``, ``fl.edge.eval``, ``fl.edge.epsilon``). A device op's
scope path (``jit(block_fn)/while/body/fl.local/vmap(fl.adam)/sub``) is
the ``tf_op`` stat of its event metadata, which ``jax.profiler.ProfileData``
does not expose; :func:`op_paths` reads it from the ``.xplane.pb`` wire
format. A metadata entry's name is the op's HLO text, the name that
``ProfileData`` gives its events, so ops join to paths by that text.

:func:`load` extends :func:`bench.trace.load` and leaves every field it
fills as it was. Run from the root of a checkout, this traces a cell's
window through the harness and prints the by-scope reduction beside the
harness's result:

    PYTHONPATH=src python3 -m bench.scopes --workload <cell> --seed <n> \
        --seconds <s>
"""
from __future__ import annotations

import glob
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from . import trace as tr

#: the prefix of every scope and host span the program opens
PROGRAM_PREFIX = "fl."
#: scopes that partition the program's device work
PARTITION = ("fl.local", "fl.exchange", "fl.eval")
#: per-layer metric -> the scope whose self time it reads
SCOPE_METRICS = {
    "private_grad_ms": "fl.private",
    "proxy_dp_ms": "fl.proxy",
    "adam_ms": "fl.adam",
    "exchange_ms": "fl.exchange",
    "eval_ms": "fl.eval",
    "attention_ms": "fl.attention",
    "dml_loss_ms": "fl.loss",
}
#: per-layer metric -> the host spans whose device idle time it reads
IDLE_METRICS = {
    "dispatch_idle_ms": ("fl.dispatch",),
    "edge_idle_ms": ("fl.edge.eval", "fl.edge.epsilon"),
}


@dataclass
class ScopedTrace(tr.Trace):
    """A :class:`bench.trace.Trace` with, per device, each op text's scope
    path (``None`` where one text has two paths) and the program's host
    spans."""

    op_paths: Dict[str, Dict[str, Optional[str]]] = field(
        default_factory=dict)
    program_spans: List[tr.Span] = field(default_factory=list)


# -- the wire format ---------------------------------------------------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint or fixed-width field, bytes for a length-delimited one."""
    i = 0
    while i < len(buf):
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} is not decoded")
        yield tag >> 3, v


def _map_entries(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_paths(path: str) -> Dict[str, Dict[str, Optional[str]]]:
    """Per device plane, each op text -> its ``tf_op`` scope path, read from
    the plane's event metadata (``XPlane.event_metadata``, field 4; its
    stats, field 5, name their kind through ``XPlane.stat_metadata``, field
    5). A text given two different paths maps to ``None``."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, Optional[str]]] = {}
    for fno, plane in _fields(space):
        if fno != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 4:
                events.append(_map_entries(v)[1])
            elif f == 5:
                sid, meta = _map_entries(v)
                stat_names[sid] = next(
                    (x.decode() for g, x in _fields(meta) if g == 2), "")
        if not name.startswith("/device:") or "CUSTOM" in name:
            continue
        paths: Dict[str, Optional[str]] = {}
        for ev in events:
            text, tf_op = "", None
            for f, v in _fields(ev):
                if f == 2:
                    text = v.decode()
                elif f == 5:
                    tf_op = _tf_op(v, stat_names) or tf_op
            if tf_op is None:
                continue
            paths[text] = tf_op if paths.get(text, tf_op) == tf_op else None
        out[name] = paths
    return out


def _tf_op(stat: bytes, stat_names: Dict[int, str]) -> Optional[str]:
    """The value of an ``XStat`` whose kind is ``tf_op``, held as a string
    (field 5) or as a reference to a string (field 7)."""
    kind, value = None, None
    for f, v in _fields(stat):
        if f == 1:
            kind = stat_names.get(v)
        elif f == 5:
            value = v.decode()
        elif f == 7:
            value = stat_names.get(v)
    return value if kind == "tf_op" else None


def newest(path_or_dir: str) -> str:
    """The newest ``.xplane.pb`` under a directory, or the file itself."""
    if not os.path.isdir(path_or_dir):
        return path_or_dir
    found = sorted(glob.glob(os.path.join(path_or_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path_or_dir}")
    return found[-1]


def load(path_or_dir: str) -> ScopedTrace:
    """:func:`bench.trace.load`, plus each op's scope path and the host
    spans whose names start with ``fl.``."""
    from jax.profiler import ProfileData

    path = newest(path_or_dir)
    base = tr.load(path)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(PROGRAM_PREFIX)]
    return ScopedTrace(devices=base.devices, spans=base.spans,
                       op_paths=op_paths(path),
                       program_spans=sorted(spans, key=lambda s: s[1]))


# -- scopes ------------------------------------------------------------------


def components(path: str) -> List[str]:
    """A scope path's components, split at the slashes outside brackets,
    each with its transform wrappers taken off, and without the trailing
    ``:<type>`` of a ``tf_op`` stat:
    ``a/transpose(jvp(fl.loss))/div:`` -> ``[a, fl.loss, div]``."""
    parts, depth, cur = [], 0, ""
    for c in path.rsplit(":", 1)[0]:
        depth += (c == "(") - (c == ")")
        if c == "/" and depth == 0:
            parts.append(cur)
            cur = ""
        else:
            cur += c
    parts.append(cur)
    out = []
    for p in parts:
        while p.endswith(")") and "(" in p:
            p = p[p.index("(") + 1:-1]
        out.append(p)
    return out


def in_scope(path: Optional[str], scope: str) -> bool:
    return path is not None and scope in components(path)


def self_ns(ops: Sequence[tr.Op], lo: float, hi: float) -> Dict[str, float]:
    """Self nanoseconds per op text (ops nested in another, as a ``while``
    body's are in the ``while``, are taken out of it), over the ops that
    start in ``[lo, hi)``."""
    inside = [(text, s, e, text) for _, s, e, text in ops if lo <= s < hi]
    return {k: v * 1e9 for k, v in tr.self_times(inside).items()}


def scope_ns(self_times: Dict[str, float], paths: Dict[str, Optional[str]],
             scope: str) -> float:
    """Self nanoseconds (of :func:`self_ns`) of the ops whose path holds
    ``scope``."""
    return sum(ns for text, ns in self_times.items()
               if in_scope(paths.get(text), scope))


def ambiguous(paths: Dict[str, Optional[str]]) -> int:
    """How many op texts carry two different paths."""
    return sum(p is None for p in paths.values())


def idle_in_spans_ns(ops: Sequence[tr.Op], spans: Sequence[tr.Span],
                     names: Sequence[str], lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi]`` in which no op ran and one of the spans
    named ``names`` was open."""
    open_ = tr.union(((s, e) for n, s, e in spans if n in names), lo, hi)
    total = 0.0
    for gs, ge in tr.idle_gaps(ops, lo, hi):
        total += sum(max(0.0, min(ge, e) - max(gs, s)) for s, e in open_)
    return total


def per_round_ms(ns_per_device: Sequence[float], rounds: int
                 ) -> Optional[float]:
    """Milliseconds a round, the mean over the chips."""
    if rounds <= 0 or not ns_per_device:
        return None
    return sum(ns_per_device) / len(ns_per_device) / 1e6 / rounds


def metrics(t: ScopedTrace, lo: float, hi: float, rounds: int
            ) -> Dict[str, Optional[float]]:
    """The nine per-layer readings of a traced window, in ms a round; a
    scope or span the trace does not hold reads ``None``."""
    out: Dict[str, Optional[float]] = {}
    devs = [(self_ns(ops, lo, hi), t.op_paths.get(d, {}))
            for d, ops in t.devices.items()]
    for name, scope in SCOPE_METRICS.items():
        if not any(in_scope(p, scope) for _, paths in devs
                   for p in paths.values()):
            out[name] = None
            continue
        out[name] = per_round_ms([scope_ns(st, paths, scope)
                                  for st, paths in devs], rounds)
    names = {n for n, _, _ in t.program_spans}
    for name, spans in IDLE_METRICS.items():
        out[name] = (per_round_ms(
            [idle_in_spans_ns(ops, t.program_spans, spans, lo, hi)
             for ops in t.devices.values()], rounds)
            if names & set(spans) else None)
    return out


def by_scope(t: ScopedTrace, lo: float, hi: float, rounds: int,
             top: int = 10) -> Dict:
    """What the window's busy time went to, per chip-mean and a round: each
    scope's self ms, the share of busy time under the partitioning scopes,
    the longest unscoped ops, the top ops with their paths, the ambiguous
    op texts, and the longest idle gaps named by the innermost span open
    in each, the program's or else the harness's."""
    n = max(1, len(t.devices))
    busy = sum(tr.busy_ns(ops, lo, hi) for ops in t.devices.values()) / n
    scopes: Dict[str, float] = {}
    ops_ns: Dict[Tuple[str, Optional[str]], float] = {}
    unscoped_ns = 0.0
    for d, ops in t.devices.items():
        paths = t.op_paths.get(d, {})
        for text, ns in self_ns(ops, lo, hi).items():
            p = paths.get(text)
            k = (tr.short_name(text), p)
            ops_ns[k] = ops_ns.get(k, 0.0) + ns / n
            for c in set(components(p or "")):
                if c.startswith(PROGRAM_PREFIX):
                    scopes[c] = scopes.get(c, 0.0) + ns / n
            if not any(in_scope(p, s) for s in PARTITION):
                unscoped_ns += ns / n
    gaps = []
    spans = sorted(t.spans + t.program_spans, key=lambda sp: sp[1])
    for ops in t.devices.values():
        gaps += tr.attribute_gaps(ops, spans, lo, hi)
    ranked = sorted(ops_ns.items(), key=lambda kv: -kv[1])

    def ms(ns):
        return ns / 1e6 / max(1, rounds)

    return {
        "rounds": rounds, "busy_ms": ms(busy),
        "scopes_ms": {k: ms(v) for k, v in sorted(scopes.items())},
        "partition_share": 1.0 - unscoped_ns / busy if busy else None,
        "top_ops": [[k, ms(v), p] for (k, p), v in ranked[:top]],
        "top_unscoped": [[k, ms(v), p] for (k, p), v in ranked
                         if not any(in_scope(p, s) for s in PARTITION)
                         ][:top],
        "ambiguous": sum(ambiguous(p) for p in t.op_paths.values()),
        "idle_gaps_ms": [[k, v * 1e3] for k, v in
                         sorted(gaps, key=lambda g: -g[1])[:top]],
    }


# -- the script --------------------------------------------------------------


def traced(spec: Dict, root: Path, cell: str, seed: int, seconds: float,
           allow_cpu: bool = False, log=print) -> Dict:
    """One ``--trace 1`` run of ``cell`` through the harness, with
    ``scopes`` (the nine readings), ``by_scope`` and ``traced_round_s`` of
    its traced window added to the result."""
    import jax

    from . import harness

    seen: Dict = {}
    record = harness.traced_record

    def traced_record(trace_dir, cell_, peaks, round_flops, rounds):
        rec = record(trace_dir, cell_, peaks, round_flops, rounds)
        t = load(trace_dir)
        seen["scopes"] = metrics(t, rec.lo, rec.hi, rounds)
        seen["by_scope"] = by_scope(t, rec.lo, rec.hi, rounds)
        seen["traced_round_s"] = rec.window_s / rounds
        return rec

    # an executable loaded from the persistent cache keeps the metadata of
    # the program that wrote it: key the cache on metadata too, so that a
    # cached program without these scopes is never read back
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    harness.traced_record = traced_record
    try:
        out = harness.run_cell(spec, root, cell, seed, seconds, True,
                               time.perf_counter(), allow_cpu=allow_cpu,
                               log=log)
    finally:
        harness.traced_record = record
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          keyed)
    out.update(seen)
    return out


def main(argv: List[str]) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Trace a cell's window through the harness and print "
        "its result with the by-scope reduction as the last line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[1]
    spec = json.loads((root / "BENCHMARK.json").read_text())
    out = traced(spec, root, args.workload, args.seed, args.seconds,
                 log=lambda msg: print(msg, file=sys.stderr, flush=True))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
