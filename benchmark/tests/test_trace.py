"""The reduction from a profiler trace to busy time, idle share, top
device ops and idle gaps, on a small recorded trace and on hand-made
ones."""

import json
import os

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_v5e_slice.json")


def _rows(ops, spans, device="/device:TPU:0"):
    return {"planes": [
        {"name": device, "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": spans}]}]}


def _busy_by_sweep(ops, lo, hi):
    """Covered length of [lo, hi] by counting open intervals at each
    boundary (independent of trace._union)."""
    edges = []
    for _, s, d in ops:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort(key=lambda x: (x[0], -x[1]))
    covered, depth, last = 0, 0, None
    for t, step in edges:
        if depth > 0:
            covered += t - last
        depth += step
        last = t
    return covered


def test_recorded_slice():
    with open(DATA) as f:
        rows = json.load(f)
    r = trace.reduce_rows(rows)
    ops = rows["planes"][0]["lines"][0]["events"]
    span = [e for e in rows["planes"][1]["lines"][0]["events"]
            if e[0] == "bench/traced"][0]
    lo, hi = span[1], span[1] + span[2]
    assert r["window_s"] == pytest.approx(span[2] / 1e9)
    assert r["busy_s"] == pytest.approx(_busy_by_sweep(ops, lo, hi) / 1e9)
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    assert r["devices"] == 1
    # the longest gap is the host packing the next chunk
    assert r["idle_gaps"][0][0] == "bench/prepare_call"
    assert r["idle_gaps"][0][1] == pytest.approx(0.004153427)
    assert sum(g for _, g in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-12
    names = [n for n, _ in r["top_ops"]]
    assert names[:2] == ["copy", "_to_affine_t"]
    assert sum(s for _, s in r["top_ops"]) <= r["busy_s"] + 1e-12


def test_union_nesting_and_clipping():
    ops = [["%while.3 = (s32[]) while(...)", 0, 100],
           ["%_miller_t.1 = s32[2] custom-call(...)", 10, 50],  # nested
           ["%fusion.7 = f32[] fusion(...)", 90, 30],           # overlaps end
           ["%copy.2 = s32[] copy(...)", 200, 50],              # clipped
           ["%copy.9 = s32[] copy(...)", 400, 10]]              # outside
    spans = [["bench/traced", 0, 220], ["bench/verify_call", 0, 150],
             ["bench/prepare_call", 150, 60]]
    r = trace.reduce_rows(_rows(ops, spans))
    assert r["busy_s"] * 1e9 == pytest.approx(120 + 20)
    assert r["window_s"] * 1e9 == pytest.approx(220)
    top = dict(r["top_ops"])
    assert top["_miller_t"] * 1e9 == pytest.approx(50)
    # 100 less the 50 nested in it and the 10 the fusion overlaps
    assert top["while"] * 1e9 == pytest.approx(40)
    assert sum(top.values()) == pytest.approx(r["busy_s"])
    assert top["copy"] * 1e9 == pytest.approx(20)
    assert r["idle_gaps"] == [["bench/prepare_call", pytest.approx(80e-9)]]


def test_no_window_no_device():
    assert trace.reduce_rows(_rows([["x", 0, 5]], [])) is None
    r = trace.reduce_rows(_rows([], [["bench/traced", 0, 10]]))
    assert r["busy_s"] == 0 and r["devices"] == 0
    assert trace.idle_share_pct(r) is None  # nothing ran: no share


def test_op_names():
    assert trace.op_name("%_f12_call.15 = s32[2] custom-call(x)") == "_f12_call"
    assert trace.op_name("%copy-start.2 = (s32[]) copy-start(x)") == "copy-start"
    assert trace.op_name("fusion") == "fusion"


def test_reads_an_xplane_file(tmp_path):
    """The file reader on a trace recorded here (CPU: host spans only)."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/traced"):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    rows = trace.rows_from_file(trace.find_xplane(str(tmp_path)))
    names = {e[0] for p in rows["planes"] for line in p["lines"]
             for e in line["events"]}
    assert "bench/traced" in names
    r = trace.reduce_rows(rows)
    assert r["window_s"] > 0 and r["devices"] == 0
