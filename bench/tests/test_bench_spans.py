"""The reduction of the program's spans and scopes on hand-built events."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.spans import (OUTSIDE, UNSCOPED, Span, nest, reduce_events,
                         scope_of)
from bench.trace import Op


def span(name, start, end, **args):
    return Span(name, start, end, args, [])


def decode_tick(t0, tick):
    """A 100 ns decode tick: watchdog, then decode (dispatch, sync,
    rows_ok, bookkeeping)."""
    return [span("sched.step", t0, t0 + 100, tick=tick, kind="decode"),
            span("sched.watchdog", t0 + 1, t0 + 5),
            span("sched.decode", t0 + 5, t0 + 99),
            span("sched.decode_dispatch", t0 + 5, t0 + 15),
            span("sched.decode_sync", t0 + 15, t0 + 80),
            span("sched.rows_ok", t0 + 80, t0 + 90)]


def admit_tick(t0, tick):
    """A 300 ns admitting tick: admit (prefill, sync, rows_ok, insert),
    then its decode."""
    return [span("sched.step", t0, t0 + 300, tick=tick, kind="admit"),
            span("sched.admit", t0, t0 + 200, n=3),
            span("sched.prefill", t0, t0 + 20),
            span("sched.admit_sync", t0 + 20, t0 + 150),
            span("sched.rows_ok", t0 + 150, t0 + 160),
            span("sched.insert", t0 + 160, t0 + 170),
            span("sched.decode", t0 + 200, t0 + 300),
            span("sched.decode_dispatch", t0 + 200, t0 + 210)]


#: window [0, 1000): decode ticks at 0 and 100, an admit tick at 500
THREAD = decode_tick(0, 1) + decode_tick(100, 2) + admit_tick(500, 3)


def busy(*iv):
    return {"/device:TPU:0": [Op(f"op{i}", a, b, False)
                              for i, (a, b) in enumerate(iv)]}


def reduce(threads=(THREAD,), planes=None, scoped=None):
    planes = planes if planes is not None else busy((0, 1000))
    return reduce_events([list(t) for t in threads], planes, scoped or {},
                         0, 1000)


def test_nesting_gives_self_times():
    r = reduce()
    p = r.per_span
    assert p["sched.step"] == {"count": 3, "total_s": pytest.approx(500e-9),
                               "self_s": pytest.approx((2 + 2 + 0) * 1e-9)}
    # decode: 94 long, 10 + 65 + 10 in children; the admit tick's: 100 - 10
    assert p["sched.decode"]["count"] == 3
    assert p["sched.decode"]["self_s"] == pytest.approx((9 + 9 + 90) * 1e-9)
    assert p["sched.admit"]["self_s"] == pytest.approx(30e-9)
    assert p["sched.rows_ok"]["count"] == 3
    assert p["sched.decode_dispatch"]["total_s"] == pytest.approx(30e-9)


def test_nest_attaches_children_by_containment():
    roots = nest([[span("b", 10, 20), span("a", 0, 100),
                   span("c", 20, 30), span("d", 100, 110)]])
    assert [r.name for r in roots] == ["a", "d"]
    assert [c.name for c in roots[0].children] == ["b", "c"]


def test_idle_gap_goes_to_the_innermost_span():
    # busy everywhere but [20, 30) (decode_sync), [80, 90) (rows_ok),
    # [95, 105) (mid 100: the second tick's step, before its watchdog)
    # and [400, 500) (outside every tick)
    r = reduce(planes=busy((0, 20), (30, 80), (90, 95), (105, 400),
                           (500, 1000)))
    idle = {k: v * 1e9 for k, v in r.idle_by_span}
    assert idle == pytest.approx({"sched.decode_sync": 10,
                                  "sched.rows_ok": 10, "sched.step": 10,
                                  OUTSIDE: 100})
    assert r.idle_by_span[0][0] == OUTSIDE


def test_decode_idle_divides_by_decode_ticks_only():
    # idle [50, 70) in tick 1, [150, 160) in tick 2, [550, 650) in the
    # admit tick: only the decode ticks' 30 count, over 2 ticks
    r = reduce(planes=busy((0, 50), (70, 150), (160, 550), (650, 1000)))
    assert r.decode_ticks == 2
    assert r.decode_idle_s == pytest.approx(30e-9)
    assert r.decode_idle_ms() == pytest.approx(15e-6)
    assert reduce(threads=()).decode_idle_ms() is None


def test_ops_attributed_to_scopes_from_the_op_name():
    scoped = {"/device:TPU:0": [
        (0, 100, scope_of("jit(step)/while/body/closed_call/"
                          "approx.quantize/mul")),
        (100, 150, scope_of("jit(step)/approx.rescale/convert_element_type")),
        (150, 250, scope_of("jit(step)/while/body/model.kv_cache/"
                            "dynamic_slice")),
        (250, 300, scope_of("jit(step)/model.head/dot_general")),
        (300, 400, scope_of("jit(step)/jit(_pad)/pad")),
        (400, 450, scope_of("params['embed']")),
        (450, 500, None),               # an XLA copy with no op_name
        (990, 1100, None)]}             # runs past the window
    r = reduce(scoped=scoped)
    assert r.scoped
    assert {k: v * 1e9 for k, v in r.scopes.items()} == pytest.approx(
        {"approx.quantize": 100, "approx.rescale": 50, "model.kv_cache": 100,
         "model.head": 50, UNSCOPED: 210})
    assert r.scope_pct("approx.", 1000e-9) == pytest.approx(15.0)
    assert r.scope_pct("model.kv_cache", 1000e-9) == pytest.approx(10.0)
    assert r.scope_pct("approx.", 0.0) is None


def test_no_scope_anywhere_reads_as_nothing():
    r = reduce(scoped={"/device:TPU:0": [(0, 100, None)]})
    assert not r.scoped and r.scopes == pytest.approx({UNSCOPED: 100e-9})
    assert r.scope_pct("approx.", 1e-6) is None
    assert r.scope_pct("model.kv_cache", 1e-6) is None


def test_scope_of_skips_jit_frames_and_primitives():
    assert scope_of("jit(f)/jit(logmatmul_pallas)/logmatmul_pallas/"
                    "pallas_call") is None
    assert scope_of("jit(f)/model.head/approx.quantize/mul") == "model.head"


def test_longest_tick_picks_the_longest_and_splits_it():
    r = reduce(planes=busy((0, 600), (700, 1000)))
    t = r.longest_tick
    assert (t["tick"], t["kind"]) == (3, "admit")
    assert t["ms"] == pytest.approx(300e-6)
    assert t["idle_ms"] == pytest.approx(100e-6)
    split = dict(t["self_ms"])
    assert split["sched.admit_sync"] == pytest.approx(130e-6)
    assert split["sched.decode"] == pytest.approx(90e-6)
    assert sum(split.values()) == pytest.approx(300e-6)


def test_dispatch_ms_is_the_mean_dispatch_span():
    assert reduce().mean_ms("sched.decode_dispatch") == pytest.approx(10e-6)
    assert reduce(threads=()).mean_ms("sched.decode_dispatch") is None


def test_spans_outside_the_window_are_left_out():
    late = decode_tick(2000, 9)
    r = reduce(threads=(THREAD + late,))
    assert r.per_span["sched.step"]["count"] == 3
    assert r.longest_tick["tick"] == 3


# --------------------------------------------------- op_name from the file --
def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _len(field, payload):
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field, n):
    return _varint(field << 3) + _varint(n)


def _stat(meta_id, *, text=None, number=None):
    body = _int(1, meta_id)
    if text is not None:
        body += _len(5, text.encode())
    if number is not None:               # a double: wire type 1, skipped
        body += _varint(2 << 3 | 1) + b"\x00" * 8
    return body


def _plane(name, stat_names, events, line=b""):
    body = _int(1, 3) + _len(2, name.encode())
    if line:
        body += _len(3, line)
    for i, (ev_name, stats) in enumerate(events, 1):
        meta = _int(1, i) + _len(2, ev_name.encode())
        meta += b"".join(_len(5, st) for st in stats)
        body += _len(4, _int(1, i) + _len(2, meta))
    for sid, sname in stat_names.items():
        body += _len(5, _int(1, sid) + _len(2, _int(1, sid)
                                             + _len(2, sname.encode())))
    return body


def test_op_names_read_from_the_event_metadata(tmp_path):
    from bench.spans import device_scoped_ops, op_names

    fused = "%bitcast_multiply_fusion.18 = s32[960,2560]{1,0} fusion(%a)"
    copy = "%copy.53 = bf16[32,8,1024,5,64]{4,3,2,1,0} copy(%b)"
    kernel = ('%logmatmul_pallas.46 = s32[8,2560]{1,0} custom-call(%c), '
              'custom_call_target="tpu_custom_call"')
    device = _plane("/device:TPU:0", {7: "tf_op", 8: "flops"}, [
        (fused, [_stat(8, number=1.0),
                 _stat(7, text="jit(f)/while/body/approx.quantize/mul:")]),
        (copy, [_stat(8, number=0.0)]),
        (kernel, [_stat(7, text="jit(f)/jit(logmatmul_pallas)/"
                                "logmatmul_pallas/pallas_call:")])],
        line=b"\x08\x01\x12\x03XLA")
    host = _plane("/host:CPU", {1: "tf_op"},
                  [("sched.step", [_stat(1, text="jit(g)/model.head/x:")])])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_len(1, host) + _len(1, device) + _len(2, b"errors"))

    names = op_names(str(path))
    assert list(names) == ["/device:TPU:0"]
    assert names["/device:TPU:0"] == {
        fused: "jit(f)/while/body/approx.quantize/mul",
        kernel: "jit(f)/jit(logmatmul_pallas)/logmatmul_pallas/pallas_call"}

    ops = [Op(fused, 0, 10, False), Op(copy, 10, 20, False),
           Op(kernel, 20, 30, True),
           Op("%while.2 = (s32[]) while(%t)", 0, 30, False)]
    assert device_scoped_ops({"/device:TPU:0": ops}, names) == {
        "/device:TPU:0": [(0, 10, "approx.quantize"), (10, 20, None)]}


# ---------------------------------------------------------------- the tool --
def test_tool_reads_a_rehearsed_window():
    """``python -m bench.spans`` on the CPU rehearsal: one ``sched.step`` per
    tick of the window, the phases inside, and no device plane to read."""
    root = Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_ENABLE_X64", None)
    p = subprocess.run([sys.executable, "-m", "bench.spans", "--workload",
                        "smollm-360m.decode", "--seed", "2147483711",
                        "--seconds", "2", "--rehearse"], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    per = out["per_span"]
    assert per["sched.step"]["count"] == out["ticks"] > 0
    assert per["sched.decode_dispatch"]["count"] == per["sched.decode"][
        "count"] > 0
    assert out["decode_dispatch_ms"] > 0
    assert out["longest_tick"]["ms"] > 0
    assert out["busy_s"] == 0.0
    assert out["decode_idle_ms"] is None and out["approx_pct"] is None
