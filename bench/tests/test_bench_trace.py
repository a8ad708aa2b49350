"""The trace reduction on a hand-built event list."""
import pytest

from bench.trace import Op, WINDOW_SPAN, is_pallas, reduce_events, union

SPANS = [(WINDOW_SPAN, 0, 1000), ("bench.admit_tick", 0, 400),
         ("bench.loadgen", 400, 450), ("bench.decode_tick", 450, 900)]


def plane():
    return [Op("logmatmul", 10, 300, True),     # Pallas
            Op("add_fusion", 250, 350, False),   # overlaps it
            Op("copy", 500, 800, False),
            Op("attention", 900, 950, True),
            Op("rsqrt_fusion", 990, 1200, False)]  # runs past the window


def test_union_merges_overlaps_and_touching():
    assert union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 6]]


def test_busy_idle_and_pallas_split():
    r = reduce_events({"/device:TPU:0": plane()}, SPANS)
    ns = 1e-9
    # busy: [10,350] + [500,800] + [900,950] + [990,1000]
    assert r.busy_s == pytest.approx(700 * ns)
    assert r.window_s == pytest.approx(1000 * ns)
    # Pallas: [10,300] + [900,950]
    assert r.pallas_s == pytest.approx(340 * ns)
    assert r.kernels == pytest.approx({"logmatmul": 290 * ns,
                                       "attention": 50 * ns})
    assert r.devices == 1


def test_idle_gaps_named_by_host_span():
    r = reduce_events({"/device:TPU:0": plane()}, SPANS)
    gaps = {k: v * 1e9 for k, v in r.gaps}
    # [0,10] admit, [350,500] loadgen (mid 425), [800,900] decode,
    # [950,990] outside every tick span
    assert gaps == pytest.approx({"bench.admit_tick": 10,
                                  "bench.loadgen": 150,
                                  "bench.decode_tick": 100,
                                  "between bench spans": 40})
    assert sum(gaps.values()) == pytest.approx(300)


def test_ops_ranked_by_device_time_and_averaged_over_planes():
    r = reduce_events({"/device:TPU:0": plane(), "/device:TPU:1": plane()},
                      SPANS)
    names = [k for k, _ in r.ops]
    assert names[0] == "copy" and names[1] == "logmatmul"
    assert dict(r.ops)["rsqrt_fusion"] == pytest.approx(10e-9)
    assert r.busy_s == pytest.approx(700e-9)


def test_op_names_are_shortened_and_loops_left_out():
    ops = [Op("%while.2 = (s32[], bf16[8,1,960]{2,0,1}) while(%t)", 0, 500,
              False),
           Op("%logmatmul_pallas.46 = s32[8,2560]{1,0:T(8,128)} "
              "custom-call(s32[8,1024]{1,0})", 10, 100, True),
           Op("%logmatmul_pallas.47 = s32[8,2560]{1,0:T(8,128)} "
              "custom-call(s32[8,1024]{1,0})", 100, 200, True)]
    r = reduce_events({"/device:TPU:0": ops}, SPANS)
    assert r.ops == [["logmatmul_pallas s32[8,2560]", pytest.approx(190e-9)]]
    assert r.busy_s == pytest.approx(500e-9)


def test_no_pallas_event_reads_as_unknown():
    ops = [Op("add_fusion", 0, 10, False)]
    r = reduce_events({"/device:TPU:0": ops}, SPANS)
    assert r.pallas_s is None


def test_no_window_span_is_an_error():
    with pytest.raises(RuntimeError):
        reduce_events({"/device:TPU:0": plane()}, SPANS[1:])


def test_pallas_kernels_are_tpu_custom_calls():
    assert is_pallas('%logmatmul_pallas.46 = s32[8,2560]{1,0} custom-call('
                     's32[8,1024]{1,0} %a), custom_call_target="tpu_custom_call"')
    assert not is_pallas("%copy.53 = bf16[32,8,1024,5,64]{4,3,2,1,0} copy(%b)")


def test_logmatmul_roofline_fails_when_its_kernel_is_renamed():
    from types import SimpleNamespace

    from bench import spec
    read = spec.reader("logmatmul_roofline")
    r = reduce_events({"/device:TPU:0": plane()}, SPANS)
    run = SimpleNamespace(trace=r, peak={"int8_ops_s": 1.0})
    with pytest.raises(RuntimeError, match="logmatmul_pallas"):
        read(run)
    assert read(SimpleNamespace(trace=None, peak={})) is None
