"""The reduction from a device trace to numbers, on hand-built traces:
busy time as the union of overlapping operations, control-flow
operations left out, operation classes, shares, and idle gaps attributed
to the harness span the host was in."""
import pytest

from chipbench import trace as tr

# instruction texts as the TPU profiler names XLA Ops events
CONV_FUSION = ("%multiply_subtract_fusion.120 = f32[100,3,3,16,16]{4,3,0,2,1:"
               "T(8,128)} fusion(f32[100,3,3,16,16]{4,3,0,2,1:T(8,128)S(1)} "
               "%copy-done.29), kind=kOutput, calls=%fused_computation.502")
LOOP_FUSION = ("%fusion.1298 = f32[100,20,32,32,8,2]{1,5,4,0,3,2:T(2,128)} "
               "fusion(f32[100,20,32,32,8,2]{1,5,4,0,3,2:T(2,128)} "
               "%bitcast.1470), kind=kLoop, calls=%fused_computation.195")
PALLAS = ("%closed_call.99 = (f32[256,1,128]{2,1,0:T(1,128)S(1)}, "
          "s32[256,1,128]{2,1,0:T(1,128)S(1)}) custom-call(f32[8192,128]"
          "{1,0:T(8,128)S(1)} %get-tuple-element.1), "
          "custom_call_target=\"tpu_custom_call\"")
SORT = ("%sort.26 = (f32[1048576]{0:T(1024)}, s32[1048576]{0:T(1024)S(1)}) "
        "sort(f32[1048576]{0:T(1024)S(1)} %get-tuple-element.1532)")
WHILE = ("%while.9 = (s32[]{:T(128)}, f32[1048576]{0:T(1024)S(1)}) "
         "while((s32[]{:T(128)}, f32[1048576]{0:T(1024)S(1)}) %tuple.1)")
ALL_GATHER = ("%all-gather.3 = s32[400]{0:T(512)} all-gather(s32[100]"
              "{0:T(128)} %fusion.7), replica_groups={{0,1,2,3}}")
ALL_REDUCE_START = ("%all-reduce-start.1 = f32[]{:T(128)} all-reduce-start("
                    "f32[]{:T(128)} %reduce.2), to_apply=%add")


def op(start, end, text):
    return tr.parse_op(start, end - start, text)


@pytest.mark.parametrize("text,opcode,kind", [
    (CONV_FUSION, "fusion", "kOutput"), (LOOP_FUSION, "fusion", "kLoop"),
    (PALLAS, "custom-call", ""), (SORT, "sort", ""), (WHILE, "while", ""),
    (ALL_GATHER, "all-gather", ""),
    (ALL_REDUCE_START, "all-reduce-start", "")])
def test_parse_op_reads_opcode_and_fusion_kind(text, opcode, kind):
    o = tr.parse_op(0, 1, text)
    assert (o.opcode, o.kind) == (opcode, kind)
    assert o.name == text.split(" = ")[0].lstrip("%")


@pytest.mark.parametrize("text,conv,topk,coll", [
    (CONV_FUSION, True, False, False), (LOOP_FUSION, False, False, False),
    (PALLAS, False, True, False), (SORT, False, True, False),
    (WHILE, False, False, False), (ALL_GATHER, False, False, True),
    (ALL_REDUCE_START, False, False, True)])
def test_operation_classes(text, conv, topk, coll):
    o = tr.parse_op(0, 1, text)
    assert (tr.is_convolution(o), tr.is_topk(o), tr.is_collective(o)) == \
        (conv, topk, coll)


def test_union_merges_overlaps_and_clips_to_the_window():
    got = tr.union([(5, 10), (0, 3), (8, 14), (2, 4), (20, 30)], 1, 25)
    assert got == [(1, 4), (5, 14), (20, 25)]
    assert tr.length(got) == 3 + 9 + 5


def _trace():
    # one chip, window 0..100: a while loop spans 10..90; inside it a conv
    # fusion 10..40 overlapping a loop fusion 30..50, a sort 60..70 and a
    # pallas call 65..80; idle 0..10, 50..60 and 80..100
    dev = [op(10, 90, WHILE), op(10, 40, CONV_FUSION), op(30, 50, LOOP_FUSION),
           op(60, 70, SORT), op(65, 80, PALLAS)]
    spans = [("bench.window", 0, 100), ("bench.call", 0, 100),
             ("bench.run_rounds", 0, 55), ("bench.device_get", 55, 100)]
    return tr.from_parts({"/device:TPU:0": dev}, spans)


def test_busy_and_idle_from_overlapping_operations():
    t = _trace()
    assert t.window == (0, 100)
    # the while loop is a container and does not count: busy 10..50, 60..80
    assert tr.busy_ns(t) == {"/device:TPU:0": 60}
    assert tr.busy_s(t) == pytest.approx(60e-9)
    assert 1 - tr.busy_s(t) / t.window_s == pytest.approx(0.4)


def test_shares_over_busy_time():
    t = _trace()
    assert tr.share(t, tr.is_convolution) == pytest.approx(30 / 60)
    # sort 60..70 and pallas 65..80 overlap: their union is 20
    assert tr.share(t, tr.is_topk) == pytest.approx(20 / 60)
    assert tr.share(t, tr.is_collective) is None


def test_busy_averages_over_chips():
    t = _trace()
    t.devices["/device:TPU:1"] = [op(0, 100, SORT)]
    assert tr.busy_s(t) == pytest.approx((60 + 100) / 2 * 1e-9)
    assert tr.share(t, tr.is_topk) == pytest.approx((20 + 100) / 160)


def test_breakdown_attributes_gaps_to_the_innermost_span():
    b = tr.breakdown(_trace())
    gaps = dict(b["idle_gaps"])
    # 0..10 during run_rounds, 50..60 midpoint 55 in device_get, 80..100
    # in device_get
    assert gaps == pytest.approx({"bench.run_rounds": 10e-9,
                                  "bench.device_get": 30e-9})
    ops = dict(b["device_ops"])
    assert "while.9" not in ops
    assert ops["multiply_subtract_fusion.120"] == pytest.approx(30e-9)
    assert [n for n, _ in b["device_ops"]][0] == \
        "multiply_subtract_fusion.120"


def test_gap_outside_every_span_is_named_so():
    t = tr.from_parts({"/device:TPU:0": [op(0, 10, SORT)]},
                      [("bench.window", 0, 30)])
    assert dict(tr.breakdown(t)["idle_gaps"]) == pytest.approx(
        {"outside any harness span": 20e-9})
