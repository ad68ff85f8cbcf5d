"""The trace reduction: device busy and idle time, per-kernel device time and
idle gaps labelled by the benchmark's host spans, on a hand-made trace with
known answers."""
import pytest

from bench import trace


def hand_trace():
    """A 1,000 ns window. Device ops, named as a TPU trace names them (the HLO
    instruction): [100, 200) a fusion, [250, 300) the strider kernel,
    [280, 320) a fusion that reads the kernel's output (overlapping, and not
    the kernel), [700, 800) the GLM kernel nested in a loop over
    [690, 810), and [1100, 1150) after the window. Host: Session.sql over [150, 500), BatchedServer.step over
    [600, 950)."""
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["bench.window", 0, 1000, ""],
        ["Session.sql", 150, 350, ""],
        ["BatchedServer.step", 600, 350, ""],
        ["DevicePut", 220, 40, ""],
    ]}, {"name": "another thread", "events": [["busy.elsewhere", 0, 1000, ""]]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.1)", 100, 100, ""],
            ["%strider_decode.1 = f32[4,512,2002]{2,1,0:T(8,128)S(1)} "
             "custom-call(u32[512,8192]{1,0:T(8,128)} %pages.1)", 250, 50, ""],
            ["%fusion.12 = f32[512,2000]{1,0} fusion(f32[4,512,2002]{2,1,0} "
             "%strider_decode.1)", 280, 40, ""],
            ["%while.2 = (s32[], f32[2000]) while((s32[], f32[2000]) "
             "%tuple.27)", 690, 120, ""],
            ["%glm_grad.6 = f32[1,2048]{1,0} custom-call(f32[512,2048]{1,0} "
             "%pad.16)", 700, 100, ""],
            ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p.2)", 1100, 50, ""],
        ]},
        {"name": "XLA Modules", "events": [["jit_impl(123)", 90, 900, ""]]},
    ]}
    return {"planes": [host, dev, {"name": "/device:TPU_NON_CORE:0",
                                   "lines": []}]}


def test_op_names_are_the_instruction_not_its_operands():
    assert trace.op_name("%strider_decode.1 = f32[4] custom-call(u32[2] "
                         "%pages.1)") == "strider_decode"
    assert trace.op_name("%copy-start.12 = (f32[2]) copy-start(%x)") == "copy-start"
    assert trace.op_name("fusion.3") == "fusion"


def test_busy_idle_kernels_and_gap_labels():
    red = trace.reduce(hand_trace(), kernels=("strider_decode", "glm_grad",
                                              "paged_attention"))
    assert red["window_s"] == pytest.approx(1000e-9)
    # union: [100, 200) + [250, 320) + [690, 810) = 290 ns
    assert red["busy_s"] == pytest.approx(290e-9)
    assert red["devices"] == 1
    assert red["kernel_s"]["strider_decode"] == pytest.approx(50e-9)
    assert red["kernel_s"]["glm_grad"] == pytest.approx(100e-9)
    assert red["kernel_s"]["paged_attention"] == 0.0
    ops = dict(red["breakdown"]["device_ops"])
    # the loop's own time is its span less the kernel nested in it
    assert ops == pytest.approx({"fusion": 140e-9, "strider_decode": 50e-9,
                                 "glm_grad": 100e-9, "while": 20e-9})
    # gaps [0,100) mid 50: no span; [200,250) and [320,690) mids 225, 505:
    # 225 in a DevicePut inside Session.sql, 505 in neither; [810,1000)
    # mid 905 in the step. Other threads' events label nothing.
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"no benchmark span": 100e-9 + 370e-9,
                                  "Session.sql/DevicePut": 50e-9,
                                  "BatchedServer.step": 190e-9})
    assert sum(gaps.values()) == pytest.approx(1000e-9 - 290e-9)


def test_a_trace_without_the_window_or_a_device_is_refused():
    t = hand_trace()
    t["planes"][0]["lines"][0]["events"].pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(t)
    t = hand_trace()
    t["planes"] = [t["planes"][0]]
    with pytest.raises(ValueError, match="device"):
        trace.reduce(t)


def test_recorded_chip_trace():
    """A slice of a TPU v5e trace of ``sn_logistic.train`` (bench/testdata):
    the reduction agrees with a count made here by other means."""
    import json
    import os

    from benchfix import REPO

    with open(os.path.join(REPO, "bench/testdata/trace_sn_logistic_train.json")) as f:
        rec = json.load(f)
    red = trace.reduce(rec, kernels=("strider_decode", "glm_grad"))
    (w0, dw), = [(s, d) for n, s, d, _ in rec["planes"][0]["lines"][0]["events"]
                 if n == "bench.window"]
    ops = rec["planes"][1]["lines"][0]["events"]
    # busy: mark every nanosecond an op runs, in the window
    busy = set()
    for _, s, d, _ in ops:
        busy.update(range(max(s, w0), min(s + d, w0 + dw)))
    assert red["window_s"] == pytest.approx(dw / 1e9)
    assert red["busy_s"] == pytest.approx(len(busy) / 1e9)
    for k in ("strider_decode", "glm_grad"):
        own = sum(d for n, s, d, _ in ops if n.startswith(f"%{k}."))
        assert own > 0 and red["kernel_s"][k] == pytest.approx(own / 1e9)
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert set(gaps) <= {"Session.sql", "no benchmark span"}
    assert sum(gaps.values()) == pytest.approx((dw - len(busy)) / 1e9)
    # the device sat idle in Session.sql nearly all of this slice
    assert red["busy_s"] / red["window_s"] < 0.01
