"""The trace reduction and the roofline's byte counts."""

import pytest

from bench import roofline, trace_reduce

MS = 1_000_000  # ns


def _trace():
    """Window 0-100 ms; device ops at 10-30, 20-40 (overlap) and 60-70 ms;
    host spans: a flush over 0-50 with a scan inside 6-45, and a
    construction round over 50-100."""
    fp = ('%fingerprint_bank_pallas.1 = u32[2,2,256]{2,1,0} custom-call('
          'u32[2,8,256]{2,1,0} %a, u32[2,8,2]{2,1,0} %b, u32[2,1,4]{2,1,0} '
          '%c), custom_call_target="tpu_custom_call", metadata={op_name='
          '"jit(round)/jit(fingerprint_bank_pallas)/pallas_call"}')
    pad = ('%fusion.1 = u32[2,8,256]{2,1,0} fusion(u32[2,3,256]{2,1,0} %w), '
           'metadata={op_name="jit(round)/jit(fingerprint_bank_pallas)/pad"}')
    ops = [("fusion.1", 10 * MS, 30 * MS, {"long_name": pad}),
           ("fusion.2", 20 * MS, 40 * MS, {}),
           ("fingerprint_bank_pallas.1", 60 * MS, 70 * MS, {"long_name": fp}),
           ("late", 150 * MS, 160 * MS, {})]
    mods = [("jit_scan(123)", 10 * MS, 40 * MS, {}),
            ("jit_round(9)", 60 * MS, 70 * MS, {})]
    return {"host": [(trace_reduce.WINDOW, 0, 100 * MS),
                     ("scheduler.flush", 0, 50 * MS),
                     ("scanner.scan", 6 * MS, 45 * MS),
                     ("construction.round", 50 * MS, 100 * MS)],
            "devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": mods}}}


def test_busy_is_the_union_of_op_intervals():
    r = trace_reduce.reduce_loaded(_trace())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.040)    # 10-40 and 60-70 ms
    assert r["programs"] == pytest.approx({"jit_scan": 0.030,
                                           "jit_round": 0.010})
    assert "late" not in r["ops"]


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = dict(trace_reduce.reduce_loaded(_trace())["breakdown"]["idle_gaps"])
    # 0-10 and 40-60 ms: mid 5 ms (flush) and 50 ms (round); 70-100 (round)
    assert gaps == pytest.approx({"scheduler.flush": 0.010,
                                  "construction.round": 0.050})


def test_kernel_bytes_and_roofline_share():
    r = trace_reduce.reduce_loaded(_trace())
    k = r["kernels"]["fingerprint_bank_pallas"]
    want = 4 * (2 * 2 * 256 + 2 * 8 * 256 + 2 * 8 * 2 + 2 * 1 * 4)
    assert k["bytes"] == want and k["count"] == 1
    share = roofline.kernel_share(
        {"trace": r, "device_kind": "TPU v5 lite"}, "fingerprint_bank_pallas")
    assert share == pytest.approx(100 * want / 819e9 / 0.010)
    assert roofline.kernel_share(
        {"trace": r, "device_kind": "TPU v5 lite"}, "expand_bank_pallas") is None


def test_hlo_bytes():
    assert roofline.hlo_bytes("s32[4,128]{1,0} f(bf16[8]{0}, pred[3])") == \
        4 * 4 * 128 + 2 * 8 + 3
    assert roofline.hlo_bytes("no shapes here") == 0


def test_call_bytes_of_compiled_hlo():
    """Compiled HLO (as XLA:TPU gives it for a v5e) names a custom call's
    operands without shapes; their shapes are the layout constraints."""
    text = ('%expand_bank_pallas.1 = s32[4,128,20,16]{3,2,1,0:T(8,128)S(1)} '
            'custom-call(%copy.4, %bitcast.5), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={s32[4,128,16]'
            '{2,1,0}, s32[4,20,16]{2,1,0}}, frontend_attributes='
            '{kernel_metadata={}}, metadata={op_name="jit(expand_bank_pallas)'
            '/pallas_call"}, backend_config={"custom_call_config":{"body":'
            '"TUzvUgFNTElSMjIu"}}')
    assert roofline.call_bytes(text) == 4 * (4 * 128 * 20 * 16
                                             + 4 * 128 * 16 + 4 * 20 * 16)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_trace_without_window_is_an_error():
    tr = _trace()
    tr["host"] = tr["host"][1:]
    with pytest.raises(ValueError):
        trace_reduce.reduce_loaded(tr)
