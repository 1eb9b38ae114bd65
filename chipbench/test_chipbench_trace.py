"""The trace reduction, on hand-made intervals, on their dump and
restore, and on a trace recorded on a TPU v5e: two calls of
``paper-ltfl-u30``'s window, with the device's operations merged into
busy intervals except the quantizer kernel's, which keep their names."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import trace
from chipbench.spec import metric_reader

HERE = Path(__file__).resolve().parent
DEV = "/device:TPU:0"
QUANT = ("%vmap_jit_stochastic_quant_dyn__.3 = f32[4] custom-call(f32[4] "
         "%fusion.1), custom_call_target=\"tpu_custom_call\"")
TAKES_QUANT = ("%multiply_reduce_fusion.9 = f32[4] fusion(f32[4] "
               "%vmap_jit_stochastic_quant_dyn__.3, f32[4] %custom-call.2)")


def _trace():
    ev = trace.Event
    return trace.Trace(
        window=(0.0, 100.0),
        ops={DEV: [ev("fusion.1", 5, 20),
                   ev(QUANT, 15, 30, QUANT + " jit(_segment)/"
                      "vmap(jit(stochastic_quant_dyn))/pallas_call"),
                   ev("fusion.2", 50, 70), ev(TAKES_QUANT, 50, 55),
                   ev("late", 95, 120)]},
        modules={DEV: [ev("jit__segment", 5, 30), ev("jit__segment", 50, 70),
                       ev("jit_other", 80, 90)]},
        host=[ev(trace.CALL, 0, 40), ev("chipbench.absorb", 30, 40),
              ev(trace.CALL, 40, 100)])


def test_union_gaps_and_busy_time():
    t = _trace()
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.idle_gaps(t, DEV) == [(0, 5), (30, 50), (70, 95)]
    assert trace.busy_s(t, DEV) == pytest.approx(50e-9)
    assert trace.between_executions(t, DEV, "_segment") == \
        pytest.approx(20e-9)
    assert trace.op_seconds(t, DEV, "stochastic_quant_dyn", "pallas_call") \
        == pytest.approx(15e-9)
    # by the whole instruction, an op that takes the kernel's output counts
    assert trace.op_seconds(t, DEV, "stochastic_quant_dyn",
                            any_of=("mosaic", "custom-call")) == \
        pytest.approx(20e-9)
    assert trace.op_seconds(t, DEV, any_of=("mosaic", "custom-call"),
                            own=("stochastic_quant_dyn",)) == \
        pytest.approx(15e-9)
    assert trace.op_seconds(t, DEV, "fusion", any_of=("mosaic",)) == 0
    b = trace.breakdown(t, DEV)
    assert b["device_ops"][0] == ["fusion.2", pytest.approx(20e-9)]
    assert b["idle_gaps"][:2] == [["chipbench.call", pytest.approx(25e-9)],
                                  ["chipbench.absorb", pytest.approx(20e-9)]]


def test_readers_on_hand_made_trace():
    t = _trace()
    ctx = SimpleNamespace(trace=t, chips=1)
    assert metric_reader("idle_share")(ctx) == pytest.approx(50.0)
    assert metric_reader("host_gap_share")(ctx) == pytest.approx(20.0)


def test_readers_on_a_chip_trace():
    """Pinned on the recorded trace: the device's idle share, the wait
    between segment executions, and the quantizer kernel's time."""
    t = trace.restore(str(HERE / "testdata" / "paper-ltfl-u30.trace.json.gz"))
    dev = t.devices()[0]
    ctx = SimpleNamespace(trace=t, chips=1)
    assert t.window_s == pytest.approx(2.625164435)
    assert metric_reader("idle_share")(ctx) == pytest.approx(2.00556206)
    assert metric_reader("host_gap_share")(ctx) == pytest.approx(0.98627525)
    assert trace.op_seconds(t, dev, any_of=("tpu_custom_call",),
                            own=("stochastic_quant_dyn",)) == \
        pytest.approx(0.047365024)
    gaps = trace.breakdown(t, dev)["idle_gaps"]
    assert gaps[0][0] == "chipbench.absorb"


def test_dump_and_restore_keep_the_window(tmp_path):
    t = _trace()
    path = str(tmp_path / "events.json.gz")
    trace.dump(t, path)
    back = trace.restore(path)
    assert back.window == t.window
    assert trace.idle_gaps(back, DEV) == trace.idle_gaps(t, DEV)
    assert trace.op_seconds(back, DEV, "stochastic_quant_dyn") == \
        trace.op_seconds(t, DEV, "stochastic_quant_dyn")
    assert [e.name for e in back.host] == [e.name for e in t.host]
