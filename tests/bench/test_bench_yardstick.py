"""The benchmark's yardstick: the table of peaks, the algorithmic byte
counts and the trace reduction."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import bytes_model, peaks, trace_reduce

FIXTURE = Path(__file__).with_name("fixtures")


def test_known_device_has_its_peaks_and_unknown_raises():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flop_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")


FLAT = {"ways": 8}


def test_request_bytes_from_geometry():
    # one set of 8 ways x 5 lanes x 4 B read, one 20 B entry written
    assert bytes_model.request_bytes(FLAT) == 8 * 20 + 20
    assert bytes_model.request_bytes({"ways": 16}) == 16 * 20 + 20


def test_request_bytes_ignore_block_and_dma_shapes(monkeypatch):
    """The count reads only the configuration: changing the kernels' lane
    padding or L2 DMA block moves nothing, nor does the number of sets."""
    from repro.kernels import kway_probe, replay
    before = bytes_model.request_bytes(FLAT)
    monkeypatch.setattr(replay, "L2_BLOCK", 1)
    monkeypatch.setattr(kway_probe, "LANES", 8)
    assert bytes_model.request_bytes(FLAT) == before
    assert bytes_model.request_bytes({**FLAT, "num_sets": 1 << 22}) == before


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur),
              end_ns=float(start + dur))


def _fake_trace():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("window", 1000, 10000),
        _ev("replay_segment", 1000, 6000),
        _ev("readback", 6500, 1500),
        _ev("replay_segment", 8000, 3000),
    ])])
    ops = [_ev("fusion.1", 1500, 2000), _ev("sort.2", 3000, 2500),   # overlap
           _ev("fusion.1", 8500, 1500), _ev("fusion.1", 10500, 2000)]  # clipped
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_fn(123)", 1500, 4000),
                                       _ev("jit_fn(123)", 8500, 4000)]),
        NS(name="XLA Ops", events=ops)])
    return NS(planes=[host, dev, NS(name="/device:TPU:0 extra", lines=[])])


def test_summary_of_a_small_trace():
    s = trace_reduce.summarize(_fake_trace(), chips=1)
    assert s["window_s"] == pytest.approx(10000e-9)
    # busy: [1500, 5500] + [8500, 10000] + [10500, 11000] = 4000 + 1500 + 500
    assert s["busy_s"] == pytest.approx(6000e-9)
    assert trace_reduce.idle_pct(s) == pytest.approx(40.0)
    assert s["ops_s"]["fusion.1"] == pytest.approx(4000e-9)
    assert s["ops_s"]["sort.2"] == pytest.approx(2500e-9)
    assert trace_reduce.device_time(s, r"^jit_fn", modules=True) \
        == pytest.approx((4000 + 2500) * 1e-9)
    # gaps [1000,1500] and [10000,10500] lie in segments; the middle of
    # [5500,8500] lies in the readback, the innermost span covering it
    gaps = s["idle_gaps_s"]
    assert gaps["replay_segment"] == pytest.approx(1000e-9)
    assert gaps["readback"] == pytest.approx(3000e-9)
    b = trace_reduce.breakdown(s)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(4000e-9)]
    assert b["idle_gaps"][0][0] == "readback"


def test_trace_without_a_device_is_refused():
    with pytest.raises(ValueError, match="no /device:TPU"):
        trace_reduce.summarize(NS(planes=[_fake_trace().planes[0]]), chips=1)


def test_reduction_of_a_recorded_chip_trace():
    """A 1 s traced window of the tiered replay on a TPU v5e: three calls
    of the Pallas hierarchy kernel, each packing and unpacking the L2."""
    s = trace_reduce.summarize(trace_reduce.load(FIXTURE), chips=1)
    assert s["window_s"] == pytest.approx(1.116222655)
    assert s["busy_s"] == pytest.approx(1.100963257)
    assert trace_reduce.idle_pct(s) == pytest.approx(1.36705682613)
    kernel = trace_reduce.device_time(s, r"^%_replay_hier_jit")
    assert kernel == pytest.approx(0.995710591)
    assert trace_reduce.device_time(s, r"^jit__replay_hier_jit\(", modules=True) \
        <= s["busy_s"]
    b = trace_reduce.breakdown(s)
    assert b["device_ops"][0][0] == "%_replay_hier_jit.1"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert {name for name, _ in b["idle_gaps"]} <= set(trace_reduce.SPANS)
