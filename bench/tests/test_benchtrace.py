"""The reduction from traces and spans to metrics: hand-made planes and
windows, and a trace recorded on a TPU v5e (a 64^3 round trip of each codec,
``bench/record_trace.py``)."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import cells, loop, run, trace_reduce

TRACE = Path(__file__).parent / "data" / "roundtrip64.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def planes():
    """Two harness spans and device ops on one chip: compress [0, 100) with
    ops [10, 30) and [20, 50) (busy 40), decompress [200, 300) with op
    [250, 260) (busy 10); a gap of 150 between them, 90 inside decompress."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.compress:vx", 0, 100), ev("other", 0, 5), ev("bench.decompress:vx", 200, 100)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("%fusion.1 = f32[8]{0} fusion(...)", 10, 20), ev("%pack", 20, 30),
                                   ev("%pack", 250, 10), ev("%while.2 = (s32[]) while(...)", 250, 5)]),
        NS(name="XLA Modules", events=[ev("jit_f(123)", 0, 300)])])
    return [host, dev]


def test_merge_and_overlap():
    assert trace_reduce.merge([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]
    assert trace_reduce.overlap([(0, 3), (5, 10)], [(2, 6), (8, 20)]) == 1 + 1 + 2


def test_summary_shares_ops_and_gaps():
    s = trace_reduce.summarize(planes())
    assert [sp.label for sp in s.spans] == ["compress:vx", "decompress:vx"]
    assert s.window == (0, 300) and s.window_s == pytest.approx(300e-9)
    assert s.busy_s() == pytest.approx(50e-9)
    assert s.busy_s("compress") == pytest.approx(40e-9)
    assert s.idle_share("compress") == pytest.approx(0.6)
    assert s.idle_share("decompress") == pytest.approx(0.9)
    assert s.idle_share("fetch") is None
    assert s.top_ops() == [["jit_f/pack", pytest.approx(40e-9)], ["jit_f/fusion.1", pytest.approx(20e-9)]]
    gaps = s.idle_gaps()
    assert gaps[0] == ["between spans", pytest.approx(200e-9)]
    assert gaps[1] == ["decompress:vx", pytest.approx(40e-9)]
    assert sum(g[1] for g in gaps) + s.busy_s() == pytest.approx(s.window_s)


def test_no_spans_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.summarize(planes()[1:])


def window():
    return loop.Window(phase_s={"compress": 0.5, "fetch": 0.25, "upload": 1.0, "decompress": 0.4},
                       seconds=2.5, snapshots=2, ops=12, raw_bytes=6_000_000_000,
                       stream_bytes=1_500_000_000, counted={}, kept={})


def test_end_to_end_arithmetic():
    e = loop.end_to_end(window(), 31.5)
    assert e == {"compress_gbps": pytest.approx(12.0), "decompress_gbps": pytest.approx(15.0),
                 "host_roundtrip_gbps": pytest.approx(2.4), "compression_ratio": pytest.approx(4.0),
                 "setup_s": 31.5}


def test_per_layer_readers():
    w = window()
    s = trace_reduce.summarize(planes())
    peak = cells.peak("TPU v5 lite")
    ctx = run.Context("tpu-sz", w.raw_bytes, w.stream_bytes, w.phase_s, s, peak)
    read = {m["name"]: cells.reader(m["name"])(ctx) for m in cells.load_benchmark()["per_layer"]}
    assert read["idle_share.compress"] == pytest.approx(60.0)
    assert read["idle_share.decompress"] == pytest.approx(90.0)
    assert read["sz.encode_hbm_share"] == pytest.approx(100 * 7.5e9 / (819e9 * 40e-9))
    assert read["sz.decode_hbm_share"] == pytest.approx(100 * 7.5e9 / (819e9 * 10e-9))
    assert read["zfp.encode_hbm_share"] is None and read["zfp.decode_hbm_share"] is None
    assert read["fetch_gbps"] == pytest.approx(6.0)
    assert read["upload_gbps"] == pytest.approx(1.5)
    silent = run.Context("tpu-sz", 1, 1, {}, None, peak)
    assert all(cells.reader(n)(silent) is None for n in read)


@pytest.fixture(scope="module")
def chip_trace():
    return trace_reduce.read(str(TRACE))


def test_recorded_trace_spans_and_window(chip_trace):
    s = chip_trace
    assert [sp.phase for sp in s.spans] == list(loop.PHASES) * 2  # SZ, then ZFP
    assert {sp.field for sp in s.spans} == {"baryon_density"}
    assert len(s.busy) == 1  # one TPU plane
    assert s.window_s == pytest.approx(1.06275867)
    assert s.busy_s() == pytest.approx(0.071173259)
    assert s.busy_s() <= sum(s.op_ns.values()) / 1e9 + 1e-12
    assert s.idle_share("compress") == pytest.approx(0.8794259929892834)
    assert s.idle_share("decompress") == pytest.approx(0.9890258738097111)


def test_recorded_trace_ops_and_gaps(chip_trace):
    s = chip_trace
    top = s.top_ops()
    assert len(top) == 10 and top[0] == ["jit_fused_compress/fusion.25", pytest.approx(0.048676384)]
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    gaps = s.idle_gaps(10**6)
    labels = {sp.label for sp in s.spans} | {"between spans"}
    assert all(g[0] in labels for g in gaps)
    assert sum(g[1] for g in gaps) + s.busy_s() == pytest.approx(s.window_s)
    assert s.idle_gaps()[0] == ["fetch:baryon_density", pytest.approx(0.005604495)]
