"""The trace reduction on a small synthetic trace."""
import pathlib
import sys
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from benchlib import trace as T  # noqa: E402
from benchlib.peaks import V5E  # noqa: E402
from benchlib.spec import metric_reader  # noqa: E402

FLASH = ('%closed_call.18 = bf16[32,4096,128]{2,1,0} custom-call('
         'bf16[32,4096,128]{2,1,0} %a, bf16[32,4096,128]{2,1,0} %b, '
         'bf16[32,4096,128]{2,1,0} %c), custom_call_target="tpu_custom_call"')


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def hlo(name, opcode, shape="f32[8]{0}"):
    return f"%{name} = {shape} {opcode}(f32[8]{{0}} %x)"


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 1000, 1000),
        ev("bench.batch", 1000, 100),
        ev("bench.dispatch", 1100, 50),
        ev("bench.wait", 1150, 850),
        ev("other", 1000, 10)])])
    d0 = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            ev(hlo("fusion.1", "fusion"), 900, 200),   # starts before window
            ev(hlo("while.9", "while", "(s32[], f32[8]{0})"), 1250, 400),
            ev(FLASH, 1300, 100),
            ev(hlo("fusion.3", "fusion"), 1350, 150),    # overlaps the kernel
            ev(hlo("alloc", "custom-call"), 1360, 0),
            ev(hlo("all-reduce-done.4", "all-reduce-done",
                   "(f32[8]{0}, f32[8]{0})"), 1450, 150),
            ev(hlo("copy-done.5", "copy-done", "f32[8]{0:S(5)}"), 1800, 100)]),
        NS(name="XLA Modules", events=[ev("jit_step", 900, 1100)])])
    d1 = NS(name="/device:TPU:1", lines=[NS(name="XLA Ops", events=[
        ev(hlo("fusion.1", "fusion"), 1000, 500)])])
    return [host, d0, d1, NS(name="/device:TPU:0 SparseCore", lines=[
        NS(name="XLA Ops", events=[ev("x", 1000, 1000)])])]


def test_window_clipping_and_nesting():
    t = T.from_planes(planes())
    assert t.window == (1000, 2000)
    assert sorted(t.devices) == [0, 1]
    names = [o.name for o in t.devices[0]]
    assert "while.9" not in names and "alloc" in names
    first = t.devices[0][0]
    assert (first.start, first.end) == (1000, 1100)
    assert {s[2] for s in t.spans} == {"bench.batch", "bench.dispatch",
                                       "bench.wait"}


def test_opcode_and_label():
    assert T.opcode(hlo("a.1", "all-reduce-done", "(f32[2]{0}, f32[2]{0})")
                    ) == "all-reduce-done"
    assert T.Op(0, 1, FLASH).label == "closed_call.18 tpu_custom_call"


def test_busy_union_and_idle_share():
    t = T.from_planes(planes())
    busy = T.busy_s(t)
    # chip 0: [1000,1100) + [1300,1600) + [1800,1900) = 500 ns; the while
    # [1250,1650) is not work of its own
    assert busy[0] == pytest.approx(500e-9)
    assert busy[1] == pytest.approx(500e-9)
    assert T.idle_share(t) == pytest.approx(0.5)


def test_exposed_collective():
    t = T.from_planes(planes())
    # all-reduce-done [1450,1600) minus fusion.3 [1350,1500) = 100 ns
    assert T.exposed_collective_s(t) == pytest.approx(100e-9)
    t.devices[0] = [o for o in t.devices[0] if "all-reduce" not in o.text]
    assert T.exposed_collective_s(t) is None


def test_idle_gaps_are_labelled_by_host_span():
    t = T.from_planes(planes())
    gaps = T.idle_gaps(t)
    assert [g[0] for g in gaps] == ["bench.wait"] * 3
    assert [g[1] for g in gaps] == pytest.approx([200e-9, 200e-9, 100e-9])
    top = T.top_ops(t, 2)
    assert top[0][0] == "fusion.1 fusion"
    assert top[0][1] == pytest.approx((100 + 500) * 1e-9 / 2)


def test_readers_on_the_trace():
    t = T.from_planes(planes())
    run = NS(trace=t, steps=2, peaks=V5E)
    # one flash call of 32 rows x 4096 x 128 in 100 ns of device time
    least = 2 * 2 * 32 * 4096 * 4096 * 128 / 2 / 197e12
    assert metric_reader("flash_fwd_roofline")(run) == pytest.approx(
        least / 100e-9 * 100)
    assert metric_reader("optimizer.host_xfer_ms")(run) == pytest.approx(
        100e-9 / 2 * 1e3)
    assert metric_reader("collective.exposed_ms")(run) == pytest.approx(
        100e-9 / 2 * 1e3)
    assert metric_reader("device.idle_share")(run) == pytest.approx(50.0)
    assert metric_reader("flash_fwd_roofline")(NS(trace=None)) is None


def test_one_window_span_required():
    p = planes()
    p[0].lines[0].events.append(ev("bench.window", 3000, 10))
    with pytest.raises(ValueError):
        T.from_planes(p)


def test_union():
    assert T.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
