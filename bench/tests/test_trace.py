"""The trace reduction: interval arithmetic on a hand-made trace, and the
whole reduction on a small trace recorded on a TPU v5e
(``data/gp26-fwd.xplane.pb``, 51 KB)."""
import pathlib
from types import SimpleNamespace as NS

import pytest

from bench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
KERNELS = ("kron_chain_fwd", "kron_chain_bwd", "kron_stage_grad")


US = 1000.0  # the hand-made trace counts in microseconds


def ev(name, start, dur):
    return NS(name=name, start_ns=start * US, duration_ns=dur * US)


def fake(device_ops, host=(), window=(0, 100)):
    """A ProfileData look-alike: one device plane per list of ops."""
    planes = [NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev(trace.WINDOW_SPAN, window[0], window[1] - window[0]),
        *[ev(n, s, d) for n, s, d in host]])])]
    for i, ops in enumerate(device_ops):
        planes.append(NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Modules", events=[]),
            NS(name=trace.OPS_LINE, events=[ev(f"%{n} = f32[8] op(%x)", s, d)
                                            for n, s, d in ops])]))
    return NS(planes=planes)


def test_union_and_self_times():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    # a while loop holding two ops: its own time is what they leave
    assert trace.self_times([(0, 10), (1, 3), (4, 8)]) == [4, 2, 4]


def test_op_name_ignores_operands():
    name = "%reshape.2 = f32[16] reshape(f32[4,4] %kron_chain_fwd.3)"
    assert trace.op_name(name) == "reshape.2"
    assert not trace.is_kernel(trace.op_name(name), KERNELS)
    assert trace.is_kernel("kron_chain_fwd.3", KERNELS)


def test_reduce_counts_each_kind_of_time():
    ops = [("kron_chain_fwd.1", 10, 20), ("while.3", 40, 20), ("fusion.1", 45, 5),
           ("all-to-all.1", 60, 15), ("fusion.7", 62, 4), ("fusion.9", 150, 5)]
    s = trace.reduce(fake([ops], host=[("bench.call", 30, 20)]), KERNELS)
    d = s.devices[0]
    assert s.window_s == pytest.approx(100e-6)
    assert d.busy_s == pytest.approx(55e-6)  # 10-30, 40-75; the op at 150 is outside
    assert d.kernel_s == pytest.approx(20e-6)
    assert d.collective_s == pytest.approx(15e-6)
    assert d.exposed_collective_s == pytest.approx(11e-6)  # less the fusion under it
    assert d.other_s == pytest.approx(24e-6)  # the while loop and the fusion
    assert d.ops["while.3"] == pytest.approx(15e-6)  # less the fusion inside it
    b = s.breakdown()
    assert b["device_ops"][0][0] in ("kron_chain_fwd.1", "while.3")
    # idle: 0-10, 30-40, 75-100; the longest is at the end, under no host span
    assert b["idle_gaps"][0] == ["no host span", pytest.approx(25e-6)]
    assert ["bench.call", pytest.approx(10e-6)] in b["idle_gaps"]


def test_busy_is_the_mean_over_chips():
    s = trace.reduce(fake([[("fusion.1", 0, 40)], [("fusion.1", 0, 60)]]), KERNELS)
    assert s.busy_s == pytest.approx(50e-6)


def test_no_window_span_is_an_error():
    pd = fake([[("fusion.1", 0, 40)]])
    pd.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(pd, KERNELS)


def test_recorded_chip_trace():
    """Three blocked ``gp26-fwd`` calls on one v5e (the first chip run of
    this benchmark, before the harness had its window span): the window is
    the first call's start to the last call's end."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA / "gp26-fwd.xplane.pb"))
    calls = [(s, e) for n, s, e in trace.host_spans(pd) if n == "bench.call"]
    assert len(calls) == 3
    s = trace.reduce(pd, KERNELS, window=(calls[0][0], calls[-1][1]))
    d = s.devices[0]
    assert [x.name for x in s.devices] == ["/device:TPU:0"]
    assert 0 < d.busy_s <= s.window_s
    # the forward runs three kron_chain_fwd kernels, 6.4 ms each, per call
    assert d.kernel_s / 3 == pytest.approx(19.1e-3, rel=0.02)
    assert d.collective_s == 0 and d.exposed_collective_s == 0
    assert d.kernel_s + d.other_s == pytest.approx(d.busy_s, rel=1e-9)
    ops = dict(s.breakdown()["device_ops"])
    assert sum(v for k, v in ops.items() if trace.is_kernel(k, KERNELS)) == pytest.approx(d.kernel_s)
    # the two relayouts of the kernels' outputs are not kernels
    assert ops["reshape.2"] > 0.01 and not trace.is_kernel("reshape.2", KERNELS)
    assert 0 < 1 - s.busy_s / s.window_s < 0.05


@pytest.mark.parametrize("op, want", [("all_to_all.24", True), ("all-to-all-start.3", True),
                                      ("all-reduce.1", True), ("kron_chain_fwd.4", False),
                                      ("reshape_broadcast_in_dim.12", False)])
def test_collectives_are_known_by_either_spelling(op, want):
    assert trace.is_collective(op) is want
