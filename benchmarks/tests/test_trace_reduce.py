"""The trace reduction: the pure functions on hand-made event lists, and the
whole of it on traces recorded on the chip (``benchmarks/testdata``: the tiny
presets through ``run.py --rehearse --trace 1 --keep-trace`` on a TPU v5e),
against values checked by hand: module starts read off the dump, busy time
recounted on a nanosecond grid."""

import os

import pytest

from benchmarks.lib import spec, trace_reduce as tr

E = tr.Event


def ops():
    # a while loop holding a, a gather and b; then c; then a reduce that d
    # overlaps for one second; idle at 10..12 and from 17
    return [E("while.1", 0, 10), E("a", 0, 4), E("all-gather.1", 4, 2),
            E("b", 6, 4), E("c", 12, 1), E("all-reduce.2", 13, 2),
            E("d", 14, 3)]


def test_interval_arithmetic():
    assert tr.merge([(3, 4), (0, 2), (1, 2.5), (5, 5)]) == [(0, 2.5), (3, 4)]
    assert tr.subtract([(0, 10)], [(1, 2), (3, 4), (9, 12)]) == [
        (0, 1), (2, 3), (4, 9)]
    assert tr.clip([(0, 2), (3, 8)], 1, 5) == [(1, 2), (3, 5)]
    assert tr.total([(0, 1), (2, 4.5)]) == 3.5
    assert tr.median([3, 1, 2]) == 2 and tr.median([4, 1, 2, 3]) == 2.5


def test_busy_idle_and_self_times():
    assert tr.busy_seconds(ops(), 0, 20) == 15
    assert tr.busy_seconds(ops(), 5, 13) == 6
    assert tr.idle_gaps(ops(), 0, 20) == [(10, 12), (17, 20)]
    leaves, self_time = tr.leaves_and_self_times(ops())
    assert self_time == {"while.1": 0, "a": 4, "all-gather.1": 2, "b": 4,
                         "c": 1, "all-reduce.2": 2, "d": 3}
    assert "while.1" not in {e.name for e in leaves}
    assert tr.top_ops(ops(), 0, 20, 2) == [("a x1", 4), ("b x1", 4)]
    layers = [E(f"fusion.{i}", i, 1, "bf16[8,4]") for i in range(3)] \
        + [E("fusion.9", 3, 2, "f32[4]"), E("copy", 5, 0.5)]
    assert tr.top_ops(layers, 0, 10, 2) == [("fusion x3 bf16[8,4]", 3),
                                            ("fusion x1 f32[4]", 2)]


def test_exposed_collective_time():
    # the gather runs alone for 2 s; of the reduce, d hides the second half
    assert tr.exposed_collective_seconds(ops(), 0, 20) == 3
    wrapped = ops()
    wrapped[4] = E("fusion.7", 12, 1, collective=True)  # c, alone
    assert tr.exposed_collective_seconds(wrapped, 0, 20) == 4
    # d is arithmetic that carries a collective along: what it hides is hidden
    wrapped[6] = E("fusion.8", 14, 3, carrier=True)
    assert tr.exposed_collective_seconds(wrapped, 0, 20) == 4
    assert tr.carrier_seconds(wrapped, 0, 20) == 3
    assert tr.carrier_seconds(wrapped, 0, 15) == 1
    assert tr.exposed_collective_seconds(ops(), 0, 12) == 2
    assert tr.exposed_collective_seconds(
        [E("fusion.1", 0, 5)], 0, 5) == 0


def test_step_window_and_gap_labels():
    modules = [E("jit_init(1)", 0, 9)] + [E("jit_step(7)", t, 1)
                                          for t in (10, 12, 14, 16.5, 18.5)]
    assert tr.step_module(modules) == "jit_step(7)"
    assert tr.step_module([E("jit_f(1)", 0, 2), E("jit_g(2)", 3, 5)]) \
        == "jit_g(2)"
    lo, hi, steps, periods = tr.steady_window(modules, "jit_step(7)")
    assert (lo, hi, steps, periods) == (12, 18.5, 3, [2, 2.5, 2])
    assert tr.steady_window(modules[:3], "jit_step(7)") is None
    spans = [E("bench.x", 9, 2.5), E("bench.y", 18, 5)]
    assert tr.label_gaps([(10, 12), (17, 20), (30, 30.5)], spans, 2) == [
        ("bench.y", 3), ("bench.x", 2)]
    assert tr.label_gaps([(30, 31)], spans) == [("host:unspanned", 1)]


def record(trace, chips, mosaic=None, collectives=None):
    from benchmarks.lib.record import RunRecord

    return RunRecord(
        cell={}, chips=chips, peaks=None, tokens_per_step=1024,
        flops_per_step=1.0, seq_len=256, attention_calls=(), trace=trace,
        steady=tr.steady_window(trace.first.modules,
                                tr.step_module(trace.first.modules)),
        hlo={"mosaic": mosaic or {}, "collectives": collectives or {}})


@pytest.fixture(scope="module")
def one_chip():
    return tr.load(os.path.join(spec.BENCH_DIR, "testdata",
                                "tiny-llama-1chip.xplane.pb.gz"))


def test_recorded_trace_one_chip(one_chip):
    assert set(one_chip.devices) == {0}
    assert {"XLA Modules", "XLA Ops", "Steps"} <= set(
        one_chip.layout["/device:TPU:0"])
    dev = one_chip.devices[0]
    name = tr.step_module(dev.modules)
    assert name.startswith("jit_step(")
    lo, hi, steps, periods = tr.steady_window(dev.modules, name)
    # eight executions start at 46737732, 51458512, ..., 67570657 ns
    assert round(lo * 1e9) == 51458512 and round(hi * 1e9) == 67570657
    assert steps == 6 and round(periods[0] * 1e9) == 4255603
    # instruction events are cut to name and result type
    assert all(" = " not in e.name for e in dev.ops)
    # a tiny step leaves the chip idle most of the time
    busy = tr.busy_seconds(dev.ops, lo, hi)
    assert round(busy * 1e9) == 2595124
    assert 1 - busy / (hi - lo) == pytest.approx(0.83893, abs=1e-5)
    # kernel sums, through the record the readers get
    run = record(one_chip, chips=1, mosaic={
        "splash_mha_fwd_residuals.15": "", "splash_mha_fwd_residuals.16": "",
        "splash_mha_dkv_no_residuals.9": "", "another_kernel.1": ""})
    sums = {}
    for e in run.kernel_events("splash"):
        sums[e.name] = sums.get(e.name, 0) + round(e.dur * 1e9)
    assert sums == {"splash_mha_fwd_residuals.15": 146478,
                    "splash_mha_fwd_residuals.16": 154336,
                    "splash_mha_dkv_no_residuals.9": 202839}
    splash_ms = spec.load_module("layer_metrics", "kernels.splash_ms")
    assert splash_ms.read(run) == pytest.approx(503653e-6 / 6)
    assert run.kernel_events("fused_ce") == []
    assert run.step_seconds == pytest.approx(2402908.5e-9)  # median of six
    assert tr.exposed_collective_seconds(dev.ops, lo, hi) == 0
    gaps = tr.idle_gaps(dev.ops, lo, hi)
    assert len(gaps) == 1392
    assert tr.total(gaps) == pytest.approx(hi - lo - busy)
    label, longest = tr.label_gaps(gaps, one_chip.host_spans)[0]
    assert label == "bench.fence" and round(longest * 1e9) == 3822827
    assert {s.name for s in one_chip.host_spans} == {
        "bench.next_batch", "bench.dispatch", "bench.report", "bench.fence"}


def test_recorded_trace_four_chips():
    """The tiny Llama preset under ``MeshSpec(fsdp=4)`` on four v5e chips:
    gathers, reduces and all-to-alls, many of them asynchronous pairs whose
    wait shows as ``async-collective-done``."""
    trace = tr.load(os.path.join(spec.BENCH_DIR, "testdata",
                                 "tiny-llama-fsdp4.xplane.pb.gz"))
    assert sorted(trace.devices) == [0, 1, 2, 3]
    # per chip: window start, busy, exposed-collective and carrier
    # nanoseconds, the last three recounted on a nanosecond grid from the
    # leaf events (exposed + carrier is what PR 22 counted as exposed)
    want = {0: (154976305, 2157314, 1385510, 339149),
            1: (154971890, 2151202, 1379394, 338964),
            2: (154966575, 2151893, 1382721, 338451),
            3: (154913421, 2145308, 1375381, 338614)}
    for ordinal, dev in trace.devices.items():
        lo, hi, steps, _ = tr.steady_window(dev.modules,
                                            tr.step_module(dev.modules))
        assert steps == 6
        got = (round(lo * 1e9), round(tr.busy_seconds(dev.ops, lo, hi) * 1e9),
               round(tr.exposed_collective_seconds(dev.ops, lo, hi) * 1e9),
               round(tr.carrier_seconds(dev.ops, lo, hi) * 1e9))
        assert got == want[ordinal]
    ops0 = trace.devices[0].ops
    kinds = {tr.COLLECTIVE.match(e.name).group(1)
             for e in ops0 if tr.COLLECTIVE.match(e.name)}
    assert kinds == {"all-gather", "all-reduce", "all-to-all",
                     "async-collective"}
    # the gradients' reduce-scatter hides under a fusion's name
    wrapped = {e.name.split(".")[0] for e in ops0 if e.collective}
    assert wrapped == {"fusion"}
    assert sum(e.collective for e in ops0) > 0
    # a matmul between a gather's start and its done carries it along
    assert {e.name.split(".")[0] for e in ops0 if e.carrier} == {"fusion"}
    assert not any(e.carrier and tr.is_collective(e) for e in ops0)


def test_collective_readers_on_the_four_chip_trace():
    """``collectives.*`` and ``device.idle`` on a trace of four chips (the
    tiny preset's, recorded on the chip in PR 22)."""
    trace = tr.load(os.path.join(spec.BENCH_DIR, "testdata",
                                 "tiny-llama-fsdp4.xplane.pb.gz"))
    run = record(trace, chips=4, collectives={
        "all-gather": 55, "all-reduce": 4, "reduce-scatter": 0,
        "all-to-all": 2, "collective-permute": 0})
    exposed = spec.load_module("layer_metrics", "collectives.exposed_ms")
    # the worst chip is chip 0: 1385510 ns over six steps
    assert exposed.read(run) == pytest.approx(1385510e-6 / 6, rel=1e-6)
    carrier = spec.load_module("layer_metrics", "collectives.carrier_ms")
    assert carrier.read(run) == pytest.approx(339149e-6 / 6, rel=1e-6)
    assert spec.load_module("layer_metrics", "collectives.count").read(run) \
        == 59
    idle = spec.load_module("layer_metrics", "device.idle").read(run)
    # the least busy chip, 3, inside the first chip's window of 19829063 ns
    assert idle == pytest.approx(100 * (1 - 2145308 / 19829063), abs=1e-3)
    run.trace = run.steady = None
    assert exposed.read(run) is None and carrier.read(run) is None
