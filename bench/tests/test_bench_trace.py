"""The trace reduction, on a hand-made trace and on a profile recorded
by ``jax.profiler`` in the test itself."""
from types import SimpleNamespace

import pytest

from bench.harness import trace as tr_mod
from bench.harness.trace import Trace

# window 0..100 ns; chip 0 runs a decode program 10..40 (two ops, one a
# kernel) and a prefill program 50..70; chip 1 is busy 10..30 with an
# all-reduce 20..30; the host sleeps 70..100 and reads back 40..50
HAND = {
    "window": [0, 100],
    "devices": [
        {"name": "/device:TPU:0",
         "modules": [["jit_decode(1)", 10, 30, "jit_decode(1)"],
                     ["jit_prefill(2)", 50, 20, "jit_prefill(2)"]],
         "ops": [["fusion.1", 10, 20, "jit_decode(1)"],
                 ["_kernel", 25, 15, "jit_decode(1)"],
                 ["fusion.2", 50, 20, "jit_prefill(2)"]]},
        {"name": "/device:TPU:1",
         "modules": [["jit_decode(1)", 10, 20, "jit_decode(1)"]],
         "ops": [["fusion.1", 10, 10, "jit_decode(1)"],
                 ["all-reduce.3", 20, 10, "jit_decode(1)"]]},
    ],
    "host": [["generate", 0, 100], ["np.asarray", 40, 10],
             ["time.sleep", 70, 30]],
}


def test_union_merges_overlaps():
    assert tr_mod.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4),
                                                               (5, 10)]
    assert tr_mod.covered_ns([(0, 10), (5, 15), (20, 21)]) == 16


def test_busy_idle_and_programs():
    t = Trace(HAND)
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_ns(0) == 50  # 10..40 (overlapping ops) and 50..70
    assert t.busy_ns(1) == 20
    assert t.idle_share() == pytest.approx(1 - 35 / 100)
    decode = lambda n: n == "jit_decode"  # noqa: E731
    assert t.module_ns(0, decode) == 30 and len(t.modules(0, decode)) == 1
    assert t.ops_ns(0, lambda n: "kernel" in n, decode) == 15
    assert t.ops_ns(0, lambda n: "kernel" in n,
                    lambda m: m == "jit_prefill") == 0
    assert t.collective_ns(1) == 10 and t.collective_ns(0) == 0


def test_breakdown_names_ops_and_gaps():
    b = Trace(HAND).breakdown()
    assert b["device_ops"][0] == ["jit_decode/fusion.1", pytest.approx(15e-9)]
    # chip 0 idles 0..10, 40..50, 70..100: the longest is the host's sleep
    assert b["idle_gaps"][0] == ["time.sleep", pytest.approx(30e-9)]
    assert b["idle_gaps"][1][0] in ("np.asarray", "generate")
    assert len(b["idle_gaps"]) == 3


def test_extract_clips_to_the_window():
    ev = lambda n, s, d, **st: SimpleNamespace(  # noqa: E731
        name=n, start_ns=s, duration_ns=d, end_ns=s + d, stats=st)
    line = lambda n, evs: SimpleNamespace(name=n, events=evs)  # noqa: E731
    pd = SimpleNamespace(planes=[
        SimpleNamespace(name="/host:CPU", lines=[line("python", [
            ev("bench.window", 100, 100), ev("step", 90, 20)])]),
        SimpleNamespace(name="/device:TPU:0", lines=[
            line("XLA Modules", [ev("jit_a(3)", 50, 100)]),
            line("XLA Ops", [ev("fusion", 60, 60, hlo_module="jit_a(3)"),
                             ev("copy", 180, 10), ev("late", 250, 5)])]),
        SimpleNamespace(name="/device:TPU:0 SparseCore", lines=[]),
    ])
    d = tr_mod.extract(pd, "bench.window")
    assert d["window"] == [100, 200]
    (dev,) = d["devices"]
    assert dev["modules"] == [["jit_a(3)", 100, 50, "jit_a(3)"]]
    assert dev["ops"] == [["fusion", 100, 20, "jit_a(3)"],
                          ["copy", 180, 10, ""]]
    assert d["host"] == [["step", 100, 10]]
    with pytest.raises(ValueError):
        tr_mod.extract(pd, "no such span")


def test_traced_window_reads_a_real_profile():
    """The profiler's own file, recorded here on the CPU: the window span
    is found and the runtime's host spans are kept (the CPU has no TPU
    planes, so no device)."""
    import tiny
    from bench.harness import runner, spec
    from bench.harness.program import Program

    c = tiny.cell(True)
    prog = Program("t", c.config, 1)
    mix = spec.traffic_generator(c.traffic).make(c.traffic, 512, 1)
    prog.start()
    calls, results, window_s, tr = runner._traced_window(prog, mix)
    assert len(calls) == 1 and results[0].steps > 0
    assert 0 < tr.window_s <= window_s
    assert tr.devices == []
    names = {h[0] for h in tr.host}
    assert "PjitFunction(decode_step_multi)" in names
    assert all(tr.start <= s and s + d <= tr.end for _, s, d in tr.host)
