"""The readers of the serve engine's spans and counters, on a hand-made
trace and hand-made results: known answers, and nothing only where the
program wrote none of what they read."""
from types import SimpleNamespace

import pytest

from bench.harness import spec
from bench.harness.runner import Run
from bench.harness.trace import Trace

# four decode steps (ns): one admission and its prefill before the
# first; an admission and two prefill chunks between steps 1 and 2
HOST = [
    ["serve.admit", 0, 10], ["serve.prefill", 10, 40],
    ["serve.dispatch", 60, 10], ["serve.readback", 70, 30],
    ["serve.accept", 100, 5],
    ["serve.dispatch", 110, 10], ["serve.readback", 120, 30],
    ["serve.accept", 150, 5],
    ["serve.admit", 160, 5], ["serve.prefill", 165, 35],
    ["serve.prefill", 200, 40],
    ["serve.dispatch", 250, 10], ["serve.readback", 260, 30],
    ["serve.accept", 290, 5],
    ["serve.dispatch", 300, 10], ["serve.readback", 310, 30],
    ["PjitFunction(decode_step_multi)", 302, 3],
]


def _trace(host):
    return Trace({"window": [0, 1000], "devices": [], "host": host})


def _results(admits=(0.0, 1.0, 2.0, 3.0, 4.0), blocked=(1, 0)):
    reqs = [{"prompt_len": 4, "new_tokens": 2, "admit_s": a, "ttft_s": a,
             "latency_s": a} for a in admits]
    return [SimpleNamespace(steps=4, per_request=reqs[:2],
                            admission_blocked=blocked[0]),
            SimpleNamespace(steps=6, per_request=reqs[2:],
                            admission_blocked=blocked[1])]


def read(name, host=HOST, results=None):
    run = Run(cell=None, dims=None, chips=1, peaks={},
              results=results or _results(), trace=_trace(host))
    return spec.metric_reader(name)(run)


def test_queue_wait_p90_s():
    # numpy's linear p90 of 0, 1, 2, 3, 4
    assert read("queue_wait_p90_s") == pytest.approx(3.6)


def test_pool_blocked_share():
    assert read("pool_blocked_share") == pytest.approx(100 * 1 / 10)
    assert read("pool_blocked_share", results=_results(
        blocked=(0, 0))) == 0.0


def test_decode_stall_ms_per_step():
    # the chunks between steps 1 and 2 (35 + 40 ns) over 4 steps; the
    # prefill before the first step stalls no decoding slot
    assert read("decode_stall_ms_per_step") == pytest.approx(75e-6 / 4)
    no_prefill = [h for h in HOST if h[0] != "serve.prefill"]
    assert read("decode_stall_ms_per_step", no_prefill) == 0.0


def test_host_step_ms():
    # read-back 0 ends at 100, dispatch 1 at 120; read-back 2 ends at 290,
    # dispatch 3 at 310; steps 1 -> 2 have an admission between them
    assert read("host_step_ms") == pytest.approx(20e-6)


def test_absent_inputs_read_nothing():
    """A program that writes no ``serve.*`` span and no ``admit_s`` or
    ``admission_blocked`` gives each reader nothing to read."""
    bare = [SimpleNamespace(steps=4, per_request=[
        {"prompt_len": 4, "new_tokens": 2, "ttft_s": 0.1,
         "latency_s": 0.2}])]
    runtime_only = [h for h in HOST if not h[0].startswith("serve.")]
    for name in ("queue_wait_p90_s", "pool_blocked_share"):
        assert read(name, results=bare) is None, name
    for name in ("decode_stall_ms_per_step", "host_step_ms"):
        assert read(name, runtime_only) is None, name
