"""The serve engine's host spans and stamps in a profile recorded on the
CPU at test size, read as the benchmark reads them; and the names of the
engine's compiled programs under a (1, 4) mesh."""
import glob
import json
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

NAMES = ("serve.generate", "serve.admit", "serve.prefill", "serve.dispatch",
         "serve.readback", "serve.accept")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One call of the tiny mix untraced, then the same call traced with
    its profile kept: (result untraced, result traced, Trace, the serve.*
    events with their stats)."""
    import tiny
    from jax.profiler import ProfileData

    from bench.harness import runner, spec
    from bench.harness.program import Program

    c = tiny.cell(True)
    prog = Program("t", c.config, 1)
    mix = spec.traffic_generator(c.traffic).make(c.traffic, 512, 1)
    prog.start()
    plain = prog.generate(*mix.call(0))
    log_dir = str(tmp_path_factory.mktemp("profile"))
    _, (res,), _, tr = runner._traced_window(prog, mix, log_dir)
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    events = [(ev.name, int(ev.start_ns), int(ev.end_ns), dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name in NAMES]
    return plain, res, tr, sorted(events, key=lambda e: e[1])


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_every_span_is_written_and_counts_match(traced):
    _, res, tr, events = traced
    count = {n: sum(1 for h in tr.host if h[0] == n) for n in NAMES}
    assert all(count[n] > 0 for n in NAMES), count
    assert count["serve.generate"] == 1
    assert count["serve.dispatch"] == count["serve.readback"] == res.steps
    assert count["serve.accept"] == res.steps
    assert count["serve.prefill"] == res.prefill_chunks
    assert count["serve.admit"] >= len(res.per_request)
    # the identifiers ride as the events' stats
    (gen,) = _named(events, "serve.generate")
    assert gen[3]["requests"] == len(res.per_request)
    assert {e[3]["rid"] for e in _named(events, "serve.admit")} == set(
        range(len(res.per_request)))
    last = [e for e in _named(events, "serve.prefill") if e[3]["last"]]
    assert len(last) == len(res.per_request)


def test_each_readback_follows_its_own_dispatch(traced):
    _, res, _, events = traced
    dispatch = _named(events, "serve.dispatch")
    readback = _named(events, "serve.readback")
    accept = _named(events, "serve.accept")
    assert [e[3]["step"] for e in dispatch] == list(range(res.steps))
    for d, r, a in zip(dispatch, readback, accept):
        assert d[3]["step"] == r[3]["step"] == a[3]["step"]
        assert d[2] <= r[1] and r[2] <= a[1]
        assert d[3]["active"] >= 1
    for nxt, a in zip(dispatch[1:], accept):
        assert a[2] <= nxt[1]


def test_stamps_and_spans_share_one_timeline(traced):
    """``admit_s`` precedes ``ttft_s``, and each request's first
    ``serve.admit`` span starts where its stamp says, measured from the
    ``serve.generate`` span's start."""
    _, res, _, events = traced
    (gen,) = _named(events, "serve.generate")
    first = {}
    for e in _named(events, "serve.admit"):
        first.setdefault(e[3]["rid"], e[1])
    for r in res.per_request:
        assert 0 <= r["admit_s"] <= r["ttft_s"] <= r["latency_s"]
        at = (first[r["rid"]] - gen[1]) / 1e9
        assert at == pytest.approx(r["admit_s"], abs=1e-3), r["rid"]


def test_tokens_do_not_depend_on_the_profiler(traced):
    plain, res, _, _ = traced
    assert res.tokens == plain.tokens
    assert res.steps == plain.steps


def test_readers_read_the_recorded_profile(traced):
    """Each of the engine's per-layer metrics finds its spans or
    counters in a real profile."""
    from bench.harness import spec
    from bench.harness.runner import Run

    _, res, tr, _ = traced
    run = Run(cell=None, dims=None, chips=1, peaks={}, results=[res],
              trace=tr)
    got = {n: spec.metric_reader(n)(run) for n in (
        "queue_wait_p90_s", "pool_blocked_share",
        "decode_stall_ms_per_step", "host_step_ms")}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["pool_blocked_share"] == 0.0  # the tiny pool holds them all
    assert got["host_step_ms"] > 0


MESH_SCRIPT = textwrap.dedent("""
    import json, re
    import tiny
    from bench.harness import spec
    from bench.harness.program import Program

    c = tiny.cell(True, mesh=[1, 4])
    prog = Program("t", c.config, 3)
    eng = prog.start()
    seen = {}

    def record(attr):
        fn = getattr(eng, attr)

        def call(*a, **k):
            seen.setdefault(attr, (fn, a, k))
            return fn(*a, **k)
        setattr(eng, attr, call)

    record("_decode_multi")
    record("_slot_chunk_paged")
    mix = spec.traffic_generator(c.traffic).make(c.traffic, 512, 3)
    prog.generate(*mix.call(0))
    out = {}
    for attr, (fn, a, k) in seen.items():
        text = fn.lower(*a, **k).compile().as_text()
        out[attr] = re.search(r"HloModule ([\\w.]+)", text)[1]
    print(json.dumps(out))
""")


def test_program_names_under_a_mesh():
    """The device-trace readers match programs by name: under a (1, 4)
    mesh the engine's decode and prefill programs keep the names they
    have on one chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [HERE, ROOT, os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"_decode_multi": "jit_decode_step_multi",
                   "_slot_chunk_paged": "jit_prefill_chunk_slot_paged"}
