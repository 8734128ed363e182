"""Observability layer (runtime/trace.py): span recording and nesting,
thread safety under a real ``PrefetchEngine`` worker pool, the
attribution-sums-to-wall invariant (property-tested where hypothesis is
installed), the disabled-tracer zero-allocation fast path, Chrome/Perfetto
export schema validity, and spans and the numbered step span on the
``jax.profiler`` clock."""
import json
import threading
import time
import tracemalloc

import numpy as np
import pytest

from repro.core.offload import HostArrayStore, PinnedBufferPool
from repro.core.schedule import PrefetchEngine, WorkingSetManager
from repro.runtime import trace
from repro.runtime.trace import (Tracer, attribute_events,
                                 flatten_attribution, format_report)
from repro.testing import optional_hypothesis

given, settings, st, HAVE_HYPOTHESIS = optional_hypothesis()


@pytest.fixture
def tracer():
    t = Tracer(capacity=1 << 12)
    t.enable()
    return t


# ---------------------------------------------------------------------------
# recording basics: nesting, args, instants, ring bounds
# ---------------------------------------------------------------------------


def test_span_records_nesting_and_args(tracer):
    with tracer.span("outer", sys="compute", attr="compute"):
        with tracer.span("inner", sys="store", cls="param",
                         attr="io_wait") as sp:
            sp.set(nbytes=128, wire_bytes=64)
    ev = tracer.events()
    assert [e[0] for e in ev] == ["inner", "outer"]  # inner exits first
    inner, outer = ev
    assert inner[1] == "store" and inner[2] == "param"
    assert inner[11] == {"nbytes": 128, "wire_bytes": 64}
    # the inner span nests strictly inside the outer's time window
    assert outer[5] <= inner[5] and inner[6] <= outer[6]
    # seq pairs are ordered: outer opens first, closes last
    assert outer[7] < inner[7] < inner[8] < outer[8]


def test_instant_and_span_names(tracer):
    tracer.instant("evict", sys="sched", cls="param", unit=3)
    with tracer.span("nvme_read", sys="store"):
        pass
    assert tracer.span_names() == {"evict": 1, "nvme_read": 1}
    assert tracer.subsystems() == ["sched", "store"]


def test_ring_buffer_bounds_memory():
    t = Tracer(capacity=16)
    t.enable()
    for i in range(100):
        with t.span(f"s{i}"):
            pass
    ev = t.events()
    assert len(ev) == 16
    assert ev[0][0] == "s84"  # oldest spans fell off


# ---------------------------------------------------------------------------
# thread safety: spans recorded from a real PrefetchEngine worker pool
# ---------------------------------------------------------------------------


class _SlowHostStore(HostArrayStore):
    """Reads take long enough that the executor grows past one worker."""

    def _read_sync(self, key):
        time.sleep(0.005)
        return super()._read_sync(key)


def test_threaded_spans_under_prefetch_engine(tracer, monkeypatch):
    monkeypatch.setattr(trace, "TRACER", tracer)
    store = _SlowHostStore(pool=PinnedBufferPool(8 << 20), workers=4)
    store.trace_cls = "param"
    rows = {u: np.full((256,), u, np.float32) for u in range(24)}
    for u, a in rows.items():
        store.write(u, a)
    store.flush()
    ws = WorkingSetManager()
    pe = PrefetchEngine(lambda u: [store.read(u)], ws, trace_cls="param")
    for u in rows:  # all reads in flight at once across the pool
        pe.prefetch(u)
    for u in rows:
        with tracer.span("consume", sys="compute", attr="compute", unit=u):
            (got,) = pe.materialize(u)
            np.testing.assert_array_equal(got, rows[u])
        pe.evict(u)
    ev = tracer.events()
    names = tracer.span_names()
    assert names["consume"] == 24 and names["materialize_wait"] == 24
    assert names["host_read"] == 24  # worker-side I/O spans all landed
    tids = {e[9] for e in ev if e[0] == "host_read"}
    assert len(tids) >= 2  # genuinely recorded from multiple workers
    # every record is a complete, well-formed tuple despite the concurrency
    for e in ev:
        assert len(e) == 12 and e[6] >= e[5] and e[8] >= e[7]


# ---------------------------------------------------------------------------
# attribution: fractions sum to 1, innermost-wait-wins, overlap accounting
# ---------------------------------------------------------------------------


def _rec(name, attr, a, b, tid, cls=None):
    return (name, None, cls, attr, None, a, b, 0, 1, tid, "t", {})


def test_attribution_partitions_wall_exactly():
    MAIN = 1
    events = [
        _rec("step", "compute", 0.0, 10.0, MAIN),
        _rec("wait_p", "io_wait", 2.0, 4.0, MAIN, cls="param"),
        _rec("wait_g", "io_wait", 3.0, 6.0, MAIN, cls="grad"),
        _rec("io", "io", 1.0, 7.0, 2, cls="param"),
    ]
    att = attribute_events(events, 0.0, 12.0, MAIN)
    assert att["wall_s"] == pytest.approx(12.0)
    # waits claim [2,6] total (innermost wins over compute); classes claim
    # in sorted order, so grad takes [3,6] and param keeps [2,3]; compute
    # keeps [0,2]+[6,10], other is the uninstrumented tail [10,12]
    assert att["io_wait_by_cls"]["grad"] == pytest.approx(3.0)
    assert att["io_wait_by_cls"]["param"] == pytest.approx(1.0)
    assert att["compute_s"] == pytest.approx(6.0)
    assert att["other_s"] == pytest.approx(2.0)
    assert att["attr_frac_sum"] == pytest.approx(1.0)
    # worker busy [1,7] overlaps the post-subtraction compute union [0,2]+[6,7]
    assert att["io_busy_by_cls"]["param"] == pytest.approx(6.0)
    assert att["io_overlapped_by_cls"]["param"] == pytest.approx(2.0)
    assert att["overlap_frac"] == pytest.approx(2.0 / 6.0)
    assert att["measured_efficiency"] == pytest.approx(6.0 / 10.0)


@settings(max_examples=60, deadline=None)
@given(st.lists(
    st.tuples(st.sampled_from(["compute", "io_wait"]),
              st.sampled_from(["param", "grad", "opt", None]),
              st.floats(0.0, 100.0), st.floats(0.001, 50.0)),
    min_size=0, max_size=40))
def test_attribution_sums_to_wall_property(spans):
    """For arbitrary (overlapping, nested, out-of-window) main-thread spans,
    compute_s + io_wait_s + other_s always equals the window wall time."""
    MAIN = 7
    events = [_rec(f"s{i}", attr, a, a + d, MAIN, cls=cls)
              for i, (attr, cls, a, d) in enumerate(spans)]
    att = attribute_events(events, 10.0, 60.0, MAIN)
    total = att["compute_s"] + att["io_wait_s"] + att["other_s"]
    assert total == pytest.approx(att["wall_s"], rel=1e-9, abs=1e-9)
    assert att["attr_frac_sum"] == pytest.approx(1.0, abs=1e-9)
    assert att["compute_s"] >= 0 and att["other_s"] >= 0
    assert all(v >= 0 for v in att["io_wait_by_cls"].values())
    assert sum(att["io_wait_by_cls"].values()) == \
        pytest.approx(att["io_wait_s"])
    assert 0.0 <= att["measured_efficiency"] <= 1.0 + 1e-9


def test_flatten_attribution_keys():
    att = attribute_events(
        [_rec("w", "io_wait", 1.0, 2.0, 1, cls="param")], 0.0, 4.0, 1)
    flat = flatten_attribution(att)
    assert flat["trace_wall_s"] == pytest.approx(4.0)
    assert flat["trace_io_wait_param_s"] == pytest.approx(1.0)
    assert flat["trace_attr_frac_sum"] == pytest.approx(1.0)


def test_format_report_measured_vs_predicted():
    att = attribute_events(
        [_rec("c", "compute", 0.0, 3.0, 1),
         _rec("w", "io_wait", 3.0, 4.0, 1, cls="param")], 0.0, 4.0, 1)
    rep = format_report([att], predictions={"efficiency": 0.9,
                                            "param_efficiency": 0.9})
    assert "measured : 0.750" in rep
    assert "predicted: 0.900" in rep
    assert "param" in rep
    assert "top stall sources" in rep


# ---------------------------------------------------------------------------
# disabled fast path: shared no-op singleton, no records, no net allocation
# ---------------------------------------------------------------------------


def test_disabled_span_is_shared_noop_singleton():
    t = Tracer()
    assert not t.enabled
    s1 = t.span("a", sys="store", nbytes=1)
    s2 = t.span("b", cls="param")
    assert s1 is s2 is trace._NOOP
    with s1 as sp:
        sp.set(nbytes=5)  # no-op, never raises
    t.instant("i", sys="sched")
    assert t.events() == []


def test_disabled_span_zero_net_allocation():
    t = Tracer()
    for _ in range(100):  # warm any caches before measuring
        with t.span("x"):
            pass
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(5000):
        with t.span("x", sys="store", cls="param", nbytes=4096):
            pass
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    growth = sum(s.size_diff for s in after.compare_to(before, "filename")
                 if "trace.py" in str(s.traceback))
    assert growth < 4096  # no per-span retention on the disabled path
    assert t.events() == []


# ---------------------------------------------------------------------------
# Chrome/Perfetto export: loads, matched B/E pairs, monotonic per track
# ---------------------------------------------------------------------------


def test_chrome_export_schema(tracer, tmp_path, monkeypatch):
    monkeypatch.setattr(trace, "TRACER", tracer)
    store = HostArrayStore(pool=PinnedBufferPool(4 << 20), workers=2)
    store.trace_cls = "param"
    for u in range(8):
        store.write(u, np.ones((64,), np.float32))
    store.flush()
    futs = [store.read(u) for u in range(8)]
    with tracer.span("step", sys="compute", attr="compute"):
        with tracer.span("wait", sys="sched", attr="io_wait", cls="param"):
            for f in futs:
                f.result()
    tracer.instant("evict", sys="sched", cls="param", unit=0)
    path = tmp_path / "trace.json"
    tracer.export_chrome(str(path))

    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert events, "export produced no events"
    open_stack = {}
    last_ts = {}
    for e in events:
        assert e["ph"] in ("B", "E", "i", "C", "M")
        if e["ph"] == "M":
            continue
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        key = (e["pid"], e.get("tid"))
        # ts never goes backwards within one track
        assert e["ts"] >= last_ts.get(key, 0.0) - 1e-6
        last_ts[key] = e["ts"]
        if e["ph"] == "B":
            open_stack.setdefault(key, []).append(e["name"])
        elif e["ph"] == "E":
            assert open_stack.get(key), f"E without B on track {key}"
            assert open_stack[key].pop() == e["name"]
    assert not any(v for v in open_stack.values()), "unmatched B events"
    # the wire-byte counter track accumulated the param reads/writes
    counters = [e for e in events if e["ph"] == "C"]
    assert counters and counters[-1]["name"] == "param_wire_bytes"
    assert counters[-1]["args"]["bytes"] >= 16 * 64 * 4
    # thread tracks are labelled
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)


def test_chrome_export_survives_ring_eviction(tmp_path):
    t = Tracer(capacity=8)
    t.enable()
    for i in range(50):
        with t.span(f"s{i}", sys="store"):
            pass
    path = tmp_path / "evicted.json"
    t.export_chrome(str(path))
    events = [e for e in json.loads(path.read_text())["traceEvents"]
              if e["ph"] in ("B", "E")]
    assert len(events) == 16  # 8 complete spans -> 8 matched B/E pairs
    assert sum(e["ph"] == "B" for e in events) == \
        sum(e["ph"] == "E" for e in events)


# ---------------------------------------------------------------------------
# the profiler's clock: spans and the step span in a jax.profiler trace
# ---------------------------------------------------------------------------


def _host_events(run, tmp_path) -> dict:
    """Host-plane events recorded while ``run()`` runs under a CPU
    ``jax.profiler`` session: name -> [stats dict, ...]."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


def test_enabled_spans_land_in_the_profiler_host_plane(tracer, tmp_path):
    def run():
        with tracer.span("nvme_read", sys="store", cls="param", unit=3):
            pass
        tracer.instant("evict", sys="sched")
        tracer.wrap("jit_piece", lambda: None)()
        worker = threading.Thread(
            target=lambda: tracer.span("worker_io", sys="store").__enter__()
            .__exit__(None, None, None))
        worker.start()
        worker.join(timeout=10)
        assert not worker.is_alive()

    events = _host_events(run, tmp_path)
    for name in ("nvme_read", "evict", "jit_piece", "worker_io"):
        assert name in events, sorted(events)
    stats = events["nvme_read"][0]
    assert (stats["sys"], stats["cls"], str(stats["unit"])) == (
        "store", "param", "3")
    # the ring buffer still records every span for attribution and export
    assert tracer.span_names() == {"nvme_read": 1, "evict": 1,
                                   "jit_piece": 1, "worker_io": 1}


def test_disabled_tracer_puts_nothing_in_the_profiler(tmp_path):
    t = Tracer()
    events = _host_events(lambda: t.span("quiet", sys="store").__enter__(),
                          tmp_path)
    assert "quiet" not in events


def test_step_fn_numbers_steps_and_keeps_lower(tmp_path, monkeypatch,
                                               tracer):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(trace, "TRACER", tracer)
    tracer.disable()
    jitted = jax.jit(lambda x: x + 1)
    step = trace.StepFn(jitted)
    x = jnp.zeros(4)

    def run():
        step(x)  # the Tracer is off: numbered, no step span
        tracer.enable()
        for _ in range(2):
            step(x)
        tracer.disable()
        step(x)

    events = _host_events(run, tmp_path)
    assert step.step_num == 4
    assert sorted(int(s["step_num"]) for s in events[trace.STEP_SPAN]) == [
        1, 2]
    assert "add" in step.lower(x).as_text()  # the jitted step's own lower


def test_executor_step_is_one_numbered_step_fn():
    import dataclasses

    import jax

    from repro import configs
    from repro.config import RunConfig, TrainConfig, make_offload, make_parallel
    from repro.core.executor import InfinityExecutor
    from repro.launch.mesh import make_local_mesh

    cfg = dataclasses.replace(configs.smoke("smollm-135m"), n_layers=1)
    run = RunConfig(model=cfg, parallel=make_parallel("pjit", remat="none"),
                    offload=make_offload(opt_tier="device"),
                    train=TrainConfig())
    ex = InfinityExecutor(run, make_local_mesh(1, 1))
    step = ex.make_train_step()
    assert isinstance(step, trace.StepFn) and ex.make_train_step() is step
    state = ex.init_state(jax.random.PRNGKey(0))
    toks = jax.numpy.zeros((2, 16), jax.numpy.int32)
    batch = {"tokens": toks, "labels": toks}
    state, metrics = step(state, batch)
    assert step.step_num == 1 and metrics["loss"].shape == ()
    assert "jit_train_step" in step.lower(state, batch).as_text()


# ---------------------------------------------------------------------------
# serving latency percentiles (satellite b helper)
# ---------------------------------------------------------------------------


def test_serve_percentiles_ordered_and_empty():
    from repro.launch.serve import _percentiles

    p = _percentiles([0.001 * i for i in range(1, 101)])
    assert p["p50"] <= p["p95"] <= p["p99"]
    assert p["p50"] == pytest.approx(0.0505, rel=1e-3)
    assert _percentiles([]) == {"p50": 0.0, "p95": 0.0, "p99": 0.0}
