"""The program's spans and counters (bench/program.py) and the readers of
them, on hand-made events, a CPU trace, real registries and a hand-built
run_view."""
import json
from pathlib import Path

import pytest

from bench import harness
from bench import program as P
from bench import trace as T

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
ns = 1e-9


def _events():
    host = [("bench.window", 0, 100), ("feeder.next", 20, 15)]
    dev0 = [("fusion.1", 0, 20), ("fusion.2", 40, 20), ("fusion.3", 90, 10)]
    return {"devices": {"/device:TPU:0": dev0}, "host": host}


def _program():
    # device 0 idles 20–40 and 60–90
    return [
        ("feed.fetch", 10, 40, "feeder", ""),          # covers 20–40 of idle
        ("feed.next", 20, 15, "main", ""),
        ("feed.next", 95, 10, "main", ""),            # starts in the window
        ("feed.next", 105, 1, "main", ""),            # after it: not counted
        ("worker.wait", 70, 10, "handler-1", ""),
        ("worker.wait", 75, 10, "handler-2", ""),     # overlaps the other thread's
        ("transport.recv", 30, 4, "fetcher", "get_elements"),
        ("transport.decode", 34, 2, "fetcher", "get_elements"),
        ("transport.recv", 50, 3, "handler-1", ""),   # a request: not a batch
        ("transport.recv", 98, 5, "fetcher", "get_element"),  # clipped at 100
    ]


def test_reduce_puts_idle_time_under_each_span_name_open_on_any_thread():
    r = P.reduce(_program(), _events())
    assert dict(r["program_gaps"]) == pytest.approx({
        "feed.fetch": 20 * ns, "feed.next": 15 * ns, "worker.wait": 15 * ns,
        "transport.recv": 4 * ns, "transport.decode": 2 * ns})
    assert [n for n, _ in r["program_gaps"]][0] == "feed.fetch"
    assert r["idle_fetch_s"] == pytest.approx(20 * ns)
    assert r["feed_next_s"] == pytest.approx([15 * ns, 10 * ns])
    assert r["transport_recv_s"] == pytest.approx((4 + 2 + 2) * ns)
    assert r["worker_wait_s"] == pytest.approx(20 * ns)


def test_reduce_of_a_program_without_spans_reads_nothing():
    r = P.reduce([], _events())
    assert r == {"spans": {}, "program_gaps": [], "idle_fetch_s": None, "feed_next_s": [],
                 "transport_recv_s": None, "worker_wait_s": None}


def test_reduce_leaves_the_recorded_v5e_slice_as_trace_reduce_reads_it():
    events = json.load(open(FIXTURES / "trace_lm_v5e.json"))
    before = T.reduce(events)
    r = P.reduce([], events)
    assert r["program_gaps"] == []
    assert T.reduce(events) == before
    assert before["idle_gaps"] == [["loss.wait", pytest.approx(7.266e-6)]]


def test_load_reads_the_program_spans_with_their_thread(tmp_path):
    import threading

    import jax

    from repro.obs.tracing import Tracer, annotate

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with tr.span("feed.next", None):
                pass
            t = threading.Thread(target=lambda: annotate("transport.recv", method="get_elements")
                                 .__enter__().__exit__(None, None, None))
            with annotate("transport.recv", method="get_elements"):
                t.start()
                t.join()
            with tr.span("feeder.next", None):  # the benchmark's own span
                pass
            with jax.profiler.TraceAnnotation("NotDotted"):
                pass
    finally:
        jax.profiler.stop_trace()
    got = P.load(T.profile(str(tmp_path)))
    assert sorted(n for n, *_ in got) == ["feed.next", "transport.recv", "transport.recv"]
    recv = [e for e in got if e[0] == "transport.recv"]
    assert {e[4] for e in recv} == {"get_elements"}
    assert len({e[3] for e in recv}) == 2  # two threads, two lines


class _FakeWorker:
    def __init__(self, snap):
        self.registry = type("R", (), {"snapshot": staticmethod(lambda: snap)})()


def test_counters_sum_served_batches_and_ring_misses_over_workers():
    a = _FakeWorker({"worker_batches_served": {"kind": "counter", "value": 10.0},
                     "worker_shm_inline_total": {"kind": "counter", "value": 0.0, "series": {
                         "reason=ring_full": 7.0, "reason=too_large": 1.0}}})
    b = _FakeWorker({"worker_batches_served": {"kind": "counter", "value": 5.0},
                     "worker_shm_inline_total": {"kind": "counter", "value": 0.0}})
    orch = type("O", (), {"workers": [a, b]})()
    got = P.counters(orch)
    assert got["worker_batches_served"] == 15.0
    assert got["worker_shm_inline_total{reason=ring_full}"] == 7.0
    assert got["worker_shm_inline_total{reason=too_large}"] == 1.0
    older = type("O", (), {"workers": [_FakeWorker(
        {"worker_batches_served": {"kind": "counter", "value": 3.0}})]})()
    got = P.counters(older)
    assert got["worker_batches_served"] == 3.0 and "worker_shm_inline_total" not in got


def test_the_five_readers_compute_from_a_run_view():
    run = {"window_s": 10.0, "steps": 30, "chips": 1,
           "counters": {"client_batches": 30, "worker_batches_served": 40,
                        "worker_shm_inline_total": 30.0,
                        "worker_shm_inline_total{reason=ring_full}": 30.0},
           "trace": {"window_s": 10.0, "busy_s": 3.0},
           "program": {"feed_next_s": [0.001] * 9 + [0.5], "idle_fetch_s": 6.5,
                       "transport_recv_s": 3.0, "worker_wait_s": 0.2}}
    read = lambda name, r=run: harness.load_reader(ROOT, name)(r)  # noqa: E731
    assert read("feed.next_ms_p90") == pytest.approx(1.0)
    assert read("device.idle_fetch_share") == pytest.approx(65.0)
    assert read("transport.recv_ms_per_batch") == pytest.approx(100.0)
    assert read("worker.wait_ms_per_batch") == pytest.approx(5.0)
    assert read("worker.shm_ring_full_share") == pytest.approx(75.0)
    zero = dict(run, program={"feed_next_s": [0.0], "idle_fetch_s": 0.0,
                              "transport_recv_s": 0.0, "worker_wait_s": 0.0},
                counters=dict(run["counters"], **{"worker_shm_inline_total{reason=ring_full}": 0}))
    for name in ("feed.next_ms_p90", "device.idle_fetch_share", "transport.recv_ms_per_batch",
                 "worker.wait_ms_per_batch", "worker.shm_ring_full_share"):
        assert read(name, zero) == 0.0
    # a program without the spans and counters (one older than them)
    older = dict(run, program={"feed_next_s": [], "idle_fetch_s": None,
                               "transport_recv_s": None, "worker_wait_s": None},
                 counters={"client_batches": 30, "worker_batches_served": 40})
    for name in ("feed.next_ms_p90", "device.idle_fetch_share", "transport.recv_ms_per_batch",
                 "worker.wait_ms_per_batch", "worker.shm_ring_full_share"):
        assert read(name, older) is None
        assert read(name, {"counters": {"client_batches": 0}, "trace": None}) is None


def _program_with(name):
    return _program() + [(name, 22, 10, "router", ""), (name, 64, 4, "router", ""),
                         (name, 99, 8, "router", "")]


def test_reduce_gives_every_span_name_its_count_total_p90_and_idle():
    r = P.reduce(_program_with("moe.route"), _events())
    s = r["spans"]["moe.route"]
    assert s["count"] == 3
    assert s["total_s"] == pytest.approx((10 + 4 + 1) * ns)  # the last one clipped at 100
    assert s["p90_s"] == pytest.approx(10 * ns)
    assert s["idle_s"] == pytest.approx((10 + 4) * ns)  # 22–32 and 64–68 lie in gaps
    assert r["spans"]["feed.next"]["count"] == 2  # the third starts after the window
    assert dict(r["program_gaps"])["moe.route"] == pytest.approx(14 * ns)


def test_a_span_the_code_never_named_reaches_a_reader_file(tiny_root):
    (tiny_root / "bench" / "metrics" / "moe.route_ms_per_step.py").write_text(
        "def read(run):\n"
        "    s = run['program']['spans'].get('moe.route')\n"
        "    return None if s is None else 1e3 * s['total_s'] / run['steps']\n")
    view = {"steps": 3, "program": P.reduce(_program_with("moe.route"), _events())}
    read = harness.load_reader(tiny_root, "moe.route_ms_per_step")
    assert read(view) == pytest.approx(1e3 * 15 * ns / 3)
    assert read({"steps": 3, "program": P.reduce(_program(), _events())}) is None


@pytest.mark.parametrize("name, kept", [
    ("feed.next", True), ("executor.copy_out", True), ("moe.route", True), ("a.b.c", True),
    ("bench.window", False), ("feeder.next", False), ("step.dispatch", False),
    ("loss.wait", False), ("$profiler.py:101 start_trace", False), ("PjitFunction(f)", False),
    ("ThreadpoolListener::Record", False), ("dot_general.1", False), ("end: fusion.2", False),
    ("feed", False), ("Feed.Next", False)])
def test_the_span_rule_keeps_the_program_names_only(name, kept):
    assert P.is_program_span(name) is kept


def test_every_counter_family_and_labelled_series_reaches_the_window_delta():
    from repro.obs.registry import MetricsRegistry, get_registry

    regs = [MetricsRegistry(), MetricsRegistry()]
    orch = type("O", (), {"workers": [type("W", (), {"registry": r})() for r in regs]})()
    fam = "bench_test_moe_tokens_total"
    before = P.counters(orch)
    for r in regs:
        r.counter(fam).add(3)
        r.counter("executor_pipe_elements_total").labels(reason="too_large").add(2)
        r.histogram("bench_test_wait_seconds").observe(0.5)
    get_registry().counter("bench_test_default_total").labels(kind="x").add(4)
    delta = harness.window_delta(before, P.counters(orch))
    assert delta[fam] == 6
    assert delta["executor_pipe_elements_total{reason=too_large}"] == 4
    assert delta["bench_test_wait_seconds_count"] == 2
    assert delta["bench_test_wait_seconds_sum"] == pytest.approx(1.0)
    assert delta["bench_test_default_total{kind=x}"] == 4
    assert delta["worker_shm_inline_total{reason=ring_full}"] is None  # these workers do not count it
    assert delta["no_such_counter_total"] is None
