"""repro.obs: metrics registry math, dispatch tracing from the real
tier choosers, span profiling / Chrome-trace export, the engine's spans
on a profiler trace and its wait/flush/device counters, the retrace
alarm on the serving engine, and the disabled-mode zero-overhead
contract.

Everything here is host-side or tiny (the dispatchers run without
launching a kernel; most engine tests stub ``_execute``, the rest serve
96-bit moduli on the jnp backend), so the module adds seconds, not
minutes, to tier 1."""
import dataclasses
import json
import pathlib
import random
import time
import warnings

import jax
import numpy as np
import pytest

from repro import api, obs
from repro.configs.dot_bignum import ServeConfig, pick_modexp_window
from repro.core.div import select_div_method
from repro.core.modular import select_modexp_backend
from repro.core.mul import select_method
from repro.obs import metrics as M
from repro.obs import retrace as RT
from repro.serve import bignum_engine as BE

PY = random.Random(7)


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with empty buffers and obs off."""
    obs.reset()
    yield
    obs.reset()
    obs.disable()


def _observing():
    return api.configure(observability=True)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_series_and_total():
    c = M.Counter("c")
    c.inc(op="mul", choice="ntt")
    c.inc(2, op="mul", choice="dot")
    c.inc(op="div", choice="recip")
    assert c.value(op="mul", choice="ntt") == 1
    assert c.value(op="mul", choice="dot") == 2
    assert c.value(op="mul") == 0            # exact label set, not filter
    assert c.total(op="mul") == 3            # filter sums matching series
    assert c.total() == 4
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1)


def test_gauge_last_write_wins():
    g = M.Gauge("g")
    assert g.value(q="depth") is None
    g.set(3, q="depth")
    g.set(1, q="depth")
    assert g.value(q="depth") == 1


def test_histogram_quantiles_uniform_stream():
    # 1..100 into unit-width buckets: interpolation is exact for every
    # percentile of a uniform stream (within one bucket width)
    h = M.Histogram("h", bounds=tuple(float(b) for b in range(1, 101)))
    for v in range(1, 101):
        h.observe(v)
    assert h.count() == 100
    assert h.quantile(0.0) == 1.0            # clamped to observed min
    assert h.quantile(1.0) == 100.0          # clamped to observed max
    for q in (0.25, 0.5, 0.9, 0.95, 0.99):
        got = h.quantile(q)
        want = float(np.percentile(np.arange(1, 101), q * 100))
        assert abs(got - want) <= 1.0, (q, got, want)


def test_histogram_single_value_stream_is_exact():
    h = M.Histogram("h1")                    # default latency bounds
    for _ in range(5):
        h.observe(0.003)
    for q in (0.5, 0.95, 0.99):
        assert h.quantile(q) == pytest.approx(0.003)
    snap = h.snapshot()[""]
    assert snap["count"] == 5
    assert snap["p99"] == pytest.approx(0.003)
    assert snap["min"] == snap["max"] == pytest.approx(0.003)


def test_histogram_overflow_bucket():
    h = M.Histogram("h2", bounds=(1.0, 2.0))
    h.observe(0.5)
    h.observe(1000.0)                        # overflow bucket
    assert h.count() == 2
    assert h.quantile(1.0) == 1000.0
    # interpolated within the owning bucket, clamped to observed range
    assert 0.5 <= h.quantile(0.25) <= 1.0


def test_histogram_empty_and_bad_args():
    h = M.Histogram("h3")
    assert h.quantile(0.5) is None
    with pytest.raises(ValueError, match="in \\[0, 1\\]"):
        h.quantile(1.5)
    with pytest.raises(ValueError, match="ascending"):
        M.Histogram("bad", bounds=(2.0, 1.0))


def test_registry_get_or_create_and_kind_mismatch():
    r = M.Registry()
    c1 = r.counter("x")
    assert r.counter("x") is c1
    with pytest.raises(TypeError, match="already registered"):
        r.gauge("x")
    c1.inc(a=1)
    r.histogram("lat").observe(0.01, op="t")
    snap = r.snapshot()
    assert snap["counters"]["x"] == {"a=1": 1}
    assert snap["histograms"]["lat"]["op=t"]["count"] == 1
    json.dumps(snap)                         # JSON-serializable contract
    r.reset()
    assert r.get("x") is None


# ---------------------------------------------------------------------------
# dispatch tracing (through the REAL dispatchers)
# ---------------------------------------------------------------------------

def test_dispatch_events_from_all_choosers():
    with _observing():
        assert select_method(8192, batch=8) == "ntt"
        assert select_div_method(256, 256, batch=8) == "schoolbook"
        assert select_modexp_backend(256, batch=8, ebits=64) == "pallas"
        pick_modexp_window(17)
    by_disp = {e.dispatcher: e for e in obs.dispatch_events()}
    assert set(by_disp) == {"mul", "div", "modexp", "modexp_window"}
    ev = by_disp["mul"]
    assert (ev.nbits, ev.batch, ev.choice) == (8192, 8, "ntt")
    assert ev.rule == "ntt_min_bits"         # WHICH threshold fired
    assert dict(by_disp["modexp"].detail)["ebits"] == 64
    assert by_disp["modexp_window"].choice == "2"   # e=65537 -> w=2
    # the dispatch_total counter ticked one series per decision
    c = obs.REGISTRY.get("dispatch_total")
    assert c.value(dispatcher="mul", choice="ntt") == 1
    assert c.total() == 4


def test_dispatch_override_rule_is_visible():
    with api.configure(mul_method="karatsuba", observability=True):
        assert select_method(64, batch=1) == "karatsuba"
    (ev,) = obs.dispatch_events("mul")
    assert ev.rule == "override"


def test_dispatch_report_aggregates_and_formats():
    with _observing():
        for _ in range(3):
            select_method(1024, batch=16)
    rows = api.dispatch_report()
    (row,) = [r for r in rows if r["dispatcher"] == "mul"]
    assert row["count"] == 3 and row["choice"] == "pallas_kara"
    text = "\n".join(obs.format_report())
    assert "[mul]" in text and "pallas_kara" in text and "x3" in text


def test_trace_subscribe_and_capacity():
    seen = []
    unsub = obs.subscribe(seen.append)
    try:
        with _observing():
            select_method(64, batch=1)
        assert len(seen) == 1 and seen[0].dispatcher == "mul"
    finally:
        unsub()
    with _observing():
        for _ in range(obs.trace.CAPACITY + 5):
            select_method(64, batch=1)
    assert len(obs.dispatch_events()) == obs.trace.CAPACITY  # ring bounded


def test_disabled_mode_no_events_no_metrics():
    # observability off (the default): dispatchers answer normally but
    # allocate NO events and tick NO metrics -- the near-zero-cost path
    assert not obs.enabled()
    assert select_method(8192, batch=8) == "ntt"
    select_div_method(256, 256, batch=8)
    select_modexp_backend(256, batch=8, ebits=64)
    pick_modexp_window(17)
    assert obs.dispatch_events() == []
    assert obs.spans.spans() == []
    with obs.span("nothing", cat="execute"):
        pass
    assert obs.spans.spans() == []
    assert obs.REGISTRY.names() == []        # registry untouched


# ---------------------------------------------------------------------------
# spans / Chrome trace
# ---------------------------------------------------------------------------

def test_span_records_and_chrome_trace(tmp_path):
    with _observing():
        with obs.span("compile", cat="trace", bits=256):
            pass
        with obs.span("exec", batch=4) as args:
            time.sleep(0.02)
        with obs.span("late") as late:
            late["compiled"] = True          # added by the body: "trace"
        with pytest.raises(ValueError, match="choose from"):
            with obs.span("bad", cat="nope"):
                pass
    assert args == {"batch": 4}
    spans = obs.spans.spans()
    assert [s["cat"] for s in spans] == ["trace", "execute", "trace"]
    assert spans[2]["args"] == {"compiled": True}
    path = obs.write_chrome_trace(str(tmp_path / "t.json"))
    doc = json.loads(open(path).read())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == 3
    for e in evs:
        assert e["ph"] == "X" and {"name", "cat", "ts", "dur",
                                   "pid", "tid"} <= set(e)
    # categories land on distinct tids so the viewer separates them
    assert {e["tid"] for e in evs} == {1, 2}
    assert 0.02e6 <= evs[1]["dur"] < 1e6             # microseconds
    # timestamps are the profiler's clock (time.time_ns), in us
    assert abs(evs[2]["ts"] - time.time_ns() / 1e3) < 60e6


# ---------------------------------------------------------------------------
# serving engine: flush metrics + the retrace alarm
# ---------------------------------------------------------------------------

def _odd(bits):
    return PY.getrandbits(bits) | 1 | (1 << (bits - 1))


def _req(rid, n, e=65537):
    return BE.BignumRequest(rid=rid, op="mod_exp",
                            value=api.to_limbs(2, n.bit_length()),
                            modulus=n, exponent=e)


SMALL = ServeConfig(bucket_bits=(96, 160), exp_bucket_bits=(16, 32, 64),
                    slots=4, max_wait_s=0.02)


def _stub_engine():
    eng = BE.BignumEngine(SMALL)
    eng._execute = lambda bkey, reqs: np.zeros((SMALL.slots, 5), np.uint32)
    return eng


def test_engine_flush_populates_latency_histogram():
    eng = _stub_engine()
    n = _odd(90)
    with _observing():
        done = []
        for i in range(SMALL.slots):
            done += eng.submit(_req(i, n), now=0.001 * i)
    assert len(done) == SMALL.slots          # full flush
    h = obs.REGISTRY.get("serve_request_latency_seconds")
    assert h.count(op="mod_exp", bits=96) == SMALL.slots
    # every latency >= its queue wait; oldest request waited longest
    assert h.quantile(1.0, op="mod_exp", bits=96) >= 0.003
    st = eng.stats
    assert (st.served, st.batches, st.flush_full) == (SMALL.slots, 1, 1)
    assert eng.pending() == 0
    names = [sp["name"] for sp in obs.spans.spans()]
    assert names.count("serve.submit") == SMALL.slots
    (sp,) = [sp for sp in obs.spans.spans() if sp["name"] == "serve.flush"]
    assert sp["args"] == {"flush": 1, "op": "mod_exp", "bits": 96,
                          "lanes": SMALL.slots, "reason": "full",
                          "rids": "0 1 2 3"}


def test_engine_padded_lanes_and_deadline_reason():
    eng = _stub_engine()
    n = _odd(90)
    with _observing():
        assert eng.submit(_req(0, n), now=0.0) == []
        done = eng.flush_next_due(now=1.0)
    assert len(done) == 1
    assert eng.stats.padded_lanes == SMALL.slots - 1
    assert (eng.stats.flush_deadline, eng.stats.flush_full) == (1, 0)
    (sp,) = [sp for sp in obs.spans.spans() if sp["name"] == "serve.flush"]
    assert sp["args"]["reason"] == "deadline"


def test_engine_disabled_mode_serves_without_metrics():
    eng = _stub_engine()
    n = _odd(90)
    done = []
    for i in range(SMALL.slots):
        done += eng.submit(_req(i, n), now=0.0)
    assert len(done) == SMALL.slots
    assert obs.REGISTRY.names() == []
    assert obs.spans.spans() == []
    assert eng.stats.served == SMALL.slots   # EngineStats still tick


def test_retrace_alarm_on_new_shape_after_warm():
    # real jit bodies (jnp backend, tiny widths): warming one bucket
    # then serving a DIFFERENT bucket forces a fresh trace -> alarm
    eng = BE.BignumEngine(SMALL, backend="jnp")
    n_small, n_big = _odd(90), _odd(150)
    eng.warm("mod_exp", modulus=n_small, exponent=65537)
    assert eng._warmed and RT.count("serve") == 0
    before = RT.count()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for i in range(SMALL.slots):         # new 160-bit bucket: traces
            eng.submit(_req(i, n_big), now=0.0)
    assert RT.count("serve") - before == 1
    assert RT.count("serve", op="mod_exp", bits=160) == 1
    assert any(isinstance(x.message, obs.RetraceWarning) for x in w)
    # the warmed bucket itself replays silently (jit cache hit)
    before = RT.count()
    with warnings.catch_warnings():
        warnings.simplefilter("error", obs.RetraceWarning)
        for i in range(SMALL.slots):
            eng.submit(_req(10 + i, n_small), now=0.0)
    assert RT.count() == before


def test_retrace_policy_raise_and_ignore():
    eng = _stub_engine()
    eng._warmed = True
    with api.configure(on_retrace="raise"):
        with pytest.raises(obs.RetraceAlarm, match="zero-retrace"):
            eng._on_trace("mod_exp", 96)
    with api.configure(on_retrace="ignore"):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            eng._on_trace("mod_exp", 96)     # counts, stays silent
    assert RT.count("serve") == 2            # metric ticks regardless
    with pytest.raises(ValueError, match="on_retrace"):
        api.configure(on_retrace="panic")


def test_multiple_warms_do_not_false_alarm():
    eng = BE.BignumEngine(SMALL, backend="jnp")
    with warnings.catch_warnings():
        warnings.simplefilter("error", obs.RetraceWarning)
        eng.warm("mod_exp", modulus=_odd(90), exponent=65537)
        eng.warm("mod_exp", modulus=_odd(150), exponent=65537)
    assert RT.count("serve") == 0


# ---------------------------------------------------------------------------
# serving engine: spans on a profiler trace, and its own wait counters
# ---------------------------------------------------------------------------

FLUSH_CHILDREN = ("serve.assemble", "serve.dispatch", "serve.device_wait",
                  "serve.unpack")


def _profiled(tmp_path, body):
    """Run ``body`` under a CPU profiler trace; its host events by name
    (absolute ns on the profiler's clock) and the buffered spans."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    (env,) = [p for p in data.planes if p.name == "Task Environment"]
    t0 = dict(env.stats)["profile_start_time"]
    events = {}
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(
                    (t0 + ev.start_ns, t0 + ev.start_ns + ev.duration_ns,
                     dict(ev.stats)))
    return events


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_engine_spans_on_profiler_with_observability_off(tmp_path):
    eng = BE.BignumEngine(SMALL, backend="jnp")
    n = _odd(90)
    eng.warm("mod_exp", modulus=n, exponent=65537)
    assert not obs.enabled()
    done = []
    events = _profiled(tmp_path, lambda: [
        done.extend(eng.submit(_req(i, n), now=0.0))
        for i in range(SMALL.slots)])
    assert len(done) == SMALL.slots
    assert obs.spans.spans() == []               # buffer stays off
    # the bench. prefix is the chip benchmark's own annotations: its
    # idle-gap attribution must never read one of the program's spans
    assert not [n for n in events if n.startswith("bench.")]
    submits = events["serve.submit"]
    assert sorted(s[2]["rid"] for s in submits) == list(range(SMALL.slots))
    (flush,) = events["serve.flush"]
    assert flush[2]["flush"] == 1 and flush[2]["lanes"] == SMALL.slots
    assert flush[2]["rids"] == "0 1 2 3"
    assert "compiled" not in flush[2]            # warmed: a jit cache hit
    # the full flush runs inside the submit that filled the batch
    assert _inside(flush, max(submits))
    spans = [flush]
    for name in FLUSH_CHILDREN:
        (child,) = events[name]
        assert _inside(child, flush), name
        spans.append(child)
    # the children follow one another in order
    for a, b in zip(spans[1:], spans[2:]):
        assert a[1] <= b[0]


def test_buffered_span_starts_with_its_annotation(tmp_path):
    buffered = []

    def body():
        with _observing():
            with obs.span("probe"):
                time.sleep(0.005)
        buffered.extend(obs.spans.spans())

    events = _profiled(tmp_path, body)
    (ann,) = events["probe"]
    (sp,) = buffered
    assert abs(sp["ts"] * 1e3 - ann[0]) < 1e6   # ns: within 1 ms
    assert abs(sp["dur"] * 1e3 - (ann[1] - ann[0])) < 1e6


def test_engine_counters_wait_flush_device():
    eng = BE.BignumEngine(SMALL, backend="jnp")
    n = _odd(90)
    eng.warm("mod_exp", modulus=n, exponent=65537)
    warm = dataclasses.replace(eng.stats)
    assert warm.flush_s == warm.wait_s == 0.0
    assert warm.device_wait_s > 0                # the warm-up's run counts
    for i in range(SMALL.slots):
        eng.submit(_req(i, n), now=123.0)       # a caller's virtual clock
    st = eng.stats
    device_wait_s = st.device_wait_s - warm.device_wait_s
    assert st.batches == 1
    assert st.wait_s >= 0 and st.flush_s > 0
    assert 0 < device_wait_s <= st.flush_s
    # the engine's own clock: the callers' now never enters the counters
    assert st.wait_s < 60 and st.flush_s < 60
    assert all(isinstance(v, (int, float))
               for v in dataclasses.asdict(st).values())


def test_deadline_flush_adds_the_wait_to_wait_s():
    eng = _stub_engine()
    n = _odd(90)
    lanes = 2
    for i in range(lanes):
        eng.submit(_req(i, n), now=0.0)
    time.sleep(SMALL.max_wait_s)
    done = eng.flush_next_due(now=1.0)
    assert len(done) == lanes
    assert eng.stats.wait_s >= SMALL.max_wait_s * lanes
    assert eng.stats.device_wait_s == 0.0 <= eng.stats.flush_s


# ---------------------------------------------------------------------------
# facade
# ---------------------------------------------------------------------------

def test_api_metrics_shape_and_cache_stats_ctx():
    snap = api.metrics()
    assert set(snap) >= {"counters", "gauges", "histograms", "caches"}
    ctx = snap["caches"]["ctx"]
    assert set(ctx) == {"mont_setup", "barrett_setup"}
    for c in ctx.values():
        assert {"hits", "misses", "entries", "capacity"} <= set(c)
    json.dumps(snap, default=str)
    # mont_setup memoization is visible through the ctx counters
    n = _odd(90)
    h0 = api.cache_stats()["ctx"]["mont_setup"]
    api.mod_setup(n, 96)
    api.mod_setup(n, 96)
    h1 = api.cache_stats()["ctx"]["mont_setup"]
    assert h1["misses"] == h0["misses"] + 1
    assert h1["hits"] >= h0["hits"] + 1


def test_configure_observability_validation():
    with pytest.raises(ValueError, match="observability"):
        api.configure(observability="yes")
    with api.configure(observability=True):
        assert obs.enabled()
    assert not obs.enabled()                 # context form restores
