"""ISSUE 15: the SLO observability plane — first-class Histograms,
per-request serving traces, and fleet-wide telemetry aggregation with
straggler detection.

Three rings, each gated here:

  * Histogram — log-spaced mergeable distributions beside the int
    counters: quantile() agrees with the sorted-list convention it
    replaced (within one bucket), snapshots are never torn under
    N-thread fire, merges are associative across JSON round-trips
    (the cross-process/fleet contract), and the Prometheus exposition
    round-trip parses back to the same buckets.
  * Per-request traces — a trace_id minted at intake and threaded
    through admit/prefill/every-decode/evict/export/import/finish;
    the acceptance gate replays a chaos-killed replica's request on a
    survivor TOKEN-IDENTICALLY with the SAME trace_id and an
    export->import->replay timeline. Disarmed tracing leaves ZERO
    counters (the PR-9/12 bench-provenance contract) and stays inside
    the PR-3 per-event budget.
  * Fleet — merge_records sums counters, keeps gauges per-rank,
    bucket-merges histograms; `python -m paddle_tpu.monitor fleet`
    over >=2 synthetic rank spools flags a seeded straggler with its
    top flight spans; fleet_snapshot() single-process returns a
    one-rank view.

Plus the VLOG rank-prefix satellite: single-rank output byte-format
unchanged, multi-rank prefixed `V<level> r<rank> HH:MM:SS]`.
"""
import json
import math
import os
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import monitor as cmon
from paddle_tpu.core.monitor import Histogram, snapshot_quantile
from paddle_tpu import monitor as pmon
from paddle_tpu.inference.serving import (LLMEngine, Router,
                                          SamplingParams)
from paddle_tpu.monitor import chaos
from paddle_tpu.monitor import cli as mcli
from paddle_tpu.monitor import fleet as mfleet
from paddle_tpu.monitor import trace as mtrace
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_TOKENS = 5
PROMPT_LENS = (3, 9, 5, 12)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, ffn_hidden=128, max_seq_len=64,
                    dropout=0.0, use_flash_attention=False,
                    initializer_range=0.35)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(3)
    return [list(rng.randint(1, 128, n)) for n in PROMPT_LENS]


@pytest.fixture(scope="module")
def want(model, prompts):
    """Fault-free single-replica reference the failover trace test
    must reproduce token-for-token."""
    eng = LLMEngine(model, max_batch=4, block_size=8, num_blocks=32)
    outs = eng.generate(prompts, sampling=sp())
    assert eng.check_drained() == {}
    return outs


def sp(**kw):
    kw.setdefault("max_new_tokens", N_TOKENS)
    return SamplingParams(**kw)


def stages(req):
    return [ev["stage"] for ev in req.trace]


# ---------------------------------------------------------------------------
# ring (a): Histogram
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_observe_count_sum_min_max(self):
        h = Histogram("t")
        for v in (3.0, 700.0, 12.5):
            h.observe(v)
        s = h.snapshot()
        assert s["count"] == 3 and h.count == 3
        assert s["sum"] == pytest.approx(715.5)
        assert s["min"] == 3.0 and s["max"] == 700.0
        assert sum(s["buckets"].values()) == 3

    def test_quantile_matches_sorted_list(self):
        rng = np.random.RandomState(7)
        vals = rng.lognormal(8, 1.5, 4000).tolist()
        h = Histogram("q")
        for v in vals:
            h.observe(v)
        sv = sorted(vals)
        ratio = 10.0 ** (1.0 / h.per_decade)  # one bucket's width
        for q in (0.0, 0.5, 0.9, 0.95, 0.99, 1.0):
            exact = sv[min(len(sv) - 1, int(len(sv) * q))]
            approx = h.quantile(q)
            assert exact / ratio <= approx <= exact * ratio, (
                q, exact, approx)

    def test_quantile_clamped_to_observed_range(self):
        h = Histogram("c")
        h.observe(42.0)
        assert h.quantile(0.0) == 42.0
        assert h.quantile(1.0) == 42.0

    def test_empty_and_underflow(self):
        h = Histogram("e")
        assert h.quantile(0.5) == 0.0
        assert h.snapshot()["min"] is None
        h.observe(0.0)          # <= lo, negative-infinity-safe bin
        h.observe(-3.0)
        s = h.snapshot()
        assert s["buckets"].get(0) == 2   # underflow bucket
        assert h.quantile(0.5) == -3.0    # clamped to observed min

    def test_bucket_edges_halfopen(self):
        """(lower, upper] contract: a value lands in a bucket whose
        upper edge is >= it and whose lower edge is < it (modulo
        the one-ulp log10 slack the implementation documents)."""
        h = Histogram("edges", lo=1.0, per_decade=20, decades=6)
        vals = [1.0001, 9.99, 10.0, 123.0, 1e5]
        for v in vals:
            h.observe(v)
        for v in vals:
            idx = h._index(v)
            assert 1 <= idx <= h._nb
            assert h._edge(idx) >= v * (1 - 1e-12)
            assert h._edge(idx - 1) < v * (1 + 1e-9)

    def test_merge_associative_across_json(self):
        """(a + b) + c == a + (b + c), bucket-for-bucket, with every
        operand JSON round-tripped — the exact path fleet merge
        takes over per-rank exporter spools."""
        rng = np.random.RandomState(11)
        snaps = []
        for i in range(3):
            h = Histogram(f"m{i}")
            for v in rng.lognormal(6 + i, 1.0, 500):
                h.observe(float(v))
            snaps.append(json.loads(json.dumps(h.snapshot())))
        left = Histogram("l")
        left.merge(snaps[0])
        left.merge(snaps[1])
        left.merge(snaps[2])
        bc = Histogram("bc")
        bc.merge(snaps[1])
        bc.merge(snaps[2])
        right = Histogram("r")
        right.merge(snaps[0])
        right.merge(json.loads(json.dumps(bc.snapshot())))
        ls, rs = left.snapshot(), right.snapshot()
        assert ls["buckets"] == rs["buckets"]
        assert ls["count"] == rs["count"] == 1500
        assert ls["sum"] == pytest.approx(rs["sum"])
        assert ls["min"] == rs["min"] and ls["max"] == rs["max"]

    def test_merge_mismatched_boundaries_raises(self):
        a = Histogram("a", per_decade=20)
        b = Histogram("b", per_decade=10)
        b.observe(5.0)
        with pytest.raises(ValueError, match="boundaries"):
            a.merge(b)
        with pytest.raises(ValueError, match="boundaries"):
            a.merge(b.snapshot())

    def test_concurrent_observers_snapshot_never_torn(self):
        """N threads observing while the main thread snapshots: no
        snapshot may show sum(buckets) != count (a torn view), and
        the final count is exact."""
        h = Histogram("torn")
        n_threads, per_thread = 8, 2000
        start = threading.Event()

        def worker(seed):
            rng = np.random.RandomState(seed)
            vals = rng.lognormal(5, 2.0, per_thread)
            start.wait()
            for v in vals:
                h.observe(float(v))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        start.set()
        torn = []
        while any(t.is_alive() for t in threads):
            s = h.snapshot()
            if sum(s["buckets"].values()) != s["count"]:
                torn.append(s["count"])
            _ = h.quantile(0.5)      # reader under fire
        for t in threads:
            t.join()
        assert torn == []
        assert h.count == n_threads * per_thread
        s = h.snapshot()
        assert sum(s["buckets"].values()) == s["count"]

    def test_reset_and_env_config(self, monkeypatch):
        h = Histogram("r")
        h.observe(9.0)
        h.reset()
        assert h.count == 0 and h.snapshot()["buckets"] == {}
        monkeypatch.setenv("PADDLE_MONITOR_HIST_PER_DECADE", "5")
        monkeypatch.setenv("PADDLE_MONITOR_HIST_DECADES", "3")
        monkeypatch.setenv("PADDLE_MONITOR_HIST_LO", "10")
        h2 = Histogram("env")
        assert (h2.lo, h2.per_decade, h2.decades) == (10.0, 5, 3)

    def test_lo_must_be_positive(self):
        with pytest.raises(ValueError, match="lo"):
            Histogram("bad", lo=0.0)

    def test_snapshot_quantile_offline_flavor(self):
        h = Histogram("off")
        for v in (10, 100, 1000, 10000):
            h.observe(v)
        snap = json.loads(json.dumps(h.snapshot()))
        for q in (0.5, 0.99):
            assert snapshot_quantile(snap, q) == pytest.approx(
                h.quantile(q))


# ---------------------------------------------------------------------------
# registry + exporter carriage
# ---------------------------------------------------------------------------

class TestRegistryAndExporter:
    def test_registry_get_or_create_and_reset_all(self):
        h1 = cmon.hist_get("reg/hist/x_us")
        h1.observe(5.0)
        assert cmon.hist_get("reg/hist/x_us") is h1
        cmon.hist_observe("reg/hist/x_us", 7.0)
        assert h1.count >= 2
        cmon.registry.reset_all()
        assert h1.count == 0

    def test_telemetry_snapshot_carries_hists(self):
        cmon.hist_observe("snap/hist/y_us", 123.0)
        snap = pmon.telemetry_snapshot()
        assert "snap/hist/y_us" in snap["hists"]
        s = snap["hists"]["snap/hist/y_us"]
        assert s["count"] >= 1 and "buckets" in s
        # the flat int-stat map is UNCHANGED in shape — histograms
        # never leak into it
        assert all(isinstance(v, (int, float))
                   for v in snap["stats"].values())

    def test_jsonl_exporter_carries_hists(self, tmp_path):
        cmon.hist_observe("exp/hist/z_us", 55.0)
        path = tmp_path / "metrics.jsonl"
        pmon.MetricsExporter(str(path), interval=3600).flush()
        rec = json.loads(path.read_text().strip().splitlines()[-1])
        assert "exp/hist/z_us" in rec["hists"]

    def test_prometheus_histogram_roundtrip(self, tmp_path):
        """The acceptance gate: >= 4 histogram series (serving
        ITL/TTFT/queue-wait + jit compile) exposed as Prometheus
        `_bucket`/`_sum`/`_count` and parsed BACK to the exact
        per-bucket counts the registry holds."""
        cmon.registry.reset_all()
        rng = np.random.RandomState(5)
        series = {
            "serve/hist/itl_us": rng.lognormal(9, 1, 300),
            "serve/hist/ttft_us": rng.lognormal(11, 0.8, 40),
            "serve/hist/queue_wait_us": rng.lognormal(7, 1.5, 40),
            "jit/hist/compile_us": rng.lognormal(13, 0.5, 6),
        }
        for name, vals in series.items():
            for v in vals:
                cmon.hist_observe(name, float(v))
        path = tmp_path / "metrics.prom"
        pmon.MetricsExporter(str(path)).flush()
        text = path.read_text()
        bucket_re = re.compile(
            r'^(\S+)_bucket\{le="([^"]+)"\} (\d+)$')
        parsed = {}
        sums, counts = {}, {}
        for line in text.splitlines():
            m = bucket_re.match(line)
            if m:
                parsed.setdefault(m.group(1), []).append(
                    (m.group(2), int(m.group(3))))
            elif line.endswith(tuple("0123456789")):
                for kind, store in (("_sum", sums),
                                    ("_count", counts)):
                    name, _, val = line.partition(" ")
                    if name.endswith(kind):
                        store[name[:-len(kind)]] = float(val)
        snap = cmon.registry.snapshot_histograms()
        assert len(series) >= 4
        for name, vals in series.items():
            prom = "paddle_tpu_" + re.sub(r"[^a-zA-Z0-9_]", "_", name)
            pairs = parsed[prom]
            # +Inf terminal present, equal to _count and the registry
            assert pairs[-1][0] == "+Inf"
            assert pairs[-1][1] == len(vals) == counts[prom]
            assert sums[prom] == pytest.approx(sum(vals), rel=1e-4)
            # cumulative counts monotone nondecreasing
            cums = [c for _, c in pairs]
            assert cums == sorted(cums)
            # un-cumulate and compare against the registry's sparse
            # buckets (the round-trip: text -> exact bucket counts)
            s = snap[name]
            lo, pd = float(s["lo"]), int(s["per_decade"])
            got = {}
            prev = 0
            for le, c in pairs[:-1]:
                edge = float(le)
                idx = (0 if edge <= lo else
                       round(math.log10(edge / lo) * pd))
                got[idx] = c - prev
                prev = c
            want_buckets = {int(k): v for k, v in s["buckets"].items()
                            if int(k) <= pd * int(s["decades"])}
            assert got == want_buckets

    def test_step_timer_feeds_step_hist(self):
        cmon.registry.reset_all()
        t = pmon.StepTimer()
        t.begin_step()
        time.sleep(0.002)
        t.end_step(batch_size=4)
        s = cmon.hist_get("step/hist/time_us").snapshot()
        assert s["count"] == 1
        assert s["min"] >= 1000  # slept 2ms


# ---------------------------------------------------------------------------
# ring (b): per-request traces
# ---------------------------------------------------------------------------

class TestServingTraces:
    def test_timeline_covers_full_lifecycle(self, model, prompts):
        """admit -> prefill -> EVERY decode -> finish, with a
        non-None trace_id, readable off engine.get_request(i).trace
        (the acceptance wording)."""
        eng = LLMEngine(model, max_batch=4, block_size=8,
                        num_blocks=32)
        rids = [eng.add_request(p, sampling=sp()) for p in prompts]
        while eng.has_unfinished():
            eng.step()
        for rid in rids:
            req = eng.get_request(rid)
            assert req.trace_id is not None
            st = stages(req)
            assert st[0] == "add"
            for stage in ("admit", "prefill", "decode", "finished"):
                assert stage in st, (rid, st)
            # one decode event per generated token (prefill emits the
            # first token, decode steps the rest)
            assert st.count("decode") == len(req.output_ids)
            assert st[-1] == "finished"
            assert st.index("admit") < st.index("prefill") \
                < st.index("decode")
            # events are timestamped monotonically
            ts = [ev["ts"] for ev in req.trace]
            assert ts == sorted(ts)
        assert eng.check_drained() == {}

    def test_serving_hists_populated(self, model, prompts):
        """TTFT / ITL / queue-wait / e2e distributions off the
        Request.token_times stream: counts match the traffic
        exactly."""
        cmon.registry.reset_all()
        eng = LLMEngine(model, max_batch=4, block_size=8,
                        num_blocks=32)
        rids = [eng.add_request(p, sampling=sp()) for p in prompts]
        while eng.has_unfinished():
            eng.step()
        total = sum(len(eng.get_request(r).output_ids) for r in rids)
        hists = cmon.registry.snapshot_histograms()
        n = len(prompts)
        assert hists["serve/hist/ttft_us"]["count"] == n
        assert hists["serve/hist/queue_wait_us"]["count"] == n
        assert hists["serve/hist/e2e_us"]["count"] == n
        assert hists["serve/hist/itl_us"]["count"] == total - n
        # e2e >= ttft for every request: the merged mins respect it
        assert (hists["serve/hist/e2e_us"]["min"]
                >= hists["serve/hist/ttft_us"]["min"])

    def test_a_tokens_event_stays_out_of_the_flight_ring(
            self, model, prompts):
        """The event a decoded token leaves is in the request's own
        timeline alone: the flight ring keeps the lifecycle events
        (add, admit, prefill, finished) and none with stage `decode`,
        so a wide batch neither floods it nor feeds the collector's
        oldest generation. `mirror=False` is what says so."""
        from paddle_tpu.inference.serving.scheduler import Request
        from paddle_tpu.monitor import flight

        cmon.registry.reset_all()
        flight.recorder.clear()
        eng = LLMEngine(model, max_batch=4, block_size=8,
                        num_blocks=32)
        rids = [eng.add_request(p, sampling=sp()) for p in prompts]
        while eng.has_unfinished():
            eng.step()
        ring = [e["stage"] for e in flight.tail()
                if e["kind"] == "trace"]
        assert "decode" not in ring
        for stage in ("add", "admit", "prefill", "finished"):
            assert ring.count(stage) == len(rids), stage
        assert cmon.stat_get("trace/events") == sum(
            len(eng.get_request(r).trace) for r in rids)
        req = Request([1, 2], sampling=sp())
        flight.recorder.clear()
        mtrace.note(req, "decode", mirror=False, n=1)
        mtrace.note(req, "decode", n=2)
        assert [e["stage"] for e in req.trace] \
            == ["add", "decode", "decode"]
        assert [e["n"] for e in flight.tail()
                if e["kind"] == "trace"] == [2]

    def test_eviction_leg_recorded(self, model, prompts):
        """A chaos-injected RESOURCE_EXHAUSTED decode forces an
        eviction: the victim's timeline shows evict ->
        admit(readmit>0) -> prefill(replayed>0) — the
        recompute-on-readmit story a slow token attributes to."""
        eng = LLMEngine(model, max_batch=4, block_size=8,
                        num_blocks=32)
        with chaos.inject("serve_decode", "resource_exhausted",
                          after=2, times=1) as rule:
            rids = [eng.add_request(p, sampling=sp())
                    for p in prompts]
            while eng.has_unfinished():
                eng.step()
            assert rule.triggers == 1
        victims = [eng.get_request(r) for r in rids
                   if "evict" in stages(eng.get_request(r))]
        assert victims, "no eviction recorded in any timeline"
        for req in victims:
            st = stages(req)
            i = st.index("evict")
            assert "admit" in st[i:], st
            readmit = next(ev for ev in req.trace[i:]
                           if ev["stage"] == "admit")
            assert readmit["readmit"] >= 1
            replay = [ev for ev in req.trace[i:]
                      if ev["stage"] == "prefill"]
            assert replay and replay[0]["replayed"] >= 1
        assert eng.check_drained() == {}

    def test_trace_id_survives_failover(self, model, prompts, want):
        """THE acceptance gate: a chaos-killed replica's in-flight
        requests replay on the survivor TOKEN-IDENTICALLY, keeping
        the SAME trace_id, with the one timeline reading
        ... -> exported -> import -> admit -> prefill(replayed>0)."""
        router = Router(model, replicas=2, max_batch=4, block_size=8,
                        num_blocks=32, heartbeat_timeout_s=60.0)
        try:
            with chaos.inject("serve_decode", "raise", after=3,
                              times=1) as rule:
                rids = [router.submit(p, sampling=sp())
                        for p in prompts]
                minted = {r: router.get_request(r).trace_id
                          for r in rids}
                assert all(minted.values())
                router.wait(rids, timeout_s=120)
                assert rule.triggers == 1
            outs = [list(router.get_request(r).output_ids)
                    for r in rids]
            assert outs == want
            replayed = []
            for rid in rids:
                req = router.get_request(rid)
                assert req.trace_id == minted[rid]
                st = stages(req)
                if "import" in st:
                    replayed.append(rid)
                    # the dying replica's story is PRESERVED on the
                    # survivor: export -> import -> replay in one
                    # timeline, then re-admission and re-prefill
                    i = st.index("import")
                    assert "exported" in st[:i], st
                    assert "failover" in st, st
                    assert "admit" in st[i:] and "prefill" in st[i:]
                    replay = next(ev for ev in req.trace[i:]
                                  if ev["stage"] == "prefill")
                    assert replay["replayed"] >= 0
                    assert st[-1] == "finished"
            assert replayed, "no request records a failover replay"
            assert cmon.stat_get("serve/failovers") >= 1
            for rid in rids:
                router.release(rid)
            assert router.check_drained() == {}
        finally:
            router.shutdown()

    def test_router_route_leg_recorded(self, model, prompts):
        router = Router(model, replicas=2, max_batch=4, block_size=8,
                        num_blocks=32)
        try:
            rid = router.submit(prompts[0], sampling=sp())
            router.wait([rid], timeout_s=120)
            req = router.get_request(rid)
            route = [ev for ev in req.trace if ev["stage"] == "route"]
            assert route and route[0]["replica"] in (0, 1)
            router.release(rid)
        finally:
            router.shutdown()

    def test_disarmed_tracing_leaves_zero_counters(self, model,
                                                   prompts):
        """The PR-9/12 bench-provenance contract, extended to
        tracing: PADDLE_TRACE_SERVE=0 (disarm()) must leave NO
        trace/* counters behind and mint no ids — the disarmed path
        is one attribute read."""
        cmon.registry.reset_all()
        mtrace.disarm()
        try:
            eng = LLMEngine(model, max_batch=2, block_size=8,
                            num_blocks=32)
            rid = eng.add_request(prompts[0], sampling=sp())
            while eng.has_unfinished():
                eng.step()
            req = eng.get_request(rid)
            assert req.trace_id is None and req.trace == []
            snap = pmon.telemetry_snapshot()
            # nonzero only: earlier ARMED tests in this process may
            # have registered the (reset-to-zero) counter names; a
            # fresh disarmed process registers none at all
            leaked = {k: v for k, v in snap["stats"].items()
                      if k.startswith("trace/") and v}
            assert leaked == {}
            # ... and the request is SKIPPED by the spool, not
            # exported with half a timeline
            assert eng.export_traces()["requests"] == []
        finally:
            mtrace.arm()

    def test_request_minted_disarmed_stays_untraced(self, model,
                                                    prompts):
        """Arming mid-flight must not start half a timeline: a
        request minted while disarmed stays untraced forever."""
        from paddle_tpu.inference.serving.scheduler import Request

        mtrace.disarm()
        try:
            req = Request(prompts[0], sampling=sp())
        finally:
            mtrace.arm()
        mtrace.note(req, "late")
        assert req.trace == [] and req.trace_id is None

    def test_disarmed_note_within_budget(self):
        """The PR-3 discipline: the disarmed hot-path gate is ~one
        attribute read — far under the ~3 us/event ring budget."""
        from paddle_tpu.inference.serving.scheduler import Request

        mtrace.disarm()
        try:
            req = Request([1, 2], sampling=sp())
            n = 20000
            t0 = time.perf_counter()
            for _ in range(n):
                mtrace.note(req, "decode", n=1)
            per_event = (time.perf_counter() - t0) / n
        finally:
            mtrace.arm()
        assert per_event < 3e-6, f"{per_event * 1e6:.2f}us/event"

    def test_timeline_bounded_drops_counted(self, model, prompts,
                                            monkeypatch):
        monkeypatch.setenv("PADDLE_TRACE_EVENTS", "8")
        before = cmon.stat_get("trace/dropped")
        eng = LLMEngine(model, max_batch=2, block_size=8,
                        num_blocks=32)
        rid = eng.add_request(
            prompts[0], sampling=sp(max_new_tokens=16))
        while eng.has_unfinished():
            eng.step()
        req = eng.get_request(rid)
        assert len(req.trace) == 8
        assert req.trace_dropped > 0
        assert cmon.stat_get("trace/dropped") \
            == before + req.trace_dropped

    def test_mint_unique(self):
        ids = {mtrace.mint() for _ in range(100)}
        assert len(ids) == 100
        assert all(len(i.split(":")) == 3 for i in ids)


# ---------------------------------------------------------------------------
# trace spool + chrome rendering + CLI
# ---------------------------------------------------------------------------

class TestTraceCLI:
    def _spool(self, model, prompts):
        eng = LLMEngine(model, max_batch=4, block_size=8,
                        num_blocks=32)
        rids = [eng.add_request(p, sampling=sp()) for p in prompts]
        while eng.has_unfinished():
            eng.step()
        spool = eng.export_traces()
        return spool, rids

    def test_spool_schema(self, model, prompts):
        spool, rids = self._spool(model, prompts)
        assert spool["schema"] == mtrace.TRACE_SCHEMA
        assert len(spool["requests"]) == len(rids)
        for entry in spool["requests"]:
            assert entry["trace_id"] and entry["events"]

    def test_chrome_layout_merge_traces_compatible(self, model,
                                                   prompts):
        """rank r -> pid r*stride + 1 (disjoint from the profiler's
        host track at pid 0 in a merged view), one tid per request
        with a thread_name metadata row, stage spans as ph X."""
        spool, _ = self._spool(model, prompts)
        spool["rank"] = 2
        doc = mtrace.to_chrome([spool], pid_stride=100000)
        evs = doc["traceEvents"]
        pids = {e["pid"] for e in evs}
        assert pids == {200001}
        names = [e for e in evs if e.get("name") == "thread_name"]
        assert len(names) == len(spool["requests"])
        tids = {e["tid"] for e in names}
        assert len(tids) == len(names)     # one tid per request
        spans = [e for e in evs if e["ph"] == "X"]
        assert spans and all(e["dur"] >= 0 for e in spans)
        assert {"prefill", "decode"} <= {e["name"] for e in spans}

    def test_cli_trace_chrome_and_text(self, model, prompts,
                                       tmp_path, capsys):
        eng = LLMEngine(model, max_batch=4, block_size=8,
                        num_blocks=32)
        rids = [eng.add_request(p, sampling=sp()) for p in prompts]
        while eng.has_unfinished():
            eng.step()
        spool_path = str(tmp_path / "traces_rank0.json")
        assert eng.dump_traces(spool_path) == spool_path
        out_path = str(tmp_path / "chrome.json")
        assert mcli.main(["trace", spool_path, "-o", out_path]) == 0
        capsys.readouterr()
        doc = json.load(open(out_path))
        assert doc["traceEvents"]
        assert doc["metadata"]["source"] == mtrace.TRACE_SCHEMA
        # text mode names every request and its stages
        assert mcli.main(["trace", spool_path]) == 0
        text = capsys.readouterr().out
        for rid in rids:
            assert rid in text
        assert "prefill" in text and "decode" in text

    def test_cli_trace_rejects_non_spool(self, tmp_path, capsys):
        bad = tmp_path / "bad.json"
        bad.write_text("{}")
        assert mcli.main(["trace", str(bad)]) == 1

    def test_router_fleet_spool_tags_replicas(self, model, prompts):
        router = Router(model, replicas=2, max_batch=4, block_size=8,
                        num_blocks=32)
        try:
            rids = [router.submit(p, sampling=sp()) for p in prompts]
            router.wait(rids, timeout_s=120)
            spool = router.export_traces()
            assert {e["replica"] for e in spool["requests"]} \
                <= {0, 1}
            assert len(spool["requests"]) == len(rids)
            for rid in rids:
                router.release(rid)
        finally:
            router.shutdown()


# ---------------------------------------------------------------------------
# ring (c): fleet aggregation + stragglers
# ---------------------------------------------------------------------------

def _rank_record(rank, step_us_each, n_steps=50, itl_base=1000.0,
                 tail=None):
    h = Histogram("serve/hist/itl_us")
    for i in range(40):
        h.observe(itl_base + 10 * i)
    return {"ts": 100.0 + rank, "rank": rank,
            "stats": {"step/count": n_steps,
                      "step/total_time_us": step_us_each * n_steps,
                      "serve/tokens": 40,
                      "mem/allocated_peak": 100 + rank,
                      "serve/queue_depth": rank},
            "hists": {"serve/hist/itl_us": h.snapshot()},
            **({"flight_tail": tail} if tail else {})}


class TestFleet:
    def test_merge_counters_gauges_hists(self):
        recs = [_rank_record(0, 1000), _rank_record(1, 1100)]
        view = mfleet.merge_records(recs)
        assert view["ranks"] == [0, 1]
        assert view["counters"]["serve/tokens"] == 80
        assert view["counters"]["step/count"] == 100
        # gauges stay per-rank — never summed
        assert view["gauges"]["mem/allocated_peak"] \
            == {"0": 100, "1": 101}
        assert view["gauges"]["serve/queue_depth"] \
            == {"0": 0, "1": 1}
        merged = view["hists"]["serve/hist/itl_us"]
        assert merged["count"] == 80
        assert merged["rank_counts"] == {"0": 40, "1": 40}
        # merged quantile covers the union
        assert snapshot_quantile(merged, 1.0) == pytest.approx(
            1390.0, rel=0.15)

    def test_is_gauge_classification(self):
        assert mfleet.is_gauge("mem/allocated_peak")
        assert mfleet.is_gauge("serve/queue_depth")
        assert mfleet.is_gauge("step/last_time_us")
        assert mfleet.is_gauge("serve/replica/0/healthy")
        assert not mfleet.is_gauge("step/count")
        assert not mfleet.is_gauge("comm/all_reduce/bytes")
        assert not mfleet.is_gauge("serve/tokens")

    def test_straggler_flagged_with_attribution(self):
        """The seeded straggler: rank 1 at 2.2x the fleet median is
        flagged, and its top flight spans ride the report (the
        'slow rank spent its time in X' answer)."""
        tail = [{"kind": "collective_end", "name": "all_reduce",
                 "dur_us": 90000, "ts": 1.0},
                {"kind": "compile_end", "name": "train_step",
                 "dur_us": 30000, "ts": 2.0},
                {"kind": "serve_decode", "ts": 3.0}]   # not a span
        recs = [_rank_record(0, 1000), _rank_record(1, 1000),
                _rank_record(2, 1000), _rank_record(3, 2200,
                                                    tail=tail)]
        rep = mfleet.straggler_report(recs)
        assert rep["median_ms"] == pytest.approx(1.0)
        assert rep["slowest"] == 3
        assert len(rep["stragglers"]) == 1
        s = rep["stragglers"][0]
        assert s["rank"] == 3 and s["skew"] == pytest.approx(2.2)
        spans = s["top_spans"]
        assert spans[0] == {"kind": "collective",
                            "name": "all_reduce", "dur_us": 90000}
        assert len(spans) == 2    # the non-span event is ignored

    def test_true_median_even_rank_count(self):
        """2-rank fleet: the slow rank must not be its own median
        (the upper-middle bug) — 2.5ms vs 1.0ms flags at 1.43x."""
        recs = [_rank_record(0, 1000), _rank_record(1, 2500)]
        rep = mfleet.straggler_report(recs)
        assert rep["median_ms"] == pytest.approx(1.75)
        assert [s["rank"] for s in rep["stragglers"]] == [1]

    def test_load_spool_exporter_jsonl_and_snapshot(self, tmp_path):
        """Both artifact flavors parse: a real MetricsExporter .jsonl
        trail (last flush wins) and a raw telemetry snapshot."""
        cmon.registry.reset_all()
        cmon.stat_add("step/count", 3)
        cmon.hist_observe("serve/hist/itl_us", 500.0)
        path = tmp_path / "metrics.jsonl"
        exp = pmon.MetricsExporter(str(path), interval=3600)
        exp.flush()
        cmon.stat_add("step/count", 1)
        exp.flush()
        recs = mfleet.load_spool(str(path))
        rec = recs[pmon.telemetry_snapshot()["rank"]]
        assert rec["stats"]["step/count"] == 4      # last flush
        assert rec["hists"]["serve/hist/itl_us"]["count"] == 1
        snap_path = tmp_path / "snap.json"
        snap_path.write_text(json.dumps(pmon.telemetry_snapshot()))
        recs2 = mfleet.load_spool(str(snap_path))
        assert list(recs2.values())[0]["stats"]["step/count"] == 4

    def test_load_spool_rejects_garbage(self, tmp_path):
        bad = tmp_path / "bad.txt"
        bad.write_text("not json\nstill not\n")
        with pytest.raises(ValueError, match="no exporter records"):
            mfleet.load_spool(str(bad))

    def test_fleet_cli_over_two_rank_spools(self, tmp_path, capsys):
        """THE acceptance gate: `monitor fleet` over >= 2 synthetic
        rank spools reports merged histograms and flags the seeded
        straggler."""
        paths = []
        for rank, step_us in ((0, 1000), (1, 2500)):
            p = tmp_path / f"metrics_rank{rank}.jsonl"
            p.write_text(json.dumps(_rank_record(rank, step_us))
                         + "\n")
            paths.append(str(p))
        assert mcli.main(["fleet"] + paths) == 0
        out = capsys.readouterr().out
        assert "ranks [0, 1]" in out
        assert "serve/hist/itl_us" in out and "p99=" in out
        assert "r0=40, r1=40" in out
        assert "STRAGGLER rank 1" in out
        # gauges print PER-RANK in the text view too, never summed
        assert "serve/queue_depth  r0=0  r1=1" in out
        # --json emits the full machine-readable view
        assert mcli.main(["fleet", "--json"] + paths) == 0
        view = json.loads(capsys.readouterr().out)
        assert view["counters"]["serve/tokens"] == 80
        assert view["hists"]["serve/hist/itl_us"]["count"] == 80
        assert [s["rank"] for s
                in view["stragglers"]["stragglers"]] == [1]

    def test_fleet_cli_exit2_on_garbage(self, tmp_path, capsys):
        bad = tmp_path / "bad.jsonl"
        bad.write_text("nope\n")
        assert mcli.main(["fleet", str(bad)]) == 2
        assert "error:" in capsys.readouterr().err

    def test_fleet_view_merges_dump_bundle(self, tmp_path):
        """Flight dump bundles are first-class fleet inputs: their
        embedded telemetry merges and their flight tail feeds
        straggler attribution."""
        bundle = {"schema": "paddle_tpu.flight/1", "rank": 1,
                  "reason": "watchdog",
                  "telemetry": {
                      "stats": {"step/count": 10,
                                "step/total_time_us": 50000},
                      "hists": {}},
                  "flight_tail": [
                      {"kind": "collective_end", "name": "broadcast",
                       "dur_us": 7777, "ts": 1.0}]}
        bpath = tmp_path / "dump_rank1_pid9.json"
        bpath.write_text(json.dumps(bundle))
        spool = tmp_path / "metrics_rank0.jsonl"
        spool.write_text(json.dumps(_rank_record(0, 1000)) + "\n")
        view = mfleet.fleet_view([str(spool), str(bpath)])
        assert view["ranks"] == [0, 1]
        assert view["counters"]["step/count"] == 60
        rep = view["stragglers"]
        assert [s["rank"] for s in rep["stragglers"]] == [1]
        assert rep["stragglers"][0]["top_spans"][0]["dur_us"] == 7777

    def test_fleet_snapshot_single_process(self):
        """world_size == 1 short-circuits to a local one-rank view —
        the live entry works outside a launch too."""
        cmon.registry.reset_all()
        cmon.stat_add("step/count", 2)
        cmon.stat_add("step/total_time_us", 2000)
        cmon.hist_observe("serve/hist/itl_us", 800.0)
        view = pmon.fleet_snapshot()
        assert view is not None
        assert view["counters"]["step/count"] == 2
        assert view["hists"]["serve/hist/itl_us"]["count"] == 1
        assert view["stragglers"]["stragglers"] == []


# ---------------------------------------------------------------------------
# VLOG rank prefix (satellite)
# ---------------------------------------------------------------------------

class TestVlogRank:
    def test_single_rank_output_byte_unchanged(self, capsys,
                                               monkeypatch):
        """No world-size env: the prefix is EXACTLY the historical
        `V<level> HH:MM:SS]` — byte-identical format, no rank
        token."""
        monkeypatch.delenv("PADDLE_TRAINERS_NUM", raising=False)
        monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
        monkeypatch.setenv("GLOG_v", "1")
        cmon.VLOG(1, "hello", "world")
        err = capsys.readouterr().err
        assert re.fullmatch(r"V1 \d{2}:\d{2}:\d{2}\] hello world\n",
                            err), repr(err)

    def test_multi_rank_prefix_names_the_rank(self, capsys,
                                              monkeypatch):
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
        monkeypatch.setenv("PADDLE_TRAINER_ID", "2")
        monkeypatch.setenv("GLOG_v", "1")
        cmon.VLOG(1, "who said this")
        err = capsys.readouterr().err
        assert re.fullmatch(
            r"V1 r2 \d{2}:\d{2}:\d{2}\] who said this\n", err), \
            repr(err)


# ---------------------------------------------------------------------------
# doc drift: README covers the new surface
# ---------------------------------------------------------------------------

_TRACE_ENV_RE = re.compile(
    r"PADDLE_(?:TRACE|MONITOR_HIST|MONITOR_STRAGGLER)_[A-Z_]+")


class TestObservabilityDocDrift:
    def _readme(self):
        with open(os.path.join(REPO, "README.md")) as f:
            return f.read()

    def test_tracing_fleet_section(self):
        doc = self._readme()
        assert "Request tracing & fleet telemetry" in doc
        for word in ("Histogram", "quantile", "trace_id",
                     "monitor trace", "monitor fleet",
                     "fleet_snapshot", "straggler",
                     "export_traces"):
            assert word in doc, f"{word!r} missing from README"

    def test_env_vars_documented(self):
        """Every PADDLE_TRACE_* / PADDLE_MONITOR_HIST_* /
        PADDLE_MONITOR_STRAGGLER_* knob in the monitor sources is in
        the README env table."""
        used = set()
        for sub in ("monitor", "core"):
            srcdir = os.path.join(REPO, "paddle_tpu", sub)
            for name in os.listdir(srcdir):
                if name.endswith(".py"):
                    with open(os.path.join(srcdir, name)) as f:
                        used |= set(_TRACE_ENV_RE.findall(f.read()))
        assert used
        doc = self._readme()
        missing = sorted(v for v in used if v not in doc)
        assert not missing, (
            f"observability env vars missing from README: {missing}")

    def test_hist_series_documented(self):
        doc = self._readme()
        # expand the README's `a/{b,c}_us` brace shorthand so the
        # series list below matches either spelling
        for m in re.finditer(r"([\w/]+)\{([\w,]+)\}(\w*)", doc):
            doc += " " + " ".join(
                f"{m.group(1)}{leaf}{m.group(3)}"
                for leaf in m.group(2).split(","))
        for series in ("serve/hist/ttft_us", "serve/hist/itl_us",
                       "serve/hist/queue_wait_us",
                       "serve/hist/e2e_us", "jit/hist/compile_us",
                       "io/hist/fetch_us", "comm/hist/host_us",
                       "step/hist/time_us"):
            assert series in doc, f"{series} missing from README"
