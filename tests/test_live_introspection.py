"""Live introspection plane (ISSUE 18): in-process debug/metrics HTTP
server, on-demand capture, and fleet-wide live scraping.

Covers the tentpole end to end — loopback smoke against every
endpoint, scrape byte-compatibility with the bundle-driven fleet
report, the zero-overhead contract with PADDLE_MONITOR_SERVE unset
(HLO-equality gated, no thread/no socket) — plus the satellites:
strict Prometheus exposition round-trips (escaping, non-finite
values, cross-family name collisions), the scrape/serve CLI exit
contract, fleet.py edge cases (single rank, empty hists, mixed
schema), the README endpoints-table doc-drift gate, trace-context
arming refusal, and idempotent shutdown under the crash-dump path.

No test here sleeps > 1s; servers bind port 0 (ephemeral) only.
"""
import gc
import json
import os
import re
import socket
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, nn, optimizer as optim
from paddle_tpu.core import monitor as cmon
from paddle_tpu.monitor import fleet, flight
from paddle_tpu.monitor import server as mserver
from paddle_tpu.monitor.cli import main as cli_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leftover_server(monkeypatch):
    """Every test starts disarmed and leaves no server behind (the
    zero-overhead contract is per-test too)."""
    monkeypatch.delenv("PADDLE_MONITOR_SERVE", raising=False)
    yield
    mserver.stop_server()


def _get(url, timeout=5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


def _get_json(url, timeout=5.0):
    code, body = _get(url, timeout)
    return code, json.loads(body)


# ---------------------------------------------------------------------------
# Strict Prometheus exposition parsing (satellite: hardening)
# ---------------------------------------------------------------------------

# the exposition-format grammar, strictly: metric name, optional
# {label="value",...} with only \\ \" \n escapes inside values, one
# sample value token (decimal/scientific, +Inf/-Inf/NaN)
_PROM_LINE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*='
    r'"(?:[^"\\\n]|\\\\|\\"|\\n)*",?)*)\})?'
    r' (-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?|[+-]Inf|NaN)$')


def parse_prom(text):
    """Strict line parser; asserts on any malformed or duplicate
    series. Returns {(name, labelstring): value-token}."""
    assert text.endswith("\n"), "exposition must end with a newline"
    series = {}
    for line in text.rstrip("\n").split("\n"):
        m = _PROM_LINE.match(line)
        assert m, f"invalid exposition line: {line!r}"
        name, labels, value = m.group(1), m.group(2) or "", m.group(3)
        key = (name, labels)
        assert key not in series, f"duplicate series: {key}"
        series[key] = value
    return series


def _snap(stats=None, hists=None, ts=1700000000.0, rank=0):
    return {"ts": ts, "rank": rank, "stats": stats or {},
            "hists": hists or {}}


class TestPrometheusHardening:
    def test_live_snapshot_round_trips(self):
        cmon.stat_add("introspect/test/requests", 3)
        cmon.hist_observe("introspect/hist/lat_us", 42.0)
        series = parse_prom(monitor.prometheus_text())
        names = {n for n, _ in series}
        assert "paddle_tpu_introspect_test_requests" in names
        assert "paddle_tpu_introspect_hist_lat_us_count" in names

    def test_slashful_and_hostile_names_sanitize(self):
        stats = {"jit/hist/<lambda>/dispatch_us": 7,
                 'weird name "quoted"\nnewline': 1,
                 "unicode-μs": 2}
        series = parse_prom(monitor.prometheus_text(_snap(stats)))
        # 3 stats + export_timestamp_seconds
        assert len(series) == 4

    def test_nonfinite_values_are_valid_tokens(self):
        stats = {"g/nan": float("nan"), "g/pinf": float("inf"),
                 "g/ninf": float("-inf"), "g/bool": True,
                 "g/str": "not-a-number"}
        series = parse_prom(monitor.prometheus_text(_snap(stats)))
        vals = {n: v for (n, _), v in series.items()}
        assert vals["paddle_tpu_g_nan"] == "NaN"
        assert vals["paddle_tpu_g_pinf"] == "+Inf"
        assert vals["paddle_tpu_g_ninf"] == "-Inf"
        assert vals["paddle_tpu_g_bool"] == "1"
        assert vals["paddle_tpu_g_str"] == "NaN"

    def test_scalar_scalar_collision_antialiased(self):
        stats = {"step/time": 1, "step_time": 2}
        series = parse_prom(monitor.prometheus_text(_snap(stats)))
        colliders = [n for n, _ in series
                     if n.startswith("paddle_tpu_step_time")]
        assert len(colliders) == 2 and len(set(colliders)) == 2
        # every collider is suffixed (stable sha1 of the ORIGINAL
        # name) — neither keeps the ambiguous plain spelling
        assert all(n != "paddle_tpu_step_time" for n in colliders)

    def test_scalar_vs_hist_family_collision(self):
        h = cmon.Histogram()
        h.observe(5.0)
        # scalar sanitizes onto the histogram's own base name AND
        # onto its reserved _count series — both must be suffixed
        # away rather than alias the family
        stats = {"lat.us": 1, "lat/us_count": 9}
        hists = {"lat_us": h.snapshot()}
        series = parse_prom(
            monitor.prometheus_text(_snap(stats, hists)))
        names = {n for n, _ in series}
        # nothing aliases: 2 scalars + 3 hist series + the timestamp
        assert len(names) == 6
        # the colliding pair (lat.us vs the hist base) both moved off
        # the ambiguous plain name; the hist family stays coherent —
        # ONE suffixed base owning _bucket/_sum/_count
        assert "paddle_tpu_lat_us" not in names
        hist_bases = {n[:-len("_bucket")] for n in names
                      if n.endswith("_bucket")}
        assert len(hist_bases) == 1
        base = hist_bases.pop()
        assert {base + "_sum", base + "_count"} <= names
        assert (base + "_bucket", 'le="+Inf"') in series
        # the scalar that sanitized onto a reserved _count series got
        # suffixed away from EVERY hist family's series
        assert "paddle_tpu_lat_us_count" not in names \
            or base == "paddle_tpu_lat_us"

    def test_bucket_series_cumulative_and_terminated(self):
        h = cmon.Histogram()
        for v in (2.0, 2.0, 50.0, 1e30):  # 1e30 = overflow bin
            h.observe(v)
        series = parse_prom(
            monitor.prometheus_text(_snap(hists={"d/us": h.snapshot()})))
        buckets = [(labels, int(v)) for (n, labels), v
                   in series.items()
                   if n == "paddle_tpu_d_us_bucket"]
        assert ('le="+Inf"', 4) in buckets
        # cumulative counts never decrease, overflow only in +Inf
        finite = sorted(c for lbl, c in buckets if "Inf" not in lbl)
        assert finite == sorted(finite) and max(finite) <= 4
        assert int(series[("paddle_tpu_d_us_count", "")]) == 4

    def test_exporter_prom_file_uses_same_renderer(self, tmp_path):
        cmon.stat_add("introspect/export/one", 1)
        path = tmp_path / "m.prom"
        exp = monitor.MetricsExporter(str(path), interval=3600,
                                      fmt="prom")
        try:
            exp.flush()
        finally:
            exp.stop()
        text = path.read_text()
        parse_prom(text)
        # identical modulo the flush timestamp line
        live = monitor.prometheus_text()

        def _strip_ts(t):
            return "\n".join(
                ln for ln in t.splitlines()
                if not ln.startswith(
                    "paddle_tpu_export_timestamp_seconds"))
        assert _strip_ts(text) == _strip_ts(live)


# ---------------------------------------------------------------------------
# Loopback smoke (satellite: CI/tooling — no sleeps, ephemeral port)
# ---------------------------------------------------------------------------

class TestLoopbackSmoke:
    def test_every_endpoint_answers(self):
        srv = mserver.serve(port=0, host="127.0.0.1")
        assert srv.port != 0 and srv.running()
        code, body = _get(srv.url + "/healthz")
        assert (code, body) == (200, "ok\n")
        code, text = _get(srv.url + "/metrics")
        assert code == 200
        parse_prom(text)
        code, doc = _get_json(srv.url + "/statusz")
        assert code == 200 and doc["ok"] and doc["pid"] == os.getpid()
        assert doc["server"]["running"] is True
        assert doc["server"]["port"] == srv.port
        code, doc = _get_json(srv.url + "/flightz?n=16")
        assert code == 200 and isinstance(doc["events"], list)
        code, doc = _get_json(srv.url + "/flightz?format=chrome")
        assert code == 200 and "traceEvents" in doc
        for page in ("/memz", "/perfz", "/tracez"):
            code, doc = _get_json(srv.url + page)
            assert code == 200 and isinstance(doc, dict), page
        code, doc = _get_json(srv.url + "/")
        assert code == 200 and set(doc["routes"]) == {
            p for p, _, _ in mserver.ROUTES}
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/nope", timeout=5)
        assert ei.value.code == 404

    def test_metrics_json_is_raw_telemetry_snapshot(self):
        cmon.stat_add("introspect/raw/marker", 1)
        srv = mserver.serve(port=0, host="127.0.0.1")
        code, doc = _get_json(srv.url + "/metrics?format=json")
        assert code == 200
        assert doc["stats"]["introspect/raw/marker"] >= 1
        assert set(doc) >= {"ts", "rank", "stats"}

    def test_profilez_flight_only_window(self):
        srv = mserver.serve(port=0, host="127.0.0.1")
        flight.record("before_window")  # must NOT be in the bundle
        code, doc = _get_json(
            srv.url + "/profilez?duration_ms=20&profiler=0")
        assert code == 200
        assert doc["schema"] == mserver.PROFILEZ_SCHEMA
        assert doc["duration_ms"] == 20
        kinds = [e["kind"] for e in doc["flight"]]
        assert "profilez_begin" in kinds
        assert "before_window" not in kinds
        assert "stats" in doc["telemetry"]

    def test_tracez_weak_registry(self, monkeypatch):
        # sources an earlier test left alive (a router or engine some
        # module still holds) are not this test's: start from none
        monkeypatch.setattr(mserver, "_trace_sources", [])

        class Spooler:
            def export_traces(self):
                return {"schema": "paddle_tpu.trace/1",
                        "requests": [{"req_id": "r1"}]}

        class Broken:
            def export_traces(self):
                raise RuntimeError("boom")

        sp, br = Spooler(), Broken()
        mserver.add_trace_source(sp.export_traces)
        mserver.add_trace_source(sp.export_traces)  # idempotent
        mserver.add_trace_source(br.export_traces)
        srv = mserver.serve(port=0, host="127.0.0.1")
        code, doc = _get_json(srv.url + "/tracez")
        assert code == 200
        spools = doc["spools"]
        oks = [s for s in spools if s.get("requests")]
        errs = [s for s in spools if s.get("error")]
        assert len(oks) == 1 and len(errs) == 1
        assert "RuntimeError" in errs[0]["error"]
        # a collected source drops off the page, no unregister call
        del sp, br
        gc.collect()
        assert mserver.trace_spools() == []


# ---------------------------------------------------------------------------
# Zero-overhead contract (acceptance: env unset -> nothing happens)
# ---------------------------------------------------------------------------

def _zeroed_step():
    model = nn.Linear(4, 2)
    import jax.numpy as jnp

    for p in model.parameters():
        p._value = jnp.zeros_like(p._value)
    opt = optim.SGD(learning_rate=0.1,
                    parameters=model.parameters())
    return paddle.jit.TrainStepCompiler(model, opt,
                                        nn.CrossEntropyLoss())


class TestZeroOverhead:
    def test_disarmed_no_thread_no_socket_no_server(self):
        assert mserver._env_port() is None
        assert mserver.maybe_auto_serve("test") is None
        assert mserver.get_server() is None
        assert not any(t.name == "paddle-monitor-serve"
                       for t in threading.enumerate())

    def test_lowering_bit_identical_with_and_without_server(
            self, hlo_sans_locations):
        x = paddle.to_tensor(np.ones((4, 4), np.float32))
        y = paddle.to_tensor(np.zeros((4,), dtype="int64"))
        plain1 = hlo_sans_locations(
            _zeroed_step().lower_compiled(x, y).as_text())
        plain2 = hlo_sans_locations(
            _zeroed_step().lower_compiled(x, y).as_text())
        assert plain1 == plain2  # deterministic baseline
        mserver.serve(port=0, host="127.0.0.1")
        armed = hlo_sans_locations(
            _zeroed_step().lower_compiled(x, y).as_text())
        assert armed == plain1  # the server never touches lowering

    def test_env_falsy_spellings_disarm_but_zero_is_a_port(
            self, monkeypatch):
        for v in ("", "off", "false", "no", "nonsense"):
            monkeypatch.setenv("PADDLE_MONITOR_SERVE", v)
            assert mserver._env_port() is None, v
        monkeypatch.setenv("PADDLE_MONITOR_SERVE", "0")
        assert mserver._env_port() == 0  # ephemeral, NOT disarmed
        monkeypatch.setenv("PADDLE_MONITOR_SERVE", "8899")
        assert mserver._env_port() == 8899


# ---------------------------------------------------------------------------
# Arming (auto-serve from fit/Router, trace refusal, taken port)
# ---------------------------------------------------------------------------

class TestArming:
    def test_model_fit_auto_arms(self, monkeypatch):
        from paddle_tpu.hapi.model import Model
        from paddle_tpu.io import Dataset

        class DS(Dataset):
            def __len__(self):
                return 4

            def __getitem__(self, i):
                return (np.ones((4,), np.float32),
                        np.ones((2,), np.float32))

        monkeypatch.setenv("PADDLE_MONITOR_SERVE", "0")
        monkeypatch.setenv("PADDLE_MONITOR_SERVE_HOST", "127.0.0.1")
        m = Model(nn.Linear(4, 2))
        m.prepare(optim.SGD(learning_rate=0.1,
                            parameters=m.network.parameters()),
                  loss=lambda o, y: ((o - y) ** 2).mean())
        m.fit(DS(), batch_size=2, epochs=1, verbose=0, shuffle=False)
        srv = mserver.get_server()
        assert srv is not None and srv.running()
        # the training run's metrics are live on the wire
        code, text = _get(srv.url + "/metrics")
        assert code == 200
        assert "paddle_tpu_step_count" in text
        code, doc = _get_json(srv.url + "/flightz")
        assert code == 200
        code, doc = _get_json(srv.url + "/perfz")
        assert code == 200

    def test_router_auto_arms_and_serves_tracez(self, monkeypatch):
        from paddle_tpu.inference.serving import Router, SamplingParams
        from paddle_tpu.text.models.gpt import (GPTConfig,
                                                GPTForCausalLM)

        monkeypatch.setenv("PADDLE_MONITOR_SERVE", "0")
        monkeypatch.setenv("PADDLE_MONITOR_SERVE_HOST", "127.0.0.1")
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, ffn_hidden=64, max_seq_len=32,
                        dropout=0.0, use_flash_attention=False)
        model = GPTForCausalLM(cfg)
        model.eval()
        router = Router(model, replicas=1, max_batch=2, block_size=8,
                        num_blocks=16)
        try:
            srv = mserver.get_server()
            assert srv is not None and srv.running()
            rid = router.submit(
                [1, 2, 3], sampling=SamplingParams(max_new_tokens=2))
            router.wait([rid], timeout_s=30)
            # before release: the finished request is still spooled
            code, doc = _get_json(srv.url + "/tracez")
            assert code == 200
            reqs = [r for s in doc["spools"]
                    for r in s.get("requests") or []]
            assert any(r.get("req_id") == rid for r in reqs), \
                "router request missing from /tracez"
            router.release(rid)
            code, text = _get(srv.url + "/metrics")
            assert code == 200 and "paddle_tpu_serve_requests" in text
        finally:
            router.shutdown()

    def test_arming_refused_inside_trace(self):
        import jax

        seen = []

        def f(x):
            seen.append(mserver.maybe_auto_serve("traced"))
            return x * 2

        before = cmon.stat_get("monitor/serve/trace_skips")
        os.environ["PADDLE_MONITOR_SERVE"] = "0"
        try:
            jax.jit(f)(1.0)
        finally:
            os.environ.pop("PADDLE_MONITOR_SERVE", None)
        assert seen == [None]
        assert mserver.get_server() is None
        assert cmon.stat_get("monitor/serve/trace_skips") == before + 1

    def test_taken_port_degrades_to_counter(self, monkeypatch):
        blocker = socket.socket()
        blocker.bind(("127.0.0.1", 0))
        blocker.listen(1)
        port = blocker.getsockname()[1]
        monkeypatch.setenv("PADDLE_MONITOR_SERVE", str(port))
        monkeypatch.setenv("PADDLE_MONITOR_SERVE_HOST", "127.0.0.1")
        before = cmon.stat_get("monitor/serve/errors")
        try:
            assert mserver.maybe_auto_serve("test") is None
        finally:
            blocker.close()
        assert cmon.stat_get("monitor/serve/errors") == before + 1
        # the explicit path raises instead
        blocker = socket.socket()
        blocker.bind(("127.0.0.1", 0))
        blocker.listen(1)
        port = blocker.getsockname()[1]
        try:
            with pytest.raises(OSError):
                mserver.serve(port=port, host="127.0.0.1")
        finally:
            blocker.close()


# ---------------------------------------------------------------------------
# Shutdown + crash path (satellite: bugfix sweep)
# ---------------------------------------------------------------------------

class TestShutdown:
    def test_idempotent_everywhere(self):
        srv = mserver.serve(port=0, host="127.0.0.1")
        assert srv.running()
        mserver.stop_server()
        assert not srv.running()
        mserver.stop_server()  # second stop: no-op, no raise
        srv.shutdown()         # direct double-shutdown: no raise
        srv.shutdown()
        assert mserver.get_server() is None
        assert cmon.stat_get("monitor/serve/port") == 0

    def test_crash_dump_names_the_armed_server(self, tmp_path):
        srv = mserver.serve(port=0, host="127.0.0.1")
        path = str(tmp_path / "crash.json")
        flight.write_dump("test_crash", path=path)
        with open(path) as f:
            bundle = json.load(f)
        assert bundle["server"]["running"] is True
        assert bundle["server"]["port"] == srv.port
        mserver.stop_server()
        # a dump AFTER teardown still writes (idempotent teardown
        # cannot poison the excepthook's bundle)
        path2 = str(tmp_path / "post.json")
        flight.write_dump("test_post", path=path2)
        with open(path2) as f:
            assert json.load(f)["server"]["running"] is False


# ---------------------------------------------------------------------------
# Scrape: byte-compat with the bundle path + CLI exit contract
# ---------------------------------------------------------------------------

def _mk_record(rank, step_us, n=10):
    h = cmon.Histogram()
    for _ in range(n):
        h.observe(step_us)
    return {"ts": 1700000000.0 + rank, "rank": rank,
            "stats": {"step/count": n,
                      "step/total_time_us": step_us * n,
                      "serve/requests": 5 + rank,
                      "mem/allocated_bytes": 1000 * (rank + 1)},
            "hists": {"step/hist/time_us": h.snapshot()}}


def _start_fleet(snaps):
    servers = []
    for s in snaps:
        srv = mserver.DebugServer(
            port=0, host="127.0.0.1",
            snapshot_fn=(lambda s=s: s)).start()
        servers.append(srv)
    return servers


class TestScrape:
    def test_byte_compatible_with_bundle_driven_fleet(self, tmp_path):
        snaps = [_mk_record(0, 900.0), _mk_record(1, 2000.0)]
        paths = []
        for s in snaps:
            p = tmp_path / f"rank{s['rank']}.json"
            p.write_text(json.dumps(s))
            paths.append(str(p))
        bundle_view = fleet.fleet_view(paths)
        servers = _start_fleet(snaps)
        try:
            targets = [f"127.0.0.1:{s.port}" for s in servers]
            records, failures = fleet.scrape_records(
                targets, with_flight=False)
            assert failures == {}
            live_view = fleet.scrape_view(records)
        finally:
            for s in servers:
                s.shutdown()
        # byte-compatible modulo provenance: same counters, gauges,
        # hists, and the SAME straggler report
        for v in (bundle_view, live_view):
            v.pop("sources", None)
        assert json.dumps(bundle_view, sort_keys=True) \
            == json.dumps(live_view, sort_keys=True)
        assert [s["rank"] for s in
                live_view["stragglers"]["stragglers"]] == [1]

    def test_cli_scrape_partial_fleet_exits_1(self, tmp_path, capsys):
        snaps = [_mk_record(0, 1000.0)]
        servers = _start_fleet(snaps)
        dead = socket.socket()
        dead.bind(("127.0.0.1", 0))
        dead_port = dead.getsockname()[1]
        dead.close()  # nothing listens here any more
        try:
            rc = cli_main(["scrape", "--no-flight", "--timeout", "2",
                           f"127.0.0.1:{servers[0].port}",
                           f"127.0.0.1:{dead_port}"])
        finally:
            for s in servers:
                s.shutdown()
        captured = capsys.readouterr()
        assert rc == 1
        assert "fleet view over ranks [0]" in captured.out
        assert str(dead_port) in captured.err

    def test_cli_scrape_no_targets_reachable_exits_2(self, capsys):
        dead = socket.socket()
        dead.bind(("127.0.0.1", 0))
        dead_port = dead.getsockname()[1]
        dead.close()
        rc = cli_main(["scrape", "--no-flight", "--timeout", "2",
                       f"127.0.0.1:{dead_port}"])
        captured = capsys.readouterr()
        assert rc == 2
        assert "error:" in captured.err

    def test_cli_scrape_json_view(self, capsys):
        servers = _start_fleet([_mk_record(0, 1000.0)])
        try:
            rc = cli_main(["scrape", "--no-flight", "--json",
                           f"127.0.0.1:{servers[0].port}"])
        finally:
            for s in servers:
                s.shutdown()
        assert rc == 0
        view = json.loads(capsys.readouterr().out)
        assert view["ranks"] == [0]
        assert view["counters"]["step/count"] == 10

    def test_cli_serve_taken_port_exits_2(self, capsys):
        blocker = socket.socket()
        blocker.bind(("127.0.0.1", 0))
        blocker.listen(1)
        port = blocker.getsockname()[1]
        try:
            rc = cli_main(["serve", str(port), "--host", "127.0.0.1"])
        finally:
            blocker.close()
        assert rc == 2
        assert "error:" in capsys.readouterr().err

    def test_cli_serve_foreground_stops_with_server(self):
        rcs = []
        t = threading.Thread(
            target=lambda: rcs.append(
                cli_main(["serve", "0", "--host", "127.0.0.1"])),
            daemon=True)
        t.start()
        deadline = 5.0
        import time as _time

        t0 = _time.monotonic()
        while mserver.get_server() is None \
                or not mserver.get_server().running():
            assert _time.monotonic() - t0 < deadline
            _time.sleep(0.01)
        srv = mserver.get_server()
        code, _ = _get(srv.url + "/healthz")
        assert code == 200
        mserver.stop_server()
        t.join(timeout=5)
        assert not t.is_alive() and rcs == [0]

    def test_scraped_flight_tail_feeds_straggler_spans(self):
        # a straggler scraped WITH flight gets span attribution, the
        # same enrichment dump bundles carry
        rec = _mk_record(1, 5000.0)
        rec["flight_tail"] = [
            {"ts": 1.0, "kind": "allreduce_end", "name": "grads",
             "dur_us": 4999.0, "tid": 1}]
        fast = _mk_record(0, 100.0)
        rep = fleet.straggler_report([fast, rec])
        assert rep["stragglers"][0]["rank"] == 1
        # top_spans strips the _end suffix: span kind, not event kind
        assert rep["stragglers"][0]["top_spans"][0]["kind"] \
            == "allreduce"


# ---------------------------------------------------------------------------
# fleet.py edge cases (satellite: test coverage)
# ---------------------------------------------------------------------------

class TestFleetEdgeCases:
    def test_single_rank_fleet(self):
        view = fleet.scrape_view([_mk_record(0, 1500.0)])
        assert view["ranks"] == [0]
        strag = view["stragglers"]
        assert strag["median_ms"] == 1.5
        assert strag["stragglers"] == []  # own median, never flagged

    def test_empty_histograms(self):
        rec = _mk_record(0, 1000.0)
        rec["hists"] = {"step/hist/time_us":
                        cmon.Histogram().snapshot()}
        view = fleet.merge_records([rec])
        assert view["hists"]["step/hist/time_us"]["count"] == 0
        # and an entirely hist-less record merges too
        rec2 = {"rank": 1, "stats": {"step/count": 1}, "hists": {}}
        view = fleet.merge_records([rec, rec2])
        assert view["ranks"] == [0, 1]

    def test_rank_missing_stat_family_does_not_crash(self):
        full = _mk_record(0, 1000.0)
        bare = {"rank": 1, "stats": {"io/bytes": 5}, "hists": {}}
        view = fleet.merge_records([full, bare])
        rep = fleet.straggler_report([full, bare])
        assert view["counters"]["io/bytes"] == 5
        # only rank 0 has step telemetry; report covers it alone
        assert list(rep["step_ms"]) == ["0"]

    def test_mixed_hist_schemas_degrade_not_crash(self):
        a = cmon.Histogram(per_decade=20)
        b = cmon.Histogram(per_decade=10)  # incompatible boundaries
        for _ in range(8):
            a.observe(100.0)
        b.observe(100.0)
        recs = [
            {"rank": 0, "stats": {},
             "hists": {"h": a.snapshot()}},
            {"rank": 1, "stats": {},
             "hists": {"h": b.snapshot()}},
        ]
        before = cmon.stat_get("monitor/fleet/hist_schema_skips")
        view = fleet.merge_records(recs)  # Histogram.merge would raise
        # majority-count schema wins; the odd rank is counted out
        assert view["hists"]["h"]["count"] == 8
        assert cmon.stat_get("monitor/fleet/hist_schema_skips") > before

    def test_non_numeric_stat_value_lands_in_gauges(self):
        recs = [{"rank": 0, "stats": {"build/label": "v2.6-tpu",
                                      "step/count": 3}, "hists": {}}]
        view = fleet.merge_records(recs)
        assert view["gauges"]["build/label"]["0"] == "v2.6-tpu"
        assert view["counters"]["step/count"] == 3


# ---------------------------------------------------------------------------
# Doc drift: README endpoints table == server.ROUTES
# ---------------------------------------------------------------------------

class TestDocDrift:
    def _endpoint_rows(self):
        with open(os.path.join(REPO, "README.md")) as f:
            doc = f.read()
        m = re.search(
            r"\| endpoint \| payload \| armed by \|\n\|[-| ]+\|\n"
            r"((?:\|.*\|\n)+)", doc)
        assert m, "README endpoints table missing"
        rows = {}
        for line in m.group(1).strip().splitlines():
            cells = [c.strip() for c in line.strip("|").split("|")]
            assert len(cells) == 3, line
            rows[cells[0].strip("`")] = cells[2].strip("`")
        return rows

    def test_endpoints_table_matches_routes(self):
        rows = self._endpoint_rows()
        routes = {p: armed for p, _, armed in mserver.ROUTES}
        assert set(rows) == set(routes), (
            "README endpoints table out of sync with "
            "monitor.server.ROUTES")
        for path, armed in routes.items():
            assert rows[path] == armed, (
                f"{path}: README says armed-by {rows[path]!r}, "
                f"ROUTES says {armed!r}")

    def test_quickstart_documented(self):
        with open(os.path.join(REPO, "README.md")) as f:
            doc = f.read()
        for needle in ("Live introspection", "monitor scrape",
                       "PADDLE_MONITOR_SERVE", "monitor.serve"):
            assert needle in doc, f"{needle!r} missing from README"
