"""BENCHMARK.json against the rules a later PR's cells and metrics have to
keep too, and the harness's refusals."""
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _cells_of(metric, bench):
    return metric.get("workloads") or [w["name"] for w in bench["workloads"]]


def test_keys_names_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        for e in bench[group]:
            assert set(e) == keys, e
            assert NAME.match(e["name"]) and 1 <= len(e["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}, m
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}, m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert all(NAME.match(m["name"]) and UNIT.match(m["unit"])
               and m["better"] in ("lower", "higher") for m in metrics)
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    # a full check of 24 cells fits the driver's day
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_entry_has_its_files(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("tpubench/configs/")
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(
            REPO, "tpubench", "models", cfg["family"] + ".py"))
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for w in bench["workloads"]:
        with open(os.path.join(REPO, "tpubench", "traffic",
                               w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        assert os.path.exists(os.path.join(REPO, "tpubench", "kinds",
                                           kind + ".py"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        with open(os.path.join(REPO, "tpubench", "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        module, _, fn = spec["reader"].partition(".")
        sys.path.insert(0, REPO)
        try:
            mod = __import__(f"tpubench.readers.{module}", fromlist=[fn])
        finally:
            sys.path.remove(REPO)
        assert callable(getattr(mod, fn)), spec["reader"]
        assert spec["definition"]


def test_what_every_cell_reports(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in bench["workloads"]:
        mine = [m for m in bench["end_to_end"]
                if w["name"] in _cells_of(m, bench)]
        assert len(mine) >= 2, w["name"]
        assert any(w["name"] in _cells_of(m, bench)
                   for m in bench["per_layer"])
    for m in bench["per_layer"]:
        target = e2e[m["moves"]]
        assert set(_cells_of(m, bench)) <= set(_cells_of(target, bench)), m
    by_layer = {}
    for m in bench["per_layer"]:
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_perf_md_names_every_layer(bench):
    with open(os.path.join(REPO, "PERF.md")) as f:
        text = f.read()
    for layer in {m["layer"] for m in bench["per_layer"]}:
        assert layer in text, layer


def _run(args, cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "tpubench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_run_falls_back_to_the_cpu():
    p = _run(["--workload", "train-345m-1chip", "--seed", "1", "--seconds",
              "1", "--trace", "0"], REPO)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_nothing_runs_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "tpubench"), tmp_path / "tpubench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "train-345m-1chip", "--seed", "1", "--seconds",
              "1", "--trace", "0"], tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
