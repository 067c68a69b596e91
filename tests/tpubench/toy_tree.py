"""Builds a temporary copy of the benchmark with a toy configuration, toy
traffic mixes, toy cells and a toy per-layer metric added as files and entries
only: what a later PR does when it adds a cell."""
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "toy")

CELLS = {
    "toy-train": ("toy-train", "train_tok_s_chip", "toy_steps"),
    "toy-dp4": ("toy-train-x4", "train_tok_s_chip", "toy_steps"),
    "toy-offline": ("toy-offline", "serve_tok_s", "toy_tokens_emitted"),
}


def build(dst):
    """Copies BENCHMARK.json and tpubench/ to `dst`, then only adds."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "tpubench"),
                    os.path.join(dst, "tpubench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(dst)
    for sub in ("configs", "traffic", "metrics"):
        for f in os.listdir(os.path.join(TOY, sub)):
            shutil.copy(os.path.join(TOY, sub, f),
                        os.path.join(dst, "tpubench", sub, f))
    path = os.path.join(dst, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-gpt2", "source": "none",
        "file": "tpubench/configs/toy-gpt2.json", "reduced": [],
        "why": "toy width for the CPU tests"})
    for name, (traffic, e2e, layer_metric) in CELLS.items():
        bench["workloads"].append({
            "name": name, "config": "toy-gpt2", "traffic": traffic,
            "chips": 4 if name == "toy-dp4" else 1,
            "why": "CPU test of the harness"})
        entry, = [m for m in bench["end_to_end"] if m["name"] == e2e]
        entry["workloads"].append(name)
    for metric, moves in (("toy_steps", "train_tok_s_chip"),
                          ("toy_tokens_emitted", "setup_s")):
        bench["per_layer"].append({
            "name": metric, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "diagnostics",
            "moves": moves,
            "workloads": [c for c, v in CELLS.items() if v[2] == metric]})
    with open(path, "w") as f:
        json.dump(bench, f)
    return before


def _files(root):
    out = {}
    for d, _, fs in os.walk(os.path.join(root, "tpubench")):
        for f in fs:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def unchanged(dst, before):
    """True if every file that was there before build() added is as it was."""
    now = _files(dst)
    return all(now.get(k) == v for k, v in before.items())
