"""BENCHMARK.json and the layout: everything found by name, nothing about
one cell in code."""

import glob
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_names_units_and_keys():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)


def test_every_cell_finds_its_files_and_reports_what_it_must():
    b = _bench()
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for w in b["workloads"]:
        assert len(w["why"]) <= 200 and NAME.match(w["traffic"])
        cell = _load("workloads", f"{w['name']}.json")
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        cfg = _load("configs", f"{w['config']}.json")
        assert configs[w["config"]]["file"] == \
            f"benchmarks/configs/{w['config']}.json"
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        traffic = _load("traffic", f"{w['traffic']}.json")
        for mod in (f"jobs/{cell['job']}.py",
                    f"traffic/{traffic['generator']}.py"):
            assert os.path.exists(os.path.join(BENCH, mod)), mod
        here = lambda m: w["name"] in m.get("workloads", cells)  # noqa: E731
        e2e = {m["name"] for m in b["end_to_end"] if here(m)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in b["per_layer"] if here(m)]
        assert layer
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
            assert os.path.exists(os.path.join(
                BENCH, "layer_metrics",
                f"{m['name'].rpartition('.')[2]}.py")), m["name"]


def test_no_cell_config_or_mix_is_named_in_code():
    b = _bench()
    data_names = ({w["name"] for w in b["workloads"]}
                  | {c["name"] for c in b["configs"]}
                  | {w["traffic"] for w in b["workloads"]})
    for path in glob.glob(os.path.join(BENCH, "**", "*.py"), recursive=True):
        if os.sep + "tests" + os.sep in path:
            continue
        with open(path) as f:
            text = f.read()
        for name in data_names:
            assert not re.search(rf"(?<![\w.\-]){re.escape(name)}(?![\w\-])",
                                 text), (path, name)


def test_config_files_hold_the_published_keys():
    want = {"cerebras-gpt-590m": (1536, 12, 6144, 18),
            "cerebras-gpt-1.3b": (2048, 16, 8192, 24)}
    for name, (h, heads, ffn, layers) in want.items():
        cfg = _load("configs", f"{name}.json")
        assert (cfg["n_embd"], cfg["n_head"], cfg["n_inner"],
                cfg["n_layer"]) == (h, heads, ffn, layers)
        assert cfg["n_positions"] == 2048 and cfg["vocab_size"] == 50257
        assert cfg["n_embd"] // cfg["n_head"] == 128
        assert cfg["source"].startswith("https://huggingface.co/cerebras/")
        assert cfg["reduced"] == [] and cfg["assumed"]


def test_a_serving_cells_why_is_one_sentence_that_names_its_rate():
    """The sentence went stale in silence once (the rates of PR 22 stood in
    it through three PRs that changed what they meant)."""
    serving = 0
    for w in _bench()["workloads"]:
        with open(os.path.join(BENCH, "workloads", f"{w['name']}.json")) as f:
            text = f.read()
        cell = json.loads(text)
        if "rate_rps" not in cell:
            continue
        serving += 1
        assert cell["why"] == w["why"], w["name"]
        written = re.search(r'"rate_rps":\s*([0-9.eE+\-]+)', text).group(1)
        assert float(written) == cell["rate_rps"]
        assert re.search(rf"(?<![0-9.]){re.escape(written)} req/s", w["why"]), \
            (w["name"], written, w["why"])
    assert serving >= 3
