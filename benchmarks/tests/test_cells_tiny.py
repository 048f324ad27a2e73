"""Every kind of cell end to end on the CPU at a tiny, test-only preset
(``tests/tiny``): the control flow of ``run.execute``, the jobs, the
generators, the reference checks and the counter-based readers.  Each tiny
cell names the real cell it ``stands_for`` and reports that cell's metrics:
the tiny ``BENCHMARK.json`` is derived from the real one.  Nothing here is a
device metric; the Pallas kernels are absent on the CPU, so the
``compiled_kernels`` check is the one expected to fail."""

import glob
import json
import os
import time

import jax
import pytest

from benchmarks import run

TINY = os.path.join(os.path.dirname(__file__), "tiny")
REAL = os.path.join(run.CHECKOUT, "BENCHMARK.json")


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    """The real ``BENCHMARK.json`` with every cell replaced by the tiny cell
    that stands for it."""
    bench = run.load_json(REAL)
    tiny = {}
    for path in glob.glob(os.path.join(TINY, "workloads", "*.json")):
        cell = run.load_json(path)
        tiny[cell["stands_for"]] = dict(
            name=os.path.basename(path)[:-len(".json")],
            config=cell["config"], traffic=cell["traffic"],
            chips=cell["chips"], why=cell["why"])
    assert set(tiny) == {w["name"] for w in bench["workloads"]}
    bench["workloads"] = [tiny[w["name"]] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [tiny[w]["name"] for w in m["workloads"]]
    out = tmp_path_factory.mktemp("tiny") / "BENCHMARK.json"
    out.write_text(json.dumps(bench))
    return str(out)


def _execute(bench_file, cell, trace, monkeypatch):
    failed = []
    monkeypatch.setattr(run.Context, "log", lambda self, msg: (
        failed.append(msg) if msg.startswith("CHECK FAILED") else None))
    res = run.execute(bench_file, TINY, cell, 3, 1.5, trace,
                      run.device_info(), time.perf_counter())
    assert failed == ["CHECK FAILED: compiled_kernels"], failed
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert res["correct"] is False and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    return res


def test_train_cell(bench_file, monkeypatch):
    res = _execute(bench_file, "tiny-train", False, monkeypatch)
    assert res["attempted"] > 3
    assert set(res["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert res["metrics"]["setup_s"]["unit"] == "s"


def test_train_cell_traced_reports_counters_only_on_cpu(bench_file,
                                                        monkeypatch):
    res = _execute(bench_file, "tiny-train", True, monkeypatch)
    # no device plane in a CPU trace: the trace readers return nothing
    assert set(res["metrics"]) == {"train_step_ms", "train.window_compiles"}
    assert res["metrics"]["train.window_compiles"]["value"] == 0
    assert "breakdown" not in res


def test_train_cell_on_a_dp2_mp2_mesh(bench_file, monkeypatch):
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    from paddle_tpu.distributed import mesh as mesh_mod

    old = mesh_mod.get_mesh()
    try:
        res = _execute(bench_file, "tiny-train-4dev", True, monkeypatch)
    finally:
        mesh_mod.set_mesh(old)
    assert res["metrics"]["train.window_compiles"]["value"] == 0


def test_chat_cell(bench_file, monkeypatch):
    res = _execute(bench_file, "tiny-chat", False, monkeypatch)
    assert res["attempted"] == 9          # round(6 req/s x 1.5 s)
    assert set(res["metrics"]) == {"tbt_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_chat_cell_traced(bench_file, monkeypatch):
    res = _execute(bench_file, "tiny-chat", True, monkeypatch)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["chat.window_compiles"] == 0 and m["chat.preemptions"] == 0
    assert 1.0 <= m["chat.decode_batch_mean"] <= 4.0
    assert 0 < m["chat.kv_pool_peak_pct"] <= 100
    assert m["chat_slo_attained_pct"] == 100.0
    assert m["ttft_p95_ms"] >= m["chat.ttft_p50_ms"] > 0


def test_doc_cell_drains_every_scored_request(bench_file, monkeypatch):
    res = _execute(bench_file, "tiny-doc", False, monkeypatch)
    assert res["attempted"] == 9 and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["metrics"]["serve_tokens_per_s"]["value"] > 0
    res = _execute(bench_file, "tiny-doc", True, monkeypatch)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["doc.window_compiles"] == 0 and m["drain_s"] >= 0
    assert m["completed_tokens_per_s"] > 0


def test_an_undrained_request_is_failed(bench_file, monkeypatch):
    """A drain that ends at once leaves scored requests incomplete: each is
    ``failed`` and earns the served rate nothing."""
    real = run.load_json

    def load(path):
        cell = real(path)
        if path.endswith(os.path.join("workloads", "tiny-doc.json")):
            cell = dict(cell, drain_s=0, rate_rps=40.0)
        return cell

    monkeypatch.setattr(run, "load_json", load)
    monkeypatch.setattr(run.Context, "log", lambda self, msg: None)
    res = run.execute(bench_file, TINY, "tiny-doc", 3, 1.5, False,
                      run.device_info(), time.perf_counter())
    assert res["attempted"] == 60 and 0 < res["failed"] < 60


def test_no_tpu_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.require_tpu(1)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
