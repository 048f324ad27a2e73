"""``layer_metrics/prefill_pages_walked_pct.py`` (PR 32): the share of a
slot's block-table entries that the chunk kernel walked, from two counters
of ``engine.stats``; a program without them (the parent of PR 32) gives
``None``, and the three entries of ``BENCHMARK.json`` name the reader."""

import os

import pytest

from benchmarks import run as bench_run
from benchmarks.layer_metrics import prefill_pages_walked_pct as reader


@pytest.mark.parametrize("walked, table, want", [
    # longshort: 61 of 256 on the full layer, 39 on each sliding one
    (61 + 3 * 39, 4 * 256, 100.0 * 178 / 1024),
    (32 * 24, 32 * 24, 100.0),                      # the last chunk, 1.3b
    (0, 768, 0.0)])
def test_share_of_the_table_walked(walked, table, want):
    run = {"trace": None, "stats": {"prefill_pages_walked": walked,
                                    "prefill_pages_in_table": table}}
    assert reader.read(run) == pytest.approx(want)


@pytest.mark.parametrize("stats", [
    None, {},
    # the counters of the engine before PR 32
    {"prefill_calls": 40, "decode_pages_walked": 900,
     "decode_pages_in_table": 9000},
    # no chunk dispatch in the window: no division by zero
    {"prefill_pages_walked": 0, "prefill_pages_in_table": 0}])
def test_without_its_counters_it_reports_nothing(stats):
    assert reader.read({"trace": None, "stats": stats}) is None
    assert reader.read({"trace": None}) is None


def test_benchmark_json_enters_it_for_the_three_serving_cells():
    bench = bench_run.load_json(os.path.join(bench_run.CHECKOUT,
                                             "BENCHMARK.json"))
    mine = [m for m in bench["per_layer"]
            if m["name"].endswith(".prefill_pages_walked_pct")]
    assert [(m["name"], m["moves"], m["workloads"]) for m in mine] == [
        ("longshort.prefill_pages_walked_pct", "serve_tokens_per_s",
         ["serve-command-a-plus-longshort"]),
        ("doc.prefill_pages_walked_pct", "serve_tokens_per_s",
         ["serve-1.3b-doc"]),
        ("chat.prefill_pages_walked_pct", "tbt_p95_ms", ["serve-1.3b-chat"])]
    assert {m["layer"] for m in mine} == {"kernels"}
    assert {m["source"] for m in mine} == {"program_counter"}
    assert {m["better"] for m in mine} == {"lower"}
    # the newest entries: appended, nothing before them moved
    assert bench["per_layer"][-3:] == mine
