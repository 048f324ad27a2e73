"""``layer_metrics/decode_pages_walked_pct.py`` (PR 28): the share of the
block tables' entries that the decode kernels walked, from two counters of
``engine.stats``; a program without them (the parent of PR 28) gives
``None``, and the two entries of ``BENCHMARK.json`` name the reader."""

import os

import pytest

from benchmarks import run as bench_run
from benchmarks.layer_metrics import decode_pages_walked_pct as reader


@pytest.mark.parametrize("walked, table, want", [
    (80 * 24, 64 * 32 * 24, 100.0 * 80 / 2048),     # chat: ~80 live pairs
    (8192, 8192, 100.0),                            # every slot full
    (0, 2048, 0.0)])
def test_share_of_the_table_walked(walked, table, want):
    run = {"trace": None, "stats": {"decode_pages_walked": walked,
                                    "decode_pages_in_table": table}}
    assert reader.read(run) == pytest.approx(want)


@pytest.mark.parametrize("stats", [
    None, {},
    # the counters of the engine before PR 28
    {"decode_calls": 40, "decode_attended_tokens": 900},
    # no decode dispatch in the window: no division by zero
    {"decode_pages_walked": 0, "decode_pages_in_table": 0}])
def test_without_its_counters_it_reports_nothing(stats):
    assert reader.read({"trace": None, "stats": stats}) is None
    assert reader.read({"trace": None}) is None


def test_benchmark_json_enters_it_for_the_two_cells_that_decode_long_tables():
    bench = bench_run.load_json(os.path.join(bench_run.CHECKOUT,
                                             "BENCHMARK.json"))
    mine = [m for m in bench["per_layer"]
            if m["name"].endswith(".decode_pages_walked_pct")]
    assert [(m["name"], m["moves"], m["workloads"]) for m in mine] == [
        ("chat.decode_pages_walked_pct", "tbt_p95_ms", ["serve-1.3b-chat"]),
        ("longshort.decode_pages_walked_pct", "serve_tokens_per_s",
         ["serve-command-a-plus-longshort"])]
    assert {m["layer"] for m in mine} == {"kernels"}
    assert {m["source"] for m in mine} == {"program_counter"}
