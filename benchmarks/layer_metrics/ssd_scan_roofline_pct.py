"""Least time the chip could take for the chunk scans' work (engine.stats:
the VALID rows of ssm_scan_rows, and one pass of the state in and out a
chunk a layer) over the time the ``ssd_chunk_scan`` kernel took."""
from benchmarks import flops_falcon_h1
from benchmarks.layer_metrics import _readers, ssm_step_roofline_pct


def read(run):
    rows, sz = _readers.stat(run, "ssm_scan_rows"), run.get("ssm_sizes")
    chunks = _readers.stat(run, "prefill_calls")
    if rows is None or chunks is None or not sz:
        return None
    return ssm_step_roofline_pct.share(
        run, "scan", flops_falcon_h1.chunk_scan_work(
            sz, row_layers=rows, chunk_layers=chunks * sz["layers"]))
