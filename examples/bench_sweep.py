"""Perf sweep harness over the flagship GPT bench point (TPU only).

Usage: python examples/bench_sweep.py "batch,remat,ce_rows,seq[,dtype]" ...
  remat: 0 = off, 1 = full, d = dots (selective)
  dtype: bf16 (default; bf16 params + fp32 masters) or mb16
         (fp32 params as masters, cast-on-read bf16 compute)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
from paddle_tpu.models import GPTConfig
from paddle_tpu.utils import compile_cache


def main():
    compile_cache.configure()
    specs = sys.argv[1:] or ["12,0,2048,1024"]
    for spec in specs:
        parts = spec.split(",")
        b, r, ce, seq = parts[:4]
        dtype = {"bf16": "bfloat16", "mb16": "master-bf16"}[
            parts[4] if len(parts) > 4 else "bf16"]
        remat = {"0": False, "1": True, "d": "dots"}[r]
        cfg = GPTConfig(vocab_size=50304, hidden_size=1536, num_layers=24,
                        num_heads=12, max_seq_len=int(seq), dropout=0.0)
        try:
            out = bench._run(cfg, batch=int(b), seq=int(seq), steps=10,
                             peak_flops=197e12, dtype=dtype,
                             remat=remat, ce_rows=int(ce))
            print(f"b={b} remat={r} ce={ce} seq={seq} {dtype}: "
                  f"mfu={out['mfu']:.4f} tps={out['tokens_per_sec']:.0f}",
                  flush=True)
        except Exception as e:
            print(f"b={b} remat={r} ce={ce} seq={seq} {dtype}: FAIL "
                  f"{type(e).__name__} {str(e)[:120]}", flush=True)


if __name__ == "__main__":
    main()
