"""ResNet-50 train-step throughput probe — thin sweep wrapper over the
bench.py section (single source of truth for the harness + MFU math)."""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
from paddle_tpu.utils import compile_cache

if __name__ == "__main__":
    compile_cache.configure()
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--k", type=int, default=20)
    p.add_argument("--fmt", default="NHWC")
    p.add_argument("--depth", type=int, default=50)
    args = p.parse_args()
    r = bench._resnet50_bench(batch=args.batch, k=args.k,
                              data_format=args.fmt, depth=args.depth)
    print(r)
