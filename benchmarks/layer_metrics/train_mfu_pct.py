"""Required FLOPs per token (flops.py) x measured tokens/s per chip over the
chip's published bf16 peak (peaks.json).  End-to-end utilization, not a
kernel's roofline share."""

from benchmarks import flops


def read(run):
    rate = run["values"].get("train_tokens_per_s_per_chip")
    if rate is None or run.get("peaks") is None:
        return None
    need = flops.train_flops_per_token(run["sizes"], run["seq"])
    return 100.0 * need * rate / run["peaks"]["bf16_flops_per_s"]
