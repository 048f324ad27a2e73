"""Int8 inference walkthrough: train -> PTQ calibrate -> export -> int8
Predictor, with an fp32-vs-int8 accuracy comparison.

Run: ``python examples/infer_int8.py``
"""

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import inference as paddle_infer  # noqa: E402
from paddle_tpu import jit, nn, optimizer as opt  # noqa: E402
from paddle_tpu.utils import compile_cache  # noqa: E402
from paddle_tpu.incubate.quant import ImperativePTQ  # noqa: E402


def main():
    compile_cache.configure()
    paddle.seed(0)
    rng = np.random.RandomState(0)
    x = rng.randn(256, 16).astype("float32")
    y = (x[:, :4].sum(1) > 0).astype("int64")

    model = nn.Sequential(nn.Linear(16, 64), nn.ReLU(), nn.Linear(64, 2))
    o = opt.Adam(learning_rate=0.01, parameters=model.parameters())
    loss_fn = nn.CrossEntropyLoss()
    for _ in range(60):
        logits = model(paddle.to_tensor(x))
        loss = loss_fn(logits, paddle.to_tensor(y))
        loss.backward()
        o.step()
        o.clear_grad()

    # post-training quantization: calibrate activation scales, freeze
    ptq = ImperativePTQ()
    model = ptq.quantize(model)
    model(paddle.to_tensor(x[:64]))  # calibration pass
    model = ptq.convert(model)
    model.eval()

    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "mlp_ptq")
        jit.save(model, prefix,
                 input_spec=[jit.InputSpec([None, 16], "float32", "x")])

        fp32 = paddle_infer.create_predictor(paddle_infer.Config(prefix))
        cfg = paddle_infer.Config(prefix)
        cfg.enable_int8(min_weight_elements=0)  # tiny demo weights; the default gate keeps small layers bf16  # int8 x int8 -> int32 on the MXU
        int8 = paddle_infer.create_predictor(cfg)

        (ref,) = fp32.run([x])
        (out,) = int8.run([x])
        ref, out = np.asarray(ref), np.asarray(out)
        acc_fp32 = (ref.argmax(1) == y).mean()
        acc_int8 = (out.argmax(1) == y).mean()
        print(f"int8 matmuls rewritten: {int8._n_int8}")
        print(f"accuracy fp32={acc_fp32:.3f} int8={acc_int8:.3f} "
              f"(max |delta|={np.abs(out - ref).max():.4f})")
        assert acc_int8 >= acc_fp32 - 0.02, "int8 accuracy drop > 2%"
        print("int8 inference example OK")


if __name__ == "__main__":
    main()
