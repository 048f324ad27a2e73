"""Pipeline-parallel layer container.

Parity: ``/root/reference/python/paddle/distributed/fleet/meta_parallel/
parallel_layers/pp_layers.py`` — ``LayerDesc``:44, ``SharedLayerDesc``:62,
``SegmentLayers``:23, ``PipelineLayer``:76.

TPU-first: PipelineLayer materializes ALL stages' layers in the single SPMD
program (params are jax global arrays); the stage partition is metadata the
pipeline ENGINE (pipeline_engine.py) uses to build the shard_map 1F1B
schedule over the 'pp' mesh axis with ppermute stage transfer — replacing
the reference's send_v2/recv_v2 NCCL p2p (pp_utils/p2p_communication.py).
"""

from __future__ import annotations

import math
from typing import Callable, List, Optional, Sequence, Union

from ....nn.layer_base import Layer, LayerList
from ... import mesh as mesh_mod


class LayerDesc:
    """Deferred layer construction (pp_layers.py:44)."""

    def __init__(self, layer_func, *inputs, **kwargs):
        self.layer_func = layer_func
        self.inputs = inputs
        self.kwargs = kwargs
        if not issubclass(layer_func, Layer):
            raise TypeError("The input of LayerDesc should be Layer")

    def build_layer(self):
        return self.layer_func(*self.inputs, **self.kwargs)

    def __repr__(self):
        return f"LayerDesc({self.layer_func.__name__})"


class SharedLayerDesc(LayerDesc):
    """Weight-shared layer across stages (pp_layers.py:62 — e.g. tied
    embedding/softmax).  In SPMD the weight is one global array, so sharing
    is simple aliasing."""

    def __init__(self, key, layer_func, forward_func=None, shared_weight_attr="weight",
                 *inputs, **kwargs):
        super().__init__(layer_func, *inputs, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class SegmentLayers:
    """Partition N layers into num_parts stages (pp_layers.py:23)."""

    def __init__(self, layers_desc, num_parts, method="uniform"):
        self._layers_desc = layers_desc
        self.method = method
        self.num_parts = num_parts
        self.num_items = len(layers_desc)
        assert self.num_items >= self.num_parts, (
            "layer number should be greater than number of segments"
        )

    def do_segment(self) -> List[int]:
        if self.method == "uniform":
            return self.uniform(self.num_items, self.num_parts)
        if self.method.startswith("layer:"):
            # segment by layer-class name occurrences (pp_layers parity)
            cls_name = self.method.split(":", 1)[1]
            hits = [
                i for i, d in enumerate(self._layers_desc)
                if (d.layer_func.__name__ if isinstance(d, LayerDesc)
                    else d.__class__.__name__) == cls_name
            ]
            assert len(hits) >= self.num_parts
            per = len(hits) // self.num_parts
            result = [0] * (self.num_parts + 1)
            for p in range(1, self.num_parts):
                result[p] = hits[p * per]
            result[self.num_parts] = self.num_items
            return result
        raise ValueError(f"unknown segment method {self.method}")

    @staticmethod
    def uniform(num_items, num_parts) -> List[int]:
        result = [0] * (num_parts + 1)
        part_size = math.floor(num_items / num_parts)
        extra = num_items % num_parts
        for i in range(1, num_parts + 1):
            result[i] = result[i - 1] + part_size + (1 if i <= extra else 0)
        return result


class PipelineLayer(Layer):
    """Parity: pp_layers.py:76.  Holds the FULL layer stack (SPMD) plus the
    stage partition; run_function(stage) gives the stage's callable for the
    pipeline engine; plain __call__ runs the whole stack (single-program
    semantics, used for eval/export and as the autodiff reference)."""

    def __init__(self, layers, num_stages=None, topology=None, loss_fn=None,
                 seg_method="uniform", recompute_interval=0):
        super().__init__()
        self._loss_fn = loss_fn
        self._topo = topology
        if num_stages is None and topology is not None:
            num_stages = topology.get_dim("pipe")
        self._num_stages = num_stages or max(mesh_mod.axis_size("pp"), 1)
        self._layers_desc = list(layers)
        self._recompute_interval = recompute_interval

        seg = SegmentLayers(self._layers_desc, self._num_stages, seg_method)
        self.segment_parts = seg.do_segment()

        # build ALL layers (SPMD global program) — shared descs built once
        self._shared = {}
        built = []
        for d in self._layers_desc:
            if isinstance(d, SharedLayerDesc):
                if d.layer_name not in self._shared:
                    self._shared[d.layer_name] = d.build_layer()
                built.append((self._shared[d.layer_name], d.forward_func))
            elif isinstance(d, LayerDesc):
                built.append((d.build_layer(), None))
            elif isinstance(d, Layer):
                built.append((d, None))
            elif callable(d):
                built.append((d, None))
            else:
                raise TypeError(f"bad layer desc {d!r}")
        self._funcs = built
        self.run_functions = LayerList(
            [l for l, _ in built if isinstance(l, Layer)]
        )

    def get_num_stages(self):
        return self._num_stages

    def get_stage_from_index(self, layer_idx) -> int:
        for s in range(self._num_stages):
            if self.segment_parts[s] <= layer_idx < self.segment_parts[s + 1]:
                return s
        return self._num_stages - 1

    def stage_layers(self, stage: int):
        lo, hi = self.segment_parts[stage], self.segment_parts[stage + 1]
        return self._funcs[lo:hi]

    def run_function(self, stage: int) -> Callable:
        funcs = self.stage_layers(stage)

        def run(x):
            for layer, fwd in funcs:
                if fwd is not None:
                    x = fwd(layer, x)
                elif isinstance(x, tuple):
                    x = layer(*x)
                else:
                    x = layer(x)
            return x

        return run

    def forward(self, x):
        for layer, fwd in self._funcs:
            if fwd is not None:
                x = fwd(layer, x)
            elif isinstance(x, tuple):
                x = layer(*x)
            else:
                x = layer(x)
        return x
