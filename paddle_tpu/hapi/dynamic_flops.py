"""paddle.flops — per-layer FLOPs/params accounting (reference:
python/paddle/hapi/dynamic_flops.py): forward-post hooks record each leaf
layer's multiply-accumulate count from its real input/output shapes, summed
over one dry forward. On TPU the number doubles as the MFU denominator —
``benchmark/flops.py``'s analytic formulas are the model-specific fast
path; this is the generic layer-walk.

The per-op formulas themselves live in ``analysis/cost_model.py``
(``linear_flops``/``conv_flops``/...): the static jaxpr walker and this
layer-hook front end share one accounting, so the two tiers cannot
drift. The hook API (``custom_ops`` mapping layer classes to
``fn(layer, x, y) -> flops``) is unchanged."""
from __future__ import annotations

from typing import Optional

import numpy as np

from ..analysis import cost_model as _cm


def _numel(shape):
    n = 1
    for s in shape:
        n *= int(s)
    return n


def _count_conv(layer, x, y):
    return _cm.conv_flops(
        _numel(y.shape),
        int(layer.weight.shape[1]),      # per-group in-channels
        _numel(layer.weight.shape[2:]),  # kernel taps
        getattr(layer, "bias", None) is not None)


def _count_linear(layer, x, y):
    return _cm.linear_flops(_numel(y.shape), int(layer.weight.shape[0]),
                            getattr(layer, "bias", None) is not None)


def _count_norm(layer, x, y):
    return _cm.norm_flops(_numel(x.shape))


def _count_act(layer, x, y):
    return _cm.activation_flops(_numel(y.shape))


def _count_pool(layer, x, y):
    ks = getattr(layer, "kernel_size", 2)
    k = _numel(ks) if isinstance(ks, (list, tuple)) else int(ks) ** 2
    return _cm.pool_flops(_numel(y.shape), k)


_COUNTERS = {
    "Conv1D": _count_conv, "Conv2D": _count_conv, "Conv3D": _count_conv,
    "Conv1DTranspose": _count_conv, "Conv2DTranspose": _count_conv,
    "Conv3DTranspose": _count_conv,
    "Linear": _count_linear,
    "BatchNorm": _count_norm, "BatchNorm1D": _count_norm,
    "BatchNorm2D": _count_norm, "BatchNorm3D": _count_norm,
    "LayerNorm": _count_norm, "GroupNorm": _count_norm,
    "InstanceNorm2D": _count_norm,
    "ReLU": _count_act, "ReLU6": _count_act, "GELU": _count_act,
    "Sigmoid": _count_act, "Tanh": _count_act, "Silu": _count_act,
    "Softmax": _count_act, "LeakyReLU": _count_act,
    "MaxPool1D": _count_pool, "MaxPool2D": _count_pool,
    "MaxPool3D": _count_pool, "AvgPool1D": _count_pool,
    "AvgPool2D": _count_pool, "AvgPool3D": _count_pool,
}


def flops(net, input_size, custom_ops: Optional[dict] = None,
          print_detail: bool = False) -> int:
    """Total forward FLOPs of ``net`` on an ``input_size`` batch (reference
    paddle.flops). ``custom_ops`` maps layer CLASSES to
    ``fn(layer, x, y) -> flops`` counters, like the reference's contract."""
    import paddle_tpu as P

    custom = {cls.__name__: fn for cls, fn in (custom_ops or {}).items()}
    rows = []
    removes = []

    def attach(layer):
        name = type(layer).__name__
        counter = custom.get(name) or _COUNTERS.get(name)
        if counter is None or list(layer.children()):
            return

        def hook(lay, inputs, output, _counter=counter):
            x = inputs[0] if isinstance(inputs, (list, tuple)) else inputs
            y = output[0] if isinstance(output, (list, tuple)) else output
            n_params = int(sum(_numel(p.shape) for p in lay.parameters(
                include_sublayers=False)))
            rows.append((type(lay).__name__, list(np.shape(y)),
                         n_params, int(_counter(lay, x, y))))

        removes.append(layer.register_forward_post_hook(hook))

    for sub in net.sublayers(include_self=True):
        attach(sub)
    was_training = net.training
    net.eval()
    try:
        net(P.to_tensor(np.zeros(input_size, np.float32)))
    finally:
        if was_training:
            net.train()
        for r in removes:
            r.remove()
    total = sum(r[3] for r in rows)
    if print_detail:
        from ..base.log import get_logger

        log = get_logger()
        log.info("%-18s %-20s %12s %14s", "Layer", "Output shape",
                 "Params", "FLOPs")
        for name, shape, n_params, f in rows:
            log.info("%-18s %-20s %12d %14d", name, shape, n_params, f)
        log.info("Total FLOPs: %d  (~%.3f GFLOPs)", total, total / 1e9)
    return total
