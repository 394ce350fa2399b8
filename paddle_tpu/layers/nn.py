"""Neural-network layer functions building program ops.

≙ reference python/paddle/fluid/layers/nn.py (4.3k LoC, 60+ layers: fc:45,
embedding:153, conv2d:1172, batch_norm:1551, layer_norm:1668, ...). Each
function appends ops to the default main program via LayerHelper and returns
the output VarDesc.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import numpy as np

from ..core.program import VarDesc, default_main_program
from ..layer_helper import LayerHelper
from ..initializer import ConstantInitializer, NormalInitializer

__all__ = [
    "fc", "embedding", "dropout", "cross_entropy", "square_error_cost",
    "conv2d", "conv2d_transpose", "pool2d", "batch_norm", "layer_norm",
    "rms_norm",
    "softmax", "softmax_with_cross_entropy", "sigmoid_cross_entropy_with_logits",
    "matmul", "topk", "reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
    "reduce_prod", "mean", "mul", "dot_product", "l2_normalize", "one_hot",
    "transpose", "reshape", "concat", "split", "stack", "unstack", "expand",
    "squeeze", "unsqueeze", "flatten", "pad", "im2sequence", "lrn", "prelu",
    "relu", "log", "crop", "elementwise_add", "elementwise_sub",
    "elementwise_mul", "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow", "clip", "clip_by_norm", "scale", "cast", "gather",
    "scatter", "slice", "shape", "maxout", "smooth_l1", "warpctc",
    "label_smooth", "bilinear_interp", "resize_bilinear", "random_crop",
    "nce", "row_conv", "mean_iou", "bpr_loss", "spp", "moe_ffn",
    "moe_gated_ffn",
    "conv3d", "pool3d", "cos_sim", "multiplex", "dice_loss", "image_resize",
    "image_resize_short", "gru_unit", "lstm_unit", "uniform_random",
    "uniform_random_batch_size_like", "gaussian_random",
    "gaussian_random_batch_size_like",
]


def _current_block():
    return default_main_program().current_block()


# ---------------------------------------------------------------------------
# Core layers
# ---------------------------------------------------------------------------

def fc(input, size: int, num_flatten_dims: int = 1, param_attr=None,
       bias_attr=None, act=None, is_test=False, name=None,
       precision=None) -> VarDesc:
    """Fully connected (layers/nn.py:45): per-input mul + sum + bias + act.
    `precision`: the `mul` op's ("high" | "highest"; None: the backend's
    default)."""
    more = {"precision": precision} if precision else {}
    helper = LayerHelper("fc", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = helper.input_dtype()
    mul_results = []
    seq_src = None
    flatten_used = num_flatten_dims
    inputs_list = helper.multiple_input()
    for in_idx, input_var in enumerate(inputs_list):
        input_shape = input_var.shape
        flatten = num_flatten_dims
        # per-timestep fc on padded sequences (the reference's [T_total, D]
        # row-major sequence fc becomes [B, T, D] with x_num_col_dims=2)
        if getattr(input_var, "seq_len_var", None) and len(input_shape) > 2 \
                and num_flatten_dims == 1:
            flatten = len(input_shape) - 1
            seq_src = input_var
        flatten_used = max(flatten_used, flatten)
        param_shape = [int(np.prod(input_shape[flatten:]))] + [size]
        pa = ParamAttr_to(param_attr)
        if pa.name is not None and len(inputs_list) > 1:
            pa.name = f"{pa.name}_{in_idx}"  # one weight per fc input
        w = helper.create_parameter(pa, param_shape, dtype)
        tmp = helper.create_tmp_variable(dtype)
        helper.append_op("mul", {"X": input_var, "Y": w}, {"Out": tmp},
                         {"x_num_col_dims": flatten, "y_num_col_dims": 1,
                          **more})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_tmp_variable(dtype)
        helper.append_op("sum", {"X": mul_results}, {"Out": pre_bias})
    if bias_attr is not False:
        # bias spans the feature (last) axis: alignment follows the flatten
        # point, not the (possibly unknown at build time) tmp-var shape
        pre_act = helper.append_bias_op(pre_bias, dim_start=flatten_used,
                                        size=[size])
    else:
        pre_act = pre_bias
    out = helper.append_activation(pre_act)
    if seq_src is not None:
        from .sequence import propagate_seq
        propagate_seq(seq_src, out)
    return out


def ParamAttr_to(attr):
    from ..param_attr import ParamAttr
    a = ParamAttr.to_attr(attr)
    # each fc input needs a fresh weight: clone to avoid name reuse
    from ..param_attr import ParamAttr as PA
    return PA(name=a.name, initializer=a.initializer,
              learning_rate=a.learning_rate, regularizer=a.regularizer,
              trainable=a.trainable, gradient_clip=a.gradient_clip)


def embedding(input, size: Sequence[int], is_sparse: bool = False,
              is_distributed: bool = False, padding_idx: Optional[int] = None,
              param_attr=None, dtype: str = "float32") -> VarDesc:
    """layers/nn.py:153.

    is_sparse=True → RowSparseGrad gradients (≙ SelectedRows,
    lookup_table_op.cc sparse path; see core/selected_rows.py).
    is_distributed=True → the table is annotated vocab-sharded over the
    ('tp','dp') mesh axes; under a sharded executor GSPMD partitions the
    gather across devices and each device stores only its vocab slice
    (≙ the distributed lookup table, distribute_transpiler.py:120-180,
    re-read as a sharding annotation instead of pserver prefetch RPCs —
    see docs/distributed_embedding.md for the sync-only decision)."""
    helper = LayerHelper("embedding", param_attr=param_attr)
    w = helper.create_parameter(helper.param_attr, size, dtype)
    if is_distributed:
        # vocab (dim 0) sharded over tp and/or dp — whichever axes the
        # runtime mesh actually has (spec_for drops absent axes)
        from ..parallel.mesh import DP, TP
        w.sharding = ((TP, DP), None)
    tmp = helper.create_tmp_variable(dtype)
    padding_idx = -1 if padding_idx is None else (
        padding_idx if padding_idx >= 0 else size[0] + padding_idx)
    helper.append_op("lookup_table", {"Ids": input, "W": w}, {"Out": tmp},
                     {"is_sparse": is_sparse, "is_distributed": is_distributed,
                      "padding_idx": padding_idx})
    if getattr(input, "seq_len_var", None):
        from .sequence import propagate_seq
        propagate_seq(input, tmp)
        tmp.shape = tuple(input.shape[:2]) + (size[1],)
        tmp.dtype = dtype
    return tmp


def dropout(x, dropout_prob: float, is_test: bool = False, seed=None,
            name=None) -> VarDesc:
    helper = LayerHelper("dropout", name=name)
    out = helper.create_tmp_variable(x.dtype)
    mask = helper.create_tmp_variable(x.dtype)
    mask.stop_gradient = True
    helper.append_op("dropout", {"X": x}, {"Out": out, "Mask": mask},
                     {"dropout_prob": dropout_prob, "is_test": is_test,
                      "seed": seed if seed is not None else 0})
    return out


def cross_entropy(input, label, soft_label: bool = False) -> VarDesc:
    helper = LayerHelper("cross_entropy")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("cross_entropy", {"X": input, "Label": label}, {"Y": out},
                     {"soft_label": soft_label})
    return out


def square_error_cost(input, label) -> VarDesc:
    helper = LayerHelper("square_error_cost")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("square_error_cost", {"X": input, "Y": label}, {"Out": out})
    return out


def conv2d(input, num_filters: int, filter_size, stride=1, padding=0,
           dilation=1, groups=None, param_attr=None, bias_attr=None,
           use_cudnn: bool = True, use_mkldnn: bool = False, act=None,
           name=None) -> VarDesc:
    """layers/nn.py:1172 (NCHW). use_cudnn/use_mkldnn accepted+ignored: XLA
    owns kernel selection on TPU."""
    helper = LayerHelper("conv2d", param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    dtype = input.dtype
    num_channels = input.shape[1]
    groups = groups or 1
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    filter_shape = [num_filters, num_channels // groups] + list(filter_size)

    def _std(shape):
        fan_in = (num_channels // groups) * filter_size[0] * filter_size[1]
        return (2.0 / fan_in) ** 0.5

    w = helper.create_parameter(helper.param_attr, filter_shape, dtype,
                                default_initializer=NormalInitializer(0.0, _std(filter_shape)))
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op("conv2d", {"Input": input, "Filter": w}, {"Output": pre_bias},
                     {"strides": _pair(stride), "paddings": _pair(padding),
                      "dilations": _pair(dilation), "groups": groups,
                      "use_cudnn": use_cudnn})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2) \
        if bias_attr is not False else pre_bias
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters: int, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, use_cudnn=True, act=None,
                     name=None) -> VarDesc:
    helper = LayerHelper("conv2d_transpose", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    in_channels = input.shape[1]
    groups = groups or 1
    if filter_size is None:
        raise ValueError("filter_size must be set (output_size inference TBD)")
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    filter_shape = [in_channels, num_filters // groups] + list(filter_size)
    w = helper.create_parameter(helper.param_attr, filter_shape, dtype)
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op("conv2d_transpose", {"Input": input, "Filter": w},
                     {"Output": pre_bias},
                     {"strides": _pair(stride), "paddings": _pair(padding),
                      "dilations": _pair(dilation), "groups": groups})
    pre_act = helper.append_bias_op(pre_bias, 1, 2) if bias_attr is not False else pre_bias
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type: str = "max", pool_stride=1,
           pool_padding=0, global_pooling: bool = False, use_cudnn=True,
           ceil_mode: bool = False, name=None, exclusive=True) -> VarDesc:
    helper = LayerHelper("pool2d", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("pool2d", {"X": input}, {"Out": out},
                     {"pooling_type": pool_type, "ksize": _pair(pool_size),
                      "strides": _pair(pool_stride), "paddings": _pair(pool_padding),
                      "global_pooling": global_pooling, "ceil_mode": ceil_mode,
                      "exclusive": exclusive})
    return out


def _bn_state_vars(helper, pshape, dtype, param_attr, bias_attr,
                   moving_mean_name=None, moving_variance_name=None):
    """Batch-norm state creation: scale/bias params, persistable f32
    running mean/var, saved-stat tmp vars."""
    scale = helper.create_parameter(
        param_attr, pshape, dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bias_attr, pshape, dtype, is_bias=True)
    mean = helper.create_global_variable(
        name=moving_mean_name, dtype="float32", shape=pshape,
        persistable=True)
    mean.stop_gradient = True
    helper.set_variable_initializer(mean, ConstantInitializer(0.0))
    variance = helper.create_global_variable(
        name=moving_variance_name, dtype="float32", shape=pshape,
        persistable=True)
    variance.stop_gradient = True
    helper.set_variable_initializer(variance, ConstantInitializer(1.0))
    saved_mean = helper.create_tmp_variable("float32", stop_gradient=True)
    saved_var = helper.create_tmp_variable("float32", stop_gradient=True)
    return scale, bias, mean, variance, saved_mean, saved_var


def batch_norm(input, act=None, is_test: bool = False, momentum: float = 0.9,
               epsilon: float = 1e-5, param_attr=None, bias_attr=None,
               data_layout: str = "NCHW", in_place: bool = False, name=None,
               moving_mean_name=None, moving_variance_name=None,
               do_model_average_for_mean_and_var=False) -> VarDesc:
    """layers/nn.py:1551. Running mean/var are persistable state vars updated
    functionally each step (MeanOut/VarianceOut rebind the same names)."""
    helper = LayerHelper("batch_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    channels = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    pshape = [channels]
    scale, bias, mean, variance, saved_mean, saved_var = _bn_state_vars(
        helper, pshape, dtype, helper.param_attr, helper.bias_attr,
        moving_mean_name, moving_variance_name)
    out = helper.create_tmp_variable(dtype)
    # a relu activation folds into the op itself (≙ the reference op's
    # fuse_with_relu attr): the op's custom VJP then recomputes the mask in
    # backward instead of keeping a separate relu residual chain
    fuse_relu = act == "relu"
    helper.append_op("batch_norm",
                     {"X": input, "Scale": scale, "Bias": bias,
                      "Mean": mean, "Variance": variance},
                     {"Y": out, "MeanOut": mean, "VarianceOut": variance,
                      "SavedMean": saved_mean, "SavedVariance": saved_var},
                     {"momentum": momentum, "epsilon": epsilon,
                      "is_test": is_test, "data_layout": data_layout,
                      "fuse_with_relu": fuse_relu})
    return out if fuse_relu else helper.append_activation(out)


def layer_norm(input, scale: bool = True, shift: bool = True,
               begin_norm_axis: int = 1, epsilon: float = 1e-5,
               param_attr=None, bias_attr=None, act=None, name=None) -> VarDesc:
    helper = LayerHelper("layer_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    param_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {"X": input}
    if scale:
        inputs["Scale"] = helper.create_parameter(
            helper.param_attr, param_shape, dtype,
            default_initializer=ConstantInitializer(1.0))
    if shift:
        inputs["Bias"] = helper.create_parameter(
            helper.bias_attr, param_shape, dtype, is_bias=True)
    mean_out = helper.create_tmp_variable("float32", stop_gradient=True)
    var_out = helper.create_tmp_variable("float32", stop_gradient=True)
    out = helper.create_tmp_variable(dtype)
    helper.append_op("layer_norm", inputs,
                     {"Y": out, "Mean": mean_out, "Variance": var_out},
                     {"epsilon": epsilon, "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out)


def rms_norm(input, begin_norm_axis: int = 1, epsilon: float = 1e-5,
             param_attr=None, name=None) -> VarDesc:
    """x / sqrt(mean(x^2) + eps) * g over dims >= begin_norm_axis; the
    gain starts at 1. No mean, no bias (ops/nn_ops.py rms_norm)."""
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    gain = helper.create_parameter(
        helper.param_attr, [int(np.prod(input.shape[begin_norm_axis:]))],
        input.dtype, default_initializer=ConstantInitializer(1.0))
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("rms_norm", {"X": input, "Scale": gain}, {"Y": out},
                     {"epsilon": epsilon,
                      "begin_norm_axis": begin_norm_axis})
    return out


# ---------------------------------------------------------------------------
# Simple wrappers
# ---------------------------------------------------------------------------

def _pair(v):
    return list(v) if isinstance(v, (list, tuple)) else [v, v]


def _unary(op_type, x, attrs=None, out_dtype=None, extra_outputs=None):
    helper = LayerHelper(op_type)
    out = helper.create_tmp_variable(out_dtype or x.dtype)
    outputs = {"Out": out}
    for slot in (extra_outputs or []):
        ev = helper.create_tmp_variable(x.dtype)
        ev.stop_gradient = True
        outputs[slot] = ev
    helper.append_op(op_type, {"X": x}, outputs, attrs or {})
    return out


def _binary(op_type, x, y, attrs=None):
    helper = LayerHelper(op_type)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(op_type, {"X": x, "Y": y}, {"Out": out}, attrs or {})
    return out


def softmax(input, use_cudnn=True, name=None):
    return _unary("softmax", input)


def relu(x, name=None):
    return _unary("relu", x)


def log(x, name=None):
    return _unary("log", x)


def softmax_with_cross_entropy(logits, label, soft_label=False):
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax_out = helper.create_tmp_variable(logits.dtype)
    loss = helper.create_tmp_variable(logits.dtype)
    helper.append_op("softmax_with_cross_entropy",
                     {"Logits": logits, "Label": label},
                     {"Loss": loss, "Softmax": softmax_out},
                     {"soft_label": soft_label})
    return loss


def sigmoid_cross_entropy_with_logits(x, label, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("sigmoid_cross_entropy_with_logits",
                     {"X": x, "Label": label}, {"Out": out})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None,
           precision=None):
    helper = LayerHelper("matmul")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("matmul", {"X": x, "Y": y}, {"Out": out},
                     {"transpose_X": transpose_x, "transpose_Y": transpose_y,
                      "alpha": alpha,
                      **({"precision": precision} if precision else {})})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1):
    helper = LayerHelper("mul")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("mul", {"X": x, "Y": y}, {"Out": out},
                     {"x_num_col_dims": x_num_col_dims,
                      "y_num_col_dims": y_num_col_dims})
    return out


def dot_product(x, y):
    return reduce_sum(elementwise_mul(x, y), dim=-1, keep_dim=True)


def topk(input, k):
    helper = LayerHelper("top_k")
    values = helper.create_tmp_variable(input.dtype)
    indices = helper.create_tmp_variable("int64")
    indices.stop_gradient = True
    helper.append_op("top_k", {"X": input}, {"Out": values, "Indices": indices},
                     {"k": k})
    return values, indices


def _reduce(op_type, input, dim, keep_dim, name=None):
    helper = LayerHelper(op_type)
    out = helper.create_tmp_variable(input.dtype)
    if dim is None:
        attrs = {"dim": [0], "keep_dim": keep_dim, "reduce_all": True}
    else:
        attrs = {"dim": dim if isinstance(dim, list) else [dim],
                 "keep_dim": keep_dim, "reduce_all": False}
    helper.append_op(op_type, {"X": input}, {"Out": out}, attrs)
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_sum", input, dim, keep_dim)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_mean", input, dim, keep_dim)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_max", input, dim, keep_dim)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_min", input, dim, keep_dim)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_prod", input, dim, keep_dim)


def mean(x, name=None):
    return _unary("mean", x)


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize")
    out = helper.create_tmp_variable(x.dtype)
    norm = helper.create_tmp_variable(x.dtype)
    norm.stop_gradient = True
    helper.append_op("l2_normalize", {"X": x}, {"Out": out, "Norm": norm},
                     {"axis": axis, "epsilon": epsilon})
    return out


def one_hot(input, depth):
    return _unary("one_hot", input, {"depth": depth}, out_dtype="float32")


def transpose(x, perm, name=None):
    return _unary("transpose", x, {"axis": list(perm)})


def reshape(x, shape, actual_shape=None, act=None, inplace=True, name=None):
    out = _unary("reshape", x, {"shape": list(shape)})
    if act:
        return _unary(act, out)
    return out


def concat(input, axis=0, name=None):
    helper = LayerHelper("concat")
    out = helper.create_tmp_variable(helper.input_dtype() if False else input[0].dtype)
    helper.append_op("concat", {"X": list(input)}, {"Out": out}, {"axis": axis})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split")
    dim = dim if dim >= 0 else dim + len(input.shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        attrs = {"num": num, "sections": [], "axis": dim}
    else:
        num = len(num_or_sections)
        attrs = {"num": 0, "sections": list(num_or_sections), "axis": dim}
    outs = [helper.create_tmp_variable(input.dtype) for _ in range(num)]
    helper.append_op("split", {"X": input}, {"Out": outs}, attrs)
    return outs


def stack(x, axis=0):
    helper = LayerHelper("stack")
    out = helper.create_tmp_variable(x[0].dtype)
    helper.append_op("stack", {"X": list(x)}, {"Y": out}, {"axis": axis})
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    num = num if num is not None else x.shape[axis]
    outs = [helper.create_tmp_variable(x.dtype) for _ in range(num)]
    helper.append_op("unstack", {"X": x}, {"Y": outs}, {"axis": axis, "num": num})
    return outs


def expand(x, expand_times, name=None):
    return _unary("expand", x, {"expand_times": list(expand_times)})


def squeeze(input, axes, name=None):
    return _unary("squeeze", input, {"axes": list(axes)})


def unsqueeze(input, axes, name=None):
    return _unary("unsqueeze", input, {"axes": list(axes)})


def flatten(x, axis=1, name=None):
    return _unary("flatten", x, {"axis": axis})


def pad(x, paddings, pad_value=0.0, name=None):
    return _unary("pad", x, {"paddings": list(paddings), "pad_value": pad_value})


def crop(x, shape=None, offsets=None, name=None):
    helper = LayerHelper("crop")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("crop", {"X": x}, {"Out": out},
                     {"shape": list(shape), "offsets": list(offsets or [0] * len(shape))})
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None):
    helper = LayerHelper("im2sequence")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("im2sequence", {"X": input}, {"Out": out},
                     {"kernels": _pair(filter_size), "strides": _pair(stride),
                      "paddings": _pair(padding) + _pair(padding)})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn")
    out = helper.create_tmp_variable(input.dtype)
    mid = helper.create_tmp_variable(input.dtype)
    mid.stop_gradient = True
    helper.append_op("lrn", {"X": input}, {"Out": out, "MidOut": mid},
                     {"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def prelu(x, mode="all", param_attr=None, name=None):
    helper = LayerHelper("prelu", param_attr=param_attr, name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = list(x.shape[1:])
    alpha = helper.create_parameter(helper.param_attr, alpha_shape, x.dtype,
                                    default_initializer=ConstantInitializer(0.25))
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("prelu", {"X": x, "Alpha": alpha}, {"Out": out}, {"mode": mode})
    return out


def maxout(x, groups, name=None):
    return _unary("maxout", x, {"groups": groups})


def elementwise_add(x, y, axis=-1, act=None, name=None):
    out = _binary("elementwise_add", x, y, {"axis": axis})
    return _unary(act, out) if act else out


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    out = _binary("elementwise_sub", x, y, {"axis": axis})
    return _unary(act, out) if act else out


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    out = _binary("elementwise_mul", x, y, {"axis": axis})
    return _unary(act, out) if act else out


def elementwise_div(x, y, axis=-1, act=None, name=None):
    out = _binary("elementwise_div", x, y, {"axis": axis})
    return _unary(act, out) if act else out


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return _binary("elementwise_max", x, y, {"axis": axis})


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return _binary("elementwise_min", x, y, {"axis": axis})


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return _binary("elementwise_pow", x, y, {"axis": axis})


def clip(x, min, max, name=None):
    return _unary("clip", x, {"min": float(min), "max": float(max)})


def clip_by_norm(x, max_norm, name=None):
    return _unary("clip_by_norm", x, {"max_norm": float(max_norm)})


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    out = _unary("scale", x, {"scale": float(scale), "bias": float(bias),
                              "bias_after_scale": bias_after_scale})
    return _unary(act, out) if act else out


def cast(x, dtype):
    return _unary("cast", x, {"in_dtype": x.dtype, "out_dtype": dtype},
                  out_dtype=dtype)


def gather(input, index):
    helper = LayerHelper("gather")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("gather", {"X": input, "Index": index}, {"Out": out})
    return out


def scatter(input, index, updates, overwrite=True, name=None):
    helper = LayerHelper("scatter")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("scatter", {"X": input, "Ids": index, "Updates": updates},
                     {"Out": out}, {"overwrite": overwrite})
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("slice", {"Input": input}, {"Out": out},
                     {"axes": list(axes), "starts": list(starts), "ends": list(ends)})
    return out


def shape(input):
    helper = LayerHelper("shape")
    out = helper.create_tmp_variable("int32")
    out.stop_gradient = True
    helper.append_op("shape", {"Input": input}, {"Out": out})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss")
    diff = helper.create_tmp_variable(x.dtype)
    loss = helper.create_tmp_variable(x.dtype)
    inputs = {"X": x, "Y": y}
    if inside_weight is not None:
        inputs["InsideWeight"] = inside_weight
    if outside_weight is not None:
        inputs["OutsideWeight"] = outside_weight
    helper.append_op("smooth_l1_loss", inputs, {"Diff": diff, "Out": loss},
                     {"sigma": sigma if sigma is not None else 1.0})
    return loss


def warpctc(input, label, blank=0, norm_by_times=False):
    """CTC loss (≙ nn.py warpctc): input [B,T,C] raw logits (sequence var),
    label [B,L] int sequence var; returns Loss [B,1]."""
    from .sequence import _seq_len_of
    helper = LayerHelper("warpctc")
    loss = helper.create_tmp_variable(input.dtype)
    grad = helper.create_tmp_variable(input.dtype)
    grad.stop_gradient = True
    helper.append_op("warpctc",
                     {"Logits": input, "Label": label,
                      "LogitsLen": _seq_len_of(input, helper),
                      "LabelLen": _seq_len_of(label, helper)},
                     {"Loss": loss, "WarpCTCGrad": grad},
                     {"blank": blank, "norm_by_times": norm_by_times})
    loss.shape = (input.shape[0], 1)
    loss.dtype = input.dtype
    return loss


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    helper = LayerHelper("label_smooth")
    out = helper.create_tmp_variable(dtype)
    helper.append_op("label_smooth", {"X": label}, {"Out": out},
                     {"epsilon": float(epsilon)})
    return out


def bilinear_interp(input, out_h, out_w, name=None):
    helper = LayerHelper("bilinear_interp")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("bilinear_interp", {"X": input}, {"Out": out},
                     {"out_h": out_h, "out_w": out_w})
    return out


resize_bilinear = bilinear_interp


def random_crop(x, shape, seed=None):
    helper = LayerHelper("random_crop")
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("random_crop", {"X": x}, {"Out": out}, {"shape": list(shape)})
    return out


def nce(input, label, num_total_classes, num_neg_samples=10, param_attr=None,
        bias_attr=None, name=None):
    """layers/nn.py nce (noise-contrastive estimation head). Returns the
    per-row NCE cost [B, 1]; weights [V, D] + bias [V] are parameters."""
    helper = LayerHelper("nce", param_attr=param_attr, bias_attr=bias_attr,
                         name=name)
    dim = input.shape[-1]
    w = helper.create_parameter(helper.param_attr,
                                [num_total_classes, dim], "float32")
    b = helper.create_parameter(helper.bias_attr, [num_total_classes],
                                "float32", is_bias=True)
    cost = helper.create_tmp_variable("float32")
    sample_logits = helper.create_tmp_variable("float32")
    sample_labels = helper.create_tmp_variable("int32")
    sample_logits.stop_gradient = True
    sample_labels.stop_gradient = True
    helper.append_op("nce",
                     {"Input": input, "Label": label, "Weight": w,
                      "Bias": b},
                     {"Cost": cost, "SampleLogits": sample_logits,
                      "SampleLabels": sample_labels},
                     {"num_total_classes": num_total_classes,
                      "num_neg_samples": num_neg_samples})
    return cost


def row_conv(input, future_context_size, param_attr=None, act=None,
             name=None):
    """layers/nn.py row_conv (lookahead convolution, DeepSpeech2)."""
    helper = LayerHelper("row_conv", param_attr=param_attr, act=act,
                         name=name)
    dim = input.shape[-1]
    f = helper.create_parameter(helper.param_attr,
                                [future_context_size + 1, dim], "float32")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("row_conv", {"X": input, "Filter": f}, {"Out": out}, {})
    return helper.append_activation(out)


def mean_iou(input, label, num_classes, name=None):
    """layers/nn.py:mean_iou — returns (mean_iou, out_wrong, out_correct)."""
    helper = LayerHelper("mean_iou", name=name)
    miou = helper.create_tmp_variable("float32")
    wrong = helper.create_tmp_variable("int32")
    correct = helper.create_tmp_variable("int32")
    helper.append_op("mean_iou", {"Predictions": input, "Labels": label},
                     {"OutMeanIou": miou, "OutWrong": wrong,
                      "OutCorrect": correct},
                     {"num_classes": num_classes})
    return miou, wrong, correct


def bpr_loss(input, label, name=None):
    """layers/nn.py bpr_loss (Bayesian Personalized Ranking)."""
    helper = LayerHelper("bpr_loss", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("bpr_loss", {"X": input, "Label": label}, {"Y": out}, {})
    return out


def spp(input, pyramid_height, pool_type="max", name=None):
    """Spatial pyramid pooling layer (spp_op.cc)."""
    helper = LayerHelper("spp", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("spp", {"X": input}, {"Out": out},
                     {"pyramid_height": pyramid_height,
                      "pooling_type": pool_type})
    return out


def moe_ffn(input, num_experts, hidden_size, top_k=1, capacity_factor=1.25,
            act="relu", param_attr=None, name=None):
    """Mixture-of-Experts FFN with expert parallelism (additive — SURVEY
    §2.4 notes the reference has none). Expert weights are stacked
    [E, ...] and annotated sharded over the 'ep' mesh axis, so each
    expert's parameters live on its own devices and GSPMD inserts the
    dispatch all-to-all. Returns (out, aux_loss); add aux_loss (scaled
    ~1e-2) to the training loss for load balancing."""
    import copy
    helper = LayerHelper(name or "moe", param_attr=param_attr)
    d = int(input.shape[-1])
    from ..param_attr import ParamAttr as _PA
    from ..initializer import XavierInitializer as _Xavier

    def _attr(tag):
        # fresh copy per parameter: create_parameter fills attr.name in
        # place, and a user-supplied explicit name must not alias the five
        # distinct parameters
        a = copy.copy(_PA.to_attr(param_attr))
        if a.name is not None:
            a.name = f"{a.name}.{tag}"
        return a

    def expert_param(shape, fan_in, fan_out, tag, is_bias=False):
        p = helper.create_parameter(
            _attr(tag), [num_experts] + list(shape), "float32",
            is_bias=is_bias,
            default_initializer=None if is_bias
            else _Xavier(fan_in=fan_in, fan_out=fan_out))
        from ..parallel.mesh import EP
        p.sharding = (EP,) + (None,) * len(shape)
        return p

    gate_w = helper.create_parameter(_attr("gate"), [d, num_experts],
                                     "float32")
    w1 = expert_param([d, hidden_size], d, hidden_size, "w1")
    b1 = expert_param([hidden_size], 0, 0, "b1", is_bias=True)
    w2 = expert_param([hidden_size, d], hidden_size, d, "w2")
    b2 = expert_param([d], 0, 0, "b2", is_bias=True)
    out = helper.create_tmp_variable(input.dtype)
    aux = helper.create_tmp_variable("float32")
    helper.append_op("moe_ffn",
                     {"X": input, "GateW": gate_w, "W1": w1, "B1": b1,
                      "W2": w2, "B2": b2},
                     {"Out": out, "AuxLoss": aux},
                     {"top_k": top_k, "capacity_factor": capacity_factor,
                      "act": act})
    return out, aux


def moe_gated_ffn(input, num_experts, hidden_size, top_k, active=None,
                  name=None, router="softmax", norm_topk=False,
                  routed_scale=1.0, shared_width=0, shared_scale=1.0,
                  held=None, norm_topk_eps=None, form="", load_out=None):
    """Dropless top-k mixture of gated-SiLU experts with no bias
    (ops/moe_ops.py moe_gated_ffn). Parameters, by `name`:
    `{name}_router_w` [D, E], `{name}_gate_w` and `{name}_up_w`
    [E, D, H], `{name}_down_w` [E, H, D], each expert matrix drawn as an
    fc of its own fan would be; with `router="sigmoid_bias"` the
    selection bias `{name}_router_bias` [E] (zeros); with a
    `shared_width` the shared expert's `{name}_shared_gate_w`,
    `{name}_shared_up_w` [D, Hs] and `{name}_shared_down_w` [Hs, D],
    its output multiplied by `shared_scale`. `held` = (first, count): the
    program holds that range of the experts alone (the three expert
    weights are [count, ...]; the router keeps all `num_experts`
    columns) and computes only the pairs that fall on it.
    `norm_topk_eps`: what `norm_topk` adds to the sum it divides by
    (None: the op's own 1e-20). `form` "relu2": every expert, routed or
    shared, is TWO matrices, relu(x W_up)^2 W_down, there is no
    `{name}_gate_w` and no `{name}_shared_gate_w`, and the expert's width
    is STORED in whole tiles (256 columns a routed expert, 128 the shared
    one), zeros behind it; the routed experts' `{name}_down_w` [E, H',
    D'] also stores the model width in whole tiles of 512 columns, zeros
    behind D, which the op cuts off its product: Out is D wide ("": gated
    SiLU, every matrix stored as wide as it is).
    Returns (out, stats, experts): stats [3] int32 counts routed pairs,
    touched experts and whether any row was live among the rows
    `active` marks (every row when it is None); experts [..., top_k]
    int32 holds each row's chosen experts. `load_out` (a list): the op's
    Load [4] int32 is appended to it, what a training step counts of its
    experts (routed pairs, pairs on held experts, held experts that
    received any, the largest held expert's rows)."""
    from ..param_attr import ParamAttr as _PA
    from ..initializer import ConstantInitializer as _Const
    from ..initializer import PaddedInitializer as _Padded
    from ..initializer import XavierInitializer as _Xavier
    helper = LayerHelper("moe_gated_ffn", name=name)
    d = int(input.shape[-1])
    first, count = held or (0, num_experts)
    part = (int(first), int(count)) != (0, int(num_experts))

    def param(tag, shape, fan_in, fan_out, drawn=None):
        init = _Xavier(fan_in=fan_in, fan_out=fan_out)
        if drawn is not None:
            init = _Padded(init, drawn)
        return helper.create_parameter(
            _PA(name=f"{helper.name}_{tag}_w"), shape, "float32",
            default_initializer=init)

    if form not in ("", "relu2"):
        raise ValueError(f"unknown expert form {form!r}")
    gated = form == ""
    ins = {"X": input,
           "RouterW": param("router", [d, num_experts], d, num_experts)}

    def pair(stem, lead, width, tile, out_tile=1):
        """An expert's up and down matrices, [*lead, d, w] and [*lead, w,
        d']. The two-matrix form STORES w in whole tiles of `tile`
        columns, the columns (rows) behind `width` zeros, which
        relu(0)^2 keeps out of the result. A width like 1,856 is padded
        to 1,920 in the device's lane tiles anyway, and left unpadded
        the TPU compiler keeps the up matrix with d on the lanes and
        copies all of it transposed for the grouped matmul EVERY step;
        and XLA's grouped matmul runs 32 experts of 2,688 x w over a
        decode step's rows in 10.8 ms at w = 1,856, 8.7 at 1,920 and 3.5
        at 2,048 (PERF.md section 6, PR 51): the routed experts' tile is
        256 columns, the shared expert's (a plain product) a lane tile.
        The routed experts' DOWN matrix also stores the model width in
        whole tiles of `out_tile` columns (d' >= d, the columns behind d
        zeros that `ops.moe_ops._expert_rows` cuts off the product):
        the grouped matmul takes the widest of 512 / 256 / 128 that
        divides a product's output width as its weight tile's columns,
        2,688 = 21 x 128 left it `[512, 128]` tiles, and a grid step's
        fixed cost beside a 256 KB copy held the product to 50% of the
        HBM's rate where `[512, 512]` reads 88% (1.56 -> 1.02 ms a
        layer; 2,816 = 11 x 256 reads 72%: PERF.md section 6, PR 53)."""
        w = width if gated else -(-width // tile) * tile
        wide = d if gated else -(-d // out_tile) * out_tile
        return (param(f"{stem}up", lead + [d, w], d, width,
                      None if gated else lead + [d, width]),
                param(f"{stem}down", lead + [w, wide], width, d,
                      None if gated else lead + [width, d]))

    if gated:
        ins["WGate"] = param("gate", [count, d, hidden_size], d,
                             hidden_size)
    ins["WUp"], ins["WDown"] = pair("", [count], hidden_size, 256, 512)
    if router == "sigmoid_bias":
        ins["RouterBias"] = helper.create_parameter(
            _PA(name=f"{helper.name}_router_bias"), [num_experts],
            "float32", default_initializer=_Const(0.0))
    if shared_width:
        hs = int(shared_width)
        if gated:
            ins["SharedGate"] = param("shared_gate", [d, hs], d, hs)
        ins["SharedUp"], ins["SharedDown"] = pair("shared_", [], hs, 128)
    if active is not None:
        ins["Active"] = active
    out = helper.create_tmp_variable(input.dtype)
    stats = helper.create_tmp_variable("int32", stop_gradient=True)
    chosen = helper.create_tmp_variable("int32", stop_gradient=True)
    attrs = {"top_k": int(top_k), "router": router,
             "norm_topk": bool(norm_topk),
             "routed_scale": float(routed_scale)}
    if part:        # a program that holds every expert records what it
        attrs["first_expert"] = int(first)      # did before
    if shared_scale != 1.0:
        attrs["shared_scale"] = float(shared_scale)
    if norm_topk_eps is not None:
        attrs["norm_topk_eps"] = float(norm_topk_eps)
    if not gated:
        attrs["expert_form"] = form
    outs = {"Out": out, "Stats": stats, "Experts": chosen}
    if load_out is not None:    # a program that asks for none records
        outs["Load"] = helper.create_tmp_variable(    # what it did before
            "int32", stop_gradient=True)
        load_out.append(outs["Load"])
    helper.append_op("moe_gated_ffn", ins, outs, attrs)
    return out, stats, chosen


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, act=None,
           name=None):
    """NCDHW 3-D convolution (conv_op.cc 3-D path)."""
    helper = LayerHelper("conv3d", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    c_in = input.shape[1]
    k = (filter_size,) * 3 if isinstance(filter_size, int) \
        else tuple(filter_size)
    g = groups or 1
    w = helper.create_parameter(
        helper.param_attr, [num_filters, c_in // g] + list(k), "float32")
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("conv3d", {"Input": input, "Filter": w},
                     {"Output": out},
                     {"strides": [stride] * 3 if isinstance(stride, int)
                      else list(stride),
                      "paddings": [padding] * 3 if isinstance(padding, int)
                      else list(padding),
                      "dilations": [dilation] * 3
                      if isinstance(dilation, int) else list(dilation),
                      "groups": g})
    if bias_attr is not False:
        out = helper.append_bias_op(out, dim_start=1, dim_end=2,
                                    size=[num_filters])
    return helper.append_activation(out)


def pool3d(input, pool_size, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, exclusive=True, name=None):
    """NCDHW 3-D pooling (pool_op.cc 3-D path)."""
    helper = LayerHelper("pool3d", name=name)
    out = helper.create_tmp_variable(input.dtype)
    tri = lambda v: [v] * 3 if isinstance(v, int) else list(v)
    helper.append_op("pool3d", {"X": input}, {"Out": out},
                     {"ksize": tri(pool_size), "strides": tri(pool_stride),
                      "paddings": tri(pool_padding),
                      "pooling_type": pool_type,
                      "global_pooling": global_pooling,
                      "exclusive": exclusive})
    return out


def cos_sim(X, Y, name=None):
    """cos_sim_op.cc: row-wise cosine similarity (Y may broadcast [1, D])."""
    helper = LayerHelper("cos_sim", name=name)
    out = helper.create_tmp_variable(X.dtype)
    xn = helper.create_tmp_variable(X.dtype)
    yn = helper.create_tmp_variable(X.dtype)
    helper.append_op("cos_sim", {"X": X, "Y": Y},
                     {"Out": out, "XNorm": xn, "YNorm": yn}, {})
    out.shape = tuple(X.shape[:-1]) + (1,)
    out.dtype = X.dtype
    return out


def multiplex(inputs, index, name=None):
    """multiplex_op.cc: per-row select among candidate tensors by index."""
    helper = LayerHelper("multiplex", name=name)
    out = helper.create_tmp_variable(inputs[0].dtype)
    helper.append_op("multiplex", {"X": list(inputs), "Ids": index},
                     {"Out": out}, {})
    out.shape, out.dtype = inputs[0].shape, inputs[0].dtype
    return out


def dice_loss(input, label, epsilon=1e-5):
    """≙ layers/nn.py dice_loss: 1 - 2|X∩Y| / (|X|+|Y|), composed from
    elementwise ops exactly like the reference (no dedicated kernel)."""
    label = one_hot(label, depth=input.shape[-1])
    reduce_dims = list(range(1, len(input.shape)))
    inse = reduce_sum(elementwise_mul(input, label), dim=reduce_dims)
    dice_denominator = elementwise_add(
        reduce_sum(input, dim=reduce_dims),
        reduce_sum(label, dim=reduce_dims))
    dice_score = scale(elementwise_div(
        scale(inse, scale=2.0),
        scale(dice_denominator, bias=epsilon)), scale=-1.0, bias=1.0)
    return reduce_mean(dice_score)


def image_resize(input, out_shape=None, scale=None, resample="BILINEAR",
                 name=None):
    """≙ layers/nn.py image_resize → bilinear_interp op (NCHW)."""
    if resample not in ("BILINEAR", "NEAREST"):
        raise ValueError(f"image_resize: unsupported resample {resample!r}")
    if out_shape is None:
        if scale is None:
            raise ValueError("image_resize: give out_shape or scale")
        out_shape = [int(input.shape[2] * scale), int(input.shape[3] * scale)]
    helper = LayerHelper("image_resize", name=name)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op("bilinear_interp", {"X": input}, {"Out": out},
                     {"out_h": int(out_shape[0]), "out_w": int(out_shape[1]),
                      "method": "nearest" if resample == "NEAREST"
                      else "bilinear"})
    out.shape = tuple(input.shape[:2]) + (int(out_shape[0]), int(out_shape[1]))
    out.dtype = input.dtype
    return out


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """≙ layers/nn.py image_resize_short: resize keeping aspect ratio so
    the SHORT side hits out_short_len."""
    h, w = input.shape[2], input.shape[3]
    short = min(h, w)
    return image_resize(input, [h * out_short_len // short,
                                w * out_short_len // short], resample=resample)


def gru_unit(input, hidden, size, param_attr=None, bias_attr=None,
             activation="tanh", gate_activation="sigmoid"):
    """≙ layers/nn.py gru_unit (gru_unit_op.cc): one GRU step. `size` =
    3×hidden per the reference convention. Returns (hidden [B, D],
    reset_hidden_prev, gate)."""
    helper = LayerHelper("gru_unit", param_attr=param_attr,
                         bias_attr=bias_attr)
    d = size // 3
    weight = helper.create_parameter(helper.param_attr, [d, 3 * d],
                                     input.dtype)
    bias = helper.create_parameter(helper.bias_attr, [1, 3 * d], input.dtype,
                                   is_bias=True)
    h = helper.create_tmp_variable(input.dtype)
    gate = helper.create_tmp_variable(input.dtype)
    reset_h = helper.create_tmp_variable(input.dtype)
    helper.append_op(
        "gru_unit",
        {"Input": input, "HiddenPrev": hidden, "Weight": weight,
         "Bias": bias},
        {"Hidden": h, "Gate": gate, "ResetHiddenPrev": reset_h},
        {"activation": activation, "gate_activation": gate_activation})
    # (the op reads both attrs; see ops/volumetric_ops.py gru_unit)
    h.shape = reset_h.shape = tuple(hidden.shape)
    gate.shape = tuple(hidden.shape[:-1]) + (3 * d,)
    h.dtype = gate.dtype = reset_h.dtype = input.dtype
    return h, reset_h, gate


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """≙ layers/nn.py lstm_unit (lstm_unit_op): one LSTM step. Projects
    [x_t, h_prev] by an fc to 4D gate pre-activations (i|f|o|g layout,
    lstm_unit_op.h:63-66), then applies the cell. Returns (h, c)."""
    d = cell_t_prev.shape[-1]
    gates = fc(input=[x_t, hidden_t_prev], size=4 * d,
               param_attr=param_attr, bias_attr=bias_attr)
    helper = LayerHelper("lstm_unit", name=name)
    h = helper.create_tmp_variable(x_t.dtype)
    c = helper.create_tmp_variable(x_t.dtype)
    helper.append_op("lstm_unit", {"X": gates, "C_prev": cell_t_prev},
                     {"H": h, "C": c}, {"forget_bias": float(forget_bias)})
    h.shape = c.shape = tuple(cell_t_prev.shape)
    h.dtype = c.dtype = x_t.dtype
    return h, c


def uniform_random_batch_size_like(input, shape, input_dim_idx=0,
                                   output_dim_idx=0, min=-1.0, max=1.0,
                                   dtype="float32", seed=0):
    """uniform_random_batch_size_like_op.cc: uniform noise whose dim
    `output_dim_idx` copies `input`'s dim `input_dim_idx`."""
    helper = LayerHelper("uniform_random_batch_size_like")
    out = helper.create_tmp_variable(dtype)
    helper.append_op("uniform_random_batch_size_like", {"Input": input},
                     {"Out": out},
                     {"shape": list(shape), "min": min, "max": max,
                      "dtype": dtype, "seed": seed,
                      "input_dim_idx": input_dim_idx,
                      "output_dim_idx": output_dim_idx})
    return out


def gaussian_random(shape, mean=0.0, std=1.0, dtype="float32", seed=0):
    """gaussian_random_op.cc."""
    helper = LayerHelper("gaussian_random")
    out = helper.create_tmp_variable(dtype)
    helper.append_op("gaussian_random", {}, {"Out": out},
                     {"shape": list(shape), "mean": mean, "std": std,
                      "dtype": dtype, "seed": seed})
    out.shape, out.dtype = tuple(shape), dtype
    return out


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0, std=1.0,
                                    dtype="float32", seed=0):
    """gaussian_random_batch_size_like_op.cc."""
    helper = LayerHelper("gaussian_random_batch_size_like")
    out = helper.create_tmp_variable(dtype)
    helper.append_op("gaussian_random_batch_size_like", {"Input": input},
                     {"Out": out},
                     {"shape": list(shape), "mean": mean, "std": std,
                      "dtype": dtype, "seed": seed,
                      "input_dim_idx": input_dim_idx,
                      "output_dim_idx": output_dim_idx})
    return out


def uniform_random(shape, min=-1.0, max=1.0, dtype="float32", seed=0):
    """uniform_random_op.cc."""
    helper = LayerHelper("uniform_random")
    out = helper.create_tmp_variable(dtype)
    helper.append_op("uniform_random", {}, {"Out": out},
                     {"shape": list(shape), "min": min, "max": max,
                      "dtype": dtype, "seed": seed})
    out.shape, out.dtype = tuple(shape), dtype
    return out
