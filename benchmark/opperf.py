#!/usr/bin/env python
"""opperf — per-operator performance harness over the whole registry
(reference benchmark/opperf/opperf.py).

Sweeps ``mx.nd`` ops from ``ops.registry.list_ops()``: each op gets
synthetic inputs from a category-based argspec (tensor/nn/linalg/...),
runs forward (and backward where differentiable) under async timing, and
prints a table sorted by time. Ops without an argspec are reported as
skipped — coverage of the table IS the harness's coverage metric.

    python benchmark/opperf.py                 # all covered ops
    python benchmark/opperf.py --ops relu,Convolution --json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# ---------------------------------------------------------------------------
# argspecs: op -> (list of array shapes, kwargs). 'B' in a shape is the
# sweep batch. Categories keep this table small.
# ---------------------------------------------------------------------------
_UNARY_1D = dict.fromkeys("""
abs sign rint ceil floor trunc fix square sqrt rsqrt cbrt rcbrt exp log
log10 log2 log1p expm1 reciprocal negative sin cos tan arcsin arccos arctan
sinh cosh tanh arcsinh arccosh arctanh erf erfinv gamma gammaln digamma
relu sigmoid softsign softrelu gelu silu mish hard_sigmoid log_sigmoid erfc
degrees radians round logical_not isnan isinf isfinite zeros_like ones_like
softmax log_softmax sort topk argsort cumsum logsumexp smooth_l1
""".split(), ([("B", 1024)], {}))

_REDUCE = dict.fromkeys(
    "sum mean prod max min argmax argmin norm nansum nanprod".split(),
    ([("B", 1024)], {"axis": 1}))

_BINARY = dict.fromkeys("""
elemwise_add elemwise_sub elemwise_mul elemwise_div broadcast_power
broadcast_maximum broadcast_minimum broadcast_mod broadcast_hypot
broadcast_equal broadcast_not_equal broadcast_greater
broadcast_greater_equal broadcast_lesser broadcast_lesser_equal
broadcast_logical_and broadcast_logical_or broadcast_logical_xor
""".split(), ([("B", 1024), ("B", 1024)], {}))

_SCALAR = dict.fromkeys("""
_plus_scalar _minus_scalar _rminus_scalar _mul_scalar _div_scalar
_rdiv_scalar _power_scalar _rpower_scalar _mod_scalar _rmod_scalar
_maximum_scalar _minimum_scalar _equal_scalar _not_equal_scalar
_greater_scalar _greater_equal_scalar _lesser_scalar _lesser_equal_scalar
""".split(), ([("B", 1024)], {"scalar": 2.0}))

_MATMUL = {
    "dot": ([(512, 512), (512, 512)], {}),
    "matmul": ([("B", 256, 256), ("B", 256, 256)], {}),
    "batch_dot": ([("B", 128, 128), ("B", 128, 128)], {}),
    "linalg_gemm2": ([("B", 128, 128), ("B", 128, 128)], {}),
    "linalg_syrk": ([("B", 128, 128)], {}),
    "linalg_potrf": ("spd", {}),
    "linalg_potri": ("tri", {}),
    "linalg_trmm": ("tri_b", {}),
    "linalg_trsm": ("tri_b", {}),
    "linalg_sumlogdiag": ("spd", {}),
    "linalg_det": ("spd", {}),
    "linalg_slogdet": ("spd", {}),
    "linalg_inverse": ("spd", {}),
    "linalg_syevd": ("spd", {}),
    "linalg_gelqf": ([(64, 128)], {}),
    "linalg_extractdiag": ([("B", 64, 64)], {}),
}

_NN = {
    "FullyConnected": ([("B", 512), (256, 512), (256,)], {}),
    "Convolution": ([("B", 32, 28, 28), (64, 32, 3, 3), (64,)],
                    {"kernel": (3, 3), "pad": (1, 1), "num_filter": 64}),
    "Deconvolution": ([("B", 32, 14, 14), (32, 16, 2, 2), (16,)],
                      {"kernel": (2, 2), "stride": (2, 2),
                       "num_filter": 16}),
    "Pooling": ([("B", 32, 28, 28)], {"kernel": (2, 2), "stride": (2, 2)}),
    "BatchNorm": ([("B", 32, 14, 14), (32,), (32,), (32,), (32,)], {}),
    "LayerNorm": ([("B", 512), (512,), (512,)], {}),
    "RMSNorm": ([("B", 512), (512,)], {}),
    "Activation": ([("B", 1024)], {"act_type": "relu"}),
    "LeakyReLU": ([("B", 1024)], {"act_type": "leaky"}),
    "Embedding": ("embedding", {}),
    "Dropout": ([("B", 1024)], {"p": 0.5, "training": True}),
    "scaled_dot_product_attention":
        ([(4, 8, 128, 64), (4, 8, 128, 64), (4, 8, 128, 64)], {}),
    "flash_attention":
        ([(4, 8, 128, 64), (4, 8, 128, 64), (4, 8, 128, 64)], {}),
    "softmax_cross_entropy": ("sce", {}),
    "one_hot": ("one_hot", {"depth": 100}),
    "take": ("take", {}),
    "batch_take": ("batch_take", {}),
    "UpSampling": ([("B", 8, 16, 16)], {"scale": 2,
                                        "sample_type": "nearest"}),
    "BilinearResize2D": ([("B", 8, 16, 16)], {"height": 32, "width": 32}),
    "box_iou": ("boxes2", {}),
    "box_nms": ("nms", {"topk": 50}),
    "multibox_prior": ([("B", 8, 16, 16)], {"sizes": (0.5, 0.25),
                                            "ratios": (1.0, 2.0)}),
}

# round-3 waves: numpy-parity, fft, np.linalg, moe
_UNARY_1D.update(dict.fromkeys("""
exp2 sinc i0 fabs signbit std var median ptp cumprod nanmax nanmin
nanmean nanstd nanvar nancumsum nancumprod count_nonzero flipud fliplr
ediff1d atleast_2d atleast_3d real imag conj angle fftshift ifftshift
""".split(), ([("B", 1024)], {})))
_UNARY_1D.update({
    "roll": ([("B", 1024)], {"shift": 7}),
    "rot90": ([(64, 64)], {}),
    "tril": ([(128, 128)], {}),
    "triu": ([(128, 128)], {}),
    "trace_op": ([(128, 128)], {}),
    "moveaxis": ([(8, 16, 32)], {"source": 0, "destination": 2}),
    "diff": ([("B", 1024)], {}),
    "vander": ([(256,)], {"n": 8}),
    "quantile": ([("B", 1024)], {"q": 0.5}),
    "percentile": ([("B", 1024)], {"q": 30.0}),
    "fft": ([("B", 1024)], {}),
    "ifft": ([("B", 1024)], {}),
    "rfft": ([("B", 1024)], {}),
    "fft2": ([(64, 64)], {}),
    "fftn": ([(16, 32, 32)], {}),
})
_BINARY.update(dict.fromkeys("""
logaddexp logaddexp2 copysign heaviside fmod nextafter float_power
floor_divide isin
""".split(), ([("B", 1024), ("B", 1024)], {})))
_BINARY.update({
    "kron": ([(32, 32), (8, 8)], {}),
    "outer": ([(512,), (512,)], {}),
    "inner": ([(128, 128), (128, 128)], {}),
    "vdot": ([(128, 128), (128, 128)], {}),
    "cross": ([("B", 3), ("B", 3)], {}),
    "tensordot": ([(128, 128), (128, 128)], {"axes": 1}),
    "convolve": ([(1024,), (64,)], {}),
    "correlate": ([(1024,), (64,)], {}),
    "polyval": ([(8,), ("B", 64)], {}),
    "searchsorted": ([(1024,), (256,)], {}),
    "digitize": ([("B", 64), (32,)], {}),
})
_MATMUL.update({
    "linalg_norm": ([(256, 256)], {}),
    "linalg_solve": ("spd_b", {}),
    "linalg_qr": ([(256, 256)], {}),
    "linalg_svd": ([(128, 128)], {}),
    "linalg_eigh": ("spd", {}),
    "linalg_eigvalsh": ("spd", {}),
    "linalg_cholesky": ("spd", {}),
    "linalg_pinv": ([(128, 128)], {}),
    "linalg_matrix_power": ([(128, 128)], {"n": 3}),
    "moe_ffn": ("moe", {}),
})

ARGSPECS = {**_UNARY_1D, **_REDUCE, **_BINARY, **_SCALAR, **_MATMUL, **_NN}

_SHAPE1 = dict.fromkeys("""
cast clip flip transpose squeeze expand_dims tile repeat pad reshape
slice slice_axis shape_array size_array diag broadcast_axis broadcast_to
depth_to_space space_to_depth split stop_gradient_op identity softmin
nan_to_num argmax_channel amp_cast all_finite shuffle moments
masked_unused
""".split(), ([("B", 1024)], {}))
_SHAPE1.update({
    "cast": ([("B", 1024)], {"dtype": "float32"}),
    "clip": ([("B", 1024)], {"a_min": -1.0, "a_max": 1.0}),
    "flip": ([("B", 32)], {"axis": 1}),
    "transpose": ([(64, 32)], {}),
    "squeeze": ([(64, 1, 32)], {}),
    "expand_dims": ([("B", 32)], {"axis": 1}),
    "tile": ([(8, 8)], {"reps": (2, 2)}),
    "repeat": ([(8, 8)], {"repeats": 2}),
    "pad": ([(8, 8)], {"pad_width": ((1, 1), (1, 1))}),
    "reshape": ([(64, 32)], {"shape": (32, 64)}),
    "slice": ([(64, 32)], {"begin": (0, 0), "end": (32, 16)}),
    "slice_axis": ([(64, 32)], {"axis": 1, "begin": 0, "end": 16}),
    "broadcast_axis": ([(64, 1)], {"axis": 1, "size": 32}),
    "broadcast_to": ([(64, 1)], {"shape": (64, 32)}),
    "depth_to_space": ([(2, 16, 8, 8)], {"block_size": 2}),
    "space_to_depth": ([(2, 4, 16, 16)], {"block_size": 2}),
    "split": ([(64, 32)], {"num_outputs": 2}),
    "diag": ([(32, 32)], {}),
    "moments": ([("B", 64)], {"axes": (1,)}),
})
_MORE = {
    "where": ([("B", 64), ("B", 64), ("B", 64)], {}),
    "pick": ("pick", {}),
    "gather_nd": ("gather_nd", {}),
    "scatter_nd": None,
    "concat": ([("B", 64), ("B", 64)], {}),
    "stack": ([("B", 64), ("B", 64)], {}),
    "khatri_rao": ([(8, 16), (8, 16)], {}),
    "boolean_mask_unused": None,
    "sequence_mask": ([(16, "B", 8), ("B",)],
                      {"use_sequence_length": True}),
    "sequence_last": ([(16, "B", 8), ("B",)],
                      {"use_sequence_length": True}),
    "sequence_reverse": ([(16, "B", 8), ("B",)],
                         {"use_sequence_length": True}),
    "swapaxes_op": ([(16, 8, 4)], {"dim1": 0, "dim2": 2}),
    "slice_like": ([(64, 32), (32, 16)], {}),
    "GroupNorm": ([("B", 32, 8, 8), (32,), (32,)], {"num_groups": 4}),
    "InstanceNorm": ([("B", 32, 8, 8), (32,), (32,)], {}),
    "L2Normalization": ([("B", 64)], {}),
    "LRN": ([("B", 16, 8, 8)], {"nsize": 3}),
    "adaptive_avg_pool2d": ([("B", 8, 16, 16)], {"output_size": 4}),
    "GridGenerator": ([(4, 6)], {"transform_type": "affine",
                                 "target_shape": (8, 8)}),
    "BilinearSampler": ("bilinear_sampler", {}),
    "SpatialTransformer": ([(4, 3, 8, 8), (4, 6)],
                           {"target_shape": (8, 8)}),
    "ROIPooling": ("roi", {"pooled_size": (2, 2), "spatial_scale": 1.0}),
    "ROIAlign": ("roi", {"pooled_size": (2, 2), "spatial_scale": 1.0}),
    "Correlation": ([(2, 8, 12, 12), (2, 8, 12, 12)],
                    {"max_displacement": 1}),
    "DeformableConvolution": ("deform", {"kernel": (3, 3), "pad": (1, 1),
                                         "num_filter": 8}),
    "Crop": ([(2, 4, 16, 16)], {"h_w": (8, 8), "offset": (2, 2)}),
    "im2col": ([(2, 8, 16, 16)], {"kernel": (3, 3), "pad": (1, 1)}),
    "col2im": ("col2im", {"output_size": (16, 16), "kernel": (3, 3),
                          "pad": (1, 1)}),
    "CTCLoss": ("ctc", {}),
    "SVMOutput": ("sce", {}),
    "SoftmaxOutput": ("sce", {}),
    "LinearRegressionOutput": ([("B", 16), ("B", 16)], {}),
    "MAERegressionOutput": ([("B", 16), ("B", 16)], {}),
    "LogisticRegressionOutput": ([("B", 16), ("B", 16)], {}),
    "MakeLoss": ([("B", 16)], {}),
    "masked_softmax": ([("B", 64), ("B", 64)], {}),
    "masked_log_softmax": ([("B", 64), ("B", 64)], {}),
    "add_n": ([("B", 64), ("B", 64), ("B", 64)], {}),
    "amp_multicast": ([("B", 64), ("B", 64)], {}),
    "multi_all_finite": ([("B", 64), ("B", 64)], {}),
    "arange_like": ([("B", 16)], {}),
    "broadcast_like": ([(1, 16), ("B", 16)], {}),
    "reshape_like": ([("B", 16), ("B", 16)], {}),
    "choose_element_0index": ("batch_take", {}),
    "fill_element_0index": ("fill0", {}),
    "index_copy": ("index_copy", {}),
    "index_array": ([(8, 8)], {}),
    "sparse_retain_rows": ("index_copy_data", {}),
    "ravel_multi_index": ("ravel", {"shape": (16, 16)}),
    "unravel_index": ("unravel", {"shape": (16, 16)}),
    "interleaved_matmul_selfatt_qk": ([(16, 4, 3 * 4 * 16)], {"heads": 4}),
    "interleaved_matmul_encdec_qk": ([(16, 4, 64), (16, 4, 128)],
                                     {"heads": 4}),
    "random_uniform": ([], {"shape": (1024,)}),
    "random_normal": ([], {"shape": (1024,)}),
    "random_gamma": ([], {"shape": (1024,)}),
    "random_exponential": ([], {"shape": (1024,)}),
    "random_poisson": ([], {"shape": (1024,)}),
    "random_randint": ([], {"low": 0, "high": 10, "shape": (1024,)}),
    "random_bernoulli": ([], {"shape": (1024,)}),
    "sample_uniform": ([(8,), (8,)], {"shape": (64,)}),
    "sample_normal": ([(8,), (8,)], {"shape": (64,)}),
    "sample_gamma": ([(8,), (8,)], {"shape": (64,)}),
    "sample_exponential": ([(8,)], {"shape": (64,)}),
    "sample_poisson": ([(8,)], {"shape": (64,)}),
    "sample_negative_binomial": ([(8,), (8,)], {"shape": (64,)}),
    "sample_multinomial": ("multinomial", {}),
    "image_to_tensor": ([(32, 32, 3)], {}),
    "image_normalize": ([(3, 32, 32)], {"mean": (0.5,), "std": (0.5,)}),
    "image_resize": ([(32, 32, 3)], {"size": (16, 16)}),
    "image_crop": ([(32, 32, 3)], {"x0": 2, "y0": 2, "width": 16,
                                   "height": 16}),
    "image_flip_left_right": ([(32, 32, 3)], {}),
    "image_flip_top_bottom": ([(32, 32, 3)], {}),
    "image_random_flip_left_right": ([(32, 32, 3)], {}),
    "sgd_update": ([("B", 64), ("B", 64)], {"lr": 0.1}),
    "sgd_mom_update": ([("B", 64), ("B", 64), ("B", 64)], {"lr": 0.1}),
    "mp_sgd_update": ([("B", 64), ("B", 64), ("B", 64)], {"lr": 0.1}),
    "mp_sgd_mom_update": ([("B", 64)] * 4, {"lr": 0.1}),
    "nag_mom_update": ([("B", 64)] * 3, {"lr": 0.1, "momentum": 0.9}),
    "adam_update": ([("B", 64)] * 4, {"lr": 0.01}),
    "adamw_update": ([("B", 64)] * 4, {"lr": 0.01}),
    "rmsprop_update": ([("B", 64)] * 3, {"lr": 0.01}),
    "rmspropalex_update": ([("B", 64)] * 5, {"lr": 0.01}),
    "ftrl_update": ([("B", 64)] * 4, {"lr": 0.1}),
    "signsgd_update": ([("B", 64)] * 2, {"lr": 0.1}),
    "signum_update": ([("B", 64)] * 3, {"lr": 0.1, "momentum": 0.9}),
    "lamb_update_phase1": ([("B", 64)] * 4, {"t": 1}),
    "multibox_target": ("mbt", {}),
    "multibox_detection": ("mbd", {"nms_topk": 20}),
    "box_encode": ("box_encode", {}),
    "box_decode": ("box_decode", {}),
    "bipartite_matching": ([(4, 16, 8)], {}),
    "linalg_gemm": ([(8, 32, 32)] * 3, {}),
    "linalg_extractdiag": ([("B", 32, 32)], {}),
    "linalg_makediag": ([("B", 32)], {}),
    "linalg_extracttrian": ([("B", 16, 16)], {}),
}
_MORE = {k: v for k, v in _MORE.items() if v is not None}
ARGSPECS.update({k: v for k, v in _SHAPE1.items()
                 if k != "masked_unused"})
ARGSPECS.update(_MORE)



def _make_inputs(nd, spec, batch):
    rng = np.random.RandomState(0)

    if spec == "pick":
        return [nd.array(rng.rand(batch, 16).astype(np.float32)),
                nd.array(rng.randint(0, 16, (batch,)).astype(np.float32))]
    if spec == "gather_nd":
        return [nd.array(rng.rand(16, 16).astype(np.float32)),
                nd.array(rng.randint(0, 16, (2, batch)
                                     ).astype(np.float32))]
    if spec == "bilinear_sampler":
        grid = rng.rand(2, 2, 8, 8).astype(np.float32) * 2 - 1
        return [nd.array(rng.rand(2, 3, 8, 8).astype(np.float32)),
                nd.array(grid)]
    if spec == "roi":
        rois = np.array([[0, 1, 1, 6, 6], [1, 0, 0, 4, 4]], np.float32)
        return [nd.array(rng.rand(2, 4, 8, 8).astype(np.float32)),
                nd.array(rois)]
    if spec == "deform":
        return [nd.array(rng.rand(2, 4, 8, 8).astype(np.float32)),
                nd.array(np.zeros((2, 18, 8, 8), np.float32)),
                nd.array(rng.rand(8, 4, 3, 3).astype(np.float32))]
    if spec == "col2im":
        return [nd.array(rng.rand(2, 8 * 9, 256).astype(np.float32))]
    if spec == "ctc":
        return [nd.array(rng.randn(16, batch, 8).astype(np.float32)),
                nd.array(rng.randint(1, 8, (batch, 4)
                                     ).astype(np.float32))]
    if spec == "fill0":
        return [nd.array(rng.rand(batch, 16).astype(np.float32)),
                nd.array(rng.rand(batch).astype(np.float32)),
                nd.array(rng.randint(0, 16, (batch,)).astype(np.float32))]
    if spec == "index_copy":
        return [nd.array(rng.rand(64, 8).astype(np.float32)),
                nd.array(np.arange(4, dtype=np.float32)),
                nd.array(rng.rand(4, 8).astype(np.float32))]
    if spec == "index_copy_data":
        return [nd.array(rng.rand(64, 8).astype(np.float32)),
                nd.array(np.arange(4, dtype=np.float32))]
    if spec == "ravel":
        return [nd.array(rng.randint(0, 16, (2, batch)
                                     ).astype(np.float32))]
    if spec == "unravel":
        return [nd.array(rng.randint(0, 255, (batch,)
                                     ).astype(np.float32))]
    if spec == "multinomial":
        p = rng.rand(batch, 8).astype(np.float32)
        return [nd.array(p / p.sum(-1, keepdims=True))]
    if spec == "mbt":
        anchors = rng.rand(1, 32, 4).astype(np.float32)
        anchors[..., 2:] = anchors[..., :2] + 0.2
        labels = np.full((2, 3, 5), -1, np.float32)
        labels[:, 0] = [0, .1, .1, .4, .4]
        return [nd.array(anchors), nd.array(labels),
                nd.array(np.zeros((2, 4, 32), np.float32))]
    if spec == "mbd":
        anchors = rng.rand(1, 32, 4).astype(np.float32)
        anchors[..., 2:] = anchors[..., :2] + 0.2
        probs = rng.rand(2, 4, 32).astype(np.float32)
        return [nd.array(probs / probs.sum(1, keepdims=True)),
                nd.array(rng.rand(2, 128).astype(np.float32) * 0.1),
                nd.array(anchors)]
    if spec == "box_encode":
        boxes = rng.rand(2, 8, 4).astype(np.float32)
        boxes[..., 2:] = boxes[..., :2] + 0.2
        return [nd.array(np.ones((2, 8), np.float32)),
                nd.array(np.zeros((2, 8), np.float32)),
                nd.array(boxes), nd.array(boxes[:, :4])]
    if spec == "box_decode":
        anchors = rng.rand(1, 8, 4).astype(np.float32)
        anchors[..., 2:] = anchors[..., :2] + 0.2
        return [nd.array(rng.rand(2, 8, 4).astype(np.float32) * 0.1),
                nd.array(anchors)]
    if spec == "spd":
        a = rng.rand(8, 64, 64).astype(np.float32)
        return [nd.array(a @ a.transpose(0, 2, 1)
                         + 8 * np.eye(64, dtype=np.float32))]
    if spec == "tri":
        return [nd.array(np.tril(rng.rand(8, 64, 64)).astype(np.float32)
                         + 2 * np.eye(64, dtype=np.float32))]
    if spec == "tri_b":
        tri = np.tril(rng.rand(8, 64, 64)).astype(np.float32) \
            + 2 * np.eye(64, dtype=np.float32)
        return [nd.array(tri), nd.array(rng.rand(8, 64, 64
                                                 ).astype(np.float32))]
    if spec == "embedding":
        return [nd.array(rng.randint(0, 1000, (batch, 32)
                                     ).astype(np.int32)),
                nd.array(rng.rand(1000, 64).astype(np.float32))]
    if spec == "sce":
        return [nd.array(rng.rand(batch, 100).astype(np.float32)),
                nd.array(rng.randint(0, 100, (batch,)).astype(np.float32))]
    if spec == "one_hot":
        return [nd.array(rng.randint(0, 100, (batch,)).astype(np.float32))]
    if spec == "take":
        return [nd.array(rng.rand(1000, 64).astype(np.float32)),
                nd.array(rng.randint(0, 1000, (batch,)
                                     ).astype(np.float32))]
    if spec == "batch_take":
        return [nd.array(rng.rand(batch, 64).astype(np.float32)),
                nd.array(rng.randint(0, 64, (batch,)).astype(np.float32))]
    if spec == "spd_b":
        a = rng.rand(8, 64, 64).astype(np.float32)
        return [nd.array(a @ a.transpose(0, 2, 1)
                         + 8 * np.eye(64, dtype=np.float32)),
                nd.array(rng.rand(8, 64, 64).astype(np.float32))]
    if spec == "moe":
        E, D, H = 8, 64, 128
        return [nd.array(rng.rand(batch, D).astype(np.float32)),
                nd.array(rng.rand(D, E).astype(np.float32)),
                nd.array(rng.rand(E, D, H).astype(np.float32) * 0.1),
                nd.array(np.zeros((E, H), np.float32)),
                nd.array(rng.rand(E, H, D).astype(np.float32) * 0.1),
                nd.array(np.zeros((E, D), np.float32))]
    if spec == "boxes2":
        b = rng.rand(64, 4).astype(np.float32)
        b[:, 2:] = b[:, :2] + 0.2
        return [nd.array(b), nd.array(b)]
    if spec == "nms":
        r = rng.rand(4, 200, 6).astype(np.float32)
        r[..., 4:6] = r[..., 2:4] + 0.2
        return [nd.array(r)]
    arrays = []
    for shape in spec:
        shape = tuple(batch if s == "B" else s for s in shape)
        arrays.append(nd.array(rng.rand(*shape).astype(np.float32)))
    return arrays


def run_op(mx, name, batch, iters):
    from incubator_mxnet_tpu.ndarray import invoke_op
    from incubator_mxnet_tpu.ops import registry

    spec, kwargs = ARGSPECS[name]
    inputs = _make_inputs(mx.nd, spec, batch)
    opdef = registry.get(name)

    def call():
        return invoke_op(name, *inputs, **kwargs)

    out = call()
    (out[0] if isinstance(out, tuple) else out).asnumpy()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = call()
    (out[0] if isinstance(out, tuple) else out).asnumpy()
    fwd_ms = (time.perf_counter() - t0) / iters * 1e3

    bwd_ms = None
    if opdef.differentiable:
        from incubator_mxnet_tpu import autograd

        x = inputs[0]
        x.attach_grad()
        with autograd.record():
            out = call()
            head = out[0] if isinstance(out, tuple) else out
        head.backward(mx.nd.ones_like(head))
        x.grad.asnumpy()
        t0 = time.perf_counter()
        for _ in range(iters):
            with autograd.record():
                out = call()
                head = out[0] if isinstance(out, tuple) else out
            head.backward(mx.nd.ones_like(head))
        x.grad.asnumpy()
        bwd_ms = (time.perf_counter() - t0) / iters * 1e3
    return fwd_ms, bwd_ms


def run_train_step(fused, nparams=50, shape=(64, 64), iters=30):
    """Eager-Gluon train step (steps/s): one Trainer.step over ``nparams``
    dense parameters with synthetic grads — fused (one donated executable)
    vs per-param (one jitted dispatch per parameter)."""
    import jax.numpy as jnp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon import Parameter

    rng = np.random.RandomState(0)
    params = []
    for k in range(nparams):
        p = Parameter(name=f"p{k}", shape=shape)
        p.initialize(init="zeros")
        p.set_data(mx.nd.array(rng.rand(*shape).astype(np.float32)))
        params.append(p)
    trainer = gluon.Trainer(params, "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    trainer.fused_step(fused)
    grads = [jnp.asarray(rng.rand(*shape).astype(np.float32))
             for _ in params]

    def one_step():
        for p, g in zip(params, grads):
            p._data._grad._data = g
            p._data._grad_fresh = True
        trainer.step(1)

    one_step()                                   # compile + warm
    for p in params:
        p.data().asnumpy()
    t0 = time.perf_counter()
    for _ in range(iters):
        one_step()
    for p in params:                              # async barrier
        p.data().asnumpy()
    step_ms = (time.perf_counter() - t0) / iters * 1e3
    return step_ms


_TRAIN_STEP_ROWS = ("gluon_train_step[fused]", "gluon_train_step[perparam]")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default="",
                    help="comma-separated subset (default: all covered)")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.ops import registry

    all_ops = registry.list_ops()
    wanted = [o for o in args.ops.split(",") if o] or all_ops
    covered = [o for o in wanted if o in ARGSPECS]
    skipped = [o for o in wanted
               if o not in ARGSPECS and o not in _TRAIN_STEP_ROWS]

    rows = []
    for name in covered:
        try:
            fwd, bwd = run_op(mx, name, args.batch, args.iters)
            rows.append({"op": name, "fwd_ms": round(fwd, 4),
                         "bwd_ms": None if bwd is None else round(bwd, 4)})
        except Exception as e:  # keep sweeping
            rows.append({"op": name, "error": str(e)[:120]})
    rows.sort(key=lambda r: r.get("fwd_ms") or 0, reverse=True)

    # whole-trainer step rows (fused-vs-per-param speedup lands in the
    # BENCH json next to the per-op table)
    step_rows = [n for n in _TRAIN_STEP_ROWS
                 if not args.ops or n in wanted]
    for name in step_rows:
        try:
            ms = run_train_step(fused="fused" in name,
                                iters=max(args.iters, 10))
            rows.append({"op": name, "fwd_ms": round(ms, 4),
                         "bwd_ms": None,
                         "steps_per_s": round(1e3 / ms, 2)})
        except Exception as e:  # keep sweeping
            rows.append({"op": name, "error": str(e)[:120]})

    if args.json:
        print(json.dumps({"results": rows, "skipped": skipped}, indent=1))
        return
    print(f"# opperf: {len(covered)} covered / {len(wanted)} requested "
          f"(registry total {len(all_ops)}); batch={args.batch}")
    print(f"{'op':36} {'fwd ms':>9} {'fwd+bwd ms':>11}")
    for r in rows:
        if "error" in r:
            print(f"{r['op']:36} ERROR {r['error']}")
        else:
            b = "-" if r["bwd_ms"] is None else f"{r['bwd_ms']:.3f}"
            print(f"{r['op']:36} {r['fwd_ms']:9.3f} {b:>11}")
    if skipped:
        print(f"# skipped (no argspec): {len(skipped)}")


if __name__ == "__main__":
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    main()
