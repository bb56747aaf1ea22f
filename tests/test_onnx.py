"""ONNX export (reference python/paddle/onnx/export.py via paddle2onnx).

Validation is END-TO-END without the onnx package: the .onnx file is
re-parsed by an independent minimal protobuf reader (written against the
public onnx.proto schema, sharing no code with the writer) and executed
by a numpy interpreter of the emitted op set; outputs must match the
live model. This catches wire-format bugs AND graph-semantics bugs.
"""
import math

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn


# ---------------------------------------------------------- protobuf reader
def _read_varint(buf, i):
    val, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """Decode a message into {field: [values]} (values: int or bytes)."""
    out = {}
    i = 0
    while i < len(buf):
        tag, i = _read_varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            v, i = _read_varint(buf, i)
        elif wire == 2:
            ln, i = _read_varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wire == 5:
            v = np.frombuffer(buf[i:i + 4], np.float32)[0]
            i += 4
        else:
            raise AssertionError(f"unexpected wire type {wire}")
        out.setdefault(field, []).append(v)
    return out


_ONNX_NP = {1: np.float32, 6: np.int32, 7: np.int64, 9: np.bool_,
            10: np.float16, 11: np.float64, 3: np.int8, 2: np.uint8}


def _parse_tensor(buf):
    f = _fields(buf)
    dims = [int(d) for d in f.get(1, [])]
    dt = _ONNX_NP[int(f[2][0])]
    name = f[8][0].decode()
    arr = np.frombuffer(f[9][0], dt).reshape(dims)
    return name, arr


def _parse_attr(buf):
    f = _fields(buf)
    name = f[1][0].decode()
    atype = int(f[20][0])
    if atype == 2:
        return name, int(np.int64(f[3][0]).astype(np.int64))
    if atype == 1:
        return name, float(f[2][0])
    if atype == 3:
        return name, f[4][0].decode()
    if atype == 7:
        return name, [int(np.uint64(v).astype(np.int64)) for v in f[8]]
    if atype == 6:
        return name, [float(v) for v in f[7]]
    raise AssertionError(f"attr type {atype}")


def _parse_node(buf):
    f = _fields(buf)
    return {
        "inputs": [b.decode() for b in f.get(1, [])],
        "outputs": [b.decode() for b in f.get(2, [])],
        "op": f[4][0].decode(),
        "attrs": dict(_parse_attr(a) for a in f.get(5, [])),
    }


def _parse_value_info(buf):
    f = _fields(buf)
    name = f[1][0].decode()
    tensor_t = _fields(_fields(f[2][0])[1][0])
    elem = int(tensor_t[1][0])
    dims = [int(_fields(d)[1][0])
            for d in _fields(tensor_t[2][0]).get(1, [])]
    return name, _ONNX_NP[elem], dims


def parse_model(path):
    with open(path, "rb") as fh:
        buf = fh.read()
    m = _fields(buf)
    assert int(m[1][0]) == 8  # ir_version
    opset = _fields(m[8][0])
    g = _fields(m[7][0])
    return {
        "opset": int(opset[2][0]),
        "nodes": [_parse_node(n) for n in g.get(1, [])],
        "inits": dict(_parse_tensor(t) for t in g.get(5, [])),
        "inputs": [_parse_value_info(v) for v in g.get(11, [])],
        "outputs": [_parse_value_info(v) for v in g.get(12, [])],
    }


# ------------------------------------------------------- numpy interpreter
def _np_conv(x, w, b, strides, pads, dilations, group):
    N, C, H, W = x.shape
    O, I, kh, kw = w.shape
    ph0, pw0, ph1, pw1 = pads
    xp = np.pad(x, ((0, 0), (0, 0), (ph0, ph1), (pw0, pw1)))
    dh, dw = dilations
    eh, ew = (kh - 1) * dh + 1, (kw - 1) * dw + 1
    oh = (xp.shape[2] - eh) // strides[0] + 1
    ow = (xp.shape[3] - ew) // strides[1] + 1
    out = np.zeros((N, O, oh, ow), np.float32)
    og = O // group
    for g in range(group):
        for o in range(g * og, (g + 1) * og):
            for i in range(oh):
                for j in range(ow):
                    hs, ws_ = i * strides[0], j * strides[1]
                    patch = xp[:, g * I:(g + 1) * I, hs:hs + eh:dh,
                               ws_:ws_ + ew:dw]
                    out[:, o, i, j] = (patch * w[o]).sum(axis=(1, 2, 3))
    if b is not None:
        out += b.reshape(1, -1, 1, 1)
    return out


def _np_maxpool(x, kernel, strides, pads):
    ph0, pw0, ph1, pw1 = pads if len(pads) == 4 else (0, 0, 0, 0)
    xp = np.pad(x, ((0, 0), (0, 0), (ph0, ph1), (pw0, pw1)),
                constant_values=-np.inf)
    kh, kw = kernel
    oh = (xp.shape[2] - kh) // strides[0] + 1
    ow = (xp.shape[3] - kw) // strides[1] + 1
    out = np.full((x.shape[0], x.shape[1], oh, ow), -np.inf, x.dtype)
    for i in range(oh):
        for j in range(ow):
            hs, ws_ = i * strides[0], j * strides[1]
            out[:, :, i, j] = xp[:, :, hs:hs + kh, ws_:ws_ + kw].max(
                axis=(2, 3))
    return out


def run_graph(model, feeds):
    env = dict(model["inits"])
    env.update(feeds)
    erf = np.vectorize(math.erf)
    for nd in model["nodes"]:
        ins = [env[n] for n in nd["inputs"]]
        op, at = nd["op"], nd["attrs"]
        if op == "Identity":
            r = ins[0]
        elif op == "Add":
            r = ins[0] + ins[1]
        elif op == "Sub":
            r = ins[0] - ins[1]
        elif op == "Mul":
            r = ins[0] * ins[1]
        elif op == "Div":
            r = ins[0] / ins[1]
        elif op == "MatMul":
            r = ins[0] @ ins[1]
        elif op == "Max":
            r = np.maximum(ins[0], ins[1])
        elif op == "Min":
            r = np.minimum(ins[0], ins[1])
        elif op == "Neg":
            r = -ins[0]
        elif op == "Exp":
            r = np.exp(ins[0])
        elif op == "Log":
            r = np.log(ins[0])
        elif op == "Sqrt":
            r = np.sqrt(ins[0])
        elif op == "Reciprocal":
            r = 1.0 / ins[0]
        elif op == "Erf":
            r = erf(ins[0]).astype(ins[0].dtype)
        elif op == "Sigmoid":
            r = 1.0 / (1.0 + np.exp(-ins[0]))
        elif op == "Tanh":
            r = np.tanh(ins[0])
        elif op == "Pow":
            r = ins[0] ** ins[1]
        elif op == "Greater":
            r = ins[0] > ins[1]
        elif op == "Less":
            r = ins[0] < ins[1]
        elif op == "GreaterOrEqual":
            r = ins[0] >= ins[1]
        elif op == "LessOrEqual":
            r = ins[0] <= ins[1]
        elif op == "Equal":
            r = ins[0] == ins[1]
        elif op == "Where":
            r = np.where(ins[0], ins[1], ins[2])
        elif op == "Cast":
            r = ins[0].astype(_ONNX_NP[at["to"]])
        elif op == "Reshape":
            r = ins[0].reshape([int(d) for d in ins[1]])
        elif op == "Expand":
            r = np.broadcast_to(ins[0], [int(d) for d in ins[1]]).copy()
        elif op == "Transpose":
            r = np.transpose(ins[0], at["perm"])
        elif op == "Concat":
            r = np.concatenate(ins, axis=at["axis"])
        elif op == "ReduceSum":
            r = ins[0].sum(axis=tuple(int(a) for a in ins[1]),
                           keepdims=bool(at.get("keepdims", 1)))
        elif op == "ReduceMax":
            r = ins[0].max(axis=tuple(at["axes"]),
                           keepdims=bool(at.get("keepdims", 1)))
        elif op == "ReduceMin":
            r = ins[0].min(axis=tuple(at["axes"]),
                           keepdims=bool(at.get("keepdims", 1)))
        elif op == "Conv":
            b = ins[2] if len(ins) > 2 else None
            r = _np_conv(ins[0], ins[1], b, at["strides"], at["pads"],
                         at["dilations"], at.get("group", 1))
        elif op == "AveragePool":
            assert at.get("count_include_pad") == 1
            kh, kw = at["kernel_shape"]
            pads = at.get("pads", [0, 0, 0, 0])
            xp = np.pad(ins[0], ((0, 0), (0, 0),
                                 (pads[0], pads[2]), (pads[1], pads[3])))
            sh, sw = at["strides"]
            oh = (xp.shape[2] - kh) // sh + 1
            ow = (xp.shape[3] - kw) // sw + 1
            r = np.zeros((xp.shape[0], xp.shape[1], oh, ow), xp.dtype)
            for ii in range(oh):
                for jj in range(ow):
                    r[:, :, ii, jj] = xp[:, :, ii * sh:ii * sh + kh,
                                         jj * sw:jj * sw + kw].mean(
                        axis=(2, 3))
        elif op == "MaxPool":
            r = _np_maxpool(ins[0], at["kernel_shape"], at["strides"],
                            at.get("pads", [0, 0, 0, 0]))
        elif op == "Gather":
            r = np.take(ins[0], ins[1].astype(np.int64),
                        axis=at.get("axis", 0))
        elif op == "Split":
            parts = np.split(ins[0], np.cumsum(ins[1])[:-1].astype(int),
                             axis=at.get("axis", 0))
            for o_name, part in zip(nd["outputs"], parts):
                env[o_name] = part
            continue
        elif op == "Slice":
            starts, ends, axes = (ins[1], ins[2], ins[3])
            steps = ins[4] if len(ins) > 4 else np.ones_like(starts)
            sl = [slice(None)] * ins[0].ndim
            for s, e, a, st in zip(starts, ends, axes, steps):
                sl[int(a)] = slice(int(s), int(e), int(st))
            r = ins[0][tuple(sl)]
        else:
            raise AssertionError(f"interpreter: unhandled op {op}")
        env[nd["outputs"][0]] = r
    return [env[name] for name, _, _ in model["outputs"]]


# ------------------------------------------------------------------- tests
class TestOnnxExport:
    def _roundtrip(self, layer, xs, rtol=2e-5, atol=2e-5):
        import tempfile, os

        with paddle.no_grad():
            ref = layer(*[paddle.to_tensor(x) for x in xs])
        ref_np = np.asarray(ref.numpy())
        with tempfile.TemporaryDirectory() as td:
            path = paddle.onnx.export(
                layer, os.path.join(td, "m"), input_spec=list(xs))
            assert path.endswith(".onnx")
            model = parse_model(path)
        feeds = {name: x for (name, _, _), x in zip(model["inputs"], xs)}
        outs = run_graph(model, feeds)
        np.testing.assert_allclose(outs[0], ref_np, rtol=rtol, atol=atol)
        return model

    def test_mlp_with_norm_softmax(self):
        paddle.seed(5)
        layer = nn.Sequential(nn.Linear(8, 16), nn.GELU(),
                              nn.Linear(16, 4), nn.LayerNorm(4),
                              nn.Softmax())
        layer.eval()
        x = np.random.default_rng(0).normal(size=(3, 8)).astype(np.float32)
        model = self._roundtrip(layer, [x], rtol=1e-4, atol=1e-5)
        assert model["opset"] == 13
        ops = {n["op"] for n in model["nodes"]}
        assert "MatMul" in ops and "Erf" in ops

    def test_conv_relu_pool_classifier(self):
        paddle.seed(6)
        layer = nn.Sequential(nn.Conv2D(3, 8, 3, padding=1), nn.ReLU(),
                              nn.MaxPool2D(2), nn.Flatten(),
                              nn.Linear(8 * 4 * 4, 5))
        layer.eval()
        x = np.random.default_rng(1).normal(
            size=(2, 3, 8, 8)).astype(np.float32)
        model = self._roundtrip(layer, [x], rtol=1e-4, atol=1e-4)
        ops = {n["op"] for n in model["nodes"]}
        assert "Conv" in ops and "MaxPool" in ops

    def test_gpt_tiny_forward_exports(self):
        # the flagship model's full forward — embedding Gather, qkv
        # Split, batched attention MatMuls, softmax, tied head
        from paddle_tpu.models import (GPTConfig, GPTForPretraining,
                                       GPTModel)

        paddle.seed(1)
        m = GPTForPretraining(GPTModel(GPTConfig.preset(
            "gpt2-tiny", vocab_size=128, seq_len=16, dropout=0.0)))
        m.eval()
        toks = np.random.default_rng(0).integers(
            0, 128, (2, 16)).astype(np.int64)
        model = self._roundtrip(m, [toks], rtol=2e-4, atol=2e-4)
        ops = {n["op"] for n in model["nodes"]}
        # qkv splitting lowers to a `split` primitive or to per-head
        # `slice`s depending on the jax version — accept either spelling
        assert {"Gather", "MatMul"} <= ops
        assert "Split" in ops or "Slice" in ops

    def test_dynamic_shape_spec_rejected(self):
        from paddle_tpu.static import InputSpec

        layer = nn.Linear(4, 2)
        with pytest.raises(ValueError, match="static shapes"):
            paddle.onnx.export(layer, "/tmp/x",
                               input_spec=[InputSpec([None, 4], "float32")])

    def test_unsupported_primitive_named(self):
        class TopK(nn.Layer):
            def forward(self, x):
                v, i = paddle.topk(x, k=2)
                return v

        x = np.zeros((3, 5), np.float32)
        with pytest.raises(NotImplementedError, match="primitive"):
            paddle.onnx.export(TopK(), "/tmp/x", input_spec=[x])


class TestOnnxPooling:
    _roundtrip = TestOnnxExport._roundtrip

    def test_bn_avgpool_classifier(self):
        paddle.seed(8)
        layer = nn.Sequential(nn.Conv2D(3, 8, 3, padding=1),
                              nn.BatchNorm2D(8), nn.ReLU(),
                              nn.AvgPool2D(2), nn.Flatten(),
                              nn.Linear(8 * 4 * 4, 5))
        layer.eval()
        x = np.random.default_rng(4).normal(
            size=(2, 3, 8, 8)).astype(np.float32)
        model = self._roundtrip(layer, [x], rtol=1e-4, atol=1e-4)
        ops = {n["op"] for n in model["nodes"]}
        assert "AveragePool" in ops


class TestProtoAttrInference:
    """ISSUE-2 satellites: attr() list-type inference over ALL elements;
    _h_pad refusal of negative (cropping) pad amounts."""

    def test_mixed_int_float_list_is_floats(self):
        from paddle_tpu.onnx import _proto

        buf = _proto.attr("v", [1, 2.5])
        name, val = _parse_attr(buf)
        assert name == "v"
        assert val == [1.0, 2.5]  # A_FLOATS — 2.5 not truncated

    def test_float_first_int_later_is_floats(self):
        from paddle_tpu.onnx import _proto

        _, val = _parse_attr(_proto.attr("v", [2.5, 1]))
        assert val == [2.5, 1.0]

    def test_all_int_list_stays_ints(self):
        from paddle_tpu.onnx import _proto

        _, val = _parse_attr(_proto.attr("v", [1, 2, 3]))
        assert val == [1, 2, 3]

    def test_non_numeric_list_raises(self):
        from paddle_tpu.onnx import _proto

        with pytest.raises(TypeError, match="neither int nor float"):
            _proto.attr("v", [1, "x"])

    def test_negative_pad_refused(self):
        import jax

        class Crop(nn.Layer):
            def forward(self, x):
                return paddle.Tensor(
                    jax.lax.pad(x._data, np.float32(0.0),
                                [(-1, 0, 0), (0, 0, 0)]))

        x = np.zeros((3, 5), np.float32)
        with pytest.raises(NotImplementedError, match="negative padding"):
            paddle.onnx.export(Crop(), "/tmp/x_negpad", input_spec=[x])
