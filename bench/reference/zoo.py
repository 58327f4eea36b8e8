"""The seven DNNs of the paper's study (Kao et al., arXiv:2206.02987, Sec 6.1)
as plain 6-dim loop nests, kept with the benchmark so the reference checks
the program's answers against shapes of its own.

Each layer is ``(name, (K, C, Y, X, R, S), stride, depthwise)``: K output
channels, C input channels, Y x X output size, R x S filter.  A GEMM
(M, N, Kg) is ``(M, Kg, N, 1, 1, 1)``; a depthwise conv has K = 1.  Layer
dims follow the original papers and the torchvision definitions.
"""
from __future__ import annotations


def _conv(name, k, c, y, x, r, s, stride=1):
    return (name, (k, c, y, x, r, s), stride, False)


def _dw(name, c, y, x, r, s, stride=1):
    return (name, (1, c, y, x, r, s), stride, True)


def _gemm(name, m, n, kg):
    return (name, (m, kg, n, 1, 1, 1), 1, False)


def alexnet():
    return [_conv("conv1", 96, 3, 55, 55, 11, 11, 4),
            _conv("conv2", 256, 96, 27, 27, 5, 5),
            _conv("conv3", 384, 256, 13, 13, 3, 3),
            _conv("conv4", 384, 384, 13, 13, 3, 3),
            _conv("conv5", 256, 384, 13, 13, 3, 3),
            _gemm("fc6", 4096, 1, 9216),
            _gemm("fc7", 4096, 1, 4096),
            _gemm("fc8", 1000, 1, 4096)]


def resnet50():
    out = [_conv("conv1", 64, 3, 112, 112, 7, 7, 2)]
    for stage, blocks, c_in, mid, yx, first in (("conv2", 3, 64, 64, 56, 1),
                                                ("conv3", 4, 256, 128, 56, 2),
                                                ("conv4", 6, 512, 256, 28, 2),
                                                ("conv5", 3, 1024, 512, 14, 2)):
        for b in range(blocks):
            s = first if b == 0 else 1
            cin = c_in if b == 0 else mid * 4
            o = yx // s
            out.append(_conv(f"{stage}.{b}.conv1", mid, cin, yx, yx, 1, 1))
            out.append(_conv(f"{stage}.{b}.conv2", mid, mid, o, o, 3, 3, s))
            out.append(_conv(f"{stage}.{b}.conv3", mid * 4, mid, o, o, 1, 1))
            if b == 0:
                out.append(_conv(f"{stage}.{b}.down", mid * 4, cin, o, o, 1,
                                 1, s))
            yx = o
    out.append(_gemm("fc", 1000, 1, 2048))
    return out


def mobilenetv2():
    out = [_conv("stem", 32, 3, 112, 112, 3, 3, 2)]
    c_in, res = 32, 112
    for i, (t, c_out, n, s) in enumerate(((1, 16, 1, 1), (6, 24, 2, 2),
                                          (6, 32, 3, 2), (6, 64, 4, 2),
                                          (6, 96, 3, 1), (6, 160, 3, 2),
                                          (6, 320, 1, 1))):
        for b in range(n):
            st = s if b == 0 else 1
            mid = c_in * t
            o = res // st
            if t != 1:
                out.append(_conv(f"ir{i}.{b}.expand", mid, c_in, res, res, 1,
                                 1))
            out.append(_dw(f"ir{i}.{b}.dw", mid, o, o, 3, 3, st))
            out.append(_conv(f"ir{i}.{b}.project", c_out, mid, o, o, 1, 1))
            c_in, res = c_out, o
    out.append(_conv("head", 1280, 320, 7, 7, 1, 1))
    out.append(_gemm("fc", 1000, 1, 1280))
    return out


def mnasnet():
    out = [_conv("stem", 32, 3, 224, 224, 3, 3),
           _dw("sep.dw", 32, 112, 112, 3, 3, 2),
           _conv("sep.pw", 16, 32, 112, 112, 1, 1),
           _conv("mb1.0.expand", 96, 16, 112, 112, 1, 1),
           _dw("mb1.0.dw", 96, 56, 56, 3, 3, 2),
           _conv("mb1.0.project", 24, 96, 56, 56, 1, 1),
           _conv("mb1.1.expand", 144, 24, 56, 56, 1, 1),
           _dw("mb1.1.dw", 144, 56, 56, 3, 3),
           _conv("mb1.1.project", 24, 144, 56, 56, 1, 1),
           _conv("mb2.0.expand", 72, 24, 56, 56, 1, 1),
           _dw("mb2.0.dw", 72, 28, 28, 5, 5, 2),
           _conv("mb2.0.project", 40, 72, 28, 28, 1, 1)]
    for b in (1, 2):
        out += [_conv(f"mb2.{b}.expand", 120, 40, 28, 28, 1, 1),
                _dw(f"mb2.{b}.dw", 120, 28, 28, 5, 5),
                _conv(f"mb2.{b}.project", 40, 120, 28, 28, 1, 1)]
    out += [_conv("mb3.0.expand", 240, 40, 28, 28, 1, 1),
            _dw("mb3.0.dw", 240, 14, 14, 3, 3, 2),
            _conv("mb3.0.project", 80, 240, 14, 14, 1, 1)]
    for b in (1, 2, 3):
        k = 5 if b == 3 else 3
        out += [_conv(f"mb3.{b}.expand", 480, 80, 14, 14, 1, 1),
                _dw(f"mb3.{b}.dw", 480, 14, 14, k, k),
                _conv(f"mb3.{b}.project", 80, 480, 14, 14, 1, 1)]
    for b in (0, 1):
        cin = 80 if b == 0 else 112
        out += [_conv(f"mb4.{b}.expand", cin * 6, cin, 14, 14, 1, 1),
                _dw(f"mb4.{b}.dw", cin * 6, 14, 14, 3, 3),
                _conv(f"mb4.{b}.project", 112, cin * 6, 14, 14, 1, 1)]
    for b in (0, 1, 2):
        cin = 112 if b == 0 else 160
        out += [_conv(f"mb5.{b}.expand", cin * 6, cin, 14, 14, 1, 1),
                _dw(f"mb5.{b}.dw", cin * 6, 7, 7, 5, 5, 2 if b == 0 else 1),
                _conv(f"mb5.{b}.project", 160, cin * 6, 7, 7, 1, 1)]
    out += [_conv("mb6.0.expand", 960, 160, 7, 7, 1, 1),
            _dw("mb6.0.dw", 960, 7, 7, 3, 3),
            _conv("mb6.0.project", 320, 960, 7, 7, 1, 1),
            _conv("head", 1280, 320, 7, 7, 1, 1),
            _gemm("fc", 1000, 1, 1280)]
    return out


def bert(seq=512):
    d, dff, h = 768, 3072, 12
    return [_gemm("qkv_proj", 3 * d, seq, d),
            _gemm("attn_scores", seq, seq, d // h),
            _gemm("attn_ctx", seq, d // h, seq),
            _gemm("out_proj", d, seq, d),
            _gemm("ffn_up", dff, seq, d),
            _gemm("ffn_down", d, seq, dff)]


def dlrm():
    bot, top = [13, 512, 256, 64], [512, 512, 256, 1]
    return ([_gemm(f"bot{i}", bot[i + 1], 1, bot[i]) for i in range(3)]
            + [_gemm(f"top{i}", top[i + 1], 1, top[i]) for i in range(3)])


def ncf():
    w = [256, 256, 128, 64, 1]
    return [_gemm(f"mlp{i}", w[i + 1], 1, w[i]) for i in range(4)]


MODELS = {"alexnet": alexnet, "mnasnet": mnasnet, "resnet50": resnet50,
          "mobilenetv2": mobilenetv2, "bert": bert, "dlrm": dlrm, "ncf": ncf}


def layers(model: str):
    """``[(name, dims, stride, depthwise), ...]`` of one model."""
    return MODELS[model]()
