"""Depth tests for op families that previously had one smoke each
(VERDICT weak #3): linalg vs numpy/scipy analytic results, FFT vs np.fft,
box ops vs hand-computed IoU/NMS, quantization roundtrips, and the
MXNET_BACKWARD_DO_MIRROR remat analog.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def _nd(x):
    return mx.nd.array(np.asarray(x, np.float32))


RNG = np.random.RandomState(11)


# ---------------------------------------------------------------------------
# linalg family vs numpy (reference: src/operator/tensor/la_op.cc)
# ---------------------------------------------------------------------------

def _spd(n):
    a = RNG.normal(0, 1, (n, n)).astype(np.float32)
    return a @ a.T + n * np.eye(n, dtype=np.float32)


def test_linalg_gemm_alpha_beta():
    A = RNG.normal(0, 1, (3, 4)).astype(np.float32)
    B = RNG.normal(0, 1, (4, 5)).astype(np.float32)
    C = RNG.normal(0, 1, (3, 5)).astype(np.float32)
    out = nd.linalg_gemm(_nd(A), _nd(B), _nd(C), alpha=2.0,
                         beta=0.5).asnumpy()
    np.testing.assert_allclose(out, 2.0 * (A @ B) + 0.5 * C, rtol=1e-4,
                               atol=1e-5)
    out_t = nd.linalg_gemm(_nd(A), _nd(B.T), _nd(C), transpose_b=True
                           ).asnumpy()
    np.testing.assert_allclose(out_t, A @ B + C, rtol=1e-4, atol=1e-5)


def test_linalg_potrf_potri_sumlogdiag():
    S = _spd(4)
    L = nd.linalg_potrf(_nd(S)).asnumpy()
    np.testing.assert_allclose(L @ L.T, S, rtol=1e-3, atol=1e-3)
    assert np.allclose(L, np.tril(L))  # lower triangular
    Sinv = nd.linalg_potri(_nd(L)).asnumpy()
    np.testing.assert_allclose(Sinv, np.linalg.inv(S), rtol=1e-2, atol=1e-3)
    sld = nd.linalg_sumlogdiag(_nd(L)).asnumpy()
    np.testing.assert_allclose(sld, np.log(np.diag(L)).sum(), rtol=1e-4)


def test_linalg_trsm_trmm():
    S = _spd(4)
    L = np.linalg.cholesky(S).astype(np.float32)
    B = RNG.normal(0, 1, (4, 3)).astype(np.float32)
    X = nd.linalg_trsm(_nd(L), _nd(B)).asnumpy()
    np.testing.assert_allclose(L @ X, B, rtol=1e-3, atol=1e-3)
    Y = nd.linalg_trmm(_nd(L), _nd(B)).asnumpy()
    np.testing.assert_allclose(Y, L @ B, rtol=1e-4, atol=1e-4)


def test_linalg_syrk_syevd_gelqf():
    A = RNG.normal(0, 1, (3, 5)).astype(np.float32)
    out = nd.linalg_syrk(_nd(A), alpha=1.0).asnumpy()
    np.testing.assert_allclose(out, A @ A.T, rtol=1e-4, atol=1e-4)
    S = _spd(4)
    U, lam = nd.linalg_syevd(_nd(S))
    U, lam = U.asnumpy(), lam.asnumpy()
    np.testing.assert_allclose(np.sort(lam), np.sort(
        np.linalg.eigvalsh(S)), rtol=1e-3, atol=1e-3)
    # reference convention: rows of U are eigenvectors — A = U^T diag(l) U
    # (la_op.cc syevd docstring); assert it directly so a regression to the
    # numpy column convention fails loudly
    np.testing.assert_allclose(U.T @ np.diag(lam) @ U, S, rtol=1e-2,
                               atol=1e-2)
    A2 = RNG.normal(0, 1, (3, 5)).astype(np.float32)
    Q, L = nd.linalg_gelqf(_nd(A2))
    Q, L = Q.asnumpy(), L.asnumpy()
    np.testing.assert_allclose(L @ Q, A2, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(Q @ Q.T, np.eye(3), rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# FFT family vs np.fft (reference: src/operator/contrib/fft.cc)
# ---------------------------------------------------------------------------

def test_fft_matches_numpy():
    x = RNG.normal(0, 1, (2, 8)).astype(np.float32)
    out = nd.contrib.fft(_nd(x)).asnumpy()
    ref = np.fft.fft(x, axis=-1)
    # reference layout: interleaved re/im, last dim doubled
    np.testing.assert_allclose(out[..., 0::2], ref.real, rtol=1e-3,
                               atol=1e-4)
    np.testing.assert_allclose(out[..., 1::2], ref.imag, rtol=1e-3,
                               atol=1e-4)


def test_ifft_roundtrip():
    x = RNG.normal(0, 1, (2, 8)).astype(np.float32)
    freq = nd.contrib.fft(_nd(x))
    back = nd.contrib.ifft(freq).asnumpy()
    # reference ifft is unnormalized (like cuFFT): scale by n
    np.testing.assert_allclose(back / 8.0, x, rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# box ops vs hand computation (reference: src/operator/contrib/bounding_box.cc)
# ---------------------------------------------------------------------------

def _iou(a, b):
    x1, y1 = max(a[0], b[0]), max(a[1], b[1])
    x2, y2 = min(a[2], b[2]), min(a[3], b[3])
    inter = max(0, x2 - x1) * max(0, y2 - y1)
    ua = ((a[2] - a[0]) * (a[3] - a[1])
          + (b[2] - b[0]) * (b[3] - b[1]) - inter)
    return inter / ua if ua > 0 else 0.0


def test_box_iou_matches_manual():
    boxes1 = np.array([[0, 0, 4, 4], [2, 2, 6, 6]], np.float32)
    boxes2 = np.array([[0, 0, 4, 4], [3, 3, 5, 5], [10, 10, 12, 12]],
                      np.float32)
    out = nd.contrib.box_iou(_nd(boxes1), _nd(boxes2)).asnumpy()
    for i, a in enumerate(boxes1):
        for j, b in enumerate(boxes2):
            np.testing.assert_allclose(out[i, j], _iou(a, b), atol=1e-5,
                                       err_msg="(%d,%d)" % (i, j))


def test_box_nms_suppression_and_scores():
    # [cls, score, x1, y1, x2, y2]
    dets = np.array([
        [0, 0.9, 0, 0, 4, 4],
        [0, 0.8, 0.5, 0.5, 4.5, 4.5],   # heavy overlap with #0 -> suppressed
        [0, 0.7, 10, 10, 14, 14],       # far away -> kept
    ], np.float32)[None]
    out = nd.contrib.box_nms(_nd(dets), overlap_thresh=0.5,
                             score_index=1, coord_start=2).asnumpy()[0]
    kept_scores = sorted(s for s in out[:, 1] if s > 0)
    np.testing.assert_allclose(kept_scores, [0.7, 0.9], atol=1e-5)


# ---------------------------------------------------------------------------
# quantization roundtrips
# ---------------------------------------------------------------------------

def test_quantize_dequantize_int8_roundtrip():
    x = RNG.normal(0, 2, (4, 5)).astype(np.float32)
    mn, mxr = _nd([x.min()]), _nd([x.max()])
    q, qmin, qmax = nd.contrib.quantize(_nd(x), mn, mxr, out_type="int8")
    back = nd.contrib.dequantize(q, qmin, qmax).asnumpy()
    absmax = max(abs(x.min()), abs(x.max()))
    np.testing.assert_allclose(back, x, atol=absmax / 127 + 1e-5)


def test_requantize_int32_to_int8():
    acc = (RNG.normal(0, 1, (3, 3)) * 2 ** 20).astype(np.int32)
    mn, mxr = _nd([-2.0]), _nd([2.0])
    q, qmin, qmax = nd.contrib.requantize(
        mx.nd.array(acc, dtype=np.int32), mn, mxr)
    assert q.dtype == np.int8
    scale32 = 2.0 / 2 ** 31
    expect_f = acc.astype(np.float64) * scale32
    scale8 = 127.0 / max(abs(float(qmin.asnumpy()[0])),
                         abs(float(qmax.asnumpy()[0])))
    np.testing.assert_allclose(q.asnumpy(), np.clip(np.round(
        expect_f * scale8), -127, 127), atol=1.0)


# ---------------------------------------------------------------------------
# MXNET_BACKWARD_DO_MIRROR (recompute/mirroring analog)
# ---------------------------------------------------------------------------

def test_backward_do_mirror_same_grads(tmp_path):
    """Remat must change memory behavior only — gradients identical."""
    script = tmp_path / "mirror.py"
    script.write_text(
        "import os, sys, json\n"
        "import numpy as np\n"
        "sys.path.insert(0, %r)\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import mxnet_tpu as mx\n"
        "x = mx.sym.Variable('x')\n"
        "net = mx.sym.FullyConnected(x, num_hidden=8, name='fc')\n"
        "net = mx.sym.make_loss(mx.sym.sum(mx.sym.tanh(net)))\n"
        "ex = net.simple_bind(mx.cpu(), x=(4, 6))\n"
        "rng = np.random.RandomState(0)\n"
        "for n, a in ex.arg_dict.items():\n"
        "    a[:] = rng.normal(0, 1, a.shape).astype(np.float32)\n"
        "ex.forward(is_train=True)\n"
        "ex.backward()\n"
        "print(json.dumps({n: g.asnumpy().tolist()\n"
        "                  for n, g in ex.grad_dict.items()}))\n"
        % os.path.abspath(os.path.join(os.path.dirname(__file__),
                                       "..", "..", "..")))
    import json
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    outs = {}
    for flag in ("0", "1"):
        env["MXNET_BACKWARD_DO_MIRROR"] = flag
        p = subprocess.run([sys.executable, str(script)], env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        outs[flag] = json.loads(p.stdout.strip().splitlines()[-1])
    for name in outs["0"]:
        np.testing.assert_allclose(outs["0"][name], outs["1"][name],
                                   rtol=1e-5, atol=1e-6, err_msg=name)
