#!/usr/bin/env python
"""opperf — operator coverage + latency sweep for mxnet_tpu.

TPU-native port of the reference's `benchmark/opperf/opperf.py` harness
(which sweeps every registered operator across shape profiles with
warmup/run controls and emits the tables in
`benchmark/opperf/results/*.md`). Here the op inventory is the public
surface of `mx.np`, `mx.npx`, `mx.np.linalg`, `mx.np.random` and
`mx.np.fft`; each op is resolved to an argument template (explicit spec
or generic trial), executed with warmup, then timed with engine sync so
async dispatch can't hide execution time.

Usage:
    python benchmark/opperf.py [--output <file>.json] [--runs 10]
        [--warmup 2] [--platform cpu|tpu] [--filter SUBSTR]

Output JSON:
    {"summary": {"total": N, "covered": N, "coverage_pct": x,
                 "platform": "...", "dtype": "float32"},
     "ops": {"np.add": {"covered": true, "latency_ms": 0.01,
                         "shape": "...", "error": null}, ...}}
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import time
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


# ---------------------------------------------------------------------------
# Ops that must not be trial-called (host IO, printing, global state,
# generators) or that are not array ops at all. They don't count toward
# the op total.
# ---------------------------------------------------------------------------
SKIP = {
    # host IO / files
    "np.save", "np.savez", "np.load", "np.genfromtxt", "np.loadtxt",
    "np.savetxt", "np.fromregex", "np.savez_compressed", "np.get_include",
    # printing / global config
    "np.set_printoptions", "np.get_printoptions", "np.printoptions",
    "np.array_repr", "np.array_str", "np.array2string", "np.base_repr",
    "np.binary_repr", "np.format_float_positional",
    "np.format_float_scientific", "np.typename", "np.sctype2char",
    "np.maximum_sctype", "np.issubdtype", "np.issubsctype",
    "np.issctype", "np.isdtype", "np.obj2sctype", "np.mintypecode",
    "np.deprecate", "np.deprecate_with_doc", "np.disp", "np.info",
    "np.safe_eval", "np.lookfor", "np.source", "np.who", "np.byte_bounds",
    "np.shares_memory", "np.may_share_memory", "np.setbufsize",
    "np.getbufsize", "np.seterrcall", "np.geterrcall", "np.show_config",
    "np.show_runtime", "np.add_docstring", "np.add_newdoc",
    "np.add_newdoc_ufunc", "np.datetime_data", "np.datetime_as_string",
    "np.busday_count", "np.busday_offset", "np.is_busday", "np.iterable",
    "np.ndim", "np.size", "np.shape",  # python-level helpers, counted via array methods
    # dtype machinery (classes / non-ops)
    "np.dtype", "np.finfo", "np.iinfo", "np.result_type",
    "np.promote_types", "np.can_cast", "np.min_scalar_type",
    "np.common_type", "np.find_common_type", "np.typing",
    # random generators/state (np.random covered separately)
    "random.seed", "random.get_state", "random.set_state",
    "random.default_rng", "random.RandomState", "random.Generator",
    # npx runtime / mode switches, not ops
    "npx.set_np", "npx.reset_np", "npx.is_np_array", "npx.is_np_shape",
    "npx.waitall", "npx.load", "npx.save", "npx.current_device",
    "npx.cpu", "npx.gpu", "npx.tpu", "npx.num_gpus", "npx.device",
    "npx.dlpack", "npx.seed",
    # distributed-only (need a mesh / multiple procs)
    "npx.ring_attention",
    # in-place host mutator (exercised in tests, returns None)
    "np.fill_diagonal",
    # internal helpers leaked into namespace dir(), not ops
    "np.apply_op", "npx.apply_op", "linalg.apply_op", "fft.apply_op",
    "np.current_context", "random.current_context",
    "npx.next_key", "random.next_key",
    "np.busdaycalendar",
}


def _mat(shape, dtype="float32", seed=7):
    rng = onp.random.RandomState(seed)
    return rng.uniform(0.5, 1.5, size=shape).astype(dtype)


def build_specs(mx, LARGE):
    """Explicit argument templates for irregular signatures.

    Returns {qualname: thunk} where thunk() -> NDArray-or-tuple result.
    `LARGE=True` uses MXU-sized shapes for timing; False uses tiny shapes
    for pure coverage checking.
    """
    np = mx.np
    npx = mx.npx
    N = 1024 if LARGE else 8
    B = 32 if LARGE else 2
    a = np.array(_mat((N, N)))
    b = np.array(_mat((N, N), seed=11))
    v = np.array(_mat((N,)))
    sq = np.array(_mat((64, 64)) + onp.eye(64) * 64.0)  # well-conditioned
    spd = np.array(onp.matmul(_mat((64, 64)), _mat((64, 64)).T) +
                   onp.eye(64, dtype="float32") * 64.0)
    img = np.array(_mat((B, 16, 16, 8)))  # NHWC
    idx = np.array(onp.arange(N) % 8, dtype=onp.int32)
    seq = np.array(_mat((B, 16, 32)))     # (batch, time, feat)
    bool_a = a > 1.0

    def spec(**kw):
        return kw

    S = {}
    # --- creation ---
    for name, fn in [
        ("zeros", lambda: np.zeros((N, N))), ("ones", lambda: np.ones((N, N))),
        ("empty", lambda: np.empty((N, N))),
        ("full", lambda: np.full((N, N), 3.14)),
        ("eye", lambda: np.eye(N)), ("identity", lambda: np.identity(N)),
        ("arange", lambda: np.arange(N * N)),
        ("linspace", lambda: np.linspace(0, 1, N * N)),
        ("logspace", lambda: np.logspace(0, 1, N)),
        ("geomspace", lambda: np.geomspace(1, 10, N)),
        ("tri", lambda: np.tri(N)),
        ("indices", lambda: np.indices((N, 4))),
        ("zeros_like", lambda: np.zeros_like(a)),
        ("ones_like", lambda: np.ones_like(a)),
        ("empty_like", lambda: np.empty_like(a)),
        ("full_like", lambda: np.full_like(a, 2.0)),
        ("array", lambda: np.array(_mat((N, N)))),
        ("asarray", lambda: np.asarray(_mat((N, N)))),
        ("ascontiguousarray", lambda: np.ascontiguousarray(a)),
        ("copy", lambda: np.copy(a)),
        ("meshgrid", lambda: np.meshgrid(v, v)),
        ("fromfunction", lambda: np.fromfunction(lambda i, j: i + j, (8, 8))),
        ("fromstring", lambda: np.fromstring("1 2 3", sep=" ")),
        ("diag", lambda: np.diag(v)), ("diagflat", lambda: np.diagflat(v)),
        ("vander", lambda: np.vander(np.array(_mat((16,))))),
        ("tril_indices", lambda: np.tril_indices(16)),
        ("triu_indices", lambda: np.triu_indices(16)),
        ("diag_indices_from", lambda: np.diag_indices_from(a)),
        ("tril_indices_from", lambda: np.tril_indices_from(a)),
        ("triu_indices_from", lambda: np.triu_indices_from(a)),
        ("blackman", lambda: np.blackman(N)),
        ("hamming", lambda: np.hamming(N)), ("hanning", lambda: np.hanning(N)),
        ("kaiser", lambda: np.kaiser(N, 14.0)),
        ("bartlett", lambda: np.bartlett(N)),
        ("unravel_index", lambda: np.unravel_index(
            np.array([5, 6], dtype=onp.int32), (N, N))),
        ("ravel_multi_index", lambda: np.ravel_multi_index(
            (np.array([1, 2], dtype=onp.int64),
             np.array([3, 4], dtype=onp.int64)), (N, N))),
    ]:
        S["np." + name] = fn

    # --- shape / indexing / combining ---
    for name, fn in [
        ("reshape", lambda: np.reshape(a, (-1,))),
        ("ravel", lambda: np.ravel(a)),
        ("transpose", lambda: np.transpose(a)),
        ("swapaxes", lambda: np.swapaxes(a, 0, 1)),
        ("moveaxis", lambda: np.moveaxis(img, 1, 3)),
        ("rollaxis", lambda: np.rollaxis(img, 2)),
        ("expand_dims", lambda: np.expand_dims(a, 0)),
        ("squeeze", lambda: np.squeeze(np.expand_dims(a, 0))),
        ("broadcast_to", lambda: np.broadcast_to(v, (4, N))),
        ("broadcast_arrays", lambda: np.broadcast_arrays(v, a)),
        ("atleast_1d", lambda: np.atleast_1d(v)),
        ("atleast_2d", lambda: np.atleast_2d(v)),
        ("atleast_3d", lambda: np.atleast_3d(a)),
        ("concatenate", lambda: np.concatenate([a, b])),
        ("stack", lambda: np.stack([a, b])),
        ("vstack", lambda: np.vstack([a, b])),
        ("hstack", lambda: np.hstack([a, b])),
        ("dstack", lambda: np.dstack([a, b])),
        ("column_stack", lambda: np.column_stack([v, v])),
        ("row_stack", lambda: np.row_stack([a, b])),
        ("split", lambda: np.split(a, 2)),
        ("array_split", lambda: np.array_split(a, 3)),
        ("hsplit", lambda: np.hsplit(a, 2)),
        ("vsplit", lambda: np.vsplit(a, 2)),
        ("dsplit", lambda: np.dsplit(img, 2)),
        ("tile", lambda: np.tile(v, 2)),
        ("repeat", lambda: np.repeat(v, 2)),
        ("roll", lambda: np.roll(a, 3)),
        ("rot90", lambda: np.rot90(a)),
        ("flip", lambda: np.flip(a)), ("fliplr", lambda: np.fliplr(a)),
        ("flipud", lambda: np.flipud(a)),
        ("pad", lambda: np.pad(a, 1)),
        ("take", lambda: np.take(v, idx)),
        ("take_along_axis", lambda: np.take_along_axis(
            a, np.argsort(a, axis=1), axis=1)),
        ("put_along_axis", lambda: np.put_along_axis(
            np.copy(a), np.argsort(a, axis=1), 0.0, axis=1)),
        ("choose", lambda: np.choose(np.array([0, 1], dtype=onp.int32),
                                     [v[:2], v[1:3]])),
        ("compress", lambda: np.compress(np.array([True, False] * (N // 2)),
                                         v)),
        ("extract", lambda: np.extract(bool_a, a)),
        ("select", lambda: np.select([bool_a], [a], 0.0)),
        ("where", lambda: np.where(bool_a, a, b)),
        ("argwhere", lambda: np.argwhere(bool_a)),
        ("flatnonzero", lambda: np.flatnonzero(a)),
        ("nonzero", lambda: np.nonzero(bool_a)),
        ("delete", lambda: np.delete(v, 0)),
        ("insert", lambda: np.insert(v, 0, 1.0)),
        ("append", lambda: np.append(v, 1.0)),
        ("resize", lambda: np.resize(v, (2, N))),
        ("trim_zeros", lambda: np.trim_zeros(np.array([0., 1., 2., 0.]))),
        ("unique", lambda: np.unique(idx)),
        ("ediff1d", lambda: np.ediff1d(v)),
        ("searchsorted", lambda: np.searchsorted(np.sort(v), v)),
        ("digitize", lambda: np.digitize(v, np.array([0.5, 1.0, 1.5]))),
        ("piecewise", lambda: np.piecewise(
            v, [v < 1.0, v >= 1.0], [-1.0, 1.0])),
        ("apply_along_axis", lambda: np.apply_along_axis(
            lambda x: x, 0, _mat((4, 4)))),
        ("apply_over_axes", lambda: np.apply_over_axes(
            onp.sum, _mat((4, 4)), [0])),
    ]:
        S["np." + name] = fn

    # --- binary with special args / reductions with axes ---
    for name, fn in [
        ("matmul", lambda: np.matmul(a, b)),
        ("dot", lambda: np.dot(a, b)),
        ("vdot", lambda: np.vdot(v, v)),
        ("inner", lambda: np.inner(v, v)),
        ("outer", lambda: np.outer(v[:64], v[:64])),
        ("kron", lambda: np.kron(np.array(_mat((8, 8))),
                                 np.array(_mat((8, 8))))),
        ("tensordot", lambda: np.tensordot(a, b)),
        ("einsum", lambda: np.einsum("ij,jk->ik", a, b)),
        ("cross", lambda: np.cross(np.array(_mat((N, 3))),
                                   np.array(_mat((N, 3))))),
        ("trace", lambda: np.trace(a)),
        ("clip", lambda: np.clip(a, 0.7, 1.3)),
        ("histogram", lambda: np.histogram(v)),
        ("histogram2d", lambda: np.histogram2d(v, v)),
        ("histogramdd", lambda: np.histogramdd(a[:, :2])),
        ("histogram_bin_edges", lambda: np.histogram_bin_edges(v)),
        ("bincount", lambda: np.bincount(idx)),
        ("corrcoef", lambda: np.corrcoef(a[:8])),
        ("cov", lambda: np.cov(a[:8])),
        ("convolve", lambda: np.convolve(v[:256], v[:32])),
        ("correlate", lambda: np.correlate(v[:256], v[:32])),
        ("interp", lambda: np.interp(v, np.sort(v), v)),
        ("gradient", lambda: np.gradient(a)),
        ("diff", lambda: np.diff(v)),
        ("trapz", lambda: np.trapz(v)),
        ("percentile", lambda: np.percentile(a, 50)),
        ("quantile", lambda: np.quantile(a, 0.5)),
        ("nanpercentile", lambda: np.nanpercentile(a, 50)),
        ("nanquantile", lambda: np.nanquantile(a, 0.5)),
        ("median", lambda: np.median(a)),
        ("average", lambda: np.average(a, weights=np.ones_like(a))),
        ("ptp", lambda: np.ptp(a)),
        ("count_nonzero", lambda: np.count_nonzero(a)),
        ("allclose", lambda: np.allclose(a, a)),
        ("isclose", lambda: np.isclose(a, a)),
        ("array_equal", lambda: np.array_equal(a, a)),
        ("array_equiv", lambda: np.array_equiv(a, a)),
        ("isin", lambda: np.isin(idx, np.array([1, 2], dtype=onp.int32))),
        ("in1d", lambda: np.in1d(idx, np.array([1, 2], dtype=onp.int32))),
        ("intersect1d", lambda: np.intersect1d(idx, idx)),
        ("union1d", lambda: np.union1d(idx, idx)),
        ("setdiff1d", lambda: np.setdiff1d(idx, idx)),
        ("setxor1d", lambda: np.setxor1d(idx, idx)),
        ("polyval", lambda: np.polyval(v[:4], v)),
        ("polyfit", lambda: np.polyfit(v[:64], v[:64], 2)),
        ("poly", lambda: np.poly(v[:4])),
        ("roots", lambda: np.roots(v[:5])),
        ("heaviside", lambda: np.heaviside(a - 1.0, 0.5)),
        ("float_power", lambda: np.float_power(a, 2.0)),
        ("divmod", lambda: np.divmod(a, b)),
        ("frexp", lambda: np.frexp(a)),
        ("ldexp", lambda: np.ldexp(a, np.array(onp.ones((N, N),
                                                        dtype=onp.int32)))),
        ("modf", lambda: np.modf(a)),
        ("copysign", lambda: np.copysign(a, b)),
        ("nextafter", lambda: np.nextafter(a, b)),
        ("spacing", lambda: np.spacing(a)),
        ("angle", lambda: np.angle(a)),
        ("real", lambda: np.real(a)), ("imag", lambda: np.imag(a)),
        ("conj", lambda: np.conj(a)), ("conjugate", lambda: np.conjugate(a)),
        ("i0", lambda: np.i0(v)),
        ("sinc", lambda: np.sinc(a)),
        ("unwrap", lambda: np.unwrap(v)),
        ("nan_to_num", lambda: np.nan_to_num(a)),
        ("lexsort", lambda: np.lexsort((v[:64], v[:64]))),
        ("msort", lambda: np.msort(a)),
        ("partition", lambda: np.partition(a, 4)),
        ("argpartition", lambda: np.argpartition(a, 4)),
        ("sort_complex", lambda: np.sort_complex(v[:64])),
        ("ix_", lambda: np.ix_(onp.arange(4), onp.arange(4))),
        ("fromiter", lambda: np.fromiter(range(16), dtype="float32")),
        ("matrix_power", lambda: np.matrix_power(sq, 3)
            if hasattr(np, "matrix_power") else np.linalg.matrix_power(sq, 3)),
        ("require", lambda: np.require(_mat((4, 4)))),
        ("packbits", lambda: np.packbits(onp.array([1, 0, 1], dtype=onp.uint8))),
        ("unpackbits", lambda: np.unpackbits(
            onp.array([7], dtype=onp.uint8))),
    ]:
        S["np." + name] = fn

    # --- financial ---
    for name, fn in [
        ("fv", lambda: np.fv(0.05 / 12, 120, -100, -100)),
        ("pv", lambda: np.pv(0.05 / 12, 120, -100, 15692.93)),
        ("npv", lambda: np.npv(0.28, [-100, 39, 59, 55, 20])),
        ("pmt", lambda: np.pmt(0.075 / 12, 180, 200000)),
        ("ppmt", lambda: np.ppmt(0.0824 / 12, 1, 12, 2500)),
        ("ipmt", lambda: np.ipmt(0.0824 / 12, 1, 12, 2500)),
        ("irr", lambda: np.irr([-100, 39, 59, 55, 20])),
        ("mirr", lambda: np.mirr([-100, 39, 59, 55, 20], 0.1, 0.12)),
        ("nper", lambda: np.nper(0.07 / 12, -150, 8000)),
        ("rate", lambda: np.rate(10, 0, -3500, 10000)),
    ]:
        S["np." + name] = fn

    # --- linalg ---
    L = np.linalg
    for name, fn in [
        ("norm", lambda: L.norm(a)),
        ("svd", lambda: L.svd(sq)), ("qr", lambda: L.qr(sq)),
        ("cholesky", lambda: L.cholesky(spd)),
        ("inv", lambda: L.inv(sq)), ("pinv", lambda: L.pinv(sq)),
        ("det", lambda: L.det(sq)), ("slogdet", lambda: L.slogdet(sq)),
        ("solve", lambda: L.solve(sq, np.array(_mat((64, 4))))),
        ("lstsq", lambda: L.lstsq(sq, np.array(_mat((64, 4))))),
        ("tensorinv", lambda: L.tensorinv(
            np.array((_mat((24, 24)) + onp.eye(24, dtype="float32") * 24.0)
                     .reshape(4, 6, 8, 3)), ind=2)),
        ("tensorsolve", lambda: L.tensorsolve(
            np.array(_mat((24, 24)).reshape(4, 6, 8, 3)
                     + onp.eye(24).reshape(4, 6, 8, 3)),
            np.array(_mat((4, 6))))),
        ("eig", lambda: L.eig(sq)), ("eigh", lambda: L.eigh(spd)),
        ("eigvals", lambda: L.eigvals(sq)),
        ("eigvalsh", lambda: L.eigvalsh(spd)),
        ("matrix_rank", lambda: L.matrix_rank(sq)),
        ("matrix_power", lambda: L.matrix_power(sq, 3)),
        ("multi_dot", lambda: L.multi_dot([sq, sq, sq])),
        ("cond", lambda: L.cond(sq)),
    ]:
        S["linalg." + name] = fn

    # --- fft ---
    F = np.fft
    cv = np.array(_mat((256,)))
    for name, fn in [
        ("fft", lambda: F.fft(cv)), ("ifft", lambda: F.ifft(F.fft(cv))),
        ("rfft", lambda: F.rfft(cv)), ("irfft", lambda: F.irfft(F.rfft(cv))),
        ("fft2", lambda: F.fft2(sq)), ("ifft2", lambda: F.ifft2(F.fft2(sq))),
        ("rfft2", lambda: F.rfft2(sq)),
        ("irfft2", lambda: F.irfft2(F.rfft2(sq))),
        ("fftn", lambda: F.fftn(sq)), ("ifftn", lambda: F.ifftn(F.fftn(sq))),
        ("rfftn", lambda: F.rfftn(sq)),
        ("irfftn", lambda: F.irfftn(F.rfftn(sq))),
        ("hfft", lambda: F.hfft(F.rfft(cv))),
        ("ihfft", lambda: F.ihfft(cv)),
        ("fftfreq", lambda: F.fftfreq(256)),
        ("rfftfreq", lambda: F.rfftfreq(256)),
        ("fftshift", lambda: F.fftshift(cv)),
        ("ifftshift", lambda: F.ifftshift(cv)),
    ]:
        S["fft." + name] = fn

    # --- random (size kwarg) ---
    R = np.random
    for name in ["uniform", "normal", "lognormal", "logistic", "gumbel",
                 "laplace", "rayleigh", "exponential", "weibull", "pareto",
                 "power", "chisquare", "standard_normal",
                 "standard_exponential", "standard_cauchy", "standard_gamma",
                 "standard_t"]:
        fn = getattr(R, name, None)
        if fn is None:
            continue
        if name in ("weibull", "pareto", "power", "chisquare", "standard_t",
                    "standard_gamma"):
            S["random." + name] = (lambda f=fn: f(2.0, size=(N, N)))
        else:
            S["random." + name] = (lambda f=fn: f(size=(N, N)))
    for name, fn in [
        ("randint", lambda: R.randint(0, 10, size=(N, N))),
        ("randn", lambda: R.randn(N, N)),
        ("rand", lambda: R.rand(N, N)),
        ("random", lambda: R.random(size=(N, N))),
        ("random_sample", lambda: R.random_sample((N, N))),
        ("ranf", lambda: R.ranf((N, N))),
        ("sample", lambda: R.sample((N, N))),
        ("beta", lambda: R.beta(1.0, 2.0, size=(N, N))),
        ("gamma", lambda: R.gamma(2.0, 1.0, size=(N, N))),
        ("f", lambda: R.f(2.0, 3.0, size=(N, N))),
        ("binomial", lambda: R.binomial(10, 0.5, size=(N, N))),
        ("negative_binomial", lambda: R.negative_binomial(5, 0.5,
                                                          size=(N, N))),
        ("poisson", lambda: R.poisson(3.0, size=(N, N))),
        ("geometric", lambda: R.geometric(0.3, size=(N, N))),
        ("multinomial", lambda: R.multinomial(8, [0.25] * 4, size=(16,))),
        ("multivariate_normal", lambda: R.multivariate_normal(
            np.zeros(4), np.eye(4), size=(16,))),
        ("dirichlet", lambda: R.dirichlet(onp.ones(4), size=(16,))),
        ("choice", lambda: R.choice(N, size=(32,))),
        ("permutation", lambda: R.permutation(v)),
        ("shuffle", lambda: R.shuffle(np.copy(v))),
        ("triangular", lambda: R.triangular(0.0, 0.5, 1.0, size=(N, N))),
        ("vonmises", lambda: R.vonmises(0.0, 1.0, size=(N, N))),
        ("wald", lambda: R.wald(1.0, 1.0, size=(N, N))),
        ("zipf", lambda: R.zipf(2.0, size=(N, N))),
        ("hypergeometric", lambda: R.hypergeometric(10, 10, 10,
                                                    size=(N, N))),
        ("noncentral_chisquare", lambda: R.noncentral_chisquare(
            2.0, 1.0, size=(N, N))),
        ("noncentral_f", lambda: R.noncentral_f(2.0, 3.0, 1.0, size=(N, N))),
        ("bytes", lambda: R.bytes(16)),
    ]:
        if hasattr(R, name):
            S["random." + name] = fn

    # --- npx (nn ops with parameters) ---
    w_fc = np.array(_mat((16, 32)))
    b_fc = np.array(_mat((16,)))
    kern = np.array(_mat((4, 3, 3, 8)))   # HWIO
    gamma = np.ones(8)
    beta = np.zeros(8)
    rmean = np.zeros(8)
    rvar = np.ones(8)
    emb_w = np.array(_mat((32, 16)))
    # a minimal registered CustomOp so npx.custom is sweepable
    from mxnet_tpu import operator as _operator
    if "_opperf_scale2" not in _operator.get_all_registered_operators():
        @_operator.register("_opperf_scale2")
        class _Scale2Prop(_operator.CustomOpProp):
            def create_operator(self, ctx, shapes, dtypes):
                class _Op(_operator.CustomOp):
                    def forward(self, is_train, req, in_data, out_data,
                                aux):
                        self.assign(out_data[0], req[0], in_data[0] * 2)
                return _Op()

    for name, fn in [
        ("activation", lambda: npx.activation(a, "relu")),
        ("custom", lambda: npx.custom(a, op_type="_opperf_scale2")),
        ("relu", lambda: npx.relu(a)), ("sigmoid", lambda: npx.sigmoid(a)),
        ("log_sigmoid", lambda: npx.log_sigmoid(a)),
        ("softsign", lambda: npx.softsign(a)),
        ("softplus", lambda: npx.softplus(a)),
        ("mish", lambda: npx.mish(a)), ("gelu", lambda: npx.gelu(a)),
        ("silu", lambda: npx.silu(a)),
        ("leaky_relu", lambda: npx.leaky_relu(a)),
        ("hard_sigmoid", lambda: npx.hard_sigmoid(a)),
        ("hard_swish", lambda: npx.hard_swish(a)),
        ("softmax", lambda: npx.softmax(a)),
        ("log_softmax", lambda: npx.log_softmax(a)),
        ("masked_softmax", lambda: npx.masked_softmax(a, a > 1.0)),
        ("masked_log_softmax", lambda: npx.masked_log_softmax(a, a > 1.0)),
        ("softmin", lambda: npx.softmin(a)),
        ("fully_connected", lambda: npx.fully_connected(
            seq.reshape(-1, 32), w_fc, b_fc, num_hidden=16)),
        ("convolution", lambda: npx.convolution(
            img, kern, kernel=(3, 3), num_filter=4, layout="NHWC")),
        ("deconvolution", lambda: npx.deconvolution(
            img, np.array(_mat((8, 3, 3, 4))), kernel=(3, 3), num_filter=4,
            layout="NHWC")),
        ("pooling", lambda: npx.pooling(img, kernel=(2, 2), pool_type="max",
                                        layout="NHWC")),
        ("batch_norm", lambda: npx.batch_norm(img, gamma, beta, rmean, rvar,
                                              axis=-1)),
        ("layer_norm", lambda: npx.layer_norm(img, gamma, beta)),
        ("group_norm", lambda: npx.group_norm(
            np.array(_mat((B, 8, 16, 16))), np.ones(8), np.zeros(8),
            num_groups=2)),
        ("instance_norm", lambda: npx.instance_norm(
            np.array(_mat((B, 8, 16, 16))), gamma, beta)),
        ("rms_norm", lambda: npx.rms_norm(img, gamma)),
        ("l2_normalization", lambda: npx.l2_normalization(a)),
        ("dropout", lambda: npx.dropout(a, 0.5, mode="always")),
        ("embedding", lambda: npx.embedding(idx[:16], emb_w)),
        ("one_hot", lambda: npx.one_hot(idx[:16], 8)),
        ("topk", lambda: npx.topk(a, k=4)),
        ("pick", lambda: npx.pick(a, idx)),
        ("batch_dot", lambda: npx.batch_dot(
            np.array(_mat((B, 32, 32))), np.array(_mat((B, 32, 32))))),
        ("gather_nd", lambda: npx.gather_nd(
            a, np.array(onp.stack([onp.arange(4)] * 2), dtype=onp.int32))),
        ("sequence_mask", lambda: npx.sequence_mask(
            np.swapaxes(seq, 0, 1),
            np.array(onp.full((B,), 8), dtype=onp.int32),
            use_sequence_length=True)),
        ("index_add", lambda: npx.index_add(
            np.copy(v), np.array([[0, 1]], dtype=onp.int32),
            np.array([1.0, 2.0]))),
        ("index_update", lambda: npx.index_update(
            np.copy(v), np.array([[0, 1]], dtype=onp.int32),
            np.array([1.0, 2.0]))),
        ("scatter_nd", lambda: npx.scatter_nd(
            np.array([9.0, 8.0]), np.array([[0, 2]], dtype=onp.int32),
            (N,))),
        ("sequence_last", lambda: npx.sequence_last(
            np.swapaxes(seq, 0, 1))),
        ("sequence_reverse", lambda: npx.sequence_reverse(
            np.swapaxes(seq, 0, 1))),
        ("shape_array", lambda: npx.shape_array(a)),
        ("reshape_like", lambda: npx.reshape_like(a, a)),
        ("broadcast_like", lambda: npx.broadcast_like(v, a)),
        ("arange_like", lambda: npx.arange_like(v)),
        ("slice_axis", lambda: npx.slice_axis(a, 0, 0, 4)),
        ("slice", lambda: npx.slice(a, (0, 0), (4, 4))),
        ("slice_like", lambda: npx.slice_like(a, a)),
        ("ctc_loss", lambda: npx.ctc_loss(
            np.array(_mat((16, B, 8))),
            np.array(onp.ones((B, 4), dtype=onp.float32)))),
        ("multibox_prior", lambda: npx.multibox_prior(
            img, sizes=[0.5], ratios=[1.0])),
        ("roi_pooling", lambda: npx.roi_pooling(
            np.array(_mat((1, 8, 16, 16))),
            np.array([[0, 0, 0, 7, 7]], dtype=onp.float32),
            pooled_size=(2, 2), spatial_scale=1.0)),
        ("boolean_mask", lambda: npx.boolean_mask(a, v > 1.0)),
        ("foreach", lambda: npx.foreach(
            lambda x, s: (x * 2.0, s), seq, np.zeros(()))),
        ("while_loop", lambda: npx.while_loop(
            lambda s: s[0] < 4, lambda s: ((s[0],), (s[0] + 1,)),
            (np.zeros(()),), max_iterations=4)),
        ("cond", lambda: npx.cond(
            lambda: True, lambda: v * 2.0, lambda: v)),
        ("rnn", lambda: npx.rnn(
            np.array(_mat((16, B, 8))),
            np.array(_mat((4 * 32 * (8 + 32 + 2),))),
            np.array(_mat((1, B, 32))),
            np.array(_mat((1, B, 32))),
            mode="lstm", state_size=32, num_layers=1)),
        ("flash_attention", lambda: npx.flash_attention(
            np.array(_mat((2, 4, 128, 64))), np.array(_mat((2, 4, 128, 64))),
            np.array(_mat((2, 4, 128, 64))))),
        ("multi_sum_sq", lambda: npx.multi_sum_sq([v, v])
            if hasattr(npx, "multi_sum_sq") else None),
    ]:
        if hasattr(npx, name):
            S["npx." + name] = fn
    return S


def enumerate_ops(mx):
    """All public callables in the op namespaces -> {qualname: callable}."""
    out = {}
    mods = [("np", mx.np), ("npx", mx.npx), ("linalg", mx.np.linalg),
            ("random", mx.np.random), ("fft", mx.np.fft)]
    for prefix, mod in mods:
        for n in dir(mod):
            if n.startswith("_"):
                continue
            obj = getattr(mod, n, None)
            if not callable(obj) or isinstance(obj, type):
                continue
            out[f"{prefix}.{n}"] = obj
    return out


def generic_templates(mx, LARGE):
    np = mx.np
    N = 1024 if LARGE else 8
    a = np.array(_mat((N, N)))
    b = np.array(_mat((N, N), seed=3))
    pos = np.array(_mat((N, N)) * 0.4 + 0.05)   # in (0,1) for arc fns
    iarr = np.array(onp.arange(N * N).reshape(N, N) % 7 + 1,
                    dtype=onp.int32)
    return [
        lambda f: f(a),
        lambda f: f(pos),
        lambda f: f(a, b),
        lambda f: f(pos, pos),
        lambda f: f(iarr),
        lambda f: f(iarr, iarr),
        lambda f: f((N, N)),
        lambda f: f(N),
    ]


def sync(result, mx):
    """Force execution of whatever an op returned."""
    seen = []

    def walk(r):
        if r is None or isinstance(r, (bool, int, float, complex, str,
                                       onp.generic, onp.dtype)):
            return
        if isinstance(r, onp.ndarray):
            return
        if isinstance(r, (list, tuple)):
            for x in r:
                walk(x)
            return
        if isinstance(r, dict):
            for x in r.values():
                walk(x)
            return
        if hasattr(r, "wait_to_read"):
            seen.append(r)

    walk(result)
    for r in seen:
        r.wait_to_read()


# our np/npx names -> the reference registry names used in its opperf
# result tables (benchmark/opperf/results/*.md)
_REF_NAME_ALIASES = {
    "add": "elemwise_add", "subtract": "elemwise_sub",
    "multiply": "elemwise_mul", "divide": "elemwise_div",
    "maximum": "broadcast_maximum",
    "minimum": "broadcast_minimum", "mod": "broadcast_mod",
    "matmul": "batch_dot", "concatenate": "concat",
    "fully_connected": "FullyConnected", "convolution": "Convolution",
    "pooling": "Pooling", "batch_norm": "BatchNorm",
    "leaky_relu": "LeakyReLU", "activation": "Activation",
    "dropout": "Dropout", "embedding": "Embedding",
}


def load_ref_table(path):
    """Min forward latency (ms) per op from the reference's opperf
    results markdown (| op | fwd | bwd | mem | inputs |)."""
    table = {}
    try:
        with open(path) as f:
            for line in f:
                parts = [c.strip() for c in line.strip().split("|")]
                if len(parts) < 5 or not parts[1] or parts[1] in (
                        "Operator", ":---:", "---"):
                    continue
                try:
                    fwd = float(parts[2])
                except ValueError:
                    continue
                name = parts[1]
                if name not in table or fwd < table[name]:
                    table[name] = fwd
    except OSError:
        return {}
    return table


def annotate_vs_ref(results, ref_table):
    """Attach ref_gpu_ms + vs_ref (reference V100 latency / ours;
    >1 means this repo's op is faster than the reference's GPU op)."""
    n = 0
    for qual, rec in results.items():
        if not rec.get("covered") or not rec.get("latency_ms"):
            continue
        base = qual.split(".", 1)[-1]
        ref = ref_table.get(base) or \
            ref_table.get(_REF_NAME_ALIASES.get(base, ""))
        if ref is None:
            continue
        rec["ref_gpu_ms"] = ref
        rec["vs_ref"] = round(ref / rec["latency_ms"], 3)
        n += 1
    return n


REF_GPU_MD = ("/root/reference/benchmark/opperf/results/"
              "mxnet_operator_benchmark_results_gpu.md")

# Model-importance ordering for --top N (budget-gated TPU windows run
# the ops that dominate real models first; the rest alphabetical).
PRIORITY_SUBSTR = [
    "dot", "matmul", "conv", "dense", "fully", "batch_norm", "layer_norm",
    "relu", "activation", "softmax", "log_softmax", "add", "multiply",
    "subtract", "divide", "exp", "sum", "mean", "max", "transpose",
    "reshape", "concatenate", "split", "where", "pool", "embedding",
    "take", "gather", "tanh", "sigmoid", "sqrt", "power", "norm",
    "argmax", "topk", "einsum", "cumsum", "clip", "pad", "stack",
]


def _priority_key(name: str):
    low = name.lower()
    for i, sub in enumerate(PRIORITY_SUBSTR):
        if sub in low:
            return (0, i, name)
    return (1, 0, name)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--output", default=None)
    p.add_argument("--runs", type=int, default=10)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--platform", default="cpu", choices=["cpu", "tpu"])
    p.add_argument("--filter", default=None)
    p.add_argument("--small", action="store_true",
                   help="tiny shapes: coverage only, skip timing")
    p.add_argument("--top", type=int, default=None,
                   help="only the N most model-important ops (TPU "
                        "window budget fitting)")
    p.add_argument("--budget", type=float, default=None,
                   help="wall-clock seconds; stop sweeping (and still "
                        "write output) when exceeded")
    p.add_argument("--resume", action="store_true",
                   help="seed from an existing --output file and skip "
                        "already-covered ops (window accumulation)")
    p.add_argument("--ref-table", default=REF_GPU_MD,
                   help="reference opperf results .md for vs_ref")
    args = p.parse_args()
    t_start = time.monotonic()

    if args.platform == "cpu":
        import tpu_platform
        tpu_platform.force_cpu(1)
    import mxnet_tpu as mx
    import jax
    platform = jax.devices()[0].platform

    LARGE = not args.small
    specs = build_specs(mx, LARGE)
    ops = enumerate_ops(mx)
    for q in specs:
        ops.setdefault(q, None)
    gen = generic_templates(mx, LARGE)

    results = {}
    covered = 0
    total = 0
    names = sorted(n for n in ops if n not in SKIP)
    if args.filter:
        names = [n for n in names if args.filter in n]
    if args.top is not None:
        names = sorted(names, key=_priority_key)[:args.top]

    # --resume: a prior (possibly partial) output file seeds results,
    # and already-measured ops are skipped — short accelerator windows
    # accumulate across runs instead of each restart clobbering the
    # biggest table collected so far. Covered prior entries are seeded
    # UPFRONT (not lazily as the loop reaches them) so a budget break
    # or mid-sweep SIGKILL can never rewrite the file without them.
    # (counters stay sweep-scoped: seeded ops only count when the
    # current names selection reaches them, so --filter/--top stats
    # aren't inflated by prior full-sweep records)
    if args.resume and args.output and os.path.exists(args.output):
        try:
            with open(args.output) as f:
                for q, rec in json.load(f).get("ops", {}).items():
                    if rec.get("covered"):
                        results[q] = rec
        except (OSError, json.JSONDecodeError):
            pass

    def flush_output(partial):
        if not args.output:
            return
        summary = {"total": total, "covered": covered,
                   "platform": platform, "runs": args.runs,
                   "warmup": args.warmup, "partial": partial}
        tmp = args.output + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"summary": summary, "ops": results}, f,
                      indent=1, sort_keys=True)
        os.replace(tmp, args.output)

    budget_hit = False
    for qual in names:
        if qual in results:  # seeded from a prior resumed run
            total += 1
            covered += 1
            continue
        if args.budget is not None \
                and time.monotonic() - t_start > args.budget:
            budget_hit = True
            print(f"[opperf] budget {args.budget}s exceeded after "
                  f"{total} ops; emitting partial table",
                  file=sys.stderr, flush=True)
            break
        if args.output and total and total % 20 == 0:
            flush_output(partial=True)  # killed child still leaves data
        total += 1
        thunk = specs.get(qual)
        err = None
        if thunk is None:
            fn = ops[qual]
            for tmpl in gen:
                try:
                    r = tmpl(fn)
                    sync(r, mx)
                    thunk = (lambda t=tmpl, f=fn: t(f))
                    break
                except Exception as e:  # noqa: BLE001 — trial dispatch
                    err = f"{type(e).__name__}: {e}"
            else:
                results[qual] = {"covered": False, "latency_ms": None,
                                 "error": (err or "no template")[:200]}
                continue
        try:
            for _ in range(args.warmup):
                sync(thunk(), mx)
            t0 = time.perf_counter()
            for _ in range(args.runs):
                sync(thunk(), mx)
            dt = (time.perf_counter() - t0) / args.runs * 1e3
            results[qual] = {"covered": True,
                             "latency_ms": round(dt, 4), "error": None}
            covered += 1
        except Exception as e:  # noqa: BLE001 — report, don't abort sweep
            results[qual] = {"covered": False, "latency_ms": None,
                             "error": f"{type(e).__name__}: {e}"[:200]}

    ref_table = load_ref_table(args.ref_table)
    n_ref = annotate_vs_ref(results, ref_table) if ref_table else 0

    summary = {"total": total, "covered": covered,
               "coverage_pct": round(100.0 * covered / max(total, 1), 1),
               "platform": platform,
               "runs": args.runs, "warmup": args.warmup,
               "large_shapes": LARGE,
               "vs_ref_ops": n_ref,
               "budget_hit": budget_hit,
               "elapsed_s": round(time.monotonic() - t_start, 1)}
    doc = {"summary": summary, "ops": results}
    text = json.dumps(doc, indent=1, sort_keys=True)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text + "\n")
    print(json.dumps(summary))
    failed = [q for q, r in results.items() if not r["covered"]]
    if failed:
        print(f"uncovered ({len(failed)}):", file=sys.stderr)
        for q in failed:
            print(f"  {q}: {results[q]['error']}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
