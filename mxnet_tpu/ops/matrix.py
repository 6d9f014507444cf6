"""Shape-manipulation, indexing and linear-algebra operators.

Ref: src/operator/tensor/matrix_op.cc (Reshape/Transpose/slice/concat/...),
dot.cc (dot, batch_dot), indexing_op.cc (Embedding/take/one_hot/pick/
gather_nd/scatter_nd). ``dot``/``batch_dot`` are the MXU-bound ops — they
lower straight to XLA dot_general, which the TPU compiler tiles onto the
systolic array; everything else here is layout/gather work.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import register


# -- linalg -----------------------------------------------------------------
@register("dot")
def dot(lhs, rhs, *, transpose_a=False, transpose_b=False):
    """Matrix product; >2-D inputs behave like MXNet dot (reshape to 2-D)."""
    a, b = lhs, rhs
    if a.ndim > 2:
        a = a.reshape((-1, a.shape[-1])) if not transpose_a else a.reshape((a.shape[0], -1))
    if transpose_a:
        a = a.T
    if b.ndim > 2:
        b = b.reshape((b.shape[0], -1)) if not transpose_b else b.reshape((-1, b.shape[-1]))
    if transpose_b:
        b = b.T
    if a.ndim == 1 and b.ndim == 1:
        return jnp.dot(a, b).reshape(1)
    return jnp.matmul(a, b)


@register("batch_dot")
def batch_dot(lhs, rhs, *, transpose_a=False, transpose_b=False):
    a = jnp.swapaxes(lhs, -1, -2) if transpose_a else lhs
    b = jnp.swapaxes(rhs, -1, -2) if transpose_b else rhs
    return jnp.matmul(a, b)


@register("linalg_gemm2")
def linalg_gemm2(A, B, *, transpose_a=False, transpose_b=False, alpha=1.0, axis=-3):
    a = jnp.swapaxes(A, -1, -2) if transpose_a else A
    b = jnp.swapaxes(B, -1, -2) if transpose_b else B
    return alpha * jnp.matmul(a, b)


@register("L2Normalization")
def l2_normalization(data, *, eps=1e-10, mode="instance"):
    if mode == "instance":
        ax = tuple(range(1, data.ndim))
    elif mode == "channel":
        ax = (1,)
    elif mode == "spatial":
        ax = tuple(range(2, data.ndim))
    else:
        raise ValueError(mode)
    nrm = jnp.sqrt(jnp.sum(jnp.square(data), axis=ax, keepdims=True) + eps)
    return data / nrm


# -- shape ops --------------------------------------------------------------
@register("Reshape", aliases=["reshape"])
def reshape(data, *, shape=None, reverse=False):
    """MXNet reshape with special codes 0 (keep), -1 (infer), -2 (rest),
    -3 (merge two), -4 (split) — ref: matrix_op-inl.h :: InferReshapeShape."""
    shp = tuple(int(s) for s in shape)
    src = list(data.shape)
    if reverse:
        src = src[::-1]
        shp = tuple(reversed(shp))
    out = []
    i = 0  # index into src
    j = 0
    while j < len(shp):
        s = shp[j]
        if s == 0:
            out.append(src[i]); i += 1
        elif s == -1:
            out.append(-1); i += 1
        elif s == -2:
            out.extend(src[i:]); i = len(src)
        elif s == -3:
            out.append(src[i] * src[i + 1]); i += 2
        elif s == -4:
            d1, d2 = shp[j + 1], shp[j + 2]
            cur = src[i]
            if d1 == -1:
                d1 = cur // d2
            if d2 == -1:
                d2 = cur // d1
            out.extend([d1, d2]); i += 1; j += 2
        else:
            out.append(s)
            if i < len(src):
                i += 1
        j += 1
    if reverse:
        out = out[::-1]
    return data.reshape(tuple(out))


@register("reshape_like")
def reshape_like(lhs, rhs):
    return lhs.reshape(rhs.shape)


@register("shape_array")
def shape_array(data):
    return jnp.asarray(data.shape, dtype=jnp.int64 if False else jnp.int32)


@register("size_array")
def size_array(data):
    return jnp.asarray([data.size], dtype=jnp.int32)


@register("Flatten", aliases=["flatten"])
def flatten_op(data):
    return data.reshape((data.shape[0], -1))


@register("transpose")
def transpose(data, *, axes=None):
    if axes is None or axes == ():
        return jnp.transpose(data)
    return jnp.transpose(data, tuple(int(a) for a in axes))


@register("expand_dims")
def expand_dims(data, *, axis):
    return jnp.expand_dims(data, int(axis))


@register("squeeze")
def squeeze(data, *, axis=None):
    if axis is None:
        return jnp.squeeze(data)
    ax = axis if isinstance(axis, (tuple, list)) else (axis,)
    return jnp.squeeze(data, tuple(int(a) for a in ax))


@register("swapaxes", aliases=["SwapAxis"])
def swapaxes(data, *, dim1=0, dim2=0):
    return jnp.swapaxes(data, int(dim1), int(dim2))


@register("Concat", aliases=["concat"])
def concat(*data, dim=1):
    return jnp.concatenate(data, axis=int(dim))


@register("stack")
def stack(*data, axis=0):
    return jnp.stack(data, axis=int(axis))


@register("split", aliases=["SliceChannel"], num_outputs=None)
def split(data, *, num_outputs, axis=1, squeeze_axis=False):
    parts = jnp.split(data, int(num_outputs), axis=int(axis))
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=int(axis)) for p in parts]
    return tuple(parts) if len(parts) > 1 else parts[0]


@register("slice", aliases=["crop"])
def slice_op(data, *, begin, end, step=None):
    nd = data.ndim
    begin = tuple(begin) + (None,) * (nd - len(begin))
    end = tuple(end) + (None,) * (nd - len(end))
    step = tuple(step) + (None,) * (nd - len(step)) if step else (None,) * nd
    idx = tuple(slice(b, e, s) for b, e, s in zip(begin, end, step))
    return data[idx]


@register("slice_axis")
def slice_axis(data, *, axis, begin, end=None):
    ax = int(axis) % data.ndim
    idx = [slice(None)] * data.ndim
    idx[ax] = slice(begin, end)
    return data[tuple(idx)]


@register("slice_like")
def slice_like(data, shape_like, *, axes=()):
    axes = tuple(axes) if axes else tuple(range(shape_like.ndim))
    idx = [slice(None)] * data.ndim
    for a in axes:
        idx[a % data.ndim] = slice(0, shape_like.shape[a % shape_like.ndim])
    return data[tuple(idx)]


def _decode_index(enc):
    """Decode the hashable index form produced by ndarray._encode_index."""
    out = []
    for e in enc:
        if e[0] == "i":
            out.append(e[1])
        elif e[0] == "s":
            out.append(slice(e[1], e[2], e[3]))
        else:
            out.append(None)
    return tuple(out)


@register("_view_index")
def view_index(data, *, index):
    """Recorded basic indexing (ref: NDArray slice/at recorded as
    differentiable slice ops under autograd)."""
    return data[_decode_index(index)]


@register("_slice_assign")
def slice_assign(data, val, *, index):
    """Recorded slice assignment (ref: _slice_assign op): returns data
    with the indexed region replaced by val; vjp passes zeros into the
    assigned region of d(data) and the gathered region into d(val)."""
    return data.at[_decode_index(index)].set(val.astype(data.dtype))


@register("tile")
def tile(data, *, reps):
    return jnp.tile(data, tuple(int(r) for r in reps))


@register("repeat")
def repeat(data, *, repeats, axis=None):
    return jnp.repeat(data, int(repeats), axis=None if axis is None else int(axis))


@register("flip", aliases=["reverse"])
def flip(data, *, axis):
    ax = axis if isinstance(axis, (tuple, list)) else (axis,)
    return jnp.flip(data, tuple(int(a) for a in ax))


@register("Pad", aliases=["pad"])
def pad(data, *, mode="constant", pad_width, constant_value=0.0):
    pw = tuple(pad_width)
    pairs = tuple((int(pw[2 * i]), int(pw[2 * i + 1])) for i in range(len(pw) // 2))
    if mode == "constant":
        return jnp.pad(data, pairs, mode="constant", constant_values=constant_value)
    if mode == "edge":
        return jnp.pad(data, pairs, mode="edge")
    if mode == "reflect":
        return jnp.pad(data, pairs, mode="reflect")
    raise ValueError(mode)


@register("broadcast_to")
def broadcast_to(data, *, shape):
    tgt = tuple(int(s) if int(s) != 0 else data.shape[i]
                for i, s in enumerate(shape))
    return jnp.broadcast_to(data, tgt)


@register("broadcast_like")
def broadcast_like(lhs, rhs):
    return jnp.broadcast_to(lhs, rhs.shape)


@register("broadcast_axis", aliases=["broadcast_axes"])
def broadcast_axis(data, *, axis, size):
    ax = axis if isinstance(axis, (tuple, list)) else (axis,)
    sz = size if isinstance(size, (tuple, list)) else (size,)
    tgt = list(data.shape)
    for a, s in zip(ax, sz):
        tgt[int(a)] = int(s)
    return jnp.broadcast_to(data, tuple(tgt))


@register("zeros_like")
def zeros_like(data):
    return jnp.zeros_like(data)


@register("ones_like")
def ones_like(data):
    return jnp.ones_like(data)


# -- indexing ---------------------------------------------------------------
@register("Embedding")
def embedding(data, weight, *, input_dim, output_dim, dtype="float32", sparse_grad=False):
    """Row gather (ref: indexing_op.cc :: Embedding). XLA lowers to a
    dynamic-gather; on TPU this is HBM-bandwidth bound, so keep indices int32."""
    idx = data.astype(jnp.int32)
    # the gather and, in the backward, the scatter-add into the table's
    # gradient (docs/OBSERVABILITY.md "Device-side scopes")
    with jax.named_scope("mx.embed"):
        return jnp.take(weight, idx, axis=0)


@register("take")
def take(a, indices, *, axis=0, mode="clip"):
    idx = indices.astype(jnp.int32)
    return jnp.take(a, idx, axis=int(axis), mode="clip" if mode == "clip" else "wrap")


@register("pick")
def pick(data, index, *, axis=-1, keepdims=False, mode="clip"):
    ax = int(axis) % data.ndim
    idx = jnp.clip(index.astype(jnp.int32), 0, data.shape[ax] - 1)
    idx_exp = jnp.expand_dims(idx, ax)
    out = jnp.take_along_axis(data, idx_exp, axis=ax)
    if not keepdims:
        out = jnp.squeeze(out, axis=ax)
    return out


@register("one_hot")
def one_hot(indices, *, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    oh = jax.nn.one_hot(indices.astype(jnp.int32), int(depth), dtype=jnp.dtype(dtype))
    return oh * (on_value - off_value) + off_value


@register("gather_nd")
def gather_nd(data, indices):
    idx = indices.astype(jnp.int32)
    m = idx.shape[0]
    return data[tuple(idx[i] for i in range(m))]


@register("scatter_nd")
def scatter_nd(data, indices, *, shape):
    idx = indices.astype(jnp.int32)
    m = idx.shape[0]
    out = jnp.zeros(tuple(int(s) for s in shape), dtype=data.dtype)
    return out.at[tuple(idx[i] for i in range(m))].set(data)


@register("SequenceMask")
def sequence_mask(data, sequence_length=None, *, use_sequence_length=False, value=0.0, axis=0):
    if not use_sequence_length or sequence_length is None:
        return data
    ax = int(axis)
    maxlen = data.shape[ax]
    steps = jnp.arange(maxlen)
    shape = [1] * data.ndim
    shape[ax] = maxlen
    steps = steps.reshape(shape)
    batch_axis = 1 if ax == 0 else 0
    lshape = [1] * data.ndim
    lshape[batch_axis] = data.shape[batch_axis]
    lens = sequence_length.reshape(lshape)
    return jnp.where(steps < lens, data, jnp.asarray(value, data.dtype))


@register("SequenceLast")
def sequence_last(data, sequence_length=None, *, use_sequence_length=False, axis=0):
    ax = int(axis)
    if not use_sequence_length or sequence_length is None:
        idx = [slice(None)] * data.ndim
        idx[ax] = -1
        return data[tuple(idx)]
    last = (sequence_length.astype(jnp.int32) - 1)
    return jnp.take_along_axis(
        data, last.reshape((1,) + last.shape + (1,) * (data.ndim - 2)), axis=ax
    ).squeeze(ax)


@register("SequenceReverse")
def sequence_reverse(data, sequence_length=None, *, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=int(axis))
    maxlen = data.shape[0]
    steps = jnp.arange(maxlen)[:, None]
    lens = sequence_length.astype(jnp.int32)[None, :]
    rev_idx = jnp.where(steps < lens, lens - 1 - steps, steps)
    return jnp.take_along_axis(
        data, rev_idx.reshape(rev_idx.shape + (1,) * (data.ndim - 2)), axis=0)


# -- la_op family (ref: src/operator/tensor/la_op.cc — the advanced
# linalg operators; lower to XLA's native triangular/Cholesky/QR
# custom-calls which the TPU runs on the MXU where applicable) ----------
@register("linalg_gemm")
def linalg_gemm(A, B, C, *, transpose_a=False, transpose_b=False,
                alpha=1.0, beta=1.0, axis=-3):
    if axis != -3:
        raise NotImplementedError(
            "linalg_gemm: only the default axis=-3 layout is supported")
    a = jnp.swapaxes(A, -1, -2) if transpose_a else A
    b = jnp.swapaxes(B, -1, -2) if transpose_b else B
    return alpha * jnp.matmul(a, b) + beta * C


@register("linalg_potrf")
def linalg_potrf(A):
    """Cholesky factor L with A = L Lᵀ (lower)."""
    return jnp.linalg.cholesky(A)


@register("linalg_potri")
def linalg_potri(A):
    """Inverse from a Cholesky factor: (L Lᵀ)⁻¹ given L."""
    n = A.shape[-1]
    eye = jnp.broadcast_to(jnp.eye(n, dtype=A.dtype), A.shape)
    linv = jax.scipy.linalg.solve_triangular(A, eye, lower=True)
    return jnp.matmul(jnp.swapaxes(linv, -1, -2), linv)


@register("linalg_trsm")
def linalg_trsm(A, B, *, transpose=False, rightside=False, lower=True,
                alpha=1.0):
    """Solve triangular A X = alpha B (ref la_op trsm)."""
    a = jnp.swapaxes(A, -1, -2) if transpose else A
    lo = lower != transpose
    if rightside:
        # X A = alpha B  <=>  Aᵀ Xᵀ = alpha Bᵀ
        xt = jax.scipy.linalg.solve_triangular(
            jnp.swapaxes(a, -1, -2), jnp.swapaxes(alpha * B, -1, -2),
            lower=not lo)
        return jnp.swapaxes(xt, -1, -2)
    return jax.scipy.linalg.solve_triangular(a, alpha * B, lower=lo)


@register("linalg_trmm")
def linalg_trmm(A, B, *, transpose=False, rightside=False, lower=True,
                alpha=1.0):
    tri = jnp.tril(A) if lower else jnp.triu(A)
    a = jnp.swapaxes(tri, -1, -2) if transpose else tri
    return alpha * (jnp.matmul(B, a) if rightside else jnp.matmul(a, B))


@register("linalg_syrk")
def linalg_syrk(A, *, transpose=False, alpha=1.0):
    at = jnp.swapaxes(A, -1, -2)
    return alpha * (jnp.matmul(at, A) if transpose else jnp.matmul(A, at))


@register("linalg_gelqf", num_outputs=2)
def linalg_gelqf(A):
    """LQ factorization A = L Q (ref la_op gelqf) via QR of Aᵀ."""
    q, r = jnp.linalg.qr(jnp.swapaxes(A, -1, -2))
    return jnp.swapaxes(r, -1, -2), jnp.swapaxes(q, -1, -2)


@register("linalg_syevd", num_outputs=2)
def linalg_syevd(A):
    """Symmetric eigendecomposition (ref la_op syevd): U, lambda with
    A = Uᵀ diag(lambda) U."""
    w, v = jnp.linalg.eigh(A)
    return jnp.swapaxes(v, -1, -2), w


@register("linalg_sumlogdiag")
def linalg_sumlogdiag(A):
    return jnp.sum(jnp.log(jnp.diagonal(A, axis1=-2, axis2=-1)), axis=-1)


@register("linalg_extractdiag")
def linalg_extractdiag(A, *, offset=0):
    return jnp.diagonal(A, offset=offset, axis1=-2, axis2=-1)


@register("linalg_makediag")
def linalg_makediag(A, *, offset=0):
    eye_like = jnp.zeros(A.shape[:-1] + (A.shape[-1] + abs(offset),) * 2,
                         A.dtype)
    idx = jnp.arange(A.shape[-1])
    if offset >= 0:
        return eye_like.at[..., idx, idx + offset].set(A)
    return eye_like.at[..., idx - offset, idx].set(A)


@register("linalg_det")
def linalg_det(A):
    return jnp.linalg.det(A)


@register("linalg_slogdet", num_outputs=2)
def linalg_slogdet(A):
    sign, logdet = jnp.linalg.slogdet(A)
    return sign, logdet


@register("linalg_inverse")
def linalg_inverse(A):
    return jnp.linalg.inv(A)


# -- layout/indexing ops (ref: matrix_op.cc, indexing_op.cc) ------------
@register("depth_to_space")
def depth_to_space(data, *, block_size):
    b = int(block_size)
    n, c, h, w = data.shape
    x = data.reshape(n, b, b, c // (b * b), h, w)
    x = x.transpose(0, 3, 4, 1, 5, 2)
    return x.reshape(n, c // (b * b), h * b, w * b)


@register("space_to_depth")
def space_to_depth(data, *, block_size):
    b = int(block_size)
    n, c, h, w = data.shape
    x = data.reshape(n, c, h // b, b, w // b, b)
    x = x.transpose(0, 3, 5, 1, 2, 4)
    return x.reshape(n, c * b * b, h // b, w // b)


@register("batch_take")
def batch_take(a, indices):
    idx = indices.astype(jnp.int32)
    return a[jnp.arange(a.shape[0]), idx]


@register("UpSampling")
def upsampling(*data, scale, sample_type="nearest", num_args=1,
               num_filter=0, multi_input_mode="concat", workspace=256):
    """Upsampling (ref: nn/upsampling.cc). nearest: repeat; bilinear:
    the reference runs a Deconvolution with a fixed bilinear kernel
    (the second input is that weight) — here the equivalent
    interpolation runs directly on the MXU-friendly resize path."""
    s = int(scale)
    if sample_type == "bilinear":
        # ref semantics: a grouped Deconvolution whose weight is the
        # second INPUT (learnable; commonly bilinear-initialized, e.g.
        # FCN heads) with kernel=2s-s%2, stride=s, pad=ceil((s-1)/2)
        x, w = data[0], data[1]
        from . import get_op
        C = x.shape[1]
        k = 2 * s - s % 2
        p = -(-(s - 1) // 2)   # ceil((s-1)/2)
        return get_op("Deconvolution").impl(
            x, w, kernel=(k, k), num_filter=C, stride=(s, s), pad=(p, p),
            num_group=C, no_bias=True)
    outs = [jnp.repeat(jnp.repeat(d, s, axis=2), s, axis=3) for d in data]
    if len(outs) == 1:
        return outs[0]
    target = outs[0].shape[2:]
    fixed = []
    for o in outs:
        if o.shape[2:] != target:
            ry = target[0] // o.shape[2]
            rx = target[1] // o.shape[3]
            o = jnp.repeat(jnp.repeat(o, ry, axis=2), rx, axis=3)
        fixed.append(o)
    return jnp.concatenate(fixed, axis=1)


@register("linalg_extracttrian")
def linalg_extracttrian(A, *, offset=0, lower=True):
    """Flatten the (lower|upper) triangle band into a vector (ref
    la_op extracttrian): output length n*(n+1)/2 - |offset| adjusted,
    rows concatenated in row-major order of the kept entries."""
    n = A.shape[-1]
    rows, cols = jnp.tril_indices(n, k=offset) if lower else \
        jnp.triu_indices(n, k=offset)
    return A[..., rows, cols]


@register("linalg_maketrian")
def linalg_maketrian(A, *, offset=0, lower=True):
    """Inverse of extracttrian: scatter the packed band back into an
    otherwise-zero square matrix (ref la_op maketrian)."""
    m = A.shape[-1]
    # n(n+1)/2 + extra = m given the offset; solve for n
    k = abs(offset)
    # entries of an n x n (lower, offset>=0 widens) band:
    #   offset==0: n(n+1)/2 ; offset<0 for lower removes diagonals
    n = 1
    while _trian_len(n, offset, lower) < m:
        n += 1
    if _trian_len(n, offset, lower) != m:
        raise ValueError("maketrian: %d entries fit no square matrix "
                         "with offset %d" % (m, offset))
    rows, cols = (jnp.tril_indices(n, k=offset) if lower
                  else jnp.triu_indices(n, k=offset))
    out = jnp.zeros(A.shape[:-1] + (n, n), A.dtype)
    return out.at[..., rows, cols].set(A)


def _trian_len(n, offset, lower):
    import numpy as _np
    idx = _np.tril_indices(n, k=offset) if lower else \
        _np.triu_indices(n, k=offset)
    return len(idx[0])


@register("khatri_rao")
def khatri_rao(*arrays):
    """Column-wise Kronecker product (ref: contrib/krprod.cc
    khatri_rao): inputs (r_i, k) -> output (prod r_i, k)."""
    if not arrays:
        raise ValueError("khatri_rao needs at least one input")
    out = arrays[0]
    for a in arrays[1:]:
        # (m, k) x (n, k) -> (m*n, k): per-column outer product
        out = (out[:, None, :] * a[None, :, :]).reshape(
            out.shape[0] * a.shape[0], out.shape[1])
    return out


def _conv_tuple(v, n=2):
    t = tuple(int(x) for x in (v or ()))
    if not t:
        return (1, 1) if n == 2 else (0,) * n
    return t if len(t) == n else t + (t[-1],) * (n - len(t))


def _im2col_fn(x_shape, kernel, stride, dilate, pad):
    """Build the pure im2col mapping for static shapes; MXNet layout:
    (N, C, H, W) -> (N, C*prod(kernel), prod(out_spatial)), feature dim
    ordered (c, kh, kw) — matching tensor/im2col.h."""
    import jax.lax as lax

    k = tuple(kernel)

    def f(x):
        patches = lax.conv_general_dilated_patches(
            x, filter_shape=k, window_strides=tuple(stride),
            padding=tuple((p, p) for p in pad),
            rhs_dilation=tuple(dilate))
        # patches: (N, C*prod(k), H', W') with feature dim (c, kh, kw)
        N = x.shape[0]
        return patches.reshape(N, patches.shape[1], -1)
    return f


@register("im2col")
def im2col(data, *, kernel, stride=None, dilate=None, pad=None):
    """Rearrange conv patches into columns (ref: tensor/im2col.h,
    im2col op): (N, C, H, W) -> (N, C*prod(kernel), L)."""
    nsp = len(tuple(kernel))
    stride = _conv_tuple(stride, nsp) if stride else (1,) * nsp
    dilate = _conv_tuple(dilate, nsp) if dilate else (1,) * nsp
    pad = tuple(int(x) for x in (pad or ())) or (0,) * nsp
    return _im2col_fn(data.shape, kernel, stride, dilate, pad)(data)


@register("col2im")
def col2im(data, *, output_size, kernel, stride=None, dilate=None,
           pad=None):
    """Adjoint of im2col (ref: tensor/im2col.h col2im): overlapping
    patch columns sum back into the (N, C, *output_size) image —
    implemented as the exact VJP of im2col, the definitionally correct
    adjoint."""
    import jax

    nsp = len(tuple(kernel))
    stride = _conv_tuple(stride, nsp) if stride else (1,) * nsp
    dilate = _conv_tuple(dilate, nsp) if dilate else (1,) * nsp
    pad = tuple(int(x) for x in (pad or ())) or (0,) * nsp
    out_sp = tuple(int(x) for x in output_size)
    k = tuple(int(x) for x in kernel)
    import numpy as _np
    N = data.shape[0]
    C = data.shape[1] // int(_np.prod(k))
    x_shape = (N, C) + out_sp
    f = _im2col_fn(x_shape, k, stride, dilate, pad)
    zero = jnp.zeros(x_shape, data.dtype)
    _, vjp = jax.vjp(f, zero)
    return vjp(data)[0]


# the reference registers every la_op as `_linalg_*` and surfaces it as
# `mx.nd.linalg.*` / `linalg_*` (tensor/la_op.cc NNVM_REGISTER_OP):
# honor the underscore-prefixed names too
from . import _ALIASES as _ALIAS_TABLE  # noqa: E402
for _n in ("gemm", "gemm2", "potrf", "potri", "trmm", "trsm", "syrk",
           "gelqf", "syevd", "sumlogdiag", "extractdiag", "makediag",
           "extracttrian", "maketrian", "det", "slogdet", "inverse"):
    _ALIAS_TABLE.setdefault("_linalg_" + _n, "linalg_" + _n)
