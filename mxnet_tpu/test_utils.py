"""Testing fixtures (reference ``python/mxnet/test_utils.py``, 2,174 LoC —
the numerical contract toolkit every reference test file imports:
``assert_almost_equal``, ``check_numeric_gradient`` finite differences,
``check_consistency`` cross-backend comparison, ``rand_ndarray``)."""
from __future__ import annotations

import numbers
import os

import numpy as np

from . import ndarray as nd
from .context import Context, cpu, current_context, gpu
from .ndarray import NDArray

_rng = np.random.RandomState(1234)


def default_context():
    return current_context()


def set_default_context(ctx):
    Context._default_ctx.value = ctx


def default_dtype():
    return np.float32


def list_gpus():
    """Indices of visible accelerator chips (reference
    ``test_utils.py:list_gpus``)."""
    import jax
    try:
        return list(range(len([d for d in jax.devices()
                               if d.platform != "cpu"])))
    except RuntimeError:
        return []


def rand_shape_2d(dim0=10, dim1=10):
    return _rng.randint(1, dim0 + 1), _rng.randint(1, dim1 + 1)


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (_rng.randint(1, dim0 + 1), _rng.randint(1, dim1 + 1),
            _rng.randint(1, dim2 + 1))


def rand_shape_nd(num_dim, dim=10):
    return tuple(_rng.randint(1, dim + 1, size=num_dim))


def random_arrays(*shapes):
    """List of float32 arrays of given shapes."""
    arrays = [np.array(_rng.randn(), dtype=default_dtype()) if len(s) == 0
              else _rng.randn(*s).astype(default_dtype()) for s in shapes]
    if len(arrays) == 1:
        return arrays[0]
    return arrays


def rand_ndarray(shape, stype="default", density=None, dtype=None,
                 ctx=None, distribution=None):
    """Random NDArray; sparse stypes are densified with the requested
    density (TPU sparse policy, SURVEY.md hard-part #4)."""
    dtype = dtype or default_dtype()
    arr = _rng.uniform(size=shape).astype(dtype)
    if stype in ("row_sparse", "csr"):
        density = 0.05 if density is None else density
        mask = _rng.uniform(size=shape) < density
        arr = arr * mask
    return nd.array(arr, ctx=ctx, dtype=dtype)


def same(a, b):
    return np.array_equal(a, b)


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    return np.allclose(a, b, rtol=rtol or 1e-5, atol=atol or 1e-20,
                       equal_nan=equal_nan)


def _as_np(x):
    return x.asnumpy() if isinstance(x, NDArray) else np.asarray(x)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False, use_broadcast=True, mismatches=(10, 10)):
    """Reference ``test_utils.py:assert_almost_equal``."""
    a = _as_np(a)
    b = _as_np(b)
    rtol = rtol or 1e-5
    atol = atol or 1e-20
    if not np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=equal_nan):
        index, rel = _find_max_violation(a, b, rtol, atol)
        raise AssertionError(
            f"Error {rel} exceeds tolerance rtol={rtol}, atol={atol} at "
            f"index {index}.\n{names[0]}: {a}\n{names[1]}: {b}")


def _find_max_violation(a, b, rtol, atol):
    diff = np.abs(a - b) - atol - rtol * np.abs(b)
    violation = np.argmax(diff)
    index = np.unravel_index(violation, a.shape) if a.shape else ()
    rel = np.abs(a - b).ravel()[violation] / \
        (atol + rtol * np.abs(b).ravel()[violation] + 1e-20)
    return index, rel


def assert_allclose(a, b, rtol=1e-5, atol=1e-20):
    assert_almost_equal(a, b, rtol=rtol, atol=atol)


def check_numeric_gradient(sym, location, aux_states=None, numeric_eps=1e-3,
                           rtol=1e-2, atol=None, grad_nodes=None,
                           use_forward_train=True, ctx=None,
                           grad_stype_dict=None, dtype=np.float64):
    """Finite-difference gradient check for a Symbol (reference
    ``test_utils.py:check_numeric_gradient``)."""
    ctx = ctx or current_context()
    if isinstance(location, (list, tuple)):
        arg_names = sym.list_arguments()
        location = dict(zip(arg_names, location))
    location = {k: np.asarray(v, dtype=np.float32) for k, v in location.items()}
    shapes = {k: v.shape for k, v in location.items()}
    if grad_nodes is None:
        grad_nodes = list(location.keys())

    exe = sym.simple_bind(ctx=ctx, grad_req="write", **shapes)
    for k, v in location.items():
        exe.arg_dict[k][:] = v
    if aux_states:
        for k, v in aux_states.items():
            exe.aux_dict[k][:] = np.asarray(v)
    exe.forward(is_train=True)
    exe.backward()
    sym_grads = {k: exe.grad_dict[k].asnumpy() for k in grad_nodes
                 if exe.grad_dict.get(k) is not None}

    def loss_at(loc):
        for k, v in loc.items():
            exe.arg_dict[k][:] = v
        outs = exe.forward(is_train=use_forward_train)
        return sum(float(o.asnumpy().sum()) for o in outs)

    for name in grad_nodes:
        if name not in sym_grads:
            continue
        flat = location[name].ravel()
        num_grad = np.zeros_like(flat)
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + numeric_eps / 2
            fp = loss_at(location)
            flat[i] = orig - numeric_eps / 2
            fm = loss_at(location)
            flat[i] = orig
            num_grad[i] = (fp - fm) / numeric_eps
        loss_at(location)  # restore
        assert_almost_equal(num_grad.reshape(location[name].shape),
                            sym_grads[name], rtol=rtol,
                            atol=atol if atol is not None else 1e-4,
                            names=("numeric", "symbolic"))


def check_symbolic_forward(sym, location, expected, rtol=1e-5, atol=None,
                           aux_states=None, ctx=None, equal_nan=False,
                           dtype=np.float32):
    ctx = ctx or current_context()
    if isinstance(location, (list, tuple)):
        location = dict(zip(sym.list_arguments(), location))
    shapes = {k: np.asarray(v).shape for k, v in location.items()}
    exe = sym.simple_bind(ctx=ctx, grad_req="null", **shapes)
    for k, v in location.items():
        exe.arg_dict[k][:] = np.asarray(v, dtype=dtype)
    if aux_states:
        for k, v in aux_states.items():
            exe.aux_dict[k][:] = np.asarray(v)
    outputs = exe.forward(is_train=False)
    for out, exp in zip(outputs, expected):
        assert_almost_equal(out.asnumpy(), exp, rtol=rtol,
                            atol=atol if atol is not None else 1e-20)
    return outputs


def check_symbolic_backward(sym, location, out_grads, expected, rtol=1e-5,
                            atol=None, aux_states=None, grad_req="write",
                            ctx=None, grad_stypes=None, equal_nan=False,
                            dtype=np.float32):
    ctx = ctx or current_context()
    if isinstance(location, (list, tuple)):
        location = dict(zip(sym.list_arguments(), location))
    if isinstance(expected, (list, tuple)):
        expected = dict(zip(sym.list_arguments(), expected))
    shapes = {k: np.asarray(v).shape for k, v in location.items()}
    exe = sym.simple_bind(ctx=ctx, grad_req=grad_req, **shapes)
    for k, v in location.items():
        exe.arg_dict[k][:] = np.asarray(v, dtype=dtype)
    if aux_states:
        for k, v in aux_states.items():
            exe.aux_dict[k][:] = np.asarray(v)
    exe.forward(is_train=True)
    exe.backward([nd.array(np.asarray(g)) for g in
                  (out_grads if isinstance(out_grads, (list, tuple))
                   else [out_grads])])
    grads = {k: v.asnumpy() for k, v in exe.grad_dict.items() if v is not None}
    for name, exp in expected.items():
        assert_almost_equal(grads[name], exp, rtol=rtol,
                            atol=atol if atol is not None else 1e-20)
    return grads


def check_consistency(sym, ctx_list, scale=1.0, dtype=None,
                      arg_params=None, aux_params=None, rtol=None, atol=None,
                      raise_on_err=True, ground_truth=None, equal_nan=False,
                      use_uniform=False, rand_type=np.float64):
    """Run one symbol across contexts/dtypes and compare (reference
    ``test_utils.py:check_consistency`` — the CPU↔GPU agreement harness; here
    host-CPU ↔ TPU)."""
    tol = {np.dtype(np.float16): 1e-1, np.dtype(np.float32): 1e-3,
           np.dtype(np.float64): 1e-5}
    results = []
    for spec in ctx_list:
        ctx = spec["ctx"]
        dshapes = {k: v for k, v in spec.items()
                   if k not in ("ctx", "type_dict")}
        exe = sym.simple_bind(ctx=ctx, grad_req="write", **dshapes)
        for name, arr in exe.arg_dict.items():
            if name in dshapes:
                if use_uniform:
                    arr[:] = _rng.uniform(-scale, scale,
                                          size=arr.shape).astype(np.float32)
                else:
                    arr[:] = (_rng.randn(*arr.shape) * scale).astype(np.float32)
            elif arg_params and name in arg_params:
                arr[:] = arg_params[name]
            else:
                arr[:] = (_rng.randn(*arr.shape) * scale).astype(np.float32)
        if results:
            # reuse the first run's inputs for comparability
            for name, arr in exe.arg_dict.items():
                arr[:] = results[0]["args"][name]
        outs = exe.forward(is_train=True)
        results.append({"args": {k: v.asnumpy()
                                 for k, v in exe.arg_dict.items()},
                        "outs": [o.asnumpy() for o in outs]})
    base = ground_truth or results[0]
    for res in results[1:]:
        for o1, o2 in zip(base["outs"], res["outs"]):
            assert_almost_equal(o1, o2, rtol=rtol or 1e-3, atol=atol or 1e-4)
    return [r["outs"] for r in results]


def simple_forward(sym, ctx=None, is_train=False, **inputs):
    shapes = {k: v.shape for k, v in inputs.items()}
    exe = sym.simple_bind(ctx=ctx or current_context(), grad_req="null",
                          **shapes)
    for k, v in inputs.items():
        exe.arg_dict[k][:] = v
    outputs = [o.asnumpy() for o in exe.forward(is_train=is_train)]
    if len(outputs) == 1:
        return outputs[0]
    return outputs


class DummyIter:
    """Repeat one batch forever (reference ``test_utils.py:DummyIter``)."""

    def __init__(self, real_iter):
        self.real_iter = real_iter
        self.provide_data = real_iter.provide_data
        self.provide_label = real_iter.provide_label
        self.batch_size = real_iter.batch_size
        self.the_batch = next(iter(real_iter))

    def __iter__(self):
        return self

    def next(self):
        return self.the_batch

    __next__ = next

    def reset(self):
        pass


def download(url, fname=None, dirname=None, overwrite=False, retries=5):
    """Reference ``test_utils.py:download``.  This environment has no
    network egress, so only ``file://`` URLs and existing local paths are
    fetchable; anything else raises with a clear message (tests that need
    real downloads gate on it)."""
    import shutil
    from urllib.parse import urlparse

    parsed = urlparse(url)
    if fname is None:
        fname = parsed.path.split("/")[-1] or "download"
    if dirname:
        os.makedirs(dirname, exist_ok=True)
        fname = os.path.join(dirname, fname)
    if os.path.exists(fname) and not overwrite:
        return fname
    src = parsed.path if parsed.scheme in ("", "file") else None
    if src and os.path.exists(src):
        shutil.copyfile(src, fname)
        return fname
    raise RuntimeError(
        f"download({url!r}): no network egress in this environment; "
        "use a file:// URL or a pre-staged local path")


def fd_rand(*shape, seed=0, scale=1.0, shift=0.0):
    """Deterministic uniform tensor for the FD contract tranches."""
    return (np.random.RandomState(seed).uniform(-1, 1, shape) * scale
            + shift).astype("float32")


def fd_grad_check(sym, location, aux=None, rtol=5e-2, atol=1e-2, **kw):
    """check_numeric_gradient with the contract tranches' tolerances."""
    check_numeric_gradient(sym, location, aux_states=aux, rtol=rtol,
                           atol=atol, **kw)


def counted(name, fn):
    """``{labels: n}`` of what the telemetry counter ``name`` gained while
    ``fn`` ran, a key a label set as the snapshot writes it
    (``'{kind="plain",rows="4"}'``); telemetry is switched on for the call
    and left as it was."""
    from . import telemetry
    was_on = telemetry.is_enabled()
    telemetry.enable()
    read = lambda: dict(
        telemetry.snapshot()["counters_by_label"].get(name, {}))
    try:
        before = read()
        fn()
        after = read()
    finally:
        if not was_on:
            telemetry.disable()
    return {k: n - before.get(k, 0) for k, n in after.items()
            if n != before.get(k, 0)}
