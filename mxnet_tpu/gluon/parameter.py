"""Gluon Parameter / ParameterDict.

Reference being rebuilt: ``python/mxnet/gluon/parameter.py`` — ``Parameter``
with deferred initialization (shape holes filled at first forward),
per-context data/grad replicas, grad_req write/add/null, and
``ParameterDict`` with prefix scoping and shared-dict lookup.

TPU-native notes: replicas-per-context collapse to one logical array — device
replication/sharding is the mesh's job (``mxnet_tpu/parallel``), not the
parameter's.  ``list_data()`` keeps the reference API by returning the single
array per requested context.  Gradients attach through the tape
(``autograd.mark_variables``), the analog of the reference marking arrays as
autograd variables when ``grad_req != 'null'``.
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as _np

from .. import autograd, initializer
from .utils import _indent
from ..context import Context, current_context, cpu
from ..ndarray import NDArray
from .. import ndarray as nd


class DeferredInitializationError(RuntimeError):
    """Error for unfinished deferred initialization (reference
    ``parameter.py:40``)."""


def _is_unknown(shape):
    return shape is None or any(s in (0, None, -1) for s in shape)


class Parameter:
    """A Container holding parameters (weights) of Blocks (reference
    ``parameter.py:47``)."""

    def __init__(self, name, grad_req="write", shape=None, dtype=_np.float32,
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self._var = None
        self._data = None
        self._grad = None
        self._deferred_init = ()
        self._differentiable = differentiable
        self._allow_deferred_init = allow_deferred_init
        self._grad_req = None
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.name = name
        self._dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.grad_req = grad_req
        self.init = init
        for t, v in (("stype", stype), ("grad_stype", grad_stype)):
            if v not in ("default", "row_sparse", "csr"):
                raise ValueError(f"invalid {t} {v}: must be default, row_sparse "
                                 "or csr")
        self._stype = stype
        self._grad_stype = grad_stype

    def __repr__(self):
        s = "Parameter {name} (shape={shape}, dtype={dtype})"
        return s.format(name=self.name, shape=self.shape, dtype=self.dtype)

    # ---------------------------------------------------------------- props
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null"), \
            f"grad_req must be one of 'write', 'add', or 'null', but got '{req}'"
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null" and self._grad is not None:
            self._grad = None
            if self._data is not None:
                self._data._ag_node = None
                self._data._ag_grad = None
        elif self._data is not None:
            self._init_grad()

    @property
    def dtype(self):
        return self._dtype

    @dtype.setter
    def dtype(self, dtype):
        self.cast(dtype)

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
        else:
            assert len(self._shape) == len(new_shape) and \
                all(j in (0, i) for i, j in zip(new_shape, self._shape)), \
                f"Expected shape {new_shape} is incompatible with given shape " \
                f"{self._shape}."
            self._shape = tuple(new_shape)
        if self._deferred_init and not _is_unknown(self._shape):
            self._finish_deferred_init()

    @property
    def stype(self):
        return self._stype

    @property
    def grad_stype(self):
        return self._grad_stype

    # ------------------------------------------------------------- lifecycle
    def initialize(self, init=None, ctx=None, default_init=initializer.Uniform(),
                   force_reinit=False):
        """Initialize data and grad (reference ``parameter.py:360``).  Deferred
        when shape has unknown dims and ``allow_deferred_init``."""
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if init is None:
            init = default_init if self.init is None else self.init
        if _is_unknown(self._shape):
            if self._allow_deferred_init:
                self._deferred_init = (init, ctx, default_init, None)
                return
            raise ValueError(f"Cannot initialize Parameter '{self.name}' "
                             "because it has invalid shape: "
                             f"{self._shape}.")
        self._deferred_init = (init, ctx, default_init, None)
        self._finish_deferred_init()

    def _finish_deferred_init(self):
        if not self._deferred_init:
            return
        init, ctx, default_init, data = self._deferred_init
        self._deferred_init = ()
        assert not _is_unknown(self._shape), \
            f"Cannot initialize Parameter '{self.name}' because it has " \
            f"invalid shape: {self._shape}."
        with autograd.pause():
            if data is None:
                host = _np.zeros(self._shape, dtype=self._dtype)
                view = _HostArrayView(host)
                desc = initializer.InitDesc(self.name)
                if init is not None and init is not default_init:
                    # explicit per-parameter initializer: dispatch straight
                    # to its payload — the name-suffix rules would
                    # otherwise eat it (e.g. LSTMBias on '*_bias' params;
                    # reference parameter.py routes via desc['__init__']).
                    # Composite/callable initializers (Mixed, Load, bare
                    # functions) define only __call__ — invoke them whole.
                    initer = initializer.create(init)
                    if isinstance(initer, initializer.Initializer):
                        initer._init_weight(desc, view)
                    else:
                        initer(desc, view)
                else:
                    initializer.create(default_init)(desc, view)
                data = nd.array(host, ctx=ctx[0], dtype=self._dtype)
            self._init_impl(data, ctx)

    def _init_impl(self, data, ctx_list):
        self._ctx_list = list(ctx_list)
        self._data = data if isinstance(data, NDArray) else nd.array(data)
        self._init_grad()

    def _init_grad(self):
        if self.grad_req == "null":
            self._grad = None
            return
        if self._grad_stype == "row_sparse":
            # compressed zero-row gradient: the Embedding sparse backward
            # swaps in its rows without ever allocating (vocab, dim)
            import jax.numpy as jnp
            from ..ndarray.sparse import RowSparseNDArray
            shape = tuple(self._data.shape)
            self._grad = RowSparseNDArray.from_rows(
                jnp.zeros((0,), jnp.int32),
                jnp.zeros((0,) + shape[1:], self._data.dtype), shape)
        else:
            self._grad = nd.zeros(self._data.shape, dtype=self._data.dtype,
                                  ctx=self._data.context)
        autograd.mark_variables([self._data], [self._grad],
                                grad_reqs=self.grad_req)

    def _load_init(self, data, ctx, cast_dtype=False, dtype_source="current"):
        """Load from saved arrays (reference ``parameter.py:274``)."""
        if cast_dtype:
            if dtype_source == "current":
                data = data.astype(self.dtype)
            else:
                self._dtype = data.dtype
        if self.shape is not None and not _is_unknown(self.shape):
            if tuple(self.shape) != tuple(data.shape):
                raise AssertionError(
                    f"Failed loading Parameter '{self.name}' from saved params: "
                    f"shape incompatible expected {self.shape} vs saved {tuple(data.shape)}")
        else:
            self._shape = tuple(data.shape)
        if self.dtype is not None and not cast_dtype:
            if _np.dtype(self.dtype) != data.dtype:
                raise AssertionError(
                    f"Failed loading Parameter '{self.name}' from saved params: "
                    f"dtype incompatible expected {_np.dtype(self.dtype)} vs "
                    f"saved {data.dtype}. Set cast_dtype=True to cast the dtype "
                    "of saved params.")
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is None:
            self._deferred_init = ()
            self._init_impl(data if isinstance(data, NDArray) else nd.array(data), ctx)
        else:
            self.set_data(data)

    def _reduce(self):
        """Single logical copy (reference averages ctx replicas)."""
        return self.data().copyto(cpu()) if self._data is not None else None

    # ------------------------------------------------------------- accessors
    def _check_and_get(self, req_ctx=None):
        if self._data is not None:
            return self._data
        if self._deferred_init:
            raise DeferredInitializationError(
                f"Parameter '{self.name}' has not been initialized yet because "
                "initialization was deferred. Actual initialization happens "
                "during the first forward pass. Please pass one batch of data "
                "through the network before accessing Parameters.")
        raise RuntimeError(
            f"Parameter '{self.name}' has not been initialized. Note that you "
            "should initialize parameters and create Trainer with "
            "Block.collect_params() instead of Block.params because the later "
            "does not include Parameters of nested child Blocks")

    def data(self, ctx=None):
        """The parameter array (reference ``parameter.py:507``)."""
        return self._check_and_get(ctx)

    def list_data(self):
        return [self._check_and_get()]

    def grad(self, ctx=None):
        if self._data is not None and self._grad is None:
            raise RuntimeError(
                f"Cannot get gradient array for Parameter '{self.name}' "
                "because grad_req='null'")
        self._check_and_get()
        return self._grad

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        if self._data is None:
            if self._deferred_init:
                return self._deferred_init[1]
            raise RuntimeError(f"Parameter '{self.name}' has not been initialized")
        return list(getattr(self, "_ctx_list", [current_context()]))

    def zero_grad(self):
        """Zero the gradient buffer in place (reference ``parameter.py:562``)."""
        if self._grad is None:
            return
        from ..ndarray.sparse import RowSparseNDArray
        if isinstance(self._grad, RowSparseNDArray):
            # reset to an empty compressed gradient — never allocate the
            # dense (vocab, dim) buffer just to zero it
            import jax.numpy as jnp
            shape = tuple(self._grad.shape)
            self._grad.adopt_rows(jnp.zeros((0,), jnp.int32),
                                  jnp.zeros((0,) + shape[1:], self.dtype),
                                  shape)
            return
        self._grad[:] = 0

    def set_data(self, data):
        """Set this parameter's value everywhere (reference
        ``parameter.py:441``)."""
        if self._data is None:
            assert self._deferred_init, \
                f"Parameter '{self.name}' has not been initialized"
            # stash the value BEFORE touching the shape setter so
            # _finish_deferred_init adopts it instead of running the random
            # initializer
            self._deferred_init = self._deferred_init[:3] + (
                data if isinstance(data, NDArray) else nd.array(data),)
            self.shape = tuple(data.shape)
            return
        self.shape = tuple(data.shape)
        src = data if isinstance(data, NDArray) else nd.array(data)
        # rebind in place, keeping the tape mark
        self._data._data = src._data.astype(self._data._data.dtype) \
            if src.dtype != self._data.dtype else src._data

    def row_sparse_data(self, row_id):
        raise ValueError(f"Cannot return a copy of Parameter '{self.name}' via "
                         "row_sparse_data() because its storage type is "
                         f"{self._stype!r}; row_sparse storage is represented "
                         "densely on TPU")

    def var(self):
        """Symbol of this parameter (reference ``parameter.py:584``)."""
        if self._var is None:
            from .. import symbol
            self._var = symbol.var(self.name, shape=self.shape,
                                   dtype=self._dtype, lr_mult=self.lr_mult,
                                   wd_mult=self.wd_mult, init=self.init,
                                   stype=self._stype)
        return self._var

    def cast(self, dtype):
        """Cast data/grad to a new dtype (reference ``parameter.py:425``)."""
        self._dtype = dtype
        if self._data is None:
            return
        with autograd.pause():
            self._data = self._data.astype(dtype)
            if self._grad is not None:
                self._grad = self._grad.astype(dtype)
                autograd.mark_variables([self._data], [self._grad],
                                        grad_reqs=self.grad_req)

    def reset_ctx(self, ctx):
        """Re-assign the Parameter to other contexts (reference
        ``parameter.py:440``): the data moves, in place so every handle on
        it stays valid, and the gradient buffer is rebuilt beside it."""
        ctx_list = [ctx] if isinstance(ctx, Context) else list(ctx)
        if self._data is not None:
            import jax
            self._ctx_list = ctx_list
            self._data._data = jax.device_put(self._data._materialize(),
                                              ctx_list[0].jax_device())
            self._init_grad()
        elif self._deferred_init:
            init, _, default_init, data = self._deferred_init
            self._deferred_init = (init, ctx_list, default_init, data)


class Constant(Parameter):
    """A constant parameter: grad_req='null', initialized from `value`
    (reference ``parameter.py:598``)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = nd.array(value)
        self.value = value

        class Init(initializer.Initializer):
            def _init_weight(self, _, arr):
                arr[:] = value.asnumpy()

        init_name = f"Constant_{name}_{id(self)}"
        from .. import registry as _registry
        _registry.get_register_func(initializer.Initializer, "initializer")(
            Init, init_name)
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=init_name)

    def __repr__(self):
        return f"Constant {self.name} (shape={self.shape}, dtype={self.dtype})"

    @property
    def grad_req(self):
        return "null"

    @grad_req.setter
    def grad_req(self, req):
        if req != "null":
            import warnings
            warnings.warn("Constant parameter {} does not support grad_req other "
                          "than 'null', and new value {} is ignored."
                          .format(self.name, req))
        self._grad_req = "null"


class _HostArrayView:
    """numpy buffer quacking like an NDArray for initializer __call__."""

    __slots__ = ("_a",)

    def __init__(self, a):
        self._a = a

    @property
    def shape(self):
        return self._a.shape

    @property
    def dtype(self):
        return self._a.dtype

    def __setitem__(self, key, value):
        self._a[key] = value.asnumpy() if isinstance(value, NDArray) else value


class ParameterDict:
    """A dictionary managing Parameters with prefix scoping and sharing
    (reference ``parameter.py:636``)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    def __repr__(self):
        s = "{name}(\n{content}\n)"
        name = self._prefix + " " if self._prefix else ""
        return s.format(name=name, content="\n".join(
            [_indent("  {0}".format(v), 2) for v in self.values()]))

    def __getitem__(self, key):
        return self._params[key]

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    @property
    def prefix(self):
        return self._prefix

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._params[name]
        return None

    def get(self, name, **kwargs):
        """Retrieve or create (reference ``parameter.py:701``)."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if hasattr(param, k) and getattr(param, k) is not None:
                    existing = getattr(param, k)
                    if k == "shape" and v is not None and len(v) == len(existing):
                        inferred_shape = []
                        matched = True
                        for dim1, dim2 in zip(v, existing):
                            if dim1 != dim2 and dim1 * dim2 != 0:
                                matched = False
                                break
                            elif dim1 == dim2:
                                inferred_shape.append(dim1)
                            elif dim1 in (0, None):
                                inferred_shape.append(dim2)
                            else:
                                inferred_shape.append(dim1)
                        if matched:
                            param._shape = tuple(inferred_shape)
                            continue
                    elif k == "dtype" and _np.dtype(v) == _np.dtype(existing):
                        continue
                    assert v is None or v == existing, \
                        f"Cannot retrieve Parameter '{name}' because desired " \
                        f"attribute does not match with stored for attribute " \
                        f"'{k}': desired '{v}' vs stored '{getattr(param, k)}'."
                else:
                    setattr(param, k, v)
        return param

    def get_constant(self, name, value=None):
        """Retrieve or create a Constant (reference ``parameter.py:772``)."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise KeyError(f"No constant named '{name}'. Please specify "
                               "value if you want to create a new constant.")
            param = Constant(name, value)
            self._params[name] = param
        elif value is not None:
            assert isinstance(param, Constant), \
                f"Parameter '{name}' already exists but it is not a constant."
            if isinstance(value, NDArray):
                value = value.asnumpy()
            assert param.shape == value.shape and \
                (param.value.asnumpy() == value).all(), \
                f"Constant '{name}' already exists but it's value doesn't " \
                "match new value"
        return param

    def update(self, other):
        """Copy all Parameters in ``other`` (reference ``parameter.py:817``)."""
        for k, v in other.items():
            if k in self._params:
                assert self._params[k] is v, \
                    f"Cannot update self with other because they have different " \
                    f"Parameters with the same name '{k}'"
            else:
                self._params[k] = v

    def initialize(self, init=initializer.Uniform(), ctx=None, verbose=False,
                   force_reinit=False):
        """Initialize all managed Parameters (reference ``parameter.py:829``)."""
        if verbose:
            init.set_verbosity(verbose=verbose)
        for _, v in self.items():
            v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        for i in self.values():
            i.zero_grad()

    def reset_ctx(self, ctx):
        for i in self.values():
            i.reset_ctx(ctx)

    def list_ctx(self):
        s = set()
        for i in self.values():
            s.update(i.list_ctx())
        return list(s)

    def setattr(self, name, value):
        """Set an attribute on all managed Parameters (reference
        ``parameter.py:872``)."""
        for i in self.values():
            setattr(i, name, value)

    def save(self, filename, strip_prefix=""):
        """Save to file (reference ``parameter.py:899``)."""
        arg_dict = {}
        for param in self.values():
            weight = param._reduce()
            if not param.name.startswith(strip_prefix):
                raise ValueError(
                    f"Prefix '{strip_prefix}' is to be striped before saving, "
                    f"but Parameter's name '{param.name}' does not start with "
                    f"'{strip_prefix}'")
            arg_dict[param.name[len(strip_prefix):]] = weight
        nd.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix="", cast_dtype=False,
             dtype_source="current"):
        """Load from file (reference ``parameter.py:924``)."""
        if restore_prefix:
            for name in self.keys():
                assert name.startswith(restore_prefix), \
                    f"restore_prefix is '{restore_prefix}' but Parameters name " \
                    f"'{name}' does not start with '{restore_prefix}'"
        lprefix = len(restore_prefix)
        loaded = nd.load(filename)
        arg_dict = {(k[4:] if k.startswith("arg:") or k.startswith("aux:") else k): v
                    for k, v in loaded.items()}
        arg_dict = {restore_prefix + k: v for k, v in arg_dict.items()}
        if not allow_missing:
            for name in self.keys():
                assert name in arg_dict, \
                    f"Parameter '{name[lprefix:]}' is missing in file " \
                    f"'{filename}', which contains parameters: " \
                    f"{_brief_print_list(arg_dict.keys())}. Please make sure " \
                    "source and target networks have the same prefix."
        for name in arg_dict:
            if name not in self._params:
                assert ignore_extra, \
                    f"Parameter '{name[lprefix:]}' loaded from file " \
                    f"'{filename}' is not present in ParameterDict, which " \
                    f"contains parameters {_brief_print_list(self._params.keys())}. " \
                    "Set ignore_extra=True to ignore. "
                continue
            self[name]._load_init(arg_dict[name], ctx, cast_dtype=cast_dtype,
                                  dtype_source=dtype_source)
def _brief_print_list(lst, limit=7):
    lst = list(lst)
    if len(lst) > limit:
        return _brief_print_list(lst[:limit // 2], limit) + ", ..., " + \
            _brief_print_list(lst[-limit // 2:], limit)
    return ", ".join(f"'{str(i)}'" for i in lst)
