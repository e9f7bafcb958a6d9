"""Flat C-API-shaped surface for language bindings.

The reference exposes 114 ``extern "C" MX*`` functions
(ref: include/mxnet/c_api.h, src/c_api/*.cc) that every binding (R/Scala/
Perl/C++/Matlab — SURVEY.md §2.7) consumes: opaque handles + flat functions
returning an int status, with ``MXGetLastError`` for messages.

This module reproduces that contract over the Python substrate: integer
handles into a registry, the same function names/argument orders, status-code
returns. It is directly usable via cffi's ``embedding`` or any FFI that can
call into CPython; a compiled ``libmxnet_tpu`` shim that exports these as
real C symbols (CPython C API) is the bindings-stage follow-up.

Only the error contract differs internally: exceptions are caught and stored
for MXGetLastError, exactly like c_api_common.h's error ring.
"""
from __future__ import annotations

import json
import threading

import numpy as np

from . import ndarray as nd
from . import symbol as sym
from . import kvstore as kvs
from . import random as _random
from .base import MXNetError
from .executor import Executor
from .ndarray import NDArray

_state = threading.local()
_handles = {}
_next_handle = [1]
_lock = threading.Lock()


def _new_handle(obj):
    with _lock:
        h = _next_handle[0]
        _next_handle[0] += 1
        _handles[h] = obj
    return h


def _get(h):
    return _handles[h]


def _free(h):
    _handles.pop(h, None)


def _capi(fn):
    """Wrap: return 0 on success, -1 + stored error on exception
    (ref: API_BEGIN/API_END macros, c_api_common.h)."""
    def wrapped(*args, **kwargs):
        try:
            return 0, fn(*args, **kwargs)
        except Exception as e:  # noqa: BLE001 - the C API flattens all errors
            _state.error = "%s: %s" % (type(e).__name__, e)
            return -1, None
    wrapped.__name__ = fn.__name__
    return wrapped


def MXGetLastError():
    return getattr(_state, "error", "")


@_capi
def MXGetVersion():
    from .base import (MXNET_TPU_MAJOR, MXNET_TPU_MINOR, MXNET_TPU_PATCH)
    return MXNET_TPU_MAJOR * 10000 + MXNET_TPU_MINOR * 100 + MXNET_TPU_PATCH


@_capi
def MXRandomSeed(seed):
    _random.seed(seed)


@_capi
def MXNotifyShutdown():
    from . import engine
    engine.wait_all()


# -- NDArray ---------------------------------------------------------------

@_capi
def MXNDArrayCreate(shape, dev_type, dev_id, delay_alloc=0, dtype="float32"):
    from .context import Context
    ctx = Context(Context.devtype2str[dev_type], dev_id)
    return _new_handle(nd.zeros(tuple(shape), ctx=ctx, dtype=dtype))


@_capi
def MXNDArrayCreateFromNumpy(arr):
    return _new_handle(nd.array(np.asarray(arr)))


@_capi
def MXNDArrayFree(handle):
    _free(handle)


@_capi
def MXNDArrayGetShape(handle):
    return _get(handle).shape


@_capi
def MXNDArrayGetDType(handle):
    return str(_get(handle).dtype)


@_capi
def MXNDArrayGetContext(handle):
    ctx = _get(handle).context
    return (ctx.device_typeid, ctx.device_id)


@_capi
def MXNDArraySyncCopyToCPU(handle):
    return _get(handle).asnumpy()


@_capi
def MXNDArraySyncCopyFromCPU(handle, arr):
    _get(handle)[:] = np.asarray(arr)


@_capi
def MXNDArrayWaitToRead(handle):
    _get(handle).wait_to_read()


@_capi
def MXNDArrayWaitAll():
    nd.waitall()


@_capi
def MXNDArraySlice(handle, begin, end):
    return _new_handle(_get(handle)[begin:end])


@_capi
def MXNDArrayReshape(handle, shape):
    return _new_handle(_get(handle).reshape(tuple(shape)))


@_capi
def MXNDArraySave(fname, handles, keys=None):
    arrays = [_get(h) for h in handles]
    if keys:
        nd.save(fname, dict(zip(keys, arrays)))
    else:
        nd.save(fname, arrays)


@_capi
def MXNDArrayLoad(fname):
    data = nd.load(fname)
    if isinstance(data, dict):
        keys = list(data.keys())
        return [_new_handle(data[k]) for k in keys], keys
    return [_new_handle(a) for a in data], []


# -- operator invocation ----------------------------------------------------

@_capi
def MXListAllOpNames():
    from .ops import list_ops
    return list_ops()


@_capi
def MXImperativeInvoke(op_name, input_handles, attrs):
    from .ops import get as get_op
    from .ndarray import invoke
    opdef = get_op(op_name)
    inputs = [_get(h) for h in input_handles]
    out = invoke(opdef, inputs, dict(attrs or {}))
    outs = out if isinstance(out, list) else [out]
    return [_new_handle(o) for o in outs]


@_capi
def MXImperativeInvokeInPlace(op_name, input_handles, attrs,
                              output_handles):
    """The ``*outputs != NULL`` half of the reference MXImperativeInvoke
    contract (ref: src/c_api/c_api_ndarray.cc:322): results are written IN
    PLACE into the caller's existing NDArray handles (``out=`` semantics)
    — the handles keep identifying the same NDArrays, whose storage is
    updated. A count mismatch fails loudly instead of truncating."""
    from .ops import get as get_op
    from .ndarray import invoke
    opdef = get_op(op_name)
    inputs = [_get(h) for h in input_handles]
    targets = [_get(h) for h in output_handles]
    # invoke()'s out= path validates count/shape/dtype BEFORE any write
    # (fails loudly instead of reshaping/casting the caller's buffers) and
    # records the targets themselves with autograd — a manual copy of the
    # results here would leave the out handles off the recorded graph
    invoke(opdef, inputs, dict(attrs or {}), out=targets)
    return len(targets)


# -- Symbol ----------------------------------------------------------------

@_capi
def MXSymbolCreateVariable(name):
    return _new_handle(sym.Variable(name))


@_capi
def MXSymbolCreateAtomicSymbol(op_name, keys, vals):
    attrs = dict(zip(keys, vals))
    name = attrs.pop("name", None)
    return _new_handle((op_name, attrs, name))  # composed at MXSymbolCompose


@_capi
def MXSymbolCompose(handle, name, arg_handles, arg_keys=None):
    spec = _get(handle)
    if isinstance(spec, tuple):
        op_name, attrs, aname = spec
        args = [_get(h) for h in arg_handles]
        if arg_keys:
            kwargs = dict(zip(arg_keys, args))
            kwargs.update(attrs)
            result = getattr(sym, op_name)(name=name or aname, **kwargs)
        else:
            result = getattr(sym, op_name)(*args, name=name or aname, **attrs)
        _handles[handle] = result
        return handle
    raise MXNetError("MXSymbolCompose: handle is already composed")


@_capi
def MXSymbolCreateFromJSON(json_str):
    return _new_handle(sym.load_json(json_str))


@_capi
def MXSymbolSaveToJSON(handle):
    return _get(handle).tojson()


@_capi
def MXSymbolListArguments(handle):
    return _get(handle).list_arguments()


@_capi
def MXSymbolListOutputs(handle):
    return _get(handle).list_outputs()


@_capi
def MXSymbolListAuxiliaryStates(handle):
    return _get(handle).list_auxiliary_states()


@_capi
def MXSymbolInferShape(handle, keys, shapes):
    s = _get(handle)
    arg_shapes, out_shapes, aux_shapes = s.infer_shape(
        **dict(zip(keys, shapes)))
    return arg_shapes, out_shapes, aux_shapes


@_capi
def MXSymbolGetInternals(handle):
    return _new_handle(_get(handle).get_internals())


@_capi
def MXSymbolFree(handle):
    _free(handle)


# -- Executor --------------------------------------------------------------

@_capi
def MXExecutorBind(sym_handle, dev_type, dev_id, arg_handles,
                   grad_handles=None, grad_reqs="write", aux_handles=None):
    from .context import Context
    ctx = Context(Context.devtype2str[dev_type], dev_id)
    s = _get(sym_handle)
    args = [_get(h) for h in arg_handles]
    grads = [_get(h) if h else None for h in (grad_handles or [])] or None
    auxs = [_get(h) for h in (aux_handles or [])] or None
    ex = s.bind(ctx, args, grads, grad_reqs, auxs)
    return _new_handle(ex)


@_capi
def MXExecutorForward(handle, is_train):
    _get(handle).forward(is_train=bool(is_train))


@_capi
def MXExecutorBackward(handle, out_grad_handles=None):
    grads = ([_get(h) for h in out_grad_handles]
             if out_grad_handles else None)
    _get(handle).backward(grads)


@_capi
def MXExecutorOutputs(handle):
    return [_new_handle(o) for o in _get(handle).outputs]


@_capi
def MXExecutorFree(handle):
    _free(handle)


# -- KVStore ---------------------------------------------------------------

@_capi
def MXKVStoreCreate(kv_type):
    return _new_handle(kvs.create(kv_type))


@_capi
def MXKVStoreInit(handle, keys, value_handles):
    _get(handle).init(list(keys), [_get(h) for h in value_handles])


@_capi
def MXKVStorePush(handle, keys, value_handles, priority=0):
    _get(handle).push(list(keys), [_get(h) for h in value_handles],
                      priority=priority)


@_capi
def MXKVStorePull(handle, keys, out_handles, priority=0):
    _get(handle).pull(list(keys), out=[_get(h) for h in out_handles],
                      priority=priority)


@_capi
def MXKVStoreGetRank(handle):
    return _get(handle).rank


@_capi
def MXKVStoreGetGroupSize(handle):
    return _get(handle).num_workers


@_capi
def MXKVStoreBarrier(handle):
    _get(handle).barrier()


@_capi
def MXKVStoreFree(handle):
    _free(handle)


@_capi
def MXKVStoreGetNumDeadNode(handle, node_id, timeout_sec=60):
    return _get(handle).num_dead_node(node_id, timeout_sec)


# ---------------------------------------------------------------------------
# byte-level marshalling helpers for the compiled shim (src/capi/): the C
# side traffics raw buffers; dtype framing happens here
# ---------------------------------------------------------------------------
@_capi
def MXNDArraySyncCopyFromBytes(handle, buf, dtype="float32"):
    a = _get(handle)
    a[:] = np.frombuffer(buf, np.dtype(dtype)).reshape(a.shape)


@_capi
def MXNDArraySyncCopyToBytes(handle):
    return np.ascontiguousarray(_get(handle).asnumpy()).tobytes()


@_capi
def MXNDArraySize(handle):
    return int(_get(handle).size)


# ---------------------------------------------------------------------------
# C predict API (ref: include/mxnet/c_predict_api.h, src/c_api/
# c_predict_api.cc — the deploy/amalgamation surface) over Predictor
# ---------------------------------------------------------------------------
def _pred_create(symbol_json, param_bytes, dev_type, dev_id, input_keys,
                 input_shapes, output_names=None):
    from . import dmlc_serial
    from .predictor import Predictor
    from .context import Context
    ctx = Context(Context.devtype2str[dev_type], dev_id)
    if param_bytes:
        arrs, names = dmlc_serial.loads(bytes(param_bytes))
        params = {n: NDArray(np.asarray(a)) for n, a in zip(names, arrs)}
    else:
        params = {}
    shapes = {k: tuple(int(d) for d in s)
              for k, s in zip(input_keys, input_shapes)}
    # legacy contract: a NULL/empty param blob means "uninitialized
    # predictor" (zero weights) — keep it; a NON-empty blob with missing
    # keys is a broken deploy and raises (predictor.check_missing_params)
    pred = Predictor(symbol_json, params, shapes, ctx=ctx,
                     output_names=output_names,
                     allow_missing=not param_bytes)
    pred._pending = {}
    return _new_handle(pred)


@_capi
def MXPredCreate(symbol_json, param_bytes, dev_type, dev_id,
                 input_keys, input_shapes):
    return _pred_create(symbol_json, param_bytes, dev_type, dev_id,
                        input_keys, input_shapes)


@_capi
def MXPredCreatePartialOut(symbol_json, param_bytes, dev_type, dev_id,
                           input_keys, input_shapes, output_keys):
    """Predictor over selected output heads (ref: MXPredCreatePartialOut,
    c_predict_api.h:92-102)."""
    return _pred_create(symbol_json, param_bytes, dev_type, dev_id,
                        input_keys, input_shapes,
                        output_names=list(output_keys))


@_capi
def MXPredReshape(handle, input_keys, input_shapes):
    """Rebind an existing predictor for new input shapes; returns a NEW
    predictor handle sharing the loaded weights (the reference's
    MXPredReshape contract: old handle stays valid)."""
    import copy as _copy
    pred = _get(handle)
    new = _copy.copy(pred)     # shares symbol/params; gets its own executor
    from collections import OrderedDict as _OD
    new._exec_cache = _OD()    # executors are NOT shared across handles:
    #                            two handles at one shape must keep their
    #                            own input placeholders (set-input isolation)
    shapes = {k: tuple(int(d) for d in s)
              for k, s in zip(input_keys, input_shapes)}
    new.reshape(shapes)
    new._pending = {}
    return _new_handle(new)


@_capi
def MXPredSetInput(handle, key, buf, dtype="float32"):
    pred = _get(handle)
    shape = None
    for name in pred._input_names:
        if name == key:
            shape = pred._executor.arg_dict[name].shape
    if shape is None:
        raise MXNetError("MXPredSetInput: unknown input %r" % key)
    pred._pending[key] = np.frombuffer(buf, np.dtype(dtype)).reshape(shape)


@_capi
def MXPredForward(handle):
    pred = _get(handle)
    pred.forward(**pred._pending)


@_capi
def MXPredGetOutputShape(handle, index):
    return tuple(int(d) for d in _get(handle).outputs[index].shape)


@_capi
def MXPredGetOutput(handle, index):
    out = _get(handle).outputs[index]
    return np.ascontiguousarray(out.asnumpy(), np.float32).tobytes()


@_capi
def MXPredFree(handle):
    _free(handle)


# ---------------------------------------------------------------------------
# r5 completion: the remaining c_api.h families so the ABI reaches binding-
# generation completeness (ref: include/mxnet/c_api.h; VERDICT r4 item 2)
# ---------------------------------------------------------------------------

# -- NDArray (remaining) ----------------------------------------------------

@_capi
def MXNDArrayCreateNone():
    """Placeholder array (ref: MXNDArrayCreateNone, c_api.cc) — delayed
    alloc collapses on this substrate; an empty f32 scalar stands in."""
    return _new_handle(nd.zeros((1,)))


@_capi
def MXNDArrayCreateEx(shape, dev_type, dev_id, delay_alloc, dtype_id):
    from .context import Context
    ctx = Context(Context.devtype2str[dev_type], dev_id)
    return _new_handle(nd.zeros(tuple(shape), ctx=ctx,
                                dtype=_DTYPE_ID2NAME[int(dtype_id)]))


@_capi
def MXNDArrayAt(handle, idx):
    return _new_handle(_get(handle)[int(idx)])


@_capi
def MXNDArrayGetData(handle):
    """Raw bytes of the array (the compiled shim hands out a pointer into
    its per-call buffer; a device pointer has no meaning to a C caller
    on the host)."""
    return np.ascontiguousarray(_get(handle).asnumpy()).tobytes()


@_capi
def MXNDArraySaveRawBytes(handle):
    from . import dmlc_serial
    a = _get(handle)
    return dmlc_serial.dumps([a.asnumpy()], [""])


@_capi
def MXNDArrayLoadFromRawBytes(buf):
    from . import dmlc_serial
    arrs, _names = dmlc_serial.loads(bytes(buf))
    return _new_handle(NDArray(np.asarray(arrs[0])))


@_capi
def MXNDArrayWaitToWrite(handle):
    _get(handle).wait_to_read()  # functional arrays: read-ready == write-ready


_DTYPE_ID2NAME = {0: "float32", 1: "float64", 2: "float16", 3: "uint8",
                  4: "int32", 5: "int8", 6: "int64", 12: "bfloat16"}


# -- Function registry (legacy imperative surface; ref: c_api.cc:396-422,
#    NDArrayFunctionReg). Functions ARE ops here; a function handle is an
#    index into the sorted op list. ----------------------------------------

def _op_names_sorted():
    from .ops import list_ops
    return list_ops()


@_capi
def MXListFunctions():
    return list(range(len(_op_names_sorted())))


@_capi
def MXGetFunction(name):
    names = _op_names_sorted()
    try:
        return names.index(name)
    except ValueError:
        raise MXNetError("function %r not found" % name)


def _op_by_index(fh):
    from .ops import get as get_op
    names = _op_names_sorted()
    if not 0 <= int(fh) < len(names):
        raise MXNetError("invalid function handle %r" % fh)
    return get_op(names[int(fh)])


def _safe_arity(op):
    try:
        return op.list_inputs({}), op.num_outputs({})
    except MXNetError:  # arity depends on attrs (e.g. Custom)
        return ["data"], 1


@_capi
def MXFuncGetInfo(fh):
    op = _op_by_index(int(fh))
    ins, _ = _safe_arity(op)
    return (op.name, op.description or op.name, len(ins), list(ins),
            ["NDArray"] * len(ins), [""] * len(ins))


@_capi
def MXFuncDescribe(fh):
    op = _op_by_index(int(fh))
    ins, n_out = _safe_arity(op)
    # the *_scalar op family consumes one float via the 'scalar' attr
    # (ref: elemwise_binary_scalar_op.h); everything else takes attrs only
    n_scalar = 1 if op.name.endswith("_scalar") else 0
    return (len(ins), n_scalar, n_out, 0)  # use, scalars, mutate, type_mask


@_capi
def MXFuncInvoke(fh, use_var_handles, scalars, mutate_var_handles):
    return _func_invoke(int(fh), use_var_handles, scalars,
                        mutate_var_handles, {})


@_capi
def MXFuncInvokeEx(fh, use_var_handles, scalars, mutate_var_handles,
                   keys, vals):
    return _func_invoke(int(fh), use_var_handles, scalars,
                        mutate_var_handles, dict(zip(keys, vals)))


def _func_invoke(fh, use_vars, scalars, mutate_vars, attrs):
    from .ndarray import invoke
    op = _op_by_index(fh)
    inputs = [_get(h) for h in use_vars]
    if scalars:  # scalar args ride the attr dict (ops parse strings)
        attrs = dict(attrs)
        attrs.setdefault("scalar", str(scalars[0]))
    out = invoke(op, inputs, attrs)
    outs = out if isinstance(out, list) else [out]
    for h, o in zip(mutate_vars, outs):
        _get(h)[:] = o.asnumpy()


# -- Symbol (remaining) -----------------------------------------------------

@_capi
def MXSymbolCopy(handle):
    import copy as _copy
    return _new_handle(_copy.deepcopy(_get(handle)))


@_capi
def MXSymbolCreateFromFile(fname):
    return _new_handle(sym.load(fname))


@_capi
def MXSymbolCreateGroup(handles):
    return _new_handle(sym.Group([_get(h) for h in handles]))


@_capi
def MXSymbolGetName(handle):
    return _get(handle).name or ""


@_capi
def MXSymbolGetAttr(handle, key):
    v = _get(handle).attr(key)
    return ("", 0) if v is None else (str(v), 1)


@_capi
def MXSymbolSetAttr(handle, key, value):
    _get(handle)._set_attr(**{key: value})


@_capi
def MXSymbolListAttr(handle):
    """Recursive attr list as flat [k0, v0, k1, v1, ...] with
    ``node_name$key`` keys (ref: MXSymbolListAttr, c_api_symbolic.cc)."""
    flat = []
    for node_name, attrs in _get(handle).attr_dict().items():
        for k, v in attrs.items():
            flat += ["%s$%s" % (node_name, k), str(v)]
    return flat


@_capi
def MXSymbolListAttrShallow(handle):
    flat = []
    for k, v in (_get(handle).list_attr() or {}).items():
        flat += [str(k), str(v)]
    return flat


@_capi
def MXSymbolGetChildren(handle):
    return _new_handle(_get(handle).get_children())


@_capi
def MXSymbolGetOutput(handle, index):
    return _new_handle(_get(handle)[int(index)])


@_capi
def MXSymbolGrad(handle, wrt):
    # reference parity: v0.9.5's own MXSymbolGrad is LOG(FATAL)
    # "not implemented" (src/c_api/c_api_symbolic.cc:545-549)
    raise MXNetError("MXSymbolGrad is not implemented (matches reference "
                     "v0.9.5); bind with args_grad instead")


@_capi
def MXSymbolInferShapePartial(handle, keys, shapes):
    return _get(handle).infer_shape_partial(**dict(zip(keys, shapes)))


@_capi
def MXSymbolInferType(handle, keys, dtypes):
    arg_t, out_t, aux_t = _get(handle).infer_type(**dict(zip(keys, dtypes)))
    tostr = lambda ts: [None if t is None else np.dtype(t).name for t in ts]
    return tostr(arg_t), tostr(out_t), tostr(aux_t)


@_capi
def MXSymbolPrint(handle):
    s = _get(handle)
    lines = ["Symbol Outputs:"]
    for o in s.list_outputs():
        lines.append("\toutput[%d]=%s" % (len(lines) - 1, o))
    for a in s.list_arguments():
        lines.append("Variable:%s" % a)
    return "\n".join(lines)


@_capi
def MXSymbolSaveToFile(handle, fname):
    _get(handle).save(fname)


# -- Op introspection: what every reference binding autogenerates its
#    wrappers from (ref: MXSymbolListAtomicSymbolCreators +
#    MXSymbolGetAtomicSymbolInfo, consumed by OpWrapperGenerator.py) -------

@_capi
def MXSymbolListAtomicSymbolCreators():
    return list(range(len(_op_names_sorted())))


@_capi
def MXSymbolGetAtomicSymbolName(creator):
    return _op_names_sorted()[int(creator)]


@_capi
def MXSymbolGetAtomicSymbolInfo(creator):
    """(name, description, num_args, arg_names, arg_types, arg_descriptions,
    key_var_num_args, return_type). Tensor inputs are typed
    'NDArray-or-Symbol' exactly as the reference documents them; free-form
    attr params carry type 'string (optional)'."""
    op = _op_by_index(int(creator))
    # a creator handle names the REGISTERED entry (alias or canonical),
    # exactly like nnvm's per-alias Op entries
    reg_name = _op_names_sorted()[int(creator)]
    try:
        ins = op.list_inputs({})
    except MXNetError:
        # arity depends on attrs (e.g. Custom needs op_type): variadic
        ins = ["data"]
    names = list(ins)
    types = ["NDArray-or-Symbol"] * len(ins)
    descs = ["input: %s" % n for n in ins]
    kv = op.var_inputs_attr or ""
    return (reg_name, op.description or op.name, len(names), names, types,
            descs, kv, "NDArray-or-Symbol")


# -- Autograd (ref: MXAutograd*, c_api_ndarray.cc; python
#    contrib/autograd.py) ---------------------------------------------------

@_capi
def MXAutogradSetIsTraining(is_training):
    from . import autograd as ag
    prev = ag.is_recording()
    st = ag._st()
    st.recording = bool(is_training)
    st.training = bool(is_training)
    return 1 if prev else 0


@_capi
def MXAutogradMarkVariables(var_handles, grad_handles, grad_reqs=None):
    from . import autograd as ag
    ag.mark_variables([_get(h) for h in var_handles],
                      [_get(h) for h in grad_handles],
                      grad_reqs or "write")


@_capi
def MXAutogradComputeGradient(output_handles):
    from . import autograd as ag
    ag.compute_gradient([_get(h) for h in output_handles])


# -- DataIter (ref: MXDataIter family, c_api.cc ~708-788; creators
#    registered via MXNET_REGISTER_IO_ITER) --------------------------------

def _iter_creators():
    from . import io as mxio
    from . import image as mximg
    # the reference registers exactly the file-fed iterators at C level
    # (MXNET_REGISTER_IO_ITER in src/io/*.cc); NDArrayIter is python-only
    # there too
    return [
        ("MNISTIter", mxio.MNISTIter, "MNIST data iterator"),
        ("CSVIter", mxio.CSVIter, "CSV file iterator"),
        ("ImageRecordIter", mximg.ImageRecordIter,
         "RecordIO image iterator with decode+augment pipeline"),
        ("ImageDetIter", mximg.ImageDetIter,
         "RecordIO detection iterator (object-detection labels)"),
    ]


@_capi
def MXListDataIters():
    return list(range(len(_iter_creators())))


@_capi
def MXDataIterGetIterInfo(creator):
    import inspect
    name, cls, desc = _iter_creators()[int(creator)]
    try:
        params = [p for p in inspect.signature(cls).parameters
                  if p not in ("self", "kwargs")]
    except (TypeError, ValueError):
        params = []
    return (name, desc, len(params), params,
            ["string (optional)"] * len(params), [""] * len(params))


def _parse_param(v):
    """Iterator params arrive as strings over the C ABI; recover python
    values ('32'->int, '(3,28,28)'->tuple, 'True'->bool, paths stay str)."""
    import ast
    s = str(v)
    try:
        return ast.literal_eval(s)
    except (ValueError, SyntaxError):
        return s


class _CIter(object):
    __slots__ = ("it", "batch")

    def __init__(self, it):
        self.it = it
        self.batch = None


@_capi
def MXDataIterCreateIter(creator, keys, vals):
    _name, cls, _desc = _iter_creators()[int(creator)]
    kwargs = {k: _parse_param(v) for k, v in zip(keys, vals)}
    return _new_handle(_CIter(cls(**kwargs)))


@_capi
def MXDataIterFree(handle):
    _free(handle)


@_capi
def MXDataIterNext(handle):
    ci = _get(handle)
    try:
        ci.batch = next(ci.it)
        return 1
    except StopIteration:
        ci.batch = None
        return 0


@_capi
def MXDataIterBeforeFirst(handle):
    ci = _get(handle)
    ci.it.reset()
    ci.batch = None


def _cur_batch(handle):
    ci = _get(handle)
    if ci.batch is None:
        raise MXNetError("DataIter: no current batch (call MXDataIterNext)")
    return ci.batch


@_capi
def MXDataIterGetData(handle):
    return _new_handle(_cur_batch(handle).data[0])


@_capi
def MXDataIterGetLabel(handle):
    return _new_handle(_cur_batch(handle).label[0])


@_capi
def MXDataIterGetIndex(handle):
    idx = getattr(_cur_batch(handle), "index", None)
    return [] if idx is None else [int(i) for i in idx]


@_capi
def MXDataIterGetPadNum(handle):
    return int(getattr(_cur_batch(handle), "pad", 0) or 0)


# -- RecordIO (ref: MXRecordIO* in c_api.cc over dmlc recordio) ------------

@_capi
def MXRecordIOWriterCreate(uri):
    from .recordio import MXRecordIO
    return _new_handle(MXRecordIO(uri, "w"))


@_capi
def MXRecordIOWriterFree(handle):
    _get(handle).close()
    _free(handle)


@_capi
def MXRecordIOWriterWriteRecord(handle, buf):
    _get(handle).write(bytes(buf))


@_capi
def MXRecordIOWriterTell(handle):
    return int(_get(handle).tell())


@_capi
def MXRecordIOReaderCreate(uri):
    from .recordio import MXRecordIO
    return _new_handle(MXRecordIO(uri, "r"))


@_capi
def MXRecordIOReaderFree(handle):
    _get(handle).close()
    _free(handle)


@_capi
def MXRecordIOReaderReadRecord(handle):
    rec = _get(handle).read()
    return b"" if rec is None else bytes(rec)


@_capi
def MXRecordIOReaderSeek(handle, pos):
    r = _get(handle)
    r.handle.seek(int(pos))


# -- Rtc: runtime user kernels. The reference JIT-compiles CUDA source via
#    NVRTC (src/common/mxrtc.cc); the TPU-native analog JIT-traces a
#    user-supplied Pallas/JAX kernel body supplied as source text. ---------

@_capi
def MXRtcCreate(name, input_names, output_names, input_handles,
                output_handles, kernel_src):
    from .rtc import PallasKernel
    ns = {}
    exec(compile(kernel_src, "<mxrtc:%s>" % name, "exec"), ns)  # noqa: S102
    if name not in ns or not callable(ns[name]):
        raise MXNetError("MXRtcCreate: kernel source must define a callable "
                         "named %r" % name)
    kern = PallasKernel(ns[name], out_like=0)
    return _new_handle({"kernel": kern, "inputs": list(input_names),
                        "outputs": list(output_names)})


@_capi
def MXRtcPush(handle, input_handles, output_handles,
              gridx=1, gridy=1, gridz=1, blockx=1, blocky=1, blockz=1):
    ent = _get(handle)
    outs = ent["kernel"](*[_get(h) for h in input_handles])
    if not isinstance(outs, (list, tuple)):
        outs = [outs]
    for h, o in zip(output_handles, outs):
        _get(h)[:] = o.asnumpy()


@_capi
def MXRtcFree(handle):
    _free(handle)


# -- Profiler (ref: MXSetProfilerConfig/State, MXDumpProfile) --------------

@_capi
def MXSetProfilerConfig(mode, filename):
    from . import profiler
    profiler.profiler_set_config(
        mode if isinstance(mode, str) else ("all" if mode else "symbolic"),
        filename)


@_capi
def MXSetProfilerState(state):
    from . import profiler
    profiler.profiler_set_state(
        state if isinstance(state, str) else ("run" if state else "stop"))


@_capi
def MXDumpProfile():
    from . import profiler
    profiler.dump_profile()


# -- Executor (remaining) ---------------------------------------------------

def _bind_with(sym_handle, dev_type, dev_id, g2c_keys, g2c_dev_types,
               g2c_dev_ids, arg_handles, grad_handles, grad_reqs,
               aux_handles, shared_exec_handle=None):
    from .context import Context
    ctx = Context(Context.devtype2str[dev_type], dev_id)
    s = _get(sym_handle)
    group2ctx = {k: Context(Context.devtype2str[t], i)
                 for k, t, i in zip(g2c_keys or [], g2c_dev_types or [],
                                    g2c_dev_ids or [])} or None
    args = [_get(h) for h in arg_handles]
    grads = [_get(h) if h else None for h in (grad_handles or [])] or None
    auxs = [_get(h) for h in (aux_handles or [])] or None
    reqs = grad_reqs if isinstance(grad_reqs, str) else list(grad_reqs)
    shared = _get(shared_exec_handle) if shared_exec_handle else None
    ex = Executor(s, ctx, args, grads, reqs, auxs, group2ctx=group2ctx,
                  shared_exec=shared)
    return _new_handle(ex)


@_capi
def MXExecutorBindX(sym_handle, dev_type, dev_id, g2c_keys, g2c_dev_types,
                    g2c_dev_ids, arg_handles, grad_handles=None,
                    grad_reqs="write", aux_handles=None):
    return _bind_with(sym_handle, dev_type, dev_id, g2c_keys, g2c_dev_types,
                      g2c_dev_ids, arg_handles, grad_handles, grad_reqs,
                      aux_handles)


@_capi
def MXExecutorBindEX(sym_handle, dev_type, dev_id, g2c_keys, g2c_dev_types,
                     g2c_dev_ids, arg_handles, grad_handles=None,
                     grad_reqs="write", aux_handles=None,
                     shared_exec_handle=None):
    return _bind_with(sym_handle, dev_type, dev_id, g2c_keys, g2c_dev_types,
                      g2c_dev_ids, arg_handles, grad_handles, grad_reqs,
                      aux_handles, shared_exec_handle)


@_capi
def MXExecutorPrint(handle):
    ex = _get(handle)
    lines = ["Executor over symbol %r" % (ex._symbol.name,)]
    for n, a in ex.arg_dict.items():
        lines.append("arg %s: shape %s dtype %s" % (n, a.shape, a.dtype))
    return "\n".join(lines)


def _wrap_c_callback(addr, argspec):
    """Wrap a raw C function pointer (passed as an integer address by the
    compiled shim) into a python callable via ctypes."""
    import ctypes
    return ctypes.CFUNCTYPE(None, *argspec)(addr)


@_capi
def MXExecutorSetMonitorCallback(handle, callback_addr, closure_addr=0):
    """callback: void (*)(const char* name, NDArrayHandle out, void*).
    Called with every op output during monitored forwards (ref:
    ExecutorMonitorCallback, c_api.h:68-70;
    GraphExecutor::SetMonitorCallback, graph_executor.cc:72)."""
    import ctypes
    cfn = _wrap_c_callback(int(callback_addr),
                           (ctypes.c_char_p, ctypes.c_uint64,
                            ctypes.c_void_p))
    closure = int(closure_addr or 0)

    def py_cb(name, arr):
        # handle valid for the duration of the callback only (the reference
        # engine owns its NDArrays across the callback the same way)
        h = _new_handle(arr if isinstance(arr, NDArray) else NDArray(arr))
        try:
            cfn(str(name).encode(), h, closure)
        finally:
            _free(h)
    _get(handle).set_monitor_callback(py_cb)


# -- KVStore (remaining) ----------------------------------------------------

@_capi
def MXKVStoreGetType(handle):
    return _get(handle).type


@_capi
def MXKVStoreIsWorkerNode():
    import os
    return 1 if os.environ.get("DMLC_ROLE", "worker") == "worker" else 0


@_capi
def MXKVStoreIsServerNode():
    import os
    return 1 if os.environ.get("DMLC_ROLE", "worker") == "server" else 0


@_capi
def MXKVStoreIsSchedulerNode():
    import os
    return 1 if os.environ.get("DMLC_ROLE", "worker") == "scheduler" else 0


@_capi
def MXKVStoreRunServer(handle, controller_addr=None):
    """Server role collapses on this substrate (SURVEY §2.4: psum replaces
    ps-lite); the entry blocks until the worker group's rendezvous ends —
    here that is a no-op returning immediately, matching kvstore_server's
    thin-by-design role."""
    from . import kvstore_server
    kvstore_server._init_distributed()


@_capi
def MXKVStoreSendCommmandToServers(handle, cmd_id, cmd_body):
    kv = _get(handle)
    if int(cmd_id) == 0:  # kController optimizer install (ref: kvstore.py:226)
        import pickle
        body = bytes(cmd_body) if not isinstance(cmd_body, str) \
            else cmd_body.encode("latin-1")
        try:
            optzr = pickle.loads(body)
        except Exception as e:
            # a body that fails to unpickle means the server would train
            # with the WRONG optimizer — surface it, never swallow it
            # (the truncation bug this catches: NUL-terminated marshalling
            # of a binary pickle; use MXKVStoreSendCommmandToServersEx)
            raise MXNetError(
                "kvstore command 0 (set optimizer): body of %d bytes "
                "failed to unpickle (%s: %s); binary bodies must be sent "
                "length-explicit" % (len(body), type(e).__name__, e))
        kv.set_optimizer(optzr)
    # other commands (kSetMultiPrecision etc.) have no role here


@_capi
def MXKVStoreSetBarrierBeforeExit(handle, do_barrier):
    setattr(_get(handle), "_barrier_before_exit", bool(do_barrier))


@_capi
def MXKVStoreSetUpdater(handle, updater_addr, closure_addr=0):
    """updater: void (*)(int key, NDArrayHandle recv, NDArrayHandle local,
    void*). The C callback is invoked with handles; mutations it makes to
    ``local`` through the ABI are the update (ref: MXKVStoreUpdater,
    c_api.h:1264-1277)."""
    import ctypes
    cfn = _wrap_c_callback(int(updater_addr),
                           (ctypes.c_int, ctypes.c_uint64, ctypes.c_uint64,
                            ctypes.c_void_p))
    closure = int(closure_addr or 0)

    def py_updater(key, recv, local):
        # handles are valid for the duration of the callback only
        hr, hl = _new_handle(recv), _new_handle(local)
        try:
            cfn(int(key), hr, hl, closure)
        finally:
            _free(hr)
            _free(hl)
    _get(handle)._set_updater(py_updater)


@_capi
def MXInitPSEnv(keys, vals):
    import os
    for k, v in zip(keys, vals):
        os.environ[str(k)] = str(v)


# -- CustomOp registration through the ABI (ref: MXCustomOpRegister,
#    src/operator/custom/custom.cc). The compiled shim passes the creator
#    as a raw fn pointer; python-side registrations use operator.register.

@_capi
def MXCustomOpRegister(op_type, creator_addr=None):
    if creator_addr is None:
        raise MXNetError(
            "MXCustomOpRegister from C requires a creator callback; "
            "python CustomOpProp classes register via "
            "mxnet_tpu.operator.register(%r)" % op_type)
    raise MXNetError(
        "C-struct CustomOp creators are not supported on this substrate; "
        "register a python CustomOpProp (mxnet_tpu.operator.register) — "
        "the compiled ABI can drive it via MXImperativeInvoke('Custom')")
