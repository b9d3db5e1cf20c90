"""Expression evaluation driver: one fused XLA program per expression list.

This is the TPU replacement for the reference's per-expression cuDF JNI calls
(GpuProjectExec's columnarEval tree, basicPhysicalOperators.scala:66): the whole
bound expression list is traced once into a single jit program per
(expressions, schema, capacity, string width) key and cached — every batch in the
same shape bucket reuses the compiled executable, and XLA fuses all expressions
into one kernel pass over HBM.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import device as _device  # noqa: F401 - jax setup
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.columnar.dtypes import DType, Field, Schema
from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
from spark_rapids_tpu.exprs.core import (BoundReference, ColV, EvalCtx,
                                         Expression)
from spark_rapids_tpu.exprs.misc import Alias


def batch_to_colvs(xp, batch) -> List[ColV]:
    return [ColV(c.dtype, c.data, c.validity, c.lengths) for c in batch.columns]


def colv_to_column(v: ColV, xp, capacity: int, string_max_bytes: int) -> Tuple:
    """Normalize an output ColV to full-capacity arrays (broadcast scalars)."""
    data, validity, lengths = v.data, v.validity, v.lengths
    if v.dtype is DType.STRING:
        if data.ndim == 1:  # scalar string row
            data = xp.broadcast_to(data[None, :], (capacity, data.shape[0]))
            lengths = xp.broadcast_to(xp.reshape(lengths, (1,)), (capacity,))
            validity = xp.broadcast_to(xp.reshape(validity, (1,)), (capacity,))
    else:
        if getattr(data, "ndim", 0) == 0:
            data = xp.broadcast_to(data, (capacity,))
        if getattr(validity, "ndim", 0) == 0:
            validity = xp.broadcast_to(validity, (capacity,))
    data = data.astype(v.dtype.np_dtype()) if data.dtype != v.dtype.np_dtype() else data
    validity = validity.astype(bool)
    return data, validity, lengths


def output_schema(exprs: Sequence[Expression]) -> Schema:
    names = []
    for i, e in enumerate(exprs):
        n = e.name_hint
        if n in names:
            n = f"{n}_{i}"
        names.append(n)
    return Schema([Field(n, e.dtype(), e.nullable())
                   for n, e in zip(names, exprs)])


def reference_ordinals(exprs: Sequence[Expression]) -> Optional[List[int]]:
    """The input ordinals when every expression is a plain column reference
    (renamed or not), else None: such a projection selects columns and needs
    no program (the planner's column pruning puts them on top of scans)."""
    refs = [e.c if isinstance(e, Alias) else e for e in exprs]
    if not all(isinstance(r, BoundReference) for r in refs):
        return None
    return [r.ordinal for r in refs]


# ------------------------------------------------------------------ CPU (eager)
def eval_exprs_host(exprs: Sequence[Expression], batch: HostBatch,
                    string_max_bytes: int = 256,
                    ctx_attrs: Optional[dict] = None) -> HostBatch:
    """Eager numpy evaluation over a host batch (the CPU engine path)."""
    colvs = batch_to_colvs(np, batch)
    ctx = EvalCtx(np, colvs, batch.num_rows, string_max_bytes)
    for k, v in (ctx_attrs or {}).items():
        setattr(ctx, k, v)
    out_schema = output_schema(exprs)
    cols = []
    with np.errstate(invalid="ignore", divide="ignore", over="ignore"):
        for e, f in zip(exprs, out_schema):
            v = e.eval(ctx)
            data, validity, lengths = colv_to_column(v, np, batch.num_rows,
                                                     string_max_bytes)
            cols.append(HostColumn(f.dtype, np.asarray(data), np.asarray(validity),
                                   np.asarray(lengths) if lengths is not None else None))
    return HostBatch(out_schema, tuple(cols), batch.num_rows)


# ------------------------------------------------------------------ TPU (jitted)
from spark_rapids_tpu.serving.program_cache import (global_program_cache,
                                                    named_jit)

_PROGRAM_CACHE = global_program_cache()
#: legacy alias for the serving cache's program table (cleared by conftest
#: between modules; expression keys are tuples of frozen expressions, so
#: they can't collide with the execs' string-prefixed keys)
_JIT_CACHE: Dict[Tuple, "jax.stages.Wrapped"] = _PROGRAM_CACHE._programs


def _flatten_batch(batch: DeviceBatch) -> List:
    flat = []
    for c in batch.columns:
        flat.append(c.data)
        flat.append(c.validity)
        if c.lengths is not None:
            flat.append(c.lengths)
    return flat


def _trace_fn(exprs: Tuple[Expression, ...], schema: Schema, capacity: int,
              string_max_bytes: int, ctx_attrs: Tuple):
    def fn(*flat):
        cols = []
        i = 0
        for f in schema:
            if f.dtype is DType.STRING:
                cols.append(ColV(f.dtype, flat[i], flat[i + 1], flat[i + 2]))
                i += 3
            else:
                cols.append(ColV(f.dtype, flat[i], flat[i + 1]))
                i += 2
        ctx = EvalCtx(jnp, cols, capacity, string_max_bytes)
        for k, v in ctx_attrs:
            setattr(ctx, k, v)
        outs = []
        for e in exprs:
            v = e.eval(ctx)
            data, validity, lengths = colv_to_column(v, jnp, capacity,
                                                     string_max_bytes)
            outs.append(data)
            outs.append(validity)
            if v.dtype is DType.STRING:
                outs.append(lengths)
        return tuple(outs)
    return fn


def eval_exprs_device(exprs: Sequence[Expression], batch: DeviceBatch,
                      string_max_bytes: int = 256,
                      ctx_attrs: Optional[dict] = None) -> DeviceBatch:
    """Jitted evaluation of an expression list over a device batch."""
    exprs = tuple(exprs)
    ordinals = reference_ordinals(exprs)
    if ordinals is not None:
        return DeviceBatch(output_schema(exprs),
                           tuple(batch.columns[i] for i in ordinals),
                           batch.num_rows)
    attrs = tuple(sorted((ctx_attrs or {}).items()))
    key = (exprs, batch.schema, batch.capacity, string_max_bytes, attrs)
    fn = _PROGRAM_CACHE.get_or_build(
        key, lambda: named_jit("project", _trace_fn(
            exprs, batch.schema, batch.capacity, string_max_bytes, attrs)))
    flat_out = fn(*_flatten_batch(batch))
    out_schema = output_schema(exprs)
    cols = []
    i = 0
    for f in out_schema:
        if f.dtype is DType.STRING:
            cols.append(DeviceColumn(f.dtype, flat_out[i], flat_out[i + 1],
                                     flat_out[i + 2]))
            i += 3
        else:
            cols.append(DeviceColumn(f.dtype, flat_out[i], flat_out[i + 1]))
            i += 2
    return DeviceBatch(out_schema, tuple(cols), batch.num_rows)
