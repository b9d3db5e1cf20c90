"""Helpers shared by the plain references (numpy and pyarrow only)."""
import datetime

import numpy as np
import pyarrow as pa

_EPOCH = datetime.date(1970, 1, 1)

#: a reference computes in the precision the configuration states
#: (``float64``: inputs as stored, sums carried in extended precision) or,
#: as the control, one step below it (``float32``: inputs, products and sums
#: all in float32)
PRECISIONS = ("float64", "float32")


def days(y, m, d):
    return (datetime.date(y, m, d) - _EPOCH).days


def column(table, name):
    """A column as one numpy array (dates as int32 days since the epoch)."""
    arr = table.column(name).combine_chunks()
    if pa.types.is_date32(arr.type):
        arr = arr.cast(pa.int32())
    return arr.to_numpy(zero_copy_only=False)


def codes(table, name):
    """A string column as (int codes, list of distinct values)."""
    enc = table.column(name).combine_chunks().dictionary_encode()
    return enc.indices.to_numpy(), enc.dictionary.to_pylist()


def floats(table, name, precision):
    values = column(table, name)
    return values.astype(np.float32) if precision == "float32" else values


def total(values, precision):
    """Sum in the carried precision: extended for float64, float32 for the
    control. Returned as a Python float."""
    acc = np.float32 if precision == "float32" else np.longdouble
    return float(np.sum(values, dtype=acc))
