"""The least bytes a query has to move through HBM: every column its text
names, read once at its stored width, plus its result. From the schema and
the row counts alone, independent of the plan, so the count does not change
when an operator is rewritten. Numeric and date columns at their Arrow
widths, string columns at their mean UTF-8 length without offsets."""
import re

import pyarrow as pa


def column_bytes(column):
    """Bytes of one Arrow column's values (no offsets, no validity)."""
    if pa.types.is_string(column.type) or pa.types.is_large_string(column.type):
        return sum(string_bytes(chunk) for chunk in column.chunks)
    return column.type.bit_width // 8 * len(column)


def string_bytes(chunk):
    offsets = chunk.buffers()[1]
    width = 8 if pa.types.is_large_string(chunk.type) else 4
    view = memoryview(offsets).cast("q" if width == 8 else "i")
    return view[chunk.offset + len(chunk)] - view[chunk.offset]


def referenced(sql, tables):
    """{table: [columns]} whose names stand as whole words in the text."""
    words = set(re.findall(r"[A-Za-z_][A-Za-z_0-9]*", sql.lower()))
    return {t: [c for c in table.column_names if c.lower() in words]
            for t, table in tables.items() if t.lower() in words}


def least_bytes(sql, tables, result):
    cols = referenced(sql, tables)
    read = sum(column_bytes(tables[t].column(c))
               for t, names in cols.items() for c in names)
    return read + sum(column_bytes(result.column(c))
                      for c in result.column_names)
