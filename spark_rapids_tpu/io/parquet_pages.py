"""Raw parquet page decode: ship the file's OWN dictionary encoding to the
device instead of decoded columns.

Reference mechanism: GpuParquetScan stages raw row-group bytes on the host
and decodes ON DEVICE (`GpuParquetScan.scala:342-478` host staging,
`:576` `Table.readParquet`). pyarrow cannot hand numeric columns over
still-encoded (its ``read_dictionary`` is BYTE_ARRAY-only), so this module
reads the column-chunk bytes directly: thrift-compact page headers, codec
decompression, the RLE/bit-packed hybrid for definition levels and
dictionary indices (numpy-vectorized bit unpack), and the PLAIN dictionary
page. The result is a pa.DictionaryArray — narrow indices + small
dictionary — which DeviceBatch.from_arrow ships over the host link at a
fraction of the decoded size and decodes with an on-device gather (the
TPU-shaped analog of the reference's device-side dictionary decode; the
run-length sections stay on the host because their data-dependent control
flow has no efficient XLA lowering).

Scope (fallback to the pyarrow decoded path otherwise): flat columns
(max_repetition_level 0, max_definition_level <= 1), physical types
INT32/INT64/FLOAT/DOUBLE, every data page dictionary-encoded, codecs
pyarrow knows. Strings stay host-decoded (VERDICT round-4 item 3 allows
this split).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np
import pyarrow as pa

from spark_rapids_tpu.utils import tracing as _tracing

# parquet enums (format/PageType, format/Encoding)
_DATA_PAGE, _DICT_PAGE, _DATA_PAGE_V2 = 0, 2, 3
_ENC_PLAIN, _ENC_PLAIN_DICT, _ENC_RLE, _ENC_RLE_DICT = 0, 2, 3, 8

_PHYS_NP = {"INT32": np.int32, "INT64": np.int64,
            "FLOAT": np.float32, "DOUBLE": np.float64}


# ------------------------------------------------------------- thrift compact
class _Thrift:
    """Minimal thrift compact-protocol struct reader (PageHeader subset)."""

    def __init__(self, buf: memoryview, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def varint(self) -> int:
        out = shift = 0
        while True:
            b = self.buf[self.pos]
            self.pos += 1
            out |= (b & 0x7F) << shift
            if not b & 0x80:
                return out
            shift += 7

    def zigzag(self) -> int:
        v = self.varint()
        return (v >> 1) ^ -(v & 1)

    def read_struct(self) -> dict:
        out = {}
        fid = 0
        while True:
            byte = self.buf[self.pos]
            self.pos += 1
            if byte == 0:
                return out
            delta, ftype = byte >> 4, byte & 0x0F
            fid = fid + delta if delta else self.zigzag()
            out[fid] = self._read_value(ftype)

    def _read_value(self, ftype: int):
        if ftype in (1, 2):                 # BOOL true/false
            return ftype == 1
        if ftype in (3, 4, 5, 6):           # byte/i16/i32/i64
            return self.zigzag()
        if ftype == 7:                      # double (fixed 8, little-endian)
            v = np.frombuffer(self.buf[self.pos:self.pos + 8], "<f8")[0]
            self.pos += 8
            return float(v)
        if ftype == 8:                      # binary
            n = self.varint()
            v = bytes(self.buf[self.pos:self.pos + n])
            self.pos += n
            return v
        if ftype in (9, 10):                # list/set
            head = self.buf[self.pos]
            self.pos += 1
            size, etype = head >> 4, head & 0x0F
            if size == 15:
                size = self.varint()
            return [self._read_value(etype) for _ in range(size)]
        if ftype == 12:                     # struct
            return self.read_struct()
        raise ValueError(f"thrift compact type {ftype}")


# ------------------------------------------------------------- RLE/bit-packed
def _unpack(stream: bytes, bit_width: int, n: int) -> np.ndarray:
    """``n`` (a multiple of 8) LSB-first bit-packed values -> uint32[n].
    ``stream`` holds the n // 8 groups of ``bit_width`` bytes each, then 8
    bytes of padding. Value j of every group starts at the same bit of its
    group, so each of the eight is one strided read of an unaligned word, a
    shift and a mask over all groups: a 4-byte window holds shift (<= 7) +
    width up to width 25, wider values take an 8-byte one."""
    out = np.empty(n, np.uint32)
    word = np.dtype("<u4") if bit_width <= 25 else np.dtype("<u8")
    mask = (1 << bit_width) - 1
    for j in range(8):
        bit = j * bit_width
        w = np.ndarray((n // 8,), word, buffer=stream, offset=bit >> 3,
                       strides=(bit_width,))
        out[j::8] = (w >> (bit & 7)) & mask
    return out


def rle_bp_decode(buf: memoryview, bit_width: int,
                  out: np.ndarray) -> Tuple[int, int]:
    """Parquet RLE/bit-packed hybrid -> ``out`` (int32; its length is the
    value count), in place. One walk of the headers: an RLE run is a slice
    assignment; bit-packed groups are only located, since their bytes end to
    end are one bitstream (a group of 8 values takes exactly ``bit_width``
    bytes), then unpacked in one call and written in order to the rows the
    runs left (only the last group may be cut by the count). Returns (values
    unpacked from bit-packed groups, values filled from RLE runs)."""
    count = len(out)
    if bit_width == 0:
        out[:] = 0
        return 0, count
    rows = out.view(np.uint32)
    byte_w = (bit_width + 7) // 8
    pos = got = rle = 0
    packed: List[memoryview] = []
    runs: List[Tuple[int, int]] = []        # (first row, rows) of RLE runs
    while got < count:
        header = buf[pos]
        if header & 0x80:                   # a varint of more than a byte
            th = _Thrift(buf, pos)
            header = th.varint()
            pos = th.pos
        else:
            pos += 1
        if header & 1:                      # bit-packed groups of 8
            nbytes = (header >> 1) * bit_width
            packed.append(buf[pos:pos + nbytes])
            pos += nbytes
            got += (header >> 1) * 8
        else:                               # RLE run
            take = min(header >> 1, count - got)
            rows[got:got + take] = int.from_bytes(buf[pos:pos + byte_w],
                                                  "little")
            pos += byte_w
            runs.append((got, take))
            rle += take
            got += take
    if packed:
        stream = b"".join(packed)
        values = _unpack(stream + bytes(8), bit_width,
                         len(stream) * 8 // bit_width)
        row = first = 0
        for start, n in runs + [(count, 0)]:
            if start > row:
                rows[row:start] = values[first:first + start - row]
                first += start - row
            row = start + n
    return count - rle, rle


def run_count(idx: np.ndarray) -> int:
    """Runs of equal adjacent indices: what the stream would be as
    run-length pairs, page boundaries merged."""
    if len(idx) == 0:
        return 0
    return 1 + int(np.count_nonzero(idx[1:] != idx[:-1]))


def index_runs(idx: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(int32 run ends, run values) of the runs ``run_count`` counts."""
    starts = np.flatnonzero(np.concatenate([[True], idx[1:] != idx[:-1]]))
    return np.append(starts[1:], len(idx)).astype(np.int32), idx[starts]


# ------------------------------------------------------------- chunk decode
def _decompress(codec: str, raw: memoryview, usize: int) -> memoryview:
    if codec == "UNCOMPRESSED":
        return raw
    with _tracing.span("scan.decompress", _tracing.LAYER_TRANSFER) as sp:
        out = pa.Codec(codec.lower()).decompress(bytes(raw),
                                                 decompressed_size=usize)
        if sp is not None:
            sp.note(compressed_bytes=len(raw), bytes=usize)
    return memoryview(out)


class _DataPage:
    """A data page's header fields; its body stays unread until ``open``."""

    def __init__(self, body: int, csize: int, usize: int, nv: int,
                 n_nulls: Optional[int] = None, dlen: int = 0,
                 compressed: bool = True):
        self.body, self.csize, self.usize, self.nv = body, csize, usize, nv
        self.n_nulls = n_nulls            # None: a V1 page
        self.dlen, self.compressed = dlen, compressed

    def open(self, data: memoryview, codec: str,
             defs: Optional[np.ndarray]) -> Tuple[memoryview, int]:
        """Decode the definition levels into ``defs`` (int32[nv]; None: the
        column has none); (the values' bytes, the count of defined values)."""
        raw = data[self.body:self.body + self.csize]
        if self.n_nulls is None:                    # V1: levels inside
            page = _decompress(codec, raw, self.usize)
            if defs is None:
                return page, self.nv
            dlen = int.from_bytes(page[:4], "little")
            rle_bp_decode(page[4:4 + dlen], 1, defs)
            return page[4 + dlen:], int(np.count_nonzero(defs))
        vals = raw[self.dlen:]                      # V2: levels outside
        if self.compressed:
            vals = _decompress(codec, vals, self.usize - self.dlen)
        if defs is not None:
            if self.dlen:
                rle_bp_decode(raw[:self.dlen], 1, defs)
            else:
                defs[:] = 1
        return vals, self.nv - self.n_nulls


class _ChunkPages:
    """One column chunk: the dictionary and the dictionary-encoded prefix's
    indices, decoded into one array; the PLAIN tail (the writer's mid-chunk
    dictionary fallback) only located, and read by ``read_tail`` for a chunk
    that is kept."""

    def __init__(self, data: memoryview, codec: str, np_t, max_def: int,
                 dict_page: Tuple[int, int, int, int],
                 prefix: List[_DataPage], tail: List[_DataPage], pages: int):
        self.data, self.codec, self.np_t = data, codec, np_t
        self.max_def = max_def
        self.tail = tail                  # PLAIN pages, unread
        self.pages = pages                # headers parsed
        self.pages_decompressed = 1       # bodies opened: the dictionary's
        self.literal_values = self.rle_values = 0
        body, csize, usize, k = dict_page
        self.dictionary = np.frombuffer(
            _decompress(codec, data[body:body + csize], usize), np_t, count=k)
        self.prefix_rows = sum(p.nv for p in prefix)
        idx = np.empty(self.prefix_rows, np.int32)
        defs = np.empty(self.prefix_rows, np.int32) if max_def > 0 else None
        row = n_def = 0
        for p in prefix:
            vals, n = self._open(p, None if defs is None
                                 else defs[row:row + p.nv])
            lit, rle = rle_bp_decode(vals[1:], vals[0], idx[n_def:n_def + n])
            self.literal_values += lit
            self.rle_values += rle
            row += p.nv
            n_def += n
        self.indices = idx[:n_def]        # over the DEFINED rows
        self.prefix_defs = (defs.astype(bool)   # None: no nulls
                            if defs is not None and not bool(defs.all())
                            else None)

    def _open(self, page: _DataPage,
              defs: Optional[np.ndarray]) -> Tuple[memoryview, int]:
        self.pages_decompressed += 1
        return page.open(self.data, self.codec, defs)

    def prefix_indices(self) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Per-row indices (+validity; a null row points at slot 0)."""
        if self.prefix_defs is None:
            return self.indices, None
        full = np.zeros(self.prefix_rows, np.int32)
        full[self.prefix_defs] = self.indices
        return full, self.prefix_defs

    def read_tail(self, num_values: int) -> Tuple[
            Optional[np.ndarray], Optional[np.ndarray]]:
        """(defined PLAIN values, bool validity or None) of the tail; None
        values for inconsistent counts."""
        val_parts: List[np.ndarray] = []
        def_parts: List[np.ndarray] = []
        for p in self.tail:
            defs = np.empty(p.nv, np.int32) if self.max_def > 0 else None
            vals, n = self._open(p, defs)
            val_parts.append(np.frombuffer(vals, self.np_t, count=n))
            def_parts.append(np.ones(p.nv, np.int32) if defs is None
                             else defs)
        values = np.concatenate(val_parts)
        tdefs = np.concatenate(def_parts)
        if bool(tdefs.all()):
            if len(values) != num_values - self.prefix_rows:
                return None, None         # inconsistent counts: bail
            return values, None
        return values, tdefs.astype(bool)


def decode_dict_chunk(data: memoryview, codec: str, phys: str,
                      num_values: int, max_def: int) -> Optional[_ChunkPages]:
    """Parse one column chunk's page headers, then decode the dictionary
    and the dictionary-encoded prefix's indices; a PLAIN tail (the writer's
    fallback once the dictionary overflowed) is located, not read. Returns
    None for layouts out of scope (no dictionary page at all, dictionary
    pages after the PLAIN fallback, nested columns) — caller reads the
    column through pyarrow instead."""
    np_t = _PHYS_NP.get(phys)
    if np_t is None:
        return None
    pos = 0
    dict_page: Optional[Tuple[int, int, int, int]] = None
    prefix: List[_DataPage] = []
    tail: List[_DataPage] = []
    seen = pages = 0
    while seen < num_values and pos < len(data):
        th = _Thrift(data, pos)
        hdr = th.read_struct()
        body = th.pos
        pages += 1
        ptype = hdr.get(1)
        usize, csize = hdr.get(2, 0), hdr.get(3, 0)
        pos = body + csize
        if ptype == _DICT_PAGE:
            dh = hdr.get(7, {})
            if dh.get(2, _ENC_PLAIN) not in (_ENC_PLAIN, _ENC_PLAIN_DICT):
                return None
            dict_page = (body, csize, usize, dh.get(1, -1))
            continue
        if ptype == _DATA_PAGE:
            dh = hdr.get(5, {})
            enc = dh.get(2)
            page = _DataPage(body, csize, usize, dh.get(1, 0))
        elif ptype == _DATA_PAGE_V2:
            dh = hdr.get(8, {})
            enc = dh.get(4)
            if dh.get(6, 0):
                return None               # repetition levels: nested
            page = _DataPage(body, csize, usize, dh.get(1, 0),
                             n_nulls=dh.get(2, 0), dlen=dh.get(5, 0),
                             compressed=dh.get(7, True))
        else:
            continue                      # index pages etc.: skip
        if enc not in (_ENC_PLAIN_DICT, _ENC_RLE_DICT, _ENC_PLAIN):
            return None
        if enc == _ENC_PLAIN:
            tail.append(page)
        elif tail:                        # dict page after the PLAIN
            return None                   # fallback: not the writer layout
        else:
            prefix.append(page)
        seen += page.nv
    if (dict_page is None or seen < num_values
            or not any(p.nv for p in prefix)):
        return None
    return _ChunkPages(data, codec, np_t, max_def, dict_page, prefix, tail,
                       pages)


# ------------------------------------------------------------- file surface
class ColumnRead:
    """One row group's column read straight from the page bytes: an encoded
    prefix (DictionaryArray, or RunEndEncodedArray when the index stream was
    RLE-dominant) plus an optional host-decoded PLAIN tail. ``tail`` is None
    for the common fully-dictionary-encoded chunk."""

    def __init__(self, prefix: pa.Array, tail: Optional[pa.Array] = None):
        self.prefix = prefix
        self.tail = tail

    @property
    def num_rows(self) -> int:
        return len(self.prefix) + (len(self.tail) if self.tail is not None
                                   else 0)


def read_dict_column(path: str, pf_metadata, rg: int, col_idx: int,
                     arrow_type: pa.DataType,
                     want_runs: bool = False) -> Optional[ColumnRead]:
    """Read one row group's column from the raw page bytes, keeping the
    file's own encoding; None when ineligible OR when no encoded form is
    smaller than the decoded column (per-column fallback — shipping an
    encoding that does not shrink the link is pure overhead). One
    ``scan.chunk_decode`` span: file read, page headers, decompression,
    index decode and Arrow assembly; ``form`` says what came of it."""
    col = pf_metadata.row_group(rg).column(col_idx)
    with _tracing.span("scan.chunk_decode", _tracing.LAYER_TRANSFER) as sp:
        read, chunk = _read_chunk(path, col,
                                  pf_metadata.schema.column(col_idx),
                                  arrow_type, want_runs)
        if sp is not None:
            width = _PHYS_NP.get(col.physical_type)
            sp.note(column=col.path_in_schema, codec=col.compression,
                    compressed_bytes=col.total_compressed_size,
                    decoded_bytes=(col.num_values * np.dtype(width).itemsize
                                   if width else 0),
                    pages=chunk.pages if chunk else 0,
                    pages_decompressed=(chunk.pages_decompressed
                                        if chunk else 0),
                    literal_values=chunk.literal_values if chunk else 0,
                    rle_values=chunk.rle_values if chunk else 0,
                    form=("declined" if read is None
                          else "mixed" if read.tail is not None
                          else "ree" if pa.types.is_run_end_encoded(
                              read.prefix.type) else "dict"))
    return read


def _read_chunk(path: str, col, sc, arrow_type: pa.DataType,
                want_runs: bool) -> Tuple[Optional[ColumnRead],
                                          Optional[_ChunkPages]]:
    """(the column read or None, the chunk as far as it was decoded, or
    None when it was not) of one column chunk. The form is chosen from the
    dictionary and the prefix's indices alone: a declined chunk's PLAIN
    tail is never opened."""
    if sc.max_repetition_level != 0 or sc.max_definition_level > 1:
        return None, None
    if col.dictionary_page_offset is None:
        return None, None
    try:
        pa.Codec(col.compression.lower())
    except (ValueError, NotImplementedError):
        if col.compression != "UNCOMPRESSED":
            return None, None
    with _tracing.span("scan.chunk_io", _tracing.LAYER_TRANSFER) as sp:
        with open(path, "rb") as f:
            f.seek(col.dictionary_page_offset)
            data = memoryview(f.read(col.total_compressed_size))
        if sp is not None:
            sp.note(bytes=len(data))
    try:
        chunk = decode_dict_chunk(data, col.compression, col.physical_type,
                                  col.num_values, sc.max_definition_level)
    except Exception:       # malformed/unexpected layout: decoded fallback
        return None, None
    if chunk is None:
        return None, None
    k = len(chunk.dictionary)
    elem = chunk.dictionary.dtype.itemsize
    n_prefix = chunk.prefix_rows
    idx_w = 1 if k <= 127 else 2 if k <= 0x7FFF else 4
    dict_bytes = n_prefix * idx_w + k * elem
    ree_bytes = run_count(chunk.indices) * (4 + elem)
    decoded_bytes = n_prefix * elem
    if min(dict_bytes, ree_bytes) >= decoded_bytes:
        # no encoding survives: decoded upload is smaller
        return None, chunk
    tail = None
    if chunk.tail:
        try:
            tail_values, tail_defs = chunk.read_tail(col.num_values)
        except Exception:   # malformed tail: decoded fallback
            return None, None
        if tail_values is None:
            return None, None
        if tail_defs is None:
            tail = pa.array(tail_values)
        else:
            full = np.zeros(len(tail_defs), tail_values.dtype)
            full[tail_defs] = tail_values
            tail = pa.array(full, mask=~tail_defs)
        if not tail.type.equals(arrow_type):
            tail = tail.cast(arrow_type)
    dict_vals = pa.array(chunk.dictionary)
    if not dict_vals.type.equals(arrow_type):
        dict_vals = dict_vals.cast(arrow_type)
    if want_runs and ree_bytes < dict_bytes and chunk.prefix_defs is None:
        # RLE-dominant, null-free: ship the runs themselves. Values are the
        # per-run DECODED value (one dictionary lookup per run — k-sized
        # host work); run ends are the int32 cumulative lengths.
        ends, run_idx = index_runs(chunk.indices)
        run_values = dict_vals.take(pa.array(run_idx.astype(np.int64)))
        prefix: pa.Array = pa.RunEndEncodedArray.from_arrays(
            pa.array(ends, type=pa.int32()), run_values)
    else:
        indices, validity = chunk.prefix_indices()
        idx_t = (pa.int8() if k <= 127 else
                 pa.int16() if k <= 0x7FFF else pa.int32())
        if validity is not None:
            idx = pa.array(indices.astype(idx_t.to_pandas_dtype()),
                           mask=~validity)
        else:
            idx = pa.array(indices, type=idx_t, safe=False)
        prefix = pa.DictionaryArray.from_arrays(idx, dict_vals)
    return ColumnRead(prefix, tail), chunk
