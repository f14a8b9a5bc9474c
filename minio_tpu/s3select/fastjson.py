"""Select JSON fast path: native NDJSON field extraction.

The simdjson role (SURVEY §2.12; reference: internal/s3select/json on
minio/simdjson-go): instead of json.loads-ing every record, a native
single-pass scanner (native/njson.cc) records the byte extents of just
the TOP-LEVEL fields the query references; Python materializes only
those slices. Queries the planner can't prove eligible (SELECT *,
whole-record references, aliases used as values) fall back to the
stdlib reader — and any line that confuses the scanner is full-parsed
individually, so semantics never change.
"""

from __future__ import annotations

import ctypes
import json
import os

import numpy as np

_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SRC = os.path.join(_DIR, "njson.cc")

_lib = None
_load_error: Exception | None = None


def load():
    global _lib, _load_error
    if _load_error is not None:
        raise _load_error
    if _lib is None:
        try:
            from native._build import build
            lib = ctypes.CDLL(build("njson", _SRC))
            lib.ndjson_extract.restype = ctypes.c_long
            lib.ndjson_extract.argtypes = [
                ctypes.c_void_p, ctypes.c_long, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
                ctypes.c_void_p, ctypes.c_long]
            lib.njson_classify.restype = None
            lib.njson_classify.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_long,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p]
            _lib = lib
        except Exception as e:  # noqa: BLE001 — cache the failure
            _load_error = e
            raise
    return _lib


def referenced_fields(query) -> list[str] | None:
    """Top-level record fields a parsed Query touches, or None when
    the query isn't provably top-level (fast path ineligible)."""
    from . import sql

    fields: set[str] = set()

    def walk(node) -> bool:
        if node is None or isinstance(node, sql.Literal):
            return True
        if isinstance(node, sql.Column):
            name = node.name
            if name.lower() == "s3object" or name in query.aliases:
                return False                 # whole-record reference
            fields.add(name)
            fields.add(name.lower())
            return True
        if isinstance(node, sql.Path):
            if (node.head in query.aliases
                    or node.head.lower() == "s3object"):
                if not node.steps or node.steps[0][0] != "key":
                    return False
                fields.add(node.steps[0][1])
                fields.add(str(node.steps[0][1]).lower())
            else:
                fields.add(node.head)
                fields.add(node.head.lower())
            return True
        if isinstance(node, sql.Func):
            return all(walk(a) for a in node.args)
        if isinstance(node, sql.Agg):
            return node.arg is None or walk(node.arg)
        # generic operator nodes: walk every child Node attribute
        kids = [v for v in vars(node).values()]
        flat = []
        for v in kids:
            if isinstance(v, sql.Node):
                flat.append(v)
            elif isinstance(v, (list, tuple)):
                flat.extend(x for x in v if isinstance(x, sql.Node))
        if not flat and not isinstance(node, sql.Node):
            return False
        return all(walk(k) for k in flat)

    if query.star:
        return None
    for _, node in query.projections:
        if not walk(node):
            return None
    if query.where is not None and not walk(query.where):
        return None
    return sorted(fields)


def read_json_lines_fast(data: bytes, fields: list[str]):
    """NDJSON -> list of dicts holding ONLY `fields` (plus full dicts
    for scanner-confusing lines). Raises on toolchain absence — the
    caller falls back to the stdlib reader."""
    lib = load()
    if not fields:
        fields = ["__none__"]            # still counts/limits records
    buf = np.frombuffer(data, dtype=np.uint8)
    max_records = int(np.count_nonzero(buf == 0x0A)) + 1
    names = [f.encode() for f in fields]
    blob = b"".join(names)
    foff = np.zeros(len(names), dtype=np.int64)
    flen = np.array([len(x) for x in names], dtype=np.int64)
    np.cumsum(flen[:-1], out=foff[1:])
    blob_a = np.frombuffer(blob, dtype=np.uint8)
    out = np.empty((max_records, len(names) + 1, 2), dtype=np.int64)
    nrec = lib.ndjson_extract(
        buf.ctypes.data, buf.size, blob_a.ctypes.data,
        foff.ctypes.data, flen.ctypes.data, len(names),
        out.ctypes.data, max_records)
    if nrec < 0:
        raise RuntimeError("ndjson_extract overflow")
    nf = len(fields)
    loads = json.loads
    # Columnar assembly: C classifies every value (type + parsed
    # number + tightened string extent); Python then builds per-field
    # VALUE COLUMNS with the loop doing almost nothing, and zips the
    # columns into record dicts. One latin-1 decode of the whole
    # buffer gives O(1) string slicing (byte==char); non-ASCII
    # strings are flagged type-4 and parsed exactly.
    text = data.decode("latin-1")
    columns = []
    for f_i in range(nf):
        ext = np.ascontiguousarray(out[:nrec, f_i + 1, :])
        types = np.empty(nrec, dtype=np.int8)
        ivals = np.empty(nrec, dtype=np.int64)
        dvals = np.empty(nrec, dtype=np.float64)
        sext = np.empty((nrec, 2), dtype=np.int64)
        lib.njson_classify(buf.ctypes.data, ext.ctypes.data, nrec,
                           types.ctypes.data, ivals.ctypes.data,
                           dvals.ctypes.data, sext.ctypes.data)
        # Uniform columns (the common NDJSON shape) convert wholesale
        # at C speed; mixed columns fill per value.
        t0 = int(types[0]) if nrec else 0
        uniform = bool((types == t0).all()) if nrec else True
        if uniform and t0 == 1:
            columns.append((types, ivals.tolist()))
            continue
        if uniform and t0 == 2:
            columns.append((types, dvals.tolist()))
            continue
        if uniform and t0 == 3:
            pairs = sext.tolist()
            columns.append((types, [text[a:b] for a, b in pairs]))
            continue
        if nrec and bool(((types == 5) | (types == 6)).all()):
            columns.append((types, (types == 5).tolist()))
            continue
        col: list = [None] * nrec
        for arr, code in ((ivals, 1), (dvals, 2)):
            idx = np.nonzero(types == code)[0]
            if idx.size:
                vals = arr[idx].tolist()
                for j, v in zip(idx.tolist(), vals):
                    col[j] = v
        sidx = np.nonzero(types == 3)[0]
        if sidx.size:
            pairs = sext[sidx].tolist()
            for j, (a, b) in zip(sidx.tolist(), pairs):
                col[j] = text[a:b]
        for code, const in ((5, True), (6, False)):
            idx = np.nonzero(types == code)[0]
            if idx.size:
                for j in idx.tolist():
                    col[j] = const
        oidx = np.nonzero(types == 4)[0]
        if oidx.size:
            pairs = ext[oidx].tolist()
            for j, (a, b) in zip(oidx.tolist(), pairs):
                col[j] = loads(data[a:b])
        # type 0 (absent) and 7 (null) both read as None downstream —
        # the engine's record.get() semantics
        columns.append((types, col))
    cols = [c for _, c in columns]
    starts0 = out[:nrec, 0, 0]
    no_bail = bool((starts0 != -2).all())
    no_absent = all(not (t == 0).any() for t, _ in columns)
    if no_bail and no_absent:
        # Every record well-formed with every field present (the
        # overwhelmingly common NDJSON shape): a code-generated
        # builder assembles dict-literal records (~2x dict(zip)).
        return _rec_builder(nf)(fields, cols)
    line0 = starts0.tolist()
    line1 = out[:nrec, 0, 1].tolist()
    records = []
    append = records.append
    absent_masks = [(t == 0).tolist() for t, _ in columns]
    for r in range(nrec):
        if line0[r] == -2:               # scanner bailed: exact parse
            start = 0 if r == 0 else line1[r - 1] + 1
            obj = loads(data[start:line1[r]])
            if isinstance(obj, dict):
                append(obj)
            continue
        rec = {}
        for f_i in range(nf):
            if not absent_masks[f_i][r]:
                rec[fields[f_i]] = cols[f_i][r]
        append(rec)
    return records


_BUILDERS: dict[int, object] = {}


def _rec_builder(nf: int):
    """Code-generated list-of-dict-literals assembler for nf columns —
    a dict display per record beats dict(zip()) ~2x on the hot path."""
    fn = _BUILDERS.get(nf)
    if fn is None:
        kp = ", ".join(f"k{i}" for i in range(nf))
        ks = ", ".join(f"k{i}: v{i}" for i in range(nf))
        vs = ", ".join(f"v{i}" for i in range(nf))
        loop = (f"for ({vs},) in zip(*cols)" if nf == 1
                else f"for {vs} in zip(*cols)")
        src = (f"lambda f, cols: (lambda {kp}: "
               f"[{{{ks}}} {loop}])(*f)")
        fn = eval(src)  # noqa: S307 — generated from an int only
        _BUILDERS[nf] = fn
    return fn
