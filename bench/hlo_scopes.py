"""The named scope of each instruction of each program in a profiler
trace, read from the HLO the trace carries.

A TPU's trace names each operation by its HLO text and carries no
``op_name`` metadata on the event (on a v5e an ``XLA Ops`` event holds
only its offset, duration and time scale). The ``/host:metadata`` plane
holds each program's optimized HLO as a serialized ``HloProto``, which
JAX's ``ProfileData`` does not expose: this module reads the
``.xplane.pb`` (an ``XSpace``) with a minimal protobuf wire reader, and
from each program's instructions derives the ``ingest.*`` scope
(``SCOPES``) that ``repro.engine.insert`` puts on the stacked ingest
program's steps.
"""

from __future__ import annotations

import re

SCOPES = re.compile(r"ingest\.(plan|reclaim|scan|walk|delta)\b")


def _varint(b, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """``(field number, value)`` of each field of one protobuf message:
    a memoryview for a length-delimited field, an int otherwise."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            step = 8 if wire == 1 else 4
            v, i = b[i:i + step], i + step
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _field(b, number: int, default=None):
    return next((v for f, v in _fields(b) if f == number), default)


def _ints(v) -> list:
    """A repeated integer field: one varint, or a packed run of them."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def op_scopes(path: str) -> dict:
    """``{program launch name: {instruction: scope}}`` from the HLO the
    trace's ``/host:metadata`` plane holds for each program (one ``Hlo
    Proto`` stat per program, keyed like the ``XLA Modules`` events:
    ``jit__ingest_stacked_lsketch(12)``); ``instruction_scopes`` says
    which scope an instruction gets. JAX reads no event metadata, so this
    walks the ``.xplane.pb`` (an ``XSpace``) itself: plane = 1, its name
    = 2, event metadata = 4 (a map: value = 2; its name = 2, stats = 5),
    stat metadata = 5 (value = 2: id = 1, name = 2); a stat's metadata id
    = 1, its bytes = 6."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1 or bytes(_field(plane, 2, b"")) != b"/host:metadata":
            continue
        stat_names = {}
        for f, entry in _fields(plane):
            if f == 5:
                meta = _field(entry, 2, b"")
                stat_names[_field(meta, 1, 0)] = bytes(_field(meta, 2, b""))
        hlo_ids = {i for i, n in stat_names.items()
                   if n in (b"Hlo Proto", b"hlo_proto")}
        for f, entry in _fields(plane):
            if f != 4:
                continue
            meta = _field(entry, 2, b"")
            name = bytes(_field(meta, 2, b"")).decode()
            for sf, stat in _fields(meta):
                if sf == 5 and _field(stat, 1, 0) in hlo_ids:
                    out[name] = instruction_scopes(_field(stat, 6, b""))
    return out


def instruction_scopes(hlo_proto) -> dict:
    """``{instruction: scope}`` of one program (a serialized ``HloProto``)
    for each instruction that has one: the ``ingest.*`` scope its own
    ``op_name`` metadata names; else that of the nearest while, condition
    or call that runs its computation (XLA leaves many operations of a
    loop body without metadata); else, for a fusion, the scope most of
    its fused instructions name. ``HloProto.hlo_module`` = 1, its
    computations = 3 (id = 5, instructions = 2); an instruction's name =
    1, opcode = 2, ``OpMetadata`` = 7 (``op_name`` = 2), called
    computation ids = 38."""
    comps = {}  # computation id -> [(name, opcode, own scope, called)]
    for f, comp in _fields(_field(hlo_proto, 1, b"")):
        if f != 3:
            continue
        insts, cid = [], None
        for g, v in _fields(comp):
            if g == 5:
                cid = v
            elif g == 2:
                name = opcode = scope = None
                called = []
                for h, w in _fields(v):
                    if h == 1:
                        name = bytes(w).decode()
                    elif h == 2:
                        opcode = bytes(w).decode()
                    elif h == 7:
                        m = SCOPES.search(bytes(_field(w, 2, b"")).decode())
                        scope = m.group(0) if m else None
                    elif h == 38:
                        called += _ints(w)
                insts.append((name, opcode, scope, called))
        comps[cid] = insts
    runs = {}  # computation -> (computation, scope) of the op that runs it
    for cid, insts in comps.items():
        for _, opcode, scope, called in insts:
            if opcode != "fusion":
                for c in called:
                    runs[c] = (cid, scope)

    def enclosing(cid, depth=0):
        while cid in runs and depth < 64:
            cid, scope = runs[cid]
            if scope:
                return scope
            depth += 1
        return None

    out = {}
    for cid, insts in comps.items():
        outer = enclosing(cid)
        for name, opcode, scope, called in insts:
            if scope is None:
                scope = outer
            if scope is None and opcode == "fusion":
                inner = [s for c in called for _, _, s, _ in comps.get(c, ())
                         if s]
                scope = max(set(inner), key=inner.count) if inner else None
            if scope:
                out[name] = scope
    return out
