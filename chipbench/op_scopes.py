"""Device time of a program's operations by the named scope they were
traced under.

On a TPU trace each operation's event metadata carries a ``tf_op``
stat: the path of ``jax.named_scope`` names its HLO instruction came
from (``jit(decode_step)/.../mla_scores/dot_general``).
``jax.profiler.ProfileData`` gives events but not their metadata's
stats, so this module reads them from the ``.xplane.pb`` itself with a
small decoder of the protobuf wire format (XSpace -> XPlane ->
event_metadata -> XStat; field numbers of ``xplane.proto``).  Only the
planes' metadata is decoded; their lines are skipped.

Metadata are keyed by (program id, operation name): the ``XLA Modules``
events are named ``jit_<program>(<program id>)``, and the ``XLA Ops``
events by their HLO instruction.
"""
from __future__ import annotations

import functools
import os
import re
from typing import Dict, Iterable, Optional, Tuple

import numpy as np

from chipbench import program_spans, trace

TF_OP = "tf_op"
PROGRAM_ID = "program_id"


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of one message: ints for
    varints and fixed widths, a memoryview for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wt} in an xplane")
        yield num, wt, v


def _stat(buf, names: Dict[int, str]) -> Tuple[str, object]:
    mid, val = 0, None
    for num, _, v in _fields(buf):
        if num == 1:
            mid = v
        elif num in (3, 4):
            val = v
        elif num == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif num == 7:                          # ref_value: an interned str
            val = ("ref", v)
    if isinstance(val, tuple):
        val = names.get(val[1], "")
    return names.get(mid, ""), val


def _plane(buf) -> Tuple[str, Dict[Tuple[int, str], str]]:
    """(plane name, {(program id, op name): tf_op}) of one XPlane."""
    name, metas, stat_names = "", [], {}
    for num, _, v in _fields(buf):
        if num == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif num == 4:                          # map<int64, XEventMetadata>
            for k, _, e in _fields(v):
                if k == 2:
                    metas.append(e)
        elif num == 5:                          # map<int64, XStatMetadata>
            for k, _, e in _fields(v):
                if k != 2:
                    continue
                sid, sname = 0, ""
                for f, _, x in _fields(e):
                    if f == 1:
                        sid = x
                    elif f == 2:
                        sname = bytes(x).decode("utf-8", "replace")
                stat_names[sid] = sname
    out = {}
    for m in metas:
        op, stats = "", {}
        for f, _, x in _fields(m):
            if f == 2:
                op = bytes(x).decode("utf-8", "replace")
            elif f == 5:
                k, val = _stat(x, stat_names)
                stats[k] = val
        if TF_OP in stats:
            out[(int(stats.get(PROGRAM_ID) or 0), op)] = str(stats[TF_OP])
    return name, out


def read(path: str) -> Dict[str, Dict[Tuple[int, str], str]]:
    """{device plane name: {(program id, op name): tf_op}} of the
    ``.xplane.pb`` at ``path``."""
    st = os.stat(path)
    return _read(path, st.st_mtime_ns, st.st_size)


@functools.lru_cache(maxsize=2)
def _read(path: str, _mtime: int, _size: int):
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, _, v in _fields(buf):
        if num != 1:
            continue
        name, ops = _plane(v)
        if trace.DEVICE_PLANE.match(name):
            out[name] = ops
    return out


def program_id(event_name: str) -> Optional[int]:
    """``jit_decode_step(1260...)`` -> 1260...; None without one."""
    m = re.search(r"\((\d+)\)\s*$", event_name)
    return int(m.group(1)) if m else None


def scoped_seconds(run, program: str, scopes: Iterable[str]
                   ) -> Optional[Tuple[float, int]]:
    """(device self time in seconds of the operations of ``program``
    whose ``tf_op`` path has a scope named one of ``scopes``, summed
    over its executions inside the window; how many executions), on the
    first chip.  None where the trace holds no such operation."""
    if run.trace is None or not run.trace.devices:
        return None
    try:
        path = trace.find_trace_file(program_spans.trace_dir(run))
    except FileNotFoundError:
        return None
    planes = read(path)
    dev = run.trace.devices[0]
    ops = planes.get("/device:TPU:0", {})
    t0, t1 = run.trace.t0_ns, run.trace.t1_ns
    sel = [i for i, n in enumerate(dev.prog_names)
           if trace.program_name(n) == program
           and t0 <= dev.prog_start[i] and dev.prog_end[i] <= t1]
    ids = {program_id(dev.prog_names[i]) for i in sel}
    want = tuple(f"/{s}" for s in scopes)
    names = {op for (pid, op), tf in ops.items()
             if pid in ids and any(w in tf for w in want)}
    if not sel or not names:
        return None
    order = np.argsort(dev.prog_start[sel])
    starts = dev.prog_start[sel][order]
    ends = dev.prog_end[sel][order]
    k = np.searchsorted(starts, dev.op_start, side="right") - 1
    inside = (k >= 0) & (dev.op_end <= ends[np.maximum(k, 0)])
    idx = np.flatnonzero(inside)
    own = trace.self_times(dev.op_start[idx], dev.op_end[idx])
    hit = np.array([dev.op_names[i] in names for i in idx], bool)
    return float(own[hit].sum()) / 1e9, len(sel)
