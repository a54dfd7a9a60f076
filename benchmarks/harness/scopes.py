"""A traced run's device time by the part of the model that spent it.

Every instruction of a bucket program says under which ``jax.named_scope`` of
the program it was traced (``dynamo_tpu/models/llama.py`` ``SCOPES``; since
PR 39 the compiled ``op_name`` is the whole name stack,
``jit(step)/while/body/dynamo.attn_in/dot_general``), and a TPU capture hands
that on as the stat ``tf_op`` of the operation's event METADATA. This file
reads it, with the standard library alone, and is the one place of the
benchmark that knows the capture's format at that depth:

    python -m benchmarks.harness.scopes <trace.xplane.pb[.gz]> --describe
    python -m benchmarks.harness.scopes <trace.xplane.pb> \\
        --trim <out.gz> --runs N

Why by hand: ``jax.profiler.ProfileData`` gives an event's own stats and not
its metadata's, where ``tf_op`` lives. A capture is a serialised ``XSpace``
(``tsl/profiler/protobuf/xplane.proto``); of it this file reads

    XSpace.planes(1) -> XPlane{name(2), lines(3), event_metadata(4: map id ->
        XEventMetadata{id(1), name(2), stats(5)}), stat_metadata(5: map id ->
        XStatMetadata{id(1), name(2)})}
    XLine{name(2), timestamp_ns(3), events(4)}
    XEvent{metadata_id(1), offset_ps(2), duration_ps(3), stats(4)}
    XStat{metadata_id(1), str_value(5) | ref_value(7: the id of a
        stat_metadata entry whose NAME is the string)}

Attribution is ``xplane.summarise``'s: LEAF events of the line ``XLA Ops`` (a
``while`` or a ``conditional`` holds its body's events and would count them
twice), each to the run of the line ``XLA Modules`` that contains it; the
program ``jit_step`` is a decode dispatch, ``jit_fn`` a prefill chunk. An
operation's scope is the LAST component of its ``tf_op`` path that starts
with ``dynamo.`` (``moe_ffn`` inside ``ffn``: ``moe_ffn``); none:
``unscoped``, but for what XLA expands in its own name (``EXPANDED``). A
fusion that spans two scopes carries one name, the one XLA gave it.

What it returns (``of``), parsed once a capture and kept by path:

    kinds     {kind: {scope: seconds}}, kind = decode / prefill, or the
              module's own name for any other program; seconds averaged over
              the chips that ran anything, as ``summarise`` does
    runs      {kind: runs of that kind's program}
    unscoped  {kind: {operation key: [seconds, its tf_op]}}

and what it does NOT read as a value: a capture with no ``/device:`` plane (a
CPU rehearsal), and a program whose bucket programs spent less than
``PARTITIONED`` of their leaf time under any scope (a tree older than PR 39
names a scope only on the few operations traced inside a nested ``jax.jit``:
under 1 % of its time): both read as None, never as an error. A capture whose
device operations carry no ``tf_op`` at all RAISES: the format moved.
"""

from __future__ import annotations

import bisect
import functools
import gzip
import sys
from collections import defaultdict
from typing import Any, Dict, Iterator, List, Optional, Tuple

from . import xplane
from .catalog import BenchError

PREFIX = "dynamo."
UNSCOPED = "unscoped"
KINDS = {"jit_step": "decode", "jit_fn": "prefill"}   # module -> kind
# a step's time by group; together the groups hold every scope of the
# program's tuple (tests/test_step_scopes.py holds the two equal)
GROUPS = {
    "mixer": ("attn_in", "kv_write", "attn", "attn_full", "attn_window",
              "index_select", "attn_out", "ssm_in", "ssm_step", "ssm_scan",
              "ssm_out"),
    "ffn": ("ffn", "moe_ffn"),
    "head": ("embed", "head", "sample"),
}
# What XLA expands in its OWN name: the rewriter of ``lax.ragged_dot`` gives
# the custom calls it makes a fresh ``op_name`` (``ragged-dot-none``,
# ``ragged-dot-metadata``; seen on the chip, PR 39) and drops the
# instruction's, so no scope reaches them. The program calls
# ``lax.ragged_dot`` under one scope alone (``models/moe.py``;
# ``tests/test_step_scopes.py`` holds it there), and these go to it.
EXPANDED = {"ragged-dot-": "dynamo.moe_ffn"}
# of a bucket program's leaf seconds, the part under some scope above which
# the program is one that partitions its steps (this PR's: over 0.9; a tree
# before it: under 0.01)
PARTITIONED = 0.5


# ---- protobuf wire format -------------------------------------------------

def varint(buf, i: int) -> Tuple[int, int]:
    """-> (the base-128 varint at ``buf[i:]``, the index behind it)."""
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message's bytes: an int for a
    varint, a slice of ``buf`` for a length-delimited field (a string, bytes
    or a nested message) and for the fixed 64- and 32-bit ones."""
    i, end = 0, len(buf)
    while i < end:
        key, i = varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = varint(buf, i)
        elif wire == 2:
            n, i = varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise BenchError(f"wire type {wire} at byte {i}: not a capture "
                             f"this reader knows")
        if i > end:
            raise BenchError("a field runs past its message: cut capture")
        yield key >> 3, wire, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _named(entry) -> Tuple[int, str, List[Tuple[int, Any, int]]]:
    """A map entry of ``event_metadata`` / ``stat_metadata`` -> (id, name,
    [(stat's metadata id, str_value or None, ref_value or 0)])."""
    key, name, stats = 0, "", []
    for number, _, value in fields(entry):
        if number == 1:
            key = value
        elif number == 2:
            for n2, _, v2 in fields(value):
                if n2 == 2:
                    name = _text(v2)
                elif n2 == 5:
                    stats.append(_stat(v2))
    return key, name, stats


def _stat(buf) -> Tuple[int, Any, int]:
    sid, text, ref = 0, None, 0
    for number, _, value in fields(buf):
        if number == 1:
            sid = value
        elif number == 5:
            text = _text(value)
        elif number == 7:
            ref = value
    return sid, text, ref


def _events(line, t0_ps: int) -> List[Tuple[int, int, int, List]]:
    """-> [(start ps, end ps, metadata id, [the event's own stats])]."""
    out = []
    for buf in line:
        mid = off = dur = 0
        stats = []
        for number, _, value in fields(buf):
            if number == 1:
                mid = value
            elif number == 2:
                off = value
            elif number == 3:
                dur = value
            elif number == 4:
                stats.append(_stat(value))
        out.append((t0_ps + off, t0_ps + off + dur, mid, stats))
    return out


def _plane(buf) -> Dict[str, Any]:
    """One XPlane -> its name, its lines as {name: (timestamp ps, [event
    bytes])}, and the raw entries of its two tables."""
    plane = {"name": "", "lines": {}, "events": [], "stats": []}
    for number, _, value in fields(buf):
        if number == 2:
            plane["name"] = _text(value)
        elif number == 3:
            name, t0, events = "", 0, []
            for n2, _, v2 in fields(value):
                if n2 == 2:
                    name = _text(v2)
                elif n2 == 3:
                    t0 = v2
                elif n2 == 4:
                    events.append(v2)
            plane["lines"][name] = (t0 * 1000, events)
        elif number == 4:
            plane["events"].append(value)
        elif number == 5:
            plane["stats"].append(value)
    return plane


def load(path: str) -> memoryview:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return memoryview(f.read())


def device_planes(blob) -> List[Dict[str, Any]]:
    return [p for p in (_plane(v) for n, _, v in fields(blob) if n == 1)
            if p["name"].startswith("/device:")]


# ---- a capture by scope ---------------------------------------------------

def scope_of(tf_op: Optional[str]) -> Optional[str]:
    """The innermost ``dynamo.*`` component of an operation's name path."""
    for part in reversed((tf_op or "").rstrip(":").split("/")):
        if part.startswith(PREFIX):
            return part
    for made, scope in EXPANDED.items():
        if (tf_op or "").startswith(made):
            return scope
    return None


def _attributed(plane) -> Optional[Dict[str, Any]]:
    """One device plane -> {"runs": {module: n}, "ops": [(module, seconds,
    operation's name, its tf_op or None)]} over its leaf operations, or None
    if it ran none."""
    t0, raw = plane["lines"].get("XLA Ops", (0, []))
    if not raw:
        return None
    names, tf_of_meta = {}, {}
    stat_names = dict(_named(e)[:2] for e in plane["stats"])
    tf_ids = {i for i, n in stat_names.items() if n == "tf_op"}

    def tf_op(stats) -> Optional[str]:
        for sid, text, ref in stats:
            if sid in tf_ids:
                return text if text is not None else stat_names.get(ref)
        return None

    for entry in plane["events"]:
        key, name, stats = _named(entry)
        names[key], tf_of_meta[key] = name, tf_op(stats)
    m0, mraw = plane["lines"].get("XLA Modules", (0, []))
    runs = sorted((s, e, xplane.base_name(names.get(m, "")))
                  for s, e, m, _ in _events(mraw, m0))
    starts = [r[0] for r in runs]
    count: Dict[str, int] = defaultdict(int)
    for _, _, module in runs:
        count[module] += 1
    ops = []
    events = _events(raw, t0)
    for s, e, n in xplane.leaves([(s, e, n) for n, (s, e, _, _)
                                  in enumerate(events)]):
        i = bisect.bisect_right(starts, s) - 1
        module = runs[i][2] if i >= 0 and s < runs[i][1] else None
        _, _, m, own = events[n]
        ops.append((module, (e - s) * 1e-12, names.get(m, ""),
                    tf_op(own) or tf_of_meta.get(m)))
    return {"runs": dict(count), "ops": ops}


@functools.lru_cache(maxsize=4)
def parse(path: str) -> Optional[Dict[str, Any]]:
    """The capture at ``path`` by kind and scope (the module's text); None
    for one without a device plane that ran anything."""
    planes = [a for a in map(_attributed, device_planes(load(path))) if a]
    if not planes:
        return None
    if not any(tf for a in planes for *_, tf in a["ops"]):
        raise BenchError(
            f"no device operation of {path} carries the stat tf_op: the "
            f"capture's format moved (benchmarks/harness/scopes.py)")
    n = len(planes)
    kinds: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    unscoped: Dict[str, Dict[str, list]] = defaultdict(dict)
    runs: Dict[str, float] = defaultdict(float)
    for a in planes:
        for module, count in a["runs"].items():
            runs[KINDS.get(module, module)] += count / n
        for module, seconds, name, tf in a["ops"]:
            kind = KINDS.get(module, module or "outside a program")
            scope = scope_of(tf) or UNSCOPED
            kinds[kind][scope] += seconds / n
            if scope == UNSCOPED:
                row = unscoped[kind].setdefault(xplane.op_key(name), [0.0, tf])
                row[0] += seconds / n
    return {"kinds": {k: dict(v) for k, v in kinds.items()},
            "runs": dict(runs), "unscoped": dict(unscoped)}


def of(trace: Optional[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """What ``parse`` gives for the capture a trace summary names
    (``trace["path"]``), if its bucket programs partition their steps by
    scope; None otherwise (no capture, no device plane, an older program)."""
    path = (trace or {}).get("path")
    got = parse(path) if path else None
    if got is None:
        return None
    leaf = scoped = 0.0
    for kind in KINDS.values():
        for scope, seconds in got["kinds"].get(kind, {}).items():
            leaf += seconds
            scoped += seconds if scope != UNSCOPED else 0.0
    return got if leaf > 0 and scoped >= PARTITIONED * leaf else None


def members(group: str) -> Tuple[str, ...]:
    """The scopes whose time ``step.<group>_ms`` adds up."""
    if group == UNSCOPED:
        return (UNSCOPED,)
    return tuple(PREFIX + s for s in GROUPS[group])


def step_ms(trace, run, group: str) -> Optional[float]:
    """Milliseconds of device time a decode STEP spent under ``group``: the
    decode programs' leaf seconds there / (their runs x ``decode_steps``)."""
    got = of(trace)
    runs = got["runs"].get("decode", 0) if got else 0
    if not runs:
        return None
    spent = got["kinds"].get("decode", {})
    return (1e3 * sum(spent.get(s, 0.0) for s in members(group))
            / (runs * int(run["engine"]["decode_steps"])))


def scope_seconds(trace, scope: str, work: Dict[str, float]
                  ) -> Optional[float]:
    """Device seconds under ``scope`` in the programs of the kinds ``work``
    names ({kind: the traced dispatches' work of that kind}); None where
    ``of`` reads nothing. A kind that did such work and spent no time under
    the scope means the program no longer runs it there: an error, not a
    value."""
    got = of(trace)
    if got is None:
        return None
    total = 0.0
    for kind, amount in work.items():
        seconds = got["kinds"].get(kind, {}).get(scope, 0.0)
        if amount > 0 and seconds <= 0:
            raise BenchError(
                f"the traced {kind} programs did work under scope {scope} "
                f"and no device operation names it: the scope left the "
                f"program (dynamo_tpu/models/llama.py SCOPES)")
        total += seconds
    return total


def twin_share(least: Optional[tuple], scope: str, scrapes, trace
               ) -> Optional[float]:
    """Roofline share of one scope: ``least`` (bytes, operations, work by
    kind: a least-work function of ``routed`` / ``kinds`` / ``state``) over
    the seconds the trace files under ``scope``, in percent of the device's
    peaks; None where there is nothing to read."""
    from .routed import device_peaks, roofline_share

    peaks = device_peaks(scrapes)
    if not least or not peaks:
        return None
    bytes_, flops, work = least
    seconds = scope_seconds(trace, scope, work)
    if seconds is None:
        return None
    return roofline_share(bytes_, flops, seconds, peaks)


# ---- the operator's and the builder's view --------------------------------

def describe(path: str) -> None:
    got = parse(path)
    if got is None:
        print("no /device: plane that ran anything")
        return
    for kind, spent in sorted(got["kinds"].items()):
        total = sum(spent.values())
        print(f"{kind}: {got['runs'].get(kind, 0):g} runs, "
              f"{total:.6f} s of leaf operations")
        for scope, seconds in sorted(spent.items(), key=lambda kv: -kv[1]):
            print(f"    {scope:24s} {seconds:12.6f} s "
                  f"{100 * seconds / total:6.2f} %")
        for key, (seconds, tf) in sorted(
                got["unscoped"].get(kind, {}).items(),
                key=lambda kv: -kv[1][0])[:10]:
            print(f"      unscoped {seconds:10.6f} s  {key[:60]}  "
                  f"tf_op={tf!r}")


def _varint_bytes(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    """One field as the wire holds it: an int as a varint, anything else
    length-delimited."""
    if isinstance(value, int):
        return _varint_bytes(number << 3) + _varint_bytes(value)
    value = bytes(value)
    return _varint_bytes(number << 3 | 2) + _varint_bytes(len(value)) + value


def trim(src: str, dst: str, runs: int) -> int:
    """Write to ``dst`` (gzip) the device planes of ``src`` cut behind the
    first ``runs`` runs of the bucket programs: the lines ``XLA Modules`` and
    ``XLA Ops``, the metadata entries their events name and the stat
    entries those name, every kept entry's bytes as recorded."""
    out = b""
    for plane in device_planes(load(src)):
        names = {k: n for k, n, _ in map(_named, plane["events"])}
        m0, mraw = plane["lines"].get("XLA Modules", (0, []))
        ends = sorted(e for s, e, m, _ in _events(mraw, m0)
                      if xplane.base_name(names.get(m, "")) in KINDS)
        if not ends:
            continue
        cut = ends[min(runs, len(ends)) - 1]
        body = field(2, plane["name"].encode())
        used, stat_ids = set(), set()
        for lid, line in enumerate(("XLA Modules", "XLA Ops"), 1):
            t0, raw = plane["lines"].get(line, (0, []))
            kept = [(buf, m, own) for buf, (s, e, m, own) in
                    zip(raw, _events(raw, t0)) if e <= cut]
            used.update(m for _, m, _ in kept)
            stat_ids.update(i for *_, own in kept for sid, _, ref in own
                            for i in (sid, ref))
            body += field(3, field(1, lid) + field(2, line.encode())
                          + field(3, t0 // 1000)
                          + b"".join(field(4, b) for b, *_ in kept))
        for entry in plane["events"]:
            key, _, stats = _named(entry)
            if key in used:
                body += field(4, entry)
                for sid, _, ref in stats:
                    stat_ids.update((sid, ref))
        for entry in plane["stats"]:
            if _named(entry)[0] in stat_ids:
                body += field(5, entry)
        out += field(1, body)
    with gzip.open(dst, "wb") as f:
        f.write(out)
    return len(out)


def main(argv: List[str]) -> int:
    if len(argv) == 3 and argv[2] == "--describe":
        describe(argv[1])
        return 0
    if len(argv) == 6 and argv[2] == "--trim" and argv[4] == "--runs":
        print(trim(argv[1], argv[3], int(argv[5])), "bytes before gzip")
        return 0
    print("usage: python -m benchmarks.harness.scopes <trace.xplane.pb[.gz]> "
          "--describe | --trim <out.gz> --runs N", file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv))
